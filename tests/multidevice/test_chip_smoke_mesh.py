"""``chip_smoke.py --four-chips`` rehearsed on four simulated devices.

The chip run shards client EM over four TPUs and compares the mesh round
with the host round; here the same phase runs at d=64 on four of the
lane's simulated CPU devices, so its sharding, placement and comparisons
stay working between chip runs.
"""
import importlib.util
import pathlib
import sys

import pytest

pytestmark = pytest.mark.multidevice

SCRIPT = pathlib.Path(__file__).resolve().parents[2] / "chip_smoke.py"


def test_mesh_round_rehearsal():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    cs = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = cs      # dataclasses look it up
    spec.loader.exec_module(cs)
    report = cs.Report()
    mres, hres = cs.mesh_round(report, cs.MeshSize(n_clients=8,
                                                   n_samples=64, d=64))
    assert report.failed == []
    assert mres.info["n_shards"] == 4
    assert len(mres.model["w"].sharding.device_set) == 4
