"""CPU rehearsal of the round cell at a tiny size: a whole run is correct,
the bf16 control reads far above the program, and each fault of the timed
path makes the run incorrect."""
from __future__ import annotations

import pytest

import cb_tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return cb_tiny.checkout(tmp_path_factory.mktemp("round"))


def test_tiny_round_run_is_correct(root):
    res = cb_tiny.run(root, "tiny.round", seconds=2.0)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"round_s", "setup_s"}
    assert res["checks"]["reservoir_rows_off"]["value"] == 0.0


def test_traced_round_reports_its_host_spans(root):
    res = cb_tiny.run(root, "tiny.round", seconds=1.0, trace=True)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"client_fit_ms.round", "submit_ms.round",
                                   "close_round_ms.round"}


def test_the_bf16_control_reads_far_above_the_program(root):
    """The reference EM at one bf16 pass per product, in the program's
    place, against the same reference step."""
    import time
    from yardstick import harness as HN
    cell = HN.resolve("tiny.round", root)
    drv = HN.driver(cell)
    seed = 2 ** 33 + 17
    state = drv.setup(cell, seed, lambda m: None)
    drv.window(state, 0.5)
    program = {c.name: c.value for c in drv.check(state, cell, seed,
                                                  lambda m: None)}
    t0 = time.perf_counter()
    ctl = drv.control(state, cell, seed, lambda m: None)
    assert time.perf_counter() - t0 < 120
    assert program["em_var_gap"] <= cell.limits["em_var_gap"]
    assert not ctl["em_var_gap"] <= cell.limits["em_var_gap"], ctl


@pytest.fixture
def planted(monkeypatch):
    """Plants a fault; afterwards drops the programs traced with it."""
    import jax
    from yardstick import faults
    yield lambda name: faults.plant(name, monkeypatch.setattr)
    monkeypatch.undo()
    jax.clear_caches()


FAULTS = ["em_state_unchanged", "em_half_batch", "payload_altered",
          "head_unchanged", "head_half_batch", "head_half_steps",
          "broker_half_folded"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_in_the_timed_path_is_not_correct(root, planted, fault):
    planted(fault)
    res = cb_tiny.run(root, "tiny.round", seconds=1.0)
    assert res["correct"] is False, (fault, res["checks"])


def test_every_planted_fault_is_tested():
    from yardstick import faults
    assert set(faults.ROUND) == set(FAULTS)
