"""Simulated multi-device lane (spawned with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` by tests/_spawn.py).

The first tests in this repo to run the one-shot round on >1 device:
shard-count invariance of ``core.distributed.fedpft_transfer`` (collective
ordering + ``axis_index`` seed offsets), end-to-end invariance of the
mesh-native ``FedSession`` (wire → synthesis → head), global disjointness
of per-client PRNG seeds, and the actionable uneven-cohort error.

Everything is compared across 1-, 2- and 8-shard meshes built over the
SAME simulated host devices, so any dependence of the result on where a
client's fit ran — the ROADMAP's "untestable on one device" open item —
shows up as a tolerance failure here.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _checks import assert_finite
from repro import data as D
from repro.core import distributed as DF
from repro.core import gmm as G
from repro.core import head as H
from repro.fl import api as FA
from repro.launch.mesh import make_sim_mesh

pytestmark = pytest.mark.multidevice

N_CLASSES, I, N, DIM, K = 4, 8, 48, 6, 2
SHARD_COUNTS = (1, 2, 8)


def _gmm_cfg(cov="diag", n_iter=5):
    return G.GMMConfig(n_components=K, cov_type=cov, n_iter=n_iter)


@pytest.fixture(scope="module")
def cohort():
    dcfg = D.DatasetConfig(n_classes=N_CLASSES, n_per_class=120,
                           input_dim=DIM, class_sep=3.0)
    x, y = D.make_dataset(dcfg)
    return (x[: I * N].reshape(I, N, DIM), y[: I * N].reshape(I, N))


def test_lane_exercises_multiple_shards():
    """The acceptance gate: this lane really runs on simulated devices —
    the 8-way mesh below is 8 actual XLA devices, not a relabeled one."""
    assert jax.device_count() >= 8, (
        "lane must be spawned with XLA_FLAGS="
        "--xla_force_host_platform_device_count=8 (tests/_spawn.py)")
    assert len(make_sim_mesh(8).devices.ravel()) == 8
    assert make_sim_mesh(2).shape["data"] == 2


@pytest.mark.parametrize("cov", ["diag", "spher"])
def test_wire_invariance_across_shard_counts(cohort, cov):
    """1-, 2- and 8-shard transfers leave the SAME replicated (I, C, K, …)
    wire pytree and counts on every shard — catches collective-order and
    axis_index seed-offset bugs that a 1-device mesh cannot."""
    feats, labels = cohort
    cfg = _gmm_cfg(cov)
    results = {}
    for n in SHARD_COUNTS:
        wire, counts, lls = DF.fedpft_transfer(make_sim_mesh(n), feats,
                                               labels, N_CLASSES, cfg)
        assert_finite(wire, f"in {n}-shard wire ({cov})")
        results[n] = ({k: np.asarray(v, np.float32)
                       for k, v in jax.device_get(wire).items()},
                      np.asarray(counts), np.asarray(lls))
    ref_wire, ref_counts, ref_lls = results[1]
    assert ref_wire["mu"].shape == (I, N_CLASSES, K, DIM)
    assert ref_wire["cov"].shape == (
        (I, N_CLASSES) + G.packed_cov_shape(cov, K, DIM))
    for n in SHARD_COUNTS[1:]:
        wire_n, counts_n, lls_n = results[n]
        np.testing.assert_array_equal(ref_counts, counts_n)
        np.testing.assert_allclose(ref_lls, lls_n, rtol=1e-4, atol=1e-4)
        for field in G.WIRE_FIELDS:
            np.testing.assert_allclose(
                ref_wire[field], wire_n[field], rtol=1e-2, atol=2e-2,
                err_msg=f"{cov} wire field {field!r} differs between "
                        f"1-shard and {n}-shard execution")


def test_session_invariance_across_shard_counts(cohort):
    """The full mesh-native FedSession — transfer, codec accounting, the
    fused server phase — is shard-count invariant: the decoded messages,
    the features the plain reference synthesizes from them, and the
    trained head agree to tolerance, and comm_bytes is exactly Eqs. 9-11
    regardless of shards."""
    feats, labels = cohort
    results = {}
    for n in SHARD_COUNTS:
        sess = FA.FedSession(
            n_classes=N_CLASSES, summarizer=FA.GMMSummarizer(_gmm_cfg()),
            head=H.HeadConfig(n_steps=120, lr=3e-3), shards=n)
        res = sess.run_sharded(jax.random.PRNGKey(0), feats, labels)
        assert res.info["n_shards"] == n
        assert res.info["comm_bytes"] == \
            G.comm_bytes("diag", DIM, K, N_CLASSES, 2) * I
        pool, pool_y = FA.synthesize_looped(
            jax.random.PRNGKey(1), FA.stack_messages(res.messages),
            np.stack([m.counts for m in res.messages]), "diag")
        pool, pool_y = np.asarray(pool, np.float32), np.asarray(pool_y)
        assert_finite(res.model, f"in {n}-shard head")
        results[n] = (res, pool, pool_y)
    ref, ref_pool, ref_y = results[1]
    for n in SHARD_COUNTS[1:]:
        res, pool, pool_y = results[n]
        # decoded message params (post-wire server state)
        for m_ref, m_n in zip(ref.messages, res.messages):
            np.testing.assert_array_equal(m_ref.counts, m_n.counts)
            for field in G.WIRE_FIELDS:
                np.testing.assert_allclose(
                    np.asarray(m_ref.params[field]),
                    np.asarray(m_n.params[field]), rtol=1e-2, atol=2e-2)
        # synthesized-feature statistics
        np.testing.assert_array_equal(ref_y, pool_y)
        np.testing.assert_allclose(ref_pool.mean(axis=0),
                                   pool.mean(axis=0), atol=2e-2)
        np.testing.assert_allclose(ref_pool.std(axis=0),
                                   pool.std(axis=0), atol=2e-2)
        # trained head
        for p in ("w", "b"):
            np.testing.assert_allclose(np.asarray(ref.model[p]),
                                       np.asarray(res.model[p]),
                                       rtol=1e-2, atol=2e-2)
        agree = np.mean(
            np.argmax(np.asarray(H.head_logits(ref.model, feats[0])), -1)
            == np.argmax(np.asarray(H.head_logits(res.model, feats[0])), -1))
        assert agree >= 0.98, f"{n}-shard head predicts differently: {agree}"


def test_fused_head_invariance_across_shard_counts(cohort):
    """The zero-materialization server phase (FedSession's one route for
    a homogeneous cohort) runs the fused sampler-in-the-loop scan
    REPLICATED on the post-all_gather slot stack: same inputs + same RNG
    on every shard ⇒ the trained head is shard-count invariant, with no
    synthetic pool or chunk list ever materialized."""
    feats, labels = cohort
    results = {}
    for n in SHARD_COUNTS:
        sess = FA.FedSession(
            n_classes=N_CLASSES, summarizer=FA.GMMSummarizer(_gmm_cfg()),
            head=H.HeadConfig(n_steps=120, lr=3e-3), shards=n)
        res = sess.run_sharded(jax.random.PRNGKey(0), feats, labels)
        assert res.info["synthesis"] == "fused"
        assert "synthetic_chunks" not in res.info
        assert "synthetic_feats" not in res.info
        assert res.info["head_losses"].shape == (120,)
        assert_finite(res.model, f"in {n}-shard fused head")
        results[n] = res
    ref = results[1]
    for n in SHARD_COUNTS[1:]:
        res = results[n]
        for p in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(ref.model[p]), np.asarray(res.model[p]),
                rtol=1e-2, atol=2e-2,
                err_msg=f"fused head {p!r} differs between 1-shard and "
                        f"{n}-shard execution")
        agree = np.mean(
            np.argmax(np.asarray(H.head_logits(ref.model, feats[0])), -1)
            == np.argmax(np.asarray(H.head_logits(res.model, feats[0])), -1))
        assert agree >= 0.98, f"{n}-shard fused head predicts differently"


def test_client_seeds_disjoint_end_to_end(cohort):
    """Give every client IDENTICAL data: with globally-disjoint per-client
    seeds each fit must still differ (k-means seeding draws), and each
    client's wire must match the host-level fit with PRNGKey(i + seed) —
    the regression the host-side ``client_seeds`` unit test can't close."""
    feats, labels = cohort
    block_f = np.tile(np.asarray(feats[0])[None], (I, 1, 1))
    block_y = np.tile(np.asarray(labels[0])[None], (I, 1))
    seed = 5
    cfg = _gmm_cfg()
    wire, counts, _ = DF.fedpft_transfer(make_sim_mesh(8),
                                         jnp.asarray(block_f),
                                         jnp.asarray(block_y), N_CLASSES,
                                         cfg, seed=seed)
    mu = np.asarray(wire["mu"], np.float32)         # (I, C, K, d)
    for i in range(I):
        # end-to-end layout check: shard ⌊i/I_local⌋ really used seed i+5
        gmms, cnt, _ = G.fit_classwise_gmms(
            jax.random.PRNGKey(i + seed), jnp.asarray(block_f[i]),
            jnp.asarray(block_y[i]), N_CLASSES, cfg)
        np.testing.assert_allclose(
            mu[i], np.asarray(G.pack_wire(gmms, cfg.cov_type)["mu"],
                              np.float32), rtol=1e-2, atol=1e-2)
        np.testing.assert_array_equal(np.asarray(counts[i]),
                                      np.asarray(cnt))
    for i in range(I):
        for j in range(i + 1, I):
            assert np.abs(mu[i] - mu[j]).max() > 1e-3, (
                f"clients {i} and {j} produced identical fits on identical "
                "data — their PRNG seeds collided across shards")


def test_uneven_cohort_raises_actionable(cohort):
    """An I % n_shards != 0 cohort must fail loudly at the API boundary,
    not with a shape error from inside shard_map."""
    feats, labels = cohort
    mesh = make_sim_mesh(8)
    with pytest.raises(ValueError, match="does not shard evenly"):
        DF.fedpft_transfer(mesh, feats[:6], labels[:6], N_CLASSES,
                           _gmm_cfg())
    sess = FA.FedSession(n_classes=N_CLASSES,
                         summarizer=FA.GMMSummarizer(_gmm_cfg()), shards=8)
    with pytest.raises(ValueError, match="does not shard evenly"):
        sess.run_sharded(jax.random.PRNGKey(0), feats[:6], labels[:6])
