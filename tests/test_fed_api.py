"""Unified federation API (fl.api): wire-codec round-trip exactness,
``comm_bytes == len(payload)``, batched-vs-looped synthesis equivalence, and
end-to-end parity of the centralized / chain / DP / baseline paths through
``FedSession``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st
from repro import data as D
from repro.core import decentralized as DC
from repro.core import dp as DP
from repro.core import fedpft as FP
from repro.core import gmm as G
from repro.core import head as H
from repro.fl import api as FA

N_CLASSES = 6
DIM = 16


@pytest.fixture(scope="module")
def dataset():
    dcfg = D.DatasetConfig(n_classes=N_CLASSES, n_per_class=120,
                           input_dim=DIM, class_sep=2.0)
    return (*D.make_dataset(dcfg), *D.make_dataset(dcfg, split=1))


@pytest.fixture(scope="module")
def fp_cfg():
    return FP.FedPFTConfig(
        gmm=G.GMMConfig(n_components=2, cov_type="diag", n_iter=12),
        head=H.HeadConfig(n_steps=250, lr=3e-3))


def _gmm_session(cov="diag", K=2, **kw):
    return FA.FedSession(
        n_classes=N_CLASSES,
        summarizer=FA.GMMSummarizer(
            G.GMMConfig(n_components=K, cov_type=cov, n_iter=12)),
        head=H.HeadConfig(n_steps=250, lr=3e-3), **kw)


class TestWireCodec:
    @pytest.mark.parametrize("cov", ["full", "diag", "spher"])
    def test_comm_bytes_is_payload_length(self, key, dataset, cov):
        """Reported bytes are the actual encoded payload — and agree with
        the paper's Eqs. 9-11 at 16-bit precision."""
        x, y, *_ = dataset
        K = 2
        sess = _gmm_session(cov=cov, K=K)
        msg = sess.client_update(key, x, y)
        assert msg.comm_bytes == len(msg.payload)
        assert msg.comm_bytes == G.comm_bytes(cov, DIM, K, N_CLASSES, 2)

    @pytest.mark.parametrize("cov", ["full", "diag", "spher"])
    def test_encode_decode_reencode_byte_exact(self, key, dataset, cov):
        """decode(encode(x)) re-encodes to the *identical* byte string —
        quantization is idempotent after one round trip."""
        x, y, *_ = dataset
        sess = _gmm_session(cov=cov)
        msg = sess.client_update(key, x, y)
        msg2 = FA.encode_message(
            {k: np.asarray(v) for k, v in msg.params.items()},
            msg.counts, msg.logliks, kind="gmm", cov_type=cov,
            n_classes=N_CLASSES, codec=sess.codec)
        assert msg2.payload == msg.payload
        for k in msg.params:
            np.testing.assert_array_equal(np.asarray(msg.params[k]),
                                          np.asarray(msg2.params[k]))

    @pytest.mark.parametrize("dtype,bps", [("float16", 2), ("bfloat16", 2),
                                           ("float32", 4)])
    def test_codec_precisions(self, key, dataset, dtype, bps):
        x, y, *_ = dataset
        codec = FA.QuantizedCodec(dtype)
        assert codec.bytes_per_scalar == bps
        sess = _gmm_session(codec=codec)
        msg = sess.client_update(key, x, y)
        assert msg.comm_bytes == G.comm_bytes("diag", DIM, 2, N_CLASSES, bps)

    def test_full_cov_layout_matches_gmm_pack_wire(self, key, dataset):
        """The codec's tril packing and gmm.pack_wire/unpack_wire encode
        the SAME wire layout — a change to one without the other is a bug
        (ablations.py still measures precision through pack_wire)."""
        import ml_dtypes
        x, y, *_ = dataset
        g, _ = G.fit_gmm(key, x, jnp.ones(x.shape[0]),
                         G.GMMConfig(n_components=2, cov_type="full",
                                     n_iter=5))
        ref = np.asarray(G.pack_wire(g, "full")["cov"]).astype(np.float32)
        cod = FA._pack_cov(np.asarray(g["cov"], np.float32), "full") \
            .astype(ml_dtypes.bfloat16).astype(np.float32)
        np.testing.assert_array_equal(ref, cod)
        # and both unpackers rebuild the same symmetric matrix
        d = g["cov"].shape[-1]
        ref_up = np.asarray(G.unpack_wire(G.pack_wire(g, "full"), "full",
                                          d)["cov"])
        cod_up = FA._unpack_cov(cod, "full", d)
        np.testing.assert_allclose(ref_up, cod_up, rtol=1e-6, atol=1e-6)

    def test_absent_classes_not_transmitted(self, key, dataset):
        x, y, *_ = dataset
        keep = y < 2
        sess = _gmm_session()
        msg = sess.client_update(key, x[keep], y[keep])
        assert msg.comm_bytes == G.comm_bytes("diag", DIM, 2, 2, 2)
        assert msg.header.present == (0, 1)

    def test_message_is_pytree(self, key, dataset):
        """v2 messages are registered pytrees: decoded params are leaves,
        wire payload/header are aux — homogeneous messages stack to the
        server's (M, C, K, …) layout with one tree.map."""
        x, y, *_ = dataset
        sess = _gmm_session()
        msgs = [sess.client_update(k, x, y)
                for k in jax.random.split(key, 3)]
        batch = FA.stack_messages(msgs)
        assert batch["mu"].shape == (3, N_CLASSES, 2, DIM)
        # jax sees through the message: tree.map touches only params
        doubled = jax.tree.map(lambda a: a * 2, msgs[0])
        np.testing.assert_allclose(np.asarray(doubled.params["mu"]),
                                   2 * np.asarray(msgs[0].params["mu"]))
        assert doubled.payload == msgs[0].payload
        # aux data is hashable, so messages cross jit boundaries directly
        total = jax.jit(lambda m: m.params["mu"].sum())(msgs[0])
        np.testing.assert_allclose(float(total),
                                   float(msgs[0].params["mu"].sum()))


class TestBatchedSynthesis:
    @pytest.mark.parametrize("cov", ["full", "diag", "spher"])
    def test_matches_looped_reference(self, key, dataset, cov, sanitized):
        """One jitted batched sample ≡ the per-(client, class) loop: same
        per-class sample counts, matching class-conditional statistics.
        Runs under the runtime sanitizer (nan/inf checks + key tracer);
        the batched-vs-looped comparison deliberately replays one key, so
        history is reset between the runs."""
        x, y, *_ = dataset
        gmms, counts, _ = G.fit_classwise_gmms(
            key, x, y, N_CLASSES,
            G.GMMConfig(n_components=2, cov_type=cov, n_iter=10))
        batch = jax.tree.map(lambda a: jnp.stack([a, a]), gmms)
        cnt2 = np.stack([np.asarray(counts)] * 2).astype(np.int64)
        sanitized.reset()
        fb, yb = FA.synthesize_batched(key, batch, cnt2, cov)
        sanitized.reset()
        fl, yl = FA.synthesize_looped(key, batch, cnt2, cov)
        assert fb.shape == fl.shape
        np.testing.assert_array_equal(np.sort(np.asarray(yb)),
                                      np.sort(np.asarray(yl)))
        for c in range(N_CLASSES):
            mb = np.mean(np.asarray(fb)[np.asarray(yb) == c], axis=0)
            ml = np.mean(np.asarray(fl)[np.asarray(yl) == c], axis=0)
            np.testing.assert_allclose(mb, ml, atol=0.5)

    def test_keys_fold_per_client_and_class(self, key, dataset):
        """Regression for the v1 key-reuse hazard: two clients holding the
        SAME mixture must draw different synthetic features."""
        x, y, *_ = dataset
        gmms, counts, _ = G.fit_classwise_gmms(
            key, x, y, N_CLASSES, G.GMMConfig(n_components=2, n_iter=10))
        batch = jax.tree.map(lambda a: jnp.stack([a, a]), gmms)
        cnt2 = np.stack([np.asarray(counts)] * 2).astype(np.int64)
        f, lbl = FA.synthesize_batched(key, batch, cnt2, "diag")
        half = f.shape[0] // 2
        assert not np.allclose(np.asarray(f[:half]), np.asarray(f[half:]))

    def test_samples_per_class_override(self, key, dataset):
        x, y, *_ = dataset
        gmms, counts, _ = G.fit_classwise_gmms(
            key, x, y, N_CLASSES, G.GMMConfig(n_components=2, n_iter=10))
        f, lbl = FA.synthesize_batched(key, gmms, counts, "diag",
                                       samples_per_class=7)
        assert f.shape[0] == 7 * N_CLASSES
        assert np.all(np.bincount(np.asarray(lbl)) == 7)

    def test_empty_counts(self, key, dataset):
        x, y, *_ = dataset
        gmms, counts, _ = G.fit_classwise_gmms(
            key, x, y, N_CLASSES, G.GMMConfig(n_components=2, n_iter=10))
        f, lbl = FA.synthesize_batched(key, gmms, np.zeros(N_CLASSES), "diag")
        assert f.shape == (0, DIM) and lbl.shape == (0,)


@pytest.mark.slow
class TestFedSessionPaths:
    def test_star_matches_pre_redesign_path(self, key, dataset, fp_cfg):
        """The codec round-trip + batched synthesis must reproduce the
        pre-redesign (f32 params, python-loop sampling) accuracy within
        quantization tolerance."""
        x, y, xt, yt = dataset
        parts = D.dirichlet_partition(np.asarray(y), 4, beta=0.5)
        clients = [(x[p], y[p]) for p in parts if len(p) > 10]
        # pre-redesign reference: v1 fit + looped f32 synthesis + head
        msgs_v1 = [FP.client_update(k, f, yy, N_CLASSES, fp_cfg)
                   for k, (f, yy) in zip(jax.random.split(key, len(clients)),
                                         clients)]
        batch = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[m.gmms for m in msgs_v1])
        cnts = np.stack([m.counts for m in msgs_v1])
        sf, sl = FA.synthesize_looped(key, batch, cnts, "diag")
        head_ref, _ = H.train_head(key, sf, sl, N_CLASSES, fp_cfg.head)
        acc_ref = float(H.accuracy(head_ref, xt, yt))
        # redesigned path
        sess = FP.session_for(N_CLASSES, fp_cfg)
        res = sess.run(key, clients)
        acc_new = float(H.accuracy(res.model, xt, yt))
        assert abs(acc_new - acc_ref) < 0.05, (acc_new, acc_ref)
        assert res.info["comm_bytes"] == sum(len(m.payload)
                                             for m in res.messages)

    def test_all_paths_share_message_schema(self, key, dataset, fp_cfg):
        """Centralized star, decentralized chain, and DP all construct and
        consume the same encoded v2 ClientMessage through FedSession."""
        x, y, xt, yt = dataset
        clients = [(x[y < 3], y[y < 3]), (x[y >= 3], y[y >= 3])]
        # star
        head, info = FP.run_fedpft(key, clients, N_CLASSES, fp_cfg)
        # chain
        msgs_c, infos_c = DC.run_chain(key, clients, N_CLASSES, fp_cfg)
        # dp
        dp_cfg = dataclasses.replace(
            fp_cfg, gmm=G.GMMConfig(n_components=1, cov_type="full",
                                    n_iter=8), normalize_features=True)
        head_dp, info_dp = DP.run_dp_fedpft(
            key, clients, N_CLASSES, dp_cfg,
            DP.DPConfig(epsilon=8.0, delta=1e-2))
        for msgs in (info["messages"], msgs_c, info_dp["messages"]):
            assert all(isinstance(m, FA.ClientMessage) for m in msgs)
        for inf, msgs in ((info, info["messages"]), (info_dp,
                                                     info_dp["messages"])):
            assert inf["comm_bytes"] == sum(m.comm_bytes for m in msgs)
        # the star head still learns both label halves
        acc = float(H.accuracy(head, xt, yt))
        assert acc > 0.7, acc
        # chain end accumulates all classes
        assert int((msgs_c[-1].counts > 0).sum()) == N_CLASSES
        # DP at generous epsilon stays above chance
        xn = xt / jnp.maximum(jnp.linalg.norm(xt, axis=-1, keepdims=True),
                              1.0)
        assert float(H.accuracy(head_dp, xn, yt)) > 1.5 / N_CLASSES

    def test_ring_topology(self, key, dataset, fp_cfg):
        """Ring = chain with wraparound: after 2 laps the FIRST client's
        refit head covers classes it never held locally."""
        x, y, xt, yt = dataset
        clients = [(x[y < 3], y[y < 3]), (x[y >= 3], y[y >= 3])]
        sess = FP.session_for(N_CLASSES, fp_cfg,
                              topology=FA.Ring(laps=2))
        res = sess.run(key, clients)
        assert len(res.messages) == 4        # 2 clients × 2 laps
        # client 0's second-lap head (index 2) sees the whole label space
        acc0_lap2 = float(H.accuracy(res.info["per_client"][2]["head"],
                                     xt, yt))
        acc0_lap1 = float(H.accuracy(res.info["per_client"][0]["head"],
                                     xt, yt))
        assert acc0_lap2 > acc0_lap1 + 0.2, (acc0_lap1, acc0_lap2)

    @pytest.mark.parametrize("cov", ["full", "diag", "spher"])
    def test_empty_class_cohort_nan_free(self, key, dataset, cov):
        """A cohort where one client holds NO samples of some class must
        stay NaN-free end-to-end: the empty slot's EM fit (all-zero
        weights under the batched classwise fit) is finite, its message
        encodes/decodes finite params, and neither the plain reference
        (looped synthesis + ``train_head``) nor the fused server phase
        ever sees a NaN."""
        x, y, xt, yt = dataset
        clients = [(x[y < 3], y[y < 3]),            # classes 3.. absent
                   (x[y >= 2], y[y >= 2])]          # classes 0-1 absent
        sess = _gmm_session(cov=cov, K=2)
        keys = jax.random.split(key, 3)
        msgs = [sess.client_update(k, f, yy, i)
                for i, (k, (f, yy)) in enumerate(zip(keys[1:], clients))]
        for m in msgs:
            assert 0 in {int(c) for c in m.header.counts}
            for leaf in jax.tree.leaves(m.params):
                assert np.isfinite(np.asarray(leaf)).all(), cov
        k_syn, k_head = jax.random.split(keys[0])
        sf, sl = FA.synthesize_looped(k_syn, FA.stack_messages(msgs),
                                      np.stack([m.counts for m in msgs]),
                                      cov)
        assert np.isfinite(np.asarray(sf)).all()
        ref, _ = H.train_head(k_head, sf, sl, N_CLASSES, sess.head)
        for leaf in jax.tree.leaves(ref):
            assert np.isfinite(np.asarray(leaf)).all()
        # every class is represented by at least one client's synthesis
        assert set(np.unique(np.asarray(sl))) == set(range(N_CLASSES))
        # the fused default never materializes the pool yet stays finite
        res_f = sess.server_aggregate(keys[0], msgs)
        assert res_f.info["synthesis"] == "fused"
        assert "synthetic_feats" not in res_f.info
        for leaf in jax.tree.leaves(res_f.model):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_dp_requires_star_topology(self, key, dataset):
        """Chain messages summarize a union that includes other clients'
        samples — Theorem 4.1's accounting doesn't cover that, so the
        session must refuse rather than transmit un-noised parameters."""
        x, y, *_ = dataset
        sess = FA.FedSession(
            n_classes=N_CLASSES,
            summarizer=FA.GMMSummarizer(
                G.GMMConfig(n_components=1, cov_type="full", n_iter=5)),
            topology=FA.Chain(), normalize_features=True,
            dp=DP.DPConfig(epsilon=1.0))
        with pytest.raises(NotImplementedError):
            sess.run(key, [(x, y)])

    def test_head_summarizer_baselines(self, key, dataset):
        """One-shot AVG / Ensemble baselines ride the same session, schema,
        and codec — comm equals the encoded head payload length."""
        x, y, xt, yt = dataset
        parts = D.iid_shards(len(y), 3)
        clients = [(x[p], y[p]) for p in parts]
        sess = FA.FedSession(
            n_classes=N_CLASSES,
            summarizer=FA.HeadSummarizer(n_steps=200, lr=3e-3),
            aggregate="avg")
        res = sess.run(key, clients)
        assert res.info["comm_bytes"] == \
            3 * (DIM * N_CLASSES + N_CLASSES) * 2
        acc = float(H.accuracy(res.model, xt, yt))
        assert acc > 0.6, acc
        ens = dataclasses.replace(sess, aggregate="ensemble")
        res_e = ens.run(key, clients)
        from repro.fl import baselines as FB
        pred = FB.ensemble_predict(res_e.model, xt)
        assert float(jnp.mean((pred == yt).astype(jnp.float32))) > 0.6


# ---------------------------------------------------------------------------
# mesh execution mode (host lane: 1 device; shard-count invariance proper
# lives in tests/multidevice, spawned with 8 simulated devices)
# ---------------------------------------------------------------------------


class TestMeshMode:
    def _cohort(self, dataset, n_clients=2):
        x, y, *_ = dataset
        N = (len(y) // n_clients // N_CLASSES) * N_CLASSES
        feats = jnp.asarray(x[: n_clients * N]).reshape(n_clients, N, DIM)
        labels = jnp.asarray(y[: n_clients * N]).reshape(n_clients, N)
        return feats, labels

    def test_run_sharded_accounts_the_mesh_wire(self, key, dataset,
                                                sanitized):
        """The 1-shard mesh session reports comm_bytes == Σ len(payload)
        == Eqs. 9-11 — the mesh path and the codec share one layout.
        Runs under the runtime sanitizer (nan/inf + key-reuse tracer)."""
        feats, labels = self._cohort(dataset)
        sess = _gmm_session(shards=1)
        res = sess.run_sharded(key, feats, labels)
        assert res.info["n_shards"] == 1
        assert res.info["comm_bytes"] == \
            sum(len(m.payload) for m in res.messages)
        # the shuffled dataset leaves every class present on both clients
        assert res.info["comm_bytes"] == \
            2 * G.comm_bytes("diag", DIM, 2, N_CLASSES, 2)
        # the padded collective itself moves the full (I, C, …) pytree
        assert res.info["mesh_wire_bytes"] == \
            2 * G.comm_bytes("diag", DIM, 2, N_CLASSES, 2)
        for m in res.messages:
            assert m.header.dtype == "bfloat16"
            # real EM logliks crossed the mesh, not fabricated zeros
            assert any(ll != 0.0 for ll in m.logliks)
        for leaf in jax.tree.leaves(res.model):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_run_dispatches_to_sharded(self, key, dataset):
        """run() with shards= stacks the client list and runs the mesh
        path — same result as calling run_sharded directly."""
        feats, labels = self._cohort(dataset)
        sess = _gmm_session(shards=1)
        direct = sess.run_sharded(key, feats, labels)
        via_run = sess.run(key, [(feats[i], labels[i])
                                 for i in range(feats.shape[0])])
        for p in ("w", "b"):
            np.testing.assert_array_equal(np.asarray(direct.model[p]),
                                          np.asarray(via_run.model[p]))

    def test_messages_from_wire_matches_host_codec(self, key, dataset):
        """gmm.pack_wire → messages_from_wire re-encodes BYTE-identical
        payloads to the host client_update path: one wire layout, two
        transports."""
        x, y, *_ = dataset
        sess = _gmm_session(cov="full")
        msgs = [sess.client_update(k, x, y)
                for k in jax.random.split(key, 2)]
        wire = jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[G.pack_wire(m.params, "full")
                              for m in msgs])
        counts = np.stack([m.counts for m in msgs])
        rebuilt = FA.messages_from_wire(wire, counts, "full", N_CLASSES,
                                        sess.codec)
        for orig, re_m in zip(msgs, rebuilt):
            assert re_m.payload == orig.payload
            assert re_m.comm_bytes == orig.comm_bytes
            for f in G.WIRE_FIELDS:
                np.testing.assert_array_equal(np.asarray(orig.params[f]),
                                              np.asarray(re_m.params[f]))

    def test_uneven_cohort_fails_fast_at_session_level(self, key, dataset):
        feats, labels = self._cohort(dataset, n_clients=2)
        sess = _gmm_session(shards=3)
        with pytest.raises(ValueError, match="does not shard evenly"):
            sess.run_sharded(key, feats, labels)

    def test_sharded_preconditions_are_actionable(self, key, dataset):
        feats, labels = self._cohort(dataset)
        base = _gmm_session(shards=1)
        with pytest.raises(ValueError, match="bfloat16"):
            dataclasses.replace(
                base, codec=FA.QuantizedCodec("float16")
            ).run_sharded(key, feats, labels)
        with pytest.raises(NotImplementedError, match="Star"):
            dataclasses.replace(base, topology=FA.Chain()
                                ).run_sharded(key, feats, labels)
        with pytest.raises(NotImplementedError, match="host"):
            dataclasses.replace(
                base, summarizer=FA.HeadSummarizer()
            ).run_sharded(key, feats, labels)
        with pytest.raises(ValueError, match="mesh=.*shards"):
            FA.FedSession(n_classes=N_CLASSES).run_sharded(key, feats,
                                                           labels)
        from repro.launch.mesh import make_sim_mesh
        with pytest.raises(ValueError, match="disagree"):
            dataclasses.replace(base, mesh=make_sim_mesh(1), shards=2
                                ).run_sharded(key, feats, labels)
        with pytest.raises(ValueError, match="share one"):
            _gmm_session(shards=1).run(
                key, [(feats[0], labels[0]), (feats[1, :8], labels[1, :8])])


# ---------------------------------------------------------------------------
# QuantizedCodec round-trip properties (satellite: hypothesis, slow lane;
# the deterministic grid below runs everywhere — _hyp skips @given tests
# when hypothesis isn't installed)
# ---------------------------------------------------------------------------

_CODEC_TOL = {"float16": (2e-3, 2e-3), "bfloat16": (1e-2, 1e-2),
              "float32": (1e-6, 1e-6)}


def _check_codec_roundtrip(cov, dtype, d, K, C, seed):
    rng = np.random.RandomState(seed)
    counts = rng.randint(0, 40, size=C).astype(np.int64)
    pi = rng.dirichlet(np.ones(K), size=C).astype(np.float32)
    mu = (rng.randn(C, K, d) * 4).astype(np.float32)
    if cov == "full":
        a = rng.randn(C, K, d, d).astype(np.float32)
        cov_arr = 0.5 * np.einsum("ckde,ckfe->ckdf", a, a) \
            + 0.1 * np.eye(d, dtype=np.float32)
    elif cov == "diag":
        cov_arr = (0.1 + rng.rand(C, K, d)).astype(np.float32)
    else:
        cov_arr = (0.1 + rng.rand(C, K)).astype(np.float32)
    codec = FA.QuantizedCodec(dtype)
    msg = FA.encode_message({"pi": pi, "mu": mu, "cov": cov_arr}, counts,
                            np.zeros(C, np.float32), kind="gmm",
                            cov_type=cov, n_classes=C, codec=codec)
    # comm accounting: actual bytes, and exactly Eqs. 9-11 at this precision
    present = np.flatnonzero(counts > 0)
    assert msg.comm_bytes == len(msg.payload)
    assert msg.comm_bytes == G.comm_bytes(cov, d, K, len(present),
                                          codec.bytes_per_scalar)
    # shapes survive the round trip (decoded params are always stacked C)
    assert msg.params["pi"].shape == (C, K)
    assert msg.params["mu"].shape == (C, K, d)
    assert msg.params["cov"].shape == cov_arr.shape
    # present-class values stay within the wire dtype's tolerance
    rtol, atol = _CODEC_TOL[dtype]
    for name, ref in (("pi", pi), ("mu", mu), ("cov", cov_arr)):
        np.testing.assert_allclose(
            np.asarray(msg.params[name])[present], ref[present],
            rtol=rtol, atol=atol * max(1.0, np.abs(ref).max()),
            err_msg=f"{cov}/{dtype} field {name!r}")
    # idempotence: a second trip through the codec is byte-identical
    msg2 = FA.encode_message(
        {k: np.asarray(v) for k, v in msg.params.items()}, counts,
        np.zeros(C, np.float32), kind="gmm", cov_type=cov, n_classes=C,
        codec=codec)
    assert msg2.payload == msg.payload


@pytest.mark.parametrize("cov", ["full", "diag", "spher"])
@pytest.mark.parametrize("dtype", ["float16", "bfloat16", "float32"])
def test_codec_roundtrip_grid(cov, dtype):
    """Deterministic corner of the property test — always runs."""
    _check_codec_roundtrip(cov, dtype, d=5, K=2, C=4, seed=0)
    _check_codec_roundtrip(cov, dtype, d=1, K=1, C=1, seed=1)


@pytest.mark.slow
@given(cov=st.sampled_from(["full", "diag", "spher"]),
       dtype=st.sampled_from(["float16", "bfloat16", "float32"]),
       d=st.integers(1, 12), K=st.integers(1, 4), C=st.integers(1, 6),
       seed=st.integers(0, 2**31 - 1))
@settings(max_examples=60, deadline=None)
def test_codec_roundtrip_property(cov, dtype, d, K, C, seed):
    """Property: for ANY family × precision × shape, encode→decode
    preserves shapes, stays within the dtype's tolerance, re-encodes
    byte-identically, and comm_bytes == len(payload) == Eqs. 9-11."""
    _check_codec_roundtrip(cov, dtype, d, K, C, seed)
