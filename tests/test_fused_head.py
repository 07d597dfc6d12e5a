"""Zero-materialization server phase (ISSUE 5): the fused
sampler-in-the-loop head trainer (``head.train_head_from_gmms``), its slot
table (``fl.planner.SlotTable``), and the ``FedSession`` server phase
built on it — distributional equivalence with planner-bucketed synthesis
(per-slot draw frequencies, per-class moment match, head-accuracy parity),
small-slot edge cases, the empty-cohort guard, and the heterogeneous
pooled fallback."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import data as D
from repro.core import gmm as G
from repro.core import head as H
from repro.fl import api as FA
from repro.fl import planner as P

N_CLASSES = 6
DIM = 16

SKEWED = np.array([
    [1, 3, 0, 700, 64, 2],
    [120, 4096, 17, 0, 1, 999],
    [0, 0, 5, 5, 2048, 31],
])


def _random_batch(key, M, C, K=2, d=DIM, cov="diag"):
    ks = jax.random.split(key, 3)
    shapes = {"full": (M, C, K, d, d), "diag": (M, C, K, d),
              "spher": (M, C, K)}
    cov_arr = 0.1 + jax.random.uniform(ks[2], shapes[cov])
    if cov == "full":
        cov_arr = jnp.eye(d)[None, None, None] * \
            (0.1 + jax.random.uniform(ks[2], (M, C, K, 1, 1)))
    return {"pi": jax.nn.softmax(jax.random.normal(ks[0], (M, C, K))),
            "mu": jax.random.normal(ks[1], (M, C, K, d)),
            "cov": cov_arr}


def _slot_stack(batch, counts, samples_per_class=None):
    """The session's own construction, plus the table for assertions."""
    stack, labels, cnt, plan = FA.fused_slot_stack(batch, counts,
                                                   samples_per_class)
    return stack, labels, cnt, plan.slot_table


class TestSlotTable:
    def test_table_covers_nonzero_slots_in_global_order(self):
        table = P.plan_synthesis(SKEWED).slot_table
        nz = np.flatnonzero(SKEWED.reshape(-1) > 0)
        np.testing.assert_array_equal(table.slots, nz)
        np.testing.assert_array_equal(table.counts,
                                      SKEWED.reshape(-1)[nz])
        assert len(table) == nz.size

    def test_cum_mass_is_normalized_and_monotone(self):
        table = P.plan_synthesis(SKEWED).slot_table
        assert table.cum_mass.dtype == np.float32
        assert np.all(np.diff(table.cum_mass) > 0)
        np.testing.assert_allclose(table.cum_mass[-1], 1.0, rtol=1e-6)
        np.testing.assert_allclose(
            table.cum_mass, np.cumsum(table.counts) / table.counts.sum(),
            rtol=1e-6)

    def test_table_is_bucketing_policy_invariant(self):
        """Rows ascend by GLOBAL slot id, so the table — and therefore
        every fused draw — is identical under pow2 and single policies."""
        t_pow2 = P.plan_synthesis(SKEWED).slot_table
        t_single = P.plan_synthesis(SKEWED, policy="single").slot_table
        np.testing.assert_array_equal(t_pow2.slots, t_single.slots)
        np.testing.assert_array_equal(t_pow2.counts, t_single.counts)
        np.testing.assert_array_equal(t_pow2.cum_mass, t_single.cum_mass)

    def test_empty_plan_empty_table(self):
        table = P.plan_synthesis(np.zeros((2, 3), np.int64)).slot_table
        assert len(table) == 0 and table.cum_mass.shape == (0,)

    def test_samples_per_class_override(self):
        table = P.plan_synthesis(SKEWED, samples_per_class=7).slot_table
        assert (table.counts == 7).all()


class TestFusedSamplerLaw:
    def test_slot_draw_frequencies_match_counts(self, key):
        """Per-slot expected draw counts: the cumulative-mass categorical
        must hit each slot ∝ its requested count."""
        table = P.plan_synthesis(SKEWED).slot_table
        cum = jnp.asarray(table.cum_mass)
        n = 200_000
        slots = np.asarray(G.draw_slots(key, cum, n))
        freq = np.bincount(slots, minlength=len(table)) / n
        expect = table.counts / table.counts.sum()
        # slots with ≥1% mass must match within 10% relative
        big = expect > 0.01
        np.testing.assert_allclose(freq[big], expect[big], rtol=0.1)
        # and nothing outside the table is ever drawn
        assert slots.min() >= 0 and slots.max() < len(table)

    @pytest.mark.parametrize("cov", ["full", "diag", "spher"])
    def test_minibatch_moments_match_chunked_synthesis(self, key, cov):
        """Per-class mean/std of fused minibatches vs the materialized
        ``synthesize_chunks`` pool — one law, two executions."""
        M, C = SKEWED.shape
        batch = _random_batch(key, M, C, cov=cov)
        stack, labels, counts, table = _slot_stack(batch, SKEWED)
        fac = G.sampling_factor(stack["cov"], cov)
        cum = jnp.asarray(table.cum_mass)
        xs, ys = [], []
        for i, k in enumerate(jax.random.split(key, 60)):
            x, y = G.sample_slot_minibatch(k, cum, stack["pi"], stack["mu"],
                                           fac, labels, 512, cov)
            xs.append(np.asarray(x))
            ys.append(np.asarray(y))
        xs, ys = np.concatenate(xs), np.concatenate(ys)
        assert np.isfinite(xs).all()
        chunks, _ = FA.synthesize_chunks(key, batch, SKEWED, cov)
        pf = np.concatenate([np.asarray(f) for f, _ in chunks])
        py = np.concatenate([np.asarray(y) for _, y in chunks])
        cls_mass = np.zeros(C)
        for c in range(C):
            cls_mass[c] = SKEWED[:, c].sum() / SKEWED.sum()
        # label law: class frequency ∝ class draw mass
        freq = np.bincount(ys, minlength=C) / len(ys)
        big = cls_mass > 0.02
        np.testing.assert_allclose(freq[big], cls_mass[big], rtol=0.15)
        for c in range(C):
            if SKEWED[:, c].sum() < 500:
                continue          # too little mass for tight moments
            np.testing.assert_allclose(xs[ys == c].mean(0),
                                       pf[py == c].mean(0), atol=0.25,
                                       err_msg=f"class {c} mean ({cov})")
            np.testing.assert_allclose(xs[ys == c].std(0),
                                       pf[py == c].std(0), atol=0.25,
                                       err_msg=f"class {c} std ({cov})")


class TestTrainHeadFromGmms:
    def _fitted_cohort(self, key):
        dcfg = D.DatasetConfig(n_classes=N_CLASSES, n_per_class=150,
                               input_dim=DIM, class_sep=2.0)
        x, y = D.make_dataset(dcfg)
        xt, yt = D.make_dataset(dcfg, split=1)
        parts = D.dirichlet_partition(np.asarray(y), 3, beta=0.5)
        cfg = G.GMMConfig(n_components=2, cov_type="diag", n_iter=12)
        gmms, counts = [], []
        for i, p in enumerate(parts):
            g, c, _ = G.fit_classwise_gmms(jax.random.fold_in(key, i),
                                           x[p], y[p], N_CLASSES, cfg)
            gmms.append(g)
            counts.append(np.asarray(c, np.int64))
        batch = jax.tree.map(lambda *xs: jnp.stack(xs), *gmms)
        return batch, np.stack(counts), xt, yt

    def test_head_accuracy_parity_with_pooled(self, key):
        """The fused head must learn the task as well as a head trained on
        the materialized pool (equivalence in law ⇒ parity in accuracy)."""
        batch, counts, xt, yt = self._fitted_cohort(key)
        hcfg = H.HeadConfig(n_steps=300, lr=3e-3)
        pf, py = FA.synthesize_batched(key, batch, counts, "diag")
        pooled, _ = H.train_head(key, pf, py, N_CLASSES, hcfg)
        stack, labels, cnt, _ = _slot_stack(batch, counts)
        fused, losses = H.train_head_from_gmms(
            key, stack["pi"], stack["mu"], stack["cov"], labels, cnt,
            N_CLASSES, hcfg, "diag")
        assert losses.shape == (hcfg.n_steps,)
        assert np.isfinite(np.asarray(losses)).all()
        acc_p = float(H.accuracy(pooled, xt, yt))
        acc_f = float(H.accuracy(fused, xt, yt))
        assert acc_f > 0.6
        assert abs(acc_p - acc_f) < 0.07, (acc_p, acc_f)

    @pytest.mark.parametrize("n_steps", [1, 20, 32, 50])
    def test_noise_window_tail_handling(self, key, n_steps):
        """n_steps below / equal to / not divisible by the noise window
        must all produce a full-length loss trace."""
        batch = _random_batch(key, 2, N_CLASSES)
        stack, labels, cnt, _ = _slot_stack(
            batch, np.full((2, N_CLASSES), 9, np.int64))
        cfg = H.HeadConfig(n_steps=n_steps, noise_window=32)
        params, losses = H.train_head_from_gmms(
            key, stack["pi"], stack["mu"], stack["cov"], labels, cnt,
            N_CLASSES, cfg, "diag")
        assert losses.shape == (n_steps,)
        assert np.isfinite(np.asarray(losses)).all()
        for leaf in jax.tree.leaves(params):
            assert np.isfinite(np.asarray(leaf)).all()

    def test_deterministic(self, key):
        batch = _random_batch(key, 2, N_CLASSES)
        stack, labels, cnt, _ = _slot_stack(
            batch, np.full((2, N_CLASSES), 50, np.int64))
        cfg = H.HeadConfig(n_steps=40)
        a, _ = H.train_head_from_gmms(key, stack["pi"], stack["mu"],
                                      stack["cov"], labels, cnt, N_CLASSES,
                                      cfg, "diag")
        b, _ = H.train_head_from_gmms(key, stack["pi"], stack["mu"],
                                      stack["cov"], labels, cnt, N_CLASSES,
                                      cfg, "diag")
        for p in ("w", "b"):
            np.testing.assert_array_equal(np.asarray(a[p]),
                                          np.asarray(b[p]))

    def test_empty_slot_table_returns_init(self, key):
        K = 2
        params, losses = H.train_head_from_gmms(
            key, jnp.zeros((0, K)), jnp.zeros((0, K, DIM)),
            jnp.zeros((0, K, DIM)), jnp.zeros((0,), jnp.int32),
            jnp.zeros((0,)), N_CLASSES, H.HeadConfig(), "diag")
        assert params["w"].shape == (DIM, N_CLASSES)
        assert losses.shape == (0,)
        for leaf in jax.tree.leaves(params):
            assert np.isfinite(np.asarray(leaf)).all()


class TestSessionFusedDefault:
    def _clients(self, key):
        dcfg = D.DatasetConfig(n_classes=N_CLASSES, n_per_class=120,
                               input_dim=DIM, class_sep=2.0)
        x, y = D.make_dataset(dcfg)
        xt, yt = D.make_dataset(dcfg, split=1)
        parts = D.dirichlet_partition(np.asarray(y), 3, beta=0.5)
        return [(x[p], y[p]) for p in parts if len(p) > 10], xt, yt

    def _session(self, **kw):
        return FA.FedSession(
            n_classes=N_CLASSES,
            summarizer=FA.GMMSummarizer(
                G.GMMConfig(n_components=2, cov_type="diag", n_iter=12)),
            head=H.HeadConfig(n_steps=250, lr=3e-3), **kw)

    def test_default_is_fused_and_never_materializes(self, key):
        clients, xt, yt = self._clients(key)
        res = self._session().run(key, clients)
        assert res.info["synthesis"] == "fused"
        assert "synthetic_feats" not in res.info
        assert "synthetic_chunks" not in res.info
        assert "synthesis_fallback" not in res.info
        assert len(res.info["synthesis_plans"]) == 1
        assert res.info["head_losses"].shape == (250,)
        assert float(H.accuracy(res.model, xt, yt)) > 0.6

    @pytest.mark.slow
    def test_fused_matches_pooled_session_accuracy(self, key):
        """The session's fused head learns the task as well as the plain
        reference on the same decoded messages: the looped
        per-(client, class) synthesis, pooled, fed to ``train_head``."""
        clients, xt, yt = self._clients(key)
        res_f = self._session().run(key, clients)
        msgs = res_f.messages
        k_syn, k_head = jax.random.split(key)
        pf, py = FA.synthesize_looped(k_syn, FA.stack_messages(msgs),
                                      np.stack([m.counts for m in msgs]),
                                      "diag")
        ref, _ = H.train_head(k_head, pf, py, N_CLASSES,
                              self._session().head)
        acc_f = float(H.accuracy(res_f.model, xt, yt))
        acc_p = float(H.accuracy(ref, xt, yt))
        assert acc_f > 0.6 and abs(acc_f - acc_p) < 0.1, (acc_f, acc_p)

    def test_heterogeneous_cohort_falls_back_to_pooled(self, key):
        """Mixed-K cohorts (paper §6.3) can't stack into one slot tensor —
        the session must keep working via the materializing path and say
        so in info."""
        clients, xt, yt = self._clients(key)
        cheap = FA.GMMSummarizer(
            G.GMMConfig(n_components=1, cov_type="spher", n_iter=10))
        rich = FA.GMMSummarizer(
            G.GMMConfig(n_components=2, cov_type="diag", n_iter=10))
        summs = tuple([rich, cheap, rich][: len(clients)])
        res = self._session(client_summarizers=summs).run(key, clients)
        assert res.info["synthesis"] == "pooled"
        assert res.info["synthesis_fallback"] == "heterogeneous cohort"
        assert float(H.accuracy(res.model, xt, yt)) > 0.5

    def test_fused_empty_cohort_guard(self, key):
        """min_class_count filtering every class must return the clean
        empty-cohort result on the fused path too."""
        clients, xt, yt = self._clients(key)
        res = self._session(min_class_count=10 ** 9).run(key, clients)
        assert res.info.get("empty_cohort") is True
        assert res.info["synthesis"] == "fused"
        assert res.info["head_losses"].shape == (0,)
        for leaf in jax.tree.leaves(res.model):
            assert np.isfinite(np.asarray(leaf)).all()


def _class_slots(x, y, classes, counts):
    """A K=1 diag slot stack with one slot per class in ``classes``: the
    class's mean and variance, drawn ``counts`` times."""
    x, y = np.asarray(x), np.asarray(y)
    mu = np.stack([x[y == c].mean(0) for c in classes])[:, None]
    var = np.stack([x[y == c].var(0) for c in classes])[:, None]
    return (jnp.ones((len(classes), 1)), jnp.asarray(mu), jnp.asarray(var),
            jnp.asarray(classes, jnp.int32), jnp.asarray(counts, jnp.int32))


class TestFusedSmallSlots:
    def test_one_row_chunk_trains_full_width(self, key, monkeypatch):
        """A class whose one slot asks for a single draw must not shrink
        the minibatch: with Σcounts = C ≪ batch_size every Adam step
        still computes its loss over a full ``batch_size`` rows, and the
        head learns every class."""
        dcfg = D.DatasetConfig(n_classes=N_CLASSES, n_per_class=80,
                               input_dim=DIM, class_sep=2.0)
        x, y = D.make_dataset(dcfg)
        xt, yt = D.make_dataset(dcfg, split=1)
        stack = _class_slots(x, y, range(N_CLASSES), [1] * N_CLASSES)
        widths = []
        real = H._xent
        monkeypatch.setattr(H, "_xent", lambda p, f, lbl, w: (
            widths.append(f.shape), real(p, f, lbl, w))[1])
        # an n_steps of its own forces a fresh trace through the patch
        cfg = H.HeadConfig(n_steps=101, batch_size=64, lr=3e-3)
        params, losses = H.train_head_from_gmms(key, *stack, N_CLASSES,
                                                cfg, "diag")
        assert widths and set(widths) == {(cfg.batch_size, DIM)}
        assert losses.shape == (cfg.n_steps,)
        assert np.isfinite(np.asarray(losses)).all()
        assert float(H.accuracy(params, xt, yt)) > 0.6

    def test_interleaving_retains_minority_chunk_class(self, key):
        """A class living ONLY in one small slot must survive training:
        the in-scan slot draw hits it ∝ its count on every step, so the
        large slots (which never carry the class) cannot run out the
        clock on it."""
        dcfg = D.DatasetConfig(n_classes=N_CLASSES, n_per_class=120,
                               input_dim=DIM, class_sep=2.0)
        x, y = D.make_dataset(dcfg)
        xt, yt = D.make_dataset(dcfg, split=1)
        stack = _class_slots(x, y, range(N_CLASSES),
                             [20] + [600] * (N_CLASSES - 1))
        cfg = H.HeadConfig(n_steps=400, lr=3e-3)
        params, _ = H.train_head_from_gmms(key, *stack, N_CLASSES, cfg,
                                           "diag")
        lo = np.asarray(yt) == 0
        acc0 = float(H.accuracy(params, jnp.asarray(np.asarray(xt)[lo]),
                                jnp.asarray(np.asarray(yt)[lo])))
        assert acc0 > 0.5, f"minority-slot class forgotten: acc0={acc0}"


class TestPaddedSlotStack:
    """The fl.ingest contract on the fused trainer: a prefix of
    identity-GMM pad rows with count 0 must not change ONE bit of the
    trained head — leading zeros are exact under the f32 cumulative mass
    and draw_slots' u≈1 clip lands on the last real row either way."""

    @pytest.mark.parametrize("cov", ["full", "diag", "spher"])
    def test_prefix_pads_train_bit_identical_head(self, key, cov):
        M, C = SKEWED.shape
        batch = _random_batch(key, M, C, cov=cov)
        stack, labels, counts, _ = _slot_stack(batch, SKEWED)
        cfg = H.HeadConfig(n_steps=60, lr=3e-3)
        base, base_losses = H.train_head_from_gmms(
            key, stack["pi"], stack["mu"], stack["cov"], labels, counts,
            N_CLASSES, cfg, cov)
        pad = G.identity_gmm(2, DIM, cov)
        n_pad = 5
        grow = lambda a, p: jnp.concatenate(
            [jnp.tile(jnp.asarray(p)[None], (n_pad,) + (1,) * p.ndim), a])
        padded, pad_losses = H.train_head_from_gmms(
            key, grow(stack["pi"], pad["pi"]), grow(stack["mu"], pad["mu"]),
            grow(stack["cov"], pad["cov"]),
            jnp.concatenate([jnp.zeros((n_pad,), jnp.int32), labels]),
            jnp.concatenate([jnp.zeros((n_pad,), jnp.int32),
                             jnp.asarray(counts)]),
            N_CLASSES, cfg, cov)
        for k in ("w", "b"):
            np.testing.assert_array_equal(np.asarray(base[k]),
                                          np.asarray(padded[k]))
        np.testing.assert_array_equal(np.asarray(base_losses),
                                      np.asarray(pad_losses))

    def test_mismatched_slot_metadata_raises(self, key):
        batch = _random_batch(key, *SKEWED.shape)
        stack, labels, counts, _ = _slot_stack(batch, SKEWED)
        with pytest.raises(ValueError, match="one label and one draw count"):
            H.train_head_from_gmms(key, stack["pi"], stack["mu"],
                                   stack["cov"], labels[:-1], counts,
                                   N_CLASSES, H.HeadConfig(n_steps=5), "diag")

    @pytest.mark.parametrize("cov", ["full", "diag", "spher"])
    def test_identity_gmm_is_sampler_safe(self, key, cov):
        pad = G.identity_gmm(3, DIM, cov)
        fac = G.sampling_factor(jnp.asarray(pad["cov"])[None], cov)
        assert np.isfinite(np.asarray(fac)).all()
        np.testing.assert_allclose(np.asarray(pad["pi"]).sum(), 1.0,
                                   rtol=1e-6)
