"""Count-stratified synthesis planner (fl/planner + fl/api.synthesize_chunks):
plan invariants (≤ 2·Σcounts padded draws), parity with the looped
reference under heavy skew, session-vs-reference head training, and the
empty-cohort guard end to end."""
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


class TestPlan:
    def test_pow2_buckets_partition_nonzero_slots(self):
        plan = P.plan_synthesis(SKEWED)
        all_slots = np.concatenate([b.slots for b in plan.buckets])
        assert sorted(all_slots.tolist()) == \
            np.flatnonzero(SKEWED.reshape(-1) > 0).tolist()
        for b in plan.buckets:
            # every slot sits in ITS power-of-two bucket: S/2 < n ≤ S
            assert b.S & (b.S - 1) == 0
            assert (b.n_eff <= b.S).all() and (b.n_eff > b.S // 2).all()

    def test_padded_draws_le_2x_requested(self):
        plan = P.plan_synthesis(SKEWED)
        assert plan.requested == SKEWED.sum()
        assert plan.padded_draws <= 2 * plan.requested
        # and the monolithic dispatch would have padded every slot to max
        assert plan.monolithic_draws == SKEWED.size * SKEWED.max()

    def test_single_policy_reproduces_monolithic_pad(self):
        plan = P.plan_synthesis(SKEWED, policy="single")
        assert plan.n_dispatches == 1
        assert plan.buckets[0].S == SKEWED.max()
        assert plan.padded_draws == \
            int((SKEWED > 0).sum()) * int(SKEWED.max())

    def test_samples_per_class_override(self):
        plan = P.plan_synthesis(SKEWED, samples_per_class=7)
        assert plan.requested == int((SKEWED > 0).sum()) * 7
        assert all(b.S == 8 for b in plan.buckets)

    def test_empty_plan(self):
        plan = P.plan_synthesis(np.zeros((3, 4), np.int64))
        assert plan.buckets == () and plan.padded_draws == 0
        assert plan.monolithic_draws == 0

    def test_1d_counts_promote(self):
        plan = P.plan_synthesis(np.array([5, 0, 9]))
        assert (plan.M, plan.C) == (1, 3)


class TestPlannedSynthesis:
    @pytest.mark.parametrize("cov", ["full", "diag", "spher"])
    def test_skewed_parity_with_looped(self, key, cov):
        """Planner output must agree with the per-slot loop on the exact
        per-slot sample counts and labels, with finite features —
        bit-compatibility in expectation (Algorithm 1, lines 13-16)."""
        M, C = SKEWED.shape
        batch = _random_batch(key, M, C, cov=cov)
        fb, yb = FA.synthesize_batched(key, batch, SKEWED, cov)
        fl_, yl = FA.synthesize_looped(key, batch, SKEWED, cov)
        assert fb.shape == fl_.shape
        np.testing.assert_array_equal(np.sort(np.asarray(yb)),
                                      np.sort(np.asarray(yl)))
        assert np.isfinite(np.asarray(fb)).all()

    def test_chunks_reconstruct_per_slot_counts(self, key):
        """Per-slot accounting: concatenating every bucket's (slot, n_eff)
        pairs reconstructs the counts matrix exactly — no slot drawn
        twice, none dropped, each at its requested count."""
        M, C = SKEWED.shape
        batch = _random_batch(key, M, C)
        chunks, plan = FA.synthesize_chunks(key, batch, SKEWED, "diag")
        seen = np.zeros(M * C, np.int64)
        for b, (f, y) in zip(plan.buckets, chunks):
            assert int(f.shape[0]) == b.requested
            np.testing.assert_array_equal(
                np.asarray(y), np.repeat((b.slots % C).astype(np.int32),
                                         b.n_eff))
            seen[b.slots] += b.n_eff
        np.testing.assert_array_equal(seen.reshape(M, C), SKEWED)

    def test_planned_deterministic(self, key):
        M, C = SKEWED.shape
        batch = _random_batch(key, M, C)
        f1, y1 = FA.synthesize_batched(key, batch, SKEWED, "diag")
        f2, y2 = FA.synthesize_batched(key, batch, SKEWED, "diag")
        np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))

    def test_uniform_counts_degenerate_to_one_bucket(self, key):
        batch = _random_batch(key, 2, 4)
        counts = np.full((2, 4), 32)
        _, plan = FA.synthesize_chunks(key, batch, counts, "diag")
        assert plan.n_dispatches == 1
        assert plan.padded_draws == plan.requested == 8 * 32

    def test_single_policy_matches_planned_statistics(self, key):
        """Same per-class totals either way; draws differ (different padded
        S per slot) but class-conditional means agree."""
        M, C = SKEWED.shape
        batch = _random_batch(key, M, C)
        fp, yp = FA.synthesize_batched(key, batch, SKEWED, "diag")
        fm, ym = FA.synthesize_batched(key, batch, SKEWED, "diag",
                                       policy="single")
        np.testing.assert_array_equal(np.bincount(np.asarray(yp), minlength=C),
                                      np.bincount(np.asarray(ym), minlength=C))
        for c in range(C):
            if np.sum(np.asarray(yp) == c) < 50:
                continue
            mp = np.mean(np.asarray(fp)[np.asarray(yp) == c], axis=0)
            mm = np.mean(np.asarray(fm)[np.asarray(ym) == c], axis=0)
            np.testing.assert_allclose(mp, mm, atol=0.5)


class TestPooledHead:
    def test_train_head_empty_pool_returns_init(self, key):
        params, losses = H.train_head(key, jnp.zeros((0, DIM)),
                                      jnp.zeros((0,), jnp.int32),
                                      N_CLASSES, H.HeadConfig())
        assert params["w"].shape == (DIM, N_CLASSES)
        assert losses.shape == (0,)


class TestSessionIntegration:
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

    @pytest.mark.slow
    def test_streamed_synthesis_matches_pooled_session(self, key):
        """The session's server phase learns the task as well as the plain
        reference on the same decoded messages (looped synthesis, pooled,
        ``train_head``) — and reports its plan without pooling."""
        clients, xt, yt = self._clients(key)
        res = self._session().run(key, clients)
        msgs = res.messages
        k_syn, k_head = jax.random.split(key)
        pf, py = FA.synthesize_looped(k_syn, FA.stack_messages(msgs),
                                      np.stack([m.counts for m in msgs]),
                                      "diag")
        ref, _ = H.train_head(k_head, pf, py, N_CLASSES,
                              self._session().head)
        acc_p = float(H.accuracy(ref, xt, yt))
        acc_s = float(H.accuracy(res.model, xt, yt))
        assert acc_s > 0.6 and abs(acc_p - acc_s) < 0.1, (acc_p, acc_s)
        assert "synthetic_feats" not in res.info
        assert "synthesis_plans" in res.info

    def test_empty_cohort_guard_end_to_end(self, key):
        """min_class_count filtering EVERY class must yield a clean result
        (initialized finite head, empty synthetic set, empty_cohort flag)
        instead of crashing train_head on a 0-row pool."""
        clients, xt, yt = self._clients(key)
        sess = self._session(min_class_count=10 ** 9)
        res = sess.run(key, clients)
        assert res.info.get("empty_cohort") is True
        assert res.info["synthetic_feats"].shape == (0, DIM)
        assert res.info["head_losses"].shape == (0,)
        for leaf in jax.tree.leaves(res.model):
            assert np.isfinite(np.asarray(leaf)).all()
        # the untrained head still predicts *something* finite
        assert np.isfinite(
            float(H.accuracy(res.model, xt, yt)))

    def test_server_aggregate_rejects_no_messages(self, key):
        with pytest.raises(ValueError):
            self._session().server_aggregate(key, [])

    def test_plans_reported_in_info(self, key):
        clients, *_ = self._clients(key)
        res = self._session().run(key, clients)
        plans = res.info["synthesis_plans"]
        assert len(plans) == 1          # homogeneous cohort → one group
        assert plans[0].padded_draws <= 2 * plans[0].requested
