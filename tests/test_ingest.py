"""Streaming cohort ingestion (ISSUE 6, DESIGN.md §9): the mergeable
SlotTable / IngestState algebra (associative, arrival-order invariant,
empty identity), the broker's admission / deadline / byte accounting, the
memory law (peak resident bytes independent of M), and end-to-end
bit-identity of `FedSession(ingest=...)` with the non-streaming fused
session — host and mesh paths."""
import dataclasses

import jax
import numpy as np
import pytest
from _checks import assert_peak_bytes
from _hyp import given, settings, st

from repro import data as D
from repro.core import gmm as G
from repro.core import head as H
from repro.fl import api as FA
from repro.fl import ingest as IG
from repro.fl import planner as P

N_CLASSES = 4
DIM = 8
K = 2

_CODEC = FA.QuantizedCodec("bfloat16")


def _msg(cid: int, counts, cov="diag", n_classes=N_CLASSES, d=DIM):
    """A deterministic synthetic GMM message for client ``cid``."""
    rs = np.random.RandomState(1000 + cid)
    counts = np.asarray(counts, np.int64)
    shapes = {"full": (n_classes, K, d, d), "diag": (n_classes, K, d),
              "spher": (n_classes, K)}
    cov_arr = (0.1 + rs.rand(*shapes[cov])).astype(np.float32)
    if cov == "full":
        cov_arr = np.eye(d, dtype=np.float32) * \
            (0.1 + rs.rand(n_classes, K, 1, 1).astype(np.float32))
    params = {"pi": rs.dirichlet(np.ones(K), n_classes).astype(np.float32),
              "mu": rs.randn(n_classes, K, d).astype(np.float32),
              "cov": cov_arr}
    return FA.encode_message(params, counts, np.zeros(1), kind="gmm",
                             cov_type=cov, n_classes=n_classes, codec=_CODEC)


def _cohort(m, seed=0, cov="diag"):
    """[(cid, msg)] with skewed random counts, every client nonempty."""
    rs = np.random.RandomState(seed)
    items = []
    for cid in range(m):
        counts = rs.randint(0, 30, N_CLASSES).astype(np.int64)
        if (counts == 0).all():
            counts[rs.randint(N_CLASSES)] = 1
        items.append((cid, _msg(cid, counts, cov=cov)))
    return items


def _empty(capacity=64, cov="diag", seed=0):
    return IG.IngestState.empty(N_CLASSES, cov, K, DIM, capacity, seed)


def _states_equal(a: IG.IngestState, b: IG.IngestState) -> bool:
    return (a.signature == b.signature
            and all(np.array_equal(getattr(a, f), getattr(b, f))
                    for f in ("slot_ids", "priority", "counts",
                              "pi", "mu", "cov"))
            and (a.n_clients, a.slots_seen, a.mass_seen)
            == (b.n_clients, b.slots_seen, b.mass_seen))


def _fold_chunks(items, chunk, state=None, spc=None, **kw):
    state = _empty(**kw) if state is None else state
    for i in range(0, len(items), chunk):
        state = IG.fold_messages(state, items[i:i + chunk],
                                 samples_per_class=spc)
    return state


# ---------------------------------------------------------------------------
# SlotTable algebra
# ---------------------------------------------------------------------------


class TestSlotTableMerge:
    def test_empty_is_identity(self):
        t = P.SlotTable.from_slots([3, 1, 9], [5, 2, 7])
        for m in (t.merge(P.SlotTable.empty()),
                  P.SlotTable.empty().merge(t)):
            np.testing.assert_array_equal(m.slots, t.slots)
            np.testing.assert_array_equal(m.counts, t.counts)
            np.testing.assert_array_equal(m.cum_mass, t.cum_mass)

    def test_merge_commutes_and_associates_bitwise(self):
        a = P.SlotTable.from_slots([0, 5], [3, 4])
        b = P.SlotTable.from_slots([2, 5, 7], [1, 1, 9])
        c = P.SlotTable.from_slots([1], [6])
        ab, ba = a.merge(b), b.merge(a)
        np.testing.assert_array_equal(ab.slots, ba.slots)
        np.testing.assert_array_equal(ab.cum_mass, ba.cum_mass)
        l, r = a.merge(b).merge(c), a.merge(b.merge(c))
        np.testing.assert_array_equal(l.slots, r.slots)
        np.testing.assert_array_equal(l.counts, r.counts)
        np.testing.assert_array_equal(l.cum_mass, r.cum_mass)

    def test_shared_slots_sum_counts(self):
        m = P.SlotTable.from_slots([2, 4], [3, 5]).merge(
            P.SlotTable.from_slots([4, 6], [2, 1]))
        np.testing.assert_array_equal(m.slots, [2, 4, 6])
        np.testing.assert_array_equal(m.counts, [3, 7, 1])

    def test_chunkwise_fold_equals_full_plan_table(self):
        """Per-client tables folded in any order == the full-cohort
        planner's table, bitwise — the mergeability the ingest state
        rests on."""
        counts = np.array([[1, 3, 0, 700], [120, 4096, 17, 0],
                           [0, 0, 5, 5], [9, 0, 0, 2]])
        full = P.plan_synthesis(counts).slot_table
        per_client = [P.plan_synthesis(counts[m][None]).slot_table
                      for m in range(counts.shape[0])]
        # re-key each client's table to global slot ids
        per_client = [P.SlotTable.from_slots(t.slots + m * counts.shape[1],
                                             t.counts)
                      for m, t in enumerate(per_client)]
        for order in ([0, 1, 2, 3], [3, 1, 0, 2]):
            acc = P.SlotTable.empty()
            for i in order:
                acc = acc.merge(per_client[i])
            np.testing.assert_array_equal(acc.slots, full.slots)
            np.testing.assert_array_equal(acc.counts, full.counts)
            np.testing.assert_array_equal(acc.cum_mass, full.cum_mass)

    def test_from_slots_rejects_bad_input(self):
        with pytest.raises(ValueError, match="≥ 1"):
            P.SlotTable.from_slots([1, 2], [3, 0])
        with pytest.raises(ValueError, match="duplicate"):
            P.SlotTable.from_slots([1, 1], [3, 2])
        with pytest.raises(ValueError, match="one count per slot"):
            P.SlotTable.from_slots([1, 2], [3])


# ---------------------------------------------------------------------------
# deterministic priorities
# ---------------------------------------------------------------------------


class TestSlotPriority:
    def test_pure_and_seed_dependent(self):
        ids = np.arange(100, dtype=np.int64)
        cnt = np.full(100, 7)
        p1 = IG.slot_priority(ids, cnt, seed=0)
        np.testing.assert_array_equal(p1, IG.slot_priority(ids, cnt, 0))
        assert not np.array_equal(p1, IG.slot_priority(ids, cnt, 1))
        assert np.unique(p1).size == 100          # no collisions here
        assert (p1 < 0).all() and np.isfinite(p1).all()

    def test_heavier_counts_win_in_aggregate(self):
        """Efraimidis–Spirakis: P(slot in top-R) grows with its weight —
        check the aggregate retention rate, not individual draws."""
        ids = np.arange(2000, dtype=np.int64)
        heavy = ids < 1000
        cnt = np.where(heavy, 100, 1)
        top = np.argsort(-IG.slot_priority(ids, cnt, 0))[:500]
        assert heavy[top].mean() > 0.9


# ---------------------------------------------------------------------------
# IngestState algebra
# ---------------------------------------------------------------------------


class TestIngestStateMerge:
    def test_empty_is_identity(self):
        s = _fold_chunks(_cohort(6), chunk=3)
        assert _states_equal(s.merge(_empty()), s)
        assert _states_equal(_empty().merge(s), s)

    def test_merge_commutes(self):
        items = _cohort(8)
        a = _fold_chunks(items[:3], chunk=2)
        b = _fold_chunks(items[3:], chunk=2)
        assert _states_equal(a.merge(b), b.merge(a))

    def test_merge_associates(self):
        items = _cohort(9)
        a, b, c = (_fold_chunks(items[i::3], chunk=2) for i in range(3))
        assert _states_equal(a.merge(b).merge(c), a.merge(b.merge(c)))

    @pytest.mark.parametrize("chunk", [1, 3, 100])
    def test_chunk_size_invariant(self, chunk):
        items = _cohort(10)
        assert _states_equal(_fold_chunks(items, chunk),
                             _fold_chunks(items, chunk=4))

    def test_arrival_order_invariant(self):
        items = _cohort(10)
        shuffled = [items[i] for i in
                    np.random.RandomState(7).permutation(len(items))]
        assert _states_equal(_fold_chunks(items, 3),
                             _fold_chunks(shuffled, 3))

    def test_under_capacity_is_exact(self):
        """No eviction below capacity: the retained table == the
        full-cohort planner table, bitwise."""
        items = _cohort(10)
        state = _fold_chunks(items, 4, capacity=N_CLASSES * 10)
        assert state.evicted == 0
        counts = np.stack([m.counts for _, m in items])
        full = P.plan_synthesis(counts).slot_table
        table = state.slot_table()
        np.testing.assert_array_equal(table.slots, full.slots)
        np.testing.assert_array_equal(table.counts, full.counts)
        np.testing.assert_array_equal(table.cum_mass, full.cum_mass)

    def test_over_capacity_keeps_top_priorities(self):
        items = _cohort(30)
        state = _fold_chunks(items, 5, capacity=16)
        assert state.retained == 16
        assert state.evicted == state.slots_seen - 16 > 0
        # survivors are exactly the global top-16 by priority
        ids, cnts = [], []
        for cid, m in items:
            present = np.flatnonzero(m.counts > 0)
            ids.append(cid * N_CLASSES + present)
            cnts.append(m.counts[present])
        ids, cnts = np.concatenate(ids), np.concatenate(cnts)
        prio = IG.slot_priority(ids, cnts, seed=0)
        top = set(ids[np.argsort(-prio)[:16]].tolist())
        assert set(state.slot_ids[state.slot_ids >= 0].tolist()) == top

    def test_canonical_layout_pads_first(self):
        state = _fold_chunks(_cohort(3), 2, capacity=64)
        ids = state.slot_ids
        n_pad = int((ids < 0).sum())
        assert (ids[:n_pad] == -1).all()           # pads lead
        real = ids[n_pad:]
        assert (np.diff(real) > 0).all()           # retained ascend
        assert (state.counts[:n_pad] == 0).all()
        assert (state.priority[:n_pad] == -np.inf).all()

    def test_signature_mismatch_raises(self):
        s = _fold_chunks(_cohort(2), 2)
        with pytest.raises(ValueError, match="incompatible"):
            s.merge(_empty(capacity=32))
        with pytest.raises(ValueError, match="schema"):
            IG.fold_messages(s, [(99, _msg(99, [1, 1, 1, 1], cov="spher"))])

    def test_samples_per_class_law_matches_planner(self):
        items = _cohort(5)
        state = _fold_chunks(items, 2, spc=7, capacity=N_CLASSES * 5)
        counts = np.stack([m.counts for _, m in items])
        full = P.plan_synthesis(counts, samples_per_class=7).slot_table
        table = state.slot_table()
        np.testing.assert_array_equal(table.slots, full.slots)
        np.testing.assert_array_equal(table.counts, full.counts)


# ---------------------------------------------------------------------------
# the broker
# ---------------------------------------------------------------------------


class TestBroker:
    def _broker(self, **kw):
        cfg = IG.IngestConfig(**{"chunk_size": 4, "capacity": 64, **kw})
        return IG.IngestBroker(cfg, N_CLASSES)

    def test_exact_byte_accounting(self):
        items = _cohort(9)
        broker = self._broker()
        for cid, m in items:
            assert broker.submit(cid, m) == IG.ADMITTED
        broker.close()
        acct = broker.accounting()
        assert acct["admitted_bytes"] == sum(len(m.payload)
                                             for _, m in items)
        assert acct["admitted_bytes"] == sum(m.comm_bytes
                                             for _, m in items)
        assert acct["admitted"] == 9 and acct["late"] == 0
        assert acct["chunks_folded"] == 3   # 4 + 4 + close() remainder

    def test_duplicate_and_over_cap_verdicts(self):
        broker = self._broker(max_clients=2)
        m = _msg(0, [5, 0, 0, 0])
        assert broker.submit(0, m) == IG.ADMITTED
        assert broker.submit(0, m) == IG.DUPLICATE
        assert broker.submit(1, _msg(1, [1, 2, 3, 4])) == IG.ADMITTED
        assert broker.submit(2, _msg(2, [1, 1, 1, 1])) == IG.OVER_CAP
        acct = broker.accounting()
        assert (acct["admitted"], acct["duplicates"],
                acct["over_cap"]) == (2, 1, 1)

    def test_deadline_closes_round_with_stragglers(self):
        """Messages after the deadline are byte-accounted stragglers; the
        state — and thus the head — covers exactly the admitted prefix."""
        items = _cohort(10)
        t = {"now": 0.0}
        broker = IG.IngestBroker(
            IG.IngestConfig(chunk_size=3, capacity=64, deadline_s=5.0),
            N_CLASSES, clock=lambda: t["now"])
        for i, (cid, m) in enumerate(items):
            t["now"] = float(i)                 # client i arrives at t=i
            verdict = broker.submit(cid, m)
            assert verdict == (IG.ADMITTED if i <= 5 else IG.LATE)
        state = broker.close()
        acct = broker.accounting()
        assert (acct["admitted"], acct["late"]) == (6, 4)
        assert acct["late_bytes"] == sum(m.comm_bytes
                                         for _, m in items[6:])
        # state == folding ONLY the admitted prefix
        assert _states_equal(state, _fold_chunks(items[:6], 3))
        # and it still trains a finite head
        pi, mu, cov, labels, counts = state.padded_stack()
        head, _ = H.train_head_from_gmms(
            jax.random.PRNGKey(0), pi, mu, cov, labels, counts, N_CLASSES,
            H.HeadConfig(n_steps=20), "diag")
        assert np.isfinite(np.asarray(head["w"])).all()

    def test_submit_after_close_is_closed(self):
        """A sealed round answers ``closed`` — not ``late`` (that verdict
        is for deadline stragglers while the round is live) — and the
        refused bytes land in the ``closed_bytes`` bucket."""
        broker = self._broker()
        broker.submit(0, _msg(0, [1, 1, 1, 1]))
        broker.close()
        m = _msg(1, [1, 1, 1, 1])
        assert broker.submit(1, m) == IG.CLOSED
        acct = broker.accounting()
        assert acct["closed"] == 1
        assert acct["closed_bytes"] == m.comm_bytes
        assert acct["late"] == 0

    def test_duplicate_after_close_is_closed(self):
        """CLOSED outranks DUPLICATE: the sealed round refuses a replayed
        client id without consulting the duplicate set."""
        broker = self._broker()
        m = _msg(0, [1, 1, 1, 1])
        assert broker.submit(0, m) == IG.ADMITTED
        broker.close()
        assert broker.submit(0, m) == IG.CLOSED
        acct = broker.accounting()
        assert acct["duplicates"] == 0 and acct["closed"] == 1

    def test_byte_conservation_across_all_verdicts(self):
        """Σ per-verdict bytes == sent_bytes with every verdict class
        exercised in one round (admitted, duplicate, over_cap, late,
        quarantined, closed)."""
        t = {"now": 0.0}
        broker = IG.IngestBroker(
            IG.IngestConfig(chunk_size=4, capacity=64, max_clients=2,
                            deadline_s=5.0),
            N_CLASSES, clock=lambda: t["now"])
        m0, m1, m2 = (_msg(i, [1, 1, 1, 1]) for i in range(3))
        assert broker.submit(0, m0) == IG.ADMITTED
        assert broker.submit(0, m0) == IG.DUPLICATE
        bad = dataclasses.replace(m1, payload=m1.payload[:-3])
        assert broker.submit(1, bad) == IG.QUARANTINED
        assert broker.submit(1, m1) == IG.ADMITTED
        assert broker.submit(2, m2) == IG.OVER_CAP
        t["now"] = 9.0
        assert broker.submit(2, m2) == IG.LATE
        broker.close()
        assert broker.submit(2, m2) == IG.CLOSED
        acct = broker.accounting()
        assert (acct["admitted"], acct["duplicates"], acct["quarantined"],
                acct["over_cap"], acct["late"], acct["closed"]) \
            == (2, 1, 1, 1, 1, 1)
        per_verdict = (acct["admitted_bytes"] + acct["duplicate_bytes"]
                       + acct["quarantined_bytes"] + acct["over_cap_bytes"]
                       + acct["late_bytes"] + acct["closed_bytes"])
        assert per_verdict == acct["sent_bytes"]
        # 2×m0 (admit+dup), m1, 3×m2 (over_cap+late+closed), 1 truncated
        assert acct["sent_bytes"] == 6 * m0.comm_bytes + bad.comm_bytes

    def test_quarantine_keeps_reservoir_clean(self):
        """A truncated payload is rejected at the wire: the reservoir
        state equals a round that never saw it, and the rejection is
        recorded with a structured reason."""
        items = _cohort(4)
        broker = self._broker()
        cid0, m0 = items[0]
        bad = dataclasses.replace(m0, payload=m0.payload[:-5])
        assert broker.submit(99, bad) == IG.QUARANTINED
        for cid, m in items:
            assert broker.submit(cid, m) == IG.ADMITTED
        state = broker.close()
        assert _states_equal(state, _fold_chunks(items, 4))
        assert broker.rejections[0].reason == "length_mismatch"
        assert broker.rejections[0].client_id == 99
        # quarantined id never admitted → doesn't trip the duplicate set
        assert 99 not in broker.admitted_ids

    def test_peak_bytes_independent_of_M(self):
        """THE memory law: same (capacity, chunk_size, message schema) →
        same peak resident bytes, whatever the cohort size.  All classes
        present keeps the message schema (and so the pending-chunk bytes)
        fixed across clients."""
        peaks = {}
        for m_clients in (16, 64):
            broker = self._broker()
            for cid in range(m_clients):
                broker.submit(cid, _msg(cid, [3, 4, 5, 6]))
            broker.close()
            peaks[m_clients] = broker.accounting()["peak_resident_bytes"]
        assert_peak_bytes(peaks[64], peaks[16], msg="peak grew with M")
        assert peaks[64] == peaks[16]

    def test_rejects_head_messages(self):
        broker = self._broker()
        rs = np.random.RandomState(0)
        head_msg = FA.encode_message(
            {"w": rs.randn(DIM, N_CLASSES).astype(np.float32),
             "b": np.zeros(N_CLASSES, np.float32)},
            np.ones(N_CLASSES), np.zeros(1), kind="head", cov_type="",
            n_classes=N_CLASSES, codec=_CODEC)
        with pytest.raises(ValueError, match="head"):
            broker.submit(0, head_msg)


# ---------------------------------------------------------------------------
# FedSession integration
# ---------------------------------------------------------------------------


def _clients(key, n=5):
    dcfg = D.DatasetConfig(n_classes=N_CLASSES, n_per_class=60,
                           input_dim=DIM, class_sep=2.0)
    x, y = D.make_dataset(dcfg)
    parts = D.dirichlet_partition(np.asarray(y), n, beta=0.5)
    return [(x[p], y[p]) for p in parts if len(p) > 5]


def _session(**kw):
    return FA.FedSession(
        n_classes=N_CLASSES,
        summarizer=FA.GMMSummarizer(
            G.GMMConfig(n_components=K, cov_type="diag", n_iter=8)),
        head=H.HeadConfig(n_steps=100, lr=3e-3), **kw)


def _heads_equal(a, b):
    return all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
               for k in ("w", "b"))


class TestSessionIngest:
    @pytest.mark.parametrize("chunk", [1, 2, 100])
    def test_bit_identical_to_fused_session(self, key, chunk, sanitized):
        """The acceptance bar: under capacity, the streaming session's
        head equals the non-streaming fused session's BITWISE, at every
        chunk size.  Runs under the runtime sanitizer; bit-identity
        *requires* replaying one key, so history is reset between runs."""
        clients = _clients(key)
        sanitized.reset()
        base = _session().run(key, clients)
        sanitized.reset()
        res = _session(ingest=IG.IngestConfig(chunk_size=chunk,
                                              capacity=64)
                       ).run(key, clients)
        assert _heads_equal(base.model, res.model)
        acct = res.info["ingest"]
        assert acct["admitted"] == len(clients)
        assert res.info["comm_bytes"] == acct["admitted_bytes"]
        assert res.messages == []           # discarded, never stacked

    def test_server_aggregate_order_invariant(self, key):
        """server_aggregate(ingest=) on a permuted message list with
        stable ids folds to the same state — the broker's algebra seen
        through the session surface."""
        items = _cohort(8)
        sess = _session(ingest=IG.IngestConfig(chunk_size=3, capacity=64))
        broker_a = IG.IngestBroker(sess.ingest, N_CLASSES)
        broker_b = IG.IngestBroker(sess.ingest, N_CLASSES)
        perm = np.random.RandomState(3).permutation(len(items))
        for cid, m in items:
            broker_a.submit(cid, m)
        for i in perm:
            broker_b.submit(*items[i])
        assert _states_equal(broker_a.close(), broker_b.close())

    def test_mesh_path_bit_identical(self, key):
        """run_sharded(ingest=) — the mesh server phase through the
        broker — equals the mesh fused path bitwise on a 1-shard mesh."""
        clients = _clients(key)
        n = min(int(f.shape[0]) for f, _ in clients)
        feats = [(f[:n], y[:n]) for f, y in clients]
        base = _session(shards=1).run(key, feats)
        res = _session(shards=1,
                       ingest=IG.IngestConfig(chunk_size=2, capacity=64)
                       ).run(key, feats)
        assert _heads_equal(base.model, res.model)
        assert "ingest" in res.info and "mesh_wire_bytes" in res.info

    def test_samples_per_class_parity(self, key):
        clients = _clients(key)
        base = _session(samples_per_class=9).run(key, clients)
        res = _session(samples_per_class=9,
                       ingest=IG.IngestConfig(chunk_size=2, capacity=64)
                       ).run(key, clients)
        assert _heads_equal(base.model, res.model)

    @pytest.mark.parametrize("cov", ["full", "spher"])
    def test_other_cov_families(self, key, cov):
        clients = _clients(key)
        mk = lambda **kw: FA.FedSession(
            n_classes=N_CLASSES,
            summarizer=FA.GMMSummarizer(
                G.GMMConfig(n_components=K, cov_type=cov, n_iter=8)),
            head=H.HeadConfig(n_steps=100, lr=3e-3), **kw)
        base = mk().run(key, clients)
        res = mk(ingest=IG.IngestConfig(chunk_size=2, capacity=64)
                 ).run(key, clients)
        assert _heads_equal(base.model, res.model)

    def test_empty_cohort_guard(self, key):
        res = _session(min_class_count=10 ** 9,
                       ingest=IG.IngestConfig(capacity=64)
                       ).run(key, _clients(key))
        assert res.info.get("empty_cohort") is True
        assert np.isfinite(np.asarray(res.model["w"])).all()

    def test_rejects_chain_topology(self, key):
        with pytest.raises(NotImplementedError, match="Star"):
            _session(topology=FA.Chain(),
                     ingest=IG.IngestConfig()).run(key, _clients(key))

    def test_compile_shape_is_capacity_not_M(self, key):
        """Stable compile keys: two cohort sizes at one capacity hand the
        fused scan identical input shapes."""
        shapes = set()
        for n in (3, 5):
            clients = _clients(key, n=n)
            cfg = IG.IngestConfig(chunk_size=2, capacity=32)
            broker = IG.IngestBroker(cfg, N_CLASSES)
            sess = _session()
            keys = jax.random.split(key, len(clients) + 1)
            for i, (k, (f, y)) in enumerate(zip(keys[1:], clients)):
                broker.submit(i, sess.client_update(k, f, y, i))
            state = broker.close()
            pi, mu, cov, labels, counts = state.padded_stack()
            shapes.add((pi.shape, mu.shape, cov.shape, labels.shape,
                        counts.shape))
        assert len(shapes) == 1
        assert next(iter(shapes))[0] == (32, K)


# ---------------------------------------------------------------------------
# hypothesis hardening (slow lane, skips without hypothesis)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestMergeAlgebraProperties:
    @given(st.integers(1, 12), st.integers(1, 12), st.integers(0, 100))
    @settings(max_examples=20, deadline=None)
    def test_state_merge_commutes(self, na, nb, seed):
        items = _cohort(na + nb, seed=seed)
        a = _fold_chunks(items[:na], chunk=3, capacity=24)
        b = _fold_chunks(items[na:], chunk=3, capacity=24)
        assert _states_equal(a.merge(b), b.merge(a))

    @given(st.integers(2, 15), st.integers(1, 7), st.integers(1, 7),
           st.integers(0, 100))
    @settings(max_examples=20, deadline=None)
    def test_chunk_and_order_invariance(self, m, c1, c2, seed):
        items = _cohort(m, seed=seed)
        perm = np.random.RandomState(seed).permutation(m)
        assert _states_equal(
            _fold_chunks(items, c1, capacity=24),
            _fold_chunks([items[i] for i in perm], c2, capacity=24))

    @given(st.integers(1, 10), st.integers(0, 100))
    @settings(max_examples=15, deadline=None)
    def test_empty_identity(self, m, seed):
        s = _fold_chunks(_cohort(m, seed=seed), chunk=4, capacity=24)
        assert _states_equal(s.merge(_empty(capacity=24)), s)
        assert _states_equal(_empty(capacity=24).merge(s), s)

    @given(st.lists(st.tuples(st.integers(0, 500), st.integers(1, 50)),
                    min_size=1, max_size=30, unique_by=lambda t: t[0]),
           st.integers(1, 5))
    @settings(max_examples=25, deadline=None)
    def test_slot_table_fold_order_free(self, pairs, n_parts):
        ids = np.array([p[0] for p in pairs])
        cnts = np.array([p[1] for p in pairs])
        full = P.SlotTable.from_slots(ids, cnts)
        parts = [P.SlotTable.from_slots(ids[i::n_parts], cnts[i::n_parts])
                 for i in range(n_parts) if ids[i::n_parts].size]
        acc = P.SlotTable.empty()
        for t in reversed(parts):
            acc = acc.merge(t)
        np.testing.assert_array_equal(acc.slots, full.slots)
        np.testing.assert_array_equal(acc.counts, full.counts)
        np.testing.assert_array_equal(acc.cum_mass, full.cum_mass)

    @given(st.integers(1, 4), st.integers(0, 50))
    @settings(max_examples=8, deadline=None)
    def test_trained_head_chunk_invariant(self, chunk, seed):
        """The end-to-end property the algebra exists for: fold order and
        chunk size never change one bit of the trained head."""
        items = _cohort(6, seed=seed)
        perm = np.random.RandomState(seed).permutation(6)
        cfg = H.HeadConfig(n_steps=30, lr=3e-3)
        heads = []
        for seq, ch in ((items, chunk), ([items[i] for i in perm], 3)):
            state = _fold_chunks(seq, ch, capacity=32)
            pi, mu, cov, labels, counts = state.padded_stack()
            head, _ = H.train_head_from_gmms(
                jax.random.PRNGKey(0), pi, mu, cov, labels, counts,
                N_CLASSES, cfg, "diag")
            heads.append(head)
        assert _heads_equal(*heads)
