"""AOT round-program cache (DESIGN.md §11): slot signatures and
canonicalization, LRU accounting, serialized-executable round-trip
determinism, the graceful jit fallback, bit-identity of padded canonical
slot stacks with the exact-shape fused path (host, mesh, ingest, broker),
and the multi-tenant acceptance law — after one pass over the canonical
grid a mixed-signature stream triggers ZERO new compiles."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gmm as G
from repro.core import head as H
from repro.fl import api as FA
from repro.fl import ingest as IG
from repro.fl import round as FR
from repro.fl.ingest import IngestConfig
from repro.launch import input_specs as IS
from repro.launch.aot_cache import (CachedProgram, ProgramCache,
                                    canonical_grid, mesh_fingerprint)

N_CLASSES = 4
DIM = 8
K = 2

_CODEC = FA.QuantizedCodec("bfloat16")
# small scans keep per-entry compile cost low; the cache's law is
# config-independent
_HEAD = H.HeadConfig(n_steps=20, batch_size=64)


def _msg(cid: int, counts, cov="diag", d=DIM, codec=_CODEC):
    """A deterministic synthetic GMM message for client ``cid``."""
    rs = np.random.RandomState(1000 + cid)
    counts = np.asarray(counts, np.int64)
    if cov == "full":
        cov_arr = np.eye(d, dtype=np.float32) * \
            (0.1 + rs.rand(N_CLASSES, K, 1, 1).astype(np.float32))
    elif cov == "diag":
        cov_arr = (0.1 + rs.rand(N_CLASSES, K, d)).astype(np.float32)
    else:
        cov_arr = (0.1 + rs.rand(N_CLASSES, K)).astype(np.float32)
    params = {"pi": rs.dirichlet(np.ones(K), N_CLASSES).astype(np.float32),
              "mu": rs.randn(N_CLASSES, K, d).astype(np.float32),
              "cov": cov_arr}
    return FA.encode_message(params, counts, np.zeros(N_CLASSES),
                             kind="gmm", cov_type=cov,
                             n_classes=N_CLASSES, codec=codec)


def _cohort(M: int, cov="diag", seed=0):
    rs = np.random.RandomState(seed)
    return [_msg(seed * 100 + i, rs.randint(1, 40, N_CLASSES), cov=cov)
            for i in range(M)]


def _sess(**kw):
    return FA.FedSession(n_classes=N_CLASSES, head=_HEAD, **kw)


def _slots(msgs, target=None):
    """The message-list round's slot stack as the round program's
    positional ``(pi, mu, cov, counts, slot_labels)``, padded up to
    ``target`` when given."""
    stack, labels, counts, _ = FA.fused_slot_stack(
        FA.stack_messages(msgs), np.stack([m.counts for m in msgs]))
    pi, mu, cov = stack["pi"], stack["mu"], stack["cov"]
    sig = FR.CohortSignature(int(mu.shape[0]), N_CLASSES, K, DIM,
                             msgs[0].header.cov_type)
    if target is not None:
        pi, mu, cov, labels, counts = FR.pad_slots(pi, mu, cov, labels,
                                                   counts, sig, target)
    return sig, tuple(map(jnp.asarray, (pi, mu, cov, counts, labels)))


def _same_head(a, b) -> bool:
    return bool(jnp.array_equal(a["w"], b["w"])
                and jnp.array_equal(a["b"], b["b"]))


# ---------------------------------------------------------------------------
# signatures
# ---------------------------------------------------------------------------


class TestSignature:
    def test_next_pow2(self):
        assert [FR.next_pow2(n) for n in (1, 2, 3, 5, 8, 9)] == \
            [1, 2, 4, 8, 8, 16]
        with pytest.raises(ValueError):
            FR.next_pow2(0)

    def test_signature_of_messages(self):
        """A message-list round stages one slot row per present (client,
        class) pair: 5 clients × 4 present classes → M = 20 slot rows,
        canonical 32, f32 slots with the full (K, d, d) covariance."""
        res = _sess(program_cache=ProgramCache()).server_aggregate(
            jax.random.PRNGKey(0), _cohort(5, cov="full"))
        sig = FR.CohortSignature(*res.info["compile"]["signature"])
        assert sig == FR.CohortSignature(M=5 * N_CLASSES, C=N_CLASSES, K=K,
                                         d=DIM, cov_type="full")
        assert sig.cov_shape == (K, DIM, DIM)
        assert sig.canonical().M == 32
        assert res.info["compile"]["canonical"] == \
            dataclasses.astuple(sig.canonical())
        # canonical is idempotent — grid points map to themselves
        assert sig.canonical().canonical() == sig.canonical()

    def test_signature_validation(self):
        with pytest.raises(ValueError, match="cov_type"):
            FR.CohortSignature(2, 4, 2, 8, "bogus")
        with pytest.raises(ValueError, match="non-positive"):
            FR.CohortSignature(0, 4, 2, 8, "diag")

    def test_round_specs_shapes(self):
        sig = FR.CohortSignature(16, N_CLASSES, K, DIM, "full")
        key, pi, mu, cov, counts, labels = IS.round_specs_for(sig)
        assert key.shape == (2,)
        assert pi.shape == (16, K) and mu.shape == (16, K, DIM)
        assert cov.shape == (16, K, DIM, DIM)
        assert counts.shape == labels.shape == (16,)
        assert pi.dtype == mu.dtype == cov.dtype == jnp.float32
        assert IS.round_specs_for(
            dataclasses.replace(sig, cov_type="diag"))[3].shape == \
            (16, K, DIM)
        assert IS.round_specs_for(
            dataclasses.replace(sig, cov_type="spher"))[3].shape == (16, K)

    def test_pad_slots_only_pads_up_along_m(self):
        """Padding prepends count-0 identity rows along M and nothing else:
        a target that differs in another axis, or is shorter than the
        stack, is refused instead of silently reshaping the cohort."""
        sig, (pi, mu, cov, counts, labels) = _slots(_cohort(3))
        canon = sig.canonical()
        out = FR.pad_slots(pi, mu, cov, labels, counts, sig, canon)
        n_pad = canon.M - sig.M
        assert [a.shape[0] for a in out] == [canon.M] * 5
        assert not out[4][:n_pad].any() and not out[3][:n_pad].any()
        np.testing.assert_array_equal(out[4][n_pad:], counts)
        with pytest.raises(ValueError, match="only pads along M"):
            FR.pad_slots(pi, mu, cov, labels, counts, sig,
                         dataclasses.replace(canon, K=K + 1))
        with pytest.raises(ValueError, match="target M"):
            FR.pad_slots(pi, mu, cov, labels, counts, sig,
                         dataclasses.replace(sig, M=sig.M - 1))


# ---------------------------------------------------------------------------
# the cache proper
# ---------------------------------------------------------------------------


class TestProgramCache:
    def test_same_canonical_signature_compiles_once(self):
        """Two cohorts whose M differs inside one power-of-two bucket share
        one executable: compile once, hit thereafter."""
        cache = ProgramCache()
        a = FR.CohortSignature(3, N_CLASSES, K, DIM, "diag")
        b = FR.CohortSignature(4, N_CLASSES, K, DIM, "diag")
        ea = cache.get(a, _HEAD)
        eb = cache.get(b, _HEAD)
        assert ea is eb
        st = cache.stats()
        assert (st["misses"], st["hits"], st["compiles"]) == (1, 1, 1)

    def test_distinct_cov_dtype_head_are_distinct_entries(self):
        cache = ProgramCache()
        base = FR.CohortSignature(4, N_CLASSES, K, DIM, "diag")
        cache.get(base, _HEAD)
        cache.get(dataclasses.replace(base, cov_type="spher"), _HEAD)
        cache.get(base, H.HeadConfig(n_steps=21, batch_size=64))
        assert len(cache) == 3 and cache.misses == 3 and cache.hits == 0

    def test_lru_eviction_order(self):
        cache = ProgramCache(max_entries=2)
        sigs = [FR.CohortSignature(m, N_CLASSES, K, DIM, "diag")
                for m in (2, 4, 8)]
        cache.get(sigs[0], _HEAD)
        cache.get(sigs[1], _HEAD)
        cache.get(sigs[2], _HEAD)          # evicts sigs[0] (oldest)
        assert cache.evictions == 1
        assert [k[0].M for k in cache.keys()] == [4, 8]
        cache.get(sigs[1], _HEAD)          # touch 4 → 8 becomes LRU
        cache.get(sigs[0], _HEAD)          # re-miss 2 → evicts 8
        assert cache.evictions == 2
        assert [k[0].M for k in cache.keys()] == [4, 2]
        st = cache.stats()
        assert st["misses"] == 4 and st["hits"] == 1

    def test_serialized_roundtrip_is_deterministic(self):
        """deserialize(serialize(compiled)) must run bit-identical to the
        live executable — the deployment artifact IS the program."""
        cache = ProgramCache()
        sig, _ = _slots(_cohort(4))
        entry = cache.get(sig, _HEAD)
        if entry.serialized is None:
            pytest.skip("backend cannot serialize executables")
        _, stack = _slots(_cohort(4), cache.canonical(sig))
        args = (jax.random.PRNGKey(3),) + stack
        head_live, losses_live = entry(*args)
        head_rt, losses_rt = entry.deserialize()(*args)
        assert _same_head(head_live, head_rt)
        assert jnp.array_equal(losses_live, losses_rt)

    def test_jit_fallback_on_compile_failure(self, monkeypatch):
        """A backend that can't AOT-compile still serves rounds (plain jit)
        and says so in the counters."""
        from repro.launch import aot_cache as AC
        monkeypatch.setattr(
            AC.IS, "round_specs_for",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("no AOT")))
        cache = ProgramCache()
        msgs = _cohort(4)
        sig, _ = _slots(msgs)
        entry = cache.get(sig, _HEAD)
        assert not entry.aot and cache.jit_fallbacks == 1 \
            and cache.compiles == 0
        # the failure stays visible: the exception rides on the entry
        assert isinstance(entry.errors["compile"], RuntimeError)
        assert str(entry.errors["compile"]) == "no AOT"
        with pytest.raises(ValueError, match="serialized"):
            entry.deserialize()
        # the fallback round is still the SAME program — bit-identical to
        # an AOT entry for the same canonical signature
        _, stack = _slots(msgs, cache.canonical(sig))
        args = (jax.random.PRNGKey(3),) + stack
        head_fb, _ = entry(*args)
        monkeypatch.undo()
        head_aot, _ = ProgramCache().get(sig, _HEAD)(*args)
        assert _same_head(head_fb, head_aot)

    def test_mesh_fingerprint_keys(self):
        from repro.launch.mesh import make_sim_mesh
        assert mesh_fingerprint(None) is None
        m = make_sim_mesh(1)
        fp = mesh_fingerprint(m)
        assert fp == mesh_fingerprint(make_sim_mesh(1)) and fp is not None

    def test_canonical_grid_rejects_non_pow2(self):
        with pytest.raises(ValueError, match="power of two"):
            canonical_grid(C=4, d=8, Ms=(3,))
        grid = canonical_grid(C=4, d=8, Ms=(4,), Ks=(1, 2),
                              cov_types=("diag", "spher"))
        assert len(grid) == 4
        assert all(s.canonical() == s for s in grid)


# ---------------------------------------------------------------------------
# padding correctness: bit-identity with the exact-shape fused path
# ---------------------------------------------------------------------------


class TestBitIdentity:
    @staticmethod
    def _round(key, route, cov, cache):
        """One round on ``route`` — every route ends in the session's one
        fused runner, cached or plain."""
        if route in ("host", "broker"):
            msgs = _cohort(5, cov=cov, seed=3)
            if route == "host":
                return _sess(program_cache=cache).server_aggregate(key, msgs)
            broker = IG.IngestBroker(IngestConfig(capacity=20), N_CLASSES)
            for i, m in enumerate(msgs):
                broker.submit(i, m)
            return _sess(program_cache=cache).aggregate_from_broker(key,
                                                                    broker)
        clients = [(np.random.RandomState(7 + i).randn(30, DIM)
                    .astype(np.float32),
                    np.random.RandomState(70 + i).randint(
                        0, N_CLASSES, 30).astype(np.int32))
                   for i in range(5)]
        summ = FA.GMMSummarizer(G.GMMConfig(K, cov, n_iter=4))
        kw = {"shards": 1} if route == "mesh" else \
            {"ingest": IngestConfig(capacity=20)}
        return _sess(summarizer=summ, program_cache=cache, **kw).run(
            key, clients)

    @pytest.mark.parametrize("cov", ["diag", "full", "spher"])
    @pytest.mark.parametrize("route", ["host", "mesh", "ingest", "broker"])
    def test_cached_equals_plain(self, key, route, cov):
        """The cached round pads the slot stack up to the canonical
        power-of-two row count with identity-GMM count-0 rows and runs the
        AOT executable; the trained head and loss trace must be
        bit-identical to the plain exact-shape run
        (``train_head_from_gmms``) on every route — message list on the
        host and on a 1-shard mesh (replicated input shardings), ingest
        session, an external broker — for every covariance family."""
        plain = self._round(key, route, cov, None)
        cache = ProgramCache()
        cached = self._round(key, route, cov, cache)
        assert "compile" not in plain.info
        assert _same_head(plain.model, cached.model)
        assert jnp.array_equal(plain.info["head_losses"],
                               cached.info["head_losses"])
        assert cached.info["compile"]["aot"]
        # five clients with every class present: the message list stages
        # one row per (client, class) slot, the reservoir its capacity —
        # 20 slot rows either way, canonical 32
        assert cached.info["compile"]["signature"][0] == 20
        sig, _, fp = cache.keys()[0]
        assert sig.M == 32 and (fp is None) == (route != "mesh")

    def test_samples_per_class_and_empty_cohort(self, key):
        msgs = _cohort(3, seed=5)
        r0 = _sess(samples_per_class=17).server_aggregate(key, msgs)
        r1 = _sess(samples_per_class=17,
                   program_cache=ProgramCache()).server_aggregate(key, msgs)
        assert _same_head(r0.model, r1.model)
        # an all-zero-count cohort (every class filtered client-side) →
        # clean empty result, no compile spent on it
        cache = ProgramCache()
        empty = [_msg(i, [0] * N_CLASSES) for i in range(3)]
        r2 = _sess(program_cache=cache).server_aggregate(key, empty)
        assert r2.info.get("empty_cohort") is True
        assert cache.misses == 0 and len(cache) == 0

    def test_heterogeneous_cohort_keeps_pooled_fallback(self, key):
        """Mixed-cov cohorts (§6.3) bypass the cache and land on the
        materializing path, exactly as without a cache."""
        msgs = _cohort(2, seed=1) + [_msg(99, [5] * N_CLASSES, cov="spher")]
        cache = ProgramCache()
        res = _sess(program_cache=cache).server_aggregate(key, msgs)
        assert res.info["synthesis"] == "pooled"
        assert res.info["synthesis_fallback"] == "heterogeneous cohort"
        assert len(cache) == 0 and "compile" not in res.info


# ---------------------------------------------------------------------------
# the multi-tenant acceptance law
# ---------------------------------------------------------------------------


class TestMultiTenant:
    def test_warm_grid_serves_stream_with_zero_new_compiles(self, key):
        """ISSUE 8 acceptance: after ONE pass over the canonical grid, a
        ≥20-cohort mixed-signature stream triggers zero new traces/
        compiles — asserted on the cache counters, per round."""
        cache = ProgramCache()
        # slot rows: 3..8 clients × 4 present classes → 12..32 → {16, 32}
        grid = canonical_grid(C=N_CLASSES, d=DIM, Ms=(16, 32), Ks=(K,),
                              cov_types=("diag", "spher"))
        cache.warmup(grid, _HEAD)
        assert cache.compiles == len(grid) == 4
        misses0, compiles0, fallbacks0 = (cache.misses, cache.compiles,
                                          cache.jit_fallbacks)
        sess = _sess(program_cache=cache)
        stream = [(3, "diag"), (5, "spher"), (4, "diag"), (8, "spher"),
                  (6, "diag"), (7, "spher"), (6, "spher")] * 3   # 21 cohorts
        keys = jax.random.split(key, len(stream))
        for k, (M, cov) in zip(keys, stream):
            res = sess.server_aggregate(k, _cohort(M, cov=cov, seed=M))
            assert res.info["compile"]["hit"], (M, cov)
        assert cache.misses == misses0
        assert cache.compiles == compiles0
        assert cache.jit_fallbacks == fallbacks0
        # the grid warms by missing; every streamed round is a pure hit
        assert cache.hits == len(stream)

    def test_info_compile_reporting(self, key):
        """info["compile"] carries hit/miss, the canonical signature, and
        compile-amortized latency that decays as the entry is reused."""
        # the first round must pay a real compile: the warm-grid test above
        # compiles the same canonical signature, and JAX's in-process
        # executable cache would otherwise serve it in about a millisecond
        jax.clear_caches()
        cache = ProgramCache()
        sess = _sess(program_cache=cache)
        r1 = sess.server_aggregate(key, _cohort(3, seed=11))
        c1 = r1.info["compile"]
        assert c1["hit"] is False and c1["aot"] is True
        # 3 clients × 4 present classes → 12 slot rows, canonical 16
        assert c1["signature"][0] == 12 and c1["canonical"][0] == 16
        assert c1["compile_us"] > 0 and c1["cache"]["entries"] == 1
        r2 = sess.server_aggregate(jax.random.PRNGKey(9),
                                   _cohort(4, seed=12))
        c2 = r2.info["compile"]
        assert c2["hit"] is True
        assert c2["amortized_us"] < c1["amortized_us"]


# ---------------------------------------------------------------------------
# CACHE-KEY analyzer rule (ISSUE 8 satellite 5)
# ---------------------------------------------------------------------------


class TestCacheKeyRule:
    def _files(self):
        from repro.analysis.core import SourceFile
        return [SourceFile.load("src/repro/fl/round.py"),
                SourceFile.load("src/repro/launch/aot_cache.py")]

    def test_live_entries_are_clean(self):
        from repro.analysis.compile import CacheKeyRule
        findings = list(CacheKeyRule().run_project(self._files()))
        assert findings == []

    def test_unstable_static_is_flagged(self):
        from repro.analysis.compile import CacheKeyRule, Entry

        class Unstable:            # fresh identity hash per construction
            pass

        bad = Entry("fake.entry", "repro/fl/round.py",
                    lambda: None, lambda: [],
                    statics=lambda: {"sig": Unstable()})
        findings = list(CacheKeyRule(entries=[bad])
                        .run_project(self._files()))
        assert any("hashes unequal" in f.message or
                   "compares or hashes" in f.message for f in findings)
        assert all(f.rule == "CACHE-KEY" for f in findings)
