"""Gaussian mixture models in pure JAX — the paper's parametric feature model.

Replaces sklearn's ``GaussianMixture`` with a jit/vmap-compatible
fixed-iteration EM so that *per-client × per-class* fits batch into one
compiled SPMD program (the paper's Algorithm 1, line 8, reshaped for TPU).
The diag/spher E-step inside that program is the Pallas kernel path
(``kernels.ops.gmm_estep_fused``: compiled on a TPU, interpreted on the
CPU, the XLA reference only after ``ops.use_pallas(False)``):
one fused call per EM iteration covers the whole stack of fits and emits
log-numerators + row logsumexp together (DESIGN.md §8).

Covariance families (paper §3): ``full`` | ``diag`` | ``spher``.

All functions take/return plain pytrees:

    gmm = {"pi": (K,), "mu": (K,d), "cov": (K,d,d) | (K,d) | (K,)}

Sample weights make EM masked-data-friendly: a class-conditional fit over a
padded feature array is just ``weights = (labels == c)`` — this is how
``vmap`` over classes works without ragged shapes.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

COV_TYPES = ("full", "diag", "spher")
_LOG2PI = jnp.log(2.0 * jnp.pi)


@dataclasses.dataclass(frozen=True)
class GMMConfig:
    n_components: int = 10
    cov_type: str = "diag"
    n_iter: int = 30
    kmeans_iter: int = 5
    reg: float = 1e-4

    def __post_init__(self):
        assert self.cov_type in COV_TYPES, self.cov_type


# ---------------------------------------------------------------------------
# log-density  (reference semantics + the full-covariance E-step; the
# diag/spher EM hot path dispatches through kernels/ops.gmm_estep_fused —
# see _estep_lr below and DESIGN.md §8)
# ---------------------------------------------------------------------------


def log_prob_components(x: jax.Array, gmm: Dict, cov_type: str) -> jax.Array:
    """log N(x_n | mu_k, Sigma_k): (N, d) -> (N, K). f32 internally."""
    x = x.astype(jnp.float32)
    mu = gmm["mu"].astype(jnp.float32)
    cov = gmm["cov"].astype(jnp.float32)
    N, d = x.shape
    K = mu.shape[0]
    if cov_type == "full":
        chol = jnp.linalg.cholesky(cov)                       # (K,d,d)
        diff = x[None] - mu[:, None]                          # (K,N,d)
        sol = jax.vmap(
            lambda L, b: jax.scipy.linalg.solve_triangular(L, b.T,
                                                           lower=True)
        )(chol, diff)                                         # (K,d,N)
        maha = jnp.sum(jnp.square(sol), axis=1).T             # (N,K)
        logdet = 2.0 * jnp.sum(
            jnp.log(jnp.diagonal(chol, axis1=-2, axis2=-1)), axis=-1)
    elif cov_type == "diag":
        inv = 1.0 / cov                                       # (K,d)
        # matmul-shaped expansion: ||x-mu||²_Σ = x²·inv - 2x·(mu·inv) + c_k
        maha = (jnp.square(x) @ inv.T
                - 2.0 * (x @ (mu * inv).T)
                + jnp.sum(jnp.square(mu) * inv, axis=-1)[None])
        logdet = jnp.sum(jnp.log(cov), axis=-1)
    else:  # spher
        var = cov                                             # (K,)
        sq = jnp.sum(jnp.square(x), axis=-1, keepdims=True)   # (N,1)
        maha = (sq - 2.0 * (x @ mu.T)
                + jnp.sum(jnp.square(mu), axis=-1)[None]) / var[None]
        logdet = d * jnp.log(var)
    return -0.5 * (d * _LOG2PI + logdet[None] + maha)


def log_prob(x: jax.Array, gmm: Dict, cov_type: str) -> jax.Array:
    """Mixture log-density: (N,d) -> (N,)."""
    comp = log_prob_components(x, gmm, cov_type)
    logpi = jnp.log(jnp.clip(gmm["pi"].astype(jnp.float32), 1e-20))
    return jax.scipy.special.logsumexp(comp + logpi[None], axis=-1)


# ---------------------------------------------------------------------------
# init (weighted k-means seeding)
# ---------------------------------------------------------------------------


def _kmeans_init(key, x, weights, cfg: GMMConfig):
    N, d = x.shape
    K = cfg.n_components
    k_choice, k_jitter = jax.random.split(key)
    # sample K seed points ∝ weights (with replacement; deterministic);
    # an all-zero weight vector (absent class under vmap) falls back to
    # uniform — jax.random.choice with p summing to 0 is unspecified
    total = jnp.sum(weights)
    p = jnp.where(total > 0, weights / jnp.maximum(total, 1e-12), 1.0 / N)
    idx = jax.random.choice(k_choice, N, (K,), p=p, replace=True)
    mu = x[idx]                                               # (K,d)
    # jitter identical seeds apart so empty clusters don't collapse EM
    mu = mu + 1e-3 * jax.random.normal(k_jitter, mu.shape, x.dtype)

    def step(mu, _):
        d2 = (jnp.sum(jnp.square(x), -1, keepdims=True)
              - 2 * x @ mu.T + jnp.sum(jnp.square(mu), -1)[None])
        assign = jax.nn.one_hot(jnp.argmin(d2, -1), K) * weights[:, None]
        cnt = jnp.sum(assign, axis=0)                         # (K,)
        new_mu = (assign.T @ x) / jnp.maximum(cnt, 1e-12)[:, None]
        mu = jnp.where((cnt > 1e-12)[:, None], new_mu, mu)
        return mu, None
    mu, _ = jax.lax.scan(step, mu, None, length=cfg.kmeans_iter)
    return mu


def _global_cov(x, weights, cfg: GMMConfig, mu0):
    d = x.shape[-1]
    wsum = jnp.maximum(jnp.sum(weights), 1e-12)
    mean = (weights @ x) / wsum
    diff = x - mean
    var = (weights @ jnp.square(diff)) / wsum + cfg.reg       # (d,)
    K = cfg.n_components
    if cfg.cov_type == "full":
        return jnp.tile(jnp.diag(var)[None], (K, 1, 1))
    if cfg.cov_type == "diag":
        return jnp.tile(var[None], (K, 1))
    return jnp.full((K,), jnp.mean(var))


# ---------------------------------------------------------------------------
# EM
# ---------------------------------------------------------------------------


def _m_step(x, resp, cfg: GMMConfig):
    """x: (N,d), resp: (N,K) already weight-multiplied."""
    N, d = x.shape
    nk = jnp.sum(resp, axis=0)                                # (K,)
    total = jnp.maximum(jnp.sum(nk), 1e-12)
    pi = nk / total
    nk_safe = jnp.maximum(nk, 1e-12)[:, None]
    mu = (resp.T @ x) / nk_safe                               # (K,d)
    if cfg.cov_type == "full":
        # Σ_k = E[xxᵀ] − μμᵀ  (one GEMM per k via einsum)
        xx = jnp.einsum("nk,nd,ne->kde", resp, x, x) / nk_safe[..., None]
        cov = xx - mu[:, :, None] * mu[:, None, :]
        cov = cov + cfg.reg * jnp.eye(d)[None]
    elif cfg.cov_type == "diag":
        x2 = (resp.T @ jnp.square(x)) / nk_safe
        cov = jnp.maximum(x2 - jnp.square(mu) + cfg.reg, cfg.reg)
    else:
        x2 = jnp.sum(resp * jnp.sum(jnp.square(x), -1, keepdims=True),
                     axis=0) / nk_safe[:, 0]
        cov = (x2 - jnp.sum(jnp.square(mu), -1)) / d + cfg.reg
        cov = jnp.maximum(cov, cfg.reg)
    return {"pi": pi, "mu": mu, "cov": cov}


def _estep_lr(x, xb, gmm, cov_type: str):
    """Fused E-step: log-numerators lr (B,N,K) + row logsumexp (B,N).

    diag/spher dispatch through ``ops.gmm_estep_fused`` (the platform's
    Pallas path, DESIGN.md §8) on the compact shared-x block
    ``x`` (Bx, N, d); full covariance stays on the Cholesky XLA path,
    vmapped over the pre-expanded ``xb`` (B, N, d).
    """
    if cov_type == "full":
        comp = jax.vmap(lambda xx, g: log_prob_components(
            xx, g, cov_type))(xb, gmm)
        lr = comp + jnp.log(jnp.clip(gmm["pi"], 1e-20))[..., None, :]
        return lr, jax.scipy.special.logsumexp(lr, axis=-1)
    return ops.gmm_estep_fused(x, gmm["mu"], gmm["cov"], gmm["pi"])


def fit_gmm_batch(keys, x: jax.Array, weights: jax.Array,
                  cfg: GMMConfig) -> Tuple[Dict, jax.Array]:
    """Weighted EM over a stack of B fits in one compiled program.

    keys: (B,) PRNG keys; weights: (B, N); x: (Bx, N, d) with
    B % Bx == 0 — each run of B // Bx consecutive fits shares one feature
    block (e.g. one client's features fit per-class, Bx = clients,
    B = clients × classes). A zero weight row masks that sample; an
    all-zero weight vector (absent class) still returns finite params.

    The diag/spher E-step of ALL B fits is ONE ``ops.gmm_estep_fused``
    call per EM iteration — a single ``pallas_call`` on TPU — instead of
    vmap-over-reference. Init and M-step are vmapped XLA.

    Returns (gmms stacked (B, …), mean logliks (B,)).
    """
    if weights.ndim != 2:
        raise ValueError(
            f"fit_gmm_batch: weights must be (B, N), got shape "
            f"{weights.shape} — add a leading axis (weights[None]) for a "
            "single fit, or use fit_gmm")
    B = weights.shape[0]
    if x.ndim != 3:
        raise ValueError(
            f"fit_gmm_batch: x must be (Bx, N, d), got shape {x.shape} — "
            "add a leading axis (x[None]) for a single shared block")
    Bx, N = x.shape[0], x.shape[1]
    if Bx == 0 or B % Bx != 0:
        raise ValueError(
            f"fit_gmm_batch: B={B} fits do not evenly share Bx={Bx} "
            f"feature blocks (weights {weights.shape} vs x {x.shape}); "
            "each block is shared by B // Bx CONSECUTIVE fits — e.g. one "
            "client's features fit per-class has Bx=clients, "
            "B=clients*classes. Reorder or repeat x so B % Bx == 0")
    if weights.shape[1] != N:
        raise ValueError(
            f"fit_gmm_batch: weights rows ({weights.shape[1]}) must match "
            f"x's sample axis N={N} (weights {weights.shape}, x {x.shape})")
    if keys.shape[0] != B:
        raise ValueError(
            f"fit_gmm_batch: need one PRNG key per fit — got {keys.shape[0]} "
            f"keys for B={B} weight rows")
    # the dispatch state is a static jit arg: a use_pallas() flip after a
    # same-shape fit must retrace, not silently reuse the old backend
    return _fit_gmm_batch(keys, x, weights, cfg, ops.backend())


@partial(jax.jit, static_argnames=("cfg", "backend"))
def _fit_gmm_batch(keys, x, weights, cfg: GMMConfig, backend):
    # EM's moments cancel (diag Σ = E[x²] − μ², full Σ = E[xxᵀ] − μμᵀ), so
    # every matmul of the fit runs at full f32 precision: a TPU's default
    # single bf16 pass leaves errors larger than ``reg`` and turns small
    # variances negative (NaN log-densities on the next E-step)
    with jax.default_matmul_precision("highest"):
        return _em(keys, x, weights, cfg)


def _em(keys, x, weights, cfg: GMMConfig):
    B = weights.shape[0]
    x = x.astype(jnp.float32)
    weights = weights.astype(jnp.float32)
    xb = jnp.broadcast_to(x[:, None], (x.shape[0], B // x.shape[0])
                          + x.shape[1:]).reshape((B,) + x.shape[1:])

    mu0 = jax.vmap(lambda k, xx, ww: _kmeans_init(k, xx, ww, cfg))(
        keys, xb, weights)
    gmm0 = {
        "pi": jnp.full((B, cfg.n_components), 1.0 / cfg.n_components),
        "mu": mu0,
        "cov": jax.vmap(lambda xx, ww, m: _global_cov(xx, ww, cfg, m))(
            xb, weights, mu0),
    }
    wsum = jnp.maximum(jnp.sum(weights, axis=-1), 1e-12)      # (B,)

    def em_iter(gmm, _):
        lr, norm = _estep_lr(x, xb, gmm, cfg.cov_type)
        resp = jnp.exp(lr - norm[..., None]) * weights[..., None]
        ll = jnp.sum(norm * weights, axis=-1) / wsum
        gmm = jax.vmap(lambda xx, rr: _m_step(xx, rr, cfg))(xb, resp)
        return gmm, ll

    gmm, lls = jax.lax.scan(em_iter, gmm0, None, length=cfg.n_iter)
    # final loglik under the *returned* parameters — the fused E-step's
    # logsumexp IS the mixture log-density, no extra pass needed
    _, norm = _estep_lr(x, xb, gmm, cfg.cov_type)
    final_ll = jnp.sum(norm * weights, axis=-1) / wsum
    return gmm, final_ll


def fit_gmm(key, x: jax.Array, weights: jax.Array,
            cfg: GMMConfig) -> Tuple[Dict, jax.Array]:
    """Weighted EM. x: (N,d); weights: (N,) nonneg (0 masks a row).

    Returns (gmm, mean_loglik) where mean_loglik is the weighted mean
    log-likelihood of the final model — the paper's ``L_EM`` (§6.2).
    The B=1 case of :func:`fit_gmm_batch` (same compiled path).
    """
    gmm, ll = fit_gmm_batch(key[None], x[None], weights[None], cfg)
    return jax.tree.map(lambda a: a[0], gmm), ll[0]


def fit_classwise_gmms_batched(keys, feats: jax.Array, labels: jax.Array,
                               n_classes: int, cfg: GMMConfig):
    """Per-class GMMs for a whole client cohort in one batched EM.

    keys: (M,) per-client keys; feats: (M, N, d); labels: (M, N) with −1
    padding. The (M × C) stack of fits shares each client's feature block
    — one ``pallas_call`` per EM iteration for the entire cohort.

    Returns (gmms stacked (M, C, …), counts (M, C), logliks (M, C)).
    """
    M = feats.shape[0]
    onehot = jax.nn.one_hot(labels, n_classes)                # (M,N,C)
    counts = jnp.sum(onehot, axis=1)                          # (M,C)
    keys_mc = jax.vmap(lambda k: jax.random.split(k, n_classes))(keys)
    weights = jnp.swapaxes(onehot, 1, 2).reshape(M * n_classes, -1)
    gmms, lls = fit_gmm_batch(keys_mc.reshape((M * n_classes,)
                                              + keys_mc.shape[2:]),
                              feats, weights, cfg)
    gmms = jax.tree.map(
        lambda a: a.reshape((M, n_classes) + a.shape[1:]), gmms)
    return gmms, counts, lls.reshape(M, n_classes)


def fit_classwise_gmms(key, feats: jax.Array, labels: jax.Array,
                       n_classes: int, cfg: GMMConfig):
    """One GMM per class (Algorithm 1, lines 6-9, batched).

    Returns (gmms stacked over class axis, counts (C,), logliks (C,)).
    Classes with zero samples get finite placeholder params — mask with
    counts. The M=1 case of :func:`fit_classwise_gmms_batched`.
    """
    gmms, counts, lls = fit_classwise_gmms_batched(
        key[None], feats[None], labels[None], n_classes, cfg)
    return jax.tree.map(lambda a: a[0], gmms), counts[0], lls[0]


# ---------------------------------------------------------------------------
# sampling  (server side — Algorithm 1, line 14)
#
# THE per-slot sampler primitives: every path that draws synthetic features
# from mixture parameters — the bucketed `fl.api._sample_stacked` dispatch,
# the fused sampler-in-the-loop head trainer
# (`core.head.train_head_from_gmms`), and the single-mixture `sample` —
# composes `sampling_factor` + `colored_noise` (and, for the fused scan,
# `draw_slots` / `sample_slot_minibatch`), so the Gaussian transform cannot
# drift between the materializing and the zero-materialization server paths.
# ---------------------------------------------------------------------------


def sampling_factor(cov, cov_type: str) -> jax.Array:
    """Per-component Gaussian sampling factor F with F·Fᵀ = Proj_PSD(Σ).

    Wire precision (or the DP mechanism) can leave Σ slightly non-PSD; the
    clamped eigh factor U·√λ₊ for ``full`` samples N(0, Proj_PSD(Σ))
    exactly and never NaNs, unlike a Cholesky.  diag/spher clamp at 0.
    Shapes: full (…, K, d, d) → (…, K, d, d); diag (…, K, d) and spher
    (…, K) stay elementwise √.
    """
    cf = cov.astype(jnp.float32)
    if cov_type == "full":
        evals, evecs = jnp.linalg.eigh(cf)
        return evecs * jnp.sqrt(jnp.maximum(evals, 0.0))[..., None, :]
    return jnp.sqrt(jnp.maximum(cf, 0.0))


def colored_noise(fac, eps, cov_type: str) -> jax.Array:
    """Standard-normal ``eps (…, d)`` → draw with covariance ``fac·facᵀ``.

    ``fac`` is :func:`sampling_factor` output already gathered to eps's
    batch shape: full (…, d, d), diag (…, d), spher (…,).
    """
    if cov_type == "full":
        return jnp.einsum("...de,...e->...d", fac, eps)
    if cov_type == "diag":
        return fac * eps
    return fac[..., None] * eps


def draw_slots(key, cum_mass: jax.Array, n: int) -> jax.Array:
    """Categorical over mixture slots ∝ counts, via the planner's
    precomputed cumulative-mass table (``fl.planner.SlotTable.cum_mass``,
    ascending with last entry 1): one uniform draw + binary search per
    sample — O(n·log G) inside the fused training scan instead of an
    O(n·G) categorical."""
    u = jax.random.uniform(key, (n,))
    return jnp.clip(jnp.searchsorted(cum_mass, u, side="right"),
                    0, cum_mass.shape[0] - 1)


def slot_gaussian(slot, comp, eps, mu, fac, cov_type: str) -> jax.Array:
    """``mu[slot, comp] + F[slot, comp]·eps`` for any leading batch shape.

    ``slot``/``comp`` index a flat (G, K, …) stack; ``eps (…, d)`` is
    standard normal; ``fac`` is :func:`sampling_factor` output.  The
    Gaussian half of the per-slot sampler, shared by the fused head
    trainer's windowed draw and :func:`sample_slot_minibatch`.
    """
    return mu[slot, comp].astype(jnp.float32) \
        + colored_noise(fac[slot, comp], eps, cov_type)


def sample_slot_minibatch(key, cum_mass, pi, mu, fac, slot_labels,
                          n: int, cov_type: str
                          ) -> Tuple[jax.Array, jax.Array]:
    """One synthetic minibatch straight from a flat (G, K, …) slot stack.

    The reference law of the fused sampler-in-the-loop head trainer
    (``core.head.train_head_from_gmms``): slot ∝ counts via ``cum_mass``
    (:func:`draw_slots`), component from ``pi``, Gaussian draw through the
    precomputed ``fac`` (:func:`sampling_factor` / :func:`slot_gaussian`).
    Returns ``(x (n, d), y (n,))`` — no pooled tensor ever exists.  (The
    fused trainer itself hoists and windows the same three draws for RNG
    throughput — equal in law, not bitwise.)
    """
    ks, kc, kn = jax.random.split(key, 3)
    slot = draw_slots(ks, cum_mass, n)                        # (n,)
    logits = jnp.log(jnp.clip(pi[slot].astype(jnp.float32), 1e-20))
    comp = jax.random.categorical(kc, logits, axis=-1)        # (n,)
    eps = jax.random.normal(kn, (n, mu.shape[-1]), jnp.float32)
    return (slot_gaussian(slot, comp, eps, mu, fac, cov_type),
            slot_labels[slot])


def identity_gmm(K: int, d: int, cov_type: str) -> Dict[str, np.ndarray]:
    """Inert placeholder mixture: uniform pi, zero means, unit covariance.

    THE padding row for fixed-capacity slot stacks (``fl.ingest``): safe
    under every sampler primitive (``sampling_factor``'s eigh/√ stays
    finite, ``slot_gaussian`` draws N(0, I)), so a padded stack can flow
    through ``head.train_head_from_gmms`` unconditionally.  Pad rows MUST
    carry draw count 0 — the cumulative-mass categorical then never
    selects them, and the trained head is bit-identical to the unpadded
    stack (prefix pads add exact zeros to the f32 cumulative mass).
    """
    if cov_type == "full":
        cov = np.tile(np.eye(d, dtype=np.float32)[None], (K, 1, 1))
    elif cov_type == "diag":
        cov = np.ones((K, d), np.float32)
    elif cov_type == "spher":
        cov = np.ones((K,), np.float32)
    else:
        raise ValueError(f"identity_gmm: unknown cov_type {cov_type!r} — "
                         f"choose one of {COV_TYPES}")
    return {"pi": np.full((K,), 1.0 / K, np.float32),
            "mu": np.zeros((K, d), np.float32), "cov": cov}


def sample(key, gmm: Dict, n: int, cov_type: str) -> jax.Array:
    """Draw n samples from the mixture: returns (n, d)."""
    kc, kn = jax.random.split(key)
    pi = jnp.clip(gmm["pi"].astype(jnp.float32), 1e-20)
    comp = jax.random.categorical(kc, jnp.log(pi), shape=(n,))
    mu = gmm["mu"].astype(jnp.float32)[comp]                  # (n,d)
    eps = jax.random.normal(kn, mu.shape, jnp.float32)
    fac = sampling_factor(gmm["cov"], cov_type)
    return mu + colored_noise(fac[comp], eps, cov_type)


# ---------------------------------------------------------------------------
# wire format / communication accounting (paper Eqs. 9-11)
#
# THE wire-layout contract: every path that moves GMM parameters —
# the in-mesh bf16 all_gather (core/distributed.fedpft_transfer via
# pack_wire) and the host-side byte codec (fl.api.QuantizedCodec via
# encode_message) — serializes the same fields in WIRE_FIELDS order with
# full covariances tril_pack'ed to packed_cov_shape.  There is exactly one
# definition of each; fl/api delegates here rather than re-deriving.
# ---------------------------------------------------------------------------

WIRE_FIELDS = ("pi", "mu", "cov")


def packed_cov_shape(cov_type: str, K: int, d: int) -> Tuple[int, ...]:
    """Per-class shape of the ``cov`` wire leaf (full covs tril-packed)."""
    if cov_type == "full":
        return (K, d * (d + 1) // 2)
    if cov_type == "diag":
        return (K, d)
    return (K,)


def n_parameters(cov_type: str, d: int, K: int, C: int) -> int:
    """Scalar count of one client's per-class GMM transfer.

    Derived from the wire layout itself (``WIRE_FIELDS`` /
    :func:`packed_cov_shape`) so Eqs. 9-11 accounting can never drift from
    what actually crosses the wire: pi (K,) + mu (K, d) + packed cov.
    """
    cov_scalars = int(np.prod(packed_cov_shape(cov_type, K, d),
                              dtype=np.int64))
    return (K + K * d + cov_scalars) * C


def comm_bytes(cov_type: str, d: int, K: int, C: int,
               bytes_per_scalar: int = 2) -> int:
    """Paper's 16-bit wire encoding (§5.1) → bytes on the wire."""
    return n_parameters(cov_type, d, K, C) * bytes_per_scalar


def nonfinite_fields(params, fields: Tuple[str, ...] = WIRE_FIELDS):
    """Names of wire fields carrying NaN/Inf — ``[]`` when clean.

    The finite-params half of the §13 wire gate: a poisoned GMM message
    must be quarantined before it reaches ``fold_messages`` or the fused
    head scan, where one NaN mean would silently poison every synthetic
    draw of its slot.
    """
    return [f for f in fields
            if not np.isfinite(np.asarray(params[f],
                                          np.float32)).all()]


def raw_feature_bytes(n_samples: int, d: int,
                      bytes_per_scalar: int = 2) -> int:
    """Cost of the Centralized baseline: ship every feature row."""
    return n_samples * (d + 1) * bytes_per_scalar  # +1 for the label


def tril_pack(cov):
    """Row-major lower-triangle packing: (…, d, d) → (…, d·(d+1)/2).

    THE wire layout for full covariances — ``pack_wire``/``unpack_wire``
    here and the federation codec's ``fl.api._pack_cov``/``_unpack_cov``
    all delegate to this pair, so the layout cannot drift between them.
    Pure indexing: works on numpy and jax arrays alike (host codec path
    vs in-jit mesh path).
    """
    d = cov.shape[-1]
    i, j = np.tril_indices(d)
    return cov[..., i, j]


def tril_unpack(packed, d: int):
    """Inverse of :func:`tril_pack`: rebuild the symmetric (…, d, d) f32
    matrix from its row-major lower triangle.  One layout, two backends:
    numpy in → numpy out (the host codec decode path stays off-device),
    jax in → jax out (traceable inside the mesh collectives)."""
    i, j = np.tril_indices(d)
    if isinstance(packed, np.ndarray):
        cov = np.zeros(packed.shape[:-1] + (d, d), np.float32)
        cov[..., i, j] = packed
        sym = cov + np.swapaxes(cov, -1, -2)
        diag_idx = np.arange(d)
        sym[..., diag_idx, diag_idx] = cov[..., diag_idx, diag_idx]
        return sym
    cov = jnp.zeros(packed.shape[:-1] + (d, d), jnp.float32)
    cov = cov.at[..., i, j].set(packed.astype(jnp.float32))
    diag = jnp.einsum("...ii->...i", cov)
    return cov + jnp.swapaxes(cov, -1, -2) - _diag_embed(diag)


def pack_wire(gmm: Dict, cov_type: str) -> Dict:
    """bf16 wire-format pytree (what actually crosses the mesh)."""
    packed = {"pi": gmm["pi"].astype(jnp.bfloat16),
              "mu": gmm["mu"].astype(jnp.bfloat16)}
    if cov_type == "full":
        # only the lower triangle is information-bearing
        packed["cov"] = tril_pack(gmm["cov"]).astype(jnp.bfloat16)
    else:
        packed["cov"] = gmm["cov"].astype(jnp.bfloat16)
    return packed


def unpack_wire(packed: Dict, cov_type: str, d: int) -> Dict:
    out = {"pi": packed["pi"].astype(jnp.float32),
           "mu": packed["mu"].astype(jnp.float32)}
    if cov_type == "full":
        out["cov"] = tril_unpack(packed["cov"], d)
    else:
        out["cov"] = packed["cov"].astype(jnp.float32)
    return out


def _diag_embed(diag):
    d = diag.shape[-1]
    return diag[..., :, None] * jnp.eye(d, dtype=diag.dtype)
