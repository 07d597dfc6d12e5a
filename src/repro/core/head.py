"""Linear classifier head over foundation features (the ``h`` in w = h∘f).

The paper trains h with Adam + cross-entropy on either real features
(Centralized oracle) or GMM-sampled synthetic features (FedPFT). One jitted
``lax.scan`` runs the whole optimization — no python step loop.

Two ways to feed it synthetic features (DESIGN.md §2):

* :func:`train_head_from_gmms` — the server phase: every Adam step draws
  its minibatch from the decoded mixture-slot stack *inside* one fused
  scan (``gmm.sample_slot_minibatch``); no pooled tensor and no per-step
  host dispatch ever exist;
* :func:`train_head` — a materialized (N, d) pool: real features (the
  Centralized oracle, chain clients) and the heterogeneous-cohort
  fallback, whose mixed K / covariance families share no slot stack.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.core import gmm as G


@dataclasses.dataclass(frozen=True)
class HeadConfig:
    n_steps: int = 500
    batch_size: int = 256
    lr: float = 1e-3          # paper: Adam 1e-4; higher works for linear head
    weight_decay: float = 0.0
    noise_window: int = 32    # fused path only: Gaussian noise is drawn in
    #   (window, batch, d) blocks inside the scan — big-batch RNG
    #   throughput, peak memory O(window·batch·d) on top of the slot stack


def init_head(key, d: int, n_classes: int) -> Dict:
    w = jax.random.normal(key, (d, n_classes), jnp.float32) / jnp.sqrt(d)
    return {"w": w * 0.01, "b": jnp.zeros((n_classes,), jnp.float32)}


def head_logits(params: Dict, feats: jax.Array) -> jax.Array:
    return feats.astype(jnp.float32) @ params["w"] + params["b"]


def _xent(params, feats, labels, weights):
    logits = head_logits(params, feats)
    lp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(lp, labels[:, None], axis=-1)[:, 0]
    return -jnp.sum(ll * weights) / jnp.maximum(jnp.sum(weights), 1e-9)


@partial(jax.jit, static_argnames=("cfg", "n_classes"))
def train_head(key, feats: jax.Array, labels: jax.Array, n_classes: int,
               cfg: HeadConfig,
               weights: Optional[jax.Array] = None) -> Tuple[Dict, jax.Array]:
    """Train a linear head on (feats, labels). weights=0 masks rows.

    Returns (head params, per-step loss trace).  An empty (N=0) pool — an
    all-filtered cohort upstream — returns the freshly-initialized head
    and an empty loss trace instead of crashing ``random.choice`` on 0
    items.
    """
    N, d = feats.shape
    if N == 0:
        return (init_head(jax.random.split(key)[0], d, n_classes),
                jnp.zeros((0,), jnp.float32))
    uniform = weights is None
    feats = feats.astype(jnp.float32)
    k_init, k_steps = jax.random.split(key)
    params = init_head(k_init, d, n_classes)
    opt = optim.adam(cfg.lr, weight_decay=cfg.weight_decay)
    opt_state = opt.init(params)
    bs = min(cfg.batch_size, N)
    if not uniform:
        p_sample = weights / jnp.maximum(jnp.sum(weights), 1e-9)

    def step(carry, k):
        params, opt_state = carry
        if uniform:
            # a categorical over a uniform p is an O(N)-per-step waste
            # inside the scan — a plain randint draws the same law
            idx = jax.random.randint(k, (bs,), 0, N)
        else:
            idx = jax.random.choice(k, N, (bs,), p=p_sample, replace=True)
        loss, grads = jax.value_and_grad(_xent)(
            params, feats[idx], labels[idx], jnp.ones((bs,), jnp.float32))
        updates, opt_state = opt.update(grads, opt_state, params)
        params = optim.apply_updates(params, updates)
        return (params, opt_state), loss

    keys = jax.random.split(k_steps, cfg.n_steps)
    (params, _), losses = jax.lax.scan(step, (params, opt_state), keys)
    return params, losses


def fused_gmm_steps(key, pi, mu, cov, slot_labels, counts, n_classes: int,
                    cfg: HeadConfig, cov_type: str):
    """The whole server phase as ONE device program (un-jitted body).

    This is the traceable core shared by :data:`_fused_gmm_scan` (the
    in-process jit used by :func:`train_head_from_gmms`) and the AOT
    round program (``fl.round.round_program``) that ``launch.aot_cache``
    lowers+compiles per canonical cohort signature — one body, so the
    cached executable is bit-identical to the default path by
    construction.

    Same minibatch law as ``gmm.sample_slot_minibatch`` per step (slot ∝
    counts, component ∝ pi, Gaussian through the precomputed factor), but
    regrouped for RNG throughput: the cheap integer draws (slot, component)
    for ALL steps are two vectorized calls up front — O(n_steps·batch)
    int32, negligible next to the slot stack — and the expensive Gaussian
    block is drawn ``cfg.noise_window`` steps at a time inside the scan,
    so the bit generator runs at big-batch throughput instead of one
    (batch, d) call per step.  Peak memory: O(window·batch·d + slot
    stack); the pooled (N, d) tensor never exists.
    """
    bs, d = cfg.batch_size, mu.shape[-1]
    W = max(1, min(cfg.noise_window, cfg.n_steps))
    n_win, tail = divmod(cfg.n_steps, W)
    fac = G.sampling_factor(cov, cov_type)                    # (G, K, …)
    mass = counts.astype(jnp.float32)
    cum_mass = jnp.cumsum(mass) / jnp.maximum(jnp.sum(mass), 1e-9)
    k_init, k_slot, k_comp, k_eps = jax.random.split(key, 4)
    slot_all = G.draw_slots(k_slot, cum_mass, cfg.n_steps * bs)
    logits = jnp.log(jnp.clip(pi.astype(jnp.float32), 1e-20))
    comp_all = jax.random.categorical(k_comp, logits[slot_all], axis=-1)
    params = init_head(k_init, d, n_classes)
    opt = optim.adam(cfg.lr, weight_decay=cfg.weight_decay)
    opt_state = opt.init(params)
    ones = jnp.ones((bs,), jnp.float32)

    def adam_step(carry, xy):
        params, opt_state = carry
        x, y = xy
        loss, grads = jax.value_and_grad(_xent)(params, x, y, ones)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optim.apply_updates(params, updates), opt_state), loss

    def window(carry, xs):
        sl, cm, k = xs                                        # (W', bs) ×2
        eps = jax.random.normal(k, sl.shape + (d,), jnp.float32)
        x = G.slot_gaussian(sl, cm, eps, mu, fac, cov_type)   # (W', bs, d)
        return jax.lax.scan(adam_step, carry, (x, slot_labels[sl]))

    carry = (params, opt_state)
    losses = []
    if n_win:
        n = n_win * W * bs
        carry, main = jax.lax.scan(
            window, carry, (slot_all[:n].reshape(n_win, W, bs),
                            comp_all[:n].reshape(n_win, W, bs),
                            jax.random.split(k_eps, n_win)))
        losses.append(main.reshape(-1))
    if tail:
        carry, rest = window(carry, (slot_all[-tail * bs:].reshape(tail, bs),
                                     comp_all[-tail * bs:].reshape(tail, bs),
                                     jax.random.fold_in(k_eps, n_win)))
        losses.append(rest)
    params = carry[0]
    if not losses:
        return params, jnp.zeros((0,), jnp.float32)
    return params, jnp.concatenate(losses) if len(losses) > 1 else losses[0]


_fused_gmm_scan = partial(jax.jit,
                          static_argnames=("n_classes", "cfg", "cov_type")
                          )(fused_gmm_steps)


def train_head_from_gmms(key, pi: jax.Array, mu: jax.Array, cov: jax.Array,
                         slot_labels: jax.Array, counts: jax.Array,
                         n_classes: int, cfg: HeadConfig,
                         cov_type: str) -> Tuple[Dict, jax.Array]:
    """Zero-materialization server phase: train the head STRAIGHT from the
    decoded mixture-slot stack — the synthetic pool never exists.

    Inputs are the flat planned-slot stack (``fl.planner.SlotTable`` order,
    ascending global slot id): ``pi (G, K)``, ``mu (G, K, d)``, ``cov``
    ``(G, K, …)`` per the covariance family, ``slot_labels (G,)`` the class
    of each slot, ``counts (G,)`` its requested draw count.  One jitted
    program runs the whole optimization; every Adam step draws its
    minibatch inside the scan — slot ∝ counts via the cumulative-mass
    table, component from ``pi``, Gaussian draw through the precomputed
    sampling factor (the ``gmm.sample_slot_minibatch`` law, windowed by
    ``cfg.noise_window`` for RNG throughput).  Peak memory is
    O(window·batch·d + slot stack) instead of O(Σcounts·d), and the
    whole optimization is one device program.  In expectation each step's minibatch follows exactly
    the law of uniform sampling from the pooled ``synthesize_chunks``
    output — equivalence is tested distributionally (tests/test_fused_head).

    Returns (head params, per-step loss trace), matching
    :func:`train_head`'s contract — an empty slot table (or all-zero
    counts) returns the freshly-initialized head and an empty loss trace.

    Zero-count rows are legal anywhere in the stack: the in-scan
    categorical draws ∝ counts, so they are never selected and only shape
    the compile key.  The streaming reservoir (``fl.ingest``) exploits
    this with a *prefix* of ``gmm.identity_gmm`` pad rows — leading zeros
    are exact under the f32 cumulative mass and ``gmm.draw_slots``' clip
    lands on the last real row, so the padded stack trains a head
    bit-identical to the unpadded one at a fixed compile shape.
    """
    G_slots = int(np.shape(mu)[0])
    if np.shape(slot_labels) != (G_slots,) or np.shape(counts) != (G_slots,):
        raise ValueError(
            f"train_head_from_gmms: slot stack has {G_slots} rows but "
            f"slot_labels is {np.shape(slot_labels)} and counts is "
            f"{np.shape(counts)} — pass one label and one draw count per "
            "slot row (fl.planner.SlotTable order)")
    total = float(np.asarray(jax.device_get(jnp.sum(
        jnp.asarray(counts).astype(jnp.float32)))))
    d = int(np.shape(mu)[-1])
    if G_slots == 0 or total <= 0.0:
        return (init_head(jax.random.split(key)[0], d, n_classes),
                jnp.zeros((0,), jnp.float32))
    return _fused_gmm_scan(key, jnp.asarray(pi), jnp.asarray(mu),
                           jnp.asarray(cov), jnp.asarray(slot_labels),
                           jnp.asarray(counts), n_classes, cfg, cov_type)


def accuracy(params: Dict, feats: jax.Array, labels: jax.Array,
             weights: Optional[jax.Array] = None) -> jax.Array:
    pred = jnp.argmax(head_logits(params, feats), axis=-1)
    hit = (pred == labels).astype(jnp.float32)
    if weights is None:
        return jnp.mean(hit)
    return jnp.sum(hit * weights) / jnp.maximum(jnp.sum(weights), 1e-9)


def classwise_01_loss(params: Dict, feats: jax.Array, labels: jax.Array,
                      n_classes: int) -> jax.Array:
    """Per-class 0-1 loss (used by the Theorem 6.1 bound evaluator)."""
    pred = jnp.argmax(head_logits(params, feats), axis=-1)
    miss = (pred != labels).astype(jnp.float32)
    onehot = jax.nn.one_hot(labels, n_classes)                # (N,C)
    cnt = jnp.sum(onehot, axis=0)
    return (miss @ onehot) / jnp.maximum(cnt, 1.0), cnt
