"""Plain references of the federation round, independent of the program.

``em_step`` is one EM iteration of a weighted diagonal Gaussian mixture,
written from its definition: log-densities from the centred squares
(x − μ)²/σ², responsibilities by softmax, and the M-step's mean and
centred second moment, all at ``Precision.HIGHEST``.  A fit that EM has
brought to its fixed point moves under it only by rounding, so the gap
between a client's transmitted mixture and one more reference step from
it measures the fit.

``em_fit`` is EM from a plain seeding (evenly spaced rows of the class,
then Lloyd iterations) whose M-step takes the variance in the moment form
Σr·x²/n_k − μ², as scikit-learn's diagonal mixture does and as the
program does.  With ``low=True`` every product takes its operands rounded
to bfloat16 and accumulates in float32, which is what one bfloat16 pass on
a TPU's matrix unit does: that is the round cell's control, the step a
change that drops the fit's float32 products would take.

``decode`` reads a diag-GMM wire payload: the present classes' π (K),
μ (K, d) and σ² (K, d), in that order, each in the wire type.
``slot_stack`` lays the decoded payloads of a round out as the server's
reservoir of mixture slots, and ``rows_off`` counts the rows in which what
the server holds after the seal differs from it.

``server_head`` is the server phase written from its definition: every
Adam step draws its minibatch from the slot stack (slot ∝ draw count by
one uniform and a search of the cumulative mass, component from π,
Gaussian noise through √σ²) and takes one Adam step on the mean
cross-entropy of a linear head.  It takes the service's head key and
spends it as the service's round does, so a sound round and the reference
train on the same draws.  Its float32 products run at the default
precision, as the program's head does (one bfloat16 pass on a TPU): Adam
at this learning rate turns any rounding difference into a different
trajectory within tens of steps, so a reference at ``HIGHEST`` parts from
a sound program by as much as a planted fault does.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LOG2PI = math.log(2.0 * math.pi)


def decode(payload: bytes, counts, K: int, d: int, wire_dtype):
    """(pi (C, K), mu (C, K, d), var (C, K, d)) float32, zeros for classes
    absent from the payload."""
    counts = np.asarray(counts)
    present = np.flatnonzero(counts > 0)
    P, C = len(present), len(counts)
    raw = np.frombuffer(payload, dtype=wire_dtype).astype(np.float32)
    sizes = [P * K, P * K * d, P * K * d]
    if raw.size != sum(sizes):
        raise ValueError(f"payload holds {raw.size} scalars, want "
                         f"{sum(sizes)}")
    pi_p, mu_p, var_p = np.split(raw, np.cumsum(sizes)[:-1])
    pi = np.zeros((C, K), np.float32)
    mu = np.zeros((C, K, d), np.float32)
    var = np.zeros((C, K, d), np.float32)
    pi[present] = pi_p.reshape(P, K)
    mu[present] = mu_p.reshape(P, K, d)
    var[present] = var_p.reshape(P, K, d)
    return pi, mu, var


def _dot(spec, a, b, low):
    if low:
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=HI)


def _logdens(x, pi, mu, var, low=False):
    """log π_k N(x_n | μ_k, diag σ²_k): (N, K), from centred squares."""
    d = x.shape[-1]
    maha = _dot("nkd,kd->nk", jnp.square(x[:, None] - mu[None]), 1.0 / var,
                low)
    return (jnp.log(jnp.clip(pi, 1e-20))[None]
            - 0.5 * (d * LOG2PI + jnp.sum(jnp.log(var), -1)[None] + maha))


@partial(jax.jit, static_argnames=("reg",))
def em_step(x, w, pi, mu, var, *, reg):
    """One EM iteration over rows weighted by ``w`` (N,), at HIGHEST, the
    variance from centred squares.  Returns (pi, mu, var, responsibility
    mass per component)."""
    resp = jax.nn.softmax(_logdens(x, pi, mu, var), -1) * w[:, None]
    nk = jnp.sum(resp, 0)
    safe = jnp.maximum(nk, 1e-12)[:, None]
    mu = _dot("nk,nd->kd", resp, x, False) / safe
    var = _dot("nk,nkd->kd", resp, jnp.square(x[:, None] - mu[None]),
               False) / safe
    return (nk / jnp.maximum(jnp.sum(nk), 1e-12), mu,
            jnp.maximum(var + reg, reg), nk)


@partial(jax.jit, static_argnames=("K", "iters", "kmeans_iters", "reg",
                                   "low"))
def em_fit(x, w, *, K, iters, kmeans_iters, reg, low):
    """EM from a plain seeding over the rows weighted by ``w``."""
    order = jnp.argsort(-w, stable=True)          # the class's rows first
    n = jnp.maximum(jnp.sum(w > 0), 1)
    mu = x[order[(jnp.arange(K) * n) // K]]

    def lloyd(mu, _):
        d2 = (jnp.sum(jnp.square(x), -1, keepdims=True)
              - 2 * _dot("nd,kd->nk", x, mu, low)
              + jnp.sum(jnp.square(mu), -1)[None])
        a = jax.nn.one_hot(jnp.argmin(d2, -1), K) * w[:, None]
        cnt = jnp.sum(a, 0)
        new = _dot("nk,nd->kd", a, x, low) / jnp.maximum(cnt, 1e-12)[:, None]
        return jnp.where((cnt > 0)[:, None], new, mu), None

    mu, _ = jax.lax.scan(lloyd, mu, None, length=kmeans_iters)
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    mean = _dot("n,nd->d", w, x, low) / wsum
    glob = _dot("n,nd->d", w, jnp.square(x - mean), low) / wsum + reg
    state = (jnp.full((K,), 1.0 / K), mu, jnp.broadcast_to(glob, mu.shape))

    def it(p, _):
        resp = jax.nn.softmax(_logdens(x, *p, low=low), -1) * w[:, None]
        nk = jnp.sum(resp, 0)
        safe = jnp.maximum(nk, 1e-12)[:, None]
        mu = _dot("nk,nd->kd", resp, x, low) / safe
        x2 = _dot("nk,nd->kd", resp, jnp.square(x), low) / safe
        var = jnp.maximum(x2 - jnp.square(mu) + reg, reg)
        return (nk / jnp.maximum(jnp.sum(nk), 1e-12), mu, var), None

    state, _ = jax.lax.scan(it, state, None, length=iters)
    return state


def fit_gaps(x, labels, pi, mu, var, reg: float, min_mass: float = 2.0):
    """The gaps of one client's classwise fits against one reference EM
    step, over the components that hold at least ``min_mass`` rows of
    responsibility in the reference step: the largest |Δμ| / max(|μ|, σ)
    and |Δσ²| / max(σ², mean σ² of the component) (relative, so the wire's
    own rounding reads about 2⁻⁹ whatever the features' scale; the floor
    keeps a dimension that a few rows leave near ``reg`` from reading float32
    cancellation as a fault), and the largest |Δπ| of any class."""
    mu_gap = var_gap = pi_gap = 0.0
    xj = jnp.asarray(x, jnp.float32)
    for c in range(pi.shape[0]):
        w = (np.asarray(labels) == c).astype(np.float32)
        if w.sum() == 0:
            continue
        p2, m2, v2, nk = (np.asarray(a, np.float64) for a in em_step(
            xj, jnp.asarray(w), jnp.asarray(pi[c]), jnp.asarray(mu[c]),
            jnp.asarray(var[c]), reg=reg))
        keep = nk >= min_mass
        pi_gap = max(pi_gap, float(np.max(np.abs(p2 - pi[c]))))
        if not keep.any():
            continue
        dm = np.abs(m2 - mu[c]) / np.maximum(np.abs(m2), np.sqrt(v2))
        dv = np.abs(v2 - var[c]) / np.maximum(v2, v2.mean(-1,
                                                          keepdims=True))
        mu_gap = max(mu_gap, float(np.max(dm[keep])))
        var_gap = max(var_gap, float(np.max(dv[keep])))
    return {"em_mu_gap": mu_gap, "em_var_gap": var_gap, "em_pi_gap": pi_gap}


def slot_stack(msgs, n_classes: int):
    """The reservoir a round's admitted messages make: one row per present
    (client, class), ascending by slot id = client · C + class, with its
    draw count and decoded (π, μ, σ²) from the payload's bytes.  Client ids
    are the messages' positions, as the cell submits them."""
    ids, counts, pis, mus, vs = [], [], [], [], []
    for i, msg in enumerate(msgs):
        h = msg.header
        pi, mu, var = decode(msg.payload, h.counts, h.K, h.d,
                             np.dtype(h.dtype))
        for c in np.flatnonzero(np.asarray(h.counts) > 0):
            ids.append(i * n_classes + c)
            counts.append(int(h.counts[c]))
            pis.append(pi[c])
            mus.append(mu[c])
            vs.append(var[c])
    order = np.argsort(ids, kind="stable")
    take = lambda xs, dt: np.asarray(xs, dt)[order]   # noqa: E731
    return {"ids": take(ids, np.int64), "counts": take(counts, np.int64),
            "pi": take(pis, np.float32), "mu": take(mus, np.float32),
            "var": take(vs, np.float32)}


def rows_off(held: dict, want: dict) -> int:
    """Rows of the reservoir the server held (None: nothing) that differ
    from ``want``: slots missing on either side, and shared slots whose
    draw count or any parameter is not bit-equal."""
    if held is None:
        return len(want["ids"])
    keep = np.asarray(held["ids"]) >= 0
    got = {k: np.asarray(v)[keep] for k, v in held.items()}
    pos = {int(s): j for j, s in enumerate(got["ids"])}
    off = len(set(pos) ^ set(int(s) for s in want["ids"]))
    for r, s in enumerate(want["ids"]):
        j = pos.get(int(s))
        if j is None:
            continue
        if got["counts"][j] != want["counts"][r] or not all(
                np.array_equal(got[k][j], want[k][r])
                for k in ("pi", "mu", "var")):
            off += 1
    return off


@partial(jax.jit, static_argnames=("n_classes", "steps", "batch", "lr",
                                   "window"))
def server_head(key, counts, pi, mu, var, labels, *, n_classes, steps,
                batch, lr, window):
    """((w (d, C), b (C)), loss of each step) after ``steps`` Adam steps
    on minibatches drawn from the slot stack; ``key`` is the head key of
    the round."""
    d = mu.shape[-1]
    k_init, k_slot, k_comp, k_eps = jax.random.split(key, 4)
    w = jax.random.normal(k_init, (d, n_classes), jnp.float32) / jnp.sqrt(d)
    params = (w * 0.01, jnp.zeros((n_classes,), jnp.float32))
    mass = counts.astype(jnp.float32)
    cum = jnp.cumsum(mass) / jnp.maximum(jnp.sum(mass), 1e-9)
    u = jax.random.uniform(k_slot, (steps * batch,))
    slot = jnp.clip(jnp.searchsorted(cum, u, side="right"), 0,
                    cum.shape[0] - 1)
    comp = jax.random.categorical(
        k_comp, jnp.log(jnp.clip(pi[slot], 1e-20)), axis=-1)
    sd = jnp.sqrt(jnp.maximum(var, 0.0))
    zeros = jax.tree.map(jnp.zeros_like, params)

    def xent(p, x, y):
        lp = jax.nn.log_softmax(x @ p[0] + p[1], -1)
        return -jnp.sum(jnp.take_along_axis(lp, y[:, None], -1)) / batch

    def adam(carry, xs):
        p, m, v, t = carry
        s, c, eps = xs
        loss, grad = jax.value_and_grad(xent)(p, mu[s, c] + sd[s, c] * eps,
                                              labels[s])
        t = t + 1
        m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, grad)
        v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, grad)
        tf = t.astype(jnp.float32)
        bc1, bc2 = 1 - 0.9 ** tf, 1 - 0.999 ** tf
        p = jax.tree.map(lambda q, a, b: q - lr * (a / bc1)
                         / (jnp.sqrt(b / bc2) + 1e-8), p, m, v)
        return (p, m, v, t), loss

    def block(carry, xs):
        s, c, k = xs
        eps = jax.random.normal(k, s.shape + (d,), jnp.float32)
        return jax.lax.scan(adam, carry, (s, c, eps))

    # the noise is drawn ``window`` steps at a time, a key per block
    W = max(1, min(window, steps))
    n_win, tail = divmod(steps, W)
    carry = (params, zeros, zeros, jnp.zeros((), jnp.int32))
    losses = []
    if n_win:
        n = n_win * W * batch
        carry, loss = jax.lax.scan(block, carry, (
            slot[:n].reshape(n_win, W, batch),
            comp[:n].reshape(n_win, W, batch),
            jax.random.split(k_eps, n_win)))
        losses.append(loss.reshape(-1))
    if tail:
        carry, loss = block(carry, (slot[-tail * batch:].reshape(tail, batch),
                                    comp[-tail * batch:].reshape(tail, batch),
                                    jax.random.fold_in(k_eps, n_win)))
        losses.append(loss)
    return carry[0], jnp.concatenate(losses)


def loss_gap(losses, ref) -> float:
    """The largest gap of a step's loss, ``losses`` against ``ref``, over
    the reference's mean loss (a step the program did not take reads its
    loss as 0)."""
    ref = np.asarray(ref, np.float64)
    got = np.zeros_like(ref)
    got[:min(len(losses), len(ref))] = np.asarray(losses,
                                                  np.float64)[:len(ref)]
    return float(np.max(np.abs(got - ref)) / np.mean(ref))


def logit_gap(head, ref, x) -> float:
    """The largest relative L2 gap of a row's centred logits, ``head``
    against ``ref``, over the rows of ``x``."""
    def centred(h):
        z = np.asarray(x, np.float64) @ np.asarray(h[0], np.float64) \
            + np.asarray(h[1], np.float64)
        return z - z.mean(-1, keepdims=True)
    zr = centred(ref)
    num = np.linalg.norm(centred(head) - zr, axis=-1)
    return float(np.max(num / np.maximum(np.linalg.norm(zr, axis=-1),
                                         1e-30)))
