"""Operations and bytes that the algorithm needs, from shapes alone.

These count the work the real inputs require, not what the compiled
program did: padding, recomputation and extra precision passes are not
work, so a utilisation computed from them falls when the program wastes.
"""
from __future__ import annotations

from typing import Iterable


def decoder_layer_params(s: dict) -> int:
    """Matrix parameters of one dense SwiGLU decoder layer."""
    d, h, hk, dh, ff = s["d"], s["h"], s["hk"], s["dh"], s["ff"]
    return d * (h + 2 * hk) * dh + h * dh * d + 3 * d * ff


def decoder_row_flops(s: dict, length: int) -> float:
    """Forward FLOPs of one causal row of ``length`` real tokens through
    the stack: 2 per matrix parameter per token, and causal attention's
    QKᵀ and PV over the ``length·(length+1)/2`` query-key pairs."""
    L, h, dh = s["L"], s["h"], s["dh"]
    dense = 2.0 * decoder_layer_params(s) * length
    attn = 2.0 * 2.0 * h * dh * length * (length + 1) / 2.0
    return L * (dense + attn)


def decoder_flops(s: dict, lengths: Iterable[int]) -> float:
    return sum(decoder_row_flops(s, int(n)) for n in lengths)


def estep_flops(n: int, d: int, k: int, fits: int) -> float:
    """The fused diag E-step of ``fits`` mixtures over one shared (n, d)
    block: the two (n, d)×(d, k) products of the expanded Mahalanobis
    term, per fit."""
    return fits * 2.0 * 2.0 * n * d * k


def estep_bytes(n: int, d: int, k: int, fits: int) -> float:
    """float32 bytes the fused E-step must move: x read once, each fit's
    mean and variance, and its (n, k) log-numerators and (n,) logsumexp
    written."""
    return 4.0 * (n * d + fits * (2 * k * d + k + n * k + n))


def em_fit_flops(class_rows: Iterable[int], d: int, k: int, iters: int,
                 kmeans_iters: int) -> float:
    """One client's classwise diag EM, each class fit over its own rows:
    per iteration an E-step and the M-step's two (k, n)×(n, d) moments,
    one more E-step for the final likelihood, and per k-means iteration a
    (n, d)×(d, k) distance product and a (k, n)×(n, d) mean.  The program
    runs each class's fit over all of the client's rows, weighted; those
    zero-weight rows are not work the fit needs."""
    total = 0.0
    for n in class_rows:
        per = 2.0 * 2.0 * n * d * k
        total += iters * 2 * per + per + kmeans_iters * per
    return total


def head_train_flops(steps: int, batch: int, d: int, classes: int) -> float:
    """Adam on a linear head: forward and backward of a (batch, d)×(d, C)
    product per step."""
    return steps * 3.0 * 2.0 * batch * d * classes
