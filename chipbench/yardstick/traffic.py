"""The one traffic generator: it reads a mix's parameters from its data file.

Every seed gets the same set of sizes, in another order.  Lengths are a
stratified draw from the mix's distribution (the quantiles at (i + ½)/n of
``design_size`` points), dealt once into groups by the fixed
``design_seed``; ``--seed`` then reorders the groups and the rows within a
group, and picks the token contents.  The order is balanced: the groups,
ranked by the power of two over their longest row (the step's cost) and
then by their tokens, are dealt into
blocks of ``block`` groups, one rank-pass at a time and in alternate
directions, so every block holds the same mix of long and short steps and
nearly the same tokens, and any window of whole blocks the same work.
So two seeds differ in which prompt comes when and what it says, not in
how much work a window holds.
"""
from __future__ import annotations

import statistics
from typing import Dict, Iterator, List, Tuple

import numpy as np

POOL_TOKENS = 1 << 20


def quantile_lengths(spec: Dict, n: int) -> np.ndarray:
    """``n`` stratified lengths of a clipped lognormal (median, sigma)."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                  for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def design_groups(spec: Dict, group: int) -> np.ndarray:
    """The fixed (n_groups, group) deal of the stratified lengths."""
    n = int(spec["design_size"])
    if n % group:
        raise ValueError(f"design_size {n} is not a multiple of {group}")
    lengths = quantile_lengths(spec, n)
    rng = np.random.default_rng(int(spec["design_seed"]))
    return rng.permutation(lengths).reshape(n // group, group)


def pow2_at_least(n: int, lo: int, hi: int) -> int:
    return int(min(max(1 << (int(n) - 1).bit_length(), lo), hi))


def group_buckets(groups: np.ndarray, lo: int, hi: int) -> List[int]:
    """Power-of-two lengths that cover each group's longest row."""
    return sorted({pow2_at_least(m, lo, hi) for m in groups.max(axis=1)})


class PromptSource:
    """Token contents: a seeded pool that prompts are slices of."""

    def __init__(self, rng: np.random.Generator, vocab: int):
        self.rng = rng
        self.pool = rng.integers(1, vocab, size=POOL_TOKENS, dtype=np.int32)

    def prompt(self, length: int) -> Tuple[int, np.ndarray]:
        off = int(self.rng.integers(0, POOL_TOKENS - length))
        return off, self.pool[off:off + length]


class GroupStream:
    """Groups of prompt lengths, cycling through the design in balanced
    blocks: the groups, ranked by (bucket, tokens), are dealt into
    blocks of ``block`` in alternate directions; each cycle sends the
    blocks, the groups in a block and the rows in a group in seeded
    orders."""

    def __init__(self, groups: np.ndarray, rng: np.random.Generator,
                 block: int = 1):
        if len(groups) % block:
            raise ValueError(f"{len(groups)} groups do not fill blocks of "
                             f"{block}")
        self.groups, self.rng = groups, rng
        self._order: List[int] = []
        steps = np.ceil(np.log2(groups.max(axis=1)))    # a step's bucket
        ranked = np.lexsort((groups.sum(axis=1), steps))
        n_blocks = len(groups) // block
        self.blocks: List[List[int]] = [[] for _ in range(n_blocks)]
        for r, g in enumerate(ranked):
            pos = r % n_blocks
            if (r // n_blocks) % 2:
                pos = n_blocks - 1 - pos
            self.blocks[pos].append(int(g))

    def __iter__(self) -> Iterator[np.ndarray]:
        return self

    def _cycle(self) -> List[int]:
        return [int(g) for b in self.rng.permutation(len(self.blocks))
                for g in self.rng.permutation(self.blocks[b])]

    def __next__(self) -> np.ndarray:
        if not self._order:
            self._order = self._cycle()[::-1]
        return self.rng.permutation(self.groups[self._order.pop()])

