"""PAL-*: Pallas kernel contract checks for the repo's two kernels.

Instead of parsing BlockSpec arithmetic out of the source, the rule runs
the real kernel entry points under ``jax.eval_shape`` with
``pl.pallas_call`` monkeypatched to a recorder — so every check sees the
*actual* grid / in_specs / out_specs / scratch_shapes the kernel would
hand to Mosaic, after the kernel's own padding logic has run.

Checks per captured ``pallas_call``:

* **PAL-DIV** (ERROR) — every blocked operand dim must divide the padded
  operand dim (`operand_dim % block_dim == 0`); a remainder means the
  grid either drops rows or reads out of bounds.
* **PAL-ALIGN** (ERROR) — the last two block dims should be multiples of
  the MXU/VPU tile (128 lanes, 8 sublanes for f32).  A block dim that
  equals the *full* (padded) operand dim is exempt: the compiler keeps
  whole-axis blocks resident and no lane remainder exists.  A dim-1 block
  on a longer axis is not exempt: the TPU compiler refuses it.
* **PAL-VMEM** (WARN) — estimated VMEM footprint (all operand + output
  blocks ×2 for double buffering, plus declared scratch) must fit the
  ~16 MiB per-core budget (see /opt/skills/guides pallas notes).

The rule probes a geometry grid per kernel (small ragged shapes + the
canonical large shapes) so padding paths are exercised, all under
``eval_shape`` — nothing is compiled or executed.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, List, Optional, Sequence, Tuple

from repro.analysis.core import Finding, SemanticRule, Severity, SourceFile

VMEM_BUDGET_BYTES = 16 * 1024 * 1024
_LANE = 128     # MXU/VPU lane tile
_SUBLANE = 8    # f32 sublane tile


@dataclasses.dataclass
class CapturedCall:
    grid: Tuple[int, ...]
    # (operand shape, block shape or None) per input
    inputs: List[Tuple[Tuple[int, ...], Optional[Tuple[int, ...]]]]
    outputs: List[Tuple[Tuple[int, ...], Optional[Tuple[int, ...]]]]
    scratch_bytes: int
    line_hint: str = ""


def _block_shape(spec) -> Optional[Tuple[int, ...]]:
    bs = getattr(spec, "block_shape", None)
    if bs is None:
        return None
    return tuple(int(b) for b in bs)


def _nbytes(shape, dtype) -> int:
    import numpy as np
    return int(math.prod(shape)) * np.dtype(dtype).itemsize


def capture_pallas_calls(thunk: Callable[[], None]) -> List[CapturedCall]:
    """Run ``thunk`` under eval_shape semantics with pallas_call recorded.

    The recorder still defers to the real ``pallas_call`` so downstream
    shape flow stays exact.
    """
    import jax
    from jax.experimental import pallas as pl

    captured: List[CapturedCall] = []
    real = pl.pallas_call

    def recorder(kernel, *, out_shape, grid=None, in_specs=None,
                 out_specs=None, scratch_shapes=None, **kw):
        inner = real(kernel, out_shape=out_shape, grid=grid,
                     in_specs=in_specs, out_specs=out_specs,
                     scratch_shapes=scratch_shapes or (), **kw)

        def wrapped(*ops):
            out_list = (list(out_shape) if isinstance(out_shape,
                        (list, tuple)) else [out_shape])
            ispecs = list(in_specs or [None] * len(ops))
            ospecs = (list(out_specs) if isinstance(out_specs,
                      (list, tuple)) else [out_specs] * len(out_list))
            scratch = 0
            for s in (scratch_shapes or ()):
                shp = getattr(s, "shape", None)
                dt = getattr(s, "dtype", None)
                if shp is not None and dt is not None:
                    scratch += _nbytes(tuple(shp), dt)
            captured.append(CapturedCall(
                grid=tuple(int(g) for g in (grid or ())),
                inputs=[(tuple(o.shape), _block_shape(s))
                        for o, s in zip(ops, ispecs)],
                outputs=[(tuple(o.shape), _block_shape(s))
                         for o, s in zip(out_list, ospecs)],
                scratch_bytes=scratch,
                line_hint=getattr(kernel, "__name__", "")))
            return inner(*ops)
        return wrapped

    pl.pallas_call = recorder
    try:
        jax.eval_shape(thunk)
    finally:
        pl.pallas_call = real
    return captured


@dataclasses.dataclass(frozen=True)
class KernelProbe:
    name: str
    anchor: str
    thunk: Callable[[], Callable[[], None]]   # builds the eval_shape thunk


def _estep_probes() -> List[KernelProbe]:
    def make(case, B, N, K, d):
        def build():
            import jax.numpy as jnp
            from repro.kernels.gmm_estep import estep_fused

            def thunk():
                x = jnp.zeros((B, N, d), jnp.float32)
                mu = jnp.zeros((B, K, d), jnp.float32)
                var = jnp.ones((B, K, d), jnp.float32)
                pi = jnp.full((B, K), 1.0 / K, jnp.float32)
                return estep_fused(x, mu, var, pi, interpret=True)
            return thunk
        return KernelProbe(f"gmm_estep.estep_fused[{case}]",
                           "repro/kernels/gmm_estep.py", build)
    return [make("tiny_ragged", 1, 37, 3, 5),
            make("mid", 2, 512, 8, 64),
            make("wide", 1, 4096, 16, 256)]


def _flash_probes() -> List[KernelProbe]:
    def make(case, B, Hq, Hkv, Sq, Sk, D, **kw):
        def build():
            import jax.numpy as jnp
            from repro.kernels.flash_attention import flash_attention

            def thunk():
                q = jnp.zeros((B, Hq, Sq, D), jnp.float32)
                k = jnp.zeros((B, Hkv, Sk, D), jnp.float32)
                v = jnp.zeros((B, Hkv, Sk, D), jnp.float32)
                return flash_attention(q, k, v, interpret=True, **kw)
            return thunk
        return KernelProbe(f"flash_attention.flash_attention[{case}]",
                           "repro/kernels/flash_attention.py", build)
    return [make("ragged", 1, 4, 2, 200, 200, 64, causal=True),
            make("train_4k", 1, 4, 2, 4096, 4096, 64, causal=True),
            make("decode", 1, 4, 2, 1, 32768, 64)]


def kernel_probes() -> List[KernelProbe]:
    return _estep_probes() + _flash_probes()


def check_call(call: CapturedCall) -> List[Tuple[str, Severity, str]]:
    """Pure checks over one captured call → [(rule, severity, message)]."""
    out: List[Tuple[str, Severity, str]] = []
    pairs = call.inputs + call.outputs
    for op_shape, block in pairs:
        if block is None:
            continue
        for od, bd in zip(op_shape[-len(block):], block):
            if bd and od % bd != 0:
                out.append((
                    "PAL-DIV", Severity.ERROR,
                    f"block dim {bd} does not divide padded operand dim "
                    f"{od} (operand {op_shape}, block {block}, grid "
                    f"{call.grid}) in '{call.line_hint}'"))
        # MXU alignment on the trailing two dims; exempt only full-axis
        # blocks (whole axis stays resident, no lane remainder) — Mosaic
        # refuses a dim-1 block on a longer axis like any other misfit
        trailing = list(zip(op_shape[-len(block):], block))[-2:]
        tiles = (_SUBLANE, _LANE)[-len(trailing):]
        for (od, bd), tile in zip(trailing, tiles):
            if bd and bd != od and bd % tile != 0:
                out.append((
                    "PAL-ALIGN", Severity.ERROR,
                    f"block dim {bd} is neither a multiple of the "
                    f"hardware tile {tile} nor the full operand axis "
                    f"{od} (block {block}) in '{call.line_hint}'"))
    import numpy as np
    vmem = call.scratch_bytes
    for op_shape, block in pairs:
        eff = block if block is not None else op_shape
        vmem += 2 * _nbytes(eff, np.float32)   # ×2: double buffering
    if vmem > VMEM_BUDGET_BYTES:
        out.append((
            "PAL-VMEM", Severity.WARN,
            f"estimated VMEM footprint {vmem / 2**20:.1f} MiB exceeds the "
            f"{VMEM_BUDGET_BYTES / 2**20:.0f} MiB budget (blocks "
            f"double-buffered + {call.scratch_bytes} B scratch) in "
            f"'{call.line_hint}'"))
    return out


class PallasContractRule(SemanticRule):
    id = "PAL"           # emits PAL-DIV / PAL-ALIGN / PAL-VMEM
    severity = Severity.ERROR
    doc = ("Pallas BlockSpec-vs-grid divisibility, MXU tile alignment and "
           "VMEM budget, checked on captured pallas_call parameters under "
           "eval_shape")
    anchors = ("repro/kernels/gmm_estep.py",
               "repro/kernels/flash_attention.py")

    def __init__(self, probes: Optional[Sequence[KernelProbe]] = None):
        self.probes = probes

    def run_project(self, files: Sequence[SourceFile]):
        findings: List[Finding] = []
        by_anchor = {a: next((f for f in files
                              if f.path.replace("\\", "/").endswith(a)),
                             None) for a in self.anchors}
        for probe in (self.probes if self.probes is not None
                      else kernel_probes()):
            src = by_anchor.get(probe.anchor)
            if src is None:
                continue
            try:
                calls = capture_pallas_calls(probe.thunk())
            except Exception as e:  # noqa: BLE001 — probe failure is a finding
                findings.append(self.finding(
                    src, 1, f"{probe.name}: probe failed under eval_shape: "
                    f"{type(e).__name__}: {e}",
                    "the kernel entry must trace for this geometry",
                    rule="PAL-DIV"))
                continue
            for call in calls:
                for rule, sev, msg in check_call(call):
                    findings.append(self.finding(
                        src, 1, f"{probe.name}: {msg}",
                        "adjust block_n/block_k or the kernel's padding "
                        "so blocks tile the padded operands",
                        severity=sev, rule=rule))
        return findings
