"""Pallas TPU kernels for the framework's compute hot-spots.

  gmm_estep        the paper's per-client EM E-step, MXU-tiled (diag/spher)
  flash_attention  backbone attention: online softmax, sliding window,
                   bidirectional prefix, GQA
  wkv6             RWKV6 chunked recurrence: VMEM-resident Dh×Dh state
                   carried across the chunk sweep
  ssd              Mamba2 SSD chunked recurrence (scalar decay → pure MXU
                   matmuls), VMEM-resident N×P state

``ops`` dispatches the E-step by platform (compiled on a TPU, interpreted
on the CPU, the reference on request); ``ref`` holds the pure-jnp oracles
that define kernel semantics.
"""
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gmm_estep import estep
from repro.kernels.ssd import ssd
from repro.kernels.wkv6 import wkv6

__all__ = ["ops", "ref", "flash_attention", "estep", "wkv6", "ssd"]
