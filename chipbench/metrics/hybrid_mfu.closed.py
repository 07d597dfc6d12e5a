"""FLOPs that the real prompt tokens completed in the window need through
the Mamba-2/attention hybrid (``yardstick/hybrid.py``'s count: matrix
products, the SSD recurrence, the conv and causal attention) ÷ (window ×
the chip's bf16 peak)."""
from yardstick import hybrid as HY


def read(run):
    rec = run.record
    if not run.peaks or not rec.get("lengths") \
            or "layer_types" not in rec.get("model", {}):
        return None
    flops = HY.flops(rec["model"], rec["lengths"])
    return 100.0 * flops / (rec["window_s"] * run.peaks["bf16_flops"])
