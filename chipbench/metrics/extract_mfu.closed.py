"""Model FLOPs that the real prompt tokens completed in the window need
(2 per matrix parameter per token and causal attention, from the
configuration's shapes) ÷ (window × the chip's bf16 peak)."""
from yardstick import counts as CT
from yardstick import weights as W


def read(run):
    rec = run.record
    if not run.peaks or not rec.get("lengths"):
        return None
    flops = CT.decoder_flops(W.dims(rec["model"]), rec["lengths"])
    return 100.0 * flops / (rec["window_s"] * run.peaks["bf16_flops"])
