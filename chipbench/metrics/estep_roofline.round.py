"""The fused E-step kernel's share of its roofline: the least time the
chip could take for the window's E-steps, max(FLOPs ÷ bf16 peak, bytes ÷
HBM bandwidth), counted at the real N, K and d with x read once per
client, ÷ the summed device time of the kernel's operations."""
from yardstick import counts as CT
from yardstick import tracing as TR

KERNEL = "estep"


def read(run):
    rec = run.record
    if run.trace is None or not run.peaks or "rounds" not in rec:
        return None
    kernel = TR.kernel_s(run.trace, KERNEL)
    if kernel is None:
        return None
    t = rec["t"]
    n, d = t["n_samples"], rec["model"]["hidden_size"]
    k, fits = t["n_components"], t["n_classes"]
    calls = rec["rounds"] * t["n_clients"] * (t["em_iters"] + 1)
    least = calls * max(CT.estep_flops(n, d, k, fits)
                        / run.peaks["bf16_flops"],
                        CT.estep_bytes(n, d, k, fits)
                        / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel
