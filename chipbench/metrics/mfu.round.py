"""The whole round's share of the chip's bf16 peak: the algorithm's FLOPs
per round (each client's classwise EM over its own rows, and the head's
Adam steps) × rounds ÷ (window × peak)."""
from yardstick import counts as CT


def read(run):
    rec = run.record
    if not run.peaks or "rounds" not in rec:
        return None
    t, d = rec["t"], rec["model"]["hidden_size"]
    em = sum(CT.em_fit_flops(rows, d, t["n_components"], t["em_iters"],
                             t["kmeans_iters"]) for rows in rec["class_rows"])
    head = CT.head_train_flops(t["head"]["n_steps"], t["head"]["batch_size"],
                               d, t["n_classes"])
    return (100.0 * rec["rounds"] * (em + head)
            / (rec["window_s"] * run.peaks["bf16_flops"]))
