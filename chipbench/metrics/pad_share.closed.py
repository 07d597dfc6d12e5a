"""Padded positions ÷ positions computed by the feature step: each step
computes rows × bucket positions, of which the admitted prompts' real
tokens are the useful part."""


def read(run):
    rec = run.record
    computed = sum(r * s for r, s in rec.get("step_shapes", ()))
    if not computed:
        return None
    return 100.0 * (computed - rec["tokens"]) / computed
