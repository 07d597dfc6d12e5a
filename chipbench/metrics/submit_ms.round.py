"""Mean host time of ``submit_update`` (wire validation and the broker's
fold) over the window's submissions."""


def read(run):
    spans = run.record.get("spans", {}).get("submit_update")
    return 1e3 * sum(spans) / len(spans) if spans else None
