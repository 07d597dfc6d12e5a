"""Mean host time of ``client_update`` (classwise EM and the encode) over
the window's clients."""


def read(run):
    spans = run.record.get("spans", {}).get("client_update")
    return 1e3 * sum(spans) / len(spans) if spans else None
