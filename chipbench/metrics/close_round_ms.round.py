"""Mean host time of ``close_round``, from the broker's seal to the new
head installed and ready on the device."""


def read(run):
    spans = run.record.get("spans", {}).get("close_round")
    return 1e3 * sum(spans) / len(spans) if spans else None
