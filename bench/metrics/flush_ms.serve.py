"""Mean host time of one ``flush()`` with the fetch of its rows, from the
benchmark's own spans around them, over the traced run's window."""


def read(layer):
    spans = getattr(layer, "spans", {}).get("flush", [])
    return 1e3 * sum(spans) / len(spans) if spans else None
