"""The share of one fleet kind's doc-ticks that took the columnar path:
100 * `fast_docs.<kind>` / (the window's sweeps x the kind's services).
None for a fleet that has no such kind."""


def read(record: dict, params: dict):
    kind = params["kind"]
    services = sum(int(g["services"]) for g in record["config"]["fleet"] if g["kind"] == kind)
    due = services * len(record.get("sweeps") or [])
    if due <= 0:
        return None
    return 100.0 * record["counters"].get("fast_docs." + kind, 0.0) / due
