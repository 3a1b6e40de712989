"""Share of the window's ticks that the tick thread spent inside no
stage span: over the window's root `worker.tick` spans, 100 * (sum of
root durations - union of the stage spans on the root's own thread,
clipped to the roots) / sum of root durations. Spans of other threads
(prefetch, writer) do not count: they cover none of the tick thread's
time. Source: the program's span ring (`record["spans"]`, Chrome trace
events, microseconds)."""

ROOT = "worker.tick"


def window_roots(record: dict) -> list:
    """The root tick spans of the window: the ring holds set-up's ticks
    too, and the window's sweeps are its last ones."""
    n = len(record.get("sweeps") or [])
    roots = [
        sp for sp in record.get("spans") or []
        if sp["name"] == ROOT and not (sp.get("args") or {}).get("parent_id")
    ]
    return roots[-n:] if n else []


def read(record: dict, params: dict):
    roots = window_roots(record)
    total = sum(r["dur"] for r in roots)
    if total <= 0:
        return None
    staged = [
        sp for sp in record["spans"] if (sp.get("args") or {}).get("stage")
    ]
    covered = 0.0
    for r in roots:
        lo, hi = r["ts"], r["ts"] + r["dur"]
        at = lo  # end of the union so far
        for s, e in sorted(
            (sp["ts"], sp["ts"] + sp["dur"]) for sp in staged
            if sp["tid"] == r["tid"] and sp["ts"] < hi and sp["ts"] + sp["dur"] > lo
        ):
            s, e = max(s, at), min(e, hi)
            if e > s:
                covered += e - s
                at = e
    return 100.0 * (total - covered) / total
