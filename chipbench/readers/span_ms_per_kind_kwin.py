"""Milliseconds the window's spans of one name lasted, per thousand windows
of the fleet kinds in `params["kinds"]` judged on the columnar path (a
kind's docs there, `fast_docs.<kind>`, times its aliases). The window's
spans are those that share a trace ID with one of its root ticks. None
where the program records no such span or judged no such doc."""

from chipbench.readers.thread_unspanned_pct import window_roots


def read(record: dict, params: dict):
    traces = {r["args"]["trace_id"] for r in window_roots(record)}
    found = [
        sp["dur"] for sp in record.get("spans") or []
        if sp["name"] == params["span"] and sp["args"].get("trace_id") in traces
    ]
    windows = sum(
        record["counters"].get("fast_docs." + g["kind"], 0.0) * len(g["aliases"])
        for g in record["config"]["fleet"] if g["kind"] in params["kinds"]
    )
    if not found or windows <= 0:
        return None
    return (sum(found) / 1e3) / (windows / 1e3)
