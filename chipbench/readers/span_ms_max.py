"""The longest of the window's spans of one name, in milliseconds: one
stalled dispatch in a window reads seconds where the others read its
usual time. The window's spans are those that share a trace ID with one
of its root ticks. None where the program records no such span."""

from chipbench.readers.thread_unspanned_pct import window_roots


def read(record: dict, params: dict):
    traces = {r["args"]["trace_id"] for r in window_roots(record)}
    found = [
        sp["dur"] for sp in record.get("spans") or []
        if sp["name"] == params["span"] and sp["args"].get("trace_id") in traces
    ]
    return max(found) / 1e3 if found else None
