"""Sum of one attribute of the window's spans of one name, per thousand
windows judged, times `params["scale"]`: a count the program takes at a
span's boundary (bytes handed to the device, rows, docs). The window's
spans are those that share a trace ID with one of its root ticks. None
where the program records no such span or attribute."""

from chipbench.readers.thread_unspanned_pct import window_roots


def read(record: dict, params: dict):
    traces = {r["args"]["trace_id"] for r in window_roots(record)}
    found = [
        sp["args"][params["attr"]] for sp in record.get("spans") or []
        if sp["name"] == params["span"]
        and sp["args"].get("trace_id") in traces
        and params["attr"] in sp["args"]
    ]
    if not found or not record["windows"]:
        return None
    return float(params.get("scale", 1.0)) * sum(found) / (record["windows"] / 1e3)
