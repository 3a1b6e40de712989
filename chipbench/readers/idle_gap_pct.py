"""One named entry of the traced window's idle gaps as a share of the
window: 100 * seconds of `params["gap"]` in `trace.idle_gaps` /
`trace.window_s`. The gaps are the device's idle time by what the host
was doing (chipbench/tracered.py); "host, no stage span" is the part the
program could not name. 0.0 when the run has gaps and none carries the
name (the list keeps the ten largest); None with no trace."""


def read(record: dict, params: dict):
    t = record.get("trace") or {}
    gaps = t.get("idle_gaps") or []
    if not gaps or not t.get("window_s"):
        return None
    seconds = sum(v for name, v in gaps if name == params["gap"])
    return 100.0 * seconds / t["window_s"]
