"""Device-busy milliseconds (union of device op intervals in the trace)
per thousand windows judged in the traced window."""


def read(record: dict, params: dict):
    t = record.get("trace") or {}
    if not t.get("busy_s") or not record["windows"]:
        return None
    return 1e3 * t["busy_s"] / (record["windows"] / 1e3)
