"""100 * (1 - device busy / traced window)."""


def read(record: dict, params: dict):
    t = record.get("trace") or {}
    if not t.get("busy_s") or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
