"""Sum of the named counters over the window."""


def read(record: dict, params: dict):
    c = record["counters"]
    if not any(k in c for k in params["keys"]):
        return None
    return sum(c.get(k, 0.0) for k in params["keys"])
