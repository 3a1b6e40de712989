"""100 * sum(num counters) / sum(den counters) over the window."""


def read(record: dict, params: dict):
    c = record["counters"]
    if params.get("den_doc_ticks"):
        den = float(record["doc_ticks"])
    else:
        den = sum(c.get(k, 0.0) for k in params["den"])
    if den <= 0:
        return None
    return 100.0 * sum(c.get(k, 0.0) for k in params["num"]) / den
