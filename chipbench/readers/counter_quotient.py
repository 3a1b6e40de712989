"""sum(num counters) / sum(den counters) over the window, as it is (no
percent): a mean per unit. None where the program counts no numerator (an
older program: the metric is left out, not read as 0) or nothing was counted."""


def read(record: dict, params: dict):
    c = record["counters"]
    num = [c[k] for k in params["num"] if k in c]
    den = sum(c.get(k, 0.0) for k in params["den"])
    if not num or den <= 0:
        return None
    return sum(num) / den
