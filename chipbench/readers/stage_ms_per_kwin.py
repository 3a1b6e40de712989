"""Seconds the program's spans spent in the named stages, per thousand
windows judged. Source: `foremast_tick_stage_seconds{stage}`."""


def read(record: dict, params: dict):
    c = record["counters"]
    found = [c["stage_s." + s] for s in params["stages"] if "stage_s." + s in c]
    if not found or not record["windows"]:
        return None
    return 1e3 * sum(found) / (record["windows"] / 1e3)
