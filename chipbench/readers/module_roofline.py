"""A program's share of its roofline: the least time the chip could take
for the bytes (or operations) its dispatches must move, over the device
time of the module found by its jit name. The bytes are a function of
shapes kept in chipbench/bytes_model.py; the peak comes from
chipbench/peaks.py by device kind. Nothing to read -> None, never 0."""

from chipbench import bytes_model, peaks


def read(record: dict, params: dict):
    t = record.get("trace") or {}
    mods = {k: v for k, v in (t.get("modules") or {}).items() if k.startswith(params["module"])}
    seconds = sum(v["seconds"] for v in mods.values())
    count = sum(v["count"] for v in mods.values())
    if seconds <= 0 or count <= 0:
        return None
    cfg = record["config"]
    group = next(g for g in cfg["fleet"] if g["kind"] == params["kind"])
    f = len(group["aliases"])
    w_bucket = bytes_model.window_bucket(cfg["window_points"])
    # rows a dispatch really carried: the kind's doc-ticks over its dispatches
    rows = record["counters"].get("fast_docs." + params["kind"], 0.0) / count
    if rows <= 0:
        return None
    fn = getattr(bytes_model, params["bytes_fn"])
    least = fn(rows, f, w_bucket, cfg["season_steps"]) / peaks.peaks(record["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * count * least / seconds
