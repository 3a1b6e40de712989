"""A program's share of its roofline: the least time the chip could take
for the bytes (or operations) its dispatches must move, over the device
time of the module found by its jit name. The bytes are a function of
shapes kept in chipbench/bytes_model.py; the peak comes from
chipbench/peaks.py by device kind. Nothing to read -> None, never 0.

A module that serves several fleet kinds (the warm single-alias scorer:
baseline-less docs and, compiled with the pairwise tests, the canary
bucket, under one jit name) lists them under `kinds`, each with its own
bytes function: the share is of the summed bytes over the module's whole
device time. The bytes are linear in the rows, so the rows a kind's
dispatches carried in all (`fast_docs.<kind>`) are all that is needed."""

from chipbench import bytes_model, peaks


def read(record: dict, params: dict):
    t = record.get("trace") or {}
    mods = {k: v for k, v in (t.get("modules") or {}).items() if k.startswith(params["module"])}
    seconds = sum(v["seconds"] for v in mods.values())
    if seconds <= 0:
        return None
    cfg = record["config"]
    w_bucket = bytes_model.window_bucket(cfg["window_points"])
    kinds = params.get("kinds") or [{"kind": params["kind"], "bytes_fn": params["bytes_fn"]}]
    least = 0.0
    for k in kinds:
        group = next((g for g in cfg["fleet"] if g["kind"] == k["kind"]), None)
        rows = record["counters"].get("fast_docs." + k["kind"], 0.0)
        if group is None or rows <= 0:
            continue
        fn = getattr(bytes_model, k["bytes_fn"])
        least += fn(rows, len(group["aliases"]), w_bucket, cfg["season_steps"])
    if least <= 0:
        return None
    return 100.0 * least / peaks.peaks(record["device_kind"])["hbm_bytes_per_s"] / seconds
