"""A program's share of its roofline where either bound may hold: the least
time the chip could take for its dispatches, the larger of operations over
the peak rate and bytes over the peak bandwidth, over the device time of
the module found by its jit name. Operations (`flops_fn(f, w_bucket)` a
document) and bytes (`bytes_fn(docs, f, dispatches)`) are functions of
shapes kept in `params["model"]` (a module under chipbench); the peaks come
from chipbench/peaks.py by device kind. Nothing to read -> None, never 0."""

import importlib

from chipbench import bytes_model, peaks


def read(record: dict, params: dict):
    t = record.get("trace") or {}
    mods = {k: v for k, v in (t.get("modules") or {}).items() if k.startswith(params["module"])}
    seconds = sum(v["seconds"] for v in mods.values())
    dispatches = sum(v["count"] for v in mods.values())
    cfg = record["config"]
    group = next((g for g in cfg["fleet"] if g["kind"] == params["kind"]), None)
    docs = record["counters"].get("fast_docs." + params["kind"], 0.0)
    if seconds <= 0 or group is None or docs <= 0:
        return None
    model = importlib.import_module("chipbench." + params["model"])
    f = len(group["aliases"])
    flops = docs * getattr(model, params["flops_fn"])(f, bytes_model.window_bucket(cfg["window_points"]))
    moved = getattr(model, params["bytes_fn"])(docs, f, dispatches)
    peak = peaks.peaks(record["device_kind"])
    least = max(flops / peak["bf16_flops_per_s"], moved / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
