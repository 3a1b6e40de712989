"""The whole sweep's share of the chip's peak: operations the judged
docs' models need over the window's seconds times the peak. Each fleet
group of the configuration names the function that counts one doc's
operations from shapes (`flops_fn`: `<module under chipbench>.<function>`);
a group that names none adds nothing. The models are tiny (hidden 32), so
this reads far under 1%; it bounds what a later PR can claim by taking a
program off the path."""

import importlib

from chipbench import bytes_model, peaks


def read(record: dict, params: dict):
    if not record.get("device_kind") or not (record.get("trace") or {}).get("busy_s"):
        return None
    cfg = record["config"]
    w_bucket = bytes_model.window_bucket(cfg["window_points"])
    flops = 0.0
    for g in cfg["fleet"]:
        if "flops_fn" not in g:
            continue
        module, fn = g["flops_fn"].rsplit(".", 1)
        per_doc = getattr(importlib.import_module("chipbench." + module), fn)
        docs = record["counters"].get("fast_docs." + g["kind"], 0.0)
        flops += docs * per_doc(len(g["aliases"]), w_bucket)
    if flops <= 0:
        return None
    peak = peaks.peaks(record["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (record["window_s"] * peak)
