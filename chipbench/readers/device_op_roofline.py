"""A kernel's share of its roofline: the least time the chip could take for
its operations or its bytes, whichever bounds, over the device time of the
ops named `params["op"]` (the kernel's own name; each layer's call is its
own HLO instruction `%<op>.<n>`, and all are summed) among the traced
window's busiest device ops.
Operations (`flops_fn(docs, f)`) and bytes (`bytes_fn(docs, f)`) of the
window's judged docs are functions of shapes kept in `params["model"]` (a
module under chipbench); the peaks come from chipbench/peaks.py by device
kind. Nothing to read (no such op among them: a program without the
kernel) -> None, never 0."""

import importlib

from chipbench import peaks


def read(record: dict, params: dict):
    t = record.get("trace") or {}
    # a traced op is named by its HLO instruction, "%<name>.<n> = <shape> custom-call(...)"
    seconds = sum(s for name, s in t.get("device_ops") or []
                  if name.lstrip("%").split(" ", 1)[0].startswith(params["op"] + "."))
    cfg = record["config"]
    group = next((g for g in cfg["fleet"] if g["kind"] == params["kind"]), None)
    docs = record["counters"].get("fast_docs." + params["kind"], 0.0)
    if seconds <= 0 or group is None or docs <= 0 or not record.get("device_kind"):
        return None
    model = importlib.import_module("chipbench." + params["model"])
    f = len(group["aliases"])
    peak = peaks.peaks(record["device_kind"])
    least = max(getattr(model, params["flops_fn"])(docs, f) / peak["bf16_flops_per_s"],
                getattr(model, params["bytes_fn"])(docs, f) / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
