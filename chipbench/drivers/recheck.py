"""Closed-loop re-check sweeps of a prefix-cached fleet: the driver of every
traffic mix whose `kind` is "recheck".

The set-up and the window are `drivers/sweep.py`'s own, unedited: the cold
tick prefills every sequence's history into the prefix cache (a fleet of
this kind has nothing to persist, so every run of a checkout prefills), the
mix's warm-up sweeps compile and run the window program, and the window is
back-to-back sweeps in which every sequence is asked again with a fresh
seeded window against its cached prefix. What this driver adds is what
`chipbench/probes.py` does not read: the backbone's own counters
(`MultivariateJudge.backbone_counters()`: tokens prefilled and scored, the
token assignments of each held expert, assignments dropped), as
`backbone.<counter>` beside the window's other counters.

The model is imported first: a tree without it exits here, non-zero, at
once, and neither hangs nor judges the fleet with another detector (an
unknown `ML_ALGORITHM` used to fall back to the univariate judge).
"""

from __future__ import annotations

import foremast_tpu.models.cohere2_moe  # noqa: F401  (a tree without the model stops here)

import gc  # noqa: E402

from chipbench.drivers import sweep  # noqa: E402

judge = sweep.judge


def _flat(counters: dict | None) -> dict:
    out = {}
    for key, v in (counters or {}).items():
        if isinstance(v, list):
            out.update({f"backbone.{key}.{i}": float(x) for i, x in enumerate(v)})
        else:
            out["backbone." + key] = float(v)
    return out


class Sweeps(sweep.Sweeps):
    """`sweep.Sweeps`, with the backbone's counters in a window's books."""

    def backbone(self) -> dict:
        return _flat(self.worker._mvj.backbone_counters())

    def window(self, *args, **kwargs) -> dict:
        before = self.backbone()
        win = super().window(*args, **kwargs)
        after = self.backbone()
        gauges = ("backbone.cache_rows_live",)
        win["counters"].update(
            {k: v if k in gauges else v - before.get(k, 0.0) for k, v in after.items()}
        )
        return win


def run(ctx) -> dict:
    cfg, args = ctx.cfg, ctx.args
    if cfg["algorithm"] != "backbone":
        raise SystemExit("traffic of kind recheck drives a fleet of kind backbone")
    sw = Sweeps(cfg, ctx.traffic, args.seed, ctx.log, ctx.out_dir, bool(args.trace))
    sw.setup()
    since_start = sw.backbone()
    gc.collect()
    gc.freeze()
    length = args.seconds
    if args.trace:
        length = min(length, float(ctx.traffic.get("trace_seconds", 20)))
    win = sw.window(length, opened=ctx.window_open)
    ctx.window_close()
    ctx.read_device_memory()
    tracer = sw.tracer
    record = {
        **{k: win[k] for k in ("window_s", "windows", "doc_ticks", "sweeps", "counters")},
        "asked_s": float(args.seconds),
        "slots": sw.fl.slots,
        "fleet_restored": sw.restored,
        "config": cfg,
        "backbone_at_window_open": since_start,
        "spans": tracer.ring.snapshot() if tracer.ring is not None else [],
    }
    metrics = {
        "windows_per_s": {"value": win["windows"] / win["window_s"], "unit": "windows/s"},
    }
    del tracer
    sw.free()
    gc.unfreeze()
    gc.collect()
    ctx.free_device()
    numbers, record["compare"] = judge(win, cfg, ctx.log)
    return {
        "metrics": metrics, "record": record, "attempted": win["attempted"],
        "failed": win["failed"], "numbers": numbers,
    }
