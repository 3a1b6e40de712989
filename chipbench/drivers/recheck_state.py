"""Closed-loop re-check sweeps of a fleet whose cache rows hold recurrent
state and latents: the driver of every traffic mix whose `kind` is
"recheck_state" (fleet kind `backbone_kda`, a linear-attention backbone).

It is `drivers/recheck.py`'s loop: the set-up and the window are
`drivers/sweep.py`'s own, unedited — the cold tick prefills every sequence's
history into its row (a fleet of this kind has nothing to persist, so every
run of a checkout prefills), the mix's warm-up sweeps compile and run the
window program, and the window is back-to-back sweeps in which every
sequence is asked again with a fresh seeded window as the continuation of
its cached prefix. What this driver adds is what `chipbench/probes.py` does
not read: the kind's own counters (`MultivariateJudge.backbone_counters()`
under `ML_ALGORITHM=backbone_kda`: tokens prefilled and scored, the latent
positions attended, the bytes of state read, the token assignments of each
held expert, assignments dropped), as `backbone_kda.<counter>` beside the
window's other counters.

The model is imported first: a tree without it exits here, non-zero, at
once, and neither hangs nor judges the fleet with another detector.
"""

from __future__ import annotations

import foremast_tpu.models.kimi_linear  # noqa: F401  (a tree without the model stops here)

import gc  # noqa: E402

from chipbench.drivers import sweep  # noqa: E402

KIND = "backbone_kda"
judge = sweep.judge


def _flat(counters: dict | None) -> dict:
    out = {}
    for key, v in (counters or {}).items():
        if isinstance(v, list):
            out.update({f"{KIND}.{key}.{i}": float(x) for i, x in enumerate(v)})
        else:
            out[f"{KIND}.{key}"] = float(v)
    return out


class Sweeps(sweep.Sweeps):
    """`sweep.Sweeps`, with the kind's counters in a window's books."""

    def kind_counters(self) -> dict:
        return _flat(self.worker._mvj.backbone_counters())

    def window(self, *args, **kwargs) -> dict:
        before = self.kind_counters()
        win = super().window(*args, **kwargs)
        after = self.kind_counters()
        gauges = (f"{KIND}.cache_rows_live",)
        win["counters"].update(
            {k: v if k in gauges else v - before.get(k, 0.0) for k, v in after.items()}
        )
        return win


def run(ctx) -> dict:
    cfg, args = ctx.cfg, ctx.args
    if cfg["algorithm"] != KIND:
        raise SystemExit(f"traffic of kind recheck_state drives a fleet of kind {KIND}")
    sw = Sweeps(cfg, ctx.traffic, args.seed, ctx.log, ctx.out_dir, bool(args.trace))
    sw.setup()
    since_start = sw.kind_counters()
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
        "kind_counters_at_window_open": since_start,
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
