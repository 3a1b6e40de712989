"""Closed-loop re-check sweeps of a fleet scored by block diffusion: the
driver of every traffic mix whose `kind` is "recheck_blocks" (fleet kind
`backbone_diffusion`).

It is `drivers/recheck_state.py`'s loop for this kind: the set-up and the
window are `drivers/sweep.py`'s own, unedited — the cold tick prefills every
sequence's history as clean blocks into its row (a fleet of this kind has
nothing to persist, so every run of a checkout prefills), the mix's warm-up
sweeps compile and run the window program, and the window is back-to-back
sweeps in which every sequence is asked again with a fresh seeded window.
What this driver adds is what `chipbench/probes.py` does not read: the
kind's own counters (`MultivariateJudge.backbone_counters()` under
`ML_ALGORITHM=backbone_diffusion`: tokens prefilled and scored, the noisy
copies' and the clean window's token-forwards, the tokens whose attention
took the fused kernel, the token assignments of each expert, assignments
dropped), as `backbone_diffusion.<counter>` beside the window's other
counters; and the window program's scores of every judgment the comparison
captures, which the kind's reference then holds point by point against its
own (`references/backbone_diffusion.py:score_numbers`) beside compare.py's
flags.

The model is imported first: a tree without it exits here, non-zero, at
once, and neither hangs nor judges the fleet with another detector.
"""

from __future__ import annotations

import foremast_tpu.models.sdar_moe  # noqa: F401  (a tree without the model stops here)

import gc  # noqa: E402

import numpy as np  # noqa: E402

from chipbench.drivers import sweep  # noqa: E402
from chipbench.references import backbone_diffusion as reference  # noqa: E402

KIND = "backbone_diffusion"


def _flat(counters: dict | None) -> dict:
    out = {}
    for key, v in (counters or {}).items():
        if isinstance(v, list):
            out.update({f"{KIND}.{key}.{i}": float(x) for i, x in enumerate(v)})
        else:
            out[f"{KIND}.{key}"] = float(v)
    return out


class Sweeps(sweep.Sweeps):
    """`sweep.Sweeps`, with the kind's counters in a window's books and the
    scores of the judgments the comparison captures."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.scored: dict = {}  # (uid, alias, sweep) -> the window's scores [W]

    def kind_counters(self) -> dict:
        return _flat(self.worker._mvj.backbone_counters())

    def keep_scores(self) -> None:
        """From now on the detector's scores of each sequence whose doc the
        fleet captures in the sweep in flight are kept (a sequence's key is
        (kind, app, alias, history), its app `app<uid>`)."""
        det = self.worker._mvj.kind_state[KIND].detector
        fl, kept, score = self.fl, self.scored, det.score
        slot_of = {int(u): s for s, u in enumerate(fl.uid)}

        def keeping(keys, scales, windows, valid):
            out = score(keys, scales, windows, valid)
            for i, key in enumerate(keys):
                slot = slot_of.get(int(key[1][3:])) if key[1].startswith("app") else None
                if slot is not None and fl.capture[slot]:
                    kept[(int(fl.uid[slot]), key[2], fl.sweep)] = out[i][valid[i]]
            return out

        det.score = keeping

    def program_scores(self, aliases: list) -> dict:
        """{(uid, sweep): [F, W]} of every judgment whose aliases were all kept."""
        out = {}
        for uid, k in {(u, k) for u, _, k in self.scored}:
            got = [self.scored.get((uid, a, k)) for a in aliases]
            if all(g is not None for g in got):
                out[(uid, k)] = np.stack(got)
        return out

    def window(self, *args, **kwargs) -> dict:
        self.keep_scores()
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
        raise SystemExit(f"traffic of kind recheck_blocks drives a fleet of kind {KIND}")
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
    program = sw.program_scores(cfg["fleet"][0]["aliases"])
    sw.free()
    gc.unfreeze()
    gc.collect()
    ctx.free_device()
    numbers, record["compare"] = sweep.judge(win, cfg, ctx.log)
    numbers.update(reference.score_numbers(program, cfg))
    return {
        "metrics": metrics, "record": record, "attempted": win["attempted"],
        "failed": win["failed"], "numbers": numbers,
    }
