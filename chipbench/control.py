"""The control of the sweep cells' comparison: each fleet kind's plain
reference put in the program's place and computed in the nearest precision
below the one the configuration states (bfloat16 for float32). Its
judgments go through the same comparison as the program's and have to come
out as not correct.

    python3 -m chipbench.control --workload <cell> --seeds 1,2,3 [--sweeps 15] [--tiny]

Prints one line a seed: the control's flip_rate beside the limit. The
benchmark's own runs do not run it; its small twin is a test under
chipbench/tests. What it computes imports nothing of the program.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from chipbench import compare, series


class ControlJob:
    """The judgments a window of `sweeps` sweeps compares: the sample and
    every spiked service of each sweep, with the windows sent (what
    compare.SweepJob takes from a run's books, drawn from the seed)."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, sweeps: int, first: int = 3):
        self.groups = cfg["fleet"]
        self.history_seed = int(cfg["fleet_seed"])
        self.n_hist, self.fam = int(cfg["history_points"]), cfg["series"]
        self.w, self.step, self.hist_end = int(cfg["window_points"]), int(cfg["step_seconds"]), 0
        self.missing = self.short = 0
        layout = series.slot_layout(self.groups)
        group_of, local = layout
        sample = series.sample(seed, len(group_of), int(traffic.get("sample_docs", 256)))
        fit_pos = series.fit_positions(
            self.groups, group_of, int(cfg["env"]["FOREMAST_COLD_CHUNK_DOCS"])
        )
        self.rows = []
        for k in range(first, first + sweeps):
            values, spikes, bases = series.draw_sweep(
                seed, k, self.groups, layout, self.w, self.n_hist, self.fam, traffic
            )
            for s in np.unique(np.concatenate([sample, spikes[0]])):
                g, i = group_of[s], local[s]
                self.rows.append({
                    "uid": int(s), "fit_pos": int(fit_pos[s]), "sweep": k,
                    "group": int(g), "sent": values[g][i].copy(),
                    "base": None if bases[g] is None else bases[g][i].copy(),
                })


def control_margin(cfg: dict, traffic: dict, seed: int, sweeps: int, log=None) -> dict:
    job = ControlJob(cfg, traffic, seed, sweeps)
    numbers, detail = compare.judge_sweeps(job, cfg, log or (lambda m: None), control=True)
    return {
        "seed": seed, "compared": len(job.rows),
        "flip_rate": numbers["flip_rate"]["value"],
        "limit": numbers["flip_rate"]["limit"],
        # each kind's own reading beside its own limit (None: not compared)
        "by_kind": {
            k[len("flip_rate."):]: [v["value"], v["limit"]]
            for k, v in numbers.items() if k.startswith("flip_rate.")
        },
        "flip_margin": numbers["flip_margin"]["value"],
        "windows_differ": int(numbers["windows_differ"]["value"]),
        "correct": compare.verdict(numbers), "detail": detail,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sweeps", type=int, default=15)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    from chipbench import spec

    cell = spec.Cell(args.workload)
    cfg = cell.sized(args.tiny)
    info = spec.device(args.tiny)
    print(json.dumps(info), flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = control_margin(cfg, cell.traffic, seed, args.sweeps,
                             log=lambda m: print(m, file=sys.stderr, flush=True))
        out["seconds"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
