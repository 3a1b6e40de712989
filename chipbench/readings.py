"""The readings a cell's limits are set from, taken in ONE process, because
a process's set-up is minutes long: the fleet is set up once, then

  * the program on each of `--seeds`: a short window (`--sweeps` sweeps at
    the cell's own load) of what that seed sends, and the comparison;
  * each fault of each fleet kind (chipbench/faults/<kind>.py) planted
    under the timed path, a window, the comparison: it has to come out as
    not correct, by the number the fault names;
  * the control (chipbench/control.py) on each of `--control-seeds`.

    python3 -m chipbench.readings --workload <cell> --seeds 1,2,3 \\
        --control-seeds 4,5,6 [--sweeps 4] [--control-sweeps 15] [--faults 1] [--tiny]

One JSON line a reading on standard output. The benchmark's own runs do
not run this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time

from chipbench import compare, control, faults, spec

T0 = time.time()


def log(msg: str) -> None:
    print(f"[readings {time.time() - T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def emit(kind: str, seed, numbers: dict, detail: dict, **more) -> None:
    print(json.dumps({
        "reading": kind, "seed": seed, "correct": compare.verdict(numbers),
        "numbers": {k: [v["value"], v["limit"]] for k, v in numbers.items()},
        "detail": detail, **more,
    }, default=float), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--sweeps", type=int, default=4)
    ap.add_argument("--control-sweeps", type=int, default=15, help="as many as a run's window holds")
    ap.add_argument("--faults", type=int, choices=(0, 1), default=1)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]

    cell = spec.Cell(args.workload)
    cfg = cell.deploy(args.tiny)
    print(json.dumps({"readings": cell.name, **spec.device(args.tiny)}), flush=True)
    driver = importlib.import_module(f"chipbench.drivers.{cell.traffic['kind']}")

    if seeds or args.faults:
        sw = driver.Sweeps(cfg, cell.traffic, seeds[0] if seeds else 1, log)
        sw.setup()
        for seed in seeds:
            t = time.perf_counter()
            win = sw.window(sweeps=args.sweeps, seed=seed)
            numbers, detail = driver.judge(win, cfg, log)
            emit("program", seed, numbers, detail, window_s=win["window_s"],
                 windows=win["windows"], seconds=time.perf_counter() - t)
        if args.faults:
            # the faults that leave docs unjudged go last: a doc left claimed
            # stays so, and would read as unjudged under every later fault
            planted = sorted(
                faults.fleet_faults(cfg["fleet"]).items(), key=lambda kv: kv[1][1] == "unjudged"
            )
            for name, (plant, number) in planted:
                seed = (seeds[-1] if seeds else 1) + 1
                with plant():
                    win = sw.window(sweeps=args.sweeps, seed=seed)
                numbers, detail = driver.judge(win, cfg, log)
                emit("fault:" + name, seed, numbers, detail, fails=number)
        sw.free()
    for seed in control_seeds:
        t = time.perf_counter()
        out = control.control_margin(cfg, cell.traffic, seed, args.control_sweeps, log=log)
        print(json.dumps({"reading": "control", **out, "seconds": time.perf_counter() - t},
                         default=float), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
