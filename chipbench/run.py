"""Run one cell once: `python3 -m chipbench.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>`.

Fails without a TPU. `--tiny` with `JAX_PLATFORMS=cpu` is the rehearsal:
the configuration's `tiny` sizes, no device metric printed.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from chipbench import compare, spec  # noqa: E402


class Context:
    """What a driver needs of the run: sizes, the traced window, the
    device's memory, a log on standard error."""

    def __init__(self, cell, cfg, args, out_dir, on_chip):
        self.cell, self.cfg, self.args = cell, cfg, args
        self.traffic = cell.traffic
        self.out_dir = out_dir
        self.on_chip = on_chip
        self.setup_s = None
        self.trace_dir = None
        self.sync = None
        self.memory_peak = None
        self.memory_held = None
        self.window_wall = None

    def log(self, msg: str) -> None:
        print(f"[chipbench {time.time() - T_START:7.1f}s] {msg}", file=sys.stderr, flush=True)

    def window_open(self) -> None:
        import jax

        # the window's edges and the sync marker are stamped on the span
        # timeline (`spans.clock()`), the clock the program's spans carry
        from foremast_tpu.observe.spans import clock

        self.setup_s = time.time() - T_START
        if self.args.trace:
            self.trace_dir = os.path.join(self.out_dir, "trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            # a marker on both clocks: spans carry wall time, the trace its own
            with jax.profiler.TraceAnnotation("chipbench.sync"):
                self.sync = clock()
                time.sleep(0.002)
        self.window_wall = clock()
        self.log(f"window open after {self.setup_s:.1f} s of set-up")

    def window_close(self) -> None:
        import jax

        from foremast_tpu.observe.spans import clock

        self.window_wall = (self.window_wall, clock())
        if self.args.trace:
            jax.profiler.stop_trace()
        self.log("window closed")

    def read_device_memory(self) -> None:
        import jax

        peaks, held = [], []
        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
                held.append(int(stats.get("bytes_in_use", 0)))
        self.memory_peak = max(peaks) if peaks else None
        # what the fullest chip still holds once the window has closed:
        # the steady state beside the peak (PERF.md gives both)
        self.memory_held = max(held) if held else None

    def free_device(self) -> None:
        import jax

        gc.collect()
        jax.clear_caches()
        gc.collect()


def _stamp(info: dict, cell, args) -> None:
    print(json.dumps({
        "chipbench": cell.name, "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "tiny": bool(args.tiny), **info,
    }), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true", help="CPU rehearsal sizes")
    args = ap.parse_args(argv)

    cell = spec.Cell(args.workload)
    cfg = cell.deploy(args.tiny)
    out_dir = os.path.join(
        spec.ROOT, "chipbench_out", cell.name, f"seed{args.seed}-trace{args.trace}"
    )
    os.makedirs(out_dir, exist_ok=True)
    info = spec.device(args.tiny)
    cache_dir = info.pop("compile_cache")
    if not args.tiny and info["device_count"] < cell.chips:
        raise SystemExit(
            f"cell {cell.name} needs {cell.chips} chips; jax sees {info['device_count']}"
        )
    _stamp({**info, "compile_cache": cache_dir}, cell, args)
    on_chip = info["platform"] == "tpu"

    from chipbench import probes

    probes.install_compile_listeners()
    ctx = Context(cell, cfg, args, out_dir, on_chip)
    driver = importlib.import_module(f"chipbench.drivers.{cell.traffic['kind']}")
    result = driver.run(ctx)

    record = result["record"]
    record["compile_cache"] = cache_dir
    record["device_kind"] = info["device_kind"] if on_chip else None
    metrics = dict(result["metrics"])
    metrics["setup_s"] = {"value": ctx.setup_s, "unit": "s"}
    device = {
        "platform": info["platform"], "kind": info["device_kind"],
        "count": info["device_count"], "memory_peak_bytes": ctx.memory_peak,
        "memory_held_bytes": ctx.memory_held,
    }
    line = {"correct": False, "attempted": result["attempted"], "failed": result["failed"]}
    if args.trace:
        from chipbench import tracered

        reduced = tracered.reduce_run(ctx, record, on_chip)
        record["trace"] = reduced
        if on_chip:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = reduced["breakdown"]
        layer = spec.read_layers(cell, record)
        if not on_chip:
            # a CPU rehearsal prints counts only: a time, a rate or a share
            # of the device comes from a chip run alone
            counted = {m["name"] for m in cell.per_layer if m["source"] == "program_counter"}
            layer = {k: v for k, v in layer.items() if k in counted}
        line["metrics"] = layer
    else:
        if not on_chip:
            metrics = {k: {"value": None, "unit": v["unit"]} for k, v in metrics.items()}
        line["metrics"] = metrics
    line["device"] = device
    numbers = result["numbers"]
    line["correct"] = bool(compare.verdict(numbers))
    line["numbers"] = {
        k: [v["value"], v["limit"]] for k, v in numbers.items()
    }
    with open(os.path.join(out_dir, "record.json"), "w", encoding="utf-8") as fh:
        json.dump({"line": line, "end_to_end": metrics, "record": record}, fh, default=float)
    # `correct` last on stderr and last in the line: each number beside its limit
    order = ["correct", "attempted", "failed", "metrics", "device", "breakdown", "numbers"]
    line = {k: line[k] for k in order if k in line}
    for k, v in numbers.items():
        print(f"compared {k} = {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    print(f"correct = {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line, default=float), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
