"""Engine benchmark: metric-windows scored per second, single chip.

BASELINE.md north star: 100k concurrent metric-windows/sec on a v5e-8 →
per-chip share 12,500 windows/sec (`vs_baseline` is measured/12,500). The
workload is BASELINE.md config 5 shaped: full pipeline per window —
pairwise rank tests (Mann-Whitney + Wilcoxon + Kruskal) on baseline vs
current, historical model fit over the 7-day window (10,080 points at the
60 s step, `metricsquery.go:75-77`), bounds, anomaly flags, verdict.

Prints ONE JSON line, stamped with the device it ran on. Measures on a
TPU only: without one it exits non-zero instead of timing XLA's CPU
backend. BENCH_SMALL=1 shrinks shapes for a CPU smoke run (no summary
artifact written).
"""

import json
import os
import time

import jax

from foremast_tpu.device import device_info, enable_compile_cache, require_tpu
from foremast_tpu.engine import scoring
from foremast_tpu.parallel.batch import throughput_batch

SMALL = os.environ.get("BENCH_SMALL") == "1"
# B: the whole pending population as ONE batch is the framework's design
# center (SURVEY.md §7.4); 32k windows ≈ an 8k-service × 4-metric tick and
# amortizes dispatch latency
B = 512 if SMALL else 32768
HIST = 512 if SMALL else 10080  # 7-day window at 60 s step
CUR = 30  # 30-min current window
# Steady-state iteration count: a continuously-scoring engine pays the
# fixed cost of a timed sequence (dispatch, the closing sync) once, not
# per tick, so the loop is long enough to amortize it.
ITERS = 3 if SMALL else 100
PER_CHIP_BASELINE = 100_000 / 8  # north-star v5e-8 target, per chip


def main():
    enable_compile_cache()
    device = device_info() if SMALL else require_tpu()
    batch = throughput_batch(B, HIST, CUR)
    batch = jax.device_put(batch)

    if os.environ.get("FOREMAST_BF16_DELTA", "1") == "1":
        # anchor-shifted bf16-delta history storage: history resides as
        # f32 anchors + bf16 deltas, halving the steady-state HBM read
        # this program is bound on. Verdict/flag parity and low-CV band
        # geometry pinned by
        # tests/test_engine.py::test_bf16_delta_scorer_matches_f32...
        # Default ON; FOREMAST_BF16_DELTA=0 opts back into f32 storage.
        slim, anchor, delta = scoring.make_bf16_delta_batch(batch)
        anchor, delta, slim = jax.device_put((anchor, delta, slim))
        jax.block_until_ready(delta)

        def run(_):
            return scoring.score_bf16_delta(slim, anchor, delta)

    else:

        def run(b):
            return scoring.score(b)

    # compile + warm up
    res = run(batch)
    jax.block_until_ready(res.verdict)

    import statistics

    # Median of REPEATS timed loops.
    REPEATS = 3
    times = []
    for _rep in range(REPEATS):
        t0 = time.perf_counter()
        for _ in range(ITERS):
            res = run(batch)
        jax.block_until_ready(res.verdict)
        times.append(time.perf_counter() - t0)

    windows_per_sec = B * ITERS / statistics.median(times)
    result = {
        "metric": "metric_windows_per_sec",
        "value": round(windows_per_sec, 1),
        "unit": "windows/s",
        "vs_baseline": round(windows_per_sec / PER_CHIP_BASELINE, 3),
        **device,
    }
    print(json.dumps(result))
    from benchmarks.report import write_summary

    write_summary("engine", result, small=SMALL)


if __name__ == "__main__":
    main()
