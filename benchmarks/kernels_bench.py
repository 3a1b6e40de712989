"""Pallas-vs-XLA judgment bench on the bf16-delta regime.

Measures the four variants of the fused moving_average_all judgment on
identical data — XLA f32 (`scoring._score_xla`), XLA bf16-delta
(`scoring.score_bf16_delta`, the shipped steady-state program), Pallas
f32 (`ops.kernels.ma_judgment`), Pallas bf16-delta
(`ops.kernels.ma_judgment_bf16_delta`) — at the headline shape,
steady-state amortized like bench.py. Measures on a TPU only: without
one it exits non-zero. `--small` is the CPU smoke: tiny shapes, Pallas
rows in INTERPRET mode and marked `interpreted: true` — that times the
Python interpreter, not a kernel, and is never a device number.

Usage: python -m benchmarks.kernels_bench [--small] [--iters N]
One JSON line per variant.
"""

from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from foremast_tpu.engine import scoring
from foremast_tpu.ops import kernels
from foremast_tpu.parallel.batch import throughput_batch


def _time(fn, iters: int) -> float:
    res = fn()
    jax.block_until_ready(res)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            res = fn()
        jax.block_until_ready(res)
        times.append(time.perf_counter() - t0)
    return statistics.median(times) / iters


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--iters", type=int, default=None)
    args = ap.parse_args(argv)
    from foremast_tpu.device import (
        device_info,
        enable_compile_cache,
        require_tpu,
    )

    enable_compile_cache()
    device = device_info() if args.small else require_tpu()
    b = 1024 if args.small else 32_768
    th = 512 if args.small else 10_080
    tc = 30
    iters = args.iters or (3 if args.small else 20)

    batch = jax.device_put(throughput_batch(b, th, tc))
    slim, anchor, delta = scoring.make_bf16_delta_batch(batch)
    anchor, delta, slim = jax.device_put((anchor, delta, slim))
    lens = jnp.sum(batch.historical.mask, axis=-1).astype(jnp.int32)
    jax.block_until_ready(delta)

    variants = {
        "xla-f32": lambda: scoring._score_xla(batch).verdict,
        "xla-bf16-delta": lambda: scoring.score_bf16_delta(
            slim, anchor, delta
        ).verdict,
        "pallas-f32": lambda: kernels.ma_judgment(
            batch.historical.values,
            batch.historical.mask,
            batch.current.values,
            batch.current.mask,
            batch.threshold,
            batch.bound,
            batch.min_lower_bound,
            batch.min_points,
        )[0],
        "pallas-bf16-delta": lambda: kernels.ma_judgment_bf16_delta(
            anchor,
            delta,
            lens,
            batch.current.values,
            batch.current.mask,
            batch.threshold,
            batch.bound,
            batch.min_lower_bound,
            batch.min_points,
        )[0],
    }
    for name, fn in variants.items():
        # off-TPU (--small only) kernels.py picks interpret mode itself
        interpreted = (
            name.startswith("pallas") and device["platform"] != "tpu"
        )
        sec = _time(fn, iters)
        print(
            json.dumps(
                {
                    "config": "k-ma-judgment",
                    "variant": name,
                    **device,
                    "interpreted": interpreted,
                    "batch": b,
                    "hist_len": th,
                    "metric": "windows_per_sec",
                    "value": round(b / sec, 1),
                    "unit": "windows/s",
                    "seconds_per_iter": round(sec, 6),
                }
            ),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
