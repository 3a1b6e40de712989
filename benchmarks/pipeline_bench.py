"""Shipped cold-tick PIPELINE benchmark: serial vs overlapped chunk loop.

`worker_bench` measures the steady-state re-check loop against an
in-memory source (zero fetch latency — exactly the regime where the
chunk pipeline has nothing to hide). This benchmark measures the other
production regime: a FLEET-COLD tick whose metric windows come from a
latency-injected fake Prometheus, where the serial chunk loop leaves
the device idle for every chunk's fetch+write round trips. Same fleet,
same seed, two runs:

  * serial    — `pipeline_depth = 1` (the pre-pipeline worker);
  * pipelined — `FOREMAST_PIPELINE_DEPTH` (default 2): chunk N+1's
    windows prefetch while chunk N judges and chunk N-1's verdicts
    drain on the writer thread.

A throwaway warm-up run (discarded) pays the XLA compiles first so both
measured phases see hot jit caches, and the two runs' final document
statuses are compared — the benchmark itself asserts write-equivalence
(the full contract is pinned in tests/test_worker_pipeline.py).

Usage: python -m benchmarks.pipeline_bench [--services N] [--latency-ms L]
       [--depth D] [--chunk-docs C] [--small]
Prints one JSON line: both cold-tick times, the speedup, and the
pipeline's occupancy stats (device-idle seconds, overlap ratio).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks.worker_bench import _add_service
from foremast_tpu.config import BrainConfig
from foremast_tpu.jobs.store import InMemoryStore
from foremast_tpu.jobs.worker import BrainWorker
from foremast_tpu.metrics.source import MetricSource

ALIASES_PER_DOC = 4  # worker_bench's reference 4-metric monitor shape


class LatencySource(MetricSource):
    """Exact-match URL->series map with an injected per-fetch sleep —
    the fake-Prometheus floor plus the one thing ArraySource elides:
    the HTTP round trip the pipeline exists to hide. Declares
    `concurrent_fetch = True` (like the real PrometheusSource) so the
    worker fans fetches over its pool and engages the pipeline."""

    concurrent_fetch = True

    def __init__(self, latency_s: float):
        self.data: dict[str, tuple[np.ndarray, np.ndarray]] = {}
        self.latency_s = latency_s

    def fetch(self, url: str):
        if self.latency_s > 0:
            time.sleep(self.latency_s)
        return self.data[url]


def build_fleet(
    services: int,
    hist_len: int,
    cur_len: int,
    now: float,
    latency_s: float,
    seed: int = 0,
):
    """One document per service x 4 aliases (worker_bench shapes), all
    cold: no tick has run, so every fit is new."""
    rng = np.random.default_rng(seed)
    store = InMemoryStore()
    source = LatencySource(latency_s)
    t_now = int(now)
    ht = t_now - 86_400 * 7 + 60 * np.arange(hist_len, dtype=np.int64)
    ct = ht[-1] + 60 + 60 * np.arange(cur_len, dtype=np.int64)
    end_time = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(t_now + 3600)
    )
    for s in range(services):
        _add_service(
            store, source, str(s), ht, ct, hist_len, cur_len, end_time, rng
        )
    return store, source


def run_phase(
    depth: int,
    services: int,
    chunk_docs: int,
    hist_len: int,
    cur_len: int,
    latency_s: float,
    algorithm: str,
    now: float,
    fetch_workers: int = 16,
):
    """One fleet-cold tick at the given pipeline depth; returns
    (cold_seconds, pipeline_stats, statuses)."""
    store, source = build_fleet(services, hist_len, cur_len, now, latency_s)
    cfg = BrainConfig(algorithm=algorithm, season_steps=24,
                      max_cache_size=4 * services + 64)
    worker = BrainWorker(
        store,
        source,
        config=cfg,
        claim_limit=services,
        worker_id=f"pipe-bench-d{depth}",
    )
    worker.cold_chunk_docs = chunk_docs
    worker.pipeline_depth = depth
    worker.fetch_workers = fetch_workers
    t0 = time.perf_counter()
    n = worker.tick(now=now + 150)
    cold_s = time.perf_counter() - t0
    assert n == services, f"claimed {n} != {services}"
    stats = dict(worker._last_pipeline or {})
    statuses = {d.id: (d.status, d.reason) for d in store._docs.values()}
    worker.close()
    return cold_s, stats, statuses


def run(
    services: int,
    latency_ms: float,
    depth: int,
    chunk_docs: int,
    hist_len: int,
    cur_len: int,
    algorithm: str,
    fetch_workers: int = 16,
) -> dict:
    now = 1_760_000_000.0
    latency_s = latency_ms / 1000.0
    args = (services, chunk_docs, hist_len, cur_len, latency_s,
            algorithm, now, fetch_workers)
    # throwaway run: pays the XLA compiles so both measured phases are
    # hot (zero injected latency — this phase only exists to compile)
    run_phase(1, services, chunk_docs, hist_len, cur_len, 0.0,
              algorithm, now)
    serial_s, serial_stats, serial_out = run_phase(1, *args)
    piped_s, piped_stats, piped_out = run_phase(depth, *args)
    assert serial_out == piped_out, (
        "pipelined tick diverged from the serial path"
    )
    return {
        "config": "p-pipelined-cold-tick",
        "services": services,
        "windows": services * ALIASES_PER_DOC,
        "latency_ms": latency_ms,
        "depth": depth,
        "fetch_workers": fetch_workers,
        "chunk_docs": chunk_docs,
        "chunks": piped_stats.get("chunks"),
        "algorithm": algorithm,
        "serial_cold_tick_seconds": round(serial_s, 3),
        "pipelined_cold_tick_seconds": round(piped_s, 3),
        "serial_stage_seconds": {
            k: serial_stats.get(k)
            for k in ("fetch_seconds", "judge_seconds", "write_seconds")
        },
        "device_idle_seconds": piped_stats.get("device_idle_seconds"),
        "overlap_ratio": piped_stats.get("overlap_ratio"),
        "write_queue_peak": piped_stats.get("write_queue_peak"),
        "equivalent": True,  # asserted above
        "metric": "cold_tick_speedup",
        "value": round(serial_s / piped_s, 3) if piped_s > 0 else None,
        "unit": "x",
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--services", type=int, default=4096)
    ap.add_argument("--latency-ms", type=float, default=3.0,
                    help="injected per-fetch latency (fake Prometheus "
                    "round trip)")
    ap.add_argument("--depth", type=int, default=2)
    ap.add_argument("--fetch-workers", type=int, default=16,
                    help="persistent fetch-pool size "
                    "(FOREMAST_FETCH_WORKERS equivalent)")
    ap.add_argument("--chunk-docs", type=int, default=512)
    ap.add_argument("--hist-len", type=int, default=512)
    ap.add_argument("--cur-len", type=int, default=30)
    ap.add_argument("--algorithm", default="moving_average_all")
    ap.add_argument(
        "--small", action="store_true", help="CPU smoke shapes (CI)"
    )
    args = ap.parse_args(argv)
    from foremast_tpu.device import enable_compile_cache

    enable_compile_cache()
    if args.small:
        args.services = min(args.services, 48)
        args.hist_len = min(args.hist_len, 128)
        args.chunk_docs = min(args.chunk_docs, 16)
        args.latency_ms = min(args.latency_ms, 1.0)
    result = run(
        args.services,
        args.latency_ms,
        args.depth,
        args.chunk_docs,
        args.hist_len,
        args.cur_len,
        args.algorithm,
        fetch_workers=args.fetch_workers,
    )
    print(json.dumps(result), flush=True)
    from benchmarks.report import write_summary

    write_summary("pipeline", result, small=args.small)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
