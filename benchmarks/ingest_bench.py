"""Ingest-plane benchmark: warm RingSource vs PrometheusSource over HTTP.

`pipeline_bench` measures how much of the fetch stage OVERLAP can hide;
this benchmark measures how much of it the push plane ELIMINATES. Same
fleet, same samples, two workers:

  * pull — `PrometheusSource` against a real localhost HTTP server
    speaking the query_range JSON matrix protocol (socket + JSON parse
    per window, the reference brain's per-tick cost floor);
  * push — `RingSource` over a ring warmed through the remote-write
    receiver (the full wire path: JSON POST -> shard push), with the
    SAME PrometheusSource wrapped as cold-miss fallback.

Both run one cold tick (fits) and one measured warm tick; the measured
number is the tick's `metric_fetch` stage seconds from the span
pipeline. The benchmark itself asserts (a) statuses + anomaly payloads
byte-identical between the two stores and (b) the fake Prometheus
served ZERO requests during the push worker's ticks — the ISSUE 5
acceptance bar, alongside the >= 5x fetch-stage speedup.

Usage: python -m benchmarks.ingest_bench [--services N] [--aliases F]
       [--hist-len H] [--small]
Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import threading
import time
import urllib.parse

import numpy as np

from foremast_tpu.config import BrainConfig
from foremast_tpu.ingest import RingStore, RingSource, start_ingest_server
from foremast_tpu.ingest.wire import canonical_series
from foremast_tpu.jobs.models import Document
from foremast_tpu.jobs.store import InMemoryStore
from foremast_tpu.jobs.worker import BrainWorker
from foremast_tpu.metrics.promql import prometheus_url
from foremast_tpu.metrics.source import PrometheusSource

NOW = 1_760_000_000.0


class FakePrometheus:
    """Localhost query_range endpoint over a samples dict — real
    sockets, real JSON, per-request slicing; counts every request."""

    def __init__(self):
        self.data: dict[str, tuple[np.ndarray, np.ndarray]] = {}
        self.requests = 0
        self._lock = threading.Lock()
        self._srv = None

    def start(self) -> str:
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        fake = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                with fake._lock:
                    fake.requests += 1
                qs = urllib.parse.parse_qs(
                    urllib.parse.urlparse(self.path).query
                )
                key = canonical_series(qs.get("query", [""])[0])
                t0 = float(qs.get("start", ["0"])[0])
                t1 = float(qs.get("end", ["0"])[0])
                t, v = fake.data.get(
                    key, (np.zeros(0, np.int64), np.zeros(0, np.float32))
                )
                lo = int(np.searchsorted(t, t0, side="left"))
                hi = int(np.searchsorted(t, t1, side="right"))
                body = json.dumps(
                    {
                        "status": "success",
                        "data": {
                            "result": [
                                {
                                    "values": [
                                        [int(ts), str(float(val))]
                                        for ts, val in zip(
                                            t[lo:hi], v[lo:hi]
                                        )
                                    ]
                                }
                            ]
                        },
                    }
                ).encode()
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self._srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(
            target=self._srv.serve_forever, daemon=True
        ).start()
        return f"http://127.0.0.1:{self._srv.server_address[1]}/api/v1/"

    def stop(self):
        if self._srv is not None:
            self._srv.shutdown()


def build_fleet(services, aliases, hist_len, cur_len, endpoint, fake, seed=0):
    """Continuous-strategy docs: current + historical windows over the
    same app series (metricsquery.go shape), one series per alias."""
    rng = np.random.default_rng(seed)
    store = InMemoryStore()
    t_now = int(NOW)
    ht = t_now - 86_400 * 7 + 60 * np.arange(hist_len, dtype=np.int64)
    ct = ht[-1] + 60 + 60 * np.arange(cur_len, dtype=np.int64)
    end_time = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(t_now + 3600)
    )
    names = ("latency", "error5xx", "tps", "cpu")[:aliases]
    for s in range(services):
        cur_parts, hist_parts = [], []
        for a in names:
            expr = (
                f"namespace_app_per_pod:{a}"
                f'{{namespace="bench",app="app{s}"}}'
            )
            key = canonical_series(expr)
            if key not in fake.data:
                hv = rng.normal(1.0, 0.1, hist_len).astype(np.float32)
                cv = (
                    1.0 + 0.05 * np.sin(np.arange(cur_len) / 3.0)
                ).astype(np.float32)
                fake.data[key] = (
                    np.concatenate([ht, ct]),
                    np.concatenate([hv, cv]),
                )
            cur_parts.append(
                f"{a}== "
                + prometheus_url(
                    {"endpoint": endpoint, "query": expr,
                     "start": int(ct[0]), "end": int(ct[-1]), "step": 60}
                )
            )
            hist_parts.append(
                f"{a}== "
                + prometheus_url(
                    {"endpoint": endpoint, "query": expr,
                     "start": int(ht[0]), "end": int(ht[-1]), "step": 60}
                )
            )
        store.create(
            Document(
                id=f"job-{s}",
                app_name=f"app{s}",
                end_time=end_time,
                current_config=" ||".join(cur_parts),
                historical_config=" ||".join(hist_parts),
                strategy="continuous",
            )
        )
    return store


def _warm_ring_via_receiver(fake, batch=256, codec="json"):
    """Ring warmed through the real wire — remote-write POSTs in either
    codec. Returns (ring, responses): the (status, body) list is the
    cross-codec byte-parity witness (ISSUE 18) — same batches, same
    receiver code path, so JSON and binary warming must answer
    byte-identical responses."""
    import urllib.request

    from foremast_tpu.ingest import BINARY_CONTENT_TYPE, encode_frame

    ring = RingStore.from_env()
    srv, _ = start_ingest_server(0, ring, host="127.0.0.1")
    port = srv.server_address[1]
    items = list(fake.data.items())
    responses = []
    try:
        for i in range(0, len(items), batch):
            group = items[i : i + batch]
            if codec == "binary":
                body = encode_frame(
                    [
                        (key, t, v, float(t[0]))
                        for key, (t, v) in group
                    ]
                )
                ctype = BINARY_CONTENT_TYPE
            else:
                body = json.dumps(
                    {
                        "timeseries": [
                            {
                                "alias": key,
                                "times": t.tolist(),
                                "values": [float(x) for x in v],
                                "start": float(t[0]),
                            }
                            for key, (t, v) in group
                        ]
                    }
                ).encode()
                ctype = "application/json"
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/v1/write",
                data=body,
                method="POST",
                headers={"Content-Type": ctype},
            )
            resp = urllib.request.urlopen(req)
            responses.append((resp.status, resp.read()))
            assert resp.status == 200
    finally:
        srv.shutdown()
    return ring, responses


def _mk_worker(store, source, services, aliases, tracer):
    cfg = BrainConfig(
        algorithm="moving_average_all",
        season_steps=24,
        max_cache_size=aliases * services + 64,
    )
    return BrainWorker(
        store, source, config=cfg, claim_limit=services,
        worker_id="ingest-bench", tracer=tracer,
    )


def _statuses(store):
    return {
        d.id: (d.status, d.reason, d.anomaly_info)
        for d in store._docs.values()
    }


def _phase(store, source, services, aliases):
    """Cold tick (fits) + measured warm tick; returns (fetch_seconds,
    warm_tick_seconds, statuses)."""
    from prometheus_client import CollectorRegistry

    from foremast_tpu.observe.spans import Tracer

    tracer = Tracer(service="ingest-bench", registry=CollectorRegistry(),
                    trace_dir=None)
    worker = _mk_worker(store, source, services, aliases, tracer)
    n = worker.tick(now=NOW + 150)
    assert n == services, f"claimed {n} != {services}"
    t0 = time.perf_counter()
    n = worker.tick(now=NOW + 300)
    warm_s = time.perf_counter() - t0
    assert n == services
    fetch_s = tracer.last_stage_seconds.get("metric_fetch", 0.0)
    statuses = _statuses(store)
    worker.close()
    return fetch_s, warm_s, statuses


def run(services: int, aliases: int, hist_len: int, cur_len: int) -> dict:
    fake = FakePrometheus()
    endpoint = fake.start()
    try:
        pull_store = build_fleet(
            services, aliases, hist_len, cur_len, endpoint, fake
        )
        push_store = build_fleet(
            services, aliases, hist_len, cur_len, endpoint, fake
        )
        bin_store = build_fleet(
            services, aliases, hist_len, cur_len, endpoint, fake
        )
        pull_fetch_s, pull_warm_s, pull_out = _phase(
            pull_store, PrometheusSource(), services, aliases
        )
        ring, json_resps = _warm_ring_via_receiver(fake)
        # the same fleet warmed over the BINARY codec: the receiver
        # must answer byte-identical responses batch for batch, and the
        # judged statuses downstream must match too (ISSUE 18 parity)
        ring_bin, bin_resps = _warm_ring_via_receiver(fake, codec="binary")
        assert json_resps == bin_resps, (
            "receiver responses diverged across wire codecs"
        )
        # let pull-phase stragglers (handler threads still draining a
        # late keep-alive connection) finish before snapshotting the
        # request counter the zero-HTTP assertion reads
        time.sleep(1.0)
        reqs_before = fake.requests
        source = RingSource(ring, fallback=PrometheusSource())
        push_fetch_s, push_warm_s, push_out = _phase(
            push_store, source, services, aliases
        )
        zero_http = fake.requests == reqs_before
        assert push_out == pull_out, (
            "push-path judgments diverged from the pull path"
        )
        _, _, bin_out = _phase(
            bin_store,
            RingSource(ring_bin, fallback=PrometheusSource()),
            services,
            aliases,
        )
        assert bin_out == push_out, (
            "binary-warmed ring judgments diverged from the JSON-warmed ring"
        )
        stats = ring.stats()
        return {
            "config": "i-ingest-warm-fetch",
            "services": services,
            "aliases": aliases,
            "windows": services * aliases,
            "hist_len": hist_len,
            "series_resident": stats["series"],
            "ring_bytes": stats["bytes"],
            "pull_fetch_seconds": round(pull_fetch_s, 4),
            "push_fetch_seconds": round(push_fetch_s, 4),
            "pull_warm_tick_seconds": round(pull_warm_s, 4),
            "push_warm_tick_seconds": round(push_warm_s, 4),
            "ring_hit_ratio": stats["hit_ratio"],
            "zero_http_warm_tick": zero_http,
            "equivalent": True,  # asserted above
            "codec_responses_identical": True,  # asserted above
            "codec_statuses_identical": True,  # asserted above
            "metric": "fetch_stage_speedup",
            "value": (
                round(pull_fetch_s / push_fetch_s, 2)
                if push_fetch_s > 0
                else None
            ),
            "unit": "x",
        }
    finally:
        fake.stop()


def _wire_fixture(n_series, samples, batch_series, seed=7):
    """Sorted-time fixture rendered once into BOTH codecs: per-batch
    JSON bodies and FMW1 frames carrying identical series/samples."""
    from foremast_tpu.ingest import encode_frame

    rng = np.random.default_rng(seed)
    base = int(NOW) - samples * 60
    t = base + 60 * np.arange(samples, dtype=np.int64)
    json_bodies, frames, entries_per_batch = [], [], []
    for lo in range(0, n_series, batch_series):
        group = []
        for s in range(lo, min(lo + batch_series, n_series)):
            key = (
                f"namespace_app_per_pod:wire"
                f'{{app="app{s}",namespace="bench"}}'
            )
            v = rng.normal(1.0, 0.1, samples).astype(np.float32)
            group.append((key, t, v, float(t[0])))
        json_bodies.append(
            json.dumps(
                {
                    "timeseries": [
                        {
                            "alias": k,
                            "times": ts.tolist(),
                            "values": [float(x) for x in vs],
                            "start": st,
                        }
                        for k, ts, vs, st in group
                    ]
                }
            ).encode()
        )
        frames.append(encode_frame(group))
        entries_per_batch.append(len(group))
    return json_bodies, frames, entries_per_batch


def _measure_codec(bodies, decode, mk_apply, repeats=2):
    """Single-threaded decode+apply passes: returns (samples, wall
    seconds, cpu seconds, per-stage wall seconds) from the FASTEST pass
    (scheduler noise only ever slows a run down). Single thread IS the
    per-worker number — the receiver scales it by the decode pool."""
    best = None
    for _ in range(repeats):
        apply_batch = mk_apply()
        stages = {"decompress": 0.0, "decode": 0.0, "apply": 0.0}
        total = 0
        c0 = time.process_time()
        w0 = time.perf_counter()
        for body in bodies:
            entries, stage_secs = decode(body)
            for k, v in stage_secs.items():
                stages[k] += v
            t0 = time.perf_counter()
            total += sum(apply_batch(entries))
            stages["apply"] += time.perf_counter() - t0
        wall = time.perf_counter() - w0
        cpu = time.process_time() - c0
        if best is None or wall < best[1]:
            best = (
                total,
                wall,
                cpu,
                {k: round(v, 4) for k, v in stages.items()},
            )
    return best


def _dirty_slo(n_series, samples_per_cycle, seconds, pushers=2):
    """Binary pushers at full rate against the REAL receiver with a
    DirtySet wired; a drain thread plays the micro-tick, popping marks
    every 20 ms. Item-closed latency = drain instant minus the
    receiver's arrival stamp — the dirty half of the push→verdict SLO
    at the binary arrival rate."""
    import urllib.request

    from foremast_tpu.ingest import (
        BINARY_CONTENT_TYPE,
        encode_frame,
        stop_ingest_server,
    )
    from foremast_tpu.reactive.dirty import DirtySet

    ring = RingStore(budget_bytes=1 << 30, shards=16)
    dirty = DirtySet(max_keys=1 << 20)
    srv, _ = start_ingest_server(0, ring, host="127.0.0.1", dirty=dirty)
    port = srv.server_address[1]
    stop = threading.Event()
    pushed = [0] * pushers
    base = int(NOW)

    def pusher(idx):
        keys = [
            f"slo:series{{app=\"app{idx}_{s}\",namespace=\"slo\"}}"
            for s in range(n_series)
        ]
        cycle = 0
        while not stop.is_set():
            t0 = base + cycle * samples_per_cycle * 60
            ts = t0 + 60 * np.arange(samples_per_cycle, dtype=np.int64)
            vs = np.full(samples_per_cycle, 1.0 + cycle, np.float32)
            frame = encode_frame([(k, ts, vs, None) for k in keys])
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/v1/write",
                data=frame,
                method="POST",
                headers={"Content-Type": BINARY_CONTENT_TYPE},
            )
            with urllib.request.urlopen(req) as resp:
                assert resp.status == 200
            pushed[idx] += n_series * samples_per_cycle
            cycle += 1

    threads = [
        threading.Thread(target=pusher, args=(i,), daemon=True)
        for i in range(pushers)
    ]
    latencies = []
    w0 = time.perf_counter()
    for th in threads:
        th.start()
    while time.perf_counter() - w0 < seconds:
        time.sleep(0.02)
        now = time.time()
        for _key, stamp in dirty.take_all():
            latencies.append(now - stamp)
    stop.set()
    for th in threads:
        th.join(timeout=10)
    elapsed = time.perf_counter() - w0
    now = time.time()
    for _key, stamp in dirty.take_all():
        latencies.append(now - stamp)
    stop_ingest_server(srv)
    total = sum(pushed)
    wire = srv._foremast_wire_stats.snapshot()
    return {
        "arrival_samples_per_sec": round(total / elapsed),
        "items_closed": len(latencies),
        "p50_close_seconds": round(float(np.percentile(latencies, 50)), 4),
        "p99_close_seconds": round(float(np.percentile(latencies, 99)), 4),
        "receiver_stage_seconds": {
            codec: c["stage_seconds"] for codec, c in wire.items()
        },
    }


def run_wire(n_series, samples, batch_series, small) -> dict:
    """The wire-protocol phase (ISSUE 18): single-threaded decode+apply
    throughput per codec with the stage breakdown, the equal-CPU
    speedup, and the dirty-set SLO under binary push load."""
    from foremast_tpu.ingest import (
        decode_frame,
        parse_push,
        snappy_compress,
        snappy_decompress,
    )

    json_bodies, frames, _ = _wire_fixture(n_series, samples, batch_series)
    snappy_frames = [snappy_compress(f) for f in frames]
    intern: dict = {}

    def dec_json(body):
        t0 = time.perf_counter()
        entries = parse_push(json.loads(body))
        return entries, {"decode": time.perf_counter() - t0}

    def dec_bin(body):
        t0 = time.perf_counter()
        entries = decode_frame(body, intern, canonicalize=True)
        return entries, {"decode": time.perf_counter() - t0}

    def dec_bin_snappy(body):
        t0 = time.perf_counter()
        raw = snappy_decompress(body)
        t1 = time.perf_counter()
        entries = decode_frame(raw, intern, canonicalize=True)
        return entries, {
            "decompress": t1 - t0,
            "decode": time.perf_counter() - t1,
        }

    def fresh_apply(canonical):
        def mk():
            store = RingStore(budget_bytes=1 << 30, shards=16)
            return lambda entries: store.push_batch(
                entries, record_lag=False, canonical=canonical
            )

        return mk

    # interning warm pass (first frame pays utf-8+canonicalize per key,
    # exactly like a pusher's first frame) is part of the measured loop
    results = {}
    for name, bodies, dec, canonical in (
        ("json", json_bodies, dec_json, False),
        ("binary", frames, dec_bin, True),
        ("binary_snappy", snappy_frames, dec_bin_snappy, True),
    ):
        total, wall, cpu, stages = _measure_codec(
            bodies, dec, fresh_apply(canonical)
        )
        results[name] = {
            "samples": total,
            "wall_seconds": round(wall, 4),
            "cpu_seconds": round(cpu, 4),
            "samples_per_sec": round(total / wall) if wall else None,
            "samples_per_cpu_sec": round(total / cpu) if cpu else None,
            "stage_seconds": stages,
        }
    assert (
        results["json"]["samples"]
        == results["binary"]["samples"]
        == results["binary_snappy"]["samples"]
    ), "codecs accepted different sample counts from the same fixture"
    speedup = round(
        results["binary"]["samples_per_cpu_sec"]
        / results["json"]["samples_per_cpu_sec"],
        2,
    )
    slo = _dirty_slo(
        n_series=64 if small else 1024,
        samples_per_cycle=8 if small else 64,
        seconds=1.0 if small else 4.0,
    )
    out = {
        "config": "i-ingest-wire-codec",
        "series": n_series,
        "samples_per_series": samples,
        "batch_series": batch_series,
        "total_samples": results["binary"]["samples"],
        "codecs": results,
        "codec_speedup_equal_cpu": speedup,
        "dirty_slo": slo,
        "metric": "binary_samples_per_sec_per_worker",
        "value": results["binary"]["samples_per_sec"],
        "unit": "samples/s",
    }
    if not small:
        assert results["binary"]["samples_per_sec"] >= 5_000_000, (
            f"binary path {results['binary']['samples_per_sec']} < 5M "
            "samples/s per worker"
        )
        assert speedup >= 6.0, f"equal-CPU speedup {speedup} < 6x JSON"
        assert slo["p99_close_seconds"] <= 0.5, (
            f"dirty-set item-closed p99 {slo['p99_close_seconds']} > 0.5 s "
            "at the binary arrival rate"
        )
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--services", type=int, default=4096)
    ap.add_argument("--aliases", type=int, default=4)
    ap.add_argument("--hist-len", type=int, default=512)
    ap.add_argument("--cur-len", type=int, default=30)
    ap.add_argument(
        "--small", action="store_true", help="CPU smoke shapes (CI)"
    )
    args = ap.parse_args(argv)
    from foremast_tpu.device import enable_compile_cache

    enable_compile_cache()
    if args.small:
        args.services = min(args.services, 24)
        args.aliases = min(args.aliases, 2)
        args.hist_len = min(args.hist_len, 128)
    # wire-protocol phase FIRST: the warm-fetch line stays the last
    # line printed (test_ingest_bench_small_smoke reads stdout[-1])
    wire_result = run_wire(
        n_series=256 if args.small else 4096,
        samples=64 if args.small else 512,
        batch_series=64 if args.small else 256,
        small=args.small,
    )
    print(json.dumps(wire_result), flush=True)
    result = run(args.services, args.aliases, args.hist_len, args.cur_len)
    print(json.dumps(result), flush=True)
    from benchmarks.report import write_summary

    write_summary("ingest", result, small=args.small)
    write_summary("ingest_wire", wire_result, small=args.small)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
