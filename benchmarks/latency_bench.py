"""Reactive-plane latency benchmark (ISSUE 12 round 14; ISSUE 15
round 17 — sliced, preemptible sweeps).

Every plane before this one is tick-paced: a pushed anomaly sits in the
ring until the next full sweep. This benchmark measures the reactive
plane end to end, with the REAL moving parts on both halves:

  * **deploy** — a Deployment PATCHed into the fake kube server (real
    HTTP, real chunked ``watch=true`` stream) dispatches through
    `StreamingInformer` to a handler that creates the analysis job
    (the barrelman→analyst chain collapsed to `store.create`, as in a
    single-binary deployment) and marks its app dirty; the reactive
    worker's micro-tick judges it. Measured: PATCH-sent →
    first-verdict-written. Bar (full shape): **≤ 1 s**.
  * **anomaly** — at the 16k-service fleet (warm, continuous
    background pushes keeping micro-ticks honestly busy, full sweeps
    interleaving on the poll cadence), K anomaly injections arrive
    through the REAL ingest receiver (HTTP POST, receiver-clock
    arrival stamps); each measures POST-sent →
    ``completed_unhealth``-written. HALF the injections deliberately
    fire while a sweep is IN FLIGHT (the sweep-preemption phase,
    ISSUE 15) — under monolithic sweeps those samples tracked sweep
    wall clock (round 14's 1.34 s max); sliced sweeps bound them by
    slice wall clock. Bar (full shape): **p99 ≤ 0.5 s INCLUDING the
    collision samples**.
  * **warm throughput** — the round-16 canary-heavy fleet (16,384
    services, 50% baseline docs) re-measured through the SLICED sweep:
    slicing must not regress the warm fleet rate. Bar (full shape):
    **≥ 108k windows/s** (round 16's number), warm-pipeline overlap
    ratio reported.
  * **parity** — the acceptance pins: a doc judged by a micro-tick is
    byte-identical (status, reason, anomaly payload) to the same doc
    judged by a full tick on an identical fleet; and a SLICED sweep's
    statuses are byte-identical to a monolithic sweep's on identical
    fleets — including a sharded-mesh arm (8 forced virtual devices,
    full runs) re-executed in a child process. Asserted in-run at
    every shape.

Usage: python -m benchmarks.latency_bench [--services N] [--inject K]
       [--small]
Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

from foremast_tpu.config import BrainConfig
from foremast_tpu.ingest import (
    RingSource,
    RingStore,
    canonical_series,
    start_ingest_server,
    stop_ingest_server,
)
from foremast_tpu.jobs.models import (
    STATUS_COMPLETED_UNHEALTH,
    STATUS_PREPROCESS_COMPLETED,
    Document,
)
from foremast_tpu.jobs.store import InMemoryStore
from foremast_tpu.jobs.worker import BrainWorker
from foremast_tpu.metrics.promql import prometheus_url
from foremast_tpu.reactive import DirtySet

HIST_LEN = 256
CUR_LEN = 30
STEP = 60
# ISSUE 15 acceptance bars (full shape): anomaly push→unhealthy p99
# INCLUDING sweep-collision samples, and the round-16 warm canary
# fleet rate the sliced sweep must not regress
ANOMALY_P99_BAR = 0.5
WARM_WPS_BAR = 108_000


def _expr(s: int) -> str:
    return f'latency{{namespace="bench",app="app{s}"}}'


def build_fleet(services: int, t_now: int):
    """Pure-push fleet anchored to the REAL clock (latency measurement
    needs wall time): 7-day-old history heads, current windows open
    another hour — every doc re-checks until the bench ends."""
    rng = np.random.default_rng(7)
    store = InMemoryStore()
    ring = RingStore(
        shards=8, budget_bytes=1 << 30, stale_seconds=3600.0
    )
    ht = t_now - 86_400 * 7 + STEP * np.arange(HIST_LEN, dtype=np.int64)
    ct = t_now - STEP * CUR_LEN + STEP * np.arange(CUR_LEN, dtype=np.int64)
    end_time = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(t_now + 7200)
    )
    keys = []
    for s in range(services):
        key = canonical_series(_expr(s))
        keys.append(key)
        hv = rng.normal(1.0, 0.1, HIST_LEN).astype(np.float32)
        cv = np.ones(CUR_LEN, np.float32)
        ring.push(
            key,
            np.concatenate([ht, ct]),
            np.concatenate([hv, cv]),
            start=float(ht[0]),
            now=float(t_now),
        )
        cur_url = prometheus_url(
            {"endpoint": "http://p/api/v1/", "query": _expr(s),
             "start": int(ct[0]), "end": int(t_now + 7200), "step": STEP}
        )
        hist_url = prometheus_url(
            {"endpoint": "http://p/api/v1/", "query": _expr(s),
             "start": int(ht[0]), "end": int(ht[-1]), "step": STEP}
        )
        store.create(
            Document(
                id=f"job-{s}",
                app_name=f"app{s}",
                end_time=end_time,
                current_config=f"latency== {cur_url}",
                historical_config=f"latency== {hist_url}",
                strategy="continuous",
            )
        )
    return store, ring, keys, ht, ct


def mk_worker(store, ring, services, dirty=None, microtick_seconds=0.05):
    cfg = BrainConfig(
        algorithm="moving_average_all",
        season_steps=24,
        max_cache_size=services + 64,
    )
    w = BrainWorker(
        store,
        RingSource(ring, fallback=None),
        config=cfg,
        # headroom over the fleet: a sweep that claims the WHOLE fleet
        # must read as unsaturated, or the run loop would never leave
        # the busy-sweep branch on a store where re-check docs are
        # immediately re-claimable
        claim_limit=services + 16,
        worker_id="latency-bench",
        dirty=dirty,
    )
    w.microtick_seconds = microtick_seconds
    w.microtick_docs = 512
    return w


def _post_push(port: int, key: str, ts, vs) -> None:
    body = json.dumps(
        {
            "timeseries": [
                {
                    "alias": key,
                    "times": [int(t) for t in ts],
                    "values": [float(v) for v in vs],
                }
            ]
        }
    ).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/v1/write",
        data=body,
        method="POST",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=10) as resp:
        resp.read()


def _await_status(store, doc_id, statuses, deadline_s: float):
    """Poll until the doc reaches one of `statuses`; returns elapsed
    monotonic seconds since call start, or None on timeout."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < deadline_s:
        doc = store.get(doc_id)
        if doc is not None and doc.status in statuses:
            return time.monotonic() - t0
        time.sleep(0.005)
    return None


def run_parity(services: int, t_now: int) -> None:
    """The acceptance pin: micro-tick vs full-tick byte-identical
    statuses on identical fleets (cold judgment AND a spiked
    re-check)."""
    store_a, ring_a, keys_a, ht, ct = build_fleet(services, t_now)
    store_b, ring_b, keys_b, _, _ = build_fleet(services, t_now)
    wa = mk_worker(store_a, ring_a, services)
    db = DirtySet(max_keys=services + 8)
    wb = mk_worker(store_b, ring_b, services, dirty=db)
    now = float(t_now)
    assert wa.tick(now=now) == services
    for k in keys_b:
        db.mark_series(k, now=now)
    assert wb.micro_tick(now=now) == services

    def statuses(store):
        return {
            d.id: (d.status, d.reason, d.anomaly_info)
            for d in store._docs.values()
        }

    assert statuses(store_a) == statuses(store_b), "cold parity broke"
    spike_t = ct[-3:]
    spike_v = np.full(3, 40.0, np.float32)
    for ring, keys in ((ring_a, keys_a), (ring_b, keys_b)):
        ring.push(keys[1], spike_t, spike_v, now=now)
    assert wa.tick(now=now + 60) == services
    db.mark_series(keys_b[1], now=now)
    assert wb.micro_tick(now=now + 60) == 1
    a, b = statuses(store_a), statuses(store_b)
    assert a["job-1"] == b["job-1"], "spiked re-check parity broke"
    assert a["job-1"][0] == STATUS_COMPLETED_UNHEALTH


def _statuses(store):
    return {
        d.id: (d.status, d.reason, d.anomaly_info)
        for d in store._docs.values()
    }


def run_sliced_parity(
    services: int, t_now: int, slice_docs: int = 32,
    expect_sharded: bool = False,
) -> None:
    """The ISSUE 15 acceptance pin: a SLICED sweep's statuses are
    byte-identical to a monolithic sweep's on identical fleets — cold
    judgment, a warm re-check, and a spiked re-check. With
    `expect_sharded`, the workers' univariate judges must be mesh-
    sharded (the child-process arm under 8 forced virtual devices),
    proving slicing composes with the ISSUE-13 device mesh."""
    store_a, ring_a, keys_a, ht, ct = build_fleet(services, t_now)
    store_b, ring_b, keys_b, _, _ = build_fleet(services, t_now)
    wa = mk_worker(store_a, ring_a, services)
    wa.sweep_slice_docs = 0  # the monolithic arm
    wb = mk_worker(store_b, ring_b, services)
    wb.sweep_slice_docs = slice_docs
    assert not wa._sweep_sliceable() and wb._sweep_sliceable()
    if expect_sharded:
        for w in (wa, wb):
            uni = w._uni
            assert hasattr(uni, "mesh_debug"), "judge is not sharded"
            assert uni.mesh_debug()["devices"] > 1
    now = float(t_now)
    assert wa.tick(now=now) == services  # cold: slow path both arms
    assert wb.tick(now=now) == services
    assert _statuses(store_a) == _statuses(store_b), "cold parity broke"
    assert wa.tick(now=now + 60) == services  # warm columnar re-check
    assert wb.tick(now=now + 60) == services
    assert _statuses(store_a) == _statuses(store_b), "warm parity broke"
    assert (wb._last_sweep or {}).get("slices", 0) > 1, wb._last_sweep
    spike_t = ct[-3:]
    spike_v = np.full(3, 40.0, np.float32)
    for ring, keys in ((ring_a, keys_a), (ring_b, keys_b)):
        for s in (1, services - 1):
            ring.push(keys[s], spike_t, spike_v, now=now)
    assert wa.tick(now=now + 120) == services
    assert wb.tick(now=now + 120) == services
    a, b = _statuses(store_a), _statuses(store_b)
    assert a == b, "spiked parity broke"
    assert a["job-1"][0] == STATUS_COMPLETED_UNHEALTH
    wa.close()
    wb.close()


_SHARDED_CHILD = """
import sys, time
sys.path.insert(0, {repo!r})
from benchmarks.latency_bench import run_sliced_parity
run_sliced_parity(128, int(time.time()), slice_docs=32, expect_sharded=True)
print("SHARDED_PARITY_OK")
"""


def run_sharded_parity_child() -> None:
    """Re-exec the sliced-vs-monolithic parity under 8 forced virtual
    devices + FOREMAST_DEVICE_MESH=auto: the sharded-mesh arm of the
    acceptance pin (a parent process that already initialized JAX
    cannot re-shape its device count)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"  # virtual HOST devices: a CPU check
    env["FOREMAST_DEVICE_MESH"] = "auto"
    env.pop("FOREMAST_SWEEP_SLICE_DOCS", None)
    out = subprocess.run(
        [sys.executable, "-c", _SHARDED_CHILD.format(repo=repo)],
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert out.returncode == 0, (out.stdout, out.stderr)
    assert "SHARDED_PARITY_OK" in out.stdout, (out.stdout, out.stderr)


def run_warm_throughput(
    small: bool, services: int = 16_384, ticks: int = 3
) -> dict:
    """The no-pipelining-regression phase: the round-16 canary-heavy
    fleet (50% baseline docs) measured warm through the SLICED sweep.
    Bar at the full shape: >= 108k windows/s (round 16's monolithic
    number) — slicing + the warm pipeline must not give back the
    canary-columnar win; the warm overlap ratio is the proof the
    pipeline actually overlaps."""
    from benchmarks.worker_bench import build_mixed_fleet
    from foremast_tpu.jobs.worker import BrainWorker as _BW

    n = 128 if small else services
    hist = 256 if small else 10_080
    now = float(int(time.time()))
    store, source, windows_by_doc = build_mixed_fleet(
        n, hist, CUR_LEN, now, baseline_frac=0.5
    )
    windows = sum(windows_by_doc.values())
    cfg = BrainConfig(
        algorithm="moving_average_all",
        season_steps=24,
        max_cache_size=4 * n + 64,
    )
    worker = _BW(
        store, source, config=cfg, claim_limit=n,
        worker_id="latency-warm",
    )
    if small:
        worker.sweep_slice_docs = 32  # slices engage at smoke shape too
    # backend-compile witness: the cold tick owns every compile; a warm
    # tick that recompiles has a dispatch cache-key leak (the static
    # recompile-hazard rule's runtime twin, docs/static-analysis.md)
    from foremast_tpu.analysis.recompile_witness import RecompileWitness

    wit = RecompileWitness()
    wit.install()
    try:
        with wit.phase("cold"):
            t0 = time.perf_counter()
            assert worker.tick(now=now + 150) == n
            cold_s = time.perf_counter() - t0
        rates = []
        # the FIRST warm tick owns the pipelined warm path's one-time
        # compiles (the cold sweep runs the monolithic program, so its
        # tick cannot warm them); every tick after it must run entirely
        # from the dispatch cache
        with wit.phase("pipeline_warmup"):
            t0 = time.perf_counter()
            assert worker.tick(now=now + 160) == n
            rates.append(windows / (time.perf_counter() - t0))
        with wit.phase("warm"):
            for k in range(1, ticks):
                t0 = time.perf_counter()
                assert worker.tick(now=now + 160 + 10 * k) == n
                rates.append(windows / (time.perf_counter() - t0))
        wit.assert_zero("warm")
    finally:
        wit.uninstall()
    wps = float(np.median(rates))
    sweep = dict(worker._last_sweep or {})
    pipe = sweep.get("pipeline") or {}
    worker.close()
    result = {
        "services": n,
        "windows": windows,
        "slice_docs": worker.sweep_slice_docs,
        "slices": sweep.get("slices"),
        "cold_sweep_seconds": round(cold_s, 3),
        "warm_windows_per_sec": round(wps, 1),
        "warm_overlap_ratio": pipe.get("overlap_ratio"),
        "warm_device_idle_seconds": pipe.get("device_idle_seconds"),
        "warm_write_queue_peak": pipe.get("write_queue_peak"),
        "recompiles": wit.snapshot(),
    }
    assert sweep.get("slices", 0) > 1, sweep  # the sliced path ran
    if not small:
        assert wps >= WARM_WPS_BAR, (
            f"sliced warm throughput {wps:.0f} w/s under the "
            f"{WARM_WPS_BAR} bar (round-16 regression)"
        )
    return result


def run_deploy_phase(
    store, ring, dirty, keys, t_now, worker=None, deadline_s=5.0
):
    """Deploy-to-first-verdict through the fake kube server's real
    watch stream. Returns measured seconds (None on timeout).

    The PATCH fires right after a sweep boundary (when `worker` is
    given): this phase measures the reactive chain — watch event →
    job create → dirty mark → micro-tick → verdict — not the tail of
    a colliding 16k full sweep; sweep collision cost is exactly what
    the anomaly phase's p99 already charges for."""
    import sys
    import os

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tests"))
    from fake_kube_server import FakeKubeServer
    from foremast_tpu.reactive.watchstream import StreamingInformer
    from foremast_tpu.watch.kubeapi import HttpKube

    doc_tpl = store.get("job-0")

    def on_deploy(event, dep, old):
        name = dep.get("metadata", {}).get("name", "")
        if name != "bench-deploy" or event not in ("add", "update"):
            return
        # the barrelman→service chain collapsed to one in-process
        # create (LocalAnalyst-style): a NEW analysis job for the
        # already-monitored app0, same warm series + history
        store.create(
            Document(
                id="job-deploy",
                app_name="app0",
                end_time=doc_tpl.end_time,
                current_config=doc_tpl.current_config,
                historical_config=doc_tpl.historical_config,
                strategy="continuous",
            )
        )
        # the deploy event is an arrival too: mark the app dirty so
        # the very next micro-tick claims the new job
        dirty.mark("app0", time.time())

    with FakeKubeServer() as srv:
        kube = HttpKube(base_url=srv.url, token="t")
        informer = StreamingInformer(kube, on_deploy)
        informer.resync()
        stop = threading.Event()

        def stream_loop():
            while not stop.is_set():
                informer.consume(1.0, stall_margin=2.0)

        t = threading.Thread(target=stream_loop, daemon=True)
        t.start()
        time.sleep(0.1)  # let the first watch window open
        if worker is not None:
            last = worker._last_tick["at"]
            wait_until = time.monotonic() + 10.0
            while (
                worker._last_tick["at"] == last
                and time.monotonic() < wait_until
            ):
                time.sleep(0.01)
        t0 = time.monotonic()
        srv.state.put(
            "deployments",
            "bench",
            {"metadata": {"name": "bench-deploy", "namespace": "bench",
                          "uid": "uid-bench-deploy"}},
        )
        elapsed = _await_status(
            store, "job-deploy",
            (STATUS_PREPROCESS_COMPLETED, STATUS_COMPLETED_UNHEALTH),
            deadline_s,
        )
        done_at = time.monotonic()
        stop.set()
        t.join(timeout=5)
        return None if elapsed is None else done_at - t0


def run(services: int, inject: int, small: bool) -> dict:
    t_now = int(time.time())
    run_parity(min(64, services), t_now)
    # sliced-vs-monolithic byte parity (ISSUE 15): in-process arm at
    # every shape; the sharded-mesh arm re-execs under 8 virtual
    # devices on full runs (tier-1 covers sharded parity separately)
    run_sliced_parity(min(128, services), t_now)
    if not small:
        run_sharded_parity_child()
    warm = run_warm_throughput(small)

    store, ring, keys, ht, ct = build_fleet(services, t_now)
    dirty = DirtySet(max_keys=max(8192, services))
    worker = mk_worker(store, ring, services, dirty=dirty)

    # receiver: the REAL arrival path for injections
    srv, _ = start_ingest_server(0, ring, host="127.0.0.1", dirty=dirty)
    port = srv.server_address[1]

    # fleet-warm: one cold sweep fits everything
    t0 = time.perf_counter()
    assert worker.tick(now=float(t_now)) == services
    warm_seconds = time.perf_counter() - t0

    # the reactive loop: real run() with micro drains + poll sweeps
    stop = threading.Event()
    loop = threading.Thread(
        target=worker.run,
        kwargs={"poll_seconds": 5.0, "stop": stop.is_set},
        daemon=True,
    )
    loop.start()

    # background pushers keep the dirty set honestly busy: every
    # second, benign fresh samples for ~1/30 of the fleet (direct ring
    # pushes + marks — the receiver handles the measured injections)
    bg_stop = threading.Event()

    def background():
        i = 0
        batch = max(1, services // 30)
        while not bg_stop.is_set():
            stamp = int(time.time())
            for _ in range(batch):
                s = i % services
                i += 1
                ring.push(
                    keys[s], [stamp], [1.0], now=float(stamp)
                )
                dirty.mark_series(keys[s], now=float(stamp))
            bg_stop.wait(1.0)

    bg = threading.Thread(target=background, daemon=True)
    bg.start()

    # measured deploy-to-first-verdict through the fake kube server
    deploy_seconds = run_deploy_phase(
        store, ring, dirty, keys, t_now, worker=worker
    )

    # anomaly injections through the REAL receiver, one app each
    # (starting high so the background pusher never overwrites them).
    # EVEN injections fire whenever; ODD injections are the SWEEP-
    # PREEMPTION phase (ISSUE 15): they wait for a sweep to be in
    # flight and post INTO it, so the sample set provably contains
    # sweep collisions — the p99 bar covers both arms pooled.
    latencies = []
    collision_latencies = []
    first_failures = 0
    for j in range(inject):
        s = services - 1 - j
        want_collision = (j % 2 == 1) and not small
        if want_collision:
            wait_until = time.monotonic() + 15.0
            while (
                not worker._sweep_active
                and time.monotonic() < wait_until
            ):
                time.sleep(0.002)
        stamp = int(time.time())
        ts = stamp - STEP * 2 + STEP * np.arange(3)
        t0 = time.monotonic()
        _post_push(port, keys[s], ts, np.full(3, 40.0, np.float32))
        # a sample only counts as a COLLISION if a sweep was verifiably
        # in flight when the push landed — a timed-out wait (or a sweep
        # that finished under the POST) must not launder a non-collision
        # sample into the collision arm's evidence
        collided = want_collision and worker._sweep_active
        elapsed = _await_status(
            store, f"job-{s}", (STATUS_COMPLETED_UNHEALTH,), 20.0
        )
        if elapsed is None:
            first_failures += 1
        else:
            sample = time.monotonic() - t0
            latencies.append(sample)
            if collided:
                collision_latencies.append(sample)

    bg_stop.set()
    bg.join(timeout=5)
    stop.set()
    loop.join(timeout=30)
    stop_ingest_server(srv)
    worker.close()

    lat = np.asarray(sorted(latencies), np.float64)
    clat = np.asarray(sorted(collision_latencies), np.float64)
    p50 = float(np.percentile(lat, 50)) if len(lat) else None
    p99 = float(np.percentile(lat, 99)) if len(lat) else None
    sweep_state = dict(worker._last_sweep or {})
    sweep_state.pop("pipeline", None)
    result = {
        "bench": "latency",
        "services": services,
        "inject": inject,
        "small": small,
        "fleet_warm_seconds": round(warm_seconds, 3),
        "sweep_seconds": round(worker._last_tick["seconds"], 3),
        "sweep": sweep_state,
        "warm_throughput": warm,
        "deploy_to_first_verdict_seconds": (
            None if deploy_seconds is None else round(deploy_seconds, 4)
        ),
        "anomaly_latency_p50_seconds": (
            None if p50 is None else round(p50, 4)
        ),
        "anomaly_latency_p99_seconds": (
            None if p99 is None else round(p99, 4)
        ),
        "anomaly_latency_max_seconds": (
            round(float(lat[-1]), 4) if len(lat) else None
        ),
        "sweep_collision_samples": len(clat),
        "sweep_collision_max_seconds": (
            round(float(clat[-1]), 4) if len(clat) else None
        ),
        "injections_timed_out": first_failures,
        "dirty": dirty.counts(),
        "parity": "byte-identical (asserted)",
        "sliced_parity": (
            "byte-identical (asserted"
            + ("" if small else ", incl. sharded-mesh arm")
            + ")"
        ),
    }

    # in-run assertions — every injection must land, and the reactive
    # bars hold at the full shape (reported informationally at smoke
    # shapes, same policy as the other benches)
    assert first_failures == 0, f"{first_failures} injections timed out"
    assert deploy_seconds is not None, "deploy never produced a verdict"
    if not small:
        assert deploy_seconds <= 1.0, (
            f"deploy-to-first-verdict {deploy_seconds:.3f}s > 1s bar"
        )
        # the sliced sweep actually ran sliced at the fleet shape, and
        # injections really collided with in-flight sweeps
        assert sweep_state.get("slices", 0) > 1, sweep_state
        assert len(clat) > 0, "no sweep-collision samples collected"
        assert p99 is not None and p99 <= ANOMALY_P99_BAR, (
            f"anomaly p99 {p99}s > {ANOMALY_P99_BAR}s bar "
            f"(incl. {len(clat)} sweep-collision samples, max "
            f"{result['sweep_collision_max_seconds']}s)"
        )
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--services", type=int, default=16_384)
    ap.add_argument("--inject", type=int, default=64)
    ap.add_argument(
        "--small", action="store_true", help="CPU smoke shapes (CI)"
    )
    args = ap.parse_args(argv)
    from foremast_tpu.device import enable_compile_cache

    enable_compile_cache()
    services = 64 if args.small else args.services
    inject = 4 if args.small else args.inject
    result = run(services, inject, args.small)
    print(json.dumps(result), flush=True)
    from benchmarks.report import write_summary

    write_summary(
        "latency",
        result,
        small=args.small,
        recompiles=result["warm_throughput"].get("recompiles"),
    )


if __name__ == "__main__":
    main()
