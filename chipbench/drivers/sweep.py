"""Closed-loop re-check sweeps: the driver of every traffic mix whose
`kind` is "sweep".

`BrainWorker.tick()` over the in-process store and the procedural source,
`claim_limit` = the fleet, the product's sliced sweeps. Set-up is the cold
tick of the whole fleet (a checkout's first run) or its rehydration through
the product's own durable restore, `BrainWorker.enable_fit_persistence`
(every later run), then the mix's warm-up sweeps. The window is
back-to-back sweeps, each doc judged each sweep on a fresh seeded window,
and closes at the end of the sweep in flight when `--seconds` have passed.
Every window whose verdict reached the store in it counts, over all of its
seconds.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import time

from chipbench import compare, fleet as fleetlib, probes, spec


def build_worker(cfg: dict, fl: fleetlib.Fleet, out_dir: str | None, traced: bool):
    from prometheus_client import CollectorRegistry

    from foremast_tpu.config import BrainConfig
    from foremast_tpu.jobs.worker import BrainWorker
    from foremast_tpu.observe.gauges import WorkerMetrics
    from foremast_tpu.observe.spans import Tracer

    bc = BrainConfig(
        algorithm=cfg["algorithm"],
        season_steps=int(cfg["season_steps"]),
        max_cache_size=int(cfg["max_cache_factor"] * fl.slots * max(fl.nwin)) + 64,
    )
    bc = dataclasses.replace(
        bc, anomaly=dataclasses.replace(bc.anomaly, threshold=float(cfg["anomaly_threshold"]))
    )
    registry = CollectorRegistry()
    tracer = Tracer(
        service="chipbench", registry=registry,
        trace_dir=out_dir if traced else None, buffer_size=1 << 18,
    )
    worker = BrainWorker(
        fl.store, fl.source, config=bc, claim_limit=fl.slots,
        worker_id="chipbench", metrics=WorkerMetrics(registry=registry),
        tracer=tracer, device_mesh=None,
    )
    return worker, registry, tracer


def state_dir(cfg: dict, fl) -> str:
    """Where a checkout keeps the fitted fleet of a configuration: a
    fixed path inside the checkout, one per (configuration, size, seed)."""
    return os.path.join(
        spec.ROOT, "chipbench_state",
        f"{cfg['name']}-{fl.slots}x{fl.n_hist}-seed{cfg['fleet_seed']}",
    )


class Sweeps:
    """The fleet and the worker of one process: `setup`, then windows."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, log, out_dir=None, traced=False):
        self.cfg, self.traffic, self.log = cfg, traffic, log
        self.fl = fleetlib.Fleet(cfg, traffic, seed)
        self.fl.draw_sample(int(traffic.get("sample_docs", 256)))
        self.worker, self.registry, self.tracer = build_worker(cfg, self.fl, out_dir, traced)
        self.k = 0
        self.restored = False
        log(f"fleet built: {self.fl.slots} docs, {int(self.fl.nwin.sum())} windows")

    def sweep(self, slice_docs: int | None = None) -> dict:
        """One tick over fresh windows. `slice_docs` makes it one rung of
        the set-up's ladder: the head of the queue, a sixth of the fleet
        (the worker keeps eight claims' worth of admitted documents and
        drops them all past that, so a rung's claim stays well over an
        eighth of what a run admits), judged in slices of that size."""
        fl, k, worker = self.fl, self.k, self.worker
        ts = time.perf_counter()
        now = fl.begin_sweep(k)
        fl.prefetch(k + 1)
        t = time.perf_counter()
        if slice_docs:
            usual = worker.claim_limit, worker.sweep_slice_docs
            worker.claim_limit = max(2 * slice_docs, fl.slots // 6)
            worker.sweep_slice_docs = slice_docs
            try:
                n = worker.tick(now=now)
            finally:
                worker.claim_limit, worker.sweep_slice_docs = usual
        else:
            n = worker.tick(now=now)
        dt = time.perf_counter() - t
        followed = fl.end_sweep()
        self.k += 1
        return {
            "sweep": k, "docs": n, "at": ts, "seconds": dt, "followed": followed,
            "harness_s": time.perf_counter() - ts - dt,
            "last_sweep": dict(self.worker._last_sweep or {}),
        }

    def setup(self) -> None:
        """The cold tick of the whole fleet and its persistence (a
        checkout's first run of the configuration), or its rehydration
        (every later run); then the mix's warm-up sweeps."""
        fl, worker, log = self.fl, self.worker, self.log
        directory = state_dir(self.cfg, fl)
        marker = os.path.join(directory, "complete")
        self.restored = os.path.exists(marker)
        if self.restored:
            t = time.perf_counter()
            counts = worker.enable_fit_persistence(directory)
            log(f"fitted fleet staged from {directory} in {time.perf_counter() - t:.1f} s: {counts}")
        s = self.sweep()
        log(f"{'rehydrating' if self.restored else 'cold'} tick: {s['docs']} docs in {s['seconds']:.1f} s")
        if s["docs"] != fl.slots:
            raise SystemExit(f"first tick judged {s['docs']} of {fl.slots} docs")
        if not self.restored:
            # mounted after the cold tick, so that the fleet is written once,
            # as one compacted snapshot, and not fit by fit as well
            t = time.perf_counter()
            worker.enable_fit_persistence(directory)
            entries = sum(j.compact() for j in worker._fit_journals.values())
            size = sum(os.path.getsize(os.path.join(directory, f)) for f in os.listdir(directory))
            open(marker, "w").close()
            log(
                f"fitted fleet persisted: {entries} entries, {size / 2**30:.2f} GiB "
                f"in {time.perf_counter() - t:.1f} s"
            )
        for _ in range(int(self.traffic.get("warmup_sweeps", 2))):
            s = self.sweep()
            log(
                f"warm-up sweep {s['sweep']}: {s['docs']} docs in {s['seconds']:.2f} s, "
                f"{s['followed']} went terminal; arena {worker._mvj.joint_state_counters()}"
            )
        # A followed job queues at the end of the store, so a fleet of
        # several kinds drifts: its later slices hold ever fewer docs of the
        # kinds that seldom go terminal, and every power-of-two batch bucket
        # on the way down is a program of its own. The configuration names
        # the slice sizes whose buckets the warm-up sweeps do not meet; a
        # rung each loads (a checkout's first run: compiles) them here, so
        # that the window does not.
        for n in self.cfg.get("warm_slices", []):
            s = self.sweep(slice_docs=int(n))
            log(f"warm-up rung, slices of {n}: {s['docs']} docs in {s['seconds']:.2f} s")

    def window(self, seconds: float = 0.0, sweeps: int = 1, opened=None,
               seed: int | None = None) -> dict:
        """Back-to-back sweeps until `seconds` have passed and the sweep
        in flight has ended, and `sweeps` sweeps at the least. `seed` draws
        what this window sends anew (the readings of several seeds in one
        process, chipbench.readings)."""
        fl = self.fl
        if seed is not None:
            if fl._thread is not None:
                fl._thread.join()
            fl.seed = int(seed)
            fl.draw_sample(int(self.traffic.get("sample_docs", 256)))
            fl._next = None
        fl.writes.clear()
        fl.captured.clear()
        fl.sent.clear()
        fl.sent_base.clear()
        fl.unexpected = 0
        probe = probes.Probe(self.worker, self.registry)
        before = probe.snapshot()
        first = self.k
        if opened:
            opened()
        t0 = time.perf_counter()
        done = []
        while time.perf_counter() - t0 < seconds or len(done) < sweeps:
            s = self.sweep()
            s["start"] = s.pop("at") - t0
            done.append(s)
        sweeps = done
        t_close = time.perf_counter()
        after = probe.snapshot()
        counters = probes.delta(before, after)
        released = int(counters.get("degraded_docs", 0))
        expected = sum(s["docs"] for s in sweeps)
        attempted = int(sum(w[2] for w in fl.writes)) + released
        return {
            "window_s": t_close - t0,
            "windows": int(sum(w[1] for w in fl.writes)),
            "doc_ticks": int(sum(w[2] for w in fl.writes)),
            "sweeps": sweeps,
            "counters": counters,
            "attempted": attempted,
            "failed": int(released + fl.unexpected + max(0, expected - attempted)),
            "job": compare.SweepJob(fl, first, self.k),
        }

    def free(self) -> None:
        """Drop the worker and its device state (the reference runs after)."""
        self.worker.close()
        self.fl.store.tap = None
        self.worker = self.registry = self.tracer = None


def judge(win: dict, cfg: dict, log) -> tuple[dict, dict]:
    numbers, detail = compare.judge_sweeps(win["job"], cfg, log)
    numbers["unjudged"]["value"] += float(win["failed"])
    return numbers, detail


def run(ctx) -> dict:
    cfg, args = ctx.cfg, ctx.args
    sw = Sweeps(cfg, ctx.traffic, args.seed, ctx.log, ctx.out_dir, bool(args.trace))
    sw.setup()
    gc.collect()
    gc.freeze()
    # a traced run's window is its first `trace_seconds` (a trace of the
    # whole window is 20 MB and 11 s to stop, of a run's 360)
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
