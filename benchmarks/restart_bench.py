"""Crash-injection restart benchmark: SIGKILL a durable worker, restart
it, and MEASURE the warm-restart contract (ISSUE 7).

A parent process serves the shared job store over real HTTP (the
scaleout bench's StoreServer) and runs one worker subprocess with the
full durable data plane mounted — RingSource over a journaled/
snapshotted RingStore plus write-through fit journals, all under one
snapshot directory. Phases:

  cold      first tick: fits + ring backfill (journals written through)
  warm      one measured warm tick (must already be 100% fast-path)
  kill      the worker SIGKILLs itself (os.kill SIGKILL — no cleanup,
            no atexit, no final snapshot) MID-TICK: after its claim
            persisted to the store, before any verdict
  recover   a REPLACEMENT process starts against the SAME snapshot
            directory, restores ring + fits, waits out the stuck-claim
            window, and ticks once

In-run assertions (the acceptance bar, enforced here — not eyeballed):

  * the recovery tick is ≥ 90% fast-path,
  * the fallback source served ZERO fetches during it (the pull path —
    Prometheus in production — was never touched),
  * every document was judged exactly once in the recovery round and
    nothing was judged twice across the kill (ledger),
  * torn on-disk state never crashed the restore (discard counters are
    reported, not hidden).

`--mesh` runs the same scenario with 3 mesh workers: the victim
restarts under its persisted worker id, re-takes its seat (ring
unmoved) and re-judges exactly its own partition warm.

Usage: python -m benchmarks.restart_bench [--services N] [--mesh] [--small]
Prints one JSON line per variant.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KILL_EXIT = -signal.SIGKILL  # Popen.returncode for a SIGKILLed child


# ---------------------------------------------------------------------------
# the worker child
# ---------------------------------------------------------------------------


class _CountingSynth:
    """The would-be pull path (Prometheus in production): counts every
    fetch that reaches it so 'zero fallback fetches' is measured."""

    concurrent_fetch = False

    def __init__(self):
        from benchmarks.scaleout_bench import SynthSource

        self.inner = SynthSource()
        self.calls = 0

    def fetch(self, url):
        self.calls += 1
        return self.inner.fetch(url)


class _SuicideRing:
    """Delegates to the ring source until armed, then SIGKILLs this
    process on the 3rd fetch — mid-tick, after the claim persisted,
    before any verdict. A real SIGKILL: no exception handler, no file
    close, no final snapshot."""

    concurrent_fetch = False

    def __init__(self, inner):
        self.inner = inner
        self.armed = False
        self.calls = 0

    def fetch(self, url):
        if self.armed:
            self.calls += 1
            if self.calls >= 3:
                os.kill(os.getpid(), signal.SIGKILL)
        return self.inner.fetch(url)

    # the ring-first cold path is part of the wrapped surface (ISSUE
    # 10): a production worker sees RingSource's hist reads directly
    def hist_columns(self, url, now=None):
        return self.inner.hist_columns(url, now)

    def hist_coverage(self, url, now=None):
        return self.inner.hist_coverage(url, now)

    def ingest_debug_state(self):
        return self.inner.ingest_debug_state()


def run_child(args) -> int:
    from foremast_tpu.config import BrainConfig
    from foremast_tpu.ingest import RingSnapshotter, RingSource, RingStore
    from foremast_tpu.jobs.worker import BrainWorker
    from benchmarks.scaleout_bench import HttpFleetStore

    worker_id = args.worker_id
    store = HttpFleetStore(args.store_url, worker_id)
    ring = RingStore(shards=4, max_points=args.ring_points)
    snap = RingSnapshotter(
        ring, args.snapshot_dir, interval_seconds=3600.0
    )
    restore_stats = snap.restore()
    snap.attach()
    fallback = _CountingSynth()
    source = _SuicideRing(RingSource(ring, fallback=fallback))

    node = None
    if args.mesh:
        import threading

        from foremast_tpu.mesh import Membership, MeshNode, MeshRouter

        membership = Membership(
            store, worker_id, lease_seconds=args.lease_seconds
        )
        router = MeshRouter(
            membership, refresh_seconds=min(1.0, args.lease_seconds / 4)
        )
        node = MeshNode(membership, router, ring_store=ring)
        node.start()
        # heartbeat on its own store client (requests.Session is not
        # thread-safe; the tick thread owns `store`) — dies with the
        # process, which is what makes lease behavior honest
        hb_store = HttpFleetStore(args.store_url, worker_id)
        hb_membership = Membership(
            hb_store, worker_id, lease_seconds=args.lease_seconds
        )
        hb_membership.join()

        def heartbeat():
            while True:
                time.sleep(args.lease_seconds / 3.0)
                hb_membership.renew(force=True)

        threading.Thread(target=heartbeat, daemon=True).start()

    cfg = BrainConfig(
        algorithm="moving_average_all",
        season_steps=24,
        max_stuck_seconds=args.max_stuck,
        max_cache_size=args.services * args.aliases + 64,
    )
    worker = BrainWorker(
        store, source, config=cfg, claim_limit=args.services,
        worker_id=worker_id, mesh=node,
    )
    worker.enable_fit_persistence(args.snapshot_dir)
    worker.attach_ring_snapshotter(snap)

    def tick(tag: str) -> int:
        store.tag = tag
        fallback.calls = 0
        cold0 = worker._cold_snapshot()
        t0 = time.perf_counter()
        n = worker.tick()
        cold1 = worker._cold_snapshot()
        store.report_tick(
            worker=worker_id, tag=tag, docs=n,
            seconds=round(time.perf_counter() - t0, 4),
            fast=worker._last_tick["fast"],
            fallback_fetches=fallback.calls,
            ring_hist_reads=(
                cold1["ring_full"] + cold1["ring_partial"]
                - cold0["ring_full"] - cold0["ring_partial"]
            ),
            http_hist_reads=(
                cold1["http"] + cold1["cache"]
                - cold0["http"] - cold0["cache"]
            ),
            restored_series=restore_stats["restored_series"],
            restored_fits=sum(
                j.counters["restored_entries"]
                for j in worker._fit_journals.values()
            ),
            discards={
                k: v
                for k, v in restore_stats["discards"].items()
                if v
            },
        )
        return n

    done: set[str] = set()

    def arrive(name: str):
        if name not in done:
            done.add(name)
            store.barrier(name)

    store.barrier("ready")
    while True:
        phase = store.phase()
        if phase == "stop":
            break
        if phase == "cold" and "cold" not in done:
            if tick("cold") > 0:
                arrive("cold")
            continue
        if phase == "warm" and "warm" not in done:
            if tick("warm") > 0:
                snap.snapshot()  # mid-life snapshot; logs cover the rest
                arrive("warm")
            continue
        if phase == "kill" and args.victim:
            source.armed = True
            tick("suicide")  # unreachable past fetch #3
            continue
        if (
            phase == "recover"
            and not args.victim
            and args.recovering
            and "recover" not in done
        ):
            # replacement process: wait out the stuck window, then tick
            if tick("recover") > 0:
                arrive("recover")
            else:
                time.sleep(0.5)
            continue
        if (
            phase == "coldfit"
            and args.coldfit
            and "coldfit" not in done
        ):
            # cold-fit recovery (ISSUE 10 satellite): this process
            # started with the fit journals WIPED — every doc re-fits
            # cold, and the restored ring must serve those fits alone
            if tick("coldfit") > 0:
                arrive("coldfit")
            else:
                time.sleep(0.2)
            continue
        if node is not None:
            node.on_tick()
        time.sleep(0.05)
    if node is not None:
        node.close()
    worker.close()
    snap.close()
    return 0


# ---------------------------------------------------------------------------
# parent orchestration
# ---------------------------------------------------------------------------


def _spawn(url, snap_dir, worker_id, args, victim=False, recovering=False,
           coldfit=False):
    cmd = [
        sys.executable, "-m", "benchmarks.restart_bench", "--child",
        "--store-url", url, "--snapshot-dir", snap_dir,
        "--worker-id", worker_id,
        "--services", str(args.services), "--aliases", str(args.aliases),
        "--max-stuck", str(args.max_stuck),
        "--lease-seconds", str(args.lease_seconds),
        "--ring-points", str(args.ring_points),
    ]
    if args.mesh:
        cmd.append("--mesh")
    if victim:
        cmd.append("--victim")
    if recovering:
        cmd.append("--recovering")
    if coldfit:
        cmd.append("--coldfit")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("FOREMAST_INGEST", None)
    log_path = os.path.join(
        tempfile.gettempdir(), f"restart_{worker_id}.log"
    )
    log_fh = open(log_path, "w")
    proc = subprocess.Popen(
        cmd, cwd=REPO, env=env, stdout=log_fh, stderr=subprocess.STDOUT,
        text=True,
    )
    log_fh.close()
    return proc


def _wait(predicate, timeout: float, what: str):
    deadline = time.monotonic() + timeout
    while not predicate():
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out waiting for {what}")
        time.sleep(0.05)


def _worker_log(worker_id: str) -> str:
    try:
        with open(
            os.path.join(tempfile.gettempdir(), f"restart_{worker_id}.log")
        ) as fh:
            return fh.read()
    except OSError:
        return ""


def run(args, mesh: bool, timeout: float = 900.0) -> dict:
    from benchmarks.scaleout_bench import StoreServer, build_fleet

    args.mesh = mesh
    n_workers = 3 if mesh else 1
    server = StoreServer()
    url = server.start()
    now = int(time.time())
    build_fleet(
        server.store, args.services, args.aliases, args.hist_len,
        args.cur_len, now,
    )
    snap_root = tempfile.mkdtemp(prefix="restart_bench_")
    dirs = {
        f"w{i}": os.path.join(snap_root, f"w{i}") for i in range(n_workers)
    }
    victim_id = f"w{n_workers - 1}"
    procs = {
        wid: _spawn(url, dirs[wid], wid, args, victim=(wid == victim_id))
        for wid in dirs
    }
    replacement = None
    try:
        _wait(
            lambda: server.barrier_count("ready") == n_workers,
            timeout, "workers to join",
        )
        if mesh:
            time.sleep(1.0)  # routers pick up full membership
        server.phase = "cold"
        _wait(
            lambda: server.barrier_count("cold") == n_workers,
            timeout, "cold ticks",
        )
        owners = server.owner_map() if mesh else {}
        server.phase = "warm"
        _wait(
            lambda: server.barrier_count("warm") == n_workers,
            timeout, "warm ticks",
        )
        warm_reports = [
            r for r in server.tick_reports() if r["tag"] == "warm"
        ]
        for r in warm_reports:
            assert r["fallback_fetches"] == 0, r
            assert r["fast"] == r["docs"], r

        # KILL: the victim SIGKILLs itself mid-tick (claim persisted)
        server.phase = "kill"
        _wait(
            lambda: procs[victim_id].poll() is not None,
            timeout, "victim to die",
        )
        assert procs[victim_id].returncode == KILL_EXIT, (
            procs[victim_id].returncode
        )
        from foremast_tpu.jobs.models import STATUS_PREPROCESS_INPROGRESS

        ledger_at_kill = server.ledger_snapshot()
        parked = {
            doc.id
            for doc in server.store.list_open()
            if doc.status == STATUS_PREPROCESS_INPROGRESS
            and not doc.app_name.startswith("__foremast")
        }
        assert parked, "victim died before persisting any claim"
        if mesh:
            orphans = {d for d, o in owners.items() if o == victim_id}
            assert parked == orphans, (len(parked), len(orphans))

        # RECOVER: replacement process, same snapshot dir + worker id
        t_restart = time.perf_counter()
        replacement = _spawn(
            url, dirs[victim_id], victim_id, args, recovering=True
        )
        server.phase = "recover"
        _wait(
            lambda: server.barrier_count("recover") == 1,
            timeout, "recovery tick",
        )
        recover_wall = time.perf_counter() - t_restart
        # the replacement retries empty ticks until the stuck-claim
        # window elapses; the measured tick is the one that claimed
        rec = next(
            r for r in server.tick_reports()
            if r["tag"] == "recover" and r["docs"] > 0
        )

        # ---- the acceptance bar, asserted in-run ----
        fast_frac = rec["fast"] / max(rec["docs"], 1)
        assert fast_frac >= 0.9, (
            f"recovery tick only {fast_frac:.0%} fast-path: {rec}"
        )
        assert rec["fallback_fetches"] == 0, rec
        assert rec["restored_series"] > 0 and rec["restored_fits"] > 0, rec
        # exactly-once: every parked doc judged once in recovery, and
        # no doc judged twice across the kill boundary
        ledger = server.ledger_snapshot()
        for doc_id in parked:
            entries = [
                e for e in ledger.get(doc_id, ())
                if e[1] == "recover"
            ]
            assert len(entries) == 1, (doc_id, entries)
            assert entries[0][0] == victim_id
        lost = [
            doc_id
            for doc_id in ledger_at_kill
            if len(ledger.get(doc_id, ())) < len(ledger_at_kill[doc_id])
        ]
        assert not lost

        # ---- cold-fit recovery (ISSUE 10 satellite, single variant):
        # stop the replacement, WIPE the fit journals (only the ring
        # snapshot/log survives), restart once more — the recovery
        # tick re-fits every doc COLD and the restored ring alone must
        # serve those fits with zero fallback fetches
        coldfit_report = None
        if not mesh:
            server.phase = "stop"
            try:
                replacement.wait(timeout=60)
            except subprocess.TimeoutExpired:
                replacement.kill()
                replacement.wait()
            for name in os.listdir(dirs[victim_id]):
                if name.startswith("fit-"):
                    os.unlink(os.path.join(dirs[victim_id], name))
            coldfit_proc = _spawn(
                url, dirs[victim_id], victim_id, args, coldfit=True
            )
            server.phase = "coldfit"
            try:
                _wait(
                    lambda: server.barrier_count("coldfit") == 1,
                    timeout, "cold-fit recovery tick",
                )
                cf = next(
                    r for r in server.tick_reports()
                    if r["tag"] == "coldfit" and r["docs"] > 0
                )
                assert cf["fast"] == 0, cf  # every doc re-fit cold
                assert cf["fallback_fetches"] == 0, cf
                assert cf["http_hist_reads"] == 0, cf
                assert (
                    cf["ring_hist_reads"]
                    >= args.services * args.aliases
                ), cf
                coldfit_report = cf
            finally:
                server.phase = "stop"
                if coldfit_proc.poll() is None:
                    try:
                        coldfit_proc.wait(timeout=60)
                    except subprocess.TimeoutExpired:
                        coldfit_proc.kill()
                        coldfit_proc.wait()

        server.phase = "stop"
        for p in list(procs.values()) + [replacement]:
            if p.returncode == KILL_EXIT:
                continue
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        return {
            "config": "r-restart-recovery",
            "variant": "mesh-3" if mesh else "single",
            "services": args.services,
            "aliases": args.aliases,
            "windows": args.services * args.aliases,
            "parked_docs_at_kill": len(parked),
            "recover_wall_seconds": round(recover_wall, 3),
            "recovery_tick_seconds": rec["seconds"],
            "recovery_fast_fraction": round(fast_frac, 4),
            "recovery_fallback_fetches": rec["fallback_fetches"],
            "restored_series": rec["restored_series"],
            "restored_fits": rec["restored_fits"],
            "restore_discards": rec.get("discards", {}),
            "exactly_once": True,  # asserted above
            # single variant: the ring-only recovery (fit journals
            # wiped) — cold fits served entirely from restored columns
            "coldfit_recovery": (
                {
                    "tick_seconds": coldfit_report["seconds"],
                    "ring_hist_reads": coldfit_report["ring_hist_reads"],
                    "fallback_fetches": coldfit_report["fallback_fetches"],
                }
                if coldfit_report is not None
                else None
            ),
            "metric": "recovery_fast_fraction",
            "value": round(fast_frac, 4),
            "unit": "fraction",
        }
    except BaseException:
        for wid, p in procs.items():
            if p.poll() is None:
                p.kill()
            out = _worker_log(wid)
            if out:
                sys.stderr.write(f"--- worker {wid} ---\n{out}\n")
        if replacement is not None and replacement.poll() is None:
            replacement.kill()
        raise
    finally:
        server.stop()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--services", type=int, default=4096)
    ap.add_argument("--aliases", type=int, default=4)
    ap.add_argument("--hist-len", type=int, default=256)
    ap.add_argument("--cur-len", type=int, default=30)
    ap.add_argument(
        "--mesh", action="store_true",
        help="3-worker mesh variant only (default runs single AND mesh)",
    )
    ap.add_argument(
        "--single", action="store_true",
        help="single-worker variant only",
    )
    ap.add_argument(
        "--small", action="store_true", help="CPU smoke shapes (CI)"
    )
    # child-mode flags (internal)
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--store-url", help=argparse.SUPPRESS)
    ap.add_argument("--snapshot-dir", dest="snapshot_dir", help=argparse.SUPPRESS)
    ap.add_argument("--worker-id", dest="worker_id", help=argparse.SUPPRESS)
    ap.add_argument("--victim", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument(
        "--recovering", action="store_true", help=argparse.SUPPRESS
    )
    ap.add_argument(
        "--coldfit", action="store_true", help=argparse.SUPPRESS
    )
    ap.add_argument(
        "--max-stuck", dest="max_stuck", type=float, default=3.0,
        help=argparse.SUPPRESS,
    )
    ap.add_argument(
        "--lease-seconds", dest="lease_seconds", type=float, default=30.0,
        help=argparse.SUPPRESS,
    )
    ap.add_argument(
        "--ring-points", type=int, default=512, help=argparse.SUPPRESS
    )
    args = ap.parse_args(argv)
    from foremast_tpu.device import enable_compile_cache

    enable_compile_cache()
    if args.child:
        return run_child(args)
    if args.small:
        args.services = min(args.services, 24)
        args.hist_len = min(args.hist_len, 128)
    variants = []
    if args.single or not args.mesh:
        variants.append(False)
    if args.mesh or not args.single:
        variants.append(True)
    results = {}
    for mesh in variants:
        result = run(args, mesh)
        results["mesh" if mesh else "single"] = result
        print(json.dumps(result), flush=True)
    from benchmarks.report import write_summary

    write_summary("restart", results, small=args.small)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
