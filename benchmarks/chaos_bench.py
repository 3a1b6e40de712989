"""Chaos soak: a 3-worker mesh under a scheduled fault plan (ISSUE 9).

Every other benchmark measures the system healthy; this one PROVES the
degradation story end to end. Three `BrainWorker`s (the shipped stack:
mesh membership + consistent-hash claims + per-worker ingest receiver)
run against a REAL HTTP store server (`scaleout_bench.StoreServer`,
grown fault hooks) and a real `PrometheusSource` whose session
synthesizes query_range responses, while a seeded `FaultPlan` walks
through the ISSUE's scheduled faults:

  baseline   healthy pass — compiles programs, proves the harness
  brownout   the store answers 503 on every write for a window: write-
             backs buffer locally (write-behind), claims/renews degrade,
             store breakers open; on heal the backlog replays
  blackhole  Prometheus goes dark: fetch faults fail fast once the
             breaker opens, docs RELEASE un-judged instead of failing
  flood      4 concurrent pushers against one latency-injected receiver
             with a small inflight cap: sheds answer 429 + Retry-After,
             pushers retry-then-buffer, the backlog drains post-flood
  skew       one worker's mesh clock runs fast by lease/2 (the pinned
             tolerance's ops guidance): nobody is falsely declared dead
  crash      one worker wedges mid-tick with claims parked (no leave, no
             renew — the SIGKILL effect in-process; `restart_bench` owns
             the real-SIGKILL variant): the ring heals on lease expiry
             and survivors re-judge the orphans via stuck-claim takeover

In-run asserts (the acceptance bar — the bench FAILS, not just reports):
zero lost or duplicated verdicts in every phase (one terminal ledger
entry per doc), every breaker re-closed at the end, recovery ≤ 2 busy
ticks per worker after each fault clears, the runtime lock witness
observes no edge missing from the committed static graph, and every
bounded structure (write-behind, pusher buffer, ring budget) stays
inside its cap.

Usage: python -m benchmarks.chaos_bench [--small]
Prints one JSON line per phase plus a summary line.
"""

from __future__ import annotations

import argparse
import json
import threading
import time
import urllib.parse

from benchmarks.scaleout_bench import (
    ALIAS_EXPR,
    HttpFleetStore,
    StoreServer,
    synth_values,
)

LEASE_SECONDS = 2.0
# comfortably above the brownout hold + the write-behind replay margin
# (the worker shaves min(store timeout, window/3) off the replay
# window so a slow replay RPC cannot cross the takeover boundary)
MAX_STUCK_SECONDS = 6.0
POLL_SECONDS = 0.05

# fault-plan schedule (plan-clock seconds; the driver moves the clock)
PROM_WINDOW = (100.0, 200.0)
RECEIVER_WINDOW = (300.0, 400.0)
SKEW_WINDOW = (500.0, 600.0)


class _Resp:
    status_code = 200

    def __init__(self, body):
        self._body = body

    def raise_for_status(self):
        pass

    def json(self):
        return self._body


class SynthSession:
    """requests-shaped session synthesizing a query_range JSON matrix
    from the URL alone — the REAL `PrometheusSource` (retries, chaos
    seam, breaker) runs unmodified on top."""

    def __init__(self):
        self.wedged = threading.Event()

    def get(self, url, timeout=None):
        if self.wedged.is_set():
            # crash emulation: this fetch never returns (the worker's
            # tick thread is a daemon — see the crash phase)
            threading.Event().wait()
        import numpy as np

        from foremast_tpu.ingest.wire import resolve_query_range

        key, t0, t1, step = resolve_query_range(url)
        if key is None or t0 is None or t1 is None:
            raise ValueError(f"unresolvable synth url {url!r}")
        ts = np.arange(int(t0), int(t1) + 1, int(step or 60), np.int64)
        vs = synth_values(key, ts)
        return _Resp(
            {
                "status": "success",
                "data": {
                    "result": [
                        {
                            "values": [
                                [int(t), str(float(v))]
                                for t, v in zip(ts, vs)
                            ]
                        }
                    ]
                },
            }
        )


class ChaosWorker:
    """One mesh worker: shipped BrainWorker + MeshNode + receiver, its
    tick loop on a daemon thread, tick log for the recovery asserts."""

    def __init__(self, wid: str, store_url: str, plan, degrade_kw):
        from foremast_tpu.chaos import (
            BreakerRegistry,
            Degradation,
            WriteBehindBuffer,
        )
        from foremast_tpu.chaos.degrade import DegradeStats
        from foremast_tpu.config import BrainConfig
        from foremast_tpu.ingest import RingStore, start_ingest_server
        from foremast_tpu.jobs.worker import BrainWorker
        from foremast_tpu.mesh import Membership, MeshNode, MeshRouter
        from foremast_tpu.metrics.source import PrometheusSource

        self.wid = wid
        stats = DegradeStats()
        self.degrade = Degradation(
            stats=stats,
            breakers=BreakerRegistry(**degrade_kw),
            write_behind=WriteBehindBuffer(
                max_docs=4096, max_age_seconds=MAX_STUCK_SECONDS,
                stats=stats,
            ),
        )
        self.fleet = HttpFleetStore(
            store_url, wid,
            chaos=plan.edge("store"),
            breaker=self.degrade.breakers.get("store"),
        )
        self.session = SynthSession()
        source = PrometheusSource(
            session=self.session, retries=1, backoff_seconds=0.01,
            chaos=plan.edge("prometheus"),
            breaker=self.degrade.breakers.get("prometheus"),
        )
        # serial fetches: 3 in-process workers threading pure-CPU synth
        # fetches would only fight the GIL, and the crash phase wedges
        # the TICK thread (a daemon), never a non-daemon pool thread
        source.concurrent_fetch = False
        membership = Membership(
            self.fleet, wid, lease_seconds=LEASE_SECONDS,
            # the skew phase runs ONE member's clock fast (w2 both
            # stamps its leases and reads peers' by this clock)
            clock=plan.edge("clock").clock() if wid == "w2" else time.time,
        )
        router = MeshRouter(membership, refresh_seconds=0.5)
        self.ring = RingStore(budget_bytes=1 << 20, shards=2)
        self.receiver, _ = start_ingest_server(
            0, self.ring, host="127.0.0.1", router=router,
            max_inflight=2, chaos=plan.edge("receiver"),
            degrade_stats=stats,
        )
        membership.ingest_address = (
            "127.0.0.1:%d" % self.receiver.server_address[1]
        )
        self.node = MeshNode(membership, router, ring_store=self.ring)
        config = BrainConfig(
            algorithm="moving_average_all",
            max_stuck_seconds=MAX_STUCK_SECONDS,
            max_cache_size=4096,
        )
        self.worker = BrainWorker(
            self.fleet, source, config=config, claim_limit=64,
            worker_id=wid, mesh=self.node, degrade=self.degrade,
        )
        self.tick_log: list[tuple[float, float, int]] = []
        self._stop = threading.Event()
        self.thread = threading.Thread(
            target=self._loop, name=f"chaos-{wid}", daemon=True
        )

    def _loop(self):
        while not self._stop.is_set():
            t0 = time.monotonic()
            try:
                n = self.worker.tick()
            except Exception:  # pragma: no cover — the bench fails below
                import logging

                logging.getLogger("chaos_bench").exception(
                    "worker %s tick crashed", self.wid
                )
                self.tick_log.append((t0, time.monotonic(), -1))
                return
            self.tick_log.append((t0, time.monotonic(), n))
            if n == 0:
                time.sleep(POLL_SECONDS)

    def busy_ticks_after(self, t: float) -> int:
        return sum(1 for t0, _, n in self.tick_log if t0 > t and n > 0)

    def crashed(self) -> bool:
        return any(n < 0 for _, _, n in self.tick_log)

    def stop(self):
        self._stop.set()


def seed_batch(server, tag: str, count: int, hist_len: int, cur_len: int):
    """`count` finalize-on-first-judgment docs (endTime in the past):
    exactly-once then means exactly one terminal ledger entry per doc."""
    from foremast_tpu.jobs.models import Document

    now = int(time.time())
    cur_t1 = now - 60
    cur_t0 = cur_t1 - 60 * (cur_len - 1)
    hist_t1 = cur_t0 - 120
    hist_t0 = hist_t1 - 60 * (hist_len - 1)
    end_time = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(now - 30)
    )
    ids = []
    for i in range(count):
        sid = f"{tag}-{i}"
        expr = urllib.parse.quote(
            ALIAS_EXPR.format(a=0, sid=sid), safe=""
        )
        doc_id = f"job-{sid}"
        server.store.create(
            Document(
                id=doc_id,
                app_name=f"app{sid}",
                end_time=end_time,
                current_config=(
                    f"m0== http://synth/api/v1/query_range?query={expr}"
                    f"&start={cur_t0}&end={cur_t1}&step=60"
                ),
                historical_config=(
                    f"m0== http://synth/api/v1/query_range?query={expr}"
                    f"&start={hist_t0}&end={hist_t1}&step=60"
                ),
                strategy="continuous",
            )
        )
        ids.append(doc_id)
    return ids


def wait_all_terminal(server, ids, timeout: float) -> float:
    """Poll until every doc is terminal; returns the completion wall
    time (monotonic). Raises on timeout — a lost verdict IS the bug
    this bench exists to catch."""
    from foremast_tpu.jobs.models import TERMINAL_STATUSES

    deadline = time.monotonic() + timeout
    while True:
        statuses = [server.store.get(i).status for i in ids]
        if all(s in TERMINAL_STATUSES for s in statuses):
            return time.monotonic()
        if time.monotonic() > deadline:
            pending = [
                (i, s)
                for i, s in zip(ids, statuses)
                if s not in TERMINAL_STATUSES
            ]
            raise AssertionError(
                f"verdicts LOST: {len(pending)} doc(s) never finalized "
                f"within {timeout}s: {pending[:5]}"
            )
        time.sleep(0.05)


def assert_exactly_once(server, ids, phase: str):
    from foremast_tpu.jobs.models import TERMINAL_STATUSES

    ledger = server.ledger_snapshot()
    for doc_id in ids:
        terminal = [
            e for e in ledger.get(doc_id, ())
            if e[2] in TERMINAL_STATUSES
        ]
        assert len(terminal) == 1, (
            f"[{phase}] doc {doc_id} has {len(terminal)} terminal "
            f"writes (expected exactly 1): {terminal}"
        )


BREAKER_OPEN_SECONDS = 0.5
# recovery is measured from the moment the system is ALLOWED to probe
# again: the breaker cooldown after a fault clears is designed
# degradation, not recovery work (plus margin for a tick already in
# flight at the boundary)
RECOVERY_GRACE = BREAKER_OPEN_SECONDS + 0.3


def assert_recovery(workers, t_clear: float, t_done: float, phase: str,
                    exclude=()):
    """Recovery bar: ≤ 2 busy ticks per worker between the fault
    clearing (plus the breaker-cooldown grace) and the batch finishing
    (idle polls don't count — the measure is how many passes over the
    work recovery needed)."""
    start = t_clear + RECOVERY_GRACE
    for cw in workers:
        if cw.wid in exclude:
            continue
        busy = sum(
            1 for t0, _, n in cw.tick_log if start < t0 <= t_done and n > 0
        )
        assert busy <= 2, (
            f"[{phase}] {cw.wid} needed {busy} busy ticks after the "
            "fault cleared (bar: ≤ 2)"
        )


def run(small: bool = False) -> list[dict]:
    from foremast_tpu.analysis import witness
    from foremast_tpu.chaos import FaultPlan

    # the witness wraps every package lock created AFTER this point
    # (workers, rings, receivers, buffers all construct below)
    wit = witness.install()

    batch = 9 if small else 24
    hist_len = 64 if small else 256
    cur_len = 16
    hold = 1.2 if small else 2.5

    clock_box = [0.0]
    plan = FaultPlan(
        rules=(
            {"edge": "prometheus", "after": PROM_WINDOW[0],
             "duration": PROM_WINDOW[1] - PROM_WINDOW[0],
             "blackhole": True},
            {"edge": "receiver", "after": RECEIVER_WINDOW[0],
             "duration": RECEIVER_WINDOW[1] - RECEIVER_WINDOW[0],
             "latency_seconds": 0.25},
            {"edge": "clock", "after": SKEW_WINDOW[0],
             "duration": SKEW_WINDOW[1] - SKEW_WINDOW[0],
             "skew_seconds": LEASE_SECONDS / 2.0},
        ),
        seed=1234,
        clock=lambda: clock_box[0],
    ).activate(now=0.0)

    server = StoreServer()
    url = server.start()
    degrade_kw = dict(
        failure_threshold=2, open_seconds=BREAKER_OPEN_SECONDS
    )
    workers = [
        ChaosWorker(f"w{i}", url, plan, degrade_kw) for i in (1, 2, 3)
    ]
    rows: list[dict] = []
    try:
        for cw in workers:
            cw.thread.start()
        # mesh convergence: every router sees 3 members
        deadline = time.monotonic() + 15
        while any(
            len(cw.node.router.members()) < 3 for cw in workers
        ):
            assert time.monotonic() < deadline, "mesh never converged"
            time.sleep(0.05)

        def phase_row(phase, ids, t_clear, t_done, **extra):
            row = {
                "config": "c-chaos-soak",
                "phase": phase,
                "docs": len(ids),
                "recovery_seconds": (
                    round(t_done - t_clear, 3) if t_clear else None
                ),
                **extra,
            }
            rows.append(row)
            print(json.dumps(row), flush=True)

        # -- baseline ---------------------------------------------------
        ids = seed_batch(server, "base", batch, hist_len, cur_len)
        t0 = time.monotonic()
        t_done = wait_all_terminal(server, ids, timeout=120)
        assert_exactly_once(server, ids, "baseline")
        phase_row("baseline", ids, t0, t_done)

        # -- store brownout --------------------------------------------
        server.add_fault(op="update", status=503)  # update + update_many
        ids = seed_batch(server, "brown", batch, hist_len, cur_len)
        time.sleep(hold)  # workers claim + judge + buffer through this

        def brown_buffered() -> int:
            return sum(
                cw.degrade.stats.docs_snapshot().get("write_buffered", 0)
                for cw in workers
            )

        # deflake (1-CPU CI hosts): the first judge pass can outlast the
        # nominal hold, so no write ever LANDS inside the fault window
        # and the mid-write asserts below would test scheduler luck, not
        # the write-behind. Keep the brownout up — bounded — until a
        # worker demonstrably buffered a write; if even the extended
        # window closes dry, record overlap_observed=False and skip the
        # mid-write asserts (exactly-once + recovery still hold).
        extend = time.monotonic() + (30.0 if small else 20.0)
        while brown_buffered() == 0 and time.monotonic() < extend:
            time.sleep(0.1)
        overlap = brown_buffered() > 0
        server.clear_faults()
        t_clear = time.monotonic()
        t_done = wait_all_terminal(server, ids, timeout=60)
        assert_exactly_once(server, ids, "brownout")
        assert_recovery(workers, t_clear, t_done, "brownout")
        buffered = brown_buffered()
        replayed = sum(
            cw.degrade.stats.docs_snapshot().get("write_replayed", 0)
            for cw in workers
        )
        if overlap:
            assert buffered > 0, "brownout never exercised the write-behind"
            assert replayed > 0, "write-behind backlog never replayed"
        phase_row(
            "brownout", ids, t_clear, t_done,
            buffered=buffered, replayed=replayed,
            overlap_observed=overlap,
        )

        # -- prometheus blackhole --------------------------------------
        clock_box[0] = PROM_WINDOW[0] + 1.0
        ids = seed_batch(server, "dark", batch, hist_len, cur_len)
        time.sleep(hold)
        clock_box[0] = PROM_WINDOW[1] + 1.0
        t_clear = time.monotonic()
        t_done = wait_all_terminal(server, ids, timeout=60)
        assert_exactly_once(server, ids, "blackhole")
        assert_recovery(workers, t_clear, t_done, "blackhole")
        released = sum(
            cw.degrade.stats.docs_snapshot().get("fetch_released", 0)
            for cw in workers
        )
        shorts = sum(
            b.short_circuits
            for cw in workers
            for b in cw.degrade.breakers.all().values()
        )
        assert released > 0, "blackhole never released a doc un-judged"
        assert shorts > 0, "no breaker ever short-circuited"
        phase_row(
            "blackhole", ids, t_clear, t_done,
            released=released, breaker_short_circuits=shorts,
        )

        # -- pusher flood ----------------------------------------------
        from foremast_tpu.mesh.routing import RoutingPusher

        clock_box[0] = RECEIVER_WINDOW[0] + 1.0
        seed_addr = workers[0].node.membership.ingest_address
        pushers = [
            RoutingPusher(
                [seed_addr], retries=0, backoff_seconds=0.01,
                buffer_bytes=1 << 20, timeout=5.0,
            )
            for _ in range(4)
        ]
        t_base = int(time.time()) - 600
        series = [
            [
                (
                    'flood_m{app="appF%d-%d"}' % (p, i),
                    [t_base + 60 * k for k in range(4)],
                    [1.0, 2.0, 3.0, 4.0],
                    None,
                )
                for i in range(6)
            ]
            for p in range(4)
        ]
        flood_threads = [
            threading.Thread(
                target=lambda p=p: pushers[p].push_cycle(series[p]),
                daemon=True,
            )
            for p in range(4)
        ]
        for t in flood_threads:
            t.start()
        for t in flood_threads:
            t.join(timeout=30)
        shed = sum(
            cw.degrade.stats.events_snapshot().get(("receiver", "shed"), 0)
            for cw in workers
        )
        buffered_push = sum(p.counters["buffered_series"] for p in pushers)
        assert shed > 0, "the flood never tripped receiver shedding"
        assert buffered_push > 0, "no pusher ever buffered a shed batch"
        clock_box[0] = RECEIVER_WINDOW[1] + 1.0  # flood over
        t_clear = time.monotonic()
        # backlog drains: one healthy cycle per pusher re-sends it
        for p in pushers:
            out = p.push_cycle([])
            assert out["errors"] == 0, out
            assert p.buffered == 0, "pusher backlog failed to drain"
        assert all(p.counters["dropped_series"] == 0 for p in pushers)
        phase_row(
            "flood", [], t_clear, time.monotonic(),
            sheds=shed, buffered_series=buffered_push,
            resent_series=sum(p.counters["resent_series"] for p in pushers),
        )

        # -- clock skew -------------------------------------------------
        rebalances_before = {
            cw.wid: cw.node.router.counters["rebalances"] for cw in workers
        }
        clock_box[0] = SKEW_WINDOW[0] + 1.0
        ids = seed_batch(server, "skew", batch, hist_len, cur_len)
        time.sleep(max(hold, 3 * LEASE_SECONDS / 3.0))  # several renews
        assert all(
            len(cw.node.router.members()) == 3 for cw in workers
        ), "a lease/2-skewed clock falsely killed a healthy member"
        t_clear = time.monotonic()
        t_done = wait_all_terminal(server, ids, timeout=60)
        assert_exactly_once(server, ids, "skew")
        clock_box[0] = SKEW_WINDOW[1] + 1.0
        for cw in workers:
            assert (
                cw.node.router.counters["rebalances"]
                == rebalances_before[cw.wid]
            ), f"skew phase rebalanced the ring on {cw.wid}"
        phase_row("skew", ids, t_clear, t_done, false_deaths=0)

        # -- worker crash -----------------------------------------------
        # arm the wedge FIRST: the victim's next busy tick claims its
        # partition, then hangs forever on the first fetch — claims
        # parked in-progress, no write-back, no renew, no leave (the
        # in-process SIGKILL effect; restart_bench owns the real one)
        victim = workers[2]
        victim.session.wedged.set()
        ids = seed_batch(server, "crash", batch, hist_len, cur_len)
        # wait until the victim's claims of this batch are parked
        deadline = time.monotonic() + 30
        while True:
            parked = [
                i
                for i in ids
                if server.store.get(i).processing_content == "w3"
                and server.store.get(i).status == "preprocess_inprogress"
            ]
            if parked:
                break
            assert time.monotonic() < deadline, (
                "w3 never claimed any crash-batch doc (partition too "
                "small?) — grow the batch"
            )
            time.sleep(0.01)
        victim.stop()  # loop flag only — its tick thread is wedged
        t_wedge = time.monotonic()
        survivors = workers[:2]
        # ring heals on lease expiry
        deadline = time.monotonic() + 30
        while any(
            len(cw.node.router.members()) != 2 for cw in survivors
        ):
            assert time.monotonic() < deadline, "ring never healed"
            time.sleep(0.05)
        t_heal = time.monotonic()
        t_done = wait_all_terminal(server, ids, timeout=60)
        assert_exactly_once(server, ids, "crash")
        # recovery bar: busy survivor ticks after the docs became
        # claimable again (stuck window past the wedge)
        t_claimable = t_wedge + MAX_STUCK_SECONDS
        assert_recovery(
            survivors, max(t_heal, t_claimable), t_done, "crash"
        )
        phase_row(
            "crash", ids, t_heal, t_done,
            parked_at_wedge=len(parked),
            heal_seconds=round(t_heal - t_wedge, 3),
        )

        # -- end-state asserts ------------------------------------------
        for cw in survivors:
            assert not cw.crashed(), f"{cw.wid} tick loop crashed"
            for edge, br in cw.degrade.breakers.all().items():
                assert br.state == "closed", (
                    f"breaker {cw.wid}/{edge} ended {br.state!r} "
                    "(every breaker must re-close)"
                )
            # bounded memory: every buffer inside its cap
            assert len(cw.degrade.write_behind) == 0
            assert len(cw.worker._judged_status) <= 16384
            assert cw.ring.stats()["bytes"] <= 1 << 20
        graph = witness.load_graph()
        assert graph is not None, "analysis_lockgraph.json missing"
        missing = wit.unobserved_edges(graph)
        assert not missing, (
            f"lock witness observed edges missing from the static "
            f"graph (run `make lockgraph`): {missing}"
        )
        summary = {
            "config": "c-chaos-soak",
            "phase": "summary",
            "phases": [r["phase"] for r in rows],
            "workers": 3,
            "docs_per_phase": batch,
            "no_lost_or_duplicated_verdicts": True,
            "breakers_reclosed": True,
            "recovery_within_2_ticks": True,
            "lock_witness_clean": True,
            "memory_bounded": True,
        }
        rows.append(summary)
        print(json.dumps(summary), flush=True)
        return rows
    finally:
        for cw in workers:
            cw.stop()
        for cw in workers:
            if not cw.session.wedged.is_set():
                cw.thread.join(timeout=10)
                cw.worker.close()
            from foremast_tpu.ingest import stop_ingest_server

            stop_ingest_server(cw.receiver, drain_seconds=1.0)
        server.stop()
        witness.uninstall()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--small", action="store_true", help="CPU smoke shapes (CI)"
    )
    args = parser.parse_args(argv)
    from foremast_tpu.device import enable_compile_cache

    enable_compile_cache()
    phases = run(small=args.small)
    from benchmarks.report import write_summary

    write_summary(
        "chaos", {"phases": phases}, small=args.small
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
