"""Elastic mesh bench: 2 → 4 → 2 workers under live load (ISSUE 11).

Every earlier membership change paid a cold refit (round 9's heal wall
was ~82 s of survivors cold-fitting inherited partitions). This bench
PROVES rebalance is now a state TRANSFER: an autoscale-driven fleet of
shipped-stack workers (BrainWorker + MeshNode + HandoffManager + ring
receiver, judging entirely from pushed samples over a real HTTP store)
scales up and back down under continuous load, and the planned moves
cost nothing.

Phases (one JSON row each, plus a summary row):

  load       2 workers under a rolling document load; the autoscale
             driver watches MEASURED tick occupancy + ring pressure and
             must verdict `scale_up` (hysteresis: consecutive breaches)
  scale_up   w3/w4 register FENCED (`joining`) mid-load; the owners
             stream them the moving ring series + fit entries; both
             activate on `done` markers (never the deadline), each
             sender finishing inside ≤ 2 ticks — and the first batch
             the joiners judge costs ZERO cold refits and ZERO fallback
             fetches (the state ARRIVED, nothing reconstructs)
  scale_down idle occupancy drives a `scale_down` verdict; w3/w4 drain
             (state `draining`: stream their partitions to survivors,
             then leave) — the survivors judge the next batch with zero
             cold refits and zero fallback fetches for the partitions
             they inherited
  fault      a chaos-plan window blackholes the peer→peer `transfer`
             edge while w5 joins: every send fails (counted), w5
             activates at its DEADLINE instead of wedging, and its
             partition cold-refits through the fallback path — the
             fleet still converges with exactly-once verdicts

In-run asserts (the bench FAILS, not just reports): one terminal
ledger write per doc per phase (zero lost or duplicated verdicts), no
`completed_unknown` regression anywhere, planned handoff inside 2
ticks, zero cold refits + zero fallback fetches on every PLANNED move,
pusher redirect convergence after each membership change, and the
runtime lock witness clean against the committed static graph.

Usage: python -m benchmarks.elastic_bench [--small]
"""

from __future__ import annotations

import argparse
import json
import threading
import time
import urllib.parse

from benchmarks.chaos_bench import (
    SynthSession,
    assert_exactly_once,
    wait_all_terminal,
)
from benchmarks.scaleout_bench import ALIAS_EXPR, HttpFleetStore, StoreServer

# the lease must comfortably outlive a BUSY tick: renewal happens at
# tick boundaries, so a lease under the tick duration makes a sender
# mid-judgment look dead to a fenced joiner — which then (by design)
# discounts its handoff and cold-refits. docs/operations.md "Elastic
# scaling" carries this sizing rule.
LEASE_SECONDS = 6.0
POLL_SECONDS = 0.05
ROUTER_REFRESH_SECONDS = 0.25
HANDOFF_DEADLINE = 5.0
PUSH_PERIOD = 0.2
OBSERVE_PERIOD = 0.15
OCCUPANCY_WINDOW = 0.6

# the chaos-plan window that blackholes the transfer edge (plan-clock
# seconds; the driver moves the injected clock)
FAULT_WINDOW = (100.0, 200.0)


class CountingSynthSession(SynthSession):
    """The chaos bench's query_range synthesizer, counting every GET —
    the bench's 'fallback fetch' meter. Planned phases must leave it at
    ZERO; the fault phase must move it (cold refit via fallback)."""

    def __init__(self):
        super().__init__()
        self.calls = 0
        self.urls: list[str] = []
        self._lock = threading.Lock()

    def get(self, url, timeout=None):
        with self._lock:
            self.calls += 1
            if len(self.urls) < 32:
                self.urls.append(url)
        return super().get(url, timeout=timeout)


class ElasticWorker:
    """One elastic seat: the shipped stack judging from its ring, with
    the planned-handoff plane mounted on its receiver."""

    def __init__(self, wid: str, store_url: str, plan, fault_edges=True):
        from foremast_tpu.chaos import BreakerRegistry, Degradation
        from foremast_tpu.chaos.degrade import DegradeStats
        from foremast_tpu.config import BrainConfig
        from foremast_tpu.ingest import (
            RingSource,
            RingStore,
            start_ingest_server,
        )
        from foremast_tpu.jobs.worker import BrainWorker
        from foremast_tpu.mesh import (
            HandoffManager,
            Membership,
            MeshNode,
            MeshRouter,
        )
        from foremast_tpu.metrics.source import PrometheusSource

        self.wid = wid
        stats = DegradeStats()
        self.degrade = Degradation(
            stats=stats,
            breakers=BreakerRegistry(
                failure_threshold=2, open_seconds=0.5
            ),
        )
        self.fleet = HttpFleetStore(store_url, wid)
        self.ring = RingStore(
            budget_bytes=8 << 20, shards=2, stale_seconds=3600.0
        )
        self.handoff = HandoffManager(
            ring_store=self.ring,
            deadline_seconds=HANDOFF_DEADLINE,
            retries=1,
            backoff_seconds=0.05,
            timeout=2.0,
            chaos=plan.edge("transfer") if fault_edges else None,
            breaker=self.degrade.breakers.get("transfer"),
        )
        self.session = CountingSynthSession()
        fallback = PrometheusSource(
            session=self.session, retries=0, backoff_seconds=0.01
        )
        fallback.concurrent_fetch = False  # GIL-bound synth fetches
        self.source = RingSource(self.ring, fallback=fallback)
        membership = Membership(
            self.fleet, wid, lease_seconds=LEASE_SECONDS
        )
        router = MeshRouter(
            membership, refresh_seconds=ROUTER_REFRESH_SECONDS
        )
        self.receiver, _ = start_ingest_server(
            0, self.ring, host="127.0.0.1", router=router,
            handoff=self.handoff, degrade_stats=stats,
        )
        membership.ingest_address = (
            "127.0.0.1:%d" % self.receiver.server_address[1]
        )
        self.node = MeshNode(
            membership, router, ring_store=self.ring, handoff=self.handoff
        )
        config = BrainConfig(
            algorithm="moving_average_all",
            max_stuck_seconds=30.0,
            max_cache_size=8192,
        )
        self.worker = BrainWorker(
            self.fleet, self.source, config=config, claim_limit=32,
            worker_id=wid, mesh=self.node, degrade=self.degrade,
        )
        self.tick_log: list[tuple[float, float, int]] = []
        self._stop = threading.Event()
        self.thread = threading.Thread(
            target=self._loop, name=f"elastic-{wid}", daemon=True
        )

    # -- loop -----------------------------------------------------------

    def _loop(self):
        while not self._stop.is_set():
            t0 = time.monotonic()
            try:
                n = self.worker.tick()
            except Exception:  # pragma: no cover — the bench fails below
                import logging

                logging.getLogger("elastic_bench").exception(
                    "worker %s tick crashed", self.wid
                )
                self.tick_log.append((t0, time.monotonic(), -1))
                return
            self.tick_log.append((t0, time.monotonic(), n))
            if n == 0:
                time.sleep(POLL_SECONDS)

    def start(self):
        self.thread.start()

    def stop_loop(self, timeout=30.0):
        self._stop.set()
        self.thread.join(timeout=timeout)
        assert not self.thread.is_alive(), f"{self.wid} tick loop stuck"

    def crashed(self) -> bool:
        return any(n < 0 for _, _, n in self.tick_log)

    # -- signals ---------------------------------------------------------

    def occupancy(self, window: float = OCCUPANCY_WINDOW) -> float:
        """Busy fraction of the trailing window — the bench-side read
        of the tick-occupancy signal the autoscale driver consumes."""
        now = time.monotonic()
        lo = now - window
        busy = 0.0
        for t0, t1, n in reversed(self.tick_log):
            if t1 < lo:
                break
            if n > 0:
                busy += min(t1, now) - max(t0, lo)
        # a tick in flight right now counts as busy from its start
        if self.tick_log:
            pass
        return min(1.0, busy / window)

    def ring_pressure(self) -> float:
        s = self.ring.stats()
        return s["bytes"] / float(8 << 20)

    def busy_ticks_between(self, t0: float, t1: float) -> int:
        return sum(
            1 for a, _, n in self.tick_log if t0 <= a <= t1 and n > 0
        )

    def cold_reads(self) -> dict:
        return self.worker._cold_snapshot()

    def close(self):
        from foremast_tpu.ingest import stop_ingest_server

        self.worker.close()
        stop_ingest_server(self.receiver, drain_seconds=1.0)


# ---------------------------------------------------------------------------
# load + push plumbing
# ---------------------------------------------------------------------------


def seed_batch(server, phase: str, apps, hist_len, cur_len, anchor):
    """One finalize-on-first-judgment doc per app, windows ANCHORED so
    every phase reuses the same fit-cache keys (the warm state planned
    handoff moves). Returns the doc ids."""
    from foremast_tpu.jobs.models import Document

    cur_t1 = anchor - 60
    cur_t0 = cur_t1 - 60 * (cur_len - 1)
    hist_t1 = cur_t0 - 120
    hist_t0 = hist_t1 - 60 * (hist_len - 1)
    end_time = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(anchor - 30)
    )
    ids = []
    for app in apps:
        sid = app[3:]
        expr = urllib.parse.quote(ALIAS_EXPR.format(a=0, sid=sid), safe="")
        doc_id = f"job-{phase}-{sid}"
        server.store.create(
            _doc(
                Document, doc_id, app, end_time,
                f"m0== http://synth/api/v1/query_range?query={expr}"
                f"&start={cur_t0}&end={cur_t1}&step=60",
                f"m0== http://synth/api/v1/query_range?query={expr}"
                f"&start={hist_t0}&end={hist_t1}&step=60",
            )
        )
        ids.append(doc_id)
    return ids


def _doc(Document, doc_id, app, end_time, cur, hist):
    return Document(
        id=doc_id, app_name=app, end_time=end_time,
        current_config=cur, historical_config=hist,
        strategy="continuous",
    )


class ContinuousPusher:
    """The live push load: every cycle re-pushes each app's CURRENT
    window through a RoutingPusher (full history goes once, up front) —
    so a joining member's ring is receiving live samples the moment the
    receivers hint the pusher at it, exactly like production."""

    def __init__(self, seed_addr, apps, hist_len, cur_len, anchor):
        import numpy as np

        from foremast_tpu.mesh import RoutingPusher

        self.pusher = RoutingPusher(
            [seed_addr], retries=1, backoff_seconds=0.05,
            timeout=5.0, buffer_bytes=8 << 20,
        )
        self.anchor = anchor
        cur_t1 = anchor - 60
        self.cur_t0 = cur_t1 - 60 * (cur_len - 1)
        hist_t1 = self.cur_t0 - 120
        self.hist_t0 = hist_t1 - 60 * (hist_len - 1)
        self._np = np
        self.apps = apps
        self.cycles: list[dict] = []  # (redirects, errors) per cycle
        self._stop = threading.Event()
        self.thread = threading.Thread(
            target=self._loop, name="elastic-pusher", daemon=True
        )

    def _series(self, t0, t1, start):
        from benchmarks.scaleout_bench import synth_values

        np = self._np
        out = []
        for app in self.apps:
            sid = app[3:]
            key = ALIAS_EXPR.format(a=0, sid=sid)
            ts = np.arange(int(t0), int(t1) + 1, 60, np.int64)
            out.append(
                (key, ts.tolist(), synth_values(key, ts).tolist(),
                 float(start))
            )
        return out

    def backfill(self, cycles=4):
        """Full-span push (history + current), repeated until the
        redirect hints converge — every series resident on its owner."""
        series = self._series(
            self.hist_t0, self.anchor - 60, self.hist_t0 - 600
        )
        for i in range(cycles):
            out = self.pusher.push_cycle(series)
            if i > 0 and out["redirects"] == 0 and out["errors"] == 0:
                return out
        raise AssertionError(
            f"pusher never converged during backfill: {out}"
        )

    def _loop(self):
        series = self._series(self.cur_t0, self.anchor - 60, self.cur_t0)
        while not self._stop.is_set():
            out = self.pusher.push_cycle(series)
            self.cycles.append(
                {"redirects": out["redirects"], "errors": out["errors"]}
            )
            self._stop.wait(PUSH_PERIOD)

    def start(self):
        self.thread.start()

    def stop(self):
        self._stop.set()
        self.thread.join(timeout=10)

    def cycles_since(self, idx: int) -> list[dict]:
        return self.cycles[idx:]


def assert_no_unknown(server, ids, phase):
    from foremast_tpu.jobs.models import STATUS_COMPLETED_UNKNOWN

    unknown = [
        i for i in ids
        if server.store.get(i).status == STATUS_COMPLETED_UNKNOWN
    ]
    assert not unknown, (
        f"[{phase}] UNKNOWN regression: {len(unknown)} doc(s) "
        f"completed_unknown: {unknown[:5]}"
    )


def assert_redirects_converged(pusher, mark, phase, settle=3,
                               timeout=10.0):
    """After a membership change, hint traffic must settle: within
    `timeout` the pusher runs `settle` consecutive hint-free cycles
    (each member hints its moved series the first time it sees them
    post-change; ONE learning cycle per hint wave, then quiet)."""
    deadline = time.monotonic() + timeout
    while True:
        cycles = pusher.cycles_since(mark)
        tail = cycles[-settle:]
        if len(tail) == settle and all(
            c["redirects"] == 0 for c in tail
        ):
            return
        assert time.monotonic() < deadline, (
            f"[{phase}] pusher never settled after the membership "
            f"change: {cycles}"
        )
        time.sleep(PUSH_PERIOD)


def _cold_delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


def run(small: bool = False) -> list[dict]:
    from foremast_tpu.analysis import witness
    from foremast_tpu.chaos import FaultPlan
    from foremast_tpu.mesh import AutoscaleConfig, AutoscaleDriver

    wit = witness.install()

    apps_n = 24 if small else 64
    hist_len = 48 if small else 192
    cur_len = 8 if small else 16
    max_load_batches = 12
    apps = [f"app{i}" for i in range(apps_n)]
    anchor = int(time.time())

    clock_box = [0.0]
    plan = FaultPlan(
        rules=(
            {"edge": "transfer", "after": FAULT_WINDOW[0],
             "duration": FAULT_WINDOW[1] - FAULT_WINDOW[0],
             "error_rate": 1.0, "kind": "timeout"},
        ),
        seed=4242,
        clock=lambda: clock_box[0],
    ).activate(now=0.0)

    server = StoreServer()
    url = server.start()
    rows: list[dict] = []
    workers: dict[str, ElasticWorker] = {}

    def phase_row(phase, **extra):
        row = {"config": "c-elastic", "phase": phase, **extra}
        rows.append(row)
        print(json.dumps(row), flush=True)

    def actives():
        return [
            w for w in workers.values()
            if w.node.state == "active" and not w._stop.is_set()
        ]

    def total_fallback():
        return sum(w.session.calls for w in workers.values())

    pusher = None
    try:
        # -- boot: 2 active workers, rings warm ------------------------
        for wid in ("w1", "w2"):
            workers[wid] = ElasticWorker(wid, url, plan)
            workers[wid].start()
        deadline = time.monotonic() + 20
        while any(
            len(w.node.router.members()) < 2
            or w.node.state != "active"
            for w in workers.values()
        ):
            assert time.monotonic() < deadline, "mesh never converged"
            time.sleep(0.05)
        pusher = ContinuousPusher(
            workers["w1"].node.membership.ingest_address,
            apps, hist_len, cur_len, anchor,
        )
        pusher.backfill()
        pusher.start()

        # -- phase: load → autoscale verdict ---------------------------
        driver = AutoscaleDriver(
            AutoscaleConfig(
                high_occupancy=0.5, low_occupancy=0.2,
                high_ring_pressure=0.95, high_write_queue=1 << 30,
                breach_ticks=3, cooldown_seconds=2.0,
                min_workers=2, max_workers=4,
            )
        )

        def observe_until(want, deadline_s, label):
            deadline = time.monotonic() + deadline_s
            while time.monotonic() < deadline:
                occ = max(w.occupancy() for w in actives())
                pressure = max(w.ring_pressure() for w in actives())
                verdict = driver.observe(
                    occ, len(actives()), ring_pressure=pressure
                )
                if verdict == want:
                    return True
                time.sleep(OBSERVE_PERIOD)
            raise AssertionError(
                f"autoscale driver never verdicted {want!r} during "
                f"{label}: {driver.debug_state()}"
            )

        t0 = time.monotonic()
        fired = threading.Event()
        verdict_thread = threading.Thread(
            target=lambda: (
                observe_until("scale_up", 60.0, "load"), fired.set()
            ),
            daemon=True,
        )
        verdict_thread.start()
        batches = 0
        while not fired.is_set():
            assert batches < max_load_batches, (
                "autoscale never fired scale_up under sustained load: "
                f"{driver.debug_state()}"
            )
            ids = seed_batch(
                server, f"load{batches}", apps, hist_len, cur_len, anchor
            )
            wait_all_terminal(server, ids, timeout=120)
            assert_exactly_once(server, ids, f"load{batches}")
            assert_no_unknown(server, ids, f"load{batches}")
            batches += 1
        verdict_thread.join(timeout=70)
        assert total_fallback() == 0, (
            "the warm 2-worker fleet fell back to HTTP "
            f"({total_fallback()} fetches) — the ring should serve "
            "everything"
        )
        cold0 = {w: workers[w].cold_reads() for w in ("w1", "w2")}
        assert all(c["http"] == 0 for c in cold0.values()), cold0
        phase_row(
            "load", workers=2, batches=batches, docs_per_batch=apps_n,
            occupancy=driver.last_signals["occupancy"],
            scale_up_after_seconds=round(time.monotonic() - t0, 3),
            cold_reads=cold0,
        )

        # -- phase: scale up 2 → 4 under in-flight load ----------------
        inflight = seed_batch(
            server, "up-inflight", apps, hist_len, cur_len, anchor
        )
        cycle_mark = len(pusher.cycles)
        t_join = time.monotonic()
        join_windows = {}
        # sequential joins (the autoscaler's one-verdict-one-worker
        # cadence): each joiner fences against a SETTLED target ring,
        # so its streamed share is exactly the share it activates with.
        # (Simultaneous joiners re-stream on the membership move —
        # pinned by test_simultaneous_joiners_restream_on_target_change
        # — but sequential is the operational recommendation.)
        for wid in ("w3", "w4"):
            t_w = time.monotonic()
            workers[wid] = ElasticWorker(wid, url, plan)
            workers[wid].start()
            # a joiner's `state` reads "active" until its first tick
            # fences it, so "joined" = the handoff recorded a completed
            # wait AND the state settled active
            deadline = time.monotonic() + 30
            while (
                workers[wid].handoff.join_wait_seconds is None
                or workers[wid].node.state != "active"
            ):
                assert time.monotonic() < deadline, (
                    f"{wid} never activated: "
                    + str(workers[wid].handoff.debug_state())
                )
                time.sleep(0.05)
            join_windows[wid] = (t_w, time.monotonic())
        t_active = time.monotonic()
        join_seconds = t_active - t_join
        # activation came from DONE markers, not the deadline (w4 joins
        # a 3-member fleet, so w3 is one of its senders)
        expected_senders = {"w3": ["w1", "w2"], "w4": ["w1", "w2", "w3"]}
        for wid in ("w3", "w4"):
            h = workers[wid].handoff.debug_state()
            assert sorted(h["done_from"]) == expected_senders[wid], (
                f"{wid} activated without every sender's done marker: {h}"
            )
            assert h["join_wait_seconds"] < HANDOFF_DEADLINE, h
        # each sender delivered inside the 2-tick bar, per join
        for jid, (w0, w1_) in join_windows.items():
            for wid in expected_senders[jid]:
                busy = workers[wid].busy_ticks_between(w0, w1_)
                assert busy <= 2, (
                    f"handoff to {jid} took {wid} {busy} busy ticks "
                    "(bar: ≤ 2)"
                )
        sent = {
            w: workers[w].handoff.counters_snapshot() for w in ("w1", "w2")
        }
        moved_series = sum(c["series_sent"] for c in sent.values())
        moved_fits = sum(c["fits_sent"] for c in sent.values())
        assert moved_series > 0 and moved_fits > 0, sent
        assert all(
            c["send"]["failed"] == 0 and c["send"]["rejected"] == 0
            for c in sent.values()
        ), sent
        wait_all_terminal(server, inflight, timeout=120)
        assert_exactly_once(server, inflight, "up-inflight")
        assert_no_unknown(server, inflight, "up-inflight")
        # the first post-activation batch: the joiners judge their
        # partition WARM — zero cold refits, zero fallback fetches
        cold_before = {w: workers[w].cold_reads() for w in ("w3", "w4")}
        ids = seed_batch(server, "up-warm", apps, hist_len, cur_len, anchor)
        wait_all_terminal(server, ids, timeout=120)
        assert_exactly_once(server, ids, "up-warm")
        assert_no_unknown(server, ids, "up-warm")
        ledger = server.ledger_snapshot()
        joiner_writes = sum(
            1
            for i in ids
            for e in ledger.get(i, ())
            if e[0] in ("w3", "w4")
        )
        assert joiner_writes > 0, (
            "the joiners judged nothing post-activation — partition "
            "never moved"
        )
        cold_delta = {
            w: _cold_delta(cold_before[w], workers[w].cold_reads())
            for w in ("w3", "w4")
        }
        for wid, delta in cold_delta.items():
            assert all(v == 0 for v in delta.values()), (
                f"{wid} paid {delta} cold refits on a PLANNED move — "
                "the transferred state should have made it warm"
            )
        for wid in ("w3", "w4"):
            assert workers[wid].session.calls == 0, (
                f"{wid} fell back to HTTP: "
                f"{workers[wid].session.urls}"
            )
        assert_redirects_converged(pusher, cycle_mark, "scale_up")
        phase_row(
            "scale_up", workers=4,
            join_seconds=round(join_seconds, 3),
            moved_series=moved_series, moved_fits=moved_fits,
            joiner_docs=joiner_writes,
            joiner_cold_refits=0, joiner_fallback_fetches=0,
        )

        # -- phase: scale down 4 → 2 (autoscale + drain) ---------------
        observe_until("scale_down", 30.0, "idle fleet")
        cycle_mark = len(pusher.cycles)
        recv_before = {
            w: workers[w].handoff.counters_snapshot() for w in ("w1", "w2")
        }
        cold_before = {w: workers[w].cold_reads() for w in ("w1", "w2")}
        t_drain = time.monotonic()
        for wid in ("w3", "w4"):
            w = workers[wid]
            w.stop_loop()
            out = w.node.drain()
            assert all(r == "ok" for r in out["targets"].values()), (
                f"{wid} drain transfers failed: {out}"
            )
        deadline = time.monotonic() + 20
        while any(
            len(workers[w].node.router.members()) != 2
            for w in ("w1", "w2")
        ):
            assert time.monotonic() < deadline, "drain never healed"
            time.sleep(0.05)
        drain_seconds = time.monotonic() - t_drain
        received = {
            w: _cold_delta(
                {
                    "series": recv_before[w]["series_received"],
                    "fits": recv_before[w]["fits_received"],
                },
                {
                    "series": workers[w].handoff.counters_snapshot()[
                        "series_received"
                    ],
                    "fits": workers[w].handoff.counters_snapshot()[
                        "fits_received"
                    ],
                },
            )
            for w in ("w1", "w2")
        }
        assert sum(r["series"] for r in received.values()) > 0, received
        assert sum(r["fits"] for r in received.values()) > 0, received
        ids = seed_batch(server, "down", apps, hist_len, cur_len, anchor)
        wait_all_terminal(server, ids, timeout=120)
        assert_exactly_once(server, ids, "down")
        assert_no_unknown(server, ids, "down")
        cold_delta = {
            w: _cold_delta(cold_before[w], workers[w].cold_reads())
            for w in ("w1", "w2")
        }
        for wid, delta in cold_delta.items():
            assert all(v == 0 for v in delta.values()), (
                f"{wid} paid {delta} cold refits inheriting a DRAINED "
                "partition — the state should have moved with it"
            )
        assert total_fallback() == 0, (
            f"planned phases cost {total_fallback()} fallback fetches"
        )
        assert_redirects_converged(pusher, cycle_mark, "scale_down")
        phase_row(
            "scale_down", workers=2,
            drain_seconds=round(drain_seconds, 3),
            inherited=received,
            survivor_cold_refits=0, survivor_fallback_fetches=0,
        )

        # -- phase: blackholed transfer degrades, never wedges ---------
        clock_box[0] = FAULT_WINDOW[0] + 1.0
        t_fault_join = time.monotonic()
        workers["w5"] = ElasticWorker("w5", url, plan)
        workers["w5"].start()
        deadline = time.monotonic() + 30
        while (
            workers["w5"].handoff.join_wait_seconds is None
            or workers["w5"].node.state != "active"
        ):
            assert time.monotonic() < deadline, (
                "w5 wedged behind a blackholed transfer: "
                + str(workers["w5"].handoff.debug_state())
            )
            time.sleep(0.05)
        h5 = workers["w5"].handoff.debug_state()
        assert h5["done_from"] == [], (
            f"a blackholed transfer still delivered done markers: {h5}"
        )
        assert h5["join_wait_seconds"] >= HANDOFF_DEADLINE * 0.9, h5
        failed_sends = sum(
            workers[w].handoff.counters_snapshot()["send"]["failed"]
            for w in ("w1", "w2")
        )
        assert failed_sends >= 1, "the fault window injected nothing"
        assert (
            plan.injections_snapshot().get(("transfer", "timeout"), 0) >= 1
        )
        cold_before5 = workers["w5"].cold_reads()
        ids = seed_batch(server, "fault", apps, hist_len, cur_len, anchor)
        wait_all_terminal(server, ids, timeout=120)
        assert_exactly_once(server, ids, "fault")
        # w5 COLD-REFIT its partition (fallback history fetches: its
        # ring never received the blackholed transfer) — the designed
        # degradation, and the fleet still converged exactly-once
        delta5 = _cold_delta(cold_before5, workers["w5"].cold_reads())
        refits5 = sum(delta5.values())
        assert refits5 > 0, (
            "w5 judged its partition with no cold refits despite the "
            f"blackholed transfer: {delta5}"
        )
        assert workers["w5"].session.calls > 0, (
            "w5's cold refits never touched the fallback — where did "
            "its history come from?"
        )
        clock_box[0] = FAULT_WINDOW[1] + 1.0
        phase_row(
            "fault", workers=3,
            join_wait_seconds=round(h5["join_wait_seconds"], 3),
            failed_sends=failed_sends,
            w5_cold_refits=refits5,
            w5_fallback_fetches=workers["w5"].session.calls,
        )

        # -- end state --------------------------------------------------
        for w in workers.values():
            assert not w.crashed(), f"{w.wid} tick loop crashed"
        graph = witness.load_graph()
        assert graph is not None, "analysis_lockgraph.json missing"
        missing = wit.unobserved_edges(graph)
        assert not missing, (
            "lock witness observed edges missing from the static "
            f"graph (run `make lockgraph`): {missing}"
        )
        summary = {
            "config": "c-elastic",
            "phase": "summary",
            "phases": [r["phase"] for r in rows],
            "apps": apps_n,
            "no_lost_or_duplicated_verdicts": True,
            "no_unknown_regression": True,
            "planned_moves_zero_cold_refits": True,
            "planned_moves_zero_fallback_fetches": True,
            "handoff_within_2_ticks": True,
            "fault_degraded_to_cold_refit": True,
            "lock_witness_clean": True,
        }
        rows.append(summary)
        print(json.dumps(summary), flush=True)
        return rows
    finally:
        if pusher is not None:
            pusher.stop()
        for w in workers.values():
            if not w._stop.is_set():
                w._stop.set()
                w.thread.join(timeout=10)
            try:
                w.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
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
        "elastic", {"phases": phases}, small=args.small
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
