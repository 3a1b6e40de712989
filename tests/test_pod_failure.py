"""Pod-mode failure semantics (VERDICT r5 #6): the LEADER dies mid-tick.

Pod mode concentrates the reference's worker-death risk: one logical
worker spans every process, the leader alone talks to ES/Prometheus,
and every fetch is a broadcast collective. This test kills the leader
process mid-tick — AFTER the claim is persisted (documents sit in
`preprocess_inprogress` on the real store) but BEFORE any verdict — and
asserts the two halves of the recovery story documented in
docs/operations.md:

  1. FOLLOWERS FAIL FAST: the surviving process's next collective
     errors out and the process EXITS (nonzero) within the test budget —
     no silent hang waiting on a dead coordinator.
  2. NOTHING IS LOST OR DOUBLE-SCORED: the in-flight claims age out
     after MAX_STUCK_IN_SECONDS and a restarted worker takes them over
     via the store's CAS claim (the reference's work-stealing,
     design.md:39); every document lands exactly one verdict, identical
     to a single-process run of the same fleet.

The store is the parent's fake-ES cluster behind a real HTTP socket, so
it survives the pod like production ES would.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NOW = 1_760_000_000.0
SERVICES = 4
HIST_LEN = 64
CUR_LEN = 16


@pytest.fixture(scope="module", autouse=True)
def lock_witness():
    """ISSUE 8: the runtime lock witness rides this module — the mesh
    crash/restart tests drive the InMemory claim path through the mesh
    partition filter (store lock -> router lock) and the restart tests
    replay the snapshot plane, all on real threads. At teardown every
    OBSERVED acquisition edge must exist in the committed static lock
    graph (the subprocess workers are outside this process's witness;
    their lock topology is the same code the in-process tests cover)."""
    from foremast_tpu.analysis import witness

    wit = witness.install()
    yield wit
    graph = witness.load_graph()
    witness.uninstall()
    assert graph is not None, "analysis_lockgraph.json missing from repo root"
    missing = wit.unobserved_edges(graph)
    assert not missing, (
        "runtime lock-acquisition edges missing from the static graph "
        f"(run `make lockgraph` and review): {missing}"
    )


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _serve_fake_es():
    from test_multihost_worker import _serve_fake_es as serve

    return serve()


_CHILD = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
# tight collective watchdog: the follower must abandon a dead leader's
# broadcast well inside the test's 180 s hang budget (60 s, not the
# production 300 s default — but wide enough for process-startup skew
# on a loaded CI host, where one interpreter can trail the other by
# tens of seconds before the first collective)
os.environ["FOREMAST_POD_TIMEOUT_SECONDS"] = "60"
import jax
jax.config.update("jax_platforms", "cpu")
addr, pid, es_url = sys.argv[1], int(sys.argv[2]), sys.argv[3]
jax.distributed.initialize(addr, 2, pid)

sys.path.insert(0, {repo!r})
from benchmarks.worker_bench import build_fleet
from foremast_tpu.config import BrainConfig
from foremast_tpu.jobs.store import ElasticsearchStore
from foremast_tpu.parallel import LeaderSource, LeaderStore, PodWorker

NOW = {now!r}
leader = pid == 0
if leader:
    _, source_in = build_fleet({services}, {hist_len}, {cur_len}, NOW)

    class DyingSource:
        # the real source, but the LEADER PROCESS DIES on the 3rd fetch
        # of the tick — after the claim was persisted to ES, before any
        # verdict. os._exit: a crash, not an exception (no cleanup, no
        # broadcast of an error object — the pod's worst case).
        concurrent_fetch = False
        def __init__(self, inner):
            self.inner = inner
            self.calls = 0
        def fetch(self, url):
            self.calls += 1
            if self.calls >= 3:
                os._exit(17)
            return self.inner.fetch(url)

    store_in = ElasticsearchStore(es_url)
    source = LeaderSource(DyingSource(source_in))
else:
    store_in = None
    source = LeaderSource(None)
store = LeaderStore(store_in)
cfg = BrainConfig(algorithm="moving_average_all", max_stuck_seconds=90.0)
worker = PodWorker(
    store, source, config=cfg, claim_limit={services},
    worker_id=f"pod-{{pid}}",
)
print(f"proc {{pid}} ticking", flush=True)
worker.tick(now=NOW + 150)  # leader dies inside; follower must ERROR
print(f"proc {{pid}} SURVIVED", flush=True)  # only reachable on a bug
"""


def test_leader_death_mid_tick_fails_fast_and_recovers(tmp_path):
    from benchmarks.worker_bench import build_fleet
    from foremast_tpu.config import BrainConfig
    from foremast_tpu.jobs.models import (
        STATUS_PREPROCESS_INPROGRESS,
        TERMINAL_STATUSES,
    )
    from foremast_tpu.jobs.store import ElasticsearchStore
    from foremast_tpu.jobs.worker import BrainWorker

    srv, fake = _serve_fake_es()
    try:
        url = f"http://127.0.0.1:{srv.server_address[1]}"
        parent_store = ElasticsearchStore(url)
        parent_store.ensure_index()
        fleet_store, _ = build_fleet(SERVICES, HIST_LEN, CUR_LEN, NOW)
        for doc in fleet_store._docs.values():
            parent_store.create(doc)

        child = tmp_path / "pod_child.py"
        child.write_text(
            _CHILD.format(
                repo=REPO,
                now=NOW,
                services=SERVICES,
                hist_len=HIST_LEN,
                cur_len=CUR_LEN,
            )
        )
        addr = f"127.0.0.1:{_free_port()}"
        env = {
            k: v for k, v in os.environ.items() if not k.startswith("JAX_")
        }
        t0 = time.monotonic()
        procs = [
            subprocess.Popen(
                [sys.executable, str(child), addr, str(pid), url],
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
                env=env,
            )
            for pid in (0, 1)
        ]
        outs = []
        try:
            for p in procs:
                out, _ = p.communicate(timeout=180)
                outs.append(out)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        elapsed = time.monotonic() - t0

        # the leader crashed with its marker code; the follower FAILED
        # FAST — nonzero exit, no hang (the 180 s communicate timeout is
        # the hang detector), and it never completed the tick
        assert procs[0].returncode == 17, outs[0]
        assert procs[1].returncode not in (0, None), outs[1]
        assert "SURVIVED" not in outs[1], outs[1]
        assert elapsed < 175, f"follower hung for {elapsed:.0f}s"

        # the claim is parked on the store: in-progress, owned by the
        # dead pod — exactly what MAX_STUCK_IN_SECONDS exists for
        stuck = [
            d["_source"]
            for d in fake.docs.values()
            if d["_source"]["status"] == STATUS_PREPROCESS_INPROGRESS
        ]
        assert stuck, "leader died before persisting any claim"

        # restarted pod (single process suffices — the store contract is
        # identical): past the stuck window, CAS takeover re-claims and
        # every document lands exactly one verdict. The stuck clock is
        # the store's WALL clock (modified_at), so the test shrinks
        # MAX_STUCK_IN_SECONDS instead of sleeping the production 90 s.
        _, source = build_fleet(SERVICES, HIST_LEN, CUR_LEN, NOW)
        takeover = BrainWorker(
            ElasticsearchStore(url),
            source,
            config=BrainConfig(
                algorithm="moving_average_all", max_stuck_seconds=2.0
            ),
            claim_limit=SERVICES,
            worker_id="takeover",
        )
        # age the dead pod's claims past the window, then tick until the
        # takeover lands (modified_at has second granularity and the
        # claim clock is wall time, so a fixed sleep is load-flaky);
        # `now` past endTime so every doc finalizes on this judgment
        total = 0
        deadline = time.monotonic() + 60
        while total < SERVICES and time.monotonic() < deadline:
            time.sleep(1.0)
            total += takeover.tick(now=NOW + 7200)
        assert total == SERVICES, f"takeover claimed {total} != {SERVICES}"

        # no lost docs, no duplicates: every document terminal, judged
        # by the takeover worker, matching the single-process reference
        ref_store, ref_source = build_fleet(SERVICES, HIST_LEN, CUR_LEN, NOW)
        ref = BrainWorker(
            ref_store,
            ref_source,
            config=BrainConfig(algorithm="moving_average_all"),
            claim_limit=SERVICES,
            worker_id="ref",
        )
        assert ref.tick(now=NOW + 7200) == SERVICES
        want = {
            d.id: (d.status, json.dumps(d.anomaly_info, sort_keys=True))
            for d in ref_store._docs.values()
        }
        assert len(fake.docs) == SERVICES
        for doc_id, (status, anom) in want.items():
            rec = fake.docs[doc_id]["_source"]
            assert rec["status"] == status, (doc_id, rec["status"], status)
            assert rec["status"] in TERMINAL_STATUSES
            assert rec["processingContent"] == "takeover"
        # a second tick finds nothing claimable: no verdict re-issued
        assert takeover.tick(now=NOW + 7300) == 0
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# ISSUE 6: a 3-worker MESH loses one worker mid-tick
# ---------------------------------------------------------------------------


class _Die(BaseException):
    """Raised from the victim's source mid-tick: a BaseException, so no
    worker-level Exception handler can soften the crash — the claim is
    persisted, no verdict is written, exactly the pod test's worst
    case at mesh scale."""


def test_mesh_worker_death_rebalances_within_two_ticks():
    """Three mesh workers share one store and partition a 12-service
    fleet by consistent hash. Worker w2 dies mid-tick (after its claim
    persisted, before any verdict). Asserts:

      1. the steady state judges every document exactly once per round,
         each by its one owner;
      2. after w2's lease expires, the ring heals and the SURVIVORS
         re-judge every orphaned document within 2 ticks — exactly
         once, via the existing stuck-claim takeover;
      3. ownership converges: each orphan's new judge is the healed
         ring's owner for it.

    Clocks are injected (membership leases never sleep); only the
    stuck-claim aging crosses a real ~1 s wall-clock second, because
    the store stamps modified_at with wall time."""
    from benchmarks.worker_bench import build_fleet
    from foremast_tpu.config import BrainConfig
    from foremast_tpu.jobs.models import STATUS_PREPROCESS_INPROGRESS
    from foremast_tpu.jobs.worker import BrainWorker
    from foremast_tpu.mesh import MESH_APP, Membership, MeshNode, MeshRouter

    SERVICES_M = 12
    store, source = build_fleet(SERVICES_M, HIST_LEN, CUR_LEN, NOW)

    clock = [1000.0]
    judged: list[tuple[str, str]] = []  # (doc_id, worker) per judgment

    orig_update, orig_many = store.update, store.update_many

    def _rec(doc, worker):
        # membership heartbeats ride the same store — not judgments
        if doc.app_name == MESH_APP:
            return
        if doc.status != STATUS_PREPROCESS_INPROGRESS:
            judged.append((doc.id, worker))

    class _DyingSource:
        concurrent_fetch = False

        def __init__(self, inner):
            self.inner = inner
            self.armed = False
            self.calls = 0

        def fetch(self, url):
            if self.armed:
                self.calls += 1
                if self.calls >= 3:
                    raise _Die()
            return self.inner.fetch(url)

    workers = {}
    nodes = {}
    dying = None
    for wid in ("w0", "w1", "w2"):
        mem = Membership(
            store, wid, lease_seconds=10.0, clock=lambda: clock[0]
        )
        router = MeshRouter(
            mem, refresh_seconds=0.0, clock=lambda: clock[0]
        )
        node = MeshNode(mem, router, clock=lambda: clock[0])
        node.start()
        nodes[wid] = node
        src = source
        if wid == "w2":
            dying = _DyingSource(source)
            src = dying
        w = BrainWorker(
            store,
            src,
            config=BrainConfig(
                algorithm="moving_average_all", max_stuck_seconds=0.0
            ),
            claim_limit=SERVICES_M,
            worker_id=wid,
            mesh=node,
        )
        workers[wid] = w
    for node in nodes.values():
        node.router.refresh(force=True)  # everyone sees all three

    current_worker = [""]

    def _u(doc):
        _rec(doc, current_worker[0])
        return orig_update(doc)

    def _um(docs):
        for d in docs:
            _rec(d, current_worker[0])
        return orig_many(docs)

    store.update, store.update_many = _u, _um

    def tick_all(now, who=("w0", "w1", "w2")):
        total = 0
        for wid in who:
            current_worker[0] = wid
            total += workers[wid].tick(now=now)
        return total

    # round 1 (cold) + round 2 (warm): every doc judged exactly once per
    # round, partitions disjoint and total
    assert tick_all(NOW + 150) == SERVICES_M
    owner_of = {
        doc_id: wid
        for doc_id, wid in judged
    }
    assert len(owner_of) == SERVICES_M
    assert len(judged) == SERVICES_M  # nothing judged twice
    judged.clear()
    clock[0] += 4.0
    assert tick_all(NOW + 160) == SERVICES_M
    assert {d: w for d, w in judged} == owner_of  # stable ownership
    assert len(judged) == SERVICES_M
    orphans = {d for d, w in owner_of.items() if w == "w2"}
    assert orphans, "w2 owned nothing — hash ring degenerate?"
    judged.clear()

    # round 3: w2 dies MID-TICK — claim persisted, then the source
    # blows up before any write-back; w0/w1 finish their ticks clean
    clock[0] += 4.0
    assert tick_all(NOW + 170, who=("w0", "w1")) == SERVICES_M - len(orphans)
    dying.armed = True
    current_worker[0] = "w2"
    import pytest as _pytest

    with _pytest.raises(_Die):
        workers["w2"].tick(now=NOW + 170)
    parked = {
        d.id
        for d in store._docs.values()
        if d.status == STATUS_PREPROCESS_INPROGRESS
    }
    assert parked == orphans  # the whole partition is stuck in-progress
    judged.clear()

    # w2's lease expires (fake clock); the store's stuck window is
    # max_stuck_seconds=0 but modified_at has 1 s granularity — cross it.
    # The survivors renew first: a live worker heartbeats every lease/3,
    # so the artificial clock jump must not expire THEIR leases too.
    clock[0] += 11.0
    nodes["w0"].membership.renew(force=True)
    nodes["w1"].membership.renew(force=True)
    time.sleep(1.1)

    # rounds 4..5: survivors only. The ≤2-tick bar: every orphan judged
    # (exactly once, by a survivor) within two survivor rounds.
    ticks_needed = 0
    for _ in range(2):
        ticks_needed += 1
        tick_all(NOW + 180, who=("w0", "w1"))
        if {d for d, _ in judged} >= orphans:
            break
        time.sleep(1.1)  # stuck-stamp granularity between rounds
    post = {}
    for d, w in judged:
        assert d not in post or post[d] == w, f"{d} judged twice"
        post.setdefault(d, w)
    assert {d for d in post} == set(owner_of)  # every doc judged again
    assert ticks_needed <= 2
    counts = {}
    for d, _w in judged:
        counts[d] = counts.get(d, 0) + 1
    assert all(n == 1 for n in counts.values()), counts

    # ownership converged onto the healed ring: each orphan's judge is
    # the ring's post-death owner, and w2 judged nothing
    for d in orphans:
        assert post[d] in ("w0", "w1")
        doc = store._docs[d]
        assert post[d] == nodes["w0"].router.owner_of_doc(doc)
    store.update, store.update_many = orig_update, orig_many


# ---------------------------------------------------------------------------
# ISSUE 7: crash-injection harness — kill a DURABLE worker mid-tick,
# restart it, and prove the restart is warm (≥ 90% fast-path, ZERO
# fallback fetches, no lost or duplicated verdicts). The `make
# bench-restart` harness does the same with a real SIGKILLed
# subprocess; these pin the contract in tier-1.
# ---------------------------------------------------------------------------


class _CountingSource:
    """Wraps the would-be pull path (Prometheus in production) and
    counts every fetch that reaches it — the "zero fallback HTTP
    fetches" meter."""

    concurrent_fetch = False

    def __init__(self, inner):
        self.inner = inner
        self.calls = 0

    def fetch(self, url):
        self.calls += 1
        return self.inner.fetch(url)


class _DyingRing:
    """Wraps a RingSource; once armed, the worker's Nth fetch raises a
    BaseException — mid-tick, AFTER the claim persisted, BEFORE any
    verdict (worker-level Exception handlers must not soften it, same
    shape as the mesh kill test). The files on disk are whatever the
    journals flushed: exactly the SIGKILL situation."""

    concurrent_fetch = False

    def __init__(self, inner, die_at=3):
        self.inner = inner
        self.armed = False
        self.calls = 0
        self.die_at = die_at

    def fetch(self, url):
        if self.armed:
            self.calls += 1
            if self.calls >= self.die_at:
                raise _Die()
        return self.inner.fetch(url)

    # the ring-first cold path is part of the wrapped surface (a
    # production worker sees RingSource directly)
    def hist_columns(self, url, now=None):
        return self.inner.hist_columns(url, now)

    def hist_coverage(self, url, now=None):
        return self.inner.hist_coverage(url, now)

    def ingest_debug_state(self):
        return self.inner.ingest_debug_state()


def _durable_worker(store, snap_dir, worker_id, data_now, fallback, *,
                    mesh=None, max_stuck=0.0):
    """One worker with the full durable data plane mounted: RingSource
    over a fresh RingStore, snapshot restore + journal attach, fit
    journals restored lazily. Returns (worker, snapshotter, dying)."""
    from foremast_tpu.config import BrainConfig
    from foremast_tpu.ingest import RingSnapshotter, RingSource, RingStore
    from foremast_tpu.jobs.worker import BrainWorker

    ring = RingStore(shards=2)
    snap = RingSnapshotter(ring, snap_dir, clock=lambda: data_now[0])
    snap.restore()
    snap.attach()
    src = RingSource(ring, fallback=fallback, clock=lambda: data_now[0])
    dying = _DyingRing(src)
    worker = BrainWorker(
        store,
        dying,
        config=BrainConfig(
            algorithm="moving_average_all",
            max_stuck_seconds=max_stuck,
            max_cache_size=256,
        ),
        claim_limit=64,
        worker_id=worker_id,
        mesh=mesh,
    )
    worker.enable_fit_persistence(snap_dir)
    worker.attach_ring_snapshotter(snap)
    return worker, snap, dying


def test_worker_crash_mid_tick_restarts_warm(tmp_path):
    """Single-worker crash harness: kill mid-tick after two healthy
    ticks, restart against the same snapshot dir, and assert the next
    tick is 100% fast-path with ZERO fallback fetches and every parked
    document re-judged exactly once (statuses identical to a worker
    that never crashed)."""
    from benchmarks.scaleout_bench import SynthSource, build_fleet
    from foremast_tpu.config import BrainConfig
    from foremast_tpu.jobs.models import (
        STATUS_PREPROCESS_COMPLETED,
        STATUS_PREPROCESS_INPROGRESS,
    )
    from foremast_tpu.jobs.store import InMemoryStore
    from foremast_tpu.jobs.worker import BrainWorker

    SERVICES_D = 8
    snap_dir = str(tmp_path / "durable")
    store = InMemoryStore()
    build_fleet(store, SERVICES_D, 2, HIST_LEN, CUR_LEN, int(NOW))

    data_now = [NOW + 150.0]
    fb1 = _CountingSource(SynthSource())
    w1, snap1, dying1 = _durable_worker(
        store, snap_dir, "w-dur", data_now, fb1
    )
    assert w1.tick(now=data_now[0]) == SERVICES_D  # cold: fits + backfill
    cold_fallback = fb1.calls
    assert cold_fallback > 0
    data_now[0] = NOW + 160
    assert w1.tick(now=data_now[0]) == SERVICES_D
    assert w1._last_tick["fast"] == SERVICES_D  # warm before the crash
    assert fb1.calls == cold_fallback  # warm tick: zero fallback already
    snap1.snapshot()  # a mid-life snapshot pass (logs cover the rest)

    # CRASH mid-tick: claim persisted, fetch #3 explodes, no verdict
    dying1.armed = True
    data_now[0] = NOW + 170
    import pytest as _pytest

    with _pytest.raises(_Die):
        w1.tick(now=data_now[0])
    parked = [
        d for d in store._docs.values()
        if d.status == STATUS_PREPROCESS_INPROGRESS
    ]
    assert parked, "crash landed before any claim persisted"
    # the dead process's file handles just vanish — no close(), no
    # final snapshot; restore must work from whatever was flushed

    # RESTART: fresh ring, fresh caches, same directory
    judged: list[str] = []
    orig_update, orig_many = store.update, store.update_many

    def _u(doc):
        if doc.status != STATUS_PREPROCESS_INPROGRESS:
            judged.append(doc.id)
        return orig_update(doc)

    def _um(docs):
        for d in docs:
            if d.status != STATUS_PREPROCESS_INPROGRESS:
                judged.append(d.id)
        return orig_many(docs)

    store.update, store.update_many = _u, _um
    try:
        data_now2 = [NOW + 400.0]
        fb2 = _CountingSource(SynthSource())
        w2, snap2, _ = _durable_worker(
            store, snap_dir, "w-dur", data_now2, fb2
        )
        restored = w2.debug_state()["durability"]
        assert restored["ring"]["restored_series"] > 0
        time.sleep(1.1)  # stuck-claim stamp granularity (wall clock)
        n = w2.tick(now=data_now2[0])
        assert n == SERVICES_D
        # THE acceptance bar: ≥ 90% fast path, zero fallback fetches
        assert w2._last_tick["fast"] >= 0.9 * SERVICES_D
        assert fb2.calls == 0, (
            f"restarted worker fell back {fb2.calls} times"
        )
        # no lost, no duplicated verdicts; statuses match the no-crash
        # steady state (open docs keep re-checking)
        assert sorted(judged) == sorted(d.id for d in store._docs.values())
        assert all(
            d.status == STATUS_PREPROCESS_COMPLETED
            for d in store._docs.values()
        )
    finally:
        store.update, store.update_many = orig_update, orig_many
        w1.close()
        w2.close()
        snap1.close()
        snap2.close()


def test_restored_ring_serves_recovery_cold_fits_zero_fallback(tmp_path):
    """Durability × cold-start interplay (ISSUE 10 satellite): even
    when the fit journals are LOST across a SIGKILL (only the ring
    snapshot/log survives), the restarted worker's recovery tick
    re-fits every document COLD — and those cold fits read the
    restored ring's resident columns, zero fallback HTTP fetches."""
    import os as _os

    from benchmarks.scaleout_bench import SynthSource, build_fleet
    from foremast_tpu.jobs.models import STATUS_PREPROCESS_COMPLETED
    from foremast_tpu.jobs.store import InMemoryStore

    SERVICES_D = 6
    snap_dir = str(tmp_path / "durable-cold")
    store = InMemoryStore()
    build_fleet(store, SERVICES_D, 2, HIST_LEN, CUR_LEN, int(NOW))

    data_now = [NOW + 150.0]
    fb1 = _CountingSource(SynthSource())
    w1, snap1, dying1 = _durable_worker(
        store, snap_dir, "w-coldfit", data_now, fb1
    )
    assert w1.tick(now=data_now[0]) == SERVICES_D  # cold: backfills ring
    snap1.snapshot()
    # CRASH mid-tick (claim persisted, no verdict)
    dying1.armed = True
    data_now[0] = NOW + 160
    import pytest as _pytest

    with _pytest.raises(_Die):
        w1.tick(now=data_now[0])

    # the fit journals are LOST (disk swap, operator wipe, version
    # bump): only the ring state survives
    for name in _os.listdir(snap_dir):
        if name.startswith("fit-"):
            _os.unlink(_os.path.join(snap_dir, name))

    data_now2 = [NOW + 400.0]
    fb2 = _CountingSource(SynthSource())
    w2, snap2, _ = _durable_worker(
        store, snap_dir, "w-coldfit", data_now2, fb2
    )
    try:
        dur = w2.debug_state()["durability"]
        assert dur["ring"]["restored_series"] > 0
        assert all(
            j["restored_entries"] == 0
            for j in dur["fit_journals"].values()
        )
        time.sleep(1.1)  # stuck-claim stamp granularity (wall clock)
        n = w2.tick(now=data_now2[0])
        assert n == SERVICES_D
        # every doc re-fit COLD (no fits survived) ...
        assert w2._last_tick["fast"] == 0
        # ... and every cold fit read the restored ring: zero fallback
        assert fb2.calls == 0, (
            f"recovery cold fits fell back {fb2.calls} times"
        )
        reads = w2.debug_state()["cold_start"]["hist_reads"]
        assert reads["ring_full"] >= SERVICES_D
        assert reads["http"] == 0 and reads["cache"] == 0
        assert all(
            d.status == STATUS_PREPROCESS_COMPLETED
            for d in store._docs.values()
        )
    finally:
        w1.close()
        w2.close()
        snap1.close()
        snap2.close()


def test_mesh_worker_crash_restart_reclaims_partition_warm(tmp_path):
    """3-worker mesh crash harness: w2 (durable) dies mid-tick, then
    RESTARTS under the same worker id + snapshot dir BEFORE its lease
    expires. The ring never moves: the restarted worker re-takes its
    seat, reclaims exactly its own parked partition, and judges it
    ≥ 90% fast-path with zero fallback fetches — while the survivors'
    partitions are untouched (no double judgment anywhere)."""
    from benchmarks.scaleout_bench import SynthSource, build_fleet
    from foremast_tpu.config import BrainConfig
    from foremast_tpu.jobs.models import STATUS_PREPROCESS_INPROGRESS
    from foremast_tpu.jobs.worker import BrainWorker
    from foremast_tpu.mesh import MESH_APP, Membership, MeshNode, MeshRouter
    from foremast_tpu.jobs.store import InMemoryStore

    SERVICES_M = 12
    store = InMemoryStore()
    build_fleet(store, SERVICES_M, 2, HIST_LEN, CUR_LEN, int(NOW))
    clock = [1000.0]
    data_now = [NOW + 150.0]
    judged: list[tuple[str, str]] = []
    current_worker = [""]
    orig_update, orig_many = store.update, store.update_many

    def _rec(doc):
        if doc.app_name == MESH_APP:
            return
        if doc.status != STATUS_PREPROCESS_INPROGRESS:
            judged.append((doc.id, current_worker[0]))

    def _u(doc):
        _rec(doc)
        return orig_update(doc)

    def _um(docs):
        for d in docs:
            _rec(d)
        return orig_many(docs)

    store.update, store.update_many = _u, _um

    def mesh_node(wid):
        mem = Membership(
            store, wid, lease_seconds=60.0, clock=lambda: clock[0]
        )
        router = MeshRouter(mem, refresh_seconds=0.0, clock=lambda: clock[0])
        node = MeshNode(mem, router, clock=lambda: clock[0])
        node.start()
        return node

    workers = {}
    snaps = {}
    fallbacks = {}
    nodes = {}
    dying = None
    try:
        for wid in ("w0", "w1", "w2"):
            nodes[wid] = mesh_node(wid)
            fallbacks[wid] = _CountingSource(SynthSource())
            w, snap, d = _durable_worker(
                store, str(tmp_path / wid), wid, data_now, fallbacks[wid],
                mesh=nodes[wid],
            )
            workers[wid] = w
            snaps[wid] = snap
            if wid == "w2":
                dying = d
        for node in nodes.values():
            node.router.refresh(force=True)

        def tick_all(now, who=("w0", "w1", "w2")):
            total = 0
            for wid in who:
                current_worker[0] = wid
                total += workers[wid].tick(now=now)
            return total

        # rounds 1 (cold) + 2 (warm): disjoint total partitions
        assert tick_all(NOW + 150) == SERVICES_M
        owner_of = dict(judged)
        assert len(judged) == SERVICES_M
        judged.clear()
        clock[0] += 4.0
        data_now[0] = NOW + 160
        assert tick_all(NOW + 160) == SERVICES_M
        assert {d: w for d, w in judged} == owner_of
        orphans = {d for d, w in owner_of.items() if w == "w2"}
        assert orphans, "w2 owned nothing — ring degenerate?"
        judged.clear()

        # round 3: w2 dies mid-tick; its partition parks in-progress
        clock[0] += 4.0
        data_now[0] = NOW + 170
        tick_all(NOW + 170, who=("w0", "w1"))
        dying.armed = True
        current_worker[0] = "w2"
        import pytest as _pytest

        with _pytest.raises(_Die):
            workers["w2"].tick(now=NOW + 170)
        parked = {
            d.id
            for d in store._docs.values()
            if d.status == STATUS_PREPROCESS_INPROGRESS
        }
        assert parked == orphans
        judged.clear()

        # RESTART w2 (same id, same dir) BEFORE the lease expires: the
        # ring does not move, so nothing rebalances away from it
        fb2 = _CountingSource(SynthSource())
        fallbacks["w2-restarted"] = fb2
        nodes["w2r"] = mesh_node("w2")
        data_now[0] = NOW + 400
        w2r, snap2r, _ = _durable_worker(
            store, str(tmp_path / "w2"), "w2", data_now, fb2,
            mesh=nodes["w2r"],
        )
        workers["w2r"] = w2r
        snaps["w2r"] = snap2r
        assert len(nodes["w2r"].router.members()) == 3  # re-joined seat
        time.sleep(1.1)  # stuck-claim stamp granularity
        clock[0] += 4.0
        current_worker[0] = "w2"
        n = w2r.tick(now=NOW + 400)
        # reclaimed EXACTLY its partition, warm, zero fallback
        assert n == len(orphans)
        assert {d for d, _ in judged} == orphans
        assert len(judged) == len(orphans)  # exactly once each
        assert w2r._last_tick["fast"] >= 0.9 * len(orphans)
        assert fb2.calls == 0
        # survivors' partitions were never touched by the restart
        assert all(w == "w2" for _, w in judged)
    finally:
        store.update, store.update_many = orig_update, orig_many
        for w in workers.values():
            w.close()
        for s in snaps.values():
            s.close()
