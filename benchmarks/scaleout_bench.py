"""Worker-mesh scale-out benchmark: N REAL processes sharding one fleet.

Every other benchmark measures one worker; this one measures the ISSUE 6
architecture end to end. A parent process serves the shared job store
over real HTTP (the production topology: independent workers against one
store) and spawns N worker subprocesses, each running the SHIPPED stack —
`BrainWorker` + `MeshNode` (membership lease in the store, consistent-hash
claim partition) + its own ingest receiver and ring shard fed through the
cold-miss backfill path. Metric data comes from `SynthSource`, a
deterministic in-process generator (every worker synthesizes identical
series from the URL alone), so the measured numbers are claim + partition
filter + fetch/ring + judge + write-back — everything except Prometheus
latency, same floor as worker_bench.

Phases (parent-orchestrated through the store server's /control plane):

  ready   all workers joined the mesh; the parent runs a ROUTED-PUSH
          cycle against the workers' receivers (`RoutingPusher`): cycle 1
          scatters blind and collects redirect hints, cycle 2 must land
          every series on its owner with zero redirects
  cold    one tick per worker (fits + ring backfill)
  prewarm one unmeasured warm round per worker (columnar program
          compiles + admission-cache build stay out of the steady-state
          window — the same discipline as every other bench here)
  warm    `--warm-ticks` measured ticks per worker; the parent wall-times
          the phase and ASSERTS exactly-once judgment: every fleet doc
          judged exactly `warm_ticks` times, all by one worker
  kill    (largest run only) one worker SIGKILLs itself mid-tick after
          its claim persisted; survivors keep ticking — the parent
          asserts every orphaned doc is re-judged by a survivor within
          2 ticks of that survivor seeing the membership drop
  stop

Single-host methodology: every worker in every run is pinned to
`nproc // max(workers)` cores (constant per-worker hardware — the
1 -> N comparison measures SCALE-OUT, not one process's XLA intra-op
threads absorbing the whole host), and the store runs in the parent
as a real HTTP service the way production ES would be a separate
system. Workers on real deployments bring their own hosts/chips, so
the single-host numbers here are the conservative floor.

Usage: python -m benchmarks.scaleout_bench [--services N] [--workers 1,4]
       [--warm-ticks K] [--small]
Prints one JSON line per worker count plus a summary line with the
1 -> max speedup.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
import urllib.parse

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALIAS_EXPR = 'synth_m{a}{{app="app{sid}"}}'
KILL_EXIT = 17


# ---------------------------------------------------------------------------
# deterministic metric source — identical series in every process
# ---------------------------------------------------------------------------


def synth_values(key: str, ts: np.ndarray) -> np.ndarray:
    """A healthy hour-period wave, phase-seeded by the series key: the
    band a moving-average fit draws around the history always contains
    the current window (same generator, same amplitude)."""
    h = int.from_bytes(
        hashlib.blake2b(key.encode(), digest_size=8).digest(), "big"
    )
    phase = (h % 4096) / 4096.0 * 2.0 * np.pi
    return (
        1.0 + 0.08 * np.sin(2.0 * np.pi * ts / 3600.0 + phase)
    ).astype(np.float32)


class SynthSource:
    """MetricSource synthesizing windows from the URL alone — the
    fake-Prometheus floor without a server (worker_bench.ArraySource
    needs the data pre-seeded; subprocesses cannot share that dict)."""

    concurrent_fetch = False

    def fetch(self, url: str):
        from foremast_tpu.ingest.wire import resolve_query_range

        key, t0, t1, step = resolve_query_range(url)
        if key is None or t0 is None or t1 is None:
            raise ValueError(f"unresolvable synth url {url!r}")
        ts = np.arange(int(t0), int(t1) + 1, int(step or 60), np.int64)
        return ts, synth_values(key, ts)


def build_fleet(store, services: int, aliases: int, hist_len: int,
                cur_len: int, now: int) -> None:
    """One continuous-strategy doc per service; series keys carry the
    app label, so documents and their pushed series hash to the same
    mesh member (mesh/routing.py route label)."""
    from foremast_tpu.jobs.models import Document

    cur_t1 = now - 60
    cur_t0 = cur_t1 - 60 * (cur_len - 1)
    hist_t1 = cur_t0 - 120  # settled AND disjoint from the current window
    hist_t0 = hist_t1 - 60 * (hist_len - 1)
    end_time = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(now + 86_400)
    )
    for sid in range(services):
        cur_parts, hist_parts = [], []
        for a in range(aliases):
            expr = urllib.parse.quote(
                ALIAS_EXPR.format(a=a, sid=sid), safe=""
            )
            cur_parts.append(
                f"m{a}== http://synth/api/v1/query_range?query={expr}"
                f"&start={cur_t0}&end={cur_t1}&step=60"
            )
            hist_parts.append(
                f"m{a}== http://synth/api/v1/query_range?query={expr}"
                f"&start={hist_t0}&end={hist_t1}&step=60"
            )
        store.create(
            Document(
                id=f"job-{sid}",
                app_name=f"app{sid}",
                end_time=end_time,
                current_config=" ||".join(cur_parts),
                historical_config=" ||".join(hist_parts),
                strategy="continuous",
            )
        )


# ---------------------------------------------------------------------------
# the shared store, served over real HTTP
# ---------------------------------------------------------------------------


class _InjectedStoreFault(Exception):
    """A fault-hook hit: the HTTP handler answers `status` (not 500),
    so clients see the same wire behavior a browning-out ES would
    produce (503s on the write path classify as transient)."""

    def __init__(self, status: int, op: str):
        super().__init__(f"injected fault: HTTP {status} on {op!r}")
        self.status = status


class StoreServer:
    """InMemoryStore behind one JSON-RPC endpoint, with the mesh claim
    filter applied SERVER-SIDE through the real membership + ring code
    (the same ownership function the workers' own routers compute) and
    a judgment ledger the parent's exactly-once assertions read."""

    def __init__(self, replicas: int = 64):
        from foremast_tpu.jobs.store import InMemoryStore

        self.store = InMemoryStore()
        self.replicas = replicas
        self._lock = threading.Lock()
        # doc id -> [(worker, phase_tag, status, wall_seconds), ...]
        self.ledger: dict[str, list] = {}
        self.ticks: list[dict] = []
        self.barriers: dict[str, set] = {}
        self.phase = "ready"
        self._owner_cache: tuple | None = None  # (members_key, {app: owner})
        # per-worker ids already shipped in full: a re-claim of a doc a
        # worker has seen returns just the id (the config blobs are
        # immutable per id and the worker's meta cache already decoded
        # them) — the bench-protocol analog of ES `_source` filtering
        self.seen: dict[str, set] = {}
        self.op_seconds: dict[str, list] = {}  # op -> [count, seconds]
        self._srv = None
        # fault hooks (ISSUE 9 satellite): chaos tests drive a REAL
        # store server answering real error statuses per RPC op —
        # {"op": substr(""=all), "status": int, "latency": seconds,
        # "times": remaining fires (None=until removed)}; clear with
        # clear_faults(). Matching faults with a status short-circuit
        # the dispatch (the op never reaches the store).
        self.faults: list[dict] = []

    def add_fault(
        self,
        op: str = "",
        status: int = 503,
        latency: float = 0.0,
        times: int | None = None,
    ) -> None:
        with self._lock:
            self.faults.append(
                {"op": op, "status": status, "latency": latency,
                 "times": times}
            )

    def clear_faults(self) -> None:
        with self._lock:
            self.faults = []

    def _take_fault(self, op: str) -> dict | None:
        with self._lock:
            for f in self.faults:
                if f["op"] and f["op"] not in op:
                    continue
                if f["times"] is not None:
                    if f["times"] <= 0:
                        continue
                    f["times"] -= 1
                return dict(f)
        return None

    # -- mesh ownership, computed from the records IN the store --------

    def _claim_filter(self, worker_id: str):
        from foremast_tpu.mesh import (
            CLAIM_STATES,
            HashRing,
            doc_route_key,
            live_members,
        )

        # the CLAIM ring only (mesh/routing.py two-ring ownership): a
        # fenced `joining` member must not claim a doc the server side
        # still routes to the current owner, or the joiner judges COLD
        # mid-handoff — exactly the refit the fence exists to prevent
        members = [
            m for m in live_members(self.store) if m.state in CLAIM_STATES
        ]
        if not members:
            return None
        key = tuple((m.worker_id, m.capacity, m.state) for m in members)
        with self._lock:
            cached = self._owner_cache
            owners = cached[1] if cached and cached[0] == key else None
        if owners is None:
            owners = {}
            with self._lock:
                self._owner_cache = (key, owners)
        ring = HashRing(
            {m.worker_id: m.capacity for m in members},
            replicas=self.replicas,
        )

        def owns(doc) -> bool:
            rk = doc_route_key(doc)
            owner = owners.get(rk)
            if owner is None:
                owner = ring.owner(rk)
                owners[rk] = owner
            return owner == worker_id

        return owns

    def owner_map(self) -> dict[str, str]:
        """app -> owner under the CURRENT live membership (parent-side:
        orphan-set computation before a kill)."""
        from foremast_tpu.mesh import HashRing, doc_route_key, live_members
        from foremast_tpu.mesh.membership import CLAIM_STATES, MESH_APP

        members = live_members(self.store)
        ring = HashRing(
            {
                m.worker_id: m.capacity
                for m in members
                if m.state in CLAIM_STATES
            },
            replicas=self.replicas,
        )
        out = {}
        for doc in self.store.list_open():
            if doc.app_name == MESH_APP:
                continue
            out[doc.id] = ring.owner(doc_route_key(doc))
        return out

    def _record(self, doc_json: dict, worker: str, tag: str) -> None:
        from foremast_tpu.mesh.membership import MESH_APP

        if doc_json.get("appName") == MESH_APP:
            return
        status = doc_json.get("status", "")
        with self._lock:
            self.ledger.setdefault(doc_json["id"], []).append(
                (worker, tag, status, time.time())
            )

    # -- RPC ------------------------------------------------------------

    def _rpc(self, req: dict) -> dict:
        t0 = time.perf_counter()
        try:
            fault = self._take_fault(req["op"])
            if fault is not None:
                if fault["latency"]:
                    time.sleep(fault["latency"])
                if fault["status"]:
                    raise _InjectedStoreFault(fault["status"], req["op"])
            return self._dispatch(req)
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                agg = self.op_seconds.setdefault(req["op"], [0, 0.0])
                agg[0] += 1
                agg[1] += dt

    def _dispatch(self, req: dict) -> dict:
        from foremast_tpu.jobs.models import Document

        op = req["op"]
        if op == "create_many":
            for d in req["docs"]:
                self.store.create(Document.from_json(d))
            return {"ok": True}
        if op == "get":
            doc = self.store.get(req["id"])
            return {"doc": doc.to_json() if doc else None}
        if op == "claim":
            worker = req["workerId"]
            filt = self._claim_filter(worker) if req.get("mesh") else None
            docs = self.store.claim(
                worker, req["maxStuck"], req["limit"], claim_filter=filt,
            )
            seen = self.seen.setdefault(worker, set())
            new = [d.to_json() for d in docs if d.id not in seen]
            ids = [d.id for d in docs]
            seen.update(ids)
            return {"ids": ids, "new": new}
        if op == "update":
            doc = Document.from_json(req["doc"])
            self.store.update(doc)
            self._record(req["doc"], req.get("workerId", "?"), req.get("tag", ""))
            return {"ok": True}
        if op == "update_many":
            # partial-update rows [id, status, statusCode, reason,
            # anomalyInfo] — the bench-protocol analog of ES partial
            # updates: a warm write-back never re-ships the immutable
            # config blobs. One store lock for the whole batch: a
            # per-row get() would take and release it 16k times per
            # round per worker, serializing the mesh on lock churn.
            from foremast_tpu.jobs.store import now_rfc3339

            worker = req.get("workerId", "?")
            tag = req.get("tag", "")
            wall = time.time()
            entries = []
            stamp = now_rfc3339()
            with self.store._lock:
                docs = self.store._docs
                for doc_id, status, code, reason, anomaly in req["rows"]:
                    doc = docs.get(doc_id)
                    if doc is None:
                        continue
                    doc.status = status
                    doc.status_code = code
                    doc.reason = reason
                    doc.anomaly_info = anomaly
                    doc.modified_at = stamp
                    entries.append((doc_id, status))
            with self._lock:
                for doc_id, status in entries:
                    self.ledger.setdefault(doc_id, []).append(
                        (worker, tag, status, wall)
                    )
            return {"ok": True}
        if op == "list_app":
            return {
                "docs": [d.to_json() for d in self.store.list_app(req["app"])]
            }
        if op == "report_tick":
            with self._lock:
                self.ticks.append(req["tick"])
            return {"ok": True}
        if op == "barrier":
            with self._lock:
                self.barriers.setdefault(req["name"], set()).add(
                    req["workerId"]
                )
            return {"ok": True}
        if op == "phase":
            return {"phase": self.phase}
        raise ValueError(f"unknown op {op!r}")

    def barrier_count(self, name: str) -> int:
        with self._lock:
            return len(self.barriers.get(name, ()))

    def ledger_snapshot(self) -> dict[str, list]:
        with self._lock:
            return {k: list(v) for k, v in self.ledger.items()}

    def tick_reports(self) -> list[dict]:
        with self._lock:
            return list(self.ticks)

    # -- HTTP plumbing ---------------------------------------------------

    def start(self) -> str:
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        outer = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"  # keep-alive: one conn per worker

            def log_message(self, *a):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length") or 0)
                try:
                    body = outer._rpc(json.loads(self.rfile.read(n)))
                    code = 200
                except _InjectedStoreFault as e:
                    body, code = {"error": str(e)}, e.status
                except Exception as e:  # noqa: BLE001 — surface to the client
                    body, code = {"error": repr(e)}, 500
                payload = json.dumps(body, separators=(",", ":")).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

        self._srv = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self._srv.daemon_threads = True
        threading.Thread(
            target=self._srv.serve_forever, daemon=True
        ).start()
        return f"http://127.0.0.1:{self._srv.server_address[1]}"

    def stop(self):
        if self._srv is not None:
            self._srv.shutdown()


class HttpFleetStore:
    """Worker-side JobStore speaking the StoreServer protocol. The mesh
    claim filter travels as `mesh: true` — ownership is evaluated
    server-side from the same membership records with the same ring
    code, so the predicate callable never needs to cross the wire."""

    def __init__(self, base_url: str, worker_id: str, chaos=None, breaker=None):
        import requests

        from foremast_tpu.jobs.store import JobStore  # noqa: F401 — interface

        self.base = base_url
        self.worker_id = worker_id
        self.tag = ""  # phase tag stamped onto judgment writes
        self._s = requests.Session()
        if chaos is not None or breaker is not None:
            # the same one-choke-point seam ElasticsearchStore carries
            # (ISSUE 9): chaos benches drive the REAL degradation paths
            # through this client too
            from foremast_tpu.chaos import GuardedSession

            self._s = GuardedSession(self._s, chaos=chaos, breaker=breaker)
        # docs the server has shipped in full (slim re-claims return
        # ids only; the shared Document objects mirror InMemoryStore's
        # same-object semantics)
        self._docs: dict = {}

    def _rpc(self, **req) -> dict:
        r = self._s.post(self.base, json=req, timeout=120)
        r.raise_for_status()
        body = r.json()
        if "error" in body:
            raise RuntimeError(body["error"])
        return body

    def create(self, doc):
        got = self._rpc(op="get", id=doc.id)["doc"]
        if got is not None:
            from foremast_tpu.jobs.models import Document

            return Document.from_json(got), False
        self._rpc(op="create_many", docs=[doc.to_json()])
        return doc, True

    def get(self, doc_id):
        from foremast_tpu.jobs.models import Document

        got = self._rpc(op="get", id=doc_id)["doc"]
        return Document.from_json(got) if got else None

    def claim(self, worker_id, max_stuck_seconds, limit=64, claim_filter=None):
        from foremast_tpu.jobs.models import Document

        body = self._rpc(
            op="claim",
            workerId=worker_id,
            maxStuck=max_stuck_seconds,
            limit=limit,
            mesh=claim_filter is not None,
        )
        for d in body["new"]:
            doc = Document.from_json(d)
            self._docs[doc.id] = doc
        for i in body["ids"]:
            if i not in self._docs:
                # the server's `seen` set says this worker ID already
                # received the doc in full, but THIS process has not —
                # a restarted worker reusing its id (restart_bench).
                # Re-fetch once; the real ES store reships _source.
                got = self._rpc(op="get", id=i)["doc"]
                self._docs[i] = Document.from_json(got)
        return [self._docs[i] for i in body["ids"]]

    def update(self, doc):
        self._rpc(
            op="update", doc=doc.to_json(), workerId=self.worker_id,
            tag=self.tag,
        )
        self._docs[doc.id] = doc
        return doc

    def update_many(self, docs):
        if docs:
            self._rpc(
                op="update_many",
                rows=[
                    [
                        d.id, d.status, d.status_code, d.reason,
                        d.anomaly_info,
                    ]
                    for d in docs
                ],
                workerId=self.worker_id,
                tag=self.tag,
            )

    def list_app(self, app_name):
        from foremast_tpu.jobs.models import Document

        return [
            Document.from_json(d)
            for d in self._rpc(op="list_app", app=app_name)["docs"]
        ]

    def list_open(self):
        raise NotImplementedError("bench store: not needed")

    def count_open(self):
        raise NotImplementedError("bench store: not needed")

    def barrier(self, name):
        self._rpc(op="barrier", name=name, workerId=self.worker_id)

    def phase(self) -> str:
        return self._rpc(op="phase")["phase"]

    def report_tick(self, **tick):
        self._rpc(op="report_tick", tick=tick)


# ---------------------------------------------------------------------------
# the worker child (spawned as `-m benchmarks.scaleout_bench --child`)
# ---------------------------------------------------------------------------


class _SuicideSource:
    """Delegates until armed, then SIGKILLs this process on the 3rd
    fetch — mid-tick, after the claim persisted, before any verdict
    (the pod-failure test's worst case, at mesh scale)."""

    concurrent_fetch = False

    def __init__(self, inner):
        self.inner = inner
        self.armed = False
        self.calls = 0

    def fetch(self, url):
        if self.armed:
            self.calls += 1
            if self.calls >= 3:
                os._exit(KILL_EXIT)
        return self.inner.fetch(url)


def run_child(args) -> int:
    # Constant per-worker hardware, set BEFORE jax imports spawn its
    # thread pools: every worker in every run of one comparison is
    # pinned to the same number of cores, so 1 -> N measures SCALE-OUT
    # (N workers' worth of hardware doing N partitions) instead of N
    # oversubscribed XLA thread pools fighting over one host's cores —
    # without pinning, each worker's judge slows ~Nx and the comparison
    # measures the scheduler, not the mesh.
    if args.cpus:
        lo, _, hi = args.cpus.partition("-")
        try:
            os.sched_setaffinity(0, range(int(lo), int(hi) + 1))
        except (OSError, AttributeError):
            pass  # non-Linux: run unpinned

    # Device-mesh sharded-judge variant (ISSUE 13): shard each worker's
    # judge over an N-device local mesh of forced virtual CPU devices
    # (the same stand-in tier-1 parity uses — worker children always
    # run on CPU: N processes cannot share a chip). MUST happen before
    # jax imports.
    if args.device_mesh > 1:
        # XLA's own variable, not a foremast knob (conftest.py precedent)
        flags = os.environ.get("XLA_FLAGS", "")  # foremast: ignore[env-contract]
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={args.device_mesh}"
            ).strip()
        os.environ["FOREMAST_DEVICE_MESH"] = str(args.device_mesh)
    else:
        # explicit OFF for the baseline runs: the pytest smoke inherits
        # an 8-virtual-device XLA_FLAGS from conftest, and "auto" would
        # silently shard the unsharded comparison arm
        os.environ["FOREMAST_DEVICE_MESH"] = "0"

    from foremast_tpu.config import BrainConfig
    from foremast_tpu.ingest import RingSource, RingStore, start_ingest_server
    from foremast_tpu.jobs.worker import BrainWorker
    from foremast_tpu.mesh import Membership, MeshNode, MeshRouter

    worker_id = f"w{args.index}"
    store = HttpFleetStore(args.store_url, worker_id)

    # the worker's own ingest shard: receiver + ring, warm current
    # windows served resident after the first backfill. The suicide
    # wrapper sits OUTSIDE the ring source — warm fetches are ring hits
    # that never reach the fallback, and the victim must die on the
    # fetches its judged tick actually makes.
    ring = RingStore(
        budget_bytes=args.ring_budget, shards=4,
        max_points=args.ring_points,
    )
    source = _SuicideSource(RingSource(ring, fallback=SynthSource()))
    membership = Membership(
        store, worker_id, lease_seconds=args.lease_seconds
    )
    router = MeshRouter(
        membership,
        replicas=args.replicas,
        refresh_seconds=min(1.0, args.lease_seconds / 4),
    )
    srv, _ = start_ingest_server(0, ring, host="127.0.0.1", router=router)
    address = f"127.0.0.1:{srv.server_address[1]}"
    membership.ingest_address = address
    node = MeshNode(membership, router, ring_store=ring)
    node.start()

    # Heartbeat thread: a cold tick at fleet scale runs far longer than
    # the bench's short lease, and a member whose lease lapses mid-tick
    # would hand its partition to a peer — double judgment by design
    # error, not by bug. Its OWN store client: requests.Session is not
    # thread-safe and the tick thread owns `store`. Dies with the
    # process, which is exactly what makes the kill phase's lease
    # expiry honest.
    hb_store = HttpFleetStore(args.store_url, worker_id)
    hb_membership = Membership(
        hb_store, worker_id, lease_seconds=args.lease_seconds,
        ingest_address=address,
    )
    hb_membership.join()
    hb_stop = threading.Event()

    def heartbeat():
        while not hb_stop.wait(args.lease_seconds / 3.0):
            hb_membership.renew(force=True)

    threading.Thread(target=heartbeat, daemon=True).start()

    cfg = BrainConfig(
        algorithm="moving_average_all",
        season_steps=24,
        max_stuck_seconds=args.max_stuck,
        max_cache_size=args.services * args.aliases + 64,
    )
    from prometheus_client import CollectorRegistry

    from foremast_tpu.observe.spans import Tracer

    tracer = Tracer(
        service=worker_id, registry=CollectorRegistry(), trace_dir=None
    )
    worker = BrainWorker(
        store, source, config=cfg, claim_limit=args.services,
        worker_id=worker_id, mesh=node, tracer=tracer,
    )

    def tick(tag: str) -> tuple[int, float]:
        store.tag = tag
        t0 = time.perf_counter()
        c0 = time.process_time()
        n = worker.tick()
        dt = time.perf_counter() - t0
        dm = worker._device_mesh_state()
        store.report_tick(
            worker=worker_id, tag=tag, docs=n, seconds=round(dt, 4),
            cpu_seconds=round(time.process_time() - c0, 4),
            members=len(router.members()),
            stages={
                k: round(v, 4)
                for k, v in tracer.last_stage_seconds.items()
            },
            # cumulative device-mesh counters (pad fraction, H2D place,
            # host gather) — the parent's roofline account reads the
            # final warm tick's values
            device_mesh=dm,
        )
        return n, dt

    cold_done = False
    prewarm_done = False
    warm_ticks = 0
    rebal_tick = 0
    arrived: set[str] = set()

    def arrive(name: str):
        if name not in arrived:
            arrived.add(name)
            store.barrier(name)

    store.barrier("ready")
    while True:
        phase = store.phase()
        if phase == "stop":
            break
        if phase == "cold" and not cold_done:
            n, _ = tick("cold")
            if n > 0:
                cold_done = True
                arrive("cold")
            continue
        if phase == "prewarm" and not prewarm_done:
            # one unmeasured warm round: first-warm costs (columnar
            # program compiles, admission-cache build) stay out of the
            # steady-state window, same discipline as every other bench
            n, _ = tick("prewarm")
            if n > 0:
                prewarm_done = True
                arrive("prewarm")
            continue
        if phase == "warm" and warm_ticks < args.warm_ticks:
            n, _ = tick(f"warm-{warm_ticks}")
            if n > 0:
                warm_ticks += 1
                if warm_ticks == args.warm_ticks:
                    arrive("warm")
            continue
        if phase == "kill":
            if args.victim:
                source.armed = True  # next tick dies after its claim
                tick("suicide")
                # unreachable past the claim (os._exit in fetch #3)
            else:
                # production-paced survivor loop: the ≤2-tick rebalance
                # bar is meaningless if an idle spin racks up hundreds
                # of empty "ticks" while the stuck window elapses
                _, dt = tick(f"rebal-{rebal_tick}")
                rebal_tick += 1
                time.sleep(max(0.0, 1.0 - dt))
            continue
        # holding between phases: keep the lease fresh AND the router
        # current (the ready-phase routed-push cycle needs every worker
        # to know the full membership before any tick runs)
        node.on_tick()
        time.sleep(0.05)
    hb_stop.set()
    node.close()
    worker.close()
    return 0


# ---------------------------------------------------------------------------
# the parent orchestration
# ---------------------------------------------------------------------------


def run_arena_check_child(args) -> int:
    """`--arena-child`: the ISSUE 19 sharded-arena capacity claims,
    demonstrated on real arenas under the sharded variant's exact
    device topology (forced virtual CPU devices). Three in-run asserts,
    one JSON verdict line for the parent:

      1. OOM-replicated-fits-sharded — a fleet whose row count blows
         the per-device budget hard-cap REFUSES on a replicated arena
         (assign -> None) and FITS a sharded arena under the identical
         per-device budget;
      2. linear capacity — aggregate sharded rows == devices x the
         replicated capacity the same budget buys;
      3. no cross-device gather leg — the compiled warm-tick program
         (`score_from_arena_sharded`, the real judgment jit) contains
         ZERO collectives: the roofline's gather leg is device-local.
    """
    n = args.device_mesh
    flags = os.environ.get("XLA_FLAGS", "")  # foremast: ignore[env-contract]
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()

    import re

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from foremast_tpu.engine import arena as ar
    from foremast_tpu.engine import scoring
    from foremast_tpu.ops.windows import MetricWindows
    from foremast_tpu.parallel import mesh as meshlib

    mesh = meshlib.make_mesh(n_data=n)
    data_spec = NamedSharding(mesh, P(meshlib.DATA_AXIS))
    season = 16
    row_bytes = 20 + 4 * season
    per_device_rows = 64
    budget = per_device_rows * row_bytes
    fleet = n * per_device_rows
    keys = [f"svc{i}" for i in range(fleet)]
    ar.set_arena_budget(budget, budget)
    try:
        # replicated layout: every device must host the WHOLE fleet, so
        # the per-device budget hard-caps and admission refuses
        rep = ar.StateArena(
            season, sharding=NamedSharding(mesh, P()), shards=1
        )
        oom_replicated = rep.assign(keys, []) is None
        # the same budget DOES buy per_device_rows replicated rows...
        assert rep.assign(keys[:per_device_rows], []) is not None
        rep_cap = rep.cap

        # ...and the sharded layout turns that per-device budget into
        # devices x the rows: the whole fleet fits
        sha = ar.StateArena(season, sharding=data_spec, shards=n)
        res = sha.assign(keys, [])
        fits_sharded = res is not None
        assert oom_replicated, (
            "replicated arena admitted a fleet past its hard cap — "
            "the capacity comparison is broken"
        )
        assert fits_sharded, (
            "sharded arena refused a fleet that fits its aggregate "
            "capacity"
        )
        assert sha.cap == n * rep_cap, (sha.cap, n, rep_cap)

        rows_g, scat = res
        sha.scatter(
            rows_g,
            scat,
            [
                (1.0, 0.0, np.zeros(season, np.float32), 3, 1.0, 100)
                for _ in scat
            ],
        )

        # compile the REAL warm-tick judgment at the fleet shape and
        # prove the gather leg is device-local: zero collectives
        tc = 16
        local = jax.device_put(
            (np.asarray(rows_g) % sha.cap_s).astype(np.int32), data_spec
        )
        batch = scoring.ScoreBatch(
            historical=MetricWindows(
                values=jax.device_put(
                    np.zeros((fleet, 0), np.float32), data_spec
                ),
                mask=jax.device_put(np.zeros((fleet, 0), bool), data_spec),
                times=None,
            ),
            current=MetricWindows(
                values=jax.device_put(
                    np.ones((fleet, tc), np.float32), data_spec
                ),
                mask=jax.device_put(np.ones((fleet, tc), bool), data_spec),
                times=None,
            ),
            baseline=MetricWindows(
                values=jax.device_put(
                    np.zeros((fleet, tc), np.float32), data_spec
                ),
                mask=jax.device_put(np.zeros((fleet, tc), bool), data_spec),
                times=None,
            ),
            threshold=jax.device_put(
                np.full(fleet, 3.0, np.float32), data_spec
            ),
            bound=jax.device_put(np.zeros(fleet, np.int32), data_spec),
            min_lower_bound=jax.device_put(
                np.zeros(fleet, np.float32), data_spec
            ),
            min_points=jax.device_put(
                np.full(fleet, 10, np.int32), data_spec
            ),
        )
        hlo = (
            scoring.score_from_arena_sharded.lower(
                batch, *sha.state, local, mesh=mesh
            )
            .compile()
            .as_text()
        )
        collectives = sorted(
            set(
                re.findall(
                    r"all-gather|all-reduce-start|all-to-all"
                    r"|collective-permute",
                    hlo,
                )
            )
        )
        assert not collectives, (
            "warm sharded program grew a cross-device leg: "
            f"{collectives}"
        )
        print(
            json.dumps(
                {
                    "devices": n,
                    "per_device_row_budget": per_device_rows,
                    "fleet_rows": fleet,
                    "oom_replicated": oom_replicated,
                    "fits_sharded": fits_sharded,
                    "replicated_capacity_rows": rep_cap,
                    "sharded_capacity_rows": sha.cap,
                    "linear_scaling": sha.cap == n * rep_cap,
                    "warm_gather_collectives": collectives,
                }
            ),
            flush=True,
        )
    finally:
        ar.set_arena_budget(None, None)
    return 0


def run_arena_check(device_mesh: int, env: dict) -> dict:
    """Spawn the `--arena-child` capacity check and return its verdict
    (the child owns the forced-device topology; keeping it out of the
    parent keeps virtual devices away from the parent's jax)."""
    out = subprocess.run(
        [
            sys.executable, "-m", "benchmarks.scaleout_bench",
            "--arena-child", "--device-mesh", str(device_mesh),
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, (
        f"arena capacity check failed:\n{out.stdout}\n{out.stderr}"
    )
    verdict = json.loads(out.stdout.strip().splitlines()[-1])
    assert verdict["oom_replicated"] and verdict["fits_sharded"], verdict
    assert verdict["linear_scaling"], verdict
    assert verdict["warm_gather_collectives"] == [], verdict
    return verdict


def _worker_log(i: int) -> str:
    try:
        with open(
            os.path.join(tempfile.gettempdir(), f"scaleout_w{i}.log")
        ) as fh:
            return fh.read()
    except OSError:
        return ""


def _wait(predicate, timeout: float, what: str):
    deadline = time.monotonic() + timeout
    while not predicate():
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out waiting for {what}")
        time.sleep(0.05)


def _routed_push_phase(server: StoreServer, services: int) -> dict:
    """Blind-scatter a sample of series at one receiver, learn the
    redirect hints, and show convergence on the second cycle."""
    from foremast_tpu.mesh import RoutingPusher, live_members

    members = live_members(server.store)
    addresses = [m.ingest_address for m in members if m.ingest_address]
    now = int(time.time())
    sample = min(512, services)
    series = []
    for sid in range(sample):
        key = ALIAS_EXPR.format(a=0, sid=sid)
        ts = np.arange(now - 300, now, 60, np.int64)
        series.append((key, ts.tolist(), synth_values(key, ts).tolist(), None))
    pusher = RoutingPusher(addresses)
    first = pusher.push_cycle(series)
    second = pusher.push_cycle(series)
    return {
        "series": sample,
        "receivers": len(addresses),
        "first_cycle_redirects": first["redirects"],
        "second_cycle_redirects": second["redirects"],
        "converged": second["redirects"] == 0,
    }


def run(
    services: int,
    aliases: int,
    hist_len: int,
    cur_len: int,
    warm_ticks: int,
    workers: int,
    kill: bool,
    cpus_per_worker: int = 0,
    lease_seconds: float = 2.0,
    max_stuck: float = 3.0,
    replicas: int = 128,
    timeout: float = 1800.0,
    device_mesh: int = 0,
) -> dict:
    kill = kill and workers > 1
    server = StoreServer(replicas=replicas)
    url = server.start()
    now = int(time.time())
    build_fleet(server.store, services, aliases, hist_len, cur_len, now)

    env = dict(os.environ)
    # a CPU mechanism harness: N worker processes cannot share a chip
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("FOREMAST_INGEST", None)
    procs = []
    for i in range(workers):
        cmd = [
            sys.executable, "-m", "benchmarks.scaleout_bench", "--child",
            "--store-url", url, "--index", str(i),
            "--services", str(services), "--aliases", str(aliases),
            "--warm-ticks", str(warm_ticks),
            "--lease-seconds", str(lease_seconds),
            "--max-stuck", str(max_stuck),
            "--replicas", str(replicas),
            "--device-mesh", str(device_mesh),
        ]
        if cpus_per_worker:
            cmd += [
                "--cpus",
                f"{i * cpus_per_worker}-{(i + 1) * cpus_per_worker - 1}",
            ]
        if kill and i == workers - 1:
            cmd.append("--victim")
        # stdout/stderr stream to a per-worker file, NOT a pipe: nobody
        # drains a pipe until the end, so a chatty child (JAX_LOG_COMPILES
        # debugging, warning storms) would block on a full pipe buffer
        # mid-phase and read as a mysterious slowdown
        log_path = os.path.join(
            tempfile.gettempdir(), f"scaleout_w{i}.log"
        )
        log_fh = open(log_path, "w")
        procs.append(
            subprocess.Popen(
                cmd, cwd=REPO, env=env,
                stdout=log_fh, stderr=subprocess.STDOUT, text=True,
            )
        )
        log_fh.close()
    victim_id = f"w{workers - 1}" if kill else None
    try:
        _wait(
            lambda: server.barrier_count("ready") == workers,
            timeout, "workers to join",
        )
        # let every worker's router pick up the FULL membership (the
        # hold loop refreshes at sub-second cadence) before pushing
        time.sleep(1.5)
        routed = _routed_push_phase(server, services)

        server.phase = "cold"
        t0 = time.perf_counter()
        _wait(
            lambda: server.barrier_count("cold") == workers,
            timeout, "cold ticks",
        )
        cold_wall = time.perf_counter() - t0

        # orphan set BEFORE the kill, under the full ring
        owners = server.owner_map() if kill else {}

        server.phase = "prewarm"
        _wait(
            lambda: server.barrier_count("prewarm") == workers,
            timeout, "prewarm ticks",
        )

        server.phase = "warm"
        t0 = time.perf_counter()
        _wait(
            lambda: server.barrier_count("warm") == workers,
            timeout, "warm ticks",
        )
        warm_wall = time.perf_counter() - t0

        # exactly-once: every doc judged warm_ticks times, by ONE worker
        ledger = server.ledger_snapshot()
        double_judged = []
        for sid in range(services):
            entries = [
                e for e in ledger.get(f"job-{sid}", ())
                if e[1].startswith("warm")
            ]
            who = {e[0] for e in entries}
            if len(entries) != warm_ticks or len(who) != 1:
                double_judged.append((f"job-{sid}", entries))
        assert not double_judged, (
            f"{len(double_judged)} docs judged off-partition or re-judged: "
            f"{double_judged[:3]}"
        )

        rebalance = None
        if kill:
            orphans = {d for d, o in owners.items() if o == victim_id}
            assert orphans, "victim owned no documents?"
            server.phase = "kill"
            _wait(
                lambda: procs[-1].poll() is not None,
                timeout, "victim to die",
            )
            assert procs[-1].returncode == KILL_EXIT

            def orphans_rejudged():
                led = server.ledger_snapshot()
                return all(
                    any(
                        e[1].startswith("rebal") and e[0] != victim_id
                        for e in led.get(d, ())
                    )
                    for d in orphans
                )

            t0 = time.perf_counter()
            _wait(orphans_rejudged, timeout, "orphan takeover")
            heal_wall = time.perf_counter() - t0

            # ≤ 2 ticks: for each survivor, the tick index where its
            # membership view first dropped vs the tick that judged its
            # newly-owned orphans
            led = server.ledger_snapshot()
            reports = server.tick_reports()
            heal_tick = {}
            for r in reports:
                tag = r["tag"]
                if tag.startswith("rebal") and r["members"] < workers:
                    k = int(tag.split("-")[1])
                    w = r["worker"]
                    heal_tick[w] = min(heal_tick.get(w, k), k)
            worst = 0
            for d in orphans:
                for w, tag, _status, _wall in led.get(d, ()):
                    if tag.startswith("rebal") and w != victim_id:
                        k = int(tag.split("-")[1])
                        # claim authority is the SERVER's membership
                        # view, which can heal a refresh-interval ahead
                        # of the survivor's local router — an orphan
                        # judged before the local view caught up is lag
                        # 0, not negative
                        lag = max(0, k - heal_tick.get(w, k))
                        worst = max(worst, lag)
                        break
            assert worst <= 1, (
                f"rebalance took {worst + 1} ticks (> 2) after the ring "
                "healed"
            )
            rebalance = {
                "orphan_docs": len(orphans),
                "heal_wall_seconds": round(heal_wall, 3),
                "worst_ticks_after_heal": worst + 1,
                "lease_seconds": lease_seconds,
                "max_stuck_seconds": max_stuck,
            }

        server.phase = "stop"
        for i, p in enumerate(procs):
            try:
                p.wait(timeout=60)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
    except BaseException:
        for i, p in enumerate(procs):
            if p.poll() is None:
                p.kill()
        for i in range(workers):
            out = _worker_log(i)
            if out:
                sys.stderr.write(f"--- worker {i} output ---\n{out}\n")
        raise
    finally:
        server.stop()

    for i, p in enumerate(procs):
        if not (kill and i == workers - 1):
            assert p.returncode == 0, (
                f"worker {i} failed:\n{_worker_log(i)}"
            )

    windows = services * aliases
    # per-worker tick timings (diagnostics: where does a phase's wall
    # clock go — judge, store, or barrier skew)
    worker_ticks: dict = {}
    for r in server.tick_reports():
        worker_ticks.setdefault(r["worker"], {})[r["tag"]] = {
            "seconds": r["seconds"],
            **({"stages": r["stages"]} if r.get("stages") else {}),
        }
    # Roofline account for the sharded-judge variant (ISSUE 13): where
    # does a warm sharded tick's wall clock go — H2D placement, device
    # dispatch, host gather (which absorbs the deferred execution), or
    # host decode. Cumulative counters come from the FINAL warm tick's
    # device_mesh report; per-stage seconds sum over the warm ticks.
    roofline = None
    if device_mesh > 1:
        # per-worker cumulative counters: warm-phase deltas = last warm
        # report minus last prewarm report (cold/prewarm H2D must not
        # pollute the steady-state account)
        base: dict[str, dict] = {}
        final: dict[str, dict] = {}
        for r in server.tick_reports():
            if not r.get("device_mesh"):
                continue
            if r["tag"].startswith("warm"):
                final[r["worker"]] = r["device_mesh"]
            elif r["tag"] in ("cold", "prewarm"):
                # only PRE-warm snapshots form the baseline: kill runs
                # emit rebal-* reports AFTER the warm phase, and using
                # those as base would make every delta negative (and
                # the <2% pad assert vacuous)
                base[r["worker"]] = r["device_mesh"]
        assert final, "sharded variant produced no device_mesh reports"

        def delta(key):
            return sum(
                d[key] - base.get(w, {}).get(key, 0)
                for w, d in final.items()
            )

        stages: dict[str, float] = {}
        for r in server.tick_reports():
            if r["tag"].startswith("warm"):
                for k, v in (r.get("stages") or {}).items():
                    stages[k] = stages.get(k, 0.0) + v
        h2d_s = delta("place_seconds")
        h2d_b = delta("place_bytes")
        gat_s = delta("fetch_seconds")
        gat_b = delta("fetch_bytes")
        pad = delta("pad_rows_total")
        rows = delta("batch_rows_total")
        dms = list(final.values())
        roofline = {
            "devices_per_worker": dms[-1]["devices"],
            "h2d_seconds": round(h2d_s, 4),
            "h2d_mb_per_s": (
                round(h2d_b / h2d_s / 1e6, 1) if h2d_s else None
            ),
            "gather_seconds": round(gat_s, 4),
            "gather_mb_per_s": (
                round(gat_b / gat_s / 1e6, 1) if gat_s else None
            ),
            "dispatch_seconds": round(stages.get("score", 0.0), 4),
            "decode_seconds": round(stages.get("decode", 0.0), 4),
            "arena_assemble_seconds": round(
                stages.get("arena_assemble", 0.0), 4
            ),
            "padded_row_fraction": (
                round(pad / rows, 5) if rows else None
            ),
            "arena_layout": dms[-1].get("arena_layout"),
            "arena_capacity_rows": dms[-1].get("arena_capacity_rows"),
            "arena_replica_bytes": dms[-1]["arena_replica_bytes"],
            "arena_total_device_bytes": dms[-1][
                "arena_total_device_bytes"
            ],
        }
        if services >= 16384:
            # acceptance bar: padding must stay noise at fleet shapes
            assert roofline["padded_row_fraction"] < 0.02, roofline
    return {
        "workers": workers,
        "cpus_per_worker": cpus_per_worker or None,
        "device_mesh": device_mesh or None,
        "roofline": roofline,
        "worker_ticks": worker_ticks,
        "services": services,
        "aliases": aliases,
        "windows": windows,
        "warm_ticks": warm_ticks,
        "cold_wall_seconds": round(cold_wall, 3),
        "warm_wall_seconds": round(warm_wall, 3),
        "fleet_warm_windows_per_sec": round(
            windows * warm_ticks / warm_wall, 1
        ),
        "no_double_judgment": True,  # asserted above
        "routed_push": routed,
        "rebalance": rebalance,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--services", type=int, default=65536)
    ap.add_argument(
        "--aliases", type=int, default=4,
        help="metric aliases per document (4 = the reference's "
        "canonical monitor shape)",
    )
    ap.add_argument("--hist-len", type=int, default=256)
    ap.add_argument("--cur-len", type=int, default=30)
    ap.add_argument("--warm-ticks", type=int, default=3)
    ap.add_argument(
        "--workers", default="1,4",
        help="comma-separated worker counts to compare",
    )
    ap.add_argument(
        "--no-kill", action="store_true",
        help="skip the kill/rebalance phase",
    )
    ap.add_argument(
        "--small", action="store_true", help="CPU smoke shapes (CI)"
    )
    ap.add_argument(
        "--device-mesh", dest="device_mesh", type=int, default=0,
        help="shard every worker's judge over an N-device local mesh "
        "(ISSUE 13 sharded-judge variant; forces N virtual host "
        "devices on CPU platforms, spans real chips on TPU hosts). "
        "0 = single-device judges (the comparison baseline)",
    )
    ap.add_argument(
        "--cpus-per-worker", type=int, default=-1,
        help="cores pinned to EVERY worker in EVERY run (default: "
        "nproc // max worker count — constant per-worker hardware, so "
        "1 -> N measures scale-out, not scheduler contention; 0 "
        "disables pinning)",
    )
    # child-mode flags (internal)
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument(
        "--arena-child", dest="arena_child", action="store_true",
        help=argparse.SUPPRESS,
    )
    ap.add_argument("--store-url", help=argparse.SUPPRESS)
    ap.add_argument("--index", type=int, default=0, help=argparse.SUPPRESS)
    ap.add_argument("--victim", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--cpus", default="", help=argparse.SUPPRESS)
    ap.add_argument(
        "--lease-seconds", dest="lease_seconds", type=float, default=2.0,
        help=argparse.SUPPRESS,
    )
    ap.add_argument(
        "--max-stuck", dest="max_stuck", type=float, default=3.0,
        help=argparse.SUPPRESS,
    )
    ap.add_argument(
        "--replicas", type=int, default=128, help=argparse.SUPPRESS
    )
    ap.add_argument(
        "--ring-budget", type=int, default=256 * 1024 * 1024,
        help=argparse.SUPPRESS,
    )
    ap.add_argument(
        "--ring-points", type=int, default=64, help=argparse.SUPPRESS
    )
    args = ap.parse_args(argv)
    from foremast_tpu.device import enable_compile_cache

    enable_compile_cache()
    if args.arena_child:
        return run_arena_check_child(args)
    if args.child:
        return run_child(args)
    if args.small:
        args.services = min(args.services, 48)
        args.hist_len = min(args.hist_len, 128)
        args.warm_ticks = min(args.warm_ticks, 2)
        if args.workers == "1,4":
            args.workers = "1,2"
    worker_counts = sorted(
        {max(1, int(w)) for w in args.workers.split(",")}
    )
    cpus_per_worker = args.cpus_per_worker
    if cpus_per_worker < 0:
        cpus_per_worker = max(
            1, (os.cpu_count() or 8) // max(worker_counts)
        )
    arena_capacity = None
    if args.device_mesh > 1:
        # ISSUE 19 capacity claims, asserted in-run before the fleet
        # spins up: OOM-replicated-fits-sharded, linear aggregate
        # capacity, zero collectives in the compiled warm gather
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        arena_capacity = run_arena_check(args.device_mesh, env)
        print(json.dumps({"arena_capacity": arena_capacity}), flush=True)
    rows = []
    for i, w in enumerate(worker_counts):
        kill = (not args.no_kill) and i == len(worker_counts) - 1
        row = run(
            args.services, args.aliases, args.hist_len, args.cur_len,
            args.warm_ticks, w, kill, cpus_per_worker=cpus_per_worker,
            device_mesh=args.device_mesh,
        )
        rows.append(row)
        print(json.dumps(row), flush=True)
    base = rows[0]["fleet_warm_windows_per_sec"]
    peak = rows[-1]["fleet_warm_windows_per_sec"]
    summary = {
        "config": (
            "s-mesh-scaleout-sharded"
            if args.device_mesh > 1
            else "s-mesh-scaleout"
        ),
        "services": args.services,
        "windows": args.services * args.aliases,
        "device_mesh": args.device_mesh or None,
        "arena_capacity": arena_capacity,
        "roofline": rows[-1]["roofline"],
        "worker_counts": worker_counts,
        "fleet_warm_windows_per_sec": {
            str(r["workers"]): r["fleet_warm_windows_per_sec"] for r in rows
        },
        "no_double_judgment": all(r["no_double_judgment"] for r in rows),
        "routed_push_converged": all(
            r["routed_push"]["converged"] for r in rows
        ),
        "rebalance": rows[-1]["rebalance"],
        "metric": "fleet_throughput_speedup",
        "value": round(peak / base, 2) if base else None,
        "unit": f"x ({worker_counts[0]} -> {worker_counts[-1]} workers)",
    }
    # the ≥3x acceptance bar applies at benchmark shapes, not CI smoke
    if args.services >= 16384 and worker_counts[-1] >= 4:
        assert summary["value"] and summary["value"] >= 3.0, summary
    print(json.dumps(summary), flush=True)
    from benchmarks.report import write_summary

    write_summary(
        "scaleout_sharded" if args.device_mesh > 1 else "scaleout",
        summary,
        small=args.small,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
