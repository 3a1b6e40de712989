"""Pod-spanning worker mode: leader claim + broadcast + SPMD judgment.

The reference scales the brain by running N shared-nothing pods against
the shared ES store (`docs/guides/design.md:35-43`); that mode works here
unchanged (independent `BrainWorker`s, CAS claims — no jax.distributed
involved). This module adds the mode the reference cannot express: ONE
logical worker spanning every host of a multi-host slice, its judgment
partitioned over the global (data, model) mesh.

The multi-controller contract is that every process must execute the
same program over the same global batch, while exactly one process may
talk to the outside world. The adapters enforce that split:

  * `broadcast_obj`   — pickle-broadcast any host object from process 0
    (two `broadcast_one_to_all` collectives: size, then payload);
  * `LeaderStore`     — JobStore adapter: process 0 claims/writes against
    the real store and broadcasts the claim set, so all processes tick
    over IDENTICAL documents; follower writes are no-ops;
  * `LeaderSource`    — MetricSource adapter: process 0 fetches, results
    broadcast. `concurrent_fetch = False` is load-bearing: fetches are
    collectives, so their ORDER must be identical on every process — a
    thread pool would interleave them nondeterministically and deadlock;
  * `PodWorker`       — BrainWorker whose tick clock is broadcast (the
    settled-history admission gates compare against `now`; divergent
    clocks near a boundary would route the same doc down different code
    paths on different processes, desynchronizing the SPMD program).

Determinism argument for everything else: given identical docs, series,
clock and caches, the worker's control flow is a pure function, so fit
caches, gap anchors and arena row assignment evolve identically on every
process — which is what lets the arena stay REPLICATED over the mesh
(see engine/arena.py `sharding`) with each process scattering identical
rows.
"""

from __future__ import annotations

import logging
import os
import pickle
import threading
import time

import jax
import numpy as np

from foremast_tpu.jobs.store import JobStore
from foremast_tpu.jobs.worker import BrainWorker
from foremast_tpu.metrics.source import MetricSource

log = logging.getLogger("foremast_tpu.parallel.distributed")


def is_leader() -> bool:
    return jax.process_index() == 0


class PodCollectiveTimeout(RuntimeError):
    """A pod broadcast did not complete within the watchdog budget —
    a peer process (usually the leader) is dead or wedged. Raised so
    the process FAILS FAST instead of hanging inside a collective
    forever: the tick aborts, the process exits, and the in-flight
    claims age out into another worker via MAX_STUCK_IN_SECONDS
    (docs/operations.md, "Pod-mode failure and recovery")."""


def _pod_timeout_seconds() -> float | None:
    raw = os.environ.get("FOREMAST_POD_TIMEOUT_SECONDS", "300")
    try:
        t = float(raw)
    except ValueError:
        return 300.0
    return t if t > 0 else None


def _broadcast_raw(obj=None):
    from jax.experimental import multihost_utils as mhu

    leader = is_leader()
    if leader:
        payload = np.frombuffer(pickle.dumps(obj), np.uint8)
        size = np.array([payload.size], np.int64)
    else:
        payload = None
        size = np.zeros(1, np.int64)
    size = np.asarray(mhu.broadcast_one_to_all(size))
    buf = payload if leader else np.zeros(int(size[0]), np.uint8)
    buf = np.asarray(mhu.broadcast_one_to_all(buf))
    return obj if leader else pickle.loads(buf.tobytes())


class _BroadcastWorker:
    """ONE persistent daemon thread executing pod broadcasts in order.

    Per-call thread spawn would land on the per-fetch hot path (a
    fleet-cold pod tick issues tens of thousands of broadcasts); a
    single worker keeps the watchdog at one Event wait per call and —
    unlike ThreadPoolExecutor — never registers an atexit join, so a
    thread wedged inside a dead peer's collective cannot block the
    fail-fast process exit the watchdog exists to guarantee."""

    def __init__(self):
        import queue

        self._tasks: "queue.Queue" = queue.Queue()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="foremast-pod-broadcast"
        )
        self._thread.start()

    def _loop(self):
        while True:
            obj, box, done = self._tasks.get()
            try:
                box.append(("ok", _broadcast_raw(obj)))
            except BaseException as e:  # noqa: BLE001 — re-raised on the caller
                box.append(("err", e))
            finally:
                done.set()

    def run(self, obj, timeout: float):
        done = threading.Event()
        box: list = []
        self._tasks.put((obj, box, done))
        if not done.wait(timeout):
            # the worker stays wedged in the dead collective; that is
            # fine — the contract is that the caller now aborts the
            # tick and the process EXITS (daemon thread, no atexit join)
            raise PodCollectiveTimeout(
                f"pod broadcast incomplete after {timeout:.0f}s — a peer "
                "process is dead or wedged; aborting the tick so "
                "in-flight claims can age out (MAX_STUCK_IN_SECONDS "
                "takeover)"
            )
        kind, val = box[0]
        if kind == "err":
            raise val
        return val


_broadcast_worker: _BroadcastWorker | None = None


def broadcast_obj(obj=None):
    """Broadcast a picklable host object from process 0 to every process.

    Followers pass anything (ignored) and receive the leader's object.
    Single-process: returns `obj` unchanged with zero collectives.

    Every pod broadcast runs under a WATCHDOG
    (`FOREMAST_POD_TIMEOUT_SECONDS`, default 300; `0` disables): the
    runtime's own failure detection takes minutes to notice a dead
    coordinator, and a follower blocked inside a collective would
    otherwise hang the pod silently while its claims sit un-aged on the
    store. On timeout `PodCollectiveTimeout` propagates — the worker
    tick aborts, the process exits, and the reference's stuck-claim
    takeover recovers the in-flight documents (VERDICT r5 #6). Only the
    tick thread may call this (collective ORDER is load-bearing), so
    the single persistent worker thread preserves sequencing."""
    if jax.process_count() == 1:
        return obj
    timeout = _pod_timeout_seconds()
    if timeout is None:
        return _broadcast_raw(obj)
    global _broadcast_worker
    if _broadcast_worker is None:
        _broadcast_worker = _BroadcastWorker()
    return _broadcast_worker.run(obj, timeout)


class LeaderStore(JobStore):
    """Only process 0 talks to the real store; claims are broadcast.

    Followers construct this with `inner=None` — they never need a real
    connection, which also means ES credentials only have to exist on
    the leader."""

    def __init__(self, inner: JobStore | None):
        if is_leader() and inner is None:
            raise ValueError("process 0 needs the real store")
        self.inner = inner

    def claim(self, worker_id, max_stuck_seconds, limit=64, claim_filter=None):
        # a leader-side claim failure must CROSS the broadcast (ISSUE 9):
        # raising before broadcast_obj would leave every follower blocked
        # in the collective while the leader's worker loop moved on —
        # silent broadcast misalignment, worse than the crash it used to
        # be. The error ships as a marker and re-raises on every process
        # with its transience preserved, so the worker's claim
        # degradation (transient -> empty tick) stays pod-consistent.
        #
        # `claim_filter` is the mesh-of-pods seam (ISSUE 13): only the
        # leader holds a worker-mesh seat, so only it passes a filter —
        # the partition-filtered claim set then broadcasts like any
        # other, and followers (whose kwarg is always None) tick over
        # the identical documents. Partitioning cannot desync the pod
        # because it is applied BEFORE the broadcast, never after.
        if is_leader():
            try:
                kw = (
                    {"claim_filter": claim_filter}
                    if claim_filter is not None
                    else {}
                )
                docs = self.inner.claim(
                    worker_id, max_stuck_seconds, limit, **kw
                )
            except Exception as e:  # noqa: BLE001 — must cross processes
                from foremast_tpu.chaos.degrade import is_transient_error

                docs = _ClaimError(repr(e), is_transient_error(e))
        else:
            docs = None
        docs = broadcast_obj(docs)
        if isinstance(docs, _ClaimError):
            if docs.transient:
                raise ConnectionError(docs.msg)
            raise RuntimeError(docs.msg)
        return docs

    def update(self, doc):
        if is_leader():
            return self.inner.update(doc)
        return doc

    def update_many(self, docs):
        if is_leader():
            self.inner.update_many(docs)

    def create(self, doc):
        if not is_leader():
            raise RuntimeError("create() is leader-only in pod mode")
        return self.inner.create(doc)

    def get(self, doc_id):
        return broadcast_obj(
            self.inner.get(doc_id) if is_leader() else None
        )

    def list_open(self):
        return broadcast_obj(
            self.inner.list_open() if is_leader() else None
        )

    def count_open(self):
        # varz-only (worker /debug/state), called from the observe-server
        # probe thread — it must NOT enter a collective: followers never
        # serve debug_state, so a broadcast here would have no matching
        # participants and hang the pod on the first scrape. Leader
        # answers locally; followers report 0 (they hold no queue).
        return self.inner.count_open() if is_leader() else 0


class LeaderSource(MetricSource):
    """Only process 0 performs metric fetches; series are broadcast.

    Every fetch is a collective, so ordering must be deterministic —
    `concurrent_fetch = False` forces the worker's serial fetch loop
    (doc order is broadcast-identical, alias order is config order).
    A leader-side fetch error must not desynchronize the cluster: the
    exception itself is broadcast and re-raised on every process, so
    all of them take the preprocess-failure branch together."""

    concurrent_fetch = False

    def __init__(self, inner: MetricSource | None):
        if is_leader() and inner is None:
            raise ValueError("process 0 needs the real source")
        self.inner = inner

    def fetch(self, url: str):
        if is_leader():
            try:
                out = self.inner.fetch(url)
            except Exception as e:  # noqa: BLE001 — must cross processes
                out = _FetchError(repr(e))
        else:
            out = None
        out = broadcast_obj(out)
        if isinstance(out, _FetchError):
            raise RuntimeError(out.msg)
        return out


class _FetchError:
    def __init__(self, msg: str):
        self.msg = msg


class _ClaimError:
    """Broadcast marker for a leader-side claim failure (see
    `LeaderStore.claim`); `transient` carries the degradation
    classification across processes."""

    def __init__(self, msg: str, transient: bool):
        self.msg = msg
        self.transient = transient


class PodWorker(BrainWorker):
    """BrainWorker for the pod-spanning mode: broadcast tick clock.

    Construct with a LeaderStore/LeaderSource pair and a judge whose
    univariate engine is a ShardedJudge over `make_global_mesh()`. The
    claim set, series, and clock are leader-broadcast, the judgment runs
    SPMD over the global mesh, and only the leader persists results.

    Control-flow-shaping knobs are ALSO leader-broadcast at
    construction: a per-host env skew in the cold-chunk size or the
    arena byte budgets would make processes issue differently-shaped
    judge programs (or one take the stacked-score fallback while its
    peers use the arena) and deadlock the collectives.
    """

    # Knob-level arena interaction only (budget read on the leader,
    # identical set on every host) — no row access involved; pod mode
    # always runs replicated arenas (batch.py:_resolve_arena_shards
    # forces shards=1 when process_count > 1), which trivially honors
    # the row-placement contract.
    # foremast: sharded-arena
    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        from foremast_tpu.engine.arena import (
            _arena_bytes,
            _arena_max_bytes,
            set_arena_budget,
        )
        from foremast_tpu.engine.scoring import (
            bf16_delta_enabled,
            set_bf16_delta,
        )

        knobs = broadcast_obj(
            (
                self.cold_chunk_docs,
                self.pipeline_depth,
                self.fetch_workers,
                _arena_bytes(),
                _arena_max_bytes(),
                bf16_delta_enabled(),
            )
            if is_leader()
            else None
        )
        # the per-tick deadline (ISSUE 9 partial-tick release) decides
        # per-doc control flow off a LOCAL wall clock: two processes
        # disagreeing on "past the budget" would judge differently-
        # shaped batches into one SPMD program and deadlock the
        # collectives. Until the release decision is leader-broadcast,
        # pod mode runs unbudgeted (the pod watchdog still bounds a
        # wedged collective via FOREMAST_POD_TIMEOUT_SECONDS).
        self._degrade.tick_budget_seconds = 0.0
        # Sliced sweeps (ISSUE 15) stay OFF in pod mode for the same
        # class of reason: slice boundaries, dirty promotion, and the
        # warm pipeline's prefetch-thread fetches are process-local
        # control flow (and LeaderSource fetches are ordered
        # collectives that must never run off the tick thread). Every
        # process runs the monolithic tick body.
        self.sweep_slice_docs = 0
        if knobs is not None and not is_leader():
            self.cold_chunk_docs = knobs[0]
            # pipeline depth/pool size are broadcast for completeness:
            # LeaderSource forces the serial (depth-1) path regardless,
            # but no control-flow-shaping knob may ever skew per host
            self.pipeline_depth = knobs[1]
            self.fetch_workers = knobs[2]
            # explicit process-local overrides, NOT os.environ writes:
            # mutating the env after threads exist is a cross-thread
            # race, and a per-host skew in either knob would dispatch
            # f32 fits on one process and bf16-delta fits on its peers —
            # differently-shaped SPMD programs over the shared mesh
            set_arena_budget(knobs[3], knobs[4])
            set_bf16_delta(knobs[5])

    def tick(self, now: float | None = None) -> int:
        if now is None:
            now = broadcast_obj(time.time() if is_leader() else None)
        return super().tick(now=now)
