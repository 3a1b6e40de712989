"""Sliced, preemptible sweeps (ISSUE 15): parity, preemption triage,
and the preemption × degradation interactions.

The contracts pinned here:

  * a SLICED sweep's statuses are byte-identical to a monolithic
    sweep's on identical fleets (the acceptance parity arm);
  * slice-boundary preemption triages arrivals correctly — pooled
    docs PROMOTE into the next slice, arrivals for docs outside the
    sweep's claim run a NESTED micro-tick between slices, in-flight
    collisions requeue at the front with their original stamps;
  * a micro-tick preempting a slice composes with tick-budget
    release: the nested cycle restores the sweep's deadline, the
    expired remainder releases in one bulk write, and every claimed
    doc is judged exactly once OR released — never both, never twice;
  * write-behind entries buffered by slice writes are stamped at the
    SWEEP's claim instant (not the write failure, not a nested
    micro's claim) and replay exactly once across a store brownout
    that begins mid-sweep.

Plus the ChunkPipeline extensions the sweep rides on: lazy chunk
iterators with the END sentinel, the boundary hook, and on_drained.
"""

from __future__ import annotations

import threading
import time

import numpy as np
import pytest

from benchmarks.latency_bench import _statuses, build_fleet, mk_worker
from foremast_tpu.chaos.degrade import (
    REASON_DEADLINE,
    REASON_FETCH,
)
from foremast_tpu.jobs import pipeline as pl
from foremast_tpu.jobs.models import (
    STATUS_COMPLETED_UNHEALTH,
    STATUS_PREPROCESS_COMPLETED,
    TERMINAL_STATUSES,
)
from foremast_tpu.reactive import DirtySet

NOW = int(time.time())


class _CountingStore:
    """Wraps a store: counts per-doc writes, optional per-call claim
    hook (fires AFTER the claim — the deterministic way to land dirty
    marks mid-sweep, past the catch-all take_all), and an injectable
    transient write fault."""

    def __init__(self, inner):
        self.inner = inner
        self.writes: dict[str, int] = {}
        self.on_claim = None
        self.fail_writes = False
        self.write_attempts = 0
        self._lock = threading.Lock()

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def claim(self, *a, **kw):
        docs = self.inner.claim(*a, **kw)
        hook, self.on_claim = self.on_claim, None
        if hook is not None:
            hook(docs)
        return docs

    def _count(self, docs):
        with self._lock:
            for d in docs:
                self.writes[d.id] = self.writes.get(d.id, 0) + 1

    def update(self, doc):
        with self._lock:
            self.write_attempts += 1
        if self.fail_writes:
            raise ConnectionError("injected store brownout")
        doc = self.inner.update(doc)
        self._count([doc])
        return doc

    def update_many(self, docs):
        with self._lock:
            self.write_attempts += 1
        if self.fail_writes:
            raise ConnectionError("injected store brownout")
        self.inner.update_many(docs)
        self._count(docs)


def _sliced_worker(services, slice_docs=8, dirty=None, claim_limit=None):
    store, ring, keys, ht, ct = build_fleet(services, NOW)
    wrapped = _CountingStore(store)
    w = mk_worker(wrapped, ring, services, dirty=dirty)
    if claim_limit is not None:
        w.claim_limit = claim_limit
    w.sweep_slice_docs = slice_docs
    return w, wrapped, store, ring, keys, ct


# -- parity ----------------------------------------------------------------


def test_sliced_vs_monolithic_byte_parity():
    """Cold + warm + spiked sweeps: statuses byte-identical between
    the monolithic arm and the sliced arm (the pack/dispatch/decode
    helpers are shared, so parity is by construction — this pins it)."""
    wa, _, sa, ring_a, keys_a, ct = _sliced_worker(24, slice_docs=0)
    wb, _, sb, ring_b, keys_b, _ = _sliced_worker(24, slice_docs=8)
    assert not wa._sweep_sliceable() and wb._sweep_sliceable()
    now = float(NOW)
    assert wa.tick(now=now) == 24
    assert wb.tick(now=now) == 24
    assert _statuses(sa) == _statuses(sb)
    spike = np.full(3, 40.0, np.float32)
    for ring, keys in ((ring_a, keys_a), (ring_b, keys_b)):
        ring.push(keys[3], ct[-3:], spike, now=now)
    assert wa.tick(now=now + 60) == 24
    assert wb.tick(now=now + 60) == 24
    a, b = _statuses(sa), _statuses(sb)
    assert a == b
    assert a["job-3"][0] == STATUS_COMPLETED_UNHEALTH
    assert wb._last_sweep["slices"] == 3
    wa.close()
    wb.close()


# -- preemption triage -----------------------------------------------------


def test_boundary_promotes_pooled_doc():
    """An arrival for a claimed-but-unfetched doc promotes its slice
    to the front: the sweep itself delivers the verdict, the arrival
    is attributed through the sweep ledger, and the dirty set counts
    the promotion."""
    dirty = DirtySet(max_keys=64)
    w, cs, store, ring, keys, ct = _sliced_worker(32, 8, dirty=dirty)
    now = float(NOW)
    assert w.tick(now=now) == 32  # cold: fits cached

    # spike the LAST pool doc's series, and mark it dirty AFTER the
    # sweep's claim (mid-sweep arrival, past the catch-all drain)
    def on_claim(_docs):
        ring.push(keys[31], ct[-3:], np.full(3, 40.0, np.float32), now=now)
        dirty.mark_series(keys[31], now=now)

    cs.on_claim = on_claim
    assert w.tick(now=now + 60) == 32
    sweep = w._last_sweep
    assert sweep["promoted"] >= 1, sweep
    assert sweep["preempt_microticks"] == 0, sweep
    assert dirty.counts()["promoted"] >= 1
    assert store._docs["job-31"].status == STATUS_COMPLETED_UNHEALTH
    assert len(dirty) == 0  # consumed, not requeued
    w.close()


def test_boundary_microtick_judges_unclaimed_doc():
    """An arrival for a doc OUTSIDE the sweep's claim (bounded
    claim_limit) runs a nested micro-tick between slices — the doc is
    judged DURING the sweep, not after it."""
    dirty = DirtySet(max_keys=64)
    w, cs, store, ring, keys, ct = _sliced_worker(
        32, 8, dirty=dirty, claim_limit=24
    )
    now = float(NOW)
    # the 24-doc claim cap leaves the insertion-order tail (job-24..31)
    # permanently outside the sweep's claim — exactly the docs only a
    # micro-tick can reach mid-sweep
    assert w.tick(now=now) == 24

    judged_mid_sweep = {}

    def on_claim(docs):
        claimed = {d.id for d in docs}
        # job-31 re-checks but was NOT claimed by this sweep iff the
        # claim cap bit it; pick any unclaimed doc deterministically
        victim = next(
            f"job-{s}" for s in range(31, -1, -1)
            if f"job-{s}" not in claimed
        )
        s = int(victim.split("-")[1])
        ring.push(keys[s], ct[-3:], np.full(3, 40.0, np.float32), now=now)
        dirty.mark_series(keys[s], now=now)
        judged_mid_sweep["id"] = victim

    cs.on_claim = on_claim
    assert w.tick(now=now + 60) > 0
    sweep = w._last_sweep
    assert sweep["preempt_microticks"] >= 1, sweep
    assert sweep["preempt_docs"] >= 1, sweep
    assert (
        store._docs[judged_mid_sweep["id"]].status
        == STATUS_COMPLETED_UNHEALTH
    )
    w.close()


# -- preemption x degradation ---------------------------------------------


def test_microtick_preempts_then_budget_release():
    """A sweep whose budget expires after the first boundary: the
    nested micro-tick runs (and restores the sweep's deadline), the
    pooled remainder releases in ONE bulk write with
    reason=deadline_released, and every claimed doc is judged exactly
    once or released — never both."""
    dirty = DirtySet(max_keys=64)
    w, cs, store, ring, keys, ct = _sliced_worker(
        32, 8, dirty=dirty, claim_limit=24
    )
    now = float(NOW)
    assert w.tick(now=now) == 24

    # burn the budget inside slice 1's prepare: the fetch hook sleeps
    # past the budget, so every LATER slice's prepare sees an expired
    # deadline and drains the pool as one release bundle
    w._degrade.tick_budget_seconds = 0.05
    orig_fetch = w.source.fetch
    slept = []

    def slow_fetch(url):
        if not slept:
            slept.append(1)
            time.sleep(0.12)
        return orig_fetch(url)

    w.source.fetch = slow_fetch

    def on_claim(docs):
        claimed = {d.id for d in docs}
        victim = next(
            f"job-{s}" for s in range(31, -1, -1)
            if f"job-{s}" not in claimed
        )
        s = int(victim.split("-")[1])
        dirty.mark_series(keys[s], now=now)

    cs.on_claim = on_claim
    before = w._degrade.stats.docs_snapshot().get(REASON_DEADLINE, 0)
    n = w.tick(now=now + 60)
    sweep = w._last_sweep
    released = (
        w._degrade.stats.docs_snapshot().get(REASON_DEADLINE, 0) - before
    )
    # the nested micro ran, the sweep's own deadline survived it, and
    # the remainder released; judged + released covers the claim with
    # no overlap (exactly-once)
    assert sweep["preempt_microticks"] >= 1, sweep
    assert released > 0, (sweep, released)
    # every claimed doc is accounted exactly once: judged slices +
    # the one bulk deadline release cover the whole 24-doc claim
    # (n counts both; the released remainder is 24 - judged)
    assert n == 24, (n, released, sweep)
    open_docs = [
        d for d in store._docs.values()
        if d.status == STATUS_PREPROCESS_COMPLETED
    ]
    assert len(open_docs) >= released  # released docs stay claimable
    w.close()


def test_write_behind_replay_across_slice_boundary():
    """A store brownout beginning mid-sweep: slice writes buffer into
    write-behind — stamped at the SWEEP's claim instant — and replay
    exactly once when the store heals, original stamps preserved."""
    w, cs, store, ring, keys, ct = _sliced_worker(32, 8)
    now = float(NOW)
    assert w.tick(now=now) == 32  # cold, store healthy

    claim_stamp = []

    def on_claim(_docs):
        cs.fail_writes = True  # brownout begins AFTER the claim
        claim_stamp.append(w._tick_claim_mono)

    cs.on_claim = on_claim
    writes_before = dict(cs.writes)
    assert w.tick(now=now + 60) == 32
    buf = w._degrade.write_behind
    assert len(buf) == 32, len(buf)
    # every buffered entry is stamped at the sweep's claim instant —
    # NOT the (later) write-failure instant; the exactly-once age
    # window measures from the claim
    with buf._lock:
        stamps = [at for at, _ in buf._entries]
    assert all(at == claim_stamp[0] for at in stamps), stamps
    assert cs.writes == writes_before  # nothing landed during brownout

    cs.fail_writes = False  # store heals; next tick replays FIRST
    assert w.tick(now=now + 120) == 32
    assert len(buf) == 0
    # each doc's buffered verdict landed exactly once (one replay
    # bulk write) plus the healed tick's own judgment write
    assert all(
        cs.writes[d] - writes_before.get(d, 0) == 2
        for d in (f"job-{s}" for s in range(32))
    ), cs.writes
    replayed = w._degrade.stats.docs_snapshot().get("write_replayed", 0)
    assert replayed == 32, replayed
    w.close()


def test_chaos_store_brownout_mid_sweep_exactly_once():
    """Brownout that begins between slices (first slice lands, the
    rest buffer): the ledger stays exactly-once — every doc's verdict
    is written exactly once for that sweep, split between direct
    writes and the replay."""
    w, cs, store, ring, keys, ct = _sliced_worker(32, 8)
    now = float(NOW)
    assert w.tick(now=now) == 32

    flipped = []
    orig_update_many = cs.inner.update_many

    def tripwire(docs):
        # heal-side counter: flip the fault after the FIRST slice's
        # bulk write lands
        orig_update_many(docs)
        if not flipped:
            flipped.append(1)
            cs.fail_writes = True

    cs.inner.update_many = tripwire
    writes_before = dict(cs.writes)
    assert w.tick(now=now + 60) == 32
    cs.inner.update_many = orig_update_many
    buf = w._degrade.write_behind
    assert 0 < len(buf) < 32  # some landed, some buffered
    buffered = len(buf)
    cs.fail_writes = False
    assert w.tick(now=now + 120) == 32
    assert len(buf) == 0
    for s in range(32):
        doc_id = f"job-{s}"
        delta = cs.writes[doc_id] - writes_before.get(doc_id, 0)
        # 1 write for the brownout sweep (direct or replayed) + 1 for
        # the healed sweep — never a double write
        assert delta == 2, (doc_id, delta, buffered)
    w.close()


# -- ChunkPipeline extensions ---------------------------------------------


def _pool():
    from concurrent.futures import ThreadPoolExecutor

    return ThreadPoolExecutor(max_workers=1)


def test_pipeline_lazy_iterator_end_sentinel():
    """run() over an unbounded iterator stops at the first END payload
    from fetch, in both serial and pipelined modes, and counts only
    the real chunks."""
    import itertools

    for pool in (None, _pool()):
        seen = []
        budget = [4]

        def fetch(i):
            if budget[0] <= 0:
                return pl.END
            budget[0] -= 1
            return f"payload-{i}"

        pipe = pl.ChunkPipeline(
            fetch,
            lambda i, p: (i, p),
            lambda i, r: seen.append(r),
            depth=2,
            prefetch_pool=pool,
        )
        stats = pipe.run(itertools.count())
        assert len(seen) == 4, seen
        assert stats.chunks == 4
        assert stats.completed
        budget[0] = 4
        if pool is not None:
            pool.shutdown()


def test_pipeline_real_payload_queued_behind_end_still_judged():
    """Depth >= 3 runs 2+ concurrent prefetch workers: a fully
    prepared chunk can be QUEUED BEHIND the END that raced it for the
    pool's last items. END must stop SUBMISSION, not abandon already-
    prepared work to the abort drain (that would silently release a
    healthy sweep's claimed slice every sweep)."""
    import itertools
    from concurrent.futures import ThreadPoolExecutor

    judged = []
    drained = []
    payloads = {0: pl.END, 1: "prep-1"}
    pool = ThreadPoolExecutor(max_workers=2)
    pipe = pl.ChunkPipeline(
        lambda i: payloads.get(i, pl.END),
        lambda i, p: p,
        lambda i, r: judged.append(r),
        depth=3,
        prefetch_pool=pool,
        on_drained=lambda i, p: drained.append(p),
    )
    stats = pipe.run(itertools.count())
    assert judged == ["prep-1"], (judged, drained)
    assert drained == []
    assert stats.completed
    pool.shutdown()


def test_pipeline_boundary_hook_runs_between_chunks():
    boundaries = []
    for pool in (None, _pool()):
        boundaries.clear()
        order = []
        pipe = pl.ChunkPipeline(
            lambda c: c,
            lambda c, p: order.append(("judge", c)) or c,
            lambda c, r: None,
            depth=2,
            prefetch_pool=pool,
            boundary=lambda: boundaries.append(len(order)),
        )
        pipe.run([1, 2, 3])
        assert boundaries == [1, 2, 3]  # after each chunk's judgment
        if pool is not None:
            pool.shutdown()


def test_pipeline_on_drained_gets_unjudged_prefetches():
    """A judge abort drains completed-but-unjudged prefetches through
    on_drained so a side-effecting fetch stage can give work back."""
    drained = []
    prefetching_2 = threading.Event()

    def fetch(c):
        if c == 2:
            prefetching_2.set()
        return f"prep-{c}"

    def judge(c, p):
        if c == 1:
            # abort only once chunk 2's prefetch is under way: one not
            # yet started is cancelled on the abort and owes nothing
            assert prefetching_2.wait(10)
            raise RuntimeError("boom")
        return p

    pool = _pool()
    pipe = pl.ChunkPipeline(
        fetch,
        judge,
        lambda c, r: None,
        depth=3,
        prefetch_pool=pool,
        on_drained=lambda c, p: drained.append((c, p)),
    )
    with pytest.raises(RuntimeError):
        pipe.run([1, 2, 3])
    # chunk 1 aborted the run; at depth 3 chunk 2 (and possibly 3) was
    # already prefetched and must drain through on_drained
    assert (2, "prep-2") in drained, drained
    pool.shutdown()


def test_sweep_abort_releases_pooled_and_prepared_docs():
    """A judge-stage death mid-sweep: prepared-but-unjudged slices and
    the un-sliced pool remainder release un-judged (claimable again),
    never parked behind the stuck-takeover window."""
    w, cs, store, ring, keys, ct = _sliced_worker(32, 8)
    now = float(NOW)
    assert w.tick(now=now) == 32  # warm the fits

    calls = []
    orig = w._uni.judge_columnar_async

    def dying(*a, **kw):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("device died")
        return orig(*a, **kw)

    w._uni.judge_columnar_async = dying
    with pytest.raises(RuntimeError):
        w.tick(now=now + 60)
    w._uni.judge_columnar_async = orig
    # nothing may be left in preprocess_inprogress: slice 1 judged,
    # slice 2 released via the StageError partial, prepared slice 3 +
    # the pool remainder released via on_drained / the sweep finally
    stuck = [
        d.id for d in store._docs.values()
        if d.status not in TERMINAL_STATUSES
        and d.status != STATUS_PREPROCESS_COMPLETED
    ]
    assert stuck == [], stuck
    # and the next sweep judges everything again, cleanly
    assert w.tick(now=now + 120) == 32
    w.close()


# -- ISSUE 30: a slice's programs are issued, then the prefetch is released --
#
# From the recorded spans and the store, never from timings: a fleet of
# all four kinds (bivariate, LSTM-hybrid, univariate, canary: one of each
# in every four docs, so every slice holds both joint dispatch groups and
# both columnar buckets), swept sliced and, on a twin fleet, by the
# monolithic `_fast_tick`.

FLEET_NOW = 1_760_000_000.0
FOUR_KINDS = 16  # docs; slices of 8


def _four_kind_worker(slice_docs, trace_dir=None):
    import dataclasses

    from benchmarks.worker_bench import ArraySource, _add_joint_service
    from foremast_tpu.config import BrainConfig
    from foremast_tpu.jobs import BrainWorker
    from foremast_tpu.jobs.store import InMemoryStore
    from prometheus_client import CollectorRegistry

    from foremast_tpu.observe.spans import Tracer

    rng = np.random.default_rng(31)  # a draw on which no clean doc goes terminal
    store, source = InMemoryStore(), ArraySource()
    t_now = int(FLEET_NOW)
    ht = t_now - 86_400 * 7 + 60 * np.arange(256, dtype=np.int64)
    ct = ht[-1] + 60 + 60 * np.arange(30, dtype=np.int64)
    end_time = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(t_now + 3600))
    for s in range(FOUR_KINDS):
        f = (2, 4, 1, 1)[s % 4]
        doc_id = _add_joint_service(
            store, source, str(s), ht, ct, f, end_time, rng
        )
        if s % 4 == 3:
            # a canary: the same window again as its baseline pods'
            doc = store.get(doc_id)
            base_url = f"http://prom/base?q=m0:app{s}&step=60"
            _, cv = source.data[f"http://prom/cur?q=m0:app{s}&step=60"]
            source.data[base_url] = (ct - 3600, cv.copy())
            doc.baseline_config = f"m0== {base_url}"
            doc.strategy = "canary"
    cfg = BrainConfig(
        algorithm="auto", season_steps=24, max_cache_size=8 * FOUR_KINDS
    )
    cfg = dataclasses.replace(
        cfg, anomaly=dataclasses.replace(cfg.anomaly, threshold=4.0)
    )
    tracer = None
    if trace_dir is not None:
        tracer = Tracer(
            service="test", registry=CollectorRegistry(),
            trace_dir=str(trace_dir), buffer_size=1 << 14,
        )
    w = BrainWorker(
        store, source, config=cfg, claim_limit=2 * FOUR_KINDS,
        worker_id="issue30-w", tracer=tracer,
    )
    w.judge.lstm_steps = 10  # CI speed; identical on both workers
    w.sweep_slice_docs = slice_docs
    w.pipeline_depth = 2
    assert w.tick(now=FLEET_NOW + 150) == FOUR_KINDS  # cold: fits
    assert w.tick(now=FLEET_NOW + 200) == FOUR_KINDS  # warm: compiles
    return w, store, source, tracer


def _spike(source, sid, f):
    """The last 3 points of every metric moved by 0.6; a 2-metric doc's
    metrics move APART (the bivariate kind follows a common excursion)."""
    for m in range(f):
        url = f"http://prom/cur?q=m{m}:app{sid}&step=60"
        ct, cv = source.data[url]
        spiked = cv.copy()
        spiked[-3:] += -0.6 if (f == 2 and m == 1) else 0.6
        source.data[url] = (ct, spiked)


def _written(store):
    return {
        d.id: (d.status, d.status_code, d.reason, d.anomaly_info)
        for d in store._docs.values()
    }


@pytest.fixture(scope="module")
def four_kind_sweeps(tmp_path_factory):
    """The sweep under test, sliced and traced (A) and monolithic (B), on
    twin fleets with one doc of each kind spiked in each slice."""
    a, a_store, a_src, tracer = _four_kind_worker(
        8, tmp_path_factory.mktemp("issue30")
    )
    b, b_store, b_src, _ = _four_kind_worker(0)
    assert a._sweep_sliceable() and not b._sweep_sliceable()
    for src in (a_src, b_src):
        for sid, f in ((0, 2), (1, 4), (2, 1), (3, 1), (9, 4), (12, 2)):
            _spike(src, sid, f)
    before = tracer.ring.total
    assert a.tick(now=FLEET_NOW + 260) == FOUR_KINDS
    events = tracer.ring.snapshot()[-(tracer.ring.total - before):]
    assert b.tick(now=FLEET_NOW + 260) == FOUR_KINDS
    out = {
        "events": sorted(events, key=lambda e: e["ts"]),
        "sliced": _written(a_store),
        "monolithic": _written(b_store),
        "last_sweep": dict(a._last_sweep),
        "fast_kinds": (dict(a._fast_kinds), dict(b._fast_kinds)),
    }
    a.close()
    b.close()
    return out


def _by_slice(events):
    """The tick thread's `judge.score` and `judge.decode` events of each
    slice: those between its `pipeline.wait_prefetch` and the next."""
    waits = [e for e in events if e["name"] == "pipeline.wait_prefetch"]
    tick_tid = waits[0]["tid"]
    out = {}
    for e in events:
        if e["tid"] != tick_tid or e["name"] not in (
            "judge.score", "judge.decode"
        ):
            continue
        k = max(w["args"]["slice"] for w in waits if w["ts"] <= e["ts"])
        out.setdefault(k, {"judge.score": [], "judge.decode": []})[
            e["name"]
        ].append(e)
    return out


def _end(e):
    return e["ts"] + e["dur"]


def test_every_program_of_a_slice_is_issued_before_one_is_gathered(
    four_kind_sweeps,
):
    slices = _by_slice(four_kind_sweeps["events"])
    assert sorted(slices) == [0, 1]
    for k, spans in slices.items():
        # bivariate, LSTM, univariate, canary: four issues; the two joint
        # groups are gathered on this thread (the buckets': the writer)
        assert len(spans["judge.score"]) == 4, k
        assert len(spans["judge.decode"]) == 2, k
        last_issue = max(_end(e) for e in spans["judge.score"])
        assert last_issue <= min(e["ts"] for e in spans["judge.decode"]), k
    # the writer's gathers of a slice's buckets come after its issues too
    tick_tid = slices[0]["judge.score"][0]["tid"]
    writer = [
        e for e in four_kind_sweeps["events"]
        if e["name"] == "judge.decode" and e["tid"] != tick_tid
    ]
    assert len(writer) == 4
    for k in (0, 1):
        last_issue = max(_end(e) for e in slices[k]["judge.score"])
        assert all(last_issue <= e["ts"] for e in writer[2 * k: 2 * k + 2])


def test_next_slices_admission_opens_after_the_last_issue(four_kind_sweeps):
    """The prefetch of slice k+1 is submitted from inside slice k's
    judgment, once its programs are in flight: `worker.admit` of k+1
    opens after slice k's last `judge.score` closes (and the pack,
    `joint_prep` and `h2d` before it have run alone)."""
    events = four_kind_sweeps["events"]
    slices = _by_slice(events)
    admits = [e for e in events if e["name"] == "worker.admit"]
    assert len(admits) == 2  # the END probe admits nothing
    last_issue_0 = max(_end(e) for e in slices[0]["judge.score"])
    assert admits[1]["ts"] >= last_issue_0
    host_alone = [
        e for e in events
        if e["name"] in ("worker.pack_joint", "judge.joint_prep", "judge.h2d")
        and e["ts"] < last_issue_0
    ]
    assert host_alone and all(_end(e) <= admits[1]["ts"] for e in host_alone)
    waits = [
        (e["args"]["slice"], e["args"]["at_issue"])
        for e in events if e["name"] == "pipeline.wait_prefetch"
    ]
    assert waits == [(0, 0), (1, 1), (2, 1)]
    pipe = four_kind_sweeps["last_sweep"]["pipeline"]
    assert pipe["completed"] and pipe["released_at_issue"] == 2
    assert four_kind_sweeps["last_sweep"]["slices"] == 2


def test_released_sweep_writes_what_the_monolithic_tick_writes(
    four_kind_sweeps,
):
    """Status, code, reason and anomaly payload of every doc, byte for
    byte, against `_fast_tick` on the twin fleet."""
    import json

    a, b = four_kind_sweeps["sliced"], four_kind_sweeps["monolithic"]
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    unhealthy = {
        k for k, v in a.items() if v[0] == STATUS_COMPLETED_UNHEALTH
    }
    # both joint kinds in both slices went through a payload
    assert {"job-0", "job-1", "job-9", "job-12"} <= unhealthy
    assert set(a["job-1"][3]["values"]) == {"m0", "m1", "m2", "m3"}
    fa, fb = four_kind_sweeps["fast_kinds"]
    assert fa == fb
    assert all(fa[k] for k in ("bivariate", "lstm", "univariate", "baseline"))


def test_issue_that_raises_on_the_second_group_writes_no_joint_doc():
    """The first slice's second joint group dies at issue: nothing of
    either group is decided or written, the slice's every doc and the
    rest of the sweep are released un-judged, and the next slice's
    prefetch was never submitted."""
    from foremast_tpu.chaos.degrade import REASON_ABORT

    w, store, source, _ = _four_kind_worker(8)
    _spike(source, 0, 2)  # would go terminal if its group were decided
    before = _written(store)
    orig = w._mvj.joint_columnar_issue
    calls = []

    def dying(mode, *a, **kw):
        calls.append(mode)
        if len(calls) == 2:
            raise RuntimeError("device died")
        return orig(mode, *a, **kw)

    w._mvj.joint_columnar_issue = dying
    admitted = []
    orig_admit = w._admit_fast
    w._admit_fast = lambda docs, now: (
        admitted.append(len(docs)) or orig_admit(docs, now)
    )
    aborted = w._degrade.stats.docs_snapshot().get(REASON_ABORT, 0)
    with pytest.raises(RuntimeError, match="device died"):
        w.tick(now=FLEET_NOW + 260)
    w._mvj.joint_columnar_issue = orig
    assert len(calls) == 2 and set(calls) == {"bivariate", "lstm"}
    assert admitted == [8]  # slice 1 was never prepared
    assert _written(store) == before  # all released as they were claimed
    assert (
        w._degrade.stats.docs_snapshot()[REASON_ABORT] - aborted
        == FOUR_KINDS
    )
    assert w._last_sweep["pipeline"]["released_at_issue"] == 0
    # and the next sweep judges everything, the spiked doc included
    assert w.tick(now=FLEET_NOW + 320) == FOUR_KINDS
    assert _written(store)["job-0"][0] == STATUS_COMPLETED_UNHEALTH
    w.close()
