"""Slow-path chunk-pipeline coverage (jobs/pipeline.py + worker rework):
serial-vs-pipelined write equivalence on a mixed warm/cold claim set,
fetch-failure isolation mid-pipeline, clean exception drain, depth-1
degradation for `concurrent_fetch = False` sources, and the persistent
fetch-pool satellites."""

import threading
import time

import numpy as np
import pytest

from benchmarks.worker_bench import _add_service, build_fleet
from foremast_tpu.config import BrainConfig
from foremast_tpu.jobs import BrainWorker
from foremast_tpu.jobs.models import (
    STATUS_PREPROCESS_COMPLETED,
    STATUS_PREPROCESS_FAILED,
    STATUS_PREPROCESS_INPROGRESS,
)

NOW = 1_760_000_000.0
HIST_LEN = 256
CUR_LEN = 30


def _mk(services, chunk_docs=2, depth=2, algorithm="moving_average_all",
        hook=None, seed=0):
    """Worker over a worker_bench fleet, slow path forced (the fast
    path would otherwise consume the warm subset) and the source
    declaring blocking fetches so the pipeline may engage."""
    store, source = build_fleet(services, HIST_LEN, CUR_LEN, NOW, seed=seed)
    # ArraySource is in-memory (concurrent_fetch=False); pose as a
    # blocking source so the worker pools fetches + engages the pipeline
    source.concurrent_fetch = True
    cfg = BrainConfig(algorithm=algorithm, season_steps=24,
                      max_cache_size=4 * services + 64)
    worker = BrainWorker(
        store, source, config=cfg, claim_limit=2 * services,
        worker_id="pipe-w", on_verdict=hook,
    )
    worker.cold_chunk_docs = chunk_docs
    worker.pipeline_depth = depth
    worker._fast_tick = lambda docs, now: (0, docs)  # force slow path
    return worker, store, source


def _grow_fleet(store, source, sids, seed=42):
    """Add fresh (cold) services to an existing fleet, deterministically
    (same seed => identical series across two fleets)."""
    rng = np.random.default_rng(seed)
    t_now = int(NOW)
    ht = t_now - 86_400 * 7 + 60 * np.arange(HIST_LEN, dtype=np.int64)
    ct = ht[-1] + 60 + 60 * np.arange(CUR_LEN, dtype=np.int64)
    end_time = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(t_now + 3600)
    )
    for sid in sids:
        _add_service(store, source, sid, ht, ct, HIST_LEN, CUR_LEN,
                     end_time, rng)


def _statuses(store):
    return {
        d.id: (d.status, d.reason, d.anomaly_info)
        for d in store._docs.values()
    }


def _record_writes(store):
    """Ordered (doc id, status) log of every store write."""
    writes = []
    orig_update, orig_many = store.update, store.update_many

    def _u(doc):
        writes.append((doc.id, doc.status))
        return orig_update(doc)

    def _um(docs):
        writes.extend((d.id, d.status) for d in docs)
        return orig_many(docs)

    store.update, store.update_many = _u, _um
    return writes


def test_pipelined_equals_serial_on_mixed_warm_cold_claims():
    """Tick 1 warms 6 services' fits; 4 cold services join; tick 2's
    claim set is then mixed warm/cold across 5 chunks. The pipelined
    worker must produce the identical statuses, anomaly payloads,
    ordered store-write sequence, verdicts, and fit-cache key set as
    the serial (depth-1) worker."""
    verdicts_a, verdicts_b = [], []
    hook_a = lambda doc, vs: verdicts_a.append(
        (doc.id, [(v.alias, v.verdict) for v in vs])
    )
    hook_b = lambda doc, vs: verdicts_b.append(
        (doc.id, [(v.alias, v.verdict) for v in vs])
    )
    a, a_store, a_src = _mk(6, chunk_docs=2, depth=2, hook=hook_a)
    b, b_store, b_src = _mk(6, chunk_docs=2, depth=1, hook=hook_b)

    assert a.tick(now=NOW + 150) == 6
    assert b.tick(now=NOW + 150) == 6
    assert _statuses(a_store) == _statuses(b_store)

    # cold newcomers (identical on both fleets), plus a current-window
    # spike on a warm doc so anomaly payloads cross the pipeline too
    _grow_fleet(a_store, a_src, ["n0", "n1", "n2", "n3"])
    _grow_fleet(b_store, b_src, ["n0", "n1", "n2", "n3"])
    for src in (a_src, b_src):
        url = next(u for u in src.data if "cur" in u and "latency:app2" in u)
        ct, cv = src.data[url]
        spiked = cv.copy()
        spiked[-3:] = 40.0
        src.data[url] = (ct, spiked)

    writes_a = _record_writes(a_store)
    writes_b = _record_writes(b_store)
    assert a.tick(now=NOW + 200) == 10
    assert b.tick(now=NOW + 200) == 10

    assert a._last_pipeline["pipelined"] is True
    assert a._last_pipeline["chunks"] == 5
    assert b._last_pipeline["pipelined"] is False
    assert _statuses(a_store) == _statuses(b_store)
    assert writes_a == writes_b  # same docs, same statuses, same ORDER
    assert verdicts_a == verdicts_b
    keys_a = sorted(map(str, a._fit_cache._d.keys()))
    keys_b = sorted(map(str, b._fit_cache._d.keys()))
    assert keys_a == keys_b and keys_a
    a.close()
    b.close()


def test_fetch_failure_marks_only_its_doc_mid_pipeline():
    """A fetch blowing up for one doc in a middle chunk must mark ONLY
    that doc preprocess_failed; every other doc (including later
    chunks, already prefetching) judges normally."""
    worker, store, source = _mk(8, chunk_docs=2, depth=2)
    orig_fetch = source.fetch

    def fetch(url):
        if "latency:app5" in url and "cur" in url:
            raise RuntimeError("boom")
        return orig_fetch(url)

    source.fetch = fetch
    assert worker.tick(now=NOW + 150) == 8
    assert worker._last_pipeline["pipelined"] is True
    sts = {d.id: d.status for d in store._docs.values()}
    assert sts.pop("job-5") == STATUS_PREPROCESS_FAILED
    assert store.get("job-5").reason == "metric fetch failed"
    assert all(s == STATUS_PREPROCESS_COMPLETED for s in sts.values())
    worker.close()


def test_judge_exception_drains_cleanly_and_persists_prior_chunks():
    """A judge failure on chunk 3 must: write every chunk judged before
    it (the writer drains its queue), leave later docs claimed-but-
    unjudged, join the writer thread, and leave the worker usable."""
    worker, store, source = _mk(8, chunk_docs=2, depth=2)
    orig_judge = worker.judge.judge
    calls = []

    def judge(tasks):
        calls.append(len(tasks))
        if len(calls) == 3:
            raise RuntimeError("device on fire")
        return orig_judge(tasks)

    worker.judge.judge = judge
    with pytest.raises(RuntimeError, match="device on fire"):
        worker.tick(now=NOW + 150)

    sts = {d.id: d.status for d in store._docs.values()}
    for sid in (0, 1, 2, 3):  # chunks 1-2: judged AND persisted
        assert sts[f"job-{sid}"] == STATUS_PREPROCESS_COMPLETED
    for sid in (4, 5, 6, 7):  # failing chunk onward: never judged
        assert sts[f"job-{sid}"] == STATUS_PREPROCESS_INPROGRESS
    assert len(calls) == 3  # feeding stopped at the failing chunk
    # the abort-path snapshot is surfaced and marked as such
    assert worker._last_pipeline["completed"] is False
    # clean drain: the per-tick writer thread is gone
    assert not [
        t for t in threading.enumerate() if t.name == "foremast-writeback"
    ]
    # the worker survives: chunks 1-2's docs are claimable again and a
    # fresh tick (judge healthy now) processes them through the same
    # pipeline machinery
    assert worker.tick(now=NOW + 200) == 4
    worker.close()


def test_fetch_failures_persist_even_when_judge_crashes():
    """The serial loop persisted a chunk's preprocess_failed markings
    BEFORE judging; the pipeline must not lose them when the judge
    dies on that same chunk — the writer persists the failures first,
    then re-raises the judge error on the tick thread."""
    worker, store, source = _mk(4, chunk_docs=2, depth=2)
    orig_fetch = source.fetch

    def fetch(url):
        if "latency:app2" in url and "cur" in url:  # doc in chunk 2
            raise RuntimeError("boom")
        return orig_fetch(url)

    source.fetch = fetch
    orig_judge = worker.judge.judge
    calls = []

    def judge(tasks):
        calls.append(len(tasks))
        if len(calls) == 2:  # chunk 2 — the one with the failed fetch
            raise RuntimeError("device on fire")
        return orig_judge(tasks)

    worker.judge.judge = judge
    with pytest.raises(RuntimeError, match="device on fire"):
        worker.tick(now=NOW + 150)
    sts = {d.id: d.status for d in store._docs.values()}
    assert sts["job-2"] == STATUS_PREPROCESS_FAILED  # not lost
    assert sts["job-0"] == sts["job-1"] == STATUS_PREPROCESS_COMPLETED
    worker.close()


def test_concurrent_fetch_false_degrades_to_depth_1():
    """Pod-mode LeaderSource (and in-memory sources) declare
    concurrent_fetch=False: fetch ORDER is load-bearing, so the
    pipeline must run the serial loop and never spawn pool threads."""
    worker, store, source = _mk(6, chunk_docs=2, depth=4)
    source.concurrent_fetch = False
    assert worker.tick(now=NOW + 150) == 6
    stats = worker._last_pipeline
    assert stats["pipelined"] is False
    assert stats["chunks"] == 3
    assert worker._fetch_pool is None and worker._prefetch_pool is None
    assert all(
        d.status == STATUS_PREPROCESS_COMPLETED
        for d in store._docs.values()
    )


def test_persistent_fetch_pool_reused_across_ticks(monkeypatch):
    """One pool per worker (FOREMAST_FETCH_WORKERS), not one per chunk
    per tick; FOREMAST_PIPELINE_DEPTH is read at construction; close()
    shuts both pools down and stays idempotent."""
    monkeypatch.setenv("FOREMAST_FETCH_WORKERS", "3")
    monkeypatch.setenv("FOREMAST_PIPELINE_DEPTH", "3")
    store, source = build_fleet(4, HIST_LEN, CUR_LEN, NOW)
    source.concurrent_fetch = True
    worker = BrainWorker(
        store, source, config=BrainConfig(algorithm="moving_average_all",
                                          season_steps=24),
        claim_limit=4, worker_id="pool-w",
    )
    assert worker.fetch_workers == 3
    assert worker.pipeline_depth == 3
    worker.cold_chunk_docs = 2
    worker._fast_tick = lambda docs, now: (0, docs)
    assert worker.tick(now=NOW + 150) == 4
    pool = worker._fetch_pool
    assert pool is not None and pool._max_workers == 3
    assert worker._prefetch_pool is not None
    assert worker.tick(now=NOW + 160) == 4
    assert worker._fetch_pool is pool  # reused, not rebuilt
    worker.close()
    assert worker._fetch_pool is None and worker._prefetch_pool is None
    worker.close()  # idempotent


# -- ChunkPipeline unit-level drain semantics ---------------------------


def _pipe(fetch, judge, write, depth=2):
    from concurrent.futures import ThreadPoolExecutor

    from foremast_tpu.jobs.pipeline import ChunkPipeline

    pool = ThreadPoolExecutor(max_workers=max(1, depth - 1))
    return ChunkPipeline(fetch, judge, write, depth=depth,
                         prefetch_pool=pool), pool


def test_pipeline_write_error_propagates_and_stops_feeding():
    written = []

    def write(chunk, result):
        if result == 2:
            raise ValueError("store down")
        written.append(result)

    pipe, pool = _pipe(lambda c: c, lambda c, p: p, write)
    with pytest.raises(ValueError, match="store down"):
        pipe.run([1, 2, 3, 4, 5])
    pool.shutdown(wait=True)
    # FIFO writer: chunk 1 landed, chunk 2 failed, later chunks drain
    # unwritten — fail fast exactly where the serial loop would stop
    assert written == [1]


def test_pipeline_fetch_error_surfaces_after_draining_writes():
    written = []

    def fetch(chunk):
        if chunk == 3:
            raise RuntimeError("fetch exploded")
        return chunk

    pipe, pool = _pipe(fetch, lambda c, p: p, lambda c, r: written.append(r))
    with pytest.raises(RuntimeError, match="fetch exploded"):
        pipe.run([1, 2, 3, 4])
    pool.shutdown(wait=True)
    assert written == [1, 2]  # everything judged before the failure


def test_pipeline_stage_error_writes_partial_and_aborts():
    """StageError from the judge: feeding stops immediately (no later
    chunk touches the broken judge), the carried partial result still
    rides the writer queue, and the wrapped error propagates."""
    from foremast_tpu.jobs.pipeline import StageError

    written, judged = [], []

    def judge(chunk, payload):
        judged.append(chunk)
        if chunk == 2:
            raise StageError(RuntimeError("dead"), ("partial", chunk))
        return payload

    pipe, pool = _pipe(lambda c: c, judge,
                       lambda c, r: written.append(r), depth=2)
    with pytest.raises(RuntimeError, match="dead"):
        pipe.run([1, 2, 3, 4])
    pool.shutdown(wait=True)
    assert judged == [1, 2]
    assert written == [1, ("partial", 2)]


def test_pipeline_stats_account_stages():
    pipe, pool = _pipe(lambda c: c, lambda c, p: p, lambda c, r: None,
                       depth=3)
    stats = pipe.run([1, 2, 3, 4])
    pool.shutdown(wait=True)
    assert stats.pipelined is True
    assert stats.chunks == 4
    assert stats.wall_seconds > 0
    d = stats.as_dict()
    assert d["depth"] == 3
    assert 0.0 <= d["overlap_ratio"] < 1.0
    # serial fallback: single chunk
    stats1 = pipe.run([1])
    assert stats1.pipelined is False


# -- a judge that owns the release (ISSUE 30) ---------------------------
#
# Built with `judge_releases=True`; the cases above, built without it,
# pin the cold path's order (the successor is submitted BEFORE the
# judgment starts).


class _RecordingPool:
    """A prefetch pool that notes, on the submitting thread, which chunk
    each submit carries: what has been SUBMITTED is then a fact of the
    tick thread's own order, not of thread scheduling."""

    def __init__(self, workers=1):
        from concurrent.futures import ThreadPoolExecutor

        self.pool = ThreadPoolExecutor(max_workers=workers)
        self.submitted = []

    def submit(self, fn, chunk):
        self.submitted.append(chunk)
        return self.pool.submit(fn, chunk)

    def shutdown(self):
        self.pool.shutdown(wait=True)


def _releasing_pipe(fetch, judge, write, depth=2, **kw):
    from foremast_tpu.jobs.pipeline import ChunkPipeline

    pool = _RecordingPool(max(1, depth - 1))
    pipe = ChunkPipeline(
        fetch, judge, write, depth=depth, prefetch_pool=pool,
        judge_releases=True, **kw,
    )
    return pipe, pool


def _wait_prefetch_spans(run, trace_dir):
    """Run `run()` under a tracer; the `pipeline.wait_prefetch` events'
    (slice, at_issue), in order."""
    from prometheus_client import CollectorRegistry

    from foremast_tpu.observe.spans import Tracer

    tracer = Tracer(
        service="pipe-test", registry=CollectorRegistry(),
        trace_dir=str(trace_dir),
    )
    with tracer.span("root"):
        run()
    return [
        (e["args"]["slice"], e["args"]["at_issue"])
        for e in tracer.ring.snapshot()
        if e["name"] == "pipeline.wait_prefetch"
    ]


def test_release_is_what_submits_the_successor(tmp_path):
    """Inside the judge of chunk k, chunk k+1 has not been submitted, so
    its fetch has not started, until `release()` is called; after the
    call it has been, and it starts."""
    started = {c: threading.Event() for c in (1, 2, 3)}
    seen = []

    def fetch(chunk):
        started[chunk].set()
        return chunk

    def judge(chunk, payload, release):
        nxt = chunk + 1
        before = list(pool.submitted)
        began = nxt in started and started[nxt].is_set()
        release()
        if nxt in started:
            assert started[nxt].wait(10)  # it runs while we still judge
        seen.append((chunk, before, began, list(pool.submitted)))
        return payload

    written = []
    pipe, pool = _releasing_pipe(fetch, judge, lambda c, r: written.append(r))
    spans = _wait_prefetch_spans(lambda: pipe.run([1, 2, 3]), tmp_path)
    pool.shutdown()
    assert seen == [
        (1, [1], False, [1, 2]),
        (2, [1, 2], False, [1, 2, 3]),
        (3, [1, 2, 3], False, [1, 2, 3]),  # nothing left to submit
    ]
    assert written == [1, 2, 3]
    stats = pipe.last_stats
    assert stats.completed and stats.released_at_issue == 2
    assert stats.as_dict()["released_at_issue"] == 2
    # the first chunk was submitted before any judgment, the others from
    # inside one
    assert spans == [(0, 0), (1, 1), (2, 1)]


@pytest.mark.parametrize("depth", [2, 3])
def test_a_judge_that_never_releases_still_gets_every_chunk(depth, tmp_path):
    """The pipeline makes the refill itself once such a judge returns:
    every chunk is fetched, judged and written, in order; none counts as
    released at issue."""
    judged, written, at_return = [], [], []

    def judge(chunk, payload, release):
        judged.append(chunk)
        at_return.append(list(pool.submitted))
        return payload * 10

    pipe, pool = _releasing_pipe(
        lambda c: c, judge, lambda c, r: written.append((c, r)), depth=depth
    )
    spans = _wait_prefetch_spans(
        lambda: pipe.run([1, 2, 3, 4, 5]), tmp_path
    )
    pool.shutdown()
    assert judged == [1, 2, 3, 4, 5]
    assert written == [(c, 10 * c) for c in judged]
    assert pool.submitted == [1, 2, 3, 4, 5]
    # while chunk k is judged, only the depth - 1 before it run ahead
    assert at_return[0] == list(range(1, depth))
    assert pipe.last_stats.released_at_issue == 0
    assert spans == [(i, 0) for i in range(5)]


def test_release_twice_submits_once():
    def judge(chunk, payload, release):
        release()
        release()
        return payload

    written = []
    pipe, pool = _releasing_pipe(
        lambda c: c, judge, lambda c, r: written.append(r)
    )
    stats = pipe.run([1, 2, 3, 4])
    pool.shutdown()
    assert pool.submitted == [1, 2, 3, 4]  # each once
    assert written == [1, 2, 3, 4]
    assert stats.released_at_issue == 3


@pytest.mark.parametrize("released_first", [False, True])
def test_stage_error_submits_nothing_further(released_first):
    """A judge that dies submits no further chunk: one that had not yet
    released leaves its successor unsubmitted; one that had leaves it to
    the drain (`on_drained`), and nothing after it. The partial result is
    written either way."""
    from foremast_tpu.jobs.pipeline import StageError

    written, drained = [], []
    fetched_3 = threading.Event()

    def fetch(chunk):
        if chunk == 3:
            fetched_3.set()
        return f"prep-{chunk}"

    def judge(chunk, payload, release):
        if chunk == 2:
            if released_first:
                release()
                # one not yet started would be cancelled by the drain
                assert fetched_3.wait(10)
            raise StageError(RuntimeError("dead"), ("partial", chunk))
        release()
        return payload

    pipe, pool = _releasing_pipe(
        fetch, judge, lambda c, r: written.append(r),
        on_drained=lambda c, p: drained.append((c, p)),
    )
    with pytest.raises(RuntimeError, match="dead"):
        pipe.run([1, 2, 3, 4])
    pool.shutdown()
    assert written == ["prep-1", ("partial", 2)]
    if released_first:
        assert pool.submitted == [1, 2, 3]
        assert drained == [(3, "prep-3")]
    else:
        assert pool.submitted == [1, 2]
        assert drained == []
    assert not pipe.last_stats.completed


def test_plain_judge_exception_before_release_submits_nothing_further():
    def judge(chunk, payload, release):
        if chunk == 2:
            raise RuntimeError("boom")
        release()
        return payload

    written = []
    pipe, pool = _releasing_pipe(
        lambda c: c, judge, lambda c, r: written.append(r)
    )
    with pytest.raises(RuntimeError, match="boom"):
        pipe.run([1, 2, 3, 4])
    pool.shutdown()
    assert pool.submitted == [1, 2]
    assert written == [1]  # everything judged before the failure


def test_released_lazy_source_ends_on_END_and_counts_its_probe(tmp_path):
    """The sliced sweep's shape: an unbounded iterator whose fetch says
    END. Four real chunks and the END probe: the probe too is submitted
    from inside a judge (4 of 5 prefetches), END is never judged, and
    the boundary hook runs after each real chunk."""
    import itertools

    from foremast_tpu.jobs.pipeline import END

    judged, written, boundaries = [], [], []

    def judge(i, payload, release):
        release()
        judged.append(i)
        return payload

    pipe, pool = _releasing_pipe(
        lambda i: f"prep-{i}" if i < 4 else END,
        judge,
        lambda i, r: written.append(r),
        boundary=lambda: boundaries.append(len(judged)),
    )
    spans = _wait_prefetch_spans(
        lambda: pipe.run(itertools.count()), tmp_path
    )
    pool.shutdown()
    assert judged == [0, 1, 2, 3]
    assert written == [f"prep-{i}" for i in range(4)]
    assert boundaries == [1, 2, 3, 4]
    assert pool.submitted == [0, 1, 2, 3, 4]  # nothing past the END probe
    stats = pipe.last_stats
    assert stats.completed and stats.chunks == 4
    assert stats.released_at_issue == 4
    assert spans == [(0, 0), (1, 1), (2, 1), (3, 1), (4, 1)]


def test_serial_loop_hands_the_judge_a_release_that_does_nothing():
    """No pool (a `concurrent_fetch = False` source) or depth 1: the
    judge still takes three arguments, and nothing runs ahead."""
    from foremast_tpu.jobs.pipeline import ChunkPipeline

    order = []

    def fetch(chunk):
        order.append(("fetch", chunk))
        return chunk

    def judge(chunk, payload, release):
        release()
        order.append(("judge", chunk))
        return payload

    pipe = ChunkPipeline(
        fetch, judge, lambda c, r: order.append(("write", c)),
        depth=2, prefetch_pool=None, judge_releases=True,
    )
    stats = pipe.run([1, 2])
    assert not stats.pipelined and stats.released_at_issue == 0
    assert order == [
        ("fetch", 1), ("judge", 1), ("write", 1),
        ("fetch", 2), ("judge", 2), ("write", 2),
    ]
