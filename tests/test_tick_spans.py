"""The tick's span tree is closed (ISSUE 24).

Between the open and the close of the root `worker.tick` span, every
instant of each of a sliced sweep's three threads (prefetch, tick,
writer) belongs to exactly one STAGE span. Pinned here, structurally and
without timing, on the tiny mixed fleet of tests/test_joint_fast_tick.py
(2-alias bivariate + 4-alias LSTM-hybrid + single-alias docs) judged by a
sliced, pipelined warm sweep:

  * the functions that used to run under no span now run under the stage
    docs/observability.md's table names, on the thread it names;
  * stage spans of one thread never overlap (they are siblings, so
    `foremast_tick_stage_seconds` sums are self time), but for the
    tracer's own: a generation-2 collection's `python.gc` nests in the
    span it stopped, and `trace.flush` runs on a thread of its own;
  * every stage of `TICK_STAGES` but `fit` and `gc` is observed by a warm
    sweep;
  * spans open per slice / dispatch group / wait, never per doc: a sweep
    of 2N docs records as many spans as one of N docs at the same slice
    count;
  * `PipelineStats` carries the tick thread's three waits, and they are
    the `wait` stage;
  * a slice's first `worker.pack_joint` span says which way its docs were
    packed (ISSUE 25), and the bulk pack opens no span of its own.
"""

from __future__ import annotations

import dataclasses
import threading
import time

import pytest
from prometheus_client import CollectorRegistry

from benchmarks.worker_bench import build_mixed_fleet
from foremast_tpu.config import BrainConfig
from foremast_tpu.engine.kinds import lstm as lstm_kind
from foremast_tpu.jobs import BrainWorker, joint_decide, joint_pack
from foremast_tpu.jobs.pipeline import PipelineStats
from foremast_tpu.observe import spans
from foremast_tpu.observe.spans import TICK_STAGES, Tracer, current_span

NOW = 1_760_000_000.0
HIST_LEN = 256
CUR_LEN = 30


def _worker(services: int, slice_docs: int, trace_dir):
    """A sliced, pipelined worker over `services` mixed docs — two joint
    (one bivariate, one LSTM-hybrid), first in claim order, the rest
    single-alias — cold-fitted and warmed: the next tick is an all-warm
    sweep of two slices."""
    store, source, _ = build_mixed_fleet(
        services, HIST_LEN, CUR_LEN, NOW, joint_frac=2 / services
    )
    cfg = BrainConfig(
        algorithm="auto", season_steps=24, max_cache_size=4 * services + 64
    )
    cfg = dataclasses.replace(
        cfg, anomaly=dataclasses.replace(cfg.anomaly, threshold=4.0)
    )
    registry = CollectorRegistry()
    tracer = Tracer(
        service="test", registry=registry, trace_dir=str(trace_dir),
        buffer_size=1 << 14,
    )
    w = BrainWorker(
        store, source, config=cfg, claim_limit=2 * services,
        worker_id="span-w", tracer=tracer,
    )
    w.judge.lstm_steps = 10  # CI speed
    w.sweep_slice_docs = slice_docs
    w.pipeline_depth = 2
    assert w._sweep_sliceable()
    assert w.tick(now=NOW + 150) == services  # cold: fits
    assert w.tick(now=NOW + 200) == services  # warm: compiles
    return w, source, registry, tracer


# spans of the tracer itself, not of the tick's tree: a generation-2
# collection whenever one runs, the ring's background dump every 10 s
TRACER_OWN = ("python.gc", "trace.flush")


def _sweep_events(tracer, fn):
    """Run `fn` (one tick) and return the span events it recorded, the
    tracer's own apart."""
    before = tracer.ring.total
    fn()
    n = tracer.ring.total - before
    events = tracer.ring.snapshot()[-n:] if n else []
    return [e for e in events if e["name"] not in TRACER_OWN]


def _thread_kind() -> str:
    name = threading.current_thread().name
    if name.startswith("foremast-prefetch"):
        return "prefetch"
    if name.startswith("foremast-writeback"):
        return "writer"
    return "tick" if threading.current_thread() is threading.main_thread() else name


class _Recorder:
    """Wraps callables to record (stage of the ambient span, thread) at
    every call."""

    def __init__(self):
        self.seen: dict[str, set] = {}

    def wrap(self, name, fn):
        def wrapped(*a, **kw):
            sp = current_span()
            self.seen.setdefault(name, set()).add(
                (sp.stage if sp is not None else None, _thread_kind())
            )
            return fn(*a, **kw)

        return wrapped


@pytest.fixture(scope="module")
def swept(tmp_path_factory):
    """One warm sliced sweep of the 12-doc fleet with every formerly
    un-spanned function wrapped; one joint doc's aliases are put out of
    step so its windows take `align_series`."""
    w, source, registry, tracer = _worker(
        12, 6, tmp_path_factory.mktemp("spans")
    )
    url = "http://prom/cur?q=m0:app1&step=60"
    ct, cv = source.data[url]
    source.data[url] = (ct[:-1], cv[:-1])
    rec = _Recorder()
    mp = pytest.MonkeyPatch()
    for name in (
        "_admit_fast", "_pack_uni", "_decide_status", "_log_judged",
        "_tick_done", "_observe_verdicts", "_preempt_between_slices",
    ):
        mp.setattr(w, name, rec.wrap(name, getattr(w, name)))
    mp.setattr(w._mvj, "_place_joint", rec.wrap("_place_joint", w._mvj._place_joint))
    mp.setattr(lstm_kind, "ae_cutoff", rec.wrap("ae_cutoff", lstm_kind.ae_cutoff))
    mp.setattr(
        joint_pack, "align_series",
        rec.wrap("align_series", joint_pack.align_series),
    )
    mp.setattr(
        joint_decide, "decide_group",
        rec.wrap("decide_group", joint_decide.decide_group),
    )
    mp.setattr(w.store, "claim", rec.wrap("store.claim", w.store.claim))
    mp.setattr(
        w.store, "update_many", rec.wrap("store.update_many", w.store.update_many)
    )
    mp.setattr(source, "fetch", rec.wrap("source.fetch", source.fetch))
    stage_counts = _stage_counts(registry)
    try:
        events = _sweep_events(tracer, lambda: w.tick(now=NOW + 260))
    finally:
        mp.undo()
    out = {
        "seen": rec.seen,
        "events": events,
        "last_sweep": dict(w._last_sweep),
        "stage_seconds": dict(tracer.last_stage_seconds),
        "observed": {
            k: v - stage_counts.get(k, 0)
            for k, v in _stage_counts(registry).items()
        },
    }
    w.close()
    return out


def _stage_counts(registry) -> dict:
    return {
        s.labels["stage"]: s.value
        for fam in registry.collect()
        if fam.name == "foremast_tick_stage_seconds"
        for s in fam.samples
        if s.name.endswith("_count")
    }


# (a) each formerly un-spanned function runs under the stage, and on the
# thread, that docs/observability.md's table names
@pytest.mark.parametrize(
    "fn, expected",
    [
        ("store.claim", {("claim", "tick")}),
        ("_admit_fast", {("admit", "prefetch")}),
        ("source.fetch", {("metric_fetch", "prefetch")}),
        ("_pack_uni", {("pack", "prefetch")}),
        ("align_series", {("pack", "tick")}),
        ("ae_cutoff", {("pack", "tick")}),
        ("_place_joint", {("h2d", "tick")}),
        # univariate docs decide one at a time on the writer; joint docs
        # a dispatch group at a time on the tick thread (`decide_group`,
        # which logs only the docs that carry a trace ID: none here)
        ("_decide_status", {("decide", "writer")}),
        ("_log_judged", {("decide", "writer")}),
        ("store.update_many", {("write_back", "writer")}),
        ("_observe_verdicts", {("housekeeping", "writer")}),
        ("_preempt_between_slices", {("housekeeping", "tick")}),
        ("_tick_done", {("housekeeping", "tick")}),
        ("decide_group", {("decide", "tick")}),
    ],
)
def test_unspanned_function_now_runs_under_its_stage(swept, fn, expected):
    assert swept["seen"][fn] == expected


def test_sweep_was_sliced_pipelined_and_all_warm(swept):
    ls = swept["last_sweep"]
    assert ls["slices"] == 2 and ls["slow_docs"] == 0
    assert ls["pipeline"]["pipelined"] and ls["pipeline"]["completed"]


# (b) stage spans of one thread are siblings: they never overlap
def test_stage_spans_of_one_thread_never_overlap(swept):
    events = swept["events"]
    (root,) = [e for e in events if e["name"] == "worker.tick"]
    staged = [e for e in events if "stage" in e["args"]]
    assert staged
    by_tid: dict = {}
    for e in staged:
        assert e["args"]["trace_id"] == root["args"]["trace_id"]
        by_tid.setdefault(e["tid"], []).append(e)
    assert len(by_tid) == 3  # prefetch, tick, writer
    for evs in by_tid.values():
        evs.sort(key=lambda e: e["ts"])
        for a, b in zip(evs, evs[1:]):
            # ts/dur are microseconds rounded to 0.1 at a 1.8e15 epoch:
            # a float there resolves 0.25 us
            assert a["ts"] + a["dur"] <= b["ts"] + 1.0, (a["name"], b["name"])
    tick = by_tid[root["tid"]]
    assert tick[0]["ts"] >= root["ts"] - 1.0
    assert tick[-1]["ts"] + tick[-1]["dur"] <= root["ts"] + root["dur"] + 1.0


def test_stage_span_attrs_count_at_the_boundary(swept):
    by_name: dict = {}
    for e in swept["events"]:
        by_name.setdefault(e["name"], []).append(e["args"])
    assert sum(a["docs"] for a in by_name["worker.claim"]) == 12
    admit = by_name["worker.admit"]
    assert sum(a["docs"] for a in admit) == 12
    assert sum(a["joint"] for a in admit) == 2 and sum(a["slow"] for a in admit) == 0
    (h2d_bi, h2d_lstm) = by_name["judge.h2d"]
    assert h2d_bi["bytes"] > 0 and h2d_lstm["bytes"] > h2d_bi["bytes"]
    assert {a["scattered"] for a in by_name["judge.arena_assemble"]} == {0}
    decided = [a for a in by_name["worker.decide"] if "unhealthy" in a]
    assert sum(a["docs"] for a in decided) == 2  # the joint docs
    assert {a["slice"] for a in by_name["pipeline.wait_prefetch"]} == {0, 1, 2}


# (f) ISSUE 25: the slice's first pack span (the one over the alignment,
# before any dispatch group's `rows`) says which way its docs went
def test_first_pack_joint_span_counts_bulk_and_aligned(swept):
    packs = [
        e["args"] for e in swept["events"] if e["name"] == "worker.pack_joint"
    ]
    (first,) = [a for a in packs if "rows" not in a]
    # the bivariate doc in bulk; the lstm doc, one alias a point short,
    # through align_series
    assert (first["docs"], first["bulk"], first["aligned"]) == (2, 1, 1)
    assert (first["empty"], first["demoted"]) == (0, 0)
    assert sorted(a["rows"] for a in packs if "rows" in a) == [1, 1]


@pytest.mark.parametrize(
    "case, dispatch_groups",
    [
        ("distinct_equal_stamps", 1),
        ("mixed_lengths_one_dispatch", 1),  # two (n) classes, one group
        ("fallback_in_the_middle", 1),
        ("one_empty_alias", 1),
        ("bucket_drift_demotes", 1),
        ("kinds_interleaved", 3),
    ],
)
def test_pack_joint_spans_one_a_slice_and_one_a_dispatch_group(
    case, dispatch_groups, tmp_path
):
    from tests.test_joint_fast_tick import (
        _PACK_CASES,
        _judge_joint_fast,
        _run_pack,
    )

    tracer = Tracer(
        service="test", registry=CollectorRegistry(), trace_dir=str(tmp_path)
    )
    with tracer.span("worker.tick"):
        _run_pack(_judge_joint_fast, _PACK_CASES[case][0]())
    packs = [
        e["args"]
        for e in tracer.ring.snapshot()
        if e["name"] == "worker.pack_joint"
    ]
    assert len(packs) == 1 + dispatch_groups
    first = packs[0]
    assert "rows" not in first
    assert (
        first["bulk"] + first["aligned"] + first["empty"] + first["demoted"]
        == first["docs"]
    )
    assert sum(a["rows"] for a in packs[1:]) == first["bulk"] + first["aligned"]


# (c) a warm joint + univariate sweep observes every stage but `fit` (a
# cold tick's) and `gc` (a generation-2 collection's, whenever one runs)
@pytest.mark.parametrize("stage", [s for s in TICK_STAGES if s not in ("fit", "gc")])
def test_warm_sweep_observes_every_stage(swept, stage):
    assert swept["observed"].get(stage, 0) > 0


def test_tick_stages_are_the_thirteen():
    # the thirteen of the tick, and `gc`, which nests in any of them
    assert len(TICK_STAGES) == len(set(TICK_STAGES)) == 14
    assert TICK_STAGES[-1] == "gc"


# (d) no per-doc spans: N and 2N docs at the same slice count, the same
# spans (both fleets' first slice holds the two joint docs)
def test_span_count_follows_slices_not_docs(swept, tmp_path):
    w, _, _, tracer = _worker(6, 3, tmp_path)
    events = _sweep_events(tracer, lambda: w.tick(now=NOW + 260))
    assert w._last_sweep["slices"] == 2 and w._last_sweep["slow_docs"] == 0
    w.close()

    def names(evs):
        return sorted(e["name"] for e in evs)

    # the 12-doc sweep's one out-of-step doc changes no span
    assert names(events) == names(swept["events"])
    assert sum(
        e["args"]["docs"] for e in events if e["name"] == "worker.admit"
    ) == 6


# (e) the tick thread's three waits, in stats and as the `wait` stage
def test_pipeline_stats_carry_the_three_waits(swept):
    p = swept["last_sweep"]["pipeline"]
    waits = [p["device_idle_seconds"], p["write_wait_seconds"], p["drain_seconds"]]
    assert all(w >= 0.0 for w in waits)
    # as_dict rounds each to 1e-4; the stats' clock pairs sit just
    # outside the spans' own
    assert sum(waits) == pytest.approx(swept["stage_seconds"]["wait"], abs=2e-3)
    fresh = PipelineStats(2).as_dict()
    assert fresh["write_wait_seconds"] == fresh["drain_seconds"] == 0.0


def test_serial_pipeline_waits_nothing():
    from foremast_tpu.jobs.pipeline import ChunkPipeline

    pipe = ChunkPipeline(lambda c: c, lambda c, p: p, lambda c, r: None, depth=1)
    stats = pipe.run([[1], [2]])
    assert not stats.pipelined
    assert stats.write_wait_seconds == stats.drain_seconds == 0.0


# the span timeline's one clock, and the helpers the stage spans use
def test_clock_is_the_span_timeline():
    t0 = spans.clock()
    s = spans.Span("x", "t", "")
    t1 = spans.clock()
    assert t0 <= s.ts <= t1
    # anchored to the wall clock at import; they part only by its slew
    assert abs(spans.clock() - time.time()) < 5.0


def test_span_yields_span_or_none_and_note_is_safe():
    with spans.span("no.tracer", stage="pack") as s:
        assert s is None
        spans.note(s, rows=1)
    with spans.span("no.tracer.device", device=True) as s:
        assert s is None
    tracer = Tracer(service="t", registry=CollectorRegistry(), trace_dir=None)
    with tracer.span("root"):
        with spans.span("child", stage="pack", rows=1) as s:
            spans.note(s, rows=2, demoted=0)
    assert s.attrs == {"rows": 2, "demoted": 0}


def test_a_span_reads_no_os_entropy(monkeypatch):
    """`os.urandom` is a syscall made with the GIL released: where
    syscalls are slow (the benchmark's TPU host) another busy Python
    thread takes the GIL for a whole switch interval — 5.4 ms a span,
    measured. IDs come from a PRNG seeded once."""
    import os

    def boom(_n):
        raise AssertionError("a span read os.urandom")

    monkeypatch.setattr(os, "urandom", boom)
    tracer = Tracer(service="t", registry=CollectorRegistry(), trace_dir=None)
    with tracer.span("root") as root:
        with spans.span("child", stage="pack") as child:
            pass
    ids = {spans.new_trace_id() for _ in range(1000)}
    ids |= {root.trace_id, root.span_id, child.span_id}
    assert len(ids) == 1003
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids)
