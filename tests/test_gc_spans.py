"""The tracer's own costs as spans: a generation-2 garbage collection is a
`python.gc` span of stage `gc` (a `gc.callbacks` hook, one a process,
recording into the newest Tracer), and the ring's background dump runs
under a `trace.flush` span of stage `housekeeping`.

Automatic collections are switched off around each case, so that only the
collections a case asks for run; `gc.collect()` still calls the hooks."""

from __future__ import annotations

import gc
import json
import threading
import time
import types

import pytest
from prometheus_client import CollectorRegistry

from foremast_tpu.observe import spans
from foremast_tpu.observe.spans import Tracer, span


@pytest.fixture(autouse=True)
def no_automatic_gc():
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()


def _tracer(tmp_path, **kw) -> Tracer:
    return Tracer(service="gc-test", registry=CollectorRegistry(), trace_dir=str(tmp_path), **kw)


def _gc_spans(tracer: Tracer) -> list:
    return [e for e in tracer.ring.snapshot() if e["name"] == "python.gc"]


def test_a_generation_2_collection_is_one_span_in_the_span_it_stopped(tmp_path):
    tracer = _tracer(tmp_path)
    with tracer.span("worker.tick") as root:
        with span("judge.result_wait", stage="decode") as waited:
            gc.collect(2)
    (e,) = _gc_spans(tracer)
    assert e["args"]["stage"] == "gc" and e["args"]["generation"] == 2
    assert e["args"]["collected"] >= 0 and e["args"]["uncollectable"] >= 0
    # nested in the span it stopped, on its thread, inside its time
    assert e["args"]["parent_id"] == waited.span_id
    assert e["args"]["trace_id"] == root.trace_id
    assert e["tid"] == threading.get_ident() & 0x7FFFFFFF
    (w,) = [x for x in tracer.ring.snapshot() if x["name"] == "judge.result_wait"]
    assert w["ts"] <= e["ts"] and e["ts"] + e["dur"] <= w["ts"] + w["dur"] + 1.0
    assert tracer.last_stage_seconds["gc"] == pytest.approx(e["dur"] / 1e6, abs=1e-6)


def test_a_collection_outside_any_span_is_in_the_stage_at_once(tmp_path):
    """Its seconds are the stage's before any later span closes (a window
    that opens after it must not count it); its event reaches the ring at
    the next close or dump."""
    registry = CollectorRegistry()
    tracer = Tracer(service="gc-test", registry=registry, trace_dir=str(tmp_path))
    gc.collect(2)
    assert registry.get_sample_value("foremast_tick_stage_seconds_count", {"stage": "gc"}) == 1
    seconds = registry.get_sample_value("foremast_tick_stage_seconds_sum", {"stage": "gc"})
    assert seconds > 0 and tracer.last_stage_seconds["gc"] == seconds
    assert len(tracer.ring) == 0
    path = tracer.flush()
    (e,) = [json.loads(line) for line in open(path)]
    assert e["name"] == "python.gc" and e["args"]["parent_id"] == ""
    assert e["dur"] == pytest.approx(seconds * 1e6, abs=0.1)


def test_younger_generations_record_nothing(tmp_path):
    tracer = _tracer(tmp_path)
    with tracer.span("worker.tick"):
        gc.collect(0)
        gc.collect(1)
    assert _gc_spans(tracer) == []
    assert [e["name"] for e in tracer.ring.snapshot()] == ["worker.tick"]


def test_two_tracers_register_one_hook(tmp_path):
    first, second = _tracer(tmp_path), _tracer(tmp_path)
    assert sum(cb is spans._on_gc for cb in gc.callbacks) == 1
    # the newest records
    with second.span("worker.tick"):
        gc.collect(2)
    assert len(_gc_spans(second)) == 1 and _gc_spans(first) == []


def test_a_collected_tracer_leaves_a_hook_that_neither_raises_nor_records(tmp_path, monkeypatch):
    older = _tracer(tmp_path)
    newest = _tracer(tmp_path)
    gone = spans._gc_tracer
    del newest
    gc.collect()
    assert gone() is None
    raised = []
    monkeypatch.setattr("sys.unraisablehook", raised.append)
    with older.span("worker.tick"):
        gc.collect(2)
    assert raised == []
    assert _gc_spans(older) == [] and spans._gc_open is None


def test_the_gc_stage_reads_zero_before_any_collection():
    from chipbench.probes import Probe

    registry = CollectorRegistry()
    Tracer(service="gc-test", registry=registry, trace_dir=None)
    worker = types.SimpleNamespace(_uni=None, _mvj=None, _fast_kinds={})
    snap = Probe(worker, registry).snapshot()
    assert snap["stage_s.gc"] == 0.0 and snap["stage_n.gc"] == 0.0


def test_the_background_dump_runs_under_a_trace_flush_span(tmp_path):
    tracer = _tracer(tmp_path)
    tracer.AUTOFLUSH_SECONDS = 0.0
    with tracer.span("worker.tick"):
        pass
    deadline = time.monotonic() + 30
    while not [e for e in tracer.ring.snapshot() if e["name"] == "trace.flush"]:
        assert time.monotonic() < deadline, "no trace.flush span"
        time.sleep(0.01)
    (e,) = [e for e in tracer.ring.snapshot() if e["name"] == "trace.flush"]
    # its own root, on the dump's thread, counting what it wrote: the tick
    assert e["args"]["stage"] == "housekeeping" and e["args"]["parent_id"] == ""
    assert e["args"]["events"] == 1
    assert e["tid"] != threading.get_ident() & 0x7FFFFFFF
    assert tracer.last_stage_seconds["housekeeping"] == pytest.approx(e["dur"] / 1e6, abs=1e-6)


def test_a_collection_inside_the_rings_lock_is_handed_over_not_recorded(tmp_path):
    """A collection can stop a thread that holds the ring's lock: the hook
    hands its event over, and the next span's close puts it in the ring."""
    tracer = _tracer(tmp_path)

    def stopped_inside_the_lock():
        with tracer.ring._lock:
            spans._on_gc("start", {"generation": 2, "collected": 0, "uncollectable": 0})
            spans._on_gc("stop", {"generation": 2, "collected": 3, "uncollectable": 0})

    t = threading.Thread(target=stopped_inside_the_lock, daemon=True)
    t.start()
    t.join(10)
    assert not t.is_alive(), "the hook blocked on the ring's lock"
    with tracer.span("worker.tick"):
        pass
    (e,) = _gc_spans(tracer)
    assert e["args"]["collected"] == 3
