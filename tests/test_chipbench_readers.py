"""The three per-layer readers ISSUE 24 adds to chipbench, on hand-written
records: exact arithmetic, the None / 0.0 cases, and another thread's
spans that must not count for the tick thread."""

from __future__ import annotations

import pytest

from chipbench.readers import (
    idle_gap_pct,
    span_attr_per_kwin,
    span_ms_max,
    thread_unspanned_pct,
)

TICK, PREFETCH = 11, 22


def _span(name, ts, dur, tid=TICK, trace="t1", stage=None, parent="r", **attrs):
    args = {"trace_id": trace, "span_id": name, "parent_id": parent, **attrs}
    if stage:
        args["stage"] = stage
    return {"name": name, "ts": float(ts), "dur": float(dur), "tid": tid, "args": args}


def _root(ts, dur, trace="t1", tid=TICK):
    return _span("worker.tick", ts, dur, tid=tid, trace=trace, parent="")


def _record(spans, sweeps=1, windows=4000):
    return {"spans": spans, "sweeps": [{}] * sweeps, "windows": windows}


# -- thread_unspanned_pct ---------------------------------------------------


def test_unspanned_is_root_time_outside_the_tick_threads_stage_spans():
    rec = _record([
        _root(1000, 100),
        _span("worker.claim", 1000, 10, stage="claim"),
        _span("judge.decode", 1020, 50, stage="decode"),
    ])
    assert thread_unspanned_pct.read(rec, {}) == pytest.approx(40.0)


def test_unspanned_ignores_other_threads_and_stageless_spans():
    rec = _record([
        _root(1000, 100),
        _span("worker.claim", 1000, 10, stage="claim"),
        # the prefetch thread fetches for the whole tick: none of it is
        # the tick thread's time
        _span("worker.fetch", 1000, 100, tid=PREFETCH, stage="metric_fetch"),
        # a span with no stage names no stage's seconds
        _span("arena.scatter", 1010, 90),
    ])
    assert thread_unspanned_pct.read(rec, {}) == pytest.approx(90.0)


def test_unspanned_takes_the_union_and_clips_to_the_root():
    rec = _record([
        _root(1000, 100),
        _span("a", 990, 30, stage="claim"),       # clipped to [1000, 1020)
        _span("b", 1010, 30, stage="pack"),       # overlaps a: adds [1020, 1040)
        _span("c", 1090, 50, stage="decide"),     # clipped to [1090, 1100)
        _span("d", 1200, 50, stage="decide"),     # outside the root
    ])
    assert thread_unspanned_pct.read(rec, {}) == pytest.approx(50.0)


def test_unspanned_reads_the_windows_roots_only():
    # set-up's tick (wholly un-spanned) is in the ring too: the window is
    # the LAST len(sweeps) roots, summed
    rec = _record(
        [
            _root(0, 500, trace="setup"),
            _root(1000, 100, trace="t1"),
            _span("x", 1000, 100, trace="t1", stage="pack"),
            _root(2000, 300, trace="t2"),
            _span("y", 2000, 100, trace="t2", stage="pack"),
            # a nested micro-tick's span is no root
            _span("worker.tick", 2100, 50, trace="t2", parent="r"),
        ],
        sweeps=2,
    )
    assert thread_unspanned_pct.read(rec, {}) == pytest.approx(100.0 * 200 / 400)


@pytest.mark.parametrize(
    "rec",
    [
        _record([], sweeps=2),
        _record([_span("worker.claim", 0, 10, stage="claim")]),
        _record([_root(0, 100)], sweeps=0),
        {"sweeps": [{}], "windows": 1},
    ],
)
def test_unspanned_is_none_without_a_root(rec):
    assert thread_unspanned_pct.read(rec, {}) is None


# -- idle_gap_pct -----------------------------------------------------------

GAP = {"gap": "host, no stage span"}


def test_idle_gap_is_the_named_entrys_share_of_the_window():
    rec = {"trace": {"window_s": 20.0, "idle_gaps": [
        ["host, no stage span", 11.0], ["worker.fetch[metric_fetch]", 2.0],
    ]}}
    assert idle_gap_pct.read(rec, GAP) == pytest.approx(55.0)
    assert idle_gap_pct.read(rec, {"gap": "worker.fetch[metric_fetch]"}) == pytest.approx(10.0)


def test_idle_gap_is_zero_when_every_gap_has_a_name():
    rec = {"trace": {"window_s": 20.0, "idle_gaps": [["judge.decode[decode]", 1.0]]}}
    assert idle_gap_pct.read(rec, GAP) == 0.0


@pytest.mark.parametrize(
    "rec",
    [
        {},
        {"trace": None},
        {"trace": {"window_s": 20.0, "idle_gaps": []}},
        {"trace": {"window_s": None, "idle_gaps": [["host, no stage span", 1.0]]}},
    ],
)
def test_idle_gap_is_none_without_a_trace(rec):
    assert idle_gap_pct.read(rec, GAP) is None


# -- span_ms_max ------------------------------------------------------------

WAIT = {"span": "judge.result_wait"}


def test_span_ms_max_is_the_windows_longest_span_in_ms():
    rec = _record(
        [
            # set-up's longest (a compile) is not the window's
            _root(0, 9_000_000, trace="setup"),
            _span("judge.result_wait", 10, 8_000_000, trace="setup", stage="decode"),
            _root(10_000_000, 300_000, trace="t1"),
            _span("judge.result_wait", 10_000_010, 60_000, trace="t1", stage="decode"),
            _root(11_000_000, 4_300_000, trace="t2"),
            _span("judge.result_wait", 11_000_010, 4_190_000, trace="t2", stage="decode"),
            _span("judge.decode", 15_190_010, 5_000_000, trace="t2", stage="decode"),
        ],
        sweeps=2,
    )
    assert span_ms_max.read(rec, WAIT) == pytest.approx(4190.0)
    assert span_ms_max.read(rec, {"span": "judge.decode"}) == pytest.approx(5000.0)


@pytest.mark.parametrize(
    "spans",
    [
        [],
        # the parent commit: the root and `judge.decode`, no result wait
        [_root(1000, 100), _span("judge.decode", 1010, 50, stage="decode")],
        # the span in set-up's tick only
        [
            _root(0, 100, trace="setup"),
            _span("judge.result_wait", 10, 5, trace="setup", stage="decode"),
            _root(1000, 100),
        ],
    ],
)
def test_span_ms_max_is_none_without_the_span(spans):
    assert span_ms_max.read(_record(spans, sweeps=1), WAIT) is None


# -- span_attr_per_kwin -----------------------------------------------------

H2D = {"span": "judge.h2d", "attr": "bytes", "scale": 1e-6}


def test_span_attr_sums_the_windows_spans_per_thousand_windows():
    rec = _record(
        [
            _root(0, 500, trace="setup"),
            _span("judge.h2d", 10, 5, trace="setup", stage="h2d", bytes=9_000_000),
            _root(1000, 100, trace="t1"),
            _span("judge.h2d", 1010, 5, trace="t1", stage="h2d", bytes=3_000_000),
            _span("judge.h2d", 1050, 5, trace="t1", stage="h2d", bytes=1_000_000),
            _span("judge.score", 1060, 5, trace="t1", stage="score", bytes=7),
        ],
        windows=4000,
    )
    assert span_attr_per_kwin.read(rec, H2D) == pytest.approx(1.0)  # 4 MB / 4 kwin
    assert span_attr_per_kwin.read(
        rec, {"span": "judge.score", "attr": "bytes"}
    ) == pytest.approx(7 / 4)


@pytest.mark.parametrize(
    "spans",
    [
        [],
        # the parent commit: the root is there, the span is not
        [_root(1000, 100), _span("judge.score", 1010, 5, stage="score")],
        # the span without the attribute
        [_root(1000, 100), _span("judge.h2d", 1010, 5, stage="h2d")],
    ],
)
def test_span_attr_is_none_where_the_program_records_none(spans):
    assert span_attr_per_kwin.read(_record(spans), H2D) is None


def test_pack_bulk_layer_reads_bulk_from_the_slices_first_pack_span():
    """ISSUE 25's layer file through the reader it names: only a slice's
    first `worker.pack_joint` span carries `bulk`; the dispatch groups'
    (with `rows`) and set-up's do not count."""
    import json
    import os

    from chipbench import readers

    path = os.path.join(
        os.path.dirname(readers.__file__), os.pardir, "layers",
        "pack_bulk_docs_per_kwin.sweep.json",
    )
    with open(path) as f:
        spec = json.load(f)
    assert spec["reader"] == "span_attr_per_kwin"
    rec = _record(
        [
            _root(0, 500, trace="setup"),
            _span("worker.pack_joint", 10, 5, trace="setup", stage="pack",
                  docs=8192, bulk=8192, aligned=0, empty=0, demoted=0),
            _root(1000, 100, trace="t1"),
            _span("worker.pack_joint", 1010, 5, trace="t1", stage="pack",
                  docs=600, bulk=600, aligned=0, empty=0, demoted=0),
            _span("worker.pack_joint", 1020, 5, trace="t1", stage="pack",
                  docs=600, rows=600),
            _span("worker.pack_joint", 1050, 5, trace="t1", stage="pack",
                  docs=400, bulk=400, aligned=0, empty=0, demoted=0),
            _span("worker.pack_joint", 1060, 5, trace="t1", stage="pack",
                  docs=400, rows=400),
        ],
        windows=4000,  # 1,000 4-alias docs
    )
    assert span_attr_per_kwin.read(rec, spec["params"]) == pytest.approx(250.0)
    # one doc in ten through align_series reads 225
    for sp in rec["spans"]:
        if "bulk" in sp["args"]:
            sp["args"]["aligned"] = sp["args"]["bulk"] // 10
            sp["args"]["bulk"] -= sp["args"]["aligned"]
    assert span_attr_per_kwin.read(rec, spec["params"]) == pytest.approx(225.0)
    # the parent commit's spans carry no `bulk`: the metric is left out
    for sp in rec["spans"]:
        sp["args"].pop("bulk", None)
    assert span_attr_per_kwin.read(rec, spec["params"]) is None


def test_decide_bulk_layer_sums_bulk_over_the_joint_decide_spans():
    """The decide layer file through the reader it names: every joint
    `worker.decide` span (a dispatch group's, and the window-less docs')
    carries `bulk`; the writer thread's univariate decide does not, and
    set-up's spans do not count."""
    import json
    import os

    from chipbench import readers

    path = os.path.join(
        os.path.dirname(readers.__file__), os.pardir, "layers",
        "decide_bulk_docs_per_kwin.sweep.json",
    )
    with open(path) as f:
        spec = json.load(f)
    assert spec["reader"] == "span_attr_per_kwin"
    assert spec["params"] == {"span": "worker.decide", "attr": "bulk"}
    rec = _record(
        [
            _root(0, 500, trace="setup"),
            _span("worker.decide", 10, 5, trace="setup", stage="decide",
                  docs=8192, bulk=8192, unhealthy=0, payload_points=0),
            _root(1000, 100, trace="t1"),
            _span("worker.decide", 1010, 1, trace="t1", stage="decide",
                  docs=4, bulk=4),
            _span("worker.decide", 1020, 5, trace="t1", stage="decide",
                  docs=596, bulk=596, unhealthy=90, payload_points=600),
            _span("worker.decide", 1040, 5, trace="t1", stage="decide",
                  docs=400, bulk=400, unhealthy=60, payload_points=400),
            # univariate decide on the writer: no `bulk`
            _span("worker.decide", 1050, 5, tid=PREFETCH, trace="t1",
                  stage="decide", docs=2000),
        ],
        windows=6000,  # 1,000 4-alias docs + 2,000 1-alias
    )
    assert span_attr_per_kwin.read(rec, spec["params"]) == pytest.approx(
        1000 / 6.0
    )
    # the parent commit's spans carry no `bulk`: the metric is left out
    for sp in rec["spans"]:
        sp["args"].pop("bulk", None)
    assert span_attr_per_kwin.read(rec, spec["params"]) is None


def test_prefetch_at_issue_layer_counts_the_waits_released_from_a_judge():
    """ISSUE 30's layer file through the reader it names: a sweep of four
    slices waits for five prefetches, four of them submitted from inside
    a judge (`at_issue` 1); a program that records no such attr (the
    parent) leaves the metric out."""
    import json
    import os

    from chipbench import readers

    path = os.path.join(
        os.path.dirname(readers.__file__), os.pardir, "layers",
        "prefetch_at_issue_per_mwin.sweep.json",
    )
    with open(path) as f:
        spec = json.load(f)
    assert spec["reader"] == "span_attr_per_kwin"
    waits = [
        _span("pipeline.wait_prefetch", 1000 + 10 * i, 2, stage="wait",
              slice=i, at_issue=int(i > 0))
        for i in range(5)
    ]
    rec = _record(
        [
            _root(0, 500, trace="setup"),
            _span("pipeline.wait_prefetch", 10, 2, trace="setup",
                  stage="wait", slice=1, at_issue=1),
            _root(1000, 100, trace="t1"),
            *waits,
        ],
        windows=131_072,
    )
    assert span_attr_per_kwin.read(rec, spec["params"]) == pytest.approx(
        4 / 0.131072
    )
    for sp in rec["spans"]:
        sp["args"].pop("at_issue", None)
    assert span_attr_per_kwin.read(rec, spec["params"]) is None
