"""Observability tests: gauges, worker hook, logs, profiler no-op."""

import json
import logging
import time

import numpy as np
import pytest
from prometheus_client import CollectorRegistry, generate_latest

from foremast_tpu.config import BrainConfig
from foremast_tpu.jobs import BrainWorker, Document, InMemoryStore
from foremast_tpu.metrics import ReplaySource
from foremast_tpu.observe import (
    BrainGauges,
    JsonFormatter,
    make_verdict_hook,
    setup_logging,
)


def test_gauges_publish_triplet():
    reg = CollectorRegistry()
    g = BrainGauges(registry=reg)
    g.publish("error5xx", "ns1", "demo", upper=1.5, lower=0.0, anomaly_value=40.1)
    text = generate_latest(reg).decode()
    assert 'foremastbrain_error5xx_upper{app="demo",exported_namespace="ns1"} 1.5' in text
    assert "foremastbrain_error5xx_lower" in text
    assert 'foremastbrain_error5xx_anomaly{app="demo",exported_namespace="ns1"} 40.1' in text


def test_worker_publishes_gauges(demo_traces):
    nt, nv = demo_traces["normal"]
    st, sv = demo_traces["spike"]
    hist = np.tile(nv, 6).astype(np.float32)
    ht = 1700000000 + 60 * np.arange(len(hist), dtype=np.int64)
    src = ReplaySource()
    src.register("hist", (ht, hist))
    src.register("cur", (st, sv))
    store = InMemoryStore()
    store.create(
        Document(
            id="g1",
            app_name="demo",
            current_config=(
                "error4xx== http://x/cur?query=namespace_pod"
                "%3Ahttp_server_requests_error_4xx%7Bnamespace%3D%22ns%22%7D"
            ),
            historical_config=(
                "error4xx== http://x/hist?query=namespace_app_per_pod"
                "%3Ahttp_server_requests_error_4xx%7Bnamespace%3D%22ns%22%7D"
            ),
        )
    )
    reg = CollectorRegistry()
    gauges = BrainGauges(registry=reg)
    worker = BrainWorker(
        store, src, BrainConfig(), on_verdict=make_verdict_hook(gauges, "ns")
    )
    worker.tick(now=1e12)
    text = generate_latest(reg).decode()
    # gauge named after the HISTORICAL query's base series (the reference
    # browser contract, metrics.js:15-23), not the job's short alias
    g = "foremastbrain_namespace_app_per_pod_http_server_requests_error_4xx"
    assert f"{g}_upper" in text
    assert 'app="demo"' in text
    assert f"{g}_anomaly" in text  # spike published


def test_verdict_hook_derives_namespace_from_query():
    """exported_namespace comes from the job's PromQL selector so gauges
    land next to the base series they model (UI joins on it)."""
    reg = CollectorRegistry()
    gauges = BrainGauges(registry=reg)
    hook = make_verdict_hook(gauges, "fallback-ns")

    class V:
        alias = "latency"
        upper = [1.0]
        lower = [0.5]
        anomaly_pairs = []

    doc = Document(
        id="n1",
        app_name="shop",
        current_config=(
            "latency== http://prom/api/v1/query_range?query=namespace_pod"
            "%3Alatency%7Bnamespace%3D%22prod%22%2Cpod%3D~%22a%7Cb%22%7D"
        ),
    )
    hook(doc, [V()])
    text = generate_latest(reg).decode()
    assert 'exported_namespace="prod"' in text

    # no namespace selector in the query -> static fallback
    doc2 = Document(id="n2", app_name="shop", current_config="latency== http://x/q")
    hook(doc2, [V()])
    text = generate_latest(reg).decode()
    assert 'exported_namespace="fallback-ns"' in text


def test_json_logging(capsys):
    import io

    buf = io.StringIO()
    setup_logging(stream=buf)
    log = logging.getLogger("foremast_tpu.test")
    log.info("hello")
    rec = json.loads(buf.getvalue().strip())
    assert rec["msg"] == "hello" and rec["level"] == "info"


def test_worker_metrics_counters(demo_traces):
    from foremast_tpu.observe.gauges import WorkerMetrics

    nt, nv = demo_traces["normal"]
    st, sv = demo_traces["spike"]
    hist = np.tile(nv, 6).astype(np.float32)
    ht = 1700000000 + 60 * np.arange(len(hist), dtype=np.int64)
    src = ReplaySource()
    src.register("hist", (ht, hist))
    src.register("cur", (st, sv))
    store = InMemoryStore()
    store.create(
        Document(
            id="wm1",
            app_name="demo",
            current_config=(
                "error4xx== http://x/cur?query=namespace_pod"
                "%3Ahttp_server_requests_error_4xx%7Bnamespace%3D%22ns%22%7D"
            ),
            historical_config=(
                "error4xx== http://x/hist?query=namespace_app_per_pod"
                "%3Ahttp_server_requests_error_4xx%7Bnamespace%3D%22ns%22%7D"
            ),
        )
    )
    reg = CollectorRegistry()
    metrics = WorkerMetrics(registry=reg)
    BrainWorker(store, src, BrainConfig(), metrics=metrics).tick(now=1e12)
    text = generate_latest(reg).decode()
    assert 'foremast_worker_jobs_total{status="completed_unhealth"} 1.0' in text
    assert "foremast_worker_windows_total 1.0" in text
    assert "foremast_worker_tick_seconds_count 1.0" in text


def test_series_names_rejects_wrapped_expressions():
    """Gauge naming falls back to the alias for non-bare-selector queries:
    `sum(rate(...))` must not name a gauge "sum" (two such aliases would
    collide into one family and overwrite each other)."""
    from foremast_tpu.observe.gauges import _series_names

    cfg = (
        "a== http://x?query=sum%28rate%28m1%5B5m%5D%29%29"
        " ||b== http://x?query=namespace_app_per_pod%3Alat%7Bapp%3D%22s%22%7D"
        " ||c== http://x?query=bare_series&start=1&end=2"
    )
    names = _series_names(cfg)
    assert "a" not in names  # wrapped expression: alias fallback
    assert names["b"] == "namespace_app_per_pod:lat"
    assert names["c"] == "bare_series"


def test_series_names_requires_query_param_boundary():
    """`subquery=foo` (or any param merely ending in "query") must not
    derive a gauge name (ADVICE r2): the match anchors to a real `query=`
    parameter at the URL's query-string boundary."""
    from foremast_tpu.observe.gauges import _series_names

    cfg = (
        "a== http://x?subquery=not_a_series&other=1"
        " ||b== http://x?start=1&query=real_series&end=2"
    )
    names = _series_names(cfg)
    assert "a" not in names  # no bare `query=`: alias fallback
    assert names["b"] == "real_series"


def test_series_names_drops_same_series_collisions():
    """Two aliases of one job resolving to the SAME base series must not
    share a gauge family (last verdict would silently win — ADVICE r2):
    both fall back to their alias-named gauges."""
    from foremast_tpu.observe.gauges import _series_names

    cfg = (
        "p50== http://x?query=latency_series%7Bq%3D%220.5%22%7D"
        " ||p99== http://x?query=latency_series%7Bq%3D%220.99%22%7D"
        " ||ok== http://x?query=other_series"
    )
    names = _series_names(cfg)
    assert "p50" not in names and "p99" not in names
    assert names["ok"] == "other_series"


# ---------------------------------------------------------------------------
# span pipeline (observe/spans.py)
# ---------------------------------------------------------------------------


def _tracer(tmp_dir=None):
    from foremast_tpu.observe.spans import Tracer

    return Tracer(
        service="test",
        registry=CollectorRegistry(),
        trace_dir=str(tmp_dir) if tmp_dir is not None else None,
    )


def test_span_nesting_and_ambient_parenting():
    """Nested spans parent to the innermost open span and share its trace
    ID — including via the module-level ambient helper, which is how the
    engine/store instrument without a tracer reference."""
    from foremast_tpu.observe.spans import current_span, span

    tracer = _tracer()
    with tracer.span("root") as root:
        assert current_span() is root
        assert root.parent_id == ""
        with span("child", stage="fit") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
            with span("grandchild") as g:
                assert g.trace_id == root.trace_id
                assert g.parent_id == child.span_id
        assert current_span() is root
    assert current_span() is None
    # stage spans feed the last-tick breakdown
    assert "fit" in tracer.last_stage_seconds
    # explicit trace_id adoption starts a fresh root under that ID
    with tracer.span("adopted", trace_id="req0000cafe") as s:
        assert s.trace_id == "req0000cafe" and s.parent_id == ""
    # separate roots mint separate trace IDs
    with tracer.span("other") as s2:
        pass
    assert s2.trace_id != root.trace_id
    # ...and each new root restarts the breakdown — /debug/state must
    # describe the latest tick only, never a mix of ticks
    assert "fit" not in tracer.last_stage_seconds
    # ambient helper with no open span: structured no-op
    with span("orphan") as none_span:
        assert none_span is None


def test_stage_breakdown_accumulates_repeated_stages():
    """A tick opens several spans per stage (chunked fetch/write-back,
    per-bucket score); the /debug/state breakdown must attribute the SUM
    of a stage's time, not just the final chunk's."""
    from foremast_tpu.observe.spans import span

    tracer = _tracer()
    with tracer.span("tick"):
        durations = []
        for _ in range(3):
            with span("chunk", stage="metric_fetch") as s:
                time.sleep(0.002)
            durations.append(s.duration)
    assert tracer.last_stage_seconds["metric_fetch"] == pytest.approx(
        sum(durations)
    )


def test_inherit_span_propagates_to_executor_threads():
    """Fetch-pool threads must see the tick's ambient span so their log
    records keep its trace_id (executor threads start context-empty)."""
    from concurrent.futures import ThreadPoolExecutor

    from foremast_tpu.observe.spans import current_span, inherit_span

    tracer = _tracer()

    def probe(_):
        sp = current_span()
        return sp.trace_id if sp is not None else None

    with tracer.span("tick") as root:
        with ThreadPoolExecutor(max_workers=4) as pool:
            ids = list(pool.map(inherit_span(probe), range(8)))
        assert ids == [root.trace_id] * 8
        # the submitting thread's context is untouched
        assert current_span() is root
    # without the wrapper the pool thread sees no span
    with tracer.span("tick2"):
        with ThreadPoolExecutor(max_workers=1) as pool:
            assert list(pool.map(probe, range(1))) == [None]


def test_span_ring_thread_safety():
    """Concurrent adds never lose the total count and never grow the
    buffer past capacity (newest spans win)."""
    import threading

    from foremast_tpu.observe.spans import SpanRing

    ring = SpanRing(capacity=128)

    def add_many(k):
        for i in range(500):
            ring.add({"name": f"t{k}-{i}"})

    threads = [
        threading.Thread(target=add_many, args=(k,)) for k in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert ring.total == 8 * 500
    assert len(ring) == 128
    snap = ring.snapshot()
    assert len(snap) == 128 and all(isinstance(e, dict) for e in snap)


def test_perfetto_dump_schema(tmp_path):
    """The JSONL dump is newline-delimited Chrome trace events —
    complete ("X") events with microsecond ts/dur and numeric pid/tid,
    the exact shape Perfetto's JSON importer accepts."""
    tracer = _tracer(tmp_path)
    with tracer.span("root"):
        with tracer.span("inner", stage="score", rows=4):
            pass
    path = tracer.flush()
    events = [json.loads(line) for line in open(path)]
    assert len(events) == 2
    for e in events:
        assert e["ph"] == "X" and e["cat"] == "foremast"
        assert isinstance(e["ts"], (int, float))
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["name"] and e["args"]["trace_id"] and e["args"]["span_id"]
    inner = next(e for e in events if e["name"] == "inner")
    assert inner["args"]["stage"] == "score" and inner["args"]["rows"] == 4


def test_json_formatter_exc_info_and_trace_correlation():
    """ctx_log/JsonFormatter records carry the active trace/span IDs and
    the full traceback on the exc_info path (ISSUE 1 satellite)."""
    import io

    buf = io.StringIO()
    setup_logging(stream=buf)
    log = logging.getLogger("foremast_tpu.test.exc")
    tracer = _tracer()
    with tracer.span("op") as sp:
        try:
            raise ValueError("boom")
        except ValueError:
            log.exception("failed")
    log.info("outside")
    exc_rec, out_rec = [
        json.loads(line) for line in buf.getvalue().splitlines()
    ]
    assert exc_rec["level"] == "error" and exc_rec["msg"] == "failed"
    assert "ValueError: boom" in exc_rec["exc"]
    assert "Traceback" in exc_rec["exc"]
    assert exc_rec["trace_id"] == sp.trace_id
    assert exc_rec["span_id"] == sp.span_id
    # outside any span the keys are absent, not empty
    assert "trace_id" not in out_rec and "span_id" not in out_rec


def test_gauge_family_cap_enforced():
    """BrainGauges really bounds its family set now (ISSUE 1 satellite):
    past the cap new metric names are dropped and counted while existing
    families keep updating."""
    reg = CollectorRegistry()
    g = BrainGauges(registry=reg, max_families=2)
    for m in ["m_a", "m_b", "m_c", "m_d"]:
        g.publish(m, "ns", "app", upper=1.0, lower=0.0)
    text = generate_latest(reg).decode()
    assert "foremastbrain_m_a_upper" in text
    assert "foremastbrain_m_b_upper" in text
    assert "foremastbrain_m_c_upper" not in text
    assert "foremastbrain_m_d_upper" not in text
    assert "foremastbrain_gauge_families_dropped_total 2.0" in text
    # the counter counts distinct FAMILIES, not publishes: republishing
    # a dropped name every tick must not inflate it
    g.publish("m_c", "ns", "app", upper=1.0, lower=0.0)
    text = generate_latest(reg).decode()
    assert "foremastbrain_gauge_families_dropped_total 2.0" in text
    # families created before the cap keep updating normally
    g.publish("m_a", "ns", "app", upper=9.0, lower=0.5)
    text = generate_latest(reg).decode()
    assert (
        'foremastbrain_m_a_upper{app="app",exported_namespace="ns"} 9.0'
        in text
    )
    # a second BrainGauges on the same registry shares the dropped
    # counter instead of exploding on duplicate registration
    g2 = BrainGauges(registry=reg, max_families=2)
    assert g2.dropped is g.dropped


def test_metrics_lint_default_registry_clean():
    """Tier-1 dashboard contract: every family the deployed
    worker+service+controller exports conforms to the naming convention
    and documented label sets (ISSUE 1 satellite)."""
    from foremast_tpu.observe.metrics_lint import (
        default_registry_families,
        lint_registry,
    )

    assert lint_registry(default_registry_families()) == []


def test_metrics_lint_flags_violations():
    from prometheus_client import Counter, Gauge

    from foremast_tpu.observe.metrics_lint import lint_registry

    reg = CollectorRegistry()
    Gauge("acme_rogue_metric", "wrong prefix", registry=reg)
    Counter(
        "foremast_worker_jobs", "undocumented extra label",
        ["status", "shard"], registry=reg,
    ).labels(status="done", shard="0").inc()
    problems = lint_registry(reg)
    assert any("acme_rogue_metric" in p for p in problems)
    assert any("shard" in p for p in problems)


def _demo_store_and_source(demo_traces, job_id="e2e"):
    nt, nv = demo_traces["normal"]
    st, sv = demo_traces["spike"]
    hist = np.tile(nv, 6).astype(np.float32)
    ht = 1700000000 + 60 * np.arange(len(hist), dtype=np.int64)
    src = ReplaySource()
    src.register("hist", (ht, hist))
    src.register("cur", (st, sv))
    store = InMemoryStore()
    store.create(
        Document(
            id=job_id,
            app_name="demo",
            # the correlation ID the service would have minted at create
            trace_id="svc00000cafe",
            current_config=(
                "error4xx== http://x/cur?query=namespace_pod"
                "%3Ahttp_server_requests_error_4xx%7Bnamespace%3D%22ns%22%7D"
            ),
            historical_config=(
                "error4xx== http://x/hist?query=namespace_app_per_pod"
                "%3Ahttp_server_requests_error_4xx%7Bnamespace%3D%22ns%22%7D"
            ),
        )
    )
    return store, src


def test_e2e_judgment_trace_pipeline(demo_traces, tmp_path):
    """ISSUE 1 acceptance: one demo judgment produces (1) stage
    histograms for >= 5 distinct stage labels, (2) a Perfetto-loadable
    JSONL dump whose spans share one trace ID, (3) JSON log lines
    carrying that same trace ID — then the controller leg (HttpKube over
    tests/fake_kube_server.py) lands its poll/transition/pause spans and
    transition counter in the same registry."""
    import io

    from prometheus_client import CollectorRegistry

    from foremast_tpu.observe.spans import Tracer
    from foremast_tpu.watch.analyst import LocalAnalyst
    from foremast_tpu.watch.controller import MonitorController
    from foremast_tpu.watch.crds import (
        DeploymentMonitor,
        MonitorPhase,
        MonitorStatus,
        Remediation,
        RemediationOption,
    )
    from foremast_tpu.watch.kubeapi import HttpKube
    from tests.fake_kube_server import FakeKubeServer

    store, src = _demo_store_and_source(demo_traces)
    buf = io.StringIO()
    setup_logging(stream=buf)
    reg = CollectorRegistry()
    tracer = Tracer(service="worker", registry=reg, trace_dir=str(tmp_path))
    worker = BrainWorker(store, src, BrainConfig(), tracer=tracer)
    worker.tick(now=1e12)

    # (1) stage histograms: >= 5 distinct stage labels on /metrics
    text = generate_latest(reg).decode()
    stages = {
        line.split('stage="')[1].split('"')[0]
        for line in text.splitlines()
        if line.startswith("foremast_tick_stage_seconds_count")
    }
    assert len(stages) >= 5, stages
    assert {"claim", "metric_fetch", "score", "decide"} <= stages

    # (2) Perfetto-loadable JSONL: valid events sharing ONE trace ID (the
    # tracer's own spans, a generation-2 collection's and the background
    # dump's, belong to no judgment)
    path = tracer.flush()
    dumped = [json.loads(line) for line in open(path)]
    events = [e for e in dumped if e["name"] not in ("python.gc", "trace.flush")]
    assert len(events) >= 5
    trace_ids = {e["args"]["trace_id"] for e in events}
    assert len(trace_ids) == 1
    (tid,) = trace_ids
    by_id = {e["args"]["span_id"]: e for e in events}
    roots = [e for e in events if not e["args"]["parent_id"]]
    assert len(roots) == 1 and roots[0]["name"] == "worker.tick"
    for e in events:
        assert e["ph"] == "X"
        if e["args"]["parent_id"]:
            assert e["args"]["parent_id"] in by_id  # parents are real spans

    # (3) JSON log lines carry the same trace ID
    logs = [json.loads(line) for line in buf.getvalue().splitlines()]
    traced = [rec for rec in logs if "trace_id" in rec]
    assert traced and all(rec["trace_id"] == tid for rec in traced)
    assert any(rec["msg"] == "tick complete" for rec in traced)
    # per-doc judgment line joins the tick trace to the REQUEST trace
    # the service stamped on the document
    judged = [rec for rec in traced if rec["msg"] == "judgment"]
    assert len(judged) == 1
    assert judged[0]["job_trace_id"] == "svc00000cafe"
    assert judged[0]["job_id"] == "e2e"

    # worker varz: stage breakdown + cache/arena state for /debug/state
    state = worker.debug_state()
    assert state["last_tick"]["docs"] == 1
    assert state["model_cache"]["fit_entries"] >= 1
    assert state["trace"]["spans_total"] >= len(dumped) >= len(events)
    # `gc` exists at 0 from the tracer's start; a collection may add it
    assert set(state["trace"]["last_stage_seconds"]) - {"gc"} == stages - {"gc"}

    # controller leg over a real HTTP kube fake: the unhealthy verdict
    # drives poll -> transition -> pause, counted and spanned
    with FakeKubeServer() as srv:
        kube = HttpKube(base_url=srv.url)
        srv.state.put(
            "deployments",
            "demo",
            {"metadata": {"name": "demo"}, "spec": {}},
        )
        kube.upsert_monitor(
            DeploymentMonitor(
                name="demo",
                namespace="demo",
                remediation=Remediation(option=RemediationOption.AUTO_PAUSE),
                status=MonitorStatus(
                    job_id="e2e", phase=MonitorPhase.RUNNING
                ),
            )
        )
        ctl = MonitorController(
            kube,
            analyst_factory=lambda ep: LocalAnalyst(store),
            tracer=tracer,
            registry=reg,
        )
        ctl.tick()
        mon = kube.get_monitor("demo", "demo")
        assert mon.status.phase == MonitorPhase.UNHEALTHY
        assert srv.state.objects["deployments"][("demo", "demo")]["spec"][
            "paused"
        ]
        ctl.tick()  # re-poll of an unchanged phase is NOT a transition
    text = generate_latest(reg).decode()
    assert (
        'foremast_controller_transitions_total{phase="Unhealthy"} 1.0'
        in text
    )
    names = {e["name"] for e in tracer.ring.snapshot()}
    assert {
        "controller.poll",
        "controller.get_status",
        "controller.update",
        "controller.pause",
    } <= names


def test_observe_server_endpoints(demo_traces):
    """The worker scrape port serves /metrics, /healthz and /debug/state
    (the reference exposed /metrics only)."""
    import urllib.error
    import urllib.request

    from prometheus_client import CollectorRegistry

    from foremast_tpu.observe.spans import Tracer, start_observe_server

    store, src = _demo_store_and_source(demo_traces, job_id="varz")
    reg = CollectorRegistry()
    tracer = Tracer(service="worker", registry=reg)
    worker = BrainWorker(store, src, BrainConfig(), tracer=tracer)
    worker.tick(now=1e12)
    srv, _thread = start_observe_server(
        0, registry=reg, state_fn=worker.debug_state, host="127.0.0.1"
    )
    try:
        base = f"http://127.0.0.1:{srv.server_address[1]}"

        def get(path):
            with urllib.request.urlopen(base + path) as r:
                return r.status, r.read().decode()

        code, body = get("/metrics")
        assert code == 200
        assert "foremast_tick_stage_seconds_bucket" in body
        code, body = get("/healthz")
        health = json.loads(body)
        assert code == 200 and health["ok"] and health["version"]
        code, body = get("/debug/state")
        state = json.loads(body)
        assert code == 200
        assert state["queue_depth"] == 0  # the one job completed
        assert state["store_ok"] is True
        assert state["config_fingerprint"]
        assert state["last_tick"]["docs"] == 1
        assert set(state["trace"]["last_stage_seconds"]) >= {
            "claim",
            "score",
            "decide",
        }
        try:
            get("/nope")
            raise AssertionError("expected 404")
        except urllib.error.HTTPError as e:
            assert e.code == 404
    finally:
        srv.shutdown()
        srv.server_close()




def test_controller_counts_only_phase_changes():
    """foremast_controller_transitions_total counts phase CHANGES: a
    poll that re-asserts the current phase must not increment (a rate()
    over the counter would otherwise measure poll frequency)."""
    from prometheus_client import CollectorRegistry

    from foremast_tpu.watch.analyst import JobStatus
    from foremast_tpu.watch.controller import MonitorController
    from foremast_tpu.watch.crds import (
        DeploymentMonitor,
        MonitorPhase,
        MonitorStatus,
    )
    from foremast_tpu.watch.kubeapi import InMemoryKube

    class StubAnalyst:
        phase = MonitorPhase.RUNNING

        def get_status(self, job_id):
            return JobStatus(phase=self.phase)

    stub = StubAnalyst()
    kube = InMemoryKube()
    kube.upsert_monitor(
        DeploymentMonitor(
            name="demo",
            namespace="demo",
            status=MonitorStatus(job_id="j1", phase=MonitorPhase.RUNNING),
        )
    )
    reg = CollectorRegistry()
    ctl = MonitorController(
        kube, analyst_factory=lambda ep: stub, registry=reg
    )
    ctl.tick()
    ctl.tick()  # still Running: re-assertions, not transitions
    text = generate_latest(reg).decode()
    assert 'phase="Running"' not in text
    stub.phase = MonitorPhase.UNHEALTHY
    ctl.tick()
    text = generate_latest(reg).decode()
    assert (
        'foremast_controller_transitions_total{phase="Unhealthy"} 1.0'
        in text
    )
