"""The model-backed kinds through `BrainWorker.tick()` against their plain
references (ISSUE 27, Tentpole section 3, case (a); ISSUE 31): claim, admit,
fetch, pack, cold prefill, warm window, decide, write-back, with no side
script. A cold tick, two warm ticks and a followed job, on the small models
of tests/test_backbone_model.py (kind `backbone`: Cohere2-MoE, context 20 >
sliding window 8) and tests/test_kimi_linear_model.py (kind `backbone_kda`:
Kimi-Linear). The cases here are the detector's, so they run over both
models; what only one model has is in that model's own files.

Tolerance. The model computes in float32 here, so the program's scores and
the reference's differ by the order of sums alone (2e-5 nats, as in
test_backbone_model.py); the threshold is put in the first gap over 1e-3 nats between two
reference scores from their 85th percentile up, fifty times that, so
every flag has to agree and every payload to match exactly.
"""

import dataclasses
import json
import time

import numpy as np
import pytest

from foremast_tpu.config import BrainConfig
from foremast_tpu.jobs import (
    BrainWorker,
    STATUS_COMPLETED_UNHEALTH,
    STATUS_PREPROCESS_COMPLETED,
)
from foremast_tpu.jobs.models import Document
from foremast_tpu.jobs.store import InMemoryStore
from foremast_tpu.metrics.source import MetricSource
from foremast_tpu.models import cohere2_moe_reference, kimi_linear_reference
from tests.test_backbone_model import tiny
from tests.test_kimi_linear_model import tiny as tiny_kda

# kind -> (the small model file's dict, the plain reference)
MODELS = {
    "backbone": (tiny, cohere2_moe_reference),
    "backbone_kda": (tiny_kda, kimi_linear_reference),
}

NOW = 1_760_000_000.0
CONTEXT, WINDOW, TICKS = 20, 6, 3
SERVICES = {"a": ("latency", "error4xx", "error5xx", "tps"), "b": ("latency", "tps"), "c": ("cpu",)}


class Source(MetricSource):
    concurrent_fetch = False

    def __init__(self):
        self.data = {}

    def fetch(self, url):
        return self.data[url]


def series(rng, n, at):
    t = at + np.arange(n)
    return (1.0 + 0.3 * np.sin(t / 3.0) + 0.2 * rng.standard_normal(n)).astype(np.float32)


class Fleet:
    def __init__(self):
        self.store, self.source = InMemoryStore(), Source()
        rng = np.random.default_rng(11)
        t0 = int(NOW) - 7 * 86_400
        self.ht = t0 + 60 * np.arange(CONTEXT, dtype=np.int64)
        self.end_time = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime(NOW + 3600))
        self.hist = {(s, a): series(rng, CONTEXT, 0) for s, al in SERVICES.items() for a in al}
        self.windows = [
            {(s, a): series(rng, WINDOW, CONTEXT + k) for s, al in SERVICES.items() for a in al}
            for k in range(TICKS)
        ]
        self.gen = dict.fromkeys(SERVICES, 0)
        for s in SERVICES:
            self.create(s)

    def url(self, which, s, a):
        return f"http://prom/{which}?q={a}:app{s}&end={self.ht[-1] + 60}&step=60"

    def create(self, s):
        cur = " ||".join(f"{a}== {self.url('cur', s, a)}" for a in SERVICES[s])
        hist = " ||".join(f"{a}== {self.url('hist', s, a)}" for a in SERVICES[s])
        for a in SERVICES[s]:
            self.source.data[self.url("hist", s, a)] = (self.ht, self.hist[(s, a)])
        doc = Document(
            id=f"job-{s}-{self.gen[s]}", app_name=f"app{s}", end_time=self.end_time,
            current_config=cur, historical_config=hist, strategy="continuous",
        )
        self.store.create(doc)
        return doc.id

    def install(self, k):
        self.ct = self.ht[-1] + 60 * (1 + k + np.arange(WINDOW, dtype=np.int64))
        for (s, a), w in self.windows[k].items():
            self.source.data[self.url("cur", s, a)] = (self.ct, w)

    def follow_terminal(self):
        followed = []
        for s in SERVICES:
            doc = self.store._docs[f"job-{s}-{self.gen[s]}"]
            if doc.status == STATUS_COMPLETED_UNHEALTH:
                self.gen[s] += 1
                followed.append(self.create(s))
        return followed


@pytest.fixture(params=sorted(MODELS))
def model_file(request, tmp_path, monkeypatch):
    """(kind, the model file's dict, its reference), the file in place."""
    kind = request.param
    d = MODELS[kind][0]("float32")
    path = tmp_path / f"tiny-{kind}.json"
    path.write_text(json.dumps(d))
    monkeypatch.setenv("FOREMAST_BACKBONE_MODEL", str(path))
    monkeypatch.setenv("FOREMAST_BACKBONE_CONTEXT", str(CONTEXT))
    monkeypatch.setenv("FOREMAST_BACKBONE_ROWS", "8")
    return kind, d, MODELS[kind][1]


def reference_scores(d, fleet, ref):
    """{(tick, service, alias): scores [WINDOW]} by one full forward each."""
    return {
        (k, s, a): np.asarray(ref.window_scores(d, d["share"], fleet.hist[(s, a)], w)[0])
        for k in range(TICKS) for (s, a), w in fleet.windows[k].items()
    }


def gap_threshold(scores: dict) -> float:
    flat = np.sort(np.concatenate(list(scores.values())))
    at = int(0.85 * len(flat))
    while flat[at + 1] - flat[at] < 1e-3:
        at += 1
    return float((flat[at] + flat[at + 1]) / 2)


def test_cold_tick_two_warm_ticks_and_a_followed_job_match_the_reference(model_file):
    kind, d, ref = model_file
    fleet = Fleet()
    want = reference_scores(d, fleet, ref)
    thr = gap_threshold(want)
    cfg = BrainConfig(algorithm=kind, max_cache_size=64)
    cfg = dataclasses.replace(cfg, anomaly=dataclasses.replace(cfg.anomaly, threshold=thr))
    worker = BrainWorker(fleet.store, fleet.source, config=cfg, claim_limit=16, worker_id="bb")
    det = worker._mvj.backbone
    seen_unhealthy = seen_healthy = 0
    for k in range(TICKS):
        fleet.install(k)
        before = det.counters()
        assert worker.tick(now=NOW + 150 + 60 * k) == len(SERVICES)
        after = det.counters()
        if k == 0:
            # cold: every sequence prefilled once, all but its last point
            assert after["prefill_tokens"] == 7 * (CONTEXT - 1)
            assert after["cache_misses"] == 7 and worker._fast_kinds[kind] == 0
        else:
            # warm, a followed job included: nothing prefilled, every row found
            assert after["prefill_tokens"] == before["prefill_tokens"]
            assert after["cache_misses"] == before["cache_misses"]
            assert after["cache_hits"] - before["cache_hits"] == 7
            assert worker._fast_kinds[kind] == k * len(SERVICES)
        assert after["window_tokens"] - before["window_tokens"] == 7 * WINDOW
        assert after["dropped_tokens"] == 0 and after["cache_rows_live"] == 7
        # the test's Cohere2 share holds 2 of 16 experts: blocks add their rows back
        sorted_share = kind != "backbone"
        assert after["sorted_moe_tokens"] == (after["window_tokens"] if sorted_share else 0)
        if kind == "backbone":
            assert after["fused_attn_tokens"] == 0  # off a TPU every dispatch attends through `attend`
        else:
            # every window token saw the 19 cached positions and its own window up to itself
            seen = after["latent_positions"] - before["latent_positions"]
            assert seen == 7 * (WINDOW * (CONTEXT - 1) + WINDOW * (WINDOW + 1) // 2)
        for s, aliases in SERVICES.items():
            doc = fleet.store._docs[f"job-{s}-{fleet.gen[s]}"]
            over = np.stack([want[(k, s, a)] > thr for a in aliases]).any(axis=0)
            if not over.any():
                assert doc.status == STATUS_PREPROCESS_COMPLETED
                seen_healthy += 1
                continue
            seen_unhealthy += 1
            assert doc.status == STATUS_COMPLETED_UNHEALTH
            # the flagged (timestamp, value) pairs of every alias
            for a in aliases:
                pairs = doc.anomaly_info["values"][a]
                np.testing.assert_array_equal(pairs[0::2], fleet.ct[over])
                np.testing.assert_array_equal(
                    np.asarray(pairs[1::2], np.float32), fleet.windows[k][(s, a)][over]
                )
        fleet.follow_terminal()
    assert seen_unhealthy >= 2 and seen_healthy >= 2
    assert sum(fleet.gen.values()) >= 1  # a followed job was judged warm


def test_program_scores_match_the_reference_through_the_judge(model_file):
    """The scores themselves, not only the flags they give: the detector's
    prefill and window dispatches on the fleet's own sequences."""
    kind, d, ref = model_file
    fleet = Fleet()
    want = reference_scores(d, fleet, ref)
    from foremast_tpu.engine.backbone import BackboneDetector

    det = BackboneDetector(model_types=(d["model_type"],))
    keys = [(kind, s, a, "h") for s, al in SERVICES.items() for a in al]
    hists = [fleet.hist[k[1:3]] for k in keys]
    entries = det.ensure(keys, hists)
    scales = np.array([e[0] for e in entries], np.float32)
    for k in range(TICKS):
        cur = np.zeros((len(keys), 8), np.float32)
        cur[:, :WINDOW] = np.stack([fleet.windows[k][key[1:3]] for key in keys])
        valid = np.broadcast_to(np.arange(8) < WINDOW, cur.shape)
        got = det.score(keys, scales, cur, valid)
        for i, key in enumerate(keys):
            np.testing.assert_allclose(got[i, :WINDOW], want[(k, *key[1:3])], atol=2e-5)
    assert det.counters()["cache_hits"] == TICKS * len(keys)


def test_score_reads_back_its_dispatch_under_stage_spans_alone(model_file, monkeypatch, tmp_path):
    """One warm `score` under a Tracer: six stage spans, in order, siblings
    on one thread; the wait for the dispatch runs under `judge.result_wait`,
    and its one read-back and the counting under `judge.decode`."""
    import jax
    from prometheus_client import CollectorRegistry

    from foremast_tpu.engine import backbone
    from foremast_tpu.observe.spans import Tracer, current_span

    kind, d, _ = model_file
    fleet = Fleet()
    det = backbone.BackboneDetector(model_types=(d["model_type"],))
    keys = [(kind, s, a, "h") for s, al in SERVICES.items() for a in al]
    scales = np.array(
        [e[0] for e in det.ensure(keys, [fleet.hist[k[1:3]] for k in keys])], np.float32
    )
    cur = np.stack([fleet.windows[0][key[1:3]] for key in keys])
    valid = np.ones(cur.shape, bool)
    seen = []

    def under_span(name, fn):
        def wrapped(*a, **kw):
            seen.append((name, current_span().name))
            return fn(*a, **kw)

        return wrapped

    monkeypatch.setattr(
        det.model, "window_counters", under_span("window_counters", det.model.window_counters)
    )
    monkeypatch.setattr(jax, "device_get", under_span("device_get", jax.device_get))
    monkeypatch.setattr(
        jax, "block_until_ready", under_span("block_until_ready", jax.block_until_ready)
    )
    tracer = Tracer(service="test", registry=CollectorRegistry(), trace_dir=str(tmp_path))
    with tracer.span("worker.tick") as root:
        det.score(keys, scales, cur, valid)
    staged = [
        e for e in tracer.ring.snapshot() if "stage" in e["args"] and e["name"] != "python.gc"
    ]
    assert [e["name"] for e in staged] == [
        "judge.tokenize", "judge.arena_assemble", "judge.h2d", "judge.score",
        "judge.result_wait", "judge.decode",
    ]
    assert {e["args"]["parent_id"] for e in staged} == {root.span_id}
    assert len({e["tid"] for e in staged}) == 1
    for a, b in zip(staged, staged[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1.0, (a["name"], b["name"])
    assert seen == [
        ("block_until_ready", "judge.result_wait"),
        ("device_get", "judge.decode"),
        ("window_counters", "judge.decode"),
    ]
    assert det.counters()["window_tokens"] == valid.sum()


def test_a_recycled_row_sends_its_document_back_to_the_prefill(model_file, monkeypatch):
    """A cache smaller than the fleet recycles rows; the document whose row
    went finds no warm entry and is prefilled again: slower, never wrong."""
    monkeypatch.setenv("FOREMAST_BACKBONE_ROWS", "4")
    fleet = Fleet()
    cfg = BrainConfig(algorithm=model_file[0], max_cache_size=64)
    cfg = dataclasses.replace(cfg, anomaly=dataclasses.replace(cfg.anomaly, threshold=1e9))
    worker = BrainWorker(fleet.store, fleet.source, config=cfg, claim_limit=16, worker_id="bb")
    det = worker._mvj.backbone
    for k in range(2):
        fleet.install(k)
        assert worker.tick(now=NOW + 150 + 60 * k) == len(SERVICES)
        assert all(doc.status == STATUS_PREPROCESS_COMPLETED for doc in fleet.store._docs.values())
    assert det.counters()["cache_misses"] > 7 and det.arena.cap == 4


def test_an_unknown_algorithm_is_an_error_at_load():
    assert BrainConfig.from_env({"ML_ALGORITHM": "backbone"}).algorithm == "backbone"
    assert BrainConfig.from_env({"ML_ALGORITHM": "backbone_kda"}).algorithm == "backbone_kda"
    assert BrainConfig.from_env({"ML_ALGORITHM": "ewma"}).algorithm == "ewma"
    with pytest.raises(ValueError, match="unknown ML_ALGORITHM 'backbon'"):
        BrainConfig.from_env({"ML_ALGORITHM": "backbon"})


def test_known_univariate_names_are_the_engine_s_registry():
    import foremast_tpu.models  # noqa: F401  (registers seasonal, prophet, ...)
    from foremast_tpu.config import JOINT_ALGORITHMS, UNIVARIATE_ALGORITHMS
    from foremast_tpu.engine.multivariate import MULTIVARIATE_ALGOS
    from foremast_tpu.engine.scoring import AI_MODEL

    assert UNIVARIATE_ALGORITHMS == frozenset(AI_MODEL)
    assert JOINT_ALGORITHMS == MULTIVARIATE_ALGOS
