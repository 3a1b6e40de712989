"""Kind `backbone_kda` through `BrainWorker.tick()` (ISSUE 31): the
Kimi-Linear backbone on the same entry points as kind `backbone` —
`BrainWorker.tick()` -> `JOINT_KINDS` -> `BackboneDetector` -> `TreeArena`
— against the plain reference, on the small model of
tests/test_kimi_linear_model.py. The fleet, the reference's scores and the
threshold's place are tests/test_backbone_worker.py's, which also runs its
detector-level cases over this kind.

What is this kind's own here: a warm tick leaves every leaf of every row
bit-identical (the window runs as the continuation of the cached state and
what it made of the state is thrown away), the kind's counters and gauge
families, and a model file of the wrong `model_type` under either kind.
"""

import dataclasses
import json

import jax
import numpy as np
import pytest

from foremast_tpu.config import BrainConfig
from foremast_tpu.engine.kinds import JOINT_KINDS
from foremast_tpu.jobs import (
    BrainWorker,
    STATUS_COMPLETED_UNHEALTH,
    STATUS_PREPROCESS_COMPLETED,
)
from foremast_tpu.models import kimi_linear_reference as ref
from tests.test_backbone_model import tiny as tiny_cohere
from tests.test_backbone_worker import (
    CONTEXT, NOW, SERVICES, TICKS, WINDOW, Fleet, gap_threshold, reference_scores,
)
from tests.test_kimi_linear_model import tiny

KIND = "backbone_kda"


@pytest.fixture
def model_file(tmp_path, monkeypatch):
    d = tiny("float32")
    path = tmp_path / "tiny-kimi.json"
    path.write_text(json.dumps(d))
    monkeypatch.setenv("FOREMAST_BACKBONE_MODEL", str(path))
    monkeypatch.setenv("FOREMAST_BACKBONE_CONTEXT", str(CONTEXT))
    monkeypatch.setenv("FOREMAST_BACKBONE_ROWS", "8")
    return d


def _rows(det) -> dict:
    return {name: np.asarray(leaf).copy() for name, leaf in det.arena.state.items()}


def test_cold_tick_two_warm_ticks_and_a_followed_job_leave_the_rows_as_they_were(model_file):
    """The verdicts are the reference's on every tick, a followed job
    included; after the cold tick no leaf of any row changes by a bit."""
    from prometheus_client import CollectorRegistry

    from foremast_tpu.observe.gauges import WorkerMetrics

    d = model_file
    fleet = Fleet()
    want = reference_scores(d, fleet, ref)
    thr = gap_threshold(want)
    cfg = BrainConfig(algorithm=KIND, max_cache_size=64)
    cfg = dataclasses.replace(cfg, anomaly=dataclasses.replace(cfg.anomaly, threshold=thr))
    registry = CollectorRegistry()
    worker = BrainWorker(fleet.store, fleet.source, config=cfg, claim_limit=16, worker_id="kda",
                         metrics=WorkerMetrics(registry=registry))
    det = worker._mvj.backbone
    assert det.model.MODEL_TYPE == "kimi_linear"
    held = None
    unhealthy = 0
    for k in range(TICKS):
        fleet.install(k)
        assert worker.tick(now=NOW + 150 + 60 * k) == len(SERVICES)
        if held is None:
            held = _rows(det)
            assert set(held) == {"S", "conv", "c", "kr", "n", "last", "scale"}
            assert held["S"].dtype == np.float32
            assert np.abs(held["S"]).max() > 0 and np.abs(held["c"].astype(np.float32)).max() > 0
        else:
            for name, leaf in _rows(det).items():
                np.testing.assert_array_equal(leaf, held[name], err_msg=name)
        for s, aliases in SERVICES.items():
            doc = fleet.store._docs[f"job-{s}-{fleet.gen[s]}"]
            over = np.stack([want[(k, s, a)] > thr for a in aliases]).any(axis=0)
            status = STATUS_COMPLETED_UNHEALTH if over.any() else STATUS_PREPROCESS_COMPLETED
            assert doc.status == status, (k, s)
            unhealthy += int(over.any())
        fleet.follow_terminal()
    assert unhealthy >= 2 and sum(fleet.gen.values()) >= 1
    c = det.counters()
    assert c["prefill_tokens"] == 7 * (CONTEXT - 1) and c["cache_misses"] == 7
    assert c["window_tokens"] == TICKS * 7 * WINDOW and c["dropped_tokens"] == 0
    assert c["latent_positions"] == TICKS * 7 * (WINDOW * (CONTEXT - 1) + WINDOW * (WINDOW + 1) // 2)
    assert "fused_attn_tokens" not in c and len(c["expert_tokens"]) == 8  # Cohere2's kernel's counter
    assert c["fused_kda_tokens"] == 0  # off a TPU every dispatch runs `kda_chunks`
    assert c["sorted_moe_tokens"] == c["window_tokens"]
    assert worker._mvj.backbone_counters() == c
    assert worker._fast_kinds[KIND] == (TICKS - 1) * len(SERVICES) and worker._fast_kinds["backbone"] == 0
    # the gauge families carry the kind, and only the counters its model keeps
    samples = {
        (sample.name, sample.labels.get("kind")): sample.value
        for family in registry.collect() if family.name.startswith("foremast_backbone")
        for sample in family.samples if not sample.name.endswith("_created")
    }
    assert samples[("foremast_backbone_window_tokens_total", KIND)] == c["window_tokens"]
    assert samples[("foremast_backbone_latent_positions_total", KIND)] == c["latent_positions"]
    assert samples[("foremast_backbone_sorted_moe_tokens_total", KIND)] == c["window_tokens"]
    assert samples[("foremast_backbone_cache_rows_live", KIND)] == 7
    assert {kind for _, kind in samples} == {KIND}
    worker.close()


@pytest.mark.parametrize("kind,wrong", [("backbone", tiny), ("backbone_kda", tiny_cohere)])
def test_a_model_file_of_the_wrong_model_type_is_an_error_at_load(kind, wrong, tmp_path, monkeypatch):
    """Each kind takes its own model's files: the other's is refused when
    the kind builds its detector, by name, and nothing is judged with it."""
    path = tmp_path / "wrong.json"
    path.write_text(json.dumps(wrong("float32")))
    monkeypatch.setenv("FOREMAST_BACKBONE_MODEL", str(path))
    monkeypatch.setenv("FOREMAST_BACKBONE_CONTEXT", str(CONTEXT))
    monkeypatch.setenv("FOREMAST_BACKBONE_ROWS", "8")
    fleet = Fleet()
    worker = BrainWorker(fleet.store, fleet.source, config=BrainConfig(algorithm=kind),
                         claim_limit=16, worker_id="wrong")
    with pytest.raises(ValueError, match="model_type .* is not one this detector kind takes"):
        worker._mvj.backbone
    worker.close()


def test_both_kinds_are_one_class_over_one_detector_and_default_to_their_own_file():
    from foremast_tpu.engine.backbone import MODELS, load_model
    from foremast_tpu.engine.kinds.backbone import BackboneKind

    a, b, c = JOINT_KINDS["backbone"], JOINT_KINDS[KIND], JOINT_KINDS["backbone_diffusion"]
    assert type(a) is type(b) is type(c) is BackboneKind
    assert (a.model_types, b.model_types, c.model_types) == (
        ("cohere2_moe",), ("kimi_linear",), ("sdar_moe",))
    assert a.selectors == {"backbone": (1, None)} and b.selectors == {KIND: (1, None)}
    assert set(MODELS) == {"cohere2_moe", "kimi_linear", "sdar_moe"}
    for kind in (a, b, c):
        model, cfg = load_model(None, kind.model_types)
        assert model.MODEL_TYPE == kind.model_types[0] and cfg.hidden_size in (4096, 2304, 2048)
    # the interface the detector reaches a model through
    for name in MODELS.values():
        module = __import__(name, fromlist=["x"])
        for attr in ("MODEL_TYPE", "DEFAULT_MODEL_FILE", "Config", "cached_span", "prefill_seqs",
                     "prefill_chunk_len", "cache_template", "init_params", "series_scale",
                     "tokenize", "prefill_chunk", "finish_rows", "score_window",
                     "window_counters", "WINDOW_COUNTERS"):
            assert hasattr(module, attr), (name, attr)


def test_the_three_leaf_row_is_one_arena_row():
    """`TreeArena(fixed_rows=)` over the row of state, tails and latents:
    its byte count is the template's, allocated once."""
    from foremast_tpu.engine.arena import TreeArena
    from foremast_tpu.models import kimi_linear as m

    cfg = m.Config.from_dict(tiny("bfloat16"))
    template = m.cache_template(cfg, 24)
    arena = TreeArena(template, fixed_rows=5)
    want = sum(int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
               for leaf in jax.tree.leaves(template))
    assert arena.row_bytes == want == 4 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 2) + 24 * 40 * 2 + 12
