"""Kind `backbone_diffusion` through `BrainWorker.tick()`: the
block-diffusion backbone on the same entry points as kinds `backbone` and
`backbone_kda` — claim, admit, fetch, pack, the cold prefill of whole blocks,
one window dispatch a tick for each alias count, decide, write-back — against the plain
reference's block-by-block rule, on the small model of
tests/test_sdar_moe_model.py. The fleet, the reference's scores and the
threshold's place are tests/test_backbone_worker.py's.

Tolerance: the model computes in float32 here, so the program's scores and
the reference's differ by the order of sums alone (2e-5 nats); the
threshold sits in a gap of the reference's scores fifty times wider, so
every flag has to agree and every payload to match exactly.
"""

import dataclasses
import json

import numpy as np
import pytest

from foremast_tpu.config import BrainConfig
from foremast_tpu.jobs import (
    BrainWorker,
    STATUS_COMPLETED_UNHEALTH,
    STATUS_PREPROCESS_COMPLETED,
)
from foremast_tpu.models import sdar_moe_reference as ref
from tests.test_backbone_worker import (
    CONTEXT, NOW, SERVICES, TICKS, WINDOW, Fleet, gap_threshold, reference_scores,
)
from tests.test_sdar_moe_model import tiny

KIND = "backbone_diffusion"
SEQS = sum(len(a) for a in SERVICES.values())


@pytest.fixture
def model_file(tmp_path, monkeypatch):
    d = tiny("float32")
    path = tmp_path / "tiny-sdar.json"
    path.write_text(json.dumps(d))
    monkeypatch.setenv("FOREMAST_BACKBONE_MODEL", str(path))
    monkeypatch.setenv("FOREMAST_BACKBONE_CONTEXT", str(CONTEXT))
    monkeypatch.setenv("FOREMAST_BACKBONE_ROWS", "8")
    return d


def _rows(det) -> dict:
    return {name: np.asarray(leaf).copy() for name, leaf in det.arena.state.items()}


def test_cold_tick_two_warm_ticks_and_a_followed_job_match_the_reference(model_file, monkeypatch):
    """The verdicts and payloads are the reference's on every tick, a
    followed job included; one window dispatch a tick; after the cold tick
    no row changes by a bit; the kind's counters and gauge families."""
    from prometheus_client import CollectorRegistry

    from foremast_tpu.observe.gauges import WorkerMetrics

    d = model_file
    fleet = Fleet()
    want = reference_scores(d, fleet, ref)
    thr = gap_threshold(want)
    cfg = BrainConfig(algorithm=KIND, max_cache_size=64)
    cfg = dataclasses.replace(cfg, anomaly=dataclasses.replace(cfg.anomaly, threshold=thr))
    registry = CollectorRegistry()
    worker = BrainWorker(fleet.store, fleet.source, config=cfg, claim_limit=16, worker_id="sdar",
                         metrics=WorkerMetrics(registry=registry))
    det = worker._mvj.backbone
    assert det.model.MODEL_TYPE == "sdar_moe" and det.ctx_cap == 24
    dispatches = []
    score_window = det.model.score_window
    monkeypatch.setattr(det.model, "score_window",
                        lambda *a, **k: dispatches.append(1) or score_window(*a, **k))
    held = None
    unhealthy = healthy = 0
    for k in range(TICKS):
        fleet.install(k)
        before = len(dispatches)
        assert worker.tick(now=NOW + 150 + 60 * k) == len(SERVICES)
        if k:
            # warm: one window dispatch a tick for each alias count the pack
            # stacks apart (4, 2 and 1 here; the cell's fleet has only 4s),
            # a followed job's sequences in it too
            assert len(dispatches) - before == len({len(a) for a in SERVICES.values()})
        if held is None:
            held = _rows(det)
            assert set(held) == {"k", "v", "n", "last", "scale"}
            assert int((held["n"] == CONTEXT).sum()) == SEQS  # 20 points: 5 whole blocks
        else:
            for name, leaf in _rows(det).items():
                np.testing.assert_array_equal(leaf, held[name], err_msg=name)
        for s, aliases in SERVICES.items():
            doc = fleet.store._docs[f"job-{s}-{fleet.gen[s]}"]
            over = np.stack([want[(k, s, a)] > thr for a in aliases]).any(axis=0)
            if not over.any():
                assert doc.status == STATUS_PREPROCESS_COMPLETED, (k, s)
                healthy += 1
                continue
            unhealthy += 1
            assert doc.status == STATUS_COMPLETED_UNHEALTH, (k, s)
            for a in aliases:
                pairs = doc.anomaly_info["values"][a]
                np.testing.assert_array_equal(pairs[0::2], fleet.ct[over])
                np.testing.assert_array_equal(
                    np.asarray(pairs[1::2], np.float32), fleet.windows[k][(s, a)][over])
        fleet.follow_terminal()
    assert unhealthy >= 2 and healthy >= 2 and sum(fleet.gen.values()) >= 1
    c = det.counters()
    assert c["prefill_tokens"] == SEQS * CONTEXT and c["cache_misses"] == SEQS
    assert c["window_tokens"] == TICKS * SEQS * WINDOW and c["dropped_tokens"] == 0
    # a window of 6: 4 noisy copies of 4 tokens a point, block 0's 4 clean tokens
    assert c["denoise_tokens"] == TICKS * SEQS * 4 * WINDOW
    assert c["clean_tokens"] == TICKS * SEQS * 4
    assert c["fused_attn_tokens"] == 0  # off a TPU every dispatch attends through `attend`
    assert c["sorted_moe_tokens"] == c["window_tokens"]
    assert len(c["expert_tokens"]) == 8
    assert worker._mvj.backbone_counters() == c
    assert worker._fast_kinds[KIND] == (TICKS - 1) * len(SERVICES)
    samples = {
        (sample.name, sample.labels.get("kind")): sample.value
        for family in registry.collect() if family.name.startswith("foremast_backbone")
        for sample in family.samples if not sample.name.endswith("_created")
    }
    assert samples[("foremast_backbone_denoise_tokens_total", KIND)] == c["denoise_tokens"]
    assert samples[("foremast_backbone_clean_tokens_total", KIND)] == c["clean_tokens"]
    assert samples[("foremast_backbone_window_tokens_total", KIND)] == c["window_tokens"]
    assert samples[("foremast_backbone_sorted_moe_tokens_total", KIND)] == c["window_tokens"]
    assert samples[("foremast_backbone_cache_rows_live", KIND)] == SEQS
    assert {kind for _, kind in samples} == {KIND}
    worker.close()


def test_program_scores_match_the_reference_through_the_detector(model_file):
    """The scores themselves, through the detector's prefill and window
    dispatches, for histories that are and are not whole blocks (20 and 19
    points: the latter caches its newest 16)."""
    from foremast_tpu.engine.backbone import BackboneDetector

    d = model_file
    fleet = Fleet()
    det = BackboneDetector(model_types=("sdar_moe",))
    keys = [(KIND, s, a, "h") for s, al in SERVICES.items() for a in al]
    hists = [fleet.hist[k[1:3]][(i % 2):] for i, k in enumerate(keys)]
    entries = det.ensure(keys, hists)
    assert [e[1] for e in entries] == [20 if i % 2 == 0 else 16 for i in range(len(keys))]
    scales = np.array([e[0] for e in entries], np.float32)
    for k in range(TICKS):
        cur = np.zeros((len(keys), 8), np.float32)
        cur[:, :WINDOW] = np.stack([fleet.windows[k][key[1:3]] for key in keys])
        valid = np.broadcast_to(np.arange(8) < WINDOW, cur.shape)
        got = det.score(keys, scales, cur, valid)
        for i, key in enumerate(keys):
            want, _ = ref.window_scores(d, d["share"], hists[i], fleet.windows[k][key[1:3]])
            np.testing.assert_allclose(got[i, :WINDOW], np.asarray(want), atol=2e-5)
    assert det.counters()["cache_hits"] == TICKS * len(keys)
