"""Tests of the cell `command-a-plus-ep8-7d.prefix-recheck` beside those
that find it by name in test_chipbench.py (the `--tiny` rehearsal of every
cell, both faults of every fleet kind, the contract of BENCHMARK.json):

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_backbone_cell.py -q
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import backbone_model, spec  # noqa: E402

CELL = "command-a-plus-ep8-7d.prefix-recheck"


def _model_file(cfg: dict) -> dict:
    return json.load(open(os.path.join(ROOT, cfg["env"]["FOREMAST_BACKBONE_MODEL"])))


def test_byte_and_operation_functions_match_the_program_s_shapes():
    """row_bytes, weight_bytes and the leaf's capacity are the program's own
    (the arena's template, the parameters' shapes, the detector's rounding);
    the operations are the ISSUE's reckoning."""
    import jax

    from foremast_tpu.engine.arena import TreeArena
    from foremast_tpu.engine.backbone import BackboneDetector
    from foremast_tpu.models import cohere2_moe as m

    cfg = spec.Cell(CELL).config
    model = m.Cohere2MoeConfig.from_file(os.path.join(ROOT, cfg["env"]["FOREMAST_BACKBONE_MODEL"]))
    det = BackboneDetector(
        model_file=os.path.join(ROOT, cfg["env"]["FOREMAST_BACKBONE_MODEL"]),
        context=cfg["history_points"], rows=int(cfg["env"]["FOREMAST_BACKBONE_ROWS"]),
    )
    cached = cfg["history_points"] - 1
    assert det.ctx_cap == backbone_model.context_capacity(cached) == 10112
    arena = TreeArena(m.cache_template(model, det.ctx_cap), fixed_rows=48)
    assert arena.row_bytes == backbone_model.row_bytes() == cfg["row_bytes"]["backbone"] == 91_750_412
    shapes = jax.eval_shape(lambda: m.init_params(model))
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert held == backbone_model.weight_bytes() == 9_466_626_048
    # the tick the issue reckons: 1,440 new tokens x 4.9 GFLOP = 7.0 TFLOP
    assert abs(backbone_model.token_flops() / 4.8936e9 - 1) < 1e-3
    tick = 12 * backbone_model.window_flops(4, 32)
    assert abs(tick / 7.0468e12 - 1) < 1e-3
    # the dense part is two operations a parameter a token touches: attention,
    # router, one routed expert in expectation, the shared experts, the head
    expert = 3 * 4096 * 4096
    touched = 4 * (142_606_336 + 4096 * 128 + (1 + 4) * expert) + 32768 * 4096
    attention = 4 * 128 * 128 * (10079 + 15.5 + 3 * 4096)
    assert abs(backbone_model.token_flops() - (2 * touched + attention)) < 1e3
    # a tick's least bytes: the weights once and every row's attended part
    assert backbone_model.window_bytes(12, 4, 1) == held + 48 * 4096 * (10079 + 3 * 4096)


def test_the_configuration_holds_the_model_file_s_keys_and_states_its_cuts():
    """Every key of the model file (the catalog row's config, verbatim)
    stands in the configuration under the same name with the same value,
    but for the keys `reduced` lists, which give what this chip holds and
    agree with the model file's `share`; the published counts are stated."""
    cfg = spec.Cell(CELL).config
    model = _model_file(cfg)
    entry = next(c for c in spec.benchmark()["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size", "services"]
    own = {"name", "source", "what", "share", "weights_seed", "assumed"}
    for key, value in model.items():
        if key in own or key in cfg["reduced"]:
            continue
        assert cfg[key] == value, key
    share = model["share"]
    assert cfg["share"] == share
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (
        share["layers_held"], share["experts_held"], share["vocab_rows_held"])
    assert cfg["published"] == {k: model[k] for k in ("num_hidden_layers", "num_experts", "vocab_size")}
    # the floors a model_config keeps: a whole period and four layers, 8
    # routed experts, an eighth of the vocabulary; no width differs
    assert cfg["layer_types"][:4] == ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= model["vocab_size"]
    # the fleet fills the cache, whose capacity is fixed at it
    seqs = sum(g["services"] * len(g["aliases"]) for g in cfg["fleet"])
    assert seqs == int(cfg["env"]["FOREMAST_BACKBONE_ROWS"]) == 48
    assert int(cfg["env"]["FOREMAST_SWEEP_SLICE_DOCS"]) % int(cfg["env"]["FOREMAST_COLD_CHUNK_DOCS"]) == 0
    assert cfg["anomaly_threshold"] == cfg["score_threshold_nats"] > np.log(cfg["vocab_size"])


@pytest.mark.parametrize("seed", [2700000301, 2700000302])
def test_the_control_below_the_stated_precision_fails_the_kind_s_limit(seed):
    """The reference with float8 weights and bfloat16 sums, in the program's
    place at the rehearsal's size: `flip_rate.backbone` over its limit."""
    from chipbench import control

    cell = spec.Cell(CELL)
    out = control.control_margin(cell.sized(True), cell.traffic, seed=seed, sweeps=15)
    value, limit = out["by_kind"]["backbone"]
    assert out["correct"] is False and limit is not None and value > limit, out


def test_the_reference_s_reuse_of_a_history_is_checked_not_assumed(monkeypatch):
    """A continuation that does not see the history's keys as one full
    forward does stops the comparison."""
    from chipbench.references import backbone as ref

    cell = spec.Cell(CELL)
    cfg = cell.sized(True)
    model = ref.model_of(cfg)
    rng = np.random.default_rng(3)
    seqs = [{"history": 1 + 0.3 * rng.standard_normal(40).astype(np.float32),
             "windows": 1 + 0.3 * rng.standard_normal((3, 30)).astype(np.float32)}]
    got = ref.score_sequences(model, seqs)
    assert got[0].shape == (3, 30) and np.isfinite(got[0]).all()
    real = ref.windows_layer

    def forgetful(model, w, kind, xw, k_hist, v_hist, n, real_windows):
        return real(model, w, kind, xw, k_hist[::-1], v_hist, n, real_windows)

    monkeypatch.setattr(ref, "windows_layer", forgetful)
    with pytest.raises(SystemExit, match="differs from one full forward"):
        ref.score_sequences(model, seqs)


def test_the_new_readers_read_what_they_say_and_nothing_from_an_older_program():
    from chipbench.readers import counter_max_over_mean, module_compute_roofline

    record = {"counters": {"backbone.expert_tokens.0": 10.0, "backbone.expert_tokens.1": 30.0}}
    assert counter_max_over_mean.read(record, {"prefix": "backbone.expert_tokens."}) == 1.5
    assert counter_max_over_mean.read({"counters": {}}, {"prefix": "backbone.expert_tokens."}) is None
    params = spec.layer_metric("backbone_window_roofline")["params"]
    cfg = spec.Cell(CELL).config
    empty = {"counters": {}, "config": cfg, "trace": {"modules": {}}, "device_kind": "TPU v5 lite"}
    assert module_compute_roofline.read(empty, params) is None
    traced = {
        "counters": {"fast_docs.backbone": 120.0}, "config": cfg, "device_kind": "TPU v5 lite",
        "trace": {"modules": {"jit_score_window": {"seconds": 1.0, "count": 10.0}}},
    }
    # ten dispatches of 12 docs: 70.5 TFLOP of model work in a second of
    # device time is 35.8% of 197 TFLOP/s (the operations bound: 0.358 s
    # against 0.169 s for the bytes)
    share = module_compute_roofline.read(traced, params)
    assert abs(share - 100 * 10 * 12 * backbone_model.window_flops(4, 32) / 197e12) < 1e-9
    assert 35 < share < 36.5
