"""Tests of the cell `kimi-linear-ep2-7d.state-recheck` beside those that
find it by name in test_chipbench.py (the `--tiny` rehearsal of every cell,
both faults of every fleet kind, the contract of BENCHMARK.json):

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_kimi_linear_cell.py -q
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import kimi_linear_model, spec  # noqa: E402

CELL = "kimi-linear-ep2-7d.state-recheck"
KIND = "backbone_kda"


def _model_file(cfg: dict) -> dict:
    return json.load(open(os.path.join(ROOT, cfg["env"]["FOREMAST_BACKBONE_MODEL"])))


def test_byte_and_operation_functions_match_the_program_s_shapes():
    """row_bytes, weight_bytes, state_bytes and the leaves' capacity are the
    program's own (the arena's template, the parameters' shapes, the
    detector's rounding); the operations are the ISSUE's reckoning redone."""
    import jax

    from foremast_tpu.engine.arena import TreeArena
    from foremast_tpu.engine.backbone import BackboneDetector
    from foremast_tpu.models import kimi_linear as m

    cfg = spec.Cell(CELL).config
    path = os.path.join(ROOT, cfg["env"]["FOREMAST_BACKBONE_MODEL"])
    model = m.Config.from_file(path)
    det = BackboneDetector(model_file=path, context=cfg["history_points"],
                           rows=int(cfg["env"]["FOREMAST_BACKBONE_ROWS"]),
                           model_types=("kimi_linear",))
    cached = cfg["history_points"] - 1
    assert det.ctx_cap == kimi_linear_model.context_capacity(cached) == 10112
    assert (det.prefill_seqs, det.chunk) == (2, 2528)  # four equal chunks, one shape
    arena = TreeArena(m.cache_template(model, det.ctx_cap), fixed_rows=192)
    assert arena.row_bytes == kimi_linear_model.row_bytes() == cfg["row_bytes"][KIND] == 20_332_556
    assert m.state_bytes(model) == kimi_linear_model.state_bytes() == 4 * (2_097_152 + 73_728)
    shapes = jax.eval_shape(lambda: m.init_params(model))
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert held == kimi_linear_model.weight_bytes() == 8_565_960_192
    kda, mla = kimi_linear_model.mixer_params()
    assert (kda, mla) == (39_510_016, 29_114_368)
    # a token: 1.175 GFLOP outside attention + 0.703 GFLOP of absorbed latent
    # attention over 10,079 + 15.5 positions; the tick's 5,760 tokens 10.8 TFLOP
    attention = 2 * 32 * (2 * 512 + 64) * (10079 + 15.5)
    assert abs(attention / 0.7029e9 - 1) < 1e-3
    assert abs((kimi_linear_model.token_flops() - attention) / 1.1754e9 - 1) < 1e-3
    tick = 48 * kimi_linear_model.window_flops(4, 32)
    assert abs(tick / 10.819e12 - 1) < 1e-3
    # the dense part is two operations a parameter a token touches (the
    # mixers, the dense FFN, the router, four routed experts in expectation
    # and the shared one a layer, the head) + the state's two products
    expert = 3 * 2304 * 1024
    touched = (4 * kda + mla + 3 * 2304 * 9216 + 4 * (2304 * 256 + (4 + 1) * expert)
               + 2304 * 81920)
    state = 4 * 2 * 32 * 128 * 128
    assert abs(kimi_linear_model.token_flops() - attention - 2 * (touched + state)) < 1e3
    # a tick's least bytes: the weights once, every row's state, tails and
    # cached latents
    assert kimi_linear_model.window_bytes(48, 4, 1) == held + 192 * (
        4 * (2_097_152 + 73_728) + 10079 * 576 * 2)


def test_the_configuration_holds_the_model_file_s_keys_and_states_its_cuts():
    """Every key of the model file (the catalog row's config, verbatim)
    stands in the configuration under the same name with the same value,
    but for the keys `reduced` lists, which give what this chip holds and
    agree with the model file's `share`; the published counts are stated."""
    cfg = spec.Cell(CELL).config
    model = _model_file(cfg)
    entry = next(c for c in spec.benchmark()["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size", "services"]
    assert entry["source"] == cfg["source"] and model["source"] in entry["source"]
    assert "metricsquery.go:43,75-77" in entry["source"]
    own = {"name", "source", "what", "share", "weights_seed", "assumed"}
    for key, value in model.items():
        if key in own or key in cfg["reduced"]:
            continue
        assert cfg[key] == value, key
    share = model["share"]
    assert cfg["share"] == share and share["chips_sharing_a_layer"] == 2
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (
        share["layers_held"], share["experts_held"], share["vocab_rows_held"])
    assert cfg["published"] == {k: model[k] for k in ("num_hidden_layers", "num_experts", "vocab_size")}
    # the floors a model_config keeps: the leading dense layer + a whole
    # period of four layers at 3 KDA : 1 MLA, 8 routed experts, an eighth of
    # the vocabulary; no width differs
    lin = cfg["linear_attn_config"]
    held = ["kda" if li in lin["kda_layers"] else "mla" for li in range(1, cfg["num_hidden_layers"] + 1)]
    assert held == ["kda", "kda", "kda", "mla", "kda"] and cfg["first_k_dense_replace"] == 1
    assert cfg["num_experts"] >= 8 and cfg["vocab_size"] * 8 >= model["vocab_size"]
    # the fleet fills the cache, whose capacity is fixed at it, and a sweep
    # is one slice: one dispatch of every sequence
    seqs = sum(g["services"] * len(g["aliases"]) for g in cfg["fleet"])
    assert seqs == int(cfg["env"]["FOREMAST_BACKBONE_ROWS"]) == 192
    assert int(cfg["env"]["FOREMAST_SWEEP_SLICE_DOCS"]) == cfg["services"] == 48
    assert int(cfg["env"]["FOREMAST_SWEEP_SLICE_DOCS"]) % int(cfg["env"]["FOREMAST_COLD_CHUNK_DOCS"]) == 0
    assert cfg["algorithm"] == cfg["fleet"][0]["kind"] == KIND
    assert cfg["anomaly_threshold"] == cfg["score_threshold_nats"] > np.log(cfg["vocab_size"])
    assert cfg["guarantees"]["prefix_cache"].startswith("a row is read, never written")
    for name in ("flip_floor", "flip_rate"):
        assert cfg["correct_limits"][name] == cfg["correct_limits"][f"{name}.{KIND}"]


@pytest.mark.parametrize("seed", [3100000301, 3100000302])
def test_the_control_below_the_stated_precision_fails_the_kind_s_limit(seed):
    """The reference with float8 weights, bfloat16 sums and a bfloat16
    state, in the program's place at the rehearsal's size:
    `flip_rate.backbone_kda` over its limit."""
    from chipbench import control

    cell = spec.Cell(CELL)
    out = control.control_margin(cell.sized(True), cell.traffic, seed=seed, sweeps=15)
    value, limit = out["by_kind"][KIND]
    assert out["correct"] is False and limit is not None and value > limit, out


@pytest.mark.parametrize("forgotten", ["state", "latents"])
def test_the_reference_s_reuse_of_a_history_is_checked_not_assumed(monkeypatch, forgotten):
    """A continuation that does not start from the history's state, or does
    not see the history's keys as one full forward does, stops the
    comparison."""
    from chipbench.references import backbone_kda as ref

    cell = spec.Cell(CELL)
    model = ref.model_of(cell.sized(True))
    rng = np.random.default_rng(3)
    seqs = [{"history": 1 + 0.3 * rng.standard_normal(40).astype(np.float32),
             "windows": 1 + 0.3 * rng.standard_normal((3, 30)).astype(np.float32)}]
    got = ref.score_sequences(model, seqs)
    assert got[0].shape == (3, 30) and np.isfinite(got[0]).all()
    real = ref.windows_layer

    def forgetful(model, w, kind, xw, left, rows):
        if forgotten == "state" and kind == "kda":
            left = (left[0], 0.5 * left[1])
        if forgotten == "latents" and kind == "mla":
            left = (left[0][::-1], *left[1:])
        return real(model, w, kind, xw, left, rows)

    monkeypatch.setattr(ref, "windows_layer", forgetful)
    with pytest.raises(SystemExit, match="differs from one full forward"):
        ref.score_sequences(model, seqs)


def test_the_references_agree_on_the_small_model():
    """The benchmark's copy (bfloat16-held weights widened a matrix at a
    time, cached continuation) and the program's plain reference (one
    forward over [history; window]) give the same scores."""
    import jax

    from chipbench.references import backbone_kda as ref
    from foremast_tpu.models import kimi_linear_reference as plain

    model = ref.model_of(spec.Cell(CELL).sized(True))
    rng = np.random.default_rng(4)
    hist = 1 + 0.3 * rng.standard_normal(40).astype(np.float32)
    wins = 1 + 0.3 * rng.standard_normal((2, 30)).astype(np.float32)
    said = []
    with jax.default_matmul_precision("highest"):
        got = ref.score_sequences(model, [{"history": hist, "windows": wins}], log=said.append)[0]
    for i in range(2):
        want, _ = plain.window_scores(model, model["share"], hist, wins[i])
        np.testing.assert_allclose(got[i], np.asarray(want), atol=2e-5)
    # every program that holds a product of weights was compiled side by side
    # with the others before the run, at the shapes the run then called it with
    assert "programs compiled side by side" in said[0]
    assert said[-1].endswith("where first called: 0"), said[-1]


def test_a_piece_that_fails_ahead_of_the_run_costs_its_head_start_not_the_run(monkeypatch):
    """`compile_ahead` decides no number: a piece whose thread raises there
    (the router's, here, once) is logged and compiles where the run first
    calls it, and the scores are the ones of an undisturbed run."""
    import jax

    from chipbench.references import backbone_kda as ref

    model = ref.model_of(spec.Cell(CELL).sized(True))
    rng = np.random.default_rng(8)
    seqs = [{"history": 1 + 0.3 * rng.standard_normal(40).astype(np.float32),
             "windows": 1 + 0.3 * rng.standard_normal((2, 30)).astype(np.float32)}]
    with jax.default_matmul_precision("highest"):
        want = ref.score_sequences(model, seqs)[0]
    route, calls = ref._route, []

    def once_broken(*args, **kwargs):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: planted")
        return route(*args, **kwargs)

    once_broken._cache_size = route._cache_size
    once_broken.__name__ = route.__name__
    monkeypatch.setattr(ref, "_route", once_broken)
    said = []
    with jax.default_matmul_precision("highest"):
        got = ref.score_sequences(model, seqs, log=said.append)[0]
    np.testing.assert_array_equal(got, want)
    assert any("1 of" in s and "RESOURCE_EXHAUSTED: planted" in s for s in said), said


def test_an_expert_s_tokens_in_several_blocks_of_rows_score_the_same(monkeypatch):
    """At the published widths a sequence routes more than ROW_PAD of its
    10,240 tokens to one expert now and then: they go through the one
    program a block at a time."""
    from chipbench.references import backbone_kda as ref

    model = ref.model_of(spec.Cell(CELL).sized(True))
    rng = np.random.default_rng(6)
    seqs = [{"history": 1 + 0.3 * rng.standard_normal(40).astype(np.float32),
             "windows": 1 + 0.3 * rng.standard_normal((2, 30)).astype(np.float32)}]
    want = ref.score_sequences(model, seqs)[0]
    monkeypatch.setattr(ref, "ROW_PAD", 8)
    np.testing.assert_allclose(ref.score_sequences(model, seqs)[0], want, atol=1e-5)


def test_the_new_reader_and_the_accepted_ones_read_this_kind_s_counters():
    from chipbench.readers import counter_max_over_mean, counter_quotient, module_compute_roofline

    params = spec.layer_metric("kimi_latent_positions_per_token.sweep")["params"]
    record = {"counters": {"backbone_kda.latent_positions": 58_144_320.0,
                           "backbone_kda.window_tokens": 5760.0}}
    assert counter_quotient.read(record, params) == 10094.5
    # an older program counts tokens under another name or none: left out, not 0
    assert counter_quotient.read({"counters": {"backbone_kda.window_tokens": 5760.0}}, params) is None
    assert counter_quotient.read({"counters": {}}, params) is None
    load = spec.layer_metric("kimi_expert_load_max_over_mean.sweep")["params"]
    record = {"counters": {"backbone_kda.expert_tokens.0": 10.0, "backbone_kda.expert_tokens.1": 30.0,
                           "backbone.expert_tokens.0": 99.0}}
    assert counter_max_over_mean.read(record, load) == 1.5
    params = spec.layer_metric("kimi_window_roofline")["params"]
    cfg = spec.Cell(CELL).config
    empty = {"counters": {}, "config": cfg, "trace": {"modules": {}}, "device_kind": "TPU v5 lite"}
    assert module_compute_roofline.read(empty, params) is None
    traced = {
        "counters": {"fast_docs.backbone_kda": 480.0}, "config": cfg, "device_kind": "TPU v5 lite",
        "trace": {"modules": {"jit_score_window": {"seconds": 1.0, "count": 10.0}}},
    }
    # ten dispatches of 48 docs: 108.2 TFLOP of model work in a second of
    # device time is 54.9% of 197 TFLOP/s (the operations bound: 0.549 s
    # against 0.152 s for the bytes)
    share = module_compute_roofline.read(traced, params)
    assert abs(share - 100 * 10 * 48 * kimi_linear_model.window_flops(4, 32) / 197e12) < 1e-9
    assert 54 < share < 56
    assert kimi_linear_model.window_bytes(480, 4, 10) / 819e9 < 0.16


def test_the_traced_rehearsal_prints_this_kind_s_counted_metrics():
    """`--tiny --trace 1` on the CPU: counts only, among them the new
    reader's; the parent's driver import is the first thing a run does."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed", str(2**31 + 31),
         "--seconds", "2", "--trace", "1", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line["numbers"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == {"kimi_cache_hit_pct.sweep", "kimi_expert_load_max_over_mean.sweep",
                        "kimi_latent_positions_per_token.sweep", "kimi_compiles_in_window.sweep",
                        "kimi_program_loads_in_window.sweep"}
    assert got["kimi_cache_hit_pct.sweep"] == 100.0
    assert got["kimi_compiles_in_window.sweep"] == got["kimi_program_loads_in_window.sweep"] == 0.0
    # 39 cached positions and a 30-point window's own, up to the token: 39 + 15.5
    assert got["kimi_latent_positions_per_token.sweep"] == 54.5
    source = open(os.path.join(ROOT, "chipbench", "drivers", "recheck_state.py")).read()
    first_import = next(l for l in source.splitlines() if l.startswith(("import ", "from ")) and "__future__" not in l)
    assert first_import.startswith("import foremast_tpu.models.kimi_linear")
