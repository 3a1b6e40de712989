"""Tests of the cell `sdar-30b-a3b-pp12-7d.block-recheck` beside those that
find it by name in test_chipbench.py (the `--tiny` rehearsal of every cell,
both faults of every fleet kind, the contract of BENCHMARK.json):

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_sdar_cell.py -q
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

from chipbench import sdar_model, spec  # noqa: E402

CELL = "sdar-30b-a3b-pp12-7d.block-recheck"
KIND = "backbone_diffusion"


def _model_file(cfg: dict) -> dict:
    return json.load(open(os.path.join(ROOT, cfg["env"]["FOREMAST_BACKBONE_MODEL"])))


def test_byte_and_operation_functions_match_the_program_s_shapes():
    """row_bytes, weight_bytes and the leaves' capacity are the program's
    own (the arena's template, the parameters' shapes, the detector's
    rounding); the token-forwards are the program's own count of a 30-point
    window; the operations are the configuration's `byte_reckoning` redone."""
    import jax
    import jax.numpy as jnp

    from foremast_tpu.engine.arena import TreeArena
    from foremast_tpu.engine.backbone import BackboneDetector
    from foremast_tpu.models import sdar_moe as m

    cfg = spec.Cell(CELL).config
    path = os.path.join(ROOT, cfg["env"]["FOREMAST_BACKBONE_MODEL"])
    model = m.Config.from_file(path)
    det = BackboneDetector(model_file=path, context=cfg["history_points"],
                           rows=int(cfg["env"]["FOREMAST_BACKBONE_ROWS"]),
                           model_types=("sdar_moe",))
    assert det.ctx_cap == sdar_model.context_capacity(10080) == 10112
    assert (det.prefill_seqs, det.chunk) == (2, 2528)  # four equal chunks, one shape
    arena = TreeArena(m.cache_template(model, det.ctx_cap), fixed_rows=80)
    assert arena.row_bytes == sdar_model.row_bytes() == cfg["row_bytes"][KIND] == 82_837_516
    shapes = jax.eval_shape(lambda: m.init_params(model))
    held = sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert held == sdar_model.weight_bytes()
    assert abs(held / 6.23e9 - 1) < 2e-3  # 4 layers of 623.1 M + 622.3 M of vocabulary
    # the token-forwards a sequence: what the program counts from its masks
    noisy, clean, _ = sdar_model.token_forwards()
    assert (noisy, clean) == (120, 28)
    small = m.Config.from_dict(_model_file(cfg) | {"compute_dtype": "float32"})
    assert m.window_tokens(small, 32) == 160 >= noisy + clean
    # a token-forward 0.455 GFLOP outside attention, attention 0.661 over the
    # cached 10,080 positions, the head 0.622 a scored point: 14.7 TFLOP a tick
    assert abs(sdar_model.token_flops() / 0.4552e9 - 1) < 1e-3
    assert abs(sdar_model.attention_flops_per_key() * 10080 / 0.6606e9 - 1) < 1e-3
    tick = 20 * sdar_model.window_flops(4, 32)
    assert abs(tick / 14.7e12 - 1) < 6e-3
    # the kernel's part is the attention alone
    assert sdar_model.attention_flops(20, 4) == pytest.approx(
        80 * sdar_model.attention_flops_per_key() * (148 * 10080 + sdar_model.token_forwards()[2]))
    # a tick's least bytes: the weights once, every row's cached keys and values
    assert sdar_model.window_bytes(20, 4, 1) == held + 80 * 10080 * 4 * 2 * 4 * 128 * 2
    assert jnp.dtype(m.cache_template(model, 10112)["k"].dtype).itemsize == 2


def test_the_configuration_holds_the_model_file_s_keys_and_states_its_cuts():
    """Every key of the model file (the catalog row's config, verbatim)
    stands in the configuration under the same name with the same value,
    but for the keys `reduced` lists; the published depth is stated; the
    deployment shares no layer."""
    cfg = spec.Cell(CELL).config
    model = _model_file(cfg)
    entry = next(c for c in spec.benchmark()["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers", "services"]
    assert entry["source"] == cfg["source"] and model["source"] in entry["source"]
    assert "metricsquery.go:43,75-77" in entry["source"]
    own = {"name", "source", "what", "share", "weights_seed", "assumed"}
    for key, value in model.items():
        if key in own or key in cfg["reduced"]:
            continue
        assert cfg[key] == value, key
    share = model["share"]
    assert cfg["share"] == share and share["chips_sharing_a_layer"] == 1
    assert cfg["num_hidden_layers"] == share["layers_held"] == 4
    assert (share["experts_held"], share["vocab_rows_held"]) == (cfg["num_experts"], cfg["vocab_size"])
    assert cfg["published"] == {"num_hidden_layers": model["num_hidden_layers"]} == {
        "num_hidden_layers": 48}
    seqs = sum(g["services"] * len(g["aliases"]) for g in cfg["fleet"])
    assert seqs == int(cfg["env"]["FOREMAST_BACKBONE_ROWS"]) == 80
    assert int(cfg["env"]["FOREMAST_SWEEP_SLICE_DOCS"]) == cfg["services"] == 20
    assert cfg["algorithm"] == cfg["fleet"][0]["kind"] == KIND
    assert cfg["anomaly_threshold"] == cfg["score_threshold_nats"] > np.log(cfg["vocab_size"])
    assert cfg["guarantees"]["prefix_cache"].startswith("a row is read, never written")
    for name in ("flip_floor", "flip_rate"):
        assert cfg["correct_limits"][name] == cfg["correct_limits"][f"{name}.{KIND}"]


@pytest.mark.parametrize("seed", [3400000301, 3400000302])
def test_the_control_below_the_stated_precision_fails_the_kind_s_limits(seed):
    """The reference with float8 weights and bfloat16 sums, in the
    program's place at the rehearsal's size: `flip_rate.backbone_diffusion`
    over its limit, and its scores held point by point against the
    reference's over both of the per-point limits."""
    from chipbench import control, series
    from chipbench.references import backbone_diffusion as ref

    cell = spec.Cell(CELL)
    cfg = cell.sized(True)
    out = control.control_margin(cfg, cell.traffic, seed=seed, sweeps=15)
    value, limit = out["by_kind"][KIND]
    assert out["correct"] is False and limit is not None and value > limit, out
    job = control.ControlJob(cfg, cell.traffic, seed, 15)
    group = cfg["fleet"][0]

    def history(uid):
        return series.history(job.history_seed, uid, len(group["aliases"]), job.n_hist, job.fam)

    ref.judge(job.rows, group, cfg, history)
    want = dict(ref.scored)
    got = ref.judge(job.rows, group, cfg, history, control=True)["scores"]
    assert ref.scored == want  # the control's judgment keeps nothing
    numbers = ref.score_numbers(
        {(r["uid"], r["sweep"]): got[i] for i, r in enumerate(job.rows)}, cfg)
    for name in (f"score_gap.{KIND}", f"score_gap_max.{KIND}"):
        assert numbers[name]["value"] > numbers[name]["limit"], numbers


def test_float8_rounding_is_arithmetic_and_matches_the_cast():
    """The control's weights are rounded onto float8_e4m3fn's grid by float32
    arithmetic, equal to the cast for every finite bfloat16 value it holds
    (a backend's cast is not relied on)."""
    import jax.numpy as jnp

    from chipbench.references import backbone_diffusion as ref

    x = jnp.asarray(np.arange(1 << 16, dtype=np.uint16).view(jnp.bfloat16))
    f = np.asarray(x, np.float32)
    keep = np.isfinite(f) & (np.abs(f) <= 448)
    want = np.asarray(x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16), np.float32)[keep]
    got = np.asarray(ref.to_float8(x), np.float32)[keep]
    np.testing.assert_array_equal(got, want)
    assert (np.abs(got - f[keep]) > 0).mean() > 0.9  # it rounds: 4 bits of mantissa go


def test_the_per_point_comparison_reads_a_missing_judgment_as_broken():
    from chipbench.compare import BROKEN
    from chipbench.references import backbone_diffusion as ref

    want = {(1, 3): np.zeros((4, 30), np.float32), (2, 3): np.ones((4, 30), np.float32)}
    got = {(1, 3): np.full((4, 30), 0.5, np.float32), (2, 3): np.ones((4, 30), np.float32)}
    assert ref.score_gaps(got, want) == (0.25, 0.5)
    assert ref.score_gaps({(1, 3): got[(1, 3)]}, want) == (BROKEN, BROKEN)
    assert ref.score_gaps(got, {}) == (BROKEN, BROKEN)


def test_the_reference_s_reuse_of_a_history_is_checked_not_assumed(monkeypatch):
    """Windows scored against a history whose keys do not stand where one
    full forward puts them stop the comparison."""
    from chipbench.references import backbone_diffusion as ref

    model = ref.model_of(spec.Cell(CELL).sized(True))
    rng = np.random.default_rng(3)
    seqs = [{"history": 1 + 0.3 * rng.standard_normal(42).astype(np.float32),
             "windows": 1 + 0.3 * rng.standard_normal((3, 30)).astype(np.float32)}]
    got = ref.score_sequences(model, seqs)
    assert got[0].shape == (3, 30) and np.isfinite(got[0]).all()
    real = ref.history_layer

    def forgetful(model, w, x, n):
        x, k, v = real(model, w, x, n)
        return x, k[::-1], v

    monkeypatch.setattr(ref, "history_layer", forgetful)
    with pytest.raises(SystemExit, match="differs from one full forward"):
        ref.score_sequences(model, seqs)


def test_the_references_agree_on_the_small_model():
    """The benchmark's copy (bfloat16-held weights widened a matrix at a
    time, every window's block b of every sequence in one forward a step)
    and the program's plain reference (one forward a scored point) give the
    same scores, for a history that is no whole number of blocks."""
    import jax

    from chipbench.references import backbone_diffusion as ref
    from foremast_tpu.models import sdar_moe_reference as plain

    model = ref.model_of(spec.Cell(CELL).sized(True))
    rng = np.random.default_rng(4)
    hist = 1 + 0.3 * rng.standard_normal(41).astype(np.float32)
    wins = 1 + 0.3 * rng.standard_normal((2, 30)).astype(np.float32)
    said = []
    with jax.default_matmul_precision("highest"):
        got = ref.score_sequences(model, [{"history": hist, "windows": wins}], log=said.append)[0]
    for i in range(2):
        want, _ = plain.window_scores(model, model["share"], hist, wins[i])
        np.testing.assert_allclose(got[i], np.asarray(want), atol=2e-5)
    assert any("against one full forward" in line for line in said), said


def test_the_new_reader_and_the_accepted_ones_read_this_kind_s_counters():
    from chipbench.readers import counter_quotient, device_op_roofline, module_compute_roofline

    params = spec.layer_metric("sdar_denoise_tokens_per_token.sweep")["params"]
    record = {"counters": {"backbone_diffusion.denoise_tokens": 9600.0,
                           "backbone_diffusion.window_tokens": 2400.0}}
    assert counter_quotient.read(record, params) == 4.0
    # an older program counts no copies: left out, not 0
    assert counter_quotient.read({"counters": {"backbone_diffusion.window_tokens": 2400.0}},
                                 params) is None
    cfg = spec.Cell(CELL).config
    params = spec.layer_metric("sdar_window_roofline")["params"]
    traced = {
        "counters": {"fast_docs.backbone_diffusion": 200.0}, "config": cfg,
        "device_kind": "TPU v5 lite",
        "trace": {"modules": {"jit_score_window": {"seconds": 1.0, "count": 10.0}},
                  "device_ops": [["%sdar_attn_blocks.7 = bf16[80,4,1280,128] custom-call(...)", 0.1],
                                 ["%sdar_attn_blocks.6 = bf16[80,4,1280,128] custom-call(...)", 0.3],
                                 ["%fusion.1 = f32[12800,2048] fusion(...)", 0.1]]},
    }
    # ten dispatches of 20 docs: 147 TFLOP of model work in a second of
    # device time is ~74.6% of 197 TFLOP/s
    share = module_compute_roofline.read(traced, params)
    assert abs(share - 100 * 200 * sdar_model.window_flops(4, 32) / 197e12) < 1e-9
    assert 74 < share < 76
    kernel = spec.layer_metric("sdar_attn_roofline")["params"]
    got = device_op_roofline.read(traced, kernel)
    assert got == pytest.approx(100 * sdar_model.attention_flops(200, 4) / 197e12 / 0.4)
    # a program without the kernel, or a CPU run: nothing to read
    assert device_op_roofline.read(
        {**traced, "trace": {"device_ops": [["%fusion.1 = f32[8] fusion(sdar_attn_blocks.7)", 0.1]]}},
        kernel) is None
    assert device_op_roofline.read({**traced, "device_kind": None}, kernel) is None


def test_the_traced_rehearsal_prints_this_kind_s_counted_metrics():
    """`--tiny --trace 1` on the CPU: `correct` and counts only; the
    model's import is the first thing a run does, so a tree without the
    model exits at once."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELL, "--seed", str(2**31 + 34),
         "--seconds", "2", "--trace", "1", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line["numbers"]
    # the per-point comparison ran: every compared judgment had the program's scores
    gap, limit = line["numbers"][f"score_gap.{KIND}"]
    assert 0 < gap <= limit and line["numbers"][f"score_gap_max.{KIND}"][0] < 1
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(got) == {"sdar_denoise_tokens_per_token.sweep", "sdar_expert_load_max_over_mean.sweep",
                        "sdar_cache_hit_pct.sweep", "sdar_compiles_in_window.sweep",
                        "sdar_fused_attn_pct.sweep", "sdar_program_loads_in_window.sweep"}
    assert got["sdar_denoise_tokens_per_token.sweep"] == 4.0
    assert got["sdar_cache_hit_pct.sweep"] == 100.0 and got["sdar_compiles_in_window.sweep"] == 0.0
    # a CPU run attends through `attend`: no token took the kernel
    assert got["sdar_fused_attn_pct.sweep"] == 0.0 and got["sdar_program_loads_in_window.sweep"] == 0.0
    source = open(os.path.join(ROOT, "chipbench", "drivers", "recheck_blocks.py")).read()
    first_import = next(l for l in source.splitlines()
                        if l.startswith(("import ", "from ")) and "__future__" not in l)
    assert first_import.startswith("import foremast_tpu.models.sdar_moe")
