"""The block-diffusion backbone (`models/sdar_moe.py`) against its plain
reference (`models/sdar_moe_reference.py`) at a size that keeps every ratio
of SDAR-30B-A3B-Chat that a small model can: 8 query / 2 key-value heads
(groups of 4 here, 8 there), q/k RMSNorm and rotate-half RoPE at theta 1e6,
16 experts top-4 under a softmax router renormalised, no shared expert,
blocks of 4, half of experts and vocabulary held. The run through
`BrainWorker.tick()` is tests/test_sdar_moe_worker.py.

Tolerances. In float32 the program and the reference differ only by the
order of sums (the one dispatch of every copy against one forward a scored
point, the two-part softmax, the sorted expert blocks, XLA's CPU dots
against `highest`): logits of magnitude ~0.5 agree to 2e-5. In bfloat16, the
precision the configuration states, every activation and cached key is
rounded to 8 bits of mantissa once a layer: logits agree to 1e-2 and scores
(-log p over 64 ids, ~4.2 nats) to 1e-2 nats (4.6e-3 and 1.6e-3 the largest
seen on four sequences). Weights rounded to float8 (3 bits of mantissa, the
control below that precision) miss by 5.6e-2 and 2.1e-2 there, and the test
below holds that they miss by more than the tolerance.
"""

import copy
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from foremast_tpu.models import cohere2_attention as fa
from foremast_tpu.models import sdar_moe as m
from foremast_tpu.models import sdar_moe_reference as ref

TINY = {
    "model_type": "sdar_moe", "hidden_size": 64, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_experts": 16, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "rope_theta": 1000000, "rms_norm_eps": 1e-6,
    "num_hidden_layers": 8, "vocab_size": 128, "tie_word_embeddings": False,
    "attention_bias": False, "hidden_act": "silu", "decoder_sparse_step": 1,
    "mlp_only_layers": [], "use_sliding_window": False, "rope_scaling": None,
    "block_length": 4, "weights_seed": 7,
    "share": {"chips_sharing_a_layer": 2, "index": 0, "experts_held": 8,
              "vocab_rows_held": 64, "layers_held": 4},
}
CONTEXT, WINDOW, CAP = 22, 6, 24  # 22 points: 20 cached (5 blocks), the oldest 2 cut


def tiny(dtype="float32", **share):
    d = copy.deepcopy(TINY)
    d["compute_dtype"] = dtype
    d["share"].update(share)
    return d


def series(seed, n):
    rng = np.random.default_rng(seed)
    return (1.0 + 0.3 * np.sin(np.arange(n) / 3.0) + 0.2 * rng.standard_normal(n)).astype(np.float32)


def prefill(cfg, params, histories, rows, chunk=8, cap=CAP):
    """The judge's cold path in small: tokenise, cut to the span a row
    caches, prefill in chunks into an arena-shaped state whose rows hold
    another sequence's leavings (7s), finish the rows. -> (state, scales)"""
    state = jax.tree.map(lambda leaf: jnp.full((max(rows) + 2, *leaf.shape), 7, leaf.dtype),
                         m.cache_template(cfg, cap))
    v = cfg.share.vocab_rows_held
    scale = np.array([m.series_scale(h) for h in histories], np.float32)
    ids = np.zeros((len(histories), cap), np.int32)
    n = np.zeros(len(histories), np.int32)
    for b, h in enumerate(histories):
        tok = m.tokenize(h, scale[b], v)
        first, stop = m.cached_span(cfg, len(tok))
        ids[b, : stop - first], n[b] = tok[first:stop], stop - first
    r = jnp.asarray(rows, jnp.int32)
    for start in range(0, int(n.max()), chunk):
        state, _ = m.prefill_chunk(cfg, params, state, r, jnp.asarray(ids[:, start : start + chunk]),
                                   jnp.int32(start), jnp.asarray(n))
    return m.finish_rows(state, r, jnp.asarray(n), jnp.zeros_like(r), jnp.asarray(scale)), scale


def window(cfg, params, state, rows, scale, wins, points=8):
    """score_window over windows `wins` in a bucket of `points`."""
    ids = np.zeros((len(wins), points), np.int32)
    valid = np.zeros((len(wins), points), bool)
    for i, w in enumerate(wins):
        ids[i, : len(w)] = m.tokenize(w, scale[i], cfg.share.vocab_rows_held)
        valid[i, : len(w)] = True
    return m.score_window(cfg, params, state, jnp.asarray(rows, jnp.int32), jnp.asarray(ids),
                          jnp.asarray(valid), with_logits=True)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 1e-2)])
@pytest.mark.parametrize("chunk", [8, 24])
def test_one_dispatch_of_every_copy_is_the_block_by_block_rule(dtype, tol, chunk):
    """Chunked prefill of whole blocks into rows that held another's
    leavings, then ONE window dispatch of every block's clean tokens and B
    noisy copies = the reference's rule, one full forward a scored point:
    two sequences (histories of 22 and 21 points: cut to 20), a window of 6
    (a last block of 2 observed points and 2 mask tokens) in a bucket of 8."""
    d = tiny(dtype)
    cfg = m.Config.from_dict(d)
    params = m.init_params(cfg)
    hists = [series(1, CONTEXT), series(2, CONTEXT - 1)]
    wins = [series(3, WINDOW), series(4, WINDOW)]
    state, scale = prefill(cfg, params, hists, [3, 1], chunk)
    scores, counts, dropped, denoise, clean, logits = window(cfg, params, state, [3, 1], scale, wins)
    for i in range(2):
        want_s, want_l = ref.window_scores(d, d["share"], hists[i], wins[i])
        np.testing.assert_allclose(np.asarray(logits[i, :WINDOW]), np.asarray(want_l), atol=tol)
        np.testing.assert_allclose(np.asarray(scores[i, :WINDOW]), np.asarray(want_s), atol=tol)
    assert int(counts.sum()) > 0 and int(dropped) == 0
    # 4 copies of 4 tokens a scored point; block 0's clean tokens, read by block 1
    assert denoise.tolist() == [4 * WINDOW] * 2 and clean.tolist() == [4, 4]


def test_a_float8_weight_program_fails_the_bfloat16_tolerance():
    """The control: the program with every bfloat16 weight rounded to
    float8_e4m3fn misses the reference by more than the 1e-2 the bfloat16
    program is held to, in its logits and in its scores."""
    d = tiny("bfloat16")
    cfg = m.Config.from_dict(d)
    params = jax.tree.map(
        lambda w: w.astype(jnp.float8_e4m3fn).astype(w.dtype) if w.dtype == jnp.bfloat16 else w,
        m.init_params(cfg))
    hists, wins = [series(1, CONTEXT), series(2, CONTEXT)], [series(3, WINDOW), series(4, WINDOW)]
    state, scale = prefill(cfg, params, hists, [0, 1])
    scores, *_, logits = window(cfg, params, state, [0, 1], scale, wins)
    worst_s = worst_l = 0.0
    for i in range(2):
        want_s, want_l = ref.window_scores(d, d["share"], hists[i], wins[i])
        worst_s = max(worst_s, float(np.abs(np.asarray(scores[i, :WINDOW]) - want_s).max()))
        worst_l = max(worst_l, float(np.abs(np.asarray(logits[i, :WINDOW]) - want_l).max()))
    assert worst_s > 1e-2 and worst_l > 1e-2


def test_a_score_sees_its_block_before_it_and_nothing_after():
    """The logits that score(b, s) is read from are unchanged by the points
    (b, j >= s) and by every later block, and changed by the points (b, j <
    s) and by earlier blocks."""
    cfg = m.Config.from_dict(tiny())
    params = m.init_params(cfg)
    state, scale = prefill(cfg, params, [series(1, CONTEXT)], [0])
    base = series(3, 8)

    def logits(w):
        return np.asarray(window(cfg, params, state, [0], scale, [w])[-1][0])

    want = logits(base)
    for at in range(8):
        moved = base.copy()
        moved[at] += 2.0  # a token id far from the old one
        got = logits(moved)
        for p in range(8):
            if at // 4 > p // 4 or (at // 4 == p // 4 and at % 4 >= p % 4):
                np.testing.assert_array_equal(got[p], want[p], err_msg=f"{at} {p}")
            else:
                assert np.abs(got[p] - want[p]).max() > 1e-4, (at, p)


def test_a_partial_last_block_is_scored_at_its_observed_points_only():
    """A window of 6 in a bucket of 8: its last block holds 2 observed points
    and 2 mask tokens, what the bucket holds past the window changes no
    score, and no copy of points 6 and 7 runs (4 x 6 noisy token-forwards)."""
    cfg = m.Config.from_dict(tiny())
    params = m.init_params(cfg)
    state, scale = prefill(cfg, params, [series(1, CONTEXT)], [0])
    w = series(3, WINDOW)
    ids = np.zeros((1, 8), np.int32)
    ids[0, :WINDOW] = m.tokenize(w, scale[0], 64)
    valid = jnp.asarray(np.arange(8)[None] < WINDOW)
    got = []
    for tail in (0, 40):
        ids[0, WINDOW:] = tail
        sc, _, _, denoise, clean = m.score_window(cfg, params, state, jnp.asarray([0]),
                                                  jnp.asarray(ids), valid)
        got.append(np.asarray(sc[0, :WINDOW]))
        assert int(denoise[0]) == 4 * WINDOW and int(clean[0]) == 4
    np.testing.assert_array_equal(got[0], got[1])


def test_a_history_that_is_no_whole_number_of_blocks_is_cut_at_the_old_end():
    """22 points cache their newest 20 (positions 0..19 = points 2..21):
    the same rows, bit for bit, as the 20 newest points alone; the window
    scores the same."""
    cfg = m.Config.from_dict(tiny())
    params = m.init_params(cfg)
    assert m.cached_span(cfg, 22) == (2, 22) and m.cached_span(cfg, 20) == (0, 20)
    h = series(1, CONTEXT)
    ids = m.tokenize(h, m.series_scale(h), 64)
    cut = ids[2:]
    a, scale = prefill(cfg, params, [h], [0])
    # the newest 20 tokenised under the whole history's scale: the rows' ids
    state = jax.tree.map(lambda leaf: jnp.full((2, *leaf.shape), 7, leaf.dtype),
                         m.cache_template(cfg, CAP))
    padded = np.zeros((1, CAP), np.int32)
    padded[0, :20] = cut
    for start in range(0, 20, 8):
        state, _ = m.prefill_chunk(cfg, params, state, jnp.asarray([0]),
                                   jnp.asarray(padded[:, start : start + 8]), jnp.int32(start),
                                   jnp.asarray([20]))
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(a[name][0, :, :, :20]),
                                      np.asarray(state[name][0, :, :, :20]))
    assert int(a["n"][0]) == 20


def test_a_30_point_window_runs_120_noisy_and_28_clean_tokens():
    """The cell's window: 30 points in a bucket of 32 = 7 whole blocks and
    one of 2 points; 7 x 4 x 4 + 2 x 4 = 120 noisy token-forwards and the 28
    clean tokens of blocks 0-6, counted by the program from its masks'
    liveness; a sequence of padding runs none. Every live token is routed to
    4 of the 16 experts in each of the 4 layers (the whole layer held)."""
    cfg = m.Config.from_dict(tiny(chips_sharing_a_layer=1, experts_held=16))
    params = m.init_params(cfg)
    state, _ = prefill(cfg, params, [series(1, CONTEXT)], [0])
    valid = np.zeros((2, 32), bool)
    valid[0, :30] = True
    _, counts, _, denoise, clean = m.score_window(
        cfg, params, state, jnp.asarray([0, 0]), jnp.zeros((2, 32), jnp.int32), jnp.asarray(valid))
    assert denoise.tolist() == [120, 0] and clean.tolist() == [28, 0]
    assert int(counts.sum()) == (120 + 28) * 4 * 4
    got = m.window_counters(cfg, CAP, valid, denoise, clean)
    assert got == {"denoise_tokens": 120, "clean_tokens": 28, "fused_attn_tokens": 0}
    assert set(got) == set(m.WINDOW_COUNTERS)
    assert m.window_tokens(cfg, 32) == 160


def test_block_visibility_is_the_rule():
    """Codes block * 8 + copy: a clean token sees its own block's clean
    tokens and earlier blocks'; a noisy copy sees earlier blocks' clean
    tokens and itself; nothing sees a token that is not live."""
    c = m.COPY_CODES
    codes = jnp.asarray([0, 0, c, c, c + 1, c + 1, c + 2, 2 * c])
    live = jnp.asarray([True] * 7 + [False])
    seen = np.asarray(m.block_visible(codes[:, None], codes[None, :], live[None, :]))
    want = np.array([
        [1, 1, 0, 0, 0, 0, 0, 0],  # clean, block 0
        [1, 1, 0, 0, 0, 0, 0, 0],
        [1, 1, 1, 1, 0, 0, 0, 0],  # clean, block 1
        [1, 1, 1, 1, 0, 0, 0, 0],
        [1, 1, 0, 0, 1, 1, 0, 0],  # block 1's first copy
        [1, 1, 0, 0, 1, 1, 0, 0],
        [1, 1, 0, 0, 0, 0, 1, 0],  # block 1's second copy
        [1, 1, 1, 1, 0, 0, 0, 0],  # clean, block 2 (not live as a key)
    ], bool)
    np.testing.assert_array_equal(seen, want)


def test_the_two_shares_add_up_to_the_uncut_expert_layer():
    """The routed parts of both shares add up to the uncut reference's
    expert layer (no shared expert: nothing is counted once); a softmax
    over all 16 experts, the top-4 renormalised; nothing is dropped."""
    d = tiny()
    whole = ref.whole_share(d)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (12, 64), jnp.float32)
    xn = ref.rms_norm(x, 1e-6)
    with jax.default_matmul_precision("highest"):
        want = ref.routed(d, ref.layer_weights(d, whole, 1), xn)
    total, seen = jnp.zeros_like(x), 0
    for index in range(2):
        cfg = m.Config.from_dict(tiny(index=index))
        lp = m.init_params(cfg)["layers"][1]
        top_i, top_w = m.route(cfg, lp, xn)
        np.testing.assert_allclose(np.asarray(top_w.sum(axis=1)), 1.0, rtol=1e-6)
        part, sizes, done = m.routed_experts(cfg, lp, xn, jnp.ones(12, bool), route=m.route)
        total = total + part
        seen += int(sizes.sum())
        assert int(done) == int(sizes.sum())
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=1e-5)
    assert seen == 4 * 12


def test_the_model_file_holds_the_catalog_row_and_its_share():
    """config.json's keys as the architecture catalog gives them, verbatim;
    a row at the published widths is 82,837,516 bytes."""
    with open(m.DEFAULT_MODEL_FILE, encoding="utf-8") as fh:
        d = json.load(fh)
    row = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936,
    }
    for key, value in row.items():
        assert d[key] == value, key
    assert set(d) - set(row) == {"name", "source", "what", "share", "block_length",
                                 "weights_seed", "assumed"}
    cfg = m.Config.from_file()
    assert (cfg.share.layers_held, cfg.share.experts_held, cfg.block_length) == (4, 128, 4)
    assert cfg.mask_token_id == 151935 and cfg.group == 8
    leaves = m.cache_template(cfg, 10112)
    assert leaves["k"].shape == (4, 4, 10112, 128)
    row_bytes = sum(int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
                    for leaf in jax.tree.leaves(leaves))
    assert row_bytes == 4 * 2 * 4 * 10112 * 128 * 2 + 12 == 82_837_516
    assert m.prefill_chunk_len(cfg, 10112) == 2528
    with pytest.raises(ValueError, match="model_type"):
        m.Config.from_dict(dict(tiny(), model_type="qwen3_moe"))


# -- the fused kernel's block visibility, in Pallas' interpreter -------------


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
def test_the_window_program_with_the_kernel_steered_in_matches_attend(monkeypatch, dtype, tol):
    """`score_window(with_logits=True)` once as every CPU run takes it and
    once with `fused_attend_rows(own_visible=block_visible)` (interpreter, blocks of 8
    keys) in `attend`'s place: all four layers, rows out of order, a
    sequence of padding."""
    cfg = m.Config.from_dict(tiny(dtype))
    params = m.init_params(cfg)
    state, _ = prefill(cfg, params, [series(i, CONTEXT - i) for i in range(3)], [2, 0, 1])
    rows = jnp.asarray([2, 0, 1], jnp.int32)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 63, (3, 8)), jnp.int32)
    valid = jnp.ones((3, 8), bool).at[:, WINDOW:].set(False).at[2].set(False)

    def program():
        def fresh(cfg, *args):  # a new function each call: jit traces it anew
            return m.score_window.__wrapped__(cfg, *args, with_logits=True)

        return jax.jit(fresh, static_argnums=0)(cfg, params, state, rows, ids, valid)

    want = program()
    calls = []

    def steered(*args, **kwargs):
        calls.append((kwargs["own_visible"], kwargs["name"]))
        return fa.fused_attend_rows(*args, **{**kwargs, "key_block": 8}, interpret=True)

    monkeypatch.setattr(m, "fused_window_attention", lambda *a: True)
    monkeypatch.setattr(m, "fused_attend_rows", steered)
    got = program()
    assert calls == [(m.block_visible, "sdar_attn_blocks")] * 4
    np.testing.assert_allclose(np.asarray(got[5]), np.asarray(want[5]), atol=tol)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=tol)
    for a, b in zip(got[1:5], want[1:5]):
        if dtype == "float32":
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_path_is_attend_off_a_tpu_and_where_the_kernel_does_not_tile(monkeypatch):
    cfg = m.Config.from_file()
    assert not m.fused_window_attention(cfg, 10112, 32)  # published widths, no TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert m.fused_window_attention(cfg, 10112, 32)  # 160 tokens a sequence
    assert not m.fused_window_attention(cfg, 10080, 32)  # a leaf that is not whole lane tiles
    assert not m.fused_window_attention(m.Config.from_dict(tiny()), 10112, 32)  # head_dim 16
