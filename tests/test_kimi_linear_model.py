"""The Kimi-Linear backbone (`models/kimi_linear.py`) against its plain
reference (`models/kimi_linear_reference.py`) at a size that keeps every
ratio of Kimi-Linear-48B-A3B-Instruct: 3 KDA layers to 1 MLA layer after
one leading dense layer, a 4-tap convolution, a latent of 32 + 8 under 4
heads, 16 experts top-4 + 1 shared, half of experts and vocabulary held.
ISSUE 31's satellite list; the run through `BrainWorker.tick()` is
tests/test_kimi_linear_worker.py.

Tolerances. In float32 the program and the reference differ only by the
order of sums (the chunkwise recurrence against the token recurrence, the
absorbed latent products, the two-part softmax, the sorted expert blocks,
XLA's CPU dots against `highest`): logits of magnitude ~0.5 agree to 3e-5.
In bfloat16, the precision the configuration states, every activation, conv
tail and latent is rounded to 8 bits of mantissa once a layer (the KDA
state and its algebra stay float32): logits agree to 0.03 and scores (-log
p over 64 ids, ~4.2 nats) to 0.03 nats.
"""

import copy
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from foremast_tpu.models import kimi_linear as m
from foremast_tpu.models import kimi_linear_reference as ref

TINY = {
    "model_type": "kimi_linear", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_experts": 16, "num_experts_per_token": 4,
    "num_shared_experts": 1, "first_k_dense_replace": 1, "routed_scaling_factor": 2.446,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid", "rms_norm_eps": 1e-5,
    "num_attention_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "q_lora_rank": None, "mla_use_nope": True,
    "linear_attn_config": {
        "num_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4,
        "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
    },
    "num_hidden_layers": 8, "vocab_size": 128, "tie_word_embeddings": False, "weights_seed": 7,
    "share": {"chips_sharing_a_layer": 2, "index": 0, "experts_held": 8,
              "vocab_rows_held": 64, "layers_held": 5},
}
CONTEXT, WINDOW = 20, 6


def tiny(dtype="float32", **share):
    d = copy.deepcopy(TINY)
    d["compute_dtype"] = dtype
    d["share"].update(share)
    return d


def series(seed, n):
    rng = np.random.default_rng(seed)
    return (1.0 + 0.3 * np.sin(np.arange(n) / 3.0) + 0.2 * rng.standard_normal(n)).astype(np.float32)


def prefill(cfg, params, histories, ctx_cap, rows, chunk):
    """The judge's cold path in small: tokenise, prefill in chunks into an
    arena-shaped state whose rows hold another sequence's leavings (7s),
    finish the rows. -> (state, scales)"""
    state = jax.tree.map(
        lambda leaf: jnp.full((max(rows) + 2, *leaf.shape), 7, leaf.dtype),
        m.cache_template(cfg, ctx_cap),
    )
    v = cfg.share.vocab_rows_held
    scale = m.series_scale(np.stack(histories))
    ids = m.tokenize(np.stack(histories), scale, v)
    n = np.full(len(histories), ids.shape[1] - 1, np.int32)
    padded = np.zeros((len(histories), ctx_cap), np.int32)
    padded[:, : ids.shape[1] - 1] = ids[:, :-1]
    r = jnp.asarray(rows, jnp.int32)
    for start in range(0, int(n.max()), chunk):
        stop = min(start + chunk, ctx_cap)
        state, _ = m.prefill_chunk(cfg, params, state, r, jnp.asarray(padded[:, start:stop]),
                                   jnp.int32(start), jnp.asarray(n))
    return m.finish_rows(state, r, jnp.asarray(n), jnp.asarray(ids[:, -1]), jnp.asarray(scale)), scale


@pytest.mark.parametrize("dtype,tol", [("float32", 3e-5), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("chunk", [7, 24])
def test_prefill_then_window_through_the_cache_is_one_full_forward(dtype, tol, chunk):
    """Chunked prefill into the rows (chunks of 7 do not divide the 19
    cached positions; 24 is one chunk) + the window program as their
    continuation = the reference's ONE forward over [history; window]: two
    sequences in rows that are not the first and held another's state, a
    window shorter than its bucket."""
    d = tiny(dtype)
    cfg = m.Config.from_dict(d)
    params = m.init_params(cfg)
    hists = [series(1, CONTEXT), series(2, CONTEXT)]
    wins = [series(3, WINDOW), series(4, WINDOW)]
    state, scale = prefill(cfg, params, hists, 24, rows=[3, 1], chunk=chunk)
    ids = np.zeros((2, 8), np.int32)
    ids[:, :WINDOW] = m.tokenize(np.stack(wins), scale, cfg.share.vocab_rows_held)
    valid = np.broadcast_to(np.arange(8)[None, :] < WINDOW, (2, 8))
    scores, counts, dropped, attended, logits = m.score_window(
        cfg, params, state, jnp.asarray([3, 1], jnp.int32), jnp.asarray(ids),
        jnp.asarray(valid), with_logits=True,
    )
    for i in range(2):
        want_s, want_l = ref.window_scores(d, d["share"], hists[i], wins[i])
        np.testing.assert_allclose(np.asarray(logits[i, :WINDOW]), np.asarray(want_l), atol=tol)
        np.testing.assert_allclose(np.asarray(scores[i, :WINDOW]), np.asarray(want_s), atol=tol)
    assert int(counts.sum()) > 0 and int(dropped) == 0
    # every cached position and the window's own up to the token, the one MLA layer
    assert attended.tolist() == [WINDOW * (CONTEXT - 1) + WINDOW * (WINDOW + 1) // 2] * 2


def test_a_short_group_s_filler_writes_nothing_into_the_row_it_names():
    """A prefill group filled up with a sequence of no tokens names the
    first member's row: the member's state, tail and latents are the ones
    that stay."""
    cfg = m.Config.from_dict(tiny())
    params = m.init_params(cfg)
    hist = series(5, CONTEXT)
    alone, _ = prefill(cfg, params, [hist], 24, rows=[2], chunk=24)
    scale = m.series_scale(hist[None])
    ids = np.zeros((2, 24), np.int32)
    ids[0, : CONTEXT - 1] = m.tokenize(hist[None], scale, 64)[0, :-1]
    state = jax.tree.map(lambda leaf: jnp.full((4, *leaf.shape), 7, leaf.dtype),
                         m.cache_template(cfg, 24))
    state, _ = m.prefill_chunk(cfg, params, state, jnp.asarray([2, 2], jnp.int32), jnp.asarray(ids),
                               jnp.int32(0), jnp.asarray([CONTEXT - 1, 0], jnp.int32))
    for name in ("S", "conv"):
        np.testing.assert_array_equal(np.asarray(state[name][2]), np.asarray(alone[name][2]))
    for name in ("c", "kr"):
        np.testing.assert_array_equal(
            np.asarray(state[name][2, :, : CONTEXT - 1]), np.asarray(alone[name][2, :, : CONTEXT - 1]))


def _kda_case(t, heads=2, d=8, seed=0, fast=()):
    """q, k (normalised), v, g, beta [1, T, H, d]; the channels in `fast`
    decay by e^-6 a step."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(kk, (1, t, heads, d), jnp.float32) for kk in keys[:3])
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.exp(jax.random.uniform(keys[3], (1, t, heads, d), jnp.float32, -6.0, 0.5))
    for c in fast:
        g = g.at[..., c].set(-6.0)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, t, heads), jnp.float32))
    return q, k, v, g, beta


@pytest.mark.parametrize("t,chunk,sub", [(64, 64, 16), (50, 32, 8), (6, 64, 16), (96, 32, 16)])
def test_chunkwise_kda_is_the_token_recurrence_past_float32_s_exponent_range(t, chunk, sub):
    """The chunkwise form equals the recurrence a token at a time, with
    channels whose log decay sums to -6 x 64 = -384 inside one chunk (far
    past the -88 at which e^(-G) overflows) and to -96 inside one sub-chunk:
    nothing overflows, nothing is NaN, and the state carried out of the
    chunks is the recurrence's own."""
    q, k, v, g, beta = _kda_case(t, fast=(0, 3))
    assert float(jnp.cumsum(g, axis=1)[0, min(t, chunk) - 1, 0, 0]) < -6.0 * min(t, chunk) + 1
    s0 = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (1, 2, 8, 8), jnp.float32)
    o, s_end = m.kda_chunks(q, k, v, g, beta, s0, chunk=chunk, sub=sub)

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, :, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + jnp.einsum("hk,hv->hkv", k_t, u)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    with jax.default_matmul_precision("highest"):
        want_s, want_o = jax.lax.scan(step, s0[0], (q[0], k[0], v[0], g[0], beta[0]))
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(s_end)).all()
    np.testing.assert_allclose(np.asarray(o[0]), np.asarray(want_o), atol=2e-6)
    np.testing.assert_allclose(np.asarray(s_end[0]), np.asarray(want_s), atol=2e-6)
    # and from the reference's own zero state, through its own function
    o0, _ = m.kda_chunks(q, k, v, g, beta, jnp.zeros_like(s0), chunk=chunk, sub=sub)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            np.asarray(o0[0]), np.asarray(ref.kda_recurrence(q[0], k[0], v[0], g[0], beta[0])),
            atol=2e-6)


def test_a_padded_token_leaves_the_state_alone():
    """g = 0 and beta = 0 (what a token that is not valid gets): the state
    after the chunk is the state after the real tokens."""
    q, k, v, g, beta = _kda_case(12)
    s0 = jnp.zeros((1, 2, 8, 8), jnp.float32)
    _, whole = m.kda_chunks(q[:, :9], k[:, :9], v[:, :9], g[:, :9], beta[:, :9], s0)
    g, beta = g.at[:, 9:].set(0.0), beta.at[:, 9:].set(0.0)
    _, padded = m.kda_chunks(q, k, v, g, beta, s0)
    np.testing.assert_allclose(np.asarray(padded), np.asarray(whole), atol=1e-6)


def test_absorbed_mla_over_latents_is_explicit_keys_and_values_a_head():
    """q' = [W_kb q_n; q_r] against the cached [c; k_r], the probabilities
    summing c and W_vb applied to the sum = the reference's per-head K and V,
    half of the positions from the cache and half the dispatch's own."""
    d = tiny()
    cfg = m.Config.from_dict(d)
    lp = m.init_params(cfg)["layers"][3]
    w = ref.layer_weights(d, d["share"], 3)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (CONTEXT, 64), jnp.float32)
    xn = ref.rms_norm(x, 1e-5)
    with jax.default_matmul_precision("highest"):
        want = ref.mla(d, w, xn)
    q, latents = m.mla_project(cfg, lp, xn[None])
    half = CONTEXT // 2
    lat = cfg.kv_lora_rank
    # the row's leaves: c [Ck, L], k_r [r, Ck] (positions last), 6 slots unused
    c_c = jnp.concatenate([latents[0, :half, :lat], jnp.zeros((6, lat))])
    kr_c = jnp.concatenate([latents[0, :half, lat:], jnp.zeros((6, cfg.qk_rope_head_dim))]).T
    summed, attended = m.latent_attend(cfg, q[0, half:], 0, c_c, kr_c, half, latents[0, half:],
                                       jnp.ones(CONTEXT - half, bool))
    rest = CONTEXT - half
    assert int(attended) == rest * half + rest * (rest + 1) // 2
    got = m.mla_output(cfg, lp, summed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[half:]), atol=2e-5)
    # the first half, with nothing cached
    first, attended = m.latent_attend(cfg, q[0, :half], 0, c_c, kr_c, 0, latents[0, :half],
                                      jnp.ones(half, bool))
    assert int(attended) == half * (half + 1) // 2
    np.testing.assert_allclose(np.asarray(m.mla_output(cfg, lp, first)), np.asarray(want[:half]),
                               atol=2e-5)


def test_the_two_shares_add_up_to_the_uncut_layer():
    """The routed parts of both shares, with what every chip computes alike
    (the mixer, the shared expert) counted once, add up to the uncut
    reference's expert layer; routing drops nothing."""
    d = tiny()
    whole = ref.whole_share(d)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (12, 64), jnp.float32)
    xn = ref.rms_norm(x, 1e-5)
    with jax.default_matmul_precision("highest"):
        routed_all, shared = ref.ffn_parts(d, ref.layer_weights(d, whole, 1), xn)
    valid = jnp.ones(12, bool)
    total = jnp.zeros_like(x)
    seen = 0
    for index in range(2):
        cfg = m.Config.from_dict(tiny(index=index))
        lp = m.init_params(cfg)["layers"][1]
        part, sizes, done = m.routed_experts(cfg, lp, xn, valid, route=m.route)
        total = total + part
        seen += int(sizes.sum())
        assert int(done) == int(sizes.sum())
        if index == 0:
            np.testing.assert_allclose(
                np.asarray(m.gated_ffn(xn, lp["sg"], lp["su"], lp["sd"])), np.asarray(shared),
                atol=1e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(routed_all), atol=1e-5)
    assert seen == 4 * 12


def test_the_router_scales_renormalises_and_chooses_by_the_bias_alone():
    """sigmoid + bias top-4: a bias that lifts expert 5 over everything puts
    it into every token's choice and leaves its WEIGHT the sigmoid's own;
    the weights of a token sum to the scaling factor; under a router that
    sends every token to expert 0 nothing is dropped, padding is routed
    nowhere, and the result is the reference's."""
    d = tiny(chips_sharing_a_layer=1, experts_held=16)
    cfg = m.Config.from_dict(d)
    lp = dict(m.init_params(cfg)["layers"][1])
    xn = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (40, 64), jnp.float32))
    lp["router_bias"] = lp["router_bias"].at[5].set(10.0)
    top_i, top_w = m.route(cfg, lp, xn)
    assert bool((top_i == 5).any(axis=1).all())
    np.testing.assert_allclose(np.asarray(top_w.sum(axis=1)), 2.446, rtol=1e-6)
    s = jax.nn.sigmoid(jnp.dot(xn, lp["router"], precision="highest"))
    chosen = jnp.take_along_axis(s, top_i, axis=1)
    np.testing.assert_allclose(
        np.asarray(top_w), np.asarray(chosen / chosen.sum(axis=1, keepdims=True) * 2.446), rtol=1e-5)
    lp["router"] = lp["router"].at[:, 0].set(1.0)
    valid = jnp.arange(40) < 33
    y, sizes, done = m.routed_experts(cfg, lp, xn, valid, route=m.route)
    assert int(sizes.sum()) == 4 * 33 == int(done) and int(sizes[0]) == 33 == int(sizes[5])
    with jax.default_matmul_precision("highest"):
        w = ref.layer_weights(d, d["share"], 1)
        w["router"] = w["router"].at[:, 0].set(1.0)
        w["router_bias"] = w["router_bias"].at[5].set(10.0)
        want = ref.routed(d, w, xn)
    np.testing.assert_allclose(np.asarray(y[:33]), np.asarray(want[:33]), atol=2e-5)
    assert not np.asarray(y[33:]).any()


def test_layer_kinds_come_from_the_one_indexed_lists_and_layer_1_is_dense():
    cfg = m.Config.from_dict(tiny())
    assert cfg.layer_kinds == ("kda", "kda", "kda", "mla", "kda", "kda", "kda", "mla")
    assert cfg.layers == ("kda", "kda", "kda", "mla", "kda") and (cfg.n_kda, cfg.n_mla) == (4, 1)
    layers = m.init_params(cfg)["layers"]
    assert "dd" in layers[0] and "router" not in layers[0]
    assert all("router" in lp and "dd" not in lp for lp in layers[1:])
    assert "wkvb" in layers[3] and "wqkv" not in layers[3]
    assert [ref.layer_kind(tiny(), li) for li in range(5)] == list(cfg.layers)
    bad = tiny()
    bad["linear_attn_config"]["kda_layers"] = [1, 2, 3, 4]
    with pytest.raises(ValueError, match="layer 4 has to be in exactly one"):
        m.Config.from_dict(bad)
    with pytest.raises(ValueError, match="model_type='cohere2_moe'"):
        m.Config.from_dict(dict(tiny(), model_type="cohere2_moe"))


def test_the_model_file_holds_the_catalog_row_and_its_share():
    """config.json's keys as the architecture catalog gives them, verbatim;
    the row at the published widths is the ISSUE's 20,332,556 bytes."""
    with open(m.DEFAULT_MODEL_FILE, encoding="utf-8") as fh:
        d = json.load(fh)
    row = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512, "mla_use_nope": True,
        "model_max_length": 1048576, "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
        "num_attention_heads": 32, "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
        "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True,
        "v_head_dim": 128, "vocab_size": 163840,
    }
    for key, value in row.items():
        assert d[key] == value, key
    lin = d["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) == list(range(1, 28))
    assert set(d) - set(row) == {"name", "source", "what", "linear_attn_config", "share",
                                 "weights_seed", "assumed"}
    cfg = m.Config.from_file()
    assert cfg.layers == ("kda", "kda", "kda", "mla", "kda")
    assert cfg.share.experts_held == 128 and cfg.share.vocab_rows_held == 81920
    leaves = m.cache_template(cfg, 10112)
    assert leaves["c"].shape == (1, 10112, 512) and leaves["kr"].shape == (1, 64, 10112)
    row_bytes = sum(int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
                    for leaf in jax.tree.leaves(leaves))
    assert row_bytes == 4 * 2_097_152 + 4 * 73_728 + 10_112 * 576 * 2 + 12 == 20_332_556
    assert leaves["S"].dtype == jnp.float32 and leaves["conv"].dtype == jnp.bfloat16
    assert m.state_bytes(cfg) == 4 * (2_097_152 + 73_728)


def test_window_counters_count_what_the_program_attended_to():
    """`latent_positions` is what the window program counted under its own
    masks: two real sequences of 6 points against rows of 19 and 10 cached
    positions and one of padding, which attends to nothing."""
    cfg = m.Config.from_dict(tiny())
    params = m.init_params(cfg)
    state, _ = prefill(cfg, params, [series(1, CONTEXT), series(2, CONTEXT)], 24, [2, 0], chunk=24)
    state = {**state, "n": state["n"].at[0].set(10)}  # row 0 holds 10 positions
    valid = np.zeros((3, 8), bool)
    valid[:2, :6] = True
    *_, attended = m.score_window(cfg, params, state, jnp.asarray([2, 0, 0], jnp.int32),
                                  jnp.zeros((3, 8), jnp.int32), jnp.asarray(valid))
    assert attended.tolist() == [6 * 19 + 21, 6 * 10 + 21, 0]
    got = m.window_counters(cfg, 24, valid, attended)
    assert set(got) == set(m.WINDOW_COUNTERS)
    assert got["latent_positions"] == 6 * 19 + 21 + 6 * 10 + 21
