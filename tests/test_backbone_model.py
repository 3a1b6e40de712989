"""The Cohere2-MoE backbone (`models/cohere2_moe.py`) against its plain
reference (`models/cohere2_moe_reference.py`) at a size that keeps every
ratio of command-a-plus-05-2026: 8 query / 2 key-value heads, 16 experts
top-4 + 2 shared, a context (20) longer than the sliding window (8), one
period of 3 sliding + 1 full layers, an eighth of experts and vocabulary
held. ISSUE 27, Tentpole section 3, cases (b)-(e); case (a), through
`BrainWorker.tick()`, is tests/test_backbone_worker.py.

Tolerances. In float32 the program and the reference differ only by the
order of sums (the two-part softmax, the sorted expert blocks, XLA's CPU
dots against `highest`): logits of magnitude ~0.1 agree to 2e-5. In
bfloat16, the precision the configuration states, every activation is
rounded to 8 bits of mantissa once a layer: logits agree to 0.02 and
scores (-log p over 64 ids, ~4.2 nats) to 0.02 nats.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from foremast_tpu.models import cohere2_moe as m
from foremast_tpu.models import cohere2_moe_reference as ref

TINY = {
    "model_type": "cohere2_moe", "hidden_size": 64, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 64,
    "num_experts": 16, "num_experts_per_tok": 4, "num_shared_experts": 2,
    "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "sliding_window": 8, "rope_theta": 50000, "layer_norm_eps": 1e-5,
    "logit_scale": 1, "vocab_size": 512, "weights_seed": 7,
    "share": {"chips_sharing_a_layer": 8, "index": 0, "experts_held": 2,
              "vocab_rows_held": 64, "layers_held": 4},
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


def prefill(cfg, params, histories, ctx_cap, rows):
    """The judge's cold path in small: tokenise, prefill in chunks into an
    arena-shaped state, finish the rows. -> state"""
    state = jax.tree.map(
        lambda leaf: jnp.zeros((max(rows) + 2, *leaf.shape), leaf.dtype),
        m.cache_template(cfg, ctx_cap),
    )
    v = cfg.share.vocab_rows_held
    scale = m.series_scale(np.stack(histories))
    ids = m.tokenize(np.stack(histories), scale, v)
    n = np.full(len(histories), ids.shape[1] - 1, np.int32)
    chunk = m.prefill_chunk_len(cfg, ctx_cap)
    padded = np.zeros((len(histories), ctx_cap), np.int32)
    padded[:, : ids.shape[1] - 1] = ids[:, :-1]
    r = jnp.asarray(rows, jnp.int32)
    for start in range(0, int(n.max()), chunk):
        stop = min(start + chunk, ctx_cap)
        state, _ = m.prefill_chunk(cfg, params, state, r, jnp.asarray(padded[:, start:stop]),
                                   jnp.int32(start), jnp.asarray(n))
    return m.finish_rows(state, r, jnp.asarray(n), jnp.asarray(ids[:, -1]), jnp.asarray(scale)), scale


@pytest.mark.parametrize("dtype,tol_logits,tol_scores", [
    ("float32", 2e-5, 2e-5),
    ("bfloat16", 2e-2, 2e-2),
])
def test_prefill_then_window_through_the_cache_is_one_full_forward(dtype, tol_logits, tol_scores):
    """(b): chunked prefill into the rows + the window program against them
    = the reference's ONE forward over [history; window], two sequences in
    rows that are not the first, a window shorter than its bucket."""
    d = tiny(dtype)
    cfg = m.Cohere2MoeConfig.from_dict(d)
    params = m.init_params(cfg)
    hists = [series(1, CONTEXT), series(2, CONTEXT)]
    wins = [series(3, WINDOW), series(4, WINDOW)]
    state, scale = prefill(cfg, params, hists, 24, rows=[3, 1])
    ids = np.zeros((2, 8), np.int32)
    ids[:, :WINDOW] = m.tokenize(np.stack(wins), scale, cfg.share.vocab_rows_held)
    valid = np.arange(8)[None, :] < WINDOW
    scores, counts, dropped, logits = m.score_window(
        cfg, params, state, jnp.asarray([3, 1], jnp.int32), jnp.asarray(ids),
        jnp.asarray(np.broadcast_to(valid, (2, 8))), with_logits=True,
    )
    for i in range(2):
        want_s, want_l = ref.window_scores(d, d["share"], hists[i], wins[i])
        np.testing.assert_allclose(np.asarray(logits[i, :WINDOW]), np.asarray(want_l), atol=tol_logits)
        np.testing.assert_allclose(np.asarray(scores[i, :WINDOW]), np.asarray(want_s), atol=tol_scores)
    assert int(counts.sum()) > 0 and int(dropped) == 0


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """(c): the routed parts of all eight shares, with what every chip
    computes alike (attention, the shared experts) counted once, add up to
    the uncut reference's layer output."""
    d = tiny()
    whole = ref.whole_share(d)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(3), (12, 64), jnp.float32)
    pos = jnp.arange(12, dtype=jnp.int32)
    with jax.default_matmul_precision("highest"):
        attn, routed_all, shared = ref.layer_parts(d, ref.layer_weights(d, whole, 0), x, pos,
                                                   "sliding_attention")
    xn = m.layer_norm(x, jnp.ones(64), 1e-5)
    valid = jnp.ones(12, bool)
    total = jnp.zeros_like(x)
    seen = 0
    for index in range(8):
        cfg = m.Cohere2MoeConfig.from_dict(tiny(index=index))
        lp = m.init_params(cfg)["layers"][0]
        part, sizes, done = m.routed_experts(cfg, lp, xn, valid)
        total = total + part
        seen += int(sizes.sum())
        assert int(done) == int(sizes.sum())
        if index == 0:
            np.testing.assert_allclose(
                np.asarray(m.shared_experts(cfg, lp, xn)), np.asarray(shared), atol=1e-5
            )
    np.testing.assert_allclose(np.asarray(total), np.asarray(routed_all), atol=1e-5)
    # (e): routing drops nothing: the assignments all shares received are
    # top-k times the tokens
    assert seen == 4 * 12


def test_routing_drops_nothing_under_imbalance():
    """(e): one share holding ALL experts receives exactly k assignments a
    real token, padding none, however uneven the routing: a router whose
    first column dominates sends every token to expert 0."""
    d = tiny(chips_sharing_a_layer=1, experts_held=16)
    cfg = m.Cohere2MoeConfig.from_dict(d)
    lp = dict(m.init_params(cfg)["layers"][0])
    lp["router"] = lp["router"].at[:, 0].set(1.0)
    xn = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (40, 64), jnp.float32))
    valid = jnp.arange(40) < 33
    y, sizes, done = m.routed_experts(cfg, lp, xn, valid)
    assert int(sizes.sum()) == 4 * 33 == int(done) and int(sizes[0]) == 33
    with jax.default_matmul_precision("highest"):
        w = ref.layer_weights(d, d["share"], 0)
        w["router"] = w["router"].at[:, 0].set(1.0)
        want = ref.routed(d, w, xn)
    np.testing.assert_allclose(np.asarray(y[:33]), np.asarray(want[:33]), atol=1e-5)
    assert not np.asarray(y[33:]).any()


def test_a_sliding_layer_past_its_window_differs_from_a_full_one():
    """(d): at a context longer than the window, a sliding layer's output
    matches the masked, rotated reference and differs from a full layer's
    on the same weights."""
    d = tiny()
    w = ref.layer_weights(d, d["share"], 0)
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(9), (CONTEXT, 64), jnp.float32)
    pos = jnp.arange(CONTEXT, dtype=jnp.int32)
    xn = ref.layer_norm(x, 1e-5)
    with jax.default_matmul_precision("highest"):
        slid = ref.attention(d, w, xn, pos, "sliding_attention")
        full = ref.attention(d, w, xn, pos, "full_attention")
    assert float(jnp.abs(slid - full)[9:].max()) > 1e-3
    cfg = m.Cohere2MoeConfig.from_dict(d)
    lp = m.init_params(cfg)["layers"][0]
    q, k, v = m._project(cfg, lp, xn[None], pos[None], True)
    none = jnp.zeros((cfg.num_key_value_heads, 8, cfg.head_dim), jnp.float32)
    got = m.attend(q[0], pos, k[0], v[0], pos, jnp.ones(CONTEXT, bool), none, none,
                   jnp.zeros(8, jnp.int32), jnp.zeros(8, bool), cfg.group, cfg.sliding_window)
    np.testing.assert_allclose(np.asarray(got @ lp["wo"]), np.asarray(slid), atol=2e-5)


def test_tokeniser_matches_the_reference_and_its_edges():
    hist = np.array([[0.0, 0.0, 0.0], [1.0, -3.0, 2.0]], np.float32)
    scale = m.series_scale(hist)
    np.testing.assert_array_equal(scale, [1.0, 2.0])
    np.testing.assert_array_equal(scale, ref.series_scale(hist))
    vals = np.array([[-100.0, 0.0, 14.99], [100.0, 2.0, -30.0]], np.float32)
    ids = m.tokenize(vals, scale, 512)
    np.testing.assert_array_equal(ids, ref.tokenize(vals, scale, 512))
    np.testing.assert_array_equal(ids, [[0, 256, 511], [511, 273, 0]])


def test_the_model_file_holds_the_catalog_row_and_its_share():
    cfg = m.Cohere2MoeConfig.from_file()
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (
        4096, 128, 8, 128)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.num_shared_experts) == (128, 8, 4)
    assert cfg.layers == ("sliding_attention",) * 3 + ("full_attention",)
    assert cfg.share.experts_held == 16 and cfg.share.vocab_rows_held == 32768
    row = sum(int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype).itemsize
              for leaf in jax.tree.leaves(m.cache_template(cfg, 10112)))
    assert row == 2 * 8 * 128 * 2 * (10112 + 3 * 4096) + 12
