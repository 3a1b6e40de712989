"""The backbone's fused attention kernel
(`models/cohere2_attention.py:fused_attend_rows`) against `attend` on the
same arena rows, in Pallas' interpreter at toy widths; the path predicate;
the whole window program with the kernel steered in; and the kernel's
compile for a described v5e at the published widths (nothing runs there:
what Mosaic refuses for tiling or fast memory is refused here, at no chip
time). ISSUE 28.

Tolerances: float32 inputs 1e-5 (the online softmax takes the same sums in
another order); bfloat16 2e-2, `test_backbone_model.py`'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from foremast_tpu.models import cohere2_attention as fa
from foremast_tpu.models import cohere2_moe as m
from tests.test_backbone_model import CONTEXT, WINDOW, prefill, series, tiny

T, HKV, D = 8, 2, 16
ROWS, LAYERS, LAYER = 6, 2, 1  # more arena rows and layer slots than the dispatch reads


def _case(sliding, cap, ns, group, dtype, key_block, padding_row=False):
    """(fused, attend a sequence) [S, T, Hq * D] for sequences holding
    `ns` cached positions in rows taken out of order."""
    s = len(ns)
    keys = jax.random.split(jax.random.PRNGKey(cap + group + s), 5)
    draw = lambda k, shape: jax.random.normal(k, shape, jnp.float32).astype(dtype)  # noqa: E731
    kleaf = draw(keys[0], (ROWS, LAYERS, HKV, cap, D))
    vleaf = draw(keys[1], (ROWS, LAYERS, HKV, cap, D))
    q = draw(keys[2], (s, T, HKV * group, D))
    kn, vn = draw(keys[3], (s, T, HKV, D)), draw(keys[4], (s, T, HKV, D))
    rows = jnp.asarray(ROWS - 1 - np.arange(s), jnp.int32)
    n = jnp.asarray(ns, jnp.int32)
    pos = n[:, None] + jnp.arange(T, dtype=jnp.int32)
    valid = jnp.ones((s, T), bool).at[:, T - 2:].set(False)
    if padding_row:  # what `BackboneDetector.score` appends: nothing of it is valid
        valid = valid.at[-1].set(False)
    window = cap if sliding else None
    got = fa.fused_attend_rows(
        kleaf, vleaf, rows, n, q, kn, vn, pos, valid, layer=LAYER, group=group,
        window=window, key_block=key_block, interpret=True,
    )
    slot = jnp.arange(cap, dtype=jnp.int32)
    want = []
    for i in range(s):
        pos_c, valid_c = fa.cached_positions(slot, n[i], cap if sliding else None)
        want.append(m.attend(
            q[i], pos[i], kn[i], vn[i], pos[i], valid[i], kleaf[rows[i], LAYER],
            vleaf[rows[i], LAYER], pos_c, valid_c, group, window,
        ))
    return np.asarray(got, np.float32), np.asarray(jnp.stack(want), np.float32)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("group", [2, 16])
@pytest.mark.parametrize(
    "sliding,cap,ns,key_block",
    [
        # full layer: a block and a half, the capacity whole, one key, a
        # capacity the block does not divide (the last block starts early)
        (False, 24, (5, 24, 12), 8),
        (False, 24, (1, 19, 24), 16),
        # ring of 16: not yet full, just full, wrapped once and a bit,
        # wrapped twice onto a block's edge
        (True, 16, (5, 16, 21, 40), 8),
        # a ring the block does not divide, n not a multiple of the block
        (True, 24, (3, 37, 24), 16),
    ],
    ids=["full-tail", "full-clamped", "ring", "ring-clamped"],
)
def test_fused_kernel_matches_attend(sliding, cap, ns, key_block, group, dtype, tol):
    got, want = _case(sliding, cap, ns, group, jnp.dtype(dtype), key_block)
    np.testing.assert_allclose(got, want, atol=tol)


@pytest.mark.parametrize("sliding", [False, True], ids=["full", "ring"])
def test_a_padding_sequence_with_nothing_valid_matches_attend(sliding):
    got, want = _case(sliding, 16, (9, 16, 9), 2, jnp.float32, 8, padding_row=True)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_path_is_attend_off_a_tpu_and_where_the_kernel_does_not_tile(monkeypatch):
    cfg = m.Cohere2MoeConfig.from_file()
    assert jax.default_backend() == "cpu"
    assert not m.fused_window_attention(cfg, 10112, 32)  # published widths, no TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert m.fused_window_attention(cfg, 10112, 32)
    assert not m.fused_window_attention(cfg, 10112, 30)  # tokens: not whole sublane tiles
    assert not m.fused_window_attention(cfg, 10080, 32)  # a leaf that is not whole lane tiles
    assert m.fused_window_attention(cfg, 49152, 32)
    assert not m.fused_window_attention(cfg, 65536, 32)  # a head's K and V outgrow the VMEM
    toy = m.Cohere2MoeConfig.from_dict(tiny())
    assert not m.fused_window_attention(toy, 10112, 32)  # head_dim 16


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
def test_the_window_program_with_the_kernel_steered_in_matches_attend(monkeypatch, dtype, tol):
    """`score_window(with_logits=True)` over prefilled rows, once as every
    CPU run takes it and once with the kernel (interpreter, blocks of 8
    keys) in `attend`'s place: all four layers, the leaf's layer slots, a
    ring that has wrapped (context 20, window 8)."""
    cfg = m.Cohere2MoeConfig.from_dict(tiny(dtype))
    params = m.init_params(cfg)
    state, _ = prefill(cfg, params, [series(i, CONTEXT) for i in range(3)], 24, [2, 0, 1])
    rows = jnp.asarray([2, 0, 1], jnp.int32)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (3, 8)), jnp.int32)
    valid = jnp.ones((3, 8), bool).at[:, WINDOW:].set(False).at[2].set(False)

    def program():
        def fresh(cfg, *args):  # a new function each call: jit traces it anew
            return m.score_window.__wrapped__(cfg, *args, with_logits=True)

        return jax.jit(fresh, static_argnums=0)(cfg, params, state, rows, ids, valid)

    want_s, want_c, _, want_l = program()
    calls = []

    def steered(*args, **kwargs):
        calls.append(kwargs["window"] is not None)
        return fa.fused_attend_rows(*args, **kwargs, key_block=8, interpret=True)

    monkeypatch.setattr(m, "fused_window_attention", lambda *a: True)
    monkeypatch.setattr(m, "fused_attend_rows", steered)
    got_s, got_c, dropped, got_l = program()
    assert calls == [True, True, True, False]
    np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l), atol=tol)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=tol)
    if dtype == "float32":
        np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    assert int(dropped) == 0


def test_the_prefill_never_takes_the_kernel(monkeypatch):
    """`prefill_chunk` writes rows through the loop's carry: whatever the
    predicate says, it attends through `attend`."""
    cfg = m.Cohere2MoeConfig.from_dict(tiny())
    monkeypatch.setattr(m, "fused_window_attention", lambda *a: True)
    monkeypatch.setattr(m, "fused_attend_rows", lambda *a, **k: pytest.fail("kernel in prefill"))
    attend, seen = m.attend, []
    monkeypatch.setattr(m, "attend", lambda *a, **k: seen.append(1) or attend(*a, **k))

    def fresh(cfg, *args):  # a new function: traced here, under the patches
        return m.prefill_chunk.__wrapped__(cfg, *args)

    state = jax.tree.map(
        lambda leaf: jnp.zeros((2, *leaf.shape), leaf.dtype), m.cache_template(cfg, 24)
    )
    ids = jnp.zeros((1, 8), jnp.int32)
    state, counts = jax.jit(fresh, static_argnums=0)(
        cfg, m.init_params(cfg), state, jnp.asarray([1]), ids, jnp.int32(0), jnp.asarray([8])
    )
    assert seen and int(counts.sum()) > 0


# -- the kernel compiled for the chip it runs on, at the published widths -----


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from describing it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "sliding,layers,cap", [(False, 1, 10112), (True, 3, 4096)], ids=["full", "ring"]
)
def test_the_kernel_compiles_for_a_v5e_at_the_published_widths(one_chip, sliding, layers, cap):
    cfg = m.Cohere2MoeConfig.from_file()
    s, t, hkv, d = 48, 32, cfg.num_key_value_heads, cfg.head_dim

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    leaf = sd((s, layers, hkv, cap, d), cfg.dtype)
    new = sd((s, t, hkv, d), cfg.dtype)
    compiled = fa.fused_attend_rows.lower(
        leaf, leaf, sd((s,), jnp.int32), sd((s,), jnp.int32),
        sd((s, t, cfg.num_attention_heads, d), cfg.dtype), new, new,
        sd((s, t), jnp.int32), sd((s, t), jnp.bool_),
        layer=layers - 1, group=cfg.group, window=cfg.sliding_window if sliding else None,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # nothing of the rows' size is gathered or held beside the leaves
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 48 * t * cfg.num_attention_heads * d * 2 + 1


def test_the_kda_chunk_kernel_compiles_for_a_v5e_at_the_published_widths(one_chip):
    """Kind `backbone_kda`'s kernel (`models/kimi_kda.py`, ISSUE 32) at its
    cell's shapes: 192 sequences of a 32-token window, 32 heads of 128, the
    state read from a [192, 4, 32, 128, 128] leaf. Here, because this is the
    one tier-1 file that loads libtpu."""
    from foremast_tpu.models import kimi_kda, kimi_linear

    cfg = kimi_linear.Config.from_file()
    s, t, heads, d = 192, 32, cfg.kda_heads, cfg.kda_head_dim

    def sd(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = kimi_kda.fused_kda_rows.lower(
        sd((s, cfg.n_kda, heads, d, d)), sd((s,), jnp.int32), sd((s, t, 3 * heads * d)),
        sd((s, t, heads * d)), sd((s, t, heads)), slot=cfg.n_kda - 1, heads=heads,
        qk_norm=True, o_eps=cfg.rms_norm_eps,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # no batch of the states is gathered: nothing is held beside the leaf
    assert compiled.memory_analysis().temp_size_in_bytes < s * t * heads * 4 + 1


def test_the_block_kernel_compiles_for_a_v5e_at_the_published_widths(one_chip):
    """Kind `backbone_diffusion`'s use of the kernel (`own_visible=`
    `sdar_moe.block_visible`: the dispatch's own keys seen by (block, copy)
    code; device op `sdar_moe.ATTN_OP`) at its cell's shapes: 80
    sequences of 160 tokens (a 32-point bucket's clean blocks and 4 noisy
    copies of each), 32 query heads on 4 key-value heads of 128, rows of
    10,112 positions in a [80, 4, 4, 10112, 128] leaf, keys 512 a step."""
    from foremast_tpu.models import sdar_moe

    cfg = sdar_moe.Config.from_file()
    s, t, hkv, d = 80, sdar_moe.window_tokens(cfg, 32), cfg.num_key_value_heads, cfg.head_dim

    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    leaf = sd((s, cfg.share.layers_held, hkv, 10112, d), cfg.dtype)
    new = sd((s, t, hkv, d), cfg.dtype)
    compiled = fa.fused_attend_rows.lower(
        leaf, leaf, sd((s,), jnp.int32), sd((s,), jnp.int32),
        sd((s, t, cfg.num_attention_heads, d), cfg.dtype), new, new,
        sd((s, t), jnp.int32), sd((s, t), jnp.bool_),
        layer=cfg.share.layers_held - 1, group=cfg.group, window=None,
        own_visible=sdar_moe.block_visible, name=sdar_moe.ATTN_OP, key_block=sdar_moe.KEY_BLOCK,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text() and sdar_moe.ATTN_OP in compiled.as_text()
    # nothing of the rows' size is gathered or held beside the leaves
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * s * t * cfg.num_attention_heads * d * 2 + 1
