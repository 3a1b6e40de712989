"""The KDA chunk kernel (`models/kimi_kda.py:fused_kda_rows`) in Pallas'
interpreter against `kimi_linear.kda_chunks` and the recurrence a token at
a time, on arena-shaped leaves; the path predicate; the whole window
program with the kernel steered in; the counter. ISSUE 32. (The kernel's
compile for a described v5e at the published widths is in
`test_backbone_attention.py`, the one tier-1 file that loads libtpu.)

Tolerance: `test_chunkwise_kda_is_the_token_recurrence_past_float32_s_
exponent_range`'s own, atol 2e-6, decay sums past float32's exponent range
included.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from foremast_tpu.models import kimi_kda as kk
from foremast_tpu.models import kimi_linear as m
from tests.test_kimi_linear_model import CONTEXT, WINDOW, _kda_case, prefill, series, tiny

ROWS, SLOTS, SLOT = 5, 3, 1  # more arena rows and layer slots than a dispatch reads
ATOL = 2e-6


def _dispatch(t, heads, d, rows, fast=(0, 3)):
    """A dispatch of len(rows) sequences of t tokens: q, k, v, g [S, t, H,
    d], beta [S, t, H], and a leaf [ROWS, SLOTS, H, d, d] of states."""
    cases = [_kda_case(t, heads=heads, d=d, seed=i, fast=fast) for i in range(len(rows))]
    q, k, v, g, beta = (jnp.concatenate([c[i] for c in cases]) for i in range(5))
    leaf = 0.1 * jax.random.normal(jax.random.PRNGKey(9), (ROWS, SLOTS, heads, d, d), jnp.float32)
    return (q, k, v, g, beta), leaf, jnp.asarray(rows, jnp.int32)


def _fused(leaf, rows, q, k, v, g, beta, **norms):
    s, t, heads, d = q.shape
    qkv = jnp.concatenate([a.reshape(s, t, heads * d) for a in (q, k, v)], axis=-1)
    out = kk.fused_kda_rows(
        leaf, rows, qkv, g.reshape(s, t, heads * d), beta, slot=SLOT, heads=heads,
        interpret=True, **norms,
    )
    return np.asarray(out).reshape(s, t, heads, d)


def _recurrence(s0, q, k, v, g, beta):
    """One sequence a token at a time: s0 [H, d, d]; q .. [t, H, d]."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, :, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + jnp.einsum("hk,hv->hkv", k_t, u)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.lax.scan(step, s0, (q, k, v, g, beta))[1])


@pytest.mark.parametrize(
    "t,heads,d",
    [(64, 2, 8), (6, 2, 8), (30, 2, 8), (30, 4, 16), (30, 2, 128)],
    ids=["chunk-64-sub-16", "chunk-8-of-6", "window-30-in-32", "small-model-widths", "d-128"],
)
def test_the_kernel_is_kda_chunks_and_the_token_recurrence_past_float32_s_exponent_range(
        t, heads, d):
    """The one-chunk cases of the chunkwise test and the window's own (30
    tokens in a chunk of 32, sub-chunks of 16): channels 0 and 3 decay by
    e^-6 a token, -384 inside a chunk of 64 and -96 inside a sub-chunk
    (e^88 overflows): nothing overflows, nothing is NaN."""
    args, leaf, rows = _dispatch(t, heads, d, [4, 0, 2])
    g = args[3]
    assert float(jnp.cumsum(g, axis=1)[0, t - 1, 0, 0]) < -6.0 * t + 1
    got = _fused(leaf, rows, *args)
    assert np.isfinite(got).all()
    want, _ = m.kda_chunks(*args, leaf[rows, SLOT])
    np.testing.assert_allclose(got, np.asarray(want), atol=ATOL)
    for i, row in enumerate(np.asarray(rows)):
        np.testing.assert_allclose(
            got[i], _recurrence(leaf[row, SLOT], *(a[i] for a in args)), atol=ATOL)


@pytest.mark.parametrize("heads,d", [(4, 16), (2, 128)], ids=["small-model-widths", "d-128"])
def test_the_norms_of_a_head_taken_inside_the_kernel_are_kda_mix_s(heads, d):
    """`qk_norm`: q = L2norm(q~) d^-1/2 and k = L2norm(k~) of what SiLU(conv)
    made; `o_eps`: each head's o under its RMS norm (the gain is the
    caller's): `kda_mix`'s own arithmetic on either side of `kda_chunks`."""
    (q, k, v, g, beta), leaf, rows = _dispatch(30, heads, d, [1, 4])
    q, k = 3.0 * q + 0.1, 0.5 * k - 0.2  # not normalised

    def l2(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    o, _ = m.kda_chunks(l2(q) * d ** -0.5, l2(k), v, g, beta, leaf[rows, SLOT])
    np.testing.assert_allclose(
        _fused(leaf, rows, q, k, v, g, beta, qk_norm=True), np.asarray(o), atol=ATOL)
    # in o's own units: the norm multiplies a small head's rounding with its values
    rms = np.sqrt(np.mean(np.square(o), axis=-1, keepdims=True) + 1e-5)
    normed = _fused(leaf, rows, q, k, v, g, beta, qk_norm=True, o_eps=1e-5)
    np.testing.assert_allclose(normed * rms, np.asarray(o), atol=ATOL)
    np.testing.assert_allclose(np.mean(np.square(normed), axis=-1), 1.0, atol=0.05)


def test_the_cut_into_chunk_and_sub_chunk_is_kda_chunks_own():
    assert (kk.KDA_CHUNK, kk.KDA_SUB) == (m.KDA_CHUNK, m.KDA_SUB) == (64, 16)  # one definition
    assert [kk.chunk_shape(t) for t in (6, 8, 30, 32, 33, 64)] == [
        (8, 8), (8, 8), (32, 16), (32, 16), (48, 16), (64, 16)]


def test_padded_tokens_leave_the_real_tokens_o_as_it_was():
    """A window's 30 points in its 32 slots: the two padded tokens carry g
    = 0 and beta = 0 (and whatever q, k, v the convolution made of them)."""
    (q, k, v, g, beta), leaf, rows = _dispatch(32, 2, 8, [1, 3])
    g, beta = g.at[:, 30:].set(0.0), beta.at[:, 30:].set(0.0)
    padded = _fused(leaf, rows, q, k, v, g, beta)
    real = _fused(leaf, rows, *(a[:, :30] for a in (q, k, v, g, beta)))
    np.testing.assert_allclose(padded[:, :30], real, atol=1e-7)


def test_a_sequence_with_no_valid_token_is_finite_and_moves_no_other():
    """What `BackboneDetector.score` fills a short batch up with: nothing
    of the sequence is valid; it names another sequence's row."""
    (q, k, v, g, beta), leaf, _ = _dispatch(32, 2, 8, [2, 4, 2])
    g, beta = g.at[2].set(0.0), beta.at[2].set(0.0)
    got = _fused(leaf, jnp.asarray([2, 4, 2], jnp.int32), q, k, v, g, beta)
    assert np.isfinite(got).all()
    alone = _fused(leaf, jnp.asarray([2, 4], jnp.int32), *(a[:2] for a in (q, k, v, g, beta)))
    np.testing.assert_array_equal(got[:2], alone)


@pytest.mark.parametrize(
    "rows", [[4, 0, 3, 1], [2, 2, 0, 2]], ids=["out-of-order", "repeated"])
def test_each_sequence_reads_the_state_of_the_row_it_names(rows):
    args, leaf, r = _dispatch(16, 2, 8, rows)
    got = _fused(leaf, r, *args)
    for i, row in enumerate(rows):
        np.testing.assert_allclose(
            got[i], _recurrence(leaf[row, SLOT], *(a[i] for a in args)), atol=ATOL)
    # and another slot's, or another row's, state is another answer
    other = _fused(leaf.at[:, SLOT].set(leaf[:, SLOT + 1]), r, *args)
    assert np.abs(other - got).max() > 1e-3


def test_the_leaf_is_bit_identical_after_the_call():
    args, leaf, rows = _dispatch(30, 2, 8, [3, 1])
    before = np.asarray(leaf).copy()
    _fused(leaf, rows, *args)
    np.testing.assert_array_equal(np.asarray(leaf), before)


@pytest.mark.parametrize(
    "backend,head_dim,tokens,write_at,want",
    [
        ("cpu", 128, 32, None, False),  # every CPU run, the tier-1 tests
        ("tpu", 128, 32, None, True),  # the window program at the published widths
        ("tpu", 128, 30, None, True),
        ("tpu", 128, 64, None, True),
        ("tpu", 128, 32, 0, False),  # a prefill chunk writes the state back ...
        ("tpu", 128, 2528, 2528, False),  # ... and is 40 chunks long
        ("tpu", 128, 65, None, False),  # more tokens than a chunk
        ("tpu", 64, 32, None, False),  # a head that is no lane tile
        ("tpu", 16, 32, None, False),  # the small model's
    ],
)
def test_the_path_is_kda_chunks_off_a_tpu_and_where_the_kernel_does_not_apply(
        monkeypatch, backend, head_dim, tokens, write_at, want):
    assert jax.default_backend() == "cpu"
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert kk.fused_applies(head_dim, tokens, write_at) is want
    if backend == "tpu" and write_at is None:
        cfg = m.Config.from_file() if head_dim == 128 else m.Config.from_dict(tiny())
        if cfg.kda_head_dim == head_dim:
            assert m.fused_window_kda(cfg, tokens) is want
            assert not m.fused_window_kda(cfg, tokens, write_at=jnp.int32(0))


def _window_program(cfg, params, state, rows, ids, valid):
    def fresh(cfg, *args):  # a new function each call: jit traces it anew
        return m.score_window.__wrapped__(cfg, *args, with_logits=True)

    return jax.jit(fresh, static_argnums=0)(cfg, params, state, rows, ids, valid)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
def test_the_window_program_with_the_kernel_steered_in_matches_kda_chunks(monkeypatch, dtype, tol):
    """`score_window(with_logits=True)` over prefilled rows, once as every
    CPU run takes it and once with the kernel (interpreter) in
    `kda_chunks`' place in all four KDA layers: rows out of order, a window
    of 6 in 8 slots, a sequence with nothing valid; the state is not
    gathered on that path and the arena is left as it was."""
    cfg = m.Config.from_dict(tiny(dtype))
    params = m.init_params(cfg)
    state, _ = prefill(cfg, params, [series(i, CONTEXT) for i in range(3)], 24, [2, 0, 1], 7)
    rows = jnp.asarray([2, 0, 1], jnp.int32)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 64, (3, 8)), jnp.int32)
    valid = jnp.ones((3, 8), bool).at[:, WINDOW:].set(False).at[2].set(False)
    before = jax.tree.map(lambda a: np.asarray(a).copy(), state)
    want = _window_program(cfg, params, state, rows, ids, valid)
    slots, chunks = [], []
    kda_chunks = m.kda_chunks

    def steered(leaf, rows, *args, slot, **kwargs):
        slots.append(slot)
        return kk.fused_kda_rows(leaf, rows, *args, slot=slot, interpret=True, **kwargs)

    monkeypatch.setattr(m, "fused_applies", lambda d, t, write_at: write_at is None)
    monkeypatch.setattr(m, "fused_kda_rows", steered)
    monkeypatch.setattr(m, "kda_chunks", lambda *a, **k: chunks.append(1) or kda_chunks(*a, **k))
    got = _window_program(cfg, params, state, rows, ids, valid)
    assert slots == list(range(cfg.n_kda)) and not chunks
    for g, w in zip(got, want):
        if g.dtype == jnp.int32 and dtype == "float32":
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        elif g.dtype != jnp.int32:
            np.testing.assert_allclose(
                np.asarray(g, np.float32), np.asarray(w, np.float32), atol=tol)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), b), state, before)


def test_the_prefill_takes_kda_chunks_whatever_the_backend(monkeypatch):
    """`prefill_chunk` carries the state across chunks and writes it back:
    on a TPU too its KDA layers run `kda_chunks`."""
    cfg = m.Config.from_dict(tiny())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kk, "LANE", 16)  # the small model's heads pass for lane tiles
    assert m.fused_window_kda(cfg, 8)
    monkeypatch.setattr(m, "fused_kda_rows", lambda *a, **k: pytest.fail("kernel in prefill"))
    seen, kda_chunks = [], m.kda_chunks
    monkeypatch.setattr(m, "kda_chunks", lambda *a, **k: seen.append(1) or kda_chunks(*a, **k))

    def fresh(cfg, *args):  # a new function: traced here, under the patches
        return m.prefill_chunk.__wrapped__(cfg, *args)

    state = jax.tree.map(
        lambda leaf: jnp.zeros((2, *leaf.shape), leaf.dtype), m.cache_template(cfg, 24))
    state, counts = jax.jit(fresh, static_argnums=0)(
        cfg, m.init_params(cfg), state, jnp.asarray([1]), jnp.zeros((1, 8), jnp.int32),
        jnp.int32(0), jnp.asarray([8]))
    assert len(seen) == cfg.n_kda and int(counts.sum()) > 0


def test_fused_kda_tokens_counts_the_window_s_tokens_when_and_only_when_the_kernel_ran(
        monkeypatch):
    valid = np.zeros((4, 32), bool)
    valid[:3, :30] = True
    attended = np.zeros(4, np.int32)
    big, small = m.Config.from_file(), m.Config.from_dict(tiny())
    count = lambda cfg, v=valid: m.window_counters(cfg, 24, v, attended)["fused_kda_tokens"]  # noqa: E731
    assert "fused_kda_tokens" in m.WINDOW_COUNTERS
    assert count(big) == 0 and count(small) == 0  # off a TPU every dispatch runs `kda_chunks`
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert count(big) == 90  # the real points of the dispatches that took the kernel
    assert count(small) == 0  # heads of 16: no lane tile, `kda_chunks` on a TPU too
    assert count(big, np.ones((2, 72), bool)) == 0  # more than a chunk
    # the predicate the program traces under and the one the counter asks are one call
    calls = []
    monkeypatch.setattr(
        m, "fused_applies", lambda *a: calls.append(a) or False)
    assert count(big) == 0 and calls == [(128, 32, None)]


def test_head_block_holds_a_whole_sequence_at_the_published_widths():
    assert kk.head_block(32, 128, 32) == 32  # 2 MiB of state + 2.5 MiB of q, k, v, g, o, twice
    assert kk.head_block(32, 128, 64) == 32
    assert kk.head_block(32, 256, 64) == 16  # a head of 256: 0.41 MiB + 0.31 MiB, twice
    assert kk.head_block(12, 512, 64) == 6  # the largest divisor that fits
    assert all(kk.head_block(h, 128, 32) == h for h in (1, 2, 4, 6))
