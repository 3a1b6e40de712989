"""The chunk algebra of a KDA layer in a window dispatch as ONE Pallas TPU
kernel: what `kimi_linear.kda_chunks` computes for a dispatch of one chunk,
with each head's state read from the arena leaf where it lies and
everything between the inputs and `o` living and dying in VMEM.

A grid step is one sequence and a block of its heads. The state comes from
the leaf `state["S"]` [capacity, KDA layers, H, d, d] by block index
(rows[s], slot, head block, 0, 0) through scalar prefetch: no gathered copy,
nothing written (the window program reads rows and never writes them; the
state after the chunk is not computed: `score_window` throws it away). q, k
and v arrive as the convolution leaves them, side by side in one [S, T, 3 H
d] array, and g as [S, T, H d]: a head is a whole lane tile of the last
axis and is cut out inside the kernel. Splitting an axis of heads off
outside ([S, T, H, d]) is on the chip a copy into another tiling (0.3 ms an
array a layer, measured: PERF.md section 6), so what `kda_mix` does a head at
a time on either side of the algebra, the L2 norms of q and k and the RMS
norm of o, can be done here too (`qk_norm`, `o_eps`).

Inside a step the heads are taken `128 // C` at a time, their C tokens
stacked to rows r = (head, token) of one [R, d] tile, so that every matrix
of the algebra is a whole MXU tile and a product serves all the stacked
heads at once (pairs of different heads are exact zeros under a mask, and
products of block-diagonal matrices stay block-diagonal). With L the sum of
g inside a token's sub-chunk up to the token (a doubling scan down the
rows), G the same inside the chunk (L plus the earlier sub-chunks' totals:
sums only, no difference of long sums is taken) and b the token's beta:

    A_ti  = sum_c (b_t k_tc) k_ic e^(G_tc - G_ic)   i < t     (N = B A)
    A'_ti = sum_c q_tc k_ic e^(G_tc - G_ic)          i <= t
    U = (I + N)^-1 (B V - (B K * e^G) S_0),  O = (Q * e^G) S_0 + A' U

No factor e^(-G) is ever formed, as in `kda_chunks`: a pair of different
sub-chunks goes through the later one's anchor, e^(L_t) e^(-(sum of g after
i and before t's sub-chunk)), both exponents <= 0; a pair inside a
sub-chunk takes the difference L_t - L_i itself. (I + N)^-1 is
`_unit_lower_inverse`'s doublings. A padded token has g = 0 and beta = 0 and
leaves every other token's `o` alone. Float32 operands, every product at
`highest`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
KDA_CHUNK = 64  # tokens a chunk of the chunkwise recurrence
KDA_SUB = 16  # tokens a sub-chunk: pairs inside one take their difference itself
HIGHEST = lax.Precision.HIGHEST
_VMEM_LIMIT = 64 * 1024 * 1024  # of the v5e's 128 MiB
_VMEM_BLOCKS = 24 * 1024 * 1024  # what a step's blocks may take of it, double-buffered


def chunk_shape(tokens: int, chunk: int = KDA_CHUNK, sub: int = KDA_SUB) -> tuple:
    """(tokens a chunk, tokens a sub-chunk) `kda_chunks` cuts `tokens` into."""
    sub = min(sub, -(-tokens // 8) * 8)
    return min(chunk, -(-tokens // sub) * sub), sub


def head_block(heads: int, head_dim: int, chunk: int) -> int:
    """Heads a grid step holds: all of a sequence's where their state, q, k,
    v, g and o fit the kernel's share of VMEM twice over (a grid step costs
    ~1 us whatever it holds), else the largest divisor that does."""
    per_head = 2 * 4 * (head_dim * head_dim + 5 * chunk * head_dim)
    hb = max(1, min(heads, _VMEM_BLOCKS // per_head))
    while heads % hb:
        hb -= 1
    return hb


def fused_applies(head_dim: int, tokens: int, write_at) -> bool:
    """Whether a dispatch's KDA layers take `fused_kda_rows`: the backend is
    a TPU, the dispatch is a window's (nothing is written back: no
    `write_at`; its tokens fit one chunk) and a head is a whole lane tile.
    Nothing selects it otherwise: no option, no variable."""
    return (
        jax.default_backend() == "tpu"
        and write_at is None
        and 0 < tokens <= KDA_CHUNK
        and head_dim % LANE == 0
    )


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, precision=HIGHEST, preferred_element_type=jnp.float32)


def _kernel(rows_ref, q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, o_ref, *, sub: int, group: int,
            qk_norm: bool, o_eps):
    del rows_ref  # read by the index maps
    c = q_ref.shape[0]
    hb, d, _ = s_ref.shape
    nb = c // sub
    r = group * c
    f32 = jnp.float32
    # what a row r = (head, token) is, down the rows and along the columns
    ri = lax.broadcasted_iota(jnp.int32, (r, r), 0)
    ci = lax.broadcasted_iota(jnp.int32, (r, r), 1)
    same_head = ri // c == ci // c
    same_sub = ri // sub == ci // sub
    blk_r, blk_c = ri % c // sub, ci % c // sub
    pos_c = ci % sub
    row = lax.broadcasted_iota(jnp.int32, (r, 1), 0)
    pos, blk = row % sub, row % c // sub
    # a head's [C, C] matrix among the `group` packed side by side, [C, R]
    eye = (lax.broadcasted_iota(jnp.int32, (c, r), 0)
           == lax.broadcasted_iota(jnp.int32, (c, r), 1) % c).astype(f32)

    def of_sub(x, j):  # row j of each sub-chunk, handed to every row of that sub-chunk
        x = x.reshape(r // sub, sub, x.shape[-1])
        return jnp.broadcast_to(x[:, j:j + 1], x.shape).reshape(r, x.shape[-1])

    def of_head(x, t):  # row t of each head's chunk, handed to every row of that head
        x = x.reshape(group, c, x.shape[-1])
        return jnp.broadcast_to(x[:, t:t + 1], x.shape).reshape(r, x.shape[-1])

    def packed(x):  # block-diagonal [R, R] -> the heads' blocks side by side [C, R]
        return functools.reduce(jnp.add, (x[h * c:(h + 1) * c] for h in range(group)))

    def diagonal(x):  # and back
        return jnp.where(same_head, jnp.concatenate([x] * group, axis=0), 0.0)

    for at in range(0, hb, group):
        def stack(ref, at=at):
            return jnp.concatenate(
                [ref[:, (at + h) * d:(at + h + 1) * d] for h in range(group)], axis=0)

        q, k, g = stack(q_ref), stack(k_ref), stack(g_ref)
        if qk_norm:  # as `kda_mix` makes them of SiLU(conv): a row is one head's d channels
            q = q * (lax.rsqrt(jnp.sum(q * q, axis=1, keepdims=True) + 1e-6) * d ** -0.5)
            k = k * lax.rsqrt(jnp.sum(k * k, axis=1, keepdims=True) + 1e-6)
        beta = jnp.concatenate([b_ref[:, at + h:at + h + 1] for h in range(group)], axis=0)
        kb = k * beta
        # sums of g, never differences of long sums: g_sub, inside a token's
        # sub-chunk up to the token (a doubling scan down the rows); the
        # sub-chunks' totals; g_cum, inside the chunk up to the token
        g_sub, span = g, 1
        while span < sub:
            g_sub = g_sub + jnp.where(pos >= span, pltpu.roll(g_sub, span, 0), 0.0)
            span *= 2
        total = [of_head(g_sub, (b + 1) * sub - 1) for b in range(nb - 1)]
        g_cum = g_sub
        for b, t in enumerate(total):
            g_cum = g_cum + jnp.where(blk > b, t, 0.0)
        # pairs of different sub-chunks, through the later one's anchor: what
        # lies after a token and before sub-chunk b is the rest of its own
        # sub-chunk and the whole ones between
        lead = jnp.exp(g_sub)
        left = jnp.concatenate([kb * lead, q * lead], axis=0)
        after = of_sub(g_sub, sub - 1) - g_sub
        pairs = jnp.zeros((2 * r, r), f32)
        for b in range(1, nb):
            between = functools.reduce(
                jnp.add, (jnp.where(blk < bb, total[bb], 0.0) for bb in range(1, b)), 0.0)
            right = k * jnp.exp(jnp.where(blk < b, after + between, 0.0))
            reach = same_head & (blk_r == b) & (blk_c < b)
            pairs = pairs + jnp.where(
                jnp.concatenate([reach, reach], axis=0),
                _dot(left, right, (((1,), (1,)), ((), ()))), 0.0)
        # pairs inside a sub-chunk: the difference itself, a column at a time
        a_kk, a_qk = pairs[:r], pairs[r:]
        for j in range(sub):
            w = of_sub(k, j) * jnp.exp(jnp.where(pos >= j, g_sub - of_sub(g_sub, j), -jnp.inf))
            col = same_sub & (pos_c == j)
            a_kk = jnp.where(col & (ri > ci), jnp.sum(kb * w, axis=1, keepdims=True), a_kk)
            a_qk = jnp.where(col & (ri >= ci), jnp.sum(q * w, axis=1, keepdims=True), a_qk)
        # (I + N)^-1 = (I - N)(I + N^2)(I + N^4)...: N^C = 0. The left factor
        # of each product is kept packed: C rows through the MXU, not R
        n = packed(a_kk)
        inv = eye - n
        power = _dot(n, a_kk)
        span = 2
        while span < c:
            power_d = diagonal(power)
            inv = inv + _dot(inv, power_d)
            span *= 2
            if span < c:
                power = _dot(power, power_d)
        decayed = jnp.exp(g_cum)
        into = jnp.concatenate([kb * decayed, q * decayed], axis=0)  # [2R, d]
        from_state = []
        for h in range(group):
            rows = jnp.concatenate(
                [into[h * c:(h + 1) * c], into[r + h * c:r + (h + 1) * c]], axis=0)
            from_state.append(_dot(rows, s_ref[at + h]))  # [2C, d]
        taken = jnp.concatenate([x[:c] for x in from_state], axis=0)
        read = jnp.concatenate([x[c:] for x in from_state], axis=0)
        u = _dot(diagonal(inv), stack(v_ref) * beta - taken)
        o = read + _dot(a_qk, u)
        if o_eps is not None:  # the head's RMS norm, its gain left to the caller
            o = o * lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + o_eps)
        for h in range(group):
            o_ref[:, (at + h) * d:(at + h + 1) * d] = o[h * c:(h + 1) * c]


@functools.partial(
    jax.jit, static_argnames=("slot", "heads", "qk_norm", "o_eps", "interpret"))
def fused_kda_rows(leaf, rows, qkv, g, beta, *, slot: int, heads: int, qk_norm: bool = False,
                   o_eps: float | None = None, interpret: bool = False):
    """One KDA layer's chunk algebra for every sequence of a window
    dispatch, each from the state in arena row rows[s] of `leaf` [rows, KDA
    layers, H, d, d] (layer `slot`), which is read where it lies and left
    as it is. qkv [S, T, 3 H d]: q, k and v side by side, each [H d] wide
    (the convolution's output as it stands: no axis of heads is split off,
    which on the chip would be a copy into another tiling); g [S, T, H d]
    the log decay <= 0 (0 at a padded token); beta [S, T, H] (0 at a padded
    token); all float32; T at most one chunk. `qk_norm`: q and k are what
    SiLU(conv) made and the kernel normalises them as `kda_mix` does, q =
    L2norm(q) d^-1/2, k = L2norm(k); otherwise they are taken as they are.
    `o_eps`: each head's o leaves RMS-normalised with that epsilon (the
    gain is the caller's). -> o [S, T, H d] float32."""
    s, t, _ = g.shape
    d = leaf.shape[-1]
    c, sub = chunk_shape(t)
    if c != t:
        qkv, g, beta = (jnp.pad(a, ((0, 0), (0, c - t), (0, 0))) for a in (qkv, g, beta))
    hb = head_block(heads, d, c)
    blocks = heads // hb
    group = max(1, min(hb, LANE // c))
    while hb % group:
        group -= 1

    def part(n):  # the n-th [H d] of an array's last axis (qkv: q, k, v), a block of heads at a time
        return pl.BlockSpec((None, c, hb * d), lambda i, j, rows: (i, 0, n * blocks + j))

    out = pl.pallas_call(
        functools.partial(_kernel, sub=sub, group=group, qk_norm=qk_norm, o_eps=o_eps),
        out_shape=jax.ShapeDtypeStruct((s, c, heads * d), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s, blocks),
            in_specs=[
                part(0), part(1), part(2), part(0),
                pl.BlockSpec((None, None, c, hb), lambda i, j, rows: (i, j, 0, 0)),
                pl.BlockSpec((None, None, hb, d, d), lambda i, j, rows: (rows[i], slot, j, 0, 0)),
            ],
            out_specs=part(0),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * s * heads * c * (4 * d * d + 2 * c * d + 10 * c * c),
            transcendentals=s * heads * c * d * (sub + 3),
            bytes_accessed=4 * s * heads * (d * d + c * (5 * d + 1)),
        ),
        name="kimi_kda_chunk",
        interpret=interpret,
    )(rows, qkv, qkv, qkv, g, beta.reshape(s, c, blocks, hb).transpose(0, 2, 1, 3), leaf)
    return out[:, :t]
