"""The backbone's attention over cached rows: which key a query sees, and
the fused pass the window program takes on a TPU.

`visible` and `cached_positions` are the mask arithmetic of BOTH paths:
`cohere2_moe.attend` (every CPU run, the prefill) builds its [Tq, Tk] masks
from them, and the kernel here evaluates them on a block of keys at a time.
A caller whose dispatch's own keys are not seen by position hands the
kernel its own rule for them (`own_visible`).

`fused_attend_rows` is one layer's attention of a window dispatch as ONE
Pallas TPU kernel. A grid step is one (sequence, key-value head): the
head's cached keys and values come from the arena leaf where they lie
(block index (rows[s], layer, h, 0, 0) through scalar prefetch: no gathered
copy, nothing written), the step's queries are the [group x T, D] block of
every query head that reads this key-value head, and a loop inside the
kernel walks the row in blocks of `KEY_BLOCK` keys under an online softmax
(running max, running sum, float32 accumulator), so a block's scores live
and die in VMEM. The dispatch's own keys and values (T a sequence, causal
among themselves) are the FIRST block of the same kernel and start the
running sums: one softmax, nothing merged outside, and the output leaves
the kernel normalised, in the compute dtype.

Precision is `attend`'s: operands in the compute dtype, QK^T accumulated in
float32, scores, max, sum and accumulator float32, probabilities cast to
the compute dtype for PV. Blocks are skipped only where the mask makes
them wholly invisible (slots >= the row's cached positions).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASKED = -1e30
LANE = 128
KEY_BLOCK = 1024  # cached keys a step of the kernel's inner loop
_VMEM_LIMIT = 64 * 1024 * 1024  # of the v5e's 128 MiB: a head's K and V twice (double-buffered) ...
_VMEM_BLOCK = 16 * 1024 * 1024  # ... beside a block's scores, probabilities, queries and sums


def visible(pos_q, pos_k, valid_k, window: int | None):
    """[Tq, Tk] bool: key j is seen by query i. pos_q [Tq, 1]; pos_k,
    valid_k [1, Tk]."""
    gap = pos_q - pos_k
    seen = valid_k & (gap >= 0)
    return seen & (gap < window) if window else seen


def cached_positions(slot, n, ring: int | None):
    """(position, whether there is one) of cache slots `slot` once n
    positions are cached. A full layer's slot is its position; a ring's
    (slot = position mod ring) holds the largest p < n congruent to it."""
    if ring is None:
        return slot, slot < n
    return slot + ring * ((n - 1 - slot) // ring), slot < n


def fused_applies(head_dim: int, itemsize: int, tokens: int, capacities) -> bool:
    """Whether a window dispatch takes `fused_attend_rows`: the backend is
    a TPU, the shapes meet the kernel's tiling (head_dim and every leaf's
    capacity whole lane tiles, the window's tokens whole sublane tiles)
    and a head's keys and values fit the kernel's VMEM twice over.
    Nothing selects it otherwise: no option, no variable."""
    return (
        jax.default_backend() == "tpu"
        and head_dim % LANE == 0
        and tokens % 8 == 0
        and all(
            c % LANE == 0 and 4 * c * head_dim * itemsize <= _VMEM_LIMIT - _VMEM_BLOCK
            for c in capacities
        )
    )


def _kernel(rows_ref, n_ref, q_ref, pq_ref, kn_ref, vn_ref, pn_ref, ok_ref, kc_ref, vc_ref,
            o_ref, m_ref, l_ref, acc_ref, *, group: int, window, ring, key_block: int,
            own_visible=None):
    del rows_ref  # read by the index maps
    n = n_ref[pl.program_id(0)]
    gt, d = q_ref.shape
    t = gt // group
    cap = kc_ref.shape[0]
    q = q_ref[...]
    pos_q = pq_ref[...]
    scale = d ** -0.5

    def take(k, v, seen, first=False):
        """One block of keys k, values v [Tk, D] into the running softmax
        (`first`: the block starts it, nothing is rescaled); seen [T, Tk]
        repeats over the group (rows of q run (g, t))."""
        sc = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        sc = jnp.where(seen[None], sc.reshape(group, t, -1), MASKED)
        m_new = sc.max(axis=-1, keepdims=True)
        if not first:
            m_old = m_ref[...]
            m_new = jnp.maximum(m_old, m_new)
            alpha = jnp.exp(m_old - m_new)
        e = jnp.exp(sc - m_new)
        den = e.sum(axis=-1, keepdims=True)
        pv = jnp.dot(e.reshape(gt, -1).astype(v.dtype), v, preferred_element_type=jnp.float32)
        pv = pv.reshape(group, t, d)
        l_ref[...] = den if first else alpha * l_ref[...] + den
        acc_ref[...] = pv if first else alpha * acc_ref[...] + pv
        m_ref[...] = m_new

    def cached(j, carry):
        # the last block starts where it still fits and leaves out the
        # slots the block before it has taken
        first = j * key_block
        start = pl.multiple_of(jnp.minimum(first, cap - key_block), math.gcd(key_block, cap))
        slot = start + lax.broadcasted_iota(jnp.int32, (1, key_block), 1)
        pos_k, held = cached_positions(slot, n, ring)
        # foremast: ignore[jit-hygiene] — a static argument
        if own_visible is not None:
            # every query lies past the row: it sees every cached key
            seen = held & (slot >= first)
        else:
            seen = visible(pos_q, pos_k, held & (slot >= first), window)
        take(kc_ref[pl.ds(start, key_block), :], vc_ref[pl.ds(start, key_block), :], seen)
        return carry

    # the dispatch's own keys start the sums: no state to zero and rescale.
    # A padding row sees none of them and sums them under MASKED; its first
    # visible cached key rescales that to nothing, as in `attend`
    take(kn_ref[...], vn_ref[...],
         # foremast: ignore[jit-hygiene] — a static argument
         own_visible(pos_q, pn_ref[...], ok_ref[...] != 0) if own_visible is not None
         else visible(pos_q, pn_ref[...], ok_ref[...] != 0, window), True)
    lax.fori_loop(0, pl.cdiv(jnp.minimum(n, cap), key_block), cached, 0)
    o_ref[...] = (acc_ref[...] / l_ref[...]).reshape(gt, d).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("layer", "group", "window", "key_block", "interpret", "own_visible", "name"),
)
def fused_attend_rows(kleaf, vleaf, rows, cached_n, q, kn, vn, pos, valid, *, layer: int,
                      group: int, window: int | None, key_block: int = KEY_BLOCK,
                      interpret: bool = False, own_visible=None, name: str | None = None):
    """One layer's attention for every sequence of a window dispatch, each
    against arena row rows[s] of the leaves kleaf / vleaf [rows, layers of
    the type, Hkv, capacity, D] (slot `layer`), which holds cached_n[s]
    positions. q [S, T, Hq, D] at pos [S, T]; kn, vn [S, T, Hkv, D] the
    dispatch's own keys and values (`valid` [S, T]). With a `window` (a
    sliding layer) the leaf is a ring and the window bounds the gap. With
    `own_visible` (no window) `pos` holds whatever that rule reads of a
    token instead of its position: own_visible(pos_q [T, 1], pos_k, valid_k
    [1, T]) -> [T, T] bool says which of the dispatch's own keys a query
    sees, and every query sees every cached key. `name`: the device op's
    name (`backbone_attn_full` / `_sliding` by default).
    -> [S, T, Hq * D]."""
    s, t, hq, d = q.shape
    hkv = hq // group
    cap = kleaf.shape[-2]
    gt = group * t
    key_block = min(key_block, cap)
    qh = q.reshape(s, t, hkv, group, d).transpose(0, 2, 3, 1, 4).reshape(s, hkv, gt, d)
    per_seq = lambda i, h, rows, n: (i, 0, 0)  # noqa: E731
    per_head = lambda i, h, rows, n: (i, h, 0, 0)  # noqa: E731
    cache = pl.BlockSpec(
        (None, None, None, cap, d), lambda i, h, rows, n: (rows[i], layer, h, 0, 0)
    )
    kernel = functools.partial(
        _kernel, group=group, window=window, ring=cap if window else None, key_block=key_block,
    )
    # foremast: ignore[jit-hygiene] — a static argument
    if own_visible is not None:
        kernel = functools.partial(kernel, own_visible=own_visible)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((s, hkv, gt, d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s, hkv),
            in_specs=[
                pl.BlockSpec((None, None, gt, d), per_head),
                pl.BlockSpec((None, t, 1), per_seq),
                pl.BlockSpec((None, None, t, d), per_head),
                pl.BlockSpec((None, None, t, d), per_head),
                pl.BlockSpec((None, 1, t), per_seq),
                pl.BlockSpec((None, 1, t), per_seq),
                cache,
                cache,
            ],
            out_specs=pl.BlockSpec((None, None, gt, d), per_head),
            scratch_shapes=[
                pltpu.VMEM((group, t, 1), jnp.float32),
                pltpu.VMEM((group, t, 1), jnp.float32),
                pltpu.VMEM((group, t, d), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"), vmem_limit_bytes=_VMEM_LIMIT
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * s * hkv * gt * (cap + t) * d,
            transcendentals=s * hkv * gt * (cap + t),
            bytes_accessed=2 * s * hkv * (cap + t + gt) * d * q.dtype.itemsize,
        ),
        name=name or ("backbone_attn_sliding" if window else "backbone_attn_full"),
        interpret=interpret,
    )(
        rows, cached_n, qh, pos[:, :, None],
        kn.transpose(0, 2, 1, 3), vn.transpose(0, 2, 1, 3), pos[:, None, :],
        valid[:, None, :].astype(jnp.int32), kleaf, vleaf,
    )
    return out.reshape(s, hkv, group, t, d).transpose(0, 3, 1, 2, 4).reshape(s, t, hq * d)
