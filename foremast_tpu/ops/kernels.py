"""Pallas TPU kernels for the scoring hot path.

The engine's hot loop (SURVEY.md §3.2) under the deployed default
algorithm `moving_average_all` (`foremast-brain.yaml:24-25`) is:
one pass over the [B, Th] 7-day history for masked mean/std, then a tiny
[B, Tc] band comparison. The XLA path (`engine/scoring.py`) expresses this
as several fused elementwise/reduce ops; the kernels here collapse the
entire judgment into ONE `pallas_call` so each history block is read from
HBM exactly once and everything downstream (bounds, flags, verdict)
happens on VMEM-resident data — the "native layer" of this framework
(the reference has no native code to port; SURVEY.md §2 maps its role to
XLA/Pallas kernels).

Kernels:
  * `masked_stats`  — count/mean/std of a masked [B, T] batch, one pass
    (sum, sum-of-squares, count accumulated together).
  * `ma_judgment`   — the full moving_average_all judgment: stats ->
    band (threshold * sigma, lower floored at min_lower_bound) ->
    bound-selector flags (1=upper/2=lower/3=both) -> measurability gate
    (min_points) -> verdict codes. Exact-output parity with the XLA path
    is pinned by tests/test_kernels.py.

All wrappers pad B to the sublane tile and T to the 128-lane tile with
masked-out slots (masking is already the framework's ragged-window
idiom), and run in interpreter mode automatically off-TPU so tests and
CPU meshes execute the same code path.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Verdict codes — must match engine/scoring.py (HEALTHY/UNHEALTHY/UNKNOWN).
_HEALTHY, _UNHEALTHY, _UNKNOWN = 0, 1, 2

TILE_B = 32  # sublane-aligned batch tile (f32 min 8; 32 amortizes grid)
LANE = 128


def use_pallas() -> bool:
    """Kernel dispatch gate: FOREMAST_PALLAS=1 opts in.

    Default OFF: no shipped path dispatches the kernel — the rank tests
    dominate the scoring program and the MA-stats pass is memory-bound
    either way. Whether it earns a path is a chip measurement that has
    not been made (ROADMAP D6/S3); `chip_smoke.py` only proves it still
    lowers and agrees with the XLA program."""
    return os.environ.get("FOREMAST_PALLAS", "") == "1"


def _interpret(interpret: bool | None) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _pad_axis(x: jax.Array, mult: int, axis: int, fill) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _pad_bt(values: jax.Array, mask: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Pad [B, T] to (TILE_B, LANE) multiples; padding is masked out."""
    v = _pad_axis(_pad_axis(values, LANE, 1, 0.0), TILE_B, 0, 0.0)
    m = _pad_axis(_pad_axis(mask, LANE, 1, False), TILE_B, 0, False)
    return v, m.astype(values.dtype)


def _col(x, b_padded, dtype):
    """[B] (or scalar) parameter -> padded [Bp, 1] column."""
    x = jnp.asarray(x, dtype)
    if x.ndim == 0:
        x = jnp.full((b_padded,), x, dtype)
    else:
        x = _pad_axis(x, TILE_B, 0, 0)
    return x[:, None]


# ---------------------------------------------------------------------------
# masked_stats
# ---------------------------------------------------------------------------


def _stats_kernel(v_ref, m_ref, cnt_ref, mean_ref, std_ref):
    v = v_ref[:]
    m = m_ref[:]
    cnt = jnp.sum(m, axis=-1, keepdims=True)  # [TB, 1]
    c = jnp.maximum(cnt, 1.0)
    mu = jnp.sum(v * m, axis=-1, keepdims=True) / c
    # two-pass variance on the VMEM-resident block: same numerics as
    # windows.masked_std (E[x^2]-E[x]^2 cancels catastrophically when
    # mu >> sigma), and the second pass costs no extra HBM traffic
    d = (v - mu) * m
    var = jnp.sum(d * d, axis=-1, keepdims=True) / c
    cnt_ref[:] = cnt
    mean_ref[:] = mu
    std_ref[:] = jnp.sqrt(var)


@functools.partial(jax.jit, static_argnames=("interpret",))
def masked_stats(
    values: jax.Array, mask: jax.Array, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-pass masked (count, mean, std[ddof=0]) over the time axis.

    values [B, T] float32, mask [B, T] bool -> three [B] float32 arrays.
    """
    b = values.shape[0]
    v, m = _pad_bt(values.astype(jnp.float32), mask)
    bp, tp = v.shape
    grid = (bp // TILE_B,)
    row_spec = pl.BlockSpec((TILE_B, tp), lambda i: (i, 0), memory_space=pltpu.VMEM)
    col_spec = pl.BlockSpec((TILE_B, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)
    out = jax.ShapeDtypeStruct((bp, 1), jnp.float32)
    cnt, mean, std = pl.pallas_call(
        _stats_kernel,
        grid=grid,
        in_specs=[row_spec, row_spec],
        out_specs=(col_spec, col_spec, col_spec),
        out_shape=(out, out, out),
        interpret=_interpret(interpret),
    )(v, m)
    return cnt[:b, 0], mean[:b, 0], std[:b, 0]


# ---------------------------------------------------------------------------
# ma_judgment — the fused default-algorithm scoring kernel
# ---------------------------------------------------------------------------


def _judgment_kernel(
    hv_ref, hm_ref, cv_ref, cm_ref, thr_ref, bnd_ref, mlb_ref, mnp_ref,
    verdict_ref, anom_ref, upper_ref, lower_ref,
):
    hv = hv_ref[:]
    hm = hm_ref[:]
    cnt = jnp.sum(hm, axis=-1, keepdims=True)  # [TB, 1]
    c = jnp.maximum(cnt, 1.0)
    mu = jnp.sum(hv * hm, axis=-1, keepdims=True) / c
    d = (hv - mu) * hm  # two-pass variance, see _stats_kernel
    sigma = jnp.sqrt(jnp.sum(d * d, axis=-1, keepdims=True) / c)

    band = thr_ref[:] * sigma  # [TB, 1]
    up = mu + band
    lo = jnp.maximum(mu - band, mlb_ref[:])

    cur = cv_ref[:]
    curm = cm_ref[:] > 0.0
    bnd = bnd_ref[:].astype(jnp.int32)
    use_up = (bnd == 1) | (bnd == 3)
    use_lo = (bnd == 2) | (bnd == 3)
    flags = curm & (((cur > up) & use_up) | ((cur < lo) & use_lo))

    ncur = jnp.sum(cm_ref[:], axis=-1, keepdims=True)
    measurable = (cnt >= mnp_ref[:]) & (ncur > 0.0)
    flags = flags & measurable
    any_anom = jnp.any(flags, axis=-1, keepdims=True)
    verdict_ref[:] = jnp.where(
        measurable,
        jnp.where(any_anom, _UNHEALTHY, _HEALTHY),
        _UNKNOWN,
    ).astype(jnp.int32)
    anom_ref[:] = flags.astype(jnp.float32)
    upper_ref[:] = jnp.broadcast_to(up, cur.shape)
    lower_ref[:] = jnp.broadcast_to(lo, cur.shape)


def _judgment_bf16_kernel(
    anchor_ref, delta_ref, lens_ref, cv_ref, cm_ref, thr_ref, bnd_ref,
    mlb_ref, mnp_ref, verdict_ref, anom_ref, upper_ref, lower_ref,
):
    # moments straight off the bf16 deltas with f32 accumulation —
    # E[v] = anchor + E[d], Var[v] = Var[d]; left-packed deltas are
    # exact zeros past `lens`, so plain sums ARE the masked sums
    # (identical algebra to scoring.score_bf16_delta)
    d = delta_ref[:].astype(jnp.float32)
    n = lens_ref[:]  # [TB, 1] f32 valid counts
    c = jnp.maximum(n, 1.0)
    s1 = jnp.sum(d, axis=-1, keepdims=True)
    s2 = jnp.sum(d * d, axis=-1, keepdims=True)
    mean_d = s1 / c
    mean = jnp.where(n > 0, anchor_ref[:] + mean_d, 0.0)
    var = jnp.where(
        n > 0, jnp.maximum(s2 / c - mean_d * mean_d, 0.0), 0.0
    )
    sigma = jnp.sqrt(var)

    band = thr_ref[:] * sigma
    up = mean + band
    lo = jnp.maximum(mean - band, mlb_ref[:])

    cur = cv_ref[:]
    curm = cm_ref[:] > 0.0
    bnd = bnd_ref[:].astype(jnp.int32)
    use_up = (bnd == 1) | (bnd == 3)
    use_lo = (bnd == 2) | (bnd == 3)
    flags = curm & (((cur > up) & use_up) | ((cur < lo) & use_lo))

    ncur = jnp.sum(cm_ref[:], axis=-1, keepdims=True)
    measurable = (n >= mnp_ref[:]) & (ncur > 0.0)
    flags = flags & measurable
    any_anom = jnp.any(flags, axis=-1, keepdims=True)
    verdict_ref[:] = jnp.where(
        measurable,
        jnp.where(any_anom, _UNHEALTHY, _HEALTHY),
        _UNKNOWN,
    ).astype(jnp.int32)
    anom_ref[:] = flags.astype(jnp.float32)
    upper_ref[:] = jnp.broadcast_to(up, cur.shape)
    lower_ref[:] = jnp.broadcast_to(lo, cur.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ma_judgment_bf16_delta(
    anchor: jax.Array,
    delta: jax.Array,
    lens: jax.Array,
    cur_values: jax.Array,
    cur_mask: jax.Array,
    threshold: jax.Array,
    bound: jax.Array,
    min_lower_bound: jax.Array,
    min_points: jax.Array,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """`ma_judgment` on the bf16-delta history layout (VERDICT r5 #5).

    anchor [B] f32, delta [B, Th] bf16 (anchor-shifted, LEFT-PACKED:
    exact zeros past `lens`), lens [B] int32 — the same wire layout as
    `scoring.score_bf16_delta`/`fit_ma_from_bf16_delta`, so the kernel
    reads 2 B/point like the shipped XLA program instead of the f32
    kernel's 5 B/point. Same outputs/semantics as `ma_judgment` up to
    bf16 rounding of the deviations (parity pinned by tests)."""
    b, tc = cur_values.shape
    dv = _pad_axis(_pad_axis(delta, LANE, 1, 0), TILE_B, 0, 0)
    cv, cm = _pad_bt(cur_values.astype(jnp.float32), cur_mask)
    bp = dv.shape[0]
    thp = dv.shape[1]
    tcp = cv.shape[1]
    f32 = jnp.float32
    anc = _col(anchor, bp, f32)
    nvl = _col(lens, bp, f32)
    thr = _col(threshold, bp, f32)
    bnd = _col(bound, bp, jnp.int32)
    mlb = _col(min_lower_bound, bp, f32)
    mnp = _col(min_points, bp, f32)

    grid = (bp // TILE_B,)
    hist_spec = pl.BlockSpec((TILE_B, thp), lambda i: (i, 0), memory_space=pltpu.VMEM)
    cur_spec = pl.BlockSpec((TILE_B, tcp), lambda i: (i, 0), memory_space=pltpu.VMEM)
    col_spec = pl.BlockSpec((TILE_B, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)

    verdict, anom, upper, lower = pl.pallas_call(
        _judgment_bf16_kernel,
        grid=grid,
        in_specs=[col_spec, hist_spec, col_spec, cur_spec, cur_spec,
                  col_spec, col_spec, col_spec, col_spec],
        out_specs=(col_spec, cur_spec, cur_spec, cur_spec),
        out_shape=(
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((bp, tcp), f32),
            jax.ShapeDtypeStruct((bp, tcp), f32),
            jax.ShapeDtypeStruct((bp, tcp), f32),
        ),
        interpret=_interpret(interpret),
    )(anc, dv, nvl, cv, cm, thr, bnd, mlb, mnp)
    return (
        verdict[:b, 0],
        anom[:b, :tc] > 0.0,
        upper[:b, :tc],
        lower[:b, :tc],
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def ma_judgment(
    hist_values: jax.Array,
    hist_mask: jax.Array,
    cur_values: jax.Array,
    cur_mask: jax.Array,
    threshold: jax.Array,
    bound: jax.Array,
    min_lower_bound: jax.Array,
    min_points: jax.Array,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused moving_average_all judgment (see module docstring).

    hist [B, Th], cur [B, Tc]; threshold/bound/min_lower_bound/min_points
    scalar or [B]. Returns (verdict [B] int32, anomalies [B, Tc] bool,
    upper [B, Tc], lower [B, Tc]) — matches the XLA path in
    engine/scoring.py for algorithm="moving_average_all" (fp32 tolerance;
    parity pinned by tests).
    """
    b, tc = cur_values.shape
    hv, hm = _pad_bt(hist_values.astype(jnp.float32), hist_mask)
    cv, cm = _pad_bt(cur_values.astype(jnp.float32), cur_mask)
    bp, thp = hv.shape
    tcp = cv.shape[1]
    f32 = jnp.float32
    thr = _col(threshold, bp, f32)
    bnd = _col(bound, bp, jnp.int32)
    mlb = _col(min_lower_bound, bp, f32)
    mnp = _col(min_points, bp, f32)

    grid = (bp // TILE_B,)
    hist_spec = pl.BlockSpec((TILE_B, thp), lambda i: (i, 0), memory_space=pltpu.VMEM)
    cur_spec = pl.BlockSpec((TILE_B, tcp), lambda i: (i, 0), memory_space=pltpu.VMEM)
    col_spec = pl.BlockSpec((TILE_B, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)

    verdict, anom, upper, lower = pl.pallas_call(
        _judgment_kernel,
        grid=grid,
        in_specs=[hist_spec, hist_spec, cur_spec, cur_spec,
                  col_spec, col_spec, col_spec, col_spec],
        out_specs=(col_spec, cur_spec, cur_spec, cur_spec),
        out_shape=(
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((bp, tcp), f32),
            jax.ShapeDtypeStruct((bp, tcp), f32),
            jax.ShapeDtypeStruct((bp, tcp), f32),
        ),
        interpret=_interpret(interpret),
    )(hv, hm, cv, cm, thr, bnd, mlb, mnp)
    return (
        verdict[:b, 0],
        anom[:b, :tc] > 0.0,
        upper[:b, :tc],
        lower[:b, :tc],
    )
