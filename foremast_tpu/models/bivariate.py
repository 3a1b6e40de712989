"""Bivariate normal detector for 2-metric jobs.

Reference model zoo: "2 metrics: Bivariate Normal Distribution"
(`docs/guides/design.md:78`). The historical joint distribution of two
metrics (e.g. latency x tps) is fit as a 2-D Gaussian; current points are
scored by Mahalanobis distance, anomalous where d^2 exceeds the chi^2(2)
quantile implied by the configured threshold.

Batched closed-form fit — means/covariances are masked moment sums over the
[B, T] history, the 2x2 inverse is explicit (no linalg solve inside jit),
so the whole detector is a handful of fused VPU ops.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from foremast_tpu.ops.windows import masked_mean


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class BivariateFit:
    """mean: [B, 2]; cov: [B, 2, 2]; valid: [B] (enough points, non-singular)."""

    mean: jax.Array
    cov: jax.Array
    valid: jax.Array


def fit_bivariate(
    x: jax.Array, y: jax.Array, mask: jax.Array, min_points: int = 10
) -> BivariateFit:
    """Fit a 2-D Gaussian to paired histories. x/y/mask: [B, T].

    Short-history entry point (ISSUE 10 admission): the fit is moment-
    based, so any history clearing `min_points` yields a VALID,
    verdict-capable Gaussian — a newcomer admitted on 1-2 days of ring
    coverage fits exactly like a 7-day history, just with wider moment
    uncertainty; below the floor `valid=False` degrades the job to
    UNKNOWN, never to a fragile fit."""
    mx = masked_mean(x, mask)
    my = masked_mean(y, mask)
    m = mask.astype(x.dtype)
    n = jnp.sum(m, axis=-1)
    dx = (x - mx[:, None]) * m
    dy = (y - my[:, None]) * m
    denom = jnp.maximum(n, 1.0)
    sxx = jnp.sum(dx * dx, axis=-1) / denom
    syy = jnp.sum(dy * dy, axis=-1) / denom
    sxy = jnp.sum(dx * dy, axis=-1) / denom
    mean = jnp.stack([mx, my], axis=-1)
    cov = jnp.stack(
        [jnp.stack([sxx, sxy], -1), jnp.stack([sxy, syy], -1)], axis=-2
    )
    det = sxx * syy - sxy * sxy
    # relative conditioning test: scale-free, so low-magnitude metric pairs
    # (e.g. error rates ~1e-4) stay valid while truly degenerate
    # (perfectly-correlated or zero-variance) fits are rejected
    valid = (n >= min_points) & (det > 1e-6 * sxx * syy) & (sxx * syy > 0)
    return BivariateFit(mean=mean, cov=cov, valid=valid)


def mahalanobis2(fit: BivariateFit, x: jax.Array, y: jax.Array) -> jax.Array:
    """Squared Mahalanobis distance of current points. x/y: [B, T] -> [B, T]."""
    dx = x - fit.mean[:, 0:1]
    dy = y - fit.mean[:, 1:2]
    sxx = fit.cov[:, 0, 0][:, None]
    syy = fit.cov[:, 1, 1][:, None]
    sxy = fit.cov[:, 0, 1][:, None]
    det = jnp.maximum(sxx * syy - sxy * sxy, 1e-30)
    # explicit 2x2 inverse
    return (syy * dx * dx - 2.0 * sxy * dx * dy + sxx * dy * dy) / det


def detect_bivariate(
    fit: BivariateFit,
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    threshold: jax.Array | float = 2.0,
) -> jax.Array:
    """Anomaly flags [B, T]: d^2 > threshold^2 per-axis-sigma equivalent.

    `threshold` keeps the reference's "number of sigmas" semantics
    (`foremast-brain.yaml:26-27`): a point is anomalous when it lies outside
    the ellipsoid whose per-axis radius is threshold sigmas, i.e.
    d^2 > threshold^2 (chi^2(2) generalization of |z| > threshold).
    Windows with an invalid fit flag nothing (unknown, not unhealthy).
    """
    threshold = jnp.asarray(threshold, x.dtype)
    if threshold.ndim == 1:
        threshold = threshold[:, None]
    d2 = mahalanobis2(fit, x, y)
    return mask & (d2 > threshold * threshold) & fit.valid[:, None]


@partial(jax.jit, static_argnames=("min_points",))
def fit_bivariate_bf16_delta(
    anchor_x: jax.Array,
    delta_x: jax.Array,
    anchor_y: jax.Array,
    delta_y: jax.Array,
    mask: jax.Array,
    min_points: int = 10,
) -> BivariateFit:
    """`fit_bivariate` from an anchor-shifted bf16-delta history upload.

    Mirrors `scoring.fit_forecast_bf16_delta`: the paired histories ship
    as (f32 anchor [B], bf16 delta [B, T]) per metric — 2 B/point on the
    wire instead of f32's 4 — and f32 values are reconstructed
    in-program (transient HBM; the saving is H2D bytes on cold joint
    fleet ticks). Deltas are packed masked
    (exact zeros in masked slots), so reconstruction multiplies the mask
    back in to keep masked slots at exact zero like the f32 pack."""
    m = mask.astype(jnp.float32)
    x = (anchor_x[:, None] + delta_x.astype(jnp.float32)) * m
    y = (anchor_y[:, None] + delta_y.astype(jnp.float32)) * m
    return fit_bivariate(x, y, mask, min_points=min_points)


@jax.jit
def detect_bivariate_from_rows(
    mean: jax.Array,
    cov: jax.Array,
    rows: jax.Array,
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    threshold: jax.Array,
) -> jax.Array:
    """`detect_bivariate` against ARENA-resident fits (engine.arena
    .TreeArena): `mean` [capacity, 2] / `cov` [capacity, 2, 2] hold one
    fitted Gaussian per arena row and `rows` [B] indexes the batch's
    fits, so a warm re-check tick ships only the current windows and a
    row-index vector — the joint counterpart of
    `scoring.score_from_arena`. Only VALID fits are ever admitted to the
    arena (the judge caches invalid fits nowhere), so the gathered state
    carries no validity flag.

    Mesh contract (ISSUE 13): per-row independent along [B] — `x`/`y`/
    `mask` may arrive with their leading axis sharded over a data axis
    (B a multiple of it) with `mean`/`cov` replicated; the gather then
    reads each device's local arena replica, zero collectives."""
    fit = BivariateFit(
        mean=jnp.take(mean, rows, axis=0),
        cov=jnp.take(cov, rows, axis=0),
        valid=jnp.ones(rows.shape, bool),
    )
    return detect_bivariate(fit, x, y, mask, threshold)


@partial(jax.jit, static_argnames=("mesh",))
def detect_bivariate_from_rows_sharded(
    mean: jax.Array,
    cov: jax.Array,
    rows: jax.Array,
    x: jax.Array,
    y: jax.Array,
    mask: jax.Array,
    threshold: jax.Array,
    mesh=None,
) -> jax.Array:
    """`detect_bivariate_from_rows` against a DATA-AXIS-SHARDED arena
    (ISSUE 19): `mean`/`cov` block-shard their [capacity] leading axis
    over `mesh`'s data axis and `rows` [B] carries LOCAL (per-shard)
    indices — the judge's block placement rule guarantees each batch
    position's fit lives on the device holding that position, so the
    gather runs as a shard_map against each device's OWN block: zero
    cross-chip transfer, without replication's per-device HBM copy."""
    from foremast_tpu.parallel import mesh as meshlib

    g = meshlib.shard_rows_take({"mean": mean, "cov": cov}, rows, mesh)
    fit = BivariateFit(
        mean=g["mean"],
        cov=g["cov"],
        valid=jnp.ones(rows.shape, bool),
    )
    return detect_bivariate(fit, x, y, mask, threshold)
