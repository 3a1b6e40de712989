"""Seasonal-residual multivariate Gaussian — the joint contextual detector.

Companion to the learned LSTM detector for 3+ metric jobs (reference model
zoo: "3+ metrics: Deep Learning (LSTM)", `docs/guides/design.md:84`). Pure
reconstruction scoring has a structural blind spot: an autoencoder that
*sees* an in-window anomaly can reproduce ("copy") it, and a plain
marginal check misses contextual anomalies (a spike at a seasonal trough
lands near the marginal mean). This detector closes both gaps with two
closed-form, TPU-native pieces:

  1. per-metric causal Holt-Winters residuals — `hw_continue` predictions
     never see the point they score, so an anomaly cannot be copied, and
     the seasonal state removes the cycle, so trough-masked spikes stand
     out;
  2. a full-covariance Gaussian over the F-dimensional residual vector —
     co-movement between metrics is learned from historical residuals, so
     a single metric deviating from the pack (correlation break) scores a
     large Mahalanobis distance even when its marginal z-score is modest.

Threshold calibration: the reference's thresholds are "number of sigmas"
(`foremast-brain.yaml:26-27`). A fixed d^2 > thr^2 rule would get tighter
with F (chi^2_F mass grows with F), so the cutoff is the chi^2_F quantile
whose tail mass equals the two-sided normal tail P(|z| > thr) — the same
false-positive rate as the univariate detectors at the same configured
threshold, at any metric count.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial

import jax
import jax.numpy as jnp

from foremast_tpu.ops.forecasters import Forecast, holt_winters, hw_continue

# Holt-Winters smoothing used for residual extraction (fixed, not
# grid-fit: residual covariance absorbs model error, and fixed params keep
# the fit cacheable per job without a per-metric grid search).
HW_PARAMS = (0.3, 0.05, 0.1)
SEASON_LENGTH = 24


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MVNState:
    """Fitted residual model for a batch of F-metric jobs.

    hw:    Forecast with [B*F]-flattened leaves (terminal HW state per
           (job, metric) series; season [B*F, m])
    mu:    [B, F]    historical residual means
    cov:   [B, F, F] historical residual covariance (ridge-regularized)
    valid: [B]       enough history + well-conditioned covariance
    """

    hw: Forecast
    mu: jax.Array
    cov: jax.Array
    valid: jax.Array


@functools.lru_cache(maxsize=256)
def chi2_quantile(threshold: float, dof: int) -> float:
    """chi^2_dof cutoff with the same tail mass as P(|z| > threshold).

    Host-side (scipy), called once per judgment batch with static dof."""
    from scipy import stats

    p_tail = 2.0 * stats.norm.sf(threshold)
    p_tail = min(max(p_tail, 1e-300), 1.0)
    return float(stats.chi2.ppf(1.0 - p_tail, dof))


@partial(jax.jit, static_argnames=("season_length", "min_points", "ridge"))
def fit_residual_mvn(
    hist: jax.Array,
    mask: jax.Array | None = None,
    season_length: int = SEASON_LENGTH,
    min_points: int = 10,
    ridge: float = 1e-6,
) -> MVNState:
    """Fit per-metric HW + residual covariance.

    hist: [B, F, Th] aligned joint histories (joint observations are
    intersected upstream, `multivariate._align`, so every metric of a job
    shares one validity pattern); mask: [B, Th] valid-prefix mask for
    bucket-padded batches (None = all valid).

    Identifiability guard (the same 2-cycle rule as `fit_holt_winters`
    and the auto screen): a batch whose static length holds fewer than
    two full seasons fits with season length 1 instead — the HW
    degenerates to Holt's linear method, residuals stay causal and the
    covariance still learns co-movement; only the (unidentifiable) cycle
    is dropped. Without this, a daily-configured engine (m=1440) would
    either disable the MVN outright on sub-2-day histories (empty warm
    region -> valid=False) or score against a season memorized from one
    partial cycle. That m=1 degradation is also the short-history entry
    point for cold-start admission (ISSUE 10): a newcomer's 1-2 pushed
    days fit a valid Holt-residual Gaussian immediately, and background
    refinement refits at the full season once coverage clears two
    cycles."""
    b, f, th = hist.shape
    a, bt, g = HW_PARAMS
    m_eff = int(season_length) if th >= 2 * int(season_length) else 1
    if mask is None:
        mask = jnp.ones((b, th), bool)
    flat = hist.reshape(b * f, th)
    mflat = jnp.repeat(mask, f, axis=0)
    fc = holt_winters(flat, mflat, m_eff, a, bt, g)
    resid = (flat - fc.pred).reshape(b, f, th)
    # drop the first season: those predictions come from init state
    warm_mask = mask & (jnp.arange(th)[None, :] >= m_eff)  # [B, Th]
    n = jnp.maximum(jnp.sum(warm_mask, axis=-1), 1)  # [B]
    w = warm_mask[:, None, :].astype(resid.dtype)  # [B, 1, Th]
    mu = jnp.sum(resid * w, axis=-1) / n[:, None]  # [B, F]
    rc = (resid - mu[:, :, None]) * w
    # full-precision accumulation: the 10k-term residual outer products
    # feed a solve — TPU default-bf16 matmul accumulation would quantize
    # the covariance (same hazard as the seasonal Gram, seasonal._design)
    cov = (
        jnp.einsum("bft,bgt->bfg", rc, rc, precision=jax.lax.Precision.HIGHEST)
        / n[:, None, None]
    )
    # scale-aware ridge keeps tiny-magnitude metrics invertible without
    # distorting their geometry
    tr = jnp.trace(cov, axis1=-2, axis2=-1) / f  # [B]
    eye = jnp.eye(f, dtype=cov.dtype)
    cov = cov + (ridge * tr + 1e-12)[:, None, None] * eye
    # conditioning: det of the ridged cov must be positive and finite
    sign, logdet = jnp.linalg.slogdet(cov)
    valid = (n >= min_points) & (sign > 0) & jnp.isfinite(logdet)
    return MVNState(hw=fc, mu=mu, cov=cov, valid=valid)


@partial(jax.jit, static_argnames=("season_length", "min_points", "ridge"))
def fit_residual_mvn_bf16_delta(
    anchor: jax.Array,
    delta: jax.Array,
    mask: jax.Array | None = None,
    season_length: int = SEASON_LENGTH,
    min_points: int = 10,
    ridge: float = 1e-6,
) -> MVNState:
    """`fit_residual_mvn` from an anchor-shifted bf16-delta upload.

    hist ships as (f32 anchor [B, F], bf16 delta [B, F, Th]) — the same
    2 B/point wire layout as `scoring.fit_forecast_bf16_delta`; f32
    values are reconstructed in-program (transient HBM, the saving is
    the H2D bound of cold joint fleet ticks). Deltas are packed masked
    (exact zeros), so masked slots reconstruct to exact zero like the
    f32 pack path."""
    values = anchor[:, :, None] + delta.astype(jnp.float32)
    if mask is not None:
        values = values * mask[:, None, :]
    return fit_residual_mvn(
        values,
        mask,
        season_length=season_length,
        min_points=min_points,
        ridge=ridge,
    )


def _d2(state: MVNState, cur: jax.Array, upd: jax.Array) -> jax.Array:
    """d^2 [B, Tc] with per-(job, t) state-update gating.

    upd [B, Tc] False carries HW state THROUGH a point (it is still
    scored — the residual is measured against the un-updated prediction
    — but cannot contaminate later predictions); the phase advances
    either way (hw_continue mask semantics).

    Mesh contract (ISSUE 13; gathered-state layouts in ISSUE 19):
    per-row independent along [B] — the [B*F] reshape below multiplies
    the leading axis, which a data-axis sharding of `cur` follows
    cleanly (B a multiple of the axis), and the per-job `linalg.solve`
    batches row-locally. The MVNState rows arrive already gathered per
    batch position — from a replicated arena via a global take, or from
    a data-axis-SHARDED arena via the shard_map local gather in
    `multivariate.lstm_joint_score_from_rows_sharded` — either way the
    state leading axis shards exactly like `cur`. Nothing here may
    reduce across [B]."""
    b, f, tc = cur.shape
    a, bt, g = HW_PARAMS
    flat = cur.reshape(b * f, tc)
    # named scopes: the phase rides every op's name in a device trace
    with jax.named_scope("hw_continue"):
        pred, _ = hw_continue(
            state.hw,
            flat,
            jnp.repeat(upd, f, axis=0),
            state.hw.season.shape[-1],
            a,
            bt,
            g,
        )
    with jax.named_scope("mvn_judge"):
        resid = (flat - pred).reshape(b, f, tc)
        d = resid - state.mu[:, :, None]  # [B, F, Tc]
        # solve per job: cov [B,F,F] x X = d -> d^T cov^-1 d per step
        sol = jnp.linalg.solve(state.cov, d)  # [B, F, Tc]
        return jnp.sum(d * sol, axis=1)  # [B, Tc]


@jax.jit
def residual_mvn_d2(state: MVNState, cur: jax.Array) -> jax.Array:
    """Mahalanobis d^2 [B, Tc] for aligned joint current windows
    [B, F, Tc]: causal HW residual per metric against the historical
    residual Gaussian. The season length is the STATE's own (its buffer
    width): a short-history fit that degenerated to m=1 (see
    `fit_residual_mvn`) must be continued at m=1, not zeroed against
    the configured length."""
    return _d2(state, cur, jnp.ones(cur.shape[::2], bool))


@jax.jit
def residual_mvn_d2_robust(
    state: MVNState, cur: jax.Array, gate_cutoff: jax.Array | float
) -> jax.Array:
    """Two-pass outlier-robust d^2 (the judge's scoring path).

    The plain pass lets every observed point update the HW state, so an
    anomalous spike at t contaminates the t+1 prediction and manufactures
    an ECHO — a false borderline d^2 right after every true anomaly.
    Robust filtering: pass 1 computes plain d^2; pass 2 recomputes it
    with state updates gated OFF at every point pass 1 put over
    `gate_cutoff` [B]. Echoes vanish (the spike never enters the state)
    while a sustained true shift keeps scoring high — the state can no
    longer absorb it, which strictly helps recall."""
    d2 = _d2(state, cur, jnp.ones(cur.shape[::2], bool))
    gate = jnp.asarray(gate_cutoff, d2.dtype)
    if gate.ndim == 1:
        gate = gate[:, None]
    return _d2(state, cur, ~(d2 > gate))


@jax.jit
def score_residual_mvn(
    state: MVNState,
    cur: jax.Array,
    d2_cutoff: jax.Array | float,
) -> jax.Array:
    """Anomaly flags [B, Tc]: d^2 (`residual_mvn_d2`) exceeding the
    calibrated cutoff (see `chi2_quantile`). Invalid fits flag nothing."""
    d2 = residual_mvn_d2(state, cur)
    cutoff = jnp.asarray(d2_cutoff, d2.dtype)
    if cutoff.ndim == 1:
        cutoff = cutoff[:, None]
    return (d2 > cutoff) & state.valid[:, None]
