"""Batched masked time-series forecasters.

The reference brain's model zoo (reference `docs/guides/design.md:57-93`):
moving average, exponential smoothing (EWMA), double exponential smoothing,
Holt-Winters (+ Prophet, approximated separately in models/seasonal.py).
Deployed default algorithm is `moving_average_all`
(`deploy/foremast/3_brain/foremast-brain.yaml:24-25`).

TPU-first design notes:
  * every forecaster is batched over a leading [B] axis and jit-friendly;
  * ragged history is handled by validity masks, never by dynamic shapes;
  * EWMA is a linear recurrence, so it runs as `lax.associative_scan`
    (log-depth on the VPU, and shardable along time for sequence
    parallelism — see parallel/seqparallel.py);
  * Holt / Holt-Winters run as `lax.scan` with the whole batch inside the
    carry, so XLA emits one fused loop over time for all series at once;
  * smoothing parameters are *fit* by a vectorized grid search (vmap over
    the grid), not per-series Python loops.

All forecasters return a `Forecast` carrying in-sample one-step-ahead
predictions (for residual scale), a residual scale, and terminal state
(level/trend/season) from which `horizon` extrapolates future bounds.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from foremast_tpu.ops.windows import masked_mean, masked_moments, masked_std


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Forecast:
    """Fitted forecaster state for a batch of series.

    pred:   [B, T] one-step-ahead in-sample predictions
    scale:  [B]    residual standard deviation (deviation unit for bounds)
    level:  [B]    terminal level
    trend:  [B]    terminal per-step trend (0 for trendless models)
    season: [B, m] terminal seasonal offsets (m=1 zeros when non-seasonal)
    season_phase: [B] int32 — season index of the *next* (first forecast) step
    """

    pred: jax.Array
    scale: jax.Array
    level: jax.Array
    trend: jax.Array
    season: jax.Array
    season_phase: jax.Array


def _finalize(
    pred, values, mask, level, trend, season=None, season_phase=None, scale=None
):
    if scale is None:
        resid = values - pred
        scale = masked_std(resid, mask, ddof=0)
    b = values.shape[0]
    if season is None:
        season = jnp.zeros((b, 1), dtype=values.dtype)
        season_phase = jnp.zeros((b,), dtype=jnp.int32)
    return Forecast(
        pred=pred,
        scale=scale,
        level=level,
        trend=trend,
        season=season,
        season_phase=season_phase,
    )


def horizon(fc: Forecast, h: int) -> jax.Array:
    """Extrapolate h future points from terminal state -> [B, h]."""
    steps = jnp.arange(1, h + 1, dtype=fc.level.dtype)  # [h]
    base = fc.level[:, None] + fc.trend[:, None] * steps[None, :]
    m = fc.season.shape[-1]
    idx = (fc.season_phase[:, None] + jnp.arange(h)[None, :]) % m  # [B,h]
    seas = jnp.take_along_axis(fc.season, idx, axis=-1)
    return base + seas


# ---------------------------------------------------------------------------
# Moving averages
# ---------------------------------------------------------------------------


def moving_average_all(values: jax.Array, mask: jax.Array) -> Forecast:
    """Global-mean model over the whole masked history.

    This is the reference's deployed default `moving_average_all`
    (`foremast-brain.yaml:24-25`): the "model" is the historical mean, the
    deviation unit is the historical std, and bounds are
    mean +/- threshold * std.

    Uses `masked_moments` — mean and variance in ONE fused reduction over
    the [B, 10k] history (the two-pass mean-then-centered-squares form
    reads the 7-day window twice, and this model is pure HBM bandwidth).
    """
    b, t_len = values.shape
    if t_len == 0:  # empty-history batch: unmeasurable, not a crash
        zeros = jnp.zeros((b,), values.dtype)
        return _finalize(values, values, mask, level=zeros, trend=zeros, scale=zeros)
    _, mu, var = masked_moments(values, mask)
    scale = jnp.sqrt(var)
    pred = jnp.broadcast_to(mu[:, None], values.shape)
    zeros = jnp.zeros_like(mu)
    return _finalize(pred, values, mask, level=mu, trend=zeros, scale=scale)


def moving_average(values: jax.Array, mask: jax.Array, window: int = 10) -> Forecast:
    """Causal rolling mean of the previous `window` time steps.

    pred[t] = mean of valid points in [t-window, t); falls back to the
    running global mean until enough history accumulates.
    """
    v = values * mask
    m = mask.astype(values.dtype)
    # prefix sums shifted so position t sums strictly-previous samples
    csum_v = jnp.cumsum(v, axis=-1)
    csum_m = jnp.cumsum(m, axis=-1)
    pad = jnp.zeros_like(csum_v[..., :1])
    prev_v = jnp.concatenate([pad, csum_v[..., :-1]], axis=-1)
    prev_m = jnp.concatenate([pad, csum_m[..., :-1]], axis=-1)
    lo_v = jnp.roll(prev_v, window, axis=-1).at[..., :window].set(0.0)
    lo_m = jnp.roll(prev_m, window, axis=-1).at[..., :window].set(0.0)
    win_v = prev_v - lo_v
    win_m = prev_m - lo_m
    run_mean = prev_v / jnp.maximum(prev_m, 1.0)
    pred = jnp.where(win_m > 0, win_v / jnp.maximum(win_m, 1.0), run_mean)
    # first point has no history at all: predict itself (zero residual)
    pred = jnp.where((prev_m == 0), values, pred)
    # terminal level: mean of the last `window` valid points
    last_mask = mask & (csum_m > jnp.maximum(csum_m[..., -1:] - window, 0))
    level = masked_mean(values, last_mask)
    zeros = jnp.zeros_like(level)
    return _finalize(pred, values, mask, level=level, trend=zeros)


# ---------------------------------------------------------------------------
# Exponential smoothing (associative-scan form)
# ---------------------------------------------------------------------------


def _linrec_assoc(elem_a, elem_b):
    """Compose linear recurrence elements l_t = a*l_{t-1} + b."""
    a1, b1 = elem_a
    a2, b2 = elem_b
    return a1 * a2, a2 * b1 + b2


def ewma_levels(values: jax.Array, mask: jax.Array, alpha) -> jax.Array:
    """Exponentially weighted level after each step, [B, T].

    Implemented as `lax.associative_scan` over the linear recurrence
    l_t = (1-a_t) l_{t-1} + a_t x_t — log-depth, and the same composition
    law the sequence-parallel path uses across devices.
    `alpha` may be scalar or [B] (per-series), broadcast over time.
    """
    alpha = jnp.asarray(alpha, dtype=values.dtype)
    if alpha.ndim == 1:
        alpha = alpha[:, None]
    is_first = mask & (jnp.cumsum(mask, axis=-1) == 1)
    a_eff = jnp.where(mask, alpha, 0.0)
    a_eff = jnp.where(is_first, 1.0, a_eff)
    a = 1.0 - a_eff
    b = a_eff * values
    comp_a, comp_b = jax.lax.associative_scan(_linrec_assoc, (a, b), axis=-1)
    return comp_b  # composed-from-start b is the level (l_0 treated as 0)


def ewma(values: jax.Array, mask: jax.Array, alpha: float = 0.3) -> Forecast:
    """EWMA forecaster: pred[t] is the EW level of points before t."""
    levels = ewma_levels(values, mask, alpha)
    # one-step-ahead: prediction at t is the level after t-1; before any
    # history exists, predict the point itself (zero residual)
    shifted = jnp.concatenate([levels[..., :1] * 0, levels[..., :-1]], axis=-1)
    inited_before = (jnp.cumsum(mask, axis=-1) - mask) > 0
    pred = jnp.where(inited_before, shifted, values)
    level = levels[..., -1]
    zeros = jnp.zeros_like(level)
    return _finalize(pred, values, mask, level=level, trend=zeros)


# ---------------------------------------------------------------------------
# Double exponential smoothing (Holt's linear trend)
# ---------------------------------------------------------------------------


def double_exponential(
    values: jax.Array, mask: jax.Array, alpha: float = 0.3, beta: float = 0.1
) -> Forecast:
    """Holt's linear method, batched inside a single `lax.scan` over time.

    Masked steps carry (level, trend) through unchanged. Initialization:
    level <- first valid point, trend <- 0 (updated from data thereafter).
    """
    alpha = jnp.asarray(alpha, dtype=values.dtype)
    beta = jnp.asarray(beta, dtype=values.dtype)
    b = values.shape[0]

    def step(carry, xs):
        level, trend, inited = carry
        x, m = xs
        pred = level + trend
        new_level = alpha * x + (1.0 - alpha) * (level + trend)
        new_trend = beta * (new_level - level) + (1.0 - beta) * trend
        # first valid point: initialize level=x, trend=0
        first = m & ~inited
        upd = m & inited
        level_out = jnp.where(first, x, jnp.where(upd, new_level, level))
        trend_out = jnp.where(first, 0.0, jnp.where(upd, new_trend, trend))
        pred_out = jnp.where(inited, pred, x)  # zero residual pre-init
        return (level_out, trend_out, inited | m), pred_out

    init = (
        jnp.zeros((b,), values.dtype),
        jnp.zeros((b,), values.dtype),
        jnp.zeros((b,), bool),
    )
    (level, trend, _), preds = jax.lax.scan(
        step, init, (values.T, mask.T)
    )  # scan over time with batch inside
    pred = preds.T
    return _finalize(pred, values, mask, level=level, trend=trend)


# ---------------------------------------------------------------------------
# Holt-Winters (additive seasonal)
# ---------------------------------------------------------------------------


# Season lengths up to this are run with all m phase updates unrolled in
# the scan body (fastest small-m shape, measured below); longer seasons
# (daily m=1440 at the reference's 60 s step) take the rolled path whose
# compiled program is O(1) in m — unrolling 1440 phases emits O(T) HLO
# and explodes compile time.
_HW_UNROLL_MAX = 64


def _hw_season_blocked(values, mask, m_len, alpha, beta, gamma, init_level, init_season):
    """Small-m Holt-Winters body: scan over whole seasons, phases unrolled.

    TPU shape choice: the scan iterates over T/m seasons with the m phase
    updates unrolled inside the body, and the seasonal state carried as a
    tuple of m per-phase [B] vectors. Each phase's slot is then a *static*
    index — no one-hot scatter and no [B, m] buffer rewrite per time step,
    which cuts the sequential loop to T/m steps and the per-step memory
    traffic by ~m x versus the naive time-step scan (measured 34.6k ->
    ~80k windows/s on a v5e chip at B=1024, T=2016, m=24, 8-point grid).
    The math per time step is identical to the textbook recurrence.
    (Also measured and rejected on the same config: a matrix-form
    parallelization over phases via precomputed A-powers — chain T/m
    matmul steps — lands at 44-62k; scan unroll=2 at 68-79k; decimated
    grid selection + full-res final per-series pass at 29-55k. The fused
    season body wins because fit time tracks the sequential substep chain
    almost exclusively.)
    """
    b, t_len = values.shape
    # pad the series to whole seasons; padded steps are masked, so state
    # carries through them unchanged and their preds are sliced away
    n_seasons = -(-t_len // m_len)
    t_pad = n_seasons * m_len - t_len
    v = jnp.pad(values, ((0, 0), (0, t_pad))) if t_pad else values
    mk = jnp.pad(mask, ((0, 0), (0, t_pad))) if t_pad else mask
    xs = v.T.reshape(n_seasons, m_len, b)
    ms = mk.T.reshape(n_seasons, m_len, b)

    def season_step(carry, chunk):
        level, trend, season, inited = carry  # season: tuple of m [B] rows
        x_c, m_c = chunk  # [m, B] each
        season = list(season)
        preds = []
        for p in range(m_len):  # unrolled; p is this step's phase
            x, msk = x_c[p], m_c[p]
            s_t = season[p]
            pred = level + trend + s_t
            new_level = alpha * (x - s_t) + (1.0 - alpha) * (level + trend)
            new_trend = beta * (new_level - level) + (1.0 - beta) * trend
            new_s = gamma * (x - new_level) + (1.0 - gamma) * s_t
            upd = msk & inited
            season[p] = jnp.where(upd, new_s, s_t)
            level = jnp.where(upd, new_level, level)
            trend = jnp.where(upd, new_trend, trend)
            preds.append(jnp.where(inited, pred, x))
            inited = inited | msk
        return (level, trend, tuple(season), inited), jnp.stack(preds)

    init = (
        init_level,
        jnp.zeros((b,), values.dtype),
        tuple(init_season[:, p] for p in range(m_len)),
        jnp.zeros((b,), bool),
    )
    (level, trend, season_t, _), preds = jax.lax.scan(season_step, init, (xs, ms))
    pred = preds.reshape(n_seasons * m_len, -1).T[..., :t_len]
    pred = pred.reshape(values.shape)
    season = jnp.stack(season_t, axis=-1)  # [B, m]
    return pred, level, trend, season


def _hw_rolled(values, mask, m_len, alpha, beta, gamma, init_level, init_season):
    """Long-season Holt-Winters body: one scan step per time step with the
    seasonal state as a [m, B] carry indexed by a *dynamic* phase.

    The phase p = t mod m is shared by the whole batch (season indexing is
    by absolute time-step index), so the per-step seasonal access is a
    single dynamic row slice + in-place row write — O(B) traffic per step
    and O(1) HLO in m, which is what makes daily cycles (m=1440,
    `metricsquery.go:43` 60 s step over the 7-day window) compile at all.
    The recurrence is bit-identical to the season-blocked body.
    """
    b, t_len = values.shape
    phases = jnp.arange(t_len, dtype=jnp.int32) % m_len

    def step(carry, xs):
        level, trend, season, inited = carry  # season: [m, B]
        x, msk, p = xs
        s_t = jax.lax.dynamic_slice_in_dim(season, p, 1, axis=0)[0]  # [B]
        pred = level + trend + s_t
        new_level = alpha * (x - s_t) + (1.0 - alpha) * (level + trend)
        new_trend = beta * (new_level - level) + (1.0 - beta) * trend
        new_s = gamma * (x - new_level) + (1.0 - gamma) * s_t
        upd = msk & inited
        row = jnp.where(upd, new_s, s_t)
        season = jax.lax.dynamic_update_slice_in_dim(season, row[None], p, axis=0)
        level = jnp.where(upd, new_level, level)
        trend = jnp.where(upd, new_trend, trend)
        pred = jnp.where(inited, pred, x)
        return (level, trend, season, inited | msk), pred

    init = (
        init_level,
        jnp.zeros((b,), values.dtype),
        init_season.T,  # [m, B]
        jnp.zeros((b,), bool),
    )
    (level, trend, season, _), preds = jax.lax.scan(
        step, init, (values.T, mask.T, phases)
    )
    return preds.T, level, trend, season.T


def holt_winters(
    values: jax.Array,
    mask: jax.Array,
    season_length: int = 24,
    alpha: float = 0.3,
    beta: float = 0.05,
    gamma: float = 0.1,
) -> Forecast:
    """Additive Holt-Winters, batched.

    Season indexing uses the absolute time-step index modulo m (windows are
    regularly sampled — 60 s PromQL step in the reference,
    `metricsquery.go:43` — so gaps keep their phase).

    Two compile shapes for one recurrence: season lengths up to
    `_HW_UNROLL_MAX` scan over whole seasons with the m phase updates
    unrolled (`_hw_season_blocked`); longer seasons — the reference's
    canonical *daily* cycle is m=1440 at the 60 s step — take the rolled
    per-step scan (`_hw_rolled`), whose program size is independent of m.

    `alpha`/`beta`/`gamma` may be scalars or per-series [B] arrays.

    Initialization: level <- mean of the first season's valid points,
    seasonal offsets <- first-season residuals vs that mean.
    """
    m_len = int(season_length)
    b, t_len = values.shape
    dtype = values.dtype
    alpha = jnp.asarray(alpha, dtype)
    beta = jnp.asarray(beta, dtype)
    gamma = jnp.asarray(gamma, dtype)

    first_season_mask = mask & (jnp.arange(t_len)[None, :] < m_len)
    init_level = masked_mean(values, first_season_mask)  # [B]
    # seasonal init: first-season residuals (0 where that slot was invalid)
    pad = m_len - min(m_len, t_len)
    fs_vals = values[:, :m_len]
    fs_mask = first_season_mask[:, :m_len]
    if pad:
        fs_vals = jnp.pad(fs_vals, ((0, 0), (0, pad)))
        fs_mask = jnp.pad(fs_mask, ((0, 0), (0, pad)))
    init_season = jnp.where(fs_mask, fs_vals - init_level[:, None], 0.0)

    body = _hw_season_blocked if m_len <= _HW_UNROLL_MAX else _hw_rolled
    pred, level, trend, season = body(
        values, mask, m_len, alpha, beta, gamma, init_level, init_season
    )
    # horizon continues right after each series' LAST VALID point: phase
    # from the last valid absolute index (consistent with the in-fit
    # "gaps keep their phase" indexing), not the bucket-padded array
    # length — a [B, 288]-valid history packed into a [B, 512] bucket must
    # not shift the seasonal forecast by 512 % m
    last_valid = jnp.max(
        jnp.where(mask, jnp.arange(t_len)[None, :], -1), axis=-1
    )
    phase_next = ((last_valid + 1) % m_len).astype(jnp.int32)
    return _finalize(
        pred, values, mask, level=level, trend=trend, season=season, season_phase=phase_next
    )


def _guard_unidentifiable(fc: Forecast, values, mask, m_len: int) -> Forecast:
    """Per-series 2-cycle identifiability select.

    The static guards in the fit entries key off the (bucket-padded)
    batch length; a series with fewer than two cycles of REAL points can
    ride a long bucket past them and get a memorized noise season. This
    select keeps the global-mean model for exactly those series — the
    dynamic companion to the static early-outs."""
    enough = jnp.sum(mask, axis=-1) >= 2 * m_len  # [B]
    ma = moving_average_all(values, mask)
    ma = Forecast(
        pred=ma.pred,
        scale=ma.scale,
        level=ma.level,
        trend=ma.trend,
        season=jnp.zeros_like(fc.season),
        season_phase=fc.season_phase,
    )

    def sel(a_leaf, b_leaf):
        keep = enough.reshape((-1,) + (1,) * (a_leaf.ndim - 1))
        return jnp.where(keep, a_leaf, b_leaf)

    return jax.tree_util.tree_map(sel, fc, ma)


# auto_univariate: a series must beat the global-mean model's in-sample
# SSE by at least this factor for the structured (Holt-Winters) fit to be
# selected — in-sample SSE alone always favors the flexible model, so the
# margin screens for REAL seasonality/trend instead of soaked-up noise.
AUTO_SSE_RATIO = 0.5


@partial(jax.jit, static_argnames=("season_length",))
def fit_auto_univariate(
    values: jax.Array, mask: jax.Array, season_length: int = 24
) -> Forecast:
    """Structure-screened model selection, per series.

    The deployed default `moving_average_all` is blind to seasonality and
    trend (its band must widen to cover the cycle), while a flexible fit
    on a genuinely flat series merely soaks up noise. This fit runs three
    candidates — the global mean, an ADAPTIVE structured fit, and the
    changepoint-trend+Fourier seasonal model (models/seasonal.py,
    period=m) — and picks per series: a structured model wins only where
    it explains at least half the mean model's variance (AUTO_SSE_RATIO);
    between the two structured fits the lower SSE wins.

    The adaptive candidate depends on the season length: small m (<=
    _HW_UNROLL_MAX) uses the fitted Holt-Winters; LONG cycles (m=1440
    daily at the 60 s step) use the pooled phase-means fit
    (fit_phase_means) — Holt-Winters there would burn a T-step
    sequential scan for (T/m)-sample-noisy per-phase state, while the
    pooled fit is one parallel reduction and carries arbitrary cycle
    shapes. Long seasons also add a phase-SIGNIFICANCE routing gate
    (Bonferroni-corrected z on the pooled phase means): sparse cycle
    features — a cron-style burst 10 sigmas high but <1% of samples —
    cannot move the SSE ratio, yet a phase-blind band false-flags every
    burst occurrence.

    The screen is scored on the *warm* region only (absolute index >= m):
    Holt-Winters' first season has near-zero residuals by construction
    (seasonal state is initialized from those very residuals), which would
    bias an all-points SSE toward HW by a full season's share.

    Histories shorter than two full cycles keep the mean model outright:
    seasonal structure is unidentifiable from <2 periods, and a "fitted"
    cycle there would be pure noise soak-up. One jitted program.
    """
    m_len = int(season_length)
    t_len = values.shape[1]
    ma = moving_average_all(values, mask)
    if t_len < 2 * m_len:  # also the guard inside both structured fits
        return ma
    # import at call time: models.seasonal imports this module at top level
    from foremast_tpu.models.seasonal import fit_seasonal

    # Long seasons swap the adaptive candidate: Holt-Winters needs a
    # T-step sequential scan and its per-phase state is ~(T/m)-sample
    # noisy, while the pooled phase-means fit is one parallel reduction
    # and representation-free (sharp cron-style cycle features included)
    # — see fit_phase_means. Its in-sample SSE is ~(1-m/T) optimistic
    # (each phase mean includes the scored point), comfortably inside
    # the AUTO_SSE_RATIO=0.5 margin that keeps flat series on the mean
    # model.
    if m_len <= _HW_UNROLL_MAX:
        hw = fit_holt_winters(values, mask, m_len)
    else:
        hw = fit_phase_means(values, mask, m_len)
    se = fit_seasonal(values, mask, period=m_len)
    warm = (mask & (jnp.arange(t_len)[None, :] >= m_len)).astype(values.dtype)

    def sse(fc):
        r = (values - fc.pred) * warm
        return jnp.sum(r * r, axis=-1)  # [B]

    sse_ma, sse_hw, sse_se = sse(ma), sse(hw), sse(se)
    use_struct = jnp.minimum(sse_hw, sse_se) < AUTO_SSE_RATIO * sse_ma  # [B]
    prefer_se = sse_se <= sse_hw  # [B]
    if m_len > _HW_UNROLL_MAX:
        # The SSE-ratio gate is blind to SPARSE cycle features: a
        # cron-style burst 10 sigmas high but 10/1440 of the cycle wide
        # moves total SSE by <1%, yet a phase-blind band false-flags
        # every burst occurrence. Under "no structure" a pooled phase
        # mean is ~N(0, sigma^2/k), so a phase whose |mean| * sqrt(k) /
        # sigma clears a Bonferroni-corrected normal quantile (alpha =
        # 1e-3 over m phases; ~4.9 sigmas at m=1440, comfortably above
        # the ~3.8 max-of-1440 null expectation) is real structure —
        # route those series to the phase-means fit regardless of SSE.
        from scipy import stats as _stats  # host-side, static per m

        z_thr = float(_stats.norm.ppf(1.0 - 1e-3 / m_len))
        kcnt = _phase_counts(mask, m_len, values.dtype)  # [B, m]
        z = jnp.abs(hw.season) * jnp.sqrt(jnp.maximum(kcnt, 1.0)) / jnp.maximum(
            hw.scale[:, None], 1e-30
        )
        z_gate = jnp.max(z, axis=-1) > z_thr
        use_struct = use_struct | z_gate
        # A z-gated series carries a sharp phase feature only the
        # phase-means fit can represent — force that candidate even when
        # a level shift hands the Fourier/changepoint fit the lower SSE
        # (min-SSE there would re-create the burst false-flags this gate
        # exists to prevent).
        prefer_se = prefer_se & ~z_gate

    def sel(flag, a_leaf, b_leaf):
        return jnp.where(
            flag.reshape((-1,) + (1,) * (a_leaf.ndim - 1)), a_leaf, b_leaf
        )

    # ma's seasonal buffer is [B, 1] zeros; expand to the structured [B, m]
    # so all three Forecasts share one structure (se/hw phases are both
    # (last_valid + 1) mod m, so the select is phase-consistent)
    ma = Forecast(
        pred=ma.pred,
        scale=ma.scale,
        level=ma.level,
        trend=ma.trend,
        season=jnp.zeros_like(hw.season),
        season_phase=hw.season_phase,
    )
    structured = jax.tree_util.tree_map(partial(sel, prefer_se), se, hw)
    return jax.tree_util.tree_map(partial(sel, use_struct), structured, ma)


def _phase_counts(mask: jax.Array, m_len: int, dtype) -> jax.Array:
    """Valid observations per phase, [B, m] — the k the phase-means fit
    pools over AND the z-gate in the auto screen tests against (one
    definition so the two can never desynchronize)."""
    b, t_len = mask.shape
    n_seasons = -(-t_len // m_len)
    pad = n_seasons * m_len - t_len
    mm = mask.astype(dtype)
    return jnp.sum(
        jnp.pad(mm, ((0, 0), (0, pad))).reshape(b, n_seasons, m_len), axis=1
    )


@partial(jax.jit, static_argnames=("season_length",))
def fit_phase_means(
    values: jax.Array, mask: jax.Array, season_length: int = 1440
) -> Forecast:
    """Pooled per-phase means + linear trend — the long-season workhorse.

    For daily cycles (m=1440 at the 60 s step) the 7-day window holds
    only ~7 observations per phase; Holt-Winters burns a 10,080-step
    sequential scan to produce 7-sample-noisy per-phase state, and a
    low-order Fourier basis cannot represent SHARP cycle features (a
    cron job's minute-wide daily spike). This model is the TPU-native
    answer: detrend with a masked linear fit, then pool each phase's
    residuals across seasons — season[p] = mean of detrended values at
    absolute index ≡ p (mod m). Everything is a parallel reduction
    (reshape to [B, seasons, m], masked mean over the seasons axis) —
    no sequential chain at all — and the cycle shape is unconstrained.

    The residual scale uses leave-one-out corrected residuals: with k
    observations per phase, the in-sample residual against a mean that
    INCLUDES the point shrinks by (k-1)/k, so r_loo = r * k/(k-1) —
    at k=7 an uncorrected band would be ~8% too tight. Points at phases
    observed exactly ONCE carry an identically-zero residual (the phase
    mean IS the point) and are EXCLUDED from the scale reduction — on
    gappy histories they would deflate the band below the true noise.

    Same identifiability rule as every seasonal fit: under two full
    cycles (static batch length or per-series valid count) the series
    keeps the global-mean model.
    """
    m_len = int(season_length)
    b, t_len = values.shape
    dtype = values.dtype
    if t_len < 2 * m_len:
        return moving_average_all(values, mask)

    # Backfit the masked linear trend and the pooled phase means jointly.
    # Time is NOT orthogonal to the phase dummies (the mean time of phase
    # p's occurrences grows linearly in p), so a single detrend-then-pool
    # pass leaves cycle leakage in the slope — on a pure 20-amplitude
    # daily sine the one-shot slope drifts the level by ~2.7 and inflates
    # the band ~2x (round-4 regression find). Alternating the two LS fits
    # contracts that leakage by ~(m/T)^2 per iteration (1/49 at 7 daily
    # cycles), so 3 iterations are exact to float precision; everything
    # stays a parallel reduction. Normalized time keeps the Gram terms
    # TPU bf16-matmul-safe.
    tn = (jnp.arange(t_len, dtype=dtype) / t_len)[None, :]  # [1, T]
    mm = mask.astype(dtype)
    n = jnp.maximum(jnp.sum(mm, axis=-1), 1.0)
    st = jnp.sum(tn * mm, axis=-1)
    stt = jnp.sum(tn * tn * mm, axis=-1)
    denom = stt - st * st / n
    n_seasons = -(-t_len // m_len)
    pad = n_seasons * m_len - t_len
    k = _phase_counts(mask, m_len, dtype)  # [B, m] observations per phase
    phase_idx = jnp.arange(t_len) % m_len
    season = jnp.zeros((b, m_len), dtype)
    for _ in range(3):
        y = values - jnp.take(season, phase_idx, axis=1)
        sx = jnp.sum(y * mm, axis=-1)
        stx = jnp.sum(tn * y * mm, axis=-1)
        slope_n = jnp.where(
            denom > 1e-12, (stx - st * sx / n) / jnp.maximum(denom, 1e-12), 0.0
        )
        intercept = sx / n - slope_n * st / n
        detrended = values - (intercept[:, None] + slope_n[:, None] * tn)
        dv = jnp.pad(detrended * mm, ((0, 0), (0, pad))).reshape(
            b, n_seasons, m_len
        )
        season = jnp.where(k > 0, jnp.sum(dv, axis=1) / jnp.maximum(k, 1.0), 0.0)

    pred = (
        intercept[:, None]
        + slope_n[:, None] * tn
        + jnp.take(season, phase_idx, axis=1)
    )
    # leave-one-out residuals: k/(k-1) per the point's own phase count;
    # k=1 points are zero-information (their residual is exactly 0) and
    # drop out of the scale estimate entirely
    k_at = jnp.take(k, phase_idx, axis=1)  # [B, T]
    loo = k_at / jnp.maximum(k_at - 1.0, 1.0)
    resid = (values - pred) * loo
    scale_mask = mask & (k_at > 1.5)
    scale = masked_std(resid, scale_mask, ddof=0)
    # pathological gap patterns can leave NO multiply-observed phase;
    # an empty scale estimate (0) would mean a zero-width band — fall
    # back to the plain residual std rather than flag everything
    scale = jnp.where(
        jnp.sum(scale_mask, axis=-1) > 0,
        scale,
        masked_std(values - pred, mask, ddof=0),
    )

    last_valid = jnp.max(jnp.where(mask, jnp.arange(t_len)[None, :], -1), axis=-1)
    lv = last_valid.astype(dtype)
    fc = Forecast(
        pred=pred,
        scale=scale,
        level=intercept + slope_n * lv / t_len,
        trend=slope_n / t_len,
        season=season,
        season_phase=((last_valid + 1) % m_len).astype(jnp.int32),
    )
    return _guard_unidentifiable(fc, values, mask, m_len)


def hw_continue(
    fc: Forecast,
    values: jax.Array,
    mask: jax.Array,
    season_length: int = 24,
    alpha: float = 0.3,
    beta: float = 0.05,
    gamma: float = 0.1,
) -> tuple[jax.Array, Forecast]:
    """Continue a fitted Holt-Winters recurrence over new points, causally.

    pred[:, t] is the one-step-ahead forecast made from state updated
    through values[:, :t] — the prediction never sees the point it scores,
    so residuals are contamination-free anomaly evidence (unlike
    autoencoder reconstruction, which can copy an in-window anomaly).
    Starts from `fc`'s terminal (level, trend, season, phase); masked
    steps carry state through but still advance the phase (gaps keep
    their place in the cycle). Returns (pred [B, T], updated Forecast).

    T here is a current window (tens of points), so a plain per-step scan
    is cheap; the heavy 7-day fit stays in `fit_holt_winters`/`holt_winters`.
    """
    m_len = int(season_length)
    b, t_len = values.shape
    dtype = values.dtype
    alpha = jnp.asarray(alpha, dtype)
    beta = jnp.asarray(beta, dtype)
    gamma = jnp.asarray(gamma, dtype)
    season = fc.season
    if season.shape[-1] != m_len:  # non-seasonal fit: zero offsets
        season = jnp.zeros((b, m_len), dtype)

    rows = jnp.arange(b)

    def step(carry, xs):
        level, trend, season, phase = carry
        x, m = xs
        # per-series dynamic phase: one gathered element + one scattered
        # write per step (O(B), not an O(B*m) one-hot — the seasonal
        # buffer is [B, 1440] for daily cycles)
        s_t = jnp.take_along_axis(season, phase[:, None], axis=1)[:, 0]  # [B]
        pred = level + trend + s_t
        new_level = alpha * (x - s_t) + (1.0 - alpha) * (level + trend)
        new_trend = beta * (new_level - level) + (1.0 - beta) * trend
        new_s = gamma * (x - new_level) + (1.0 - gamma) * s_t
        season_out = season.at[rows, phase].set(jnp.where(m, new_s, s_t))
        level_out = jnp.where(m, new_level, level)
        trend_out = jnp.where(m, new_trend, trend)
        return (level_out, trend_out, season_out, (phase + 1) % m_len), pred

    init = (fc.level, fc.trend, season, fc.season_phase)
    (level, trend, season, phase), preds = jax.lax.scan(
        step, init, (values.T, mask.T)
    )
    out = Forecast(
        pred=preds.T,
        scale=fc.scale,
        level=level,
        trend=trend,
        season=season,
        season_phase=phase,
    )
    return preds.T, out


_HW_GRID = (
    (0.1, 0.01, 0.05),
    (0.1, 0.05, 0.1),
    (0.3, 0.05, 0.1),
    (0.3, 0.1, 0.2),
    (0.5, 0.1, 0.1),
    (0.5, 0.05, 0.3),
    (0.7, 0.1, 0.1),
    (0.8, 0.2, 0.2),
)


@partial(jax.jit, static_argnames=("season_length",))
def fit_holt_winters(
    values: jax.Array, mask: jax.Array, season_length: int = 24
) -> Forecast:
    """Per-series fitted Holt-Winters: vectorized grid search over smoothing
    parameters (SURVEY.md section 7 "hard parts" (c)) — the whole grid runs as
    one vmapped program; each series independently picks its SSE-minimizing
    (alpha, beta, gamma).

    Histories shorter than two full seasons are seasonally unidentifiable:
    every grid point memorizes the single partial cycle (the seasonal
    state is initialized from those very residuals, so in-sample SSE ~ 0
    and the fitted band degenerates to ~zero width), while the unfilled
    seasonal slots zero out the horizon. Such SERIES get the global-mean
    model instead — a static early-out when the whole batch is short,
    plus a per-series select (`_guard_unidentifiable`) because bucket
    padding can carry a short real history inside a long batch.
    """
    if values.shape[1] < 2 * int(season_length):
        return moving_average_all(values, mask)
    grid = jnp.asarray(_HW_GRID, dtype=values.dtype)  # [G,3]

    def run(params):
        a, bta, g = params[0], params[1], params[2]
        fc = holt_winters(values, mask, season_length, a, bta, g)
        resid = (values - fc.pred) * mask
        sse = jnp.sum(resid * resid, axis=-1)  # [B]
        return fc, sse

    fcs, sses = jax.vmap(run)(grid)  # Forecast with leading [G], sse [G,B]
    best = jnp.argmin(sses, axis=0)  # [B]

    def pick(leaf):
        # leaf: [G, B, ...] -> [B, ...] selecting per-series best grid point
        moved = jnp.moveaxis(leaf, 0, 1)  # [B, G, ...]
        idx = best.reshape((-1,) + (1,) * (moved.ndim - 1))
        return jnp.take_along_axis(moved, idx, axis=1).squeeze(1)

    fc = jax.tree_util.tree_map(pick, fcs)
    return _guard_unidentifiable(fc, values, mask, int(season_length))
