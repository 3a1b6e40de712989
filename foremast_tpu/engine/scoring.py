"""The batched health-judgment engine — the reference brain's hot loop,
re-centered as one jitted TPU program.

Reference semantics being reproduced (`foremast-brain/README.md:5-11`,
`docs/guides/design.md:31-33`):
  1. compute the historical model from the 7-day window;
  2. for canary strategies, run pairwise same-distribution tests between
     baseline and current (Mann-Whitney / Wilcoxon / Kruskal / Friedman,
     combinable via ML_PAIRWISE_ALGORITHM);
  3. if the distributions differ, *lower the threshold*;
  4. threshold-based anomaly detection of current points against the
     historical model's bounds (per-metric-type threshold/bound matrix,
     `foremast-brain.yaml:26-73`);
  5. fail fast: any anomaly -> unhealthy (`design.md:43`).

TPU-first re-design: instead of one job at a time on a CPU sliver, the
whole (service x metric) population is one `[B, T]` batch; every step above
is a masked array op, and the entire judgment is a single `jax.jit`
program. Ragged windows are validity masks; per-metric-type config rows are
gathered into dense `[B]` operand vectors host-side (config.AnomalyConfig
.gather); strategy/bound/algorithm switches are `jnp.where` selects, not
Python branches.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from foremast_tpu.config import (
    PAIRWISE_ALL,
    PAIRWISE_ANY,
    PAIRWISE_FRIEDMAN,
    PAIRWISE_KRUSKAL,
    PAIRWISE_MANN_WHITE,
    PAIRWISE_WILCOXON,
)

# Engine-internal selector (NOT a config choice): compile the judgment
# WITHOUT the pairwise rank tests. Only valid when the caller proves the
# baseline is absent — see pairwise_decision.
PAIRWISE_NONE = "NONE"
from foremast_tpu.ops import kernels
from foremast_tpu.ops.anomaly import compute_bounds, detect_anomalies
from foremast_tpu.ops.forecasters import (
    Forecast,
    double_exponential,
    ewma,
    fit_auto_univariate,
    fit_holt_winters,
    fit_phase_means,
    horizon,
    moving_average,
    moving_average_all,
)
from foremast_tpu.ops.ranks import (
    friedman_chi_square,
    kruskal_wallis,
    mann_whitney_u,
    wilcoxon_signed_rank,
)
from foremast_tpu.ops.windows import MetricWindows

# Verdict codes (map onto the ES status machine, converter.go:13-26:
# HEALTHY -> completed_health, UNHEALTHY -> completed_unhealth,
# UNKNOWN -> completed_unknown).
HEALTHY = 0
UNHEALTHY = 1
UNKNOWN = 2

# The model registry — the reference's "AI_MODEL" table lives in
# `src/models/modelclass.py` of the external brain repo
# (`foremast-brain/README.md:22`); deployed default is `moving_average_all`
# (`foremast-brain.yaml:24-25`). Each entry: (values, mask) -> Forecast.
AI_MODEL = {
    "moving_average_all": moving_average_all,
    "moving_average": moving_average,
    "ewma": ewma,
    "exponential_smoothing": ewma,
    "double_exponential_smoothing": double_exponential,
    "holtwinters": fit_holt_winters,
    "holt_winters": fit_holt_winters,
    # pooled per-phase means + linear trend: the long-season (daily)
    # workhorse — parallel reductions, representation-free cycle shape
    "phase_means": fit_phase_means,
    # structure-screened per-series selection (MA vs structured fits):
    # the recommended default where metric shapes are unknown
    "auto_univariate": fit_auto_univariate,
}


def register_model(name: str, fit_fn) -> None:
    """Extend the registry (used by models/ for seasonal + learned models)."""
    AI_MODEL[name] = fit_fn


# Registry entries that take a season/period dimension, with the keyword
# each expects — the engine threads one configured value (ML_SEASON_STEPS,
# config.BrainConfig.season_steps) through all of them.
_SEASON_KWARG = {
    "holtwinters": "season_length",
    "holt_winters": "season_length",
    "phase_means": "season_length",
    "auto_univariate": "season_length",
    "seasonal": "period",
    "prophet": "period",
}


def _fit_model(algorithm: str, values, mask, season_length: int):
    fit = AI_MODEL.get(algorithm)
    if fit is None:
        # models/ registers its detectors (seasonal/prophet/...) on import;
        # resolve lazily so the registry works without callers importing it
        import foremast_tpu.models  # noqa: F401

        fit = AI_MODEL[algorithm]
    kw = _SEASON_KWARG.get(algorithm)
    return fit(values, mask, **({kw: season_length} if kw else {}))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ScoreBatch:
    """One fixed-shape batch of scoring work.

    historical: [B, Th] 7-day model window (60 s step, ~10,080 pts max)
    current:    [B, Tc] the window under judgment
    baseline:   [B, Tc] pre-deploy window (mask all-False when absent —
                rollingUpdate strategy has no baseline, metricsquery.go:111-116)
    threshold/bound/min_lower_bound: [B] per-window config vectors
    min_points: [B] minimum historical points to measure at all
    """

    historical: MetricWindows
    current: MetricWindows
    baseline: MetricWindows
    threshold: jax.Array
    bound: jax.Array
    min_lower_bound: jax.Array
    min_points: jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ScoreResult:
    """Batched judgment output.

    verdict:  [B] int32 (0 healthy / 1 unhealthy / 2 unknown)
    anomalies:[B, Tc] bool — which current points breached bounds
    upper/lower: [B, Tc] the model band over the current window (published
                 as foremastbrain:*_{upper,lower} gauges)
    p_value:  [B] combined pairwise p (1.0 when no baseline)
    dist_differs: [B] bool — pairwise tests rejected same-distribution
    """

    verdict: jax.Array
    anomalies: jax.Array
    upper: jax.Array
    lower: jax.Array
    p_value: jax.Array
    dist_differs: jax.Array


def pairwise_decision(
    current: MetricWindows,
    baseline: MetricWindows,
    algorithm: str,
    p_threshold: float,
    min_mw: int,
    min_wilcoxon: int,
    min_kruskal: int,
    min_friedman: int = 20,
) -> tuple[jax.Array, jax.Array]:
    """Combined same-distribution decision, [B] (p_combined, differs).

    ALL = every applicable test must reject to call it different;
    ANY = one rejection suffices (`foremast-brain/README.md:34`). Tests
    whose min-points gate fails are inconclusive (p=1, not counted).

    `PAIRWISE_NONE` is the compile-time skip for callers that can PROVE
    the baseline is absent (the worker's columnar fast path compiles it
    for its baseline-LESS bucket; the canary bucket — baseline-carrying
    docs, ISSUE 14 — compiles the configured algorithm with the real
    [B, Tc] baseline buffer instead): an empty baseline gates every
    test off anyway — the result is the (p=1, differs=False) constant —
    but the rank tests' comparison matrices still execute inside the
    program. At fleet batch sizes those dominate the warm judgment's
    memory traffic, so the skip is a large win with byte-identical
    outputs. `algorithm` is static in every jit entry point, so this is
    a Python branch, not a device select.
    """
    x, xm = current.values, current.mask
    if algorithm == PAIRWISE_NONE:
        b = x.shape[0]
        return jnp.ones(b, x.dtype), jnp.zeros(b, bool)
    y, ym = baseline.values, baseline.mask
    _, p_mw, ok_mw = mann_whitney_u(x, xm, y, ym, min_points=min_mw)
    _, p_wx, ok_wx = wilcoxon_signed_rank(x, xm, y, ym, min_points=min_wilcoxon)
    _, p_kw, ok_kw = kruskal_wallis(x, xm, y, ym, min_points=min_kruskal)
    _, p_fr, ok_fr = friedman_chi_square(x, xm, y, ym, min_points=min_friedman)

    rej_mw = ok_mw & (p_mw < p_threshold)
    rej_wx = ok_wx & (p_wx < p_threshold)
    rej_kw = ok_kw & (p_kw < p_threshold)
    rej_fr = ok_fr & (p_fr < p_threshold)

    if algorithm == PAIRWISE_MANN_WHITE:
        differs, p = rej_mw, p_mw
    elif algorithm == PAIRWISE_WILCOXON:
        differs, p = rej_wx, p_wx
    elif algorithm == PAIRWISE_KRUSKAL:
        differs, p = rej_kw, p_kw
    elif algorithm == PAIRWISE_FRIEDMAN:
        differs, p = rej_fr, p_fr
    elif algorithm == PAIRWISE_ANY:
        differs = rej_mw | rej_wx | rej_kw | rej_fr
        p = jnp.minimum(
            jnp.minimum(jnp.minimum(p_mw, p_wx), p_kw), p_fr
        )
    elif algorithm == PAIRWISE_ALL:
        any_ok = ok_mw | ok_wx | ok_kw | ok_fr
        all_rej = (
            (rej_mw | ~ok_mw)
            & (rej_wx | ~ok_wx)
            & (rej_kw | ~ok_kw)
            & (rej_fr | ~ok_fr)
        )
        differs = any_ok & all_rej
        # max over *applicable* tests only: gated-out tests have p forced to
        # 1.0 and would otherwise mask a rejection in the published p
        p = jnp.maximum(
            jnp.maximum(
                jnp.where(ok_mw, p_mw, 0.0), jnp.where(ok_wx, p_wx, 0.0)
            ),
            jnp.maximum(
                jnp.where(ok_kw, p_kw, 0.0), jnp.where(ok_fr, p_fr, 0.0)
            ),
        )
        p = jnp.where(any_ok, p, 1.0)
    else:  # pragma: no cover - config validates
        raise ValueError(f"unknown pairwise algorithm {algorithm!r}")
    return p, differs


# jitted form of pairwise_decision for callers outside an enclosing jit
# (the multivariate judge runs it stand-alone per joint-job batch)
pairwise = partial(
    jax.jit,
    static_argnames=(
        "algorithm",
        "p_threshold",
        "min_mw",
        "min_wilcoxon",
        "min_kruskal",
        "min_friedman",
    ),
)(pairwise_decision)


# Threshold multiplier applied when baseline and current distributions
# differ ("lower the threshold", design.md:33): tighter bounds => more
# sensitive detection during a suspicious canary.
DIFF_THRESHOLD_FACTOR = 0.5


def tile_season(s: np.ndarray, m: int) -> np.ndarray:
    """Tile a host-side season buffer's last axis from length l to m.

    Exact whenever l | m: the tiled buffer satisfies tiled[i] = s[i mod l],
    which commutes with every (phase + k) mod m lookup downstream — so
    non-seasonal [..., 1] zero buffers (and m=1 Holt fits) stack next to
    full-season ones in a single batch. Shared by the univariate fit-cache
    scorer and the multivariate MVN scorer."""
    ell = s.shape[-1]
    if ell == m:
        return s
    assert m % ell == 0, f"incompatible season lengths {ell} vs {m}"
    return np.tile(s, (1,) * (s.ndim - 1) + (m // ell,))


# Trend extrapolation across a hist->cur gap is capped at one day of
# steps (60 s step): a pathologically stale fit + huge gap must not run a
# linear trend off to infinity. Deliberately independent of the season
# length — non-seasonal models carry a [B, 1] season buffer, and a cap of
# 10*m would collapse to 10 steps for exactly the trended models that
# need the advance. Shared with the residual-MVN host path.
GAP_TREND_CAP_STEPS = 1440


def _advance_gap(fc: Forecast, gap_steps: jax.Array | None) -> Forecast:
    """Advance terminal forecaster state across the real hist->cur gap.

    The fitted phase assumes the scored window starts one step after the
    history's last point; a drifted re-check tick (the fit-cache headline
    path) or a lagged fetch starts later. The seasonal phase advances by
    the TRUE gap mod m (clamping would corrupt the phase — 10*m ≡ 0);
    only the trend extrapolation is bounded against runaway level drift
    (GAP_TREND_CAP_STEPS), mirroring the residual-MVN path
    (engine/kinds/lstm.py, `LstmKind._judge_group`). Trendless, seasonless models (the
    deployed moving_average_all default) are bit-for-bit unaffected."""
    if gap_steps is None:
        return fc
    m = fc.season.shape[-1]
    gap = gap_steps.astype(jnp.int32)
    return dataclasses.replace(
        fc,
        season_phase=((fc.season_phase + gap) % m).astype(jnp.int32),
        level=fc.level
        + fc.trend
        * jnp.minimum(gap, GAP_TREND_CAP_STEPS).astype(fc.level.dtype),
    )


_STATIC = (
    "algorithm",
    "season_length",
    "pairwise_algorithm",
    "p_threshold",
    "min_mw",
    "min_wilcoxon",
    "min_kruskal",
    "min_friedman",
)


def _judgment_tail(
    batch: ScoreBatch,
    pred: jax.Array,
    scale: jax.Array,
    n_hist: jax.Array,
    pairwise_algorithm: str,
    p_threshold: float,
    min_mw: int,
    min_wilcoxon: int,
    min_kruskal: int,
    min_friedman: int = 20,
) -> ScoreResult:
    """Everything after the model fit: pairwise -> threshold lowering ->
    bounds -> flags -> measurability gate -> verdict. Shared by the XLA
    program and the context-parallel path (parallel/seqparallel.py) so the
    judgment semantics can never diverge."""
    cur = batch.current
    p, differs = pairwise_decision(
        cur,
        batch.baseline,
        pairwise_algorithm,
        p_threshold,
        min_mw,
        min_wilcoxon,
        min_kruskal,
        min_friedman,
    )
    eff_threshold = jnp.where(
        differs, batch.threshold * DIFF_THRESHOLD_FACTOR, batch.threshold
    )
    upper, lower = compute_bounds(pred, scale, eff_threshold, batch.min_lower_bound)
    anomalies = detect_anomalies(cur.values, cur.mask, upper, lower, batch.bound)
    n_cur = cur.count()
    measurable = (n_hist >= batch.min_points) & (n_cur > 0)
    any_anom = jnp.any(anomalies, axis=-1)
    verdict = jnp.where(
        measurable,
        jnp.where(any_anom, UNHEALTHY, HEALTHY),
        UNKNOWN,
    ).astype(jnp.int32)
    # anomalies only count when measurable (unknown windows report none)
    anomalies = anomalies & measurable[:, None]
    return ScoreResult(
        verdict=verdict,
        anomalies=anomalies,
        upper=upper,
        lower=lower,
        p_value=p,
        dist_differs=differs,
    )


# jitted form for callers outside an enclosing jit (the context-parallel
# path); static args match the dispatcher's
judgment_tail = partial(
    jax.jit,
    static_argnames=(
        "pairwise_algorithm",
        "p_threshold",
        "min_mw",
        "min_wilcoxon",
        "min_kruskal",
        "min_friedman",
    ),
)(_judgment_tail)


@partial(jax.jit, static_argnames=_STATIC)
def _score_xla(
    batch: ScoreBatch,
    gap_steps: jax.Array | None = None,
    algorithm: str = "moving_average_all",
    season_length: int = 24,
    pairwise_algorithm: str = PAIRWISE_ALL,
    p_threshold: float = 0.05,
    min_mw: int = 20,
    min_wilcoxon: int = 20,
    min_kruskal: int = 5,
    min_friedman: int = 20,
) -> ScoreResult:
    """The pure-XLA scoring program (partitions under GSPMD for the
    sharded path — no custom calls, so the mesh slices it freely)."""
    hist = batch.historical
    fc: Forecast = _fit_model(algorithm, hist.values, hist.mask, season_length)
    fc = _advance_gap(fc, gap_steps)
    pred = horizon(fc, batch.current.length)  # [B, Tc] forecast

    return _judgment_tail(
        batch,
        pred,
        fc.scale,
        hist.count(),
        pairwise_algorithm,
        p_threshold,
        min_mw,
        min_wilcoxon,
        min_kruskal,
        min_friedman,
    )


@partial(jax.jit, static_argnames=_STATIC)
def _score_pallas(
    batch: ScoreBatch,
    gap_steps: jax.Array | None = None,
    algorithm: str = "moving_average_all",
    season_length: int = 24,
    pairwise_algorithm: str = PAIRWISE_ALL,
    p_threshold: float = 0.05,
    min_mw: int = 20,
    min_wilcoxon: int = 20,
    min_kruskal: int = 5,
    min_friedman: int = 20,
) -> ScoreResult:
    """Fused-kernel path: pairwise stays XLA; the moving_average_all
    judgment runs as one pallas_call (ops/kernels.py)."""
    # dispatcher guarantees moving_average_all, whose forecast is the
    # global mean — trendless and seasonless, so the gap is a no-op too
    del algorithm, season_length, gap_steps
    cur = batch.current
    p, differs = pairwise_decision(
        cur,
        batch.baseline,
        pairwise_algorithm,
        p_threshold,
        min_mw,
        min_wilcoxon,
        min_kruskal,
        min_friedman,
    )
    eff_threshold = jnp.where(
        differs, batch.threshold * DIFF_THRESHOLD_FACTOR, batch.threshold
    )
    verdict, anomalies, upper, lower = kernels.ma_judgment(
        batch.historical.values,
        batch.historical.mask,
        cur.values,
        cur.mask,
        eff_threshold,
        batch.bound,
        batch.min_lower_bound,
        batch.min_points,
    )
    return ScoreResult(
        verdict=verdict,
        anomalies=anomalies,
        upper=upper,
        lower=lower,
        p_value=p,
        dist_differs=differs,
    )


@partial(jax.jit, static_argnames=("algorithm", "season_length"))
def fit_forecast(
    values: jax.Array,
    mask: jax.Array,
    algorithm: str = "moving_average_all",
    season_length: int = 24,
) -> Forecast:
    """Fit the historical model alone (no judgment) — the program behind
    the univariate fit cache: a re-check tick whose history is unchanged
    skips this and replays the cached terminal state through
    `score_from_state`."""
    return _fit_model(algorithm, values, mask, season_length)


@partial(jax.jit, static_argnames=("algorithm", "season_length"))
def fit_forecast_bf16_delta(
    anchor: jax.Array,
    delta: jax.Array,
    lens: jax.Array,
    algorithm: str = "moving_average_all",
    season_length: int = 24,
) -> Forecast:
    """`fit_forecast` from a bf16-delta upload (any algorithm).

    Values are reconstructed IN-PROGRAM — f32(anchor + delta) over the
    valid prefix, mask from `lens` — and fed to the same fit. The
    reconstruction is transient HBM; what it buys is the 2 B/point WIRE
    upload (vs 5 B/point f32 values + bool mask): fewer H2D bytes on
    the cold fleet tick. Deviation
    precision is bf16's ~3 significant digits relative to the window's
    own range — pinned for the seasonal fits by the quality gates in
    tests/test_engine.py."""
    t = delta.shape[1]
    mask = jnp.arange(t, dtype=jnp.int32)[None, :] < lens[:, None]
    values = (anchor[:, None] + delta.astype(jnp.float32)) * mask
    return _fit_model(algorithm, values, mask, season_length)


@partial(
    jax.jit,
    static_argnames=(
        "pairwise_algorithm",
        "p_threshold",
        "min_mw",
        "min_wilcoxon",
        "min_kruskal",
        "min_friedman",
    ),
)
def score_from_state(
    batch: ScoreBatch,
    level: jax.Array,
    trend: jax.Array,
    season: jax.Array,
    season_phase: jax.Array,
    scale: jax.Array,
    n_hist: jax.Array,
    gap_steps: jax.Array | None = None,
    pairwise_algorithm: str = PAIRWISE_ALL,
    p_threshold: float = 0.05,
    min_mw: int = 20,
    min_wilcoxon: int = 20,
    min_kruskal: int = 5,
    min_friedman: int = 20,
) -> ScoreResult:
    """Judgment from fitted forecaster terminal state (no history scan).

    Identical semantics to `_score_xla`: the in-sample `pred` is never
    consumed by the judgment — only `horizon` extrapolation from terminal
    (level, trend, season, phase), the residual `scale`, and the history
    point count feed `_judgment_tail` — so a cached fit reproduces the
    fresh-fit verdict bit for bit (including the `gap_steps` phase/level
    advance, applied identically in both programs)."""
    fc = Forecast(
        pred=jnp.zeros((level.shape[0], 0), level.dtype),
        scale=scale,
        level=level,
        trend=trend,
        season=season,
        season_phase=season_phase,
    )
    fc = _advance_gap(fc, gap_steps)
    pred = horizon(fc, batch.current.length)
    return _judgment_tail(
        batch,
        pred,
        scale,
        n_hist,
        pairwise_algorithm,
        p_threshold,
        min_mw,
        min_wilcoxon,
        min_kruskal,
        min_friedman,
    )


@partial(
    jax.jit,
    static_argnames=(
        "pairwise_algorithm",
        "p_threshold",
        "min_mw",
        "min_wilcoxon",
        "min_kruskal",
        "min_friedman",
    ),
)
def score_from_arena(
    batch: ScoreBatch,
    level: jax.Array,
    trend: jax.Array,
    season: jax.Array,
    season_phase: jax.Array,
    scale: jax.Array,
    n_hist: jax.Array,
    rows: jax.Array,
    gap_steps: jax.Array | None = None,
    pairwise_algorithm: str = PAIRWISE_ALL,
    p_threshold: float = 0.05,
    min_mw: int = 20,
    min_wilcoxon: int = 20,
    min_kruskal: int = 5,
    min_friedman: int = 20,
) -> ScoreResult:
    """Judgment from ARENA-resident terminal state (engine.arena).

    The batch's fitted state is assembled on device — `rows` [B] indexes
    into the arena's [capacity] state vectors / [capacity, m] season
    buffer — so a warm re-check tick ships only current windows and a
    [B] int32 index array; the gather fuses into the same program as the
    judgment tail. Semantics are exactly `score_from_state` of the
    gathered rows."""
    take = lambda a: jnp.take(a, rows, axis=0)  # noqa: E731
    return score_from_state(
        batch,
        take(level),
        take(trend),
        take(season),
        take(season_phase),
        take(scale),
        take(n_hist),
        gap_steps=gap_steps,
        pairwise_algorithm=pairwise_algorithm,
        p_threshold=p_threshold,
        min_mw=min_mw,
        min_wilcoxon=min_wilcoxon,
        min_kruskal=min_kruskal,
        min_friedman=min_friedman,
    )


@partial(
    jax.jit,
    static_argnames=(
        "mesh",
        "pairwise_algorithm",
        "p_threshold",
        "min_mw",
        "min_wilcoxon",
        "min_kruskal",
        "min_friedman",
    ),
)
def score_from_arena_sharded(
    batch: ScoreBatch,
    level: jax.Array,
    trend: jax.Array,
    season: jax.Array,
    season_phase: jax.Array,
    scale: jax.Array,
    n_hist: jax.Array,
    rows: jax.Array,
    mesh=None,
    gap_steps: jax.Array | None = None,
    pairwise_algorithm: str = PAIRWISE_ALL,
    p_threshold: float = 0.05,
    min_mw: int = 20,
    min_wilcoxon: int = 20,
    min_kruskal: int = 5,
    min_friedman: int = 20,
) -> ScoreResult:
    """`score_from_arena` against a DATA-AXIS-SHARDED arena (ISSUE 19).

    The arena's [capacity] leading axis is block-sharded over `mesh`'s
    data axis and the judge's block placement rule guarantees every
    batch position's row lives on the device holding that position, so
    `rows` [B] carries LOCAL (per-shard) indices and the gather runs as
    a shard_map — device-local by construction, zero cross-chip
    transfer on a warm tick (the replicated variant achieved the same
    by paying capacity_bytes of HBM on every device). Semantics are
    exactly `score_from_state` of the gathered rows."""
    from foremast_tpu.parallel import mesh as meshlib

    gathered = meshlib.shard_rows_take(
        (level, trend, season, season_phase, scale, n_hist), rows, mesh
    )
    return score_from_state(
        batch,
        *gathered,
        gap_steps=gap_steps,
        pairwise_algorithm=pairwise_algorithm,
        p_threshold=p_threshold,
        min_mw=min_mw,
        min_wilcoxon=min_wilcoxon,
        min_kruskal=min_kruskal,
        min_friedman=min_friedman,
    )


# -- anchor-shifted bf16-delta history storage (FOREMAST_BF16_DELTA) ---------
#
# The headline kernel is HBM-bound on the [B, 10080] f32 history read.
# Raw bf16 storage was measured and refused in
# round 3: XLA materialized the fp32 upcast AND bf16's 8-bit mantissa
# quantizes low-CV series (100 +- 0.1 has ulp 0.5). This is the principled
# variant flagged there: store each window as (f32 anchor, bf16 DELTAS
# from the anchor). Deviations keep ~3 significant digits relative to the
# window's own range (what the band width is made of), and the
# moving-average moments never reconstruct values at all —
# E[v] = anchor + E[d], Var[v] = Var[d] — so the program reads half the
# bytes with f32 accumulation. Only meaningful where the history RESIDES
# in bf16 across reads (steady-state scoring); the shipped warm worker
# path reads no history at all.


# Explicit override beats the env: pod-mode followers adopt the
# leader's broadcast value via set_bf16_delta() — a per-host skew here
# dispatches differently-shaped SPMD programs, and mutating os.environ
# after threads start is a cross-thread race.
_BF16_DELTA_OVERRIDE: bool | None = None


def set_bf16_delta(enabled: bool | None) -> None:
    """Pin the bf16-delta gate for this process (None clears the
    override back to the env default)."""
    global _BF16_DELTA_OVERRIDE
    _BF16_DELTA_OVERRIDE = enabled if enabled is None else bool(enabled)


def bf16_delta_enabled() -> bool:
    """FOREMAST_BF16_DELTA gate (default ON): anchor-shifted bf16-delta
    history handling for the moving-average family — the steady-state
    headline storage AND the worker's cold-fit upload (judge.
    _score_with_fit_cache), where history H2D is the cold-tick bound.
    Set FOREMAST_BF16_DELTA=0 for full-f32 behavior."""
    import os

    if _BF16_DELTA_OVERRIDE is not None:
        return _BF16_DELTA_OVERRIDE
    return os.environ.get("FOREMAST_BF16_DELTA", "1") == "1"


@jax.jit
def fit_ma_from_bf16_delta(anchor: jax.Array, delta: jax.Array, lens: jax.Array):
    """moving_average_all terminal state from bf16-delta history upload.

    `delta` [B, T] bf16 (anchor-shifted, left-packed: padding slots are
    exact zeros), `anchor` [B] f32, `lens` [B] int32 valid counts — the
    mask is reconstructed on device from lengths, so the upload is
    2 B/point instead of 5 B/point (f32 values + bool mask). Matches
    ops.forecasters.moving_average_all's moments up to bf16 rounding of
    the deviations (same pinned tolerance as score_bf16_delta)."""
    n = lens.astype(jnp.float32)
    s1 = jnp.sum(delta, axis=1, dtype=jnp.float32)
    d32 = delta.astype(jnp.float32)
    s2 = jnp.sum(d32 * d32, axis=1)
    nn = jnp.maximum(n, 1.0)
    mean_d = s1 / nn
    mean = jnp.where(n > 0, anchor + mean_d, 0.0)
    var = jnp.where(n > 0, jnp.maximum(s2 / nn - mean_d * mean_d, 0.0), 0.0)
    return mean, jnp.sqrt(var), lens


@jax.jit
def pack_hist_bf16_delta(values: jax.Array, mask: jax.Array):
    """[B, T] f32 history -> (anchor [B] f32, delta [B, T] bf16).

    anchor = first masked value per row (a member of the sample, so
    deltas are bounded by the window range — same conditioning argument
    as windows.masked_moments); masked slots pack as exact 0."""
    first_idx = jnp.argmax(mask, axis=-1)
    c = jnp.take_along_axis(values, first_idx[..., None], axis=-1)[..., 0]
    c = jnp.where(mask.any(axis=-1), c, 0.0)
    d = ((values - c[..., None]) * mask).astype(jnp.bfloat16)
    return c, d


def make_bf16_delta_batch(batch: ScoreBatch):
    """(slim_batch, anchor, delta) for `score_bf16_delta`.

    Pins the structural contract in one place: the slim batch carries a
    [B, 0] values buffer (no f32 history resides on device) but keeps
    the FULL [B, T] mask, which score_bf16_delta reads for the valid
    counts. Used by bench.py, the multichip dry run, and the tests."""
    import dataclasses

    anchor, delta = pack_hist_bf16_delta(
        batch.historical.values, batch.historical.mask
    )
    b = batch.historical.values.shape[0]
    slim = dataclasses.replace(
        batch,
        historical=MetricWindows(
            values=jnp.zeros((b, 0), jnp.float32),
            mask=batch.historical.mask,
            times=None,
        ),
    )
    return slim, anchor, delta


@partial(
    jax.jit,
    static_argnames=(
        "pairwise_algorithm",
        "p_threshold",
        "min_mw",
        "min_wilcoxon",
        "min_kruskal",
        "min_friedman",
    ),
)
def score_bf16_delta(
    batch: ScoreBatch,
    anchor: jax.Array,
    delta: jax.Array,
    pairwise_algorithm: str = PAIRWISE_ALL,
    p_threshold: float = 0.05,
    min_mw: int = 20,
    min_wilcoxon: int = 20,
    min_kruskal: int = 5,
    min_friedman: int = 20,
) -> ScoreResult:
    """moving_average_all judgment from bf16-delta history storage.

    `batch.historical` carries only the mask (values may be [B, 0]); the
    moments come from the bf16 deltas with f32 accumulation. Semantics
    match `_score_xla(algorithm="moving_average_all")` up to bf16
    rounding of the deviations (pinned by test + quality gate)."""
    mask = batch.historical.mask
    m = mask.astype(jnp.float32)
    n = jnp.sum(m, axis=-1)
    # deltas were packed masked (exact zeros in masked slots), so plain
    # sums ARE the masked sums; accumulate in f32 off the bf16 reads
    s1 = jnp.sum(delta, axis=-1, dtype=jnp.float32)
    d32 = delta.astype(jnp.float32)
    s2 = jnp.sum(d32 * d32, axis=-1)
    nn = jnp.maximum(n, 1.0)
    mean_d = s1 / nn
    mean = jnp.where(n > 0, anchor + mean_d, 0.0)
    var = jnp.where(n > 0, jnp.maximum(s2 / nn - mean_d * mean_d, 0.0), 0.0)
    b = mean.shape[0]
    fc = Forecast(
        pred=jnp.zeros((b, 0), jnp.float32),
        scale=jnp.sqrt(var),
        level=mean,
        trend=jnp.zeros_like(mean),
        season=jnp.zeros((b, 1), jnp.float32),
        season_phase=jnp.zeros((b,), jnp.int32),
    )
    pred = horizon(fc, batch.current.length)
    return _judgment_tail(
        batch,
        pred,
        fc.scale,
        n.astype(jnp.int32),
        pairwise_algorithm,
        p_threshold,
        min_mw,
        min_wilcoxon,
        min_kruskal,
        min_friedman,
    )


def _is_multi_device(batch: ScoreBatch) -> bool:
    """True when the batch is placed across >1 device (GSPMD path)."""
    sharding = getattr(batch.current.values, "sharding", None)
    if sharding is None:
        return False
    try:
        return len(sharding.device_set) > 1
    except Exception:  # tracers / abstract values: assume the safe path
        return True


def score(batch: ScoreBatch, **kwargs) -> ScoreResult:
    """Judge a whole batch in one compiled program (call stack 3.2 of
    SURVEY.md collapsed into array ops).

    Un-jitted dispatcher over two jitted programs so (a) the
    FOREMAST_PALLAS gate is honored at *call* time, not frozen into a
    trace cache, and (b) multi-device batches always take the XLA
    program, which GSPMD partitions freely (a pallas_call has no
    partitioning rule and would force a gather).
    """
    algorithm = kwargs.get("algorithm", "moving_average_all")
    if (
        algorithm == "moving_average_all"
        and kernels.use_pallas()
        and not _is_multi_device(batch)
    ):
        return _score_pallas(batch, **kwargs)
    return _score_xla(batch, **kwargs)
