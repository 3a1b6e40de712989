"""The plain reference of fleet kind `univariate`: a single-metric job under
`ML_ALGORITHM=auto`.

Straightforward `jax.numpy`, float32, matmuls at `highest` precision, no
arena, no cache, no bucketing, no bf16 wire format. It imports nothing of
`foremast_tpu` and takes nothing the program has made: its inputs are the
seeded histories and the windows the harness sent, its models it fits
itself. The semantics follow the reference brain's model zoo as the
program documents it (`ops/forecasters.py:fit_auto_univariate`,
`models/seasonal.py`, `engine/scoring.py`):

  * three candidate models of the history: the global mean; the
    long-season adaptive candidate, pooled per-phase means beside a linear
    trend (three alternations of the two least-squares fits), whose scale
    is the leave-one-out residual's; and the Prophet substitute, a
    piecewise-linear trend (8 hinges over the first 90%) plus 3 Fourier
    harmonics of the season, by ridge regression;
  * the screen, scored on the warm region (index >= season): a structured
    model wins only where its squared error is under half the mean
    model's; between the two the lower error wins; a phase whose pooled
    mean clears the Bonferroni-corrected normal quantile (1e-3 over the
    season's phases) sends the series to the phase-means fit whatever the
    errors say;
  * the judgment: the chosen model's terminal state advanced over the
    history->window gap (phase by the true gap, level by at most 1,440
    steps of trend), extrapolated over the window, and the band at the
    alias's threshold and bound from the per-type matrix
    (`foremast-brain.yaml:26-73`, the configuration's `type_rules`, matched
    by substring; an alias that matches none takes the global threshold,
    upper bound); the lower bound is floored at the rule's minimum.

Only long seasons are followed (season > 64 steps: the program's small-season
candidate is a fitted Holt-Winters, which no cell uses). Histories have no
gaps: they are the seeded series.

`dtype` is the precision everything is computed in: float32 for the
reference, bfloat16 for the control that must come out as not correct.

The margin of a point is the least change of its standardized residual
that flips its flag: its distance, in residual scales, from the bound
that decides it.
"""

from __future__ import annotations

import functools
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

AUTO_SSE_RATIO = 0.5
SEASONAL_ORDER = 3
SEASONAL_KNOTS = 8
SEASONAL_RIDGE = 1e-3
GAP_TREND_CAP_STEPS = 1440
MIN_HISTORICAL_POINTS = 10
LONG_SEASON = 64
BOUND_UPPER, BOUND_LOWER, BOUND_BOTH = 1, 2, 3
_BOUNDS = {"upper": BOUND_UPPER, "lower": BOUND_LOWER, "both": BOUND_BOTH}
MODELS = ("mean", "phase_means", "seasonal")


def rule_for(alias: str, cfg: dict) -> tuple[float, int, float]:
    """(threshold, bound, min_lower_bound) of an alias: the first row of the
    per-type matrix whose type is a substring of it, else the global rule."""
    low = alias.lower()
    for mtype, r in cfg.get("type_rules", {}).items():
        if mtype.lower() in low:
            return float(r["threshold"]), _BOUNDS[r["bound"]], float(r.get("min_lower_bound", 0.0))
    return float(cfg["anomaly_threshold"]), BOUND_UPPER, 0.0


def _z_gate(m: int) -> float:
    from scipy import stats

    return float(stats.norm.ppf(1.0 - 1e-3 / m))


def _std(r):
    mu = jnp.mean(r, axis=-1, keepdims=True)
    return jnp.sqrt(jnp.mean((r - mu) ** 2, axis=-1))


def _phase_means(x, m: int):
    """Pooled per-phase means beside a linear trend -> (pred, level, trend,
    season [N, m], scale, counts [m])."""
    n, t_len = x.shape
    dt = x.dtype
    tn = (jnp.arange(t_len, dtype=jnp.float32) / t_len).astype(dt)
    phase = jnp.arange(t_len) % m
    k = jnp.zeros((m,), jnp.float32).at[phase].add(1.0).astype(dt)
    tbar = jnp.mean(tn)
    stt = jnp.sum((tn - tbar) ** 2)
    season = jnp.zeros((n, m), dt)
    for _ in range(3):
        y = x - season[:, phase]
        ybar = jnp.mean(y, axis=-1)
        slope = jnp.sum((tn - tbar)[None, :] * (y - ybar[:, None]), axis=-1) / stt
        intercept = ybar - slope * tbar
        detrended = x - (intercept[:, None] + slope[:, None] * tn[None, :])
        sums = jnp.zeros((n, m), dt).at[:, phase].add(detrended)
        season = sums / jnp.maximum(k, 1.0)[None, :]
    pred = intercept[:, None] + slope[:, None] * tn[None, :] + season[:, phase]
    k_at = k[phase]
    loo = k_at / jnp.maximum(k_at - 1.0, 1.0)
    resid = (x - pred) * loo[None, :]
    many = k_at > 1.5
    cnt = jnp.maximum(jnp.sum(many), 1).astype(dt)
    mu = jnp.sum(jnp.where(many[None, :], resid, 0.0), axis=-1) / cnt
    var = jnp.sum(jnp.where(many[None, :], (resid - mu[:, None]) ** 2, 0.0), axis=-1) / cnt
    scale = jnp.where(jnp.sum(many) > 0, jnp.sqrt(var), _std(x - pred))
    last = jnp.asarray(t_len - 1, dt)
    return pred, intercept + slope * last / t_len, slope / t_len, season, scale, k


def _design(t, period: int, t_scale: float, knots, dt):
    tt = (t.astype(jnp.float32) / t_scale).astype(dt)
    cols = [jnp.ones_like(tt), tt]
    for c in knots:
        cols.append(jnp.maximum(tt - jnp.asarray(c / t_scale, dt), 0.0))
    for h in range(1, SEASONAL_ORDER + 1):
        w = jnp.asarray(2.0 * math.pi * h / (period / t_scale), dt)
        cols += [jnp.sin(w * tt), jnp.cos(w * tt)]
    return jnp.stack(cols, axis=-1)


def _seasonal(x, m: int):
    """Piecewise-linear trend + Fourier seasonality by ridge regression ->
    (pred, level, trend, season [N, m], scale)."""
    n, t_len = x.shape
    dt = x.dtype
    hi = 0.9 * (t_len - 1)
    knots = [hi * (j + 1) / (SEASONAL_KNOTS + 1) for j in range(SEASONAL_KNOTS)]
    t_scale = float(t_len)
    d = _design(jnp.arange(t_len), m, t_scale, knots, dt)
    gram = (d.T @ d).astype(jnp.float32) + SEASONAL_RIDGE * jnp.eye(d.shape[1], dtype=jnp.float32)
    rhs = (x @ d).astype(jnp.float32)
    w = jnp.linalg.solve(gram, rhs.T).T.astype(dt)
    pred = w @ d.T
    scale = _std(x - pred)
    lv = jnp.asarray((t_len - 1) / t_scale, dt)
    level = w[:, 0] + w[:, 1] * lv
    trend = w[:, 1] / t_scale
    for j, c in enumerate(knots):
        cn = c / t_scale
        level = level + w[:, 2 + j] * jnp.maximum(lv - jnp.asarray(cn, dt), 0.0)
        trend = trend + w[:, 2 + j] * float((t_len - 1) / t_scale > cn) / t_scale
    cycle = _design(jnp.arange(m), m, t_scale, (), dt)[:, 2:]
    season = w[:, 2 + SEASONAL_KNOTS:] @ cycle.T
    return pred, level, trend, season, scale


@functools.partial(jax.jit, static_argnames=("m", "dtype"))
def _fit(hist, m: int, dtype):
    """hist [N, T] -> the chosen model's terminal state and which it is."""
    x = hist.astype(dtype)
    n, t_len = x.shape
    mean = jnp.mean(x, axis=-1)
    ma = {
        "level": mean, "trend": jnp.zeros_like(mean), "scale": _std(x),
        "season": jnp.zeros((n, 1), dtype), "phase": jnp.zeros((n,), jnp.int32),
        "model": jnp.zeros((n,), jnp.int32),
    }
    if t_len < 2 * m:
        return ma
    pm_pred, pm_level, pm_trend, pm_season, pm_scale, k = _phase_means(x, m)
    se_pred, se_level, se_trend, se_season, se_scale = _seasonal(x, m)
    warm = (jnp.arange(t_len) >= m).astype(dtype)[None, :]

    def sse(pred):
        r = (x - pred) * warm
        return jnp.sum(r * r, axis=-1)

    sse_ma, sse_pm, sse_se = sse(mean[:, None]), sse(pm_pred), sse(se_pred)
    z = jnp.abs(pm_season) * jnp.sqrt(jnp.maximum(k, 1.0))[None, :] / jnp.maximum(pm_scale, 1e-30)[:, None]
    z_gate = jnp.max(z, axis=-1) > _z_gate(m)
    use_struct = (jnp.minimum(sse_pm, sse_se) < AUTO_SSE_RATIO * sse_ma) | z_gate
    prefer_se = (sse_se <= sse_pm) & ~z_gate

    def pick(a_se, a_pm, a_ma):
        shape = (-1,) + (1,) * (a_pm.ndim - 1)
        structured = jnp.where(prefer_se.reshape(shape), a_se, a_pm)
        return jnp.where(use_struct.reshape(shape), structured, a_ma)

    return {
        "level": pick(se_level, pm_level, ma["level"]),
        "trend": pick(se_trend, pm_trend, ma["trend"]),
        "scale": pick(se_scale, pm_scale, ma["scale"]),
        "season": pick(se_season, pm_season, jnp.zeros_like(pm_season)),
        "phase": jnp.full((n,), t_len % m, jnp.int32),
        "model": jnp.where(use_struct, jnp.where(prefer_se, 2, 1), 0).astype(jnp.int32),
    }


@functools.partial(jax.jit, static_argnames=("dtype",))
def _bands(st, cur, gaps, thr, mlb, dtype):
    """The window's forecast and band: st rows [K, ...], cur [K, W] ->
    (pred, upper, lower, scale), float32."""
    x = cur.astype(dtype)
    k, w = x.shape
    m = st["season"].shape[-1]
    gap = gaps.astype(jnp.int32)
    level = st["level"] + st["trend"] * jnp.minimum(gap, GAP_TREND_CAP_STEPS).astype(dtype)
    phase = (st["phase"] + gap) % m
    steps = jnp.arange(1, w + 1).astype(dtype)
    idx = (phase[:, None] + jnp.arange(w)[None, :]) % m
    pred = level[:, None] + st["trend"][:, None] * steps[None, :] + jnp.take_along_axis(st["season"], idx, axis=-1)
    band = thr.astype(dtype)[:, None] * st["scale"][:, None]
    upper = pred + band
    lower = jnp.maximum(pred - band, mlb.astype(dtype)[:, None])
    f32 = jnp.float32
    return pred.astype(f32), upper.astype(f32), lower.astype(f32), st["scale"].astype(f32)


def flags_and_margins(cur, upper, lower, scale, bound):
    """numpy: cur/upper/lower [K, W], scale [K], bound [K] -> (flags, the
    distance of every point from the bound that decides it, in scales)."""
    s = np.maximum(scale, 1e-30)[:, None]
    up_on = np.isin(bound, (BOUND_UPPER, BOUND_BOTH))[:, None]
    lo_on = np.isin(bound, (BOUND_LOWER, BOUND_BOTH))[:, None]
    over, under = (cur > upper) & up_on, (cur < lower) & lo_on
    d_up = np.where(up_on, np.abs(cur - upper) / s, np.inf)
    d_lo = np.where(lo_on, np.abs(cur - lower) / s, np.inf)
    flags = over | under
    margins = np.where(over, d_up, np.where(under, d_lo, np.minimum(d_up, d_lo)))
    return flags, margins.astype(np.float32)


class UnivariateReference:
    def __init__(self, season: int, dtype=jnp.float32):
        self.season = int(season)
        if self.season <= LONG_SEASON:
            raise SystemExit("the univariate reference follows the long-season candidate only")
        self.dtype = dtype

    def fit(self, hist: np.ndarray, block: int = 256) -> dict:
        """hist [N, T] f32, in blocks (the last padded by repeating a row)."""
        n = hist.shape[0]
        outs = []
        with jax.default_matmul_precision("highest"):
            for i in range(0, n, block):
                take = np.minimum(np.arange(i, i + block), n - 1)
                st = _fit(jnp.asarray(hist[take]), m=self.season, dtype=self.dtype)
                outs.append(jax.tree.map(lambda a: np.asarray(a)[: min(block, n - i)], st))
        return jax.tree.map(lambda *xs: np.concatenate(xs), *outs)

    def bands(self, st: dict, idx, cur, gaps, thr, mlb):
        sub = {k: jnp.asarray(v[idx]) for k, v in st.items() if k != "model"}
        out = _bands(sub, jnp.asarray(cur), jnp.asarray(gaps, jnp.int32),
                     jnp.asarray(thr, jnp.float32), jnp.asarray(mlb, jnp.float32), dtype=self.dtype)
        return [np.asarray(a, np.float32) for a in out]


def prepare(rows: list, group: dict, cfg: dict, history, control: bool, log):
    """Fit every distinct service of `rows` -> (reference, state, row index,
    windows [K, W], gaps, threshold, bound, floor: [K] each)."""
    uids = sorted({r["uid"] for r in rows})
    at = {u: i for i, u in enumerate(uids)}
    t = time.perf_counter()
    hist = np.stack([history(u)[0] for u in uids])
    ref = UnivariateReference(cfg["season_steps"], jnp.bfloat16 if control else jnp.float32)
    st = ref.fit(hist)
    if log:
        chosen = np.bincount(st["model"], minlength=3)
        log(
            f"{group['kind']} reference fitted {len(uids)} services in "
            f"{time.perf_counter() - t:.1f} s; models chosen: "
            + ", ".join(f"{MODELS[i]} {int(c)}" for i, c in enumerate(chosen))
        )
    thr, bound, mlb = rule_for(group["aliases"][0], cfg)
    k = len(rows)
    idx = np.array([at[r["uid"]] for r in rows])
    cur = np.stack([r["sent"][0] for r in rows]).astype(np.float32)
    gaps = np.array([r["sweep"] for r in rows], np.int32)
    return (ref, st, idx, cur, gaps, np.full(k, thr, np.float32),
            np.full(k, bound, np.int32), np.full(k, mlb, np.float32))


def judge(rows: list, group: dict, cfg: dict, history, control: bool = False, log=None) -> dict:
    """-> {"flags" [K, W], "margins" [K, W]} of this group's judgments (uid,
    sweep, the window sent [1, W])."""
    ref, st, idx, cur, gaps, thr, bound, mlb = prepare(rows, group, cfg, history, control, log)
    _pred, upper, lower, scale = ref.bands(st, idx, cur, gaps, thr, mlb)
    flags, margins = flags_and_margins(cur, upper, lower, scale, bound)
    if int(cfg["history_points"]) < MIN_HISTORICAL_POINTS:
        flags[:] = False
    return {"flags": flags, "margins": margins, "models": st["model"][idx]}
