"""The plain reference of fleet kind `baseline`: the canary bucket, a
single-metric job that carries a baseline window.

The univariate judgment (`chipbench/references/univariate.py`: the same
models, screen and band) with the pairwise gate before it, as the program
documents it (`ops/ranks.py`, `engine/scoring.py:pairwise_decision`,
`foremast-brain.yaml:74-79`):

  * Mann-Whitney U (normal approximation, tie-corrected, continuity 0.5),
    Wilcoxon signed-rank (paired by position, zero differences dropped, no
    continuity), Kruskal-Wallis (chi^2, 1 dof) and the two-group Friedman
    chi-square (1 dof) of the current window against the baseline, each
    gated on its minimum of points (20 / 20 / 5 / 20); tie-averaged ranks
    by their definition (points below + (points equal + 1) / 2);
  * `ML_PAIRWISE_ALGORITHM`'s default ALL: the distributions differ where
    some test applies and every test that applies rejects at
    `ML_PAIRWISE_THRESHOLD` 0.05;
  * where they differ the alias's threshold is halved ("lower the
    threshold", design.md:33) before the band is drawn.

Straightforward `jax.numpy`, float32 (bfloat16 for the control); imports
nothing of `foremast_tpu`.

The margin of a point is its distance from the bound that decides it at the
threshold in force, as for `univariate`; where the gate's other answer
would flip the point's flag, it is at most the gate's own margin, the least
|ln(p / 0.05)| that changes the combined decision: a doc whose p-value sits
on the threshold costs nothing either way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.references import univariate

P_THRESHOLD = 0.05
MIN_MANN_WHITNEY, MIN_WILCOXON, MIN_KRUSKAL, MIN_FRIEDMAN = 20, 20, 5, 20
DIFF_THRESHOLD_FACTOR = 0.5


def _sf_normal(z):
    return 0.5 * jax.scipy.special.erfc(z / jnp.sqrt(jnp.asarray(2.0, z.dtype)))


def _sf_chi2_1(x):
    """Survival function of chi^2 with one degree of freedom."""
    return jax.scipy.special.erfc(jnp.sqrt(jnp.maximum(x, 0.0) / 2.0))


def _ranks(v):
    """Tie-averaged ranks along the last axis, and sum over tie groups of
    (t^3 - t) = sum over points of (t_i^2 - 1)."""
    less = jnp.sum(v[..., None, :] < v[..., :, None], axis=-1).astype(v.dtype)
    equal = jnp.sum(v[..., None, :] == v[..., :, None], axis=-1).astype(v.dtype)
    return less + (equal + 1.0) * 0.5, jnp.sum(equal * equal - 1.0, axis=-1)


@functools.partial(jax.jit, static_argnames=("dtype",))
def pairwise(cur, base, dtype):
    """cur [K, Nx], base [K, Ny] -> p [K, 4] and applies [K, 4] of
    (Mann-Whitney, Wilcoxon, Kruskal-Wallis, Friedman)."""
    x, y = cur.astype(dtype), base.astype(dtype)
    nx, ny = x.shape[-1], y.shape[-1]
    n = nx + ny
    ranks, tie = _ranks(jnp.concatenate([x, y], axis=-1))
    r1 = jnp.sum(ranks[..., :nx], axis=-1)
    # Mann-Whitney
    u1 = r1 - nx * (nx + 1.0) / 2.0
    var = nx * ny / 12.0 * ((n + 1.0) - tie / (n * (n - 1.0)))
    sd = jnp.sqrt(jnp.maximum(var, 0.0))
    z = jnp.maximum((jnp.abs(u1 - nx * ny / 2.0) - 0.5) / jnp.maximum(sd, 1e-30), 0.0)
    ok_mw = (sd > 0) & (min(nx, ny) >= MIN_MANN_WHITNEY)
    p_mw = jnp.clip(2.0 * _sf_normal(z), 0.0, 1.0)
    # Kruskal-Wallis
    r2 = n * (n + 1.0) * 0.5 - r1
    h = 12.0 / (n * (n + 1.0)) * (r1 * r1 / nx + r2 * r2 / ny) - 3.0 * (n + 1.0)
    corr = 1.0 - tie / (n * n * n - n)
    h = jnp.maximum(h / jnp.maximum(corr, 1e-30), 0.0)
    ok_kw = (corr > 0) & (min(nx, ny) >= MIN_KRUSKAL)
    p_kw = jnp.clip(_sf_chi2_1(h), 0.0, 1.0)
    # the paired tests pair by position
    k = min(nx, ny)
    d = x[..., :k] - y[..., :k]
    nz = d != 0.0
    big = jnp.asarray(3.0e38, dtype)
    ad = jnp.where(nz, jnp.abs(d), big)
    less = jnp.sum((ad[..., None, :] < ad[..., :, None]) & nz[..., None, :], axis=-1).astype(dtype)
    equal = jnp.sum((ad[..., None, :] == ad[..., :, None]) & nz[..., None, :], axis=-1).astype(dtype)
    rk = jnp.where(nz, less + (equal + 1.0) * 0.5, 0.0)
    tie_w = jnp.sum(jnp.where(nz, equal * equal - 1.0, 0.0), axis=-1)
    m = jnp.sum(nz, axis=-1).astype(dtype)
    w_plus = jnp.sum(jnp.where(nz & (d > 0), rk, 0.0), axis=-1)
    var_w = m * (m + 1.0) * (2.0 * m + 1.0) / 24.0 - tie_w / 48.0
    sd_w = jnp.sqrt(jnp.maximum(var_w, 0.0))
    z_w = jnp.abs(w_plus - m * (m + 1.0) / 4.0) / jnp.maximum(sd_w, 1e-30)
    ok_wx = (m > 0) & (sd_w > 0) & (k >= MIN_WILCOXON)
    p_wx = jnp.clip(2.0 * _sf_normal(z_w), 0.0, 1.0)
    # two-group Friedman: ranks within each pair are 1/2, or 1.5 each on a tie
    plus = jnp.sum(d > 0, axis=-1).astype(dtype)
    minus = jnp.sum(d < 0, axis=-1).astype(dtype)
    ties = k - plus - minus
    c1 = 2.0 * plus + minus + 1.5 * ties
    c2 = 2.0 * minus + plus + 1.5 * ties
    stat = 2.0 / k * (c1 * c1 + c2 * c2) - 9.0 * k
    c = 1.0 - ties / k
    stat = jnp.maximum(stat / jnp.maximum(c, 1e-30), 0.0)
    ok_fr = (c > 0) & (k >= MIN_FRIEDMAN)
    p_fr = jnp.clip(_sf_chi2_1(stat), 0.0, 1.0)
    applies = jnp.stack([ok_mw, ok_wx, ok_kw, ok_fr], axis=-1)
    p = jnp.stack([p_mw, p_wx, p_kw, p_fr], axis=-1).astype(jnp.float32)
    return jnp.where(applies, p, 1.0), applies


def gate(p: np.ndarray, applies: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """ALL: (differs [K], the least |ln(p / threshold)| that changes it)."""
    rejects = applies & (p < P_THRESHOLD)
    differs = applies.any(axis=1) & (rejects | ~applies).all(axis=1)
    dist = np.abs(np.log(np.maximum(p, 1e-300) / P_THRESHOLD))
    # differs -> same: the nearest rejecting test stops rejecting; same ->
    # differs: every applicable test that does not reject has to
    undo = np.where(rejects, dist, np.inf).min(axis=1)
    do = np.where(applies & ~rejects, dist, 0.0).max(axis=1)
    margin = np.where(differs, undo, np.where(applies.any(axis=1), do, np.inf))
    return differs, margin.astype(np.float32)


def judge(rows: list, group: dict, cfg: dict, history, control: bool = False, log=None) -> dict:
    """-> {"flags" [K, W], "margins" [K, W]} of this group's judgments (uid,
    sweep, the window sent [1, W], its baseline window [1, points])."""
    ref, st, idx, cur, gaps, thr, bound, mlb = univariate.prepare(
        rows, group, cfg, history, control, log
    )
    base = np.stack([r["base"][0] for r in rows]).astype(np.float32)
    p, applies = pairwise(jnp.asarray(cur), jnp.asarray(base), dtype=ref.dtype)
    differs, gate_margin = gate(np.asarray(p, np.float64), np.asarray(applies))
    out = []
    for lowered in (False, True):
        t = thr * DIFF_THRESHOLD_FACTOR if lowered else thr
        _pred, upper, lower, scale = ref.bands(st, idx, cur, gaps, t, mlb)
        out.append(univariate.flags_and_margins(cur, upper, lower, scale, bound))
    (f_full, m_full), (f_low, m_low) = out
    d = differs[:, None]
    flags = np.where(d, f_low, f_full)
    margins = np.where(d, m_low, m_full)
    # where the gate's other answer would flip the flag, it bounds the margin
    margins = np.where(f_low != f_full, np.minimum(margins, gate_margin[:, None]), margins)
    if log:
        log(f"baseline reference: the pairwise gate fired on {int(differs.sum())} of {len(rows)} judgments")
    return {"flags": flags, "margins": margins.astype(np.float32), "differs": differs, "p": p}
