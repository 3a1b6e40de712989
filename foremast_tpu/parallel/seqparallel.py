"""Sequence/context parallelism for long metric windows.

The reference's longest series is the 7-day history (~10k points) — one
chip's worth. But the framework treats long-context as first-class: when
windows outgrow a single device's HBM (year-long histories, 1 s steps, or
very wide batches), the *time* axis itself is sharded over the mesh and
recurrences run as distributed scans.

Two primitives, both built on `shard_map` + XLA collectives over ICI:

  * `sharded_linear_scan` — the EWMA/exponential-smoothing family is the
    linear recurrence l_t = a_t l_{t-1} + b_t, whose composition law
    (a1,b1)o(a2,b2) = (a1 a2, a2 b1 + b2) is associative. Each device
    scans its local time block, `all_gather`s the per-block composed
    elements (2 scalars per series per device — tiny on ICI), computes its
    exclusive prefix, and applies it locally. One collective total.
  * `sharded_masked_moments` — global masked mean/var across a time-sharded
    window via `psum` (the partial-sum trick), for bounds computed against
    statistics of a sequence no single chip holds.
  * `sharded_phase_means` — the daily-seasonal (phase-pooled) fit over a
    time-sharded window: trend moments, per-phase sums/counts, and the
    leave-one-out residual scale are all per-block partial sums, so the
    whole long-season fit (including the trend<->season backfit rounds)
    costs a handful of batched psums plus one pmax.

This is the all-to-all/ring-style sequence-parallel design of the scaling
playbook applied to scans rather than attention: the sequence axis maps to
mesh axis `model`, batch stays on `data`.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from foremast_tpu.ops.forecasters import _linrec_assoc as _compose
from foremast_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS


def sharded_linear_scan(a: jax.Array, b: jax.Array, mesh: Mesh) -> jax.Array:
    """Distributed l_t = a_t * l_{t-1} + b_t (l_0 = 0) with time sharded.

    a, b: [B, T] with B sharded over `data` and T sharded over `model`.
    Returns l: [B, T] with the same sharding. The cross-device step moves
    2 scalars per (series, device) over ICI.
    """

    def local(a_blk, b_blk):
        # local inclusive scan of composed elements
        ca, cb = jax.lax.associative_scan(_compose, (a_blk, b_blk), axis=-1)
        # per-block total = last composed element
        tot_a = ca[..., -1:]
        tot_b = cb[..., -1:]
        # gather all block totals along the sequence axis group
        gat_a = jax.lax.all_gather(tot_a, MODEL_AXIS, axis=-1, tiled=True)  # [B, D]
        gat_b = jax.lax.all_gather(tot_b, MODEL_AXIS, axis=-1, tiled=True)
        idx = jax.lax.axis_index(MODEL_AXIS)
        # exclusive prefix over preceding blocks: compose blocks < idx
        d = gat_a.shape[-1]
        mask = jnp.arange(d) < idx  # [D]
        # composing with identity (1, 0) where masked out
        pa = jnp.where(mask, gat_a, 1.0)
        pb = jnp.where(mask, gat_b, 0.0)

        def fold(carry, i):
            ca_, cb_ = carry
            return _compose((ca_, cb_), (pa[..., i], pb[..., i])), None

        (pre_a, pre_b), _ = jax.lax.scan(
            fold,
            (jnp.ones_like(tot_a[..., 0]), jnp.zeros_like(tot_b[..., 0])),
            jnp.arange(d),
        )
        # apply prefix state l_prev = pre_b (l_0 = 0): l = ca * l_prev + cb
        return ca * pre_b[..., None] + cb

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, MODEL_AXIS), P(DATA_AXIS, MODEL_AXIS)),
        out_specs=P(DATA_AXIS, MODEL_AXIS),
        check_vma=False,
    )
    return fn(a, b)


def sharded_ewma(
    values: jax.Array, mask: jax.Array, alpha: float, mesh: Mesh
) -> jax.Array:
    """EWMA levels over a time-sharded window (mirrors ops.ewma_levels).

    values/mask: [B, T] sharded (data, model). First-valid-point
    initialization needs the global running count of valid points, computed
    as a second distributed linear scan (a=1, b=mask).
    """
    # global prefix count of valid points, inclusive
    cnt = sharded_linear_scan(
        jnp.ones_like(values), mask.astype(values.dtype), mesh
    )
    is_first = mask & (cnt == 1.0)
    a_eff = jnp.where(mask, jnp.asarray(alpha, values.dtype), 0.0)
    a_eff = jnp.where(is_first, 1.0, a_eff)
    return sharded_linear_scan(1.0 - a_eff, a_eff * values, mesh)


def sharded_masked_stats(
    values: jax.Array, mask: jax.Array, mesh: Mesh
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Global masked (count, mean, var) over a time-sharded window ->
    three [B] arrays replicated along `model`. One psum over ICI."""

    def local(v, m):
        mf = m.astype(v.dtype)
        s1 = jax.lax.psum(jnp.sum(v * mf, axis=-1), MODEL_AXIS)
        s2 = jax.lax.psum(jnp.sum(v * v * mf, axis=-1), MODEL_AXIS)
        n = jax.lax.psum(jnp.sum(mf, axis=-1), MODEL_AXIS)
        mean = jnp.where(n > 0, s1 / jnp.maximum(n, 1.0), 0.0)
        var = jnp.where(n > 0, s2 / jnp.maximum(n, 1.0) - mean * mean, 0.0)
        return n, mean, jnp.maximum(var, 0.0)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, MODEL_AXIS), P(DATA_AXIS, MODEL_AXIS)),
        out_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        check_vma=False,
    )
    return fn(values, mask)


def sharded_masked_moments(
    values: jax.Array, mask: jax.Array, mesh: Mesh
) -> tuple[jax.Array, jax.Array]:
    """(mean, var) view of `sharded_masked_stats` (kept for callers that
    don't need the count)."""
    _, mean, var = sharded_masked_stats(values, mask, mesh)
    return mean, var


def sharded_phase_means(
    values: jax.Array,
    mask: jax.Array,
    season_length: int,
    mesh: Mesh,
) -> tuple[
    jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array
]:
    """Daily-seasonal (phase-pooled) fit over a TIME-SHARDED window —
    context parallelism for the long-season workhorse
    (`ops.forecasters.fit_phase_means`).

    values/mask: [B, T] with B over `data` and T over `model`. For
    year-long 60 s histories (~525k points) no single chip need hold the
    window: every statistic the fit needs — the masked linear trend, the
    per-phase pooled sums/counts, and the centered leave-one-out residual
    scale — is a per-block partial sum, so the whole fit costs eight
    batched (pytree) psums plus one pmax over ICI. Phase alignment
    requires the local block length to be a multiple of `season_length`
    (asserted; pad the window host-side), which makes every block's phase
    grid start at offset ≡ 0 (mod m).

    Semantics match `fit_phase_means` including the per-series 2-cycle
    identifiability rule: series with fewer than two cycles of VALID
    points keep the global-mean model (zero season/trend, historical
    mean/std as level/scale).

    Returns (season [B, m], level [B], trend [B], scale [B],
    season_phase [B] int32, n_hist [B] int32), replicated along `model`
    — the full terminal state `horizon` / `engine.scoring.score_from_state`
    consume.
    """
    m_len = int(season_length)
    n_model = mesh.shape[MODEL_AXIS]
    t_total = values.shape[1]
    t_loc = t_total // n_model
    assert t_total % n_model == 0, (
        f"model-axis size ({n_model}) must divide the time axis ({t_total})"
    )
    assert t_loc % m_len == 0, (
        f"local block ({t_loc}) must be a multiple of season_length "
        f"({m_len}) so every block is phase-aligned — pad the window"
    )

    def local(v, mk):
        b, t_blk = v.shape
        idx = jax.lax.axis_index(MODEL_AXIS)
        gidx = idx * t_blk + jnp.arange(t_blk)  # global time index, int
        tn = gidx.astype(v.dtype) / t_total  # normalized (bf16-matmul-safe)
        mf = mk.astype(v.dtype)
        phase = gidx % m_len

        # psum 1 (batched): mask-only trend moments, raw value moments
        # (identifiability guard), and per-phase counts — the block is
        # phase-aligned, so a local reshape gives exact phase columns
        n, st, stt, sx0, sxx, k = jax.lax.psum(
            (
                jnp.sum(mf, axis=-1),
                jnp.sum(tn * mf, axis=-1),
                jnp.sum(tn * tn * mf, axis=-1),
                jnp.sum(v * mf, axis=-1),
                jnp.sum(v * v * mf, axis=-1),
                jnp.sum(mf.reshape(b, t_blk // m_len, m_len), axis=1),
            ),
            MODEL_AXIS,
        )
        nn = jnp.maximum(n, 1.0)
        denom = stt - st * st / nn

        # Backfit trend <-> pooled phase means — same iteration count and
        # math as `fit_phase_means` (see its cycle/trend-leakage comment);
        # two batched psums per round, so the whole fit is 8 psums + pmax
        # (1 moments + 3 rounds x 2 + 1 residual-scale).
        season = jnp.zeros((b, m_len), v.dtype)
        for _ in range(3):
            y = v - jnp.take(season, phase, axis=1)
            sx, stx = jax.lax.psum(
                (jnp.sum(y * mf, axis=-1), jnp.sum(tn * y * mf, axis=-1)),
                MODEL_AXIS,
            )
            slope_n = jnp.where(
                denom > 1e-12,
                (stx - st * sx / nn) / jnp.maximum(denom, 1e-12),
                0.0,
            )
            intercept = sx / nn - slope_n * st / nn
            det = (v - (intercept[:, None] + slope_n[:, None] * tn)) * mf
            ssum = jax.lax.psum(
                jnp.sum(det.reshape(b, t_blk // m_len, m_len), axis=1),
                MODEL_AXIS,
            )
            season = jnp.where(k > 0, ssum / jnp.maximum(k, 1.0), 0.0)

        # centered leave-one-out residual scale (k=1 phases carry zero
        # information and are excluded; degenerate gap patterns fall back
        # to the plain residual std — same rules as fit_phase_means)
        k_at = jnp.take(k, phase, axis=1)
        pred = (
            intercept[:, None]
            + slope_n[:, None] * tn
            + jnp.take(season, phase, axis=1)
        )
        loo = k_at / jnp.maximum(k_at - 1.0, 1.0)
        smask = mf * (k_at > 1.5)
        r = (v - pred) * loo
        r_all = (v - pred) * mf
        # psum 3 (batched): residual norms/means for both scale paths
        ss, s1, n2, ss_all, s1_all = jax.lax.psum(
            (
                jnp.sum(r * r * smask, axis=-1),
                jnp.sum(r * smask, axis=-1),
                jnp.sum(smask, axis=-1),
                jnp.sum(r_all * r_all, axis=-1),
                jnp.sum(r_all, axis=-1),
            ),
            MODEL_AXIS,
        )

        def _std(sq, s1_, cnt):
            c = jnp.maximum(cnt, 1.0)
            mu = s1_ / c
            return jnp.sqrt(jnp.maximum(sq / c - mu * mu, 0.0))

        scale = jnp.where(
            n2 > 0, _std(ss, s1, n2), _std(ss_all, s1_all, nn)
        )

        # terminal level/trend/phase at the LAST globally valid index
        local_last = jnp.max(jnp.where(mk, gidx[None, :], -1), axis=-1)
        last_valid = jax.lax.pmax(local_last, MODEL_AXIS)
        level = intercept + slope_n * last_valid.astype(v.dtype) / t_total
        trend = slope_n / t_total
        season_phase = ((last_valid + 1) % m_len).astype(jnp.int32)

        # per-series 2-cycle identifiability: under-observed series keep
        # the global-mean model (fit_phase_means applies the same select
        # via _guard_unidentifiable)
        enough = n >= 2.0 * m_len
        mean_v = jnp.where(n > 0, sx0 / nn, 0.0)
        var_v = jnp.maximum(sxx / nn - mean_v * mean_v, 0.0)
        season = jnp.where(enough[:, None], season, 0.0)
        level = jnp.where(enough, level, mean_v)
        trend = jnp.where(enough, trend, 0.0)
        scale = jnp.where(enough, scale, jnp.sqrt(var_v))
        return season, level, trend, scale, season_phase, n.astype(jnp.int32)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, MODEL_AXIS), P(DATA_AXIS, MODEL_AXIS)),
        out_specs=(
            P(DATA_AXIS, None),
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(DATA_AXIS),
            P(DATA_AXIS),
        ),
        check_vma=False,
    )
    return fn(values, mask)


def score_time_sharded(
    batch,
    mesh: Mesh,
    config=None,
    algorithm: str = "moving_average_all",
    gap_steps: jax.Array | None = None,
):
    """Full judgment with the HISTORY time axis sharded over `model` —
    context parallelism end-to-end.

    For histories no single chip holds (year-long windows, 1 s steps):
    place `batch.historical` as [B over data, Th over model]; the model
    fit reduces over ICI, and everything downstream (pairwise tests,
    bounds, flags, verdict) runs on the short data-sharded current/
    baseline windows. Two fits are supported:

      * `moving_average_all` (the deployed default) — one psum of masked
        moments; semantics match `engine.scoring.score`.
      * `phase_means` (the daily-seasonal workhorse) — the distributed
        phase-pooled fit (`sharded_phase_means`, season from
        `config.season_steps`), whose terminal state feeds the SAME
        jitted judgment program the fit cache uses
        (`scoring.score_from_state`), so bounds/flags/verdicts cannot
        diverge from the single-chip path.

    `config`: a BrainConfig for season/pairwise/threshold parameters.
    `gap_steps` [B]: hist->cur gap for drifted re-check windows — the
    seasonal phase must advance by it exactly like every other
    phase_means path (`scoring._advance_gap`; `judge._gap_steps`
    computes it from task timestamps). Ignored by the trendless,
    seasonless mean model.
    """
    from foremast_tpu.config import BrainConfig
    from foremast_tpu.engine import scoring

    cfg = config or BrainConfig()
    pw = dict(
        pairwise_algorithm=cfg.pairwise.algorithm,
        p_threshold=cfg.pairwise.threshold,
        min_mw=cfg.pairwise.min_mann_white_points,
        min_wilcoxon=cfg.pairwise.min_wilcoxon_points,
        min_kruskal=cfg.pairwise.min_kruskal_points,
        min_friedman=cfg.pairwise.min_friedman_points,
    )

    if algorithm == "phase_means":
        season, level, trend, scale, phase, n_hist = sharded_phase_means(
            batch.historical.values,
            batch.historical.mask,
            cfg.season_steps,
            mesh,
        )
        return scoring.score_from_state(
            batch,
            level,
            trend,
            season,
            phase,
            scale,
            n_hist,
            gap_steps=gap_steps,
            **pw,
        )
    if algorithm != "moving_average_all":
        raise ValueError(
            f"score_time_sharded supports moving_average_all and "
            f"phase_means, not {algorithm!r}"
        )

    n, mean, var = sharded_masked_stats(
        batch.historical.values, batch.historical.mask, mesh
    )
    pred = jnp.broadcast_to(mean[:, None], batch.current.values.shape)
    # the jitted shared tail: judgment semantics are defined once, in
    # engine/scoring — this path can never diverge from _score_xla
    return scoring.judgment_tail(batch, pred, jnp.sqrt(var), n, **pw)
