"""Kind `lstm`: the LSTM-autoencoder hybrid for jobs of 2+ metrics
(`docs/guides/design.md:57-93`, "3+ metrics: Deep Learning").

An LSTM-AE fleet is trained per (app, alias-set) (`models/lstm_ae.py`), and
a seasonal-residual Gaussian is fitted beside it
(`models/residual_mvn.py`): reconstruction flags UNION residual flags,
with a confirmation band for borderline residual evidence. The cold path
fits and scores on the host's schedule; the warm path is one from-rows
program over `TreeArena` rows (`lstm_joint_score_from_rows`).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from foremast_tpu.engine import scoring
from foremast_tpu.engine.judge import bucket_length, infer_step
from foremast_tpu.engine.kinds.base import ArenaKind, pack_bf16_delta_rows
from foremast_tpu.models.lstm_ae import (
    AEParams,
    LSTMAEConfig,
    LSTMParams,
    ae_cutoff,
    fit_many,
    score_many_cutoff,
    score_rows_cutoff,
)
from foremast_tpu.models.residual_mvn import (
    MVNState,
    chi2_quantile,
    fit_residual_mvn,
    fit_residual_mvn_bf16_delta,
    residual_mvn_d2_robust,
)
from foremast_tpu.ops.forecasters import Forecast

# Sigmas ABOVE the configured threshold at which residual-MVN evidence is
# strong enough to flag alone; below it (but above the configured cutoff)
# a point needs corroboration (AE agreement or a neighboring exceedance).
# Measured on the quality scenarios (th=240..1008, F=4, thr=4): clean
# points top out 1.1-1.5x the base chi^2 cutoff while true joint
# anomalies — including single-metric correlation breaks, the weakest
# family — clear the +1-sigma quantile; +2 demoted real breaks into the
# band and cost recall. See the confirmation-band comment in
# LstmKind._judge_group.
MVN_CONFIRM_MARGIN = 1.0


# Checkpoint-blob coercion: rebuilds device params (jnp) and host MVN
# arrays from whatever layout Orbax restored; the H2D uploads and scalar
# reads here are the rehydration contract.
# foremast: device-boundary
def _coerce_entry(entry) -> tuple:
    """Normalize a cache entry to (AEParams, float, float, mvn | None).

    `mvn` is the seasonal-residual Gaussian state as a plain 9-tuple of
    host values — (level [F], trend [F], season [F, m], phase [F],
    resid_mu [F], cov [F, F], valid bool, hist_last_ts int, hist_len int);
    the two trailing ints are the time anchor `_mvn_fresh` checks — see
    `LstmKind._judge_group`. Orbax restores NamedTuple pytrees as plain dicts
    and tuples as lists (models/cache.py load); scoring stacks entries
    with jax.tree.map, so every entry must share exact structures. Legacy
    3-tuples (pre-mvn checkpoints) coerce with mvn=None and are refit."""
    params, mu, sd = entry[0], entry[1], entry[2]
    mvn = entry[3] if len(entry) > 3 else None
    changed = not (isinstance(entry, tuple) and len(entry) == 4)
    if not isinstance(params, AEParams):
        changed = True

        def lstm(d) -> LSTMParams:
            return LSTMParams(
                w_x=jnp.asarray(d["w_x"]),
                w_h=jnp.asarray(d["w_h"]),
                b=jnp.asarray(d["b"]),
            )

        params = AEParams(
            enc=lstm(params["enc"]),
            dec=lstm(params["dec"]),
            w_out=jnp.asarray(params["w_out"]),
            b_out=jnp.asarray(params["b_out"]),
        )
    mvn_ok = mvn is None or (
        isinstance(mvn, tuple)
        and len(mvn) == 9
        and all(isinstance(a, np.ndarray) for a in mvn[:6])
        and isinstance(mvn[6], bool)
    )
    if not mvn_ok:
        if not (hasattr(mvn, "__len__") and len(mvn) == 9):
            # unknown/older layout: drop — the judge refits the MVN
            mvn = None
        else:
            mvn = (
                np.asarray(mvn[0], np.float32),
                np.asarray(mvn[1], np.float32),
                np.asarray(mvn[2], np.float32),
                np.asarray(mvn[3], np.int32),
                np.asarray(mvn[4], np.float32),
                np.asarray(mvn[5], np.float32),
                bool(np.asarray(mvn[6])),
                int(np.asarray(mvn[7])),
                int(np.asarray(mvn[8])),
            )
        changed = True
    return (params, float(mu), float(sd), mvn) if changed else entry



@jax.jit
def lstm_joint_score_from_rows(state, rows, x, mask, cut, cutoff, hi_cutoff, gaps):
    """The LSTM-AE hybrid judgment from ARENA-resident joint state —
    the joint counterpart of `scoring.score_from_arena` (ISSUE 4
    tentpole): one compiled program gathers each doc's state row on
    device (`rows` [S] into the TreeArena leaves), runs the AE
    reconstruction check and the echo-robust residual-MVN check, and
    applies the confirmation-band corroboration rule — exactly the
    `LstmKind._judge_group` scoring tail, with zero per-tick state upload.
    Its four phases carry `jax.named_scope`s (`gather_rows`, `ae_score`,
    `hw_continue`, `mvn_judge` — here, in `score_rows_cutoff` and in
    `residual_mvn._d2`), so a device trace's op names say which phase an
    op belongs to.

    state: TreeArena pytree — `ae` (stacked AEParams), `level`/`trend`/
    `season`/`phase` (per-metric HW terminal state, season tiled to the
    arena width), `rmu`/`cov` (residual Gaussian), `valid`.
    x [S, 1, tc, F] padded aligned current windows; mask [S, tc] real
    points; cut [S] gamma-calibrated AE error cutoffs; cutoff/hi_cutoff
    [S] chi^2 base / strong-evidence cutoffs; gaps [S] int32 hist->cur
    gap steps (phase advance — the arena state itself stays pristine).
    Returns anomaly flags [S, tc] bool."""
    ae_flags, _err = score_rows_cutoff(
        state["ae"], rows, x, mask[:, None, :], cut
    )
    with jax.named_scope("gather_rows"):
        st = jax.tree.map(
            lambda leaf: jnp.take(leaf, rows, axis=0),
            {k: v for k, v in state.items() if k != "ae"},
        )
    return _lstm_joint_judgment(
        ae_flags[:, 0, :], st, x, mask, cutoff, hi_cutoff, gaps
    )


@partial(jax.jit, static_argnames=("mesh",))
def lstm_joint_score_from_rows_sharded(
    state, rows, x, mask, cut, cutoff, hi_cutoff, gaps, mesh=None
):
    """`lstm_joint_score_from_rows` against a DATA-AXIS-SHARDED
    TreeArena (ISSUE 19): every leaf (the stacked AEParams included)
    block-shards its [capacity] leading axis over `mesh`'s data axis
    and `rows` [S] carries LOCAL (per-shard) indices, so the whole-tree
    gather runs as one shard_map against each device's own block —
    zero cross-chip transfer — before the identical judgment tail."""
    from foremast_tpu.parallel import mesh as meshlib

    with jax.named_scope("gather_rows"):
        gathered = meshlib.shard_rows_take(state, rows, mesh)
    with jax.named_scope("ae_score"):
        ae_flags, _err = score_many_cutoff(
            gathered["ae"], x, mask[:, None, :], cut
        )
    st = {k: v for k, v in gathered.items() if k != "ae"}
    return _lstm_joint_judgment(
        ae_flags[:, 0, :], st, x, mask, cutoff, hi_cutoff, gaps
    )


def _lstm_joint_judgment(ae_flags, st, x, mask, cutoff, hi_cutoff, gaps):
    """Shared scoring tail of the two from-rows LSTM programs: HW gap
    advance, echo-robust residual-MVN distance, confirmation-band
    corroboration. `ae_flags` [S, tc]; `st` the gathered per-batch (not
    per-capacity) non-AE state dict."""
    s, f = x.shape[0], x.shape[-1]
    m = st["season"].shape[-1]
    with jax.named_scope("hw_continue"):
        gap = gaps.astype(jnp.int32)
        # phase advances by the TRUE gap (mod m); only the trend
        # extrapolation is bounded — same rule as the object path and
        # the univariate scorer's _advance_gap
        phase = ((st["phase"] + gap[:, None]) % m).astype(jnp.int32)
        level = st["level"] + st["trend"] * jnp.minimum(
            gap, scoring.GAP_TREND_CAP_STEPS
        ).astype(jnp.float32)[:, None]
        hw = Forecast(
            pred=jnp.zeros((s * f, 0), jnp.float32),
            scale=jnp.zeros((s * f,), jnp.float32),
            level=level.reshape(-1),
            trend=st["trend"].reshape(-1),
            season=st["season"].reshape(s * f, m),
            season_phase=phase.reshape(-1),
        )
    mvn = MVNState(hw=hw, mu=st["rmu"], cov=st["cov"], valid=st["valid"])
    cur_sf = jnp.swapaxes(x[:, 0], 1, 2)  # [S, F, tc]
    # the two passes' `hw_continue` loops and `mvn_judge` solves carry
    # their scopes from `residual_mvn._d2`
    d2 = residual_mvn_d2_robust(mvn, cur_sf, cutoff)
    with jax.named_scope("mvn_judge"):
        # confirmation band (see LstmKind._judge_group): strong evidence
        # flags alone; borderline needs AE agreement or a BORDERLINE
        # neighbor
        valid = st["valid"][:, None] & mask
        over = (d2 > cutoff[:, None]) & valid
        strong = (d2 > hi_cutoff[:, None]) & valid
        border = over & ~strong
        neighbor = jnp.pad(border[:, :-1], ((0, 0), (1, 0))) | jnp.pad(
            border[:, 1:], ((0, 0), (0, 1))
        )
        mvn_flags = strong | (border & (ae_flags | neighbor))
        return ae_flags | mvn_flags


class LstmKind(ArenaKind):
    name = "lstm"
    selectors = {"lstm_autoencoder": (2, None), "auto": (3, None)}
    needs_gaps = True
    pins_bucket = True

    def cache_key(self, config, app, aliases, hist_keys, tc) -> tuple:
        # per (app, aliases, feature-count, window-bucket, season): job ids
        # differ per run, but different SERVICES with the same standard
        # alias set (the instrument starter emits identical names for every
        # app) must never share a model; season_steps keys the entry too —
        # the cached MVN season buffer's length must match the configured
        # season at score time. The key predates the warm path and does
        # not hold the history's identity: the entry is anchored to its
        # history through mvn[7]/mvn[8], which `admissible` checks.
        return (self.name, app, aliases, len(aliases), tc, config.season_steps)

    def _key(self, judge, j, tc: int) -> tuple:
        return self.cache_key(
            judge.config,
            j.tasks[0].app,
            tuple(t.alias for t in j.tasks),
            None,
            tc,
        )

    def admissible(self, judge, entry, meta) -> bool:
        tc, _mu, _sd, _step, last_ts, n_hist = meta
        # same 2-window floor as judge_cold's explicit min-history gate —
        # warm admission must never accept a job the slow path would
        # refuse to fit
        if n_hist < max(judge.config.min_historical_points, 2 * tc):
            return False
        # orbax-restored entries coerce on the slow path first; a
        # stale-anchored MVN (same app redeployed over a different
        # history) must refit there too
        if (
            not isinstance(entry, tuple)
            or len(entry) != 4
            or not isinstance(entry[0], AEParams)
        ):
            return False
        mvn = entry[3]
        return mvn is not None and mvn[7] == last_ts and mvn[8] == n_hist

    def judge_cold(self, judge, jobs: list) -> list:
        threshold = judge.config.anomaly.rule_for(None).threshold
        min_pts = judge.config.min_historical_points
        out: list = []
        # one batched pairwise call for ALL jobs (gated-out ones included)
        # — same shape discipline as the bivariate path
        all_joints = [judge._joint(job_tasks) for job_tasks in jobs]
        all_pw = judge._pairwise(all_joints)
        # group by (feature count, per-JOB window bucket): fit_many needs
        # uniform [S, W, T, F], and using a group-wide max tc would let one
        # long-current job starve a short-history job into all-masked
        # training windows (mu=sd=0 -> everything flags)
        groups: dict[tuple[int, int], list] = {}
        for j, p in zip(all_joints, all_pw):
            f = j.hist_v.shape[0]
            tc = bucket_length(max(len(j.cur_t), 1))
            # Explicit min-history gate: the history must fill at least
            # TWO training windows of this job's own bucket (and clear
            # the configured minimum). One window is not a model: the
            # AE's mu/sd cutoff calibration comes from the training
            # reconstruction errors, and a single-window "distribution"
            # degenerates — measured, it flags clean in-band noise as
            # UNHEALTHY (the short-history regression test). Too-short
            # jobs degrade to UNKNOWN, never to a fragile fit.
            if len(j.cur_t) == 0 or len(j.hist_t) < max(min_pts, 2 * tc):
                out.extend(judge._unknown(j.tasks, p))
            else:
                groups.setdefault((f, tc), []).append((j, p))

        for (f, tc), pairs in groups.items():
            out.extend(
                self._judge_group(
                    judge, [j for j, _ in pairs], [p for _, p in pairs], f, tc, threshold
                )
            )
        return out

    # Slow-path LSTM/MVN group stage: fit + dispatch + gather + verdict
    # decode in one body (cold-fit latency regime; the warm path is
    # judge_warm).
    # foremast: device-boundary
    def _judge_group(
        self,
        judge,
        joints: list,
        pw: list[tuple[np.ndarray, np.ndarray]],
        f: int,
        tc: int,
        threshold: float,
    ) -> list:
        cfg = LSTMAEConfig(features=f)
        # entry per joint job, kept locally — the bounded ModelCache may
        # evict mid-batch, so never re-read what was just trained
        entries: dict[int, tuple] = {}
        to_train: list = []
        for j in joints:
            cached = judge.cache.get(self._key(judge, j, tc))
            if cached is None:
                to_train.append(j)
            else:
                entry = _coerce_entry(cached)
                if entry is not cached:  # orbax-restored form: fix once
                    judge.cache.put(self._key(judge, j, tc), entry)
                entries[id(j)] = entry

        if to_train:
            # chop each history into tc-length windows (newest-aligned);
            # every job has >= 1 real window (admission: hist >= tc), and
            # shorter histories pad with fully-masked windows. The 8-window
            # cap is justified empirically: raising it to 32 (and steps to
            # 150) left joint-detection F1 unchanged — the AE's blind spot
            # is structural (it copies in-window anomalies), which the
            # residual-Gaussian companion below covers instead.
            n_win = min(max(len(j.hist_t) // tc for j in to_train), 8)
            xs, ms = [], []
            for j in to_train:
                wins, wmask = [], []
                usable = (len(j.hist_t) // tc) * tc
                chunks = j.hist_v[:, len(j.hist_t) - usable:].reshape(f, -1, tc)
                for w in range(min(chunks.shape[1], n_win)):
                    wins.append(chunks[:, -(w + 1), :].T)  # [tc, F]
                    wmask.append(np.ones(tc, bool))
                while len(wins) < n_win:
                    wins.append(np.zeros((tc, f), np.float32))
                    wmask.append(np.zeros(tc, bool))
                xs.append(np.stack(wins))  # [n_win, tc, F]
                ms.append(np.stack(wmask))
            x = jnp.asarray(np.stack(xs))  # [S, n_win, tc, F]
            mask = jnp.asarray(np.stack(ms))
            params, mu, sd, _ = fit_many(
                jax.random.key(0), x, mask, cfg, steps=judge.lstm_steps
            )
            mu_np, sd_np = np.asarray(mu), np.asarray(sd)
            for i, j in enumerate(to_train):
                leaf = jax.tree.map(lambda a, i=i: a[i], params)
                entry = (leaf, float(mu_np[i]), float(sd_np[i]), None)
                entries[id(j)] = entry

        # seasonal-residual Gaussian companion (models/residual_mvn.py):
        # fitted once per job next to the AE and cached with it — catches
        # contextual anomalies the reconstruction path copies. Unlike the
        # AE (window-normalized, roughly phase-free), the MVN's HW state is
        # TIME-ANCHORED, so a cached fit is only reused for the exact same
        # history (last timestamp + length); a later deployment of the
        # same app refits instead of replaying a phase-stale season.
        def _mvn_fresh(j, mvn) -> bool:
            return (
                mvn is not None
                and len(j.hist_t) == mvn[8]
                and int(j.hist_t[-1]) == mvn[7]
            )

        need_mvn = [
            j for j in joints if not _mvn_fresh(j, entries[id(j)][3])
        ]
        # Partition by the 2-cycle identifiability rule BEFORE bucketing:
        # fit_residual_mvn's season guard keys off the batch's STATIC
        # length, so a 12-hour job bucket-padded next to a 3-day job would
        # be fitted at the long batch's m and land an empty warm region
        # (valid=False). Short jobs get their own m=1 (Holt) fit instead.
        # The short partition is fitted at m=1 EXPLICITLY: its bucket can
        # still round up past 2*season (a 1.5-day job pads to 4096 > 2880),
        # which would defeat fit_residual_mvn's static-length guard.
        season = judge.config.season_steps
        for need, m_part in (
            ([j for j in need_mvn if len(j.hist_t) >= 2 * season], season),
            ([j for j in need_mvn if len(j.hist_t) < 2 * season], 1),
        ):
            if need:
                self._fit_mvn_batch(judge, need, entries, f, tc, m_part)

        # score every joint job against its (possibly cached) model
        out: list = []
        ordered = [entries[id(j)] for j in joints]
        stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *[e[0] for e in ordered])
        mu = jnp.asarray([e[1] for e in ordered])
        sd = jnp.asarray([e[2] for e in ordered])
        cur_rows = []
        cur_masks = []
        for j in joints:
            row = np.zeros((tc, f), np.float32)
            n = min(len(j.cur_t), tc)
            row[:n] = j.cur_v[:, :n].T
            m = np.zeros(tc, bool)
            m[:n] = True
            cur_rows.append(row[None])  # [1, tc, F]
            cur_masks.append(m[None])
        cur_np = np.stack(cur_rows)  # [S, 1, tc, F]
        cur_mask = np.stack(cur_masks)[:, 0, :]  # [S, tc] real points
        xq = jnp.asarray(cur_np)
        mq = jnp.asarray(cur_mask[:, None, :])
        # canary check: a differing alias lowers the job's joint recon-error
        # threshold (design.md:33), same rule as the bivariate path; the
        # cutoff is the gamma-quantile calibration (models/lstm_ae.ae_cutoff)
        eff_thr = judge._effective_thresholds(pw, threshold)
        cut = ae_cutoff(np.asarray(mu), np.asarray(sd), eff_thr)
        flags, _err = score_many_cutoff(stacked, xq, mq, jnp.asarray(cut))
        flags = np.asarray(flags)[:, 0, :]  # [S, tc]

        # hybrid judgment: reconstruction flags UNION residual-Gaussian
        # flags — the learned model covers pattern deviations, the
        # closed-form covers contextual/correlation-break anomalies it
        # can copy (see models/residual_mvn.py docstring)
        s_count = len(joints)
        mvns = [entries[id(j)][3] for j in joints]
        levels = np.stack([m[0] for m in mvns])  # [S, F]
        trends = np.stack([m[1] for m in mvns])
        # entries may mix season widths (identifiability partitions fit
        # short histories at m=1; scoring.tile_season documents exactness)
        m_len = max(m[2].shape[-1] for m in mvns)
        seasons = np.stack(
            [scoring.tile_season(m[2], m_len) for m in mvns]
        )  # [S, F, m]
        phases = np.stack([m[3] for m in mvns]).astype(np.int64)
        # advance each job's HW state across the real history->current gap
        # (from timestamps) so the seasonal phase lines up with the window
        # being scored; the fitted phase assumes cur starts one step after
        # the history's last point
        for i, j in enumerate(joints):
            step = infer_step(j.hist_t)
            # every scored joint job becomes warm-path state: entry is
            # already in the cache (trained/refit jobs were put by
            # _fit_mvn_batch); the meta records the warm-band inputs and
            # the time anchors the columnar path advances phases with
            judge._record_joint(self, j, tc, step=step)
            k = int(round((float(j.cur_t[0]) - mvns[i][7]) / max(step, 1.0)))
            gap = max(k - 1, 0)
            # phase advances by the TRUE gap (mod m — clamping here would
            # corrupt the phase, e.g. 10*m ≡ 0); only the trend
            # extrapolation is bounded against runaway level drift (same
            # cap as the univariate scorer's _advance_gap)
            phases[i] = (phases[i] + gap) % m_len
            levels[i] = levels[i] + trends[i] * min(
                gap, scoring.GAP_TREND_CAP_STEPS
            )
        hw = Forecast(
            pred=jnp.zeros((s_count * f, 0), jnp.float32),
            scale=jnp.zeros((s_count * f,), jnp.float32),
            level=jnp.asarray(levels.reshape(-1)),
            trend=jnp.asarray(trends.reshape(-1)),
            season=jnp.asarray(seasons.reshape(s_count * f, -1)),
            season_phase=jnp.asarray(phases.reshape(-1).astype(np.int32)),
        )
        state = MVNState(
            hw=hw,
            mu=jnp.asarray(np.stack([m[4] for m in mvns])),
            cov=jnp.asarray(np.stack([m[5] for m in mvns])),
            valid=jnp.asarray(np.asarray([m[6] for m in mvns])),
        )
        # same padded buffer the AE scored, in the MVN's [S, F, tc] layout
        cur_sf = cur_np[:, 0].transpose(0, 2, 1)
        cutoffs = np.asarray(
            [chi2_quantile(float(eff_thr[i]), f) for i in range(s_count)],
            np.float32,
        )
        # Strong-evidence cutoff for the confirmation band: the chi^2
        # quantile at (threshold + MVN_CONFIRM_MARGIN) sigmas. The chi^2
        # calibration is exact only for Gaussian residuals; real HW
        # residuals are heavier-tailed, so points BETWEEN the two cutoffs
        # (borderline by construction — measured FPs land 1.1-1.6x the
        # base cutoff while true anomalies clear 2x, benchmarks/quality.py)
        # flag
        # only with corroboration: the AE reconstruction flags the same
        # point, or a NEIGHBORING point also exceeds the base cutoff (a
        # sustained shift). Fail-fast + AutoRollback semantics
        # (design.md:43, MonitorController.go:214-229) make every false
        # point a potential rollback, so borderline single-point evidence
        # from one detector alone is not enough.
        hi_cutoffs = np.asarray(
            [
                chi2_quantile(float(eff_thr[i]) + MVN_CONFIRM_MARGIN, f)
                for i in range(s_count)
            ],
            np.float32,
        )
        d2 = np.asarray(
            residual_mvn_d2_robust(
                state, jnp.asarray(cur_sf), jnp.asarray(cutoffs)
            )
        )
        # cur_mask keeps bucket padding out of the band logic: a padded
        # zero can land a borderline d^2 and would otherwise corroborate
        # the last REAL point through the neighbor rule
        valid = np.asarray(state.valid)[:, None] & cur_mask
        over = (d2 > cutoffs[:, None]) & valid
        strong = (d2 > hi_cutoffs[:, None]) & valid
        border = over & ~strong
        # A neighboring exceedance corroborates a borderline point only if
        # it is itself BORDERLINE (a sustained moderate shift spans
        # consecutive moderate points). A STRONG neighbor must not count:
        # the causal HW state absorbs each observed point, so a strong
        # spike at t contaminates the t+1 prediction and manufactures a
        # borderline echo right next to itself — exactly the false point
        # this rule would otherwise confirm.
        neighbor = np.zeros_like(border)
        neighbor[:, 1:] |= border[:, :-1]
        neighbor[:, :-1] |= border[:, 1:]
        mvn_flags = strong | (border & (flags | neighbor))
        flags = flags | mvn_flags

        for i, j in enumerate(joints):
            out.extend(
                judge._emit(j, flags[i, : len(j.cur_t)], float(eff_thr[i]), pw[i])
            )
        return out

    # Cold MVN fit stage: uploads aligned histories, runs the jitted
    # fit, gathers the state tuple to host numpy for the cache entry.
    # foremast: device-boundary
    def _fit_mvn_batch(
        self,
        judge,
        need: list,
        entries: dict[int, tuple],
        f: int,
        tc: int,
        season: int,
    ) -> None:
        """Fit the residual MVN for one identifiability partition and fold
        the state into each job's cache entry (time-anchored)."""
        thb = bucket_length(max(len(j.hist_t) for j in need))
        hist = np.zeros((len(need), f, thb), np.float32)
        hmask = np.zeros((len(need), thb), bool)
        for i, j in enumerate(need):
            nh = j.hist_v.shape[1]
            hist[i, :, :nh] = j.hist_v
            hmask[i, :nh] = True
        if scoring.bf16_delta_enabled():
            # cold joint fits ship anchor + bf16 deltas: the [S, F, Th]
            # aligned-history upload is the H2D bound of a joint-cold
            # tick, the same regime as the univariate cold-fit upload
            anchor, delta = pack_bf16_delta_rows(hist, hmask[:, None, :])
            st = fit_residual_mvn_bf16_delta(
                jnp.asarray(anchor),
                jnp.asarray(delta),
                jnp.asarray(hmask),
                season_length=season,
            )
        else:
            st = fit_residual_mvn(
                jnp.asarray(hist), jnp.asarray(hmask), season_length=season
            )
        n = len(need)
        lv = np.asarray(st.hw.level, np.float32).reshape(n, f)
        tr = np.asarray(st.hw.trend, np.float32).reshape(n, f)
        se = np.asarray(st.hw.season, np.float32).reshape(n, f, -1)
        ph = np.asarray(st.hw.season_phase, np.int32).reshape(n, f)
        rmu = np.asarray(st.mu, np.float32)
        cov = np.asarray(st.cov, np.float32)
        va = np.asarray(st.valid)
        for i, j in enumerate(need):
            e = entries[id(j)]
            entry = (
                e[0],
                e[1],
                e[2],
                (
                    lv[i],
                    tr[i],
                    se[i],
                    ph[i],
                    rmu[i],
                    cov[i],
                    bool(va[i]),
                    int(j.hist_t[-1]),
                    len(j.hist_t),
                ),
            )
            entries[id(j)] = entry
            judge.cache.put(self._key(judge, j, tc), entry)

    def template(self, f: int, m: int):
        sd = jax.ShapeDtypeStruct
        h = LSTMAEConfig(features=f).hidden

        def cell():
            return LSTMParams(
                w_x=sd((f, 4 * h), jnp.float32),
                w_h=sd((h, 4 * h), jnp.float32),
                b=sd((4 * h,), jnp.float32),
            )

        return {
            "ae": AEParams(
                enc=cell(),
                dec=cell(),
                w_out=sd((h, f), jnp.float32),
                b_out=sd((f,), jnp.float32),
            ),
            "level": sd((f,), jnp.float32),
            "trend": sd((f,), jnp.float32),
            "season": sd((f, m), jnp.float32),
            "phase": sd((f,), jnp.int32),
            "rmu": sd((f,), jnp.float32),
            "cov": sd((f, f), jnp.float32),
            "valid": sd((), jnp.bool_),
        }

    def row_tree(self, entry, m: int):
        mvn = entry[3]
        return {
            "ae": jax.tree.map(np.asarray, entry[0]),
            "level": mvn[0],
            "trend": mvn[1],
            "season": scoring.tile_season(mvn[2], m),
            "phase": mvn[3].astype(np.int32),
            "rmu": mvn[4],
            "cov": mvn[5],
            "valid": np.bool_(mvn[6]),
        }

    def season_need(self, entries: list) -> int:
        return max(e[3][2].shape[-1] for e in entries)

    def operands(self, entries, thr, f, sb, s0, cur, mask, gaps):
        cut = ae_cutoff(
            np.asarray([e[1] for e in entries] + [1.0] * (sb - s0)),
            np.asarray([e[2] for e in entries] + [1.0] * (sb - s0)),
            np.full(sb, thr, np.float32),
        )
        cutoff = np.full(sb, chi2_quantile(thr, f), np.float32)
        hi = np.full(
            sb,
            chi2_quantile(thr + MVN_CONFIRM_MARGIN, f),
            np.float32,
        )
        host = (
            np.ascontiguousarray(cur.transpose(0, 2, 1))[:, None],
            mask,
        )
        return host, (
            cut,
            cutoff,
            hi,
            gaps if gaps is not None else np.zeros(sb, np.int32),
        )

    program = staticmethod(lstm_joint_score_from_rows)
    program_sharded = staticmethod(lstm_joint_score_from_rows_sharded)
