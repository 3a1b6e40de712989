"""Multivariate job judgment — the reference's metric-count model rule.

Reference model zoo (`docs/guides/design.md:57-93`): 1 metric -> the
univariate forecasters; 2 metrics -> Bivariate Normal Distribution; 3+
metrics -> Deep Learning (LSTM). The brain selects via its AI_MODEL
registry; here the same selection is explicit:

  * `ML_ALGORITHM=auto`             -> by metric count (the design.md rule)
  * `ML_ALGORITHM=bivariate_normal` -> joint 2-metric judgment (pairs only)
  * `ML_ALGORITHM=lstm_autoencoder` -> joint judgment for 2+ metrics
  * `ML_ALGORITHM=backbone`         -> the shared sequence backbone, every
                                       alias of every job one sequence
                                       (engine/backbone.py)
  * any univariate name             -> univariate per-metric (HealthJudge);
                                       an unknown name is refused when the
                                       configuration is loaded (config.py)

Joint detectors align the job's metrics on common timestamps (a joint
observation needs every coordinate), judge the joint series, and
attribute flagged timestamps back to every alias in the job (the wire
format is per-alias anomaly pairs, `Barrelman.go:593-620`). Per-alias
gauge bounds stay meaningful via marginal mean +/- threshold * sigma.

Canary pairwise semantics (`docs/guides/design.md:31-33`,
`foremast-brain/README.md:5-11`) apply to joint jobs exactly as to
univariate ones: every metric's current window is tested against its
baseline window (Mann-Whitney / Wilcoxon / Kruskal / Friedman per
ML_PAIRWISE_ALGORITHM), and if ANY metric's distributions differ the
job's joint detection threshold is lowered by
`scoring.DIFF_THRESHOLD_FACTOR` — a suspicious canary gets tighter
bounds. Per-alias p-values and differ flags ride the verdicts so the
wire format carries the same evidence as the univariate path.

LSTM-AE fleets are trained per (app, alias-set) with a bounded
`ModelCache` (`MAX_CACHE_SIZE`, `foremast-brain/README.md:30`) so repeat
judgments of the same service skip training.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from foremast_tpu.config import BrainConfig
from foremast_tpu.engine import scoring
from foremast_tpu.engine.judge import (
    HealthJudge,
    MetricTask,
    MetricVerdict,
    bucket_length,
    infer_step,
)
from foremast_tpu.models.bivariate import (
    detect_bivariate,
    detect_bivariate_from_rows,
    detect_bivariate_from_rows_sharded,
    fit_bivariate,
    fit_bivariate_bf16_delta,
)
from foremast_tpu.models.cache import ModelCache
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
from foremast_tpu.observe.spans import note, span
from foremast_tpu.ops.forecasters import Forecast
from foremast_tpu.ops.windows import MetricWindows

log = logging.getLogger("foremast_tpu.engine.multivariate")

ALGO_BIVARIATE = "bivariate_normal"
ALGO_LSTM = "lstm_autoencoder"
ALGO_AUTO = "auto"
ALGO_BACKBONE = "backbone"
MULTIVARIATE_ALGOS = frozenset(
    {ALGO_BIVARIATE, ALGO_LSTM, ALGO_AUTO, ALGO_BACKBONE}
)

# Sigmas ABOVE the configured threshold at which residual-MVN evidence is
# strong enough to flag alone; below it (but above the configured cutoff)
# a point needs corroboration (AE agreement or a neighboring exceedance).
# Measured on the quality scenarios (th=240..1008, F=4, thr=4): clean
# points top out 1.1-1.5x the base chi^2 cutoff while true joint
# anomalies — including single-metric correlation breaks, the weakest
# family — clear the +1-sigma quantile; +2 demoted real breaks into the
# band and cost recall. See the confirmation-band comment in
# _judge_lstm_group.
MVN_CONFIRM_MARGIN = 1.0

# Univariate fallbacks when a multivariate algorithm is configured but the
# job's metric count doesn't fit. `auto` means "pick the best model for
# the job's shape", so its univariate branch uses the structure screen
# (flat -> global mean, seasonal/trend -> fitted Holt-Winters; quality
# floors in benchmarks/quality.py). Explicitly-configured bivariate/lstm
# keep the reference's deployed default for their misfit jobs — the operator chose
# a specific algorithm, not "best available" (`foremast-brain.yaml:24-25`).
FALLBACK_UNIVARIATE = "moving_average_all"
FALLBACK_AUTO = "auto_univariate"


def select_mode(algorithm: str, n_metrics: int) -> str:
    """'univariate' | 'bivariate' | 'lstm' | 'backbone' for a job with
    n_metrics."""
    if algorithm == ALGO_BACKBONE:
        return "backbone"
    if algorithm == ALGO_AUTO:
        if n_metrics <= 1:
            return "univariate"
        return "bivariate" if n_metrics == 2 else "lstm"
    if algorithm == ALGO_BIVARIATE:
        return "bivariate" if n_metrics == 2 else "univariate"
    if algorithm == ALGO_LSTM:
        return "lstm" if n_metrics >= 2 else "univariate"
    return "univariate"


def align_series(
    times: list[np.ndarray], vals: list[np.ndarray]
) -> tuple[np.ndarray, np.ndarray]:
    """Common timestamps + stacked values [F, n] for one job's window set.

    Joint observations exist only where every metric has a sample. The
    one alignment routine for BOTH the object path (`_align`) and the
    worker's joint columnar path — the two must never diverge on how a
    ragged alias set intersects."""
    times = [np.asarray(t, np.int64) for t in times]
    vals = [np.asarray(v, np.float32) for v in vals]
    common = times[0]
    for t in times[1:]:
        common = np.intersect1d(common, t, assume_unique=False)
    if len(common) == 0:
        return common, np.zeros((len(times), 0), np.float32)
    cols = []
    for t, v in zip(times, vals):
        # first occurrence per timestamp (times may repeat in raw traces)
        order = np.argsort(t, kind="stable")
        ts = t[order]
        idx = np.searchsorted(ts, common)
        cols.append(v[order][idx])
    return common, np.stack(cols, axis=0)


def _align(tasks: list[MetricTask], which: str) -> tuple[np.ndarray, np.ndarray]:
    """`align_series` over one job's task windows (which: 'hist'/'cur')."""
    return align_series(
        [getattr(t, f"{which}_times") for t in tasks],
        [getattr(t, f"{which}_values") for t in tasks],
    )


def _marginal_bounds(hist: np.ndarray, threshold: float, tc: int):
    """Per-metric constant gauge bounds from historical moments.

    hist [F, n] -> (upper [F, tc], lower [F, tc]) — mean +/- thr*sigma,
    the same semantics every univariate detector publishes."""
    if hist.shape[1] == 0:
        z = np.zeros((hist.shape[0], tc), np.float32)
        return z, z
    mu = hist.mean(axis=1)
    sd = hist.std(axis=1)
    up = np.repeat((mu + threshold * sd)[:, None], tc, axis=1).astype(np.float32)
    lo = np.repeat(
        np.maximum(mu - threshold * sd, 0.0)[:, None], tc, axis=1
    ).astype(np.float32)
    return up, lo


def _pack_np(rows: list[np.ndarray], length: int) -> tuple[np.ndarray, np.ndarray]:
    """Ragged rows -> host ([B, length] values, [B, length] mask)."""
    b = len(rows)
    out = np.zeros((b, length), np.float32)
    mask = np.zeros((b, length), bool)
    for i, r in enumerate(rows):
        n = min(len(r), length)
        out[i, :n] = r[:n]
        mask[i, :n] = True
    return out, mask


def _pack(rows: list[np.ndarray], length: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Ragged rows -> ([B, length] values, [B, length] mask) on device."""
    out, mask = _pack_np(rows, length)
    return jnp.asarray(out), jnp.asarray(mask)


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
    `_judge_lstm_group`. Orbax restores NamedTuple pytrees as plain dicts
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


@dataclasses.dataclass
class _JointJob:
    tasks: list[MetricTask]
    hist_t: np.ndarray
    hist_v: np.ndarray  # [F, nh]
    cur_t: np.ndarray
    cur_v: np.ndarray  # [F, nc]


def _pack_bf16_delta_rows(values: np.ndarray, mask: np.ndarray):
    """Anchor-shifted bf16-delta pack of left-packed joint histories.

    values [..., T] f32 with a valid-prefix mask [..., T] (broadcastable)
    -> (anchor [...] f32, delta [..., T] bf16). Anchor is the first slot
    (left-packed rows put the first valid value there; all-masked rows
    anchor 0), the same shift `judge._pack_hist_bf16_host` uses, so cold
    joint fits ship 2 B/point instead of 5."""
    import ml_dtypes

    anchor = (values[..., 0] * mask[..., 0]).astype(np.float32)
    delta = (values - anchor[..., None]) * mask
    return anchor, delta.astype(ml_dtypes.bfloat16)


@jax.jit
def lstm_joint_score_from_rows(state, rows, x, mask, cut, cutoff, hi_cutoff, gaps):
    """The LSTM-AE hybrid judgment from ARENA-resident joint state —
    the joint counterpart of `scoring.score_from_arena` (ISSUE 4
    tentpole): one compiled program gathers each doc's state row on
    device (`rows` [S] into the TreeArena leaves), runs the AE
    reconstruction check and the echo-robust residual-MVN check, and
    applies the confirmation-band corroboration rule — exactly the
    `_judge_lstm_group` scoring tail, with zero per-tick state upload.
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
        # confirmation band (see _judge_lstm_group): strong evidence
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


class MultivariateJudge:
    """Dispatcher: routes each job to univariate/bivariate/LSTM judgment.

    Drop-in for HealthJudge at the worker level: same
    `judge(tasks) -> [MetricVerdict]` surface over the flat task list.
    """

    def __init__(
        self,
        config: BrainConfig | None = None,
        univariate: HealthJudge | None = None,
        cache: ModelCache | None = None,
    ):
        self.config = config or BrainConfig()
        uni_cfg = self.config
        if self.config.algorithm in MULTIVARIATE_ALGOS:
            fallback = (
                FALLBACK_AUTO
                if self.config.algorithm == ALGO_AUTO
                else FALLBACK_UNIVARIATE
            )
            uni_cfg = dataclasses.replace(self.config, algorithm=fallback)
        self.univariate = univariate or HealthJudge(uni_cfg)
        if self.univariate.config.algorithm in MULTIVARIATE_ALGOS:
            # an injected judge (e.g. ShardedJudge) built from the raw
            # config must not hand a multivariate algorithm name to the
            # univariate scoring program
            self.univariate.config = uni_cfg
        self.cache = cache or ModelCache(self.config.max_cache_size)
        self.lstm_steps = int(os.environ.get("FOREMAST_LSTM_STEPS", "60"))
        # Joint columnar support (ISSUE 4 tentpole): per-key warm-path
        # metadata the slow path records next to every joint fit —
        # aligned-history moments (the per-alias gauge bounds), the
        # time anchors for the MVN phase advance, and the window bucket
        # the model was fitted at. Keyed by (mode, app, aliases, the
        # per-alias fit keys), so a redeploy with new historical ranges
        # can never replay a stale-phase model.
        self.joint_meta = ModelCache(self.config.max_cache_size)
        # device arenas holding joint-model state rows (TreeArena), one
        # per (mode, feature count); monotone counter base folds retired
        # arenas like HealthJudge._counters_base
        self._joint_arenas: dict = {}
        self._joint_counters_base = {
            "hits": 0,
            "misses": 0,
            "evictions": 0,
            "shard_moves": 0,
            "fallbacks": 0,
        }
        # the shared sequence backbone (ISSUE 27), built at its first
        # use: its weights are gigabytes
        self._backbone = None
        # backbone sequence key -> the joint cache key of its document
        self._backbone_doc: dict = {}
        # joint columnar batch-padding accounting (ISSUE 13) — the
        # joint-path counterpart of HealthJudge.pad_rows_total; the
        # worker's device_mesh varz sums both
        self.pad_rows_total = 0
        self.batch_rows_total = 0

    # -- public ----------------------------------------------------------

    def judge(self, tasks: list[MetricTask]) -> list[MetricVerdict]:
        if not tasks:
            return []
        by_job: dict[str, list[MetricTask]] = {}
        for t in tasks:
            by_job.setdefault(t.job_id, []).append(t)

        uni: list[MetricTask] = []
        bi: list[list[MetricTask]] = []
        lstm: list[list[MetricTask]] = []
        backbone: list[list[MetricTask]] = []
        for job_tasks in by_job.values():
            mode = select_mode(self.config.algorithm, len(job_tasks))
            if mode == "bivariate":
                bi.append(job_tasks)
            elif mode == "lstm":
                lstm.append(job_tasks)
            elif mode == "backbone":
                backbone.append(job_tasks)
            else:
                uni.extend(job_tasks)

        out: list[MetricVerdict] = []
        if uni:
            out.extend(self.univariate.judge(uni))
        if bi:
            out.extend(self._judge_bivariate(bi))
        if lstm:
            out.extend(self._judge_lstm(lstm))
        if backbone:
            out.extend(self._judge_backbone(backbone))
        return out

    # -- shared helpers --------------------------------------------------

    def _joint(self, job_tasks: list[MetricTask]) -> _JointJob:
        ht, hv = _align(job_tasks, "hist")
        ct, cv = _align(job_tasks, "cur")
        return _JointJob(job_tasks, ht, hv, ct, cv)

    # Pairwise decode stage: gathers the jitted rank-test program's
    # (p, differs) result for host emission.
    # foremast: device-boundary
    def _pairwise(
        self, joints: list[_JointJob]
    ) -> list[tuple[np.ndarray, np.ndarray]]:
        """Per-job (p [F], differs [F]) — each alias's raw current window
        tested against its own baseline window, exactly the univariate
        canary check (`design.md:31-33`). Metrics without a baseline (the
        rollingUpdate strategy) fail every min-points gate and report
        (1.0, False)."""
        cfg = self.config
        tasks = [t for j in joints for t in j.tasks]
        if all(t.base_values is None for t in tasks):
            # baseline-less batch (rollingUpdate): provably (1.0, False)
            # everywhere — skip the packing + kernel dispatch entirely
            return [
                (np.ones(len(j.tasks)), np.zeros(len(j.tasks), bool))
                for j in joints
            ]
        tc = bucket_length(
            max(
                max(
                    len(t.cur_values),
                    0 if t.base_values is None else len(t.base_values),
                )
                for t in tasks
            )
        )
        empty = (np.zeros(0, np.int64), np.zeros(0, np.float32))
        cur = MetricWindows.from_ragged(
            [(t.cur_times, t.cur_values) for t in tasks], tc
        )
        base = MetricWindows.from_ragged(
            [
                (t.base_times, t.base_values)
                if t.base_values is not None
                else empty
                for t in tasks
            ],
            tc,
        )
        p, differs = scoring.pairwise(
            cur,
            base,
            algorithm=cfg.pairwise.algorithm,
            p_threshold=cfg.pairwise.threshold,
            min_mw=cfg.pairwise.min_mann_white_points,
            min_wilcoxon=cfg.pairwise.min_wilcoxon_points,
            min_kruskal=cfg.pairwise.min_kruskal_points,
            min_friedman=cfg.pairwise.min_friedman_points,
        )
        p, differs = np.asarray(p), np.asarray(differs)
        out, i = [], 0
        for j in joints:
            f = len(j.tasks)
            out.append((p[i : i + f], differs[i : i + f]))
            i += f
        return out

    def _unknown(
        self,
        job_tasks: list[MetricTask],
        pairwise: tuple[np.ndarray, np.ndarray] | None = None,
    ) -> list[MetricVerdict]:
        """UNKNOWN verdicts still carry real pairwise evidence when it was
        computed (parity with the univariate ScoreResult, which always
        publishes p/differs regardless of measurability)."""
        return [
            MetricVerdict(
                job_id=t.job_id,
                alias=t.alias,
                verdict=scoring.UNKNOWN,
                anomaly_pairs=[],
                upper=np.zeros(len(t.cur_values), np.float32),
                lower=np.zeros(len(t.cur_values), np.float32),
                p_value=1.0 if pairwise is None else float(pairwise[0][f]),
                dist_differs=False
                if pairwise is None
                else bool(pairwise[1][f]),
            )
            for f, t in enumerate(job_tasks)
        ]

    def _effective_thresholds(
        self,
        pw: list[tuple[np.ndarray, np.ndarray]],
        threshold: float,
    ) -> np.ndarray:
        """Per-job joint threshold: lowered by DIFF_THRESHOLD_FACTOR when
        ANY alias's distributions differ (design.md:33) — the one rule both
        joint paths share."""
        return np.asarray(
            [
                threshold * scoring.DIFF_THRESHOLD_FACTOR
                if bool(d.any())
                else threshold
                for _, d in pw
            ],
            np.float32,
        )

    def _emit(
        self,
        job: _JointJob,
        flags: np.ndarray,  # [nc] bool over the aligned current points
        threshold: float,
        pairwise: tuple[np.ndarray, np.ndarray] | None = None,  # (p[F], differs[F])
    ) -> list[MetricVerdict]:
        """Joint flags -> per-alias verdicts in the reference wire form."""
        flagged_times = job.cur_t[flags]
        verdict = scoring.UNHEALTHY if flags.any() else scoring.HEALTHY
        up, lo = _marginal_bounds(job.hist_v, threshold, max(len(job.cur_t), 1))
        out = []
        for f, t in enumerate(job.tasks):
            # pairs carry each alias's own measured value at the joint
            # anomalous timestamps
            vals = job.cur_v[f][flags]
            pairs: list[float] = []
            for ts, v in zip(flagged_times, vals):
                pairs.extend([float(ts), float(v)])
            out.append(
                MetricVerdict(
                    job_id=t.job_id,
                    alias=t.alias,
                    verdict=verdict,
                    anomaly_pairs=pairs,
                    upper=up[f],
                    lower=lo[f],
                    p_value=1.0 if pairwise is None else float(pairwise[0][f]),
                    dist_differs=False
                    if pairwise is None
                    else bool(pairwise[1][f]),
                )
            )
        return out

    # -- bivariate -------------------------------------------------------

    # Slow-path bivariate stage: fit + dispatch + gather + verdict
    # decode in one body (cold-fit latency regime; the warm path is
    # joint_columnar).
    # foremast: device-boundary
    def _judge_bivariate(self, jobs: list[list[MetricTask]]) -> list[MetricVerdict]:
        threshold = self.config.anomaly.rule_for(None).threshold
        min_pts = self.config.min_historical_points
        # pairwise evidence is computed for EVERY job — even ones that end
        # up UNKNOWN — so the wire always carries it (univariate parity)
        all_joints = [self._joint(job_tasks) for job_tasks in jobs]
        all_pw = self._pairwise(all_joints)
        joints, pw, out = [], [], []
        for j, p in zip(all_joints, all_pw):
            if len(j.hist_t) < min_pts or len(j.cur_t) == 0:
                out.extend(self._unknown(j.tasks, p))
            else:
                joints.append(j)
                pw.append(p)
        if not joints:
            return out

        th = bucket_length(max(len(j.hist_t) for j in joints))
        tc = bucket_length(max(len(j.cur_t) for j in joints))
        hx_np, hm_np = _pack_np([j.hist_v[0] for j in joints], th)
        hy_np, _ = _pack_np([j.hist_v[1] for j in joints], th)
        cx, cm = _pack([j.cur_v[0] for j in joints], tc)
        cy, _ = _pack([j.cur_v[1] for j in joints], tc)

        eff_thr = self._effective_thresholds(pw, threshold)
        if scoring.bf16_delta_enabled():
            # cold joint fits ship anchor + bf16 deltas (2 B/point) —
            # the same wire layout as the univariate cold-fit upload
            ax, dx = _pack_bf16_delta_rows(hx_np, hm_np)
            ay, dy = _pack_bf16_delta_rows(hy_np, hm_np)
            fit = fit_bivariate_bf16_delta(
                jnp.asarray(ax),
                jnp.asarray(dx),
                jnp.asarray(ay),
                jnp.asarray(dy),
                jnp.asarray(hm_np),
                min_points=min_pts,
            )
        else:
            fit = fit_bivariate(
                jnp.asarray(hx_np),
                jnp.asarray(hy_np),
                jnp.asarray(hm_np),
                min_points=min_pts,
            )
        flags = np.asarray(detect_bivariate(fit, cx, cy, cm, jnp.asarray(eff_thr)))
        valid = np.asarray(fit.valid)
        mean_np = np.asarray(fit.mean)
        cov_np = np.asarray(fit.cov)
        for i, j in enumerate(joints):
            if not valid[i]:
                out.extend(self._unknown(j.tasks, pw[i]))
            else:
                # valid fits become warm-path state: the entry is the
                # fitted Gaussian, the meta carries the warm-band inputs
                # (invalid fits cache NOTHING, so the columnar path can
                # never turn an UNKNOWN doc healthy)
                self._record_joint(
                    "bivariate", j, 0, entry=(mean_np[i], cov_np[i])
                )
                out.extend(
                    self._emit(
                        j, flags[i, : len(j.cur_t)], float(eff_thr[i]), pw[i]
                    )
                )
        return out

    # -- the shared sequence backbone (ISSUE 27) ---------------------------

    @property
    def backbone(self):
        """The process's one `BackboneDetector`; its cache is counted with
        the joint arenas."""
        if self._backbone is None:
            from foremast_tpu.engine.backbone import BackboneDetector

            self._backbone = BackboneDetector()
            self._joint_arenas[("backbone", 0)] = self._backbone.arena
        return self._backbone

    def backbone_counters(self) -> dict | None:
        return None if self._backbone is None else self._backbone.counters()

    def _drop_evicted(self) -> None:
        """A recycled cache row takes its document's warm entry with it:
        the next tick finds none and prefills again."""
        evicted = self._backbone.evicted
        while evicted:
            doc_key = self._backbone_doc.pop(evicted.pop(), None)
            if doc_key is not None:
                self.cache.pop(doc_key)

    def _judge_backbone(
        self, jobs: list[list[MetricTask]]
    ) -> list[MetricVerdict]:
        """Slow path of kind `backbone`: each alias of a job is one
        sequence; those with no cache row are prefilled ("fit"), then the
        window program scores every sequence's current window and a
        timestamp is anomalous where any alias's score exceeds the
        threshold (nats). Jobs go through in groups of at most the cache's
        capacity in sequences."""
        det = self.backbone
        min_pts = max(self.config.min_historical_points, 2)
        all_joints = [self._joint(job_tasks) for job_tasks in jobs]
        all_pw = self._pairwise(all_joints)
        out: list[MetricVerdict] = []
        group: list = []
        seqs = 0
        for j, p in zip(all_joints, all_pw):
            if len(j.hist_t) < min_pts or len(j.cur_t) == 0:
                out.extend(self._unknown(j.tasks, p))
                continue
            if group and seqs + len(j.tasks) > det.capacity:
                out.extend(self._judge_backbone_group(group))
                group, seqs = [], 0
            group.append((j, p))
            seqs += len(j.tasks)
        if group:
            out.extend(self._judge_backbone_group(group))
        return out

    def _judge_backbone_group(self, pairs: list) -> list[MetricVerdict]:
        det = self._backbone
        thr = float(self.config.anomaly.rule_for(None).threshold)
        keys, hists, passing = [], [], []
        for j, _ in pairs:
            for f, t in enumerate(j.tasks):
                key = ("backbone", t.app, t.alias, t.fit_key)
                if t.fit_key is None:
                    # unsettled history: a row for this judgment alone
                    key = ("backbone", "__passing__", t.job_id, t.alias)
                    passing.append(key)
                keys.append(key)
                hists.append(j.hist_v[f])
        entries = det.ensure(keys, hists)
        self._drop_evicted()
        tc = bucket_length(max(len(j.cur_t) for j, _ in pairs))
        cur = np.zeros((len(keys), tc), np.float32)
        valid = np.zeros((len(keys), tc), bool)
        at = 0
        for j, _ in pairs:
            f, n = j.cur_v.shape
            cur[at : at + f, :n] = j.cur_v
            valid[at : at + f, :n] = True
            at += f
        scales = np.array([e[0] for e in entries], np.float32)
        scores = det.score(keys, scales, cur, valid)
        det.arena.release(passing)
        out: list[MetricVerdict] = []
        at = 0
        for j, pw in pairs:
            f, n = j.cur_v.shape
            flags = (scores[at : at + f, :n] > thr).any(axis=0)
            doc_keys = self._joint_keys("backbone", j, tc)
            if doc_keys is not None:
                seq_keys = tuple(keys[at : at + f])
                self._record_joint(
                    "backbone", j, tc, entry=(seq_keys, scales[at : at + f])
                )
                for k in seq_keys:
                    self._backbone_doc[k] = doc_keys[0]
            out.extend(self._emit(j, flags, thr, pw))
            at += f
        return out

    def _backbone_columnar(self, entries: list, cur, mask) -> np.ndarray:
        """Warm judgment of admitted backbone docs: cur [S, F, tcb], mask
        [S, tcb] -> flags [S, tcb], a timestamp flagged where any of the
        doc's F sequences scores over the threshold."""
        s0, f, tcb = cur.shape
        thr = float(self.config.anomaly.rule_for(None).threshold)
        with span("judge.joint_prep", stage="pack", rows=s0):
            seq_keys = [k for e in entries for k in e[0]]
            scales = np.concatenate([e[1] for e in entries])
            valid = np.repeat(mask, f, axis=0)
        scores = self.backbone.score(
            seq_keys, scales, cur.reshape(s0 * f, tcb), valid
        )
        self.batch_rows_total += s0 * f
        return (scores > thr).reshape(s0, f, tcb).any(axis=1) & mask

    # -- LSTM autoencoder ------------------------------------------------

    def _judge_lstm(self, jobs: list[list[MetricTask]]) -> list[MetricVerdict]:
        threshold = self.config.anomaly.rule_for(None).threshold
        min_pts = self.config.min_historical_points
        out: list[MetricVerdict] = []
        # one batched pairwise call for ALL jobs (gated-out ones included)
        # — same shape discipline as the bivariate path
        all_joints = [self._joint(job_tasks) for job_tasks in jobs]
        all_pw = self._pairwise(all_joints)
        # group by (feature count, per-JOB window bucket): fit_many needs
        # uniform [S, W, T, F], and using a group-wide max tc would let one
        # long-current job starve a short-history job into all-masked
        # training windows (mu=sd=0 -> everything flags)
        groups: dict[tuple[int, int], list[tuple[_JointJob, tuple]]] = {}
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
                out.extend(self._unknown(j.tasks, p))
            else:
                groups.setdefault((f, tc), []).append((j, p))

        for (f, tc), pairs in groups.items():
            out.extend(
                self._judge_lstm_group(
                    [j for j, _ in pairs], [p for _, p in pairs], f, tc, threshold
                )
            )
        return out

    # Slow-path LSTM/MVN group stage: fit + dispatch + gather + verdict
    # decode in one body (cold-fit latency regime; the warm path is
    # joint_columnar).
    # foremast: device-boundary
    def _judge_lstm_group(
        self,
        joints: list[_JointJob],
        pw: list[tuple[np.ndarray, np.ndarray]],
        f: int,
        tc: int,
        threshold: float,
    ) -> list[MetricVerdict]:
        cfg = LSTMAEConfig(features=f)
        # entry per joint job, kept locally — the bounded ModelCache may
        # evict mid-batch, so never re-read what was just trained
        entries: dict[int, tuple] = {}
        to_train: list[_JointJob] = []
        for j in joints:
            cached = self.cache.get(self._key(j, tc))
            if cached is None:
                to_train.append(j)
            else:
                entry = _coerce_entry(cached)
                if entry is not cached:  # orbax-restored form: fix once
                    self.cache.put(self._key(j, tc), entry)
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
                jax.random.key(0), x, mask, cfg, steps=self.lstm_steps
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
        def _mvn_fresh(j: _JointJob, mvn) -> bool:
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
        season = self.config.season_steps
        for need, m_part in (
            ([j for j in need_mvn if len(j.hist_t) >= 2 * season], season),
            ([j for j in need_mvn if len(j.hist_t) < 2 * season], 1),
        ):
            if need:
                self._fit_mvn_batch(need, entries, f, tc, m_part)

        # score every joint job against its (possibly cached) model
        out: list[MetricVerdict] = []
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
        eff_thr = self._effective_thresholds(pw, threshold)
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
            self._record_joint("lstm", j, tc, step=step)
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
                self._emit(j, flags[i, : len(j.cur_t)], float(eff_thr[i]), pw[i])
            )
        return out

    # Cold MVN fit stage: uploads aligned histories, runs the jitted
    # fit, gathers the state tuple to host numpy for the cache entry.
    # foremast: device-boundary
    def _fit_mvn_batch(
        self,
        need: list[_JointJob],
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
            anchor, delta = _pack_bf16_delta_rows(hist, hmask[:, None, :])
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
            self.cache.put(self._key(j, tc), entry)

    def _key(self, j: _JointJob, tc: int) -> tuple:
        # per (app, aliases, feature-count, window-bucket, season): job ids
        # differ per run, but different SERVICES with the same standard
        # alias set (the instrument starter emits identical names for every
        # app) must never share a model; season_steps keys the entry too —
        # the cached MVN season buffer's length must match the configured
        # season at score time
        return (
            "lstm",
            j.tasks[0].app,
            tuple(t.alias for t in j.tasks),
            j.hist_v.shape[0],
            tc,
            self.config.season_steps,
        )

    # -- joint columnar fast path (ISSUE 4 tentpole) ----------------------
    #
    # The slow path above records, next to every joint fit, the warm-path
    # metadata a history-free re-check needs; the worker's fast tick then
    # admits joint docs whose (entry, meta) pair is cached and scores them
    # through one arena-gathered program per model kind — no MetricTask
    # objects, no history fetch, no per-tick state upload.

    def _joint_keys(self, mode: str, j: _JointJob, tc: int):
        """(cache_key, meta_key) for a joint job, or None when any alias
        lacks a fit key (unsettled history — never warm-admissible)."""
        aliases = tuple(t.alias for t in j.tasks)
        app = j.tasks[0].app
        hkeys = tuple(t.fit_key for t in j.tasks)
        if any(k is None for k in hkeys):
            return None
        if mode == "bivariate":
            # history identity IS part of the key: two live docs for the
            # same app/aliases over different historical ranges (two
            # deployments) must never share a fitted Gaussian — the lstm
            # key predates this path and is instead anchored to its
            # history via the entry's mvn[7]/mvn[8] check in
            # columnar_joint_peek
            key = ("bivariate", app, aliases, hkeys)
        elif mode == "backbone":
            # the history's identity is the prefix cache's key
            key = ("backbone", app, aliases, hkeys)
        else:
            key = self._key(j, tc)
        return key, ("jmeta", mode, app, aliases, hkeys)

    def _record_joint(
        self,
        mode: str,
        j: _JointJob,
        tc: int,
        entry=None,
        step: float | None = None,
    ) -> None:
        """Fold one slow-path joint judgment into warm-path state.

        meta layout: (tc, hist_mu [F], hist_sd [F], step, last_ts,
        n_hist) — the aligned-history moments reproduce `_marginal_bounds`
        without the history, and (step, last_ts) anchor the MVN phase
        advance. The meta is only REPLACED when its anchors change, so a
        stable fleet keeps stable meta identity (the worker revalidates
        admission by identity, exactly like the univariate path)."""
        keys = self._joint_keys(mode, j, tc)
        if keys is None:
            return
        key, meta_key = keys
        if entry is not None:
            self.cache.put(key, entry)
        last_ts = int(j.hist_t[-1])
        n_hist = len(j.hist_t)
        prev = self.joint_meta.peek(meta_key)
        if (
            prev is not None
            and prev[0] == tc
            and prev[4] == last_ts
            and prev[5] == n_hist
        ):
            return
        self.joint_meta.put(
            meta_key,
            (
                tc,
                j.hist_v.mean(axis=1),
                j.hist_v.std(axis=1),
                infer_step(j.hist_t) if step is None else step,
                last_ts,
                n_hist,
            ),
        )

    def columnar_joint_peek(self, mode: str, app: str, aliases: tuple, hist_keys: tuple):
        """Warm-admission probe: (cache_key, entry, meta_key, meta) when
        this joint job can be scored columnar — both the fitted state and
        the warm metadata are cached, the history clears the same
        measurability gates the object path applies, and (lstm) the MVN
        state is anchored to exactly the history the meta describes.
        None otherwise (the doc stays on the slow path). Lock-free peeks:
        admission runs per doc per tick."""
        meta = self.joint_meta.peek(("jmeta", mode, app, aliases, hist_keys))
        if meta is None:
            return None
        tc, _mu, _sd, _step, last_ts, n_hist = meta
        min_pts = self.config.min_historical_points
        if mode == "bivariate":
            if n_hist < min_pts:
                return None
            key = ("bivariate", app, aliases, hist_keys)
            entry = self.cache.peek(key)
            if entry is None:
                return None
        elif mode == "backbone":
            if n_hist < max(min_pts, 2) or self._backbone is None:
                return None
            key = ("backbone", app, aliases, hist_keys)
            entry = self.cache.peek(key)
            # a warm entry is worth what its rows are: a restored or
            # handed-over entry, or one whose row was recycled, prefills
            rows = self._backbone.arena.rows
            if entry is None or any(k not in rows for k in entry[0]):
                return None
        else:
            # same 2-window floor as _judge_lstm's explicit min-history
            # gate — warm admission must never accept a job the slow
            # path would refuse to fit
            if n_hist < max(min_pts, 2 * tc):
                return None
            key = (
                "lstm",
                app,
                aliases,
                len(aliases),
                tc,
                self.config.season_steps,
            )
            entry = self.cache.peek(key)
            # orbax-restored entries coerce on the slow path first; a
            # stale-anchored MVN (same app redeployed over a different
            # history) must refit there too
            if (
                not isinstance(entry, tuple)
                or len(entry) != 4
                or not isinstance(entry[0], AEParams)
            ):
                return None
            mvn = entry[3]
            if mvn is None or mvn[7] != last_ts or mvn[8] != n_hist:
                return None
        return key, entry, ("jmeta", mode, app, aliases, hist_keys), meta

    def _bi_template(self):
        sd = jax.ShapeDtypeStruct
        return {
            "mean": sd((2,), jnp.float32),
            "cov": sd((2, 2), jnp.float32),
        }

    def _lstm_template(self, f: int, m: int):
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

    def _joint_sharding(self):
        uni = self.univariate
        return uni._arena_sharding() if isinstance(uni, HealthJudge) else None

    def _joint_shards(self) -> int:
        """Row-space shard count for joint arenas — the univariate
        judge's (ISSUE 19): joint TreeArenas block-partition their row
        space over the same data axis as the batch buffers, so warm
        joint gathers are device-local like the univariate path."""
        uni = self.univariate
        return uni._arena_shards() if isinstance(uni, HealthJudge) else 1

    def _joint_multiple(self) -> int:
        """Joint batch leading-axis multiple — the univariate judge's
        (a ShardedJudge's data-axis size), so the joint from-rows
        programs partition over the same mesh (ISSUE 13)."""
        uni = self.univariate
        return uni._batch_multiple() if isinstance(uni, HealthJudge) else 1

    def _place_joint(self, *arrays):
        """Leading-axis placement for joint columnar buffers, through
        the univariate judge's `_place_cols` hook (identity on a plain
        judge; data-axis NamedSharding device_put + partition assert on
        a ShardedJudge)."""
        uni = self.univariate
        if isinstance(uni, HealthJudge):
            return uni._place_cols(*arrays)
        return arrays

    def _joint_arena_for(self, mode: str, f: int, m_need: int):
        """The (mode, f) TreeArena, season buffers at least m_need wide.
        Widening rebuilds empty (host cache entries re-scatter lazily),
        folding the dying arena's counters into the monotone base —
        the same lifecycle as HealthJudge._arena_for. None when arenas
        are disabled (FOREMAST_ARENA_BYTES=0)."""
        from foremast_tpu.engine.arena import TreeArena, _arena_bytes

        if _arena_bytes() <= 0:
            return None
        key = (mode, f)
        arena = self._joint_arenas.get(key)
        if arena is None or getattr(arena, "season_m", 0) < m_need:
            if arena is not None:
                self._retire_joint(arena)
            template = (
                self._bi_template()
                if mode == "bivariate"
                else self._lstm_template(f, m_need)
            )
            arena = TreeArena(
                template,
                sharding=self._joint_sharding(),
                shards=self._joint_shards(),
            )
            arena.season_m = m_need
            self._joint_arenas[key] = arena
        return arena

    def _retire_joint(self, arena) -> None:
        c = arena.counters()
        for k in ("hits", "misses", "evictions", "shard_moves"):
            self._joint_counters_base[k] += c.get(k, 0)

    def joint_state_counters(self) -> dict:
        """Aggregated joint-arena counters, monotone across rebuilds
        (mirrors HealthJudge.device_state_counters)."""
        agg = dict(self._joint_counters_base, rows_live=0, capacity_rows=0)
        for arena in self._joint_arenas.values():
            c = arena.counters()
            for k in (
                "hits",
                "misses",
                "evictions",
                "shard_moves",
                "rows_live",
                "capacity_rows",
            ):
                agg[k] += c.get(k, 0)
        return agg

    def _row_tree(self, mode: str, entry, m: int):
        """One arena row (host numpy pytree) from a cache entry."""
        if mode == "bivariate":
            return {"mean": entry[0], "cov": entry[1]}
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

    # The warm joint gather stage: arrays in, jitted from-rows programs
    # dispatched, flags gathered to host numpy out (the joint counterpart
    # of the worker's _decode_uni).
    # foremast: device-boundary
    def joint_columnar(
        self,
        mode: str,
        keys: list,
        entries: list,
        metas: list,
        cur: np.ndarray,
        mask: np.ndarray,
        gaps: np.ndarray | None = None,
    ) -> np.ndarray:
        """Batched warm judgment of admitted joint docs — arrays in,
        anomaly flags out (the joint counterpart of `judge_columnar`).

        cur [S, F, tcb] aligned current windows (caller-packed), mask
        [S, tcb] real points, keys/entries/metas per doc from
        `columnar_joint_peek`, gaps [S] int32 hist->cur steps (lstm).
        Returns flags [S, tcb] bool (host numpy). The batch axis is
        pow2-padded (dup of row 0, mask all-False => flags all-False) so
        claim-size jitter cannot force recompiles."""
        if mode == "backbone":
            return self._backbone_columnar(entries, cur, mask)
        s0, f, tcb = cur.shape
        thr = float(self.config.anomaly.rule_for(None).threshold)
        # Stage spans, in order, siblings on the tick thread: joint_prep
        # (pack) -> arena_assemble -> joint_prep (pack) -> h2d -> score
        # -> decode. Every host array is built BEFORE the h2d span and
        # the score span holds the jitted call alone.
        with span("judge.joint_prep", stage="pack", rows=s0):
            m_need = (
                1
                if mode == "bivariate"
                else max(e[3][2].shape[-1] for e in entries)
            )
            arena = self._joint_arena_for(mode, f, m_need)
            # batch target shape FIRST (pow2 bucket + data-axis rounding,
            # same rule as judge_columnar) — a sharded arena's assign
            # must see the PADDED position list, because row placement is
            # a function of position // (B / shards)
            sb = bucket_length(s0)
            mult = self._joint_multiple()
            if mult > 1 and sb % mult:
                sb += mult - sb % mult
            if arena is not None:
                re_ = arena.row_entry
                force = [
                    i
                    for i, (k, e) in enumerate(zip(keys, entries))
                    if re_.get(k) is not None and re_.get(k) is not e
                ]
                keys_a, entries_a = keys, entries
                if arena.shards > 1 and sb != s0:
                    # shard-qualified pad keys (ISSUE 19): one stable pad
                    # row per data-axis block (same contract as the
                    # univariate "__pad__col__@N" family — a single
                    # shared key would migrate between blocks as s0
                    # jitters); mask all-False keeps the pad rows' flags
                    # inert
                    per = sb // arena.shards
                    keys_a = list(keys) + [
                        f"__pad__joint__@{(s0 + j) // per}"
                        for j in range(sb - s0)
                    ]
                    entries_a = list(entries) + [entries[0]] * (sb - s0)
        rows = None
        state = None
        if arena is not None:
            with span(
                "judge.arena_assemble",
                stage="arena_assemble",
                rows=s0,
                device=True,
            ) as sp:
                assigned = arena.assign(keys_a, force, s0)
                if assigned is not None:
                    rows_idx, scat = assigned
                    if scat:
                        trees = [None] * len(entries_a)
                        for i in scat:
                            trees[i] = self._row_tree(
                                mode, entries_a[i], arena.season_m
                            )
                            re_[keys_a[i]] = entries_a[i]
                        arena.scatter(rows_idx, scat, trees)
                    state = arena.state
                    rows = rows_idx
                    note(sp, scattered=len(scat))
        with span("judge.joint_prep", stage="pack", rows=sb):
            stacked = None
            if rows is None:
                # arena disabled or batch over the hard byte cap: one-off
                # host stack + upload — counted, never silent (same
                # contract as the univariate fallback)
                if arena is not None:
                    self._joint_counters_base["fallbacks"] += 1
                    log.warning(
                        "joint arena fallback: %d %s rows exceed the hard "
                        "cap — full state restack this tick; raise "
                        "FOREMAST_ARENA_MAX_BYTES",
                        s0,
                        mode,
                    )
                stacked = jax.tree.map(
                    lambda *ls: np.stack(ls),
                    *[self._row_tree(mode, e, m_need) for e in entries],
                )
                rows = np.arange(s0, dtype=np.int64)
            # data-axis rounding (ISSUE 13): same rule as judge_columnar
            # — a sharded univariate judge means the joint programs
            # partition over the same mesh, so S must divide by its data
            # axis. A sharded arena assigned real pad rows above (rows is
            # already sb-long); the replicated/stacked layouts pad by
            # duplicating row 0 with an all-False mask: flags all-False,
            # dropped on the [:s0] decode.
            self.batch_rows_total += sb
            self.pad_rows_total += sb - s0
            if sb != s0:
                pad = sb - s0
                cur = np.concatenate(
                    [cur, np.zeros((pad, f, tcb), np.float32)]
                )
                mask = np.concatenate([mask, np.zeros((pad, tcb), bool)])
                if len(rows) != sb:
                    rows = np.concatenate(
                        [rows, np.full(pad, rows[0], rows.dtype)]
                    )
                if gaps is not None:
                    gaps = np.concatenate([gaps, np.zeros(pad, np.int32)])
            # sharded-arena dispatch (ISSUE 19): when the joint arena row
            # space is block-partitioned over the data axis, ship LOCAL
            # (per-shard) indices through the same placement hook as the
            # batch buffers and run the shard_map from-rows programs —
            # device-local gather, zero cross-chip transfer. The stacked
            # fallback keeps global rows + the replicated programs.
            sharded = (
                arena is not None and arena.shards > 1 and stacked is None
            )
            if sharded:
                rows = (rows % arena.cap_s).astype(np.int32)
            thr_arr = np.full(sb, thr, np.float32)
            if mode == "bivariate":
                host = (cur[:, 0], cur[:, 1], mask)
                operands = (thr_arr,)
            else:
                cut = ae_cutoff(
                    np.asarray([e[1] for e in entries] + [1.0] * (sb - s0)),
                    np.asarray([e[2] for e in entries] + [1.0] * (sb - s0)),
                    thr_arr,
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
                operands = (
                    cut,
                    cutoff,
                    hi,
                    gaps if gaps is not None else np.zeros(sb, np.int32),
                )
        with span("judge.h2d", stage="h2d", rows=sb, device=True) as sp:
            # everything the dispatch hands to the device: batch buffers
            # and (sharded) local rows through the placement hook, the
            # per-row operands, and the stacked fallback's whole state
            handed = [rows, *host, *operands]
            if stacked is not None:
                handed += jax.tree.leaves(stacked)
                state = jax.tree.map(jnp.asarray, stacked)
            note(sp, bytes=sum(int(a.nbytes) for a in handed))
            if sharded:
                (rows,) = self._place_joint(rows)
            rows_j = jnp.asarray(rows)
            placed = [jnp.asarray(a) for a in self._place_joint(*host)]
            operands = [jnp.asarray(a) for a in operands]
        mesh = self.univariate.mesh if sharded else None
        with span(
            "judge.score", stage="score", rows=sb, device=True
        ):
            if mode == "bivariate":
                if sharded:
                    flags = detect_bivariate_from_rows_sharded(
                        state["mean"], state["cov"], rows_j,
                        *placed, *operands, mesh=mesh,
                    )
                else:
                    flags = detect_bivariate_from_rows(
                        state["mean"], state["cov"], rows_j,
                        *placed, *operands,
                    )
            elif sharded:
                flags = lstm_joint_score_from_rows_sharded(
                    state, rows_j, *placed, *operands, mesh=mesh
                )
            else:
                flags = lstm_joint_score_from_rows(
                    state, rows_j, *placed, *operands
                )
        with span("judge.decode", stage="decode", rows=sb, device=True):
            return np.asarray(flags)[:s0]
