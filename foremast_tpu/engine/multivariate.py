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
import os

import numpy as np

from foremast_tpu.config import JOINT_ALGORITHMS, BrainConfig
from foremast_tpu.engine import scoring
from foremast_tpu.engine.judge import (
    HealthJudge,
    MetricTask,
    MetricVerdict,
    bucket_length,
    infer_step,
)
from foremast_tpu.engine.kinds import (
    JOINT_KINDS,
    UNIVARIATE,
    JointKind,
    JointPending,
    kinds_under,
    select_mode,
)
from foremast_tpu.engine.kinds.lstm import lstm_joint_score_from_rows
from foremast_tpu.models.cache import ModelCache
from foremast_tpu.ops.windows import MetricWindows

__all__ = [
    "MULTIVARIATE_ALGOS",
    "MultivariateJudge",
    "align_series",
    "lstm_joint_score_from_rows",
    "select_mode",
]

ALGO_BIVARIATE = "bivariate_normal"
ALGO_LSTM = "lstm_autoencoder"
ALGO_AUTO = "auto"
# the selector names `ML_ALGORITHM` may take for a joint kind: config's list,
# which tests/test_joint_kinds.py holds to the kinds' own `selectors`
MULTIVARIATE_ALGOS = JOINT_ALGORITHMS


# Univariate fallbacks when a multivariate algorithm is configured but the
# job's metric count doesn't fit. `auto` means "pick the best model for
# the job's shape", so its univariate branch uses the structure screen
# (flat -> global mean, seasonal/trend -> fitted Holt-Winters; quality
# floors in benchmarks/quality.py). Explicitly-configured bivariate/lstm
# keep the reference's deployed default for their misfit jobs — the operator chose
# a specific algorithm, not "best available" (`foremast-brain.yaml:24-25`).
FALLBACK_UNIVARIATE = "moving_average_all"
FALLBACK_AUTO = "auto_univariate"


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


@dataclasses.dataclass
class _JointJob:
    tasks: list[MetricTask]
    hist_t: np.ndarray
    hist_v: np.ndarray  # [F, nh]
    cur_t: np.ndarray
    cur_v: np.ndarray  # [F, nc]


class MultivariateJudge:
    """Dispatcher: routes each job to the univariate judge or to the joint
    kind that takes it (`engine/kinds`), and holds what every joint kind
    shares: alignment, the pairwise tests, the verdicts' wire form, the
    warm metadata and the joint arenas.

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
        if kinds_under(self.config.algorithm):
            fallback = (
                FALLBACK_AUTO
                if self.config.algorithm == ALGO_AUTO
                else FALLBACK_UNIVARIATE
            )
            uni_cfg = dataclasses.replace(self.config, algorithm=fallback)
        self.univariate = univariate or HealthJudge(uni_cfg)
        if kinds_under(self.univariate.config.algorithm):
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
        # what a kind keeps for this judge beyond the two caches, under the
        # kind's name (`backbone`: its detector, built at its first use)
        self.kind_state: dict = {}
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
        by_kind: dict[str, list[list[MetricTask]]] = {}
        for job_tasks in by_job.values():
            mode = select_mode(self.config.algorithm, len(job_tasks))
            if mode == UNIVARIATE:
                uni.extend(job_tasks)
            else:
                by_kind.setdefault(mode, []).append(job_tasks)

        out: list[MetricVerdict] = []
        if uni:
            out.extend(self.univariate.judge(uni))
        for name, kind in JOINT_KINDS.items():
            if name in by_kind:
                out.extend(kind.judge_cold(self, by_kind[name]))
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

    # -- what the benchmark and the tests reach by name --------------------

    def _backbone_kind(self):
        """The model-backed kind `ML_ALGORITHM` selects (`backbone`,
        `backbone_kda`): the one kind under it that keeps a detector."""
        for kind in kinds_under(self.config.algorithm):
            if kind.keeps_counters:
                return kind
        raise ValueError(
            f"ML_ALGORITHM={self.config.algorithm!r} selects no model-backed kind"
        )

    @property
    def backbone(self):
        """The process's one `BackboneDetector`, of the kind selected."""
        return self._backbone_kind().state(self).detector

    def backbone_counters(self) -> dict | None:
        return self._backbone_kind().counters(self)

    def _bi_template(self):
        return JOINT_KINDS["bivariate"].template()

    def _lstm_template(self, f: int, m: int):
        return JOINT_KINDS["lstm"].template(f, m)

    # -- joint columnar fast path (ISSUE 4 tentpole) ----------------------
    #
    # The slow path above records, next to every joint fit, the warm-path
    # metadata a history-free re-check needs; the worker's fast tick then
    # admits joint docs whose (entry, meta) pair is cached and scores them
    # through one arena-gathered program per model kind — no MetricTask
    # objects, no history fetch, no per-tick state upload.

    def _joint_keys(self, kind: JointKind, j: _JointJob, tc: int):
        """(cache_key, meta_key) for a joint job, or None when any alias
        lacks a fit key (unsettled history — never warm-admissible)."""
        aliases = tuple(t.alias for t in j.tasks)
        app = j.tasks[0].app
        hkeys = tuple(t.fit_key for t in j.tasks)
        if any(k is None for k in hkeys):
            return None
        return (
            kind.cache_key(self.config, app, aliases, hkeys, tc),
            ("jmeta", kind.name, app, aliases, hkeys),
        )

    def _record_joint(
        self,
        kind: JointKind,
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
        keys = self._joint_keys(kind, j, tc)
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
        the warm metadata are cached and the kind admits them
        (`JointKind.admissible`: the same measurability gates the object
        path applies). None otherwise (the doc stays on the slow path).
        Lock-free peeks: admission runs per doc per tick."""
        meta_key = ("jmeta", mode, app, aliases, hist_keys)
        meta = self.joint_meta.peek(meta_key)
        if meta is None:
            return None
        kind = JOINT_KINDS[mode]
        key = kind.cache_key(self.config, app, aliases, hist_keys, meta[0])
        entry = self.cache.peek(key)
        if not kind.admissible(self, entry, meta):
            return None
        return key, entry, meta_key, meta

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

    def _joint_arena_for(self, kind, f: int, m_need: int):
        """The (kind, f) TreeArena, season buffers at least m_need wide.
        Widening rebuilds empty (host cache entries re-scatter lazily),
        folding the dying arena's counters into the monotone base —
        the same lifecycle as HealthJudge._arena_for. None when arenas
        are disabled (FOREMAST_ARENA_BYTES=0)."""
        from foremast_tpu.engine.arena import TreeArena, _arena_bytes

        if _arena_bytes() <= 0:
            return None
        key = (kind.name, f)
        arena = self._joint_arenas.get(key)
        if arena is None or getattr(arena, "season_m", 0) < m_need:
            if arena is not None:
                self._retire_joint(arena)
            arena = TreeArena(
                kind.template(f, m_need),
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

    def joint_columnar(
        self,
        mode: str,
        keys: list,
        entries: list,
        metas: list,
        cur: np.ndarray,
        mask: np.ndarray,
        gaps: np.ndarray | None = None,
        issued: JointPending | None = None,
    ) -> np.ndarray:
        """Batched warm judgment of admitted joint docs — arrays in,
        anomaly flags out (the joint counterpart of `judge_columnar`).

        cur [S, F, tcb] aligned current windows (caller-packed), mask
        [S, tcb] real points, keys/entries/metas per doc from
        `columnar_joint_peek`, gaps [S] int32 hist->cur steps (the kinds
        with `needs_gaps`). Returns flags [S, tcb] bool (host numpy).

        `issued`: what `joint_columnar_issue` returned for these same
        arrays. The call is then the gather half alone — every warm
        joint judgment's flags reach the host through this return."""
        if issued is None:
            issued = self.joint_columnar_issue(
                mode, keys, entries, metas, cur, mask, gaps
            )
        return issued.wait()

    def joint_columnar_issue(
        self,
        mode: str,
        keys: list,
        entries: list,
        metas: list,
        cur: np.ndarray,
        mask: np.ndarray,
        gaps: np.ndarray | None = None,
    ) -> JointPending:
        """`joint_columnar` up to the device program's issue (tick thread:
        arena assignment and issue order are load-bearing). Hand the
        returned pending back to `joint_columnar(..., issued=)` for the
        flags; the sliced sweep issues a slice's every group before it
        gathers one (the joint counterpart of `judge_columnar_async`)."""
        return JOINT_KINDS[mode].issue_warm(
            self, keys, entries, metas, cur, mask, gaps
        )
