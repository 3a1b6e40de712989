"""Detector-quality benchmark: point-level anomaly F1 per algorithm.

Synthetic scenario families probe where each detector should win:

  * flat    — stationary noise + injected spikes (the golden-trace shape);
              every detector should score well.
  * seasonal— strong cycle + spikes; the global-mean band must widen
              to cover the cycle, so moving_average_all loses recall or
              precision while holt_winters / seasonal track the cycle.
  * trend   — steady drift + spikes; trendless models mis-center bounds.
  * shift   — mid-history level step; global-trend fits mis-center the
              band (the changepoint trend localizes it).
  * daily-1440 / daily-1440-sharp — the reference's real workload shape
    (m=1440 at the 60 s step over the 7-day history), smooth and
    cron-burst variants.
  * joint scenarios + clean-window job-level false alarms for the
    multivariate hybrid.

Each scenario builds B windows with known injected anomaly points; F1 is
computed over current-window points against ground truth. Usage:

    python -m benchmarks.quality [--small]

One JSON line per (scenario, algorithm).
"""

from __future__ import annotations

import argparse
import json

import jax.numpy as jnp
import numpy as np

from functools import partial

from benchmarks import prf1
from foremast_tpu.engine import scoring
from foremast_tpu.ops.windows import MetricWindows

ALGORITHMS = (
    "moving_average_all",
    "ewma",
    "double_exponential_smoothing",
    "holt_winters",
    "seasonal_p24",
    "auto_univariate",
)

# One cycle of the compact synthetic season, in time steps — matches
# fit_holt_winters' signature default season_length=24 so the bare
# registry call tracks it. The DAILY scenario measures the reference's
# real workload shape instead: m=1440 at the 60 s PromQL step
# (`metricsquery.go:43`) over the full 7-day 10,080-pt history, threaded
# through scoring.score(..., season_length=1440).
PERIOD = 24
PERIOD_DAILY = 1440
TH_DAILY = 10_080


def _register_models() -> None:
    """Register the period-matched seasonal variant (deployment config in
    production — default period is 1440, daily at the 60 s step). Called
    from entry points, NOT at import: a benchmark module must not mutate
    the engine's model registry as an import side effect."""
    from foremast_tpu.models.seasonal import fit_seasonal

    scoring.register_model("seasonal_p24", partial(fit_seasonal, period=PERIOD))

SPIKE_SIGMA = 8.0  # injected spike size in noise-sigmas
NOISE = 0.05
SEASON_AMP = 0.5  # seasonal swing: 10x the noise -> dominates a global band
TREND_PER_STEP = 0.002
SHIFT_LEVEL = 0.5  # mid-history step (a redeploy / traffic migration)
SHIFT_FRAC = 0.55  # shift position as a fraction of the history


def gen(kind: str, b: int, th: int, tc: int, seed: int = 0, period: int = PERIOD):
    """(hist [B,Th], cur [B,Tc], truth [B,Tc] bool)."""
    rng = np.random.default_rng(seed)
    t_hist = np.arange(th)[None, :]
    t_cur = (th + np.arange(tc))[None, :]

    def signal(t):
        if kind == "flat":
            return 1.0 + 0.0 * t
        if kind == "seasonal":
            return 1.0 + SEASON_AMP * np.sin(2 * np.pi * t / period)
        if kind == "sharp-seasonal":
            # a cron-style burst: 10 steps of every cycle sit 10x the
            # noise above the base — unrepresentable by low-order
            # Fourier, exactly what the pooled phase-means fit carries
            return 1.0 + SEASON_AMP * (
                (t % period) < max(10, period // 144)
            ).astype(float)
        if kind == "trend":
            return 1.0 + TREND_PER_STEP * t
        if kind == "shift":
            # seasonal series with a mid-history LEVEL SHIFT: a global
            # linear trend fits a bogus slope through the step and
            # mis-centers the horizon band; the changepoint trend
            # (models/seasonal.py hinges) localizes it
            return (
                1.0
                + SEASON_AMP * np.sin(2 * np.pi * t / period)
                + SHIFT_LEVEL * (t >= SHIFT_FRAC * th)
            )
        raise ValueError(kind)

    hist = signal(t_hist) + rng.normal(0, NOISE, (b, th))
    cur = signal(t_cur) + rng.normal(0, NOISE, (b, tc))
    truth = np.zeros((b, tc), bool)
    for i in range(b):
        idx = rng.choice(tc, size=2, replace=False)
        cur[i, idx] += SPIKE_SIGMA * NOISE
        truth[i, idx] = True
    return hist.astype(np.float32), cur.astype(np.float32), truth


def make_batch(hist: np.ndarray, cur: np.ndarray) -> scoring.ScoreBatch:
    b = hist.shape[0]

    def win(v):
        return MetricWindows(
            values=jnp.asarray(v),
            mask=jnp.ones(v.shape, bool),
            times=jnp.zeros(v.shape, jnp.int32),
        )

    return scoring.ScoreBatch(
        historical=win(hist),
        current=win(cur),
        baseline=MetricWindows(
            values=jnp.zeros_like(jnp.asarray(cur)),
            mask=jnp.zeros(cur.shape, bool),
            times=jnp.zeros(cur.shape, jnp.int32),
        ),
        threshold=jnp.full((b,), 4.0, jnp.float32),
        bound=jnp.full((b,), 1, jnp.int32),  # upper: spikes are positive
        min_lower_bound=jnp.zeros((b,), jnp.float32),
        min_points=jnp.full((b,), 10, jnp.int32),
    )


def _prf_from_flags(flags: np.ndarray, truth: np.ndarray):
    """(precision, recall, f1) from point flags vs ground truth."""
    tp = int((flags & truth).sum())
    fp = int((flags & ~truth).sum())
    fn = int((~flags & truth).sum())
    return prf1(tp, fp, fn)


def score_algorithm(batch, truth: np.ndarray, algorithm: str, season_length: int = 24):
    _register_models()  # idempotent: any entry point may call first
    res = scoring.score(batch, algorithm=algorithm, season_length=season_length)
    precision, recall, f1 = _prf_from_flags(np.asarray(res.anomalies), truth)
    return f1, precision, recall


# -- joint (multivariate) scenarios -----------------------------------------


def _joint_tasks(metrics: np.ndarray, cur: np.ndarray, job_prefix: str):
    """[B, F, Th] hist + [B, F, Tc] cur -> flat MetricTask list."""
    from foremast_tpu.engine.judge import MetricTask

    b, f, th = metrics.shape
    tc = cur.shape[-1]
    t0 = 1_700_000_000
    ht = t0 + 60 * np.arange(th, dtype=np.int64)
    ct = t0 + 60 * (th + np.arange(tc, dtype=np.int64))
    tasks = []
    for i in range(b):
        for j in range(f):
            tasks.append(
                MetricTask(
                    job_id=f"{job_prefix}{i}",
                    alias=f"m{j}",
                    metric_type=None,
                    hist_times=ht,
                    hist_values=metrics[i, j],
                    cur_times=ct,
                    cur_values=cur[i, j],
                    app=f"{job_prefix}{i}",
                )
            )
    return tasks, ct


def draw_comoving(rng, b: int, f: int, n: int, t_start: int, period: int = PERIOD):
    """[B, F, n] co-moving seasonal metrics: shared latent (sine + noise)
    plus per-metric offset and idiosyncratic noise. Shared between the
    joint benchmark scenarios and the residual-MVN unit tests so both
    always validate the same data family."""
    t = (t_start + np.arange(n))[None, :]
    latent = 0.3 * np.sin(2 * np.pi * t / period) + rng.normal(0, 0.05, (b, n))
    return np.stack(
        [1.0 + 0.5 * j + latent + rng.normal(0, 0.05, (b, n)) for j in range(f)],
        axis=1,
    ).astype(np.float32)


def gen_correlated_pair(b: int, th: int, tc: int, seed: int = 0):
    """2 tightly-correlated metrics; anomalies are OFF-RIDGE points whose
    marginals stay in range — only a joint model can see them."""
    rng = np.random.default_rng(seed)
    rho = 0.95

    def draw(n):
        x = rng.normal(0, 1, (b, n))
        y = rho * x + np.sqrt(1 - rho * rho) * rng.normal(0, 1, (b, n))
        return 1.0 + 0.2 * x, 2.0 + 0.3 * y

    hx, hy = draw(th)
    cx, cy = draw(tc)
    truth = np.zeros((b, tc), bool)
    for i in range(b):
        idx = rng.choice(tc, size=2, replace=False)
        # break the ridge: push x up, y down by ~2.5 marginal sigmas
        cx[i, idx] += 2.5 * 0.2
        cy[i, idx] -= 2.5 * 0.3
        truth[i, idx] = True
    hist = np.stack([hx, hy], axis=1).astype(np.float32)
    cur = np.stack([cx, cy], axis=1).astype(np.float32)
    return hist, cur, truth


def gen_joint_lstm(b: int, f: int, th: int, tc: int, seed: int = 0, kind="all"):
    """f co-moving seasonal metrics (shared latent + idiosyncratic noise).

    kind="all":   simultaneous all-metric spikes (+0.6, ~8 idio-sigmas) at
                  random positions INCLUDING seasonal troughs — there the
                  spiked value lands near the marginal mean, so only a
                  phase-aware (contextual) model can see it.
    kind="break": ONE metric deviates +/-0.6 while the others follow the
                  shared latent — a correlation break that is invisible
                  marginally and to per-metric models.
    """
    rng = np.random.default_rng(seed)
    hist = draw_comoving(rng, b, f, th, 0)
    cur = draw_comoving(rng, b, f, tc, th)
    truth = np.zeros((b, tc), bool)
    for i in range(b):
        idx = rng.choice(tc, size=2, replace=False)
        if kind == "all":
            cur[i, :, idx] += 0.6
        else:
            for pos in idx:
                j = rng.integers(0, f)
                cur[i, j, pos] += rng.choice([-1.0, 1.0]) * 0.6
        truth[i, idx] = True
    return hist.astype(np.float32), cur.astype(np.float32), truth


def score_joint(kind: str, b: int, th: int, tc: int):
    """F1 for the joint detectors through MultivariateJudge (the shipped
    dispatch), point-level over aligned current timestamps."""
    import dataclasses

    from foremast_tpu.config import BrainConfig
    from foremast_tpu.engine.multivariate import MultivariateJudge

    if kind == "bivariate":
        hist, cur, truth = gen_correlated_pair(b, th, tc)
        algo = "bivariate_normal"
    else:
        hist, cur, truth = gen_joint_lstm(
            b, 4, th, tc, kind="break" if kind == "lstm-break" else "all"
        )
        algo = "lstm_autoencoder"
    tasks, ct = _joint_tasks(hist, cur, kind)
    # season_steps pinned to the synthetic cycle these scenarios draw
    # (draw_comoving, period=PERIOD); the deployed default is daily 1440
    cfg = BrainConfig(algorithm=algo, season_steps=PERIOD)
    cfg = dataclasses.replace(
        cfg, anomaly=dataclasses.replace(cfg.anomaly, threshold=4.0, rules=())
    )
    judge = MultivariateJudge(cfg)
    verdicts = judge.judge(tasks)
    flagged: dict[str, set] = {}
    for v in verdicts:
        flagged.setdefault(v.job_id, set()).update(v.anomaly_pairs[0::2])
    tp = fp = fn = 0
    for i in range(b):
        got = flagged.get(f"{kind}{i}", set())
        want = {float(t) for t, is_a in zip(ct, truth[i]) if is_a}
        tp += len(got & want)
        fp += len(got - want)
        fn += len(want - got)
    return prf1(tp, fp, fn)


def fleet_mix(b: int, th: int, tc: int, seed: int = 0):
    """ONE batch mixing every univariate shape — the production
    condition: `auto_univariate` must route each series to its model
    inside a single compiled program, with no per-batch tuning. Returns
    (f1, precision, recall) over the whole mixed fleet plus the
    per-kind F1 dict."""
    _register_models()
    kinds = ("flat", "seasonal", "trend", "shift", "sharp-seasonal")
    per = max(b // len(kinds), 1)
    hists, curs, truths = [], [], []
    for j, kind in enumerate(kinds):
        h, c, tr = gen(kind, per, th, tc, seed=seed + j)
        hists.append(h)
        curs.append(c)
        truths.append(tr)
    truth = np.concatenate(truths)
    batch = make_batch(np.concatenate(hists), np.concatenate(curs))
    res = scoring.score(batch, algorithm="auto_univariate", season_length=PERIOD)
    flags = np.asarray(res.anomalies)
    precision, recall, f1 = _prf_from_flags(flags, truth)
    by_kind = {}
    for j, kind in enumerate(kinds):
        sl = slice(j * per, (j + 1) * per)
        _, _, kf1 = _prf_from_flags(flags[sl], truth[sl])
        by_kind[kind] = round(kf1, 3)
    return f1, precision, recall, by_kind


def joint_clean_false_alarms(b: int, th: int, tc: int) -> tuple[int, int]:
    """Job-level false alarms on CLEAN joint windows (no injected
    anomalies): how many of `b` healthy deployments the joint hybrid
    detector would mark Unhealthy. Fail-fast + AutoRollback semantics
    (design.md:43) turn every falsely-flagged job into a potential
    rollback, so this is the metric that prices the detector's tail —
    point precision alone hides it. Returns (false_alarm_jobs, jobs)."""
    import dataclasses

    from foremast_tpu.config import BrainConfig
    from foremast_tpu.engine import scoring as engine_scoring
    from foremast_tpu.engine.multivariate import MultivariateJudge

    rng = np.random.default_rng(7)
    hist = draw_comoving(rng, b, 4, th, 0)
    cur = draw_comoving(rng, b, 4, tc, th)
    tasks, _ = _joint_tasks(hist, cur, "clean")
    cfg = BrainConfig(algorithm="lstm_autoencoder", season_steps=PERIOD)
    cfg = dataclasses.replace(
        cfg, anomaly=dataclasses.replace(cfg.anomaly, threshold=4.0, rules=())
    )
    verdicts = MultivariateJudge(cfg).judge(tasks)
    bad_jobs = {
        v.job_id for v in verdicts if v.verdict == engine_scoring.UNHEALTHY
    }
    return len(bad_jobs), b


JOINT_SCENARIOS = ("bivariate", "lstm", "lstm-break")


# -- mixed univariate + joint WORKER tick (VERDICT r4 #5) --------------------


def _unspike(cur: np.ndarray, truth: np.ndarray, kind: str) -> np.ndarray:
    """Exact clean twin of a generated current window: the injection
    constants are known, so subtracting them at the truth positions
    reconstructs the pre-spike draw bit for bit."""
    clean = cur.copy()
    if kind == "bivariate":
        # gen_correlated_pair: x +2.5*0.2, y -2.5*0.3 at truth
        for i in range(cur.shape[0]):
            clean[i, 0, truth[i]] -= 2.5 * 0.2
            clean[i, 1, truth[i]] += 2.5 * 0.3
    elif kind == "lstm":
        for i in range(cur.shape[0]):
            clean[i, :, truth[i]] -= 0.6
    else:  # univariate kinds ([B, 1, Tc]): SPIKE_SIGMA * NOISE at truth
        view = clean[:, 0, :]
        view[truth] -= SPIKE_SIGMA * NOISE
    return clean


def mixed_fleet_tick(per_uni: int, per_joint: int, th: int, tc: int,
                     seed: int = 0):
    """One WORKER claim set mixing every univariate shape AND joint jobs.

    The production condition no prior round tested: a single
    `BrainWorker.tick` under the `auto` multivariate selector carries
    single-alias docs (routed to the univariate fallback — and, when
    warm, the columnar fast path) NEXT TO 2-alias bivariate and 4-alias
    LSTM-hybrid docs (routed to joint models on the slow path).

    Tick 1 runs CLEAN currents (everything healthy — fits + model
    caches warm up); anomalies are then injected into the current
    windows and tick 2 judges the whole mixed fleet warm. Per-kind
    point F1 is computed from the persisted anomaly_info, and one clean
    doc per kind must stay healthy through both ticks (the
    cross-contamination guard). Returns {kind: (f1, n_docs)} plus the
    false-alarm count."""
    import dataclasses

    from foremast_tpu.config import BrainConfig
    from foremast_tpu.jobs.models import (
        STATUS_COMPLETED_UNHEALTH,
        STATUS_PREPROCESS_COMPLETED,
        Document,
    )
    from foremast_tpu.jobs.store import InMemoryStore
    from foremast_tpu.jobs.worker import BrainWorker
    from foremast_tpu.metrics.source import MetricSource

    _register_models()

    class _Src(MetricSource):
        concurrent_fetch = False

        def __init__(self):
            self.data = {}

        def fetch(self, url):
            return self.data[url]

    store, source = InMemoryStore(), _Src()
    t0 = 1_700_000_000
    ht = t0 + 60 * np.arange(th, dtype=np.int64)
    ct = t0 + 60 * (th + np.arange(tc, dtype=np.int64))
    now = float(ct[-1]) + 600.0  # hist settled, endTime still ahead
    end_time = str(int(now) + 3600)

    uni_kinds = ("flat", "seasonal", "trend", "shift", "sharp-seasonal")
    fleets = {}  # kind -> (cur_clean [B,F,Tc], cur_spiked, truth [B,Tc])
    for j, kind in enumerate(uni_kinds):
        h, c, tr = gen(kind, per_uni + 1, th, tc, seed=seed + j)
        fleets[kind] = (h[:, None, :], c[:, None, :], tr)
    hb, cb, trb = gen_correlated_pair(per_joint + 1, th, tc, seed=seed + 7)
    fleets["bivariate"] = (hb, cb, trb)
    hl, cl, trl = gen_joint_lstm(per_joint + 1, 4, th, tc, seed=seed + 8)
    fleets["lstm"] = (hl, cl, trl)

    doc_kind = {}
    doc_truth = {}
    clean_docs = set()
    for kind, (hist, cur, truth) in fleets.items():
        b, f, _ = hist.shape
        clean = _unspike(
            cur, truth,
            kind if kind in ("bivariate", "lstm") else "uni",
        )
        for i in range(b):
            doc_id = f"{kind}-{i}"
            cur_parts, hist_parts = [], []
            for m in range(f):
                cu = f"http://prom/cur?q=m{m}:{doc_id}&step=60"
                hu = (
                    f"http://prom/hist?q=m{m}:{doc_id}"
                    f"&end={int(ht[-1]) + 60}&step=60"
                )
                source.data[cu] = (ct, clean[i, m])
                source.data[hu] = (ht, hist[i, m])
                cur_parts.append(f"m{m}== {cu}")
                hist_parts.append(f"m{m}== {hu}")
            store.create(
                Document(
                    id=doc_id,
                    app_name=doc_id,
                    end_time=end_time,
                    current_config=" ||".join(cur_parts),
                    historical_config=" ||".join(hist_parts),
                    strategy="continuous",
                )
            )
            doc_kind[doc_id] = kind
            if i == b - 1:
                clean_docs.add(doc_id)  # stays clean on tick 2
            else:
                doc_truth[doc_id] = truth[i]

    cfg = BrainConfig(algorithm="auto", season_steps=PERIOD)
    cfg = dataclasses.replace(
        cfg, anomaly=dataclasses.replace(cfg.anomaly, threshold=4.0, rules=())
    )
    n_docs = len(doc_kind)
    worker = BrainWorker(
        store, source, config=cfg, claim_limit=n_docs, worker_id="mix-w"
    )
    assert worker.tick(now=now) == n_docs
    healthy_after_1 = sum(
        1 for d in store._docs.values()
        if d.status == STATUS_PREPROCESS_COMPLETED
    )
    assert healthy_after_1 == n_docs, (
        f"tick 1 must be all-healthy, got {healthy_after_1}/{n_docs}"
    )

    # inject the anomalies for the warm mixed tick
    for kind, (hist, cur, truth) in fleets.items():
        b, f, _ = hist.shape
        for i in range(b - 1):  # last doc per kind stays clean
            doc_id = f"{kind}-{i}"
            for m in range(f):
                cu = f"http://prom/cur?q=m{m}:{doc_id}&step=60"
                source.data[cu] = (ct, cur[i, m])
    assert worker.tick(now=now + 60) == n_docs

    tp = {k: 0 for k in fleets}
    fp = dict(tp)
    fn = dict(tp)
    false_alarms = 0
    for doc_id, kind in doc_kind.items():
        doc = store._docs[doc_id]
        if doc_id in clean_docs:
            if doc.status != STATUS_PREPROCESS_COMPLETED:
                false_alarms += 1
            continue
        truth = doc_truth[doc_id]
        want = {float(t) for t, is_a in zip(ct, truth) if is_a}
        got = set()
        if doc.status == STATUS_COMPLETED_UNHEALTH:
            for pairs in doc.anomaly_info["values"].values():
                got.update(pairs[0::2])
        tp[kind] += len(got & want)
        fp[kind] += len(got - want)
        fn[kind] += len(want - got)
    by_kind = {
        k: (prf1(tp[k], fp[k], fn[k])[2], tp[k] + fn[k]) for k in fleets
    }
    return by_kind, false_alarms


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--small", action="store_true")
    args = ap.parse_args(argv)
    from foremast_tpu.device import enable_compile_cache

    enable_compile_cache()
    _register_models()
    b = 32 if args.small else 256
    th = 240 if args.small else 1008  # ~10-42 cycles of the 24-step season
    tc = 30
    for kind in ("flat", "seasonal", "trend", "shift"):
        # one draw + one batch per scenario: every algorithm judges the
        # exact same arrays
        hist, cur, truth = gen(kind, b, th, tc)
        batch = make_batch(hist, cur)
        for algo in ALGORITHMS:
            f1, p, r = score_algorithm(batch, truth, algo)
            print(
                json.dumps(
                    {
                        "scenario": kind,
                        "algorithm": algo,
                        "f1": round(f1, 3),
                        "precision": round(p, 3),
                        "recall": round(r, 3),
                    }
                ),
                flush=True,
            )
    # The reference's real workload shape: a DAILY cycle (m=1440 at the
    # 60 s step) over the full 7-day history. The global-mean default must
    # swallow the whole cycle in its band; the auto screen must route
    # these series to a pooled structured fit (fit_auto_univariate
    # docstring) and keep point F1 >= 0.99.
    db = 8 if args.small else 128
    for daily_kind, label in (
        ("seasonal", "daily-1440"),
        # sharp cron-style bursts: the pooled phase-means candidate's
        # scenario (low-order Fourier cannot represent the shape)
        ("sharp-seasonal", "daily-1440-sharp"),
    ):
        hist, cur, truth = gen(daily_kind, db, TH_DAILY, tc, period=PERIOD_DAILY)
        batch = make_batch(hist, cur)
        for algo in ("moving_average_all", "auto_univariate", "seasonal", "phase_means"):
            f1, p, r = score_algorithm(batch, truth, algo, season_length=PERIOD_DAILY)
            print(
                json.dumps(
                    {
                        "scenario": label,
                        "algorithm": algo,
                        "f1": round(f1, 3),
                        "precision": round(p, 3),
                        "recall": round(r, 3),
                    }
                ),
                flush=True,
            )
    mf1, mp, mr, by_kind = fleet_mix(b, th, tc)
    print(
        json.dumps(
            {
                "scenario": "fleet-mix",
                "algorithm": "auto_univariate",
                "f1": round(mf1, 3),
                "precision": round(mp, 3),
                "recall": round(mr, 3),
                "per_kind_f1": by_kind,
            }
        ),
        flush=True,
    )
    # mixed WORKER tick: every univariate shape + bivariate + LSTM jobs
    # in ONE claim set under the `auto` selector (VERDICT r4 #5)
    mixed_by_kind, mixed_fa = mixed_fleet_tick(
        4 if args.small else 12,
        3 if args.small else 8,
        th,
        tc,
    )
    print(
        json.dumps(
            {
                "scenario": "mixed-worker-tick",
                "algorithm": "auto",
                "per_kind_f1": {
                    k: round(v[0], 3) for k, v in mixed_by_kind.items()
                },
                "clean_doc_false_alarms": mixed_fa,
            }
        ),
        flush=True,
    )
    jb = 16 if args.small else 64  # LSTM trains one model per job
    fa, n_jobs = joint_clean_false_alarms(jb, th, tc)
    print(
        json.dumps(
            {
                "scenario": "joint-clean-windows",
                "algorithm": "lstm_autoencoder",
                "job_false_alarms": fa,
                "jobs": n_jobs,
                "false_alarms_per_10k_jobs": round(fa / n_jobs * 10_000, 1),
            }
        ),
        flush=True,
    )
    for kind in JOINT_SCENARIOS:
        p, r, f1 = score_joint(kind, jb, th, tc)
        print(
            json.dumps(
                {
                    "scenario": f"joint-{kind}",
                    "algorithm": (
                        "bivariate_normal" if kind == "bivariate"
                        else "lstm_autoencoder"
                    ),
                    "f1": round(f1, 3),
                    "precision": round(p, 3),
                    "recall": round(r, 3),
                }
            ),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
