"""Benchmark suite — the five BASELINE.md configs plus golden-trace F1.

`bench.py` at the repo root prints the single headline line the driver
records; this suite measures every BASELINE config individually:

  1. single-metric pairwise health check (latency)
  2. 4-metric joint score (latency + error4xx + error5xx + tps, Mann-Whitney)
  3. Holt-Winters seasonal forecaster anomaly bounds (fitted per series)
  4. LSTM-autoencoder multivariate detector (train + score)
  5. cluster-wide batch: 10k services x 4 metrics x 30-min windows
  F1. anomaly F1 on the spring-boot-demo canary trace (quality gate —
      the reference's CPU brain flags exactly the data2.txt spikes, so
      parity means F1 = 1.0 on this trace)

Usage: python -m benchmarks.suite [--small] [--config N]
Prints one JSON line per config, stamped with the device it ran on.
--small shrinks shapes for CPU smoke runs (CI); full shapes measure on a
single TPU chip only (without one the suite exits non-zero) — the v5e-8
north star (100k windows/sec) divides to 12.5k windows/sec/chip,
reported as `vs_target_per_chip` where windows/sec is the metric.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import prf1

PER_CHIP_TARGET = 100_000 / 8


def _bench(fn, *args, iters=5):
    """Compile, warm, then time `iters` dispatches (block at the end).

    For cheap-per-iteration programs pass a high `iters`: the fixed
    cost of a timed sequence (dispatch, the closing sync — bench.py
    rationale) must amortize for the number to reflect steady state
    rather than harness overhead."""
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


_EMITTED: list[dict] = []  # this process's rows, for the JSON summary
_DEVICE: dict = {}  # device_info(), stamped on every row (set by main)


def _emit(config, metric, value, unit, **extra):
    line = {"config": config, "metric": metric, "value": round(value, 2), "unit": unit}
    line.update(extra)
    line.update(_DEVICE)
    _EMITTED.append(line)
    print(json.dumps(line), flush=True)


def _score_batch(b, th, tc, seed=0):
    from foremast_tpu.parallel.batch import throughput_batch

    return jax.device_put(throughput_batch(b, th, tc, seed=seed))


# ---------------------------------------------------------------------------


def config1_single_metric_pairwise(small: bool):
    """Canary check on one metric per service: pairwise + MA bounds."""
    from foremast_tpu.engine import scoring

    b = 1024 if small else 8192
    batch = _score_batch(b, 512 if small else 10080, 10)
    dt = _bench(lambda x: scoring.score(x), batch, iters=5 if small else 100)
    wps = b / dt
    _emit(
        "1-single-metric-pairwise",
        "windows_per_sec",
        wps,
        "windows/s",
        vs_target_per_chip=round(wps / PER_CHIP_TARGET, 3),
    )


def config2_four_metric_joint(small: bool):
    """4 metrics per service, Mann-Whitney joint verdict."""
    from foremast_tpu.engine import scoring
    from foremast_tpu.config import PAIRWISE_MANN_WHITE

    services = 512 if small else 4096
    b = services * 4
    batch = _score_batch(b, 512 if small else 10080, 30)
    dt = _bench(
        lambda x: scoring.score(x, pairwise_algorithm=PAIRWISE_MANN_WHITE),
        batch,
        iters=5 if small else 100,
    )
    _emit(
        "2-four-metric-mann-whitney",
        "services_per_sec",
        services / dt,
        "services/s",
        windows_per_sec=round(b / dt, 1),
    )


def config3_holt_winters(small: bool):
    """Fitted Holt-Winters bounds (grid-search fit per series).

    Fit time tracks the sequential scan chain (T/m season steps) almost
    independently of batch width, so the fleet batch size is the lever:
    B=8192 windows amortize one scan the way a worker tick batching
    thousands of claimed jobs does."""
    from foremast_tpu.engine import scoring

    b = 128 if small else 8192
    th = 512 if small else 2016  # 7 d at 5-min resample: the scan length
    batch = _score_batch(b, th, 30)
    dt = _bench(lambda x: scoring.score(x, algorithm="holt_winters"), batch)
    wps = b / dt
    _emit(
        "3-holt-winters-bounds",
        "windows_per_sec",
        wps,
        "windows/s",
        scan_length=th,
        batch=b,
    )

    # re-check tick (SURVEY hard part (d)): warm fit cache -> no history
    # packing/upload/scan, only the judgment tail on the current window.
    # Measured through the SHIPPED path (HealthJudge.judge over MetricTasks,
    # host packing + decode included), not a device-resident shortcut.
    from foremast_tpu.config import BrainConfig
    from foremast_tpu.engine.judge import HealthJudge, MetricTask
    from foremast_tpu.models.cache import ModelCache

    rng = np.random.default_rng(0)
    hist_v = np.asarray(rng.normal(1.0, 0.2, (b, th)), np.float32)
    cur_v = np.asarray(rng.normal(1.0, 0.2, (b, 30)), np.float32)
    ht = 1_700_000_000 + 60 * np.arange(th, dtype=np.int64)
    ct = ht[-1] + 60 + 60 * np.arange(30, dtype=np.int64)
    tasks = [
        MetricTask(
            job_id=f"j{i}", alias="m", metric_type=None,
            hist_times=ht, hist_values=hist_v[i],
            cur_times=ct, cur_values=cur_v[i],
            fit_key=f"app{i}|m|u{i}",
        )
        for i in range(b)
    ]
    # season_steps pinned to the classic 24-step shape this config has
    # tracked since round 1 (config 3d measures the daily m=1440 path)
    judge = HealthJudge(BrainConfig(algorithm="holt_winters", season_steps=24))
    judge.judge(tasks[:8])  # compile
    t0 = time.perf_counter()
    judge.judge(tasks)  # cold shipped tick: pack + upload + fit + decode
    cold_dt = time.perf_counter() - t0
    judge.fit_cache = ModelCache(b + 1)
    judge.judge(tasks)  # fill the cache
    t0 = time.perf_counter()
    iters = 2
    for _ in range(iters):
        judge.judge(tasks)
    dt = (time.perf_counter() - t0) / iters
    _emit(
        "3-holt-winters-recheck",
        "windows_per_sec",
        b / dt,
        "windows/s",
        batch=b,
        cold_shipped_windows_per_sec=round(b / cold_dt, 1),
        engine_only_windows_per_sec=round(wps, 1),
    )


def config3d_daily_season(small: bool):
    """Daily-season scoring (ML_SEASON_STEPS=1440): the auto screen —
    global mean + Holt-Winters(1440) rolled scan + trend/Fourier seasonal
    — over full 7-day 10,080-pt histories (the reference's canonical
    workload, `metricsquery.go:43,75-77`). Small mode keeps the same code
    path (rolled HW: m > _HW_UNROLL_MAX) on CPU-feasible shapes."""
    from foremast_tpu.engine import scoring

    b = 64 if small else 2048
    th = 720 if small else 10_080
    m = 288 if small else 1440
    batch = _score_batch(b, th, 30)
    dt = _bench(
        lambda x: scoring.score(x, algorithm="auto_univariate", season_length=m),
        batch,
        iters=3 if small else 20,
    )
    wps = b / dt
    _emit(
        "3d-daily-season-auto",
        "windows_per_sec",
        wps,
        "windows/s",
        scan_length=th,
        season=m,
        batch=b,
    )


def config4_lstm_ae(small: bool):
    """LSTM-autoencoder fleet: train S per-service models, then score."""
    from foremast_tpu.models.lstm_ae import LSTMAEConfig, fit_many, score_many

    s = 32 if small else 256  # services (one model each)
    n_win, t_len, f = 8, 30, 4
    steps = 20 if small else 100
    cfg = LSTMAEConfig(features=f, hidden=16 if small else 32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0.5, 0.1, size=(s, n_win, t_len, f)).astype(np.float32))
    mask = jnp.ones((s, n_win, t_len), bool)

    t0 = time.perf_counter()
    params, mu, sd, _ = fit_many(jax.random.key(0), x, mask, cfg, steps=steps)
    jax.block_until_ready(mu)
    train_s = time.perf_counter() - t0

    dt = _bench(lambda *a: score_many(*a), params, x, mask, mu, sd, 3.0)
    wps = s * n_win / dt
    _emit(
        "4-lstm-autoencoder",
        "windows_scored_per_sec",
        wps,
        "windows/s",
        models_trained=s,
        train_steps=steps,
        train_seconds=round(train_s, 2),
    )

    # the hybrid judgment's closed-form companion (models/residual_mvn.py):
    # per-job HW fit + residual covariance over [S, F, Th], then causal
    # continuation + Mahalanobis over the current windows
    from foremast_tpu.models.residual_mvn import (
        chi2_quantile,
        fit_residual_mvn,
        score_residual_mvn,
    )

    th = 256 if small else 1024
    hist = jnp.asarray(
        rng.normal(0.5, 0.1, size=(s, f, th)).astype(np.float32)
    )
    cur = jnp.asarray(rng.normal(0.5, 0.1, size=(s, f, t_len)).astype(np.float32))
    t0 = time.perf_counter()
    state = fit_residual_mvn(hist)
    jax.block_until_ready(state.cov)
    fit_s = time.perf_counter() - t0
    cut = chi2_quantile(4.0, f)
    dt = _bench(lambda st, c: score_residual_mvn(st, c, cut), state, cur)
    _emit(
        "4b-residual-mvn",
        "windows_scored_per_sec",
        s / dt,
        "windows/s",
        jobs=s,
        hist_len=th,
        fit_seconds=round(fit_s, 2),
    )


def config5_cluster_batch(small: bool):
    """BASELINE config 5: 10k services x 4 metrics x 30-min windows.

    On one chip this is the per-chip share of the fleet; the driver's
    dryrun exercises the same program sharded over an 8-device mesh."""
    from foremast_tpu.engine import scoring

    services = 1250 if small else 10_000
    b = services * 4
    batch = _score_batch(b, 256 if small else 1440, 30)  # 1-day hist/window
    dt = _bench(lambda x: scoring.score(x), batch, iters=3 if small else 50)
    wps = b / dt
    _emit(
        "5-cluster-batch",
        "windows_per_sec",
        wps,
        "windows/s",
        services=services,
        vs_target_per_chip=round(wps / PER_CHIP_TARGET, 3),
    )


def config_f1_golden_trace(small: bool):
    """Quality gate: F1 on the demo canary traces (BASELINE 'CPU-parity
    anomaly F1'). data2.txt carries the injected spikes; every spike point
    must flag and nothing else (the reference demo's pass criterion —
    docs/guides/installation.md:84-143 runbook)."""
    import csv
    import os
    from datetime import datetime, timezone

    from foremast_tpu.engine.judge import HealthJudge, MetricTask

    data = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "tests", "data")

    def load(name):
        # rows are "YYYY-mm-dd HH:MM:SS,value" (the reference demo's
        # FileErrorGenerator trace format)
        ts, vs = [], []
        with open(os.path.join(data, name)) as f:
            for row in csv.reader(f):
                if row:
                    dt = datetime.strptime(row[0], "%Y-%m-%d %H:%M:%S")
                    ts.append(int(dt.replace(tzinfo=timezone.utc).timestamp()))
                    vs.append(float(row[1]))
        return np.asarray(ts, np.int64), np.asarray(vs, np.float32)

    nt, nv = load("demo_canary_normal.csv")
    st, sv = load("demo_canary_spike.csv")
    hist_t = np.concatenate([nt - 86400 * (i + 1) for i in range(6)])
    hist_v = np.tile(nv, 6)

    task = MetricTask(
        job_id="golden", alias="error5xx", metric_type="error5xx",
        hist_times=hist_t, hist_values=hist_v,
        cur_times=st, cur_values=sv,
        base_times=nt, base_values=nv,
    )
    (verdict,) = HealthJudge().judge([task])
    flagged = set(verdict.anomaly_pairs[0::2])
    truth = {float(t) for t, v in zip(st, sv) if v > 10.0}  # the 40.x spikes
    tp = len(flagged & truth)
    fp = len(flagged - truth)
    fn = len(truth - flagged)
    precision, recall, f1 = prf1(tp, fp, fn)
    _emit(
        "f1-golden-trace",
        "anomaly_f1",
        f1,
        "f1",
        precision=round(precision, 3),
        recall=round(recall, 3),
        spikes=len(truth),
    )


CONFIGS = {
    "1": config1_single_metric_pairwise,
    "2": config2_four_metric_joint,
    "3": config3_holt_winters,
    "3d": config3d_daily_season,
    "4": config4_lstm_ae,
    "5": config5_cluster_batch,
    "f1": config_f1_golden_trace,
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--small", action="store_true", help="CPU smoke shapes")
    ap.add_argument(
        "--config",
        default=None,
        choices=list(CONFIGS),
        help="run one config (1-5, f1)",
    )
    args = ap.parse_args(argv)
    from foremast_tpu.device import (
        device_info,
        enable_compile_cache,
        require_tpu,
    )

    enable_compile_cache()
    _DEVICE.update(device_info() if args.small else require_tpu())
    keys = [args.config] if args.config else list(CONFIGS)
    for k in keys:
        CONFIGS[k](args.small)
    from benchmarks.report import write_summary

    write_summary("suite", {"configs": list(_EMITTED)}, small=args.small)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
