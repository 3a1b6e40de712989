"""Fleet-scale END-TO-END worker benchmark: `BrainWorker.tick` measured
through claim -> fetch -> judge -> write-back.

BASELINE.md's north star is "100k concurrent metric-windows scored/sec"
— scored by the SYSTEM, not by a kernel. The suite's config 3r measures
the shipped judge; this module measures the whole worker loop the way
the reference's brain runs it (`docs/guides/design.md:35-43`): a fake
job store holding one document per service (4 metric aliases each, the
reference's 4-metric monitor shape) and an in-memory metric source, so
the measured time is claim CAS + config decode + window fetch + batch
pack + device scoring + verdict decode + ES-document write-back — every
host byte the production loop pays, minus only real network latency.

The re-check loop is the steady state being measured: every document's
endTime is in the future, so each tick re-judges the same fleet
(status `preprocess_completed` -> claimable again), exactly like the
reference brain re-checking until endTime (`design.md:43`).

Usage: python -m benchmarks.worker_bench [--services N] [--ticks K]
       [--algorithm A] [--season M] [--small]
Prints one JSON line per phase (cold, warm steady state).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from foremast_tpu.config import BrainConfig
from foremast_tpu.jobs.models import (
    STATUS_PREPROCESS_COMPLETED,
    Document,
)
from foremast_tpu.jobs.store import InMemoryStore
from foremast_tpu.jobs.worker import BrainWorker
from foremast_tpu.metrics.source import MetricSource

ALIASES = ("latency", "error4xx", "error5xx", "tps")


class ArraySource(MetricSource):
    """Exact-match URL->series map: O(1) fetch, no parsing.

    ReplaySource's substring scan is O(routes) per fetch — fine for
    tests, quadratic at fleet scale. This source is the fake-Prometheus
    floor: the benchmark charges the worker for everything EXCEPT real
    HTTP latency."""

    concurrent_fetch = False

    def __init__(self):
        self.data: dict[str, tuple[np.ndarray, np.ndarray]] = {}

    def fetch(self, url: str):
        return self.data[url]


def _add_service(
    store, source, sid, ht, ct, hist_len, cur_len, end_time, rng,
    baseline=False,
):
    """Create one service's document + its 4 per-alias series. Returns
    (doc_id, urls) so churn can retire the service cleanly.

    `baseline=True` (ISSUE 14): the doc is CANARY-shaped — every alias
    also carries a baselineConfig URL serving a pre-deploy window of the
    same clean distribution (so the pairwise rank tests run every tick
    but don't reject: the healthy-canary steady state), exactly the
    reference's baseline-pods-vs-canary-pods headline query shape
    (metricsquery.go:111-116)."""
    cur_parts = []
    hist_parts = []
    base_parts = []
    urls = []
    for a in ALIASES:
        cur_url = f"http://prom/cur?q={a}:app{sid}&end={int(ct[0]) - 60}&step=60"
        hist_url = (
            f"http://prom/hist?q={a}:app{sid}"
            f"&end={ht[-1] + 60}&step=60"
        )
        # per-(service, alias) series so fits cannot alias each
        # other; current rides well inside the fitted band (+-0.5
        # sigma) so the fleet stays on the healthy re-check path —
        # Gaussian current tails would turn ~half the fleet
        # completed_unhealth (terminal) on the first tick
        hv = rng.normal(1.0, 0.1, hist_len).astype(np.float32)
        cv = (
            1.0
            + 0.05 * np.sin(np.arange(cur_len) / 3.0)
        ).astype(np.float32)
        source.data[cur_url] = (ct, cv)
        source.data[hist_url] = (ht, hv)
        urls.extend((cur_url, hist_url))
        cur_parts.append(f"{a}== {cur_url}")
        hist_parts.append(f"{a}== {hist_url}")
        if baseline:
            base_url = f"http://prom/base?q={a}:app{sid}&step=60"
            # the baseline pods' window: same signal family with its
            # own noise draw — same distribution, so the rank tests
            # hold (differs=False) and the canary stays healthy
            bv = (
                1.0
                + 0.05 * np.sin(np.arange(cur_len) / 3.0)
                + rng.normal(0, 0.01, cur_len)
            ).astype(np.float32)
            source.data[base_url] = (ct - 3600, bv)
            urls.append(base_url)
            base_parts.append(f"{a}== {base_url}")
    doc = Document(
        id=f"job-{sid}",
        app_name=f"app{sid}",
        end_time=end_time,
        current_config=" ||".join(cur_parts),
        historical_config=" ||".join(hist_parts),
        baseline_config=" ||".join(base_parts),
        strategy="canary" if baseline else "continuous",
    )
    store.create(doc)
    return doc.id, urls


def _add_joint_service(
    store, source, sid, ht, ct, f, end_time, rng
):
    """One service of f co-moving metrics (m0..m{f-1}) whose clean
    current windows continue the historical latent — under the `auto`
    selector the doc routes to the bivariate (f=2) or LSTM-hybrid
    (f>=3) detector, or the univariate fallback (f=1), and stays on the
    healthy re-check path."""
    from benchmarks.quality import draw_comoving

    r = np.random.default_rng(int(rng.integers(0, 2**31)))
    hist = draw_comoving(r, 1, f, len(ht), 0)[0]  # [f, hist_len]
    cur = draw_comoving(r, 1, f, len(ct), len(ht))[0]
    cur_parts = []
    hist_parts = []
    for m in range(f):
        cur_url = f"http://prom/cur?q=m{m}:app{sid}&step=60"
        hist_url = (
            f"http://prom/hist?q=m{m}:app{sid}&end={ht[-1] + 60}&step=60"
        )
        source.data[cur_url] = (ct, cur[m])
        source.data[hist_url] = (ht, hist[m])
        cur_parts.append(f"m{m}== {cur_url}")
        hist_parts.append(f"m{m}== {hist_url}")
    doc = Document(
        id=f"job-{sid}",
        app_name=f"app{sid}",
        end_time=end_time,
        current_config=" ||".join(cur_parts),
        historical_config=" ||".join(hist_parts),
        strategy="continuous",
    )
    store.create(doc)
    return doc.id


def build_fleet(
    services: int,
    hist_len: int,
    cur_len: int,
    now: float,
    seed: int = 0,
):
    """One document per service x 4 aliases, re-check steady state."""
    store, source, _ = build_mixed_fleet(
        services, hist_len, cur_len, now, joint_frac=0.0, seed=seed
    )
    return store, source


def build_mixed_fleet(
    services: int,
    hist_len: int,
    cur_len: int,
    now: float,
    joint_frac: float = 0.0,
    seed: int = 0,
    baseline_frac: float = 0.0,
):
    """One document per service, re-check steady state.

    joint_frac = 0: every service is the reference's 4-alias monitor
    shape, scored per alias by the configured univariate algorithm.
    joint_frac > 0 (the ISSUE 4 mixed-fleet condition, run under the
    `auto` selector): that fraction of services are JOINT docs —
    alternating 2-alias bivariate and 4-alias LSTM-hybrid — and the
    REST are single-alias docs (under `auto`, metric count IS the model
    selector, so a 4-alias doc is itself a joint doc; the univariate
    share of a mixed auto fleet is its single-metric services).
    baseline_frac > 0 (the ISSUE 14 canary-heavy condition, univariate
    fleets only): that fraction of services are CANARY docs — every
    alias carries a baselineConfig window, so the doc judges through
    the pairwise rank tests each tick. Returns (store, source,
    windows_by_doc)."""
    if joint_frac > 0 and baseline_frac > 0:
        raise ValueError("joint_frac and baseline_frac are separate modes")
    rng = np.random.default_rng(seed)
    store = InMemoryStore()
    source = ArraySource()
    t_now = int(now)
    ht = t_now - 86_400 * 7 + 60 * np.arange(hist_len, dtype=np.int64)
    ct = ht[-1] + 60 + 60 * np.arange(cur_len, dtype=np.int64)
    # endTime one hour out: every tick lands in the keep-re-checking
    # branch (STATUS_PREPROCESS_COMPLETED), the production steady state
    end_time = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(t_now + 3600)
    )
    n_joint = int(round(services * joint_frac))
    n_canary = int(round(services * baseline_frac))
    windows_by_doc: dict[str, int] = {}
    for s in range(services):
        if joint_frac > 0 and s < n_joint:
            f = 2 if s % 2 == 0 else 4
            doc_id = _add_joint_service(
                store, source, str(s), ht, ct, f, end_time, rng
            )
            windows_by_doc[doc_id] = f
        elif joint_frac > 0:
            doc_id = _add_joint_service(
                store, source, str(s), ht, ct, 1, end_time, rng
            )
            windows_by_doc[doc_id] = 1
        else:
            doc_id, _ = _add_service(
                store, source, str(s), ht, ct, hist_len, cur_len,
                end_time, rng, baseline=s < n_canary,
            )
            windows_by_doc[doc_id] = len(ALIASES)
    return store, source, windows_by_doc


def run(
    services: int,
    ticks: int,
    algorithm: str,
    season: int,
    hist_len: int,
    cur_len: int,
    churn: float = 0.0,
    joint_frac: float = 0.0,
) -> dict:
    now = 1_760_000_000.0
    if joint_frac > 0 and churn > 0:
        raise ValueError("--churn and --joint-frac are separate modes")
    store, source, windows_by_doc = build_mixed_fleet(
        services, hist_len, cur_len, now, joint_frac=joint_frac
    )
    cfg = BrainConfig(
        algorithm=algorithm,
        season_steps=season,
        max_cache_size=4 * services + 64,
    )
    if joint_frac > 0:
        import dataclasses

        # joint detectors read the BASE threshold (their aliases match no
        # per-type rule); the quality scenarios calibrate them at 4 sigma
        # — at the deployed 2.0 default a clean fleet would page
        cfg = dataclasses.replace(
            cfg, anomaly=dataclasses.replace(cfg.anomaly, threshold=4.0)
        )
    worker = BrainWorker(
        store,
        source,
        config=cfg,
        claim_limit=services,
        worker_id="bench-worker",
    )
    windows = sum(windows_by_doc.values())

    from foremast_tpu.jobs.models import TERMINAL_STATUSES

    def open_count() -> int:
        with store._lock:
            return sum(
                1
                for d in store._docs.values()
                if d.status not in TERMINAL_STATUSES
            )

    # per-tick claimed WINDOW counts: mixed fleets carry 2/4 windows per
    # doc, so throughput must be measured in what was actually claimed
    claimed_windows: list[int] = []
    orig_claim = store.claim

    def _claim(worker_id, stuck, limit):
        docs = orig_claim(worker_id, stuck, limit)
        claimed_windows.append(
            sum(windows_by_doc.get(d.id, len(ALIASES)) for d in docs)
        )
        return docs

    store.claim = _claim

    # time-to-first-verdict: wrap the store's write path so the cold
    # tick's FIRST persisted judgment is timestamped (VERDICT r4 #7 —
    # progressive admission means a 16k-service cold tick should land
    # its first verdicts within one doc-chunk's work, not after the
    # whole fleet's fit)
    first_write = [None]
    orig_update, orig_many = store.update, store.update_many

    def _u(doc):
        if first_write[0] is None:
            first_write[0] = time.perf_counter()
        return orig_update(doc)

    def _um(docs):
        if first_write[0] is None and docs:
            first_write[0] = time.perf_counter()
        return orig_many(docs)

    store.update, store.update_many = _u, _um

    # Ticks start 150 s after job creation: the watcher builds each
    # historical range ending at deploy start (`metricsquery.go:65-72`),
    # so for the first ~2 min of a job's life the range is not yet
    # "settled" (HIST_SETTLED_SECONDS ingestion margin) and the worker
    # correctly refuses to cache series or fits. Production re-check
    # ticks — the steady state this measures — happen for the remaining
    # ~28 min of the job's 30-min window with settled histories.
    # cold: first tick pays fetch, pack, upload, fit, compile
    t0 = time.perf_counter()
    n = worker.tick(now=now + 150)
    cold_s = time.perf_counter() - t0
    first_verdict_s = (
        first_write[0] - t0 if first_write[0] is not None else cold_s
    )
    store.update, store.update_many = orig_update, orig_many
    assert n == services, f"claimed {n} != {services}"
    cold_windows = claimed_windows[0] if claimed_windows else windows

    # churn bookkeeping: retire the oldest live services, admit fresh
    # ones (new ids, new series) before each warm tick — the VERDICT r4
    # ask #4 regime where every tick mixes a few cold fits into the
    # warm fleet and bumps the fit-cache version
    rng = np.random.default_rng(1)
    t_now = int(now)
    ht = t_now - 86_400 * 7 + 60 * np.arange(hist_len, dtype=np.int64)
    ct = ht[-1] + 60 + 60 * np.arange(cur_len, dtype=np.int64)
    end_time = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(t_now + 3600)
    )
    live = [str(s) for s in range(services)]
    url_map = {}  # sid -> urls (lazy: only churned-in services tracked)
    next_sid = services
    n_churn = max(1, int(services * churn)) if churn > 0 else 0

    def apply_churn():
        nonlocal next_sid
        for _ in range(n_churn):
            sid = live.pop(0)
            with store._lock:
                store._docs.pop(f"job-{sid}", None)
            for u in url_map.pop(sid, ()):
                source.data.pop(u, None)
            nsid = str(next_sid)
            next_sid += 1
            did, urls = _add_service(
                store, source, nsid, ht, ct, hist_len, cur_len,
                end_time, rng,
            )
            windows_by_doc[did] = len(ALIASES)
            url_map[nsid] = urls
            live.append(nsid)

    # warm steady state: same fleet re-checked (hist + fit caches hot);
    # under --churn, each tick also fits n_churn cold newcomers
    times = []
    warm_rates = []
    for k in range(ticks):
        if n_churn:
            apply_churn()
        expected = open_count()
        t0 = time.perf_counter()
        n = worker.tick(now=now + 160 + 10 * k)
        dt = time.perf_counter() - t0
        times.append(dt)
        warm_rates.append(claimed_windows[-1] / dt)
        assert n == expected, f"claimed {n} != {expected}"
    warm_s = float(np.median(times))
    out = {
        "services": services,
        "windows": windows,
        "algorithm": algorithm,
        "cold_tick_seconds": round(cold_s, 3),
        "cold_first_verdict_seconds": round(first_verdict_s, 3),
        "cold_windows_per_sec": round(cold_windows / cold_s, 1),
        "warm_tick_seconds": round(warm_s, 3),
        "warm_windows_per_sec": round(float(np.median(warm_rates)), 1),
        "warm_ticks_measured": ticks,
    }
    if n_churn:
        out["churn_per_tick"] = n_churn
        counters = worker._uni.device_state_counters()
        out["arena_fallbacks"] = counters.get("fallbacks", 0)
    if joint_frac > 0:
        n_joint = int(round(services * joint_frac))
        # per-kind columnar doc counts: bivariate/lstm > 0 is the
        # acceptance proof that joint docs rode the fast path
        out["joint_services"] = n_joint
        out["joint_fraction"] = joint_frac
        out["fast_path_docs"] = dict(worker._fast_kinds)
        out["joint_arena"] = worker._mvj.joint_state_counters()
        # clean fleets should stay open; terminal docs here are joint
        # false alarms (priced by the quality benchmark's clean-window
        # scenario) — reported, never hidden
        out["terminal_docs"] = services - open_count()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--services", type=int, default=10_000)
    ap.add_argument("--ticks", type=int, default=3)
    ap.add_argument("--algorithm", default="moving_average_all")
    ap.add_argument("--season", type=int, default=24)
    ap.add_argument("--hist-len", type=int, default=10_080)
    ap.add_argument("--cur-len", type=int, default=30)
    ap.add_argument(
        "--churn",
        type=float,
        default=0.0,
        help="fraction of services retired + replaced before each warm "
        "tick (e.g. 0.1 = 10%% churn: that many cold fits per tick)",
    )
    ap.add_argument(
        "--joint-frac",
        type=float,
        default=0.0,
        help="fraction of services that are JOINT docs (alternating "
        "2-alias bivariate and 4-alias LSTM-hybrid) — the ISSUE 4 "
        "mixed-fleet mode; forces ML_ALGORITHM=auto semantics, so pair "
        "with --algorithm auto",
    )
    ap.add_argument(
        "--small", action="store_true", help="CPU smoke shapes (CI)"
    )
    ap.add_argument(
        "--profile",
        default=None,
        metavar="OUT.pstats",
        help="cProfile the warm ticks into OUT.pstats",
    )
    args = ap.parse_args(argv)
    from foremast_tpu.device import enable_compile_cache

    enable_compile_cache()
    if args.small:
        args.services = min(args.services, 128)
        args.hist_len = min(args.hist_len, 512)
    if args.joint_frac > 0:
        from foremast_tpu.engine.multivariate import MULTIVARIATE_ALGOS

        if args.algorithm not in MULTIVARIATE_ALGOS:
            args.algorithm = "auto"
    if args.profile:
        import cProfile

        # profile everything; cold-tick compile noise is excluded by
        # enabling only around the warm phase inside run() — simplest
        # honest alternative: profile a second run() whose compiles are
        # already cached in-process
        run(args.services, 1, args.algorithm, args.season,
            args.hist_len, args.cur_len)
        prof = cProfile.Profile()
        prof.enable()
        result = run(args.services, args.ticks, args.algorithm,
                     args.season, args.hist_len, args.cur_len,
                     churn=args.churn, joint_frac=args.joint_frac)
        prof.disable()
        prof.dump_stats(args.profile)
    else:
        result = run(args.services, args.ticks, args.algorithm,
                     args.season, args.hist_len, args.cur_len,
                     churn=args.churn, joint_frac=args.joint_frac)
    result["config"] = (
        "w-mixed-fleet-tick" if args.joint_frac > 0
        else "w-shipped-worker-tick"
    )
    result["metric"] = "warm_windows_per_sec"
    result["value"] = result["warm_windows_per_sec"]
    result["unit"] = "windows/s"
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
