"""Cold-start benchmark: ring-resident cold fits, short-history
admission, background refinement (ISSUE 10).

Rounds 5/8 left the cold/churn path as the last order-of-magnitude
bound: a 16k daily-season COLD tick paid a full 7-day history
fetch+upload per doc, and a 10%-churn tick re-paid the churned
fraction's share every tick. The ingest ring already holds
that history resident — this benchmark measures the tentpole that lets
cold fits read it from there:

  * **pull-cold** — the round-5 baseline: PrometheusSource against a
    real localhost query_range server, fleet-cold tick (HTTP fetch +
    pack + upload + fit per doc);
  * **ring-cold** — same fleet, same samples, ring-resident: the cold
    tick's historical windows come straight off ring columns
    (`RingSource.hist_columns`), ZERO HTTP — asserted in-run against
    the fake Prometheus's request counter, along with byte-identical
    statuses vs the pull worker;
  * **churn** — 10% of services retired and replaced before a warm
    tick (their series already pushed, the ingest-plane steady state):
    the cold fits ride ring columns, zero HTTP — asserted;
  * **newcomers** — services with only ~2 days of pushed coverage get
    verdict-capable PROVISIONAL fits in their first tick
    (short-history admission, `FOREMAST_ADMIT_MIN_COVERAGE_SECONDS`) —
    non-UNKNOWN verdicts asserted via the on_verdict hook;
  * **refinement** — coverage then closes the newcomers' windows and
    steady ticks drain the provisional book in bounded batches; the
    refined fits are asserted BYTE-IDENTICAL to a fresh worker's
    from-scratch fits on the same ring (band parity).

Acceptance bars (asserted in-run at the full 16k daily-season shape;
reported informationally at smaller shapes): ring-cold tick <= 120 s,
churn tick <= 8 s, first verdict <= 10 s.

Usage: python -m benchmarks.cold_bench [--services N] [--hist-len H]
       [--algorithm A] [--season M] [--newcomers K] [--small]
Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks.ingest_bench import FakePrometheus, build_fleet
from foremast_tpu.config import BrainConfig
from foremast_tpu.engine import UNKNOWN
from foremast_tpu.ingest import RingSource, RingStore
from foremast_tpu.ingest.wire import canonical_series
from foremast_tpu.jobs.models import Document, TERMINAL_STATUSES
from foremast_tpu.jobs.store import InMemoryStore
from foremast_tpu.jobs.worker import BrainWorker
from foremast_tpu.metrics.promql import prometheus_url
from foremast_tpu.metrics.source import PrometheusSource

NOW = 1_760_000_000.0
ALIASES = 4
# full-shape acceptance bars (ISSUE 10)
FULL_SERVICES = 16_384
FULL_HIST = 10_080
BAR_COLD_SECONDS = 120.0
BAR_CHURN_SECONDS = 8.0
BAR_FIRST_VERDICT_SECONDS = 10.0


def _statuses(store):
    return {
        d.id: (d.status, d.reason, d.anomaly_info)
        for d in store._docs.values()
    }


def _mk_worker(store, source, services, cfg, hook=None):
    return BrainWorker(
        store, source, config=cfg, claim_limit=services,
        worker_id="cold-bench", on_verdict=hook,
    )


def _first_write_probe(store):
    """Wrap the store's write path to timestamp the first persisted
    judgment (time-to-first-verdict, VERDICT r4 #7)."""
    first = [None]
    orig_update, orig_many = store.update, store.update_many

    def _u(doc):
        if first[0] is None:
            first[0] = time.perf_counter()
        return orig_update(doc)

    def _um(docs):
        if first[0] is None and docs:
            first[0] = time.perf_counter()
        return orig_many(docs)

    store.update, store.update_many = _u, _um

    def unwrap():
        store.update, store.update_many = orig_update, orig_many
        return first[0]

    return unwrap


def _push_fake_into_ring(ring, fake, start):
    """The pusher's steady state: every series the fleet monitors is
    resident with full coverage (direct push API — the receiver wire
    path is priced by `make bench-ingest`)."""
    for key, (t, v) in fake.data.items():
        ring.push(key, t, v, start=float(start), now=NOW)


def _add_churn_services(store, fake, ring, endpoint, count, hist_len,
                        cur_len, seed):
    """Retire the oldest `count` open docs and admit `count` fresh
    services whose series are already pushed (ring + fake agree)."""
    rng = np.random.default_rng(seed)
    t_now = int(NOW)
    ht = t_now - 86_400 * 7 + 60 * np.arange(hist_len, dtype=np.int64)
    ct = ht[-1] + 60 + 60 * np.arange(cur_len, dtype=np.int64)
    end_time = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(t_now + 3600)
    )
    with store._lock:
        open_ids = [
            d.id for d in store._docs.values()
            if d.status not in TERMINAL_STATUSES
        ][:count]
        for did in open_ids:
            store._docs.pop(did, None)
    names = ("latency", "error5xx", "tps", "cpu")[:ALIASES]
    for k in range(count):
        app = f"churn{seed}-{k}"
        cur_parts, hist_parts = [], []
        for a in names:
            expr = (
                f"namespace_app_per_pod:{a}"
                f'{{namespace="bench",app="{app}"}}'
            )
            key = canonical_series(expr)
            hv = rng.normal(1.0, 0.1, hist_len).astype(np.float32)
            cv = (
                1.0 + 0.05 * np.sin(np.arange(cur_len) / 3.0)
            ).astype(np.float32)
            t_all = np.concatenate([ht, ct])
            v_all = np.concatenate([hv, cv])
            fake.data[key] = (t_all, v_all)
            ring.push(key, t_all, v_all, start=float(ht[0]), now=NOW)
            cur_parts.append(
                f"{a}== " + prometheus_url(
                    {"endpoint": endpoint, "query": expr,
                     "start": int(ct[0]), "end": int(ct[-1]), "step": 60}
                )
            )
            hist_parts.append(
                f"{a}== " + prometheus_url(
                    {"endpoint": endpoint, "query": expr,
                     "start": int(ht[0]), "end": int(ht[-1]), "step": 60}
                )
            )
        store.create(
            Document(
                id=f"churn-{seed}-{k}",
                app_name=app,
                end_time=end_time,
                current_config=" ||".join(cur_parts),
                historical_config=" ||".join(hist_parts),
                strategy="continuous",
            )
        )
    return count


def _newcomer_docs(ring, count, coverage_seconds, seed=11):
    """Newcomer services: docs request the full 7-day history, the
    ring holds only `coverage_seconds` of live pushes (pure-push
    world: the fallback has nothing more for a true newcomer)."""
    rng = np.random.default_rng(seed)
    store = InMemoryStore()
    base = int(NOW)
    t1 = base - 1000
    t0 = t1 - 7 * 86_400
    # pushes stop SHORT of the requested window's head (within the
    # staleness slack), so the admitted fit is genuinely PROVISIONAL —
    # in-window data can still arrive and refinement has work to do
    push_end = t1 - 200
    push0 = push_end - int(coverage_seconds)
    end_time = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(base + 3600)
    )
    endpoint = "http://prom/api/v1/"
    keys = []
    for s in range(count):
        expr = (
            f'namespace_app_per_pod:latency{{namespace="bench",app="nc{s}"}}'
        )
        key = canonical_series(expr)
        keys.append(key)
        pt = np.arange(push0, push_end + 1, 60, dtype=np.int64)
        pv = rng.normal(1.0, 0.1, len(pt)).astype(np.float32)
        ring.push(key, pt, pv, now=NOW)
        cur_t1 = push_end - 60
        cur_t0 = cur_t1 - 28 * 60
        cur_url = prometheus_url(
            {"endpoint": endpoint, "query": expr, "start": int(cur_t0),
             "end": int(cur_t1), "step": 60}
        )
        hist_url = prometheus_url(
            {"endpoint": endpoint, "query": expr, "start": int(t0),
             "end": int(t1), "step": 60}
        )
        store.create(
            Document(
                id=f"nc-{s}",
                app_name=f"nc{s}",
                end_time=end_time,
                current_config=f"latency== {cur_url}",
                historical_config=f"latency== {hist_url}",
                strategy="continuous",
            )
        )
    return store, keys, t1


def run(services, hist_len, cur_len, algorithm, season, newcomers,
        churn_frac=0.1, full_bars=False) -> dict:
    fake = FakePrometheus()
    endpoint = fake.start()
    cfg = BrainConfig(
        algorithm=algorithm,
        season_steps=season,
        max_cache_size=ALIASES * services + newcomers + 64,
    )
    try:
        # -- phase 1: pull-cold baseline (the round-5 regime) ----------
        pull_store = build_fleet(
            services, ALIASES, hist_len, cur_len, endpoint, fake
        )
        pull_worker = _mk_worker(
            pull_store, PrometheusSource(), services, cfg
        )
        t0 = time.perf_counter()
        n = pull_worker.tick(now=NOW + 150)
        pull_cold_s = time.perf_counter() - t0
        assert n == services, f"pull cold claimed {n} != {services}"
        pull_statuses = _statuses(pull_store)
        pull_worker.close()

        # -- phase 2: ring-cold (tentpole) -----------------------------
        # size the ring to the fleet (docs/operations.md "Ingest
        # plane" sizing rule: 12 B/pt at pow2 capacities — residency
        # is a host-RAM budget, and an under-budgeted ring evicts the
        # very histories this benchmark measures reading)
        pow2_pts = 256
        while pow2_pts < hist_len + cur_len:
            pow2_pts *= 2
        n_series = ALIASES * (services + services // 10) + ALIASES
        # 3x: the budget is a CAP (no allocation behind it), and crc32
        # shard skew at small fleets needs slack per shard slice
        budget = 3 * n_series * pow2_pts * 12
        ring = RingStore(budget_bytes=budget, max_points=pow2_pts)
        t_hist0 = int(NOW) - 86_400 * 7
        _push_fake_into_ring(ring, fake, start=t_hist0)
        ring_store = build_fleet(
            services, ALIASES, hist_len, cur_len, endpoint, fake
        )
        reqs_before = fake.requests
        source = RingSource(ring, fallback=PrometheusSource())
        ring_worker = _mk_worker(ring_store, source, services, cfg)
        unwrap = _first_write_probe(ring_store)
        t0 = time.perf_counter()
        n = ring_worker.tick(now=NOW + 150)
        ring_cold_s = time.perf_counter() - t0
        first_w = unwrap()
        first_verdict_s = (first_w - t0) if first_w else ring_cold_s
        assert n == services, f"ring cold claimed {n} != {services}"
        zero_http_cold = fake.requests == reqs_before
        assert zero_http_cold, (
            f"ring-cold tick touched HTTP: {fake.requests - reqs_before} "
            "fetches (the ring covers every window — the bar is zero)"
        )
        assert _statuses(ring_store) == pull_statuses, (
            "ring-cold judgments diverged from the pull path"
        )
        cold_reads = ring_worker.debug_state()["cold_start"]["hist_reads"]
        assert cold_reads["ring_full"] >= services * ALIASES, cold_reads

        # -- phase 3: churn tick (10% cold fits from the ring) ---------
        n_churn = max(1, int(services * churn_frac))
        _add_churn_services(
            ring_store, fake, ring, endpoint, n_churn, hist_len,
            cur_len, seed=1,
        )
        reqs_before = fake.requests
        t0 = time.perf_counter()
        n = ring_worker.tick(now=NOW + 300)
        churn_s = time.perf_counter() - t0
        assert n == services, f"churn tick claimed {n} != {services}"
        zero_http_churn = fake.requests == reqs_before
        assert zero_http_churn, "churn tick touched HTTP"
        ring_worker.close()

        # -- phase 4: short-history newcomer admission -----------------
        nc_ring = RingStore.from_env()
        coverage = 2 * 86_400 if hist_len >= 2880 else 7_200.0
        floor = min(86_400.0, coverage / 2)
        nc_store, nc_keys, nc_t1 = _newcomer_docs(
            nc_ring, newcomers, coverage
        )
        nc_source = RingSource(nc_ring, fallback=None, admit_floor=floor)
        verdicts = {}
        nc_worker = _mk_worker(
            nc_store, nc_source, newcomers, cfg,
            hook=lambda d, vs: verdicts.setdefault(d.id, []).extend(vs),
        )
        t0 = time.perf_counter()
        n = nc_worker.tick(now=NOW + 150)
        nc_tick_s = time.perf_counter() - t0
        assert n == newcomers
        unknown = sum(
            1 for vs in verdicts.values()
            if all(v.verdict == UNKNOWN for v in vs)
        )
        assert unknown == 0, (
            f"{unknown}/{newcomers} newcomers UNKNOWN on their first "
            "tick — short-history admission did not engage"
        )
        pending = len(nc_worker._refine_book)
        assert pending == newcomers, (pending, newcomers)

        # -- phase 5: background refinement + band parity --------------
        rng = np.random.default_rng(12)
        for key in nc_keys:
            # the window head fills in: coverage closes the window
            tail = np.arange(
                nc_t1 - 140, nc_t1 + 121, 60, dtype=np.int64
            )
            nc_ring.push(
                key, tail,
                rng.normal(1.0, 0.1, len(tail)).astype(np.float32),
                now=NOW,
            )
        budget = max(1, newcomers // 4)
        nc_worker.refine_docs_per_tick = budget
        refine_ticks = 0
        k = 0
        while len(nc_worker._refine_book) and refine_ticks < 64:
            k += 1
            nc_worker.tick(now=NOW + 150 + 10 * k)
            refine_ticks += 1
        assert not len(nc_worker._refine_book), "refine book never drained"
        k += 1
        nc_worker.tick(now=NOW + 150 + 10 * k)  # terminal refits land

        fresh_store, _, _ = _newcomer_docs(nc_ring, newcomers, coverage)
        fresh = _mk_worker(fresh_store, nc_source, newcomers, cfg)
        fresh.tick(now=NOW + 150 + 10 * k)
        mismatched = 0
        compared = 0
        for fkey, entry in list(nc_worker._fit_cache._d.items()):
            other = fresh._fit_cache.peek(fkey)
            if other is None:
                mismatched += 1
                continue
            compared += 1
            for a, b in zip(entry, other):
                if not np.array_equal(np.asarray(a), np.asarray(b)):
                    mismatched += 1
                    break
        band_parity = mismatched == 0 and compared >= newcomers
        assert band_parity, (
            f"refined fits diverged from from-scratch fits "
            f"({mismatched} mismatched / {compared} compared)"
        )
        refine_counts = nc_worker._refine_book.debug_state()
        nc_worker.close()
        fresh.close()

        if full_bars:
            assert ring_cold_s <= BAR_COLD_SECONDS, ring_cold_s
            assert churn_s <= BAR_CHURN_SECONDS, churn_s
            assert first_verdict_s <= BAR_FIRST_VERDICT_SECONDS, (
                first_verdict_s
            )

        return {
            "config": "c-cold-ring-tick",
            "services": services,
            "windows": services * ALIASES,
            "hist_len": hist_len,
            "algorithm": algorithm,
            "season": season,
            "pull_cold_tick_seconds": round(pull_cold_s, 2),
            "ring_cold_tick_seconds": round(ring_cold_s, 2),
            "cold_speedup": round(pull_cold_s / ring_cold_s, 2),
            "first_verdict_seconds": round(first_verdict_s, 3),
            "churn_docs": n_churn,
            "churn_tick_seconds": round(churn_s, 2),
            "zero_http_cold": zero_http_cold,
            "zero_http_churn": zero_http_churn,
            "newcomers": newcomers,
            "newcomer_tick_seconds": round(nc_tick_s, 3),
            "newcomer_unknown": unknown,
            "refine_ticks_to_drain": refine_ticks,
            "refine_counts": refine_counts,
            "band_parity": band_parity,
            "bars_asserted": full_bars,
            "metric": "ring_cold_tick_seconds",
            "value": round(ring_cold_s, 2),
            "unit": "s",
        }
    finally:
        fake.stop()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--services", type=int, default=FULL_SERVICES)
    ap.add_argument("--hist-len", type=int, default=FULL_HIST)
    ap.add_argument("--cur-len", type=int, default=30)
    ap.add_argument("--algorithm", default="phase_means")
    ap.add_argument("--season", type=int, default=1440)
    ap.add_argument("--newcomers", type=int, default=512)
    ap.add_argument("--churn", type=float, default=0.1)
    ap.add_argument(
        "--small", action="store_true", help="CPU smoke shapes (CI)"
    )
    args = ap.parse_args(argv)
    from foremast_tpu.device import enable_compile_cache

    enable_compile_cache()
    if args.small:
        args.services = min(args.services, 24)
        args.hist_len = min(args.hist_len, 512)
        args.season = min(args.season, 24)
        args.newcomers = min(args.newcomers, 4)
        if args.algorithm == "phase_means":
            args.algorithm = "moving_average_all"
    full_bars = (
        args.services >= FULL_SERVICES and args.hist_len >= FULL_HIST
    )
    result = run(
        args.services, args.hist_len, args.cur_len, args.algorithm,
        args.season, args.newcomers, churn_frac=args.churn,
        full_bars=full_bars,
    )
    print(json.dumps(result), flush=True)
    from benchmarks.report import write_summary

    write_summary("cold", result, small=args.small)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
