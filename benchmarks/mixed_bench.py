"""Mixed-fleet benchmark suite — `make bench-mixed` (ISSUE 4 + ISSUE 14).

Four phases, one JSON line each:

  1. **joint** — the round-7 condition: 15% joint (bivariate/LSTM-
     hybrid) docs under the `auto` selector, warm throughput with the
     joint docs on the columnar path (worker_bench --joint-frac).
  2. **canary** — the ISSUE 14 headline: a canary-HEAVY fleet (>= 50%
     baseline-carrying docs) judged twice on identical fleets — the
     columnar canary bucket (default) vs the object path
     (`worker._canary_fast = False`) — with IN-RUN asserts:
     statuses byte-identical after every tick, warm throughput >= 3x
     the object arm, and >= 12.5k windows/s/chip (full shapes only;
     CPU-host proxy for the per-chip bar, like rounds 7-15).
  3. **scenario matrix** — strategy x regime point-F1 sweep
     (benchmarks/scenarios.py), floors asserted in-run; extends the
     `fleet_mix` table with the strategy dimension.
  4. **fan-in** — the canary fleet fed PURE-PUSH through the real
     ingest receiver by 1 vs 8 concurrent pushers (scenarios.
     FAN_IN_SHAPES): per-shape receiver apply rate, a warm tick judged
     entirely from the ring (zero HTTP by construction — the source has
     no fallback), and statuses asserted IDENTICAL across fan-in shapes
     (fan-in is a wire topology, never a semantics).

Usage: python -m benchmarks.mixed_bench [--services N] [--ticks K]
       [--small] [--skip-joint]
"""

from __future__ import annotations

import argparse
import json
import threading
import time
import urllib.request

import numpy as np

from foremast_tpu.config import BrainConfig
from foremast_tpu.jobs.worker import BrainWorker

NOW = 1_760_000_000.0

# in-run bars (full shapes only): the ISSUE 14 acceptance criteria
CANARY_SPEEDUP_BAR = 3.0
CANARY_WPS_PER_CHIP_BAR = 12_500.0
# scenario-matrix F1 floors (seeded draws, so these are exact pins at
# the bench shape): the stair regime's recall is priced separately —
# spikes near a freshly-learned step hide inside the widened band
F1_FLOOR = 0.95
F1_FLOOR_STAIR = 0.85


def _statuses(store):
    return {
        d.id: (d.status, d.reason, d.anomaly_info)
        for d in store._docs.values()
    }


def run_canary(
    services: int,
    ticks: int,
    hist_len: int,
    cur_len: int,
    baseline_frac: float = 0.5,
    assert_bars: bool = True,
) -> dict:
    """Phase 2: canary-heavy fleet, three arms on identical fleets with
    byte parity asserted between every pair:

      * columnar   — the default: canary docs on the pairwise-active
        columnar bucket, baseline-less docs on the PAIRWISE_NONE one;
      * canary_off — `worker._canary_fast = False` (the pre-
        round-16 default: canary docs object, the rest columnar);
      * object     — the whole fleet on the per-task object path (the
        ~10k w/s path VERDICT r5 #9 pinned — the acceptance bar's
        denominator: "warm throughput >= 3x the object-path baseline
        on the same fleet").
    """
    from benchmarks.worker_bench import build_mixed_fleet

    def mk(arm: str):
        store, source, windows = build_mixed_fleet(
            services, hist_len, cur_len, NOW,
            baseline_frac=baseline_frac,
        )
        cfg = BrainConfig(
            algorithm="moving_average_all",
            season_steps=24,
            max_cache_size=4 * services + 64,
        )
        worker = BrainWorker(
            store, source, config=cfg, claim_limit=services,
            worker_id="canary-bench",
        )
        if arm == "canary_off":
            # the object path for canary docs: the bench clears the
            # worker's flag so one process measures all arms
            worker._canary_fast = False
        elif arm == "object":
            worker._fast_tick = lambda docs, now: (0, docs)
        return worker, store, sum(windows.values())

    # backend-compile witness over all three arms: each arm's cold tick
    # may compile (fresh shapes for that routing), its warm ticks must
    # not — a warm recompile is a dispatch cache-key leak (the static
    # recompile-hazard rule's runtime twin, docs/static-analysis.md)
    from foremast_tpu.analysis.recompile_witness import RecompileWitness

    wit = RecompileWitness()
    wit.install()
    arms = ("columnar", "canary_off", "object")
    results = {}
    stores = {}
    fast_kinds = None
    windows = 0
    try:
        for name in arms:
            worker, store, windows = mk(name)
            with wit.phase(f"{name}_cold"):
                t0 = time.perf_counter()
                n = worker.tick(now=NOW + 150)
                cold_s = time.perf_counter() - t0
            assert n == services, f"{name}: claimed {n} != {services}"
            rates = []
            # first warm tick per arm: the arm's pipelined warm path
            # compiles once here (process-global dispatch cache, so a
            # later arm may inherit an earlier arm's programs); the
            # remaining ticks must be pure cache hits
            with wit.phase(f"{name}_warmup"):
                t0 = time.perf_counter()
                n = worker.tick(now=NOW + 160)
                rates.append(windows / (time.perf_counter() - t0))
            assert n == services, f"{name}: claimed {n} != {services}"
            with wit.phase(f"{name}_warm"):
                for k in range(1, ticks):
                    t0 = time.perf_counter()
                    n = worker.tick(now=NOW + 160 + 10 * k)
                    dt = time.perf_counter() - t0
                    assert n == services, (
                        f"{name}: claimed {n} != {services}"
                    )
                    rates.append(windows / dt)
            wit.assert_zero(f"{name}_warm")
            results[name] = {
                "cold_tick_seconds": round(cold_s, 3),
                "warm_windows_per_sec": round(float(np.median(rates)), 1),
            }
            stores[name] = store
            if name == "columnar":
                fast_kinds = dict(worker._fast_kinds)
            worker.close()
    finally:
        wit.uninstall()

    # byte parity across every arm — the opt-out knob's contract AND
    # the columnar path's: same fleet, same verdicts, bit for bit
    ref = _statuses(stores["columnar"])
    for name in arms[1:]:
        other = _statuses(stores[name])
        assert other == ref, {
            k: (ref[k], other[k]) for k in ref if ref[k] != other[k]
        }
    n_canary = int(round(services * baseline_frac))
    assert fast_kinds["baseline"] > 0, fast_kinds
    speedup = (
        results["columnar"]["warm_windows_per_sec"]
        / results["object"]["warm_windows_per_sec"]
    )
    out = {
        "config": "w-canary-fleet-tick",
        "services": services,
        "windows": windows,
        "canary_services": n_canary,
        "baseline_frac": baseline_frac,
        "columnar": results["columnar"],
        "canary_columnar_off": results["canary_off"],
        "object_path": results["object"],
        "vs_canary_off": round(
            results["columnar"]["warm_windows_per_sec"]
            / results["canary_off"]["warm_windows_per_sec"],
            2,
        ),
        "fast_path_docs": fast_kinds,
        "equivalent": True,  # asserted above, all three arms
        "metric": "canary_warm_speedup_vs_object",
        "value": round(speedup, 2),
        "unit": "x",
        "recompiles": wit.snapshot(),
    }
    if assert_bars:
        assert speedup >= CANARY_SPEEDUP_BAR, (
            f"canary warm speedup {speedup:.2f}x under the "
            f"{CANARY_SPEEDUP_BAR}x bar: {results}"
        )
        wps = results["columnar"]["warm_windows_per_sec"]
        assert wps >= CANARY_WPS_PER_CHIP_BAR, (
            f"canary-heavy warm throughput {wps} w/s under the "
            f"{CANARY_WPS_PER_CHIP_BAR} w/s/chip bar"
        )
        out["bars"] = {
            "speedup_3x_vs_object": True,
            "wps_per_chip_12500": True,
        }
    return out


def run_scenarios(b: int, th: int, tc: int, assert_floors: bool = True):
    """Phase 3: the strategy x regime F1 matrix with in-run floors."""
    from benchmarks.scenarios import scenario_matrix

    rows = scenario_matrix(b, th, tc)
    if assert_floors:
        for row in rows:
            floor = F1_FLOOR_STAIR if row["regime"] == "stair" else F1_FLOOR
            assert row["f1"] >= floor, (row, floor)
    return rows


# -- phase 4: pusher fan-in over the real receiver -----------------------


def _build_push_fleet(services, hist_len, cur_len, baseline_frac, endpoint):
    """Canary fleet whose URLs are query_range-shaped (resolvable to
    ring series keys); returns (store, series) where series maps
    key -> (times, values) covering history + current + baseline."""
    from foremast_tpu.ingest.wire import canonical_series
    from foremast_tpu.jobs.models import Document
    from foremast_tpu.jobs.store import InMemoryStore
    from foremast_tpu.metrics.promql import prometheus_url

    rng = np.random.default_rng(0)
    store = InMemoryStore()
    series: dict[str, tuple] = {}
    t_now = int(NOW)
    ht = t_now - 86_400 * 7 + 60 * np.arange(hist_len, dtype=np.int64)
    ct = ht[-1] + 60 + 60 * np.arange(cur_len, dtype=np.int64)
    bt = ct - 3600
    end_time = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(t_now + 3600)
    )
    n_canary = int(round(services * baseline_frac))
    for s in range(services):
        cur_parts, hist_parts, base_parts = [], [], []
        for a in ("latency", "error5xx"):
            expr = f'job:{a}{{app="app{s}"}}'
            hv = rng.normal(1.0, 0.1, hist_len).astype(np.float32)
            cv = (
                1.0 + 0.05 * np.sin(np.arange(cur_len) / 3.0)
            ).astype(np.float32)
            series[canonical_series(expr)] = (
                np.concatenate([ht, ct]),
                np.concatenate([hv, cv]),
            )
            cur_parts.append(
                f"{a}== "
                + prometheus_url(
                    {"endpoint": endpoint, "query": expr,
                     "start": int(ct[0]), "end": int(ct[-1]), "step": 60}
                )
            )
            hist_parts.append(
                f"{a}== "
                + prometheus_url(
                    {"endpoint": endpoint, "query": expr,
                     "start": int(ht[0]), "end": int(ht[-1]), "step": 60}
                )
            )
            if s < n_canary:
                # baseline pods are their OWN series (different label
                # set), pushed like any other
                bexpr = f'job:{a}{{app="app{s}",track="baseline"}}'
                bv = (
                    1.0
                    + 0.05 * np.sin(np.arange(cur_len) / 3.0)
                    + rng.normal(0, 0.01, cur_len)
                ).astype(np.float32)
                series[canonical_series(bexpr)] = (bt, bv)
                base_parts.append(
                    f"{a}== "
                    + prometheus_url(
                        {"endpoint": endpoint, "query": bexpr,
                         "start": int(bt[0]), "end": int(bt[-1]),
                         "step": 60}
                    )
                )
        store.create(
            Document(
                id=f"job-{s}",
                app_name=f"app{s}",
                end_time=end_time,
                current_config=" ||".join(cur_parts),
                historical_config=" ||".join(hist_parts),
                baseline_config=" ||".join(base_parts),
                strategy="canary" if s < n_canary else "continuous",
            )
        )
    return store, series


def run_fanin(services, hist_len, cur_len, fan_in_shapes):
    """Phase 4: the canary fleet PURE-PUSH — series pushed through the
    real receiver by N concurrent pushers, judged from the ring with no
    fallback configured. Statuses must be identical across fan-in
    shapes (wire topology, not semantics); per-shape apply rate and the
    canary fast-path engagement are reported."""
    from foremast_tpu.ingest import RingSource, RingStore, start_ingest_server

    rows = []
    status_sets = []
    for fan_in in fan_in_shapes:
        store, series = _build_push_fleet(
            services, hist_len, cur_len, 0.5, "http://prom/api/v1/"
        )
        ring = RingStore.from_env()
        srv, _ = start_ingest_server(0, ring, host="127.0.0.1")
        port = srv.server_address[1]
        items = list(series.items())
        samples = sum(len(t) for t, _ in series.values())

        def push(worklist):
            batch = 64
            for i in range(0, len(worklist), batch):
                body = json.dumps(
                    {
                        "timeseries": [
                            {
                                "alias": key,
                                "times": t.tolist(),
                                "values": [float(x) for x in v],
                                "start": float(t[0]),
                            }
                            for key, (t, v) in worklist[i : i + batch]
                        ]
                    }
                ).encode()
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/api/v1/write",
                    data=body,
                    method="POST",
                )
                resp = urllib.request.urlopen(req)
                assert resp.status == 200
        t0 = time.perf_counter()
        try:
            if fan_in == 1:
                push(items)
            else:
                # collect per-thread failures and re-raise: a swallowed
                # push error would otherwise surface far away as a
                # status-parity assert, misattributing an ingest-push
                # failure to a judgment-semantics bug
                errors: list[BaseException] = []

                def worker(worklist):
                    try:
                        push(worklist)
                    except BaseException as e:  # noqa: BLE001 — re-raised below
                        errors.append(e)

                threads = [
                    threading.Thread(target=worker, args=(items[j::fan_in],))
                    for j in range(fan_in)
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                if errors:
                    raise RuntimeError(
                        f"{len(errors)} of {fan_in} pushers failed"
                    ) from errors[0]
            push_s = time.perf_counter() - t0
        finally:
            srv.shutdown()
        source = RingSource(ring)  # NO fallback: pure push, zero HTTP
        cfg = BrainConfig(
            algorithm="moving_average_all",
            season_steps=24,
            max_cache_size=4 * services + 64,
        )
        worker = BrainWorker(
            store, source, config=cfg, claim_limit=services,
            worker_id=f"fanin-{fan_in}",
        )
        assert worker.tick(now=NOW + 150) == services
        t0 = time.perf_counter()
        assert worker.tick(now=NOW + 200) == services
        warm_s = time.perf_counter() - t0
        assert worker._fast_kinds["baseline"] > 0, worker._fast_kinds
        worker.close()
        status_sets.append(_statuses(store))
        rows.append(
            {
                "config": "w-canary-fanin",
                "fan_in": fan_in,
                "services": services,
                "series": len(series),
                "samples": samples,
                "push_seconds": round(push_s, 3),
                "push_samples_per_sec": round(samples / push_s, 1),
                "warm_tick_seconds": round(warm_s, 3),
                "pure_push": True,
            }
        )
    first = status_sets[0]
    for shape_statuses in status_sets[1:]:
        assert shape_statuses == first, (
            "fan-in shape changed judgments — wire topology leaked "
            "into semantics"
        )
    for row in rows:
        row["equivalent_across_shapes"] = True
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--services", type=int, default=16_384)
    ap.add_argument("--ticks", type=int, default=5)
    ap.add_argument("--hist-len", type=int, default=10_080)
    ap.add_argument("--cur-len", type=int, default=30)
    ap.add_argument(
        "--skip-joint", action="store_true",
        help="skip the round-7 joint phase (canary/scenario focus)",
    )
    ap.add_argument(
        "--small", action="store_true", help="CPU smoke shapes (CI)"
    )
    args = ap.parse_args(argv)
    from foremast_tpu.device import enable_compile_cache

    enable_compile_cache()
    small = args.small
    if small:
        args.services = min(args.services, 64)
        args.hist_len = min(args.hist_len, 256)
        args.ticks = min(args.ticks, 2)

    # phase 1: joint mixed fleet (round 7's condition, unchanged)
    if not args.skip_joint:
        from benchmarks.worker_bench import run as run_joint

        joint = run_joint(
            max(args.services // 4, 16) if small else args.services,
            args.ticks,
            "auto",
            24,
            args.hist_len,
            args.cur_len,
            joint_frac=0.15,
        )
        joint["config"] = "w-mixed-fleet-tick"
        print(json.dumps(joint), flush=True)

    # phase 2: canary-heavy fleet, columnar vs object, bars in-run
    canary = run_canary(
        args.services,
        args.ticks,
        args.hist_len,
        args.cur_len,
        assert_bars=not small,
    )
    print(json.dumps(canary), flush=True)

    # phase 3: scenario matrix (floors in-run at every shape — the
    # seeded draws make them exact pins)
    b = 16 if small else 128
    th = 240 if small else 1008
    scenario_rows = []
    for row in run_scenarios(b, th, 30):
        row["config"] = "q-scenario-matrix"
        scenario_rows.append(row)
        print(json.dumps(row), flush=True)

    # phase 3b: label-shape routing/ownership cells (ISSUE 15
    # satellite — ROADMAP item 4's multi-cluster / multi-tenant
    # generator gap): doc↔series co-location and ownership spread must
    # be invariant across label shapes, asserted inside the cell
    from benchmarks.scenarios import LABEL_SHAPES, label_shape_routing_cell

    label_rows = []
    for shape in LABEL_SHAPES:
        row = label_shape_routing_cell(
            shape, services=64 if small else 1024
        )
        label_rows.append(row)
        print(json.dumps(row), flush=True)

    # phase 4: pusher fan-in shapes over the real receiver
    from benchmarks.scenarios import FAN_IN_SHAPES

    fan_services = 16 if small else 1024
    fan_hist = min(args.hist_len, 256) if small else 2048
    fanin_rows = run_fanin(
        fan_services, fan_hist, args.cur_len, FAN_IN_SHAPES
    )
    for row in fanin_rows:
        print(json.dumps(row), flush=True)
    from benchmarks.report import write_summary

    write_summary(
        "mixed",
        {
            "canary": canary,
            "scenario_matrix": scenario_rows,
            "label_shapes": label_rows,
            "fan_in": fanin_rows,
        },
        small=small,
        recompiles=canary.get("recompiles"),
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
