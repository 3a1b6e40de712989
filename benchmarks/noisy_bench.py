"""Noisy-neighbor QoS benchmark (ISSUE 20 round 20).

PR 15 proved routing is label-shape-invariant; this round proves the
QoS plane makes tenancy a SCHEDULING dimension. The fleet is the
`noisy_neighbor` tenant regime from `benchmarks.scenarios`: one whale
tenant owns NOISY_FACTOR x every quiet tenant's share of services, and
during the measured phase it floods the REAL ingest receiver far past
its byte-rate envelope. Three claims, asserted in-run:

  * **isolation** — the quiet tenants' anomaly injections (pushed
    through the same receiver, judged by the same worker) keep the
    push→verdict latency and F1 they had in a SOLO control run with no
    whale at all: p99 within 1.5x (+250 ms grace) of control and F1
    byte-equal. Weighted-fair claim ordering (dirty-set drain + sweep
    pool, equal weights — fairness, not hand-tuned throttling) plus
    ring-byte envelopes are what hold the line.
  * **targeted backpressure** — every 429 + Retry-After lands on the
    whale's pushes; the quiet tenants' POSTs all answer 200 and their
    shed counter stays zero. The whale's series evictions are charged
    to the whale; the quiet tenants' warm series stay resident.
  * **attribution** — the run's per-tenant ledger (sheds, evictions,
    claims, resident ring bytes) is visible in GET /debug/state's
    `tenants` section and exported as `foremast_tenant_*`; the bench
    pins the end-state snapshot into BENCH_rNN.json (`tenants` key).

A fourth phase pins the PARITY contract: with zero or one tenant
configured, statuses/reasons/anomaly payloads on the sliced warm path
are byte-identical between an untenanted worker and a single-tenant
registry — the QoS plane reorders claims and redirects pressure, it
never changes a verdict.

Usage: python -m benchmarks.noisy_bench [--services N] [--inject K]
       [--small]
Prints one JSON line.
"""

from __future__ import annotations

import argparse
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np

from benchmarks.latency_bench import (
    CUR_LEN,
    HIST_LEN,
    STEP,
    _await_status,
    mk_worker,
)
from benchmarks.scenarios import WHALE_TENANT, tenant_fleet, tenant_weighted_specs
from foremast_tpu.ingest import (
    RingStore,
    canonical_series,
    start_ingest_server,
    stop_ingest_server,
)
from foremast_tpu.jobs.models import (
    STATUS_COMPLETED_UNHEALTH,
    Document,
)
from foremast_tpu.jobs.store import InMemoryStore
from foremast_tpu.metrics.promql import prometheus_url
from foremast_tpu.reactive import DirtySet
from foremast_tpu.tenant import (
    TenantRegistry,
    TenantSpec,
    accounting_for,
    set_tenancy,
)

TENANTS = 4
# quiet-tenant QoS bars (full shape): p99 within this factor of the
# solo control (plus an absolute grace for scheduler jitter at small
# sample counts), F1 exactly equal
P99_FACTOR = 1.5
P99_GRACE_S = 0.25
# uniform per-tenant envelopes: rate low enough that the whale's flood
# trips admission within one batch, ring slice big enough that the
# quiet tenants' warm series never evict
INGEST_BYTES_PER_S = 64 * 1024
RING_BYTES_PER_TENANT = 8 << 20


def _expr(s: int, tenant: str) -> str:
    return (
        f'latency{{app="app{s}",namespace="bench",tenant="{tenant}"}}'
    )


def build_fleet(indices, assignments, t_now: int, tenancy=None):
    """The latency bench's push fleet, tenant-labeled: series selectors
    and doc query configs carry `tenant="<t>"`, so registry resolution
    sees the same label on both the push path and the claim path.
    `indices` picks which service indices exist (the control run builds
    only the quiet ones — SAME ids, keys and data as the treatment
    run's quiet subset)."""
    rng = np.random.default_rng(7)
    store = InMemoryStore()
    ring = RingStore(
        shards=8, budget_bytes=1 << 30, stale_seconds=3600.0,
        tenancy=tenancy,
    )
    ht = t_now - 86_400 * 7 + STEP * np.arange(HIST_LEN, dtype=np.int64)
    ct = t_now - STEP * CUR_LEN + STEP * np.arange(CUR_LEN, dtype=np.int64)
    end_time = time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime(t_now + 7200)
    )
    keys = {}
    for s in indices:
        expr = _expr(s, assignments[s])
        key = canonical_series(expr)
        keys[s] = key
        hv = rng.normal(1.0, 0.1, HIST_LEN).astype(np.float32)
        cv = np.ones(CUR_LEN, np.float32)
        ring.push(
            key,
            np.concatenate([ht, ct]),
            np.concatenate([hv, cv]),
            start=float(ht[0]),
            now=float(t_now),
        )
        cur_url = prometheus_url(
            {"endpoint": "http://p/api/v1/", "query": expr,
             "start": int(ct[0]), "end": int(t_now + 7200), "step": STEP}
        )
        hist_url = prometheus_url(
            {"endpoint": "http://p/api/v1/", "query": expr,
             "start": int(ht[0]), "end": int(ht[-1]), "step": STEP}
        )
        store.create(
            Document(
                id=f"job-{s}",
                app_name=f"app{s}",
                end_time=end_time,
                current_config=f"latency== {cur_url}",
                historical_config=f"latency== {hist_url}",
                strategy="continuous",
            )
        )
    return store, ring, keys, ht, ct


def _post(port: int, payload: dict):
    """POST a push; returns (status, headers) — 429 is an ANSWER here
    (the admission verdict under test), not an error."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/api/v1/write",
        data=json.dumps(payload).encode(),
        method="POST",
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            resp.read()
            return resp.status, dict(resp.headers)
    except urllib.error.HTTPError as e:
        e.read()
        return e.code, dict(e.headers)


def _push_payload(key: str, ts, vs) -> dict:
    return {
        "timeseries": [
            {
                "alias": key,
                "times": [int(t) for t in ts],
                "values": [float(v) for v in vs],
            }
        ]
    }


def _statuses(store):
    return {
        d.id: (d.status, d.reason, d.anomaly_info)
        for d in store._docs.values()
    }


def run_parity(services: int, t_now: int) -> None:
    """The ISSUE 20 parity pin: zero-vs-one-tenant byte-identical
    statuses on identical fleets through the SLICED warm path (cold
    judgment, a warm re-check, a spiked re-check)."""
    assignments = ["default"] * services
    indices = list(range(services))
    arms = []
    try:
        for reg in (
            None,
            TenantRegistry({"default": TenantSpec(name="default")}),
        ):
            set_tenancy(reg)
            store, ring, keys, ht, ct = build_fleet(
                indices, assignments, t_now
            )
            w = mk_worker(store, ring, services)
            w.sweep_slice_docs = 32
            now = float(t_now)
            assert w.tick(now=now) == services
            assert w.tick(now=now + 60) == services  # warm sliced
            spike_t = ct[-3:]
            spike_v = np.full(3, 40.0, np.float32)
            ring.push(keys[1], spike_t, spike_v, now=now)
            assert w.tick(now=now + 120) == services
            arms.append(_statuses(store))
            w.close()
    finally:
        set_tenancy(None)
    assert arms[0] == arms[1], "zero-vs-one-tenant parity broke"
    assert arms[0]["job-1"][0] == STATUS_COMPLETED_UNHEALTH


def run_phase(
    indices,
    assignments,
    inject_at,
    t_now: int,
    tenancy,
    whale_keys=None,
    small: bool = False,
) -> dict:
    """One measured arm: fleet up, worker run loop + receiver, anomaly
    injections into the quiet services at `inject_at`, optional whale
    flood against the same receiver. Returns latencies, F1 inputs, the
    flood's answer codes, and the end-state /debug/state tenants
    section."""
    set_tenancy(tenancy)
    try:
        store, ring, keys, ht, ct = build_fleet(
            indices, assignments, t_now, tenancy=tenancy
        )
        services = len(indices)
        dirty = DirtySet(max_keys=max(8192, 4 * services), tenancy=tenancy)
        worker = mk_worker(store, ring, services, dirty=dirty)
        srv, _ = start_ingest_server(
            0, ring, host="127.0.0.1", dirty=dirty, tenancy=tenancy
        )
        port = srv.server_address[1]
        t0 = time.perf_counter()
        assert worker.tick(now=float(t_now)) == services
        warm_seconds = time.perf_counter() - t0
        stop = threading.Event()
        loop = threading.Thread(
            target=worker.run,
            kwargs={"poll_seconds": 5.0, "stop": stop.is_set},
            daemon=True,
        )
        loop.start()

        flood_codes: dict[int, int] = {}
        flood_stop = threading.Event()
        flood_thread = None
        if whale_keys:
            # the whale: large batches of fresh samples over its whole
            # series population, as fast as the socket allows. Each
            # batch decodes to ~60 KB of columns — the burst bucket
            # (2 x INGEST_BYTES_PER_S = 128 KB) drains within two
            # batches, so admission MUST shed the flood for the rest
            # of the phase
            def flood():
                i = 0
                per_batch = min(64, len(whale_keys))
                n_samples = 60
                while not flood_stop.is_set():
                    stamp = int(time.time())
                    times = [
                        int(t)
                        for t in stamp - STEP * (n_samples - 1)
                        + STEP * np.arange(n_samples)
                    ]
                    body = {
                        "timeseries": [
                            {
                                "alias": whale_keys[
                                    (i + j) % len(whale_keys)
                                ],
                                "times": times,
                                "values": [1.0] * n_samples,
                            }
                            for j in range(per_batch)
                        ]
                    }
                    i += per_batch
                    code, _hdrs = _post(port, body)
                    flood_codes[code] = flood_codes.get(code, 0) + 1
                    if code == 429:
                        # a real pusher honors Retry-After; the bench
                        # keeps hammering on a short leash so the
                        # governor stays saturated for the whole phase
                        flood_stop.wait(0.02)

            flood_thread = threading.Thread(target=flood, daemon=True)
            flood_thread.start()
            time.sleep(0.3)  # let the flood reach steady state first

        latencies = []
        timeouts = 0
        quiet_codes: dict[int, int] = {}
        for s in inject_at:
            stamp = int(time.time())
            ts = stamp - STEP * 2 + STEP * np.arange(3)
            t0 = time.monotonic()
            code, _hdrs = _post(
                port,
                _push_payload(keys[s], ts, np.full(3, 40.0, np.float32)),
            )
            quiet_codes[code] = quiet_codes.get(code, 0) + 1
            elapsed = _await_status(
                store, f"job-{s}", (STATUS_COMPLETED_UNHEALTH,), 20.0
            )
            if elapsed is None:
                timeouts += 1
            else:
                latencies.append(time.monotonic() - t0)

        if flood_thread is not None:
            flood_stop.set()
            flood_thread.join(timeout=5)
        stop.set()
        loop.join(timeout=30)

        # F1 over the QUIET services: injected spikes are the positive
        # class, every other quiet service must stay healthy
        spiked = set(inject_at)
        tp = fp = fn = 0
        whale_set = {
            s for s in indices if assignments[s] == WHALE_TENANT
        }
        for s in indices:
            if s in whale_set:
                continue
            doc = store.get(f"job-{s}")
            unhealthy = (
                doc is not None
                and doc.status == STATUS_COMPLETED_UNHEALTH
            )
            if s in spiked:
                tp += unhealthy
                fn += not unhealthy
            else:
                fp += unhealthy
        f1 = (
            2 * tp / (2 * tp + fp + fn) if (2 * tp + fp + fn) else 1.0
        )

        # quiet residency: the whale's flood must not have evicted the
        # quiet tenants' warm series out of the ring
        resident = sum(
            1
            for s in indices
            if s not in whale_set
            and ring.query(
                keys[s], float(ht[0]), float(ct[-1]), now=time.time()
            )
            is not None
        )
        tenants_dbg = None
        if tenancy is not None:
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/state", timeout=10
            ) as resp:
                tenants_dbg = json.load(resp).get("tenants")
        stop_ingest_server(srv)
        worker.close()
        lat = np.asarray(sorted(latencies), np.float64)
        return {
            "latencies": latencies,
            "p50": float(np.percentile(lat, 50)) if len(lat) else None,
            "p99": float(np.percentile(lat, 99)) if len(lat) else None,
            "timeouts": timeouts,
            "f1": round(f1, 4),
            "quiet_codes": quiet_codes,
            "flood_codes": flood_codes,
            "quiet_resident": resident,
            "quiet_total": len(indices) - len(whale_set),
            "fleet_warm_seconds": round(warm_seconds, 3),
            "tenants": tenants_dbg,
        }
    finally:
        set_tenancy(None)


def run(services: int, inject: int, small: bool) -> dict:
    t_now = int(time.time())
    run_parity(min(96, services), t_now)

    assignments = tenant_fleet("noisy_neighbor", services, TENANTS)
    quiet = [
        s for s in range(services) if assignments[s] != WHALE_TENANT
    ]
    whale = [
        s for s in range(services) if assignments[s] == WHALE_TENANT
    ]
    inject_at = quiet[-min(inject, len(quiet)):]

    # solo-tenant CONTROL: the quiet services alone, untenanted
    control = run_phase(
        quiet, assignments, inject_at, t_now, tenancy=None, small=small
    )

    # TREATMENT: full fleet, whale flooding, equal-weight registry with
    # uniform envelopes — fairness and budgets, not hand-tuned throttles
    spec_map = tenant_weighted_specs(
        TENANTS,
        ring_bytes=RING_BYTES_PER_TENANT,
        ingest_bytes_per_s=INGEST_BYTES_PER_S,
    )
    reg = TenantRegistry(
        {n: TenantSpec.from_json(n, d) for n, d in spec_map.items()}
    )
    whale_keys = [
        canonical_series(_expr(s, WHALE_TENANT)) for s in whale
    ]
    treatment = run_phase(
        list(range(services)),
        assignments,
        inject_at,
        t_now,
        tenancy=reg,
        whale_keys=whale_keys,
        small=small,
    )
    acct = accounting_for(reg).snapshot()

    result = {
        "bench": "noisy",
        "services": services,
        "tenants": TENANTS,
        "whale_services": len(whale),
        "quiet_services": len(quiet),
        "inject": len(inject_at),
        "small": small,
        "control": {
            k: control[k]
            for k in ("p50", "p99", "f1", "timeouts", "fleet_warm_seconds")
        },
        "treatment": {
            k: treatment[k]
            for k in ("p50", "p99", "f1", "timeouts", "fleet_warm_seconds")
        },
        "quiet_push_codes": treatment["quiet_codes"],
        "whale_flood_codes": treatment["flood_codes"],
        "quiet_resident": (
            f"{treatment['quiet_resident']}/{treatment['quiet_total']}"
        ),
        "accounting": acct,
        "debug_state_tenants": treatment["tenants"] is not None,
        "parity": "zero-vs-one-tenant byte-identical (asserted)",
    }

    # -- in-run asserts (the acceptance criteria) -----------------------
    assert control["timeouts"] == 0 and treatment["timeouts"] == 0, (
        control["timeouts"], treatment["timeouts"],
    )
    # targeted backpressure: every quiet POST answered 200; the whale
    # was shed, and ONLY the whale carries shed charges
    assert set(treatment["quiet_codes"]) == {200}, treatment["quiet_codes"]
    assert treatment["flood_codes"].get(429, 0) > 0, (
        f"whale flood never shed: {treatment['flood_codes']}"
    )
    for name, row in acct.items():
        if name != WHALE_TENANT:
            assert row["shed"] == 0, (name, row)
    assert acct.get(WHALE_TENANT, {}).get("shed", 0) > 0, acct
    # isolation: quiet residency intact, F1 unchanged vs control
    assert treatment["quiet_resident"] == treatment["quiet_total"], (
        result["quiet_resident"]
    )
    assert treatment["f1"] == control["f1"], (
        f"quiet F1 moved: control {control['f1']} vs "
        f"treatment {treatment['f1']}"
    )
    # attribution visible end to end
    assert treatment["tenants"] is not None, "/debug/state tenants missing"
    assert WHALE_TENANT in treatment["tenants"].get("accounting", {}), (
        treatment["tenants"]
    )
    if not small:
        bar = control["p99"] * P99_FACTOR + P99_GRACE_S
        assert treatment["p99"] <= bar, (
            f"quiet p99 {treatment['p99']:.3f}s past the noisy bar "
            f"{bar:.3f}s (control {control['p99']:.3f}s)"
        )
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--services", type=int, default=2048)
    ap.add_argument("--inject", type=int, default=32)
    ap.add_argument(
        "--small", action="store_true", help="CPU smoke shapes (CI)"
    )
    args = ap.parse_args(argv)
    from foremast_tpu.device import enable_compile_cache

    enable_compile_cache()
    services = 96 if args.small else args.services
    inject = 4 if args.small else args.inject
    result = run(services, inject, args.small)
    print(json.dumps(result), flush=True)
    from benchmarks.report import write_summary

    write_summary(
        "noisy",
        result,
        small=args.small,
        tenants=result["accounting"],
    )


if __name__ == "__main__":
    main()
