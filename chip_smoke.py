"""chip_smoke.py — the quickest proof that the shipped judgment path runs
on the chip.

One process, which holds the chip for its whole life and starts no child
that needs it. Drives, through the entry points a user would call:

  A  the job plane: REST create -> job store -> BrainWorker claim ->
     fetch -> pack -> device -> decode -> write-back -> REST read, on the
     golden canary traces (and the same traces through `cli score`);
  B  one fleet at full width on ONE device, cold then warm: BASELINE
     config 5's shape (4 aliases a service, 10,080-point histories,
     30-point current windows, a quarter of the docs canary-shaped),
     then a joint share (bivariate + LSTM-hybrid) — warm ticks must be
     100 % columnar with zero backend compiles;
  C  the same fleet over a four-chip mesh vs one device, byte-identical
     (only when jax sees >= 4 devices);
  D  the three Pallas kernels with interpret=False at B=4,096 and
     Th in {10,080, 16,384}, against the XLA program.

Every check is a hard assertion: any failure is a non-zero exit and no
result line. Without a TPU it exits non-zero naming the platform it
found. The last stdout line is one JSON object,
{"ok": true, "device": {"platform", "kind", "count"}}.

Widths are never cut. `--services` lowers Phase B/C's service count if
the wall-clock limit bites; the count used is printed. Per-phase wall
times are SMOKE TIMINGS (compile included), not metrics.

    python chip_smoke.py [--services N] [--out DIR] [--seed S]
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import io
import json
import os
import re
import sys
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
NOW = 1_760_000_000.0  # fleet clock (benchmarks/worker_bench.py's)
ALIAS = "error4xx"  # Phase A's metric (see _canary_request)
END_TIME = "2026-07-30T00:10:00Z"  # Phase A's job window end
CUR_LEN = 30  # the reference's 30-point current window (bucket 32)
MESH_DEVICES = 4  # Phase C's data axis


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything the smoke's cost depends on. The defaults are the
    chip run; tests/test_chip_smoke.py passes a toy set."""

    services: int = 1024  # Phase B/C univariate+canary fleet
    joint_services: int = 128  # half joint: 32 bivariate + 32 LSTM-hybrid
    # (0 skips the joint share: the tier-1 toy run's time budget)
    hist_len: int = 10_080  # 7 days at the 60 s step -> the 16,384 bucket
    warm_ticks: int = 3
    kernel_batch: int = 4096
    kernel_hist: tuple[int, ...] = (10_080, 16_384)
    kernel_interpret: bool = False
    seed: int = 0


def check(cond, what: str, detail=None) -> None:
    """A hard assertion that survives `python -O`."""
    if not cond:
        raise AssertionError(what if detail is None else f"{what}: {detail!r}")


# ---------------------------------------------------------------------------
# Phase A — the job plane answers requests
# ---------------------------------------------------------------------------


def _canary_request(app: str) -> dict:
    """The golden canary request of docs/quickstart.md §1; the query
    names route each category to its own replay trace. Judged as
    error4xx (the deployed 3-sigma rule, as tests/test_cli.py does):
    under error5xx's 2-sigma rule the normal trace flags its own
    2.5-sigma peak when it is also its own history."""

    def metric(query):
        return {
            ALIAS: {
                "dataSourceType": "prometheus",
                "parameters": {
                    "endpoint": "http://prom/", "query": query,
                    "start": "0", "end": "600", "step": "60",
                },
            }
        }

    return {
        "appName": app,
        "strategy": "canary",
        "startTime": "2026-07-30T00:00:00Z",
        "endTime": END_TIME,
        "metrics": {
            "current": metric(f"{app}-current"),
            "baseline": metric(f"{app}-baseline"),
            "historical": metric(f"{app}-historical"),
        },
    }


def _trace(name: str) -> str:
    return os.path.join(REPO, "tests", "data", f"demo_canary_{name}.csv")


@contextlib.contextmanager
def _served(app):
    """Serve an aiohttp app on 127.0.0.1:<ephemeral> from a background
    thread (host code only); yields the base URL."""
    from aiohttp import web

    loop = asyncio.new_event_loop()
    runner = web.AppRunner(app)
    loop.run_until_complete(runner.setup())
    site = web.TCPSite(runner, "127.0.0.1", 0)
    loop.run_until_complete(site.start())
    port = runner.addresses[0][1]
    thread = threading.Thread(target=loop.run_forever, daemon=True)
    thread.start()
    try:
        yield f"http://127.0.0.1:{port}"
    finally:
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=10)
        check(not thread.is_alive(), "REST server thread did not stop")
        loop.run_until_complete(runner.cleanup())
        loop.close()


def _http(url: str, body: dict | None = None) -> dict:
    # no proxy: the server is this process
    opener = urllib.request.build_opener(urllib.request.ProxyHandler({}))
    req = urllib.request.Request(
        url,
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with opener.open(req, timeout=30) as resp:
        return json.loads(resp.read())


def _check_golden(resp: dict, want_status: str, where: str) -> None:
    check(resp["status"] == want_status, f"{where} status", resp)
    values = resp.get("anomalyInfo", {}).get("values", {})
    if want_status == "anomaly":
        flagged = [round(v, 3) for v in values[ALIAS][1::2]]
        check(flagged == [40.134, 40.466], f"{where} anomaly points", flagged)
    else:
        check(not values, f"{where} anomaly points on a healthy job", values)


def phase_a(out_dir: str) -> dict:
    from prometheus_client import CollectorRegistry

    from foremast_tpu import cli
    from foremast_tpu.config import BrainConfig
    from foremast_tpu.engine.multivariate import MultivariateJudge
    from foremast_tpu.jobs.models import (
        STATUS_COMPLETED_HEALTH,
        STATUS_COMPLETED_UNHEALTH,
    )
    from foremast_tpu.jobs.store import InMemoryStore, parse_time
    from foremast_tpu.jobs.worker import BrainWorker
    from foremast_tpu.metrics.source import ReplaySource
    from foremast_tpu.parallel.batch import sharded_univariate
    from foremast_tpu.service.app import make_app

    cases = {
        "smoke-spike": ("spike", "anomaly", STATUS_COMPLETED_UNHEALTH),
        "smoke-normal": ("normal", "success", STATUS_COMPLETED_HEALTH),
    }
    store = InMemoryStore()
    source = ReplaySource()
    for app, (current, _, _) in cases.items():
        source.register_csv(f"{app}-current", _trace(current))
        source.register_csv(f"{app}-baseline", _trace("normal"))
        source.register_csv(f"{app}-historical", _trace("normal"))

    # the worker exactly as cli.cmd_worker builds it (the compile cache
    # was enabled before the first computation, in run())
    config = BrainConfig.from_env()
    univariate = sharded_univariate(config)
    judge = MultivariateJudge(config, univariate=univariate)
    worker = BrainWorker(
        store, source, config=config, judge=judge, worker_id="smoke-a"
    )
    mesh_shape = dict(univariate.mesh.shape) if univariate is not None else None
    try:
        with _served(
            make_app(store=store, registry=CollectorRegistry())
        ) as base:
            job_ids = {}
            for app in cases:
                created = _http(
                    f"{base}/v1/healthcheck/create", _canary_request(app)
                )
                check(created["statusCode"] == 201, "create", created)
                job_ids[app] = created["jobId"]
            # clamp "now" past endTime so the healthy job finalizes
            # (cli.cmd_score's one-shot rule)
            now = max(time.time(), parse_time(END_TIME) + 1)
            judged = worker.tick(now=now)
            check(judged == len(cases), "phase A docs judged", judged)
            for app, (_, external, internal) in cases.items():
                resp = _http(f"{base}/v1/healthcheck/id/{job_ids[app]}")
                _check_golden(resp, external, f"REST {app}")
                check(
                    store.get(job_ids[app]).status == internal,
                    f"store status of {app}",
                    store.get(job_ids[app]).status,
                )
    finally:
        worker.close()

    # the outermost CLI surface, in-process, on the same traces
    req_path = os.path.join(out_dir, "smoke_request.json")
    for app, (current, external, _) in cases.items():
        with open(req_path, "w") as fh:
            json.dump(_canary_request(app), fh)
        stdout = io.StringIO()
        with contextlib.redirect_stdout(stdout):
            rc = cli.main([
                "score", "--request", req_path,
                "--current", f"{ALIAS}={_trace(current)}",
                "--baseline", f"{ALIAS}={_trace('normal')}",
                "--historical", f"{ALIAS}={_trace('normal')}",
            ])
        check(rc == 0, f"cli score {app} exit code", rc)
        _check_golden(json.loads(stdout.getvalue()), external, f"cli {app}")
    return {"mesh": mesh_shape, "jobs": len(cases)}


# ---------------------------------------------------------------------------
# Phases B and C — one fleet at full width through BrainWorker
# ---------------------------------------------------------------------------


class _FetchTap:
    """Records where the arrays crossing a judge's ONE device->host seam
    (`HealthJudge._fetch`) live — the proof that verdicts were computed
    on the chip, not on a host fallback."""

    def __init__(self, judge):
        import jax

        self.platforms: set[str] = set()
        self.devices: set[int] = set()
        orig = judge._fetch

        def fetch(tree):
            for leaf in jax.tree.leaves(tree):
                for d in leaf.devices():
                    self.platforms.add(d.platform)
                    self.devices.add(d.id)
            return orig(tree)

        judge._fetch = fetch


def _statuses(store) -> dict:
    return {
        d.id: (d.status, json.dumps(d.anomaly_info, sort_keys=True))
        for d in store._docs.values()
    }


def _run_fleet(
    tag, sizes, witness, *, services, device_mesh, config,
    baseline_frac=0.0, joint_frac=0.0, warm_ticks, spike=False,
):
    """Cold tick, first warm tick, then `warm_ticks` steady warm ticks
    of one seeded fleet through a BrainWorker: every open doc judged
    each tick, warm ticks 100 % columnar, nothing demoted, no arena
    fallback. Returns (worker, fetch tap, statuses, facts); the worker
    comes back closed (its pools rebuild lazily if it ticks again)."""
    import numpy as np

    from benchmarks.worker_bench import build_mixed_fleet
    from foremast_tpu.jobs.models import TERMINAL_STATUSES
    from foremast_tpu.jobs.worker import BrainWorker

    store, source, _ = build_mixed_fleet(
        services, sizes.hist_len, CUR_LEN, NOW,
        joint_frac=joint_frac, baseline_frac=baseline_frac, seed=sizes.seed,
    )
    config = dataclasses.replace(config, max_cache_size=4 * services + 64)
    worker = BrainWorker(
        store, source, config=config, claim_limit=services,
        worker_id=f"smoke-{tag}", device_mesh=device_mesh,
    )
    tap = _FetchTap(worker._uni)

    def open_docs() -> int:
        return sum(
            1 for d in store._docs.values()
            if d.status not in TERMINAL_STATUSES
        )

    try:
        t0 = time.perf_counter()
        with witness.phase(f"{tag}-cold"):
            judged = worker.tick(now=NOW + 150)
        facts = {"cold_s": round(time.perf_counter() - t0, 2), "warm_s": []}
        check(judged == services, f"{tag} cold tick judged", judged)
        if spike:
            # one anomaly, so the parity arms compare a real payload
            url = next(
                u for u in source.data
                if u.startswith("http://prom/cur") and "latency:app0&" in u
            )
            ct, cv = source.data[url]
            hot = cv.copy()
            hot[-3:] = 40.0
            source.data[url] = (ct, hot.astype(np.float32))
        facts["open_docs"] = []
        # the FIRST columnar tick compiles the columnar programs (the cold
        # tick ran the object path): it must already be 100 % columnar, but
        # only the ticks after it are held to zero backend compiles
        for k in range(1 + warm_ticks):
            want = open_docs()
            before = sum(worker._fast_kinds.values())
            t0 = time.perf_counter()
            with witness.phase(f"{tag}-first-warm" if k == 0 else f"{tag}-warm"):
                judged = worker.tick(now=NOW + 160 + 10 * k)
            facts["warm_s"].append(round(time.perf_counter() - t0, 3))
            facts["open_docs"].append(want)
            check(judged == want, f"{tag} warm tick {k} judged", (judged, want))
            columnar = sum(worker._fast_kinds.values()) - before
            check(
                columnar == want and worker._last_tick["fast"] == want,
                f"{tag} warm tick {k} not 100% columnar",
                (columnar, worker._last_tick, want),
            )
        state = worker.debug_state()
        check(state["degradation"]["docs"].get("fast_demoted", 0) == 0,
              f"{tag} fast_demoted", state["degradation"]["docs"])
        check(state["arena"]["fallbacks"] == 0, f"{tag} arena fallbacks",
              state["arena"])
        if state["joint_arena"] is not None:
            check(state["joint_arena"]["fallbacks"] == 0,
                  f"{tag} joint arena fallbacks", state["joint_arena"])
        facts["fast_path_docs"] = state["fast_path_docs"]
        facts["compiles"] = {
            k: witness.count(f"{tag}-{k}") for k in ("cold", "first-warm", "warm")
        }
    finally:
        worker.close()
    return worker, tap, _statuses(store), facts


def phase_b(sizes: Sizes, witness, platform: str) -> dict:
    from foremast_tpu.config import BrainConfig
    from foremast_tpu.jobs.models import STATUS_PREPROCESS_COMPLETED

    config = BrainConfig.from_env()  # the deployed default: moving_average_all
    worker, tap, statuses, uni = _run_fleet(
        "b", sizes, witness, services=sizes.services, device_mesh=None,
        config=config, baseline_frac=0.25, warm_ticks=sizes.warm_ticks,
    )
    check(worker._device_mesh_state() is None, "phase B must pin one device")
    check(
        all(s == STATUS_PREPROCESS_COMPLETED for s, _ in statuses.values()),
        "every doc left preprocess_completed",
        sorted({s for s, _ in statuses.values()}),
    )
    check(uni["compiles"]["warm"] == 0,
          "backend compiles inside steady warm ticks", uni)
    kinds = uni["fast_path_docs"]
    check(kinds["baseline"] > 0 and kinds["univariate"] > 0,
          "canary and plain docs both columnar", kinds)
    check(tap.platforms == {platform} and len(tap.devices) == 1,
          "verdict arrays not on one device of the checked platform",
          (tap.platforms, tap.devices))

    # joint share: half the docs joint, alternating bivariate and
    # LSTM-hybrid (joint detectors read the base threshold; the quality
    # scenarios calibrate them at 4 sigma — worker_bench.run). A clean
    # joint doc may still false-alarm and go terminal on the cold tick:
    # the warm tick judges whatever stayed open, all of it columnar.
    out = {"mesh": None, "services": sizes.services, "univariate": uni,
           "joint_services": sizes.joint_services}
    if not sizes.joint_services:
        return out
    joint_cfg = dataclasses.replace(
        config,
        algorithm="auto",
        anomaly=dataclasses.replace(config.anomaly, threshold=4.0),
    )
    _, _, _, joint = _run_fleet(
        "b-joint", sizes, witness, services=sizes.joint_services,
        device_mesh=None, config=joint_cfg, joint_frac=0.5, warm_ticks=1,
    )
    check(joint["compiles"]["warm"] == 0,
          "backend compiles inside the joint steady warm tick", joint)
    kinds = joint["fast_path_docs"]
    want = sizes.joint_services // 4
    check(kinds["bivariate"] >= want and kinds["lstm"] >= want,
          "joint docs columnar on the warm tick", kinds)
    out["joint"] = joint
    return out


def _warm_program_collectives(worker) -> dict:
    """Collectives in the compiled warm-tick programs of a sharded
    worker: lower the REAL judgment jit (`score_from_arena_sharded`) on
    the operands one more warm tick dispatches — the baseline-less and
    the canary (pairwise-active) bucket — and read the HLO. The arena
    gather must be device-local: no all-gather, all-to-all,
    collective-permute or reduce-scatter anywhere. What may remain is
    the scalar `pred[]` all-reduce XLA places in the exit test of a
    data-dependent `while` (the rank tests' `gammaincc` series, whose
    loop runs until NO row of the sharded batch needs another term):
    counted and reported, and any all-reduce wider than a scalar
    fails."""
    from foremast_tpu.engine import scoring

    calls = []
    real = scoring.score_from_arena_sharded

    def tap(*args, **kwargs):
        calls.append((args, kwargs))
        return real(*args, **kwargs)

    scoring.score_from_arena_sharded = tap
    try:
        worker.tick(now=NOW + 400)
    finally:
        scoring.score_from_arena_sharded = real
        worker.close()
    check(calls, "sharded warm program was not dispatched")
    found = {}
    for args, kwargs in calls:
        hlo = real.lower(*args, **kwargs).compile().as_text()
        moved = sorted(set(re.findall(
            r"all-gather|all-to-all|collective-permute|reduce-scatter", hlo
        )))
        check(not moved, "warm sharded program moves rows across devices",
              (kwargs["pairwise_algorithm"], moved))
        reduces = re.findall(r"= (.*?)\ball-reduce(?:-start)?\(", hlo)
        for result_type in reduces:
            dims = re.findall(r"\w+\[([^\]]*)\]", result_type)
            check(dims and not any(dims),
                  "warm sharded program all-reduces more than a scalar",
                  (kwargs["pairwise_algorithm"], result_type))
        found[kwargs["pairwise_algorithm"]] = len(reduces)
    return found


def phase_c(sizes: Sizes, witness, platform: str) -> dict:
    import jax

    from foremast_tpu.config import BrainConfig
    from foremast_tpu.parallel.mesh import make_mesh

    n = MESH_DEVICES
    mesh = make_mesh(n_data=n, devices=jax.devices()[:n])
    config = BrainConfig.from_env()
    kw = dict(services=sizes.services, config=config, baseline_frac=0.25,
              warm_ticks=1, spike=True)
    sworker, stap, s_stat, sharded = _run_fleet(
        "c-mesh", sizes, witness, device_mesh=mesh, **kw
    )
    pworker, _, p_stat, single = _run_fleet(
        "c-one", sizes, witness, device_mesh=None, **kw
    )
    check(s_stat == p_stat, "statuses/anomaly payloads differ across arms", {
        k: (s_stat[k], p_stat[k]) for k in s_stat if s_stat[k] != p_stat[k]
    })
    check(any(s == "completed_unhealth" for s, _ in s_stat.values()),
          "the spiked doc must go unhealthy in both arms")
    # the in-run assert_partitioned inside ShardedJudge raised already if
    # any placement was not rows/n per device; prove placements happened
    dm = sworker._device_mesh_state()
    check(dm is not None and dm["devices"] == n, "mesh state", dm)
    check(dm["place_calls"] > 0, "no mesh placement ran", dm)
    check(dm["arena_layout"] == "sharded", "arena layout", dm)
    check(pworker._device_mesh_state() is None, "single arm is sharded")
    check(stap.platforms == {platform} and len(stap.devices) == n,
          "verdict arrays not spread over the mesh",
          (stap.platforms, stap.devices))
    arenas = list(sworker._uni._arenas.values())
    check(arenas, "no arenas built on the sharded worker")
    for arena in arenas:
        check(arena.shards == n and arena.cap == n * arena.cap_s,
              "arena shard geometry", (arena.shards, arena.cap, arena.cap_s))
        for leaf in jax.tree.leaves(arena.state):
            rows = sorted(s.data.shape[0] for s in leaf.addressable_shards)
            check(rows == [arena.cap_s] * n,
                  "arena leaf not block-sharded capacity/n rows a device",
                  (leaf.shape, rows))
    scalar_all_reduces = _warm_program_collectives(sworker)
    return {"mesh": dict(mesh.shape), "services": sizes.services,
            "sharded": sharded, "single": single,
            "arena_capacity_rows": dm["arena_capacity_rows"],
            "scalar_all_reduces_by_program": scalar_all_reduces}


# ---------------------------------------------------------------------------
# Phase D — the Pallas kernels lower and agree with the XLA program
# ---------------------------------------------------------------------------


def _kernel_batch(b: int, th: int, tc: int, seed: int):
    """A ragged, breach-carrying batch: left-packed history prefixes of
    varying length (some empty, some under min_points), every bound
    selector, and injected breaches on both sides."""
    import jax.numpy as jnp
    import numpy as np

    from foremast_tpu.engine import scoring
    from foremast_tpu.ops.windows import MetricWindows

    rng = np.random.default_rng(seed)
    hv = (0.5 + 0.05 * rng.standard_normal((b, th))).astype(np.float32)
    cv = (0.5 + 0.05 * rng.standard_normal((b, tc))).astype(np.float32)
    lens = rng.integers(th // 2, th + 1, b)
    lens[0], lens[1], lens[2] = 0, 5, th
    hm = np.arange(th)[None, :] < lens[:, None]
    hv *= hm  # masked slots exact zeros, like every packed upload
    cv[::7, 3] = 40.0  # upper breaches
    cv[3::7, tc // 2] = -40.0  # lower breaches
    cm = np.ones((b, tc), bool)
    cm[5] = False  # no current data -> UNKNOWN
    cm[6, : tc // 2] = False
    return scoring.ScoreBatch(
        historical=MetricWindows(
            values=jnp.asarray(hv), mask=jnp.asarray(hm), times=None
        ),
        current=MetricWindows(
            values=jnp.asarray(cv), mask=jnp.asarray(cm), times=None
        ),
        baseline=MetricWindows(
            values=jnp.zeros((b, tc), jnp.float32),
            mask=jnp.zeros((b, tc), bool),
            times=None,
        ),
        threshold=jnp.full((b,), 4.0, jnp.float32),
        bound=jnp.asarray(1 + np.arange(b) % 3, jnp.int32),
        min_lower_bound=jnp.zeros((b,), jnp.float32),
        min_points=jnp.full((b,), 10, jnp.int32),
    ), jnp.asarray(lens, jnp.int32)


def _agree(name, got, want, batch, tol) -> int:
    """Kernel vs XLA program, to tests/test_kernels.py's rule: bands
    within `tol`, flags and verdicts equal. A current point closer to a
    reference band than the band tolerance may legitimately land on
    either side; such rows are counted and excluded, never ignored
    silently. Returns the count (expected 0)."""
    import numpy as np

    verdict, anoms, upper, lower = (np.asarray(x) for x in got)
    ref_u, ref_l = np.asarray(want.upper), np.asarray(want.lower)
    np.testing.assert_allclose(upper, ref_u, rtol=tol, atol=tol, err_msg=name)
    np.testing.assert_allclose(lower, ref_l, rtol=tol, atol=tol, err_msg=name)
    cur = np.asarray(batch.current.values)
    near = np.zeros(cur.shape, bool)
    for band in (ref_u, ref_l):
        near |= np.abs(cur - band) <= tol * (1.0 + np.abs(band))
    near &= np.asarray(batch.current.mask)
    rows = ~near.any(axis=-1)
    check(rows.sum() >= 0.99 * len(rows), f"{name}: too many borderline rows",
          int((~rows).sum()))
    check(np.array_equal(anoms[rows], np.asarray(want.anomalies)[rows]),
          f"{name}: anomaly flags differ from the XLA program")
    check(np.array_equal(verdict[rows], np.asarray(want.verdict)[rows]),
          f"{name}: verdicts differ from the XLA program")
    return int((~rows).sum())


def phase_d(sizes: Sizes) -> dict:
    import jax
    import numpy as np

    from foremast_tpu.engine import scoring
    from foremast_tpu.ops import kernels
    from foremast_tpu.ops.windows import masked_mean, masked_std

    interp = sizes.kernel_interpret
    out = {"interpret": interp, "batch": sizes.kernel_batch, "shapes": {}}
    for th in sizes.kernel_hist:
        t0 = time.perf_counter()
        batch, lens = _kernel_batch(
            sizes.kernel_batch, th, CUR_LEN, sizes.seed + th
        )
        hist, cur = batch.historical, batch.current
        params = (batch.threshold, batch.bound, batch.min_lower_bound,
                  batch.min_points)

        cnt, mean, std = kernels.masked_stats(
            hist.values, hist.mask, interpret=interp
        )
        np.testing.assert_allclose(cnt, lens, rtol=0)
        np.testing.assert_allclose(
            mean, masked_mean(hist.values, hist.mask), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(
            std, masked_std(hist.values, hist.mask, ddof=0),
            rtol=1e-4, atol=1e-5,
        )

        want = scoring._score_xla(
            batch, pairwise_algorithm=scoring.PAIRWISE_NONE
        )
        got = kernels.ma_judgment(
            hist.values, hist.mask, cur.values, cur.mask, *params,
            interpret=interp,
        )
        border = _agree(f"ma_judgment Th={th}", got, want, batch, 1e-4)

        slim, anchor, delta = scoring.make_bf16_delta_batch(batch)
        want16 = scoring.score_bf16_delta(
            slim, anchor, delta, pairwise_algorithm=scoring.PAIRWISE_NONE
        )
        got16 = kernels.ma_judgment_bf16_delta(
            anchor, delta, lens, cur.values, cur.mask, *params,
            interpret=interp,
        )
        border16 = _agree(
            f"ma_judgment_bf16_delta Th={th}", got16, want16, batch, 1e-5
        )
        flagged = int(np.asarray(want.anomalies).sum())
        check(flagged > 0, "kernel batch carries no breach", th)
        jax.block_until_ready(got16)
        out["shapes"][str(th)] = {
            "anomalies": flagged,
            "verdicts": np.bincount(np.asarray(want.verdict), minlength=3)
            .tolist(),
            "borderline_rows": [border, border16],
            "wall_s": round(time.perf_counter() - t0, 2),
        }
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class _CacheCounter:
    """Hits and misses of JAX's persistent compilation cache, from its
    own monitoring events. RecompileWitness cannot tell them apart (a
    program loaded from the persistent cache still fires its event); a
    MISS is a program XLA really compiled."""

    EVENTS = {
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }

    def __init__(self):
        self.counts = {"hits": 0, "misses": 0}

    def _on_event(self, event: str, **_kw) -> None:
        key = self.EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1

    def install(self) -> "_CacheCounter":
        from jax import monitoring

        monitoring.register_event_listener(self._on_event)
        return self

    def uninstall(self) -> None:
        from jax import monitoring

        monitoring.unregister_event_listener(self._on_event)


def _cache_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


def run(sizes: Sizes, *, check_device: bool = True, out_dir: str | None = None):
    """The smoke's body, a function of its sizes. `check_device=False`
    is for tests/test_chip_smoke.py only: the same phases on CPU at toy
    size, kernels interpreted. Returns the report dict (also written to
    `<out_dir>/chip_smoke.json`)."""
    from foremast_tpu import device, native
    from foremast_tpu.analysis.recompile_witness import RecompileWitness

    t_start = time.perf_counter()
    cache_dir = device.enable_compile_cache()  # before ANY computation
    info = device.require_tpu() if check_device else device.device_info()
    out_dir = out_dir or os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    import jax
    import jaxlib

    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # a CPU-only install has none
        libtpu = None
    cache_before = _cache_entries(cache_dir)
    print(
        f"platform={info['platform']} device_kind={info['device_kind']} "
        f"devices={info['device_count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu} "
        f"compile_cache={cache_dir} ({cache_before} entries)",
        flush=True,
    )
    native.ensure_built()
    pack_path = "native" if native.available() else "pure-python"
    print(f"pack path: {pack_path}", flush=True)

    witness = RecompileWitness().install()
    cache = _CacheCounter().install()
    report = {"device": info, "sizes": dataclasses.asdict(sizes),
              "compile_cache": cache_dir, "pack_path": pack_path,
              "phases": {}}

    def phase(name, fn, *args):
        t0 = time.perf_counter()
        cache_t0 = dict(cache.counts)
        with witness.phase(name):
            result = fn(*args)
        result["wall_s"] = round(time.perf_counter() - t0, 2)
        result["compile_cache"] = {
            k: cache.counts[k] - cache_t0[k] for k in cache.counts
        }
        report["phases"][name] = result
        print(f"phase {name} ok on mesh={result.get('mesh')}: smoke timing "
              f"{result['wall_s']} s (compile included) "
              f"{json.dumps(result, sort_keys=True)}", flush=True)

    try:
        phase("A", phase_a, out_dir)
        phase("B", phase_b, sizes, witness, info["platform"])
        if info["device_count"] >= MESH_DEVICES:
            phase("C", phase_c, sizes, witness, info["platform"])
        else:
            print(f"four_chip: not run ({info['device_count']} device)",
                  flush=True)
        phase("D", phase_d, sizes)
    finally:
        witness.uninstall()
        cache.uninstall()
    report["programs_built"] = witness.snapshot()
    report["compile_cache_events"] = cache.counts
    report["cache_entries"] = [cache_before, _cache_entries(cache_dir)]
    report["wall_s"] = round(time.perf_counter() - t_start, 2)
    print(f"programs built (dispatch-cache misses) "
          f"{json.dumps(report['programs_built'])}; persistent compile "
          f"cache {json.dumps(cache.counts)}, entries {cache_before} -> "
          f"{report['cache_entries'][1]}; total smoke timing "
          f"{report['wall_s']} s", flush=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as fh:
        json.dump(report, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--services", type=int, default=Sizes.services,
                    help="Phase B/C service count (lower only if the "
                    "wall-clock limit bites; widths are never cut)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="output directory (default <checkout>/chiprun_out)")
    args = ap.parse_args(argv)
    report = run(Sizes(services=args.services, seed=args.seed),
                 out_dir=args.out)
    info = report["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": info["platform"], "kind": info["device_kind"],
        "count": info["device_count"],
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
