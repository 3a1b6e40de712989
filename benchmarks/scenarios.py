"""Scenario-matrix workload generation (ISSUE 14): canary-shaped fleets.

The quality benchmark's `gen()` families probe detector behavior per
SIGNAL SHAPE; this module widens the workload generator into the matrix
the fleet bench sweeps — deployment STRATEGY x traffic REGIME — so the
headline canary claim is measured on canary-shaped fleets, not just the
baseline-less ones rounds 5-15 benchmarked:

  strategy — `canary` (a baseline window rides every judgment: the
             reference's baseline-pods-vs-canary-pods headline query,
             metricsquery.go:111-116), `rolling` (rollingUpdate — no
             baseline, bounded endTime), `continuous` (no baseline,
             open-ended re-check);
  regime   — `diurnal` (daily cycle), `spiky` (benign traffic bursts in
             the history — part of the distribution, not anomalies),
             `stair` (stair-step ramps: capacity changes / migrations),
             `outage` (outage-shaped GAPS in the history — the chaos
             plane's blackhole fault vocabulary re-used as a traffic
             shape: scrapes that never happened are masked-out samples,
             exactly what a PromQL range returns after an outage).

Each scenario draws B (history, current[, baseline]) window sets with
known injected anomaly points; `scenario_matrix()` scores them through
the SAME engine entry point the worker dispatches (`scoring.score`) and
returns point-level F1 per cell plus the canary cells' pairwise
false-reject rate (clean same-distribution baselines must not lower the
threshold). `FAN_IN_SHAPES` names the pusher fan-in dimension the
ingest-fed fleet variant in `benchmarks.mixed_bench` sweeps.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks import prf1
from foremast_tpu.engine import scoring
from foremast_tpu.ops.windows import MetricWindows

STRATEGIES = ("canary", "rolling", "continuous")
REGIMES = ("diurnal", "spiky", "stair", "outage")
# pusher fan-in shapes for the ingest-fed fleet variant: how many
# concurrent pushers split the fleet's series (1 = one batching agent,
# 8 = per-node agents converging on one receiver)
FAN_IN_SHAPES = (1, 8)
# label SHAPES (ISSUE 15 satellite / ROADMAP item 4's remaining
# generator gap): how a fleet's series are labeled. `single` is the
# rounds-5..16 shape (one flat namespace); `multi_cluster` spreads the
# same apps over federated clusters (a `cluster` label on every
# series); `multi_tenant` adds a `tenant` label on top — the
# multi-team SaaS shape where one app name exists per tenant. Routing
# and ownership must be label-shape-INVARIANT: the mesh routes by the
# `app` label value alone, so a service's doc, fits, arena rows and
# pushed series co-locate on one worker no matter how many extra
# labels the selector carries (`label_shape_routing_cell` proves it).
LABEL_SHAPES = ("single", "multi_cluster", "multi_tenant")
# tenant-share REGIMES (ISSUE 20): how a multi-tenant fleet's series
# divide over tenants. `uniform` is the PR-15 shape (round-robin,
# every tenant equal); `noisy_neighbor` gives ONE tenant (`t0`, the
# whale) NOISY_FACTOR x every other tenant's share — the QoS plane's
# adversarial workload: without envelopes + weighted-fair draining the
# whale's backlog starves the quiet tenants' micro-ticks and its pushes
# evict their ring series
TENANT_REGIMES = ("uniform", "noisy_neighbor")
NOISY_FACTOR = 10
WHALE_TENANT = "t0"

PERIOD = 24
NOISE = 0.05
SPIKE_SIGMA = 8.0
STAIR_STEP = 0.4  # level jump per stair (a capacity migration)
SPIKY_BURST = 0.35  # benign burst height: tall, but part of the regime


def _regime_signal(regime: str, t: np.ndarray, th: int, rng) -> np.ndarray:
    """Deterministic base signal of one regime at time steps `t` [B, n]
    (broadcast over rows)."""
    if regime == "diurnal":
        return 1.0 + 0.5 * np.sin(2 * np.pi * t / PERIOD)
    if regime == "spiky":
        return np.ones_like(t, dtype=float)
    if regime == "stair":
        # stair-step ramps WITHIN the history (capacity changes /
        # traffic migrations at th/4, th/2, 3th/4), with the current
        # window continuing the last learned level — global-mean bands
        # mis-center across the steps; the auto screen's changepoint
        # trend localizes them. (A step AT the history/current boundary
        # is a genuine level-shift anomaly, not a regime — that case
        # belongs to the anomaly injection, not the signal.)
        return 1.0 + STAIR_STEP * np.minimum(
            np.floor(t / max(th // 4, 1)), 3.0
        )
    if regime == "outage":
        return np.ones_like(t, dtype=float)
    raise ValueError(regime)


def gen_scenario(
    strategy: str,
    regime: str,
    b: int,
    th: int,
    tc: int,
    seed: int = 0,
):
    """One scenario cell: (hist [B,Th], hist_mask, cur [B,Tc], truth
    [B,Tc] bool, base [B,Tc] | None).

    Injected anomalies are SPIKE_SIGMA-sigma points in the current
    window (two per row). The canary strategy's baseline is a clean
    same-distribution draw at the current phase — healthy canary, so
    the rank tests must hold (differs=False) while the band detection
    still catches the spikes. The spiky regime's history bursts and the
    outage regime's masked gaps are NOT anomalies: they are the regime.
    """
    rng = np.random.default_rng(
        seed + 1000 * STRATEGIES.index(strategy) + REGIMES.index(regime)
    )
    t_hist = np.arange(th)[None, :]
    t_cur = (th + np.arange(tc))[None, :]
    hist = _regime_signal(regime, t_hist, th, rng) + rng.normal(
        0, NOISE, (b, th)
    )
    cur = _regime_signal(regime, t_cur, th, rng) + rng.normal(
        0, NOISE, (b, tc)
    )
    hist_mask = np.ones((b, th), bool)
    if regime == "spiky":
        # benign bursts in the HISTORY (cron jobs, deploy traffic):
        # ~2% of samples sit SPIKY_BURST high — the fitted band must
        # absorb them (they widen sigma), not learn them as clean
        for i in range(b):
            k = max(th // 50, 2)
            idx = rng.choice(th, size=k, replace=False)
            hist[i, idx] += SPIKY_BURST
    elif regime == "outage":
        # outage-shaped gaps: two blackhole windows of ~5% of the
        # history each — masked samples, exactly a scrape outage's
        # PromQL shape (the chaos plane's fault vocabulary as data)
        gap = max(th // 20, 2)
        for i in range(b):
            for _ in range(2):
                g0 = int(rng.integers(0, th - gap))
                hist_mask[i, g0 : g0 + gap] = False
    truth = np.zeros((b, tc), bool)
    for i in range(b):
        idx = rng.choice(tc, size=2, replace=False)
        cur[i, idx] += SPIKE_SIGMA * NOISE
        truth[i, idx] = True
    base = None
    if strategy == "canary":
        # baseline pods: same signal family at the same phase, its own
        # noise draw — same distribution as a healthy canary's current
        base = _regime_signal(regime, t_cur, th, rng) + rng.normal(
            0, NOISE, (b, tc)
        )
        base = base.astype(np.float32)
    return (
        hist.astype(np.float32),
        hist_mask,
        cur.astype(np.float32),
        truth,
        base,
    )


def _batch(hist, hist_mask, cur, base):
    b, tc = cur.shape

    def win(v, m=None):
        return MetricWindows(
            values=jnp.asarray(v),
            mask=jnp.asarray(m) if m is not None else jnp.ones(v.shape, bool),
            times=jnp.zeros(v.shape, jnp.int32),
        )

    if base is None:
        baseline = MetricWindows(
            values=jnp.zeros_like(jnp.asarray(cur)),
            mask=jnp.zeros(cur.shape, bool),
            times=jnp.zeros(cur.shape, jnp.int32),
        )
    else:
        baseline = win(base)
    return scoring.ScoreBatch(
        historical=win(hist, hist_mask),
        current=win(cur),
        baseline=baseline,
        threshold=jnp.full((b,), 4.0, jnp.float32),
        bound=jnp.full((b,), 1, jnp.int32),
        min_lower_bound=jnp.zeros((b,), jnp.float32),
        min_points=jnp.full((b,), 10, jnp.int32),
    )


def score_scenario(
    strategy: str,
    regime: str,
    b: int,
    th: int,
    tc: int,
    seed: int = 0,
    algorithm: str = "auto_univariate",
):
    """(f1, precision, recall, differs_rate) for one matrix cell.

    differs_rate is the fraction of rows whose pairwise tests rejected
    same-distribution — on the clean baselines every cell draws it is
    the rank tests' false-reject rate (canary cells only; 0.0 where no
    baseline exists, the gates' hardwired outcome)."""
    hist, hist_mask, cur, truth, base = gen_scenario(
        strategy, regime, b, th, tc, seed
    )
    res = scoring.score(
        _batch(hist, hist_mask, cur, base),
        algorithm=algorithm,
        season_length=PERIOD,
    )
    flags = np.asarray(res.anomalies)
    tp = int((flags & truth).sum())
    fp = int((flags & ~truth).sum())
    fn = int((~flags & truth).sum())
    precision, recall, f1 = prf1(tp, fp, fn)
    differs_rate = float(np.asarray(res.dist_differs).mean())
    return f1, precision, recall, differs_rate


def scenario_labels(
    shape: str,
    s: int,
    clusters: int = 4,
    tenants: int = 8,
) -> dict[str, str]:
    """The label set of service index `s` under a label shape."""
    labels = {"namespace": "bench", "app": f"app{s}"}
    if shape == "single":
        return labels
    labels["cluster"] = f"c{s % clusters}"
    if shape == "multi_tenant":
        labels["tenant"] = f"t{s % tenants}"
        return labels
    if shape != "multi_cluster":
        raise ValueError(shape)
    return labels


def scenario_selector(
    shape: str,
    s: int,
    metric: str = "latency",
    clusters: int = 4,
    tenants: int = 8,
) -> str:
    """A PromQL selector for service `s` under a label shape (label
    order deliberately NON-canonical — cluster/tenant first — so the
    cell also proves canonicalization, not just extraction)."""
    labels = scenario_labels(shape, s, clusters, tenants)
    body = ",".join(
        f'{k}="{v}"' for k, v in reversed(sorted(labels.items()))
    )
    return f"{metric}{{{body}}}"


def tenant_fleet(
    regime: str,
    services: int,
    tenants: int = 4,
    factor: int = NOISY_FACTOR,
) -> list[str]:
    """Tenant name per service index under a tenant-share regime.

    `uniform` round-robins the fleet over `tenants` equal tenants;
    `noisy_neighbor` interleaves a weighted pattern in which the whale
    (WHALE_TENANT) owns `factor` slots per cycle and every other tenant
    one — so the whale's share of services (and of every per-series
    resource: pushes, ring bytes, dirty marks, claims) is `factor` x
    each neighbor's. Deterministic: the same index always maps to the
    same tenant, so control and treatment runs judge identical fleets.
    """
    if regime == "uniform":
        return [f"t{s % tenants}" for s in range(services)]
    if regime != "noisy_neighbor":
        raise ValueError(regime)
    pattern = [WHALE_TENANT] * factor + [
        f"t{i}" for i in range(1, tenants)
    ]
    return [pattern[s % len(pattern)] for s in range(services)]


def tenant_weighted_specs(
    tenants: int = 4,
    weight: float = 1.0,
    ring_bytes: int = 0,
    arena_rows: int = 0,
    ingest_bytes_per_s: int = 0,
) -> dict[str, dict]:
    """A FOREMAST_TENANTS-shaped spec map for a `tenants`-tenant fleet:
    EQUAL weights (the fairness claim under test is that weighted-fair
    draining protects quiet tenants from a whale's backlog, not that
    operators hand-tune the whale down) with optional uniform budget
    envelopes. json.dumps of the result is a valid FOREMAST_TENANTS
    value; benches feed it to TenantRegistry directly."""
    spec: dict[str, dict] = {}
    for i in range(tenants):
        s: dict = {"weight": weight}
        if ring_bytes:
            s["ring_bytes"] = int(ring_bytes)
        if arena_rows:
            s["arena_rows"] = int(arena_rows)
        if ingest_bytes_per_s:
            s["ingest_bytes_per_s"] = int(ingest_bytes_per_s)
        spec[f"t{i}"] = s
    return spec


def label_shape_routing_cell(
    shape: str,
    services: int = 256,
    workers: int = 4,
    route_label: str = "app",
) -> dict:
    """The routing/ownership proof for one label shape: every
    service's DOC route key and SERIES route key resolve to the same
    ring owner (doc↔series co-location — the invariant the mesh claim
    filter, the dirty set's ownership probe, and the receiver's
    accept-and-hint all assume), regardless of extra cluster/tenant
    labels; and ownership stays spread (no shape may collapse the
    fleet onto one member). Raises AssertionError on violation;
    returns the cell row for the bench table."""
    from foremast_tpu.ingest.wire import canonical_series
    from foremast_tpu.jobs.models import Document
    from foremast_tpu.mesh.partition import HashRing
    from foremast_tpu.mesh.routing import doc_route_key, series_route_key

    ring = HashRing([f"w{i}" for i in range(workers)])
    owners: dict[str, int] = {}
    for s in range(services):
        selector = scenario_selector(shape, s)
        key = canonical_series(selector)
        doc = Document(id=f"job-{s}", app_name=f"app{s}")
        rk_doc = doc_route_key(doc)
        rk_series = series_route_key(key, route_label)
        assert rk_doc == rk_series == f"app{s}", (
            shape, selector, rk_doc, rk_series,
        )
        owner = ring.owner(rk_doc)
        assert owner == ring.owner(rk_series), (shape, s)
        owners[owner] = owners.get(owner, 0) + 1
    # spread sanity: with blake2b points, 256 keys over 4 workers
    # cannot legally land on one member; a collapse means the label
    # shape leaked into the route key
    assert len(owners) == workers, owners
    return {
        "config": "q-label-shape-routing",
        "label_shape": shape,
        "services": services,
        "workers": workers,
        "owners": {k: owners[k] for k in sorted(owners)},
        "co_located": True,
    }


def scenario_matrix(b: int, th: int, tc: int, seed: int = 0) -> list[dict]:
    """The full strategy x regime sweep, one row dict per cell —
    `make bench-mixed` prints these (extends the `fleet_mix` table
    with the strategy dimension)."""
    rows = []
    for strategy in STRATEGIES:
        for regime in REGIMES:
            f1, precision, recall, differs = score_scenario(
                strategy, regime, b, th, tc, seed
            )
            rows.append(
                {
                    "scenario": f"{strategy}/{regime}",
                    "strategy": strategy,
                    "regime": regime,
                    "f1": round(f1, 3),
                    "precision": round(precision, 3),
                    "recall": round(recall, 3),
                    "pairwise_differs_rate": round(differs, 4),
                }
            )
    return rows
