"""Columnar fast-tick coverage (ADVICE r4): engagement, slow-path parity,
band-mode fidelity, and per-key admission revalidation under churn.

The fast path (`jobs/worker.py _fast_tick` + `judge.judge_columnar`) is
the default production route for every warm re-check tick, so these tests
pin (a) that it actually engages on settled query_range-style URLs,
(b) that its verdicts/anomaly_info match the object path bit for bit for
both the deployed default and a gap-sensitive seasonal algorithm, and
(c) that hooks receive the same band shape on warm ticks as cold ones.
"""

import numpy as np
import pytest

from benchmarks.worker_bench import build_fleet, build_mixed_fleet
from foremast_tpu.config import BrainConfig
from foremast_tpu.jobs import (
    BrainWorker,
    STATUS_COMPLETED_UNHEALTH,
    STATUS_PREPROCESS_COMPLETED,
)

NOW = 1_760_000_000.0
HIST_LEN = 512
CUR_LEN = 30


def _mk_worker(services, algorithm, season, band_mode="last", hook=None,
               seed=0, baseline_frac=0.0):
    if baseline_frac > 0:
        store, source, _ = build_mixed_fleet(
            services, HIST_LEN, CUR_LEN, NOW, seed=seed,
            baseline_frac=baseline_frac,
        )
    else:
        store, source = build_fleet(
            services, HIST_LEN, CUR_LEN, NOW, seed=seed
        )
    cfg = BrainConfig(algorithm=algorithm, season_steps=season,
                      max_cache_size=4 * services + 64)
    worker = BrainWorker(
        store, source, config=cfg, claim_limit=services,
        worker_id="fast-w", band_mode=band_mode, on_verdict=hook,
    )
    return worker, store, source


def _count_columnar(worker):
    """Wrap the univariate judge's judge_columnar with a call counter."""
    calls = []
    orig = worker._uni.judge_columnar

    def counting(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    worker._uni.judge_columnar = counting
    return calls


def _statuses(store):
    return {
        d.id: (d.status, d.reason, d.anomaly_info)
        for d in store._docs.values()
    }


def _force_slow(worker):
    worker._fast_tick = lambda docs, now: (0, docs)


@pytest.mark.parametrize(
    "algorithm,season",
    [("moving_average_all", 24), ("auto_univariate", 24)],
    ids=["deployed-default", "gap-sensitive-seasonal"],
)
def test_fast_path_engages_and_matches_slow_path(algorithm, season):
    """Two ticks: tick 1 is cold (object path fits + caches), tick 2 must
    take the columnar path and produce the SAME statuses and anomaly_info
    the object path would (ADVICE r4 medium: zero fast-path coverage)."""
    services = 6
    fast_w, fast_store, fast_src = _mk_worker(services, algorithm, season)
    slow_w, slow_store, slow_src = _mk_worker(services, algorithm, season)
    _force_slow(slow_w)
    calls = _count_columnar(fast_w)

    assert fast_w.tick(now=NOW + 150) == services
    assert slow_w.tick(now=NOW + 150) == services
    assert not calls, "cold tick must not take the fast path"
    assert _statuses(fast_store) == _statuses(slow_store)

    # spike one service's current window before the re-check tick so the
    # fast path must carry anomaly pairs through to anomaly_info
    for src in (fast_src, slow_src):
        url = next(u for u in src.data if "cur" in u and "latency:app3" in u)
        ct, cv = src.data[url]
        spiked = cv.copy()
        spiked[-3:] = 40.0
        src.data[url] = (ct, spiked)

    assert fast_w.tick(now=NOW + 200) == services
    assert slow_w.tick(now=NOW + 200) == services
    assert calls, "warm re-check tick must take the columnar fast path"
    fast_s, slow_s = _statuses(fast_store), _statuses(slow_store)
    assert fast_s == slow_s
    spiked_status = fast_s["job-3"]
    assert spiked_status[0] == STATUS_COMPLETED_UNHEALTH
    # anomaly_info carries per-alias flat [t, v, ...] pairs
    assert "latency" in spiked_status[2]["values"]
    healthy = [v for k, v in fast_s.items() if k != "job-3"]
    assert all(s[0] == STATUS_PREPROCESS_COMPLETED for s in healthy)


def test_fast_path_full_band_mode_keeps_band_shape():
    """band_mode="full" + an on_verdict hook must see the whole [Tc] band
    on BOTH cold and warm ticks (ADVICE r4 medium: the fast path silently
    truncated warm bands to length 1)."""
    band_lens = []

    def hook(doc, verdicts):
        band_lens.append([len(v.upper) for v in verdicts])

    worker, store, _ = _mk_worker(
        3, "moving_average_all", 24, band_mode="full", hook=hook
    )
    calls = _count_columnar(worker)
    worker.tick(now=NOW + 150)
    worker.tick(now=NOW + 200)
    assert calls, "warm tick should engage the fast path"
    assert band_lens, "hook never ran"
    for lens in band_lens:
        assert all(n == CUR_LEN for n in lens), band_lens


def test_fast_path_last_band_mode_is_length_one_on_warm():
    """Default band_mode="last": hooks get a length-1 band (documented
    contract — `upper[-1]` consumers) on every tick."""
    band_lens = []

    def hook(doc, verdicts):
        band_lens.append([len(v.upper) for v in verdicts])

    worker, _, _ = _mk_worker(
        3, "moving_average_all", 24, band_mode="last", hook=hook
    )
    worker.tick(now=NOW + 150)
    worker.tick(now=NOW + 200)
    assert all(n == 1 for lens in band_lens for n in lens)


def test_admission_revalidates_per_key_not_wholesale():
    """A fit-cache version bump (churn: one cold fit somewhere) must NOT
    force a full admission re-walk: entries whose fit objects are
    unchanged revalidate by identity and stay admitted; an entry whose
    fit was replaced under the same key is re-admitted with the new
    object (VERDICT r4 ask #4)."""
    services = 4
    worker, store, src = _mk_worker(services, "moving_average_all", 24)
    worker.tick(now=NOW + 150)
    worker.tick(now=NOW + 160)
    admit = worker._admit
    assert len(admit) == services
    token0 = {k: v[3] for k, v in admit.items()}

    # unrelated churn: bump the fit-cache version without touching any
    # admitted entry — every doc must stay admitted via revalidation
    worker._fit_cache.put(("x", 1, "unrelated"), (0.0, 0.0, np.zeros(1,
                          np.float32), 0, 1.0, 1))
    calls = _count_columnar(worker)
    worker.tick(now=NOW + 170)
    assert calls
    assert len(admit) == services
    assert all(admit[k][3] != token0[k] for k in admit)  # restamped

    # same-key refit: replace job-0's latency entry object; only that
    # doc's admission row may change, and it must pick up the NEW object
    key = next(
        k for k, v in worker._fit_cache._d.items()
        if "app0" in str(k) and "latency" in str(k)
    )
    old = worker._fit_cache.peek(key)
    replacement = tuple(old)  # equal value, different identity
    worker._fit_cache.put(key, replacement)
    rows_before = {k: v[1] for k, v in admit.items()}
    worker.tick(now=NOW + 180)
    assert any(r[3] is replacement for r in admit["job-0"][1])
    for k in admit:
        if k != "job-0":
            assert admit[k][1] is rows_before[k]  # untouched rowsinfo


@pytest.mark.parametrize(
    "algorithm", ["moving_average_all", "auto_univariate"],
    ids=["ma-moments-shortcut", "seasonal-reconstruct"],
)
def test_cold_fit_bf16_upload_matches_f32(monkeypatch, algorithm):
    """Cold fits upload anchor+bf16 deltas (FOREMAST_BF16_DELTA, default
    on): the deployed default via the moments shortcut, every other
    algorithm via in-program reconstruction. Verdicts, reasons, and
    anomaly_info must match the f32 fit path on both the cold tick and
    the warm re-check tick that scores from the cached state."""
    services = 5
    a_w, a_store, a_src = _mk_worker(services, algorithm, 24)
    b_w, b_store, b_src = _mk_worker(services, algorithm, 24)

    for src in (a_src, b_src):
        url = next(u for u in src.data if "cur" in u and "latency:app2" in u)
        ct, cv = src.data[url]
        spiked = cv.copy()
        spiked[-2:] = 40.0
        src.data[url] = (ct, spiked)

    assert a_w.tick(now=NOW + 150) == services  # bf16 fit upload (default)
    monkeypatch.setenv("FOREMAST_BF16_DELTA", "0")
    assert b_w.tick(now=NOW + 150) == services  # f32 fit upload
    monkeypatch.delenv("FOREMAST_BF16_DELTA")
    assert _statuses(a_store) == _statuses(b_store)
    assert _statuses(a_store)["job-2"][0] == STATUS_COMPLETED_UNHEALTH

    # the spiked doc is terminal; the warm tick re-checks the rest
    assert a_w.tick(now=NOW + 200) == services - 1
    monkeypatch.setenv("FOREMAST_BF16_DELTA", "0")
    assert b_w.tick(now=NOW + 200) == services - 1
    assert _statuses(a_store) == _statuses(b_store)


# -- canary columnar bucket (ISSUE 14) --------------------------------------


def _hook_recorder(records):
    def hook(doc, verdicts):
        for v in verdicts:
            records.append(
                (
                    doc.id,
                    v.alias,
                    int(v.verdict),
                    tuple(v.anomaly_pairs),
                    np.asarray(v.upper, np.float32).tobytes(),
                    np.asarray(v.lower, np.float32).tobytes(),
                    round(float(v.p_value), 7),
                    bool(v.dist_differs),
                )
            )

    return hook


@pytest.mark.parametrize(
    "algorithm,season",
    [("moving_average_all", 24), ("auto_univariate", 24)],
    ids=["deployed-default", "gap-sensitive-seasonal"],
)
def test_canary_fast_path_engages_and_matches_object_path(algorithm, season):
    """Baseline-carrying (canary) docs must ride the columnar fast tick
    as their own bucket (ISSUE 14) and produce statuses, anomaly_info,
    AND hook verdicts (bands + pairwise p/differs) byte-identical to
    the object path — including a doc whose BASELINE distribution
    shifted (dist_differs=True lowers the threshold in-program)."""
    services = 6
    fast_rec, slow_rec = [], []
    fast_w, fast_store, fast_src = _mk_worker(
        services, algorithm, season, baseline_frac=0.5,
        hook=_hook_recorder(fast_rec), band_mode="full",
    )
    slow_w, slow_store, slow_src = _mk_worker(
        services, algorithm, season, baseline_frac=0.5,
        hook=_hook_recorder(slow_rec), band_mode="full",
    )
    _force_slow(slow_w)
    calls = _count_columnar(fast_w)

    assert fast_w.tick(now=NOW + 150) == services
    assert slow_w.tick(now=NOW + 150) == services
    assert not calls, "cold tick must not take the fast path"
    assert _statuses(fast_store) == _statuses(slow_store)

    # spike one canary doc's current window, and SHIFT another canary
    # doc's baseline distribution (the rank tests must reject and lower
    # the threshold identically on both paths)
    for src in (fast_src, slow_src):
        url = next(
            u for u in src.data
            if u.startswith("http://prom/cur") and "latency:app1&" in u
        )
        ct, cv = src.data[url]
        spiked = cv.copy()
        spiked[-3:] = 40.0
        src.data[url] = (ct, spiked)
        burl = next(
            u for u in src.data
            if u.startswith("http://prom/base") and "latency:app0&" in u
        )
        bt, bv = src.data[burl]
        src.data[burl] = (bt, (bv + 0.5).astype(np.float32))

    fast_rec.clear()
    slow_rec.clear()
    assert fast_w.tick(now=NOW + 200) == services
    assert slow_w.tick(now=NOW + 200) == services
    assert calls, "warm re-check tick must take the columnar fast path"
    assert fast_w._fast_kinds["baseline"] > 0, fast_w._fast_kinds
    fast_s, slow_s = _statuses(fast_store), _statuses(slow_store)
    assert fast_s == slow_s
    assert fast_s["job-1"][0] == STATUS_COMPLETED_UNHEALTH
    assert sorted(fast_rec) == sorted(slow_rec)
    # the shifted-baseline doc's hook verdicts must carry the REAL
    # device pairwise outcome, not the baseline-less constants
    differs = [r for r in fast_rec if r[0] == "job-0" and r[7]]
    assert differs, "shifted baseline never rejected same-distribution"
    assert all(r[6] < 0.05 for r in differs)


def test_canary_doc_with_partial_baseline_aliases():
    """A canary doc where only SOME aliases carry baselines: the
    baseline-less aliases judge with the hardwired (p=1, False) inside
    the pairwise-active program (all-masked baseline rows), matching
    the object path bit for bit."""
    services = 3
    fast_rec, slow_rec = [], []
    fast_w, fast_store, fast_src = _mk_worker(
        services, "moving_average_all", 24, baseline_frac=1.0,
        hook=_hook_recorder(fast_rec),
    )
    slow_w, slow_store, slow_src = _mk_worker(
        services, "moving_average_all", 24, baseline_frac=1.0,
        hook=_hook_recorder(slow_rec),
    )
    _force_slow(slow_w)
    # strip ONE alias's baseline from one doc on both fleets: the doc
    # stays canary-shaped but carries a baseline-less row
    for store in (fast_store, slow_store):
        doc = store._docs["job-2"]
        parts = doc.baseline_config.split(" ||")
        doc.baseline_config = " ||".join(parts[1:])
    assert fast_w.tick(now=NOW + 150) == services
    assert slow_w.tick(now=NOW + 150) == services
    fast_rec.clear()
    slow_rec.clear()
    assert fast_w.tick(now=NOW + 200) == services
    assert slow_w.tick(now=NOW + 200) == services
    assert fast_w._fast_kinds["baseline"] == services
    assert _statuses(fast_store) == _statuses(slow_store)
    assert sorted(fast_rec) == sorted(slow_rec)
    # the stripped alias reports the baseline-less constants
    stripped = [r for r in fast_rec if r[0] == "job-2" and r[1] == "latency"]
    assert stripped and all(r[6] == 1.0 and not r[7] for r in stripped)
