"""Joint (multi-alias) columnar fast-path coverage — ISSUE 4 tentpole.

Serial-vs-columnar equivalence for mixed univariate/joint claim sets:
once a joint doc's bivariate/LSTM-hybrid fit is cached, the warm tick
claims it onto the columnar path (`worker._judge_joint_fast` +
`MultivariateJudge.joint_columnar`, scoring from arena-resident state)
— and must produce the SAME statuses, anomaly payloads, store-write
set, fit-cache keys, and hook verdicts as the per-task object path on
identical claims.
"""

import dataclasses
import logging

import numpy as np
import pytest

from benchmarks.worker_bench import build_mixed_fleet
from foremast_tpu.config import BrainConfig
from foremast_tpu.jobs import (
    BrainWorker,
    STATUS_COMPLETED_HEALTH,
    STATUS_COMPLETED_UNHEALTH,
    STATUS_COMPLETED_UNKNOWN,
    STATUS_PREPROCESS_COMPLETED,
)

NOW = 1_760_000_000.0
HIST_LEN = 256
CUR_LEN = 30
SERVICES = 12  # 2 joint (1 bivariate + 1 lstm) + 10 single-alias


def _mk_worker(joint_fast: bool, hook=None, services: int = SERVICES,
               algorithm: str = "auto", joint_frac: float = 0.17):
    store, source, windows = build_mixed_fleet(
        services, HIST_LEN, CUR_LEN, NOW, joint_frac=joint_frac
    )
    cfg = BrainConfig(algorithm=algorithm, season_steps=24,
                      max_cache_size=4 * services + 64)
    # joint detectors read the base threshold; calibrate at 4 sigma like
    # the quality scenarios (2.0 would page on clean windows)
    cfg = dataclasses.replace(
        cfg, anomaly=dataclasses.replace(cfg.anomaly, threshold=4.0)
    )
    worker = BrainWorker(
        store, source, config=cfg, claim_limit=2 * services,
        worker_id="joint-w", on_verdict=hook,
    )
    worker.judge.lstm_steps = 10  # CI speed; identical on both workers
    if not joint_fast:
        worker._joint_fast = False
    return worker, store, source, windows


def _statuses(store):
    return {
        d.id: (d.status, d.reason, d.anomaly_info)
        for d in store._docs.values()
    }


def _record_writes(store):
    writes = []
    orig_update, orig_many = store.update, store.update_many

    def _u(doc):
        writes.append((doc.id, doc.status))
        return orig_update(doc)

    def _um(docs):
        writes.extend((d.id, d.status) for d in docs)
        return orig_many(docs)

    store.update, store.update_many = _u, _um
    return writes


def _spike_joint(source, sid: str, f: int):
    """Push every metric of a joint service up 0.6 (≈8 idio-sigmas) on
    the last 3 points — the quality scenarios' all-metric spike."""
    for m in range(f):
        url = f"http://prom/cur?q=m{m}:app{sid}&step=60"
        ct, cv = source.data[url]
        spiked = cv.copy()
        spiked[-3:] += 0.6
        source.data[url] = (ct, spiked)


def test_joint_fast_path_engages_and_matches_object_path():
    """Tick 1 is cold (object path fits + caches joint models); tick 2
    must claim the joint docs onto the columnar path and produce the
    same statuses, anomaly_info, write set, and fit-cache keys the
    object path would."""
    verdicts_a, verdicts_b = {}, {}
    hook_a = lambda doc, vs: verdicts_a.setdefault(doc.id, []).append(vs)
    hook_b = lambda doc, vs: verdicts_b.setdefault(doc.id, []).append(vs)
    a, a_store, a_src, windows = _mk_worker(True, hook=hook_a)
    b, b_store, b_src, _ = _mk_worker(False, hook=hook_b)

    assert a.tick(now=NOW + 150) == SERVICES
    assert b.tick(now=NOW + 150) == SERVICES
    assert _statuses(a_store) == _statuses(b_store)
    assert a._fast_kinds["bivariate"] == 0  # cold tick: slow path only
    assert a._fast_kinds["lstm"] == 0

    # spike the lstm joint doc (sid 1, f=4) so anomaly pairs cross the
    # columnar path; the bivariate doc (sid 0) stays clean
    for src in (a_src, b_src):
        _spike_joint(src, "1", 4)

    writes_a = _record_writes(a_store)
    writes_b = _record_writes(b_store)
    assert a.tick(now=NOW + 200) == SERVICES
    assert b.tick(now=NOW + 200) == SERVICES
    sa, sb = _statuses(a_store), _statuses(b_store)
    assert sa == sb
    assert sa["job-1"][0] == STATUS_COMPLETED_UNHEALTH
    assert set(sa["job-1"][2]["values"]) == {"m0", "m1", "m2", "m3"}
    assert sa["job-0"][0] == STATUS_PREPROCESS_COMPLETED

    # the columnar worker actually took the joint fast path; the object
    # worker never did
    assert a._fast_kinds["bivariate"] == 1 and a._fast_kinds["lstm"] == 1
    assert b._fast_kinds["bivariate"] == 0 and b._fast_kinds["lstm"] == 0
    ja = a._mvj.joint_state_counters()
    assert ja["misses"] == 2 and ja["rows_live"] == 2

    # same write SET (the columnar path batches its update_many, so the
    # order differs; the persisted outcomes may not)
    assert sorted(writes_a) == sorted(writes_b)
    # same joint fit-cache key population
    assert set(a._mvj.cache._d) == set(b._mvj.cache._d)
    assert set(a._mvj.joint_meta._d) == set(b._mvj.joint_meta._d)

    # hook verdict parity on the warm tick for the joint docs: same
    # verdicts, pairs, FULL marginal bands, and pairwise evidence
    for doc_id in ("job-0", "job-1"):
        va, vb = verdicts_a[doc_id][-1], verdicts_b[doc_id][-1]
        assert len(va) == len(vb)
        for x, y in zip(va, vb):
            assert (x.alias, x.verdict, x.anomaly_pairs) == (
                y.alias, y.verdict, y.anomaly_pairs
            )
            np.testing.assert_array_equal(x.upper, y.upper)
            np.testing.assert_array_equal(x.lower, y.lower)
            assert (x.p_value, x.dist_differs) == (y.p_value, y.dist_differs)


def test_joint_admission_revalidates_by_identity():
    """A joint-cache version bump (unrelated churn) must not evict the
    admission cache: entries revalidate by identity and stay admitted."""
    a, a_store, _, _ = _mk_worker(True)
    a.tick(now=NOW + 150)
    a.tick(now=NOW + 160)
    assert len(a._jadmit) == 2  # both joint docs admitted
    token0 = {k: v[2] for k, v in a._jadmit.items()}
    jinfo0 = {k: v[1] for k, v in a._jadmit.items()}

    # unrelated churn: bump both cache versions without touching the
    # admitted entries
    a._mvj.cache.put(("unrelated",), (1,))
    a._mvj.joint_meta.put(("unrelated-meta",), (1,))
    a.tick(now=NOW + 170)
    assert len(a._jadmit) == 2
    for k in a._jadmit:
        assert a._jadmit[k][2] != token0[k]  # restamped
        assert a._jadmit[k][1] is jinfo0[k]  # jinfo NOT rebuilt
    counters = a._mvj.joint_state_counters()
    assert counters["hits"] >= 2  # tick 3 gathered, not re-scattered


def test_joint_fast_matches_slow_under_explicit_bivariate_algorithm():
    """ML_ALGORITHM=bivariate_normal: 2-alias docs ride the joint
    columnar path; the 1-alias docs fall to the univariate fallback
    (still columnar, kind=univariate)."""
    a, a_store, a_src, _ = _mk_worker(True, algorithm="bivariate_normal")
    b, b_store, b_src, _ = _mk_worker(False, algorithm="bivariate_normal")
    assert a.tick(now=NOW + 150) == SERVICES
    assert b.tick(now=NOW + 150) == SERVICES
    # off-ridge spike on the bivariate doc (sid 0): x up, y down
    for src in (a_src, b_src):
        u0 = "http://prom/cur?q=m0:app0&step=60"
        u1 = "http://prom/cur?q=m1:app0&step=60"
        ct, cv = src.data[u0]
        s = cv.copy()
        s[-2:] += 1.0
        src.data[u0] = (ct, s)
        ct, cv = src.data[u1]
        s = cv.copy()
        s[-2:] -= 1.0
        src.data[u1] = (ct, s)
    assert a.tick(now=NOW + 200) == SERVICES
    assert b.tick(now=NOW + 200) == SERVICES
    assert _statuses(a_store) == _statuses(b_store)
    assert _statuses(a_store)["job-0"][0] == STATUS_COMPLETED_UNHEALTH
    assert a._fast_kinds["bivariate"] == 1
    assert a._fast_kinds["univariate"] > 0


def test_joint_window_bucket_drift_demotes_to_slow_path():
    """A joint doc whose current-window bucket drifts from the fitted
    one must be refit on the slow path, not scored through the wrong
    compiled program — and the verdict must match the object path's."""
    a, a_store, a_src, _ = _mk_worker(True)
    b, b_store, b_src, _ = _mk_worker(False)
    assert a.tick(now=NOW + 150) == SERVICES
    assert b.tick(now=NOW + 150) == SERVICES
    # grow the lstm doc's current windows past the 32-bucket (33 > 32)
    for src in (a_src, b_src):
        for m in range(4):
            url = f"http://prom/cur?q=m{m}:app1&step=60"
            ct, cv = src.data[url]
            ct2 = np.concatenate([ct, ct[-1:] + 60 * np.arange(1, 4)])
            cv2 = np.concatenate([cv, cv[-3:]]).astype(np.float32)
            src.data[url] = (ct2, cv2)
    from foremast_tpu.chaos.degrade import REASON_DEMOTED

    demoted_before = a._degrade.stats.docs_snapshot().get(REASON_DEMOTED, 0)
    assert a.tick(now=NOW + 200) == SERVICES
    assert b.tick(now=NOW + 200) == SERVICES
    assert _statuses(a_store) == _statuses(b_store)
    # the drifted doc went through the slow path, not the lstm bucket
    assert a._fast_kinds["lstm"] == 0
    # ... and the demotion was COUNTED on the degraded-docs counter
    # (ISSUE 14 satellite: it used to ride the slow leftovers silently)
    demoted_after = a._degrade.stats.docs_snapshot().get(REASON_DEMOTED, 0)
    assert demoted_after == demoted_before + 1, (
        demoted_before, demoted_after,
    )


def test_debug_state_carries_joint_counters():
    a, _, _, _ = _mk_worker(True)
    a.tick(now=NOW + 150)
    a.tick(now=NOW + 200)
    state = a.debug_state()
    assert state["fast_path_docs"]["bivariate"] == 1
    assert state["fast_path_docs"]["lstm"] == 1
    assert state["joint_arena"]["rows_live"] == 2


def test_worker_metrics_fast_docs_counter():
    from prometheus_client import CollectorRegistry

    from foremast_tpu.observe.gauges import WorkerMetrics

    reg = CollectorRegistry()
    a, a_store, a_src, _ = _mk_worker(True)
    a.metrics = WorkerMetrics(registry=reg)
    a.tick(now=NOW + 150)
    a.tick(now=NOW + 200)
    got = {
        s.labels["kind"]: s.value
        for fam in reg.collect()
        if fam.name == "foremast_worker_fast_docs"
        for s in fam.samples
        if s.name.endswith("_total")
    }
    assert got.get("bivariate") == 1.0
    assert got.get("lstm") == 1.0
    assert got.get("univariate", 0) >= 1.0


def test_lstm_mixed_window_buckets_merge_into_one_dispatch():
    """VERDICT r5 #10 satellite: lstm docs fitted at DIFFERENT window
    buckets score in ONE merged dispatch (padded to the widest bucket)
    on the fast path — and the merged program's flags match the object
    path exactly, spikes included."""
    from benchmarks.quality import draw_comoving

    hist_len, long_cur = 1280, 300  # buckets 32 (CUR_LEN) and 512
    verdicts_a = {}
    a, a_store, a_src, _ = _mk_worker(
        True, hook=lambda d, vs: verdicts_a.setdefault(d.id, []).append(vs),
        joint_frac=0.34,
    )
    b, b_store, b_src, _ = _mk_worker(False, joint_frac=0.34)

    # regenerate lstm service 3 with a long history + long current so
    # its fitted bucket is 512 while service 1 stays at 32
    t_now = int(NOW)
    ht = t_now - 86_400 * 7 + 60 * np.arange(HIST_LEN, dtype=np.int64)
    ht3 = ht[-1] - 60 * np.arange(hist_len, dtype=np.int64)[::-1]
    ct3 = ht[-1] + 60 + 60 * np.arange(long_cur, dtype=np.int64)
    r = np.random.default_rng(99)
    hist3 = draw_comoving(r, 1, 4, hist_len, 0)[0]
    cur3 = draw_comoving(r, 1, 4, long_cur, hist_len)[0]
    for src in (a_src, b_src):
        for m in range(4):
            src.data[f"http://prom/cur?q=m{m}:app3&step=60"] = (
                ct3, cur3[m].copy()
            )
            src.data[
                f"http://prom/hist?q=m{m}:app3&end={ht[-1] + 60}&step=60"
            ] = (ht3, hist3[m].copy())

    assert a.tick(now=NOW + 150) == SERVICES
    assert b.tick(now=NOW + 150) == SERVICES
    assert _statuses(a_store) == _statuses(b_store)

    # spike the SHORT-bucket lstm doc: its flags must decode correctly
    # out of the merged (wider) dispatch
    for src in (a_src, b_src):
        _spike_joint(src, "1", 4)
    assert a.tick(now=NOW + 200) == SERVICES
    assert b.tick(now=NOW + 200) == SERVICES
    sa = _statuses(a_store)
    assert sa == _statuses(b_store)
    assert sa["job-1"][0] == STATUS_COMPLETED_UNHEALTH
    assert sa["job-3"][0] == STATUS_PREPROCESS_COMPLETED
    # both lstm docs rode the columnar path (merged dispatch)
    assert a._fast_kinds["lstm"] == 2
    assert b._fast_kinds["lstm"] == 0


# ---------------------------------------------------------------------------
# The slice's joint windows are packed in bulk (jobs/joint_pack.py) and
# decided in bulk (jobs/joint_decide.py). The per-doc loop they replaced
# stays HERE as the oracle: for the same `ok_joint`, everything handed to
# `joint_columnar`, every status / payload / hook verdict, the order of
# `updated`, the worker's job and window counters and the judgment log lines
# must be identical.
# ---------------------------------------------------------------------------


def _oracle_judge_joint_fast(self, ok_joint, now):
    """`BrainWorker._judge_joint_fast` and its decide as one per-doc loop
    (spans left out)."""
    from foremast_tpu.engine import (
        HEALTHY, UNHEALTHY, UNKNOWN, MetricVerdict,
    )
    from foremast_tpu.engine.judge import bucket_length
    from foremast_tpu.engine.multivariate import align_series

    hook = self.on_verdict
    judge = self._mvj
    thr = float(np.float32(judge.config.anomaly.rule_for(None).threshold))
    updated, demoted, empty = [], [], []
    counts = {"univariate": 0, "bivariate": 0, "lstm": 0}
    groups = {}
    for (doc, end_epoch, jinfo), series in ok_joint:
        mode = jinfo[0]
        times = [s[0] for s in series]
        vals = [s[1] for s in series]
        t0 = np.asarray(times[0], np.int64)
        if (
            len(t0) > 0
            and bool(np.all(np.diff(t0) > 0))
            and all(
                len(t) == len(t0) and np.array_equal(t, t0)
                for t in times[1:]
            )
        ):
            ct = t0
            cv = np.stack([np.asarray(v, np.float32) for v in vals])
        else:
            ct, cv = align_series(times, vals)
        n = len(ct)
        if n == 0:
            empty.append((doc, end_epoch, jinfo, vals))
            continue
        tcb = bucket_length(n)
        if jinfo[0] == "lstm" and tcb != jinfo[6][0]:
            demoted.append(doc)
            continue
        groups.setdefault((mode, len(jinfo[1])), []).append(
            (doc, end_epoch, jinfo, ct, cv, n)
        )
    for doc, end_epoch, jinfo, vals in empty:
        self._decide_status(doc, UNKNOWN, {}, now, end_epoch)
        self._log_judged(doc)
        updated.append(doc)
        counts[jinfo[0]] += 1
        if self.metrics:
            self.metrics.observe_doc(doc.status, len(jinfo[1]))
        if hook:
            hook(doc, [
                MetricVerdict(
                    job_id=doc.id, alias=alias, verdict=UNKNOWN,
                    anomaly_pairs=[],
                    upper=np.zeros(len(vals[f_i]), np.float32),
                    lower=np.zeros(len(vals[f_i]), np.float32),
                    p_value=1.0, dist_differs=False,
                )
                for f_i, alias in enumerate(jinfo[1])
            ])
    for (mode, f), sub in groups.items():
        s = len(sub)
        if mode == "lstm":
            tcb = max(it[2][6][0] for it in sub)
        else:
            tcb = bucket_length(max(it[5] for it in sub))
        cur = np.zeros((s, f, tcb), np.float32)
        mask = np.zeros((s, tcb), bool)
        gaps = np.zeros(s, np.int32) if mode == "lstm" else None
        keys, entries, metas = [], [], []
        for i, (doc, end_epoch, jinfo, ct, cv, n) in enumerate(sub):
            cur[i, :, :n] = cv[:, :n]
            mask[i, :n] = True
            keys.append(jinfo[3])
            entries.append(jinfo[4])
            metas.append(jinfo[6])
            if mode == "lstm":
                meta = jinfo[6]
                k = int(round((float(ct[0]) - meta[4]) / max(meta[3], 1.0)))
                gaps[i] = max(k - 1, 0)
        flags = judge.joint_columnar(
            mode, keys, entries, metas, cur, mask, gaps
        )
        for i, (doc, end_epoch, jinfo, ct, cv, n) in enumerate(sub):
            fl = flags[i, :n]
            jv = UNHEALTHY if fl.any() else HEALTHY
            values_map = {}
            if jv == UNHEALTHY:
                ft = ct[fl]
                for f_i, alias in enumerate(jinfo[1]):
                    pairs = np.empty(2 * len(ft), np.float64)
                    pairs[0::2] = ft
                    pairs[1::2] = cv[f_i][fl]
                    values_map[alias] = pairs.tolist()
            self._decide_status(doc, jv, values_map, now, end_epoch)
            self._log_judged(doc)
            updated.append(doc)
            counts[mode] += 1
            if self.metrics:
                self.metrics.observe_doc(doc.status, f)
            if hook:
                hook(doc, self._joint_verdicts(
                    doc, jinfo, ct, cv, n, fl, jv, thr
                ))
    return updated, demoted, counts


class _StubJudge:
    """Stands where `MultivariateJudge` does: records what
    `joint_columnar` is handed and flags every real point where some
    alias reads over 2.0 (about one clean doc in two carries a flag), and
    every padded column past a row's n, which no decide may count."""

    def __init__(self):
        from types import SimpleNamespace as NS

        self.config = NS(
            anomaly=NS(rule_for=lambda _name: NS(threshold=4.0))
        )
        self.calls = []

    def joint_columnar(self, mode, keys, entries, metas, cur, mask,
                       gaps=None, issued=None):
        if issued is None:
            issued = self.joint_columnar_issue(
                mode, keys, entries, metas, cur, mask, gaps
            )
        return issued.wait()

    def joint_columnar_issue(self, mode, keys, entries, metas, cur, mask,
                             gaps=None):
        from foremast_tpu.engine.kinds import JointPending

        self.calls.append((mode, keys, entries, metas, cur, mask, gaps))
        return JointPending(~mask | (cur.max(axis=1) > 2.0))


T0 = int(NOW) - 30 * 60


def _stamps(n=CUR_LEN):
    return T0 + 60 * np.arange(n, dtype=np.int64)


def _spec(mode="lstm", f=4, n=CUR_LEN, fitted=None, times=None, seed=0):
    """One doc of a slice: (mode, fitted bucket, [(times, values)] * F);
    `times` None = each alias brings its own equal array."""
    from foremast_tpu.engine.judge import bucket_length

    vals = np.random.default_rng(seed).standard_normal((f, n))
    series = [
        (_stamps(n) if times is None else times, vals[a].astype(np.float32))
        for a in range(f)
    ]
    if fitted is None:
        # the bivariate kind fits no window bucket: nothing to drift from
        fitted = bucket_length(n) if mode == "lstm" else 0
    return mode, fitted, series


def _edit(spec, alias, times=None, values=None):
    mode, fitted, series, *doc = spec
    series = list(series)
    t, v = series[alias]
    series[alias] = (t if times is None else times,
                     v if values is None else values)
    return (mode, fitted, series, *doc)


def _with(spec, **doc):
    """The spec's document given an `end` epoch (default an hour ahead)
    or a `trace_id`."""
    mode, fitted, series, *old = spec
    return mode, fitted, series, {**(old[0] if old else {}), **doc}


def _calm(spec):
    """The spec with its values at a tenth: no point reads over 2.0."""
    mode, fitted, series, *doc = spec
    return (mode, fitted, [(t, v * 0.1) for t, v in series], *doc)


def _spiked(spec, at=5):
    """The spec with its first alias at 3.0 in column `at`."""
    v = spec[2][0][1].copy()
    v[at] = 3.0
    return _edit(spec, 0, values=v)


def _nothing():
    """A 4-alias doc with no observation at all."""
    return (
        "lstm", 32,
        [(np.zeros(0, np.int64), np.zeros(0, np.float32))] * 4,
    )


def _case_shared_stamps():
    shared = _stamps()
    return [_spec(times=shared, seed=i) for i in range(6)]


def _case_distinct_equal_stamps():
    return [_spec(seed=i) for i in range(6)]


def _case_list_series():
    out = []
    for i in range(4):
        mode, fitted, series = _spec(seed=i)
        out.append((mode, fitted, [
            (t.tolist(), [float(x) for x in v]) for t, v in series
        ]))
    return out


def _case_alias_missing_a_point():
    ragged = _spec(seed=1)
    t, v = ragged[2][2]
    ragged = _edit(ragged, 2, times=np.delete(t, 7), values=np.delete(v, 7))
    return [_spec(seed=0), ragged, _spec(seed=2)]


def _case_duplicated_stamp():
    t = _stamps()
    t[11] = t[10]
    return [_spec(seed=0), _spec(times=t, seed=1), _spec(seed=2)]


def _case_non_monotone_stamps():
    t = _stamps()
    t[[4, 20]] = t[[20, 4]]
    return [_spec(times=t, seed=0), _spec(seed=1)]


def _case_unequal_stamps_same_length():
    shifted = _edit(_spec(seed=1), 3, times=_stamps() + 60)
    return [_spec(seed=0), shifted, _spec(seed=2)]


def _case_float_stamps_and_float64_values():
    mode, fitted, series = _spec(seed=3)
    series = [(t.astype(np.float64), v.astype(np.float64) * 1.0000001)
              for t, v in series]
    return [_spec(seed=0), (mode, fitted, series)]


def _case_one_empty_alias():
    gone = _edit(_spec(seed=1), 1, times=np.zeros(0, np.int64),
                 values=np.zeros(0, np.float32))
    return [_spec(seed=0), gone, _nothing(), _spec(seed=2)]


def _case_mixed_lengths_one_dispatch():
    # 30 -> bucket 32, 45 -> bucket 64: two classes, ONE lstm dispatch
    return [_spec(n=(30, 45)[i % 3 == 1], seed=i) for i in range(7)]


def _case_bucket_drift_demotes():
    # 33 points against a model fitted at 32: both the bulk test and
    # (ragged besides) the per-doc fallback must demote
    drifted = _spec(n=33, fitted=32, seed=1)
    ragged = _spec(n=34, fitted=32, seed=2)
    t, v = ragged[2][0]
    ragged = _edit(ragged, 0, times=t[:-1], values=v[:-1])
    return [_spec(seed=0), drifted, ragged, _spec(seed=3)]


def _case_bivariate():
    return [_spec(mode="bivariate", f=2, n=(30, 40)[i % 2], seed=i)
            for i in range(5)]


def _case_fallback_in_the_middle_keeps_its_row():
    docs = [_spec(seed=i) for i in range(9)]
    t, v = docs[4][2][1]
    docs[4] = _edit(docs[4], 1, times=np.delete(t, 0), values=np.delete(v, 0))
    return docs


def _case_kinds_interleaved():
    # group order = order of each kind's first packed doc; the first
    # lstm doc is demoted, so the bivariate group dispatches first
    return [
        _spec(n=33, fitted=32, seed=0),
        _spec(mode="bivariate", f=2, seed=1),
        _spec(seed=2),
        _spec(f=3, seed=3),
        _spec(mode="bivariate", f=2, seed=4),
        _spec(seed=5),
    ]


def _case_statuses_in_one_group():
    # closed windows (no endTime, past it, at it) beside open ones, clean
    # and flagged, and a window-less doc of each: every transition of the
    # state machine in one slice
    return [
        _with(_calm(_spec(seed=0)), end=0.0),
        _with(_calm(_spec(seed=1)), end=NOW - 60.0),
        _with(_calm(_spec(seed=2)), end=NOW),
        _calm(_spec(seed=3)),
        _with(_spiked(_calm(_spec(seed=4))), end=0.0),
        _spiked(_calm(_spec(seed=5))),
        _with(_nothing(), end=-1.0),
        _nothing(),
    ]


def _case_flag_past_n_is_padding():
    # nothing real reads over 2.0: the stub's only flags are the padded
    # columns of both classes (30 -> 64 and 45 -> 64), which count for no doc
    return [_calm(_spec(n=(30, 45)[i % 2], seed=i)) for i in range(4)]


def _case_traced_docs():
    # a doc that carries a trace ID logs its judgment, flagged, clean or
    # window-less; the others log nothing
    return [
        _with(_calm(_spec(seed=0)), trace_id="trace-a"),
        _calm(_spec(seed=1)),
        _with(_spiked(_calm(_spec(seed=2))), trace_id="trace-b", end=0.0),
        _with(_nothing(), trace_id="trace-c"),
    ]


_PACK_CASES = {
    # name: (builder, bulk, aligned, empty, demoted, dispatches)
    "shared_stamps": (_case_shared_stamps, 6, 0, 0, 0, 1),
    "distinct_equal_stamps": (_case_distinct_equal_stamps, 6, 0, 0, 0, 1),
    "list_series": (_case_list_series, 4, 0, 0, 0, 1),
    "alias_missing_a_point": (_case_alias_missing_a_point, 2, 1, 0, 0, 1),
    "duplicated_stamp": (_case_duplicated_stamp, 2, 1, 0, 0, 1),
    "non_monotone_stamps": (_case_non_monotone_stamps, 1, 1, 0, 0, 1),
    "unequal_stamps_same_length": (
        _case_unequal_stamps_same_length, 2, 1, 0, 0, 1),
    "float_stamps_float64_values": (
        _case_float_stamps_and_float64_values, 2, 0, 0, 0, 1),
    "one_empty_alias": (_case_one_empty_alias, 2, 0, 2, 0, 1),
    "mixed_lengths_one_dispatch": (
        _case_mixed_lengths_one_dispatch, 7, 0, 0, 0, 1),
    "bucket_drift_demotes": (_case_bucket_drift_demotes, 2, 0, 0, 2, 1),
    "bivariate": (_case_bivariate, 5, 0, 0, 0, 1),
    "fallback_in_the_middle": (
        _case_fallback_in_the_middle_keeps_its_row, 8, 1, 0, 0, 1),
    "kinds_interleaved": (_case_kinds_interleaved, 5, 0, 0, 1, 3),
    "statuses_in_one_group": (_case_statuses_in_one_group, 6, 0, 2, 0, 1),
    "flag_past_n_is_padding": (_case_flag_past_n_is_padding, 4, 0, 0, 0, 1),
    "traced_docs": (_case_traced_docs, 3, 0, 1, 0, 1),
}

# the statuses a case's docs end in, where they are not "some flagged doc
# among others"
_CASE_STATUSES = {
    "one_empty_alias": None,
    "statuses_in_one_group": {
        STATUS_COMPLETED_HEALTH,
        STATUS_COMPLETED_UNHEALTH,
        STATUS_COMPLETED_UNKNOWN,
        STATUS_PREPROCESS_COMPLETED,
    },
    "flag_past_n_is_padding": {STATUS_PREPROCESS_COMPLETED},
}


def _slice_of(specs):
    """`ok_joint` as `_fetch_fast` hands it over, on fresh documents."""
    from foremast_tpu.jobs.models import Document

    ok_joint = []
    for i, (mode, fitted, series, *opt) in enumerate(specs):
        opt = opt[0] if opt else {}
        f = len(series)
        aliases = tuple(f"m{a}" for a in range(f))
        # history's last stamp 1, 1.5, 2.5 or 3.5 steps before the
        # window's first: the halves pin round()'s half-to-even
        last_ts = T0 - (60, 90, 150, 210)[i % 4]
        meta = (fitted, np.full(f, 0.1 * i), np.full(f, 1.0), 60.0,
                last_ts, 512)
        jinfo = (
            mode, aliases, tuple(f"u{i}-{a}" for a in aliases),
            (mode, f"app{i}", aliases), ("entry", i),
            ("jmeta", mode, f"app{i}"), meta,
        )
        doc = Document(
            id=f"job-{i}", app_name=f"app{i}",
            trace_id=opt.get("trace_id", ""),
        )
        ok_joint.append(((doc, opt.get("end", NOW + 3600.0), jinfo), series))
    return ok_joint


def _judge_joint_fast(worker, ok_joint, now):
    """The worker's warm joint judgment, both halves, as `_fast_tick`
    runs them."""
    return worker._decide_joint_fast(
        worker._judge_joint_fast(ok_joint, now), now
    )


class _JudgmentLines(logging.Handler):
    """The worker's `judgment` log lines: (level, job, status, trace)."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.lines = []

    def emit(self, record):
        if record.getMessage() == "judgment":
            c = record.ctx
            self.lines.append(
                (record.levelno, c["job_id"], c["status"], c["job_trace_id"])
            )


def _run_pack(judge_fn, specs, hook=True):
    from prometheus_client import CollectorRegistry

    from benchmarks.worker_bench import ArraySource
    from foremast_tpu.jobs.store import InMemoryStore
    from foremast_tpu.observe.gauges import WorkerMetrics

    seen = []
    registry = CollectorRegistry()
    worker = BrainWorker(
        InMemoryStore(), ArraySource(), config=BrainConfig(),
        worker_id="pack-w",
        on_verdict=(lambda doc, vs: seen.append((doc.id, vs))) if hook
        else None,
        metrics=WorkerMetrics(registry=registry),
    )
    worker._mvj = _StubJudge()
    ok_joint = _slice_of(specs)
    logger = logging.getLogger("foremast_tpu.worker")
    lines, level = _JudgmentLines(), logger.level
    logger.addHandler(lines)
    logger.setLevel(logging.DEBUG)
    try:
        updated, demoted, counts = judge_fn(worker, ok_joint, NOW)
    finally:
        logger.removeHandler(lines)
        logger.setLevel(level)
    return {
        "calls": worker._mvj.calls,
        "updated": [
            (d.id, d.status, d.status_code, d.reason, d.anomaly_info)
            for d in updated
        ],
        "demoted": [d.id for d in demoted],
        "counts": counts,
        "hook": seen,
        "metrics": {
            (smp.name, tuple(sorted(smp.labels.items()))): smp.value
            for fam in registry.collect()
            for smp in fam.samples
            if smp.name in (
                "foremast_worker_jobs_total", "foremast_worker_windows_total"
            )
        },
        "log": lines.lines,
    }


@pytest.mark.parametrize(
    "case, hook",
    [
        p
        for c in sorted(_PACK_CASES)
        for p in (
            pytest.param(c, True, id=c),
            pytest.param(c, False, id=f"{c}-no_hook"),
        )
    ],
)
def test_bulk_pack_matches_the_per_doc_loop(case, hook):
    """For the same `ok_joint` the bulk pack hands `joint_columnar` the
    same bytes in the same rows as the per-doc loop did, and the bulk
    decide decides, demotes, reports, counts and logs the same docs in
    the same order, with an `on_verdict` hook wired (the same verdicts)
    and without (the benchmark's and a deployment's path)."""
    from foremast_tpu.jobs.joint_pack import pack_slice

    build, bulk, aligned, empty, demoted, dispatches = _PACK_CASES[case]
    want = _run_pack(_oracle_judge_joint_fast, build(), hook)
    got = _run_pack(_judge_joint_fast, build(), hook)

    assert len(got["calls"]) == len(want["calls"]) == dispatches
    for g, w in zip(got["calls"], want["calls"]):
        assert g[0] == w[0]  # mode
        assert g[1] == w[1]  # keys, row for row
        assert g[2] == w[2]  # entries
        assert len(g[3]) == len(w[3])
        assert all(a[4] == b[4] and a[0] == b[0] for a, b in zip(g[3], w[3]))
        for a, b in zip(g[4:], w[4:]):  # cur, mask, gaps
            if b is None:
                assert a is None
                continue
            assert a.dtype == b.dtype and a.shape == b.shape
            assert a.tobytes() == b.tobytes()
    assert got["updated"] == want["updated"]  # statuses, payloads, ORDER
    assert got["demoted"] == want["demoted"]
    # the worker's dict holds every `fast_kinds()` key, the oracle's three
    assert {k: n for k, n in got["counts"].items() if n} == {
        k: n for k, n in want["counts"].items() if n
    }
    # foremast_worker_jobs_total{status} and foremast_worker_windows_total
    assert got["metrics"] == want["metrics"]
    assert sum(
        v for (name, _), v in got["metrics"].items()
        if name == "foremast_worker_jobs_total"
    ) == len(got["updated"])
    assert got["log"] == want["log"]
    traced = [
        f"job-{i}" for i, sp in enumerate(build())
        if len(sp) > 3 and sp[3].get("trace_id")
    ]
    assert sorted(line[1] for line in got["log"]) == traced
    assert bool(got["hook"]) == hook
    assert [d for d, _ in got["hook"]] == [d for d, _ in want["hook"]]
    for (_, va), (_, vb) in zip(got["hook"], want["hook"]):
        assert len(va) == len(vb)
        for x, y in zip(va, vb):
            assert (x.alias, x.verdict, x.anomaly_pairs) == (
                y.alias, y.verdict, y.anomaly_pairs
            )
            np.testing.assert_array_equal(x.upper, y.upper)
            np.testing.assert_array_equal(x.lower, y.lower)
    # the case exercises what its name says: which way each doc went
    _, empties, drifted, n_bulk, n_aligned = pack_slice(_slice_of(build()))
    assert (n_bulk, n_aligned, len(empties), len(drifted)) == (
        bulk, aligned, empty, demoted
    )
    statuses = {s[1] for s in got["updated"]}
    if case in _CASE_STATUSES:
        assert _CASE_STATUSES[case] in (None, statuses)
    else:
        assert STATUS_COMPLETED_UNHEALTH in statuses


def test_bulk_pack_decide_reads_views_not_copies():
    """`decide` reads each bulk doc's `ct`/`cv` as views into the class
    stacks: one timestamp stack and one value stack a class, no per-doc
    copy."""
    from foremast_tpu.jobs.joint_pack import pack_slice

    groups, empty, demoted, bulk, aligned = pack_slice(
        _slice_of(_case_mixed_lengths_one_dispatch())
    )
    assert (bulk, aligned, empty, demoted) == (7, 0, [], [])
    (group,) = groups
    bases = {id(it[3].base) for it in group.sub} | {
        id(it[4].base) for it in group.sub
    }
    assert len(bases) == 4  # (T, V) x (30-point class, 45-point class)
    assert all(it[3].base is not None for it in group.sub)
