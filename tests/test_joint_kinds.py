"""The seam of a joint detector kind (ISSUE 29): a kind is one object in
`engine.kinds.JOINT_KINDS`, and the judge, the pack and the worker ask it.

(a) a toy kind defined HERE and registered for one test is judged cold,
then warm, through `BrainWorker.tick()` with nothing patched in `jobs/` or
`engine/multivariate.py`; (b) each real kind's cache key is the tuple the
parent built (the persisted format, written out below) and its warm gate
refuses what the parent's refused; (c) `select_mode`'s table; (d) the
lists that other layers keep of the kinds agree with the table.
"""

import re
import time
from pathlib import Path

import numpy as np
import pytest

from foremast_tpu.config import JOINT_ALGORITHMS, BrainConfig
from foremast_tpu.engine.kinds import (
    JOINT_KINDS,
    UNIVARIATE,
    JointKind,
    kinds_under,
    select_mode,
)
from foremast_tpu.engine.multivariate import (
    MULTIVARIATE_ALGOS,
    MultivariateJudge,
)
from foremast_tpu.jobs import (
    STATUS_COMPLETED_UNHEALTH,
    STATUS_PREPROCESS_COMPLETED,
    BrainWorker,
)
from foremast_tpu.jobs.models import Document
from foremast_tpu.jobs.store import InMemoryStore
from foremast_tpu.jobs.worker import fast_kinds
from foremast_tpu.metrics.source import MetricSource
from foremast_tpu.models.lstm_ae import AEParams, LSTMAEConfig, init

NOW = 1_760_000_000.0


# -- (a) a kind the engine has never heard of --------------------------------


class ToyKind(JointKind):
    """Flags every timestamp at which any alias exceeds a constant. Its
    fitted state is that constant: no arena, no program."""

    name = "toy"
    selectors = {"toy": (2, None)}
    persisted = False
    limit = 5.0

    def cache_key(self, config, app, aliases, hist_keys, tc):
        return (self.name, app, aliases, hist_keys)

    def admissible(self, judge, entry, meta):
        return (
            entry is not None
            and meta[5] >= judge.config.min_historical_points
        )

    def judge_cold(self, judge, jobs):
        thr = judge.config.anomaly.rule_for(None).threshold
        joints = [judge._joint(tasks) for tasks in jobs]
        out = []
        for j, pw in zip(joints, judge._pairwise(joints)):
            if len(j.hist_t) < judge.config.min_historical_points:
                out.extend(judge._unknown(j.tasks, pw))
                continue
            judge._record_joint(self, j, 0, entry=(self.limit,))
            flags = (j.cur_v > self.limit).any(axis=0)
            out.extend(judge._emit(j, flags, thr, pw))
        return out

    def judge_warm(self, judge, keys, entries, metas, cur, mask, gaps):
        limit = np.array([e[0] for e in entries], np.float32)
        return (cur > limit[:, None, None]).any(axis=1) & mask


class _Source(MetricSource):
    concurrent_fetch = False

    def __init__(self):
        self.data = {}

    def fetch(self, url):
        return self.data[url]


def test_a_toy_kind_is_judged_cold_then_warm_through_the_worker(monkeypatch):
    monkeypatch.setitem(JOINT_KINDS, "toy", ToyKind())
    assert select_mode("toy", 2) == "toy"
    assert select_mode("toy", 1) == UNIVARIATE

    store, source = InMemoryStore(), _Source()
    ht = int(NOW) - 86_400 + 60 * np.arange(64, dtype=np.int64)
    aliases = ("latency", "tps")

    def url(which, app, alias):
        return f"http://prom/{which}?q={alias}:{app}&end={ht[-1] + 60}&step=60"

    for app in ("calm", "loud"):
        for alias in aliases:
            source.data[url("hist", app, alias)] = (ht, np.ones(64, np.float32))
        store.create(
            Document(
                id=f"job-{app}",
                app_name=app,
                end_time=time.strftime(
                    "%Y-%m-%dT%H:%M:%SZ", time.gmtime(NOW + 3600)
                ),
                current_config=" ||".join(
                    f"{a}== {url('cur', app, a)}" for a in aliases
                ),
                historical_config=" ||".join(
                    f"{a}== {url('hist', app, a)}" for a in aliases
                ),
                strategy="continuous",
            )
        )

    def install(tick, loud_value):
        ct = ht[-1] + 60 * (1 + 8 * tick + np.arange(8, dtype=np.int64))
        for app in ("calm", "loud"):
            for alias in aliases:
                v = np.ones(8, np.float32)
                if app == "loud" and alias == "tps":
                    v[3] = loud_value
                source.data[url("cur", app, alias)] = (ct, v)
        return ct

    worker = BrainWorker(
        store, source, config=BrainConfig(algorithm="toy"), worker_id="toy-w"
    )
    assert worker._joint_fast and not worker._mv_single
    assert "toy" in worker._fast_kinds

    # cold: both docs fit on the slow path, neither is over the limit
    install(0, 1.0)
    assert worker.tick(now=NOW) == 2
    assert worker._fast_kinds["toy"] == 0
    assert store._docs["job-calm"].status == STATUS_PREPROCESS_COMPLETED
    assert store._docs["job-loud"].status == STATUS_PREPROCESS_COMPLETED

    # warm: the columnar tick, one dispatch group of the toy kind
    ct = install(1, 9.0)
    assert worker.tick(now=NOW + 60) == 2
    assert worker._fast_kinds["toy"] == 2
    assert store._docs["job-calm"].status == STATUS_PREPROCESS_COMPLETED
    loud = store._docs["job-loud"]
    assert loud.status == STATUS_COMPLETED_UNHEALTH
    assert loud.anomaly_info["values"]["tps"] == [float(ct[3]), 9.0]
    assert loud.anomaly_info["values"]["latency"] == [float(ct[3]), 1.0]
    worker.close()


# -- (a') a warm judgment in two phases (ISSUE 30) ----------------------------


def test_a_kind_that_only_judges_warm_is_issued_finished():
    """The base class's `issue_warm` runs `judge_warm` to its end: the toy
    kind above needs no edit to be issued by the sliced sweep."""
    from foremast_tpu.engine.kinds import JointPending

    assert "issue_warm" not in vars(ToyKind)
    cur = np.array([[[1.0, 9.0], [1.0, 1.0]]], np.float32)  # [S, F, tcb]
    mask = np.ones((1, 2), bool)
    pending = ToyKind().issue_warm(None, ["k"], [(5.0,)], [None], cur, mask, None)
    assert type(pending) is JointPending
    np.testing.assert_array_equal(pending.wait(), [[False, True]])


@pytest.fixture(scope="module")
def warm_arena_groups():
    """What a warm tick hands each arena kind, and the judge that took
    it: a cold-fitted worker over one bivariate and one LSTM-hybrid doc
    (and two single-alias ones)."""
    import dataclasses

    from benchmarks.worker_bench import build_mixed_fleet

    store, source, _ = build_mixed_fleet(4, 256, 30, NOW, joint_frac=0.5)
    cfg = BrainConfig(algorithm="auto", season_steps=24, max_cache_size=80)
    cfg = dataclasses.replace(
        cfg, anomaly=dataclasses.replace(cfg.anomaly, threshold=4.0)
    )
    worker = BrainWorker(store, source, config=cfg, worker_id="issue-w")
    worker.judge.lstm_steps = 10  # CI speed
    assert worker.tick(now=NOW + 150) == 4  # cold: fits
    seen = {}
    orig = worker._mvj.joint_columnar_issue

    def recording(mode, *args):
        seen[mode] = args
        return orig(mode, *args)

    worker._mvj.joint_columnar_issue = recording
    assert worker.tick(now=NOW + 200) == 4  # warm: one group a kind
    worker._mvj.joint_columnar_issue = orig
    yield worker._mvj, seen
    worker.close()


@pytest.mark.parametrize("name", ["bivariate", "lstm"])
def test_issue_then_wait_is_judge_warm(warm_arena_groups, name):
    """`ArenaKind.issue_warm` leaves the flags on the device; its
    `wait()` gives what `judge_warm` (and `joint_columnar`) give for the
    same arrays."""
    import jax

    from foremast_tpu.engine.kinds import ArenaKind, JointPending

    judge, seen = warm_arena_groups
    kind, args = JOINT_KINDS[name], seen[name]
    assert isinstance(kind, ArenaKind)
    pending = kind.issue_warm(judge, *args)
    assert isinstance(pending, JointPending)
    assert isinstance(pending.flags, jax.Array)  # issued, not gathered
    got = pending.wait()
    cur = args[3]
    assert isinstance(got, np.ndarray) and got.dtype == bool
    assert got.shape == (cur.shape[0], cur.shape[2])
    np.testing.assert_array_equal(got, kind.judge_warm(judge, *args))
    # the judge's two entries: one call, or issue then gather
    np.testing.assert_array_equal(got, judge.joint_columnar(name, *args))
    issued = judge.joint_columnar_issue(name, *args)
    np.testing.assert_array_equal(
        got, judge.joint_columnar(name, *args, issued=issued)
    )


# -- (b) the persisted keys and the warm gates of the three real kinds --------

APP, ALIASES, HKEYS = "app7", ("latency", "tps"), ("hk-latency", "hk-tps")

# what the parent's `_joint_keys` / `_key` / `columnar_joint_peek` built, by
# hand: snapshots, journals and hand-overs hold these tuples
PARENT_KEYS = {
    "bivariate": ("bivariate", APP, ALIASES, HKEYS),
    "lstm": ("lstm", APP, ALIASES, 2, 32, 1440),
    "backbone": ("backbone", APP, ALIASES, HKEYS),
}


@pytest.mark.parametrize("name", sorted(PARENT_KEYS))
def test_cache_key_is_the_tuple_the_parent_built(name):
    cfg = BrainConfig(algorithm="auto")
    assert cfg.season_steps == 1440
    key = JOINT_KINDS[name].cache_key(cfg, APP, ALIASES, HKEYS, 32)
    assert key == PARENT_KEYS[name]
    assert all(type(a) is type(b) for a, b in zip(key, PARENT_KEYS[name]))


def _meta(tc=32, last_ts=1_000, n_hist=640):
    return (tc, np.zeros(2), np.ones(2), 60.0, last_ts, n_hist)


def _lstm_entry(last_ts=1_000, n_hist=640):
    import jax

    ae = init(jax.random.key(0), LSTMAEConfig(features=2))
    assert isinstance(ae, AEParams)
    z = np.zeros(2, np.float32)
    mvn = (z, z, np.zeros((2, 1), np.float32), np.zeros(2, np.int32), z,
           np.eye(2, dtype=np.float32), True, last_ts, n_hist)
    return (ae, 0.1, 0.01, mvn)


class _Rows:
    """What `BackboneKind.admissible` reads of a detector."""

    def __init__(self, live):
        self.detector = self
        self.arena = self
        self.rows = dict.fromkeys(live, 0)


def _peek(judge, name, entry, meta):
    """`columnar_joint_peek` with `entry` and `meta` cached under the
    parent's keys: the whole probe, not the kind's arm alone."""
    judge.cache.put(PARENT_KEYS[name], entry)
    judge.joint_meta.put(("jmeta", name, APP, ALIASES, HKEYS), meta)
    return judge.columnar_joint_peek(name, APP, ALIASES, HKEYS)


@pytest.mark.parametrize(
    "name, refused",
    [
        ("bivariate", "short_history"),
        ("lstm", "short_history"),
        ("lstm", "stale_anchor"),
        ("lstm", "restored_layout"),
        ("backbone", "short_history"),
        ("backbone", "evicted_row"),
        ("backbone", "no_detector"),
    ],
)
def test_warm_gate_admits_a_sound_entry_and_refuses(name, refused):
    judge = MultivariateJudge(BrainConfig(algorithm="auto"))
    seqs = (("backbone", APP, "latency", "hk-latency"),
            ("backbone", APP, "tps", "hk-tps"))
    entry = {
        "bivariate": (np.zeros(2, np.float32), np.eye(2, dtype=np.float32)),
        "lstm": _lstm_entry(),
        "backbone": (seqs, np.ones(2, np.float32)),
    }[name]
    if name == "backbone":
        judge.kind_state["backbone"] = _Rows(seqs)

    got = _peek(judge, name, entry, _meta())
    assert got is not None
    assert got[0] == PARENT_KEYS[name] and got[1] is entry
    assert got[2] == ("jmeta", name, APP, ALIASES, HKEYS)

    if refused == "short_history":
        # bivariate: min_historical_points (10); lstm: two windows of its
        # bucket (64); backbone: max(min_historical_points, 2)
        short = {"bivariate": 9, "lstm": 63, "backbone": 9}[name]
        fits = {"bivariate": 10, "lstm": 64, "backbone": 10}[name]
        if name == "lstm":
            entry = _lstm_entry(n_hist=short)
        assert _peek(judge, name, entry, _meta(n_hist=short)) is None
        if name == "lstm":
            entry = _lstm_entry(n_hist=fits)
        assert _peek(judge, name, entry, _meta(n_hist=fits)) is not None
    elif refused == "stale_anchor":
        # the same app redeployed over another history: the meta moved on,
        # the cached MVN is still anchored to the old one
        assert _peek(judge, name, entry, _meta(last_ts=2_000)) is None
        assert _peek(judge, name, _lstm_entry(n_hist=700), _meta()) is None
    elif refused == "restored_layout":
        # an orbax-restored entry (lists, dicts) coerces on the slow path
        assert _peek(judge, name, list(entry), _meta()) is None
        assert _peek(judge, name, entry[:3], _meta()) is None
    elif refused == "evicted_row":
        judge.kind_state["backbone"] = _Rows(seqs[:1])
        assert _peek(judge, name, entry, _meta()) is None
    elif refused == "no_detector":
        # a restored or handed-over entry in a process that never prefilled
        del judge.kind_state["backbone"]
        assert _peek(judge, name, entry, _meta()) is None


def test_nothing_cached_is_not_admissible():
    judge = MultivariateJudge(BrainConfig(algorithm="auto"))
    for name in PARENT_KEYS:
        assert judge.columnar_joint_peek(name, APP, ALIASES, HKEYS) is None
        judge.joint_meta.put(("jmeta", name, APP, ALIASES, HKEYS), _meta())
        assert judge.columnar_joint_peek(name, APP, ALIASES, HKEYS) is None


# -- (c) the selector rule ----------------------------------------------------


@pytest.mark.parametrize(
    "algorithm, by_count",
    [
        ("auto", ("univariate", "bivariate", "lstm")),
        ("bivariate_normal", ("univariate", "bivariate", "univariate")),
        ("lstm_autoencoder", ("univariate", "lstm", "lstm")),
        ("backbone", ("backbone", "backbone", "backbone")),
        ("backbone_kda", ("backbone_kda", "backbone_kda", "backbone_kda")),
        ("moving_average_all", ("univariate", "univariate", "univariate")),
    ],
)
def test_select_mode_table(algorithm, by_count):
    assert tuple(select_mode(algorithm, n) for n in (1, 2, 3)) == by_count
    assert select_mode(algorithm, 6) == by_count[2]


# -- (d) the lists other layers keep ------------------------------------------


def test_fast_kinds_are_what_the_lint_exercises_and_the_docs_list():
    from foremast_tpu.observe.metrics_lint import default_registry_families

    assert fast_kinds() == (
        "univariate", "baseline", "bivariate", "lstm", "backbone", "backbone_kda",
        "backbone_diffusion",
    )
    worker = BrainWorker(InMemoryStore(), _Source(), config=BrainConfig())
    assert tuple(worker._fast_kinds) == fast_kinds()
    assert set(worker._fast_kinds.values()) == {0}
    worker.close()

    exercised = {
        sample.labels["kind"]
        for family in default_registry_families().collect()
        if family.name == "foremast_worker_fast_docs"
        for sample in family.samples
        if sample.name.endswith("_total")
    }
    assert exercised == set(fast_kinds())

    docs = Path(__file__).parent.parent / "docs" / "observability.md"
    rows = [
        line for line in docs.read_text().splitlines()
        if line.startswith("| `foremast_worker_fast_docs")
    ]
    assert len(rows) == 2  # the generated index and the operator's table
    for row in rows:
        words = set(re.findall(r"[a-z_]+", row.split("|")[3]))
        assert set(fast_kinds()) <= words, row


def test_config_lists_every_selector_the_table_answers_to():
    selectors = {s for kind in JOINT_KINDS.values() for s in kind.selectors}
    assert selectors == JOINT_ALGORITHMS
    assert MULTIVARIATE_ALGOS is JOINT_ALGORITHMS
    for algorithm in JOINT_ALGORITHMS:
        assert kinds_under(algorithm)
        assert any(
            select_mode(algorithm, n) != UNIVARIATE for n in (1, 2, 3)
        )
    assert not kinds_under("moving_average_all")
    # the flags the pack and the worker read, as the parent hard-wired them
    assert [k.name for k in JOINT_KINDS.values() if k.needs_gaps] == ["lstm"]
    assert [k.name for k in JOINT_KINDS.values() if k.pins_bucket] == ["lstm"]
    assert [k.name for k in JOINT_KINDS.values() if not k.persisted] == [
        "backbone", "backbone_kda", "backbone_diffusion"
    ]
