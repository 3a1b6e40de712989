"""The model-backed kinds, `backbone`, `backbone_kda` and
`backbone_diffusion`: every alias of every job is one sequence of ONE shared
sequence model (`engine/backbone.py`'s `BackboneDetector`: the weights, a
cache row a sequence, the chunked prefill and the window program;
docs/backbone.md). The three are one class instantiated three times: a
kind's name is its `ML_ALGORITHM` value, and `model_types` the model files it
takes (`backbone`: `cohere2_moe`; `backbone_kda`: `kimi_linear`;
`backbone_diffusion`: `sdar_moe`). The kind is what the judge, the pack and
the worker see of it: which jobs it takes, its warm entry and gates, and the
two paths around the detector."""

from __future__ import annotations

import numpy as np

from foremast_tpu.engine.judge import bucket_length
from foremast_tpu.engine.kinds.base import JointKind
from foremast_tpu.observe.spans import span


class _State:
    """What one judge keeps of the kind: the detector, and for each of its
    sequence keys the joint cache key of the document that holds it."""

    def __init__(self, model_types: tuple):
        from foremast_tpu.engine.backbone import BackboneDetector

        self.detector = BackboneDetector(model_types=model_types)
        self.doc_of: dict = {}


class BackboneKind(JointKind):
    # nothing to persist: the fitted state is the prefix cache's rows, tens
    # of MB a sequence on the device, and a warm entry without its rows is
    # worth nothing. A restarted worker prefills again.
    persisted = False
    keeps_counters = True

    def __init__(self, name: str, model_types: tuple):
        self.name = name
        self.selectors = {name: (1, None)}
        # the model files' `model_type`s this kind takes; the first one's
        # packaged file is the default
        self.model_types = tuple(model_types)

    def state(self, judge, build: bool = True) -> _State | None:
        """The judge's `_State`, built at its first use: the weights are
        gigabytes. Its cache is counted with the joint arenas."""
        st = judge.kind_state.get(self.name)
        if st is None and build:
            st = judge.kind_state[self.name] = _State(self.model_types)
            judge._joint_arenas[(self.name, 0)] = st.detector.arena
        return st

    def counters(self, judge) -> dict | None:
        st = self.state(judge, build=False)
        return None if st is None else st.detector.counters()

    def observe(self, metrics, counters: dict) -> None:
        if hasattr(metrics, "observe_backbone"):
            metrics.observe_backbone(self.name, counters)

    def cache_key(self, config, app, aliases, hist_keys, tc) -> tuple:
        # the history's identity is the prefix cache's key
        return (self.name, app, aliases, hist_keys)

    def admissible(self, judge, entry, meta) -> bool:
        st = self.state(judge, build=False)
        if (
            st is None
            or entry is None
            or meta[5] < max(judge.config.min_historical_points, 2)
        ):
            return False
        # a warm entry is worth what its rows are: a restored or
        # handed-over entry, or one whose row was recycled, prefills
        rows = st.detector.arena.rows
        return all(k in rows for k in entry[0])

    def _drop_evicted(self, judge, st: _State) -> None:
        """A recycled cache row takes its document's warm entry with it:
        the next tick finds none and prefills again."""
        evicted = st.detector.evicted
        while evicted:
            doc_key = st.doc_of.pop(evicted.pop(), None)
            if doc_key is not None:
                judge.cache.pop(doc_key)

    def judge_cold(self, judge, jobs: list) -> list:
        """Each alias of a job is one sequence; those with no cache row
        are prefilled ("fit"), then the window program scores every
        sequence's current window and a timestamp is anomalous where any
        alias's score exceeds the threshold (nats). Jobs go through in
        groups of at most the cache's capacity in sequences."""
        st = self.state(judge)
        min_pts = max(judge.config.min_historical_points, 2)
        all_joints = [judge._joint(job_tasks) for job_tasks in jobs]
        all_pw = judge._pairwise(all_joints)
        out: list = []
        group: list = []
        seqs = 0
        for j, p in zip(all_joints, all_pw):
            if len(j.hist_t) < min_pts or len(j.cur_t) == 0:
                out.extend(judge._unknown(j.tasks, p))
                continue
            if group and seqs + len(j.tasks) > st.detector.capacity:
                out.extend(self._judge_group(judge, st, group))
                group, seqs = [], 0
            group.append((j, p))
            seqs += len(j.tasks)
        if group:
            out.extend(self._judge_group(judge, st, group))
        return out

    def _judge_group(self, judge, st: _State, pairs: list) -> list:
        det = st.detector
        thr = float(judge.config.anomaly.rule_for(None).threshold)
        keys, hists, passing = [], [], []
        for j, _ in pairs:
            for f, t in enumerate(j.tasks):
                key = (self.name, t.app, t.alias, t.fit_key)
                if t.fit_key is None:
                    # unsettled history: a row for this judgment alone
                    key = (self.name, "__passing__", t.job_id, t.alias)
                    passing.append(key)
                keys.append(key)
                hists.append(j.hist_v[f])
        entries = det.ensure(keys, hists)
        self._drop_evicted(judge, st)
        tc = bucket_length(max(len(j.cur_t) for j, _ in pairs))
        cur = np.zeros((len(keys), tc), np.float32)
        valid = np.zeros((len(keys), tc), bool)
        at = 0
        for j, _ in pairs:
            f, n = j.cur_v.shape
            cur[at : at + f, :n] = j.cur_v
            valid[at : at + f, :n] = True
            at += f
        scales = np.array([e[0] for e in entries], np.float32)
        scores = det.score(keys, scales, cur, valid)
        det.arena.release(passing)
        out: list = []
        at = 0
        for j, pw in pairs:
            f, n = j.cur_v.shape
            flags = (scores[at : at + f, :n] > thr).any(axis=0)
            doc_keys = judge._joint_keys(self, j, tc)
            if doc_keys is not None:
                seq_keys = tuple(keys[at : at + f])
                judge._record_joint(
                    self, j, tc, entry=(seq_keys, scales[at : at + f])
                )
                for k in seq_keys:
                    st.doc_of[k] = doc_keys[0]
            out.extend(judge._emit(j, flags, thr, pw))
            at += f
        return out

    def judge_warm(self, judge, keys, entries, metas, cur, mask, gaps):
        """A timestamp is flagged where any of the doc's F sequences
        scores over the threshold."""
        s0, f, tcb = cur.shape
        thr = float(judge.config.anomaly.rule_for(None).threshold)
        with span("judge.joint_prep", stage="pack", rows=s0):
            seq_keys = [k for e in entries for k in e[0]]
            scales = np.concatenate([e[1] for e in entries])
            valid = np.repeat(mask, f, axis=0)
        scores = self.state(judge).detector.score(
            seq_keys, scales, cur.reshape(s0 * f, tcb), valid
        )
        judge.batch_rows_total += s0 * f
        return (scores > thr).reshape(s0, f, tcb).any(axis=1) & mask
