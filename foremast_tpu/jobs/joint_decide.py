"""Columnar decide of a slice's joint dispatch groups.

`BrainWorker._decide_joint_fast` turns each dispatch group's flags
`[S, tcb]` into statuses, anomaly payloads and counts. `decide_group` does
it with a fixed number of array operations a group, whatever S: one masked
`any` for the verdicts, one `nonzero` for the flagged points, one gather of
their stamps and values into an interleaved float64 array and ONE `tolist`
for every alias of every flagged doc; each doc then takes its slices of
those lists. `set_statuses` is one plain Python pass over the docs that
sets their fields. No NumPy call is made a doc or an alias; only a group
of several length classes fills its stamps a class at a time
(`JointGroup.stamps_at`).

Why so few calls: NumPy lets go of the GIL in any call over more than a
few hundred elements, and a tick thread that does so while the prefetch
thread runs Python may get it back only a switch interval (5 ms) later
(`joint_pack`'s docstring). So the calls stay ~15 a group, and the rest is
list slicing and attribute stores.

What is stored is what the per-doc loop it replaced stored (kept as the
oracle in tests/test_joint_fast_tick.py): the same float64 values (an
int64 stamp and a float32 value widened), the points in ascending column
order, the aliases in the doc's order, and `_decide_status`'s transitions.
"""

from __future__ import annotations

import numpy as np

from foremast_tpu.engine import HEALTHY, UNKNOWN
from foremast_tpu.jobs.models import (
    STATUS_COMPLETED_HEALTH,
    STATUS_COMPLETED_UNHEALTH,
    STATUS_COMPLETED_UNKNOWN,
    STATUS_PREPROCESS_COMPLETED,
)


def past_end(ends, now: float) -> list[bool]:
    """Per doc, whether its window has closed: `end <= 0` (a missing or
    unparseable endTime must not make a job immortal) or `now >= end`,
    which together are `end <= max(now, 0)`."""
    return (np.array(ends, np.float64) <= max(now, 0.0)).tolist()


def set_statuses(docs, past, flagged, values, clean=HEALTHY) -> dict:
    """`BrainWorker._decide_status` over many docs: `past` from
    `past_end`, `flagged` a bool a doc, `values` the anomaly payloads of
    the flagged docs in order, `clean` the verdict of the others
    (HEALTHY, or UNKNOWN for docs with no observation). Returns
    {status: docs} with every status that occurred."""
    unknown = clean == UNKNOWN
    n_closed = n_open = 0
    payload = iter(values)
    for doc, pe, bad in zip(docs, past, flagged):
        if bad:
            # fail fast (design.md:43); `AnomalyInfo(tags="",
            # values=...).to_json()`, built as the dict it returns
            doc.status = STATUS_COMPLETED_UNHEALTH
            doc.status_code = "200"
            doc.reason = "anomaly detected"
            doc.anomaly_info = {"tags": "", "values": next(payload)}
        elif pe:
            # window closed with no anomaly: healthy unless nothing measured
            if unknown:
                doc.status = STATUS_COMPLETED_UNKNOWN
                doc.reason = "insufficient data"
            else:
                doc.status = STATUS_COMPLETED_HEALTH
                doc.reason = ""
            doc.status_code = "200"
            n_closed += 1
        else:
            # keep re-checking until endTime
            doc.status = STATUS_PREPROCESS_COMPLETED
            n_open += 1
    closed = STATUS_COMPLETED_UNKNOWN if unknown else STATUS_COMPLETED_HEALTH
    counts = {
        STATUS_COMPLETED_UNHEALTH: len(values),
        closed: n_closed,
        STATUS_PREPROCESS_COMPLETED: n_open,
    }
    return {s: k for s, k in counts.items() if k}


class Decided:
    """One dispatch group's decision: `docs` in row order, `hit` the
    flags inside each row's live columns, `flagged` a bool a row,
    `statuses` {status: docs}, `points` the flagged joint observations
    of all rows."""

    __slots__ = ("docs", "hit", "flagged", "statuses", "points")

    def __init__(self, docs, hit, flagged, statuses, points):
        self.docs = docs
        self.hit = hit
        self.flagged = flagged
        self.statuses = statuses
        self.points = points


def decide_group(group, flags, cur, mask, now: float) -> Decided:
    """Decide every doc of one dispatch group (`joint_pack.JointGroup`)
    from its flags [S, tcb] and the `cur` [S, F, tcb] / `mask` [S, tcb]
    it was issued with: sets each doc's status fields."""
    sub = group.sub
    hit = flags & mask  # a flag past a row's n is padding: never counted
    rows_hit = hit.any(axis=1)
    bad = np.flatnonzero(rows_hit)
    values, points = _payloads(group, hit, bad, cur) if len(bad) else ([], 0)
    docs = [it[0] for it in sub]
    flagged = rows_hit.tolist()
    statuses = set_statuses(
        docs, past_end([it[1] for it in sub], now), flagged, values
    )
    return Decided(docs, hit, flagged, statuses, points)


def _payloads(group, hit, bad, cur):
    """The anomaly payloads of the flagged rows `bad` ({alias: [t1, v1,
    t2, v2, ...]} a row, in order) and how many points they hold."""
    rows, cols = np.nonzero(hit)  # row-major: a row's columns ascending
    ends = np.searchsorted(rows, bad, side="right").tolist()
    f = group.f
    pairs = np.empty((f, len(rows), 2), np.float64)
    pairs[:, :, 0] = group.stamps_at(rows, cols)
    pairs[:, :, 1] = cur[rows, :, cols].T
    flat = pairs.reshape(f, -1).tolist()
    values = []
    a = 0
    for i, b in zip(bad.tolist(), ends):
        aliases = group.sub[i][2][1]
        values.append(
            {alias: flat[k][2 * a : 2 * b] for k, alias in enumerate(aliases)}
        )
        a = b
    return values, len(rows)
