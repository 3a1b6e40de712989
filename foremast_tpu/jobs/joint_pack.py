"""Columnar packing of a slice's joint current windows (ISSUE 25).

`BrainWorker._judge_joint_fast` hands `joint_columnar` one `[S, F, tcb]`
buffer per (mode, F) dispatch group. The windows arrive as one
`(times, values)` pair per alias per doc; a scrape whose aliases all
carry the SAME strictly increasing timestamps needs no intersect, and a
fleet's steady state is thousands of such docs a slice. `pack_slice`
tests that for a whole slice at once — one stack of the timestamps per
(mode, F, n) class and one vectorised predicate — and sends only the docs
that fail it (ragged, duplicated, non-monotone or unequal stamps) through
`align_series`, one at a time. The choice is made from the lengths and
timestamps alone. Rows keep `ok_joint` order whichever way a doc came, so
arena assignment, flags, decide and the store's write order are those of
a per-doc loop (kept as the oracle in tests/test_joint_fast_tick.py).

What is vectorised and what is not. NumPy releases the GIL in any call
over more than a few hundred elements, and a tick thread that lets go of
it while the prefetch thread runs Python gets it back a whole switch
interval (5 ms) later. So NumPy does what moves the bytes — the two
stacks, the predicate over them, the fill of `cur` — and the per-doc
scalars (lengths, classes, fitted buckets, rows, gaps) stay in plain
lists, at a fraction of a microsecond a doc and no hand-over: ~11 such
calls a slice where index and mask arrays made it ~35. On the chip's
host a slice's alignment reads 47-65 ms alone either way, and beside
`admit` + `fetch` of the next slice 92-227 ms against 130-240 (PERF.md
section 6, PR 25); a micro-tick of three docs no longer pays the arrays'
fixed overhead (this sandbox's CPU: 32 us a call against 70-125).
"""

from __future__ import annotations

import numpy as np

from foremast_tpu.engine.judge import bucket_length
from foremast_tpu.engine.kinds import JOINT_KINDS
from foremast_tpu.engine.multivariate import align_series


def _stack(series, sel, f: int, dtype):
    """[len(sel), F, n] from the selected docs' F series each, ONE array
    construction (the cast is `np.asarray(x, dtype)`'s)."""
    if len(sel) * f != len(series):
        series = [series[j * f + a] for j in sel for a in range(f)]
    out = np.concatenate(series, dtype=dtype, casting="unsafe")
    return out.reshape(len(sel), f, -1)


class JointGroup:
    """One (mode, F) dispatch group of a slice. `sub` holds (doc,
    end_epoch, jinfo, ct, cv, n) a row, in `ok_joint` order, `ct`/`cv` of
    a bulk row views into its class's stacks; `fill` builds the buffers
    `joint_columnar` takes, and decide (`jobs/joint_decide.py`) reads the
    flagged points' stamps back through `stamps_at`."""

    __slots__ = ("mode", "f", "sub", "classes")

    def __init__(self, mode: str, f: int, sub, classes):
        self.mode = mode
        self.f = f
        self.sub = sub
        # (n, rows, V [len(rows), F, n], T [len(rows), n]) per class, rows
        # ascending; a doc that came through align_series is a class of
        # one row
        self.classes = classes

    def fill(self):
        """(cur, mask, gaps, keys, entries, metas). A kind that pins its
        docs' bucket pads to the group's widest fitted window bucket, which
        IS the widest window's bucket: a doc whose bucket drifted from its
        fitted one was demoted in `pack_slice`."""
        sub = self.sub
        s = len(sub)
        tcb = bucket_length(max(c[0] for c in self.classes))
        cur = np.zeros((s, self.f, tcb), np.float32)
        mask = np.zeros((s, tcb), bool)
        for n, rows, v, _ in self.classes:
            if len(rows) == s:  # the group's one class: every row, in order
                rows = slice(None)
            cur[rows, :, :n] = v
            mask[rows, :n] = True
        keys = [it[2][3] for it in sub]
        entries = [it[2][4] for it in sub]
        metas = [it[2][6] for it in sub]
        gaps = None
        if JOINT_KINDS[self.mode].needs_gaps:
            # whole steps between the fitted history's last stamp and the
            # window's first
            gaps = np.array(
                [
                    max(round((float(it[3][0]) - m[4]) / max(m[3], 1.0)) - 1, 0)
                    for it, m in zip(sub, metas)
                ],
                np.int32,
            )
        return cur, mask, gaps, keys, entries, metas

    def stamps_at(self, rows, cols):
        """The int64 window stamps of the points (rows[i], cols[i]), each
        inside its row's n: one gather from the group's one class, or
        from the classes spread over a [S, widest n] array."""
        if len(self.classes) == 1:  # the group's one class: every row
            return self.classes[0][3][rows, cols]
        out = np.empty((len(self.sub), max(c[0] for c in self.classes)), np.int64)
        for n, r, _, t in self.classes:
            out[r, :n] = t
        return out[rows, cols]


def pack_slice(ok_joint):
    """Align a slice's admitted joint docs.

    Returns (groups, empty, demoted, bulk, aligned): the dispatch groups
    in the order of their first packed doc; the docs with no joint
    observation, as (doc, end_epoch, jinfo, vals), and the docs whose
    window bucket drifted from the fitted one (of the kinds that pin it),
    both in `ok_joint` order;
    and how many rows were packed from the class stacks and how many
    after `align_series`."""
    by_kind: dict = {}
    for i, (item, _series) in enumerate(ok_joint):
        jinfo = item[2]
        by_kind.setdefault((jinfo[0], len(jinfo[1])), []).append(i)

    groups = []
    empty: list[int] = []
    demoted: list[int] = []
    bulk = aligned = 0
    for (mode, f), idx in by_kind.items():
        pins = JOINT_KINDS[mode].pins_bucket
        s = len(idx)
        docs = [ok_joint[i] for i in idx]
        ts = [pair[0] for _, series in docs for pair in series]
        vs = [pair[1] for _, series in docs for pair in series]
        # a candidate: every series of the doc, stamps and values, of one
        # length n > 0; a class: the candidates of one n
        lt = list(map(len, ts))
        lv = list(map(len, vs))
        by_n: dict = {}
        single = []
        for j in range(s):
            a = j * f
            n = lt[a]
            if n and lt[a : a + f].count(n) == f == lv[a : a + f].count(n):
                by_n.setdefault(n, []).append(j)
            else:
                single.append(j)
        slots: list = [None] * s
        classes = []
        for n, sel in by_n.items():
            t = _stack(ts, sel, f, np.int64)
            # every alias on the first's stamps, and those STRICTLY
            # INCREASING: align_series dedups repeated timestamps (first
            # occurrence) and sorts — a raw trace with duplicates must
            # take that path so fast and object verdicts cannot diverge
            ok = (t[:, 1:] == t[:, :1]).all(axis=(1, 2)) & (
                np.diff(t[:, 0], axis=1) > 0
            ).all(axis=1)
            if not ok.all():
                fine = ok.tolist()
                single += [j for j, o in zip(sel, fine) if not o]
                sel = [j for j, o in zip(sel, fine) if o]
                t = t[ok]
            if pins:
                # window bucket drifted from the one the model was fitted
                # at: the model no longer applies — refit on the slow
                # path instead of scoring through the wrong program
                tcb = bucket_length(n)
                fine = [docs[j][0][2][6][0] == tcb for j in sel]
                if not all(fine):
                    demoted += [idx[j] for j, o in zip(sel, fine) if not o]
                    sel = [j for j, o in zip(sel, fine) if o]
                    t = t[np.array(fine)]
            if not sel:
                continue
            v = _stack(vs, sel, f, np.float32)
            t0 = t[:, 0]
            for j, ct, cv in zip(sel, t0, v):
                slots[j] = docs[j][0] + (ct, cv, n)
            classes.append((n, sel, v, t0))
            bulk += len(sel)
        for j in sorted(single):
            item, series = docs[j]
            ct, cv = align_series(
                [pair[0] for pair in series], [pair[1] for pair in series]
            )
            n = len(ct)
            if n == 0:
                # no joint observation: decided UNKNOWN by the caller
                empty.append(idx[j])
            elif pins and bucket_length(n) != item[2][6][0]:
                demoted.append(idx[j])
            else:
                slots[j] = item + (ct, cv, n)
                classes.append((n, [j], cv[None], ct[None]))
                aligned += 1
        packed = [j for j, it in enumerate(slots) if it is not None]
        if not packed:
            continue
        if len(packed) < s:
            # a doc's row in the group: its rank among the packed docs
            row = {j: r for r, j in enumerate(packed)}
            classes = [
                (n, [row[j] for j in sel], v, t) for n, sel, v, t in classes
            ]
        groups.append(
            (
                idx[packed[0]],
                JointGroup(mode, f, [slots[j] for j in packed], classes),
            )
        )
    groups.sort(key=lambda g: g[0])
    return (
        [g for _, g in groups],
        [
            ok_joint[i][0] + ([pair[1] for pair in ok_joint[i][1]],)
            for i in sorted(empty)
        ],
        [ok_joint[i][0][0] for i in sorted(demoted)],
        bulk,
        aligned,
    )
