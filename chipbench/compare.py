"""The comparison that decides `correct` for sweep cells.

What is compared is what the window's ticks wrote to the store: for a
sample of services drawn from the seed, and for every service spiked in
a sweep, the status, the reason and the anomaly payload of each judgment,
against the plain reference run once over the same seeded histories and
the windows that were sent. Numbers, each with a limit of its own:

  flip_rate      disagreeing points whose margin is over `flip_floor`, per
                 1,000 judgments. A point disagrees where the program set
                 a flag the reference left unset, or the reverse; its
                 margin is the least change of the reference's log scores
                 (for kind `lstm`: AE error ratio, d^2 against both
                 cutoffs, at the point and its neighbours; the first
                 pass's gates up to it) that flips the reference's flag
                 there: a disagreement where the reference itself is on a
                 cutoff costs nothing, one where it is far from any costs
                 that distance. The margins are the kind's own
                 (`chipbench/references/<kind>.py`); floor and limit are
                 the configuration's `correct_limits` (`flip_floor`, or a
                 kind's own `flip_floor.<kind>`: the units differ).
  flip_margin    the widest such margin in the run. Printed, not compared:
                 it does not separate the program from the control
                 (PERF.md section 2).
  payload_err    the largest |payload value - value sent| at a flagged
                 timestamp, and infinity for a timestamp outside the
                 window or an alias left out. Exact: limit 0.
  bad_status     judgments whose status or reason is none the state
                 machine allows for the verdict. Exact: limit 0.
  unjudged       judgments due that never reached the store (the driver
                 adds released and failed documents). Exact: limit 0.

`compared.<kind>` and `flip_rate.<kind>` give each fleet kind's share of
`compared` and `flip_rate`, the latter per 1,000 of the kind's own
judgments. `flip_rate.<kind>` is compared where the configuration's
`correct_limits` give it a limit of its own, and printed otherwise.
"""

from __future__ import annotations

import importlib

import numpy as np

from chipbench import series

HEALTHY_STATUS = "preprocess_completed"
ANOMALY_STATUS = "completed_unhealth"
ANOMALY_REASON = "anomaly detected"
BROKEN = 1e30  # a payload that cannot be compared at all (finite: the line is JSON)


class SweepJob:
    """What the comparison needs, taken from the fleet's books so that
    the worker and its device state can be freed first."""

    def __init__(self, fl, first: int, last: int):
        self.seed = fl.seed
        self.history_seed = fl.fleet_seed
        self.first, self.last = first, last
        self.groups = fl.groups
        self.step, self.w, self.n_hist = fl.step, fl.w, fl.n_hist
        self.hist_end = fl.hist_end
        self.fam = fl.fam
        self.rows = []
        seen = set()
        for slot, sweep, uid, pos, status, reason, info in fl.captured:
            if sweep < first:
                continue
            seen.add((slot, sweep))
            self.rows.append({
                "slot": slot, "sweep": sweep, "uid": uid, "fit_pos": pos,
                "group": int(fl.group_of[slot]),
                "status": status, "reason": reason, "info": info,
                "sent": fl.sent[(slot, sweep)],
                "base": fl.sent_base.get((slot, sweep)),
            })
        due = {key for key in fl.sent if first <= key[1] < last}
        self.missing = len(due - seen)
        per_sweep: dict[int, int] = {}
        for _t, _w, n, sweep in fl.writes:
            per_sweep[sweep] = per_sweep.get(sweep, 0) + n
        self.short = sum(
            max(0, fl.slots - per_sweep.get(k, 0)) for k in range(first, last)
        )


def program_flags(row: dict, aliases: list, times: np.ndarray):
    """(flags [W] from the stored payload, payload error, bad status)."""
    w = len(times)
    flags = np.zeros(w, bool)
    status, info = row["status"], row["info"]
    if status == HEALTHY_STATUS:
        return flags, 0.0, 0
    if status != ANOMALY_STATUS or row["reason"] != ANOMALY_REASON:
        return flags, 0.0, 1
    values = (info or {}).get("values") or {}
    err = 0.0
    where = {int(t): i for i, t in enumerate(times)}
    stamps = None
    for j, alias in enumerate(aliases):
        pairs = values.get(alias)
        if not pairs:
            return flags, BROKEN, 0
        ts = [int(t) for t in pairs[0::2]]
        if stamps is None:
            stamps = ts
        elif ts != stamps:
            return flags, BROKEN, 0
        for t, v in zip(ts, pairs[1::2]):
            i = where.get(t)
            if i is None:
                return flags, BROKEN, 0
            flags[i] = True
            err = max(err, abs(float(v) - float(row["sent"][j, i])))
    return flags, err, 0


def reference_of(kind: str):
    """The plain reference of a fleet kind, found by its name:
    `chipbench/references/<kind>.py` (its docstring says what it gives)."""
    try:
        return importlib.import_module(f"chipbench.references.{kind}")
    except ModuleNotFoundError as e:
        raise SystemExit(
            f"no plain reference for fleet kind {kind!r}: chipbench/references/{kind}.py is missing"
        ) from e


def flip_margin(prog: np.ndarray, ref: dict) -> tuple[float, int, np.ndarray]:
    """(widest margin at a disagreeing point, windows that disagree, the
    margin of every disagreeing point)."""
    diff = prog != ref["flags"]
    at = ref["margins"][diff]
    if at.size == 0:
        return 0.0, 0, at
    return float(at.max()), int(diff.any(axis=1).sum()), at


def judge_sweeps(job, cfg: dict, log, control: bool = False) -> tuple[dict, dict]:
    """The numbers compared, each {"value", "limit"}, and the detail the
    record keeps (the margin of every disagreeing point, by fleet kind).
    `control` puts each kind's reference, computed in the precision below
    the configuration's, in the program's place."""
    limits = cfg["correct_limits"]
    floor = float(limits["flip_floor"])
    margin, payload, bad, n_rows, n_diff, n_over = 0.0, 0.0, 0, 0, 0, 0
    detail = {}
    by_kind: dict = {}
    for gi, g in enumerate(job.groups):
        rows = [r for r in job.rows if r["group"] == gi]
        if not rows:
            continue
        f = len(g["aliases"])
        kind = reference_of(g["kind"])

        def history(uid, f=f):
            return series.history(job.history_seed, uid, f, job.n_hist, job.fam)

        ref = kind.judge(rows, g, cfg, history, log=log)
        if control:
            prog = kind.judge(rows, g, cfg, history, control=True, log=log)["flags"]
        else:
            prog = np.zeros((len(rows), job.w), bool)
            for i, r in enumerate(rows):
                times = job.hist_end + job.step * (r["sweep"] + np.arange(job.w))
                prog[i], e, b = program_flags(r, g["aliases"], times)
                payload = max(payload, e)
                bad += b
        m, d, at = flip_margin(prog, ref)
        margin = max(margin, m)
        n_diff += d
        # the margins are in the kind's own units, so a kind may state a
        # floor of its own (`flip_floor.<kind>`)
        over = int((at > float(limits.get("flip_floor." + g["kind"], floor))).sum())
        n_over += over
        n_rows += len(rows)
        seen = by_kind.setdefault(g["kind"], [0, 0])
        seen[0] += len(rows)
        seen[1] += over
        detail[g["kind"]] = {
            "judgments": len(rows),
            "anomalous_program": int(prog.any(axis=1).sum()),
            "anomalous_reference": int(ref["flags"].any(axis=1).sum()),
            "windows_differ": d,
            "margins_at_disagreements": np.sort(at)[::-1][:512].tolist(),
        }
        log(
            f"compared {len(rows)} judgments of kind {g['kind']}: "
            f"{detail[g['kind']]['anomalous_program']} anomalous by the "
            f"{'control' if control else 'program'}, "
            f"{detail[g['kind']]['anomalous_reference']} by the reference, {d} windows differ"
        )
    if n_rows == 0:
        raise SystemExit("the window produced nothing to compare")
    numbers = {
        "flip_rate": {"value": 1000.0 * n_over / n_rows, "limit": float(limits["flip_rate"])},
        "flip_margin": {"value": margin, "limit": None},
        "payload_err": {"value": payload, "limit": 0.0},
        "bad_status": {"value": float(bad), "limit": 0.0},
        "unjudged": {"value": float(job.missing + job.short), "limit": 0.0},
        "compared": {"value": float(n_rows), "limit": None},
        "windows_differ": {"value": float(n_diff), "limit": None},
    }
    # each kind's own share of the two pooled numbers, per 1,000 of its own
    # judgments: compared where the configuration gives the kind a limit of
    # its own (a kind whose rows are a small share of the pool could hide
    # under the pooled limit), printed otherwise
    for kind, (k_rows, k_over) in by_kind.items():
        own = limits.get("flip_rate." + kind)
        numbers["compared." + kind] = {"value": float(k_rows), "limit": None}
        numbers["flip_rate." + kind] = {
            "value": 1000.0 * k_over / k_rows, "limit": None if own is None else float(own),
        }
    return numbers, detail


def verdict(numbers: dict) -> bool:
    """correct: every number that has a limit is within it."""
    for v in numbers.values():
        if v["limit"] is None:
            continue
        if not (v["value"] <= v["limit"]):
            return False
    return True
