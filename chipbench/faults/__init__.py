"""The faults a sweep cell can have, planted underneath the timed path:
each has to make a run come out as not correct. The tests plant them at a
size a test can hold, `chipbench.readings` at the cell's own on the chip.
(A sweep cell has no state that steps and no exchange between chips.)

A fleet kind's faults are found by its name, as its plain reference is:
`chipbench/faults/<kind>.py` gives `FAULTS`, a dict from the fault's name
to (a context manager that plants it, the compared number it has to
fail). A later PR that brings a fleet kind brings this file for it and
edits none. Every kind brings the two a sweep cell can have: half of a
dispatch group left out (`unjudged` fails) and one answer altered where it
is produced (`flip_rate` fails).
"""

from __future__ import annotations

import contextlib
import importlib


def faults_of(kind: str) -> dict:
    try:
        return importlib.import_module(f"chipbench.faults.{kind}").FAULTS
    except ModuleNotFoundError as e:
        raise SystemExit(
            f"no faults for fleet kind {kind!r}: chipbench/faults/{kind}.py is missing"
        ) from e


def fleet_faults(groups: list) -> dict:
    """`<kind>.<fault>` -> (plant, number) for every kind of the fleet."""
    out = {}
    for kind in dict.fromkeys(g["kind"] for g in groups):
        for name, entry in faults_of(kind).items():
            out[f"{kind}.{name}"] = entry
    return out


@contextlib.contextmanager
def patched(owner, name: str, wrap):
    """Replace `owner.name` by `wrap(real)` for the block."""
    real = getattr(owner, name)
    setattr(owner, name, wrap(real))
    try:
        yield
    finally:
        setattr(owner, name, real)


def joint_half_left_out(kind: str):
    """Half of each slice's joint docs of one model kind never judged."""
    from foremast_tpu.jobs.worker import BrainWorker

    def wrap(real):
        def half(self, ok_joint, now):
            mine = [it for it in ok_joint if it[0][2][0] == kind]
            drop = {id(it) for it in mine[max(1, len(mine) // 2):]}
            return real(self, [it for it in ok_joint if id(it) not in drop], now)
        return half

    return patched(BrainWorker, "_judge_joint_fast", wrap)


def joint_answer_altered(kind: str):
    """One point of every warm joint judgment of one model kind flipped
    where the flags are produced."""
    import numpy as np

    from foremast_tpu.engine.multivariate import MultivariateJudge

    def wrap(real):
        def altered(self, mode, *a, **k):
            flags = real(self, mode, *a, **k)
            if mode != kind:
                return flags
            flags = np.array(flags)
            flags[:, 3] = ~flags[:, 3]
            return flags
        return altered

    return patched(MultivariateJudge, "joint_columnar", wrap)


def columnar_half_left_out(canary: bool):
    """Half of each slice's single-alias docs of one bucket (the canary
    bucket or the baseline-less one) never packed, so never judged."""
    from foremast_tpu.jobs.worker import BrainWorker

    def wrap(real):
        def half(self, ok_items, is_canary):
            if bool(is_canary) == canary:
                ok_items = ok_items[: max(1, len(ok_items) // 2)]
            return real(self, ok_items, is_canary)
        return half

    return patched(BrainWorker, "_pack_uni", wrap)


def columnar_answer_altered(canary: bool):
    """One point of every warm single-alias judgment of one bucket flipped
    where the gathered result is unpacked, and the row's verdict with it."""
    import numpy as np

    from foremast_tpu.engine import scoring
    from foremast_tpu.engine.judge import HealthJudge

    def wrap(real):
        def altered(self, pending):
            v8, anoms, *rest = real(self, pending)
            if bool(pending.pairwise) != canary:
                return (v8, anoms, *rest)
            anoms = np.array(anoms)
            anoms[:, 3] ^= 1
            known = v8 != scoring.UNKNOWN
            v8 = np.where(
                known, np.where(anoms.any(axis=1), scoring.UNHEALTHY, scoring.HEALTHY), v8
            ).astype(v8.dtype)
            return (v8, anoms, *rest)
        return altered

    return patched(HealthJudge, "_columnar_wait", wrap)
