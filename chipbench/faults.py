"""The faults a sweep cell can have, planted underneath the timed path:
each has to make a run come out as not correct. The tests plant them at a
size a test can hold, `chipbench.readings` at the cell's own on the chip.
(A sweep cell has no state that steps and no exchange between chips.)"""

from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def half_of_the_batch_left_out():
    """Half of each slice's joint docs never judged: `unjudged` fails."""
    from foremast_tpu.jobs.worker import BrainWorker

    real = BrainWorker._judge_joint_fast

    def half(self, ok_joint, now):
        return real(self, ok_joint[: max(1, len(ok_joint) // 2)], now)

    BrainWorker._judge_joint_fast = half
    try:
        yield
    finally:
        BrainWorker._judge_joint_fast = real


@contextlib.contextmanager
def an_answer_altered():
    """One point of every warm joint judgment flipped where the flags
    are produced: `flip_rate` fails."""
    from foremast_tpu.engine.multivariate import MultivariateJudge

    real = MultivariateJudge.joint_columnar

    def altered(self, *a, **k):
        flags = np.array(real(self, *a, **k))
        flags[:, 3] = ~flags[:, 3]
        return flags

    MultivariateJudge.joint_columnar = altered
    try:
        yield
    finally:
        MultivariateJudge.joint_columnar = real


FAULTS = {
    "half_of_the_batch_left_out": half_of_the_batch_left_out,
    "an_answer_altered": an_answer_altered,
}
