"""The joint detector kinds: one object a kind, in one table.

`JOINT_KINDS` is every joint kind the engine has, keyed by the kind's
name; `select_mode` reads it to route a job. Adding a kind is one module
here and one line in the table (docs/backbone.md, "Adding a joint kind");
the judge, the pack and the worker need no edit.
"""

from __future__ import annotations

from foremast_tpu.engine.kinds.backbone import BackboneKind
from foremast_tpu.engine.kinds.base import ArenaKind, JointKind, JointPending
from foremast_tpu.engine.kinds.bivariate import BivariateKind
from foremast_tpu.engine.kinds.lstm import LstmKind

# what `select_mode` answers for a job no joint kind takes: its metrics
# are judged one by one (`HealthJudge`)
UNIVARIATE = "univariate"

# In the order `MultivariateJudge.judge` emits their verdicts.
JOINT_KINDS: dict[str, JointKind] = {
    kind.name: kind
    for kind in (
        BivariateKind(),
        LstmKind(),
        BackboneKind("backbone", ("cohere2_moe",)),
        BackboneKind("backbone_kda", ("kimi_linear",)),
        BackboneKind("backbone_diffusion", ("sdar_moe",)),
    )
}


def select_mode(algorithm: str, n_metrics: int) -> str:
    """The name of the kind that takes a job of `n_metrics` under
    `ML_ALGORITHM=algorithm`, or 'univariate'.

    The reference's metric-count rule (`docs/guides/design.md:57-93`) is
    the kinds' own `selectors`: `auto` -> bivariate at 2 metrics, lstm at
    3+; `bivariate_normal` -> bivariate at 2; `lstm_autoencoder` -> lstm
    at 2+; `backbone` -> backbone at 1+; `backbone_kda` -> backbone_kda at
    1+; `backbone_diffusion` -> backbone_diffusion at 1+. A count that fits
    no kind under
    an explicit selector falls to the univariate judge."""
    for kind in JOINT_KINDS.values():
        if kind.takes(algorithm, n_metrics):
            return kind.name
    return UNIVARIATE


def kinds_under(algorithm: str) -> tuple[JointKind, ...]:
    """The kinds `ML_ALGORITHM=algorithm` can route a job to; empty for a
    univariate algorithm."""
    return tuple(
        kind for kind in JOINT_KINDS.values() if algorithm in kind.selectors
    )


__all__ = [
    "ArenaKind",
    "JOINT_KINDS",
    "JointKind",
    "JointPending",
    "UNIVARIATE",
    "kinds_under",
    "select_mode",
]
