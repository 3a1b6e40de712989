"""Faults of fleet kind `backbone` (every alias of a doc one sequence of
the shared model; the docs ride the joint columnar path)."""

from chipbench.faults import joint_answer_altered, joint_half_left_out

FAULTS = {
    "half_of_the_batch_left_out": (lambda: joint_half_left_out("backbone"), "unjudged"),
    "an_answer_altered": (lambda: joint_answer_altered("backbone"), "flip_rate"),
}
