"""Faults of fleet kind `bivariate` (the 2-alias joint docs)."""

from chipbench.faults import joint_answer_altered, joint_half_left_out

FAULTS = {
    "half_of_the_batch_left_out": (lambda: joint_half_left_out("bivariate"), "unjudged"),
    "an_answer_altered": (lambda: joint_answer_altered("bivariate"), "flip_rate"),
}
