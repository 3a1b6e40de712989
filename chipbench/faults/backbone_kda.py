"""Faults of fleet kind `backbone_kda` (every alias of a doc one sequence
of the shared linear-attention model; the docs ride the joint columnar
path)."""

from chipbench.faults import joint_answer_altered, joint_half_left_out

FAULTS = {
    "half_of_the_batch_left_out": (lambda: joint_half_left_out("backbone_kda"), "unjudged"),
    "an_answer_altered": (lambda: joint_answer_altered("backbone_kda"), "flip_rate"),
}
