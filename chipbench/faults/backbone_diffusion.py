"""Faults of fleet kind `backbone_diffusion` (every alias of a doc one
sequence of the shared block-diffusion model; the docs ride the joint
columnar path)."""

from chipbench.faults import joint_answer_altered, joint_half_left_out

FAULTS = {
    "half_of_the_batch_left_out": (lambda: joint_half_left_out("backbone_diffusion"), "unjudged"),
    "an_answer_altered": (lambda: joint_answer_altered("backbone_diffusion"), "flip_rate"),
}
