"""Faults of fleet kind `lstm` (the joint LSTM-hybrid docs)."""

from chipbench.faults import joint_answer_altered, joint_half_left_out

FAULTS = {
    "half_of_the_batch_left_out": (lambda: joint_half_left_out("lstm"), "unjudged"),
    "an_answer_altered": (lambda: joint_answer_altered("lstm"), "flip_rate"),
}
