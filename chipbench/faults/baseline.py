"""Faults of fleet kind `baseline` (the canary bucket: single-alias docs
that carry a baseline window)."""

from chipbench.faults import columnar_answer_altered, columnar_half_left_out

FAULTS = {
    "half_of_the_batch_left_out": (lambda: columnar_half_left_out(True), "unjudged"),
    "an_answer_altered": (lambda: columnar_answer_altered(True), "flip_rate"),
}
