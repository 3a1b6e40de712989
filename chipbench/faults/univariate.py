"""Faults of fleet kind `univariate` (single-alias docs with no baseline)."""

from chipbench.faults import columnar_answer_altered, columnar_half_left_out

FAULTS = {
    "half_of_the_batch_left_out": (lambda: columnar_half_left_out(False), "unjudged"),
    "an_answer_altered": (lambda: columnar_answer_altered(False), "flip_rate"),
}
