"""Bytes and operations of the programs the benchmark holds against a
peak, as functions of shapes alone: the same whatever implements the
program. Also the reckoned bytes of one fitted row of each arena, which
size the configurations."""

from __future__ import annotations

F32 = 4


def window_bucket(n: int) -> int:
    """The power-of-two bucket (at least 8) a window of n points is judged at."""
    b = 8
    while b < n:
        b *= 2
    return b


def lstm_ae_floats(f: int, hidden: int = 32) -> int:
    """Parameters of one LSTM autoencoder: encoder and decoder cells
    (w_x [f,4h], w_h [h,4h], b [4h]) and the output map (w [h,f], b [f])."""
    cell = f * 4 * hidden + hidden * 4 * hidden + 4 * hidden
    return 2 * cell + hidden * f + f


def lstm_row_bytes(f: int, m: int, hidden: int = 32) -> int:
    """One row of the joint LSTM-hybrid arena: the AE, per-metric HW
    level/trend/season[m]/phase, residual mean and covariance, a valid
    byte. f=4, m=1440: 61,585 B."""
    floats = lstm_ae_floats(f, hidden) + f + f + f * m + f + f * f
    return F32 * floats + F32 * f + 1


def univariate_row_bytes(m: int) -> int:
    """One warm row of the univariate arena: season[m] and five 4-byte
    scalars (level, trend, phase, scale, history count). m=1: 24 B, m=1440: 5,780 B."""
    return F32 * m + 20


def bivariate_row_bytes() -> int:
    """mean [2] + cov [2,2]."""
    return F32 * 6


def joint_score_bytes(b: int, f: int, w_bucket: int, m: int, hidden: int = 32) -> int:
    """Least bytes one warm joint dispatch over b rows must move: each
    row's state read once, the windows and their mask in, five [b]
    operands (row index, AE cutoff, two chi^2 cutoffs, gap), flags out."""
    state = b * lstm_row_bytes(f, m, hidden)
    windows = b * w_bucket * f * F32 + b * w_bucket
    operands = 5 * b * F32
    flags = b * w_bucket
    return state + windows + operands + flags


def joint_score_flops(f: int, w_bucket: int, hidden: int = 32) -> int:
    """Operations one doc's warm judgment needs: the AE's encoder and
    decoder over the window (two matmuls a step, 2*K*N each) and the
    output map; the HW continuation and the F x F solve are lower order
    and left out."""
    cell = 2 * (f * 4 * hidden) + 2 * (hidden * 4 * hidden)
    return w_bucket * (2 * cell + 2 * hidden * f)


# -- the single-alias and 2-alias warm programs ------------------------------
# Each takes (b, f, w_bucket, m) like `joint_score_bytes`, and each flops
# function (f, w_bucket) like `joint_score_flops`, so that one reader serves
# every kind; f is the group's alias count (1, 1, 2).


def univariate_score_bytes(b: int, f: int, w_bucket: int, m: int) -> int:
    """Least bytes one warm univariate dispatch over b rows must move: of
    each row's state the five scalars and the window's own stretch of the
    season (the forecast reads `w_bucket` of the row's m season points, so
    m does not count: a program that reads the whole 4 m-byte row moves
    more than it must), the windows and their mask in, five [b] operands
    (row index, threshold, bound, lower floor, gap), a verdict byte and the
    packed flags out."""
    state = b * univariate_row_bytes(min(w_bucket, m))
    windows = b * w_bucket * F32 + b * w_bucket
    operands = 5 * b * F32
    out = b + b * w_bucket // 8
    return state + windows + operands + out


def univariate_score_flops(f: int, w_bucket: int) -> int:
    """Operations one doc's warm univariate judgment needs: the horizon
    (level + trend * step + season, 3 a point), the band (2 a point) and
    the two comparisons a point."""
    return 7 * w_bucket


def canary_score_bytes(b: int, f: int, w_bucket: int, m: int) -> int:
    """The univariate dispatch plus the baseline windows and their mask
    (judged at the same bucket) in, and (p, differs) out."""
    baseline = b * w_bucket * F32 + b * w_bucket
    return univariate_score_bytes(b, f, w_bucket, m) + baseline + b * (F32 + 1)


def canary_score_flops(f: int, w_bucket: int) -> int:
    """The univariate judgment plus the pairwise tests: the two-sample rank
    blocks (x against y, x against x, y against y: a less-than and an equal
    compare each and their row sums, 4 operations an entry) shared by
    Mann-Whitney and Kruskal-Wallis, the signed-rank test's block over the
    paired differences, and the sign counts of the two-group Friedman."""
    two_sample = 3 * 4 * w_bucket * w_bucket
    signed_rank = 4 * w_bucket * w_bucket
    return univariate_score_flops(f, w_bucket) + two_sample + signed_rank + 6 * w_bucket


def bivariate_score_bytes(b: int, f: int, w_bucket: int, m: int) -> int:
    """Least bytes one warm bivariate dispatch over b rows must move: each
    row's mean and covariance, the two windows and one mask in, two [b]
    operands (row index, threshold), flags out."""
    state = b * bivariate_row_bytes()
    windows = 2 * b * w_bucket * F32 + b * w_bucket
    return state + windows + 2 * b * F32 + b * w_bucket


def bivariate_score_flops(f: int, w_bucket: int) -> int:
    """The explicit 2x2 Mahalanobis form: two differences, the quadratic
    form (9) and its division a point, and the determinant once."""
    return 13 * w_bucket + 4
