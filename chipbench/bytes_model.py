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
