"""The plain reference of fleet kind `bivariate`: a 2-metric job under
`ML_ALGORITHM=auto`.

As the program documents it (`models/bivariate.py`): the mean and the full
covariance (divisor n) of the aligned 2-metric history; the squared
Mahalanobis distance of each current point by the explicit 2 x 2 inverse;
a point is anomalous where d^2 exceeds threshold^2 (the chi^2_2
generalisation of |z| > threshold the program states), the threshold being
the global one for joint kinds. A fit is valid where the history clears 10
points and the covariance is not degenerate (det > 1e-6 var_x var_y); an
invalid fit flags nothing.

Straightforward `jax.numpy`, float32 (bfloat16 for the control); imports
nothing of `foremast_tpu`. The margin of a point is the distance of
ln d^2 from ln threshold^2.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

MIN_HISTORICAL_POINTS = 10


@functools.partial(jax.jit, static_argnames=("dtype",))
def _fit(hist, dtype):
    """hist [N, 2, T] -> mean [N, 2], (sxx, syy, sxy) [N], valid [N]."""
    h = hist.astype(dtype)
    n = h.shape[-1]
    mean = jnp.mean(h, axis=-1)
    d = h - mean[..., None]
    sxx = jnp.sum(d[:, 0] * d[:, 0], axis=-1) / n
    syy = jnp.sum(d[:, 1] * d[:, 1], axis=-1) / n
    sxy = jnp.sum(d[:, 0] * d[:, 1], axis=-1) / n
    det = sxx * syy - sxy * sxy
    valid = (n >= MIN_HISTORICAL_POINTS) & (det > 1e-6 * sxx * syy) & (sxx * syy > 0)
    return mean, sxx, syy, sxy, valid


@functools.partial(jax.jit, static_argnames=("dtype",))
def _d2(mean, sxx, syy, sxy, cur, dtype):
    x = cur.astype(dtype)
    dx = x[:, 0] - mean[:, 0:1]
    dy = x[:, 1] - mean[:, 1:2]
    det = jnp.maximum(sxx * syy - sxy * sxy, 1e-30)[:, None]
    d2 = (syy[:, None] * dx * dx - 2.0 * sxy[:, None] * dx * dy + sxx[:, None] * dy * dy) / det
    return d2.astype(jnp.float32)


def judge(rows: list, group: dict, cfg: dict, history, control: bool = False, log=None) -> dict:
    """-> {"flags" [K, W], "margins" [K, W]} of this group's judgments (uid,
    sweep, the window sent [2, W])."""
    dtype = jnp.bfloat16 if control else jnp.float32
    uids = sorted({r["uid"] for r in rows})
    at = {u: i for i, u in enumerate(uids)}
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        fit = [np.asarray(a) for a in _fit(jnp.asarray(np.stack([history(u) for u in uids])), dtype=dtype)]
        if log:
            log(f"bivariate reference fitted {len(uids)} services in {time.perf_counter() - t:.1f} s")
        idx = np.array([at[r["uid"]] for r in rows])
        cur = np.stack([r["sent"] for r in rows]).astype(np.float32)
        mean, sxx, syy, sxy, valid = (a[idx] for a in fit)
        d2 = np.asarray(_d2(*(jnp.asarray(a) for a in (mean, sxx, syy, sxy)), jnp.asarray(cur), dtype=dtype))
    cutoff = float(cfg["anomaly_threshold"]) ** 2
    flags = (d2 > cutoff) & valid[:, None]
    margins = np.abs(np.log(np.maximum(d2, 1e-30)) - np.log(cutoff))
    margins = np.where(valid[:, None], margins, np.inf).astype(np.float32)
    return {"flags": flags, "margins": margins, "d2": d2}
