"""Fixed-shape masked metric-window container.

The reference brain processes one ragged time series per job (ES document ->
N `query_range` URLs -> lists of points). On TPU, ragged data kills tiling,
so the core container is a dense `[batch, T]` array plus a validity mask —
ragged windows become masks (SURVEY.md section 7.1). All downstream ops
(forecasters, rank tests, bounds) accept and respect the mask.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MetricWindows:
    """A batch of fixed-length metric windows.

    values: [..., T] float32 — metric samples (padding arbitrary where invalid)
    mask:   [..., T] bool    — True where the sample is real
    times:  [..., T] int32   — unix seconds per sample (0 where invalid);
            int32 because float32 ulp at current epochs is 128 s, which
            would collapse adjacent 60 s samples. Carried for anomaly
            reporting (the reference returns flat [t1,v1,t2,v2,...] pairs —
            foremast-barrelman `pkg/controller/Barrelman.go:593-620`).
            May be None (`from_ragged(..., device_times=False)`): no
            compiled program consumes times, and the shipped judge skips
            the upload entirely.
    """

    values: jax.Array
    mask: jax.Array
    times: jax.Array | None

    @property
    def batch_shape(self):
        return self.values.shape[:-1]

    @property
    def length(self) -> int:
        return self.values.shape[-1]

    def count(self) -> jax.Array:
        """Number of valid points per window, [...]."""
        return jnp.sum(self.mask, axis=-1)

    @staticmethod
    def from_ragged(
        series: Sequence[tuple[np.ndarray, np.ndarray]],
        length: int | None = None,
        device_times: bool = True,
    ) -> "MetricWindows":
        """Pack a list of (times, values) ragged series into one padded batch.

        Host-side helper (numpy): used by the dispatcher when packing pending
        jobs into fixed-shape batches (bucketing bounds recompiles).

        `device_times=False` skips uploading the packed times (times=None):
        no compiled scoring program reads them — anomaly timestamps are
        decoded on the host from each task's own ragged times — and the
        [B, T] int32 upload is H2D bytes nothing reads.
        None is a valid empty pytree, so jit/sharding treewalks skip it.
        """
        if length is None:
            length = max((len(v) for _, v in series), default=1)
            length = max(length, 1)
        b = len(series)

        from foremast_tpu import native

        packed = native.pack_windows(list(series), length) if b else None
        if packed is not None:
            values, times, mask = packed
        else:
            values = np.zeros((b, length), dtype=np.float32)
            times = np.zeros((b, length), dtype=np.int32)
            mask = np.zeros((b, length), dtype=bool)
            for i, (t, v) in enumerate(series):
                n = min(len(v), length)
                values[i, :n] = np.asarray(v, dtype=np.float32)[:n]
                times[i, :n] = np.asarray(t, dtype=np.int64)[:n].astype(np.int32)
                mask[i, :n] = True
        return MetricWindows(
            values=jnp.asarray(values),
            mask=jnp.asarray(mask),
            times=jnp.asarray(times) if device_times else None,
        )


def masked_moments(
    values: jax.Array, mask: jax.Array
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(n, mean, var) over the last axis in ONE fused pass.

    Uses shifted moments — d = x - x[first valid index] — so the
    E[d^2] - E[d]^2 form stays well-conditioned (the shift point is a
    member of the sample, so deviations are bounded by the sample range)
    and arbitrary padding values in masked slots can never poison the
    result. This is the bandwidth-optimal form for the 7-day histories
    the deployed-default model reduces (one read of the window); the
    two-pass `masked_mean`/`masked_var` pair remains for callers that
    need an axis argument or ddof.
    """
    m = mask.astype(values.dtype)
    first_idx = jnp.argmax(mask, axis=-1)  # 0 for all-invalid rows (gated)
    c = jnp.take_along_axis(values, first_idx[..., None], axis=-1)
    d = (values - c) * m
    n = jnp.sum(m, axis=-1)
    s1 = jnp.sum(d, axis=-1)
    s2 = jnp.sum(d * d, axis=-1)
    nn = jnp.maximum(n, 1.0)
    mean_d = s1 / nn
    mean = jnp.where(n > 0, c[..., 0] + mean_d, 0.0)
    var = jnp.where(n > 0, jnp.maximum(s2 / nn - mean_d * mean_d, 0.0), 0.0)
    return n, mean, var


def masked_mean(values: jax.Array, mask: jax.Array, axis: int = -1) -> jax.Array:
    """Mean over valid points; 0.0 where a window has no valid points."""
    m = mask.astype(values.dtype)
    n = jnp.sum(m, axis=axis)
    s = jnp.sum(values * m, axis=axis)
    return jnp.where(n > 0, s / jnp.maximum(n, 1), 0.0)


def masked_var(values: jax.Array, mask: jax.Array, axis: int = -1, ddof: int = 0) -> jax.Array:
    """Variance over valid points (ddof degrees of freedom); 0.0 if too few."""
    m = mask.astype(values.dtype)
    n = jnp.sum(m, axis=axis)
    mu = masked_mean(values, mask, axis=axis)
    d = (values - jnp.expand_dims(mu, axis)) * m
    ss = jnp.sum(d * d, axis=axis)
    denom = n - ddof
    return jnp.where(denom > 0, ss / jnp.maximum(denom, 1), 0.0)


def masked_std(values: jax.Array, mask: jax.Array, axis: int = -1, ddof: int = 0) -> jax.Array:
    return jnp.sqrt(masked_var(values, mask, axis=axis, ddof=ddof))
