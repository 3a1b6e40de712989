"""LSTM-autoencoder multivariate anomaly detector — the flagship learned
model (reference model zoo: "3+ metrics: Deep Learning (LSTM)",
`docs/guides/design.md:84`; BASELINE.md config 4: "LSTM-autoencoder
multivariate detector (train + score)").

TPU-first design:
  * pure-JAX parameters (a pytree of arrays) instead of a framework module,
    so the *service* axis can be a leading array dimension: `init_many`
    creates `[S, ...]`-stacked params and `train_step_many` vmaps one
    compiled train step over all services at once — "train many small
    models cheaply" (SURVEY.md section 7 hard part (e));
  * time runs inside `lax.scan` (one fused loop, static shapes); masked
    steps carry state through unchanged so ragged windows batch cleanly;
  * all matmuls are [B, F]x[F, 4H] / [B, H]x[H, 4H] — MXU-shaped, and the
    4H gate axis is the natural tensor-parallel shard axis (see
    `parallel/mesh.py` and `__graft_entry__.dryrun_multichip`).

Scoring: per-step reconstruction error; a window is anomalous where the
error exceeds `threshold x` the model's training-time error scale — the
same threshold/bound semantics every other detector uses.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import optax


class LSTMParams(NamedTuple):
    """One LSTM cell: gates stacked [i, f, g, o] along the last axis."""

    w_x: jax.Array  # [F_in, 4H]
    w_h: jax.Array  # [H, 4H]
    b: jax.Array  # [4H]


class AEParams(NamedTuple):
    enc: LSTMParams  # features -> hidden
    dec: LSTMParams  # zeros-input decoder conditioned on encoder state
    w_out: jax.Array  # [H, F]
    b_out: jax.Array  # [F]


@dataclasses.dataclass(frozen=True)
class LSTMAEConfig:
    features: int = 4  # metrics per service (latency/err4xx/err5xx/tps)
    hidden: int = 32
    learning_rate: float = 1e-2


def init(key: jax.Array, cfg: LSTMAEConfig) -> AEParams:
    f, h = cfg.features, cfg.hidden
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    glorot = jax.nn.initializers.glorot_uniform()

    def cell(kx, kh, fan_in):
        return LSTMParams(
            w_x=glorot(kx, (fan_in, 4 * h), jnp.float32),
            w_h=glorot(kh, (h, 4 * h), jnp.float32),
            # forget-gate bias 1.0 (standard stable-training init)
            b=jnp.zeros((4 * h,)).at[h : 2 * h].set(1.0),
        )

    return AEParams(
        enc=cell(k1, k2, f),
        dec=cell(k3, k4, f),
        w_out=glorot(k5, (h, f), jnp.float32),
        b_out=jnp.zeros((f,)),
    )


def init_many(key: jax.Array, n: int, cfg: LSTMAEConfig) -> AEParams:
    """[S, ...]-stacked params: one small model per service."""
    return jax.vmap(lambda k: init(k, cfg))(jax.random.split(key, n))


def _cell_step(p: LSTMParams, h, c, x, m):
    """One masked LSTM step. x: [B, F_in], m: [B] validity."""
    gates = x @ p.w_x + h @ p.w_h + p.b  # [B, 4H]
    hid = p.w_h.shape[0]
    i, f, g, o = jnp.split(gates, (hid, 2 * hid, 3 * hid), axis=-1)
    c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
    keep = m[:, None].astype(h.dtype)
    return keep * h_new + (1 - keep) * h, keep * c_new + (1 - keep) * c


def reconstruct(params: AEParams, x: jax.Array, mask: jax.Array) -> jax.Array:
    """Encode-decode a window. x: [B, T, F], mask: [B, T] -> recon [B, T, F]."""
    b, t, f = x.shape
    h0 = jnp.zeros((b, params.enc.w_h.shape[0]), x.dtype)

    def enc_step(carry, xs):
        h, c = carry
        xt, mt = xs
        h, c = _cell_step(params.enc, h, c, xt, mt)
        return (h, c), None

    (h_enc, c_enc), _ = jax.lax.scan(
        enc_step, (h0, h0), (jnp.swapaxes(x, 0, 1), mask.T)
    )

    zeros_in = jnp.zeros((b, f), x.dtype)
    ones = jnp.ones((b,), bool)

    def dec_step(carry, _):
        h, c = carry
        h, c = _cell_step(params.dec, h, c, zeros_in, ones)
        y = h @ params.w_out + params.b_out
        return (h, c), y

    _, ys = jax.lax.scan(dec_step, (h_enc, c_enc), None, length=t)
    return jnp.swapaxes(ys, 0, 1)  # [B, T, F]


def recon_error(params: AEParams, x: jax.Array, mask: jax.Array) -> jax.Array:
    """Per-step reconstruction error (mean squared over features), [B, T]."""
    r = reconstruct(params, x, mask)
    e = jnp.mean((r - x) ** 2, axis=-1)
    return jnp.where(mask, e, 0.0)


def loss_fn(params: AEParams, x: jax.Array, mask: jax.Array) -> jax.Array:
    e = recon_error(params, x, mask)
    n = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(e) / n


def make_optimizer(cfg: LSTMAEConfig):
    return optax.adam(cfg.learning_rate)


@partial(jax.jit, static_argnames=("cfg",))
def train_step(params, opt_state, x, mask, cfg: LSTMAEConfig):
    """One SGD step for one service's model. x: [B, T, F]."""
    loss, grads = jax.value_and_grad(loss_fn)(params, x, mask)
    updates, opt_state = make_optimizer(cfg).update(grads, opt_state, params)
    return optax.apply_updates(params, updates), opt_state, loss


@partial(jax.jit, static_argnames=("cfg",))
def train_step_many(params, opt_state, x, mask, cfg: LSTMAEConfig):
    """vmapped train step over the service axis.

    params/opt_state: [S, ...]-stacked pytrees; x: [S, B, T, F],
    mask: [S, B, T]. One compiled program trains every service's model —
    this is the program `__graft_entry__.dryrun_multichip` shards over the
    device mesh (service axis = data-parallel, gate axis = tensor-parallel).
    """

    def one(p, o, xs, ms):
        loss, grads = jax.value_and_grad(loss_fn)(p, xs, ms)
        updates, o = make_optimizer(cfg).update(grads, o, p)
        return optax.apply_updates(p, updates), o, loss

    return jax.vmap(one)(params, opt_state, x, mask)


def fit_many(
    key: jax.Array,
    x: jax.Array,
    mask: jax.Array,
    cfg: LSTMAEConfig | None = None,
    steps: int = 100,
):
    """Train S per-service models on [S, B, T, F] windows.

    Returns (params [S,...], err_mean [S], err_std [S], losses [steps, S]):
    the trained model's in-sample reconstruction-error moments.
    score_many's bound is err_mean + threshold * err_std — the same
    mean + threshold*sigma semantics every other detector uses.

    Short-history admission (ISSUE 10) feeds this the same way a full
    history does, but the caller MUST hold the PR-7 min-history gate
    (`engine/kinds/lstm.py`, `LstmKind.judge_cold`: >= 2 training windows of the job's own
    bucket) — a single-window "distribution" degenerates its cutoff
    calibration and flags clean noise. Jobs under the gate stay
    UNKNOWN until refinement grows their coverage past it.
    """
    if cfg is None:
        cfg = LSTMAEConfig(features=x.shape[-1])
    s = x.shape[0]
    params = init_many(key, s, cfg)
    opt_state = jax.vmap(make_optimizer(cfg).init)(params)

    def body(carry, _):
        p, o = carry
        p, o, loss = train_step_many(p, o, x, mask, cfg)
        return (p, o), loss

    (params, _), losses = jax.lax.scan(body, (params, opt_state), None, length=steps)
    err = jax.vmap(lambda p, xs, ms: recon_error(p, xs, ms))(params, x, mask)
    n = jnp.maximum(jnp.sum(mask, axis=(1, 2)), 1.0)
    mean_e = jnp.sum(err, axis=(1, 2)) / n
    var_e = jnp.sum(jnp.where(mask, (err - mean_e[:, None, None]) ** 2, 0.0), axis=(1, 2)) / n
    return params, mean_e, jnp.sqrt(var_e), losses


def shardings(mesh, params, opt_state, hidden: int):
    """NamedShardings for stacked params/opt_state on a (data, model) mesh.

    Rule: the leading service axis shards over `data` (one slice of the
    fleet's models per chip group); any 4H gate axis shards over `model`
    (tensor parallelism inside each LSTM cell — the gate matmul
    [B,F]x[F,4H] column-partitions cleanly, XLA inserts the reduce where
    the hidden state feeds back). Everything else is replicated.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    gate = 4 * hidden

    def spec(leaf):
        # the gate axis is always the LAST axis of w_x/w_h/b; only the last
        # dim is considered so a coincidental inner dim == 4H (e.g.
        # features == 4*hidden) can't produce a duplicated mesh axis
        dims = ["data"] + [None] * (leaf.ndim - 2)
        if leaf.ndim >= 2:
            dims.append("model" if leaf.shape[-1] == gate else None)
        return NamedSharding(mesh, P(*dims))

    return jax.tree.map(spec, params), jax.tree.map(spec, opt_state)


def ae_cutoff(err_mean, err_std, threshold) -> "np.ndarray":
    """Quantile-matched reconstruction-error cutoff, [S] (host-side).

    Reconstruction error is a squared quantity — right-skewed, never
    Gaussian — so mean + threshold*sigma underestimates its tail and the
    naive bound pays false positives at exactly the configured-sigma
    rates the other detectors hold (VERDICT r2 item 4). Instead the
    training-error moments fit a gamma (k = mean^2/var, theta =
    var/mean; chi^2-family, the natural model for squared errors), and
    the cutoff is the gamma quantile with the SAME tail mass as the
    two-sided normal tail P(|z| > threshold) — the calibration
    `residual_mvn.chi2_quantile` already applies to the MVN. Never
    returns less than the classic mean + threshold*sigma bound, so
    recalibration can only tighten precision. `threshold` may be scalar
    or [S] (per-job canary lowering)."""
    import numpy as np
    from scipy import stats

    mean = np.maximum(np.asarray(err_mean, np.float64), 1e-300)
    std = np.asarray(err_std, np.float64)
    var = np.maximum(std * std, 0.0)
    p_tail = np.clip(2.0 * stats.norm.sf(np.asarray(threshold, np.float64)), 1e-300, 1.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        k = np.where(var > 0, mean * mean / np.maximum(var, 1e-300), 1.0)
        theta = np.where(var > 0, var / mean, 0.0)
        gq = stats.gamma.ppf(1.0 - p_tail, k, scale=theta)
    gq = np.where((var > 0) & np.isfinite(gq), gq, mean)
    return np.maximum(gq, np.asarray(err_mean) + np.asarray(threshold) * std).astype(
        np.float32
    )


@jax.jit
def score_many(params, x, mask, err_mean, err_std, threshold):
    """Anomaly flags for [S, B, T, F] windows against trained models.

    Classic mean + threshold*sigma bound on the reconstruction error —
    kept for benchmarks and as the simple API; the shipped judge uses
    `score_many_cutoff` with the quantile-matched `ae_cutoff` instead
    (squared errors are right-skewed, so this bound's tail is heavier
    than the configured sigmas imply). Returns (flags [S, B, T],
    errors [S, B, T])."""
    err = jax.vmap(recon_error)(params, x, mask)
    thr = (err_mean + threshold * err_std)[:, None, None]  # [S, 1, 1]
    flags = mask & (err > thr)
    return flags, err


@jax.jit
def score_many_cutoff(params, x, mask, cutoff):
    """Anomaly flags for [S, B, T, F] windows against per-model error
    cutoffs [S] (see `ae_cutoff`). Returns (flags [S, B, T], errors)."""
    err = jax.vmap(recon_error)(params, x, mask)
    flags = mask & (err > cutoff[:, None, None])
    return flags, err


@jax.jit
def score_rows_cutoff(params, rows, x, mask, cutoff):
    """`score_many_cutoff` against ARENA-resident models (engine.arena
    .TreeArena): `params` leaves are [capacity, ...]-stacked and `rows`
    [S] indexes the batch's models, gathered ON DEVICE — the LSTM-AE
    counterpart of `scoring.score_from_arena`, so a warm joint re-check
    tick ships only the current windows and a row-index vector, never
    the ~60 KB/model parameter stack. Returns (flags [S, B, T], errors)."""
    # named scopes: the phase rides every op's name in a device trace
    with jax.named_scope("gather_rows"):
        gathered = jax.tree.map(
            lambda leaf: jnp.take(leaf, rows, axis=0), params
        )
    with jax.named_scope("ae_score"):
        return score_many_cutoff(gathered, x, mask, cutoff)


# Mesh-placement contract for the from-rows entry points (ISSUE 13,
# arena layout updated by ISSUE 19): every computation above is per-row
# independent along the leading [S] axis (vmapped scoring, axis-0
# gathers), so callers may pass `x`/`mask` with their leading axis
# sharded over a mesh's data axis and XLA partitions the program with
# zero collectives. The arena `params` stack arrives in one of two
# layouts:
#   - sharded (default): each leaf's [capacity] axis block-shards over
#     the SAME data axis and `rows` carries LOCAL (per-shard) indices —
#     the judge's block placement rule puts every batch position's row
#     on the device holding that position, so the gather runs inside
#     shard_map against each device's own block (see
#     multivariate.lstm_joint_score_from_rows_sharded).
#   - replicated (FOREMAST_ARENA_SHARDED=0, pod mode): `params` fully
#     replicated, `rows` global — the gather reads each device's local
#     replica (the ISSUE 13 layout).
# Either way: zero cross-chip transfer per warm tick. S must be a
# multiple of the data axis (the judge's batch rounding guarantees it).
# Nothing here may ever reduce ACROSS the [S] axis, or the contract
# breaks.
