"""The plain reference of fleet kind `lstm`: the joint LSTM-hybrid judgment.

The comparison finds a kind's reference by its name, as readers and
drivers are found: `chipbench/references/<kind>.py` gives
`judge(rows, group, cfg, history, control, log)` -> the reference's flags
[K, W] and, for every point, the margin by which it holds them. A later PR
that brings a fleet kind brings this file for it and edits none.

Straightforward `jax.numpy`, float32, matmuls at `highest` precision, no
arena, no cache, no bucketing, no bf16 wire format. It imports nothing
of `foremast_tpu` and takes nothing the program has made: its inputs are
the seeded histories and the windows the harness sent, its models it
fits itself. The semantics follow the reference brain's model zoo as the
program documents it (`engine/multivariate.py`, `models/`): for a
service of F >= 3 metrics

  * an LSTM autoencoder (hidden 32) trained 60 Adam steps (lr 1e-2) on
    the newest 8 windows of the history, whose per-step reconstruction
    error is held against a gamma-quantile cutoff of its in-sample
    error moments;
  * per-metric additive Holt-Winters (0.3, 0.05, 0.1; season = the
    configured steps) whose causal one-step residuals over the history
    fit a full-covariance Gaussian; a current window is continued from
    the terminal state, scored twice (the second pass gates state
    updates off at points the first put over the cutoff) and its
    Mahalanobis d^2 held against chi^2_F quantiles;
  * the hybrid rule: AE flags, or strong d^2, or borderline d^2 with an
    AE flag or a borderline neighbour.

`dtype` is the precision everything is computed in: float32 for the
reference, bfloat16 (the nearest below the float32 the configuration
states) for the control that must come out as not correct.

The AE's initial weights are a function of the service's POSITION in the
batch it was fitted with (`lstm_ae.init_many`: `split(key(0), S)[i]`), so
the caller states that position; it follows from the fleet's order and
the deployment's chunk sizes, not from anything the program returns.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

HIDDEN = 32
AE_STEPS = 60
AE_LR = 1e-2
AE_WINDOWS = 8
HW_ALPHA, HW_BETA, HW_GAMMA = 0.3, 0.05, 0.1
MVN_RIDGE = 1e-6
MVN_CONFIRM_MARGIN = 1.0
GAP_TREND_CAP_STEPS = 1440


def bucket(n: int) -> int:
    """The window bucket a model is fitted at: next power of two >= 8."""
    b = 8
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# LSTM autoencoder
# ---------------------------------------------------------------------------


def _ae_init(key, f: int):
    h = HIDDEN
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    glorot = jax.nn.initializers.glorot_uniform()
    bias = jnp.zeros((4 * h,), jnp.float32).at[h : 2 * h].set(1.0)
    return {
        "enc_wx": glorot(k1, (f, 4 * h), jnp.float32),
        "enc_wh": glorot(k2, (h, 4 * h), jnp.float32),
        "enc_b": bias,
        "dec_wx": glorot(k3, (f, 4 * h), jnp.float32),
        "dec_wh": glorot(k4, (h, 4 * h), jnp.float32),
        "dec_b": bias,
        "w_out": glorot(k5, (h, f), jnp.float32),
        "b_out": jnp.zeros((f,), jnp.float32),
    }


def _cell(wx, wh, b, h, c, x, keep):
    gates = x @ wx + h @ wh + b
    i, f, g, o = jnp.split(gates, 4, axis=-1)
    c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
    h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
    k = keep[:, None].astype(h.dtype)
    return k * h_new + (1 - k) * h, k * c_new + (1 - k) * c


def _ae_error(p, x, mask):
    """x [B, T, F], mask [B, T] -> masked per-step mean squared
    reconstruction error [B, T]. Masked steps carry the encoder state."""
    b, t, f = x.shape
    h0 = jnp.zeros((b, HIDDEN), x.dtype)

    def enc(carry, xs):
        xt, mt = xs
        return _cell(p["enc_wx"], p["enc_wh"], p["enc_b"], *carry, xt, mt), None

    state, _ = jax.lax.scan(enc, (h0, h0), (jnp.swapaxes(x, 0, 1), mask.T))
    zeros = jnp.zeros((b, f), x.dtype)
    ones = jnp.ones((b,), bool)

    def dec(carry, _):
        h, c = _cell(p["dec_wx"], p["dec_wh"], p["dec_b"], *carry, zeros, ones)
        return (h, c), h @ p["w_out"] + p["b_out"]

    _, ys = jax.lax.scan(dec, state, None, length=t)
    recon = jnp.swapaxes(ys, 0, 1)
    e = jnp.mean((recon - x) ** 2, axis=-1)
    return jnp.where(mask, e, 0.0)


def _ae_loss(p, x, mask):
    e = _ae_error(p, x, mask)
    return jnp.sum(e) / jnp.maximum(jnp.sum(mask), 1.0)


def _ae_fit_one(key, x, mask, dtype):
    """One service: x [W, T, F] training windows -> (params, err mean,
    err std). Plain Adam (b1 .9, b2 .999, eps 1e-8), AE_STEPS steps."""
    p = jax.tree.map(lambda a: a.astype(dtype), _ae_init(key, x.shape[-1]))
    x = x.astype(dtype)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)

    def step(carry, t):
        p, m, v = carry
        g = jax.grad(_ae_loss)(p, x, mask)
        m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
        tt = (t + 1).astype(jnp.float32)
        c1 = (1.0 - 0.9**tt).astype(dtype)
        c2 = (1.0 - 0.999**tt).astype(dtype)
        p = jax.tree.map(
            lambda a, mm, vv: a
            - jnp.asarray(AE_LR, dtype) * (mm / c1) / (jnp.sqrt(vv / c2) + jnp.asarray(1e-8, dtype)),
            p, m, v,
        )
        return (p, m, v), None

    (p, _, _), _ = jax.lax.scan(step, (p, m, v), jnp.arange(AE_STEPS))
    err = _ae_error(p, x, mask).astype(jnp.float32)
    n = jnp.maximum(jnp.sum(mask), 1.0)
    mean = jnp.sum(err) / n
    var = jnp.sum(jnp.where(mask, (err - mean) ** 2, 0.0)) / n
    return p, mean, jnp.sqrt(var)


def ae_cutoff(err_mean, err_std, threshold: float) -> np.ndarray:
    """Gamma quantile of the in-sample error moments with the tail mass of
    the two-sided normal tail at `threshold` sigmas, never under mean +
    threshold*std (host, float64, scipy)."""
    from scipy import stats

    mean = np.maximum(np.asarray(err_mean, np.float64), 1e-300)
    std = np.asarray(err_std, np.float64)
    var = np.maximum(std * std, 0.0)
    p_tail = np.clip(2.0 * stats.norm.sf(float(threshold)), 1e-300, 1.0)
    with np.errstate(divide="ignore", invalid="ignore"):
        k = np.where(var > 0, mean * mean / np.maximum(var, 1e-300), 1.0)
        theta = np.where(var > 0, var / mean, 0.0)
        gq = stats.gamma.ppf(1.0 - p_tail, k, scale=theta)
    gq = np.where((var > 0) & np.isfinite(gq), gq, mean)
    return np.maximum(gq, np.asarray(err_mean) + threshold * std).astype(np.float32)


def chi2_quantile(threshold: float, dof: int) -> float:
    from scipy import stats

    p_tail = min(max(2.0 * stats.norm.sf(threshold), 1e-300), 1.0)
    return float(stats.chi2.ppf(1.0 - p_tail, dof))


# ---------------------------------------------------------------------------
# Holt-Winters residual Gaussian
# ---------------------------------------------------------------------------


def _hw_fit(x, m: int):
    """x [R, T] -> (one-step predictions [R, T], level, trend, season
    [R, m]). The first point initialises and updates nothing; season and
    level start from the first season's mean and residuals."""
    a, b, g = (jnp.asarray(v, x.dtype) for v in (HW_ALPHA, HW_BETA, HW_GAMMA))
    t_len = x.shape[1]
    level0 = jnp.mean(x[:, :m], axis=1)
    season0 = x[:, :m] - level0[:, None]
    if season0.shape[1] < m:
        season0 = jnp.pad(season0, ((0, 0), (0, m - season0.shape[1])))

    def step(carry, xs):
        level, trend, season = carry
        xt, t = xs
        p = t % m
        s_t = jax.lax.dynamic_slice_in_dim(season, p, 1, axis=1)[:, 0]
        pred = level + trend + s_t
        nl = a * (xt - s_t) + (1 - a) * (level + trend)
        nt = b * (nl - level) + (1 - b) * trend
        ns = g * (xt - nl) + (1 - g) * s_t
        upd = t > 0
        season = jax.lax.dynamic_update_slice_in_dim(
            season, jnp.where(upd, ns, s_t)[:, None], p, axis=1
        )
        level = jnp.where(upd, nl, level)
        trend = jnp.where(upd, nt, trend)
        return (level, trend, season), jnp.where(upd, pred, xt)

    init = (level0, jnp.zeros_like(level0), season0)
    (level, trend, season), preds = jax.lax.scan(
        step, init, (x.T, jnp.arange(t_len, dtype=jnp.int32))
    )
    return preds.T, level, trend, season


def _hw_d2(st, cur, upd):
    """Continue the fitted recurrence over cur [N, F, W]; upd [N, W] False
    scores a point without letting it into the state. -> d^2 [N, W]."""
    n, f, w = cur.shape
    m = st["season"].shape[-1]
    a, b, g = (jnp.asarray(v, cur.dtype) for v in (HW_ALPHA, HW_BETA, HW_GAMMA))
    level = st["level"].reshape(n * f)
    trend = st["trend"].reshape(n * f)
    season = st["season"].reshape(n * f, m)
    phase = st["phase"].reshape(n * f)
    x = cur.reshape(n * f, w)
    u = jnp.repeat(upd, f, axis=0)
    rows = jnp.arange(n * f)

    def step(carry, xs):
        level, trend, season, phase = carry
        xt, ok = xs
        s_t = season[rows, phase]
        pred = level + trend + s_t
        nl = a * (xt - s_t) + (1 - a) * (level + trend)
        nt = b * (nl - level) + (1 - b) * trend
        ns = g * (xt - nl) + (1 - g) * s_t
        season = season.at[rows, phase].set(jnp.where(ok, ns, s_t))
        level = jnp.where(ok, nl, level)
        trend = jnp.where(ok, nt, trend)
        return (level, trend, season, (phase + 1) % m), pred

    _, preds = jax.lax.scan(step, (level, trend, season, phase), (x.T, u.T))
    resid = (x - preds.T).reshape(n, f, w)
    d = resid - st["rmu"][:, :, None]
    sol = jnp.linalg.solve(st["cov"].astype(jnp.float32), d.astype(jnp.float32))
    return jnp.sum(d.astype(jnp.float32) * sol, axis=1).astype(cur.dtype)


# ---------------------------------------------------------------------------
# fit and score
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("season", "w_bucket", "dtype"))
def _fit(hist, ae_keys, season: int, w_bucket: int, dtype):
    n, f, t_len = hist.shape
    x = hist.astype(dtype)
    m = season if t_len >= 2 * season else 1
    pred, level, trend, seas = _hw_fit(x.reshape(n * f, t_len), m)
    resid = (x.reshape(n * f, t_len) - pred).reshape(n, f, t_len)[:, :, m:]
    cnt = t_len - m
    rmu = jnp.sum(resid, axis=-1) / cnt
    rc = (resid - rmu[:, :, None]).astype(jnp.float32)
    cov = jnp.einsum("nft,ngt->nfg", rc.astype(dtype), rc.astype(dtype)).astype(dtype) / cnt
    tr = jnp.trace(cov, axis1=-2, axis2=-1) / f
    cov = cov + (jnp.asarray(MVN_RIDGE, dtype) * tr + jnp.asarray(1e-12, dtype))[:, None, None] * jnp.eye(f, dtype=dtype)
    sign, logdet = jnp.linalg.slogdet(cov.astype(jnp.float32))
    valid = (cnt >= 10) & (sign > 0) & jnp.isfinite(logdet)
    # AE: the newest AE_WINDOWS whole windows, newest first
    usable = (t_len // w_bucket) * w_bucket
    chunks = hist[:, :, t_len - usable :].reshape(n, f, -1, w_bucket)
    n_win = min(chunks.shape[2], AE_WINDOWS)
    wins = jnp.stack(
        [chunks[:, :, -(k + 1), :].transpose(0, 2, 1) for k in range(n_win)], axis=1
    )  # [N, n_win, w_bucket, F]
    wmask = jnp.ones(wins.shape[:3], bool)
    params, emu, esd = jax.vmap(lambda k, xx, mm: _ae_fit_one(k, xx, mm, dtype))(
        ae_keys, wins, wmask
    )
    return {
        "ae": params, "err_mean": emu, "err_std": esd,
        "level": level.reshape(n, f), "trend": trend.reshape(n, f),
        "season": seas.reshape(n, f, m),
        "phase": jnp.full((n, f), t_len % m, jnp.int32),
        "rmu": rmu, "cov": cov, "valid": valid,
    }


@functools.partial(jax.jit, static_argnames=("w_bucket", "dtype"))
def _score(st, cur, gaps, cut, cutoff, hi_cutoff, w_bucket: int, dtype):
    n, f, w = cur.shape
    m = st["season"].shape[-1]
    x = cur.astype(dtype)
    # AE on the window padded to its bucket, real points masked in
    xp = jnp.zeros((n, w_bucket, f), dtype).at[:, :w, :].set(x.transpose(0, 2, 1))
    mask = jnp.arange(w_bucket)[None, :] < w
    mask = jnp.broadcast_to(mask, (n, w_bucket))
    err = jax.vmap(lambda p, xx, mm: _ae_error(p, xx[None], mm[None])[0])(
        st["ae"], xp, mask
    )[:, :w].astype(jnp.float32)
    a_ratio = err / cut[:, None]
    # HW state advanced over the history->window gap: the phase by the
    # true gap, the level by at most GAP_TREND_CAP_STEPS of trend
    gap = gaps.astype(jnp.int32)
    adv = dict(st)
    adv["phase"] = ((st["phase"] + gap[:, None]) % m).astype(jnp.int32)
    adv["level"] = st["level"] + st["trend"] * jnp.minimum(gap, GAP_TREND_CAP_STEPS).astype(dtype)[:, None]
    d2_first = _hw_d2(adv, x, jnp.ones((n, w), bool)).astype(jnp.float32)
    gate = d2_first > cutoff
    d2 = _hw_d2(adv, x, ~gate).astype(jnp.float32)
    return {"a": a_ratio, "r": d2 / cutoff, "h": d2 / hi_cutoff, "r1": d2_first / cutoff,
            "valid": st["valid"]}


def hybrid_flags(a, r, h, valid):
    """The hybrid rule on ratio arrays [N, W] (numpy): AE flags, or strong
    d^2, or borderline d^2 with an AE flag or a borderline neighbour."""
    ae = a > 1.0
    over = (r > 1.0) & valid[:, None]
    strong = (h > 1.0) & valid[:, None]
    border = over & ~strong
    neigh = np.zeros_like(border)
    neigh[:, 1:] |= border[:, :-1]
    neigh[:, :-1] |= border[:, 1:]
    return ae | strong | (border & (ae | neigh))


class JointReference:
    """Fits and scores a block of F-metric services. `dtype` float32 is
    the reference; bfloat16 the control."""

    def __init__(self, season: int, threshold: float, window: int, dtype=jnp.float32):
        self.season = int(season)
        self.threshold = float(threshold)
        self.w_bucket = bucket(int(window))
        self.dtype = dtype

    def fit(self, hist: np.ndarray, ae_pos: np.ndarray, block: int = 2048):
        """hist [N, F, T] f32; ae_pos [N] position of each service in the
        batch its AE was fitted with. In blocks of a power-of-two size (the
        last one padded by repeating a row), so that it fits and so that every
        run finds the one compiled shape in the cache."""
        n = hist.shape[0]
        block = min(block, bucket(n))
        outs = []
        with jax.default_matmul_precision("highest"):
            keys = jax.random.split(jax.random.key(0), int(np.max(ae_pos)) + 1)
            for i in range(0, n, block):
                take = np.minimum(np.arange(i, i + block), n - 1)
                st = _fit(
                    jnp.asarray(hist[take]), keys[jnp.asarray(ae_pos[take])],
                    season=self.season, w_bucket=self.w_bucket, dtype=self.dtype,
                )
                keep = min(block, n - i)
                outs.append(jax.tree.map(lambda a: np.asarray(a)[:keep], st))
        st = jax.tree.map(lambda *xs: np.concatenate(xs), *outs)
        f = hist.shape[1]
        st["cut"] = ae_cutoff(
            st["err_mean"].astype(np.float32), st["err_std"].astype(np.float32),
            self.threshold,
        )
        st["cutoff"] = np.float32(chi2_quantile(self.threshold, f))
        st["hi_cutoff"] = np.float32(
            chi2_quantile(self.threshold + MVN_CONFIRM_MARGIN, f)
        )
        return st

    def score(self, st, idx: np.ndarray, cur: np.ndarray, gaps: np.ndarray, block: int = 4096):
        """Score windows cur [K, F, W] of the fitted services idx [K] ->
        dict of numpy ratio arrays [K, W] and the rule's flags. Fixed
        blocks, the last padded, for the same reason as `fit`."""
        keep = ("ae", "level", "trend", "season", "phase", "rmu", "cov", "valid")
        k = len(idx)
        block = min(block, bucket(k))
        parts = []
        with jax.default_matmul_precision("highest"):
            for i in range(0, k, block):
                take = np.minimum(np.arange(i, i + block), k - 1)
                rows = idx[take]
                sub = {key: jax.tree.map(lambda a: jnp.asarray(a[rows]), st[key]) for key in keep}
                out = _score(
                    sub, jnp.asarray(cur[take]), jnp.asarray(gaps[take], jnp.int32),
                    jnp.asarray(st["cut"][rows]), st["cutoff"], st["hi_cutoff"],
                    w_bucket=self.w_bucket, dtype=self.dtype,
                )
                n_keep = min(block, k - i)
                parts.append({
                    key: np.asarray(v, bool if key == "valid" else np.float32)[:n_keep]
                    for key, v in out.items()
                })
        out = {key: np.concatenate([p[key] for p in parts]) for key in parts[0]}
        out["flags"] = hybrid_flags(out["a"], out["r"], out["h"], out["valid"])
        return out


# ---------------------------------------------------------------------------
# what the comparison asks of a kind's reference
# ---------------------------------------------------------------------------

_TINY = 1e-30


def point_margins(sc: dict) -> np.ndarray:
    """[K, W] margin of every point: the least sup-norm change of the
    reference's log scores (AE ratio, d^2 against both cutoffs, at the
    point and its neighbours, each free to move on its own) that flips
    the hybrid rule's flag there, and never more than the least
    |ln(gate)| of the first pass up to the point: a gate on its cutoff
    changes the state every later d^2 is scored from."""

    def ln(x):
        return np.log(np.maximum(x, _TINY))

    def pos(x):
        return np.maximum(x, 0.0)

    a, r, h = ln(sc["a"]), ln(sc["r"]), ln(sc["h"])
    valid = sc["valid"][:, None]
    r = np.where(valid, r, -np.inf)  # an invalid fit's d^2 can flag nothing
    h = np.where(valid, h, -np.inf)

    def neighbours(x, fill):
        left = np.concatenate([np.full_like(x[:, :1], fill), x[:, :-1]], axis=1)
        right = np.concatenate([x[:, 1:], np.full_like(x[:, :1], fill)], axis=1)
        return left, right

    # cost to make a point borderline (over the base cutoff, under the high one)
    to_border = np.maximum(pos(-r), pos(h))
    # cost to make a borderline point not borderline
    un_border = np.where((r > 0) & (h <= 0), np.minimum(pos(r), pos(-h)), 0.0)
    tb_l, tb_r = neighbours(to_border, np.inf)
    ub_l, ub_r = neighbours(un_border, 0.0)
    # unflagged -> flagged: AE over, or strong, or borderline with a borderline neighbour
    up = np.minimum.reduce([
        pos(-a), pos(-h), np.maximum(to_border, np.minimum(tb_l, tb_r)),
    ])
    # flagged -> unflagged: AE under, not strong, and not (over with a borderline neighbour)
    down = np.maximum.reduce([
        pos(a), pos(h), np.minimum(pos(r), np.maximum(ub_l, ub_r)),
    ])
    flip = np.where(sc["flags"], down, up)
    gates = np.minimum.accumulate(np.abs(ln(sc["r1"])), axis=1)
    gates = np.where(valid, gates, np.inf)
    return np.minimum(flip, gates)


def judge(rows: list, group: dict, cfg: dict, history, control: bool = False, log=None) -> dict:
    """Fit the reference on every distinct service of `rows` (judgments of
    this group: uid, fit_pos, sweep, the window sent [F, W]) from
    `history(uid)` [F, n], and score each row's window. `control` computes
    everything in bfloat16. -> {"flags" [K, W], "margins" [K, W]}."""
    uids = sorted({r["uid"] for r in rows})
    at = {u: i for i, u in enumerate(uids)}
    pos = np.zeros(len(uids), np.int64)
    for r in rows:
        pos[at[r["uid"]]] = r["fit_pos"]
    t = time.perf_counter()
    hist = np.stack([history(u) for u in uids])
    ref = JointReference(
        cfg["season_steps"], cfg["anomaly_threshold"], rows[0]["sent"].shape[-1],
        dtype=jnp.bfloat16 if control else jnp.float32,
    )
    st = ref.fit(hist, pos)
    del hist
    if log:
        log(f"reference fitted {len(uids)} services in {time.perf_counter() - t:.1f} s")
    idx = np.array([at[r["uid"]] for r in rows])
    cur = np.stack([r["sent"] for r in rows])
    gaps = np.array([r["sweep"] for r in rows], np.int32)
    sc = ref.score(st, idx, cur, gaps)
    return {"flags": sc["flags"], "margins": point_margins(sc), "scores": sc}
