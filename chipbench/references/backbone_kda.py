"""The plain reference of fleet kind `backbone_kda`: every alias of a
document is one sequence of a shared Kimi-Linear model (config.json keys in
the model file the configuration's `env` names), and a timestamp is
anomalous where any alias's score -log p(id_t | history, id_<t) exceeds the
configuration's `anomaly_threshold` (nats).

As the model's config.json and the model file's `assumed` block describe
it, layers numbered from 1 as `linear_attn_config` numbers them (RMSNorm(x)
= x / sqrt(mean x^2 + eps), eps `rms_norm_eps`, gains 1):

    x <- x + Mix_l(RMSNorm(x));  x <- x + FFN_l(RMSNorm(x));  logits = RMSNorm(x) W_head

  * KDA (`kda_layers`; H heads of d): q~, k~, v~ = SiLU(Conv4(x W_q | W_k |
    W_v)) (causal depthwise, `short_conv_kernel_size` taps); q = L2norm(q~)
    d^-1/2, k = L2norm(k~), v = v~; g_t = -exp(A_log_h) softplus((x_t W_f1)
    W_f2 + dt_bias) per head and key channel; b_t = sigmoid(x_t W_b);
    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T; o_t =
    S_t^T q_t; Mix = (RMSNorm_head(o_t) * sigmoid((x_t W_g1) W_g2)) W_o.
  * MLA (`full_attn_layers`): q = x W_q -> per head [q_n; q_r]; [c; k_r] = x
    W_kva, c <- RMSNorm(c); [k_n,h; v_h] = c W_kvb,h; score (q_n.k_n,h +
    q_r.k_r) / sqrt(nope + rope), causal softmax, o_h = sum p v_h, Mix =
    concat(o) W_o; `mla_use_nope`: nothing is rotated.
  * FFN: layer 1 (`first_k_dense_replace`) (SiLU(x W_g) * x W_u) W_d of
    `intermediate_size`; every later layer sum over the top-k of sigmoid(x
    W_r) + bias (the bias moves the choice only), weights s_e / sum s x
    `routed_scaling_factor`, of the experts this share holds + the shared
    expert, each of `moe_intermediate_size`.

Tokeniser: scale = mean |history| (0 -> 1), id = clip(floor((x / scale + 15)
/ 30 * V), 0, V - 1) over the V vocabulary rows held. Weights: N(0, 0.02^2)
a tensor from fold_in(PRNGKey(weights_seed), crc32(name)), rounded to
bfloat16; A_log = log U(1, 16), dt_bias = softplus^-1(dt), log dt ~ U(log
1e-3, log 1e-1).

Straightforward `jax.numpy`, float32 under `highest`; imports nothing of
`foremast_tpu`. No cache manager, no chunked recurrence, no absorbed
projections, no sorting: a loop over the layers, and in each a loop over
the sampled sequences. A sequence's whole history (all but its last point)
goes through the layer once: KDA a token at a time (`lax.scan`, a few
sequences' recurrences side by side), MLA with explicit keys and values a
head. What it leaves — a KDA layer's state and
the last three projected inputs of its convolution, a MLA layer's K and V —
is kept as plain arrays, and each sweep's window, fed [last history id; the
window's ids but the last], runs as the continuation. Weights are held in
bfloat16 and widened a matrix at a time. For one (sequence, sweep) a run the
concatenated [history; window] also goes through in ONE forward, and the two
have to agree to 1e-5 in a logit: the reuse is checked, not assumed.

What it costs to start: a float32 product at `highest` takes the chip's
compiler 3-10 s, so every product takes rows of tokens, `rows` at a time,
whether they are a whole sequence's or a sequence's windows' (one program a
piece, not one a shape), and `compile_ahead` compiles the pieces side by
side in threads before the run (30 s where one after another took 115).

`control=True` is the same reference with weights rounded to
`float8_e4m3fn`, every product and sum in bfloat16 and a bfloat16 state:
below the precision the configuration states. The margin of a point is |max
over aliases of score - threshold| in nats.
"""

from __future__ import annotations

import functools
import json
import os
import time
import zlib
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOKEN_RANGE = 15.0
Q_BLOCK = 256  # queries a block of a history's attention
W_BLOCK = 8  # windows a block of the continuation's attention
S_BLOCK = 64  # windows a block of the continuation's recurrence; a sequence's windows are padded to whole ones
KDA_SEQS = 2  # whole sequences whose recurrences run side by side
ROW_PAD = 1024  # an expert's routed tokens go through it this many at a time, the last block padded
HEAD_ROWS = 2048  # tokens a block of the head
FAR = 1 << 30  # the position of a padded key: after every query


def model_of(cfg: dict) -> dict:
    with open(os.path.join(ROOT, cfg["env"]["FOREMAST_BACKBONE_MODEL"]), encoding="utf-8") as fh:
        return json.load(fh)


# -- weights -------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("shapes", "control"))
def _draw(root, crcs, shapes, control):
    """Tensor i: N(0, 0.02^2) of shapes[i] from fold_in(root, crcs[i]),
    rounded to bfloat16. One program a list of shapes: the layers of one
    make share it, and so do all the experts."""

    def one(crc, shape):
        w = 0.02 * jax.random.normal(jax.random.fold_in(root, crc), shape, jnp.float32)
        w = w.astype(jnp.bfloat16)
        return w.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16) if control else w

    return [one(crcs[i], s) for i, s in enumerate(shapes)]


@functools.partial(jax.jit, static_argnames=("heads", "width"))
def _decay(root, crc_a, crc_dt, heads, width):
    a_log = jnp.log(jax.random.uniform(jax.random.fold_in(root, crc_a), (heads,), jnp.float32, 1.0, 16.0))
    dt = jnp.exp(jax.random.uniform(
        jax.random.fold_in(root, crc_dt), (width,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    bias = dt + jnp.log(-jnp.expm1(-dt))
    return a_log.astype(jnp.bfloat16), bias.astype(jnp.bfloat16)


def _crc(name: str):
    return np.uint32(zlib.crc32(name.encode()))


def tensors(model: dict, named: dict, control: bool) -> dict:
    """{key: tensor} for named = {key: (tensor name, shape)}."""
    root = jax.random.PRNGKey(int(model.get("weights_seed", 0)))
    drawn = _draw(root, np.asarray([_crc(n) for n, _ in named.values()], np.uint32),
                  tuple(s for _, s in named.values()), control)
    return dict(zip(named, drawn))


def layer_kinds(model: dict) -> list:
    lin = model["linear_attn_config"]
    return ["kda" if li + 1 in lin["kda_layers"] else "mla"
            for li in range(model["share"]["layers_held"])]


def ffn_names(model: dict, prefix: str, width: int) -> dict:
    h = model["hidden_size"]
    return {"gate": (prefix + ".gate", (h, width)), "up": (prefix + ".up", (h, width)),
            "down": (prefix + ".down", (width, h))}


def layer_names(model: dict, li: int, kind: str) -> dict:
    """{key: (tensor name, shape)} of what one layer draws beside its
    experts: the mixer's under "mix.", the router's or the dense FFN's."""
    h = model["hidden_size"]
    p = f"layers.{li}."
    if kind == "kda":
        lin = model["linear_attn_config"]
        heads, d, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
        hd = heads * d
        mix = {"f_down": ("kda.f_down", (h, d)), "f_up": ("kda.f_up", (d, hd)),
               "beta": ("kda.beta", (h, heads)), "g_down": ("kda.g_down", (h, d)),
               "g_up": ("kda.g_up", (d, hd)), "wo": ("kda.o", (hd, h))}
        for n in "qkv":
            mix["w" + n], mix["conv_" + n] = (f"kda.{n}", (h, hd)), (f"kda.conv_{n}", (taps, hd))
    else:
        heads, lat = model["num_attention_heads"], model["kv_lora_rank"]
        nope, rope, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                          model["v_head_dim"])
        mix = {"wq": ("mla.q", (h, heads * (nope + rope))), "wkva": ("mla.kva", (h, lat + rope)),
               "wkvb": ("mla.kvb", (lat, heads * (nope + dv))), "wo": ("mla.o", (heads * dv, h))}
    named = {"mix." + k: (p + n, shape) for k, (n, shape) in mix.items()}
    if li < model["first_k_dense_replace"]:
        dense = ffn_names(model, p + "dense", model["intermediate_size"])
        named.update({"dense." + k: v for k, v in dense.items()})
    else:
        named["router"] = (p + "router", (h, model["num_experts"]))
        named["router_bias"] = (p + "router_bias", (model["num_experts"],))
    return named


def layer_weights(model: dict, li: int, kind: str, control: bool) -> dict:
    """One layer's weights: "mix" (the mixer's tensors), then "dense" (gate,
    up, down), or "router", "router_bias", "experts" {e: (gate, up, down)}
    of the experts this share holds and "shared" [(gate, up, down)]."""
    p = f"layers.{li}."
    drawn = tensors(model, layer_names(model, li, kind), control)
    mix = {k[4:]: v for k, v in drawn.items() if k.startswith("mix.")}
    if kind == "kda":
        lin = model["linear_attn_config"]
        root = jax.random.PRNGKey(int(model.get("weights_seed", 0)))
        mix["a_log"], mix["dt_bias"] = _decay(
            root, _crc(p + "kda.a_log"), _crc(p + "kda.dt_bias"),
            lin["num_heads"], lin["num_heads"] * lin["head_dim"])

    def ffn(prefix, width):
        return tuple(tensors(model, ffn_names(model, p + prefix, width), control).values())

    if li < model["first_k_dense_replace"]:
        return {"mix": mix, "dense": tuple(drawn[f"dense.{k}"] for k in ("gate", "up", "down"))}
    share = model["share"]
    first = share["index"] * share["experts_held"]
    width = model["moe_intermediate_size"]
    return {
        "mix": mix, "router": drawn["router"], "router_bias": drawn["router_bias"],
        "experts": {e: ffn(f"experts.{e}", width)
                    for e in range(first, first + share["experts_held"])},
        "shared": [ffn(f"shared.{j}", width) for j in range(model["num_shared_experts"])],
    }


def series_scale(history: np.ndarray) -> np.ndarray:
    s = np.abs(np.asarray(history, np.float32)).mean(axis=-1, dtype=np.float32)
    return np.where(s > 0, s, np.float32(1.0)).astype(np.float32)


def tokenize(values, scale, vocab: int) -> np.ndarray:
    u = np.asarray(values, np.float32) / np.asarray(scale, np.float32)[..., None]
    ids = np.floor((u + np.float32(TOKEN_RANGE)) / np.float32(2 * TOKEN_RANGE) * np.float32(vocab))
    return np.clip(ids, 0, vocab - 1).astype(np.int32)


# -- the layers, a piece at a time ---------------------------------------------
#
# Every matrix product takes rows of tokens, whole sequences and windows
# alike, `rows` of them at a time (`_in_rows`): one program a piece. What
# has a shape of its own for a sequence and for a block of windows (the
# convolution, the recurrence, the attention) holds no product of weights.


@functools.partial(jax.jit, static_argnames=("eps",))
def _rms(x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + jnp.asarray(eps, x.dtype))


@jax.jit
def _times(x, w):
    return x @ w.astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("eps",))
def _kda_project(x, w, eps):
    """Of tokens x [R, h], a row each: the inputs of the convolution x W_q |
    W_k | W_v [R, 3, H d], the decay's step softplus((x W_f1) W_f2 +
    dt_bias) [R, H d], sigmoid(x W_b) [R, H] and the output gate [R, H d]."""
    xn = _rms(x, eps)
    dt = xn.dtype
    proj = jnp.stack([xn @ w["w" + n].astype(dt) for n in "qkv"], axis=1)
    step = jax.nn.softplus((xn @ w["f_down"].astype(dt)) @ w["f_up"].astype(dt)
                           + w["dt_bias"].astype(dt))
    beta = jax.nn.sigmoid(xn @ w["beta"].astype(dt))
    gate = jax.nn.sigmoid((xn @ w["g_down"].astype(dt)) @ w["g_up"].astype(dt))
    return proj, step, beta, gate


@functools.partial(jax.jit, static_argnames=("heads", "d"))
def _kda_conv(proj, step, beta, tail, taps, a_log, n, heads, d):
    """q, k (normalised), v, g [T, H, d] and beta [T, H] of T consecutive
    tokens whose first n are real (the rest is padding: it neither decays
    nor writes the state) and whose convolution continues `tail` [taps - 1,
    3, H d] (zeros at a sequence's start); and the tail after the n tokens."""
    dt = proj.dtype
    t = proj.shape[0]
    carried = jnp.concatenate([tail, proj])
    k = taps.shape[0]
    act = jax.nn.silu(sum(taps[j].astype(dt) * carried[j : j + t] for j in range(k)))
    act = act.reshape(t, 3, heads, d)

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + jnp.asarray(1e-6, dt))

    q = l2(act[:, 0]) * jnp.asarray(d ** -0.5, dt)
    real = jnp.arange(t) < n
    g = jnp.where(real[:, None, None],
                  -jnp.exp(a_log.astype(dt))[None, :, None] * step.reshape(t, heads, d), 0)
    beta = jnp.where(real[:, None], beta, 0)
    after = jax.lax.dynamic_slice_in_dim(carried, n, k - 1, axis=0)
    return q, l2(act[:, 1]), act[:, 2], g, beta, after


def _taps(w: dict):
    return jnp.stack([w["conv_" + n] for n in "qkv"], axis=1)  # [K, 3, H d]


@jax.jit
def _kda_recurrence(s0, q, k, v, g, beta):
    """The delta rule a token at a time, B independent runs side by side:
    from states s0 [B, H, d, d] over q, k, v, g [B, T, H, d], beta [B, T, H]
    -> (o [B, T, H, d], the states after the T tokens). Products and sums
    are elementwise: a step is a [d, d] state a head, no matrix unit."""

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., :, None]
        u = b_t[..., None] * (v_t - (s * k_t[..., :, None]).sum(axis=-2))
        s = s + k_t[..., :, None] * u[..., None, :]
        return s, (s * q_t[..., :, None]).sum(axis=-2)

    by_token = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    s_end, o = jax.lax.scan(step, s0, by_token)
    return jnp.moveaxis(o, 0, 1), s_end


@functools.partial(jax.jit, static_argnames=("eps",))
def _kda_out(o, gate, wo, eps):
    """(RMSNorm_head(o) * gate) W_o of tokens o [R, H, d], a row each."""
    return (_rms(o, eps).reshape(o.shape[0], -1) * gate) @ wo.astype(o.dtype)


def _kda_dims(model: dict) -> dict:
    lin = model["linear_attn_config"]
    return dict(heads=lin["num_heads"], d=lin["head_dim"])


def kda_sequences(model: dict, w: dict, xs: list, ns: list):
    """One KDA layer's mixer over whole sequences xs, each [Tp, h] from its
    start with ns[i] real tokens: yields, in order, (Mix [Tp, h], (the
    convolution's tail, the state) after it). The recurrences run side by
    side, KDA_SEQS at a time (a short last group is filled up with its last
    sequence again); what a group needs is held for that group alone."""
    dims = _kda_dims(model)
    heads, d = dims["heads"], dims["d"]
    eps = model["rms_norm_eps"]
    taps = _taps(w)
    for at in range(0, len(xs), KDA_SEQS):
        group = list(range(at, min(at + KDA_SEQS, len(xs))))
        dt = xs[at].dtype
        tail = jnp.zeros((taps.shape[0] - 1, 3, heads * d), dt)
        parts, gates = [], []
        for i in group:
            proj, step, beta, gate = _kda_project(xs[i], w, eps)
            parts.append(_kda_conv(proj, step, beta, tail, taps, w["a_log"], np.int32(ns[i]), **dims))
            gates.append(gate)
        filled = parts + parts[-1:] * (KDA_SEQS - len(parts))
        stacked = [jnp.stack([p[j] for p in filled]) for j in range(5)]
        tails = [p[5] for p in parts]
        del parts, filled
        o, s_end = _kda_recurrence(jnp.zeros((KDA_SEQS, heads, d, d), dt), *stacked)
        del stacked
        for b in range(len(group)):
            yield _kda_out(o[b], gates[b], w["wo"], eps), (tails[b], s_end[b])


def kda_windows(model: dict, w: dict, xw, tail, s0, rows: int):
    """The same layer over the windows xw [Nw, W, h] of one sequence, each
    the continuation of the history's (`tail`, `s0`)."""
    dims = _kda_dims(model)
    eps = model["rms_norm_eps"]
    nw, wl, h = xw.shape
    taps = _taps(w)
    proj, step, beta, gate = _in_rows(
        lambda x: _kda_project(x, w, eps), xw.reshape(nw * wl, h), rows)
    conv = jax.vmap(lambda p, s, b: _kda_conv(p, s, b, tail, taps, w["a_log"], np.int32(wl), **dims))
    out = []
    for at in range(0, nw, S_BLOCK):
        sl = slice(at * wl, (at + S_BLOCK) * wl)
        q, k, v, g, b, _ = conv(*(a[sl].reshape(S_BLOCK, wl, *a.shape[1:]) for a in (proj, step, beta)))
        o, _ = _kda_recurrence(jnp.broadcast_to(s0, (S_BLOCK, *s0.shape)), q, k, v, g, b)
        out.append(o.reshape(S_BLOCK * wl, *o.shape[2:]))
    mix = _in_rows(lambda a: _kda_out(a[0], a[1], w["wo"], eps), (jnp.concatenate(out), gate), rows)
    return mix.reshape(nw, wl, h)


@functools.partial(jax.jit, static_argnames=("heads", "nope", "rope", "lat", "eps"))
def _mla_qkv(x, wq, wkva, wkvb, heads, nope, rope, lat, eps):
    """q, k [R, H, nope + rope], v [R, H, dv] of tokens x [R, h], a row
    each: each head's own keys and values, the shared unrotated k_r beside
    each head's k_n."""
    xn = _rms(x, eps)
    dt = xn.dtype
    t = xn.shape[0]
    q = (xn @ wq.astype(dt)).reshape(t, heads, nope + rope)
    kva = xn @ wkva.astype(dt)
    kv = (_rms(kva[:, :lat], eps) @ wkvb.astype(dt)).reshape(t, heads, -1)
    k_r = jnp.broadcast_to(kva[:, None, lat:], (t, heads, rope))
    return q, jnp.concatenate([kv[..., :nope], k_r], axis=-1), kv[..., nope:]


@jax.jit
def _attend(q, pos_q, seg_q, k, v, pos_k, seg_k):
    """Queries q [Tq, H, D] over keys k [Tk, H, D], values v [Tk, H, Dv]
    under the explicit mask: key j is seen by query i where 0 <= p_i - p_j
    and the key is of the shared history (segment -1) or of the query's own
    window. -> [Tq, H * Dv]."""
    tq, heads, d = q.shape
    gap = pos_q[:, None] - pos_k[None, :]
    seen = (gap >= 0) & ((seg_k[None, :] < 0) | (seg_k[None, :] == seg_q[:, None]))
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.asarray(d, q.dtype))
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    # a padded query sees nothing: its row is dropped by the caller
    p = jnp.where(seen.any(axis=-1)[None, :, None], p, 0)
    return jnp.einsum("hts,shd->thd", p, v).reshape(tq, -1)


def _mla_dims(model: dict) -> dict:
    return dict(heads=model["num_attention_heads"], nope=model["qk_nope_head_dim"],
                rope=model["qk_rope_head_dim"], lat=model["kv_lora_rank"],
                eps=model["rms_norm_eps"])


def _pad_rows(a, to: int, value=0):
    pad = to - a.shape[0]
    return a if pad == 0 else jnp.concatenate([a, jnp.full((pad, *a.shape[1:]), value, a.dtype)])


def _in_rows(fn, x, rows: int):
    """fn over the rows of x [N, ...] (or of each array of a tuple of such),
    `rows` of them at a time (the last block padded): the windows' tokens go
    through the very programs the whole sequences, `rows` long, have
    compiled."""
    n = jax.tree.leaves(x)[0].shape[0]
    parts = [fn(jax.tree.map(lambda a: _pad_rows(a[at : at + rows], rows), x))
             for at in range(0, n, rows)]
    return jax.tree.map(lambda *a: jnp.concatenate(a)[:n], *parts)


def _history_positions(tp: int, n):
    pos = jnp.where(jnp.arange(tp) < n, jnp.arange(tp), FAR).astype(jnp.int32)
    return pos, jnp.full(tp, -1, jnp.int32)


def mla_sequence(model: dict, w: dict, x, n: int):
    """One MLA layer's mixer over one whole sequence x [Tp, h] of n real
    tokens at positions 0 .. n - 1 (rows past n are padding: keys that lie
    after every query) -> (Mix [Tp, h], (K, V, n)). Queries go a block at a
    time over all the keys."""
    tp = x.shape[0]
    q, k, v = _mla_qkv(x, w["wq"], w["wkva"], w["wkvb"], **_mla_dims(model))
    pos, seg = _history_positions(tp, n)
    out = []
    for at in range(0, tp, Q_BLOCK):
        sl = slice(at, at + Q_BLOCK)
        out.append(_attend(q[sl], pos[sl], seg[sl], k, v, pos, seg))
    return _times(jnp.concatenate(out), w["wo"]), (k, v, n)


def mla_windows(model: dict, w: dict, xw, k_hist, v_hist, n: int, rows: int):
    """The same layer over the windows xw [Nw, W, h] of ONE sequence as
    continuations of its history, whose keys and values at this layer are
    k_hist, v_hist [Tp, H, D] (n real positions): window tokens sit at
    positions n .. n + W - 1 and see the history and their own window's
    past."""
    nw, wl, h = xw.shape
    pos_h, seg_h = _history_positions(k_hist.shape[0], n)
    pos_w = jnp.tile(n + jnp.arange(wl, dtype=jnp.int32), nw)
    seg_w = jnp.repeat(jnp.arange(nw, dtype=jnp.int32), wl)
    q, k, v = _in_rows(
        lambda x: _mla_qkv(x, w["wq"], w["wkva"], w["wkvb"], **_mla_dims(model)),
        xw.reshape(nw * wl, h), rows)
    out = []
    step = W_BLOCK * wl
    for at in range(0, nw * wl, step):
        sl = slice(at, at + step)
        out.append(_attend(
            q[sl], pos_w[sl], seg_w[sl],
            jnp.concatenate([k_hist, k[sl]]), jnp.concatenate([v_hist, v[sl]]),
            jnp.concatenate([pos_h, pos_w[sl]]), jnp.concatenate([seg_h, seg_w[sl]]),
        ))
    return _in_rows(lambda a: _times(a, w["wo"]), jnp.concatenate(out), rows).reshape(nw, wl, h)


@functools.partial(jax.jit, static_argnames=("k", "factor", "eps"))
def _route(x, router, bias, k, factor, eps):
    xn = _rms(x, eps)
    s = jax.nn.sigmoid(xn @ router.astype(xn.dtype))
    _, top_i = jax.lax.top_k(s + bias.astype(xn.dtype), k)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    return top_i, top_s / top_s.sum(axis=-1, keepdims=True) * jnp.asarray(factor, xn.dtype)


def _gated(x, gate, up, down):
    dt = x.dtype
    return (jax.nn.silu(x @ gate.astype(dt)) * (x @ up.astype(dt))) @ down.astype(dt)


@functools.partial(jax.jit, static_argnames=("eps",))
def _expert(x, gate, up, down, eps):
    """expert(RMSNorm(x)) of tokens x [R, h], a row each."""
    return _gated(_rms(x, eps), gate, up, down)


@functools.partial(jax.jit, static_argnames=("eps",))
def _expert_rows(y, x, rows, weight, gate, up, down, eps):
    """y with weight * expert(RMSNorm(x[rows])) added at `rows`."""
    return y.at[rows].add(weight[:, None] * _gated(_rms(x[rows], eps), gate, up, down))


def ffn(model: dict, w: dict, x, real: int):
    """The layer's FFN of the (un-normed) tokens x [R, h]: the dense one, or
    the sum over the held experts of (router weight) x expert, each expert
    over the tokens routed to it, + the shared experts. Rows past `real` are
    padding and are routed nowhere."""
    eps = model["rms_norm_eps"]
    if "dense" in w:
        return _expert(x, *w["dense"], eps)
    top_i, top_w = _route(x, w["router"], w["router_bias"], model["num_experts_per_token"],
                          float(model["routed_scaling_factor"]), eps)
    top_i, top_w = np.asarray(top_i)[:real], np.asarray(top_w.astype(jnp.float32))[:real]
    y = jnp.zeros_like(x)
    for e, mats in w["experts"].items():
        hit = top_i == e
        rows = np.flatnonzero(hit.any(axis=1))
        if rows.size == 0:
            continue
        weight = np.where(hit, top_w, 0.0).sum(axis=1)[rows]
        for at in range(0, rows.size, ROW_PAD):  # ROW_PAD rows a call: one shape, one program
            block = np.zeros(ROW_PAD, np.int32)
            share = np.zeros(ROW_PAD, weight.dtype)  # padding adds 0 x expert(x[0]) at row 0
            block[: rows.size - at], share[: rows.size - at] = (
                rows[at : at + ROW_PAD], weight[at : at + ROW_PAD])
            y = _expert_rows(y, x, block, jnp.asarray(share, x.dtype), *mats, eps)
    return y + sum(_expert(x, *mats, eps) for mats in w["shared"])


def sequences_layer(model: dict, w: dict, kind: str, xs: list, ns: list):
    """One layer over whole sequences xs, each [Tp, h] from its start with
    ns[i] real tokens (the rest padding, so that every sequence is one
    shape): yields, in order, (x, what the layer leaves for a continuation)."""
    if kind == "kda":
        mixed = kda_sequences(model, w["mix"], xs, ns)
    else:
        mixed = (mla_sequence(model, w["mix"], x, n) for x, n in zip(xs, ns))
    for i, (mix, left) in enumerate(mixed):
        x, xs[i] = xs[i] + mix, None  # the caller's list holds the layer's input no longer
        yield x + ffn(model, w, x, ns[i]), left


def windows_layer(model: dict, w: dict, kind: str, xw, left, rows: int):
    """One layer over the windows xw [Nw, W, h] of ONE sequence as
    continuations of what its history left at this layer; every product
    takes their tokens `rows` at a time, a whole sequence's length."""
    nw, wl, h = xw.shape
    mixer = kda_windows if kind == "kda" else mla_windows
    xw = (xw + mixer(model, w["mix"], xw, *left, rows)).reshape(nw * wl, h)
    out = []
    for at in range(0, nw * wl, rows):
        real = min(rows, nw * wl - at)
        part = _pad_rows(xw[at : at + rows], rows)
        out.append((part + ffn(model, w, part, real))[:real])
    return jnp.concatenate(out).reshape(nw, wl, h)


@functools.partial(jax.jit, static_argnames=("eps",))
def _scores(x, head, targets, eps):
    logits = _rms(x, eps) @ head.astype(x.dtype)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=1)[:, 0], logits


def head_scores(x, head, targets, eps, keep: int = 0):
    """-log p(target) of tokens x [N, h], HEAD_ROWS at a time (one program),
    and the first `keep` tokens' logits."""
    targets = jnp.asarray(targets)
    sc, first = [], None
    for at in range(0, x.shape[0], HEAD_ROWS):
        a, logits = _scores(_pad_rows(x[at : at + HEAD_ROWS], HEAD_ROWS), head,
                            _pad_rows(targets[at : at + HEAD_ROWS], HEAD_ROWS), eps)
        sc.append(np.asarray(a))
        if first is None:
            first = logits[:keep]
    return np.concatenate(sc)[: x.shape[0]], first


# -- the programs, compiled side by side ---------------------------------------


def compile_ahead(model: dict, tp: int, wl: int, dtype, control: bool, log=None) -> dict:
    """Run every program above that holds a product of weights, the
    recurrences and the draws once at the shapes `score_sequences` will call
    them with (on zeros; the draws of one layer of each make, thrown away),
    each in a thread of its own: the compiles (a minute and a half one after
    another, float32 at `highest` on the chip) run side by side, and the run
    that follows finds them in jit's cache. Nothing here decides a number: a
    shape this list misses compiles where it is first called, and so does a
    piece whose thread fails here (logged, not raised: the run that follows
    meets whatever was wrong on its own, in the open).
    -> {function: programs it held when the threads were done}."""
    h, eps = model["hidden_size"], model["rms_norm_eps"]
    precision = jax.config.jax_default_matmul_precision
    kinds = layer_kinds(model)
    dense = model["first_k_dense_replace"]
    share = model["share"]
    parts = ("gate", "up", "down")

    def zero(*shape, dt=dtype):
        return jnp.zeros(shape, dt)

    def like(named: dict, *keys):  # zeros in the place of drawn tensors
        return [zero(*named[k][1], dt=jnp.bfloat16) for k in keys]

    def kda(li):
        named = layer_names(model, li, "kda")
        keys = [k for k in named if k.startswith("mix.")]
        dims = _kda_dims(model)
        heads, d = dims["heads"], dims["d"]
        w = dict(zip((k[4:] for k in keys), like(named, *keys)),
                 a_log=zero(heads, dt=jnp.bfloat16), dt_bias=zero(heads * d, dt=jnp.bfloat16))
        return [
            lambda: _kda_project(zero(tp, h), w, eps),
            lambda: _kda_out(zero(tp, heads, d), zero(tp, heads * d), w["wo"], eps),
            *(lambda b=b, t=t: _kda_recurrence(
                zero(b, heads, d, d), *[zero(b, t, heads, d)] * 4, zero(b, t, heads))
              for b, t in ((KDA_SEQS, tp), (S_BLOCK, wl))),
        ]

    def mla(li):
        named = layer_names(model, li, "mla")
        dims = _mla_dims(model)
        heads, dk, dv = dims["heads"], dims["nope"] + dims["rope"], model["v_head_dim"]
        i32 = jnp.int32
        return [
            lambda: _mla_qkv(zero(tp, h), *like(named, "mix.wq", "mix.wkva", "mix.wkvb"), **dims),
            lambda: _times(zero(tp, heads * dv), *like(named, "mix.wo")),
            *(lambda tq=tq, tk=tk: _attend(
                zero(tq, heads, dk), zero(tq, dt=i32), zero(tq, dt=i32), zero(tk, heads, dk),
                zero(tk, heads, dv), zero(tk, dt=i32), zero(tk, dt=i32))
              for tq, tk in ((Q_BLOCK, tp), (W_BLOCK * wl, tp + W_BLOCK * wl))),
        ]

    def ffn_of(li):
        if li < dense:
            named = ffn_names(model, "", model["intermediate_size"])
            return [lambda: _expert(zero(tp, h), *like(named, *parts), eps)]
        named = {**layer_names(model, li, kinds[li]),
                 **ffn_names(model, "", model["moe_intermediate_size"])}
        return [
            lambda: _route(zero(tp, h), *like(named, "router", "router_bias"),
                           model["num_experts_per_token"], float(model["routed_scaling_factor"]), eps),
            lambda: _expert(zero(tp, h), *like(named, *parts), eps),
            lambda: _expert_rows(zero(tp, h), zero(tp, h), np.zeros(ROW_PAD, np.int32), zero(ROW_PAD),
                                 *like(named, *parts), eps),
        ]

    vocab = share["vocab_rows_held"]
    calls = [
        lambda: _scores(zero(HEAD_ROWS, h), zero(h, vocab, dt=jnp.bfloat16),
                        zero(HEAD_ROWS, dt=jnp.int32), eps),
        lambda: tensors(model, {"emb": ("", (vocab, h)), "head": ("", (h, vocab))}, control),
        lambda: tensors(model, ffn_names(model, "", model["moe_intermediate_size"]), control),
    ]
    # one layer of each make: the first KDA and MLA layer, the last dense and first expert layer
    makes = {(kind, li < dense): li for li, kind in reversed(list(enumerate(kinds)))}
    for (kind, _), li in makes.items():
        calls.append(lambda kind=kind, li=li: tensors(model, layer_names(model, li, kind), control))
    if "kda" in kinds:
        lin = model["linear_attn_config"]
        calls.append(lambda: _decay(jax.random.PRNGKey(0), _crc(""), _crc(""), lin["num_heads"],
                                    lin["num_heads"] * lin["head_dim"]))
    for kind, build in (("kda", kda), ("mla", mla)):
        if kind in kinds:
            calls += build(kinds.index(kind))
    for li in {dense - 1, dense} & set(range(len(kinds))):
        calls += ffn_of(li)

    def run(call):
        try:
            with jax.default_matmul_precision(precision):
                jax.block_until_ready(call())
        except Exception as e:  # noqa: BLE001 — a head start lost, never a run
            return f"{type(e).__name__}: {e}"[:400]
        return None

    with ThreadPoolExecutor(min(len(calls), os.cpu_count() or 1)) as pool:
        lost = [e for e in pool.map(run, calls) if e]
    if log and lost:
        log(f"backbone_kda reference: {len(lost)} of {len(calls)} pieces failed ahead of the run "
            f"and compile where first called: {lost}")
    return programs_held()


def programs_held() -> dict:
    fns = (_draw, _decay, _kda_project, _kda_out, _kda_recurrence, _mla_qkv, _times, _attend,
           _expert, _route, _expert_rows, _scores)
    return {f.__name__: f._cache_size() for f in fns}


def score_sequences(model: dict, seqs: list, control: bool = False, log=None, check: bool = True):
    """seqs: [{"history" [n] float32, "windows" [Nw, w] float32}] -> per
    sequence the scores [Nw, w] float32 of its windows' points."""
    dtype = jnp.bfloat16 if control else jnp.float32
    share = model["share"]
    vocab, h = share["vocab_rows_held"], model["hidden_size"]
    eps = model["rms_norm_eps"]
    lengths = [len(s["history"]) - 1 for s in seqs]
    wl = seqs[0]["windows"].shape[1] if seqs else 0
    # every whole sequence padded to one length: one shape, one compile
    tp = -(-(max(lengths, default=0) + (wl if check and not control else 0)) // Q_BLOCK) * Q_BLOCK
    t = time.perf_counter()
    ahead = compile_ahead(model, tp, wl, dtype, control, log) if seqs else {}
    if log:
        log(f"backbone_kda reference: {sum(ahead.values())} programs compiled side by side in "
            f"{time.perf_counter() - t:.1f} s")
    both = tensors(model, {"emb": (f"embed.{share['index']}", (vocab, h)),
                           "head": (f"head.{share['index']}", (h, vocab))}, control)
    emb, head = both["emb"], both["head"]
    work = []
    for s in seqs:
        scale = series_scale(s["history"])
        hist = tokenize(s["history"], scale, vocab)
        wins = tokenize(s["windows"], scale, vocab)
        nw = wins.shape[0]
        # the program is fed [last history id; the window's ids but the last]
        fed = np.concatenate([np.full((nw, 1), hist[-1], np.int32), wins[:, :-1]], axis=1)
        # whole blocks of windows: the padding's scores are dropped
        fed = np.concatenate([fed, np.zeros((-nw % S_BLOCK, fed.shape[1]), np.int32)])
        work.append({
            "targets": wins, "fed": fed, "history_ids": hist[:-1], "n": len(hist) - 1,
            "xw": emb[jnp.asarray(fed)].astype(dtype),
        })
    whole = None
    if check and not control and seqs:
        # one (sequence, sweep): [history; window] in ONE forward
        first = work[0]
        tokens = np.concatenate([first["history_ids"], first["fed"][0]])
        whole = {"history_ids": tokens, "n": len(tokens)}
    runs = work + ([whole] if whole is not None else [])
    for s in runs:
        s["x"] = _pad_rows(emb[jnp.asarray(s["history_ids"])].astype(dtype), tp)
    for li, kind in enumerate(layer_kinds(model)):
        t = time.perf_counter()
        w = layer_weights(model, li, kind, control)
        inputs = [s.pop("x") for s in runs]
        layer = sequences_layer(model, w, kind, inputs, [s["n"] for s in runs])
        for s, (x, left) in zip(runs, layer):
            s["x"] = x
            if s is not whole:
                s["xw"] = windows_layer(model, w, kind, s["xw"], left, tp)
            del x, left
        jax.block_until_ready([s["xw"] for s in work])
        if log:
            log(f"backbone_kda reference layer {li + 1} ({kind}): {len(work)} sequences in "
                f"{time.perf_counter() - t:.1f} s")
        del w
    out = []
    for s in work:
        nw, wl = s["targets"].shape
        sc, logits = head_scores(s["xw"][:nw].reshape(nw * wl, -1), head,
                                 s["targets"].reshape(-1), eps, keep=wl)
        out.append(sc.reshape(nw, wl))
        if whole is not None and s is work[0]:
            n = whole["n"]
            _, ref = head_scores(whole["x"][n - wl : n], head, s["targets"][0], eps, keep=wl)
            gap = float(jnp.abs(ref - logits).max())
            if log:
                log("backbone_kda reference: continuation against one full forward, logits "
                    f"differ by {gap:.2e}")
            if not gap <= 1e-5:
                raise SystemExit(
                    "backbone_kda reference: a window run as the continuation of its history's "
                    f"state and latents differs from one full forward by {gap:.3e} (limit 1e-5)"
                )
    if log and ahead:
        late = {k: v - ahead[k] for k, v in programs_held().items() if v != ahead[k]}
        log(f"backbone_kda reference: programs compiled after that, where first called: {late or 0}")
    return out


def judge(rows: list, group: dict, cfg: dict, history, control: bool = False, log=None) -> dict:
    """-> {"flags" [K, W], "margins" [K, W], "scores" [K, F, W]} of this
    group's judgments (uid, sweep, the window sent [F, W])."""
    model = model_of(cfg)
    context = int(cfg["env"]["FOREMAST_BACKBONE_CONTEXT"])
    thr = float(cfg["anomaly_threshold"])
    f = len(group["aliases"])
    by_uid: dict = {}
    for i, r in enumerate(rows):
        by_uid.setdefault(r["uid"], []).append(i)
    seqs, owner = [], []
    for uid, idx in by_uid.items():
        hist = np.asarray(history(uid), np.float32)[:, -context:]
        for a in range(f):
            seqs.append({
                "history": hist[a],
                "windows": np.stack([np.asarray(rows[i]["sent"], np.float32)[a] for i in idx]),
            })
            owner.append((idx, a))
    if log:  # what the program left on the device is this run's to know, not to guess
        held = [(d.memory_stats() or {}).get("bytes_in_use") for d in jax.local_devices()]
        log(f"backbone_kda reference: the device holds {held} B before it starts")
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        got = score_sequences(model, seqs, control=control, log=log)
    w = rows[0]["sent"].shape[-1]
    scores = np.zeros((len(rows), f, w), np.float32)
    for (idx, a), sc in zip(owner, got):
        scores[idx, a] = sc
    if log:
        log(f"backbone_kda reference{' (control)' if control else ''}: {len(seqs)} sequences, "
            f"{len(rows)} judgments in {time.perf_counter() - t:.1f} s")
    top = scores.max(axis=1)
    return {"flags": top > thr, "margins": np.abs(top - thr).astype(np.float32), "scores": scores}
