"""The plain reference of fleet kind `backbone`: every alias of a document
is one sequence of a shared Cohere2-MoE model (config.json keys in the
model file the configuration's `env` names), and a timestamp is anomalous
where any alias's score -log p(id_t | history, id_<t) exceeds the
configuration's `anomaly_threshold` (nats).

As the model's config.json and the model file's `assumed` block describe
it: x' = LN(x) (no bias, eps `layer_norm_eps`), x <- x + Attn(x') + FFN(x');
grouped-query attention, query head j on key-value head j // (heads / kv
heads), scores q.k / sqrt(head_dim), softmax; `sliding_attention` layers
rotate q and k (`rope_gptj`: interleaved pairs, theta `rope_theta`) and see
keys with 0 <= p_i - p_j < `sliding_window`, `full_attention` layers carry
no positional embedding and see every earlier key; FFN = sum over the top-k
of sigmoid(x' W_r), weights normalised over the k, of the experts this share
holds + the mean of the shared experts, expert(x) = (silu(x W_g) * x W_u)
W_d; logits = LN(x) E^T `logit_scale` over the vocabulary rows held (E the
tied embedding). Tokeniser: scale = mean |history| (0 -> 1), id =
clip(floor((x / scale + 15) / 30 * V), 0, V - 1). Weights: N(0, 0.02^2) a
tensor from fold_in(PRNGKey(weights_seed), crc32(name)), rounded to
bfloat16.

Straightforward `jax.numpy`, float32 under `highest`; imports nothing of
`foremast_tpu`. No cache manager, no ring, no sorting: a loop over the
layers, and in each a loop over the sampled sequences. A sequence's whole
history (all but its last point) goes through the layer once; the K and V
it gave are kept as plain arrays, and each sweep's window, fed [last history
id; the window's ids but the last], runs as the continuation under explicit
masks. Weights are held in bfloat16 and widened a matrix at a time (a
layer in float32 would be 4.6 GB, four of them 18.9); attention runs in
blocks of queries, the experts over the tokens routed to them, so that it
fits. For one (sequence, sweep) a run the concatenated [history; window]
also goes through in ONE forward, and the two have to agree to 1e-5: the
reuse is checked, not assumed.

`control=True` is the same reference with weights rounded to
`float8_e4m3fn` and every product and sum in bfloat16: below the precision
the configuration states. The margin of a point is |max over aliases of
score - threshold| in nats.
"""

from __future__ import annotations

import functools
import json
import os
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOKEN_RANGE = 15.0
Q_BLOCK = 256  # queries a block of a history's attention
W_BLOCK = 8  # windows a block of the continuation's attention
ROW_PAD = 1024  # an expert's routed tokens are padded to a multiple of this
FAR = 1 << 30  # the position of a padded key: after every query


def model_of(cfg: dict) -> dict:
    with open(os.path.join(ROOT, cfg["env"]["FOREMAST_BACKBONE_MODEL"]), encoding="utf-8") as fh:
        return json.load(fh)


def tensor(model: dict, name: str, shape: tuple, control: bool):
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(model.get("weights_seed", 0))), np.uint32(zlib.crc32(name.encode()))
    )
    w = (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(jnp.bfloat16)
    if control:
        w = w.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    return w


def layer_weights(model: dict, li: int, control: bool) -> dict:
    h, w, d = model["hidden_size"], model["intermediate_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"] * d, model["num_key_value_heads"] * d
    share = model["share"]
    first = share["index"] * share["experts_held"]
    p = f"layers.{li}."

    def t(name, shape):
        return tensor(model, p + name, shape, control)

    def expert(prefix):
        return t(prefix + ".gate", (h, w)), t(prefix + ".up", (h, w)), t(prefix + ".down", (w, h))

    return {
        "wq": t("attn.q", (h, hq)), "wk": t("attn.k", (h, hkv)), "wv": t("attn.v", (h, hkv)),
        "wo": t("attn.o", (hq, h)), "router": t("router", (h, model["num_experts"])),
        "experts": {e: expert(f"experts.{e}") for e in range(first, first + share["experts_held"])},
        "shared": [expert(f"shared.{j}") for j in range(model["num_shared_experts"])],
    }


def series_scale(history: np.ndarray) -> np.ndarray:
    s = np.abs(np.asarray(history, np.float32)).mean(axis=-1, dtype=np.float32)
    return np.where(s > 0, s, np.float32(1.0)).astype(np.float32)


def tokenize(values, scale, vocab: int) -> np.ndarray:
    u = np.asarray(values, np.float32) / np.asarray(scale, np.float32)[..., None]
    ids = np.floor((u + np.float32(TOKEN_RANGE)) / np.float32(2 * TOKEN_RANGE) * np.float32(vocab))
    return np.clip(ids, 0, vocab - 1).astype(np.int32)


# -- the block, a piece at a time ---------------------------------------------


@functools.partial(jax.jit, static_argnames=("eps",))
def _ln(x, eps):
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + jnp.asarray(eps, x.dtype))


@jax.jit
def _times(x, w):
    return x @ w.astype(x.dtype)


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :].astype(x.dtype), jnp.sin(ang)[:, None, :].astype(x.dtype)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("hq", "hkv", "d", "theta"))
def _qkv(xn, wq, wk, wv, pos, hq, hkv, d, theta):
    """q [T, Hq, D], k, v [T, Hkv, D]; `theta` None: no positional embedding."""
    t = xn.shape[0]
    q = (xn @ wq.astype(xn.dtype)).reshape(t, hq, d)
    k = (xn @ wk.astype(xn.dtype)).reshape(t, hkv, d)
    v = (xn @ wv.astype(xn.dtype)).reshape(t, hkv, d)
    if theta is not None:
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    return q, k, v


@functools.partial(jax.jit, static_argnames=("window",))
def _attend(q, pos_q, seg_q, k, v, pos_k, seg_k, window):
    """Queries q [Tq, Hq, D] over keys k, v [Tk, Hkv, D] under the explicit
    mask: key j is seen by query i where 0 <= p_i - p_j (< window on a
    sliding layer) and the key is of the shared history (segment -1) or of
    the query's own window. -> [Tq, Hq * D]."""
    tq, hq, d = q.shape
    hkv = k.shape[1]
    gap = pos_q[:, None] - pos_k[None, :]
    seen = (gap >= 0) & ((seg_k[None, :] < 0) | (seg_k[None, :] == seg_q[:, None]))
    if window is not None:
        seen = seen & (gap < window)
    qg = q.reshape(tq, hkv, hq // hkv, d)
    s = jnp.einsum("tkgd,skd->kgts", qg, k) / jnp.sqrt(jnp.asarray(d, q.dtype))
    p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
    # a padded query sees nothing: its row is dropped by the caller
    p = jnp.where(seen.any(axis=-1)[None, None, :, None], p, 0)
    return jnp.einsum("kgts,skd->tkgd", p, v).reshape(tq, hq * d)


@functools.partial(jax.jit, static_argnames=("k",))
def _route(xn, router, k):
    s = jax.nn.sigmoid(xn @ router.astype(xn.dtype))
    top_s, top_i = jax.lax.top_k(s, k)
    return top_i, top_s / top_s.sum(axis=-1, keepdims=True)


@jax.jit
def _expert(x, gate, up, down):
    dt = x.dtype
    return (jax.nn.silu(x @ gate.astype(dt)) * (x @ up.astype(dt))) @ down.astype(dt)


@jax.jit
def _expert_rows(y, xn, rows, weight, gate, up, down):
    """y with weight * expert(xn[rows]) added at `rows`."""
    return y.at[rows].add(weight[:, None] * _expert(xn[rows], gate, up, down))


def ffn(model: dict, w: dict, xn, real: int):
    """sum over the held experts of (router weight) x expert, each expert
    over the tokens routed to it, + the mean of the shared experts. Rows
    past `real` are padding and are routed nowhere."""
    top_i, top_w = _route(xn, w["router"], model["num_experts_per_tok"])
    top_i, top_w = np.asarray(top_i)[:real], np.asarray(top_w.astype(jnp.float32))[:real]
    y = jnp.zeros_like(xn)
    for e, mats in w["experts"].items():
        hit = top_i == e
        rows = np.flatnonzero(hit.any(axis=1))
        if rows.size == 0:
            continue
        weight = np.where(hit, top_w, 0.0).sum(axis=1)[rows]
        pad = -rows.size % ROW_PAD
        rows = np.concatenate([rows, np.zeros(pad, rows.dtype)])
        weight = np.concatenate([weight, np.zeros(pad, weight.dtype)])
        y = _expert_rows(y, xn, jnp.asarray(rows), jnp.asarray(weight, xn.dtype), *mats)
    shared = sum(_expert(xn, *mats) for mats in w["shared"])
    return y + shared / jnp.asarray(len(w["shared"]), xn.dtype)


def _pad_rows(a, to: int, value=0):
    pad = to - a.shape[0]
    return a if pad == 0 else jnp.concatenate([a, jnp.full((pad, *a.shape[1:]), value, a.dtype)])


def sequence_layer(model: dict, w: dict, kind: str, x, n: int):
    """One layer over one whole sequence x [Tp, h] of n real tokens at
    positions 0 .. n - 1 (rows past n are padding) -> (x, K, V)."""
    sliding = kind == "sliding_attention"
    tp = x.shape[0]
    pos = jnp.where(jnp.arange(tp) < n, jnp.arange(tp), FAR).astype(jnp.int32)
    seg = jnp.full(tp, -1, jnp.int32)
    xn = _ln(x, model["layer_norm_eps"])
    q, k, v = _qkv(
        xn, w["wq"], w["wk"], w["wv"], pos, model["num_attention_heads"],
        model["num_key_value_heads"], model["head_dim"],
        float(model["rope_theta"]) if sliding else None,
    )
    window = int(model["sliding_window"]) if sliding else None
    # a sliding layer's block of queries sees no key further back than the
    # window: its keys are a fixed-size slice that ends with the block
    reach = min(tp, window + Q_BLOCK) if sliding else tp
    out = []
    for at in range(0, tp, Q_BLOCK):
        lo = min(max(at + Q_BLOCK - reach, 0), tp - reach)
        out.append(_attend(
            q[at : at + Q_BLOCK], pos[at : at + Q_BLOCK], seg[at : at + Q_BLOCK],
            k[lo : lo + reach], v[lo : lo + reach], pos[lo : lo + reach], seg[lo : lo + reach],
            window,
        ))
    att = _times(jnp.concatenate(out), w["wo"])
    return x + att + ffn(model, w, xn, n), k, v


def windows_layer(model: dict, w: dict, kind: str, xw, k_hist, v_hist, n: int, real: int):
    """One layer over the windows xw [Nw, W, h] of ONE sequence as
    continuations of its history, whose keys and values at this layer are
    k_hist, v_hist [Tp, Hkv, D] (n real positions): window tokens sit at
    positions n .. n + W - 1 and see the history and their own window's
    past. Windows past `real` are padding."""
    sliding = kind == "sliding_attention"
    nw, wl, h = xw.shape
    tp = k_hist.shape[0]
    pos_h = jnp.where(jnp.arange(tp) < n, jnp.arange(tp), FAR).astype(jnp.int32)
    seg_h = jnp.full(tp, -1, jnp.int32)
    pos_w = jnp.tile(n + jnp.arange(wl, dtype=jnp.int32), nw)
    seg_w = jnp.repeat(jnp.arange(nw, dtype=jnp.int32), wl)
    xn = _ln(xw.reshape(nw * wl, h), model["layer_norm_eps"])
    q, k, v = _qkv(
        xn, w["wq"], w["wk"], w["wv"], pos_w, model["num_attention_heads"],
        model["num_key_value_heads"], model["head_dim"],
        float(model["rope_theta"]) if sliding else None,
    )
    window = int(model["sliding_window"]) if sliding else None
    if sliding:
        # no window token sees further back than the window: the history's
        # keys from there on are all a sliding layer needs
        back = max(0, n - window)
        k_hist, v_hist, pos_h, seg_h = k_hist[back:], v_hist[back:], pos_h[back:], seg_h[back:]
    out = []
    step = W_BLOCK * wl
    for at in range(0, nw * wl, step):
        sl = slice(at, at + step)
        out.append(_attend(
            q[sl], pos_w[sl], seg_w[sl],
            jnp.concatenate([k_hist, k[sl]]), jnp.concatenate([v_hist, v[sl]]),
            jnp.concatenate([pos_h, pos_w[sl]]), jnp.concatenate([seg_h, seg_w[sl]]), window,
        ))
    att = _times(jnp.concatenate(out), w["wo"])
    return (xw.reshape(nw * wl, h) + att + ffn(model, w, xn, real * wl)).reshape(nw, wl, h)


@functools.partial(jax.jit, static_argnames=("eps", "scale"))
def _scores(x, emb, targets, eps, scale):
    logits = (_ln(x, eps) @ emb.astype(x.dtype).T) * jnp.asarray(scale, x.dtype)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=1)[:, 0], logits


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def score_sequences(model: dict, seqs: list, control: bool = False, log=None, check: bool = True):
    """seqs: [{"history" [n] float32, "windows" [Nw, w] float32}] -> per
    sequence the scores [Nw, w] float32 of its windows' points."""
    dtype = jnp.bfloat16 if control else jnp.float32
    vocab = model["share"]["vocab_rows_held"]
    emb = tensor(model, f"embed.{model['share']['index']}", (vocab, model["hidden_size"]), control)
    kinds = model["layer_types"][: model["share"]["layers_held"]]
    eps, logit_scale = model["layer_norm_eps"], float(model["logit_scale"])
    work = []
    for s in seqs:
        scale = series_scale(s["history"])
        hist = tokenize(s["history"], scale, vocab)
        wins = tokenize(s["windows"], scale, vocab)
        n, (nw, wl) = len(hist) - 1, wins.shape
        # the program is fed [last history id; the window's ids but the last]
        fed = np.concatenate([np.full((nw, 1), hist[-1], np.int32), wins[:, :-1]], axis=1)
        nwp = _round_up(nw, W_BLOCK)
        fed = np.concatenate([fed, np.zeros((nwp - nw, wl), np.int32)])
        work.append({
            "n": n, "nw": nw, "targets": wins, "fed": fed, "history_ids": hist[:-1],
            "x": _pad_rows(emb[jnp.asarray(hist[:-1])].astype(dtype), _round_up(n + wl, Q_BLOCK)),
            "xw": emb[jnp.asarray(fed)].astype(dtype),
        })
    whole = None
    if check and not control and seqs:
        # one (sequence, sweep): [history; window] in ONE forward
        first = work[0]
        tokens = np.concatenate([first["history_ids"], first["fed"][0]])
        whole = {"n": len(tokens), "x": _pad_rows(emb[jnp.asarray(tokens)].astype(dtype),
                                                  first["x"].shape[0])}
    for li, kind in enumerate(kinds):
        t = time.perf_counter()
        w = layer_weights(model, li, control)
        for s in work:
            s["x"], k, v = sequence_layer(model, w, kind, s["x"], s["n"])
            s["xw"] = windows_layer(model, w, kind, s["xw"], k, v, s["n"], s["nw"])
        if whole is not None:
            whole["x"], _, _ = sequence_layer(model, w, kind, whole["x"], whole["n"])
        jax.block_until_ready([s["xw"] for s in work])
        if log:
            log(f"backbone reference layer {li} ({kind}): {len(work)} sequences in "
                f"{time.perf_counter() - t:.1f} s")
        del w
    out = []
    for s in work:
        nw, wl = s["targets"].shape
        x = s["xw"][:nw].reshape(nw * wl, -1)
        sc, logits = [], []
        for at in range(0, nw * wl, 2048):
            a, b = _scores(x[at : at + 2048], emb, jnp.asarray(s["targets"].reshape(-1)[at : at + 2048]),
                           eps, logit_scale)
            sc.append(np.asarray(a))
            logits.append(b)
        out.append(np.concatenate(sc).reshape(nw, wl))
        if whole is not None and s is work[0]:
            n, wl = whole["n"], s["targets"].shape[1]
            _, ref = _scores(whole["x"][n - wl : n], emb, jnp.asarray(s["targets"][0]), eps, logit_scale)
            gap = float(jnp.abs(ref - logits[0][:wl]).max())
            if log:
                log(f"backbone reference: continuation against one full forward, logits differ by {gap:.2e}")
            if not gap <= 1e-5:
                raise SystemExit(
                    f"backbone reference: a window run as the continuation of its cached history "
                    f"differs from one full forward by {gap:.3e} (limit 1e-5)"
                )
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
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        got = score_sequences(model, seqs, control=control, log=log)
    w = rows[0]["sent"].shape[-1]
    scores = np.zeros((len(rows), f, w), np.float32)
    for (idx, a), sc in zip(owner, got):
        scores[idx, a] = sc
    if log:
        log(f"backbone reference{' (control)' if control else ''}: {len(seqs)} sequences, "
            f"{len(rows)} judgments in {time.perf_counter() - t:.1f} s")
    top = scores.max(axis=1)
    return {"flags": top > thr, "margins": np.abs(top - thr).astype(np.float32), "scores": scores}
