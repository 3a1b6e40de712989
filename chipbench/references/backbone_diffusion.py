"""The plain reference of fleet kind `backbone_diffusion`: every alias of a
document is one sequence of a shared SDAR-MoE model (config.json keys in the
model file the configuration's `env` names), scored by block diffusion, and
a timestamp is anomalous where any alias's score exceeds the configuration's
`anomaly_threshold` (nats).

As the model's config.json and the model file's `assumed` block describe it
(RMSNorm(x) = x / sqrt(mean x^2 + eps), eps `rms_norm_eps`, gains 1):

    h = RMSNorm(x); q, k, v = h W_q, h W_k, h W_v; q, k <- RoPE(RMSNorm_head(q)), RoPE(RMSNorm_head(k))
    x <- x + Attn(q, k, v) W_o;  x <- x + sum over the top-k of softmax(RMSNorm(x) W_r), renormalised,
    of the held experts' (silu(h W_g) * h W_u) W_d;  logits = RMSNorm(x) W_head

RoPE in rotate-half form at `rope_theta`; grouped-query attention, query head
j on key-value head j // (heads / kv heads), scores q.k / sqrt(head_dim). The
block mask: positions fall in blocks of `block_length` B; a clean token sees
the clean tokens of its own block and of earlier ones; a block being
denoised sees the clean tokens of earlier blocks and, both ways, itself.

The rule. The history's newest whole blocks are the clean prefix. Block b of
the window is denoised in B steps: at step s its input holds the observed
ids at its positions < s and the mask id (the last held vocabulary row) at
the rest, and score(b, s) = -log softmax(logits at position s)[observed id].
Then the block's observed tokens are run clean, and later blocks see them.
Tokeniser: scale = mean |history| (0 -> 1), id = clip(floor((x / scale +
15) / 30 * (V - 1)), 0, V - 2) over the V vocabulary rows held. Weights:
N(0, 0.02^2) a tensor from fold_in(PRNGKey(weights_seed), crc32(name)),
rounded to bfloat16.

Straightforward `jax.numpy`, float32 under `highest`; imports nothing of
`foremast_tpu`. No cache manager, no copies side by side, no sorting: the
history of each sampled sequence goes through the layers once, its keys and
values kept as plain arrays; then the windows of every sampled (sequence,
sweep) go through block by block and step by step, as the rule is written:
each step one forward of every window's noisy block through all the layers
against the history's keys and values and the window's clean blocks before
it, then one forward of the block's clean tokens, whose keys and values are
kept. For one (sequence, sweep) a run the last scored point is also scored
by ONE forward over [history; the window's clean blocks before its block;
its noisy block], and the two have to agree to 1e-5 in a logit: the reuse is
checked, not assumed.

What it costs to start: a float32 product at `highest` takes the chip's
compiler seconds, so every product takes rows of tokens `ROWS` at a time,
history and windows alike, and the experts a block of 32 at a time: one
program a piece, each compiled where it is first called, and a few
thousand dispatches a run (a dispatch an expert, tens of thousands, did not
end inside a traced run's time on the chip).

`control=True` is the same reference with weights rounded to
`float8_e4m3fn` and every product and sum in bfloat16: below the precision
the configuration states. The rounding is float32 arithmetic onto that
format's grid (`to_float8`: 3 bits below the leading one, steps of 2^-9
below 2^-6, ties to even), the same on every backend and equal, value for
value, to a cast where the backend does cast. The margin of a point is
|max over aliases of score - threshold| in nats.

Beside compare.py's flags, the scores themselves: `judge` keeps what it
scored (`scored`, {(uid, sweep): [F, W]}, the last judgment that was not
the control's), and `score_numbers` holds the program's scores of the same
judgments against them point by point (`score_gap.backbone_diffusion`, the
median |program - reference| in nats, and `score_gap_max.`, the largest),
each under the configuration's `correct_limits`.
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

from chipbench.compare import BROKEN

KIND = "backbone_diffusion"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOKEN_RANGE = 15.0
ROWS = 2048  # tokens a block of every row-wise piece
Q_BLOCK = 512  # queries a block of a history's attention
W_BLOCK = 128  # windows a block of the windows' attention; a sequence's windows are padded to whole ones
E_BLOCK = 32  # experts a call of the expert layer
HEAD_ROWS = 2048  # tokens a block of the head


def model_of(cfg: dict) -> dict:
    with open(os.path.join(ROOT, cfg["env"]["FOREMAST_BACKBONE_MODEL"]), encoding="utf-8") as fh:
        return json.load(fh)


# -- weights -------------------------------------------------------------------


def to_float8(w):
    """w rounded to the nearest `float8_e4m3fn` value (ties to even, held in
    w's dtype) by float32 arithmetic: |w| = m 2^e, m in [0.5, 1), keeps 3
    bits below its leading one, and below 2^-6 the grid is the subnormals'
    2^-9."""
    x = w.astype(jnp.float32)
    _, e = jnp.frexp(x)
    step = jnp.ldexp(jnp.float32(1), jnp.maximum(e - 1, -6) - 3)
    return jnp.clip(jnp.round(x / step) * step, -448.0, 448.0).astype(w.dtype)


@functools.partial(jax.jit, static_argnames=("shapes", "control"))
def _draw(root, crcs, shapes, control):
    """Tensor i: N(0, 0.02^2) of shapes[i] from fold_in(root, crcs[i]),
    rounded to bfloat16 (and, `control`, to float8_e4m3fn's grid)."""

    def one(crc, shape):
        w = 0.02 * jax.random.normal(jax.random.fold_in(root, crc), shape, jnp.float32)
        w = w.astype(jnp.bfloat16)
        return to_float8(w) if control else w

    return [one(crcs[i], s) for i, s in enumerate(shapes)]


@functools.partial(jax.jit, static_argnames=("shape", "control"))
def _draw_stacked(root, crcs, shape, control):
    """Tensor i of one shape for each crc: the same draws as `_draw`'s, stacked."""
    return jax.vmap(lambda crc: _draw(root, crc[None], (shape,), control)[0])(crcs)


def tensors(model: dict, named: dict, control: bool) -> dict:
    """{key: tensor} for named = {key: (tensor name, shape)}, held in bfloat16."""
    root = jax.random.PRNGKey(int(model.get("weights_seed", 0)))
    crcs = np.asarray([np.uint32(zlib.crc32(n.encode())) for n, _ in named.values()], np.uint32)
    return dict(zip(named, _draw(root, crcs, tuple(s for _, s in named.values()), control)))


def _attn_names(model: dict, li: int) -> dict:
    h, d = model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"] * d, model["num_key_value_heads"] * d
    p = f"layers.{li}."
    return {"wq": (p + "attn.q", (h, hq)), "wk": (p + "attn.k", (h, hkv)),
            "wv": (p + "attn.v", (h, hkv)), "wo": (p + "attn.o", (hq, h)),
            "router": (p + "router", (h, model["num_experts"]))}


def _expert_names(model: dict, li: int, e: int) -> dict:
    h, w = model["hidden_size"], model["moe_intermediate_size"]
    p = f"layers.{li}.experts.{e}."
    return {"gate": (p + "gate", (h, w)), "up": (p + "up", (h, w)), "down": (p + "down", (w, h))}


def layer_weights(model: dict, li: int, control: bool) -> dict:
    """One layer's attention and router, and the experts this share holds
    {e: (gate, up, down)}."""
    share = model["share"]
    first = share["index"] * share["experts_held"]
    w = tensors(model, _attn_names(model, li), control)
    root = jax.random.PRNGKey(int(model.get("weights_seed", 0)))
    held = range(first, first + share["experts_held"])
    for part in ("gate", "up", "down"):
        named = [_expert_names(model, li, e)[part] for e in held]
        crcs = np.asarray([np.uint32(zlib.crc32(n.encode())) for n, _ in named], np.uint32)
        w[part] = _draw_stacked(root, crcs, named[0][1], control)  # [held experts, ...]
    return w


def series_scale(history: np.ndarray) -> np.ndarray:
    s = np.abs(np.asarray(history, np.float32)).mean(axis=-1, dtype=np.float32)
    return np.where(s > 0, s, np.float32(1.0)).astype(np.float32)


def tokenize(values, scale, vocab: int) -> np.ndarray:
    """Onto the vocab - 1 ids below the mask id vocab - 1."""
    u = np.asarray(values, np.float32) / np.asarray(scale, np.float32)[..., None]
    ids = np.floor((u + np.float32(TOKEN_RANGE)) / np.float32(2 * TOKEN_RANGE) * np.float32(vocab - 1))
    return np.clip(ids, 0, vocab - 2).astype(np.int32)


# -- the pieces ----------------------------------------------------------------


def _rms(x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + jnp.asarray(eps, x.dtype))


def _rope(x, pos, theta):
    """x [R, H, D] at positions pos [R], rotate-half."""
    d = x.shape[-1]
    ang = pos[:, None].astype(jnp.float32) * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    cos, sin = jnp.cos(ang)[:, None, :].astype(x.dtype), jnp.sin(ang)[:, None, :].astype(x.dtype)
    x0, x1 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("hq", "hkv", "d", "eps", "theta"))
def _qkv(x, pos, wq, wk, wv, hq, hkv, d, eps, theta):
    """q [R, Hq, D], k, v [R, Hkv, D] of tokens x [R, h] at positions pos."""
    xn = _rms(x, eps)
    dt = xn.dtype
    r = xn.shape[0]
    q = _rope(_rms((xn @ wq.astype(dt)).reshape(r, hq, d), eps), pos, theta)
    k = _rope(_rms((xn @ wk.astype(dt)).reshape(r, hkv, d), eps), pos, theta)
    return q, k, (xn @ wv.astype(dt)).reshape(r, hkv, d)


@jax.jit
def _times(x, w):
    return x @ w.astype(x.dtype)


def _softmax_out(q, keys, values, seen):
    """Queries q [..., T, Hkv, G, D] over key sets keys[i] [..., K_i, Hkv, D]
    (a leading axis of 1 is shared), seen[i] [..., T, K_i] -> [..., T, Hkv *
    G * D] under ONE softmax over all the sets."""
    d = q.shape[-1]
    scale = jnp.asarray(d ** -0.5, q.dtype)
    scores = [jnp.where(m[..., :, None, None, :], jnp.einsum("...thgd,...khd->...thgk", q, k) * scale,
                        -jnp.inf) for k, m in zip(keys, seen)]
    p = jax.nn.softmax(jnp.concatenate(scores, axis=-1), axis=-1)
    out, at = 0.0, 0
    for k, v in zip(keys, values):
        out = out + jnp.einsum("...thgk,...khd->...thgd", p[..., at : at + k.shape[-3]], v)
        at += k.shape[-3]
    return out.reshape(*out.shape[:-3], -1)


@functools.partial(jax.jit, static_argnames=("block",))
def _attend_history(q, pos_q, k, v, pos_k, live_k, block):
    """A block of a whole sequence's queries q [Tq, Hq, D] over all its keys
    [Tk, Hkv, D] under the block mask (a key sees queries of its block and
    of later ones; `live_k`: the key is a real token). -> [Tq, Hq * D]."""
    tq, hq, d = q.shape
    hkv = k.shape[1]
    seen = (pos_k[None, :] // block <= pos_q[:, None] // block) & live_k[None, :]
    return _softmax_out(q.reshape(tq, hkv, hq // hkv, d), [k], [v], [seen])


@jax.jit
def _attend_windows(q, kh, vh, live_h, kc, vc, live_c, ko, vo):
    """A block of windows' B tokens each: queries q [N, B, Hq, D] over the
    history's keys kh, vh [Tk, Hkv, D] (`live_h`), each window's clean
    blocks so far kc, vc [N, P, Hkv, D] (`live_c` [P]) and its own block's
    ko, vo [N, B, Hkv, D], seen both ways. -> [N, B, Hq * D]."""
    n, b, hq, d = q.shape
    hkv = kh.shape[1]
    seen = [jnp.broadcast_to(live_h, (n, b, live_h.shape[0])),
            jnp.broadcast_to(live_c, (n, b, live_c.shape[0])), jnp.ones((n, b, b), bool)]
    return _softmax_out(q.reshape(n, b, hkv, hq // hkv, d), [kh[None], kc, ko], [vh[None], vc, vo],
                        seen)


@functools.partial(jax.jit, static_argnames=("k", "eps", "renorm"))
def _route(x, router, k, eps, renorm):
    xn = _rms(x, eps)
    p = jax.nn.softmax(xn @ router.astype(xn.dtype), axis=-1)
    top_p, top_i = jax.lax.top_k(p, k)
    return top_i, (top_p / top_p.sum(axis=-1, keepdims=True) if renorm else top_p)


@functools.partial(jax.jit, static_argnames=("eps",))
def _experts(y, x, rows, weight, gate, up, down, eps):
    """y with weight[e, c] * expert_e(RMSNorm(x[rows[e, c]])) added at rows[e, c] for a
    block of experts (gate, up [E, h, w], down [E, w, h]) over the rows routed to each
    (rows [E, C], padded with row 0 at weight 0)."""
    xn = _rms(x[rows], eps)  # [E, C, h]
    dt = xn.dtype
    mid = jax.nn.silu(jnp.einsum("ech,ehw->ecw", xn, gate.astype(dt))) * jnp.einsum(
        "ech,ehw->ecw", xn, up.astype(dt))
    out = jnp.einsum("ecw,ewh->ech", mid, down.astype(dt)) * weight[..., None]
    return y.at[rows.reshape(-1)].add(out.reshape(-1, out.shape[-1]))


@functools.partial(jax.jit, static_argnames=("eps",))
def _scores(x, head, targets, eps):
    logits = _rms(x, eps) @ head.astype(x.dtype)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[:, None], axis=1)[:, 0], logits


def _pad_rows(a, to: int):
    pad = to - a.shape[0]
    return a if pad == 0 else jnp.concatenate([a, jnp.zeros((pad, *a.shape[1:]), a.dtype)])


def _in_rows(fn, x, rows: int = ROWS):
    """fn over the rows of x [N, ...] (or of each array of a tuple), `rows`
    of them at a time, the last block padded: one program a piece."""
    n = jax.tree.leaves(x)[0].shape[0]
    parts = [fn(jax.tree.map(lambda a: _pad_rows(a[at : at + rows], rows), x))
             for at in range(0, n, rows)]
    return jax.tree.map(lambda *a: jnp.concatenate(a)[:n], *parts)


def _dims(model: dict) -> dict:
    return dict(hq=model["num_attention_heads"], hkv=model["num_key_value_heads"],
                d=model["head_dim"], eps=model["rms_norm_eps"], theta=float(model["rope_theta"]))


def qkv(model: dict, w: dict, x, pos):
    return _in_rows(lambda a: _qkv(a[0], a[1], w["wq"], w["wk"], w["wv"], **_dims(model)), (x, pos))


def ffn(model: dict, w: dict, x, real: int):
    """The held experts' weighted outputs for the (un-normed) tokens x [R,
    h], each expert over the tokens routed to it, E_BLOCK experts a call
    (each expert's rows padded to the block's largest count, rounded up to
    a power of two); rows past `real` are padding and are routed nowhere."""
    eps = model["rms_norm_eps"]
    top_i, top_w = _in_rows(lambda a: _route(a, w["router"], model["num_experts_per_tok"], eps,
                                             bool(model.get("norm_topk_prob", True))), x)
    top_i, top_w = np.asarray(top_i)[:real], np.asarray(top_w.astype(jnp.float32))[:real]
    share = model["share"]
    first = share["index"] * share["experts_held"]
    y = jnp.zeros_like(x)
    for at in range(0, share["experts_held"], E_BLOCK):
        hit = [top_i == first + e for e in range(at, min(at + E_BLOCK, share["experts_held"]))]
        picked = [np.flatnonzero(m.any(axis=1)) for m in hit]
        c = 256
        while c < max(len(p) for p in picked):
            c *= 2
        rows = np.zeros((len(hit), c), np.int32)
        weight = np.zeros((len(hit), c), np.float32)
        for e, (m, p) in enumerate(zip(hit, picked)):
            rows[e, : len(p)] = p
            weight[e, : len(p)] = np.where(m, top_w, 0.0).sum(axis=1)[p]
        part = slice(at, at + E_BLOCK)
        y = _experts(y, x, rows, jnp.asarray(weight, x.dtype), w["gate"][part], w["up"][part],
                     w["down"][part], eps)
    return y


def history_layer(model: dict, w: dict, x, n: int):
    """One layer over one whole sequence x [Tp, h] of n real clean tokens at
    positions 0 .. n - 1 under the block mask -> (x, K, V)."""
    tp = x.shape[0]
    pos = jnp.arange(tp, dtype=jnp.int32)
    live = pos < n
    q, k, v = qkv(model, w, x, pos)
    out = [_attend_history(q[at : at + Q_BLOCK], pos[at : at + Q_BLOCK], k, v, pos, live,
                           model["block_length"]) for at in range(0, tp, Q_BLOCK)]
    x = x + _in_rows(lambda a: _times(a, w["wo"]), jnp.concatenate(out))
    return x + ffn(model, w, x, n), k, v


def block_forward(model: dict, layers: list, work: list, tokens, b: int, keep: bool):
    """Every window's block b of every sequence, tokens [S, Nw, B] (noisy or
    clean), through all the layers against its sequence's history keys and
    values and the window's clean blocks before b -> the final hidden [S,
    Nw, B, h]; `keep`: the block's keys and values at each layer join the
    windows' clean blocks. The products take every sequence's rows
    together; the attention goes a sequence and a block of windows at a time."""
    ns, nw, bl = tokens.shape
    h = model["hidden_size"]
    pos = jnp.asarray(np.concatenate(
        [np.tile(seq["n"] + b * bl + np.arange(bl, dtype=np.int32), nw) for seq in work]))
    x = work[0]["emb"][jnp.asarray(tokens.reshape(-1))].astype(work[0]["dtype"])
    live_c = jnp.arange(work[0]["clean_k"][0].shape[1]) < b * bl
    for li, w in enumerate(layers):
        q, k, v = (a.reshape(ns, nw, bl, *a.shape[1:]) for a in qkv(model, w, x, pos))
        out = []
        for i, seq in enumerate(work):
            kh, vh = seq["hist"][li]
            live_h = jnp.arange(kh.shape[0]) < seq["n"]
            kc, vc = seq["clean_k"][li], seq["clean_v"][li]
            out += [_attend_windows(q[i, at : at + W_BLOCK], kh, vh, live_h, kc[at : at + W_BLOCK],
                                    vc[at : at + W_BLOCK], live_c, k[i, at : at + W_BLOCK],
                                    v[i, at : at + W_BLOCK]) for at in range(0, nw, W_BLOCK)]
            if keep:
                seq["clean_k"][li] = kc.at[:, b * bl : (b + 1) * bl].set(k[i])
                seq["clean_v"][li] = vc.at[:, b * bl : (b + 1) * bl].set(v[i])
        x = x + _in_rows(lambda a: _times(a, w["wo"]), jnp.concatenate(out).reshape(ns * nw * bl, -1))
        x = x + ffn(model, w, x, ns * nw * bl)
    return x.reshape(ns, nw, bl, h)


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


def score_sequences(model: dict, seqs: list, control: bool = False, log=None, check: bool = True):
    """seqs: [{"history" [n] float32, "windows" [Nw, w] float32}] -> per
    sequence the scores [Nw, w] float32 of its windows' points."""
    dtype = jnp.bfloat16 if control else jnp.float32
    share = model["share"]
    vocab, h, eps = share["vocab_rows_held"], model["hidden_size"], model["rms_norm_eps"]
    bl = model["block_length"]
    wl = seqs[0]["windows"].shape[1] if seqs else 0
    nb = -(-wl // bl)
    hists = []
    for s in seqs:
        scale = series_scale(s["history"])
        ids = tokenize(s["history"], scale, vocab)
        hists.append((ids[len(ids) % bl :], tokenize(s["windows"], scale, vocab)))
    # every whole sequence padded to one length, room for a window's blocks
    # included (the reuse check's one forward): one shape, one compile
    longest = max((len(ids) for ids, _ in hists), default=0)
    tp = -(-(longest + nb * bl) // Q_BLOCK) * Q_BLOCK
    both = tensors(model, {"emb": (f"embed.{share['index']}", (vocab, h)),
                           "head": (f"head.{share['index']}", (h, vocab))}, control)
    t = time.perf_counter()
    layers = [layer_weights(model, li, control) for li in range(share["layers_held"])]
    if log:
        log(f"backbone_diffusion reference: weights drawn in {time.perf_counter() - t:.1f} s")
    work = []
    most = max((wins.shape[0] for _, wins in hists), default=0)
    nwp = -(-most // W_BLOCK) * W_BLOCK  # whole blocks of windows: the padding's scores are dropped
    for ids, wins in hists:
        nw = wins.shape[0]
        targets = np.zeros((nwp, nb * bl), np.int32)
        targets[:nw, :wl] = wins
        work.append({"ids": ids, "n": len(ids), "targets": targets, "nw": nw, "tp": tp,
                     "dtype": dtype, "emb": both["emb"]})
    whole = None
    if check and not control and seqs:
        # the first sequence's first window, its last point: ONE forward over
        # [history; its clean blocks before; its noisy block at that step]
        first = work[0]
        b, s = divmod(wl - 1, bl)
        noisy = np.full(bl, vocab - 1, np.int32)
        noisy[:s] = first["targets"][0, b * bl : b * bl + s]
        tokens = np.concatenate([first["ids"], first["targets"][0, : b * bl], noisy])
        whole = {"ids": tokens, "n": len(tokens), "at": len(tokens) - bl + s}
    t = time.perf_counter()
    for seq in work + ([whole] if whole is not None else []):
        x = _pad_rows(both["emb"][jnp.asarray(seq["ids"])].astype(dtype), tp)
        seq["hist"] = []
        for w in layers:
            x, k, v = history_layer(model, w, x, seq["n"])
            seq["hist"].append((k, v))
        seq["x"] = x
    jax.block_until_ready([s["x"] for s in work])
    if log:
        log(f"backbone_diffusion reference: {len(work)} histories of {tp} positions in "
            f"{time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    hkv, d = model["num_key_value_heads"], model["head_dim"]
    targets = np.stack([seq["targets"] for seq in work]) if work else None  # [S, Nw, P]
    scores = np.zeros(targets.shape if work else (0,), np.float32)
    for seq in work:
        nwp = seq["targets"].shape[0]
        seq["clean_k"] = [jnp.zeros((nwp, nb * bl, hkv, d), dtype) for _ in layers]
        seq["clean_v"] = [jnp.zeros((nwp, nb * bl, hkv, d), dtype) for _ in layers]
    for b in range(nb if work else 0):
        block = targets[:, :, b * bl : (b + 1) * bl]
        for s in range(min(bl, wl - b * bl)):
            noisy = np.where(np.arange(bl) < s, block, vocab - 1).astype(np.int32)
            x = block_forward(model, layers, work, noisy, b, keep=False)[:, :, s]
            sc, logits = head_scores(x.reshape(-1, h), both["head"], block[:, :, s].reshape(-1),
                                     eps, keep=1)
            scores[:, :, b * bl + s] = sc.reshape(scores.shape[:2])
            if b * bl + s == wl - 1:
                last_logits = logits
        if (b + 1) * bl < wl:
            block_forward(model, layers, work, block, b, keep=True)
    for seq in work:
        del seq["clean_k"], seq["clean_v"], seq["hist"], seq["x"]
    if log:
        log(f"backbone_diffusion reference: {len(work)} sequences' windows block by block in "
            f"{time.perf_counter() - t:.1f} s")
    if whole is not None:
        _, ref = head_scores(whole["x"][whole["at"] : whole["at"] + 1], both["head"],
                             work[0]["targets"][:1, wl - 1], eps, keep=1)
        gap = float(jnp.abs(ref - last_logits).max())
        if log:
            log(f"backbone_diffusion reference: block by block against one full forward, logits "
                f"differ by {gap:.2e}")
        if not gap <= 1e-5:
            raise SystemExit(
                "backbone_diffusion reference: a window scored as the continuation of its cached "
                f"history differs from one full forward by {gap:.3e} (limit 1e-5)"
            )
    return [scores[i, : seq["nw"], :wl] for i, seq in enumerate(work)]


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
        log(f"backbone_diffusion reference: the device holds {held} B before it starts")
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        got = score_sequences(model, seqs, control=control, log=log)
    w = rows[0]["sent"].shape[-1]
    scores = np.zeros((len(rows), f, w), np.float32)
    for (idx, a), sc in zip(owner, got):
        scores[idx, a] = sc
    if log:
        log(f"backbone_diffusion reference{' (control)' if control else ''}: {len(seqs)} "
            f"sequences, {len(rows)} judgments in {time.perf_counter() - t:.1f} s")
    if not control:
        scored.clear()
        scored.update({(int(r["uid"]), int(r["sweep"])): scores[i] for i, r in enumerate(rows)})
    top = scores.max(axis=1)
    return {"flags": top > thr, "margins": np.abs(top - thr).astype(np.float32), "scores": scores}


scored: dict = {}  # what the last judgment that was not the control's scored: {(uid, sweep): [F, W]}


def score_gaps(got: dict, want: dict) -> tuple[float, float]:
    """(median, largest) |got - want| in nats over every point of every
    judgment in `want` ({(uid, sweep): scores [F, W]}); nothing to compare,
    or a judgment `got` lacks, reads BROKEN."""
    if not want or any(k not in got for k in want):
        return BROKEN, BROKEN
    gap = np.abs(np.stack([got[k] for k in want]) - np.stack([want[k] for k in want]))
    return float(np.median(gap)), float(gap.max())


def score_numbers(program: dict, cfg: dict) -> dict:
    """The program's scores ({(uid, sweep): [F, W]}, as the window program
    gave them) against what the last `judge` scored, as compared numbers."""
    median, largest = score_gaps(program, scored)
    limits = cfg["correct_limits"]
    return {
        f"score_gap.{KIND}": {"value": median, "limit": float(limits[f"score_gap.{KIND}"])},
        f"score_gap_max.{KIND}": {"value": largest, "limit": float(limits[f"score_gap_max.{KIND}"])},
    }
