"""The plain reference of `models/sdar_moe.py`: the SDAR-MoE forward pass as
its config.json and the model file's `assumed` block describe it, and the
block-diffusion score as its rule is written, in straightforward
`jax.numpy`, float32 under `jax.default_matmul_precision("highest")`. Dense
loops over the experts, explicit masks, one full forward over a whole
sequence for every scored point: no cache, no copies side by side, no
chunks, no batching, no sorting. It reads the model file's plain dict and
imports nothing of the program.

`share` is the model file's block (`whole_share` gives the uncut layer). The
router always scores all `num_experts` and takes its top-k over all of them;
experts held elsewhere add nothing.

The layer: h = RMSNorm(x); q, k, v = h W_q, h W_k, h W_v; q and k RMSNorm'd
a head and turned by rotate-half RoPE; x += softmax(q.k / sqrt(D) under the
block mask) v W_o; h = RMSNorm(x); x += sum over the top-k of softmax(h W_r),
renormalised, of the held experts' (silu(h W_g) * h W_u) W_d. Head: RMSNorm(x)
W_head over the held vocabulary rows. The block mask: the positions are cut
into blocks of `block_length`, and position i sees position j where j's
block is not after i's, so the last block of a sequence, whatever it holds,
sees every earlier block and itself both ways.

The score of window point s of block b (`window_scores`): ONE forward over
[the history's newest whole blocks; the window's blocks before b, as
observed; block b with its points < s observed and the mask id at the
rest], and -log softmax of the logits at block b's position s, at the
observed id. Tokeniser: scale = mean |history| (0 -> 1), id =
clip(floor((x / scale + 15) / 30 * (V - 1)), 0, V - 2) over the V vocabulary
rows held; the mask id is V - 1. Weights: N(0, 0.02^2) a tensor from
fold_in(PRNGKey(weights_seed), crc32(name)), rounded to bfloat16, gains 1.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

TOKEN_RANGE = 15.0


def whole_share(cfg: dict) -> dict:
    return {
        "chips_sharing_a_layer": 1, "index": 0, "experts_held": cfg["num_experts"],
        "vocab_rows_held": cfg["vocab_size"], "layers_held": cfg["num_hidden_layers"],
    }


def tensor(cfg: dict, name: str, shape: tuple):
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(cfg.get("weights_seed", 0))), np.uint32(zlib.crc32(name.encode()))
    )
    drawn = 0.02 * jax.random.normal(key, shape, jnp.float32)
    return drawn.astype(jnp.bfloat16).astype(jnp.float32)


def layer_weights(cfg: dict, share: dict, li: int) -> dict:
    h, w, d = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    first = share["index"] * share["experts_held"]
    p = f"layers.{li}."
    return {
        "wq": tensor(cfg, p + "attn.q", (h, hq)),
        "wk": tensor(cfg, p + "attn.k", (h, hkv)),
        "wv": tensor(cfg, p + "attn.v", (h, hkv)),
        "wo": tensor(cfg, p + "attn.o", (hq, h)),
        "router": tensor(cfg, p + "router", (h, cfg["num_experts"])),
        "experts": {
            e: tuple(tensor(cfg, f"{p}experts.{e}.{m}", s)
                     for m, s in (("gate", (h, w)), ("up", (h, w)), ("down", (w, h))))
            for e in range(first, first + share["experts_held"])
        },
    }


def rms_norm(x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def rope_neox(x, pos, theta):
    """x [T, H, D]: dims m and m + D/2 turned by pos * theta^(-2m / D)."""
    d = x.shape[-1]
    ang = pos[:, None].astype(jnp.float32) * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x0, x1 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1)


def attention(cfg: dict, w: dict, xn, pos):
    """Grouped-query attention of one sequence xn [T, h] at positions pos
    under the block mask."""
    t = xn.shape[0]
    d, hq, hkv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = rope_neox(rms_norm((xn @ w["wq"]).reshape(t, hq, d), eps), pos, theta)
    k = rope_neox(rms_norm((xn @ w["wk"]).reshape(t, hkv, d), eps), pos, theta)
    v = (xn @ w["wv"]).reshape(t, hkv, d)
    block = pos // cfg["block_length"]
    mask = block[None, :] <= block[:, None]
    # query head j reads key-value head j // (hq / hkv)
    k, v = jnp.repeat(k, hq // hkv, axis=1), jnp.repeat(v, hq // hkv, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(d))
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", p, v).reshape(t, hq * d) @ w["wo"]


def _expert(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routing(cfg: dict, w: dict, xn):
    """(expert ids [T, k], weights [T, k]) over ALL experts: softmax, top-k,
    renormalised."""
    p = jax.nn.softmax(xn @ w["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        top_p = top_p / top_p.sum(axis=-1, keepdims=True)
    return top_i, top_p


def routed(cfg: dict, w: dict, xn):
    """The held experts' weighted outputs: a dense loop, every held expert
    over every token, weighted by what the router gave it (0 where the
    token was not routed to it)."""
    top_i, top_w = routing(cfg, w, xn)
    y = jnp.zeros_like(xn)
    for e, mats in w["experts"].items():
        weight = jnp.where(top_i == e, top_w, 0.0).sum(axis=-1)
        y = y + weight[:, None] * _expert(xn, *mats)
    return y


def forward(cfg: dict, share: dict, ids):
    """logits [T, vocabulary rows held] of one sequence of ids [T] at
    positions 0 .. T - 1 under the block mask."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        pos = jnp.arange(len(ids), dtype=jnp.int32)
        x = tensor(cfg, f"embed.{share['index']}", (share["vocab_rows_held"], cfg["hidden_size"]))[
            jnp.asarray(ids)]
        for li in range(share["layers_held"]):
            w = layer_weights(cfg, share, li)
            x = x + attention(cfg, w, rms_norm(x, eps), pos)
            x = x + routed(cfg, w, rms_norm(x, eps))
        head = tensor(cfg, f"head.{share['index']}", (cfg["hidden_size"], share["vocab_rows_held"]))
        return rms_norm(x, eps) @ head


def series_scale(history) -> np.ndarray:
    s = np.abs(np.asarray(history, np.float32)).mean(axis=-1, dtype=np.float32)
    return np.where(s > 0, s, np.float32(1.0)).astype(np.float32)


def tokenize(values, scale, vocab: int) -> np.ndarray:
    """Onto the vocab - 1 ids below the mask id vocab - 1."""
    u = np.asarray(values, np.float32) / np.asarray(scale, np.float32)[..., None]
    ids = np.floor((u + np.float32(TOKEN_RANGE)) / np.float32(2 * TOKEN_RANGE) * np.float32(vocab - 1))
    return np.clip(ids, 0, vocab - 2).astype(np.int32)


def window_scores(cfg: dict, share: dict, history, window):
    """score(b, s) of every point of `window`, block by block and step by
    step, each by ONE forward -> (scores [w], logits [w, V])."""
    v = share["vocab_rows_held"]
    bl = cfg["block_length"]
    scale = series_scale(history)
    hist = tokenize(history, scale, v)
    hist = hist[len(hist) % bl:]  # the newest whole blocks
    win = tokenize(window, scale, v)
    scores, logits = [], []
    for at in range(len(win)):
        b, s = divmod(at, bl)
        block = np.full(bl, v - 1, np.int32)
        block[:s] = win[b * bl : at]
        ids = np.concatenate([hist, win[: b * bl], block])
        out = forward(cfg, share, ids)[len(hist) + at]
        scores.append(-jax.nn.log_softmax(out)[win[at]])
        logits.append(out)
    return jnp.stack(scores), jnp.stack(logits)
