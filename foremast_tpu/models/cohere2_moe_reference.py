"""The plain reference of `models/cohere2_moe.py`: the Cohere2-MoE forward
pass as its config.json describes it, in straightforward `jax.numpy`,
float32 under `jax.default_matmul_precision("highest")`. Dense loops over
the experts, explicit masks, one full forward over a whole sequence: no
cache, no ring, no chunks, no batching, no sorting. It reads the model
file's plain dict and imports nothing of the program.

`share` is the model file's block: give it the chip's part
(`experts_held` of the experts from `index * experts_held` on) or the whole
layer (`chips_sharing_a_layer` 1, every expert held). The router always
scores all `num_experts` and takes its top-k over all of them; experts held
elsewhere add nothing.

Departures from the published description, as the model file's `assumed`
lists them: `average` = the mean of the shared experts' outputs added to
the routed sum; `intermediate_size` = the width of one expert; no
positional embedding on `full_attention` layers; weights N(0, 0.02^2) from
a seed, rounded to bfloat16 (the values the program holds), gains 1.
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
    h, w, d = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
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
        "shared": [
            tuple(tensor(cfg, f"{p}shared.{j}.{m}", s)
                  for m, s in (("gate", (h, w)), ("up", (h, w)), ("down", (w, h))))
            for j in range(cfg["num_shared_experts"])
        ],
    }


def embedding(cfg: dict, share: dict):
    return tensor(cfg, f"embed.{share['index']}", (share["vocab_rows_held"], cfg["hidden_size"]))


def layer_norm(x, eps):
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def rope_gptj(x, pos, theta):
    """x [T, H, D]: pair (2m, 2m+1) turned by pos * theta^(-2m / D)."""
    d = x.shape[-1]
    ang = pos[:, None].astype(jnp.float32) * theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1).reshape(x.shape)


def attention(cfg: dict, w: dict, xn, pos, kind: str):
    t = xn.shape[0]
    d, hq, hkv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    q = (xn @ w["wq"]).reshape(t, hq, d)
    k = (xn @ w["wk"]).reshape(t, hkv, d)
    v = (xn @ w["wv"]).reshape(t, hkv, d)
    gap = pos[:, None] - pos[None, :]
    mask = gap >= 0
    if kind == "sliding_attention":
        q, k = rope_gptj(q, pos, cfg["rope_theta"]), rope_gptj(k, pos, cfg["rope_theta"])
        mask = mask & (gap < cfg["sliding_window"])
    # query head j reads key-value head j // (hq / hkv)
    k, v = jnp.repeat(k, hq // hkv, axis=1), jnp.repeat(v, hq // hkv, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(d))
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", p, v).reshape(t, hq * d) @ w["wo"]


def _expert(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routing(cfg: dict, w: dict, xn):
    """(expert ids [T, k], weights [T, k]) over ALL experts."""
    s = jax.nn.sigmoid(xn @ w["router"])
    top_s, top_i = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    return top_i, top_s / top_s.sum(axis=-1, keepdims=True)


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


def shared(cfg: dict, w: dict, xn):
    return sum(_expert(xn, *mats) for mats in w["shared"]) / len(w["shared"])


def layer_parts(cfg: dict, w: dict, x, pos, kind: str):
    """(Attn(x'), routed part of FFN(x'), shared part of FFN(x')): the
    layer's output is x plus the three."""
    xn = layer_norm(x, cfg["layer_norm_eps"])
    return attention(cfg, w, xn, pos, kind), routed(cfg, w, xn), shared(cfg, w, xn)


def forward(cfg: dict, share: dict, ids):
    """logits [T, vocabulary rows held] of one sequence of ids [T]."""
    with jax.default_matmul_precision("highest"):
        emb = embedding(cfg, share)
        pos = jnp.arange(len(ids), dtype=jnp.int32)
        x = emb[jnp.asarray(ids)]
        for li in range(share["layers_held"]):
            w = layer_weights(cfg, share, li)
            x = x + sum(layer_parts(cfg, w, x, pos, cfg["layer_types"][li]))
        return (layer_norm(x, cfg["layer_norm_eps"]) @ emb.T) * cfg["logit_scale"]


def series_scale(history) -> np.ndarray:
    s = np.abs(np.asarray(history, np.float32)).mean(axis=-1, dtype=np.float32)
    return np.where(s > 0, s, np.float32(1.0)).astype(np.float32)


def tokenize(values, scale, vocab: int) -> np.ndarray:
    u = np.asarray(values, np.float32) / np.asarray(scale, np.float32)[..., None]
    ids = np.floor((u + np.float32(TOKEN_RANGE)) / np.float32(2 * TOKEN_RANGE) * np.float32(vocab))
    return np.clip(ids, 0, vocab - 1).astype(np.int32)


def window_scores(cfg: dict, share: dict, history, window):
    """score_t = -log p(id_t | history, id_<t) for the points of `window`,
    by ONE forward over [history; window] -> (scores [w], logits [w, V])."""
    scale = series_scale(history)
    v = share["vocab_rows_held"]
    ids = np.concatenate([tokenize(history, scale, v), tokenize(window, scale, v)])
    n, w = len(history), len(window)
    # the logits at position p predict the id at p + 1
    logits = forward(cfg, share, ids[:-1])[n - 1 : n - 1 + w]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -logp[jnp.arange(w), ids[n:]], logits
