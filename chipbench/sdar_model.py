"""Bytes and operations of the `backbone_diffusion` kind's window program
(the SDAR-MoE block of foremast_tpu/models/sdar_moe.py at the widths of
`configs/sdar-30b-a3b-pp12-7d.json`), as functions of shapes alone: the
same whatever implements the scoring rule. `window_flops` is the group's
`flops_fn`; the tests hold all of it against the program's own shapes.

A document's judgment scores f sequences (one an alias) of `window_points`
points each against a cached prefix of `history_points` positions (whole
blocks of `block_length` B) by block diffusion: block b of the window is
run B times as a noisy copy, one a scored point (its points before that one
observed, the rest masked), and once clean where a later block has a point
to score, so that later blocks see it. A noisy copy of block b sees the
cached prefix, the window's clean blocks before b and its own B tokens; a
clean token of block b sees the prefix and the clean blocks up to b.
"""

from __future__ import annotations

import json
import os

BF16, F32 = 2, 4
_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = json.load(
    open(os.path.join(_HERE, "configs", "sdar-30b-a3b-pp12-7d.json"), encoding="utf-8")
)


def _shape(cfg: dict | None = None) -> dict:
    cfg = cfg or CONFIG
    return {
        "h": cfg["hidden_size"], "w": cfg["moe_intermediate_size"], "layers": cfg["num_hidden_layers"],
        "hq": cfg["num_attention_heads"], "hkv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
        "experts_held": cfg["share"]["experts_held"], "experts_all": cfg["num_experts"],
        "top_k": cfg["num_experts_per_tok"], "vocab": cfg["share"]["vocab_rows_held"],
        "block": cfg["block_length"],
        "cached": cfg["history_points"] // cfg["block_length"] * cfg["block_length"],
        "points": cfg["window_points"],
    }


def context_capacity(cached: int) -> int:
    """Positions a row's leaves hold: the cached positions rounded up to a
    multiple of 128 (8 for a toy context)."""
    g = 128 if cached > 128 else 8
    return -(-cached // g) * g


def kv_bytes_per_position(cfg: dict | None = None) -> int:
    s = _shape(cfg)
    return s["layers"] * 2 * s["hkv"] * s["d"] * BF16


def row_bytes(cfg: dict | None = None) -> int:
    """One sequence's arena row: keys and values of every held layer to the
    leaves' capacity, and three 4-byte scalars (scale, cached positions, the
    last history id)."""
    s = _shape(cfg)
    return context_capacity(s["cached"]) * kv_bytes_per_position(cfg) + 12


def layer_params(cfg: dict | None = None) -> tuple:
    """(a layer's attention matrices, its router, one expert) in parameters."""
    s = _shape(cfg)
    attn = s["h"] * s["hq"] * s["d"] * 2 + s["h"] * s["hkv"] * s["d"] * 2
    return attn, s["h"] * s["experts_all"], 3 * s["h"] * s["w"]


def weight_bytes(cfg: dict | None = None) -> int:
    """The share's weights: matrices in bfloat16; norm gains in float32."""
    s = _shape(cfg)
    attn, router, expert = layer_params(cfg)
    matrices = s["layers"] * (attn + router + s["experts_held"] * expert) + 2 * s["vocab"] * s["h"]
    gains = s["layers"] * (2 * s["h"] + 2 * s["d"]) + s["h"]
    return BF16 * matrices + F32 * gains


def token_forwards(cfg: dict | None = None, points: int | None = None) -> tuple:
    """(noisy copies' token-forwards, clean window tokens, keys the
    window's own tokens see beyond the cached prefix, summed over them) of
    one sequence's window of `points` points, by the rule."""
    s = _shape(cfg)
    points = s["points"] if points is None else points
    b = s["block"]
    noisy = clean = own = 0
    for p in range(points):
        blk = p // b
        noisy += b
        own += b * (b * blk + b)  # its copy's B tokens see the clean blocks before and itself
    for blk in range(-(-points // b) - 1):  # blocks a later block reads: all but the last
        clean += b
        own += b * (b * blk + b)
    return noisy, clean, own


def token_flops(cfg: dict | None = None) -> float:
    """Operations one token-forward needs outside attention (a
    multiply-add is two): the attention projections, the router and its
    top-k experts held here (top-k times the share of the experts held),
    every held layer."""
    s = _shape(cfg)
    attn, router, expert = layer_params(cfg)
    routed = s["top_k"] * s["experts_held"] / s["experts_all"] * expert
    return 2.0 * s["layers"] * (attn + router + routed)


def attention_flops_per_key(cfg: dict | None = None) -> float:
    """Operations of one query token against one key, every held layer:
    q.k and p v over every query head."""
    s = _shape(cfg)
    return 2.0 * 2 * s["layers"] * s["hq"] * s["d"]


def sequence_flops(cfg: dict | None = None, points: int | None = None) -> float:
    """One sequence's window: every token-forward of the rule, its
    attention over the cached prefix and the window's own keys, and the
    head at each scored point over the held vocabulary."""
    s = _shape(cfg)
    points = s["points"] if points is None else points
    noisy, clean, own = token_forwards(cfg, points)
    tokens = noisy + clean
    attention = attention_flops_per_key(cfg) * (tokens * s["cached"] + own)
    return tokens * token_flops(cfg) + attention + points * 2.0 * s["h"] * s["vocab"]


def window_flops(f: int, w_bucket: int) -> float:
    """The group's `flops_fn`: operations one document's warm judgment
    needs, f sequences of the window's real points (the bucket's padding is
    no work the rule needs)."""
    return f * sequence_flops(points=min(_shape()["points"], w_bucket))


def window_bytes(docs: float, f: int, dispatches: float) -> float:
    """Least bytes the window program's dispatches must move: the weights
    once a dispatch, and of each sequence's row the keys and values of
    every cached position."""
    s = _shape()
    return dispatches * weight_bytes() + docs * f * s["cached"] * kv_bytes_per_position()


def attention_flops(docs: float, f: int) -> float:
    """The attention kernel's share of `window_flops`: every token-forward's
    query against the cached prefix and the window's own keys it sees."""
    s = _shape()
    noisy, clean, own = token_forwards()
    return docs * f * attention_flops_per_key() * ((noisy + clean) * s["cached"] + own)


def attention_bytes(docs: float, f: int) -> float:
    """Least bytes the attention kernel must move: each sequence's cached
    keys and values, once a layer, and its tokens' queries, own keys and
    values and outputs."""
    s = _shape()
    noisy, clean, _ = token_forwards()
    per_token = s["layers"] * (2 * s["hq"] + 2 * s["hkv"]) * s["d"] * BF16
    return docs * f * (s["cached"] * kv_bytes_per_position() + (noisy + clean) * per_token)
