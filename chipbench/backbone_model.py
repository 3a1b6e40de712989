"""Bytes and operations of the `backbone` kind's window program (the
Cohere2-MoE block of foremast_tpu/models/cohere2_moe.py at the widths of
`configs/command-a-plus-ep8-7d.json`), as functions of shapes alone: the
same whatever implements the program. `window_flops` is the group's
`flops_fn`; the tests hold all of it against the program's own shapes.

A document's judgment scores f sequences (one an alias) of `window_points`
tokens each against a cached prefix of `history_points` - 1 positions.
"""

from __future__ import annotations

import json
import os

BF16, F32 = 2, 4
_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = json.load(
    open(os.path.join(_HERE, "configs", "command-a-plus-ep8-7d.json"), encoding="utf-8")
)


def _shape(cfg: dict | None = None) -> dict:
    cfg = cfg or CONFIG
    d, hq, hkv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    return {
        "h": cfg["hidden_size"], "w": cfg["intermediate_size"], "d": d, "hq": hq, "hkv": hkv,
        "experts_held": cfg["num_experts"], "experts_all": cfg["published"]["num_experts"],
        "top_k": cfg["num_experts_per_tok"], "shared": cfg["num_shared_experts"],
        "vocab": cfg["vocab_size"], "window": cfg["sliding_window"],
        "full": sum(k == "full_attention" for k in kinds),
        "sliding": sum(k == "sliding_attention" for k in kinds),
        "cached": cfg["history_points"] - 1, "points": cfg["window_points"],
    }


def context_capacity(cached: int) -> int:
    """Positions a full layer's leaf holds: the cached positions rounded up
    to a multiple of 128 (8 for a toy context)."""
    g = 128 if cached > 128 else 8
    return -(-cached // g) * g


def row_bytes(cfg: dict | None = None) -> int:
    """One sequence's row of the prefix cache: K and V of every cached
    position for each full layer (to the leaf's capacity), of the last
    `sliding_window` for each sliding layer, and three 4-byte scalars
    (scale, cached positions, last history id)."""
    s = _shape(cfg)
    per_position = 2 * s["hkv"] * s["d"] * BF16
    full = s["full"] * context_capacity(s["cached"]) * per_position
    sliding = s["sliding"] * min(s["window"], context_capacity(s["cached"])) * per_position
    return full + sliding + 12


def layer_weight_bytes(cfg: dict | None = None) -> int:
    """One layer's held weights: attention, router, held experts, shared
    experts, in bfloat16 (the LayerNorm gain is float32)."""
    s = _shape(cfg)
    attn = 2 * s["h"] * s["hq"] * s["d"] + 2 * s["h"] * s["hkv"] * s["d"]
    experts = (s["experts_held"] + s["shared"]) * 3 * s["h"] * s["w"]
    return BF16 * (attn + s["h"] * s["experts_all"] + experts) + F32 * s["h"]


def weight_bytes(cfg: dict | None = None) -> int:
    s = _shape(cfg)
    return (
        (s["full"] + s["sliding"]) * layer_weight_bytes(cfg)
        + BF16 * s["vocab"] * s["h"] + F32 * s["h"]
    )


def token_flops(cfg: dict | None = None) -> float:
    """Operations one window token needs (a multiply-add is two): the
    projections, attention over what it sees (a sliding layer's window; a
    full layer's whole prefix and, on average, half the window), the
    router, its routed experts held here (top-k times the share of the
    experts held, the expectation under any routing that is even over the
    chips), the shared experts, and the head over the held vocabulary."""
    s = _shape(cfg)
    proj = 2 * s["h"] * (2 * s["hq"] * s["d"] + 2 * s["hkv"] * s["d"])
    per_key = 2 * 2 * s["hq"] * s["d"]
    seen_full = s["cached"] + (s["points"] + 1) / 2
    seen_sliding = min(s["window"], seen_full)
    attn = per_key * (s["full"] * seen_full + s["sliding"] * seen_sliding)
    expert = 3 * 2 * s["h"] * s["w"]
    routed = s["top_k"] * s["experts_held"] / s["experts_all"] * expert
    ffn = 2 * s["h"] * s["experts_all"] + routed + s["shared"] * expert
    layers = s["full"] + s["sliding"]
    return layers * (proj + ffn) + attn + 2 * s["h"] * s["vocab"]


def window_flops(f: int, w_bucket: int) -> float:
    """The group's `flops_fn`: operations one document's warm judgment
    needs, f sequences of the window's real points (the bucket's padding is
    no work the model needs)."""
    s = _shape()
    return f * min(s["points"], w_bucket) * token_flops()


def window_bytes(docs: float, f: int, dispatches: float) -> float:
    """Least bytes the window program's dispatches must move: the weights
    once a dispatch, and of each sequence's row what its tokens attend to
    (every cached position of a full layer, a sliding layer's window)."""
    s = _shape()
    per_position = 2 * s["hkv"] * s["d"] * BF16
    row = per_position * (s["full"] * s["cached"] + s["sliding"] * min(s["window"], s["cached"]))
    return dispatches * weight_bytes() + docs * f * row
