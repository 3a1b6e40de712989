"""Bytes and operations of the `backbone_kda` kind's window program (the
Kimi-Linear block of foremast_tpu/models/kimi_linear.py at the widths of
`configs/kimi-linear-ep2-7d.json`), as functions of shapes alone: the same
whatever implements the program. `window_flops` is the group's `flops_fn`;
the tests hold all of it against the program's own shapes.

A document's judgment scores f sequences (one an alias) of `window_points`
tokens each as the continuation of a cached prefix of `history_points` - 1
positions: a float32 state and a convolution tail a KDA layer, [c; k_r] a
cached position a MLA layer.
"""

from __future__ import annotations

import json
import os

BF16, F32 = 2, 4
_HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = json.load(
    open(os.path.join(_HERE, "configs", "kimi-linear-ep2-7d.json"), encoding="utf-8")
)


def _shape(cfg: dict | None = None) -> dict:
    cfg = cfg or CONFIG
    lin = cfg["linear_attn_config"]
    layers = range(1, cfg["num_hidden_layers"] + 1)
    kda = sum(li in lin["kda_layers"] for li in layers)
    return {
        "h": cfg["hidden_size"], "dense_w": cfg["intermediate_size"],
        "w": cfg["moe_intermediate_size"],
        "kda": kda, "mla": cfg["num_hidden_layers"] - kda, "dense": cfg["first_k_dense_replace"],
        "kh": lin["num_heads"], "kd": lin["head_dim"], "taps": lin["short_conv_kernel_size"],
        "mh": cfg["num_attention_heads"], "lat": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
        "experts_held": cfg["num_experts"], "experts_all": cfg["published"]["num_experts"],
        "top_k": cfg["num_experts_per_token"], "shared": cfg["num_shared_experts"],
        "vocab": cfg["vocab_size"],
        "cached": cfg["history_points"] - 1, "points": cfg["window_points"],
    }


def context_capacity(cached: int) -> int:
    """Positions a MLA layer's leaves hold: the cached positions rounded up
    to a multiple of 128 (8 for a toy context)."""
    g = 128 if cached > 128 else 8
    return -(-cached // g) * g


def state_bytes(cfg: dict | None = None) -> int:
    """One sequence's recurrent part of a row: each KDA layer's float32
    state [H, d, d] and the last taps - 1 projected inputs of q, k and v."""
    s = _shape(cfg)
    tail = (s["taps"] - 1) * 3 * s["kh"] * s["kd"] * BF16
    return s["kda"] * (s["kh"] * s["kd"] * s["kd"] * F32 + tail)


def latent_bytes_per_position(cfg: dict | None = None) -> int:
    s = _shape(cfg)
    return s["mla"] * (s["lat"] + s["rope"]) * BF16


def row_bytes(cfg: dict | None = None) -> int:
    """One sequence's arena row: the recurrent part, the latents to the
    leaves' capacity, and three 4-byte scalars (scale, cached positions,
    last history id)."""
    s = _shape(cfg)
    return state_bytes(cfg) + context_capacity(s["cached"]) * latent_bytes_per_position(cfg) + 12


def mixer_params(cfg: dict | None = None) -> tuple:
    """(a KDA mixer's, a MLA mixer's) matrix parameters."""
    s = _shape(cfg)
    hd = s["kh"] * s["kd"]
    kda = (4 * s["h"] * hd + 2 * (s["h"] * s["kd"] + s["kd"] * hd) + s["h"] * s["kh"]
           + 3 * s["taps"] * hd)
    mla = (s["h"] * s["mh"] * (s["nope"] + s["rope"]) + s["h"] * (s["lat"] + s["rope"])
           + s["lat"] * s["mh"] * (s["nope"] + s["dv"]) + s["mh"] * s["dv"] * s["h"])
    return kda, mla


def weight_bytes(cfg: dict | None = None) -> int:
    """The share's weights: matrices in bfloat16; norm gains, A_log,
    dt_bias and the router's bias in float32."""
    s = _shape(cfg)
    hd = s["kh"] * s["kd"]
    kda, mla = mixer_params(cfg)
    kda_f32 = s["kh"] + hd + s["kd"]  # A_log, dt_bias, the head norm's gain
    mla_f32 = s["lat"]  # the latent norm's gain
    expert = 3 * s["h"] * s["w"]
    moe = s["h"] * s["experts_all"] + (s["experts_held"] + s["shared"]) * expert
    dense = 3 * s["h"] * s["dense_w"]
    layers = s["kda"] + s["mla"]
    matrices = (s["kda"] * kda + s["mla"] * mla + s["dense"] * dense
                + (layers - s["dense"]) * moe + 2 * s["vocab"] * s["h"])
    floats = (s["kda"] * kda_f32 + s["mla"] * mla_f32 + layers * 2 * s["h"]
              + (layers - s["dense"]) * s["experts_all"] + s["h"])
    return BF16 * matrices + F32 * floats


def token_flops(cfg: dict | None = None) -> float:
    """Operations one window token needs (a multiply-add is two): the
    mixers' projections (the convolution's taps among them), a KDA layer's
    read of its state and update of it (two d x d products a head), the
    absorbed MLA attention over what the token sees (every cached position
    and, on average, half the window: latent + rope to score, latent to sum,
    a head), the router, its routed experts held here (top-k times the
    share of the experts held: the expectation under routing even over the
    chips), the shared expert, the dense layer, and the head over the held
    vocabulary. The chunk algebra inside a window (pairs of its own tokens)
    is left out: it is what an implementation chooses."""
    s = _shape(cfg)
    kda, mla = mixer_params(cfg)
    state = 2 * 2 * s["kh"] * s["kd"] * s["kd"]
    seen = s["cached"] + (s["points"] + 1) / 2
    attn = 2 * s["mh"] * (2 * s["lat"] + s["rope"]) * seen
    expert = 3 * 2 * s["h"] * s["w"]
    routed = s["top_k"] * s["experts_held"] / s["experts_all"] * expert
    moe = 2 * s["h"] * s["experts_all"] + routed + s["shared"] * expert
    dense = 3 * 2 * s["h"] * s["dense_w"]
    layers = s["kda"] + s["mla"]
    return (s["kda"] * (2 * kda + state) + s["mla"] * (2 * mla + attn)
            + s["dense"] * dense + (layers - s["dense"]) * moe + 2 * s["h"] * s["vocab"])


def window_flops(f: int, w_bucket: int) -> float:
    """The group's `flops_fn`: operations one document's warm judgment
    needs, f sequences of the window's real points (the bucket's padding is
    no work the model needs)."""
    s = _shape()
    return f * min(s["points"], w_bucket) * token_flops()


def window_bytes(docs: float, f: int, dispatches: float) -> float:
    """Least bytes the window program's dispatches must move: the weights
    once a dispatch, and of each sequence's row its recurrent part and the
    latents of every cached position."""
    s = _shape()
    row = state_bytes() + s["cached"] * latent_bytes_per_position()
    return dispatches * weight_bytes() + docs * f * row
