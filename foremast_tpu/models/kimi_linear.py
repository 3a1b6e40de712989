"""A linear-attention sequence backbone as a detector: the Kimi-Linear block
(`model_type: kimi_linear`, e.g. Kimi-Linear-48B-A3B-Instruct) at the widths
of a model file (`models/configs/*.json`: config.json's keys verbatim plus
a `share` block), scoring a monitor's current window as the continuation of
its cached 7-day history. It stands behind the same interface as
`models/cohere2_moe.py` (`engine/backbone.py` has the list) and is kind
`backbone_kda`; docs/backbone.md is the operator's page.

The layers, numbered from 1 as `linear_attn_config` numbers them (RMSNorm,
eps `rms_norm_eps`, statistics in float32; sequential residuals):

    x <- x + Mix_l(RMSNorm(x));  x <- x + FFN_l(RMSNorm(x));  logits = RMSNorm(x) W_head

  * KDA, Kimi Delta Attention (`kda_layers`; H heads of d): q~, k~, v~ =
    SiLU(Conv4(x W_q | W_k | W_v)), a causal depthwise convolution of
    `short_conv_kernel_size` taps; q = L2norm(q~) d^-1/2, k = L2norm(k~), v =
    v~; per head and key channel g_t = -exp(A_log_h) softplus((x_t W_f1) W_f2
    + dt_bias), a_t = exp(g_t); b_t = sigmoid(x_t W_b) a head;
        S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,  o_t = S_t^T q_t
    Mix = (RMSNorm_head(o_t) * sigmoid((x_t W_g1) W_g2)) W_o. The program
    runs it chunkwise (`kda_chunks`): with G the running sum of g inside a
    chunk and u_t = b_t (v_t - S~_t^T k_t) the token's correction, U = (I + B
    A)^-1 B (V - (K * e^G) S_0), A_ti = sum_c k_tc k_ic e^(G_tc - G_ic) (i <
    t), O = (Q * e^G) S_0 + A' U (A' the same with q and i <= t), S_C =
    Diag(e^G_C) S_0 + (K * e^(G_C - G))^T U. No factor e^(-G) is ever formed:
    a pair (t, i) of different sub-chunks goes through the later one's
    anchor a (the sum up to its first token), e^(G_t - a) e^(a - G_i), both
    exponents <= 0; a pair inside a sub-chunk takes the difference itself.
    On a TPU the WINDOW program runs the same algebra as one Pallas kernel
    a layer (`models/kimi_kda.py:fused_kda_rows`): a grid step is a sequence
    and its heads, each head's S_0 is read from the arena leaf where it
    lies, everything between the inputs and O stays in VMEM, and S_C, which
    the window program throws away, is not computed. `fused_window_kda` is
    the test (the backend, no `write_at`, the tokens fit one chunk, d a
    whole lane tile); no option selects it. `kda_chunks` runs everywhere
    else: every CPU run, narrower heads, and the prefill always (chunks
    under a scan, the state carried and written back).
  * MLA, latent attention (`full_attn_layers`; `q_lora_rank` null): q = x
    W_q -> per head [q_n; q_r]; [c; k_r] = x W_kva, c <- RMSNorm(c); [k_n,h;
    v_h] = c W_kvb,h; score (q_n.k_n,h + q_r.k_r) / sqrt(nope + rope), causal
    softmax, o_h = sum p v_h, Mix = concat(o) W_o. `mla_use_nope`: no
    rotation anywhere. The program absorbs W_kvb: q'_h = [W_kb,h q_n; q_r]
    scores against the cached [c; k_r] (`kv_lora_rank` + rope values a
    position, shared by every head), the probabilities sum c itself, and
    W_vb,h is applied to that sum.
  * FFN. The first `first_k_dense_replace` layers: (SiLU(x W_g) * x W_u)
    W_d of width `intermediate_size`. Every later one: s = sigmoid(x W_r)
    over all `num_experts` (float32); E = top-k of s + bias
    (`e_score_correction_bias`, the choice only; one group, so the grouped
    top-k is the plain one); w_e = s_e / sum_E s * `routed_scaling_factor`;
    FFN = sum_{e in E, held} w_e expert_e(x) + the shared experts. A process
    holds `share.experts_held` experts from `share.index * experts_held` on
    and computes their part alone (`cohere2_moe.routed_experts`, the grouped
    product both models share): nothing is dropped, no capacity factor.

The detector. Tokeniser and scale are `cohere2_moe`'s (mean scaling over
`share.vocab_rows_held` ids). A sequence's arena row (`cache_template`)
holds three kinds of leaf: `S`, each KDA layer's float32 state [H, d, d],
which a prefill chunk reads and rewrites whole (the row is the recurrence's
carry between chunks); `conv`, each KDA layer's last taps - 1 projected
inputs of q, k and v; `c` and `kr`, each MLA layer's latents [c; k_r] of
every cached position, appended to; and n, last, scale as Cohere2's row. Warm,
`score_window` feeds [last history id; the window's ids but the last] as
the continuation of the row and throws the advanced state away: rows are
read, never written. Latent attention is `latent_attend`, a sequence at a
time over its row's latents where they lie, in both programs; the window
program also returns the positions its queries attended to, counted from
the masks the softmax was taken under (`latent_positions`).

Precision: weights, activations, conv tails and latents in `compute_dtype`
(bfloat16); the KDA state, its chunk algebra (float32 operands at `highest`,
in `kda_chunks` and in the kernel alike: half a percent of a token's
operations; the two agree to 2e-6), accumulation, softmax, norms, router,
decay sums and log-softmax in float32. The plain reference is
`models/kimi_linear_reference.py`.
"""

from __future__ import annotations

import dataclasses
import json
import os
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from foremast_tpu.models.cohere2_moe import (  # the code both backbones share
    Share,
    cached_span,
    finish_rows,
    routed_experts,
    router_scores,
    series_scale,
    sorted_combine,
    tensor,
    tokenize,
)
from foremast_tpu.models.kimi_kda import KDA_CHUNK, KDA_SUB, fused_applies, fused_kda_rows

__all__ = [
    "Config", "MODEL_TYPE", "cache_template", "cached_span", "finish_rows", "init_params",
    "prefill_chunk", "prefill_chunk_len", "prefill_seqs", "score_window", "series_scale",
    "tokenize", "window_counters", "WINDOW_COUNTERS",
]

MODEL_TYPE = "kimi_linear"
KDA, MLA = "kda", "mla"
DEFAULT_MODEL_FILE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "configs", "kimi-linear-48b-a3b-instruct.json"
)
PREFILL_CHUNK = 2560  # tokens a sequence a prefill dispatch, at most
PREFILL_SEQS = 2  # sequences a prefill dispatch: ~5,000 tokens, ~160 assignments a held expert
Q_BLOCK = 32  # query tokens a block of a prefill chunk's latent attention
HEAD_BLOCK = 512  # tokens a block of the head: a block's logits are reduced to scores
MASKED = -1e30
HIGHEST = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    hidden_size: int
    intermediate_size: int  # the leading dense layers' FFN
    moe_intermediate_size: int  # ONE expert, routed and shared alike
    num_experts: int  # the router's width: every published expert
    num_experts_per_token: int
    num_shared_experts: int
    first_k_dense_replace: int
    routed_scaling_factor: float
    moe_renormalize: bool
    rms_norm_eps: float
    num_attention_heads: int  # MLA
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kda_heads: int
    kda_head_dim: int
    conv_taps: int
    layer_kinds: tuple  # the published depth; `share.layers_held` are run
    vocab_size: int
    share: Share
    weights_seed: int = 0
    compute_dtype: str = "bfloat16"

    @staticmethod
    def from_dict(d: dict) -> "KimiLinearConfig":
        for key, want in (
            ("model_type", MODEL_TYPE), ("moe_router_activation_func", "sigmoid"),
            ("hidden_act", "silu"), ("q_lora_rank", None), ("rope_scaling", None),
            ("num_expert_group", 1), ("topk_group", 1), ("moe_layer_freq", 1),
            ("mla_use_nope", True), ("tie_word_embeddings", False),
            ("num_nextn_predict_layers", 0),
        ):
            if d.get(key, want) != want:
                raise ValueError(f"model file: {key}={d[key]!r}, only {want!r} is written down")
        lin = d["linear_attn_config"]
        kinds = []
        for layer in range(1, int(d["num_hidden_layers"]) + 1):
            kda, mla = layer in lin["kda_layers"], layer in lin["full_attn_layers"]
            if kda == mla:
                raise ValueError(
                    f"model file: layer {layer} has to be in exactly one of "
                    "linear_attn_config's kda_layers and full_attn_layers"
                )
            kinds.append(KDA if kda else MLA)
        share = d.get("share") or {
            "chips_sharing_a_layer": 1, "index": 0,
            "experts_held": d["num_experts"],
            "vocab_rows_held": d["vocab_size"],
            "layers_held": d["num_hidden_layers"],
        }
        return KimiLinearConfig(
            hidden_size=int(d["hidden_size"]),
            intermediate_size=int(d["intermediate_size"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts=int(d["num_experts"]),
            num_experts_per_token=int(d["num_experts_per_token"]),
            num_shared_experts=int(d["num_shared_experts"]),
            first_k_dense_replace=int(d["first_k_dense_replace"]),
            routed_scaling_factor=float(d["routed_scaling_factor"]),
            moe_renormalize=bool(d.get("moe_renormalize", True)),
            rms_norm_eps=float(d["rms_norm_eps"]),
            num_attention_heads=int(d["num_attention_heads"]),
            kv_lora_rank=int(d["kv_lora_rank"]),
            qk_nope_head_dim=int(d["qk_nope_head_dim"]),
            qk_rope_head_dim=int(d["qk_rope_head_dim"]),
            v_head_dim=int(d["v_head_dim"]),
            kda_heads=int(lin["num_heads"]),
            kda_head_dim=int(lin["head_dim"]),
            conv_taps=int(lin["short_conv_kernel_size"]),
            layer_kinds=tuple(kinds),
            vocab_size=int(d["vocab_size"]),
            share=Share(**{f.name: int(share[f.name]) for f in dataclasses.fields(Share)}),
            weights_seed=int(d.get("weights_seed", 0)),
            compute_dtype=str(d.get("compute_dtype", "bfloat16")),
        )

    @staticmethod
    def from_file(path: str | None = None) -> "KimiLinearConfig":
        with open(path or DEFAULT_MODEL_FILE, encoding="utf-8") as fh:
            return KimiLinearConfig.from_dict(json.load(fh))

    def __post_init__(self):
        s = self.share
        if (s.index + 1) * s.experts_held > self.num_experts:
            raise ValueError("share: experts held run past num_experts")
        if (s.index + 1) * s.vocab_rows_held > self.vocab_size:
            raise ValueError("share: vocabulary rows held run past vocab_size")
        if not 0 < s.layers_held <= len(self.layer_kinds):
            raise ValueError("share: layers_held outside the published depth")

    @property
    def layers(self) -> tuple:
        return self.layer_kinds[: self.share.layers_held]

    @property
    def n_kda(self) -> int:
        return sum(k == KDA for k in self.layers)

    @property
    def n_mla(self) -> int:
        return len(self.layers) - self.n_kda

    @property
    def latent_width(self) -> int:
        """Values a cached position keeps a MLA layer: [c; k_r]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def num_experts_per_tok(self) -> int:  # the name the shared expert layer reads
        return self.num_experts_per_token

    @property
    def expert_offset(self) -> int:
        return self.share.index * self.share.experts_held

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)


Config = KimiLinearConfig  # the name `engine/backbone.py` loads a model file through


# -- weights -----------------------------------------------------------------


def _key(cfg: KimiLinearConfig, name: str):
    return jax.random.fold_in(
        jax.random.PRNGKey(cfg.weights_seed), np.uint32(zlib.crc32(name.encode()))
    )


def _held_f32(x):
    """float32 values that bfloat16 holds: what the references draw too."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def decay_rates(cfg: KimiLinearConfig, name: str):
    """A_log [H] = log U(1, 16) (model file: `assumed`)."""
    u = jax.random.uniform(_key(cfg, name), (cfg.kda_heads,), jnp.float32, 1.0, 16.0)
    return _held_f32(jnp.log(u))


def dt_bias(cfg: KimiLinearConfig, name: str):
    """The inverse softplus of dt, log dt ~ U(log 1e-3, log 1e-1)."""
    width = cfg.kda_heads * cfg.kda_head_dim
    dt = jnp.exp(jax.random.uniform(
        _key(cfg, name), (width,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    return _held_f32(dt + jnp.log(-jnp.expm1(-dt)))


def init_params(cfg: KimiLinearConfig) -> dict:
    """The share's weights. q, k and v of a KDA layer are held side by side
    (one product, one convolution); shared experts are held fused, which is
    their sum exactly."""
    h = cfg.hidden_size
    held = range(cfg.expert_offset, cfg.expert_offset + cfg.share.experts_held)
    layers = []
    for li, kind in enumerate(cfg.layers):
        p = f"layers.{li}."

        def t(name, shape, p=p):
            return tensor(cfg, p + name, shape)

        lp = {"ln1": jnp.ones((h,), jnp.float32), "ln2": jnp.ones((h,), jnp.float32)}
        if kind == KDA:
            d, hd = cfg.kda_head_dim, cfg.kda_heads * cfg.kda_head_dim
            lp.update({
                "wqkv": jnp.concatenate([t(f"kda.{n}", (h, hd)) for n in "qkv"], axis=1),
                "conv": jnp.concatenate(
                    [t(f"kda.conv_{n}", (cfg.conv_taps, hd)) for n in "qkv"], axis=1),
                "f_down": t("kda.f_down", (h, d)), "f_up": t("kda.f_up", (d, hd)),
                "dt_bias": dt_bias(cfg, p + "kda.dt_bias"),
                "a_log": decay_rates(cfg, p + "kda.a_log"),
                "beta": t("kda.beta", (h, cfg.kda_heads)),
                "g_down": t("kda.g_down", (h, d)), "g_up": t("kda.g_up", (d, hd)),
                "o_norm": jnp.ones((d,), jnp.float32),
                "wo": t("kda.o", (hd, h)),
            })
        else:
            heads, lat = cfg.num_attention_heads, cfg.kv_lora_rank
            nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
            lp.update({
                "wq": t("mla.q", (h, heads * (nope + rope))),
                "wkva": t("mla.kva", (h, lat + rope)),
                "kv_norm": jnp.ones((lat,), jnp.float32),
                "wkvb": t("mla.kvb", (lat, heads * (nope + dv))),
                "wo": t("mla.o", (heads * dv, h)),
            })
        if li < cfg.first_k_dense_replace:
            w = cfg.intermediate_size
            lp.update({"dg": t("dense.gate", (h, w)), "du": t("dense.up", (h, w)),
                       "dd": t("dense.down", (w, h))})
        else:
            w, shared = cfg.moe_intermediate_size, range(cfg.num_shared_experts)
            lp.update({
                "router": t("router", (h, cfg.num_experts)),
                "router_bias": t("router_bias", (cfg.num_experts,)).astype(jnp.float32),
                "eg": jnp.stack([t(f"experts.{e}.gate", (h, w)) for e in held]),
                "eu": jnp.stack([t(f"experts.{e}.up", (h, w)) for e in held]),
                "ed": jnp.stack([t(f"experts.{e}.down", (w, h)) for e in held]),
                "sg": jnp.concatenate([t(f"shared.{j}.gate", (h, w)) for j in shared], axis=1),
                "su": jnp.concatenate([t(f"shared.{j}.up", (h, w)) for j in shared], axis=1),
                "sd": jnp.concatenate([t(f"shared.{j}.down", (w, h)) for j in shared], axis=0),
            })
        layers.append(lp)
    rows = cfg.share.vocab_rows_held
    return {
        "embed": tensor(cfg, f"embed.{cfg.share.index}", (rows, h)),
        "head": tensor(cfg, f"head.{cfg.share.index}", (h, rows)),
        "ln_f": jnp.ones((h,), jnp.float32),
        "layers": layers,
    }


# -- the cache row -------------------------------------------------------------


def prefill_chunk_len(cfg: KimiLinearConfig, ctx_cap: int) -> int:
    """Tokens a prefill chunk holds: the row's capacity in the fewest equal
    chunks of whole query blocks none longer than PREFILL_CHUNK, so that
    every dispatch of a history is one shape, one compile (10,112 positions:
    4 of 2,528); a capacity that does not divide so goes in chunks of
    PREFILL_CHUNK and a shorter last one."""
    k = -(-ctx_cap // PREFILL_CHUNK)
    while k * Q_BLOCK <= ctx_cap:
        if ctx_cap % (k * Q_BLOCK) == 0:
            return ctx_cap // k
        k += 1
    return min(PREFILL_CHUNK, ctx_cap)


def prefill_seqs(cfg: KimiLinearConfig, ctx_cap: int) -> int:
    """Sequences a prefill dispatch holds."""
    return PREFILL_SEQS


def cache_template(cfg: KimiLinearConfig, ctx_cap: int) -> dict:
    """One arena row: the recurrence's state, the convolution's tail and
    the latents of a sequence's cached prefix."""
    sd = jax.ShapeDtypeStruct
    hd, d = cfg.kda_heads * cfg.kda_head_dim, cfg.kda_head_dim
    return {
        "S": sd((cfg.n_kda, cfg.kda_heads, d, d), jnp.float32),
        "conv": sd((cfg.n_kda, cfg.conv_taps - 1, 3 * hd), cfg.dtype),
        # the latents [c; k_r] as two leaves, k_r with its positions last:
        # the chip lays a leaf out with the axis that is a multiple of 128
        # on its lanes whatever the shape says (576 and 64 are none), and a
        # program that wants it otherwise copies the whole leaf in and out
        # of every dispatch
        "c": sd((cfg.n_mla, ctx_cap, cfg.kv_lora_rank), cfg.dtype),
        "kr": sd((cfg.n_mla, cfg.qk_rope_head_dim, ctx_cap), cfg.dtype),
        "n": sd((), jnp.int32),
        "last": sd((), jnp.int32),
        "scale": sd((), jnp.float32),
    }


def state_bytes(cfg: KimiLinearConfig) -> int:
    """Bytes of recurrent state and convolution tail one sequence's window
    dispatch reads."""
    d = cfg.kda_head_dim
    tail = (cfg.conv_taps - 1) * 3 * cfg.kda_heads * d * cfg.dtype.itemsize
    return cfg.n_kda * (cfg.kda_heads * d * d * 4 + tail)


def fused_window_kda(cfg: KimiLinearConfig, tokens: int, write_at=None) -> bool:
    """Whether a dispatch of `tokens` a sequence runs its KDA layers' chunk
    algebra through the fused kernel (`kimi_kda.fused_kda_rows`) or through
    `kda_chunks`: decided by the backend, the program's kind (`write_at`: a
    prefill chunk's, which writes the state back) and the shapes alone,
    once for the whole program. The detector counts `fused_kda_tokens` by
    the same call."""
    return fused_applies(cfg.kda_head_dim, tokens, write_at)


WINDOW_COUNTERS = ("latent_positions", "state_bytes_read", "fused_kda_tokens",
                   "sorted_moe_tokens")


def window_counters(cfg: KimiLinearConfig, ctx_cap: int, valid, attended) -> dict:
    """What one window dispatch adds to the detector's counters beside the
    tokens it scored (`valid` [S, W] real points): `fused_kda_tokens`, the
    tokens of a dispatch whose KDA layers took the fused kernel;
    `sorted_moe_tokens`, those whose expert rows came back to them by the
    inverse gather of `routed_experts` (`sorted_combine`);
    `latent_positions`, the
    positions a MLA layer's queries attended to (every cached one, and the
    window's own up to the token), summed over tokens: `attended` [S], what
    `score_window` itself counted under its softmax's masks over all its
    MLA layers, so it falls if the program ever attends to less;
    `state_bytes_read`, the float32 state and convolution tails the
    dispatch's real sequences own: the request's size, not read off the
    program."""
    valid = np.asarray(valid, bool)
    return {
        "latent_positions": int(np.asarray(attended, np.int64).sum()) // max(cfg.n_mla, 1),
        "state_bytes_read": int(valid.any(axis=1).sum()) * state_bytes(cfg),
        "fused_kda_tokens": int(valid.sum()) if fused_window_kda(cfg, valid.shape[1]) else 0,
        "sorted_moe_tokens": int(valid.sum()) if sorted_combine(cfg) else 0,
    }


# -- pieces --------------------------------------------------------------------


def rms_norm(x, gain, eps: float):
    xf = x.astype(jnp.float32)
    return (xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps) * gain).astype(x.dtype)


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def gated_ffn(x, gate, up, down):
    """(SiLU(x W_g) * x W_u) W_d, float32."""
    mid = (jax.nn.silu(_dot(x, gate)) * _dot(x, up)).astype(x.dtype)
    return _dot(mid, down)


def route(cfg: KimiLinearConfig, lp: dict, xn):
    """Sigmoid selection over ALL experts: top-k of s + bias; the weights
    are s's own, renormalised over the chosen, times the scaling factor."""
    s = router_scores(lp["router"], xn)
    _, top_i = lax.top_k(s + lp["router_bias"], cfg.num_experts_per_token)
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    # foremast: ignore[jit-hygiene] — a static field of the config
    if cfg.moe_renormalize:
        top_s = top_s / top_s.sum(axis=-1, keepdims=True)
    return top_i, top_s * cfg.routed_scaling_factor


# -- KDA -----------------------------------------------------------------------


def _hdot(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def _pair_products(x, k, g_cum, sub: int, inclusive: bool):
    """P[t, i] = sum_c x[t, c] k[i, c] exp(G[t, c] - G[i, c]) for i < t (i <=
    t: `inclusive`), 0 elsewhere; x, k, g_cum [..., C, d], C a multiple of
    `sub`. Every exponent formed is <= 0: no e^(-G), so a channel whose
    decay sums past float32's exponent range inside a chunk underflows to
    the 0 it is, and never overflows."""
    *lead, c, d = x.shape
    nb = c // sub
    xs, ks, gs = (a.reshape(*lead, nb, sub, d) for a in (x, k, g_cum))
    # pairs of different sub-chunks, through the later one's anchor: the
    # sum of g up to the sub-chunk's first token
    anchor = jnp.concatenate(
        [jnp.zeros_like(gs[..., :1, -1, :]), gs[..., :-1, -1, :]], axis=-2)  # [..., nb, d]
    left = xs * jnp.exp(gs - anchor[..., None, :])
    earlier = (jnp.arange(c)[None, :] < (jnp.arange(nb) * sub)[:, None])[..., None]  # [nb, C, 1]
    reach = jnp.where(earlier, anchor[..., :, None, :] - g_cum[..., None, :, :], -jnp.inf)
    right = k[..., None, :, :] * jnp.exp(reach)  # [..., nb, C, d]; 0 from the sub-chunk on
    off = _hdot("...ntd,...nid->...nti", left, right).reshape(*lead, c, c)
    # pairs inside a sub-chunk: the difference itself
    t_, i_ = jnp.arange(sub)[:, None], jnp.arange(sub)[None, :]
    seen = (i_ <= t_ if inclusive else i_ < t_)[..., None]  # [sub, sub, 1]
    gap = jnp.where(seen, gs[..., :, None, :] - gs[..., None, :, :], -jnp.inf)
    diag = (xs[..., :, None, :] * ks[..., None, :, :] * jnp.exp(gap)).sum(axis=-1)  # [..., nb, sub, sub]
    eye = jnp.eye(nb, dtype=diag.dtype)[:, None, :, None]  # [nb, 1, nb, 1]
    blocks = (diag[..., :, :, None, :] * eye).reshape(*lead, c, c)
    return off + blocks


def _unit_lower_inverse(n):
    """(I + N)^-1 for N strictly lower triangular [..., C, C]: N^C = 0, so
    the inverse is (I - N)(I + N^2)(I + N^4)... in log2(C) products."""
    c = n.shape[-1]
    x = jnp.eye(c, dtype=n.dtype) - n
    p = _hdot("...ij,...jk->...ik", n, n)
    span = 2
    while span < c:
        x = x + _hdot("...ij,...jk->...ik", x, p)
        span *= 2
        # foremast: ignore[jit-hygiene] — `span` and `c` are Python ints
        if span < c:
            p = _hdot("...ij,...jk->...ik", p, p)
    return x


def kda_chunks(q, k, v, g, beta, s0, chunk: int = KDA_CHUNK, sub: int = KDA_SUB):
    """The delta rule with per-channel decay, chunkwise. q, k [B, T, H, d]
    (normalised), v [B, T, H, dv], g [B, T, H, d] (log decay <= 0; 0 at a
    padded token), beta [B, T, H] (0 at a padded token), s0 [B, H, d, dv];
    all float32 -> (o [B, T, H, dv], the state after the T tokens)."""
    b, t, h, d = q.shape
    dv = v.shape[-1]
    sub = min(sub, -(-t // 8) * 8)
    c = min(chunk, -(-t // sub) * sub)
    tp = -(-t // c) * c
    n = tp // c

    def chunks(a):  # [B, T, H, ...] -> [B, H, N, C, ...]
        a = jnp.pad(a, ((0, 0), (0, tp - t)) + ((0, 0),) * (a.ndim - 2))
        a = jnp.moveaxis(a, 2, 1)
        return a.reshape(b, h, n, c, *a.shape[3:])

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    g_cum = jnp.cumsum(g, axis=-2)
    a_kk = _pair_products(k, k, g_cum, sub, inclusive=False)
    a_qk = _pair_products(q, k, g_cum, sub, inclusive=True)
    solve = _unit_lower_inverse(beta[..., None] * a_kk) * beta[..., None, :]  # (I + B A)^-1 B
    decayed = jnp.exp(g_cum)
    w = _hdot("...ti,...id->...td", solve, k * decayed)  # what S_0 takes off each correction
    u0 = _hdot("...ti,...id->...td", solve, v)
    q_in = q * decayed
    g_end = g_cum[..., -1:, :]
    k_out = k * jnp.exp(g_end - g_cum)
    carry_decay = jnp.exp(g_end[..., 0, :])[..., None]  # [B, H, N, d, 1]

    def step(s, x):
        w_c, u_c, q_c, a_c, k_c, dec_c = x
        u = u_c - _hdot("bhtk,bhkv->bhtv", w_c, s)
        o = _hdot("bhtk,bhkv->bhtv", q_c, s) + _hdot("bhti,bhiv->bhtv", a_c, u)
        return dec_c * s + _hdot("bhtk,bhtv->bhkv", k_c, u), o

    per_chunk = tuple(jnp.moveaxis(a, 2, 0) for a in (w, u0, q_in, a_qk, k_out, carry_decay))
    # foremast: ignore[jit-hygiene] — `n` is a Python int read from the shapes
    if n == 1:
        s_end, o = step(s0, tuple(a[0] for a in per_chunk))
        o = o[:, :, None]
    else:
        s_end, o = lax.scan(step, s0, per_chunk)
        o = jnp.moveaxis(o, 0, 2)
    o = jnp.moveaxis(o.reshape(b, h, tp, dv), 1, 2)[:, :t]
    return o, s_end


def kda_mix(cfg: KimiLinearConfig, lp: dict, xn, valid, tail, s0, in_rows=None):
    """One KDA layer's mixer over xn [B, T, h] (`valid` [B, T]) as the
    continuation of the carried convolution tail [B, taps - 1, 3 H d] and
    state s0 [B, H, d, d] -> (Mix [B, T, h] float32, the state after the
    valid tokens, [tail; the chunk's projected inputs] [B, taps - 1 + T, 3 H d]).
    With `in_rows` (the arena leaf `S`, rows [B], the layer's slot: a window
    dispatch where `fused_window_kda` holds) there is no s0: the fused
    kernel reads each state in its row, and no state comes back."""
    b, t, _ = xn.shape
    heads, d = cfg.kda_heads, cfg.kda_head_dim
    hd = heads * d
    with jax.named_scope("kda_conv"):
        proj = _dot(xn, lp["wqkv"]).astype(xn.dtype)
        carried = jnp.concatenate([tail, proj], axis=1)
        taps = lp["conv"].astype(jnp.float32)
        conv = sum(
            taps[j] * carried[:, j : j + t].astype(jnp.float32) for j in range(cfg.conv_taps)
        )
        act = jax.nn.silu(conv)

    def gates(split_heads: bool):
        """(g, beta, gate); g [B, T, H, d] or, for the kernel, [B, T, H d]."""
        dt = _dot(_dot(xn, lp["f_down"]).astype(xn.dtype), lp["f_up"]) + lp["dt_bias"]
        # foremast: ignore[jit-hygiene] — the program's path, a Python value
        if split_heads:
            g = -jnp.exp(lp["a_log"])[:, None] * jax.nn.softplus(dt).reshape(b, t, heads, d)
            g = jnp.where(valid[..., None, None], g, 0.0)
        else:
            g = -jnp.repeat(jnp.exp(lp["a_log"]), d) * jax.nn.softplus(dt)
            g = jnp.where(valid[..., None], g, 0.0)
        beta = jnp.where(valid[..., None], jax.nn.sigmoid(_dot(xn, lp["beta"])), 0.0)
        gate = jax.nn.sigmoid(_dot(_dot(xn, lp["g_down"]).astype(xn.dtype), lp["g_up"]))
        return g, beta, gate

    # foremast: ignore[jit-hygiene] — the program's path, a Python value
    if in_rows is not None:
        # the kernel's way: no axis of heads is ever split off (on the chip
        # [B, T, H d] -> [B, T, H, d] is a copy into another tiling), and the
        # per-head norms of q, k and o are taken inside the kernel
        leaf, rows, slot = in_rows
        with jax.named_scope("kda_gates"):
            g, beta, gate = gates(split_heads=False)
        with jax.named_scope("kda_chunk"):
            o = fused_kda_rows(leaf, rows, act, g, beta, slot=slot, heads=heads, qk_norm=True,
                               o_eps=cfg.rms_norm_eps)
            o = o * jnp.tile(lp["o_norm"], heads) * gate
        return _dot(o.astype(xn.dtype), lp["wo"]), None, carried
    with jax.named_scope("kda_conv"):
        act = act.reshape(b, t, 3, heads, d)

        def l2(x):
            return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

        q, k, v = l2(act[:, :, 0]) * d ** -0.5, l2(act[:, :, 1]), act[:, :, 2]
    with jax.named_scope("kda_gates"):
        g, beta, gate = gates(split_heads=True)
    with jax.named_scope("kda_chunk"):
        o, s_end = kda_chunks(q, k, v, g, beta, s0)
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.rms_norm_eps)
        o = (o * lp["o_norm"]).reshape(b, t, hd) * gate
    return _dot(o.astype(xn.dtype), lp["wo"]), s_end, carried


# -- MLA -----------------------------------------------------------------------


def mla_project(cfg: KimiLinearConfig, lp: dict, xn):
    """xn [B, T, h] -> (absorbed queries q' [B, T, H, latent + rope], the
    new positions' latents [c; k_r] [B, T, latent + rope]), both in the
    compute dtype."""
    b, t, _ = xn.shape
    heads, lat = cfg.num_attention_heads, cfg.kv_lora_rank
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = _dot(xn, lp["wq"]).astype(xn.dtype).reshape(b, t, heads, nope + rope)
    kva = _dot(xn, lp["wkva"])
    c = rms_norm(kva[..., :lat], lp["kv_norm"], cfg.rms_norm_eps)
    latents = jnp.concatenate([c, kva[..., lat:]], axis=-1).astype(xn.dtype)
    w_kb = lp["wkvb"].reshape(lat, heads, -1)[..., :nope]
    q_lat = jnp.einsum("bthn,lhn->bthl", q[..., :nope], w_kb,
                       preferred_element_type=jnp.float32).astype(xn.dtype)
    return jnp.concatenate([q_lat, q[..., nope:]], axis=-1), latents


def latent_attend(cfg: KimiLinearConfig, q, at_q, c_c, kr_c, n_c, lat_n, valid_n):
    """One sequence, one layer: absorbed queries q [Tq, H, L + r], the
    at_q-th .. of the dispatch's own positions, over the cached latents c_c
    [Ck, L] and kr_c [r, Ck] (as the row holds them; the first n_c positions
    are real) and the dispatch's own lat_n [Tn, L + r] (`valid_n`; causal),
    under ONE softmax taken in two parts. -> (sum of p c [Tq, H, L] float32,
    the positions the valid queries attended to, summed: int32)."""
    tq, heads, width = q.shape
    lat = cfg.kv_lora_rank
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    qf = q.reshape(tq * heads, width)
    sc = jnp.einsum("qd,kd->qk", qf[:, :lat], c_c, preferred_element_type=jnp.float32)
    sc = (sc + _dot(qf[:, lat:], kr_c)) * scale
    sn = jnp.einsum("qd,kd->qk", qf, lat_n, preferred_element_type=jnp.float32) * scale
    seen_c = jnp.arange(c_c.shape[0]) < n_c
    own = at_q + jnp.arange(tq)
    seen_n = (jnp.arange(lat_n.shape[0])[None, :] <= own[:, None]) & valid_n[None, :]
    sc = jnp.where(seen_c[None, :], sc, MASKED)
    sn = jnp.where(jnp.repeat(seen_n, heads, axis=0), sn, MASKED)
    m = jnp.maximum(sc.max(axis=-1), sn.max(axis=-1))[:, None]
    ec, en = jnp.exp(sc - m), jnp.exp(sn - m)
    den = ec.sum(axis=-1) + en.sum(axis=-1)
    out = _dot(ec.astype(q.dtype), c_c) + _dot(en.astype(q.dtype), lat_n[:, :lat])
    attended = jnp.sum(
        jnp.where(lax.dynamic_slice_in_dim(valid_n, at_q, tq), seen_c.sum() + seen_n.sum(axis=1), 0),
        dtype=jnp.int32)
    return (out / den[:, None]).reshape(tq, heads, lat), attended


def mla_output(cfg: KimiLinearConfig, lp: dict, summed):
    """W_vb,h on each head's sum of latents, then W_o: [..., T, H, L] -> [..., T, h]."""
    heads, lat = cfg.num_attention_heads, cfg.kv_lora_rank
    w_vb = lp["wkvb"].reshape(lat, heads, -1)[..., cfg.qk_nope_head_dim:]
    o = jnp.einsum("...thl,lhv->...thv", summed.astype(lp["wo"].dtype), w_vb,
                   preferred_element_type=jnp.float32).astype(lp["wo"].dtype)
    return _dot(o.reshape(*o.shape[:-2], -1), lp["wo"])


def _row(leaf, row, slot: int):
    """One row's slice of one layer's slot of an arena leaf, where it lies."""
    return lax.dynamic_slice(leaf, (row, slot) + (0,) * (leaf.ndim - 2),
                             (1, 1) + leaf.shape[2:])[0, 0]


def _write_rows(leaf, slot: int, rows, active, new):
    """new[b] into row rows[b] of one layer's slot, a sequence at a time
    in the dispatch's order; a sequence that is not `active` writes back
    what its row holds (its row may be another's: a short group is filled
    up with such). The leaf rides the loop's carry: updated in place."""
    def step(b, leaf):
        idx = (rows[b], slot) + (0,) * (leaf.ndim - 2)
        old = lax.dynamic_slice(leaf, idx, (1, 1) + leaf.shape[2:])
        return lax.dynamic_update_slice(leaf, jnp.where(active[b], new[b][None, None], old), idx)

    return lax.fori_loop(0, rows.shape[0], step, leaf)


def _mla_rows(cfg: KimiLinearConfig, leaves, slot: int, rows, q, latents, valid, cached_n,
              write_at):
    """One MLA layer's attention for every sequence of the dispatch, each
    against its own row's latents (`leaves`: the arena's `c` and `kr`) read
    where they lie. With `write_at` (a prefill chunk's first position) the
    sequence's new latents are then written into its row; a sequence with
    no valid token writes nothing (its row may be another's: a short group
    is filled up with such). The leaves ride the loop's carry: read, then
    updated in place. -> (sum of p c [S, T, H, L], positions attended [S],
    the leaves)."""
    s, t = q.shape[:2]
    lat = cfg.kv_lora_rank

    def one(c_row, kr_row, qs, ls, ok, n_c):
        def block(a):
            return latent_attend(cfg, a[0], a[1], c_row, kr_row, n_c, ls, ok)

        # foremast: ignore[jit-hygiene] — shapes, read while tracing
        if t <= Q_BLOCK or t % Q_BLOCK:
            return block((qs, 0))
        # a prefill chunk's scores are never held whole
        nb = t // Q_BLOCK
        out, attended = lax.map(
            block, (qs.reshape(nb, Q_BLOCK, *qs.shape[1:]), jnp.arange(nb) * Q_BLOCK))
        return out.reshape(t, *out.shape[2:]), attended.sum()

    # foremast: ignore[jit-hygiene] — the program's kind, a Python value
    if write_at is None:
        def read(args):
            row, *rest = args
            return one(_row(leaves[0], row, slot), _row(leaves[1], row, slot), *rest)

        return *lax.map(read, (rows, q, latents, valid, cached_n)), leaves

    def step(b, carry):
        c_leaf, kr_leaf, out, seen = carry
        take = lambda x: lax.dynamic_index_in_dim(x, b, 0, keepdims=False)  # noqa: E731
        row, ls, ok = take(rows), take(latents), take(valid)
        summed, attended = one(_row(c_leaf, row, slot), _row(kr_leaf, row, slot), take(q), ls,
                               ok, take(cached_n))
        written = []
        for leaf, new, idx in ((c_leaf, ls[:, :lat], (row, slot, write_at, 0)),
                               (kr_leaf, ls[:, lat:].T, (row, slot, 0, write_at))):
            old = lax.dynamic_slice(leaf, idx, (1, 1, *new.shape))
            written.append(
                lax.dynamic_update_slice(leaf, jnp.where(ok.any(), new[None, None], old), idx))
        return (written[0], written[1], lax.dynamic_update_slice(out, summed[None], (b, 0, 0, 0)),
                seen.at[b].set(attended))

    out = jnp.zeros((s, t, cfg.num_attention_heads, lat), jnp.float32)
    c_leaf, kr_leaf, out, seen = lax.fori_loop(
        0, s, step, (*leaves, out, jnp.zeros(s, jnp.int32)))
    return out, seen, (c_leaf, kr_leaf)


# -- the layers ------------------------------------------------------------------


def _layer_slots(cfg: KimiLinearConfig):
    """(layer kind, its index among the held layers of its own kind) a layer."""
    seen = {KDA: 0, MLA: 0}
    out = []
    for kind in cfg.layers:
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


def _forward(cfg: KimiLinearConfig, params, state, rows, ids, valid, cached_n, write_at=None):
    """The layers held, over ids [S, T] (`valid`: real tokens), each
    sequence as the continuation of arena row rows[s], which holds
    `cached_n[s]` positions. With `write_at` (a prefill chunk's first
    position) each layer's advanced state, tail and new latents go into the
    rows of the sequences that had a valid token and the updated state is
    returned; the last layer's FFN, which would feed nothing, is then left
    out. -> (x [S, T, h], assignments a held expert received [held],
    assignments routed to a held expert and not multiplied (0), positions
    the MLA layers' queries attended to [S], state)."""
    s, t = ids.shape
    x = params["embed"][ids]
    counts = jnp.zeros(cfg.share.experts_held, jnp.int32)
    dropped = jnp.int32(0)
    attended = jnp.zeros(s, jnp.int32)
    slots = _layer_slots(cfg)
    keep = cfg.conv_taps - 1
    # a row recycled from another sequence still holds that one's state:
    # a history's first chunk starts from nothing
    carried = None if write_at is None else write_at > 0
    active = valid.any(axis=1)
    done = jnp.sum(valid, axis=1, dtype=jnp.int32)
    for li, (lp, (kind, slot)) in enumerate(zip(params["layers"], slots)):
        xn = rms_norm(x, lp["ln1"], cfg.rms_norm_eps)
        # foremast: ignore[jit-hygiene] — the layer's kind, read from the static config
        if kind == KDA:
            # foremast: ignore[jit-hygiene] — backend, program kind and shapes: Python values
            if fused_window_kda(cfg, t, write_at):
                # the kernel reads each row's state where it lies: no batch of it is formed
                mix, s_end, seen = kda_mix(
                    cfg, lp, xn, valid, state["conv"][rows, slot], None,
                    in_rows=(state["S"], rows, slot))
            else:
                s0, tail = state["S"][rows, slot], state["conv"][rows, slot]
                # foremast: ignore[jit-hygiene] — the program's kind, a Python value
                if carried is not None:
                    s0, tail = jnp.where(carried, s0, 0.0), jnp.where(carried, tail, 0)
                mix, s_end, seen = kda_mix(cfg, lp, xn, valid, tail, s0)
            # foremast: ignore[jit-hygiene]
            if write_at is not None:
                # the tail after the chunk: the last taps - 1 valid positions
                new_tail = jax.vmap(
                    lambda a, at: lax.dynamic_slice_in_dim(a, at, keep, axis=0)
                )(seen, done)
                state = {
                    **state,
                    "S": _write_rows(state["S"], slot, rows, active, s_end),
                    "conv": _write_rows(state["conv"], slot, rows, active, new_tail),
                }
        else:
            q, latents = mla_project(cfg, lp, xn)
            with jax.named_scope("mla_latent_attn"):
                summed, seen_l, (c_leaf, kr_leaf) = _mla_rows(
                    cfg, (state["c"], state["kr"]), slot, rows, q, latents, valid, cached_n,
                    write_at)
                mix = mla_output(cfg, lp, summed)
            attended = attended + seen_l
            state = {**state, "c": c_leaf, "kr": kr_leaf}
        x = (x.astype(jnp.float32) + mix).astype(x.dtype)
        # foremast: ignore[jit-hygiene] — `li` counts the Python loop
        if write_at is not None and li == len(slots) - 1:
            break
        xn = rms_norm(x, lp["ln2"], cfg.rms_norm_eps)
        flat = xn.reshape(s * t, -1)
        # foremast: ignore[jit-hygiene] — `li` against a static field
        if li < cfg.first_k_dense_replace:
            with jax.named_scope("dense_ffn"):
                ffn = gated_ffn(flat, lp["dg"], lp["du"], lp["dd"])
        else:
            routed, sizes, multiplied = routed_experts(
                cfg, lp, flat, valid.reshape(s * t), route=route)
            with jax.named_scope("moe_shared"):
                ffn = routed + gated_ffn(flat, lp["sg"], lp["su"], lp["sd"])
            counts = counts + sizes
            dropped = dropped + sizes.sum() - multiplied
        x = (x.astype(jnp.float32) + ffn.reshape(x.shape)).astype(x.dtype)
    return x, counts, dropped, attended, state


# -- the two programs ----------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def prefill_chunk(cfg: KimiLinearConfig, params, state, rows, ids, start, n):
    """One chunk of a batch's histories into their rows: ids [B, L] are the
    tokens at positions start .. start + L - 1, n [B] the positions each
    sequence caches in all (its history but the last point). The chunk
    continues what the rows hold (the state and tail after `start`
    positions, the latents of the positions below `start`; nothing where
    start is 0), then the advanced state, the new tail and the chunk's
    latents are written where they belong. The state is donated: the
    arena's buffers are updated in place.
    -> (state, assignments a held expert received [held])."""
    b, length = ids.shape
    pos = jnp.broadcast_to(start + jnp.arange(length, dtype=jnp.int32), (b, length))
    valid = pos < n[:, None]
    _, counts, _, _, state = _forward(
        cfg, params, state, rows, ids, valid, jnp.minimum(start, n), write_at=start
    )
    return state, counts


def head_scores(cfg: KimiLinearConfig, params, x, ids, with_logits: bool = False):
    """-log p(id) of each token from the final hidden x [N, h], over the
    held vocabulary rows. The logits [N, V] are never held whole: a block
    of tokens' logits is reduced to its scores before the next is formed.
    -> scores [N] float32 (and, `with_logits`, the logits)."""
    n = x.shape[0]
    xf = rms_norm(x, params["ln_f"], cfg.rms_norm_eps)

    def block(a):
        xb, idb = a
        logits = _dot(xb, params["head"])
        picked = jnp.take_along_axis(logits, idb[:, None], axis=1)[:, 0]
        return jax.nn.logsumexp(logits, axis=-1) - picked, logits

    # foremast: ignore[jit-hygiene] — shapes and a static flag, read while tracing
    if with_logits or n <= HEAD_BLOCK or n % HEAD_BLOCK:
        scores, logits = block((xf, ids))
        return (scores, logits) if with_logits else scores
    nb = n // HEAD_BLOCK
    scores = lax.map(lambda a: block(a)[0], (xf.reshape(nb, HEAD_BLOCK, -1), ids.reshape(nb, -1)))
    return scores.reshape(n)


@partial(jax.jit, static_argnames=("cfg", "with_logits"))
def score_window(cfg: KimiLinearConfig, params, state, rows, ids, valid, with_logits=False):
    """score_t = -log p(id_t | history, id_<t) for the windows ids [S, W]
    (`valid` [S, W]: real points) of the sequences cached in `rows` [S].
    The program is fed [the row's last history id; the window's ids but the
    last] and runs them as the continuation of each row's state, tail and
    latents; the state is read, never written, and what the window made of
    it is thrown away.
    -> (scores [S, W] float32, assignments a held expert received [held],
    assignments dropped: 0, the positions each sequence's valid tokens
    attended to in the MLA layers [S] int32: `window_counters`' `attended`)
    and, `with_logits`, the logits [S, W, vocabulary rows held]."""
    s, w = ids.shape
    inp = jnp.concatenate([state["last"][rows][:, None], ids[:, :-1]], axis=1)
    x, counts, dropped, attended, _ = _forward(
        cfg, params, state, rows, inp, valid, state["n"][rows])
    with jax.named_scope("lm_head"):
        out = head_scores(cfg, params, x.reshape(s * w, -1), ids.reshape(s * w), with_logits)
    # foremast: ignore[jit-hygiene] — a static flag
    if with_logits:
        return out[0].reshape(s, w), counts, dropped, attended, out[1].reshape(s, w, -1)
    return out.reshape(s, w), counts, dropped, attended
