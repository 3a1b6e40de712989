"""A shared sequence backbone as a detector: the Cohere2-MoE block
(`model_type: cohere2_moe`, e.g. command-a-plus-05-2026) at the widths of a
model file (`models/configs/*.json`: config.json's keys verbatim plus a
`share` block), scoring a monitor's current window as the continuation of
its cached 7-day history. docs/backbone.md is the operator's page.

The block, for layer l and a token at position p (h hidden, LN = LayerNorm
without bias, statistics in float32):

    x' = LN_l(x);  x <- x + Attn_l(x') + FFN_l(x')         (parallel block)

  * Attn: grouped-query attention, no bias, no qk-norm; query head j reads
    key-value head j // (heads / kv heads); scores q.k / sqrt(head_dim),
    softmax in float32. `sliding_attention` layers rotate q and k by RoPE
    (`rope_gptj`: interleaved pairs (2m, 2m+1), every dim) and see keys with
    0 <= p_i - p_j < sliding_window; `full_attention` layers carry NO
    positional embedding and see every earlier key.
  * FFN: s = sigmoid(x' W_r) over all `num_experts`; E = its top-k; w_e =
    s_e / sum_E s; FFN = sum_{e in E, held} w_e expert_e(x') + the mean of
    the shared experts, expert(x) = (silu(x W_g) * x W_u) W_d. A process
    holds `share.experts_held` experts from `share.index * experts_held`
    on: the router keeps its published width and top-k, only held experts
    contribute, nothing is dropped and there is no capacity factor. The
    held experts run as a grouped matrix product over the assignments
    sorted by expert, in blocks of rows that each belong to one expert.
  * Head: logits = LN_f(x) E^T logit_scale over the held vocabulary rows
    (tied: E is also the embedding).

The detector. A series is tokenised by mean scaling (Chronos, arXiv:
2403.07815 section 3.1): scale = mean |history| (0 -> 1), id = clip(floor((x
/ scale + 15) / 30 * V), 0, V - 1). Cold, the history is prefilled in chunks
into ONE `TreeArena` row per sequence (`cache_template`): the full layers'
K/V of every position, each sliding layer's K/V of the last
`sliding_window` positions as a ring (slot = position mod ring), the scale,
the count of cached positions n and the last history id, which is NOT
cached: it is the window program's first input. Warm, `score_window` feeds
[last id; the window's ids but the last] at positions n .. n + w - 1 and
reads the rows IN PLACE by row index (no gathered copy of the batch's
caches; rows are never written): score_t = -log p(id_t | history, id_<t).
On a TPU, at widths its tiling meets, the window program's attention is
one fused kernel a layer over the rows where they lie
(`cohere2_attention.fused_attend_rows`; `fused_window_attention` decides,
from the backend and the shapes alone); everywhere else, and in the
prefill always, it is `attend`, a sequence at a time.

Precision: weights, activations and cache in `compute_dtype` (bfloat16);
accumulation, softmax, LN statistics, router and log-softmax in float32.
The plain reference is `models/cohere2_moe_reference.py`.
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

from foremast_tpu.models.cohere2_attention import (
    MASKED,
    cached_positions,
    fused_applies,
    fused_attend_rows,
    visible,
)

SLIDING = "sliding_attention"
FULL = "full_attention"
DEFAULT_MODEL_FILE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "configs",
    "command-a-plus-05-2026.json",
)
MODEL_TYPE = "cohere2_moe"  # the model files this module runs (`engine/backbone.py`)
PREFILL_CHUNK = 2048  # tokens a sequence a prefill dispatch, at most
# sequences a prefill dispatch holds (each up to PREFILL_CHUNK tokens): what
# the prefill's activations leave room for beside 13.9 GB of weights and cache
PREFILL_SEQS = 2
TOKEN_RANGE = 15.0  # a scaled value in [-15, 15) maps onto the vocabulary


@dataclasses.dataclass(frozen=True)
class Share:
    """What one of `chips_sharing_a_layer` chips holds of each layer."""

    chips_sharing_a_layer: int
    index: int
    experts_held: int
    vocab_rows_held: int
    layers_held: int


@dataclasses.dataclass(frozen=True)
class Cohere2MoeConfig:
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int  # width of ONE expert (model file: `assumed`)
    num_experts: int  # the router's width: every published expert
    num_experts_per_tok: int
    num_shared_experts: int
    layer_types: tuple  # the published pattern; `share.layers_held` are run
    sliding_window: int
    rope_theta: float
    layer_norm_eps: float
    logit_scale: float
    vocab_size: int
    share: Share
    weights_seed: int = 0
    compute_dtype: str = "bfloat16"

    @staticmethod
    def from_dict(d: dict) -> "Cohere2MoeConfig":
        unsupported = {
            "model_type": "cohere2_moe",
            "expert_selection_fn": "sigmoid",
            "hidden_act": "silu",
            "position_embedding_type": "rope_gptj",
            "shared_expert_combination_strategy": "average",
        }
        for key, want in unsupported.items():
            if d.get(key, want) != want:
                raise ValueError(f"model file: {key}={d[key]!r}, only {want!r} is written down")
        for key, want in (
            ("use_parallel_block", True), ("norm_topk_prob", True),
            ("use_qk_norm", False), ("attention_bias", False),
            ("tie_word_embeddings", True), ("first_k_dense_replace", 0),
        ):
            if d.get(key, want) != want:
                raise ValueError(f"model file: {key}={d[key]!r} is not supported")
        share = d.get("share") or {
            "chips_sharing_a_layer": 1, "index": 0,
            "experts_held": d["num_experts"],
            "vocab_rows_held": d["vocab_size"],
            "layers_held": d["num_hidden_layers"],
        }
        return Cohere2MoeConfig(
            hidden_size=int(d["hidden_size"]),
            num_attention_heads=int(d["num_attention_heads"]),
            num_key_value_heads=int(d["num_key_value_heads"]),
            head_dim=int(d["head_dim"]),
            intermediate_size=int(d["intermediate_size"]),
            num_experts=int(d["num_experts"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            num_shared_experts=int(d["num_shared_experts"]),
            layer_types=tuple(d["layer_types"][: int(d["num_hidden_layers"])]),
            sliding_window=int(d["sliding_window"]),
            rope_theta=float(d["rope_theta"]),
            layer_norm_eps=float(d["layer_norm_eps"]),
            logit_scale=float(d["logit_scale"]),
            vocab_size=int(d["vocab_size"]),
            share=Share(**{f.name: int(share[f.name]) for f in dataclasses.fields(Share)}),
            weights_seed=int(d.get("weights_seed", 0)),
            compute_dtype=str(d.get("compute_dtype", "bfloat16")),
        )

    @staticmethod
    def from_file(path: str | None = None) -> "Cohere2MoeConfig":
        with open(path or DEFAULT_MODEL_FILE, encoding="utf-8") as fh:
            return Cohere2MoeConfig.from_dict(json.load(fh))

    def __post_init__(self):
        s = self.share
        if (s.index + 1) * s.experts_held > self.num_experts:
            raise ValueError("share: experts held run past num_experts")
        if (s.index + 1) * s.vocab_rows_held > self.vocab_size:
            raise ValueError("share: vocabulary rows held run past vocab_size")
        if not 0 < s.layers_held <= len(self.layer_types):
            raise ValueError("share: layers_held outside the published depth")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads have to be a multiple of key-value heads")

    @property
    def layers(self) -> tuple:
        return self.layer_types[: self.share.layers_held]

    @property
    def n_full(self) -> int:
        return sum(t == FULL for t in self.layers)

    @property
    def n_sliding(self) -> int:
        return len(self.layers) - self.n_full

    @property
    def group(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def expert_offset(self) -> int:
        return self.share.index * self.share.experts_held

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)


Config = Cohere2MoeConfig  # the name `engine/backbone.py` loads a model file through


# -- weights -----------------------------------------------------------------


@partial(jax.jit, static_argnames=("shape", "dtype"))
def _draw(key, shape, dtype):
    drawn = 0.02 * jax.random.normal(key, shape, jnp.float32)
    return drawn.astype(jnp.bfloat16).astype(dtype)


def tensor(cfg: Cohere2MoeConfig, name: str, shape: tuple):
    """One named weight: N(0, 0.02^2) from fold_in(PRNGKey(weights_seed),
    crc32(name)), rounded to bfloat16 (and held in the compute dtype). The name, not the place in
    a stack, keys the draw: expert e is the same tensor whichever share
    holds it."""
    key = jax.random.fold_in(
        jax.random.PRNGKey(cfg.weights_seed), np.uint32(zlib.crc32(name.encode()))
    )
    return _draw(key, tuple(shape), cfg.compute_dtype)


def init_params(cfg: Cohere2MoeConfig) -> dict:
    """The share's weights. Shared experts are held fused: gate and up
    concatenated over the experts' widths, down stacked the same way, which
    is their sum exactly."""
    h, w, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    hq, hkv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
    held = range(cfg.expert_offset, cfg.expert_offset + cfg.share.experts_held)
    shared = range(cfg.num_shared_experts)
    layers = []
    for li in range(len(cfg.layers)):
        p = f"layers.{li}."

        def t(name, shape, p=p):
            return tensor(cfg, p + name, shape)

        layers.append({
            "ln": jnp.ones((h,), jnp.float32),
            "wq": t("attn.q", (h, hq)),
            "wk": t("attn.k", (h, hkv)),
            "wv": t("attn.v", (h, hkv)),
            "wo": t("attn.o", (hq, h)),
            "router": t("router", (h, cfg.num_experts)),
            "eg": jnp.stack([t(f"experts.{e}.gate", (h, w)) for e in held]),
            "eu": jnp.stack([t(f"experts.{e}.up", (h, w)) for e in held]),
            "ed": jnp.stack([t(f"experts.{e}.down", (w, h)) for e in held]),
            "sg": jnp.concatenate([t(f"shared.{j}.gate", (h, w)) for j in shared], axis=1),
            "su": jnp.concatenate([t(f"shared.{j}.up", (h, w)) for j in shared], axis=1),
            "sd": jnp.concatenate([t(f"shared.{j}.down", (w, h)) for j in shared], axis=0),
        })
    return {
        "embed": tensor(cfg, f"embed.{cfg.share.index}", (cfg.share.vocab_rows_held, h)),
        "ln_f": jnp.ones((h,), jnp.float32),
        "layers": layers,
    }


# -- tokeniser ---------------------------------------------------------------


def series_scale(history: np.ndarray) -> np.ndarray:
    """mean |history| along the last axis; 0 -> 1."""
    s = np.abs(np.asarray(history, np.float32)).mean(axis=-1, dtype=np.float32)
    return np.where(s > 0, s, np.float32(1.0)).astype(np.float32)


def tokenize(values: np.ndarray, scale: np.ndarray, vocab: int) -> np.ndarray:
    """ids [..., n] int32 of float32 values under a per-series scale [...]."""
    u = np.asarray(values, np.float32) / np.asarray(scale, np.float32)[..., None]
    ids = np.floor((u + np.float32(TOKEN_RANGE)) / np.float32(2 * TOKEN_RANGE) * np.float32(vocab))
    return np.clip(ids, 0, vocab - 1).astype(np.int32)


# -- the cache row -----------------------------------------------------------


def cached_span(cfg, n: int) -> tuple:
    """The history points [first, stop) of n that a row caches: all but the
    last, which is the window program's first input (`last`)."""
    return 0, n - 1


def ring_size(cfg: Cohere2MoeConfig, ctx_cap: int) -> int:
    return min(cfg.sliding_window, ctx_cap)


def prefill_chunk_len(cfg: Cohere2MoeConfig, ctx_cap: int) -> int:
    """Tokens a full prefill chunk holds: it has to divide the ring so that
    a chunk's slots are contiguous."""
    ring = ring_size(cfg, ctx_cap)
    chunk = min(PREFILL_CHUNK, ring)
    if ring % chunk:
        raise ValueError(f"a prefill chunk of {chunk} does not divide the ring of {ring}")
    return chunk


def prefill_seqs(cfg: Cohere2MoeConfig, ctx_cap: int) -> int:
    """Sequences a prefill dispatch holds."""
    return PREFILL_SEQS


def fused_window_attention(cfg: Cohere2MoeConfig, ctx_cap: int, tokens: int) -> bool:
    """Whether `score_window` over windows of `tokens` against rows of
    `ctx_cap` positions attends through the fused kernel
    (`cohere2_attention.fused_attend_rows`) or through `attend`: decided by
    the backend and the shapes alone, once for the whole program. The
    detector counts `fused_attn_tokens` by the same call."""
    return fused_applies(
        cfg.head_dim, cfg.dtype.itemsize, tokens, (ctx_cap, ring_size(cfg, ctx_cap))
    )


WINDOW_COUNTERS = ("fused_attn_tokens", "sorted_moe_tokens")


def window_counters(cfg: Cohere2MoeConfig, ctx_cap: int, valid) -> dict:
    """What one window dispatch adds to the detector's counters beside the
    tokens it scored (`valid` [S, W]): the tokens whose attention took the
    fused kernel, and those whose expert rows came back to them by the
    inverse gather of `routed_experts` (`sorted_combine`)."""
    fused = fused_window_attention(cfg, ctx_cap, valid.shape[1])
    n = int(valid.sum())
    return {"fused_attn_tokens": n if fused else 0,
            "sorted_moe_tokens": n if sorted_combine(cfg) else 0}


def cache_template(cfg: Cohere2MoeConfig, ctx_cap: int) -> dict:
    """One arena row: a sequence's cached prefix, head-major so that one
    head's keys are contiguous."""
    sd = jax.ShapeDtypeStruct
    hkv, d = cfg.num_key_value_heads, cfg.head_dim
    ring = ring_size(cfg, ctx_cap)
    return {
        "kf": sd((cfg.n_full, hkv, ctx_cap, d), cfg.dtype),
        "vf": sd((cfg.n_full, hkv, ctx_cap, d), cfg.dtype),
        "ks": sd((cfg.n_sliding, hkv, ring, d), cfg.dtype),
        "vs": sd((cfg.n_sliding, hkv, ring, d), cfg.dtype),
        "n": sd((), jnp.int32),
        "last": sd((), jnp.int32),
        "scale": sd((), jnp.float32),
    }


# -- the block ---------------------------------------------------------------


def layer_norm(x, gain, eps: float):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps) * gain).astype(x.dtype)


def rope(x, pos, theta: float):
    """`rope_gptj`: x [..., T, H, D] rotated in interleaved pairs (2m, 2m+1)
    by pos [..., T] * theta^(-2m / D), in float32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32)
    x0, x1 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def attend(q, pos_q, kn, vn, pos_n, valid_n, kc, vc, pos_c, valid_c, group: int,
           window: int | None, seen_n=None):
    """One sequence, one layer: queries q [Tq, Hq, D] at pos_q [Tq] over
    the cached keys kc/vc [Hkv, Ck, D] (positions pos_c, `valid_c`) and the
    dispatch's own kn/vn [Tn, Hkv, D] (positions pos_n, `valid_n`), under
    ONE softmax taken in two parts, so that neither the keys nor the scores
    are concatenated. `seen_n` [Tq, Tn], where given, says which of the
    dispatch's own keys a query sees in place of their positions (a
    block-diffusion dispatch: `sdar_moe`). -> [Tq, Hq * D]."""
    tq, hq, d = q.shape
    hkv = hq // group
    qh = q.reshape(tq, hkv, group, d).transpose(1, 2, 0, 3).reshape(hkv, group * tq, d)
    scale = d ** -0.5
    sc = jnp.einsum("hqd,hkd->hqk", qh, kc, preferred_element_type=jnp.float32) * scale
    sn = jnp.einsum("hqd,khd->hqk", qh, kn, preferred_element_type=jnp.float32) * scale
    # rows of qh run (g, t): the masks repeat over g
    seen_c = visible(pos_q[:, None], pos_c[None, :], valid_c[None, :], window)
    # foremast: ignore[jit-hygiene] — None or an array: decided while tracing
    if seen_n is None:
        seen_n = visible(pos_q[:, None], pos_n[None, :], valid_n[None, :], window)
    sc = jnp.where(jnp.tile(seen_c, (group, 1)), sc, MASKED)
    sn = jnp.where(jnp.tile(seen_n, (group, 1)), sn, MASKED)
    m = jnp.maximum(sc.max(axis=-1), sn.max(axis=-1))[..., None]
    ec, en = jnp.exp(sc - m), jnp.exp(sn - m)
    den = ec.sum(axis=-1) + en.sum(axis=-1)
    out = jnp.einsum("hqk,hkd->hqd", ec.astype(q.dtype), vc, preferred_element_type=jnp.float32)
    out += jnp.einsum("hqk,khd->hqd", en.astype(q.dtype), vn, preferred_element_type=jnp.float32)
    out = out / den[..., None]
    return out.reshape(hkv, group, tq, d).transpose(2, 0, 1, 3).reshape(tq, hq * d).astype(q.dtype)


def router_scores(router, xn):
    """sigmoid(xn W_r) over ALL experts [T, experts], float32."""
    logits = jnp.dot(
        xn.astype(jnp.float32), router.astype(jnp.float32), precision=lax.Precision.HIGHEST
    )
    return jax.nn.sigmoid(logits)


def route(cfg: Cohere2MoeConfig, lp: dict, xn):
    """Sigmoid selection over ALL experts: (top-k expert ids [T, k], their
    weights s_e / sum_E s [T, k]) in float32."""
    top_s, top_i = lax.top_k(router_scores(lp["router"], xn), cfg.num_experts_per_tok)
    return top_i, top_s / top_s.sum(axis=-1, keepdims=True)


def _block_rows(cfg, tokens: int) -> int:
    """Rows of one block of the grouped product: about what one expert is
    assigned when routing is even, a power of two in [8, 512]."""
    even = tokens * cfg.num_experts_per_tok // cfg.num_experts
    rows = 8
    while rows < min(even, 512):
        rows *= 2
    return rows


def sorted_combine(cfg) -> bool:
    """Whether `routed_experts` brings the expert rows back to their tokens
    by one inverse gather of rows sorted once (a share that holds at least
    half the experts) or adds each block's rows to its tokens inside the
    loop over blocks. The sorted buffers hold all T x k assignments, of
    which a share holding a fraction of the experts multiplies that
    fraction: at Command-A's share (16 of 128 experts) writing and
    gathering them cost more than the block-wise adds they replace (a
    window dispatch 7% slower on a TPU v5e, with the buffer zero-filled),
    at Kimi-Linear's (128 of 256) less. Decided by the config alone, once
    for every program; the detector counts `sorted_moe_tokens` by the same
    call."""
    return 2 * cfg.share.experts_held >= cfg.num_experts


def routed_experts(cfg, lp: dict, xn, valid, route=route):
    """The held experts' part of the FFN for tokens xn [T, h] (`valid` [T]:
    padding is routed nowhere) -> (y [T, h] float32, assignments a held
    expert received [experts_held] int32, assignments multiplied () int32).
    `cfg` is any model's config that says how many experts a token takes,
    which are held here and of how many (`num_experts_per_tok`,
    `num_experts`, `share.experts_held`, `expert_offset`); `route(cfg, lp,
    xn)` is the model's router: expert ids and weights [T, k].

    The T x k assignments are sorted ONCE by expert (those of experts held
    elsewhere, and padding, last) and the sorted run is cut into blocks of
    rows that each belong to ONE expert; a loop over as many blocks as the
    routing needs multiplies each by its expert's three matrices. Every
    assignment to a held expert is in exactly one block, whatever the
    imbalance; the third result, the live rows of the blocks the loop runs,
    says so. Where `sorted_combine` holds, the rows are gathered once in
    sorted order, a block is a contiguous slice of them, and its float32
    output goes to the same place of a sorted buffer. A block's tail past
    its expert's rows is written over by the next block, which starts
    inside that tail, and the loop runs the blocks in order, so every row
    holds its own expert's output at the end. One gather by the inverse of
    the sort then brings each token its k rows, weighted and summed in
    float32; an assignment to an expert held elsewhere, or of padding,
    reads the buffer's last row, which no block reaches and which is set to
    0. Elsewhere each block gathers its rows and adds them, weighted, back
    to their tokens."""
    t, h = xn.shape
    k, held = cfg.num_experts_per_tok, cfg.share.experts_held
    rows = _block_rows(cfg, t)
    n = t * k
    with jax.named_scope("moe_route"):
        top_i, top_w = route(cfg, lp, xn)
        local = top_i - cfg.expert_offset
        mine = (local >= 0) & (local < held) & valid[:, None]
        key = jnp.where(mine, local, held).reshape(n)
    if not sorted_combine(cfg):
        return _added_by_block(cfg, lp, xn, top_w, key, rows)
    with jax.named_scope("moe_route"):
        slot = lax.iota(jnp.int32, n)
        sorted_key, order = lax.sort((key, slot), num_keys=1, is_stable=True)
        edges = jnp.searchsorted(sorted_key, jnp.arange(held + 1, dtype=jnp.int32),
                                 method="compare_all").astype(jnp.int32)
        starts, sizes = edges[:-1], edges[1:] - edges[:-1]
        blocks = (sizes + rows - 1) // rows
        ends = jnp.cumsum(blocks)
        # the block table: block b is expert[b]'s rows from at[b]; a bound on
        # the blocks any routing needs sizes it
        b = jnp.arange(n // rows + held, dtype=jnp.int32)
        expert = jnp.minimum(jnp.searchsorted(ends, b, side="right", method="compare_all"),
                             held - 1).astype(jnp.int32)
        j = b - (ends[expert] - blocks[expert])
        at = starts[expert] + j * rows
        live = jnp.where(b < ends[-1], jnp.clip(sizes[expert] - j * rows, 0, rows), 0)
        # padded by one block so that the last block's slice stays inside
        xs = xn[jnp.pad(order // k, (0, rows))]
    with jax.named_scope("moe_experts"):

        def body(i, ys):
            e = expert[i]
            xb = lax.dynamic_slice_in_dim(xs, at[i], rows)
            up = jnp.dot(xb, lp["eu"][e], preferred_element_type=jnp.float32)
            gate = jnp.dot(xb, lp["eg"][e], preferred_element_type=jnp.float32)
            mid = (jax.nn.silu(gate) * up).astype(xn.dtype)
            yb = jnp.dot(mid, lp["ed"][e], preferred_element_type=jnp.float32)
            return lax.dynamic_update_slice_in_dim(ys, yb, at[i], axis=0)

        # no fill: every row a token reads is written below or by a block
        ys = lax.dynamic_update_slice_in_dim(
            lax.empty((n + rows, h), jnp.float32), jnp.zeros((1, h), jnp.float32), n + rows - 1, 0)
        ys = lax.fori_loop(0, ends[-1], body, ys)
    with jax.named_scope("moe_combine"):
        _, inv = lax.sort((order, slot), num_keys=1)
        inv = jnp.where(key < held, inv, n + rows - 1).reshape(t, k).T
        w = top_w.T[..., None]
        # a token's j-th rows, one gather a j: no [T, k, h] array is formed
        y = lax.fori_loop(0, k, lambda j, acc: acc + ys[inv[j]] * w[j],
                          jnp.zeros((t, h), jnp.float32))
    return y, sizes, live.sum()


def _added_by_block(cfg, lp: dict, xn, top_w, key, rows: int):
    """`routed_experts` where `sorted_combine` does not hold: each block
    gathers its rows by token and adds its weighted output back to them."""
    t, h = xn.shape
    k, held = cfg.num_experts_per_tok, cfg.share.experts_held
    with jax.named_scope("moe_route"):
        order = jnp.argsort(key, stable=True)
        # padded by one block so that the last block's slice stays inside
        tok = jnp.pad(order // k, (0, rows)).astype(jnp.int32)
        wgt = jnp.pad(top_w.reshape(t * k)[order], (0, rows))
        sizes = jnp.zeros(held + 1, jnp.int32).at[key].add(1)[:held]
        starts = jnp.cumsum(sizes) - sizes
        blocks = (sizes + rows - 1) // rows
        ends = jnp.cumsum(blocks)
    with jax.named_scope("moe_experts"):

        def body(b, carry):
            y, done = carry
            e = jnp.searchsorted(ends, b, side="right").astype(jnp.int32)
            j = b - (ends[e] - blocks[e])
            at = starts[e] + j * rows
            live = jnp.arange(rows) < sizes[e] - j * rows
            tb = lax.dynamic_slice(tok, (at,), (rows,))
            wb = jnp.where(live, lax.dynamic_slice(wgt, (at,), (rows,)), 0.0)
            xb = xn[tb]
            up = jnp.dot(xb, lp["eu"][e], preferred_element_type=jnp.float32)
            gate = jnp.dot(xb, lp["eg"][e], preferred_element_type=jnp.float32)
            mid = (jax.nn.silu(gate) * up).astype(xn.dtype)
            yb = jnp.dot(mid, lp["ed"][e], preferred_element_type=jnp.float32)
            return y.at[tb].add(yb * wb[:, None]), done + live.sum(dtype=jnp.int32)

        y, done = lax.fori_loop(
            0, ends[-1], body, (jnp.zeros((t, h), jnp.float32), jnp.int32(0))
        )
    return y, sizes, done


def shared_experts(cfg: Cohere2MoeConfig, lp: dict, xn):
    """The mean of the shared experts' outputs, float32."""
    with jax.named_scope("moe_shared"):
        gate = jnp.dot(xn, lp["sg"], preferred_element_type=jnp.float32)
        up = jnp.dot(xn, lp["su"], preferred_element_type=jnp.float32)
        mid = (jax.nn.silu(gate) * up).astype(xn.dtype)
        y = jnp.dot(mid, lp["sd"], preferred_element_type=jnp.float32)
    return y / cfg.num_shared_experts


def _project(cfg: Cohere2MoeConfig, lp: dict, xn, pos, sliding: bool):
    """x' [S, T, h] -> q [S, T, Hq, D], k, v [S, T, Hkv, D]; sliding layers
    rotate q and k."""
    s, t, _ = xn.shape
    d = cfg.head_dim
    q = jnp.dot(xn, lp["wq"], preferred_element_type=jnp.float32).astype(xn.dtype)
    k = jnp.dot(xn, lp["wk"], preferred_element_type=jnp.float32).astype(xn.dtype)
    v = jnp.dot(xn, lp["wv"], preferred_element_type=jnp.float32).astype(xn.dtype)
    q = q.reshape(s, t, cfg.num_attention_heads, d)
    k = k.reshape(s, t, cfg.num_key_value_heads, d)
    v = v.reshape(s, t, cfg.num_key_value_heads, d)
    # `sliding` is the layer's type: a Python bool read from the static
    # config while tracing
    if sliding:  # foremast: ignore[jit-hygiene]
        q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
    return q, k, v


def _layer_slots(cfg: Cohere2MoeConfig):
    """(layer type, its index among the layers of its own type) a layer."""
    seen = {SLIDING: 0, FULL: 0}
    out = []
    for kind in cfg.layers:
        out.append((kind, seen[kind]))
        seen[kind] += 1
    return out


def _attend_rows(cfg: Cohere2MoeConfig, state, names, slot: int, rows, q, k, v, pos, valid,
                 cached_n, sliding: bool, q_block: int | None, write_at, fused: bool):
    """One layer's attention for every sequence of the dispatch, each
    against its own arena row read where it lies. q [S, T, Hq, D], k, v
    [S, T, Hkv, D]. With `write_at` the sequence's new keys and values are
    then written into its row, head-major, at slot `write_at mod capacity`
    (a full layer's capacity is past every position, so that is the
    position itself); positions that are not `valid` keep what the row
    held: in a ring they are still needed. `fused` (the window program on
    a TPU: `fused_window_attention`): one kernel over the rows in place
    instead of `attend` a sequence. -> (att [S, T, Hq * D], state)."""
    s, t = q.shape[:2]
    hkv, d = cfg.num_key_value_heads, cfg.head_dim
    cap = state[names[0]].shape[-2]
    window = cfg.sliding_window if sliding else None
    # foremast: ignore[jit-hygiene] — decided from the backend and shapes while tracing
    if fused:
        att = fused_attend_rows(
            state[names[0]], state[names[1]], rows, cached_n, q, k, v, pos, valid,
            layer=slot, group=cfg.group, window=window,
        )
        return att, state

    def row_of(leaf, row):
        return lax.dynamic_slice(leaf, (row, slot, 0, 0, 0), (1, 1, hkv, cap, d))[0, 0]

    def one(kc, vc, qs, kn, vn, ps, ok, n):
        pos_c, valid_c = cached_positions(
            jnp.arange(cap, dtype=jnp.int32), n, cap if sliding else None
        )

        def block(a):
            return attend(a[0], a[1], kn, vn, ps, ok, kc, vc, pos_c, valid_c, cfg.group, window)

        if q_block is None or t <= q_block:
            return block((qs, ps))
        # a prefill chunk's scores are never held whole
        nb = t // q_block
        blocks = (qs.reshape(nb, q_block, *qs.shape[1:]), ps.reshape(nb, q_block))
        return lax.map(block, blocks).reshape(t, -1)

    if write_at is None:
        def read(args):
            row, *rest = args
            return one(row_of(state[names[0]], row), row_of(state[names[1]], row), *rest)

        return lax.map(read, (rows, q, k, v, pos, valid, cached_n)), state

    # the leaves ride the loop's carry: read, then updated in place
    at = write_at % cap

    def step(b, carry):
        kleaf, vleaf, out = carry
        take = lambda x: lax.dynamic_index_in_dim(x, b, 0, keepdims=False)  # noqa: E731
        row, kn, vn, ok = take(rows), take(k), take(v), take(valid)
        att = one(row_of(kleaf, row), row_of(vleaf, row), take(q), kn, vn, take(pos), ok,
                  take(cached_n))
        idx = (row, slot, 0, at, 0)
        keep = ok[None, None, None, :, None]
        leaves = []
        for leaf, new in ((kleaf, kn), (vleaf, vn)):
            new = new.transpose(1, 0, 2)[None, None]
            old = lax.dynamic_slice(leaf, idx, new.shape)
            leaves.append(lax.dynamic_update_slice(leaf, jnp.where(keep, new, old), idx))
        return leaves[0], leaves[1], lax.dynamic_update_slice(out, att[None], (b, 0, 0))

    out = jnp.zeros((s, t, q.shape[2] * d), q.dtype)
    kleaf, vleaf, out = lax.fori_loop(0, s, step, (state[names[0]], state[names[1]], out))
    return out, {**state, names[0]: kleaf, names[1]: vleaf}


def _forward(cfg: Cohere2MoeConfig, params, state, rows, ids, pos, valid, cached_n,
             q_block: int | None = None, write_at=None):
    """The layers held, over ids [S, T] at pos [S, T] (`valid`: real
    tokens), each sequence against arena row rows[s], which holds
    `cached_n[s]` positions. With `write_at` (a prefill chunk's first
    position) each layer's new keys and values go into the rows and the
    updated state is returned; the last layer's FFN, which would feed
    nothing, is then left out. -> (x [S, T, h], assignments a held expert
    received [held], assignments routed to a held expert and not multiplied
    (0), state)."""
    s, t = ids.shape
    x = params["embed"][ids]
    counts = jnp.zeros(cfg.share.experts_held, jnp.int32)
    dropped = jnp.int32(0)
    slots = _layer_slots(cfg)
    # the prefill writes rows through a loop's carry and keeps `attend`
    fused = write_at is None and fused_window_attention(cfg, state["kf"].shape[-2], t)
    for li, (lp, (kind, slot)) in enumerate(zip(params["layers"], slots)):
        sliding = kind == SLIDING
        names = ("ks", "vs") if sliding else ("kf", "vf")
        xn = layer_norm(x, lp["ln"], cfg.layer_norm_eps)
        q, k, v = _project(cfg, lp, xn, pos, sliding)
        with jax.named_scope("attn_sliding" if sliding else "attn_full"):
            att, state = _attend_rows(cfg, state, names, slot, rows, q, k, v, pos, valid,
                                      cached_n, sliding, q_block, write_at, fused)
            a = jnp.dot(att.reshape(s * t, -1), lp["wo"], preferred_element_type=jnp.float32)
        # foremast: ignore[jit-hygiene] — `li` counts the Python loop
        if write_at is not None and li == len(slots) - 1:
            break
        flat = xn.reshape(s * t, -1)
        routed, sizes, done = routed_experts(cfg, lp, flat, valid.reshape(s * t))
        ffn = routed + shared_experts(cfg, lp, flat)
        counts = counts + sizes
        dropped = dropped + sizes.sum() - done
        x = (x.astype(jnp.float32) + (a + ffn).reshape(x.shape)).astype(x.dtype)
    return x, counts, dropped, state


# -- the two programs ----------------------------------------------------------

Q_BLOCK = 32  # query tokens a block of a prefill chunk's attention: 512 query rows a key-value head, the window program's own shape


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def prefill_chunk(cfg: Cohere2MoeConfig, params, state, rows, ids, start, n):
    """One chunk of a batch's histories into their rows: ids [B, L] are the
    tokens at positions start .. start + L - 1, n [B] the positions each
    sequence caches in all (its history but the last point). The chunk
    attends to what the rows hold (positions < start) and to itself, then
    its keys and values are written where they belong. The state is
    donated: the arena's buffers are updated in place.
    -> (state, assignments a held expert received [held])."""
    b, length = ids.shape
    pos = jnp.broadcast_to(start + jnp.arange(length, dtype=jnp.int32), (b, length))
    valid = pos < n[:, None]
    _, counts, _, state = _forward(
        cfg, params, state, rows, ids, pos, valid, jnp.minimum(start, n),
        q_block=Q_BLOCK if length % Q_BLOCK == 0 else None, write_at=start,
    )
    return state, counts


@partial(jax.jit, donate_argnames=("state",))
def finish_rows(state, rows, n, last, scale):
    """What a prefilled row holds beside its keys and values."""
    return {
        **state,
        "n": state["n"].at[rows].set(n),
        "last": state["last"].at[rows].set(last),
        "scale": state["scale"].at[rows].set(scale),
    }


@partial(jax.jit, static_argnames=("cfg", "with_logits"))
def score_window(cfg: Cohere2MoeConfig, params, state, rows, ids, valid, with_logits=False):
    """score_t = -log p(id_t | history, id_<t) for the windows ids [S, W]
    (`valid` [S, W]: real points) of the sequences cached in `rows` [S].
    The program is fed [the row's last history id; the window's ids but the
    last] at positions n .. n + W - 1 and runs them through the layers
    against the rows in place; the state is read, never written.
    -> (scores [S, W] float32, assignments a held expert received [held],
    assignments dropped: 0) and, `with_logits`, the logits [S, W, vocabulary
    rows held]."""
    s, w = ids.shape
    n = state["n"][rows]
    inp = jnp.concatenate([state["last"][rows][:, None], ids[:, :-1]], axis=1)
    pos = n[:, None] + jnp.arange(w, dtype=jnp.int32)
    x, counts, dropped, _ = _forward(cfg, params, state, rows, inp, pos, valid, n)
    with jax.named_scope("lm_head"):
        xf = layer_norm(x, params["ln_f"], cfg.layer_norm_eps).reshape(s * w, -1)
        logits = lax.dot_general(
            xf, params["embed"], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * cfg.logit_scale
        logp = jax.nn.log_softmax(logits, axis=-1)
        scores = -jnp.take_along_axis(logp, ids.reshape(s * w, 1), axis=1).reshape(s, w)
    if with_logits:
        return scores, counts, dropped, logits.reshape(s, w, -1)
    return scores, counts, dropped
