"""A block-diffusion sequence backbone as a detector: the SDAR-MoE block
(`model_type: sdar_moe`, e.g. SDAR-30B-A3B-Chat: a Qwen3-MoE decoder trained
as a block-diffusion model) at the widths of a model file
(`models/configs/*.json`: config.json's keys verbatim plus a `share` block
and the block length), scoring a monitor's current window against its cached
7-day history. It stands behind the same interface as `models/cohere2_moe.py`
(`engine/backbone.py` has the list) and is kind `backbone_diffusion`;
docs/backbone.md is the operator's page.

One layer for hidden x (RMSNorm(x) = x / sqrt(mean x^2 + eps) * g, eps
`rms_norm_eps`, statistics in float32; sequential residuals):

    h = RMSNorm(x);  q, k, v = h W_q, h W_k, h W_v
    q, k <- RoPE(RMSNorm_head(q)), RoPE(RMSNorm_head(k))    (q-norm, k-norm over a head's dims)
    x <- x + Attn(q, k, v) W_o
    h = RMSNorm(x);  x <- x + sum_{e in top-k} p_e (silu(h W_g,e) * h W_u,e) W_d,e

  * RoPE in rotate-half (NeoX) form: dims m and m + D/2 turn together by
    pos * theta^(-2m / D), theta `rope_theta`.
  * Attention: grouped-query, query head j on key-value head j // (heads /
    kv heads), scores q.k / sqrt(head_dim), softmax in float32, under the
    block mask below. No bias.
  * Router: p = softmax(h W_r) over ALL `num_experts`; the top-k are kept
    and renormalised to sum to 1 (`norm_topk_prob`). No shared expert; every
    layer is an expert layer. A process holds `share.experts_held` experts
    from `share.index * experts_held` on (`cohere2_moe.routed_experts`, the
    grouped product the backbones share): nothing is dropped, no capacity
    factor.
  * Head: logits = RMSNorm(x) W_head (untied) over the held vocabulary rows;
    the mask token's row takes part in the softmax.

The block mask (BD3-LM, arXiv:2503.09573; SDAR). The sequence is cut into
blocks of `block_length` B positions. A clean token sees every clean token of
its own block and of all earlier blocks. A block being denoised holds observed
tokens and mask tokens; it sees the clean tokens of all earlier blocks and,
both ways, its own positions.

The detector's score. The history is cached as clean blocks: a row holds the
history's newest whole blocks (`cached_span`), positions 0 .. n - 1, prefilled
in chunks of whole blocks under the block mask. The window's points then form
blocks from position n on; block b is denoised in B steps, one token
revealed a step left to right: at step s its input holds the observed ids at
its positions < s and the mask id at the rest, and

    score(b, s) = -log softmax(head(position s of that input))[x_{b,s}].

Later blocks see block b's clean observed tokens. An incomplete last block is
padded with mask tokens and scored at its observed positions only. The rule
is teacher-forced, so every copy (b, s) is independent of the others given the
clean window: `score_window` runs the window's clean tokens and every copy of
every block as ONE dispatch (a sequence's tokens: W clean, then W x B noisy,
each with a code block * COPY_CODES + copy that the mask is taken from:
`block_visible`), reads the rows in place and never writes
them, and takes the head only at the scored positions, 512 of them a block
(`kimi_linear.head_scores`). On a TPU, at widths
its tiling meets, its attention is the backbones' fused kernel over the rows
where they lie (`cohere2_attention.fused_attend_rows` told `block_visible`
for the dispatch's own keys; device op `sdar_attn_blocks`;
`fused_window_attention` decides from the backend and the
shapes alone); everywhere else, and in the prefill always, it is
`cohere2_moe.attend`, a sequence at a time.

Precision: weights, activations and cache in `compute_dtype` (bfloat16);
accumulation, softmax, norm statistics, RoPE, router and log-softmax in
float32. The plain reference is `models/sdar_moe_reference.py`.
"""

from __future__ import annotations

import dataclasses
import json
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from foremast_tpu.models import cohere2_moe
from foremast_tpu.models.cohere2_attention import fused_applies, fused_attend_rows
from foremast_tpu.models.cohere2_moe import (  # the code the backbones share
    Share,
    attend,
    finish_rows,
    routed_experts,
    series_scale,
    tensor,
)
from foremast_tpu.models.kimi_linear import head_scores, rms_norm

__all__ = [
    "Config", "MODEL_TYPE", "cache_template", "cached_span", "finish_rows", "init_params",
    "prefill_chunk", "prefill_chunk_len", "prefill_seqs", "score_window", "series_scale",
    "tokenize", "window_counters", "WINDOW_COUNTERS",
]

MODEL_TYPE = "sdar_moe"
DEFAULT_MODEL_FILE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "configs", "sdar-30b-a3b-chat.json"
)
PREFILL_CHUNK = 2560  # tokens a sequence a prefill dispatch, at most
PREFILL_SEQS = 2  # sequences a prefill dispatch: ~5,000 tokens, ~320 assignments an expert
Q_BLOCK = 32  # query tokens a block of a prefill chunk's attention
KEY_BLOCK = 512  # cached keys a step of the fused kernel: 1,280 query rows a head ride it
COPY_CODES = 8  # a token's code is block * COPY_CODES + copy (`block_visible`)
ATTN_OP = "sdar_attn_blocks"  # the fused kernel's device op in this model's window program


def block_visible(code_q, code_k, live_k):
    """[Tq, Tk] bool: key j of a block-diffusion dispatch is seen by query
    i. A token's code is block * COPY_CODES + copy: copy 0 is a clean token
    of its block, copy c > 0 a token of the block's c-th noisy copy. A clean
    token sees the clean tokens of its own block and of every earlier one; a
    noisy copy sees the clean tokens of earlier blocks and every token of
    itself (both ways). code_q [Tq, 1]; code_k, live_k [1, Tk]."""
    block_q, copy_q = code_q // COPY_CODES, code_q % COPY_CODES
    block_k, copy_k = code_k // COPY_CODES, code_k % COPY_CODES
    clean = (copy_k == 0) & ((block_k < block_q) | ((block_k == block_q) & (copy_q == 0)))
    return live_k & (clean | ((copy_k != 0) & (code_k == code_q)))


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    moe_intermediate_size: int  # ONE expert
    num_experts: int  # the router's width: every published expert
    num_experts_per_tok: int
    norm_topk_prob: bool
    rope_theta: float
    rms_norm_eps: float
    num_hidden_layers: int  # the published depth; `share.layers_held` are run
    vocab_size: int
    block_length: int  # B (model file: `assumed`)
    share: Share
    weights_seed: int = 0
    compute_dtype: str = "bfloat16"

    @staticmethod
    def from_dict(d: dict) -> "SdarMoeConfig":
        for key, want in (
            ("model_type", MODEL_TYPE), ("hidden_act", "silu"), ("attention_bias", False),
            ("tie_word_embeddings", False), ("use_sliding_window", False),
            ("mlp_only_layers", []), ("decoder_sparse_step", 1), ("rope_scaling", None),
        ):
            if d.get(key, want) != want:
                raise ValueError(f"model file: {key}={d[key]!r}, only {want!r} is written down")
        share = d.get("share") or {
            "chips_sharing_a_layer": 1, "index": 0,
            "experts_held": d["num_experts"],
            "vocab_rows_held": d["vocab_size"],
            "layers_held": d["num_hidden_layers"],
        }
        return SdarMoeConfig(
            hidden_size=int(d["hidden_size"]),
            num_attention_heads=int(d["num_attention_heads"]),
            num_key_value_heads=int(d["num_key_value_heads"]),
            head_dim=int(d["head_dim"]),
            moe_intermediate_size=int(d["moe_intermediate_size"]),
            num_experts=int(d["num_experts"]),
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            norm_topk_prob=bool(d.get("norm_topk_prob", True)),
            rope_theta=float(d["rope_theta"]),
            rms_norm_eps=float(d["rms_norm_eps"]),
            num_hidden_layers=int(d["num_hidden_layers"]),
            vocab_size=int(d["vocab_size"]),
            block_length=int(d["block_length"]),
            share=Share(**{f.name: int(share[f.name]) for f in dataclasses.fields(Share)}),
            weights_seed=int(d.get("weights_seed", 0)),
            compute_dtype=str(d.get("compute_dtype", "bfloat16")),
        )

    @staticmethod
    def from_file(path: str | None = None) -> "SdarMoeConfig":
        with open(path or DEFAULT_MODEL_FILE, encoding="utf-8") as fh:
            return SdarMoeConfig.from_dict(json.load(fh))

    def __post_init__(self):
        s = self.share
        if (s.index + 1) * s.experts_held > self.num_experts:
            raise ValueError("share: experts held run past num_experts")
        if (s.index + 1) * s.vocab_rows_held > self.vocab_size:
            raise ValueError("share: vocabulary rows held run past vocab_size")
        if not 0 < s.layers_held <= self.num_hidden_layers:
            raise ValueError("share: layers_held outside the published depth")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads have to be a multiple of key-value heads")
        if not 0 < self.block_length < COPY_CODES:
            raise ValueError(f"block_length has to lie in 1 .. {COPY_CODES - 1}")

    @property
    def group(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def mask_token_id(self) -> int:
        """The last held vocabulary row (model file: `assumed`): the
        tokeniser quantises onto the ids below it and never emits it."""
        return self.share.vocab_rows_held - 1

    @property
    def expert_offset(self) -> int:
        return self.share.index * self.share.experts_held

    @property
    def dtype(self):
        return jnp.dtype(self.compute_dtype)


Config = SdarMoeConfig  # the name `engine/backbone.py` loads a model file through


def init_params(cfg: SdarMoeConfig) -> dict:
    """The share's weights, drawn by tensor name (`cohere2_moe.tensor`);
    norm gains 1."""
    h, w, d = cfg.hidden_size, cfg.moe_intermediate_size, cfg.head_dim
    hq, hkv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
    held = range(cfg.expert_offset, cfg.expert_offset + cfg.share.experts_held)
    ones = partial(jnp.ones, dtype=jnp.float32)
    layers = []
    for li in range(cfg.share.layers_held):
        p = f"layers.{li}."

        def t(name, shape, p=p):
            return tensor(cfg, p + name, shape)

        layers.append({
            "ln1": ones((h,)), "ln2": ones((h,)), "q_norm": ones((d,)), "k_norm": ones((d,)),
            "wq": t("attn.q", (h, hq)), "wk": t("attn.k", (h, hkv)),
            "wv": t("attn.v", (h, hkv)), "wo": t("attn.o", (hq, h)),
            "router": t("router", (h, cfg.num_experts)),
            "eg": jnp.stack([t(f"experts.{e}.gate", (h, w)) for e in held]),
            "eu": jnp.stack([t(f"experts.{e}.up", (h, w)) for e in held]),
            "ed": jnp.stack([t(f"experts.{e}.down", (w, h)) for e in held]),
        })
    rows = cfg.share.vocab_rows_held
    return {
        "embed": tensor(cfg, f"embed.{cfg.share.index}", (rows, h)),
        "head": tensor(cfg, f"head.{cfg.share.index}", (h, rows)),
        "ln_f": ones((h,)),
        "layers": layers,
    }


# -- tokeniser and the cache row -------------------------------------------------


def tokenize(values: np.ndarray, scale: np.ndarray, vocab: int) -> np.ndarray:
    """`cohere2_moe.tokenize` onto the `vocab - 1` ids below the mask token
    (the last row), which is therefore never emitted."""
    return cohere2_moe.tokenize(values, scale, vocab - 1)


def cached_span(cfg: SdarMoeConfig, n: int) -> tuple:
    """The history points [first, stop) of n that a row caches: the newest
    whole blocks. A history that is no whole number of blocks is cut at its
    old end, and no point of it is fed to the window program."""
    return n % cfg.block_length, n


def prefill_chunk_len(cfg: SdarMoeConfig, ctx_cap: int) -> int:
    """Tokens a prefill chunk holds: the row's capacity in the fewest equal
    chunks of whole query blocks none longer than PREFILL_CHUNK (10,112
    positions: 4 of 2,528); a capacity that does not divide so goes in
    chunks of PREFILL_CHUNK and a shorter last one. A chunk is whole blocks."""
    k = -(-ctx_cap // PREFILL_CHUNK)
    chunk = min(PREFILL_CHUNK, ctx_cap)
    while k * Q_BLOCK <= ctx_cap:
        if ctx_cap % (k * Q_BLOCK) == 0:
            chunk = ctx_cap // k
            break
        k += 1
    if chunk % cfg.block_length:
        raise ValueError(f"a prefill chunk of {chunk} is no whole number of blocks")
    return chunk


def prefill_seqs(cfg: SdarMoeConfig, ctx_cap: int) -> int:
    """Sequences a prefill dispatch holds."""
    return PREFILL_SEQS


def cache_template(cfg: SdarMoeConfig, ctx_cap: int) -> dict:
    """One arena row: every held layer's keys (rotated) and values of the
    cached clean blocks, head-major so that one head's keys are contiguous;
    `last` is kept for the row's shape and read by nothing."""
    sd = jax.ShapeDtypeStruct
    shape = (cfg.share.layers_held, cfg.num_key_value_heads, ctx_cap, cfg.head_dim)
    return {
        "k": sd(shape, cfg.dtype),
        "v": sd(shape, cfg.dtype),
        "n": sd((), jnp.int32),
        "last": sd((), jnp.int32),
        "scale": sd((), jnp.float32),
    }


def window_tokens(cfg: SdarMoeConfig, points: int) -> int:
    """Tokens a sequence's window dispatch runs for a window bucket of
    `points`: the clean window, then B noisy copies of each block."""
    padded = -(-points // cfg.block_length) * cfg.block_length
    return padded * (1 + cfg.block_length)


def fused_window_attention(cfg: SdarMoeConfig, ctx_cap: int, points: int) -> bool:
    """Whether `score_window` over windows of `points` against rows of
    `ctx_cap` positions attends through the fused kernel or through
    `attend`: decided by the backend and the shapes alone, once for the
    whole program. The detector counts `fused_attn_tokens` by the same call."""
    return fused_applies(cfg.head_dim, cfg.dtype.itemsize, window_tokens(cfg, points), (ctx_cap,))


WINDOW_COUNTERS = ("denoise_tokens", "clean_tokens", "fused_attn_tokens")


def window_counters(cfg: SdarMoeConfig, ctx_cap: int, valid, denoise, clean) -> dict:
    """What one window dispatch adds to the detector's counters beside the
    tokens it scored (`valid` [S, W]): `denoise_tokens`, the noisy copies'
    token-forwards, and `clean_tokens`, the clean window tokens that later
    blocks read, both as `score_window` itself counted them a sequence from
    the liveness its attention masks are taken under (`denoise`, `clean`
    [S]); `fused_attn_tokens`, the scored tokens of dispatches whose
    attention took the fused kernel."""
    fused = fused_window_attention(cfg, ctx_cap, valid.shape[1])
    return {
        "denoise_tokens": int(np.asarray(denoise, np.int64).sum()),
        "clean_tokens": int(np.asarray(clean, np.int64).sum()),
        "fused_attn_tokens": int(valid.sum()) if fused else 0,
    }


# -- the block -----------------------------------------------------------------


def rope(x, pos, theta: float):
    """Rotate-half (NeoX) RoPE: x [..., T, H, D], dims m and m + D/2 turned
    together by pos [..., T] * theta^(-2m / D), in float32."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[..., None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    xf = x.astype(jnp.float32)
    x0, x1 = xf[..., : d // 2], xf[..., d // 2 :]
    return jnp.concatenate([x0 * cos - x1 * sin, x1 * cos + x0 * sin], axis=-1).astype(x.dtype)


def route(cfg: SdarMoeConfig, lp: dict, xn):
    """Softmax selection over ALL experts: (top-k expert ids [T, k], their
    probabilities renormalised over the k [T, k]) in float32."""
    logits = jnp.dot(xn.astype(jnp.float32), lp["router"].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    top_p, top_i = lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.num_experts_per_tok)
    # foremast: ignore[jit-hygiene] — a static field of the config
    if cfg.norm_topk_prob:
        top_p = top_p / top_p.sum(axis=-1, keepdims=True)
    return top_i, top_p


def _project(cfg: SdarMoeConfig, lp: dict, xn, pos):
    """h [S, T, h] -> q [S, T, Hq, D], k, v [S, T, Hkv, D]: q and k normed
    a head, then rotated."""
    s, t, _ = xn.shape
    d = cfg.head_dim

    def proj(w, heads):
        return jnp.dot(xn, w, preferred_element_type=jnp.float32).astype(xn.dtype).reshape(
            s, t, heads, d)

    q = proj(lp["wq"], cfg.num_attention_heads)
    k = proj(lp["wk"], cfg.num_key_value_heads)
    v = proj(lp["wv"], cfg.num_key_value_heads)
    with jax.named_scope("qk_norm_rope"):
        q = rope(rms_norm(q, lp["q_norm"], cfg.rms_norm_eps), pos, cfg.rope_theta)
        k = rope(rms_norm(k, lp["k_norm"], cfg.rms_norm_eps), pos, cfg.rope_theta)
    return q, k, v


def _attend_rows(cfg: SdarMoeConfig, state, layer: int, rows, q, k, v, pos, codes, live,
                 cached_n, write_at, fused: bool):
    """One layer's attention for every sequence of the dispatch, each against
    its own arena row read where it lies: the cached positions below
    cached_n[s], and the dispatch's own keys as `block_visible` sees them by
    the tokens' codes (`live`: the keys that are real). q [S, T, Hq, D], k, v
    [S, T, Hkv, D]. With `write_at` (a prefill chunk's first position) the
    sequence's new keys and values are then written into its row at their
    positions; a token that is not live writes nothing. `fused`: the kernel
    over the rows in place instead of `attend` a sequence.
    -> (att [S, T, Hq * D], state)."""
    s, t = q.shape[:2]
    hkv, d = cfg.num_key_value_heads, cfg.head_dim
    cap = state["k"].shape[-2]
    # foremast: ignore[jit-hygiene] — decided from the backend and shapes while tracing
    if fused:
        att = fused_attend_rows(state["k"], state["v"], rows, cached_n, q, k, v, codes, live,
                                layer=layer, group=cfg.group, window=None,
                                own_visible=block_visible, name=ATTN_OP, key_block=KEY_BLOCK)
        return att, state
    slot = jnp.arange(cap, dtype=jnp.int32)

    def row_of(leaf, row):
        return lax.dynamic_slice(leaf, (row, layer, 0, 0, 0), (1, 1, hkv, cap, d))[0, 0]

    def one(kc, vc, qs, kn, vn, ps, cs, ok, n):
        def block(a):
            qb, pb, cb = a
            seen = block_visible(cb[:, None], cs[None, :], ok[None, :])
            return attend(qb, pb, kn, vn, ps, ok, kc, vc, slot, slot < n, cfg.group, None,
                          seen_n=seen)

        # foremast: ignore[jit-hygiene] — shapes, read while tracing
        if t <= Q_BLOCK or t % Q_BLOCK:
            return block((qs, ps, cs))
        # a prefill chunk's scores are never held whole
        nb = t // Q_BLOCK
        return lax.map(block, (qs.reshape(nb, Q_BLOCK, *qs.shape[1:]), ps.reshape(nb, Q_BLOCK),
                               cs.reshape(nb, Q_BLOCK))).reshape(t, -1)

    # foremast: ignore[jit-hygiene] — the program's kind, a Python value
    if write_at is None:
        def read(args):
            row, *rest = args
            return one(row_of(state["k"], row), row_of(state["v"], row), *rest)

        return lax.map(read, (rows, q, k, v, pos, codes, live, cached_n)), state

    # the leaves ride the loop's carry: read, then updated in place
    def step(b, carry):
        kleaf, vleaf, out = carry
        take = lambda x: lax.dynamic_index_in_dim(x, b, 0, keepdims=False)  # noqa: E731
        row, kn, vn, ok = take(rows), take(k), take(v), take(live)
        att = one(row_of(kleaf, row), row_of(vleaf, row), take(q), kn, vn, take(pos),
                  take(codes), ok, take(cached_n))
        idx = (row, layer, 0, write_at, 0)
        keep = ok[None, None, None, :, None]
        leaves = []
        for leaf, new in ((kleaf, kn), (vleaf, vn)):
            new = new.transpose(1, 0, 2)[None, None]
            old = lax.dynamic_slice(leaf, idx, new.shape)
            leaves.append(lax.dynamic_update_slice(leaf, jnp.where(keep, new, old), idx))
        return leaves[0], leaves[1], lax.dynamic_update_slice(out, att[None], (b, 0, 0))

    out = jnp.zeros((s, t, q.shape[2] * d), q.dtype)
    kleaf, vleaf, out = lax.fori_loop(0, s, step, (state["k"], state["v"], out))
    return out, {**state, "k": kleaf, "v": vleaf}


def _forward(cfg: SdarMoeConfig, params, state, rows, ids, pos, codes, live, cached_n,
             write_at=None, fused: bool = False):
    """The layers held, over ids [S, T] at pos [S, T] with codes [S, T]
    (`live`: real tokens), each sequence against arena row rows[s], which
    holds `cached_n[s]` positions. With `write_at` each layer's new keys and
    values go into the rows and the updated state is returned; the last
    layer's FFN, which would feed nothing, is then left out.
    -> (x [S, T, h], assignments a held expert received [held], assignments
    routed to a held expert and not multiplied (0), state)."""
    s, t = ids.shape
    x = params["embed"][ids]
    counts = jnp.zeros(cfg.share.experts_held, jnp.int32)
    dropped = jnp.int32(0)
    last = len(params["layers"]) - 1
    for li, lp in enumerate(params["layers"]):
        xn = rms_norm(x, lp["ln1"], cfg.rms_norm_eps)
        with jax.named_scope("attn_block"):
            q, k, v = _project(cfg, lp, xn, pos)
            att, state = _attend_rows(cfg, state, li, rows, q, k, v, pos, codes, live, cached_n,
                                      write_at, fused)
            a = jnp.dot(att.reshape(s * t, -1), lp["wo"], preferred_element_type=jnp.float32)
        x = (x.astype(jnp.float32) + a.reshape(x.shape)).astype(x.dtype)
        # foremast: ignore[jit-hygiene] — `li` counts the Python loop
        if write_at is not None and li == last:
            break
        flat = rms_norm(x, lp["ln2"], cfg.rms_norm_eps).reshape(s * t, -1)
        routed, sizes, done = routed_experts(cfg, lp, flat, live.reshape(s * t), route=route)
        counts = counts + sizes
        dropped = dropped + sizes.sum() - done
        x = (x.astype(jnp.float32) + routed.reshape(x.shape)).astype(x.dtype)
    return x, counts, dropped, state


# -- the two programs ----------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def prefill_chunk(cfg: SdarMoeConfig, params, state, rows, ids, start, n):
    """One chunk of a batch's histories into their rows as clean blocks: ids
    [B, L] are the tokens at positions start .. start + L - 1 (start and L
    whole blocks), n [B] the positions each sequence caches in all. The chunk
    sees what the rows hold (positions < start) and, under the block mask,
    itself; then its keys and values are written where they belong. The
    state is donated: the arena's buffers are updated in place.
    -> (state, assignments a held expert received [held])."""
    b, length = ids.shape
    pos = jnp.broadcast_to(start + jnp.arange(length, dtype=jnp.int32), (b, length))
    codes = (pos // cfg.block_length) * COPY_CODES
    _, counts, _, state = _forward(
        cfg, params, state, rows, ids, pos, codes, pos < n[:, None], jnp.minimum(start, n),
        write_at=start,
    )
    return state, counts


def window_layout(cfg: SdarMoeConfig, ids, valid, n):
    """The dispatch's tokens for windows ids [S, W] (`valid` [S, W]) over
    rows holding n [S] positions: (ids, positions, codes, live) [S, T] with
    T = `window_tokens(W)`, and the index of each window point's scored
    token [W]. A sequence's first P = W rounded up to whole blocks tokens
    are its clean window (code block * COPY_CODES; live where the point is
    real and a later block has a real point: only later blocks read it),
    then for each block b and step s the B tokens of copy (b, s): the
    observed ids at the block's positions < s and the mask id at the rest
    (code block * COPY_CODES + s + 1; live where point (b, s) is real: it is
    then scored at its position s)."""
    s, w = ids.shape
    bl = cfg.block_length
    p = -(-w // bl) * bl
    nb = p // bl
    ids = jnp.pad(ids, ((0, 0), (0, p - w)))
    valid = jnp.pad(valid, ((0, 0), (0, p - w)))
    at = jnp.arange(p, dtype=jnp.int32)
    block_real = valid.reshape(s, nb, bl).any(axis=-1)
    # a block is read by later blocks where one of them has a real point
    read_later = jnp.flip(jnp.cumsum(jnp.flip(block_real, 1), axis=1), 1) - block_real > 0
    clean_live = valid & jnp.repeat(read_later, bl, axis=1)
    step = jnp.arange(bl)
    # [S, nb, step, i]: copy (b, s) at in-block position i
    blocks = ids.reshape(s, nb, 1, bl)
    seen = (step[None, :] < step[:, None])[None, None] & valid.reshape(s, nb, 1, bl)
    copy_ids = jnp.where(seen, blocks, cfg.mask_token_id)
    copy_pos = jnp.broadcast_to(at.reshape(nb, 1, bl), (s, nb, bl, bl))
    copy_codes = (jnp.arange(nb)[:, None, None] * COPY_CODES + step[:, None] + 1).astype(jnp.int32)
    copy_live = jnp.broadcast_to(valid.reshape(s, nb, bl, 1), (s, nb, bl, bl))
    flat = lambda a: a.reshape(s, p * bl)  # noqa: E731
    tok = jnp.concatenate([ids, flat(copy_ids)], axis=1)
    pos = n[:, None] + jnp.concatenate([jnp.broadcast_to(at, (s, p)), flat(copy_pos)], axis=1)
    codes = jnp.concatenate([
        jnp.broadcast_to((at // bl) * COPY_CODES, (s, p)),
        jnp.broadcast_to(copy_codes, (s, nb, bl, bl)).reshape(s, p * bl),
    ], axis=1)
    live = jnp.concatenate([clean_live, flat(copy_live)], axis=1)
    scored = p + at * bl + at % bl
    return tok, pos, codes, live, scored[:w]


@partial(jax.jit, static_argnames=("cfg", "with_logits"))
def score_window(cfg: SdarMoeConfig, params, state, rows, ids, valid, with_logits=False):
    """score(b, s) for the windows ids [S, W] (`valid` [S, W]: real points)
    of the sequences cached in `rows` [S], by the block-diffusion rule of the
    module's docstring, every copy of every block in this ONE dispatch
    against the rows in place; the state is read, never written.
    -> (scores [S, W] float32, assignments a held expert received [held],
    assignments dropped: 0, the noisy copies' live tokens [S] int32, the
    clean window's live tokens [S] int32: `window_counters`' `denoise` and
    `clean`) and, `with_logits`, the logits [S, W, vocabulary rows held]."""
    s, w = ids.shape
    n = state["n"][rows]
    tok, pos, codes, live, scored = window_layout(cfg, ids, valid, n)
    fused = fused_window_attention(cfg, state["k"].shape[-2], w)
    x, counts, dropped, _ = _forward(cfg, params, state, rows, tok, pos, codes, live, n,
                                     fused=fused)
    clean_len = tok.shape[1] // (1 + cfg.block_length)
    denoise = jnp.sum(live[:, clean_len:], axis=1, dtype=jnp.int32)
    clean = jnp.sum(live[:, :clean_len], axis=1, dtype=jnp.int32)
    with jax.named_scope("lm_head"):
        out = head_scores(cfg, params, x[:, scored].reshape(s * w, -1), ids.reshape(s * w),
                          with_logits)
    # foremast: ignore[jit-hygiene] — a static flag
    if with_logits:
        return out[0].reshape(s, w), counts, dropped, denoise, clean, out[1].reshape(s, w, -1)
    return out.reshape(s, w), counts, dropped, denoise, clean
