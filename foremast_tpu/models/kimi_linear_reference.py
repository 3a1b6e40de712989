"""The plain reference of `models/kimi_linear.py`: the Kimi-Linear forward
pass (`model_type: kimi_linear`, e.g. Kimi-Linear-48B-A3B-Instruct) as its
config.json and the model file's `assumed` block describe it, in
straightforward `jax.numpy`, float32 under
`jax.default_matmul_precision("highest")`. KDA as the token-by-token
recurrence (`lax.scan`), MLA with explicit per-head keys and values, a
dense loop over the experts, one full forward over a whole sequence: no
cache, no chunking, no absorbed projections, no sorting, no kernels. It
reads the model file's plain dict and imports nothing of the program.

The layers (h hidden; RMSNorm(x) = x / sqrt(mean x^2 + eps) * gain, eps
`rms_norm_eps`, gains 1), numbered from 1 as `linear_attn_config` does:

    x <- x + Mix_l(RMSNorm(x));  x <- x + FFN_l(RMSNorm(x))
    logits = RMSNorm(x) W_head                      (untied head)

  * KDA (layers in `kda_layers`; H = `num_heads` heads of d = `head_dim`):
    q~, k~, v~ = SiLU(Conv4(x W_q)), SiLU(Conv4(x W_k)), SiLU(Conv4(x W_v))
    (causal depthwise convolution of `short_conv_kernel_size` taps);
    q = L2norm(q~) d^-1/2, k = L2norm(k~), v = v~; per head and key channel
    g_t = -exp(A_log_h) softplus((x_t W_f1) W_f2 + dt_bias), a_t = exp(g_t);
    b_t = sigmoid(x_t W_b) per head;
    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T;  o_t = S_t^T q_t;
    Mix = (RMSNorm_head(o_t) * sigmoid((x_t W_g1) W_g2)) W_o.
  * MLA (layers in `full_attn_layers`; `q_lora_rank` null): q = x W_q ->
    per head [q_n (`qk_nope_head_dim`); q_r (`qk_rope_head_dim`)];
    [c; k_r] = x W_kva, c <- RMSNorm(c) (`kv_lora_rank` wide);
    [k_n,h; v_h] = c W_kvb,h; score (q_n.k_n,h + q_r.k_r) / sqrt(nope +
    rope), causal softmax, o_h = sum p v_h, Mix = concat(o) W_o.
    `mla_use_nope`: NO rotation is applied to the "rope" dimensions.
  * FFN of the first `first_k_dense_replace` layers: (SiLU(x W_g) * x W_u)
    W_d of width `intermediate_size`. Every later layer: s = sigmoid(x W_r)
    over all `num_experts`; E = top-`num_experts_per_token` of s + bias
    (`e_score_correction_bias`: the choice only); w_e = s_e / sum_E s *
    `routed_scaling_factor`; FFN = sum_{e in E, held} w_e expert_e(x) + the
    shared experts, each of width `moe_intermediate_size`.

`share` is the model file's block: give it the chip's part (`experts_held`
of the experts from `index * experts_held` on, `vocab_rows_held` rows of
embedding and head, the first `layers_held` layers) or the whole model
(`whole_share`). The router always scores all `num_experts` and takes its
top-k over all of them; experts held elsewhere add nothing.

Readings that the config does not settle are the model file's `assumed`:
the gate's parameterisation and the draws of `A_log` (log U(1, 16)) and
`dt_bias` (inverse softplus of a log-uniform dt in [1e-3, 1e-1]); the
low-rank gates' rank = `head_dim`; no rotation under `mla_use_nope`; the
top-level `head_dim` 72 inert; every other weight N(0, 0.02^2) from a seed,
rounded to bfloat16 (the values the program holds), gains 1.
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


def _key(cfg: dict, name: str):
    return jax.random.fold_in(
        jax.random.PRNGKey(int(cfg.get("weights_seed", 0))), np.uint32(zlib.crc32(name.encode()))
    )


def _held(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def tensor(cfg: dict, name: str, shape: tuple):
    return _held(0.02 * jax.random.normal(_key(cfg, name), shape, jnp.float32))


def decay_rates(cfg: dict, name: str, heads: int):
    """A_log [H] = log U(1, 16)."""
    return _held(jnp.log(jax.random.uniform(_key(cfg, name), (heads,), jnp.float32, 1.0, 16.0)))


def dt_bias(cfg: dict, name: str, width: int):
    """The inverse softplus of dt, log dt ~ U(log 1e-3, log 1e-1)."""
    dt = jnp.exp(jax.random.uniform(
        _key(cfg, name), (width,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    return _held(dt + jnp.log(-jnp.expm1(-dt)))


def layer_kind(cfg: dict, li: int) -> str:
    """'kda' or 'mla' for the layer at index li (layer li + 1 of the lists)."""
    lin = cfg["linear_attn_config"]
    if li + 1 in lin["kda_layers"]:
        return "kda"
    if li + 1 in lin["full_attn_layers"]:
        return "mla"
    raise ValueError(f"layer {li + 1} is in neither kda_layers nor full_attn_layers")


def _ffn_mats(cfg, prefix, width):
    h = cfg["hidden_size"]
    return tuple(tensor(cfg, f"{prefix}.{m}", s)
                 for m, s in (("gate", (h, width)), ("up", (h, width)), ("down", (width, h))))


def layer_weights(cfg: dict, share: dict, li: int) -> dict:
    h = cfg["hidden_size"]
    p = f"layers.{li}."
    w = {}
    if layer_kind(cfg, li) == "kda":
        lin = cfg["linear_attn_config"]
        heads, d, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
        hd = heads * d
        for n in "qkv":
            w["w" + n] = tensor(cfg, f"{p}kda.{n}", (h, hd))
            w["conv_" + n] = tensor(cfg, f"{p}kda.conv_{n}", (taps, hd))
        w.update({
            "f_down": tensor(cfg, p + "kda.f_down", (h, d)),
            "f_up": tensor(cfg, p + "kda.f_up", (d, hd)),
            "dt_bias": dt_bias(cfg, p + "kda.dt_bias", hd),
            "a_log": decay_rates(cfg, p + "kda.a_log", heads),
            "beta": tensor(cfg, p + "kda.beta", (h, heads)),
            "g_down": tensor(cfg, p + "kda.g_down", (h, d)),
            "g_up": tensor(cfg, p + "kda.g_up", (d, hd)),
            "wo": tensor(cfg, p + "kda.o", (hd, h)),
        })
    else:
        heads = cfg["num_attention_heads"]
        nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
        lat = cfg["kv_lora_rank"]
        w.update({
            "wq": tensor(cfg, p + "mla.q", (h, heads * (nope + rope))),
            "wkva": tensor(cfg, p + "mla.kva", (h, lat + rope)),
            "wkvb": tensor(cfg, p + "mla.kvb", (lat, heads * (nope + dv))),
            "wo": tensor(cfg, p + "mla.o", (heads * dv, h)),
        })
    if li < cfg["first_k_dense_replace"]:
        w["dense"] = _ffn_mats(cfg, p + "dense", cfg["intermediate_size"])
    else:
        first = share["index"] * share["experts_held"]
        width = cfg["moe_intermediate_size"]
        w["router"] = tensor(cfg, p + "router", (h, cfg["num_experts"]))
        w["router_bias"] = tensor(cfg, p + "router_bias", (cfg["num_experts"],))
        w["experts"] = {
            e: _ffn_mats(cfg, f"{p}experts.{e}", width)
            for e in range(first, first + share["experts_held"])
        }
        w["shared"] = [_ffn_mats(cfg, f"{p}shared.{j}", width)
                       for j in range(cfg["num_shared_experts"])]
    return w


def embedding(cfg: dict, share: dict):
    return tensor(cfg, f"embed.{share['index']}", (share["vocab_rows_held"], cfg["hidden_size"]))


def head(cfg: dict, share: dict):
    return tensor(cfg, f"head.{share['index']}", (cfg["hidden_size"], share["vocab_rows_held"]))


def rms_norm(x, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


# -- KDA -----------------------------------------------------------------------


def short_conv(x, taps):
    """Causal depthwise convolution: y_t = sum_j taps[j] x_{t - (K - 1) + j},
    zeros before the sequence. x [T, C], taps [K, C]."""
    k = taps.shape[0]
    xp = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(taps[j] * xp[j : j + x.shape[0]] for j in range(k))


def kda_inputs(cfg: dict, w: dict, xn):
    """q, k [T, H, d] (normalised), v [T, H, d], g [T, H, d] (log decay, < 0),
    beta [T, H] of the normed input xn [T, h]."""
    lin = cfg["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    t = xn.shape[0]

    def branch(n):
        return jax.nn.silu(short_conv(xn @ w["w" + n], w["conv_" + n])).reshape(t, heads, d)

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    q, k, v = l2(branch("q")) * d ** -0.5, l2(branch("k")), branch("v")
    dt = jax.nn.softplus((xn @ w["f_down"]) @ w["f_up"] + w["dt_bias"]).reshape(t, heads, d)
    g = -jnp.exp(w["a_log"])[None, :, None] * dt
    beta = jax.nn.sigmoid(xn @ w["beta"])
    return q, k, v, g, beta


def kda_recurrence(q, k, v, g, beta):
    """o [T, H, d] of the delta rule with per-channel decay, a token at a
    time from S_0 = 0."""
    _, heads, d = q.shape

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[:, :, None]
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + jnp.einsum("hk,hv->hkv", k_t, u)
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    s0 = jnp.zeros((heads, d, v.shape[-1]), jnp.float32)
    return jax.lax.scan(step, s0, (q, k, v, g, beta))[1]


def kda(cfg: dict, w: dict, xn):
    t = xn.shape[0]
    q, k, v, g, beta = kda_inputs(cfg, w, xn)
    o = rms_norm(kda_recurrence(q, k, v, g, beta), cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid((xn @ w["g_down"]) @ w["g_up"])
    return (o.reshape(t, -1) * gate) @ w["wo"]


# -- MLA -----------------------------------------------------------------------


def mla(cfg: dict, w: dict, xn):
    t = xn.shape[0]
    heads = cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    lat = cfg["kv_lora_rank"]
    q = (xn @ w["wq"]).reshape(t, heads, nope + rope)
    kva = xn @ w["wkva"]
    c, k_r = rms_norm(kva[:, :lat], cfg["rms_norm_eps"]), kva[:, lat:]
    kv = (c @ w["wkvb"]).reshape(t, heads, nope + dv)
    k_n, v = kv[..., :nope], kv[..., nope:]
    # the shared "rope" key, unrotated (`mla_use_nope`), beside each head's own
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r[:, None, :], (t, heads, rope))], axis=-1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(nope + rope))
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shd->thd", p, v).reshape(t, heads * dv) @ w["wo"]


# -- FFN -----------------------------------------------------------------------


def _expert(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def routing(cfg: dict, w: dict, xn):
    """(expert ids [T, k], weights [T, k]) over ALL experts: the bias moves
    the choice, never the weight."""
    s = jax.nn.sigmoid(xn @ w["router"])
    _, top_i = jax.lax.top_k(s + w["router_bias"], cfg["num_experts_per_token"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if cfg.get("moe_renormalize", True):
        top_s = top_s / top_s.sum(axis=-1, keepdims=True)
    return top_i, top_s * cfg["routed_scaling_factor"]


def routed(cfg: dict, w: dict, xn):
    """The held experts' weighted outputs: every held expert over every
    token, weighted by what the router gave it (0 where not routed)."""
    top_i, top_w = routing(cfg, w, xn)
    y = jnp.zeros_like(xn)
    for e, mats in w["experts"].items():
        weight = jnp.where(top_i == e, top_w, 0.0).sum(axis=-1)
        y = y + weight[:, None] * _expert(xn, *mats)
    return y


def shared(cfg: dict, w: dict, xn):
    return sum(_expert(xn, *mats) for mats in w["shared"])


def mixer(cfg: dict, w: dict, xn, li: int):
    return kda(cfg, w, xn) if layer_kind(cfg, li) == "kda" else mla(cfg, w, xn)


def ffn_parts(cfg: dict, w: dict, xn):
    """(the held experts' part, what every chip computes alike): a dense
    layer is all of the second kind."""
    if "dense" in w:
        return jnp.zeros_like(xn), _expert(xn, *w["dense"])
    return routed(cfg, w, xn), shared(cfg, w, xn)


def forward(cfg: dict, share: dict, ids):
    """logits [T, vocabulary rows held] of one sequence of ids [T]."""
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        x = embedding(cfg, share)[jnp.asarray(ids)]
        for li in range(share["layers_held"]):
            w = layer_weights(cfg, share, li)
            x = x + mixer(cfg, w, rms_norm(x, eps), li)
            x = x + sum(ffn_parts(cfg, w, rms_norm(x, eps)))
        return rms_norm(x, eps) @ head(cfg, share)


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
