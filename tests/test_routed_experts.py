"""The grouped expert product every backbone shares
(`cohere2_moe.routed_experts`, with each model's own router) against a plain
per-token float32 reference: each token's k assignments to held experts, one
expert at a time, weighted and summed in the order of its top-k. Cases: an
expert's rows no multiple of the block, T x k no multiple of the block rows,
one expert that takes several blocks (a router column that dominates, as the
block-diffusion model's mask tokens route alike), a share that holds some of
the experts from an offset, padding tokens, and no token at all: each on
the combine its share takes, and both combines for every router.

Beside it, the structure the rewrite is for: in the block-diffusion window
program at the cell's published widths, the loop over blocks writes its rows
by slice and holds no scatter; the rows go back to their tokens by a gather
after the loop. Lowering needs shapes alone: no weight is drawn.
"""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from foremast_tpu.models import cohere2_moe, kimi_linear, sdar_moe
from tests.test_backbone_model import tiny as tiny_cohere
from tests.test_kimi_linear_model import tiny as tiny_kimi
from tests.test_sdar_moe_model import tiny as tiny_sdar

# model -> (module, the tiny model file, the first layer with experts, a share
# that holds some experts from an offset)
MODELS = {
    "cohere2_moe": (cohere2_moe, tiny_cohere, 0, {"index": 3}),
    "kimi_linear": (kimi_linear, tiny_kimi, 1, {"index": 1}),
    "sdar_moe": (sdar_moe, tiny_sdar, 0, {"index": 1}),
}
# case -> (tokens, padding tokens at the end, share overrides ("offset": the
# model's above), a dominant router column). The model files' shares take
# one combine or the other (`sorted_combine`); the last two cases take each
# for every router.
CASES = {
    "uneven_sizes": (37, 0, {}, False),
    "several_blocks_one_expert": (41, 0, {}, True),
    "offset_share": (37, 0, "offset", False),
    "padding": (41, 9, {}, True),
    "all_padding": (16, 16, {}, False),
    "all_experts_held": (41, 0, {"chips_sharing_a_layer": 1, "experts_held": 16}, True),
    "an_eighth_held_from_an_offset": (37, 3, {"chips_sharing_a_layer": 8, "experts_held": 2,
                                              "index": 1}, False),
}


def _layer(name: str, case: str):
    module, tiny, li, offset = MODELS[name]
    t, pad, share, dominant = CASES[case]
    cfg = module.Config.from_dict(tiny("float32", **(offset if share == "offset" else share)))
    lp = dict(module.init_params(cfg)["layers"][li])
    if dominant:
        lp["router"] = lp["router"].at[:, cfg.expert_offset].set(1.0)
    xn = jax.random.normal(jax.random.PRNGKey(11), (t, cfg.hidden_size), jnp.float32)
    if dominant:  # every token's sum of x is large: the column wins everywhere
        xn = jnp.abs(xn)
    valid = jnp.arange(t) < t - pad
    return module, cfg, lp, xn, valid


def per_token(module, cfg, lp, xn, valid):
    """y [T, h] and the assignments each held expert received, a token and
    an assignment at a time."""
    top_i, top_w = (np.asarray(a) for a in module.route(cfg, lp, xn))
    eu, eg, ed = (np.asarray(lp[m], np.float32) for m in ("eu", "eg", "ed"))
    x = np.asarray(xn, np.float32)
    held = cfg.share.experts_held
    y = np.zeros(x.shape, np.float32)
    sizes = np.zeros(held, np.int64)
    for t in np.flatnonzero(np.asarray(valid)):
        for e, w in zip(top_i[t] - cfg.expert_offset, top_w[t]):
            if 0 <= e < held:
                gate = x[t] @ eg[e]
                mid = gate / (1.0 + np.exp(-gate)) * (x[t] @ eu[e])
                y[t] += np.float32(w) * (mid @ ed[e])
                sizes[e] += 1
    return y, sizes


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("name", sorted(MODELS))
def test_the_grouped_product_equals_the_per_token_reference(name, case):
    module, cfg, lp, xn, valid = _layer(name, case)
    rows = cohere2_moe._block_rows(cfg, xn.shape[0])
    y, sizes, multiplied = jax.jit(
        lambda lp, xn, valid: cohere2_moe.routed_experts(cfg, lp, xn, valid, route=module.route)
    )(lp, xn, valid)
    want, want_sizes = per_token(module, cfg, lp, xn, valid)
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(sizes), want_sizes)
    assert int(multiplied) == int(want_sizes.sum())
    assert not np.asarray(y)[~np.asarray(valid)].any()
    t, pad, _, dominant = CASES[case]
    if case != "all_padding":
        assert (t * cfg.num_experts_per_tok) % rows and want_sizes.sum() > 0
    if dominant:
        assert want_sizes[0] == t - pad > rows  # one expert over several blocks
    if case.startswith(("all_experts", "an_eighth")):
        assert cohere2_moe.sorted_combine(cfg) == (case == "all_experts_held")


def _sdar_window_program_text() -> str:
    """The block-diffusion window program at the cell's shapes (80 sequences
    of 10,112 cached positions, a 32-point window bucket), lowered with its
    ops' scopes."""
    with open(sdar_moe.DEFAULT_MODEL_FILE, encoding="utf-8") as fh:
        cfg = sdar_moe.Config.from_dict(json.load(fh))
    seqs, cap, w = 80, 10112, 32
    sd = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: sdar_moe.init_params(cfg))
    state = {k: sd((seqs, *leaf.shape), leaf.dtype)
             for k, leaf in sdar_moe.cache_template(cfg, cap).items()}
    args = (sd((seqs,), jnp.int32), sd((seqs, w), jnp.int32), sd((seqs, w), jnp.bool_))

    def window_program(cfg, *args):
        return sdar_moe.score_window.__wrapped__(cfg, *args)

    lowered = jax.jit(window_program, static_argnums=0).trace(cfg, params, state, *args).lower()
    return lowered.as_text(debug_info=True)


def test_the_expert_loop_writes_by_slice_and_holds_no_scatter():
    text = _sdar_window_program_text()
    # every op's location names its scope path and primitive; a loop's body
    # is under `while/body`
    loc = dict(re.findall(r'^#(loc\d+) = loc\("([^"]*)"', text, re.M))

    def ops(op: str, where: str) -> int:
        """Ops `op` (one line each) under the scope path `where`."""
        n = 0
        for line in text.splitlines():
            ref = re.search(r"loc\(#(loc\d+)\)\s*$", line)
            n += bool(ref) and op in line and where in loc.get(ref.group(1), "")
        return n

    scatters = [name for name in loc.values() if "scatter" in name]
    assert not [name for name in scatters if "moe_" in name], scatters
    body = "moe_experts/while/body"
    assert ops("stablehlo.dot_general", body) == 4 * 3  # the three products, 4 layers
    assert ops("stablehlo.dynamic_update_slice", body) == 4
