"""Kinds `backbone` and `backbone_kda` keep their programs while code they
share with a third user changes (`models/sdar_moe.py` reaches
`cohere2_attention.fused_attend_rows`, `cohere2_moe.attend` and
`cohere2_moe.routed_experts`): the window program and the prefill of
Cohere2-MoE and of Kimi-Linear lower to the very text pinned here
(sha256 of `lower().as_text()` and of the jaxpr), at the small widths of
their model tests and
at the published widths of their cells, on the path every CPU run takes and
on the path a TPU takes (the backend reported as a TPU and the program lowered
for one: the fused kernels in place). A kernel's serialised body is left out
of the lowered text, since its bytes differ with what the process lowered
before; the jaxpr beside it holds the kernel's body. Lowering needs shapes
alone: no weight is drawn, nothing is compiled, and no TPU library is
loaded.

A change that is meant to alter one of these programs updates its digest
here, in the same change, and says why.
"""

import hashlib
import json
import re

import jax
import jax.numpy as jnp
import pytest

from foremast_tpu.models import cohere2_moe, kimi_linear
from tests.test_backbone_model import tiny as tiny_cohere
from tests.test_kimi_linear_model import tiny as tiny_kimi

# (model, widths, path) -> (window program, prefill chunk)
DIGESTS = {
    ("cohere2_moe", "tiny", "cpu"): (
        "3c2056ad8d2fc0bb85e61a4e58f820ec12b46457d7b3409be903443f2a758fdb",
        "0453d5699ed2dd70be2fde7eadfad1a3b52b6159f6e4766980f054dacc26f95f",
    ),
    ("cohere2_moe", "published", "cpu"): (
        "7ca2ac30d5b944606c5a825b20e7190b6d012e0f89f80166c6a1885fb206f799",
        "4661c4ff1e35d9ff4eb433575bac6bf36eadfbbaca78775a9828258cdb466796",
    ),
    ("cohere2_moe", "published", "tpu"): (
        "f9a82ab35ea57137c9ab64956494a39db190d54b4f92a317e99b87e19611e3c6",
        "4661c4ff1e35d9ff4eb433575bac6bf36eadfbbaca78775a9828258cdb466796",
    ),
    ("kimi_linear", "tiny", "cpu"): (
        "64d189bf356f8793ae3b468a5d8c0db126fd3652fa7567b10fddf736460986d6",
        "4bbb43bf8e6156e666a259437a5170f63d42000b07efe0c7e1b6267b9676314f",
    ),
    ("kimi_linear", "published", "cpu"): (
        "3c6430f720ee19140486029fef88276c2e4665f5c35521f74a77ecd08f0bad98",
        "e3635a9d2835e41d6675574cd8ee42fdbe9db15012f69b1fe45bcec64374e8c3",
    ),
    ("kimi_linear", "published", "tpu"): (
        "f02471233cf7fbcf9573d5d0bd69ffa59a8daa14bc1ed972906ca161b38180f1",
        "0e825cc33d0b09dc52666e1a86db95b8fbd102c1dee2a697c693c0b705efe3b4",
    ),
}


def _model_file(name: str, widths: str) -> dict:
    module = {"cohere2_moe": cohere2_moe, "kimi_linear": kimi_linear}[name]
    if widths == "tiny":
        return (tiny_cohere if name == "cohere2_moe" else tiny_kimi)("bfloat16")
    with open(module.DEFAULT_MODEL_FILE, encoding="utf-8") as fh:
        return json.load(fh)


def digests(name: str, widths: str, path: str) -> tuple:
    """sha256 of the lowered window program and prefill chunk at the cell's
    shapes (published: 48 or 192 rows of 10,112 positions, a 32-point
    window bucket) or the model test's (8 rows of 24)."""
    module = {"cohere2_moe": cohere2_moe, "kimi_linear": kimi_linear}[name]
    cfg = module.Config.from_dict(_model_file(name, widths))
    seqs = {"tiny": 8, "published": 48 if name == "cohere2_moe" else 192}[widths]
    cap, w = (24, 8) if widths == "tiny" else (10112, 32)
    sd = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: module.init_params(cfg))
    state = {k: sd((seqs, *leaf.shape), leaf.dtype)
             for k, leaf in module.cache_template(cfg, cap).items()}
    i32 = jnp.int32
    chunk, ps = module.prefill_chunk_len(cfg, cap), module.prefill_seqs(cfg, cap)
    lower = {"lowering_platforms": ("tpu",)} if path == "tpu" else {}
    # new functions each call: jit's cache of traces would not see the backend
    def window_program(cfg, *args):
        return module.score_window.__wrapped__(cfg, *args)

    def prefill_program(cfg, *args):
        return module.prefill_chunk.__wrapped__(cfg, *args)

    programs = (
        (window_program, {}, (sd((seqs,), i32), sd((seqs, w), i32), sd((seqs, w), jnp.bool_))),
        (prefill_program, {"donate_argnums": 2},
         (sd((ps,), i32), sd((ps, chunk), i32), sd((), i32), sd((ps,), i32))),
    )
    out = []
    for fn, donate, args in programs:
        text = jax.jit(fn, static_argnums=0, **donate).trace(cfg, params, state, *args)
        text = re.sub(r"\\22body\\22: \\22[^\\]*\\22", "body", text.lower(**lower).as_text())
        text += str(jax.make_jaxpr(fn, static_argnums=0)(cfg, params, state, *args))
        out.append(hashlib.sha256(text.encode()).hexdigest())
    return tuple(out)


@pytest.mark.parametrize("name,widths,path", sorted(DIGESTS))
def test_the_programs_lower_to_the_text_they_lowered_to(name, widths, path, monkeypatch):
    if path == "tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert digests(name, widths, path) == DIGESTS[(name, widths, path)]
