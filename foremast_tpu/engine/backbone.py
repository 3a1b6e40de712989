"""The model-backed detector kinds (`backbone`, `backbone_kda`,
`backbone_diffusion`): ONE set of sequence-model weights shared by the
fleet, a cache row per sequence in a fixed-capacity `TreeArena`, and the two
dispatches that use them — the chunked prefill of a cold history ("fit")
and the warm window program.

The model is a module of `foremast_tpu/models/`, chosen by the model file's
`model_type` (`MODELS`), and the detector reaches it through these names
alone (docs/backbone.md, "The model interface"):

    MODEL_TYPE, DEFAULT_MODEL_FILE, Config.from_dict(model file's dict)
    cached_span(cfg, n)                  the history points [first, stop) of n a row caches
    prefill_seqs(cfg, ctx_cap), prefill_chunk_len(cfg, ctx_cap)
    cache_template(cfg, ctx_cap)         one arena row's pytree of shapes
    init_params(cfg)                     the share's weights, from the seed
    series_scale(history), tokenize(values, scale, vocab)
    prefill_chunk(cfg, params, state, rows, ids, start, n) -> (state, counts)
    finish_rows(state, rows, n, last, scale) -> state
    score_window(cfg, params, state, rows, ids, valid)
        -> (scores, counts, dropped, *what the program itself counted a sequence)
    WINDOW_COUNTERS, window_counters(cfg, ctx_cap, valid, *counted)
        -> the names of the model's own counters, and what a dispatch adds to them

`MultivariateJudge` owns one `BackboneDetector` a kind and calls `ensure`
from its slow path and `score` from both. Each alias of a document is one
sequence, keyed by its history's fit key, so a followed job of the same
service finds its rows and prefills nothing. A row is device state only: it
is never journalled, and a restarted worker prefills again
(docs/backbone.md).
"""

from __future__ import annotations

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from foremast_tpu.engine.arena import TreeArena
from foremast_tpu.observe.spans import note, span

# model file's `model_type` -> the module that runs it
MODELS = {
    "cohere2_moe": "foremast_tpu.models.cohere2_moe",
    "kimi_linear": "foremast_tpu.models.kimi_linear",
    "sdar_moe": "foremast_tpu.models.sdar_moe",
}


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def _hbm(array) -> dict:
    """The allocator's state of the device that holds `array`, as span
    attrs: `hbm_in_use` and `hbm_largest_free`, where the backend reports
    them (a CPU reports neither)."""
    stats = next(iter(array.devices())).memory_stats() or {}
    return {
        attr: int(stats[key])
        for attr, key in (("hbm_in_use", "bytes_in_use"),
                          ("hbm_largest_free", "largest_free_block_bytes"))
        if key in stats
    }


def load_model(model_file: str | None, model_types: tuple):
    """(the model's module, its config) for `model_file`, or for the packaged
    file of the first of `model_types` where none is named. A file whose
    `model_type` is not one of `model_types` (what the kind takes) is an
    error here, at load."""
    if not model_file:
        model_file = importlib.import_module(MODELS[model_types[0]]).DEFAULT_MODEL_FILE
    with open(model_file, encoding="utf-8") as fh:
        d = json.load(fh)
    found = d.get("model_type")
    if found not in model_types:
        raise ValueError(
            f"model file {model_file}: model_type {found!r} is not one this detector "
            f"kind takes ({', '.join(model_types)})"
        )
    model = importlib.import_module(MODELS[found])
    return model, model.Config.from_dict(d)


class BackboneDetector:
    def __init__(self, model_file: str | None = None, context: int | None = None,
                 rows: int | None = None, model_types: tuple = ("cohere2_moe",)):
        env = os.environ.get
        self.model, self.cfg = load_model(
            model_file or env("FOREMAST_BACKBONE_MODEL") or None, model_types
        )
        model = self.model
        # history points a sequence keeps (the newest); the model says
        # which of them its row caches (`cached_span`), and the leaves are
        # sized to that rounded up to a multiple of 128 (8 for a toy
        # context), never to a power of two
        self.context = int(context or env("FOREMAST_BACKBONE_CONTEXT", "10080"))
        first, stop = model.cached_span(self.cfg, self.context)
        cached = stop - first
        self.ctx_cap = _round_up(cached, 128 if cached > 128 else 8)
        self.capacity = int(rows or env("FOREMAST_BACKBONE_ROWS", "64"))
        self.prefill_seqs = model.prefill_seqs(self.cfg, self.ctx_cap)
        self.chunk = model.prefill_chunk_len(self.cfg, self.ctx_cap)
        self.arena = TreeArena(
            model.cache_template(self.cfg, self.ctx_cap), fixed_rows=self.capacity
        )
        self.evicted: list = []
        self.arena.on_evict = self.evicted.append
        self._params = None
        held = self.cfg.share.experts_held
        self.expert_tokens = np.zeros(held, np.int64)  # window program only
        self.prefill_tokens = 0
        self.window_tokens = 0
        self.dropped_tokens = 0
        # the model's own counters of its window dispatches (every model:
        # `sorted_moe_tokens`; Cohere2: `fused_attn_tokens`; Kimi-Linear:
        # `latent_positions`, `fused_kda_tokens`; SDAR:
        # `denoise_tokens`, `clean_tokens`, `fused_attn_tokens`)
        self.model_counters = dict.fromkeys(model.WINDOW_COUNTERS, 0)

    @property
    def params(self):
        if self._params is None:
            with span("backbone.init_weights", device=True):
                self._params = self.model.init_params(self.cfg)
        return self._params

    def counters(self) -> dict:
        c = self.arena.counters()
        return {
            "prefill_tokens": self.prefill_tokens,
            "window_tokens": self.window_tokens,
            **self.model_counters,
            "cache_rows_live": c["rows_live"],
            "cache_hits": c["hits"],
            "cache_misses": c["misses"],
            "dropped_tokens": self.dropped_tokens,
            "expert_tokens": self.expert_tokens.tolist(),
        }

    # -- cold: the chunked prefill ("fit") ---------------------------------

    # foremast: device-boundary
    def ensure(self, keys: list, histories: list) -> list:
        """Rows for the sequences `keys`, each with its history [n]
        float32; those that have no row are tokenised and the span of it
        the model caches (`cached_span`) is prefilled. At most `capacity`
        sequences a call. -> per sequence (scale, cached positions, last
        history id)."""
        cfg, arena, model = self.cfg, self.arena, self.model
        hists = [np.asarray(h, np.float32)[-self.context:] for h in histories]
        assigned = arena.assign(keys, [])
        if assigned is None:
            raise RuntimeError(
                f"backbone: {len(keys)} sequences in one dispatch group exceed the "
                f"cache's {self.capacity} rows (FOREMAST_BACKBONE_ROWS)"
            )
        rows, cold = assigned
        out = [None] * len(keys)
        missing = set(cold)
        for i, key in enumerate(keys):
            if i not in missing:
                out[i] = arena.row_entry[key]
        if not cold:
            return out
        vocab = cfg.share.vocab_rows_held
        todo = sorted(cold, key=lambda i: -len(hists[i]))
        with span("judge.prefill", stage="fit", seqs=len(todo), device=True) as sp:
            tokens = chunks = 0
            for at in range(0, len(todo), self.prefill_seqs):
                members = todo[at : at + self.prefill_seqs]
                # a short last group is filled up with sequences of no
                # tokens: nothing of theirs is valid, so nothing is written
                real = len(members)
                scale = np.ones(self.prefill_seqs, np.float32)
                ids = np.zeros((self.prefill_seqs, self.ctx_cap + 1), np.int32)
                n = np.zeros(self.prefill_seqs, np.int32)
                last = np.zeros(self.prefill_seqs, np.int32)
                row = np.full(self.prefill_seqs, rows[members[0]], np.int32)
                for b, i in enumerate(members):
                    scale[b] = model.series_scale(hists[i])
                    tok = model.tokenize(hists[i], scale[b], vocab)
                    first, stop = model.cached_span(cfg, len(tok))
                    ids[b, : stop - first] = tok[first:stop]
                    n[b], last[b], row[b] = stop - first, tok[-1], rows[i]
                r, nj = jnp.asarray(row), jnp.asarray(n)
                for start in range(0, int(n.max()), self.chunk):
                    stop = min(start + self.chunk, self.ctx_cap)
                    arena.state, _ = model.prefill_chunk(
                        cfg, self.params, arena.state, r, jnp.asarray(ids[:, start:stop]),
                        jnp.int32(start), nj,
                    )
                    chunks += 1
                arena.state = model.finish_rows(
                    arena.state, r[:real], nj[:real], jnp.asarray(last[:real]),
                    jnp.asarray(scale[:real]),
                )
                for b, i in enumerate(members):
                    out[i] = arena.row_entry[keys[i]] = (
                        float(scale[b]), int(n[b]), int(last[b])
                    )
                tokens += int(n.sum())
            self.prefill_tokens += tokens
            note(sp, tokens=tokens, chunks=chunks)
        return out

    # -- warm: the window program -------------------------------------------

    # foremast: device-boundary
    def score(self, keys: list, scales: np.ndarray, windows: np.ndarray,
              valid: np.ndarray) -> np.ndarray:
        """score_t of the windows [S, W] float32 (`valid` [S, W]: real
        points) of the sequences `keys`, whose rows have to be live. The
        batch is padded to a power of two, or to the cache's capacity, so
        that a claim's size cannot force a compile. -> [S, W] float32."""
        cfg, arena, model = self.cfg, self.arena, self.model
        s, w = windows.shape
        with span("judge.tokenize", stage="pack", seqs=s, tokens=int(valid.sum())):
            ids = model.tokenize(windows, scales, cfg.share.vocab_rows_held)
            sb = 8
            while sb < s:
                sb *= 2
            sb = max(s, min(sb, _round_up(self.capacity, 8)))
            if sb != s:
                ids = np.concatenate([ids, np.zeros((sb - s, w), np.int32)])
                valid = np.concatenate([valid, np.zeros((sb - s, w), bool)])
        with span("judge.arena_assemble", stage="arena_assemble", rows=s, device=True):
            assigned = arena.assign(keys, [])
            if assigned is None or assigned[1]:
                raise RuntimeError(
                    "backbone: a warm sequence has no cache row: the fleet outgrew "
                    f"the cache's {self.capacity} rows (FOREMAST_BACKBONE_ROWS)"
                )
            rows = np.full(sb, assigned[0][0], np.int32)
            rows[:s] = assigned[0]
        with span("judge.h2d", stage="h2d", rows=sb, device=True) as sp:
            note(sp, bytes=int(rows.nbytes + ids.nbytes + valid.nbytes))
            handed = (jnp.asarray(rows), jnp.asarray(ids), jnp.asarray(valid))
        with span("judge.score", stage="score", rows=sb, seqs=s,
                  tokens=int(valid.sum()), device=True):
            scores, counts, dropped, *counted = model.score_window(
                cfg, self.params, arena.state, *handed
            )
        out = (scores, counts, dropped, counted)
        # the wait for the program alone: device idle under it is time the
        # runtime had not yet run the program (a launch, a transfer, an
        # allocation) or not yet told the host it ended, where idle under
        # `judge.decode` is the copy
        with span("judge.result_wait", stage="decode", rows=sb, device=True) as sp:
            jax.block_until_ready(out)
            if sp is not None:
                note(sp, **_hbm(scores))
        with span("judge.decode", stage="decode", rows=sb, device=True):
            scores, counts, dropped, counted = jax.device_get(out)
            scores = scores[:s]
            self.window_tokens += int(valid.sum())
            counted = [c[:s] for c in counted]
            for name, n in model.window_counters(
                cfg, self.ctx_cap, valid[:s], *counted
            ).items():
                self.model_counters[name] += n
            self.expert_tokens += counts
            self.dropped_tokens += int(dropped)
        return scores
