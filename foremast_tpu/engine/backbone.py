"""The `backbone` detector kind: ONE set of sequence-model weights shared
by the fleet (`models/cohere2_moe.py`), a prefix cache row per sequence in
a fixed-capacity `TreeArena`, and the two dispatches that use them — the
chunked prefill of a cold history ("fit") and the warm window program.

`MultivariateJudge` owns one `BackboneDetector` under
`ML_ALGORITHM=backbone` and calls `ensure` from its slow path and `score`
from both. Each alias of a document is one sequence, keyed by its history's
fit key, so a followed job of the same service finds its rows and prefills
nothing. A row is device state only: it is never journalled, and a
restarted worker prefills again (docs/backbone.md).
"""

from __future__ import annotations

import os

import jax.numpy as jnp
import numpy as np

from foremast_tpu.engine.arena import TreeArena
from foremast_tpu.models import cohere2_moe as model
from foremast_tpu.observe.spans import note, span


# sequences a prefill dispatch holds (each up to 2,048 tokens a chunk): what
# the prefill's activations leave room for beside 13.9 GB of weights and cache
PREFILL_SEQS = 2


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


class BackboneDetector:
    def __init__(self, model_file: str | None = None, context: int | None = None,
                 rows: int | None = None):
        env = os.environ.get
        self.cfg = model.Cohere2MoeConfig.from_file(
            model_file or env("FOREMAST_BACKBONE_MODEL") or None
        )
        # history points a sequence keeps (the newest); all but the last
        # are cached, and the leaves are sized to that rounded up to a
        # multiple of 128 (8 for a toy context), never to a power of two
        self.context = int(context or env("FOREMAST_BACKBONE_CONTEXT", "10080"))
        cached = self.context - 1
        self.ctx_cap = _round_up(cached, 128 if cached > 128 else 8)
        self.capacity = int(rows or env("FOREMAST_BACKBONE_ROWS", "64"))
        self.prefill_seqs = PREFILL_SEQS
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
        self.fused_attn_tokens = 0  # of window_tokens: dispatches that took the fused kernel
        self.dropped_tokens = 0

    @property
    def params(self):
        if self._params is None:
            with span("backbone.init_weights", device=True):
                self._params = model.init_params(self.cfg)
        return self._params

    def counters(self) -> dict:
        c = self.arena.counters()
        return {
            "prefill_tokens": self.prefill_tokens,
            "window_tokens": self.window_tokens,
            "fused_attn_tokens": self.fused_attn_tokens,
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
        float32; those that have no row are tokenised and prefilled. At
        most `capacity` sequences a call. -> per sequence (scale, cached
        positions, last history id)."""
        cfg, arena = self.cfg, self.arena
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
                    ids[b, : len(tok)] = tok
                    n[b], last[b], row[b] = len(tok) - 1, tok[-1], rows[i]
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
        cfg, arena = self.cfg, self.arena
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
            scores, counts, dropped = model.score_window(
                cfg, self.params, arena.state, *handed
            )
        with span("judge.decode", stage="decode", rows=sb, device=True):
            scores = np.asarray(scores)[:s]
        tokens = int(valid.sum())
        self.window_tokens += tokens
        if model.fused_window_attention(cfg, self.ctx_cap, w):
            self.fused_attn_tokens += tokens
        self.expert_tokens += np.asarray(counts)
        self.dropped_tokens += int(dropped)
        return scores
