"""sharding-contract: device placement stays behind the `_place` seam.

The device-mesh fast path (CHANGES PR 13) works because placement is
CENTRALIZED: every buffer entering a sharded dispatch goes through
`HealthJudge._place` / `_place_cols`, which `ShardedJudge` overrides
with the mesh `device_put`. A direct `jnp.asarray`/`jax.device_put` in
warm-path code commits the buffer to the DEFAULT device first, turning
the sharded placement into a second copy (engine/judge.py's host-buffer
comment pins this), or — worse — silently bypassing the partition and
breaking byte parity across arms. ISSUE 19 rewrote the arena seam
(replicated rows -> data-axis-sharded row blocks); this rule keeps the
NEW layout's invariants from drifting into a parity break.

Two checks, both scoped to the warm-path modules:

  * PLACEMENT — in ``foremast_tpu/jobs/`` (the worker never touches
    jax directly: buffers stay host numpy until the judge places them)
    and ``foremast_tpu/parallel/batch.py`` (the sharded judge itself),
    a direct ``jnp.asarray``/``jnp.array``/``jax.device_put`` call
    outside the placement hooks (`_place`, `_place_cols`) is a
    finding. `parallel/mesh.py` is the placement LIBRARY (the hooks
    call into it) and `parallel/seqparallel.py`/`distributed.py` are
    jit-interior collective code, so they are out of scope by design.
  * SHARDED ARENA — arena references from sharded code
    (``foremast_tpu/parallel/``) must sit in a function annotated
    ``# foremast: sharded-arena``: the arena block-partitions its ROW
    space over the data axis (`ShardedJudge._arena_sharding`, ISSUE
    19) with row placement tied to batch position (position i of a
    B-row batch lives on shard ``i // (B / shards)``), so warm gathers
    take LOCAL indices inside shard_map and never cross chips. Any new
    arena touchpoint in parallel/ must declare it honors that
    contract — global indices fed to the local gather, or a
    concatenate/reshape that re-blocks the row axis, silently turns
    the device-local gather into garbage rows or an all-gather. The
    annotation inventory lives in docs/static-analysis.md.
"""

from __future__ import annotations

import ast

from foremast_tpu.analysis.core import Finding
from foremast_tpu.analysis.interproc import Program, dotted, own_body_walk

RULE = "sharding-contract"
ARENA_MARKER = "sharded-arena"

PLACEMENT_HOOKS = frozenset({"_place", "_place_cols"})
PLACEMENT_SCOPE = ("foremast_tpu/jobs/", "foremast_tpu/parallel/batch.py")
ARENA_SCOPE = ("foremast_tpu/parallel/",)
_PLACERS = frozenset({"jnp.asarray", "jnp.array", "jax.device_put",
                      "jax.numpy.asarray", "jax.numpy.array", "device_put"})


def check_sharding_contract(program: Program) -> list[Finding]:
    findings: list[Finding] = []
    for fn in program.functions:
        rel = fn.module.relpath
        if rel.startswith(PLACEMENT_SCOPE) and fn.name not in PLACEMENT_HOOKS:
            findings.extend(_placement_findings(fn))
        if rel.startswith(ARENA_SCOPE):
            findings.extend(_arena_findings(fn))
    return findings


def _placement_findings(fn) -> list[Finding]:
    out: list[Finding] = []
    for node in own_body_walk(fn.node):
        if not isinstance(node, ast.Call):
            continue
        d = dotted(node.func)
        if d in _PLACERS:
            out.append(
                fn.module.finding(
                    RULE,
                    node,
                    f"direct `{d}` in warm-path code (`{fn.name}`): "
                    "buffers entering a sharded dispatch must go through "
                    "`_place`/`_place_cols` — committing to the default "
                    "device first makes the mesh placement a second copy "
                    "(or bypasses the partition entirely)",
                    hint="keep the buffer host-side (numpy) and let the "
                    "judge's placement hook put it on the mesh; bench-only "
                    "constructors may suppress with `# foremast: "
                    "ignore[sharding-contract]` + a citation",
                )
            )
    return out


def _arena_findings(fn) -> list[Finding]:
    if fn.module.marked_def(fn.node, ARENA_MARKER):
        return []
    out: list[Finding] = []
    for node in own_body_walk(fn.node):
        name = None
        if isinstance(node, ast.Name):
            name = node.id
        elif isinstance(node, ast.Attribute):
            name = node.attr
        if name is None or "arena" not in name.lower():
            continue
        # line-level annotation also counts (single-expression touches)
        if fn.module.marked(getattr(node, "lineno", fn.node.lineno),
                            ARENA_MARKER):
            continue
        out.append(
            fn.module.finding(
                RULE,
                node,
                f"arena reference `{name}` in sharded code (`{fn.name}`) "
                "without the sharded-arena annotation — arena rows "
                "block-shard over the data axis with position-tied "
                "placement (ShardedJudge._arena_sharding, ISSUE 19); "
                "code that touches them from parallel/ must declare it "
                "honors that layout",
                hint="mark the enclosing def (or this line) with "
                "`# foremast: sharded-arena` after checking the access "
                "keeps row placement aligned with batch position (local "
                "indices into shard_map gathers, no row-axis re-blocking) "
                "— docs/static-analysis.md",
            )
        )
        break  # one finding per function is enough signal
    return out
