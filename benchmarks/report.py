"""Machine-readable bench summaries: ``BENCH_rNN.json`` (ISSUE 15).

Every ``make bench-*`` entry point folds its headline result into one
JSON artifact per benchmark round at the repo root:

    BENCH_r17.json
    {
      "round": 17,
      "generated_by": "benchmarks.report",
      "results": {
        "latency": {"asserts_passed": true, ...headline numbers...},
        "mixed":   {...},
        ...
      }
    }

so the perf trajectory (throughput, p50/p99, in-run asserts) is
diffable across PRs. One file per round, one key per bench — re-running
a bench inside the same round overwrites only its own key.

Round resolution: ``FOREMAST_BENCH_ROUND`` when set (re-running a bench
for an existing round), else the highest existing ``BENCH_rNN.json``
**plus one** — a bench run is, by definition, the next round.

``--small`` smoke runs never write (tier-1 tests must not dirty the
tree); pass ``path`` to redirect (tests use a tmpdir).
"""

from __future__ import annotations

import json
import os
import re

_ROUND_RE = re.compile(r"^BENCH_r(\d+)\.json$")


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def current_round(root: str | None = None) -> int:
    """The round this bench run measures (see module docstring)."""
    env = os.environ.get("FOREMAST_BENCH_ROUND", "")
    if env:
        try:
            return int(env)
        except ValueError:
            pass
    root = _repo_root() if root is None else root
    try:
        names = os.listdir(root)
    except OSError:
        names = []
    rounds = [int(m.group(1)) for m in map(_ROUND_RE.match, names) if m]
    return (max(rounds) + 1) if rounds else 1


def write_summary(
    bench: str,
    result: dict,
    small: bool = False,
    asserts_passed: bool = True,
    path: str | None = None,
    recompiles: dict | None = None,
    tenants: dict | None = None,
) -> str | None:
    """Fold one bench's headline result into the round's JSON artifact.

    Returns the file path written, or None for smoke runs. `result`
    must already be JSON-serializable (every bench prints it as a JSON
    line — this is the same dict). `recompiles` is the bench's
    RecompileWitness snapshot ({"total": N, "<phase>": n, ...}) when it
    ran one — benches assert zero WARM-phase backend compiles in-run
    (docs/static-analysis.md, rule recompile-hazard); the artifact pins
    the counts so a cache-key leak shows up as a diff even where no
    phase asserts. `tenants` is the run's end-state per-tenant
    accounting snapshot ({tenant: {shed, evictions, claims,
    ring_bytes}}, ISSUE 20) when the bench ran tenanted — pinned so a
    QoS regression (sheds landing on quiet tenants, evictions charged
    to the wrong tenant) is a JSON diff, not just an in-run assert.
    Failures to write are raised: a CI lane asking for the artifact
    must not silently get prose only."""
    if small:
        return None
    if path is None:
        rnd = current_round()
        path = os.path.join(_repo_root(), f"BENCH_r{rnd:02d}.json")
    else:
        # an explicit BENCH_rNN.json names its own round
        named = _ROUND_RE.match(os.path.basename(path))
        rnd = (
            int(named.group(1))
            if named
            else current_round(os.path.dirname(path) or ".")
        )
    doc = {"round": rnd, "generated_by": "benchmarks.report", "results": {}}
    try:
        with open(path) as f:
            existing = json.load(f)
    except OSError:
        existing = None  # absent: start fresh
    except ValueError as e:
        raise ValueError(
            f"{path} exists but is not JSON; refusing to overwrite a "
            "foreign artifact — set FOREMAST_BENCH_ROUND"
        ) from e
    if existing is not None:
        if not (
            isinstance(existing, dict)
            and existing.get("generated_by") == "benchmarks.report"
        ):
            # a file we did not write (e.g. a driver artifact from an
            # early round) must never be clobbered — fail loudly, the
            # round resolution is misconfigured
            raise ValueError(
                f"{path} exists with a foreign schema; refusing to "
                "overwrite — set FOREMAST_BENCH_ROUND to the intended "
                "round"
            )
        doc = existing
        doc["round"] = rnd
        if not isinstance(doc.get("results"), dict):
            doc["results"] = {}
    entry = dict(result, asserts_passed=asserts_passed)
    if recompiles is not None:
        entry["recompiles"] = recompiles
    if tenants is not None:
        entry["tenants"] = tenants
    doc["results"][bench] = entry
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path
