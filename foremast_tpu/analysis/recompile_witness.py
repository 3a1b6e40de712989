"""Runtime recompile witness: pin the zero-warm-recompile contract.

The static rules (`recompile_hazard.py`) catch the *shapes* of dispatch
-cache blowups — unbucketed dims, unbounded statics, per-call `jax.jit`
— but the contract users feel is dynamic: after the cold tick has
compiled every bucket program, a warm tick must execute entirely from
XLA's compilation cache. A single warm-path recompile is a multi-second
latency cliff on TPU (PAPER.md §design: the health manager's verdict
cadence is the product surface), and nothing in tier-1 used to notice.

This module closes that loop the way `witness.py` does for lock order:

  * `install()` registers a ``jax.monitoring`` duration listener for
    the ``/jax/core/compile/backend_compile_duration`` event — fired
    once per program that misses the in-process dispatch cache, never
    on a dispatch-cache hit — so the count is the ground truth the
    static rules approximate. The event wraps JAX's persistent
    compilation cache lookup too: a program loaded from that cache
    still counts, so the witness reads the same with the cache cold or
    warm (``/jax/compilation_cache/cache_misses`` is the event that
    tells the two apart — chip_smoke.py counts it);
  * `phase("warm")` scopes counts to a named region: benches wrap the
    cold tick and the warm loop separately and assert the warm count is
    ZERO in-run (`benchmarks/latency_bench.py`,
    `benchmarks/mixed_bench.py`), and the counts land in the round's
    ``BENCH_rNN.json`` via `benchmarks.report.write_summary`'s
    ``recompiles`` field;
  * production workers run it under ``FOREMAST_RECOMPILE_WITNESS=1``
    (`cli.cmd_worker`), which logs the total compile count at exit —
    a warm fleet whose count keeps growing has a cache-key leak.

Everything jax-touching is imported lazily inside `install()`: the
static runner (`make check`) imports this package and must never pay —
or wedge on — an accelerator backend init (see `core.py`).
"""

from __future__ import annotations

import atexit
import contextlib
import logging
import os

log = logging.getLogger("foremast_tpu.analysis")

# one event per dispatch-cache miss; dispatch-cache hits fire nothing
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class RecompileWitness:
    """Counts backend compiles, total and per named phase."""

    def __init__(self):
        self.total = 0
        self.phases: dict[str, int] = {}
        self._phase: str | None = None
        self._installed = False

    # -- the jax.monitoring listener -------------------------------------

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if not self._installed or not event.startswith(COMPILE_EVENT):
            return
        self.total += 1
        if self._phase is not None:
            self.phases[self._phase] = self.phases.get(self._phase, 0) + 1

    def install(self) -> "RecompileWitness":
        if not self._installed:
            try:
                from jax import monitoring
            except Exception:  # no jax: stay a zero-counting stub
                return self
            monitoring.register_event_duration_secs_listener(self._on_event)
            self._installed = True
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        self._installed = False
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._on_event)

    # -- phases and counts -----------------------------------------------

    @contextlib.contextmanager
    def phase(self, name: str):
        """Attribute compiles inside the block to `name`. Phases do not
        nest (benches are sequential cold/warm regions); the previous
        phase resumes on exit."""
        prev, self._phase = self._phase, name
        try:
            yield self
        finally:
            self._phase = prev

    def count(self, phase: str | None = None) -> int:
        if phase is None:
            return self.total
        return self.phases.get(phase, 0)

    def snapshot(self) -> dict:
        """JSON-shaped counts for BENCH_rNN.json's `recompiles` field."""
        return {"total": self.total, **dict(sorted(self.phases.items()))}

    def assert_zero(self, phase: str) -> None:
        """The in-run bench gate: a warm phase that compiled ANYTHING is
        a dispatch-cache regression, not a slow run."""
        n = self.count(phase)
        assert n == 0, (
            f"recompile witness: {n} backend compile(s) during the "
            f"'{phase}' phase — the warm path must run entirely from the "
            f"dispatch cache (docs/static-analysis.md, rule "
            f"recompile-hazard); counts: {self.snapshot()}"
        )


# ---------------------------------------------------------------------------
# module-level lifecycle (mirrors analysis/witness.py)
# ---------------------------------------------------------------------------

_current: RecompileWitness | None = None


def install() -> RecompileWitness:
    """Install (or return the already-installed) process witness."""
    global _current
    if _current is None:
        _current = RecompileWitness()
    return _current.install()


def uninstall() -> None:
    global _current
    if _current is not None:
        _current.uninstall()
        _current = None


def current() -> RecompileWitness | None:
    return _current


def install_from_env(env=None) -> RecompileWitness | None:
    """`FOREMAST_RECOMPILE_WITNESS=1` wiring for long-lived entry
    points (cli worker): install before the first dispatch, log the
    compile count at interpreter exit — never raise."""
    e = os.environ if env is None else env
    if e.get("FOREMAST_RECOMPILE_WITNESS", "") != "1":
        return None
    witness = install()

    def _report():
        log.info(
            "recompile witness: %d backend compile(s) this process "
            "(a warm fleet whose count keeps growing has a dispatch "
            "cache-key leak): %s",
            witness.total, witness.snapshot(),
        )

    atexit.register(_report)
    return witness
