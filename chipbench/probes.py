"""What the harness reads from the program: its spans' stage seconds, its
counters and JAX's compile events. Cumulative snapshots; a window's
numbers are the difference of two."""

from __future__ import annotations

import threading

_COMPILE_EVENTS = {"cache_misses": 0, "backend_compiles": 0}
_LOCK = threading.Lock()
_INSTALLED = False


def install_compile_listeners() -> None:
    """Count `/jax/compilation_cache/cache_misses` (a real XLA compile)
    and backend-compile durations (a program that missed the in-process
    dispatch cache, whether it then compiled or loaded)."""
    global _INSTALLED
    if _INSTALLED:
        return
    import jax.monitoring as mon

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_misses":
            with _LOCK:
                _COMPILE_EVENTS["cache_misses"] += 1

    def on_duration(name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            with _LOCK:
                _COMPILE_EVENTS["backend_compiles"] += 1

    mon.register_event_listener(on_event)
    mon.register_event_duration_secs_listener(on_duration)
    _INSTALLED = True


def compile_events() -> dict:
    with _LOCK:
        return dict(_COMPILE_EVENTS)


class Probe:
    def __init__(self, worker, registry):
        self.worker, self.registry = worker, registry

    def snapshot(self) -> dict:
        snap: dict = {}
        for fam in self.registry.collect():
            if fam.name == "foremast_tick_stage_seconds":
                for s in fam.samples:
                    if s.name.endswith("_sum"):
                        snap["stage_s." + s.labels["stage"]] = s.value
                    elif s.name.endswith("_count"):
                        snap["stage_n." + s.labels["stage"]] = s.value
        w = self.worker
        degrade = getattr(w, "_degrade", None)
        snap["degraded_docs"] = float(
            sum(degrade.stats.docs_snapshot().values()) if degrade is not None else 0
        )
        for arena, counters in (
            ("uni", w._uni.device_state_counters() if w._uni is not None else {}),
            ("joint", w._mvj.joint_state_counters() if getattr(w, "_mvj", None) else {}),
        ):
            for key, v in counters.items():
                snap[f"arena.{arena}.{key}"] = float(v)
        for kind, v in w._fast_kinds.items():
            snap["fast_docs." + kind] = float(v)
        for key, v in compile_events().items():
            snap["compile." + key] = float(v)
        return snap


_GAUGES = ("rows_live", "capacity_rows")


def delta(before: dict, after: dict) -> dict:
    out = {}
    for key, v in after.items():
        if key.rsplit(".", 1)[-1] in _GAUGES:
            out[key] = v
        else:
            out[key] = v - before.get(key, 0.0)
    return out
