"""What a process got from JAX, stated once: the backend it landed on,
the gate that refuses to measure anywhere but a TPU, and where the
persistent compile cache lives.

Every entry point that starts device work (`cli worker`, `chip_smoke.py`,
`bench.py`, the `benchmarks/` mains) calls `enable_compile_cache()`
before its first computation; the ones whose numbers are only meaningful
on the chip call `require_tpu()` right after. jax is imported inside the
functions so importing this module never initializes a backend.
"""

from __future__ import annotations

import os

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its
    directory. `JAX_COMPILATION_CACHE_DIR` wins — JAX has already read
    it, so no directory is set here at all. Otherwise the cache sits at
    the FIXED path `<checkout>/.jax_cache`: the path is part of the
    cache key, so a temp dir, pid or timestamp would never hit. Must
    run before the first jax computation."""
    import jax

    # JAX's own variable, not a foremast knob
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):  # foremast: ignore[env-contract]
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(_REPO, ".jax_cache")
        )
    # the default gates skip fast/small compiles; every judgment bucket
    # should persist, including sub-second ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax.config.jax_compilation_cache_dir


def device_info() -> dict:
    """The device as JAX reports it — stamped on every benchmark line."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def require_tpu() -> dict:
    """`device_info()`, or exit non-zero naming the platform found. A
    number from XLA's CPU backend must never be recorded as a device
    metric, so measurement entry points fail here instead of falling
    back (their `--small` / `BENCH_SMALL=1` CPU smoke modes skip it)."""
    info = device_info()
    if info["platform"] != "tpu":
        raise SystemExit(
            f"no TPU: jax selected platform {info['platform']!r} "
            f"({info['device_count']} x {info['device_kind']}); this entry "
            "point only measures on the chip"
        )
    return info
