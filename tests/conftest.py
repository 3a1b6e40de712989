"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax import.

Tests run on XLA's CPU backend: sharding correctness is validated on
`--xla_force_host_platform_device_count=8` CPU devices standing in for a
v5e-8 (SURVEY.md section 4 implication). The chip is reached only through
`chip_smoke.py` and the benchmarks, which do NOT import this file.
"""

import os

# tests never take the chip
os.environ["JAX_PLATFORMS"] = "cpu"
# XLA's own variable, not a foremast knob — the registry enumerates
# OUR config surface, not the toolchain's
_flags = os.environ.get("XLA_FLAGS", "")  # foremast: ignore[env-contract]
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Results must not depend on what an earlier run left behind, and tests
# write nothing into the checkout: the persistent compile cache stays
# off under test — here and in every child process a test spawns —
# wherever JAX_COMPILATION_CACHE_DIR or device.enable_compile_cache
# point it. JAX's own switch, read at import.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import numpy as np
import pytest


@pytest.fixture(scope="session")
def demo_traces():
    """The reference demo's golden canary traces as (times, values) arrays.

    data1: normal trace (~0.1-0.6); data2: same shape of traffic with
    injected 40.134 / 40.466 spikes (reference
    `examples/spring-boot-demo/src/main/resources/data{1,2}.txt`,
    replayed by `FileErrorGenerator.java:27-37`).
    """
    here = os.path.dirname(__file__)
    from datetime import datetime, timezone

    def load(name):
        ts, vs = [], []
        with open(os.path.join(here, "data", name)) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                t, v = line.split(",")
                dt = datetime.strptime(t, "%Y-%m-%d %H:%M:%S").replace(
                    tzinfo=timezone.utc
                )
                ts.append(int(dt.timestamp()))
                vs.append(float(v))
        return np.asarray(ts, dtype=np.int64), np.asarray(vs, dtype=np.float32)

    return {"normal": load("demo_canary_normal.csv"), "spike": load("demo_canary_spike.csv")}
