"""True multi-process distributed test: two Python processes form one
jax.distributed cluster, build the global (data, model) mesh, and run a
cross-process reduction.

This is the only place the multi-host claims are exercised with real
process boundaries (everything else uses virtual devices in one process).
The child initializes jax.distributed FIRST because this test image's
import shims touch the backend during deep imports; on real TPU pods the
runtime auto-initializes, which init_distributed treats as idempotent
(the regression this test caught).
"""

import os
import socket
import subprocess
import sys

_CHILD = """
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
addr, pid = sys.argv[1], int(sys.argv[2])
jax.distributed.initialize(addr, 2, pid)

sys.path.insert(0, {repo!r})
import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from foremast_tpu.parallel import init_distributed, make_global_mesh

os.environ["JAX_COORDINATOR_ADDRESS"] = addr
os.environ["JAX_NUM_PROCESSES"] = "2"
os.environ["JAX_PROCESS_ID"] = str(pid)
assert init_distributed() is True  # idempotent over the prior initialize

mesh = make_global_mesh()
assert jax.device_count() == 8, jax.device_count()
assert mesh.shape == {{"data": 8, "model": 1}}, mesh.shape

x = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("data")), np.full(4, 1.0 + pid, np.float32), (8,)
)
assert float(jax.jit(jnp.sum)(x)) == 12.0  # 4x1 (proc0) + 4x2 (proc1)

assert make_global_mesh(n_model=2).shape == {{"data": 4, "model": 2}}
print(f"proc {{pid}} ok", flush=True)
"""


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_global_mesh(tmp_path):
    # bounded by the 150 s communicate() timeout below
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = tmp_path / "child.py"
    child.write_text(_CHILD.format(repo=repo))
    addr = f"127.0.0.1:{_free_port()}"
    env = {k: v for k, v in os.environ.items() if not k.startswith("JAX_")}
    procs = [
        subprocess.Popen(
            [sys.executable, str(child), addr, str(pid)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=150)
            outs.append(out)
    finally:
        # a child hung at the init barrier (peer crashed) must not leak
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert f"proc {pid} ok" in out
