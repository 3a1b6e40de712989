"""Record the small TPU trace the reduction is tested against (run on the
chip, once, by the PR that changes the reduction):

    python3 -m chipbench.tests.record_trace <out_dir>

Writes `v5e_small.xplane.pb` and `v5e_small.expected.json` (what
`tracered.reduce` gives for it, to be read and checked by hand before it
is committed under chipbench/testdata/).
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from chipbench import tracered
    from foremast_tpu.device import require_tpu

    require_tpu()

    @jax.jit
    def small_gather_score(table, rows, x):
        return jnp.sum(jnp.take(table, rows, axis=0) * x, axis=-1)

    table = jnp.ones((4096, 256), jnp.float32)
    rows = jnp.arange(512, dtype=jnp.int32)
    x = jnp.ones((512, 256), jnp.float32)
    small_gather_score(table, rows, x).block_until_ready()
    trace_dir = os.path.join(out_dir, "_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(tracered.SYNC):
        time.sleep(0.002)
    for _ in range(5):
        small_gather_score(table, rows, x).block_until_ready()
        time.sleep(0.01)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[-1]
    dst = os.path.join(out_dir, "v5e_small.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(trace_dir)
    pd = tracered.load(dst)
    red = tracered.reduce(pd)
    red["planes"] = [
        {"plane": p.name, "lines": [[ln.name, len(list(ln.events))] for ln in p.lines]}
        for p in pd.planes
    ]
    red["sync_ns"] = tracered.sync_ns(pd)
    with open(os.path.join(out_dir, "v5e_small.expected.json"), "w") as fh:
        json.dump(red, fh, indent=1)
    print(json.dumps({"bytes": os.path.getsize(dst), **{k: red[k] for k in ("devices_traced", "busy_s", "modules")}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
