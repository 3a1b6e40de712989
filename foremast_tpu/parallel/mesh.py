"""Device-mesh construction and sharding helpers.

The reference's only "parallelism" is N shared-nothing brain pods polling a
queue (SURVEY.md section 2.8). The TPU-native replacement is a 2-D
`jax.sharding.Mesh`:

  * `data`  — the (service x metric) batch axis: pure DP over ICI; the
    scoring program partitions with zero collectives (embarrassingly
    parallel windows), matching "batched scoring: one jitted program
    scoring 100k windows as array dims in HBM";
  * `model` — tensor-parallel axis for the learned detectors (LSTM gate
    dimension) and the sequence-parallel axis for long-window scans.

Works identically on real TPU slices and on virtual CPU devices
(`xla_force_host_platform_device_count`), which is how multi-chip tests and
the driver's `dryrun_multichip` run without hardware.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(
    n_data: int | None = None,
    n_model: int = 1,
    devices: list | None = None,
) -> Mesh:
    """Build a (data, model) mesh over the available devices.

    Defaults to all devices on the data axis (the scoring engine's natural
    layout: DP over windows). `n_data=None` derives it from the device
    count / n_model.
    """
    devs = devices if devices is not None else jax.devices()
    if n_data is None:
        n_data = len(devs) // n_model
    need = n_data * n_model
    if need > len(devs):
        raise ValueError(f"mesh {n_data}x{n_model} needs {need} devices, have {len(devs)}")
    grid = np.asarray(devs[:need]).reshape(n_data, n_model)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def data_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    """Shard the leading (batch) axis over `data`, replicate the rest."""
    return NamedSharding(mesh, P(DATA_AXIS, *([None] * (ndim - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_leading(tree, mesh: Mesh):
    """device_put every array in a pytree with its leading axis on `data`."""
    return jax.tree.map(
        lambda a: jax.device_put(a, data_sharding(mesh, np.ndim(a))), tree
    )


def pad_to_multiple(n: int, k: int) -> int:
    """Smallest multiple of k >= n (batch padding for even sharding)."""
    return ((n + k - 1) // k) * k


def shard_rows_take(tree, rows, mesh: Mesh):
    """Device-local row gather from data-axis-sharded arena state
    (ISSUE 19). `tree` leaves are [capacity, ...] arrays block-sharded
    over DATA_AXIS (capacity = n_data * cap_s); `rows` [B] holds LOCAL
    (per-shard) row indices with its leading axis sharded over the same
    data blocks — the arena's block placement rule guarantees position
    i's row lives in the device holding batch position i. Expressed as a
    shard_map (composes inside jit) so each device takes rows from its
    OWN capacity block and XLA can never insert a collective for the
    gather: a plain global `jnp.take` on a sharded operand is free to
    all-gather it, which is exactly the cross-chip leg the sharded
    arena exists to delete."""
    import jax.numpy as jnp

    spec = jax.tree.map(
        lambda a: P(DATA_AXIS, *([None] * (np.ndim(a) - 1))), tree
    )
    return jax.shard_map(
        lambda rs, t: jax.tree.map(lambda a: jnp.take(a, rs, axis=0), t),
        mesh=mesh,
        in_specs=(P(DATA_AXIS), spec),
        out_specs=spec,
        check_vma=False,
    )(rows, tree)


# ---------------------------------------------------------------------------
# Worker device mesh (ISSUE 13): every BrainWorker's judge runs over a
# local device mesh by default — FOREMAST_DEVICE_MESH selects the shape.
# ---------------------------------------------------------------------------


def device_mesh_spec(env: dict | None = None) -> tuple[int | None, int] | None:
    """Parse `FOREMAST_DEVICE_MESH` (+ `FOREMAST_DEVICE_MESH_MODEL`).

    Returns (n_data, n_model) for `make_mesh`, or None when the device
    mesh is disabled. Accepted spellings:

      * unset / "auto" — all local devices on the data axis (n_data=None
        derives it from the device count; on a stock CPU host that is a
        1-device mesh, i.e. the identity);
      * "0" / "off"    — disabled: no mesh placement at all (the
        pre-ISSUE-13 single-device behavior);
      * "N"            — N devices on the data axis;
      * "NxM"          — explicit (data, model) grid (the axis override;
        `FOREMAST_DEVICE_MESH_MODEL` sets M for the other spellings).

    Malformed values warn and fall back to "auto" — a templated env must
    never kill worker startup (the FOREMAST_MICROTICK_* precedent)."""
    import logging
    import os

    e = os.environ if env is None else env
    raw = (e.get("FOREMAST_DEVICE_MESH") or "auto").strip().lower()
    n_model = 1
    raw_model = (e.get("FOREMAST_DEVICE_MESH_MODEL") or "").strip()
    if raw_model:
        try:
            n_model = max(1, int(raw_model))
        except ValueError:
            logging.getLogger("foremast_tpu.mesh").warning(
                "FOREMAST_DEVICE_MESH_MODEL=%r unparseable; using 1",
                raw_model,
            )
    if raw in ("0", "off", "none", "disabled"):
        return None
    if raw in ("auto", ""):
        return (None, n_model)
    try:
        if "x" in raw:
            d, _, m = raw.partition("x")
            di, mi = int(d), int(m)
            # zero on either axis means OFF, matching the bare "0"
            # spelling — a templated "{data}x{model}" with data=0 must
            # disable, not clamp up to a 1-wide axis
            if di <= 0 or mi <= 0:
                return None
            return (di, mi)
        return (max(1, int(raw)), n_model)
    except ValueError:
        logging.getLogger("foremast_tpu.mesh").warning(
            "FOREMAST_DEVICE_MESH=%r unparseable; using 'auto'", raw
        )
        return (None, n_model)


def worker_device_mesh(env: dict | None = None) -> Mesh | None:
    """The mesh a BrainWorker's judge should span, from the env.

    None means disabled (plain single-device judge). A resolved
    1-device mesh is returned as None too: `device_put` with a 1-device
    NamedSharding is semantically the identity, so the worker skips the
    ShardedJudge wrapper entirely rather than paying hook overhead for
    placement that changes nothing.

    Multi-controller processes always get None: a pod's judge must span
    the GLOBAL mesh (cli --sharded builds it explicitly before the
    worker exists) — an env-resolved LOCAL mesh on each process would
    hand one SPMD program differently-placed operands per host."""
    spec = device_mesh_spec(env)
    if spec is None:
        return None
    if jax.process_count() > 1:
        return None
    n_devs = len(jax.devices())
    n_data, n_model = spec
    if n_data is None:
        n_data = max(1, n_devs // n_model)
    if n_data * n_model > n_devs:
        # infeasible grid (a fleet-templated knob on a smaller host):
        # warn and fall back to the all-local auto mesh — the same
        # never-kill-startup contract as the spec parser above
        import logging

        logging.getLogger("foremast_tpu.mesh").warning(
            "FOREMAST_DEVICE_MESH %dx%d needs %d devices, have %d; "
            "falling back to the all-local auto mesh",
            n_data, n_model, n_data * n_model, n_devs,
        )
        n_data, n_model = n_devs, 1
    if n_data * n_model <= 1:
        return None
    return make_mesh(n_data=n_data, n_model=n_model)


def assert_partitioned(arr, n_data: int) -> None:
    """In-run proof the leading batch axis is actually partitioned: every
    addressable shard must hold exactly rows/n_data rows (ISSUE 13
    acceptance — 'sharding is placement' is only true if the placement
    happened; a silently-replicated batch would still be correct and
    ~n_data times slower, which is exactly the failure mode an assert
    exists for). O(#local devices) host work per call, no data read."""
    rows = arr.shape[0]
    if rows % n_data != 0:
        raise AssertionError(
            f"batch rows {rows} not a multiple of the data axis {n_data}"
        )
    shards = arr.addressable_shards
    want = rows // n_data
    # Iterates addressable_shards and reads shard SHAPES only ("no
    # data read" is this assert's contract).
    # foremast: ignore[device-flow]
    got = sorted(s.data.shape[0] for s in shards)
    n_local = len(shards)
    if any(g != want for g in got):
        raise AssertionError(
            f"batch leading axis not partitioned over the mesh: "
            f"{n_local} local shards of rows {got[:4]}..., want "
            f"{want} (= {rows}/{n_data}) each"
        )


# ---------------------------------------------------------------------------
# Multi-host (the reference's NCCL/MPI-equivalent layer, SURVEY.md §2.8:
# its distribution is shared-nothing pods over HTTP/ES; ours is XLA
# collectives over ICI within a slice and DCN across slices)
# ---------------------------------------------------------------------------


def init_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> bool:
    """Initialize jax.distributed for multi-host meshes.

    No-op (returns False) when single-process: explicit args win, then the
    standard cluster envs (JAX_COORDINATOR_ADDRESS / NUM_PROCESSES /
    PROCESS_ID, or a TPU pod's metadata which jax auto-detects). Safe to
    call twice. After this, `jax.devices()` is global and `make_mesh`
    spans all hosts.
    """
    import os

    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    env_np = os.environ.get("JAX_NUM_PROCESSES")
    num_processes = num_processes if num_processes is not None else (
        int(env_np) if env_np else None
    )
    env_pid = os.environ.get("JAX_PROCESS_ID")
    process_id = process_id if process_id is not None else (
        int(env_pid) if env_pid else None
    )
    if coordinator_address is None and num_processes is None:
        return False  # single-host: nothing to coordinate
    if jax.distributed.is_initialized():
        # idempotent: a prior initialize (ours, the runtime's TPU-pod
        # auto-init, or an embedding application's) wins. Re-calling
        # jax.distributed.initialize here would raise the generic
        # "must be called before any JAX calls" error, not a clean
        # already-initialized signal.
        return True
    # a connect or barrier failure surfaces to the caller — swallowing it
    # would leave this process on a local-only "global" mesh while its
    # peers hang at the init barrier
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def make_global_mesh(n_model: int = 1) -> Mesh:
    """A (data, model) mesh over ALL hosts' devices.

    Axis order puts `data` outermost so the batch axis crosses DCN (pure
    DP needs no inter-chip traffic there — each host scores its slice and
    only verdict gathers cross hosts) while `model` stays inside a host's
    ICI domain where tensor-parallel collectives are cheap. This is the
    scaling-book recipe: collectives ride ICI, DCN only sees the
    embarrassingly-parallel axis.
    """
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    if n_model > 1:
        local = [d for d in devs if d.process_index == devs[0].process_index]
        # groups of n_model consecutive devices form the model axis
        # (row-major reshape), so each host's device count must divide
        # cleanly or a group would straddle hosts and its collectives
        # would ride DCN
        if n_model > len(local) or len(local) % n_model != 0:
            raise ValueError(
                f"model axis {n_model} must evenly divide the {len(local)} "
                "devices of a single host — tensor parallelism must stay "
                "inside ICI"
            )
    return make_mesh(n_model=n_model, devices=devs)
