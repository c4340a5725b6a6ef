"""Multi-host (multi-process) distributed runtime support.

The reference's DP config rides NCCL/DDP across GPU workers (SURVEY.md §3b,
reconstructed); the TPU-native equivalent is jax's multi-controller SPMD:
every host runs THE SAME program, `jax.distributed.initialize` wires the
processes into one runtime, the mesh spans all hosts' devices, and XLA's
partitioner inserts the cross-host collectives (over ICI within a slice,
DCN across slices) exactly as it does single-host — no NCCL calls, no rank
bookkeeping in framework code.

What changes for the actor-learner loop (and what this module provides):
- every host runs its own actor fleet + batcher and contributes
  `local_batch_size(global_B)` unrolls per step;
- host-local `[T, B_local, ...]` batches become one globally-sharded
  `[T, B_global, ...]` array via `jax.make_array_from_process_local_data`
  (`place_batch`) — the multi-host replacement for a NCCL scatter;
- the jit train step is unchanged: the same donated pjit program runs on
  every host over the global mesh (runtime/learner.py calls `place_batch`
  whenever a mesh is present, so single-host behavior is identical:
  `place_batch` degenerates to a sharded `device_put`).

Verified without a pod: tests/test_multihost.py runs TWO OS processes, each
with 4 virtual CPU devices, `jax.distributed`-initialized into one 8-device
global mesh, and checks both compute the identical sharded learner step —
the same mechanism scales to v5e-16 hosts (SURVEY.md §5 item 5 philosophy).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

# Environment triple set by the simulated-cluster launcher
# (parallel/simhost.py) and honored by run.py --simulate-hosts children;
# the same names work for hand-rolled multi-host launches over ssh.
ENV_COORDINATOR = "IMPALA_COORDINATOR"
ENV_NUM_HOSTS = "IMPALA_NUM_HOSTS"
ENV_HOST_ID = "IMPALA_HOST_ID"


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Wire this process into the multi-host runtime.

    Call BEFORE any jax backend touch. No-op when single-process (no
    arguments and no JAX_COORDINATOR_ADDRESS in the environment). On cloud
    TPU pods, bare `jax.distributed.initialize()` autodetects everything;
    elsewhere pass the triple explicitly (run.py --coordinator/--num-hosts/
    --host-id flags).
    """
    if coordinator_address is None:
        coordinator_address = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if (
        coordinator_address is None
        and num_processes is None
        and process_id is None
    ):
        return  # single-process run
    _enable_cpu_collectives()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def _enable_cpu_collectives() -> None:
    """Give the CPU backend a real cross-process collectives impl.

    XLA:CPU refuses multiprocess computations unless the client is built
    with a collectives backend ("Multiprocess computations aren't
    implemented on the CPU backend"); jax plumbs gloo-over-TCP through
    `jax_cpu_collectives_implementation`. Flip it ONLY when the run is
    explicitly pinned to CPU (the simulated-cluster harness and the CI
    box both export JAX_PLATFORMS=cpu) and before first backend touch —
    on a real pod JAX_PLATFORMS is unset and this is a no-op.
    """
    plats = jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS", "")
    if "cpu" not in (plats or "").split(","):
        return
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except (AttributeError, ValueError):  # jaxlib without gloo: leave be
        pass


def bootstrap() -> "HostTopology":
    """Cluster bootstrap from the environment (idempotent single-process).

    Reads the IMPALA_COORDINATOR / IMPALA_NUM_HOSTS / IMPALA_HOST_ID
    triple (set by parallel/simhost.py for simulated CPU clusters, or by
    whatever launches the job on a real pod) and joins the runtime; with
    none of them set this is a plain single-process run. Returns the
    resulting `topology()` so callers can size their feed planes. Must be
    called before the first jax backend touch, like `initialize`.
    """
    coord = os.environ.get(ENV_COORDINATOR)
    n = os.environ.get(ENV_NUM_HOSTS)
    pid = os.environ.get(ENV_HOST_ID)
    if coord is not None or n is not None or pid is not None:
        if coord is None or n is None or pid is None:
            raise RuntimeError(
                "partial multihost environment: need all of "
                f"{ENV_COORDINATOR}, {ENV_NUM_HOSTS}, {ENV_HOST_ID} "
                f"(got coordinator={coord!r} num_hosts={n!r} "
                f"host_id={pid!r})"
            )
        initialize(
            coordinator_address=coord,
            num_processes=int(n),
            process_id=int(pid),
        )
    else:
        initialize()
    return topology()


@dataclasses.dataclass(frozen=True)
class HostTopology:
    """This process's place in the (possibly simulated) pod slice."""

    process_index: int
    process_count: int
    local_device_count: int
    global_device_count: int

    @property
    def is_distributed(self) -> bool:
        return self.process_count > 1


def topology() -> HostTopology:
    """Snapshot of the current runtime topology (touches the backend)."""
    return HostTopology(
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        local_device_count=len(jax.local_devices()),
        global_device_count=len(jax.devices()),
    )


def global_mesh(
    num_data: Optional[int] = None, num_model: int = 1
) -> "jax.sharding.Mesh":
    """Pod-slice mesh over EVERY process's devices.

    Routed through the canonical builder (parallel/mesh.make_mesh, whose
    axis names are pinned to spec_layout.MESH_AXES) so every
    PartitionSpec from the SpecLayout tables binds to it unchanged.
    `jax.devices()` under jax.distributed enumerates globally in
    process-major order, so the row-major (data, model) reshape keeps
    each host's devices on contiguous data rows — the property
    `place_batch` relies on for contiguous host-local batch slices, and
    the property that keeps model-axis collectives intra-host (ICI)
    while only the data-axis gradient all-reduce crosses hosts.
    Validated here rather than assumed: a topology that interleaves
    hosts along the data axis raises instead of silently producing
    strided (scatter-per-row) feeds.
    """
    from torched_impala_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(num_data, num_model, devices=jax.devices())
    if jax.process_count() > 1:
        data_rows = mesh.devices  # [num_data, num_model] ndarray
        rows_of: Dict[int, list] = {}
        for row_idx in range(data_rows.shape[0]):
            for dev in data_rows[row_idx].ravel():
                rows_of.setdefault(dev.process_index, []).append(row_idx)
        for proc, rows in rows_of.items():
            rows = sorted(set(rows))
            if rows != list(range(rows[0], rows[-1] + 1)):
                raise ValueError(
                    f"host {proc}'s devices land on non-contiguous data "
                    f"rows {rows} of the ({data_rows.shape[0]}x"
                    f"{data_rows.shape[1]}) mesh; choose num_data/"
                    "num_model so each host owns a contiguous block"
                )
    return mesh


def process_count() -> int:
    """Processes in the runtime (1 when jax.distributed is uninitialized)."""
    return jax.process_count()


def process_count() -> int:
    """Processes in the runtime (1 when jax.distributed is uninitialized)."""
    return jax.process_count()


def local_batch_size(global_batch_size: int) -> int:
    """This host's share of the global batch (actors+batcher contribute
    this many unrolls per learner step)."""
    n = process_count()
    if global_batch_size % n:
        raise ValueError(
            f"global batch_size {global_batch_size} not divisible by "
            f"process count {n}"
        )
    return global_batch_size // n


def global_leaf_shape(sharding, local_shape: Tuple[int, ...]) -> Tuple[int, ...]:
    """Global array shape implied by this host's local leaf shape.

    For every dimension the local extent is scaled by
    (total shards along the dims's mesh axes) / (shards this host
    addresses) — so data-sharded dims grow by the host count while
    replicated dims (and everything single-process) pass through
    unchanged. Only NamedShardings carry the mesh structure needed for
    this; other sharding kinds return the local shape (callers fall back
    to `jax.make_array_from_process_local_data`'s own inference).
    """
    spec = getattr(sharding, "spec", None)
    mesh = getattr(sharding, "mesh", None)
    if spec is None or mesh is None:
        return tuple(local_shape)
    axes_of_dim = list(spec) + [None] * (len(local_shape) - len(spec))
    # Count distinct coordinate tuples along each mesh axis among the
    # devices this process addresses.
    local_coords: Dict[str, set] = {name: set() for name in mesh.axis_names}
    grid = mesh.devices
    here = jax.process_index()
    for pos in np.ndindex(grid.shape):
        if grid[pos].process_index == here:
            for axis_i, name in enumerate(mesh.axis_names):
                local_coords[name].add(pos[axis_i])
    out = []
    for dim, names in zip(local_shape, axes_of_dim):
        if names is None:
            out.append(dim)
            continue
        if isinstance(names, str):
            names = (names,)
        total = 1
        local = 1
        for name in names:
            total *= mesh.shape[name]
            local *= max(1, len(local_coords[name]))
        if total % local:
            return tuple(local_shape)
        out.append(dim * (total // local))
    return tuple(out)


def local_shard_slices(
    sharding, global_shape: Tuple[int, ...]
) -> Optional[Dict[Any, Tuple[slice, ...]]]:
    """Host-local shard enumeration: device -> LOCAL-frame index tuple.

    Takes the sharding's global index map restricted to this process's
    addressable devices and rebases every dimension by the host's
    minimum start offset, yielding slices into the host-local
    `[.., B_local, ..]` buffer. Returns None when the addressable
    shards are not expressible as contiguous local slices (strided
    host placement — `global_mesh` rejects those topologies up front,
    but ad-hoc meshes can still produce them).
    """
    idx_map = sharding.addressable_devices_indices_map(tuple(global_shape))
    starts = [None] * len(global_shape)
    for idx in idx_map.values():
        for d, sl in enumerate(idx):
            if not isinstance(sl, slice):
                return None
            start = 0 if sl.start is None else sl.start
            if starts[d] is None or start < starts[d]:
                starts[d] = start
    out: Dict[Any, Tuple[slice, ...]] = {}
    for dev, idx in idx_map.items():
        local = []
        for d, sl in enumerate(idx):
            start = 0 if sl.start is None else sl.start
            stop = global_shape[d] if sl.stop is None else sl.stop
            local.append(slice(start - starts[d], stop - starts[d]))
        out[dev] = tuple(local)
    return out


def place_batch(shardings: Any, arrays: Any) -> Any:
    """Host-local batch tree -> globally sharded device arrays.

    Single-process this shards each leaf with ONE `device_put` PER
    DATA-PARALLEL SHARD, sliced straight from the host buffer (a
    `traj_ring` slot view on the zero-copy path — no gather on a
    staging device, no reshard hop), then assembles the global
    `jax.Array` from the per-device pieces. Multi-process, the same
    per-shard walk runs over only this host's ADDRESSABLE shards
    (`local_shard_slices` rebases the global index map into the local
    `[T, B_local, ...]` frame) and
    `jax.make_array_from_single_device_arrays` stitches the global
    `[T, B_global, ...]` jax.Array from every host's pieces — no data
    leaves the host. Leaves whose local layout can't be enumerated fall
    back to `jax.make_array_from_process_local_data`.
    """
    multi = process_count() > 1

    def _place(sh, x):
        if not multi:
            return _put_sharded(sh, x)
        return _put_process_local(sh, x)

    def _apply(sh, subtree):
        # `shardings` may be a prefix tree (one sharding covering a
        # whole agent-state subtree), matching device_put's contract.
        return jax.tree.map(lambda x: _place(sh, x), subtree)

    return jax.tree.map(
        _apply,
        shardings,
        arrays,
        is_leaf=lambda n: isinstance(n, jax.sharding.Sharding),
    )


def _put_process_local(sharding, x):
    """One host-local leaf -> global jax.Array (multi-process path)."""
    shape = getattr(x, "shape", None)
    if shape is not None and hasattr(
        sharding, "addressable_devices_indices_map"
    ):
        global_shape = global_leaf_shape(sharding, tuple(shape))
        slices = local_shard_slices(sharding, global_shape)
        if slices is not None:
            # Shape mismatches from a bad rebase surface as ValueError in
            # the assembler below and drop to the stock path.
            try:
                pieces = [
                    jax.device_put(x[idx], dev) for dev, idx in slices.items()
                ]
                return jax.make_array_from_single_device_arrays(
                    tuple(global_shape), sharding, pieces
                )
            except (ValueError, IndexError):
                pass  # fall through to the stock assembler
    return jax.make_array_from_process_local_data(sharding, x)


def _put_sharded(sharding, x):
    """One leaf -> global jax.Array via one device_put per shard.

    Each shard is a numpy view (`x[idx]` with the slice tuple from the
    sharding's index map) of the caller's buffer — for ring slots that
    IS the slot memory, so nothing is staged host-side. Replicated
    single-device shardings keep the plain put (identical dispatch, no
    assembly overhead).
    """
    shape = getattr(x, "shape", None)
    if shape is None or not hasattr(sharding, "addressable_devices"):
        return jax.device_put(x, sharding)
    idx_map = sharding.addressable_devices_indices_map(tuple(shape))
    if len(idx_map) <= 1:
        return jax.device_put(x, sharding)
    pieces = [jax.device_put(x[idx], dev) for dev, idx in idx_map.items()]
    return jax.make_array_from_single_device_arrays(
        tuple(shape), sharding, pieces
    )
