"""Learner: batches trajectories, runs the jit-compiled V-trace train step.

The training half of the architecture (SURVEY.md §2 row 2, §4.1/§4.3 call
stacks), TPU-first:

- actors push `Trajectory`s into a bounded host queue (backpressure, the
  analog's `learner.py:78-79`);
- a batcher thread stacks B unrolls into one time-major `[T+1, B, ...]`
  batch and `jax.device_put`s it into a depth-2 device queue so the H2D DMA
  of batch k+1 overlaps the train step on batch k (the double-buffered
  replacement for TPU infeed — `jax.lax.infeed` no longer exists in jax 0.9,
  SURVEY.md §6 comms);
- `train_step` is ONE donated, jit-compiled XLA program: unroll re-forward →
  V-trace → loss → grads → global-norm clip → optimizer update;
- params are republished to actors with a frame-count version stamp
  (the analog's `(num_frames, params)`, `learner.py:83,203`).
"""

from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import functools
import itertools
import queue
import re
import sys
import threading
import time
import weakref
from typing import Any, Callable, Mapping, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from torched_impala_tpu.models.agent import Agent
from torched_impala_tpu.ops import popart as popart_ops
from torched_impala_tpu.ops import precision
from torched_impala_tpu.ops import vtrace as vtrace_ops
from torched_impala_tpu.ops.losses import (
    SUM_REDUCED_LOG_KEYS,
    ImpalaLossConfig,
    impala_loss,
    impact_loss,
)
from torched_impala_tpu.ops.popart import PopArtConfig
from torched_impala_tpu.parallel.mesh import (
    DATA_AXIS,
    batch_sharding,
    model_shardings,
    replicated,
    state_sharding,
)
from torched_impala_tpu.parallel import multihost
from torched_impala_tpu.replay import ReplayConfig, TargetParamStore
from torched_impala_tpu.runtime.param_store import ParamStore
from torched_impala_tpu.runtime.traj_ring import TrajectoryRing
from torched_impala_tpu.telemetry.profiling import watch_compiles
from torched_impala_tpu.telemetry.registry import Registry, get_registry
from torched_impala_tpu.telemetry.tracing import (
    FlightRecorder,
    get_recorder,
)
from torched_impala_tpu.runtime.types import (
    QueueClosed,
    Trajectory,
    crossed_interval,
    host_snapshot,
    owned_array,
    tree_nbytes,
)

# Minimum excess wall time (ns) a calibrated host sync must show before
# it is debited against the all-reduce overlap budget. Back-to-back
# `block_until_ready` pairs on a contended host routinely differ by tens
# of microseconds from scheduler jitter alone; real collective exposure
# at pod scale is milliseconds, so readings under this floor are noise.
_SYNC_NOISE_FLOOR_NS = 25_000


@dataclasses.dataclass(frozen=True)
class LearnerConfig:
    batch_size: int = 8
    unroll_length: int = 20
    loss: ImpalaLossConfig = ImpalaLossConfig()
    max_grad_norm: float = 40.0  # IMPALA paper's global-norm clip
    # Publish host params to actors every N steps (1 = every step).
    publish_interval: int = 1
    # Call the logger every N learner steps (materializing device scalars to
    # floats forces a device sync, so keep this > 1 for throughput runs).
    log_interval: int = 1
    # Host trajectory queue capacity (in unrolls); bounds actor lead.
    queue_capacity: Optional[int] = None
    # Device-side batch queue depth; 2 = double buffering.
    device_queue_depth: int = 2
    # PopArt value normalization (multi-task DMLab-30 config); None = off.
    # When set, the agent's net must have num_values == popart.num_values.
    popart: Optional[PopArtConfig] = None
    # Fuse K SGD steps into ONE dispatched XLA program (`lax.scan` over a
    # [K, ...] superbatch). Each host→device dispatch carries fixed latency
    # (argument handling + launch); fusing K steps amortizes it K-fold. How
    # much that is on a PCIe-attached v5e: not measured (PERF.md).
    # Costs: params publish / telemetry land every K steps instead of every
    # step (actor staleness grows by up to K-1 extra updates — V-trace is
    # built for exactly this), and K batches are resident on device at once.
    steps_per_dispatch: int = 1
    # Accumulate gradients over G microbatches of batch_size/G inside the
    # same XLA program before ONE optimizer update: the activation
    # footprint shrinks ~G-fold (only one microbatch's activations are
    # live at a time, plus a grads-sized accumulator) while the update is
    # numerically the full-batch update — exact for both loss reductions
    # (masks are all-ones on this path), pinned by tests. The HBM lever
    # for batch sizes whose activations don't fit even with remat;
    # composes with steps_per_dispatch (accumulation nests inside each
    # fused step). Composes with PopArt via the batch-end statistics
    # update (moments accumulated over microbatches, ONE EMA application
    # — exact full-batch stats at the cost of an extra gradient-free
    # forward per microbatch). batch_size must divide by G (and the
    # per-microbatch batch by the mesh's data axis).
    grad_accum: int = 1
    # Stack batches into a ring of REUSED preallocated host buffers
    # instead of fresh allocations. Measured on this image (Atari unrolls,
    # pure-numpy isolation, 2026-07-31): fresh np.stack drops from
    # 11.2 GB/s at 5 MB outputs to ~1.7 GB/s at 38-152 MB outputs (page
    # faults + first-touch zeroing on every large allocation); stacking
    # into a preallocated double buffer sustains 6-8 GB/s — a 3.6-4.9x
    # feed-path win at exactly the B=256 headline shapes, and the
    # difference between feeding the 62.5k frames/s/chip north star
    # (needs ~1.85 GB/s at 29.7 KB/frame) or not. "auto" enables reuse
    # unless a one-time probe detects that device_put ALIASES host numpy
    # memory on this backend (a zero-copy backend would see later rounds'
    # data; jax's CPU aliasing contract is version-dependent, so probe,
    # don't assume). "on"/"off" force. The ring is a double buffer; each
    # slot blocks out its previous transfer before reuse, so no in-flight
    # H2D copy can be overwritten.
    stack_buffer_reuse: str = "auto"
    # Let XLA choose the train step's INPUT layouts (jax.experimental.
    # layout AUTO) and device_put batches directly into them, instead of
    # accepting default row-major inputs and relayouting inside the
    # step. The r5 headline trace showed a 0.50 ms/step pure-layout copy
    # of the uint8 obs batch (copy.3, 9% of the device step) that this
    # moves into the double-buffered H2D transfer — off the serial
    # critical path (both benchmark cells run with it on; its own gain
    # has not been measured on the chip, ROADMAP S8).
    # Single-device (mesh=None) path only; ignored under a mesh (pjit
    # sharding x layout interplay). The step itself is AOT-compiled on the
    # first batch; numerics are identical (layouts don't change math).
    auto_layouts: bool = True
    # Zero-copy trajectory ring (runtime/traj_ring.py): vectorized
    # actors write unrolls straight into preallocated [T+1, B, ...]
    # learner batch slots and the batcher device_puts a completed slot
    # with NO host stacking — the shm-lanes -> Trajectory -> np.stack
    # copy chain collapses to one actor-side write. Opt-in (default
    # off); the actor fleet must be vectorized with env counts dividing
    # batch_size (loop.py checks). Under a mesh the slot is placed
    # shard-by-shard straight from slot memory (one device_put per
    # data-parallel shard via the SpecLayout batch-placement table —
    # no gather/reshard hop; parallel/multihost.place_batch).
    # Recycling is free-list + generation counters; a slot returns only
    # after its H2D copy completes. On backends where device_put can
    # ALIAS host numpy (the stack_buffer_reuse probe), each batch is
    # staged through one owning copy instead — still one copy fewer
    # than the queue path's actor-buffer + stack chain.
    # With steps_per_dispatch=K > 1 the ring allocates SUPERBATCH slots
    # ([K, T+1, B, ...] — traj_ring.superbatch_k): actors fill K*B
    # columns, a completed slot IS the fused dispatch's xs, and the
    # chunked-K fallback becomes the exception rather than the rule.
    traj_ring: bool = False
    # Donate the batch arrays into the train step (zero-copy feed path):
    # XLA may reuse the batch buffers as scratch, eliminating the
    # defensive staging copy between ring slot and train_step. In ring
    # mode slots are released only after the consuming step completes
    # (instead of after the H2D transfer), so donation is safe even on
    # backends where device_put aliases host memory. Off (default)
    # keeps the exact pre-existing path. Incompatible with replay (a
    # retained slot's contents must survive for re-delivery).
    donate_batch: bool = False
    # Full-bf16 train step (ISSUE 16; ops/precision.py "train_step"
    # role): 'bfloat16' casts the f32 master params to bf16 INSIDE the
    # loss closure, so the forward/backward runs in half precision
    # while gradients transpose back to f32 (convert_element_type) and
    # the optimizer, PopArt stats and V-trace recursion never see bf16
    # — the accumulator contract `precision.assert_f32_accumulators`
    # enforces on init and set_state. 'float32' (default) is the exact
    # pre-existing step. run.py gates bf16 behind a greedy-action
    # parity probe and falls back to f32 when the probe fails.
    train_dtype: str = "float32"
    # IMPACT-style replay (replay/ subsystem, docs/REPLAY.md): retain
    # ring slots for up to max_reuse deliveries and train replayed
    # batches with the clipped target-network surrogate
    # (ops.losses.impact_loss) against a TargetParamStore pinned every
    # target_update_interval steps. None — or a disabled ReplayConfig
    # (max_reuse=1, target_update_interval=0) — keeps the EXACT
    # pre-replay code path (bit-parity, tests/test_replay.py). Enabled
    # replay requires traj_ring (the ring IS the replay buffer) and
    # grad_accum=1 (no microbatch scan in the surrogate step); it
    # composes with the mesh learner (the pinned target params ride the
    # same shardings as the live ones) and with PopArt (the surrogate
    # re-expresses normalized values under ops.popart.popart_impact_loss
    # — f32 replicated stats, same as the on-policy path).
    replay: Optional[ReplayConfig] = None


class BatchLineage(NamedTuple):
    """Provenance of one assembled batch, riding the device queue next
    to the arrays: `batch` is the batcher's sequence number, `lineage`
    the consumed unrolls' flight-recorder IDs (column order), `versions`
    their param versions — the inputs of the EXACT per-batch staleness
    the train-step trace span reports (the `learner/param_lag_frames`
    gauge is the min-version summary of the same numbers). Replay mode
    adds `reuse_count` (which delivery of the slot's contents this batch
    is; 1 = fresh) and `staleness` (frame delta to the learner watermark
    at delivery) so the train-step trace span distinguishes replayed
    from fresh consumption. `ring_slot` >= 0 marks a DONATED ring batch:
    the slot's buffers back the device arrays, so step_once releases the
    slot only after the consuming step completes (-1 = not donated)."""

    batch: int
    lineage: tuple = ()
    versions: tuple = ()
    reuse_count: int = 1
    staleness: int = 0
    ring_slot: int = -1


class _InFlight(NamedTuple):
    """What a dispatched step still owes the host. The step loop settles
    it one call later, behind the NEXT step's dispatch, so the device
    never stands idle while the host waits, copies and publishes."""

    step: int  # num_steps after the step
    version: int  # num_frames after the step: what its parameters publish as
    dispatch_t0_ns: int  # where its `learner/step_in_flight` span opens
    probe: list  # one device leaf that is ready when the step (and copy) is
    snapshot: Any = None  # on-device copy of its parameters, in pieces
    requested: int = 0  # how many of its pieces have their D2H requested
    ring_slot: int = -1  # donated ring slot to recycle once it completed
    logs: Optional[dict] = None  # its log scalars, when it crossed log_interval
    meta: Optional[BatchLineage] = None


# Bytes of a version whose copies to the host may stand requested and
# unread. With a step in flight the runtime's host side of such requests
# grows outside the process's resident set, and by what is outstanding:
# 1.76 GB of parameters (the hybrid core at published widths) all
# requested at once, as the snapshot is queued or after the wait for
# it, grew the machine's count 0.4-0.65 GiB a step until a 30 s window
# met its 40 GiB, 256 MiB ahead as much, 16-64 MiB ahead 0.03-0.04 GiB a
# step (my chip runs, PR 34). The 6 MB of an LSTM preset fit whole, so
# every leaf is requested as the snapshot is queued. A larger tree
# travels in pieces of a quarter of the bound (`_piece_rows`): while one
# is read the next three stand requested, so the link and the host's
# copy overlap under the same bound (PR 35).
SNAPSHOT_COPY_AHEAD_BYTES = 64 << 20


def _piece_rows(leaf) -> int:
    """How many rows of axis 0 a piece of `leaf` holds in a snapshot; 0
    where the leaf is one piece: it fits a quarter of
    `SNAPSHOT_COPY_AHEAD_BYTES`, or it is neither whole on one device
    nor fully replicated. Rows are a multiple of 8, so that no tile of a
    piece is padded, and the pieces of a leaf are as even as that allows;
    where 8 rows alone exceed a piece, a piece is 8 rows."""
    piece = SNAPSHOT_COPY_AHEAD_BYTES // 4
    if leaf.nbytes <= piece or not leaf.sharding.is_fully_replicated:
        return 0
    fit = max(8, piece * leaf.shape[0] // leaf.nbytes // 8 * 8)
    count = -(-leaf.shape[0] // fit)
    rows = -(-leaf.shape[0] // (8 * count)) * 8
    return rows if rows < leaf.shape[0] else 0


def _request_copies(pieces, begin: int, unread: int):
    """Request the D2H of `pieces[begin:]`, in order, for as long as the
    bytes requested and not yet read stay within
    `SNAPSHOT_COPY_AHEAD_BYTES`: `(where it stopped, those bytes)`."""
    while (
        begin < len(pieces)
        and unread + pieces[begin].nbytes <= SNAPSHOT_COPY_AHEAD_BYTES
    ):
        pieces[begin].copy_to_host_async()
        unread += pieces[begin].nbytes
        begin += 1
    return begin, unread


# Threads that read the pieces of cut leaves (`_snapshot_to_host`): the
# wait for a piece's bytes and the copy into its leaf both release the
# GIL. One thread lands 1.76 GB in 0.23 s, two in 0.17 s, beside a
# device step of 0.2 s (my chip runs, PR 35).
_LANDING_THREADS = 2


class _Lease:
    """The owner every view of one version's slab leads back to (numpy
    follows a view's `base` down to the first object that is no array):
    it dies with the last of them, and `_Slabs` then has the memory back."""

    def __init__(self, raw: np.ndarray):
        self._raw = raw

    def __buffer__(self, flags):
        return memoryview(self._raw)


class _Slabs:
    """Host memory for the cut leaves of published versions: one slab a
    version, its cut leaves views of it, and a slab used again only when
    nothing can read the version that had it any more (the `ParamStore`
    keeps four, actors and the serving registry hold theirs as long as
    they like). So a landing writes memory the process has touched: on
    the chip's host a copy into pages it has not runs at 0.9-2.9 GB/s
    and varies with the machine's state, into touched ones at 18 GB/s
    (my chip runs, PR 35); and no reader sees another version's bytes."""

    def __init__(self):
        self._free: list = []
        self._lock = threading.Lock()  # a finalizer runs on any thread

    def take(self, nbytes: int) -> np.ndarray:
        """`nbytes` of uint8 that are the caller's until the last view
        of them is gone."""
        with self._lock:
            raw = self._free.pop() if self._free else None
        if raw is None or raw.nbytes != nbytes:
            raw = np.empty(nbytes, np.uint8)
        lease = _Lease(raw)
        weakref.finalize(lease, self._give, raw).atexit = False
        return np.frombuffer(lease, np.uint8)

    def _give(self, raw: np.ndarray) -> None:
        with self._lock:
            self._free.append(raw)


def _snapshot_to_host(snapshot, requested: int, slabs, readers=None):
    """`host_snapshot` of a version that is on the device as one tuple of
    pieces a leaf (`_publish_snapshot`), the first `requested` pieces
    with their D2H requested: `(host leaves, the most bytes that stood
    requested and unread)`. Piece by piece in the tree's order, the next
    pieces' copies requested as the bound allows, so the link moves them
    while the ones before are written into their leaf; a piece over the
    bound is one synchronous copy. The pieces of a cut leaf are written
    into its part of the version's slab (`slabs`), its blocks of rows,
    on the threads of `readers` (an executor) where there is one. A leaf
    that is one piece is read here as `owned_array` gives it, unless a
    reader is busy: this thread would then wait behind the pieces in
    flight and request nothing meanwhile, so the leaf goes to the readers
    too. A tree with no cut leaf is so read here alone, call for call as
    before there were pieces. Each leaf is one C-contiguous array whose
    bytes nobody else writes while anything can read them: its own, or
    its version's slab. The cut leaves of `snapshot` are emptied: such a
    piece is let go of as it is read, so its buffers on the device and on
    the host are freed then, and the runtime's host side of a version is
    what stands requested, not the version (whole leaves die with the
    caller's list as they always did: freeing 48 small buffers one by
    one cost an LSTM preset's landing 3 ms on the chip)."""
    flat = [piece for leaf in snapshot for piece in leaf]
    leaf_of = [i for i, leaf in enumerate(snapshot) for _ in leaf]
    # Where each piece lands: a whole leaf in an array of its own (None
    # here), a cut leaf's in their rows of its part of the slab, each
    # part on a cache line of its own.
    sizes = [
        sum(piece.nbytes for piece in leaf) if len(leaf) > 1 else 0
        for leaf in snapshot
    ]
    starts = list(
        itertools.accumulate((-(-size // 64) * 64 for size in sizes), initial=0)
    )
    slab = slabs.take(starts[-1]) if starts[-1] else None
    host, blocks = [], []
    for leaf, size, start in zip(snapshot, sizes, starts):
        if not size:
            host.append(None)
            blocks.append(None)
            continue
        part = slab[start : start + size].view(leaf[0].dtype)
        host.append(part.reshape((-1,) + leaf[0].shape[1:]))
        row = 0
        for piece in leaf:
            blocks.append(host[-1][row : row + piece.shape[0]])
            row += piece.shape[0]
    snapshot[:] = [leaf if len(leaf) == 1 else () for leaf in snapshot]
    unread = peak = sum(piece.nbytes for piece in flat[:requested])
    pending = collections.deque()  # (a read, its bytes, the whole leaf it is)

    def landed():
        nonlocal unread
        read, nbytes, whole = pending.popleft()
        array = read.result()
        unread -= nbytes
        if whole is not None:
            host[whole] = array

    for at, block in enumerate(blocks):
        piece = flat[at]
        nbytes = piece.nbytes
        while at >= requested:
            requested, unread = _request_copies(flat, requested, unread)
            if at < requested:
                break
            if not pending:  # alone over the bound: not requested
                requested, nbytes = at + 1, 0
                break
            landed()
        peak = max(peak, unread)
        if block is not None:
            flat[at] = None
        if readers is None or (block is None and not pending):
            array = owned_array(piece, block)
            unread -= nbytes
            if block is None:
                host[leaf_of[at]] = array
        else:
            whole = leaf_of[at] if block is None else None
            pending.append(
                (readers.submit(owned_array, piece, block), nbytes, whole)
            )
            while pending and pending[0][0].done():
                landed()
        requested, unread = _request_copies(flat, requested, unread)
        peak = max(peak, unread)
    while pending:
        landed()
    return host, peak


@functools.partial(jax.jit, static_argnames="rows")
def _publish_snapshot(params, rows):
    """Step k's parameters in fresh buffers, as one program queued right
    behind step k: a list over the tree's leaves, each a tuple of pieces.
    Step k+1 donates the originals, so the copy to the host reads these
    instead. A leaf with `rows[i]` rows a piece (`_piece_rows`) comes as
    its blocks of rows, each an output written straight from the leaf (no
    whole copy beside them), any other as one `jnp.copy`, not an
    identity: a jitted identity forwards its inputs and copies nothing."""
    return [
        tuple(
            leaf[at : at + step] for at in range(0, leaf.shape[0], step)
        )
        if step
        else (jnp.copy(leaf),)
        for leaf, step in zip(jax.tree.leaves(params), rows)
    ]


# Sanitizer for flax module names -> health gauge sub-keys
# (`health/grad_norm_<group>` must satisfy the registry NAME_RE:
# "Conv_0" -> "conv_0").
_HEALTH_GROUP_RE = re.compile(r"[^a-z0-9_]+")


def _health_param_groups(tree) -> dict:
    """Top-level module groups of a flax param/grad/update tree for the
    per-layer-group health gauges: descend through the conventional
    single 'params' wrapper, then one group per child module. Trees
    without that shape (custom containers, empty dicts) fall back to a
    single 'all' group so the gauges still exist."""
    inner = tree
    if (
        isinstance(inner, collections.abc.Mapping)
        and set(inner.keys()) == {"params"}
    ):
        inner = inner["params"]
    if not isinstance(inner, collections.abc.Mapping) or not inner:
        return {"all": tree}
    out: dict = {}
    for key in inner:
        name = (
            _HEALTH_GROUP_RE.sub("_", str(key).lower()).strip("_")
            or "group"
        )
        base, i = name, 1
        while name in out:  # post-sanitization collisions
            i += 1
            name = f"{base}_{i}"
        out[name] = inner[key]
    return out


def _put_format(x, fmt):
    """device_put into an XLA-chosen Format; leaves whose format carries
    no concrete layout (scalars/empty subtrees) take the default put."""
    if fmt.layout is None:
        return jax.device_put(x)
    return jax.device_put(x, fmt)


def resolve_kernels(agent: Agent, loss: ImpalaLossConfig, mesh):
    """Pick the kernel implementations for the devices the step runs on.

    Returns `(agent, loss, resolved)`: `loss.vtrace_implementation` is
    never 'auto' afterwards, and `resolved` names what was chosen
    (`vtrace`, `lstm`, `fused_conv`, `max_pool`, `attention`,
    `selective_scan`, `devices`, `why`; a `Learner` adds `packed_convs`,
    which needs the observations' shape).

    On one device the choice is per platform: the Pallas kernels on a
    TPU, the scan elsewhere (the model's own kernels pick compiled vs
    interpreted at lowering time, ops/pallas_util.py). On a mesh of more
    than one TPU device every kernel resolves to its XLA implementation
    — the scan V-trace, the flax LSTM cell, unfused residual blocks,
    XLA's max-pool, einsum attention, the hybrid core's scan one step at
    a time — because Mosaic kernels cannot be
    auto-partitioned and nothing here wraps them per shard yet. That is decided HERE, by
    construction, and logged once; it is never recovered from a failed
    lowering. On a TPU, one device or a mesh, a deep torso's residual
    blocks take the W-packed weight gradient (ops/conv_packed.py: XLA
    convolutions, which partition like any other). The param tree is
    identical across implementations (tests/test_pallas_lstm.py), so
    actors may keep the fused net."""
    devices = (
        list(mesh.devices.flat) if mesh is not None else jax.devices()[:1]
    )
    platform = devices[0].platform
    xla_only = mesh is not None and len(devices) > 1 and platform == "tpu"
    net = agent.net
    impl = loss.vtrace_implementation
    why = f"{len(devices)} {platform} device(s)"
    if xla_only:
        why += ": Mosaic kernels are not auto-partitioned under a mesh"
        if impl == "pallas":
            raise ValueError(
                "vtrace_implementation='pallas' on a mesh of "
                f"{len(devices)} TPU devices: Mosaic kernels cannot be "
                "auto-partitioned; use 'auto' or 'scan'"
            )
        impl = "scan"
        torso = net.torso
        if getattr(torso, "fused_blocks", False):
            torso = torso.clone(fused_blocks=False)
        if getattr(torso, "pool_kernel", False):
            torso = torso.clone(pool_kernel=False)
        net = net.clone(
            torso=torso,
            lstm_impl="flax",
            transformer=tuple(
                (k, "einsum" if k == "dense_kernel" else v)
                for k, v in net.transformer
            ),
            hybrid=tuple(
                {
                    **dict(net.hybrid),
                    "attention_kernel": "einsum",
                    "scan_kernel": False,
                }.items()
            )
            if net.hybrid
            else (),
        )
    elif impl == "auto":
        impl = vtrace_ops.resolve_implementation("auto", devices)
    if platform == "tpu" and hasattr(net.torso, "packed_gradients"):
        net = net.clone(torso=net.torso.clone(packed_gradients=True))
    kind = net._core_kind()
    resolved = {
        "vtrace": impl,
        "lstm": net.lstm_impl if kind == "lstm" else None,
        "fused_conv": bool(getattr(net.torso, "fused_blocks", False)),
        "max_pool": {True: "kernel", False: "xla"}.get(
            getattr(net.torso, "pool_kernel", None)
        ),
        "attention": (
            dict(net.transformer).get("dense_kernel")
            if kind == "transformer"
            else dict(net.hybrid).get("attention_kernel")
            if kind == "hybrid"
            else None
        ),
        # The hybrid core's scan: its Pallas kernels where the step is
        # lowered for one TPU device, the step-by-step scan elsewhere
        # (ops/selective_scan.py chooses at lowering time).
        "selective_scan": (
            None
            if kind != "hybrid"
            else "xla_scan"
            if not dict(net.hybrid).get("scan_kernel", True)
            or platform != "tpu"
            else "pallas"
        ),
        "devices": [str(d) for d in devices],
        "why": why,
    }
    if xla_only:
        import logging

        logging.getLogger(__name__).warning(
            "learner kernels -> XLA (%s): vtrace=%s lstm=%s "
            "fused_conv=%s max_pool=%s attention=%s",
            why,
            *(resolved[k] for k in (
                "vtrace", "lstm", "fused_conv", "max_pool", "attention"
            )),
        )
    return (
        dataclasses.replace(agent, net=net),
        dataclasses.replace(loss, vtrace_implementation=impl),
        resolved,
    )


def stack_trajectories(
    trajs: list[Trajectory], out: Optional[Trajectory] = None
) -> Trajectory:
    """Stack B unrolls into one time-major batch: leaves `[T(+1), B, ...]`;
    agent_state leaves concatenate on their existing batch axis.

    `out` (a Trajectory of preallocated, correctly-shaped array views)
    stacks in place — the fused-dispatch batcher passes slices of its
    `[K, ...]` superbatch so each unroll is copied exactly once."""
    if out is not None:
        np.stack([t.obs for t in trajs], axis=1, out=out.obs)
        np.stack([t.first for t in trajs], axis=1, out=out.first)
        np.stack([t.actions for t in trajs], axis=1, out=out.actions)
        np.stack(
            [t.behaviour_logits for t in trajs],
            axis=1,
            out=out.behaviour_logits,
        )
        np.stack([t.rewards for t in trajs], axis=1, out=out.rewards)
        np.stack([t.cont for t in trajs], axis=1, out=out.cont)
        if trajs[0].agent_state != ():
            jax.tree.map(
                lambda o, *xs: np.concatenate(xs, axis=0, out=o),
                out.agent_state,
                *[t.agent_state for t in trajs],
            )
        out.task[...] = [t.task for t in trajs]
        return out._replace(
            param_version=min(t.param_version for t in trajs),
            lineage_id=tuple(t.lineage_id for t in trajs),
        )
    batched = Trajectory(
        obs=np.stack([t.obs for t in trajs], axis=1),
        first=np.stack([t.first for t in trajs], axis=1),
        actions=np.stack([t.actions for t in trajs], axis=1),
        behaviour_logits=np.stack(
            [t.behaviour_logits for t in trajs], axis=1
        ),
        rewards=np.stack([t.rewards for t in trajs], axis=1),
        cont=np.stack([t.cont for t in trajs], axis=1),
        agent_state=jax.tree.map(
            lambda *xs: np.concatenate(xs, axis=0),
            *[t.agent_state for t in trajs],
        )
        if trajs[0].agent_state != ()
        else (),
        actor_id=-1,
        param_version=min(t.param_version for t in trajs),
        task=np.asarray([t.task for t in trajs], np.int32),
        lineage_id=tuple(t.lineage_id for t in trajs),
    )
    return batched


def alloc_stack_buffers(
    trajs: list[Trajectory], K: Optional[int] = None
) -> Trajectory:
    """Preallocate one stacking destination shaped for `stack_trajectories`
    output (K=None) or a `[K, ...]` superbatch slice target (K given) —
    the ring-reuse buffers LearnerConfig.stack_buffer_reuse stacks into."""
    t0, B = trajs[0], len(trajs)
    lead = () if K is None else (K,)

    def stacked(x):
        return np.empty(lead + (x.shape[0], B) + x.shape[1:], x.dtype)

    def state(x):
        return np.empty(lead + (B * x.shape[0],) + x.shape[1:], x.dtype)

    return Trajectory(
        obs=stacked(t0.obs),
        first=stacked(t0.first),
        actions=stacked(t0.actions),
        behaviour_logits=stacked(t0.behaviour_logits),
        rewards=stacked(t0.rewards),
        cont=stacked(t0.cont),
        agent_state=jax.tree.map(state, t0.agent_state),
        actor_id=-1,
        param_version=0,
        task=np.empty(lead + (B,), np.int32),
    )


def stack_superbatch(batches: list[Trajectory]) -> Trajectory:
    """Stack K already-batched trajectories along a new leading axis:
    array leaves `[K, T(+1), B, ...]`, task `[K, B]`, agent_state leaves
    `[K, B, ...]` — the xs of the fused `lax.scan` over K SGD steps.

    Reference implementation (copies each batch a second time); the
    batcher's hot path assembles unrolls directly into the superbatch via
    `stack_trajectories(..., out=slice)` instead. Kept public as the
    oracle the in-place path is tested against."""
    return Trajectory(
        obs=np.stack([b.obs for b in batches]),
        first=np.stack([b.first for b in batches]),
        actions=np.stack([b.actions for b in batches]),
        behaviour_logits=np.stack([b.behaviour_logits for b in batches]),
        rewards=np.stack([b.rewards for b in batches]),
        cont=np.stack([b.cont for b in batches]),
        agent_state=jax.tree.map(
            lambda *xs: np.stack(xs), *[b.agent_state for b in batches]
        )
        if batches[0].agent_state != ()
        else (),
        actor_id=-1,
        param_version=min(b.param_version for b in batches),
        task=np.stack([b.task for b in batches]),
    )


class Learner:
    """Single-device learner. The sharded variant lives in `parallel/`."""

    def __init__(
        self,
        *,
        agent: Agent,
        optimizer: optax.GradientTransformation,
        config: LearnerConfig,
        example_obs: np.ndarray,
        rng: jax.Array,
        logger: Optional[Callable[[Mapping[str, Any]], None]] = None,
        mesh: Optional[Mesh] = None,
        telemetry: Optional[Registry] = None,
        tracer: Optional[FlightRecorder] = None,
    ) -> None:
        """`mesh=None` → single-device jit; `mesh=Mesh(..., ('data','model'))`
        → batch sharded over `data` (gradient all-reduce inserted by the
        XLA partitioner over ICI, SURVEY.md §3b DP row) and, when the
        `model` axis is wider than 1, params/optimizer tensor-parallel
        over it (`parallel.model_shardings`: output-feature dimensions of
        weight matrices split Megatron-column-style, activations
        repartitioned by XLA as needed). The data-axis size must divide
        batch_size."""
        self._optimizer = optimizer
        self._logger = logger
        self._mesh = mesh
        # Full-bf16 step (ISSUE 16): the loss closures cast the f32
        # master params to this dtype; None = the exact f32 path.
        precision.validate_compute_dtype("train_step", config.train_dtype)
        self._train_cast = (
            jnp.dtype(config.train_dtype)
            if config.train_dtype != "float32"
            else None
        )
        # Resolve 'auto' V-trace and the model's Pallas kernels HERE,
        # where the compute devices are known (a CPU mesh in a
        # TPU-default process gets the scan; a multi-chip mesh gets the
        # XLA implementations) — see resolve_kernels.
        agent, loss, self.kernels = resolve_kernels(
            agent, config.loss, mesh
        )
        config = dataclasses.replace(config, loss=loss)
        self._agent = agent
        self._config = config
        if mesh is not None and config.batch_size % mesh.shape[DATA_AXIS]:
            raise ValueError(
                f"batch_size {config.batch_size} not divisible by data axis "
                f"{mesh.shape[DATA_AXIS]}"
            )
        # Multi-host: batch_size is the GLOBAL batch; this host's batcher
        # assembles its 1/process_count share and place_batch stitches the
        # global sharded array (parallel/multihost.py). Single-host this is
        # batch_size and a plain sharded device_put.
        self._local_batch_size = (
            multihost.local_batch_size(config.batch_size)
            if mesh is not None
            else config.batch_size
        )
        if config.popart is not None:
            net_nv = agent.net.num_values
            if net_nv != config.popart.num_values:
                # Out-of-range columns would be silently clamped/dropped by
                # the jit-compiled gathers — fail loudly at construction.
                raise ValueError(
                    f"PopArt num_values {config.popart.num_values} != net "
                    f"value-head width {net_nv}; set ImpalaNet(num_values=K)"
                )

        # Kept (and checkpointed) so resumed runs re-derive any future
        # learner-side sampling from the same stream; today init is its only
        # consumer. Actor streams are derived from actor seeds at (re)start
        # — see utils/checkpoint.py for the determinism story.
        self._rng = rng
        self._params = agent.init_params(rng, jnp.asarray(example_obs))
        # Which of the torso's convolutions take the packed weight
        # gradient in this learner's step, `(C, H, W, p)` each: none off
        # a TPU (ops/conv_packed.py).
        packed_convs = getattr(agent.net.torso, "packed_convs", None)
        self.kernels["packed_convs"] = (
            packed_convs(np.shape(example_obs)) if packed_convs else []
        )
        if self.kernels["packed_convs"]:
            import logging

            logging.getLogger(__name__).info(
                "learner packed weight gradients (C, H, W, p): %s",
                self.kernels["packed_convs"],
            )
        self._opt_state = optimizer.init(self._params)
        self._popart_state = (
            popart_ops.init(config.popart.num_values)
            if config.popart is not None
            else ()
        )
        # Accumulators are f32-only regardless of train_dtype (the
        # ops/precision.py policy); a half-precision optimizer moment
        # or PopArt stat here means a mis-built optimizer/init — refuse
        # now, before it corrupts training slowly and invisibly.
        precision.assert_f32_accumulators(
            {
                "optimizer_state": self._opt_state,
                "popart_stats": self._popart_state,
            },
            context="Learner.__init__",
        )
        if mesh is not None:
            rep = replicated(mesh)
            # DP-only meshes (model axis 1) come out fully replicated;
            # wider model axes shard weight matrices tensor-parallel.
            self._param_shardings = model_shardings(mesh, self._params)
            self._opt_shardings = model_shardings(mesh, self._opt_state)
            self._params = jax.device_put(
                self._params, self._param_shardings
            )
            self._opt_state = jax.device_put(
                self._opt_state, self._opt_shardings
            )
            self._popart_state = jax.device_put(self._popart_state, rep)
        else:
            self._param_shardings = None
            self._opt_shardings = None
        self.num_frames = 0
        self.num_steps = 0

        # Default bounds actor lead at two dispatches' worth of unrolls: a
        # fused dispatch consumes K*B at once, so the K=1 default of 2*B
        # would make actors trickle unrolls through a too-small queue
        # during superbatch assembly instead of accumulating the next
        # dispatch's K*B while the current one computes.
        capacity = (
            config.queue_capacity
            or config.batch_size * 2 * config.steps_per_dispatch
        )
        self._traj_q: queue.Queue = queue.Queue(maxsize=capacity)
        self._batch_q: queue.Queue = queue.Queue(
            maxsize=config.device_queue_depth
        )
        # Host stacking-buffer ring (LearnerConfig.stack_buffer_reuse).
        # TWO slots suffice: a host buffer's job ends when its H2D copy
        # completes (the device array owns the data from then on —
        # non-aliasing backends only, which the "auto" probe guarantees),
        # so the batcher stacks into slot B while slot A's transfer
        # drains, and _ring_pending blocks out A's transfer before
        # restacking it. A deeper ring would only pin more batches of
        # device memory (the pending refs) for no extra overlap — a
        # measured 6x throughput collapse at B=256,K=4 on a RAM-bound
        # host. Buffers allocate lazily (shapes come from the first
        # batch); `_stack_reuse` resolves lazily too (the aliasing probe
        # does a device_put).
        if config.stack_buffer_reuse not in ("auto", "on", "off"):
            raise ValueError(
                f"stack_buffer_reuse must be auto/on/off, got "
                f"{config.stack_buffer_reuse!r}"
            )
        ring_size = 2
        self._ring: list = [None] * ring_size
        self._ring_pending: list = [None] * ring_size
        self._ring_checked: list = [False] * ring_size
        self._ring_idx = 0
        self._last_slot: Optional[int] = None
        self._stack_reuse: Optional[bool] = None
        self._stop = threading.Event()
        self._batcher_thread: Optional[threading.Thread] = None
        # A batcher-thread failure is recorded here and re-raised from the
        # learner loop so a dead pipeline fails loudly instead of hanging.
        # Single-writer atomic reference rebind (batcher writes, learner
        # thread reads) — no lock by design.
        self.error: Optional[BaseException] = None  # lint: guarded-by(gil)
        # Called on the learner thread after every SGD step with num_steps —
        # the supported place for exact-cadence side effects (interval
        # checkpointing), independent of the log_interval throttle.
        self.post_step: Optional[Callable[[int], None]] = None
        # Training-health monitor (telemetry/health.py, ISSUE 19):
        # attached via attach_health; observes the log-interval float
        # materialization in _finish_step and writes crash postmortems
        # from run(). None = the exact pre-health code path.
        self._health = None
        # Throughput telemetry (SURVEY.md §6 tracing: infeed starvation vs
        # compute is THE diagnostic; frames/sec/chip is the north-star
        # metric BASELINE.json:2).
        self._wait_accum = 0.0
        self._last_log_t: Optional[float] = None
        self._last_log_frames = 0
        self._last_log_steps = 0

        # Registry telemetry (docs/OBSERVABILITY.md "learner"/"queue"
        # rows): the four stage spans decompose one learner step into
        # host stacking, H2D dispatch, the XLA step, and param publish —
        # together with queue depth / batch wait they localize the
        # pipeline bottleneck. Resolved once; spans cost two monotonic()
        # reads + one lock on a many-ms stage. `telemetry` overrides the
        # global registry (benchmarks isolate runs with fresh ones).
        reg = telemetry if telemetry is not None else get_registry()
        self._telemetry = reg
        # jit/trace, jit/backend_compile: a compile inside a measured
        # window shows in that window's timers with its seconds.
        watch_compiles(reg)
        # Flight recorder (telemetry/tracing.py): the batcher stamps a
        # monotone batch id on every assembled batch and the stage spans
        # (host_stack / device_put / train_step / publish) carry it plus
        # the consumed unrolls' lineage IDs — the per-batch half of the
        # observability story; the registry below is the aggregate half.
        self._tracer = tracer if tracer is not None else get_recorder()
        self._batch_seq = 0
        self._last_lineage = BatchLineage(batch=-1)
        self._m_host_stack = reg.timer("learner/host_stack")
        # Bytes the stacking path COPIES per batch (the number the
        # trajectory ring drives to 0) and, ring mode only, bytes staged
        # through the aliasing-fallback owning copy before device_put.
        self._m_host_stack_bytes = reg.counter("learner/host_stack_bytes")
        self._m_ring_stage_bytes = reg.counter("learner/ring_stage_bytes")
        # The hybrid core's carry (models/hybrid.py): what one row of it
        # weighs, and how many episode starts the batch just stacked
        # holds (each resets the scan and the convolution inside the
        # unroll and bounds what attention may see). Counted on the host
        # from `first`, in the batcher; absent with any other core.
        self._m_core_resets = None
        if agent.net._core_kind() == "hybrid":
            reg.gauge("core/state_bytes_per_row").set(
                agent.net._hybrid_core(bound=False).state_bytes_per_row()
            )
            self._m_core_resets = reg.gauge("core/resets_in_batch")
        self._m_device_put = reg.timer("learner/device_put")
        self._m_train_step = reg.timer("learner/train_step")
        self._m_publish = reg.timer("learner/publish")
        self._m_batch_wait = reg.timer("learner/batch_wait")
        # The step loop's own time. One period runs from one entry of
        # step_once to the next and is cut, on this one thread and with
        # shared stamps, into phases that do not overlap and add up to it.
        # The call for step k dispatches step k first and settles step
        # k-1 after (one step in flight), so a period holds phases of both:
        #   batch_wait    the blocking get on the device-batch queue
        #   train_step    the call that ENQUEUES the compiled step k
        #   bookkeeping   the rest of _finish_step: counters, lineage,
        #                 step k-1's slot release and logger, post_step
        #   publish_copy  two pieces: queueing step k's snapshot program
        #                 and its D2H; after the wait, step k-1's copies
        #                 piece by piece into its leaves, ParamStore.publish
        #   step_wait     blocked until the device has finished step k-1
        #                 and its snapshot (steps that owe the host
        #                 something: a publish, log scalars, a ring slot)
        #   outside_step  from the return to the next entry: the caller
        # `loop_overhead` is the period less batch_wait and step_wait:
        # what the host itself spent while it was waiting for nobody.
        # Each phase is also a flight-recorder span carrying the number
        # of the step it belongs to, and `learner/step_in_flight` (span
        # only) brackets the device's work on a step from the host's
        # side: its dispatch's start to the moment its wait returns, one
        # call later, so two such spans overlap. `dispatch_lead` is
        # observed when step k-1 was still on the device as the dispatch
        # of step k returned: the time from there to step k-1 being
        # ready, that is how far ahead of the device the loop launched.
        self._m_bookkeeping = reg.timer("learner/bookkeeping")
        self._m_step_wait = reg.timer("learner/step_wait")
        self._m_publish_copy = reg.timer("learner/publish_copy")
        # How the last version reached the host (`_snapshot_to_host`):
        # its pieces (its leaf count where nothing was cut), the most
        # bytes that stood requested and unread (within
        # `SNAPSHOT_COPY_AHEAD_BYTES`), and its bytes over the time its
        # landing took, in GB/s.
        self._m_publish_pieces = reg.gauge("learner/publish_pieces")
        self._m_publish_outstanding = reg.gauge(
            "learner/publish_outstanding_peak_bytes"
        )
        self._m_publish_rate = reg.gauge("learner/publish_gb_per_s")
        self._m_outside_step = reg.timer("learner/outside_step")
        self._m_loop_overhead = reg.timer("learner/loop_overhead")
        self._m_dispatch_lead = reg.timer("learner/dispatch_lead")
        # Step-loop thread only: the open period's entry stamp, the
        # seconds it has spent waiting (batch_wait + step_wait), the last
        # return's stamp, and whether a step completed since the entry (a
        # call that timed out on the queue leaves the period open).
        self._period_t0_ns: Optional[int] = None
        self._period_wait_ns = 0
        self._period_stepped = False
        self._returned_ns: Optional[int] = None
        # The dispatched step that is not settled yet (None: nothing in
        # flight), and what settling has cost since the phase timers were
        # last observed: [step_wait, publish_copy, bookkeeping] ns. The
        # lock keeps settlements whole and in order when stop() or
        # set_state() drain from another thread than the step loop's.
        self._in_flight: Optional[_InFlight] = None
        self._settle_lock = threading.RLock()
        # Where the cut leaves of a version land, and the threads that
        # read their pieces: started and joined with the learner (none:
        # they are read on the caller's).
        self._slabs = _Slabs()
        self._readers: Optional[concurrent.futures.Executor] = None
        self._settled_ns = [0, 0, 0]
        self._m_steps_per_sec = reg.gauge("learner/steps_per_sec")
        self._m_param_lag = reg.gauge("learner/param_lag_frames")
        self._m_enqueue_block = reg.histogram("queue/enqueue_block_ms")
        # Fused dispatches that ran through the chunked K<=4 fallback
        # after a jit-boundary layout refusal (perf observatory; the
        # companion perf/mfu gauges register lazily in _observe_perf).
        self._m_fused_fallbacks = reg.counter("perf/fused_fallbacks")
        # Zero-copy feed path (donate_batch): how much of the H2D
        # dispatch wall-time landed inside a train step's compute window
        # (the overlapped-H2D design point). ns counters so a reader can
        # take window deltas; the gauge is the cumulative fraction. Read
        # by tests/test_feed_path.py and doctor; by nothing on a chip
        # (ROADMAP D6).
        # `learner/donated_batches` counts batches fed without a staging
        # copy (the donation gauge OBSERVABILITY.md documents).
        self._m_h2d_total_ns = reg.counter("perf/h2d_ns_total")
        self._m_h2d_overlap_ns = reg.counter("perf/h2d_ns_overlapped")
        self._m_h2d_overlap_frac = reg.gauge("perf/h2d_overlap_frac")
        # Gradient all-reduce overlap (meshes whose data axis spans >1
        # device — multi-host pods ride the same axis): XLA fuses the
        # collective into the step program, so it can't be timed
        # directly from the host. Instead each step accrues the ring
        # all-reduce's COST MODEL estimate (2(n-1)/n * grad bytes /
        # backend bandwidth) and debits every measured host stall on
        # step completion (donated-slot probe blocks, log-leaf
        # materialization) against it. The gauge is the cumulative
        # fraction of estimated collective time NOT covered by measured
        # stalls — i.e. hidden behind backward compute + pipeline slack.
        # Conservative by construction: ALL completion stalls debit the
        # collective, so a reduction-bound learner reads low before it
        # reads high. docs/OBSERVABILITY.md documents the semantics.
        self._m_allreduce_total_ns = reg.counter("perf/allreduce_ns_total")
        self._m_allreduce_overlap_ns = reg.counter(
            "perf/allreduce_ns_overlapped"
        )
        self._m_allreduce_overlap_frac = reg.gauge(
            "perf/allreduce_overlap_frac"
        )
        self._allreduce_est_ns: Optional[int] = None  # lazily costed
        self._allreduce_stall_ns = 0  # lint: guarded-by(gil)
        self._allreduce_total_ns = 0  # lint: guarded-by(gil)
        self._allreduce_overlap_ns = 0  # lint: guarded-by(gil)
        self._m_donated_batches = reg.counter("learner/donated_batches")
        # Written only by the batcher thread (directly or via the
        # place_batch per-shard callback); main thread only reads at
        # snapshot time — int updates are atomic under the GIL.
        self._h2d_total_ns = 0  # lint: guarded-by(gil)
        self._h2d_overlap_ns = 0  # lint: guarded-by(gil)
        # Recent train-step compute intervals + the in-flight step's
        # start, read by the batcher thread to score each H2D dispatch
        # against compute. Benign cross-thread race: stale reads only
        # under-count overlap.
        self._step_intervals: collections.deque = collections.deque(
            maxlen=64
        )
        self._step_active_since_ns: Optional[int] = None  # lint: guarded-by(gil)
        # Per-shard H2D accounting for the sharded place_batch path:
        # place_batch invokes _on_shard_h2d once per per-device put, so
        # the put's overlap credit comes from the shard intervals
        # themselves, not the whole dispatch window (batcher thread
        # only — reset by _put_batch before each placement).
        self._put_shards = 0  # lint: guarded-by(gil)
        self._put_overlap_ns = 0  # lint: guarded-by(gil)
        reg.gauge("queue/capacity").set(capacity)
        # Live depth, read lazily at snapshot time. Weakref: the global
        # registry must not keep a dead learner's queue (and its queued
        # trajectory arrays) alive.
        q_ref = weakref.ref(self._traj_q)

        def _depth() -> float:
            q = q_ref()
            return float("nan") if q is None else q.qsize()

        reg.gauge("queue/depth", fn=_depth)

        # IMPACT replay (replay/ subsystem): validated BEFORE the ring is
        # built — an enabled config changes the ring's slot count and
        # retention mode. A disabled ReplayConfig normalizes to None so
        # every later `self._replay is None` check IS the bit-parity
        # switch (tests/test_replay.py).
        rp = config.replay
        if rp is not None:
            rp.validate()
        self._replay: Optional[ReplayConfig] = (
            rp if rp is not None and rp.enabled else None
        )
        if self._replay is not None:
            if not config.traj_ring:
                raise ValueError(
                    "replay requires traj_ring=True: the trajectory ring "
                    "IS the circular replay buffer (docs/REPLAY.md)"
                )
            if config.grad_accum != 1:
                raise ValueError(
                    "replay requires grad_accum=1 (the surrogate step "
                    "has no microbatch scan)"
                )

        if config.popart is not None and config.loss.fused_epilogue:
            raise ValueError(
                "fused_epilogue does not compose with PopArt yet (the "
                "per-task rescaling epilogue keeps the separate loss "
                "path; PopArt stats stay f32 either way)"
            )

        # Zero-copy trajectory ring (LearnerConfig.traj_ring): slots are
        # complete [T+1, B, ...] batches actors write in place. Sized so
        # the device queue can hold its depth in transferred slots while
        # one slot fills and one spare absorbs jitter; replay-with-reuse
        # adds two more so retained slots don't starve the free list.
        self.traj_ring: Optional[TrajectoryRing] = None
        if config.traj_ring:
            if config.steps_per_dispatch > 1 and self._replay is not None:
                raise ValueError(
                    "traj_ring superbatch (steps_per_dispatch > 1) does "
                    "not compose with replay: a retained slot cannot be "
                    "re-delivered column-by-column across K sub-batches"
                )
            replaying = (
                self._replay is not None and self._replay.max_reuse > 1
            )
            # donate_batch holds each slot one step PAST its transfer
            # (released after the consuming step), which would leave the
            # free list empty at steady state and serialize writers on
            # the release — two extra slots restore the slack so ready
            # slots are waiting whenever the device queue has room and
            # the H2D dispatch lands inside the next step's compute.
            self.traj_ring = TrajectoryRing(
                num_slots=config.device_queue_depth
                + 2
                + (2 if replaying else 0)
                + (2 if config.donate_batch else 0),
                unroll_length=config.unroll_length,
                batch_size=self._local_batch_size,
                example_obs=np.asarray(example_obs),
                num_actions=agent.net.num_actions,
                agent_state_example=agent.initial_state(1),
                superbatch_k=config.steps_per_dispatch,
                telemetry=reg,
                tracer=self._tracer,
                max_reuse=self._replay.max_reuse if replaying else 1,
                replay_mix=self._replay.replay_mix if replaying else 1.0,
                staleness_frames=(
                    self._replay.staleness_frames if replaying else 0
                ),
                sampler_seed=(
                    self._replay.sampler_seed if replaying else 0
                ),
            )

        self.param_store = ParamStore()
        self._publish_now()

        # Target network (replay/target_store.py): pinned on-device copy
        # of the params the surrogate clips against, refreshed every
        # target_update_interval steps from step_once. Initialized from
        # the just-published init params so step 1 has a target.
        self._target_store: Optional[TargetParamStore] = None
        if self._replay is not None:
            self._target_store = TargetParamStore(
                self.param_store,
                update_interval=self._replay.target_update_interval,
                max_lag_frames=self._replay.target_max_lag_frames,
                telemetry=reg,
            )
            self._target_store.update(self._params, version=0, step=0)

        if config.steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got "
                f"{config.steps_per_dispatch}"
            )
        G = config.grad_accum
        if G < 1:
            raise ValueError(f"grad_accum must be >= 1, got {G}")
        if G > 1:
            if config.batch_size % G:
                raise ValueError(
                    f"batch_size {config.batch_size} not divisible by "
                    f"grad_accum {G}"
                )
            if mesh is not None and (config.batch_size // G) % mesh.shape[
                DATA_AXIS
            ]:
                raise ValueError(
                    f"microbatch {config.batch_size // G} not divisible "
                    f"by data axis {mesh.shape[DATA_AXIS]}"
                )
        fused = config.steps_per_dispatch > 1
        step_impl = self._train_multi_impl if fused else self._train_step_impl
        if config.donate_batch:
            if self._replay is not None:
                raise ValueError(
                    "donate_batch does not compose with replay: a "
                    "retained slot's contents must survive the step for "
                    "re-delivery, donation lets XLA scribble on them"
                )
        # AUTO-layout machinery (config.auto_layouts): compiled lazily by
        # the batcher from the first assembled batch's avals, so cheap
        # Learner constructions (tests, doctor) pay nothing.
        self._auto_compiled = None
        self._batch_formats = None
        self._auto_lock = threading.Lock()
        self._auto_jit = None
        # Fused-dispatch layout fallback (ISSUE 10 satellite): once a
        # K>4 superbatch hits a jit-boundary layout refusal, dispatch in
        # chunks of this size instead of crashing (0 = fast path).
        self._fused_fallback_k = 0
        # Live perf/* gauges; built lazily on the first finished step so
        # cheap Learner constructions (tests, doctor) pay nothing.
        self._cost_model = None
        # Replay step: a SEPARATE jit program taking the target params
        # as a fourth (non-donated — reused across steps) state arg.
        # auto_layouts stays off under replay: the AOT machinery
        # compiles the standard step's formats, which the replay
        # program would then refuse.
        self._replay_step = None
        # donate_batch extends donation past the state triple to the
        # eight batch arguments (argnums 3..10): XLA may reuse the
        # batch buffers as scratch, so the feed path never stages a
        # defensive copy between ring slot and step (the zero-copy
        # contract; the ring slot recycles only after the consuming
        # step completes). Identical under the mesh — pjit donates the
        # per-shard buffers the batcher placed straight from slot
        # memory.
        donate = (
            tuple(range(11)) if config.donate_batch else (0, 1, 2)
        )
        if config.donate_batch:
            # Batch buffers rarely match an output shape, so XLA
            # reports them "not usable" for output reuse on some
            # backends — expected here (donation still frees XLA to
            # scratch over them); don't warn once per compile.
            import warnings

            warnings.filterwarnings(
                "ignore",
                message="Some donated buffers were not usable",
            )
        if mesh is None:
            self._train_step = jax.jit(step_impl, donate_argnums=donate)
            if self._replay is not None:
                self._replay_step = jax.jit(
                    self._train_step_replay_impl, donate_argnums=(0, 1, 2)
                )
            if config.auto_layouts and self._replay is None:
                from jax.experimental.layout import Format, Layout

                auto = Format(Layout.AUTO)
                self._auto_jit = jax.jit(
                    step_impl,
                    donate_argnums=donate,
                    in_shardings=auto,
                    out_shardings=auto,
                )
        else:
            from torched_impala_tpu.parallel import spec_layout

            rep = replicated(mesh)
            # The eight feed-path shardings come from the SpecLayout
            # batch-placement table (plain [T+1, B, ...] or fused
            # [K, T+1, B, ...] layouts; the K axis stays unsharded —
            # steps are sequential by construction). Prefix pytrees:
            # one sharding covers each whole subtree (tasks and
            # agent_state leaves are [B, ...]).
            self._batch_shardings = spec_layout.feed_shardings(
                mesh, superbatch=fused
            )
            self._train_step = jax.jit(
                step_impl,
                donate_argnums=donate,
                in_shardings=(
                    self._param_shardings,
                    self._opt_shardings,
                    rep,
                )
                + self._batch_shardings,
                out_shardings=(
                    self._param_shardings,
                    self._opt_shardings,
                    rep,
                    rep,
                ),
            )
            if self._replay is not None:
                # The pinned target params ride the live params'
                # shardings (TargetParamStore's jnp.copy preserves
                # them); replay pins K=1, so the batch shardings are
                # the plain layout.
                self._replay_step = jax.jit(
                    self._train_step_replay_impl,
                    donate_argnums=(0, 1, 2),
                    in_shardings=(
                        self._param_shardings,
                        self._opt_shardings,
                        rep,
                        self._param_shardings,
                    )
                    + self._batch_shardings,
                    out_shardings=(
                        self._param_shardings,
                        self._opt_shardings,
                        rep,
                        rep,
                    ),
                )

    # ---- the hot loop: one fused XLA program ---------------------------

    def _compute_grads(
        self,
        params,
        popart_state,
        obs,
        first,
        actions,
        behaviour_logits,
        rewards,
        cont,
        tasks,
        agent_state,
        fixed_new_popart=None,
    ):
        """(grads, logs, new_popart_state) for one (micro)batch.

        `fixed_new_popart`: precomputed post-update PopArt stats (the
        gradient-accumulation batch-end scheme); forwarded to the loss so
        every microbatch is expressed under the same full-batch stats."""
        cfg = self._config.loss
        pa_cfg = self._config.popart

        def loss_fn(p):
            if self._train_cast is not None:
                # Full-bf16 step: lower the f32 master params to the
                # train compute dtype inside the differentiated
                # closure — the convert_element_type transpose brings
                # gradients back as f32, so grads/optimizer/PopArt
                # stay on the f32 accumulator contract.
                p = precision.cast_to_compute(p, self._train_cast)
            discounts = cfg.discount * cont
            if pa_cfg is None:
                net_out, _ = self._agent.unroll(p, obs, first, agent_state)
                values = jnp.squeeze(net_out.values, -1)  # [T+1, B]
                out = impala_loss(
                    target_logits=net_out.policy_logits[:-1],
                    behaviour_logits=behaviour_logits,
                    values=values[:-1],
                    bootstrap_value=values[-1],
                    actions=actions,
                    rewards=rewards,
                    discounts=discounts,
                    config=cfg,
                )
                return out.total, (out.logs, popart_state)
            policy_logits, norm_values = self._popart_forward(
                p, obs, first, agent_state, tasks
            )
            out, new_pa = popart_ops.popart_impala_loss(
                target_logits=policy_logits[:-1],
                behaviour_logits=behaviour_logits,
                norm_values=norm_values[:-1],
                norm_bootstrap=norm_values[-1],
                actions=actions,
                rewards=rewards,
                discounts=discounts,
                tasks=tasks,
                state=popart_state,
                popart_config=pa_cfg,
                config=cfg,
                fixed_new_state=fixed_new_popart,
            )
            return out.total, (out.logs, new_pa)

        (_, (logs, new_popart)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        return grads, logs, new_popart

    def _popart_forward(self, params, obs, first, agent_state, tasks):
        """(policy_logits, norm_values) with each env's task column
        selected — the net emits normalized per-task values [T+1, B, K].
        Shared by the gradient loss and the grad-accum statistics pass so
        the two can't compute moments from different V-trace targets."""
        net_out, _ = self._agent.unroll(params, obs, first, agent_state)
        norm_values = jnp.take_along_axis(
            net_out.values, tasks[None, :, None], axis=-1
        )[..., 0]  # [T+1, B]
        return net_out.policy_logits, norm_values

    def _train_step_impl(
        self,
        params,
        opt_state,
        popart_state,
        obs,
        first,
        actions,
        behaviour_logits,
        rewards,
        cont,
        tasks,
        agent_state,
    ):
        G = self._config.grad_accum
        if G == 1:
            grads, logs, new_popart = self._compute_grads(
                params, popart_state, obs, first, actions,
                behaviour_logits, rewards, cont, tasks, agent_state,
            )
        else:
            # Split the batch axis into [G, Bm] and scan, accumulating
            # grads; only one microbatch's activations are ever live.
            Bm = self._config.batch_size // G

            def split_tb(x):  # [T(+1), B, ...] -> [G, T(+1), Bm, ...]
                return x.reshape(
                    (x.shape[0], G, Bm) + x.shape[2:]
                ).swapaxes(0, 1)

            def split_b(x):  # [B, ...] -> [G, Bm, ...]
                return x.reshape((G, Bm) + x.shape[1:])

            micro = (
                split_tb(obs),
                split_tb(first),
                split_tb(actions),
                split_tb(behaviour_logits),
                split_tb(rewards),
                split_tb(cont),
                split_b(tasks),
                jax.tree.map(split_b, agent_state),
            )

            pa_cfg = self._config.popart
            if pa_cfg is None:
                fixed_new = None
            else:
                # Batch-end statistics update: the full-batch PopArt loss
                # expresses every term under the POST-update stats, which
                # depend on the whole batch's V-trace targets — so an
                # extra forward-only scan accumulates the per-task target
                # moments first (they are additive across microbatches),
                # ONE EMA application reproduces exactly the full-batch
                # `update`, and the gradient scan below runs under those
                # fixed stats. Costs one extra (gradient-free) forward
                # per microbatch — the price of exact full-batch numerics;
                # activations still peak at one microbatch.
                def stats_body(carry, xs):
                    (obs_m, first_m, actions_m, logits_m, rewards_m,
                     cont_m, tasks_m, astate_m) = xs
                    policy_logits, norm_values = self._popart_forward(
                        params, obs_m, first_m, astate_m, tasks_m
                    )
                    moments = popart_ops.popart_target_moments(
                        target_logits=policy_logits[:-1],
                        behaviour_logits=logits_m,
                        norm_values=norm_values[:-1],
                        norm_bootstrap=norm_values[-1],
                        actions=actions_m,
                        rewards=rewards_m,
                        discounts=self._config.loss.discount * cont_m,
                        tasks=tasks_m,
                        state=popart_state,
                        popart_config=pa_cfg,
                        config=self._config.loss,
                    )
                    return jax.tree.map(jnp.add, carry, moments), None

                zero = jnp.zeros((pa_cfg.num_values,), jnp.float32)
                (cnt, tot, tot_sq), _ = jax.lax.scan(
                    stats_body, (zero, zero, zero), micro
                )
                fixed_new = jax.lax.stop_gradient(
                    popart_ops.apply_moments(
                        popart_state, pa_cfg, cnt, tot, tot_sq
                    )
                )

            def body(acc, xs):
                g, logs, _ = self._compute_grads(
                    params, popart_state, *xs,
                    fixed_new_popart=fixed_new,
                )
                return jax.tree.map(jnp.add, acc, g), logs

            acc0 = jax.tree.map(jnp.zeros_like, params)
            grads, logs_seq = jax.lax.scan(body, acc0, micro)
            if self._config.loss.reduction == "mean":
                # Microbatch grads are means over Bm; the full-batch mean
                # is their average (equal per-microbatch step counts).
                grads = jax.tree.map(lambda g: g / G, grads)
            logs = {
                k: jnp.sum(v, axis=0)
                if (
                    k in SUM_REDUCED_LOG_KEYS
                    and self._config.loss.reduction == "sum"
                )
                else jnp.mean(v, axis=0)
                for k, v in logs_seq.items()
            }
            new_popart = popart_state if fixed_new is None else fixed_new
        grad_norm = optax.global_norm(grads)
        if self._config.max_grad_norm is not None:
            scale = jnp.minimum(
                1.0, self._config.max_grad_norm / (grad_norm + 1e-8)
            )
            grads = jax.tree.map(lambda g: g * scale, grads)
        updates, opt_state = self._optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        pa_cfg = self._config.popart
        if pa_cfg is not None:
            # Preserve outputs precisely across the stats move (the "Art"
            # half of PopArt): rescale the value head for the new (mu, sigma).
            params = popart_ops.rescale_params(
                params, popart_state, new_popart, pa_cfg
            )
        logs = dict(logs)
        logs["grad_norm_unclipped"] = grad_norm
        logs["weight_norm"] = optax.global_norm(params)
        if self._config.loss.health_diagnostics:
            logs.update(
                self._health_step_logs(
                    grads=grads,
                    updates=updates,
                    params=params,
                    popart_before=popart_state,
                    popart_after=new_popart,
                )
            )
        return params, opt_state, new_popart, logs

    def _health_step_logs(
        self, *, grads, updates, params, popart_before, popart_after
    ) -> dict:
        """Learner-side in-jit health diagnostics (ISSUE 19): per-layer-
        group gradient norms and update-to-weight ratios from trees the
        step already holds, plus PopArt stats drift from the (pre, post)
        state pair. Only reached when
        `config.loss.health_diagnostics` — the disabled step stays
        bit-identical to the pre-diagnostics program."""
        logs: dict = {}
        param_groups = _health_param_groups(params)
        for name, g in _health_param_groups(grads).items():
            logs[f"health_grad_norm_{name}"] = optax.global_norm(g)
        for name, u in _health_param_groups(updates).items():
            w = param_groups.get(name)
            if w is None:
                continue
            logs[f"health_update_ratio_{name}"] = optax.global_norm(u) / (
                optax.global_norm(w) + 1e-8
            )
        pa_cfg = self._config.popart
        if pa_cfg is not None:
            # Per-step drift of the normalization statistics: a healthy
            # run settles toward 0 as mu/nu converge; sustained drift
            # means the return distribution is still moving (or PopArt's
            # step size is fighting a nonstationary task mix).
            logs["health_popart_mu_drift"] = jnp.mean(
                jnp.abs(popart_after.mu - popart_before.mu)
            )
            logs["health_popart_sigma_drift"] = jnp.mean(
                jnp.abs(
                    popart_ops.sigma(popart_after, pa_cfg)
                    - popart_ops.sigma(popart_before, pa_cfg)
                )
            )
        return logs

    def _train_step_replay_impl(
        self,
        params,
        opt_state,
        popart_state,
        target_params,
        obs,
        first,
        actions,
        behaviour_logits,
        rewards,
        cont,
        tasks,
        agent_state,
    ):
        """One IMPACT surrogate step (ops.losses.impact_loss): the target
        net re-forwards the unroll to anchor the V-trace corrections and
        the clipped learner/target ratio; the grad-clip + optimizer tail
        is identical to `_train_step_impl`. `target_params` is NOT
        donated — the same pinned copy serves every step until the
        TargetParamStore refreshes it. With PopArt on (ISSUE 15: the
        lifted PopArt+replay carve-out) the step runs
        `ops.popart.popart_impact_loss` and rescales the LIVE value head
        across the stats move — the pinned target copy is a snapshot of
        already-rescaled params, so it never needs in-step rescaling."""
        cfg = self._config.loss
        rp = self._config.replay
        pa_cfg = self._config.popart
        if self._train_cast is not None:
            # The gradient-free target anchor runs at the same train
            # compute dtype as the learner forward it clips against.
            target_params = precision.cast_to_compute(
                target_params, self._train_cast
            )
        target_out, _ = self._agent.unroll(
            target_params, obs, first, agent_state
        )
        target_logits = jax.lax.stop_gradient(
            target_out.policy_logits[:-1]
        )

        def loss_fn(p):
            if self._train_cast is not None:
                # Same master-params-in-f32 contract as _compute_grads.
                p = precision.cast_to_compute(p, self._train_cast)
            if pa_cfg is None:
                net_out, _ = self._agent.unroll(
                    p, obs, first, agent_state
                )
                values = jnp.squeeze(net_out.values, -1)  # [T+1, B]
                out = impact_loss(
                    learner_logits=net_out.policy_logits[:-1],
                    target_logits=target_logits,
                    behaviour_logits=behaviour_logits,
                    values=values[:-1],
                    bootstrap_value=values[-1],
                    actions=actions,
                    rewards=rewards,
                    discounts=cfg.discount * cont,
                    clip_epsilon=rp.target_clip_epsilon,
                    config=cfg,
                )
                return out.total, (out.logs, popart_state)
            policy_logits, norm_values = self._popart_forward(
                p, obs, first, agent_state, tasks
            )
            out, new_pa = popart_ops.popart_impact_loss(
                learner_logits=policy_logits[:-1],
                target_logits=target_logits,
                behaviour_logits=behaviour_logits,
                norm_values=norm_values[:-1],
                norm_bootstrap=norm_values[-1],
                actions=actions,
                rewards=rewards,
                discounts=cfg.discount * cont,
                tasks=tasks,
                state=popart_state,
                popart_config=pa_cfg,
                clip_epsilon=rp.target_clip_epsilon,
                config=cfg,
            )
            return out.total, (out.logs, new_pa)

        (_, (logs, new_popart)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        grad_norm = optax.global_norm(grads)
        if self._config.max_grad_norm is not None:
            scale = jnp.minimum(
                1.0, self._config.max_grad_norm / (grad_norm + 1e-8)
            )
            grads = jax.tree.map(lambda g: g * scale, grads)
        updates, opt_state = self._optimizer.update(
            grads, opt_state, params
        )
        params = optax.apply_updates(params, updates)
        if pa_cfg is not None:
            params = popart_ops.rescale_params(
                params, popart_state, new_popart, pa_cfg
            )
        logs = dict(logs)
        logs["grad_norm_unclipped"] = grad_norm
        logs["weight_norm"] = optax.global_norm(params)
        if self._config.loss.health_diagnostics:
            logs.update(
                self._health_step_logs(
                    grads=grads,
                    updates=updates,
                    params=params,
                    popart_before=popart_state,
                    popart_after=new_popart,
                )
            )
        return params, opt_state, new_popart, logs

    def _train_multi_impl(
        self, params, opt_state, popart_state, *stacked
    ):
        """K chained SGD steps in one XLA program (steps_per_dispatch > 1).

        `stacked` mirrors `_train_step_impl`'s batch arguments with a
        leading K axis; `lax.scan` slices one batch per step and threads
        (params, opt_state, popart_state) through. Returned logs are the
        LAST step's (the state actors will see), so log semantics match
        the unfused path."""

        def body(carry, xs):
            p, o, pa, logs = self._train_step_impl(*carry, *xs)
            return (p, o, pa), logs

        (params, opt_state, popart_state), logs_seq = jax.lax.scan(
            body, (params, opt_state, popart_state), stacked
        )
        logs = jax.tree.map(lambda x: x[-1], logs_seq)
        return params, opt_state, popart_state, logs

    # ---- data plumbing -------------------------------------------------

    def enqueue(self, traj: Trajectory) -> None:
        """Called by actors; blocks when the learner is behind (backpressure).
        Raises QueueClosed after `stop()` so blocked actors can exit."""
        t0 = time.monotonic()
        while True:
            if self._stop.is_set():
                raise QueueClosed()
            try:
                self._traj_q.put(traj, timeout=0.5)
                now = time.monotonic()
                # Time spent blocked on a full queue: ~0 means the learner
                # keeps up; growing p95 means actors outrun it (the
                # backpressure diagnostic, ISSUE 2 queue row).
                self._m_enqueue_block.observe((now - t0) * 1e3)
                # The queue hop of the lineage chain: the span duration
                # IS the backpressure this unroll paid to get in.
                self._tracer.complete(
                    "queue/enqueue",
                    int(t0 * 1e9),
                    int((now - t0) * 1e9),
                    {"lid": traj.lineage_id},
                )
                return
            except queue.Full:
                continue

    def _batcher_loop(self) -> None:
        try:
            self._batcher_loop_impl()
        except BaseException as e:  # noqa: BLE001 — surfaced via self.error
            self.error = e
            raise

    def _collect_trajs(self) -> Optional[list[Trajectory]]:
        """Block for B unrolls from the host queue; None on stop."""
        B = self._local_batch_size
        trajs: list[Trajectory] = []
        while len(trajs) < B:
            if self._stop.is_set():
                return None
            try:
                trajs.append(self._traj_q.get(timeout=0.5))
            except queue.Empty:
                continue
        return trajs

    def _ensure_auto_compiled(self, example_arrays) -> None:
        """AOT-compile the AUTO-layout train step from the first batch's
        avals (batcher thread); re-lay the live state into the compiled
        formats. Thread-safe; runs once."""
        with self._auto_lock:
            if self._auto_compiled is not None or self._auto_jit is None:
                return
            def aval(x):
                x = np.asanyarray(x) if not hasattr(x, "dtype") else x
                return jax.ShapeDtypeStruct(x.shape, x.dtype)

            state = (self._params, self._opt_state, self._popart_state)
            with self._tracer.span("learner/compile"):
                compiled = self._auto_jit.lower(
                    *jax.tree.map(aval, state),
                    *jax.tree.map(aval, example_arrays),
                ).compile()
            fmt_args, _ = compiled.input_formats
            state_fmts, batch_fmts = fmt_args[:3], fmt_args[3:]
            # One-time on-device relayout of the live state into the
            # compiled formats (donation then keeps in == out formats,
            # so chained steps never relayout again).
            self._params = jax.tree.map(
                _put_format, self._params, state_fmts[0]
            )
            self._opt_state = jax.tree.map(
                _put_format, self._opt_state, state_fmts[1]
            )
            self._popart_state = jax.tree.map(
                _put_format, self._popart_state, state_fmts[2]
            )
            self._state_formats = state_fmts
            self._batch_formats = batch_fmts
            self._auto_compiled = compiled

    def _stack_reuse_enabled(self) -> bool:
        """Resolve LearnerConfig.stack_buffer_reuse, probing once for the
        aliasing hazard in "auto" mode: if device_put ALIASES host numpy
        memory on this backend (zero-copy), reusing the buffer would let
        later rounds' data bleed into batches still referenced on device,
        so reuse must stay off."""
        if self._stack_reuse is None:
            mode = self._config.stack_buffer_reuse
            if mode in ("on", "off"):
                self._stack_reuse = mode == "on"
            else:
                # Capability probe: CAN device_put zero-copy (alias) host
                # buffers on this backend? Measured on the jax CPU
                # backend: 64-byte-aligned large buffers get aliased,
                # others copied — an alignment lottery per allocation, so
                # a single trial is meaningless and ANY aliasing
                # capability disqualifies reuse (an aliased ring buffer
                # would corrupt queued batches on restack). TPU backends
                # always copy H2D, so the probe enables reuse exactly
                # where the feed-path win matters. np.shares_memory is
                # timing-independent (a mutate-and-read probe raced
                # jax's async materialization and flaked).
                # The hazard is the H2D copy from THIS host's buffers, so
                # the probe must target a process-LOCAL device: under a
                # multihost mesh, devices.flat[0] can belong to another
                # process, and reading such an array back raises (killed
                # the batcher thread in the 2-process test).
                if self._mesh is None:
                    target = None
                else:
                    local = set(jax.local_devices())
                    target = next(
                        (
                            dev
                            for dev in self._mesh.devices.flat
                            if dev in local
                        ),
                        None,
                    )
                    if target is None:
                        # No mesh device is process-local (a degenerate
                        # config: this process feeds no shard). A probe
                        # against an off-mesh device wouldn't reflect the
                        # actual feed path, so be conservative: treat as
                        # aliased -> reuse off (ADVICE r4 item 2).
                        self._stack_reuse = False
                        return self._stack_reuse
                aliased = False
                for _ in range(8):
                    probe = np.zeros((1 << 20,), np.uint8)
                    if target is None:
                        d = jax.device_put(probe)
                    else:
                        d = jax.device_put(probe, target)
                    # One-time capability probe, memoized in
                    # self._stack_reuse — deliberate sync, not a
                    # per-step stall (flagged by --hot-loop-depth 1).
                    jax.block_until_ready(d)  # lint: allow(jit-boundary/host-sync-in-hot-loop)
                    aliased |= bool(
                        np.shares_memory(np.asarray(d), probe)
                    )
                    if aliased:
                        break
                self._stack_reuse = not aliased
        return self._stack_reuse

    def _stack_out(
        self, trajs: list[Trajectory], K: Optional[int] = None
    ) -> Optional[Trajectory]:
        """Next ring stacking buffer (None when reuse is off). Blocks out
        the slot's previous device transfer before handing it back."""
        if not self._stack_reuse_enabled():
            return None
        i = self._ring_idx % len(self._ring)
        self._ring_idx += 1
        pending = self._ring_pending[i]
        if pending is not None:
            # The device arrays built from this slot's previous round:
            # until block_until_ready returns, jax's (possibly background-
            # dispatched) copy may still read the host buffer, so the
            # block must NEVER be skipped — strong references, not
            # weakrefs (a dead weakref can't prove the copy ran; an early
            # version skipped the block on dead refs and raced).
            # donate_batch exception: a DELETED leaf proves the
            # consuming step already ran, which implies the transfer
            # completed — and block_until_ready on it would raise.
            pending = [
                leaf
                for leaf in pending
                if not getattr(leaf, "is_deleted", lambda: False)()
            ]
            if pending:
                jax.block_until_ready(pending)
            self._ring_pending[i] = None
        if self._ring[i] is None:
            self._ring[i] = alloc_stack_buffers(trajs, K)
        self._last_slot = i
        return self._ring[i]

    def _record_pending_transfer(self, on_device) -> None:
        """Remember the device arrays built from the last ring slot so the
        slot blocks them out before reuse. Strong references by design:
        a dead weakref cannot prove the (possibly background-dispatched)
        copy ran, so the block must never be skippable. The refs pin at
        most the two ring slots' batches in device memory — usually still
        alive in the device queue anyway — and are dropped as the ring
        wraps."""
        if not self._stack_reuse_enabled() or self._last_slot is None:
            return
        slot, self._last_slot = self._last_slot, None
        leaves = jax.tree.leaves(on_device)
        if not self._ring_checked[slot]:
            # One-time per-slot safety net (covers a force-"on" config on
            # an aliasing backend the auto probe would have rejected): if
            # any device array actually aliases this slot's host buffers,
            # restacking would corrupt live batches — surrender the ring
            # and fall back to fresh allocation permanently. Costs one
            # D2H read per slot, not per batch; skipped when the arrays
            # aren't host-addressable (multihost shards).
            self._ring_checked[slot] = True
            bufs = [
                leaf
                for leaf in jax.tree.leaves(self._ring[slot])
                if isinstance(leaf, np.ndarray)
            ]
            try:
                aliased = any(
                    np.shares_memory(np.asarray(d), b)
                    for d in leaves
                    for b in bufs
                )
            except Exception:
                aliased = False
            if aliased:
                self._stack_reuse = False
                self._ring = [None] * len(self._ring)
                self._ring_pending = [None] * len(self._ring_pending)
                return
        self._ring_pending[slot] = leaves

    def _next_batch_lineage(
        self,
        lineage,
        versions,
        reuse_count: int = 1,
        staleness: int = 0,
        ring_slot: int = -1,
    ) -> BatchLineage:
        """Stamp the next batch id on the consumed unrolls' provenance
        (batcher thread only — the sequence needs no lock)."""
        bid = self._batch_seq
        self._batch_seq += 1
        meta = BatchLineage(
            batch=bid,
            lineage=tuple(lineage),
            versions=tuple(int(v) for v in versions),
            reuse_count=int(reuse_count),
            staleness=int(staleness),
            ring_slot=int(ring_slot),
        )
        self._last_lineage = meta
        return meta

    def _assemble_batch(self) -> Optional[Trajectory]:
        trajs = self._collect_trajs()
        if trajs is None:
            return None
        meta = self._next_batch_lineage(
            (t.lineage_id for t in trajs),
            (t.param_version for t in trajs),
        )
        t0_ns = time.monotonic_ns()
        with self._m_host_stack.time():
            batch = stack_trajectories(trajs, out=self._stack_out(trajs))
        self._tracer.complete(
            "learner/host_stack",
            t0_ns,
            time.monotonic_ns() - t0_ns,
            {"batch": meta.batch, "lineage": list(meta.lineage)},
        )
        self._count_stack_bytes(batch)
        if self._m_core_resets is not None:
            self._m_core_resets.set(int(np.count_nonzero(batch.first)))
        return batch

    def _count_stack_bytes(self, batch: Trajectory) -> None:
        """Account the bytes `stack_trajectories` just copied — the
        per-batch host copy cost the trajectory ring eliminates
        (tests/test_traj_ring.py reads this counter on both paths)."""
        self._m_host_stack_bytes.inc(
            tree_nbytes(
                (
                    batch.obs,
                    batch.first,
                    batch.actions,
                    batch.behaviour_logits,
                    batch.rewards,
                    batch.cont,
                    batch.task,
                    batch.agent_state,
                )
            )
        )

    def _assemble_superbatch(self, K: int) -> Optional[Trajectory]:
        """`[K, ...]` superbatch, each slice stacked in place so every
        unroll is copied once (not batch-then-restack). The destination is
        a ring buffer when reuse is on, else a fresh allocation shaped
        from the first round's trajectories."""
        sb: Optional[Trajectory] = None
        versions = []
        lids: list = []
        unroll_versions: list = []
        for k in range(K):
            trajs = self._collect_trajs()
            if trajs is None:
                return None
            lids.extend(t.lineage_id for t in trajs)
            unroll_versions.extend(
                int(t.param_version) for t in trajs
            )
            if sb is None:
                sb = self._stack_out(trajs, K)
                if sb is None:  # reuse off: fresh allocation
                    sb = alloc_stack_buffers(trajs, K)
            view = Trajectory(
                obs=sb.obs[k],
                first=sb.first[k],
                actions=sb.actions[k],
                behaviour_logits=sb.behaviour_logits[k],
                rewards=sb.rewards[k],
                cont=sb.cont[k],
                agent_state=jax.tree.map(lambda x: x[k], sb.agent_state),
                actor_id=-1,
                param_version=0,
                task=sb.task[k],
            )
            with self._m_host_stack.time():
                versions.append(
                    stack_trajectories(trajs, out=view).param_version
                )
            self._count_stack_bytes(view)
        self._next_batch_lineage(lids, unroll_versions)
        return sb._replace(param_version=min(versions))

    def _validate_tasks(self, task: np.ndarray) -> None:
        if self._config.popart is None:
            return
        bad = int(task.max(initial=0))
        if bad >= self._config.popart.num_values or task.min(
            initial=0
        ) < 0:
            raise ValueError(
                f"actor task ids "
                f"{sorted(set(task.ravel().tolist()))} "
                f"out of range for PopArt num_values="
                f"{self._config.popart.num_values}"
            )

    def _put_batch(self, arrays):
        """H2D placement of one assembled batch 8-tuple, honoring
        AUTO-layout formats / the mesh — shared by the queue and
        trajectory-ring batcher loops."""
        if self._mesh is None:
            # Locals, not repeated attribute reads: step_once's
            # layout-mismatch fallback nulls these from the main
            # thread and must not race this thread mid-branch.
            if self._auto_jit is not None:
                # First batch: AOT-compile with XLA-chosen layouts
                # and learn the batch input formats; later batches
                # transfer STRAIGHT into the step's preferred
                # layouts (no in-step relayout).
                if self._batch_formats is None:
                    self._ensure_auto_compiled(arrays)
                fmts = self._batch_formats
            else:
                fmts = None
            if fmts is not None:
                return jax.tree.map(_put_format, arrays, fmts)
            return jax.device_put(arrays)
        # Single-host: one device_put PER DATA SHARD, sliced straight
        # from the host buffer (a ring slot view on the zero-copy path)
        # and credited shard-by-shard to the h2d overlap telemetry.
        # Multi-host: this host's local slice becomes its shards of the
        # global batch array.
        self._put_shards = 0
        self._put_overlap_ns = 0
        return multihost.place_batch(
            self._batch_shardings, arrays, on_shard=self._on_shard_h2d
        )

    def _on_shard_h2d(self, nbytes: int, t0_ns: int, t1_ns: int) -> None:
        """place_batch per-shard completion callback (batcher thread):
        credit each shard's own transfer interval so
        perf/h2d_overlap_frac stays honest under the mesh (the whole
        dispatch window would over-count idle gaps between shards)."""
        self._put_shards += 1
        self._put_overlap_ns += self._note_h2d(t0_ns, t1_ns)

    def _note_h2d(self, t0_ns: int, t1_ns: int) -> int:
        """Score one H2D dispatch interval against the learner's recent
        train-step compute intervals (batcher thread; the overlap half
        of the zero-copy feed path). Returns the overlapped ns and
        updates the perf/h2d_* counters plus the cumulative
        perf/h2d_overlap_frac gauge."""
        total = max(0, t1_ns - t0_ns)
        ov = 0
        for s0, s1 in tuple(self._step_intervals):
            ov += max(0, min(t1_ns, s1) - max(t0_ns, s0))
        active = self._step_active_since_ns
        if active is not None:
            # The in-flight step has no end yet; everything past its
            # start overlaps compute. The min() cap below absorbs the
            # benign race where it finishes mid-call and lands in
            # _step_intervals too.
            ov += max(0, t1_ns - max(t0_ns, active))
        ov = min(ov, total)
        self._h2d_total_ns += total
        self._h2d_overlap_ns += ov
        self._m_h2d_total_ns.inc(total)
        self._m_h2d_overlap_ns.inc(ov)
        if self._h2d_total_ns:
            self._m_h2d_overlap_frac.set(
                self._h2d_overlap_ns / self._h2d_total_ns
            )
        return ov

    def _timed_sync(self, tree) -> None:
        """block_until_ready(tree), crediting only the GENUINE device
        wait to the allreduce stall accumulator: a second block on the
        now-ready tree measures the pure API/host overhead of the call
        itself, and only the first call's excess over twice that
        baseline counts. On a synchronous backend (CPU) both calls cost
        the same few microseconds and the stall reads ~0 — correct,
        since nothing was left executing for the host to wait on."""
        if not self._allreduce_est_ns:
            # No collective to account for: plain block, no calibration.
            jax.block_until_ready(tree)
            return
        t0 = time.monotonic_ns()
        jax.block_until_ready(tree)
        waited = time.monotonic_ns() - t0
        t1 = time.monotonic_ns()
        jax.block_until_ready(tree)
        baseline = time.monotonic_ns() - t1
        excess = waited - 2 * baseline
        # Scheduler-quantum noise floor: on a contended host a pair of
        # back-to-back calls can differ by tens of microseconds without
        # any device wait at all. Collective exposure that matters at
        # production scale is >= milliseconds; drop sub-floor readings
        # instead of letting contention jitter masquerade as stalls.
        if excess > _SYNC_NOISE_FLOOR_NS:
            self._allreduce_stall_ns += excess

    def _cost_allreduce_ns(self) -> int:
        """Per-step gradient all-reduce estimate for this learner's mesh.

        Ring cost over the data axis (perf/costmodel.allreduce_ns) on
        the full gradient payload (grads mirror the param tree). 0 when
        there is no mesh or the data axis is a single device — the
        gauge then stays unset, which is the honest reading (there IS
        no cross-shard reduction to hide)."""
        if self._mesh is None:
            return 0
        n = int(dict(self._mesh.shape).get("data", 1))
        if n <= 1:
            return 0
        from torched_impala_tpu.perf import costmodel

        nbytes = sum(
            leaf.nbytes for leaf in jax.tree.leaves(self._params)
        )
        device = self._mesh.devices.flat[0]
        if device.platform == "cpu":
            bw = costmodel.LOOPBACK_BYTES_PER_S  # simulated pods
        else:
            peaks = costmodel.DEVICE_PEAKS.get(device.device_kind)
            if peaks is None:
                return 0  # unknown device: the gauge stays unset
            bw = peaks.allreduce_bytes_per_s
        return costmodel.allreduce_ns(nbytes, n, bw)

    def _push_device_batch(
        self,
        on_device,
        param_version: int,
        meta: Optional[BatchLineage] = None,
    ) -> bool:
        """Bounded put into the device queue; False when stopping. Queue
        items are `(arrays, param_version, BatchLineage)` — the lineage
        rides next to the batch so the train-step trace span can name
        the exact unrolls (and staleness) it consumed."""
        while True:
            if self._stop.is_set():
                return False
            try:
                self._batch_q.put(
                    (on_device, param_version, meta), timeout=0.5
                )
                return True
            except queue.Full:
                continue

    def _batcher_loop_impl(self) -> None:  # lint: hot-loop
        if self.traj_ring is not None:
            self._ring_batcher_loop()
            return
        K = self._config.steps_per_dispatch
        while not self._stop.is_set():
            batch = (
                self._assemble_batch()
                if K == 1
                else self._assemble_superbatch(K)
            )
            if batch is None:
                return
            self._validate_tasks(batch.task)
            arrays = (
                batch.obs,
                batch.first,
                batch.actions,
                batch.behaviour_logits,
                batch.rewards,
                batch.cont,
                batch.task,
                batch.agent_state,
            )
            # Span covers the host-side DISPATCH of the H2D transfer
            # (jax's copy itself may complete asynchronously — the
            # double-buffering design point); a growing value here still
            # flags the feed path, which is what the breakdown is for.
            meta = self._last_lineage
            put_t0 = time.monotonic_ns()
            put_span = self._m_device_put.time()
            put_span.__enter__()
            on_device = self._put_batch(arrays)
            put_span.__exit__()
            put_dur = time.monotonic_ns() - put_t0
            if self._put_shards == 0:
                # Sharded placement already credited each per-device
                # put interval via _on_shard_h2d; only the unsharded
                # paths score the whole dispatch window.
                self._note_h2d(put_t0, put_t0 + put_dur)
            self._tracer.complete(
                "learner/device_put",
                put_t0,
                put_dur,
                {"batch": meta.batch},
            )
            self._record_pending_transfer(on_device)
            if not self._push_device_batch(
                on_device, batch.param_version, meta
            ):
                return

    def _ring_batcher_loop(self) -> None:  # lint: hot-loop
        """Trajectory-ring consumer: completed slots already ARE batches,
        so the host_stack stage collapses to a view handoff and the slot
        is device_put directly. Slots recycle only after their H2D copy
        completes (`release_after_transfer`), bounded by the device
        queue depth so recycling never gates the current transfer.

        Aliasing backends (the stack_buffer_reuse probe says device_put
        may ALIAS host numpy): recycling an aliased slot would corrupt
        the queued batch, so each batch stages through ONE owning copy
        instead and the slot recycles immediately — still one copy fewer
        than the queue path's actor-buffer + np.stack chain; the copy is
        accounted under learner/ring_stage_bytes, not host_stack.

        donate_batch short-circuits BOTH fallbacks (zero-copy contract):
        no staging copy and no transfer-bounded recycling, because the
        slot is released only after the consuming step completes
        (step_once, via meta.ring_slot) — at that point XLA is done
        reading (and possibly scribbling on) the slot's memory, and the
        next acquire/commit cycle rewrites every column anyway."""
        ring = self.traj_ring
        keep = self._config.device_queue_depth
        inflight: collections.deque = collections.deque()
        donate = self._config.donate_batch
        copy_before_put = (
            not self._stack_reuse_enabled() and not donate
        )
        alias_checked = donate
        while not self._stop.is_set():
            view = ring.pop_ready(timeout=0.5)
            if view is None:
                continue
            meta = self._next_batch_lineage(
                view.lineage,
                view.versions,
                reuse_count=view.reuse_count,
                staleness=view.staleness,
                ring_slot=view.slot if donate else -1,
            )
            stack_t0 = time.monotonic_ns()
            with self._m_host_stack.time():
                arrays = view.arrays
                if copy_before_put:
                    arrays = jax.tree.map(
                        lambda x: np.array(x, copy=True), arrays
                    )
            self._tracer.complete(
                "learner/host_stack",
                stack_t0,
                time.monotonic_ns() - stack_t0,
                {
                    "batch": meta.batch,
                    "lineage": list(meta.lineage),
                    "slot": view.slot,
                },
            )
            if copy_before_put:
                self._m_ring_stage_bytes.inc(tree_nbytes(arrays))
            self._validate_tasks(arrays[6])
            put_t0 = time.monotonic_ns()
            put_span = self._m_device_put.time()
            put_span.__enter__()
            on_device = self._put_batch(arrays)
            put_span.__exit__()
            put_dur = time.monotonic_ns() - put_t0
            if self._put_shards:
                # Sharded placement: per-device put intervals were
                # credited shard-by-shard via _on_shard_h2d.
                overlap_ns = self._put_overlap_ns
            else:
                overlap_ns = self._note_h2d(put_t0, put_t0 + put_dur)
            if donate:
                # Distinct span name for the overlapped path: report.py
                # scores learner/h2d* against compute intervals and must
                # not double-charge the overlapped part as gap.
                self._tracer.complete(
                    "learner/h2d",
                    put_t0,
                    put_dur,
                    {"batch": meta.batch, "overlap_ns": overlap_ns},
                )
            else:
                self._tracer.complete(
                    "learner/device_put",
                    put_t0,
                    put_dur,
                    {"batch": meta.batch},
                )
            if donate:
                self._m_donated_batches.inc()
            elif copy_before_put:
                # The staged copy owns its memory; the slot is free now.
                ring.release(view.slot)
            else:
                leaves = jax.tree.leaves(on_device)
                if not alias_checked:
                    # One-time safety net (covers a force-"on"
                    # stack_buffer_reuse on an aliasing backend the auto
                    # probe would have rejected): if device arrays alias
                    # the slot buffers, recycling would corrupt this
                    # batch — leak this ONE slot (its buffers back the
                    # live batch) and stage every later batch.
                    alias_checked = True
                    try:
                        aliased = any(
                            np.shares_memory(np.asarray(d), b)
                            for d in leaves
                            for b in jax.tree.leaves(view.arrays)
                        )
                    except Exception:
                        aliased = False
                    if aliased:
                        import logging

                        logging.getLogger(__name__).warning(
                            "traj_ring: device_put aliases slot buffers "
                            "on this backend; staging batches through "
                            "an owning copy (one slot leaked to protect "
                            "the in-flight batch)"
                        )
                        copy_before_put = True
                        leaves = None
                if leaves is not None:
                    inflight.append((view.slot, leaves))
                    while len(inflight) > keep:
                        s, pending = inflight.popleft()
                        ring.release_after_transfer(s, pending)
            if not self._push_device_batch(
                on_device, view.param_version, meta
            ):
                return

    def start(self) -> None:
        if self._batcher_thread is None:
            self._batcher_thread = threading.Thread(
                target=self._batcher_loop, name="batcher", daemon=True
            )
            self._batcher_thread.start()
        with self._settle_lock:
            if self._readers is None:
                self._readers = concurrent.futures.ThreadPoolExecutor(
                    _LANDING_THREADS, thread_name_prefix="learner-landing"
                )

    def stop(self) -> None:
        self._stop.set()
        if self.traj_ring is not None:
            # Wake actors blocked in ring.acquire (they raise QueueClosed
            # and exit, mirroring enqueue's contract) and the batcher's
            # pop_ready wait.
            self.traj_ring.close()
        try:
            self.drain()
        except Exception:
            # A failed step has already raised from step_once; teardown
            # (run's finally, a supervisor) must not mask that with the
            # same failure met again on the way out.
            import logging

            logging.getLogger(__name__).exception(
                "stop: settling the step in flight failed"
            )
        with self._settle_lock:
            readers, self._readers = self._readers, None
        if readers is not None:
            readers.shutdown(wait=True)

    # ---- stepping ------------------------------------------------------

    def _publish_now(self) -> None:
        """Copy the live parameters to the host and hand them to the
        actors, blocking: construction and `set_state`, where nothing is
        in flight. The step loop publishes through `_settle`."""
        t0 = time.monotonic_ns()
        leaves = jax.tree.leaves(self._params)
        requested, _ = _request_copies(leaves, 0, 0)
        with self._settle_lock:
            self._land(
                self.num_frames, [(leaf,) for leaf in leaves], requested, t0
            )
        dur = time.monotonic_ns() - t0
        self._m_publish.observe(dur / 1e9)
        self._tracer.complete(
            "learner/publish", t0, dur, {"version": self.num_frames}
        )

    def _land(self, version: int, snapshot, requested: int, t0: int) -> None:
        """Bring a version's pieces to the host (`_snapshot_to_host`)
        and publish it; `t0`: from where its landing is timed. Under
        `_settle_lock`, where `stop()` takes the readers away."""
        pieces = sum(len(leaf) for leaf in snapshot)
        host, peak = _snapshot_to_host(
            snapshot, requested, self._slabs, self._readers
        )
        self.param_store.publish(
            version, jax.tree.unflatten(jax.tree.structure(self._params), host)
        )
        self._m_publish_pieces.set(pieces)
        self._m_publish_outstanding.set(peak)
        self._m_publish_rate.set(
            sum(leaf.nbytes for leaf in host)
            / max(time.monotonic_ns() - t0, 1)
        )

    def drain(self) -> None:
        """Settle the step in flight now: wait for it, publish its
        parameters, recycle its ring slot, log it. After it the
        `ParamStore` holds the newest version and nothing of the loop is
        left on the device. `stop()`, `set_state()` and the end of `run()`
        call it; between two calls of `step_once` it is the caller's time
        and counts in `learner/outside_step` as well."""
        self._settle(time.monotonic_ns())
        self._observe_settled(0, 0)

    def _settle(
        self,
        t0: int,
        then: Optional[_InFlight] = None,
        led_from: Optional[int] = None,
    ) -> int:
        """Put `then` in flight and settle the step that was, from stamp
        `t0` on; returns the stamp where that ended (`t0` where nothing
        was in flight). What it cost is added to `_settled_ns` by phase,
        with one span each under the settled step's number. `led_from`:
        the stamp at which the next step's dispatch returned while this
        one was still on the device."""
        with self._settle_lock:
            done, self._in_flight = self._in_flight, then
            if done is None:
                return t0
            tag = {"step": done.step}
            jax.block_until_ready(done.probe)  # lint: allow(jit-boundary/host-sync-in-hot-loop)
            ready = time.monotonic_ns()
            self._period_wait_ns += ready - t0
            if led_from is not None:
                self._m_dispatch_lead.observe((ready - led_from) / 1e9)
            self._tracer.complete("learner/step_wait", t0, ready - t0, tag)
            self._tracer.complete(
                "learner/step_in_flight",
                done.dispatch_t0_ns,
                ready - done.dispatch_t0_ns,
                tag,
            )
            landed = ready
            if done.snapshot is not None:
                # Published trees own their bytes (types.owned_array).
                # The D2H of what fits the bound was requested when the
                # snapshot was queued, so this finds those bytes on the
                # host already; of a larger tree the next pieces travel
                # while the one before them is written into its leaf.
                self._land(
                    done.version, done.snapshot, done.requested, ready
                )
                landed = time.monotonic_ns()
                self._m_publish.observe((landed - t0) / 1e9)
                self._tracer.complete(
                    "learner/publish_copy", ready, landed - ready, tag
                )
                # Publish closes the lineage loop: the version stamped
                # here is what the next unrolls' lineage records carry
                # as param_version.
                self._tracer.complete(
                    "learner/publish",
                    t0,
                    landed - t0,
                    {"version": done.version},
                )
            if done.ring_slot >= 0:
                # Donated ring batch: its consuming step has completed,
                # XLA is done scribbling on the slot's buffers.
                self.traj_ring.release(done.ring_slot)
            if done.logs is not None:
                self._log_step(done)
            end = time.monotonic_ns()
            self._tracer.complete(
                "learner/bookkeeping", landed, end - landed, tag
            )
            self._settled_ns[0] += ready - t0
            self._settled_ns[1] += landed - ready
            self._settled_ns[2] += end - landed
            return end

    def _observe_settled(self, copy_ns: int, book_ns: int) -> None:
        """Observe the phase timers with what `_settle` has cost since
        the last time, plus the caller's own pieces: once per call of
        `step_once`, so that a mean per call is a mean per step."""
        with self._settle_lock:
            wait_ns, settled_copy, settled_book = self._settled_ns
            self._settled_ns = [0, 0, 0]
        if wait_ns:
            self._m_step_wait.observe(wait_ns / 1e9)
        if copy_ns + settled_copy:
            self._m_publish_copy.observe((copy_ns + settled_copy) / 1e9)
        if book_ns + settled_book:
            self._m_bookkeeping.observe((book_ns + settled_book) / 1e9)

    def step_once(self, timeout: Optional[float] = None) -> Mapping[str, Any]:  # lint: hot-loop
        """Block for one device batch, dispatch one SGD step on it, then
        settle the step before it: wait for that one, publish its
        parameters, log it. One step stays in flight, so the device has
        the next step queued while it runs the current one; `drain()`
        settles the last.

        Raises queue.Empty on timeout. Returned log values are device scalars
        (no forced sync); the configured logger receives host floats every
        `log_interval` steps, one call after the step they belong to.
        """
        if self.error is not None:
            raise RuntimeError("learner batcher thread died") from self.error
        entered = time.monotonic_ns()
        self._close_period(entered)
        # The step this call is for, on every span of its period.
        step_tag = {"step": self.num_steps + self._config.steps_per_dispatch}
        wait_from = entered
        if self._in_flight is not None and self._batch_q.empty():
            # Nothing to dispatch ahead of the device: settle the step in
            # flight while waiting for nobody, so that a starved learner
            # publishes each version as soon as the device has it, not
            # when the next batch arrives.
            wait_from = self._settle(entered)
        try:
            arrays, batch_version, meta = self._batch_q.get(
                timeout=timeout
            )
        finally:
            # Count timed-out waits too (queue.Empty propagates to the run
            # loop): starvation time must not vanish from the diagnostic
            # exactly when starvation is worst.
            step_t0_ns = self._returned_ns = time.monotonic_ns()
            wait_ns = step_t0_ns - wait_from
            self._period_wait_ns += wait_ns
            self._wait_accum += wait_ns / 1e9
            self._m_batch_wait.observe(wait_ns / 1e9)
            self._tracer.complete(
                "learner/batch_wait", wait_from, wait_ns, step_tag
            )
        # Mark the step in flight for the batcher's H2D-overlap scoring
        # (_note_h2d); _finish_step records the closed interval.
        self._step_active_since_ns = step_t0_ns
        if self._replay_step is not None:
            # IMPACT path: the pinned target params ride as a fourth
            # (non-donated) state arg. current() raises past the
            # configured staleness bound — a mis-wired refresh cadence
            # fails loudly instead of training against an ancient
            # anchor.
            _, target_params = self._target_store.current()
            (
                self._params,
                self._opt_state,
                self._popart_state,
                logs,
            ) = self._replay_step(
                self._params,
                self._opt_state,
                self._popart_state,
                target_params,
                *arrays,
            )
            return self._finish_step(logs, batch_version, meta, step_t0_ns)
        if self._fused_fallback_k:
            return self._finish_step(
                self._run_fused_chunked(arrays),
                batch_version,
                meta,
                step_t0_ns,
            )
        step = (
            self._auto_compiled
            if self._auto_compiled is not None
            else self._train_step
        )
        try:
            self._params, self._opt_state, self._popart_state, logs = step(
                self._params, self._opt_state, self._popart_state, *arrays
            )
        except ValueError as e:
            # Deliberately loose match ('layout', case-insensitive, not
            # the exact JAX-internal "layouts that disagree" wording): a
            # JAX upgrade that rewords the message must degrade to the
            # fallbacks below — which log the original error — instead
            # of turning a recoverable mismatch into a training crash
            # (ADVICE r5).
            fused_k = self._config.steps_per_dispatch
            if "layout" not in str(e).lower() or (
                self._auto_compiled is None and fused_k <= 4
            ):
                raise
            import logging

            if self._auto_compiled is not None:
                # device_put into the compiled Format came back with a
                # layout the AOT executable refuses (shape-dependent;
                # the plain jit relayouts inputs as needed). Fall back
                # permanently rather than crash training.
                logging.getLogger(__name__).warning(
                    "auto_layouts: batch layout disagreed with the "
                    "compiled formats (%s); falling back to the "
                    "standard train step",
                    str(e).splitlines()[0],
                )
                # _auto_jit=None stops the batcher's formats-put AND the
                # recompile path (in-flight formats-laid batches still
                # run: the plain jit relayouts any input). Under
                # _auto_lock: the batcher's _ensure_auto_compiled
                # re-checks _auto_jit inside the same lock, so a
                # fallback landing mid-compile can never be clobbered by
                # the compile's write-back (the race class impala-lint
                # thread-safety/unguarded-attr polices).
                with self._auto_lock:
                    self._auto_jit = None
                    self._auto_compiled = None
                    self._batch_formats = None
            else:
                # Fused K>4 superbatch refused at the jit boundary (a K=8
                # crash class seen on an earlier rig's chip): fall
                # back permanently to chunked K<=4 dispatch through the
                # same jitted scan body — one retrace for the chunk
                # shape, then steady state — instead of crashing.
                logging.getLogger(__name__).warning(
                    "fused dispatch: K=%d superbatch layout refused at "
                    "the jit boundary (%s); falling back to chunked "
                    "K<=4 dispatch (perf/fused_fallbacks counts each "
                    "chunked dispatch)",
                    fused_k,
                    str(e).splitlines()[0],
                )
                self._fused_fallback_k = 4
            # The failed call's donate_argnums may or may not have
            # consumed the state buffers depending on where validation
            # raised. Probe liveness before retrying: a retry on
            # deleted buffers would crash with a misleading "Array has
            # been deleted" — fail with an actionable message instead.
            def _alive(tree):
                return all(
                    not getattr(leaf, "is_deleted", lambda: False)()
                    for leaf in jax.tree.leaves(tree)
                )

            if not (
                _alive(self._params)
                and _alive(self._opt_state)
                and _alive(self._popart_state)
                and _alive(arrays)
            ):
                raise RuntimeError(
                    "layout fallback: the failed step consumed its "
                    "donated buffers; restart from the last "
                    "checkpoint (this path is only reachable if the "
                    "backend validates layouts after donation)"
                ) from e
            if self._fused_fallback_k:
                logs = self._run_fused_chunked(arrays)
            else:
                self._params, self._opt_state, self._popart_state, logs = (
                    self._train_step(
                        self._params,
                        self._opt_state,
                        self._popart_state,
                        *arrays,
                    )
                )
        return self._finish_step(logs, batch_version, meta, step_t0_ns)

    def _close_period(self, entered_ns: int) -> None:
        """At an entry of step_once: what the caller took since the last
        return (`learner/outside_step`) and, where a step completed since
        the period opened, the period less its waits
        (`learner/loop_overhead`); both carry that step's number."""
        tag = {"step": self.num_steps}
        if self._returned_ns is not None:
            outside_ns = entered_ns - self._returned_ns
            self._m_outside_step.observe(outside_ns / 1e9)
            self._tracer.complete(
                "learner/outside_step", self._returned_ns, outside_ns, tag
            )
        if self._period_t0_ns is None:
            self._period_t0_ns = entered_ns
        elif self._period_stepped:
            overhead_ns = (
                entered_ns - self._period_t0_ns - self._period_wait_ns
            )
            self._m_loop_overhead.observe(overhead_ns / 1e9)
            self._tracer.complete(
                "learner/loop_overhead",
                self._period_t0_ns,
                entered_ns - self._period_t0_ns,
                dict(tag, overhead_ns=overhead_ns),
            )
            self._period_t0_ns = entered_ns
            self._period_wait_ns = 0
            self._period_stepped = False

    def _run_fused_chunked(self, arrays):
        """Fused-dispatch layout fallback: run the [K, ...] superbatch
        through `self._train_step` in leading-axis chunks of
        `_fused_fallback_k`. The multi-step scan body is
        shape-polymorphic over K, so the chunk size costs one retrace —
        not a new program per step. Each chunked dispatch increments
        perf/fused_fallbacks."""
        K = self._config.steps_per_dispatch
        if K <= 1:
            # No [K, ...] superbatch axis to slice at K=1 — chunking
            # would chop the time axis instead. Degrade to the one-shot
            # step (a stray _fused_fallback_k must not corrupt shapes).
            (
                self._params,
                self._opt_state,
                self._popart_state,
                logs,
            ) = self._train_step(
                self._params, self._opt_state, self._popart_state, *arrays
            )
            return logs
        chunk = max(1, min(int(self._fused_fallback_k), K))
        logs = None
        for lo in range(0, K, chunk):
            part = jax.tree.map(
                lambda x, lo=lo: x[lo : lo + chunk], arrays
            )
            (
                self._params,
                self._opt_state,
                self._popart_state,
                logs,
            ) = self._train_step(
                self._params, self._opt_state, self._popart_state, *part
            )
        self._m_fused_fallbacks.inc()
        return logs

    def _observe_perf(self, step_dur_ns: int) -> None:
        """Live perf/* gauges (perf/costmodel): register the train-step
        root once — from the AOT executable's cost_analysis when the
        AUTO-layout path compiled one, else the static params estimate
        (CPU CI) — then fold each dispatch's wall-clock into perf/mfu
        and perf/membw_util. After the first call this is a dict lookup
        plus two gauge stores."""
        if self._cost_model is None:
            from torched_impala_tpu.perf import CostModel

            # Peaks for the device the step runs on; a device the table
            # does not know sets no utilisation gauge (costmodel).
            device = next(iter(jax.tree.leaves(self._params)[0].devices()))
            cm = CostModel.for_device(
                device.device_kind, registry=self._telemetry
            )
            cfg = self._config
            K = cfg.steps_per_dispatch
            cm.register_root(
                "train_step",
                compiled=self._auto_compiled,
                fallback_params=self._params,
                frames_per_call=cfg.unroll_length * cfg.batch_size * K,
                steps_per_call=K,
                # cost_analysis counts scan BODIES once: the grad-accum
                # microbatch body under-counts by ~accum, and the fused
                # K-step body (one body == one SGD step) by ~K.
                flops_scale=float(cfg.grad_accum * K),
            )
            self._cost_model = cm
        self._cost_model.observe_call("train_step", step_dur_ns / 1e9)

    def _finish_step(
        self, logs, batch_version, meta, step_t0_ns
    ) -> Mapping[str, Any]:
        """After the dispatch, shared by the standard and replay paths:
        counters, trace span, target-network refresh and ring staleness
        watermark for this step; its snapshot queued where it crossed
        `publish_interval`; then the step before it settled (`_settle`:
        wait, publish, slot release, log)."""
        # The DISPATCH of the XLA step as the host saw it: on an
        # asynchronous backend the call returns once the step is
        # enqueued, and the device's time on it is the
        # `learner/step_in_flight` span (or a device trace).
        dispatched = time.monotonic_ns()
        # Launched ahead of the device? Asked here, before the host does
        # anything else, without blocking.
        before = self._in_flight
        led_from = (
            dispatched
            if before is not None and not before.probe[0].is_ready()
            else None
        )
        # A device leaf of this step, taken before host counters join.
        probe = jax.tree.leaves(logs)[:1]
        step_dur_ns = dispatched - step_t0_ns
        self._step_intervals.append((step_t0_ns, dispatched))
        self._step_active_since_ns = None
        self._m_train_step.observe(step_dur_ns / 1e9)
        self._observe_perf(step_dur_ns)
        T = self._config.unroll_length
        K = self._config.steps_per_dispatch
        # Credit this dispatch's estimated gradient all-reduce cost (K
        # collectives for a fused dispatch) against the host stalls
        # accumulated since the previous step — see the perf/allreduce_*
        # registration comment for the semantics.
        if self._allreduce_est_ns is None:
            self._allreduce_est_ns = self._cost_allreduce_ns()
        if self._allreduce_est_ns > 0:
            est = self._allreduce_est_ns * K
            stall = min(self._allreduce_stall_ns, est)
            self._allreduce_stall_ns = 0
            self._allreduce_total_ns += est
            self._allreduce_overlap_ns += est - stall
            self._m_allreduce_total_ns.inc(est)
            self._m_allreduce_overlap_ns.inc(est - stall)
            self._m_allreduce_overlap_frac.set(
                self._allreduce_overlap_ns / self._allreduce_total_ns
            )
        self.num_frames += T * self._config.batch_size * K
        self.num_steps += K
        if self._replay is not None:
            # Advance the ring's staleness watermark (expires retained
            # slots eagerly) and refresh the target on its cadence.
            self.traj_ring.note_version(self.num_frames)
            self._target_store.maybe_update(
                self.num_steps, self._params, self.num_frames
            )
        self._m_param_lag.set(self.num_frames - batch_version)
        # The trace side of the staleness story: EXACT per-unroll lags
        # for THIS batch (frame counter after the update minus each
        # consumed unroll's acting param version — the same convention
        # the param_lag_frames gauge summarizes by its min-version).
        if meta is None:
            meta = BatchLineage(batch=-1)
        lags = [self.num_frames - v for v in meta.versions]
        self._tracer.complete(
            "learner/train_step",
            step_t0_ns,
            step_dur_ns,
            {
                "batch": meta.batch,
                "step": self.num_steps,
                "lineage": list(meta.lineage),
                "param_versions": list(meta.versions),
                "param_lag_frames": lags,
                "param_lag_min": (
                    min(lags) if lags
                    else self.num_frames - batch_version
                ),
                "param_lag_max": (
                    max(lags) if lags
                    else self.num_frames - batch_version
                ),
                # Replay lineage (ISSUE 9 satellite): one ring slot has
                # one slot-level reuse_count, so min == max today; the
                # pair keeps the schema stable for a future multi-slot
                # fused batch.
                "reuse_min": meta.reuse_count,
                "reuse_max": meta.reuse_count,
                "staleness": meta.staleness,
            },
        )
        self._telemetry.heartbeat("learner")
        logs = dict(logs)
        logs["num_frames"] = self.num_frames
        logs["num_steps"] = self.num_steps
        logs["param_lag_frames"] = self.num_frames - batch_version
        step_tag = {"step": self.num_steps}
        publishes = crossed_interval(
            self.num_steps, K, self._config.publish_interval
        )
        logs_due = (
            self._logger is not None or self._health is not None
        ) and crossed_interval(self.num_steps, K, self._config.log_interval)
        queued = queueing = time.monotonic_ns()
        snapshot, requested = None, 0
        if publishes:
            # Queued behind this step and before the next is ever
            # dispatched, and its D2H requested at once (before any leaf
            # is materialised: np.asarray alone would serialise one
            # synchronous transfer per leaf), so the bytes travel as soon
            # as the device has them; of a large tree, the pieces that
            # fit `SNAPSHOT_COPY_AHEAD_BYTES`.
            snapshot = _publish_snapshot(
                self._params,
                tuple(map(_piece_rows, jax.tree.leaves(self._params))),
            )
            pieces = [piece for leaf in snapshot for piece in leaf]
            requested, _ = _request_copies(pieces, 0, 0)
            probe = pieces[:1]
            queued = time.monotonic_ns()
            self._tracer.complete(
                "learner/publish_copy", queueing, queued - queueing, step_tag
            )
        self._tracer.complete(
            "learner/bookkeeping", dispatched, queueing - dispatched, step_tag
        )
        # A step that crossed no interval and holds no slot is not waited
        # for. The one before this is settled now in any case.
        owed = None
        if publishes or logs_due or meta.ring_slot >= 0:
            owed = _InFlight(
                step=self.num_steps,
                version=self.num_frames,
                dispatch_t0_ns=step_t0_ns,
                probe=probe,
                snapshot=snapshot,
                requested=requested,
                ring_slot=meta.ring_slot,
                logs=dict(logs) if logs_due else None,
                meta=meta,
            )
        resumed = self._settle(queued, owed, led_from)
        if self.post_step is not None:
            self.post_step(self.num_steps)
        self._period_stepped = True
        self._returned_ns = time.monotonic_ns()
        tail_ns = self._returned_ns - resumed
        self._observe_settled(
            queued - queueing, queueing - dispatched + tail_ns
        )
        self._tracer.complete(
            "learner/bookkeeping", resumed, tail_ns, step_tag
        )
        return logs

    def _log_step(self, done: _InFlight) -> None:
        """Hand a completed step's scalars to the logger and the health
        monitor as host floats, with the rates since the last log."""
        logs = done.logs
        now = time.monotonic()
        if self._last_log_t is not None:
            elapsed = max(now - self._last_log_t, 1e-9)
            # frames/sec of the learner pipeline, and the fraction of
            # wall time spent starved waiting for a batch: ~0 means the
            # TPU is the bottleneck, ~1 means actors/H2D are.
            logs["frames_per_sec"] = (
                done.version - self._last_log_frames
            ) / elapsed
            logs["batch_wait_frac"] = min(self._wait_accum / elapsed, 1.0)
            self._m_steps_per_sec.set(
                (done.step - self._last_log_steps) / elapsed
            )
        else:
            # Keys must exist on the first write too (CSV columns are
            # fixed by the first row).
            logs["frames_per_sec"] = float("nan")
            logs["batch_wait_frac"] = float("nan")
        self._last_log_t = now
        self._last_log_frames = done.version
        self._last_log_steps = done.step
        self._wait_accum = 0.0
        # The step has completed (_settle waited for it, and that wait is
        # the device's compute, not a stall): what materialising the
        # scalars still waits for is debited against the collective's
        # overlap credit, timed via the calibrated sync so that pure
        # conversion overhead does not read as a stall.
        device_leaves = [v for v in logs.values() if isinstance(v, jax.Array)]
        if device_leaves and self._allreduce_est_ns:
            self._timed_sync(device_leaves)  # lint: allow(jit-boundary/host-sync-in-hot-loop)
        host_logs = {
            k: float(v) if isinstance(v, (jax.Array, np.ndarray)) else v
            for k, v in logs.items()
        }
        if self._logger is not None:
            self._logger(host_logs)
        if self._health is not None:
            # The health plane rides the SAME materialized floats as
            # the logger — zero additional device syncs (the ISSUE 19
            # dispatch-count contract).
            self._health.observe(host_logs, lineage=done.meta)

    def attach_health(self, monitor) -> None:
        """Attach a `telemetry.health.HealthMonitor` (ISSUE 19): its
        observe() rides the existing log-interval float materialization
        in `_finish_step` (no extra host syncs), and its postmortem
        bundles capture this learner's config, RNG stream, and counters.
        Crash bundles come from `run`'s exception path. Pair with
        `config.loss.health_diagnostics=True` for the in-jit series —
        without the flag only the host-derived gauges (grad spike
        ratio) have data."""
        from torched_impala_tpu.utils.checkpoint import pack_rng

        self._health = monitor
        monitor.bind_context(
            config=self._config,
            get_rng=lambda: np.asarray(pack_rng(self._rng)),
            get_counters=lambda: {
                "num_steps": self.num_steps,
                "num_frames": self.num_frames,
            },
        )

    def run(
        self,
        max_steps: int,
        stop_event: Optional[threading.Event] = None,
        watchdog: Optional[Callable[[], None]] = None,
    ) -> None:
        """Learner loop: `max_steps` SGD steps, then signal stop.

        `watchdog` is invoked whenever no batch arrives within a second — it
        should raise if the producers are dead (SURVEY.md §6 failure
        detection) so a fully-stalled job fails loudly instead of hanging.

        With `steps_per_dispatch=K > 1` each dispatch takes K SGD steps, so
        the loop runs the largest multiple of K that fits in `max_steps` —
        it never overshoots the budget (optax schedules and the frame
        budget must line up with total_steps, loop.py's resume contract).
        A non-multiple remainder is left unspent, loudly.
        """
        self.start()
        K = self._config.steps_per_dispatch
        if max_steps % K:
            import warnings

            warnings.warn(
                f"step budget {max_steps} is not a multiple of "
                f"steps_per_dispatch={K}; the final {max_steps % K} "
                f"step(s) will not run",
                stacklevel=2,
            )
        steps_done = 0
        try:
            while steps_done + K <= max_steps:
                if stop_event is not None and stop_event.is_set():
                    break
                try:
                    self.step_once(timeout=1.0)
                    steps_done += K
                except queue.Empty:
                    if watchdog is not None:
                        watchdog()
            # The last step's version reaches the actors, and a failure
            # of it raises here, before the loop reports a clean end.
            self.drain()
        except BaseException as e:
            # Anomaly postmortem on the way down (ISSUE 19): bundle the
            # flight-recorder tail, health snapshots, and the last
            # batch's lineage BEFORE teardown scrambles them; then let
            # the crash propagate unchanged.
            if self._health is not None:
                self._health.on_crash(e)
            raise
        finally:
            self.stop()
            if stop_event is not None:
                stop_event.set()

    # ---- checkpoint state ----------------------------------------------

    def get_state(self) -> dict:
        """Checkpointable learner state (SURVEY.md §6 checkpoint row)."""
        # Host snapshots, not live device refs: the train step donates the
        # params/opt_state buffers, so live refs would dangle after the next
        # step_once ("Array has been deleted").
        from torched_impala_tpu.utils.checkpoint import pack_rng

        state = {
            "params": host_snapshot(self._params),
            "opt_state": host_snapshot(self._opt_state),
            "num_frames": np.asarray(self.num_frames, np.int64),
            "num_steps": np.asarray(self.num_steps, np.int64),
            "rng": np.asarray(pack_rng(self._rng)),
        }
        # Only present under PopArt: keeps non-PopArt checkpoint trees
        # identical to pre-PopArt ones (orbax restore requires matching
        # structures, so an always-present key would break old checkpoints).
        if self._config.popart is not None:
            state["popart_state"] = host_snapshot(self._popart_state)
        return state

    def get_state_device(self) -> dict:
        """`get_state`-shaped tree with ON-DEVICE clones instead of host
        snapshots — the learner-thread half of an async checkpoint save.

        `jnp.copy` dispatches an on-device copy and returns immediately
        (no host sync), and the clones are fresh buffers the train step's
        donation can never invalidate, so the resilience
        AsyncCheckpointer's writer thread can `device_get` them at its
        leisure while training continues (resilience/checkpointer.py)."""
        from torched_impala_tpu.utils.checkpoint import pack_rng

        state = {
            "params": jax.tree.map(jnp.copy, self._params),
            "opt_state": jax.tree.map(jnp.copy, self._opt_state),
            "num_frames": np.asarray(self.num_frames, np.int64),
            "num_steps": np.asarray(self.num_steps, np.int64),
            "rng": jnp.copy(pack_rng(self._rng)),
        }
        if self._config.popart is not None:
            state["popart_state"] = jax.tree.map(
                jnp.copy, self._popart_state
            )
        return state

    def set_state(self, state: Mapping[str, Any]) -> None:
        """Restore from `get_state()`-shaped tree and republish params so
        actors immediately see the restored policy at its restored frame
        count (resume restores the actor-visible param version,
        SURVEY.md §6)."""
        from torched_impala_tpu.utils.checkpoint import (
            validate_restored_shapes,
        )

        # The step in flight belongs to the state that is being replaced:
        # settle it first, so that its version cannot land on the
        # restored one.
        self.drain()
        params = state["params"]
        # Fail actionably (naming the known r5 padding change) instead of
        # with a raw tree/shape mismatch deeper in device_put/XLA.
        validate_restored_shapes(params, self._params, what="params")
        opt_state = state["opt_state"]
        popart_state = state.get("popart_state", self._popart_state)
        if self._config.popart is not None and popart_state != ():
            # Checkpoint layers may round-trip the NamedTuple as a plain
            # (mu, nu) sequence/dict; rebuild the typed state.
            if not isinstance(popart_state, popart_ops.PopArtState):
                if isinstance(popart_state, Mapping):
                    popart_state = popart_ops.PopArtState(**popart_state)
                else:
                    popart_state = popart_ops.PopArtState(*popart_state)
        # Refuse half-precision accumulator state BEFORE it replaces the
        # live f32 state: a checkpoint whose optimizer moments or PopArt
        # stats were saved in bf16 (seeded corruption, a foreign writer)
        # would degrade training silently — the ops/precision.py policy
        # says accumulators are f32-only, enforced here at the restore
        # boundary (the doctor's "mixed precision" row probes this).
        precision.assert_f32_accumulators(
            {
                "optimizer_state": opt_state,
                "popart_stats": popart_state,
            },
            context="Learner.set_state",
        )
        # Under _auto_lock: a restore landing while the batcher thread is
        # inside _ensure_auto_compiled (a seconds-long AOT compile that
        # re-lays and writes back a PRE-restore state snapshot) would
        # otherwise be silently clobbered (ADVICE r5). The lock serializes
        # the two writers: whichever runs second sees the other's result —
        # ensure re-reads live state inside the lock, and a restore that
        # waited for ensure lands in the compiled formats below.
        with self._auto_lock:
            if self._mesh is not None:
                rep = replicated(self._mesh)
                # Same layouts as construction (tensor-parallel leaves land
                # back on their shards; DP-only meshes replicate).
                params = jax.device_put(params, self._param_shardings)
                opt_state = jax.device_put(opt_state, self._opt_shardings)
                popart_state = jax.device_put(popart_state, rep)
            elif self._auto_compiled is not None:
                # Restored state must land in the compiled step's layouts
                # (the AOT executable requires exact input formats).
                fmts = self._state_formats
                params = jax.tree.map(_put_format, params, fmts[0])
                opt_state = jax.tree.map(_put_format, opt_state, fmts[1])
                popart_state = jax.tree.map(
                    _put_format, popart_state, fmts[2]
                )
            else:
                params = jax.device_put(params)
                opt_state = jax.device_put(opt_state)
                popart_state = jax.device_put(popart_state)
            self._params = params
            self._opt_state = opt_state
            self._popart_state = popart_state
        self.num_frames = int(state["num_frames"])
        self.num_steps = int(state["num_steps"])
        if "rng" in state:
            from torched_impala_tpu.utils.checkpoint import unpack_rng

            self._rng = unpack_rng(state["rng"])
        self._publish_now()
        if self.traj_ring is not None:
            # A restore landing on a live ring (survivor-driven restart
            # after a kill_host chaos fault) must not feed slots a dead
            # writer left half-committed into the restored run.
            torn = self.traj_ring.discard_torn()
            if torn:
                print(
                    f"[learner] restore discarded {torn} torn ring "
                    "slot(s) from a writer that died mid-commit",
                    file=sys.stderr,
                    flush=True,
                )
        if self._target_store is not None:
            # Re-pin the target from the restored params: a resumed run
            # must not clip against the pre-restore policy (and the old
            # target's lag bound would trip against the restored frame
            # counter).
            self._target_store.update(
                self._params,
                version=self.num_frames,
                step=self.num_steps,
            )

    # ---- introspection -------------------------------------------------

    @property
    def params(self):
        return self._params

    @property
    def opt_state(self):
        return self._opt_state

    @property
    def popart_state(self):
        return self._popart_state
