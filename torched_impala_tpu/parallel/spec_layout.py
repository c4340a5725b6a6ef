"""SpecLayout: the canonical PartitionSpec table for the whole stack.

Every mesh-axis name and every PartitionSpec the runtime uses is declared
HERE, once, as plain literals — the name-pattern map idiom for params
(quantized-weight sharding maps, SNIPPETS.md [1]), a logical-tensor table
for activations/batches (SNIPPETS.md [2]), and a naive
shard-if-divisible fallback (SNIPPETS.md [3]). Call sites build their
shardings through this module instead of inventing `P(...)` ad hoc; the
sharding-contract checker (tools/lint/sharding.py) statically parses the
literal tables below and flags any axis name or spec elsewhere in the
tree that does not resolve against them.

The tables are PURE LITERALS on purpose: `tools/lint` reads them with
`ast.literal_eval` — no jax import, no device init — so the contract is
checkable from tier-1 and from CI on a machine with no accelerator.

Why the LSTM exception exists (PARAM_PATTERNS below): flax's
`OptimizedLSTMCell` concatenates its eight gate kernels into one
`[in, 4H]` matmul operand at apply time.  Sharding one slice of a
runtime-concatenated matrix hands XLA's SPMD partitioner a
mixed replicated/sharded concatenate, which this backend miscompiles —
the product comes back scaled by the size of the replicated mesh axis
(exactly 2x on a ('data','model')=(2,4) mesh; pinned by
tests/test_parallel.py::test_tensor_parallel_step_matches_single_device).
Gate kernels therefore stay replicated; they are a negligible share of
IMPALA-scale FLOPs next to the torso.
"""

from __future__ import annotations

import fnmatch
from typing import Optional, Sequence, Tuple

# --------------------------------------------------------------------------
# The canonical tables. PURE LITERALS — parsed statically by
# tools/lint/sharding.py; do not compute entries.
# --------------------------------------------------------------------------

# Every mesh-axis name any Mesh in this codebase may declare.
MESH_AXES = ("data", "model", "seq")

# Logical-tensor table: one entry per distinct tensor layout the runtime
# ships. Each spec is a tuple with one entry per LEADING dimension
# (trailing dimensions are unsharded); `None` = replicated on that dim.
# A position naming an axis may degrade to None at a call site (the
# naive-data-shard fallback: shard when divisible, replicate otherwise),
# but never the reverse, and never a different axis.
TENSOR_TABLE = {
    # params, opt state, PopArt stats, rng keys, scalar logs
    "replicated": (),
    # [T, B, ...] learner batches: batch over data, time whole
    "batch_time_major": (None, "data"),
    # [B, ...] recurrent-state / env-state / per-env leaves
    "batch_major": ("data",),
    # [K, T, B, ...] fused-dispatch superbatches (K consumed by the scan)
    "superbatch_time_major": (None, None, "data"),
    # [K, B, ...] fused-dispatch state leaves
    "superbatch_major": (None, "data"),
    # [T, B, ...] sequence-parallel activations: unroll over seq, batch
    # over data (data entry degrades to None on a 1-d ('seq',) mesh)
    "seq_activation": ("seq", "data"),
    # [S, B, ...] KV-cache prefix blocks: replicated along seq, batch
    # over data
    "seq_prefix": (None, "data"),
    # weight matrices under tensor parallelism: output features (last
    # dim) over model — the Megatron column layout. Rank-polymorphic:
    # leading dims pad with None (see tp_column_spec).
    "tp_column": ("model",),
}

# Param-name pattern map (first match wins; matched against the
# '/'-joined tree path with integer components wildcarded, lowercase).
# Kinds: "replicated" | "tp_column" (shard last dim over model when
# divisible, else replicate).
PARAM_PATTERNS = (
    # flax OptimizedLSTMCell gate kernels — see module docstring.
    ("*/lstm/*", "replicated"),
    ("*/kernel", "tp_column"),
    ("*/embedding", "tp_column"),
)

# Feed-path batch placement (ISSUE 15): the learner's train step
# consumes exactly these eight arrays, in this order. Each role maps to
# (logical tensor name, batch-dim index) per layout, so BOTH the
# runtime (feed_shardings / sharded place_batch below) and the static
# checker (tools/lint/sharding.py feed-path rule) resolve every
# feed-path device_put through the same table. "plain" is the
# [T+1, B, ...] K=1 layout, "superbatch" the fused-dispatch
# [K, T+1, B, ...] layout.
BATCH_ROLES = (
    "obs",
    "first",
    "actions",
    "behaviour_logits",
    "rewards",
    "cont",
    "task",
    "agent_state",
)
BATCH_PLACEMENT = {
    "plain": {
        "obs": ("batch_time_major", 1),
        "first": ("batch_time_major", 1),
        "actions": ("batch_time_major", 1),
        "behaviour_logits": ("batch_time_major", 1),
        "rewards": ("batch_time_major", 1),
        "cont": ("batch_time_major", 1),
        "task": ("batch_major", 0),
        "agent_state": ("batch_major", 0),
    },
    "superbatch": {
        "obs": ("superbatch_time_major", 2),
        "first": ("superbatch_time_major", 2),
        "actions": ("superbatch_time_major", 2),
        "behaviour_logits": ("superbatch_time_major", 2),
        "rewards": ("superbatch_time_major", 2),
        "cont": ("superbatch_time_major", 2),
        "task": ("superbatch_major", 1),
        "agent_state": ("superbatch_major", 1),
    },
}

# --------------------------------------------------------------------------
# Runtime builders over the tables (jax imported lazily so static
# consumers of the literals never pay for it).
# --------------------------------------------------------------------------


def _pspec(*entries):
    from jax.sharding import PartitionSpec

    return PartitionSpec(*entries)


def tensor_spec(logical: str):
    """The canonical PartitionSpec for a logical tensor by table name."""
    try:
        return _pspec(*TENSOR_TABLE[logical])
    except KeyError:
        raise KeyError(
            f"unknown logical tensor {logical!r}; SpecLayout declares "
            f"{sorted(TENSOR_TABLE)}"
        ) from None


def batch_spec(*, time_major: bool = True):
    """`[T, B, ...]` (time-major) or `[B, ...]` learner-batch spec."""
    return tensor_spec("batch_time_major" if time_major else "batch_major")


def state_spec():
    """`[B, ...]` recurrent-state / per-env-state leaves."""
    return tensor_spec("batch_major")


def replicated_spec():
    return tensor_spec("replicated")


def seq_spec(axis_name: str = "seq", batch_axis: Optional[str] = None):
    """`[T, B, ...]` sequence-parallel activations: T over `axis_name`,
    B over `batch_axis` when the mesh has one (the ('data','seq')
    combined layout), else replicated."""
    _require_declared(axis_name)
    if batch_axis is not None:
        _require_declared(batch_axis)
    return _pspec(axis_name, batch_axis)


def prefix_spec(batch_axis: Optional[str] = None):
    """`[S, B, ...]` KV-cache prefix: whole along seq, B over
    `batch_axis` when given."""
    if batch_axis is not None:
        _require_declared(batch_axis)
    return _pspec(None, batch_axis)


def with_leading(spec, n: int = 1):
    """`spec` for a tensor that grew `n` leading unsharded dims (the
    fused-dispatch `[K, ...]` superbatch axis)."""
    return _pspec(*((None,) * n + tuple(spec)))


def tp_column_spec(rank: int):
    """Rank-`rank` Megatron column layout: last dim over 'model'."""
    return _pspec(*([None] * (rank - 1) + ["model"]))


def feed_spec(role: str, *, superbatch: bool = False):
    """The canonical PartitionSpec for one feed-path batch role."""
    layout = "superbatch" if superbatch else "plain"
    try:
        logical, _ = BATCH_PLACEMENT[layout][role]
    except KeyError:
        raise KeyError(
            f"unknown feed role {role!r}; SpecLayout declares "
            f"{BATCH_ROLES}"
        ) from None
    return tensor_spec(logical)


def feed_batch_dim(role: str, *, superbatch: bool = False) -> int:
    """Which dimension of `role`'s array is the (data-sharded) batch."""
    layout = "superbatch" if superbatch else "plain"
    try:
        return BATCH_PLACEMENT[layout][role][1]
    except KeyError:
        raise KeyError(
            f"unknown feed role {role!r}; SpecLayout declares "
            f"{BATCH_ROLES}"
        ) from None


def feed_shardings(mesh, *, superbatch: bool = False):
    """NamedShardings for the eight feed-path arrays, in BATCH_ROLES
    order — the ONLY sanctioned way for runtime code to build batch
    shardings (the sharding checker's feed-path rule flags ad-hoc
    NamedSharding construction in `runtime/`)."""
    from jax.sharding import NamedSharding

    return tuple(
        NamedSharding(mesh, feed_spec(role, superbatch=superbatch))
        for role in BATCH_ROLES
    )


def _require_declared(axis: str) -> None:
    if axis not in MESH_AXES:
        raise ValueError(
            f"mesh axis {axis!r} is not declared in SpecLayout.MESH_AXES "
            f"{MESH_AXES}; declare it there (and teach the sharding "
            "checker about it) before using it"
        )


def normalize_param_path(path: str) -> str:
    """'params/layers/3/attn/kernel' -> 'params/layers/*/attn/kernel'
    (SNIPPETS.md [1]: all layers share one sharding)."""
    parts = []
    for tok in path.replace("'", "").split("/"):
        parts.append("*" if tok.isdigit() else tok)
    return "/".join(parts).lower()


def param_spec(path: str, shape: Sequence[int], model_axis_size: int):
    """Canonical spec for one parameter (or mirrored optimizer-moment)
    leaf: first PARAM_PATTERNS match wins; `tp_column` shards the last
    dim over 'model' only when divisible (naive fallback, SNIPPETS.md
    [3]) — correctness never depends on the choice, the partitioner
    inserts whatever collectives the layout needs."""
    norm = normalize_param_path(path)
    kind = "replicated"
    for pattern, k in PARAM_PATTERNS:
        if fnmatch.fnmatchcase(norm, pattern):
            kind = k
            break
    if (
        kind == "tp_column"
        and model_axis_size > 1
        and len(shape) >= 2
        and shape[-1] % model_axis_size == 0
        and shape[-1] >= model_axis_size
    ):
        return tp_column_spec(len(shape))
    return replicated_spec()


def param_shardings(mesh, tree):
    """NamedSharding tree for a param/opt-state pytree over `mesh` —
    the runtime entry point behind `parallel.model_shardings`. Meshes
    without a 'model' axis (the ('data','seq') DP+SP mesh) replicate
    everything, like a size-1 model axis."""
    import jax
    from jax.sharding import NamedSharding

    n = dict(mesh.shape).get("model", 1)

    def rule(path, leaf):
        keys = "/".join(_path_token(p) for p in path)
        shape = getattr(leaf, "shape", ())
        return NamedSharding(mesh, param_spec(keys, shape, n))

    return jax.tree_util.tree_map_with_path(rule, tree)


def _path_token(entry) -> str:
    # DictKey('torso') -> torso; SequenceKey(0)/GetAttrKey('mu') -> 0/mu
    for attr in ("key", "idx", "name"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def naive_data_sharding(shape: Sequence[int], mesh):
    """SNIPPETS.md [3] fallback: shard dim 0 over 'data' when it
    divides, else replicate."""
    from jax.sharding import NamedSharding

    n = dict(mesh.shape).get("data", 1)
    if shape and n > 1 and shape[0] % n == 0:
        return NamedSharding(mesh, tensor_spec("batch_major"))
    return NamedSharding(mesh, replicated_spec())


# --------------------------------------------------------------------------
# shard_map: one keyword-only spelling over `jax.shard_map`, so call
# sites build their specs from the tables above.
# --------------------------------------------------------------------------


def shard_map(f, *, mesh, in_specs, out_specs):
    import jax

    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs
    )
