"""Trajectory container shared by actors, the batcher, and the learner.

Time-major, one env's unroll. Carries T+1 observations/first-flags so the
learner can bootstrap from the final step (the analog keeps the last timestep
for exactly this, `actor.py:52-92,:91`), plus the recurrent state the unroll
started from (`learner.py:96`).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import numpy as np


class QueueClosed(Exception):
    """Raised by enqueue once the learner has shut down; actors exit on it."""


class Trajectory(NamedTuple):
    """One unroll of length T (arrays are numpy on the host side).

    Attributes:
      obs: `[T+1, ...]` observations; obs[T] is the bootstrap observation.
      first: bool `[T+1]` episode-start flags aligned with obs (first[t] set
        iff obs[t] begins an episode; used for LSTM resets).
      actions: int32 `[T]` actions taken at obs[:T].
      behaviour_logits: float32 `[T, A]` actor-policy logits at act time.
      rewards: float32 `[T]` rewards following each action.
      cont: float32 `[T]` continuation flags (1 - done); the learner
        multiplies by gamma to get per-step discounts, keeping gamma a
        learner-side hyper-parameter.
      agent_state: recurrent state at obs[0] (structure matches the net's
        initial_state; () for feedforward nets).
      actor_id: which actor produced this unroll.
      param_version: frame-count stamp of the params used to act —
        the actor↔learner staleness telemetry (SURVEY.md §6 race detection).
      task: int task id of the env that produced the unroll (selects the
        PopArt value column for multi-task configs; 0 for single-task).
        Batched trajectories carry an int32 `[B]` array here.
      lineage_id: flight-recorder lineage ID of the unroll cycle that
        produced this trajectory (`a<actor>u<seq>`, telemetry/tracing.py);
        "" from writers that don't trace. Batched trajectories carry a
        tuple of the consumed unrolls' IDs.
    """

    obs: np.ndarray
    first: np.ndarray
    actions: np.ndarray
    behaviour_logits: np.ndarray
    rewards: np.ndarray
    cont: np.ndarray
    agent_state: Any
    actor_id: int = 0
    param_version: int = 0
    task: int = 0
    lineage_id: Any = ""


def host_snapshot(tree: Any) -> Any:
    """Materialize a pytree of (possibly device) arrays as host numpy that
    OWNS its memory.

    `np.asarray` of a jax CPU array can be a zero-copy VIEW of the device
    buffer; if the source array is later dropped (or its buffer donated),
    the view can silently morph into whatever the allocator reuses the
    memory for — observed live: a drained batch's "copy" turning into
    batch i+4's data. Every long-lived host capture (published actor
    params, checkpoint snapshots, trajectory start states) must own its
    bytes. On TPU `np.asarray` is already a fresh D2H copy, and the
    owndata check keeps that single-copy."""
    return jax.tree.map(owned_array, tree)


def owned_array(leaf: Any, out: Optional[np.ndarray] = None) -> np.ndarray:
    """One leaf of `host_snapshot`: host numpy that owns its bytes. With
    `out`, the bytes are written there instead (the learner lands a
    piece of a leaf in its block of rows)."""
    arr = np.asarray(leaf)
    if out is not None:
        np.copyto(out, arr)
        return out
    return arr if arr.flags.owndata else np.array(arr, copy=True)


def tree_nbytes(tree: Any) -> int:
    """Total bytes of the array leaves of a pytree.

    The copy-bytes accounting unit behind `telemetry/learner/
    host_stack_bytes` (how many bytes the batcher's stacking path copies
    per batch — the number the zero-copy trajectory ring drives to 0;
    tests/test_traj_ring.py holds both sides)."""
    return sum(
        leaf.nbytes
        for leaf in jax.tree.leaves(tree)
        if hasattr(leaf, "nbytes")
    )


def crossed_interval(num_steps: int, delta: int, interval: int) -> bool:
    """True iff advancing the step counter from `num_steps - delta` to
    `num_steps` crossed a multiple of `interval`.

    The interval check for fused dispatch: one dispatch advances the
    counter by delta = steps_per_dispatch, so `num_steps % interval == 0`
    would fire only when delta divides the interval; crossing-based checks
    fire exactly once per boundary for any (delta, interval)."""
    return (num_steps // interval) > ((num_steps - delta) // interval)
