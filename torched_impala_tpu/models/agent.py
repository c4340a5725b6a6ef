"""Agent: the stateless two-mode policy API over an ImpalaNet.

Mirrors the analog's `agent.py:62-108` (`initial_params` / `initial_state` /
`step` / `unroll`) and the reference's policy wrapper (SURVEY.md §2 Agent
row). Everything is a pure function of (params, rng, data) so actors can jit
`step` host-side and the learner can close `unroll` into its single train
step program.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from torched_impala_tpu.models.nets import ImpalaNet, NetOutput, NetState

Params = Any


class AgentOutput(NamedTuple):
    """One acting step: sampled action and the behaviour stats to store."""

    action: jax.Array  # [B] int32
    policy_logits: jax.Array  # [B, A] float32
    state: NetState


@dataclasses.dataclass(frozen=True)
class Agent:
    """Stateless policy API. Hashable/static so it can close into jits."""

    net: ImpalaNet

    def init_params(self, rng: jax.Array, example_obs: jax.Array) -> Params:
        """Initialize parameters from a single example observation `[...]`."""
        obs = example_obs[None]  # [1, ...]
        first = jnp.ones((1,), jnp.bool_)
        state = self.net.initial_state(1)
        return self.net.init(rng, obs, first, state)

    def initial_state(self, batch_size: int) -> NetState:
        return self.net.initial_state(batch_size)

    def step(
        self,
        params: Params,
        rng: jax.Array,
        obs: jax.Array,
        first: jax.Array,
        state: NetState,
    ) -> AgentOutput:
        """Sample actions for one timestep: obs `[B, ...]`, first `[B]`."""
        out, state = self.net.apply(params, obs, first, state, unroll=False)
        action = jax.random.categorical(rng, out.policy_logits, axis=-1)
        return AgentOutput(
            action=action.astype(jnp.int32),
            policy_logits=out.policy_logits,
            state=state,
        )

    def unroll(
        self,
        params: Params,
        obs: jax.Array,
        first: jax.Array,
        state: NetState,
    ) -> tuple[NetOutput, NetState]:
        """Learner re-forward: obs `[T, B, ...]`, first `[T, B]`, time-major."""
        return self.net.apply(params, obs, first, state, unroll=True)

    def unroll_with_stats(
        self,
        params: Params,
        obs: jax.Array,
        first: jax.Array,
        state: NetState,
    ) -> tuple[NetOutput, NetState, dict]:
        """`unroll`, and the scalars the net sows into its `intermediates`
        collection by name (the hybrid core's routing counts), each the
        last sown."""
        (out, state), sown = self.net.apply(
            params, obs, first, state, unroll=True, mutable=["intermediates"]
        )
        flat = jax.tree_util.tree_flatten_with_path(
            sown.get("intermediates", {}), is_leaf=lambda x: isinstance(x, tuple)
        )[0]
        stats = {path[-1].key: values[-1] for path, values in flat}
        return out, state, stats
