"""Anakin: the fully on-device actor-learner for pure-JAX envs.

The host-actor runtime (runtime/loop.py) reproduces the reference's
process-actor architecture: Python envs on host CPUs feeding a device
learner through queues (SURVEY.md §2 Orchestration row). Anakin is the
TPU-native fast path that architecture cannot reach: when the env itself
is jax (envs/jax_envs.py), the ENTIRE iteration — E envs stepped in
lockstep, batched policy sampling, trajectory assembly, V-trace loss,
backward, optimizer update — is ONE jitted XLA program. No queues, no
host↔device transfers, no Python in the loop; the rollout is a
`lax.scan` over time with envs vmapped over the batch, exactly the
"Podracer/Anakin" pattern (Hessel et al., arXiv:2104.06272).

On-policy note: actors and learner share params inside one program, so
the behaviour distribution equals the target distribution and V-trace's
importance weights are identically 1 (it degrades to the lambda-return
estimator). The full off-policy machinery still runs — same
`impala_loss`, same nets — so switching a config between host actors and
Anakin changes throughput, not semantics.

Deliberate non-goal: PopArt / multi-task stays actor-runtime-only. The
only multi-task preset is DMLab-30, whose C++ emulator can never be a
pure-JAX env; threading per-slot task ids through the fused program
would exercise a loss path no on-device env family can feed.

Data parallelism: with a mesh, params/opt state are replicated and the
env batch is sharded over the `data` axis; per-env RNG is derived by
`fold_in(key, global env index)` so resharding never changes the random
stream. XLA inserts the gradient all-reduce over ICI (parallel/mesh.py).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Mapping, Optional

import jax
import jax.numpy as jnp
import optax

from torched_impala_tpu.models.agent import Agent
from torched_impala_tpu.ops.losses import ImpalaLossConfig, impala_loss
from torched_impala_tpu.runtime.learner import resolve_kernels
from torched_impala_tpu.parallel.mesh import (
    model_shardings,
    DATA_AXIS,
    replicated,
    state_sharding,
)


@dataclasses.dataclass(frozen=True)
class AnakinConfig:
    num_envs: int  # E: global env batch (divisible by the data axis)
    unroll_length: int  # T: steps per iteration
    loss: ImpalaLossConfig = ImpalaLossConfig()
    # Fuse N rollout+update iterations into ONE dispatched XLA program
    # (`lax.scan` over the whole iteration). Anakin needs no extra data to
    # do this — env state is part of the carry — so the only cost is log
    # scalars landing every N updates. Amortizes the fixed per-dispatch
    # host latency exactly like LearnerConfig.steps_per_dispatch.
    updates_per_dispatch: int = 1


class AnakinRunner:
    """Owns (params, opt_state, env carry) and one compiled train program.

    `step()` advances every env `unroll_length` steps and applies one SGD
    update; `frames_per_step` = T * E. All state lives on device between
    calls; only the log scalars ever reach the host (and only when read).
    """

    def __init__(
        self,
        *,
        agent: Agent,
        env,
        optimizer: optax.GradientTransformation,
        config: AnakinConfig,
        rng: jax.Array,
        mesh=None,
    ) -> None:
        self._env = env
        self._optimizer = optimizer
        self._mesh = mesh
        E = config.num_envs
        if mesh is not None and E % mesh.shape[DATA_AXIS]:
            raise ValueError(
                f"num_envs {E} not divisible by data axis "
                f"{mesh.shape[DATA_AXIS]}"
            )
        # Same device-aware kernel resolution as runtime.Learner.
        agent, loss, self.kernels = resolve_kernels(
            agent, config.loss, mesh
        )
        self._agent = agent
        self._config = dataclasses.replace(config, loss=loss)

        init_key, env_key, carry_key = jax.random.split(rng, 3)
        env_state = jax.vmap(env.reset)(
            jax.vmap(jax.random.fold_in, (None, 0))(env_key, jnp.arange(E))
        )
        example_obs = env.observe(jax.tree.map(lambda x: x[0], env_state))
        self.params = agent.init_params(init_key, example_obs)
        self.opt_state = optimizer.init(self.params)
        self._carry = (
            carry_key,
            env_state,
            jnp.ones((E,), jnp.bool_),
            agent.initial_state(E),
            jnp.zeros((E,), jnp.float32),  # running episode return
        )
        self.num_steps = 0
        self.num_frames = 0

        if config.updates_per_dispatch < 1:
            raise ValueError(
                f"updates_per_dispatch must be >= 1, got "
                f"{config.updates_per_dispatch}"
            )
        step_impl = (
            self._multi_step_impl
            if config.updates_per_dispatch > 1
            else self._step_impl
        )
        if mesh is None:
            self._step_fn = jax.jit(
                step_impl, donate_argnums=(0, 1, 2)
            )
        else:
            rep = replicated(mesh)
            ss = state_sharding(mesh)  # [E, ...] leaves over `data`
            carry_shardings = (
                rep,  # rng key: replicated; per-env keys use fold_in
                jax.tree.map(lambda _: ss, self._carry[1]),
                ss,
                jax.tree.map(lambda _: ss, self._carry[3]),
                ss,
            )
            # Tensor-parallel when the mesh has a model axis wider than 1
            # (same Megatron-column layout as the Learner); degenerates to
            # replicated otherwise.
            self._param_shardings = model_shardings(mesh, self.params)
            self._opt_shardings = model_shardings(mesh, self.opt_state)
            self.params = jax.device_put(self.params, self._param_shardings)
            self.opt_state = jax.device_put(
                self.opt_state, self._opt_shardings
            )
            self._carry = jax.tree.map(
                lambda x, s: jax.device_put(x, s),
                self._carry,
                carry_shardings,
                is_leaf=lambda x: isinstance(x, jax.Array),
            )
            self._step_fn = jax.jit(
                step_impl,
                donate_argnums=(0, 1, 2),
                in_shardings=(
                    self._param_shardings,
                    self._opt_shardings,
                    carry_shardings,
                ),
                out_shardings=(
                    self._param_shardings,
                    self._opt_shardings,
                    carry_shardings,
                    rep,
                ),
            )

    @property
    def frames_per_step(self) -> int:
        return self._config.num_envs * self._config.unroll_length

    # ---- checkpoint state ---------------------------------------------

    def get_state(self) -> dict:
        """Checkpointable state, same shape as Learner.get_state: params,
        opt state, frame/step counters, and the CURRENT rollout rng (so a
        restore continues the random stream instead of replaying it). Env
        states are NOT checkpointed — like the actor runtime, envs restart
        fresh on resume (episodes in flight are lost, counters are not).

        Host SNAPSHOTS, not live device arrays: the next step() donates
        params/opt_state, which would invalidate buffers an async orbax
        save is still reading (same hazard Learner.get_state documents)."""
        import numpy as np

        from torched_impala_tpu.runtime.types import host_snapshot
        from torched_impala_tpu.utils.checkpoint import pack_rng

        return {
            "params": host_snapshot(self.params),
            "opt_state": host_snapshot(self.opt_state),
            "num_frames": np.asarray(self.num_frames, np.int64),
            "num_steps": np.asarray(self.num_steps, np.int64),
            "rng": pack_rng(self._carry[0]),
        }

    def set_state(self, state: Mapping[str, Any]) -> None:
        from torched_impala_tpu.utils.checkpoint import unpack_rng

        if self._mesh is not None:
            # Same layouts as construction (TP leaves land back on their
            # shards; DP-only meshes replicate).
            self.params = jax.device_put(
                state["params"], self._param_shardings
            )
            self.opt_state = jax.device_put(
                state["opt_state"], self._opt_shardings
            )
            put = lambda x: jax.device_put(  # noqa: E731
                x, replicated(self._mesh)
            )
        else:
            put = lambda x: x  # noqa: E731
            self.params = state["params"]
            self.opt_state = state["opt_state"]
        self.num_frames = int(state["num_frames"])
        self.num_steps = int(state["num_steps"])
        self._carry = (put(unpack_rng(state["rng"])),) + self._carry[1:]

    # ---- one fused XLA program ----------------------------------------

    def _step_impl(self, params, opt_state, carry):
        agent, env, cfg = self._agent, self._env, self._config.loss
        T, E = self._config.unroll_length, self._config.num_envs
        env_ids = jnp.arange(E)
        start_state = carry[3]
        observe = jax.vmap(env.observe)

        def body(c, _):
            key, env_state, first, agent_state, ep_ret = c
            key, act_key, env_key, reset_key = jax.random.split(key, 4)
            obs = observe(env_state)
            out = agent.step(params, act_key, obs, first, agent_state)
            env_keys = jax.vmap(jax.random.fold_in, (None, 0))(
                env_key, env_ids
            )
            next_state, reward, done = jax.vmap(env.step)(
                env_state, out.action, env_keys
            )
            ep_ret = ep_ret + reward
            completed_ret = jnp.where(done, ep_ret, 0.0)
            ep_ret = jnp.where(done, 0.0, ep_ret)
            # Auto-reset finished envs; their next step carries first=True
            # so the nets' reset-core zeroes the recurrent carry.
            reset_keys = jax.vmap(jax.random.fold_in, (None, 0))(
                reset_key, env_ids
            )
            fresh_state = jax.vmap(env.reset)(reset_keys)

            def pick(new, old):
                d = done.reshape(done.shape + (1,) * (old.ndim - 1))
                return jnp.where(d, new, old)

            next_state = jax.tree.map(pick, fresh_state, next_state)
            ys = (
                obs,
                first,
                out.action,
                out.policy_logits,
                reward,
                1.0 - done.astype(jnp.float32),
                completed_ret,
            )
            return (key, next_state, done, out.state, ep_ret), ys

        carry, ys = jax.lax.scan(body, carry, None, length=T)
        obs_t, first_t, actions, behaviour_logits, rewards, cont, done_rets = ys
        use_step_bootstrap = agent.net._core_kind() != "transformer"
        if use_step_bootstrap:
            # Bootstrap value from ONE step-mode forward on the state
            # the rollout stopped in — instead of concatenating the
            # bootstrap row onto the rollout and unrolling over [T+1]:
            # at pixel shapes that concat materialized two extra passes
            # over the whole rollout (r5 trace: copy.12 +
            # pad_add_fusion.3 = 1.48 ms of a 12.8 ms step, 234 MB each
            # at E=128/T=64). No gradient flows through the bootstrap
            # (impala_loss stop-gradients it; the baseline loss
            # regresses values[:T] only) and for ff/LSTM cores
            # step-mode from the rollout's threaded post-scan state
            # (carry[3], computed under these same params — on-policy
            # within the program) reproduces the [T+1] unroll's last
            # value exactly. NOT true for the transformer core: its
            # step-mode KV cache evicts beyond `window`, while the
            # dense unroll attends to the full cache+T context — that
            # core keeps the concat path below.
            boot_out, _ = agent.net.apply(
                params, observe(carry[1]), carry[2], carry[3],
                unroll=False,
            )
            bootstrap_value = jax.lax.stop_gradient(
                jnp.squeeze(boot_out.values, -1)  # [E]
            )
        else:
            obs_full = jnp.concatenate(
                [obs_t, observe(carry[1])[None]], axis=0
            )
            first_full = jnp.concatenate(
                [first_t, carry[2][None]], axis=0
            )

        def loss_fn(p):
            if use_step_bootstrap:
                net_out, _ = agent.unroll(p, obs_t, first_t, start_state)
                values = jnp.squeeze(net_out.values, -1)  # [T, E]
                boot = bootstrap_value
            else:
                net_out, _ = agent.unroll(
                    p, obs_full, first_full, start_state
                )
                values_full = jnp.squeeze(net_out.values, -1)  # [T+1, E]
                values, boot = values_full[:-1], values_full[-1]
                net_out = net_out._replace(
                    policy_logits=net_out.policy_logits[:-1]
                )
            out = impala_loss(
                target_logits=net_out.policy_logits,
                behaviour_logits=behaviour_logits,
                values=values,
                bootstrap_value=boot,
                actions=actions,
                rewards=rewards,
                discounts=cfg.discount * cont,
                config=cfg,
            )
            return out.total, out.logs

        (_, logs), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = self._optimizer.update(
            grads, opt_state, params
        )
        params = optax.apply_updates(params, updates)
        logs = dict(logs)
        # Episode stats from the completed-episode events inside this
        # unroll. nan when the window finished no episodes (e.g. solved
        # CartPole at T << 500) — 0.0 would read as a legitimate return.
        finished = jnp.sum(1.0 - cont)
        logs["episodes_finished"] = finished
        logs["episode_return_mean"] = jnp.where(
            finished > 0,
            jnp.sum(done_rets) / jnp.maximum(finished, 1.0),
            jnp.nan,
        )
        return params, opt_state, carry, logs

    def _multi_step_impl(self, params, opt_state, carry):
        """N chained iterations in one XLA program (updates_per_dispatch).

        Scalar logs are the LAST iteration's, except the episode stats,
        which aggregate over all N windows (a per-window mean would throw
        away N-1 windows' completed episodes)."""
        N = self._config.updates_per_dispatch

        def body(c, _):
            p, o, cr = c
            p, o, cr, logs = self._step_impl(p, o, cr)
            return (p, o, cr), logs

        (params, opt_state, carry), logs_seq = jax.lax.scan(
            body, (params, opt_state, carry), None, length=N
        )
        logs = {k: v[-1] for k, v in logs_seq.items()}
        finished = jnp.sum(logs_seq["episodes_finished"])
        per_window_sums = jnp.where(
            logs_seq["episodes_finished"] > 0,
            logs_seq["episode_return_mean"]
            * logs_seq["episodes_finished"],
            0.0,
        )
        logs["episodes_finished"] = finished
        logs["episode_return_mean"] = jnp.where(
            finished > 0,
            jnp.sum(per_window_sums) / jnp.maximum(finished, 1.0),
            jnp.nan,
        )
        return params, opt_state, carry, logs

    # ---- host-side driver ---------------------------------------------

    def step(self) -> Mapping[str, Any]:
        """One dispatch: `updates_per_dispatch` iterations of (T steps of E
        envs + one SGD update), all on device."""
        self.params, self.opt_state, self._carry, logs = self._step_fn(
            self.params, self.opt_state, self._carry
        )
        N = self._config.updates_per_dispatch
        self.num_steps += N
        self.num_frames += self.frames_per_step * N
        return logs

    def run(
        self,
        num_iterations: int,
        *,
        log_every: int = 0,
        logger: Optional[Callable[[Mapping[str, Any]], None]] = None,
    ) -> Mapping[str, Any]:
        """Run `num_iterations` dispatches (each = updates_per_dispatch
        updates); returns the final logs dict with throughput.

        `log_every` counts UPDATES (num_steps), matching the CLI's
        --log-every semantics regardless of updates_per_dispatch."""
        from torched_impala_tpu.runtime.types import crossed_interval

        logs: Mapping[str, Any] = {}
        N = self._config.updates_per_dispatch
        start_frames = self.num_frames
        t0 = time.perf_counter()
        for i in range(num_iterations):
            logs = self.step()
            if (
                logger is not None
                and log_every
                and crossed_interval(self.num_steps, N, log_every)
            ):
                host_logs = {k: float(v) for k, v in logs.items()}
                host_logs["num_steps"] = self.num_steps
                host_logs["num_frames"] = self.num_frames
                logger(host_logs)
        jax.block_until_ready(logs)
        dt = time.perf_counter() - t0
        out = {k: float(v) for k, v in logs.items()}
        out["num_steps"] = self.num_steps
        out["num_frames"] = self.num_frames
        out["frames_per_sec"] = (
            (self.num_frames - start_frames) / dt if dt > 0 else 0.0
        )
        return out
