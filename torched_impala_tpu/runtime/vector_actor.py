"""VectorActor: many envs, ONE batched policy dispatch per timestep.

The throughput-critical actor variant (SURVEY.md §8 hard part 1: "plan for
vectorized envs per actor process"). A plain `Actor` pays one jit dispatch
per env step; at reference scale (32-512 actors, BASELINE.json:7-10) that
dispatch overhead dominates. `VectorActor` steps E envs in lockstep and
batches their policy evaluation into a single `[E, ...]` jit call — host
Python only loops over envs for the (unavoidable) emulator `step()` calls.

Each unroll cycle emits E independent `Trajectory`s (one per env), so the
learner-side batcher and all staleness semantics are unchanged: a batch of
B unrolls may now come from B/E vector actors instead of B scalar ones.

The LSTM carry rides as one `[E, ...]` state; episode boundaries reset it
per-row inside the net via the `first` flags (models/nets.py reset-core
semantics), exactly as in the scalar actor.

Attached to an async (ready-set) `ProcessEnvPool` the actor drops the
lockstep barrier: each worker carries its own time index, inference runs
in WAVES over whichever ready fraction of workers has reported
(`pool.ready_fraction`, e.g. the first 75% of rows), their actions go back
through the shm action lane, and stragglers catch up on a later wave
instead of gating every wave. Waves are sized to a fixed worker count so
the jitted step sees a bounded set of batch shapes; per-env trajectories
stay time-contiguous because every row of a worker advances exactly once
per ack, into that worker's own `t` slot of the unroll buffers. The
trajectory/staleness surface is unchanged — one unroll cycle still emits
E trajectories against one param snapshot.
"""

from __future__ import annotations

import collections
import functools
import math
import threading
import time
from typing import Callable, List, Optional, Sequence

import jax
import numpy as np

from torched_impala_tpu.models.agent import Agent
from torched_impala_tpu.runtime.param_store import ParamStore
from torched_impala_tpu.runtime.traj_ring import TrajectoryRing
from torched_impala_tpu.runtime.types import (
    QueueClosed,
    Trajectory,
    host_snapshot,
)
from torched_impala_tpu.telemetry.registry import Registry, get_registry
from torched_impala_tpu.telemetry.tracing import (
    FlightRecorder,
    get_recorder,
    mint_lineage_id,
)


@functools.lru_cache(maxsize=None)
def _jitted_actor_step(agent: Agent):
    """One shared jitted step per Agent — N actors of the same agent reuse
    one traced/compiled program instead of compiling N identical ones."""

    def _step(params, key, obs, first, state):
        key, sub = jax.random.split(key)
        out = agent.step(params, sub, obs, first, state)
        return key, out

    return jax.jit(_step)


class VectorActor:
    """E envs stepped in lockstep with batched policy inference.

    Presents the same surface as `Actor` (`run`, `unroll_and_push`,
    `error`, `num_unrolls`) so the supervisor and train loop treat both
    uniformly.
    """

    def __init__(
        self,
        *,
        actor_id: int,
        envs: Sequence,
        agent: Agent,
        param_store: ParamStore,
        enqueue: Callable[[Trajectory], None],
        unroll_length: int,
        seed: int = 0,
        on_episode_return: Optional[Callable[[int, float, int], None]] = None,
        device: Optional[jax.Device] = None,
        tasks: Optional[Sequence[int]] = None,
        telemetry: Optional[Registry] = None,
        traj_ring: Optional[TrajectoryRing] = None,
        tracer: Optional[FlightRecorder] = None,
        chaos: Optional[Callable[[int], None]] = None,
    ) -> None:
        """`tasks` overrides the per-env task ids (default: each env's
        `task_id` attribute, else 0). `device` pins policy inference — see
        `Actor` for the committed-inputs mechanism.

        `traj_ring` switches the unroll to the zero-copy path: every
        timestep is written straight into a block of E columns of a
        shared learner batch slot (runtime/traj_ring.py) and `enqueue`
        is never called — the committed slot IS the batch. The env count
        must divide the ring's batch_size.

        `envs` is either a sequence of gymnasium-API envs (thread path) or
        a single batched-env object exposing
        `num_envs / task_ids / reset_all / step_all` (a
        `ProcessEnvPool` — env stepping then happens in worker processes
        while this actor does batched inference and unroll assembly)."""
        self._id = actor_id
        self._agent = agent
        self._param_store = param_store
        self._enqueue = enqueue
        self._unroll_length = unroll_length
        self._on_episode_return = on_episode_return
        self._step_fn = _jitted_actor_step(agent)
        self._device = device
        self._key = jax.random.key(seed)
        if device is not None:
            self._key = jax.device_put(self._key, device)
        self.error: Optional[BaseException] = None
        self.num_unrolls = 0  # counts emitted trajectories (E per cycle)

        # Telemetry (docs/OBSERVABILITY.md "actor" rows): wave latency is
        # one inference wave end-to-end (gather rows -> policy dispatch ->
        # actions written back / envs stepped); the heartbeat after every
        # wave feeds the stall watchdog. Metric objects are resolved ONCE
        # here so the wave loop never does a name lookup.
        reg = telemetry if telemetry is not None else get_registry()
        self._telemetry = reg
        self._m_wave_ms = reg.histogram("actor/wave_latency_ms")
        self._m_waves = reg.counter("actor/waves")
        self._m_unrolls = reg.counter("actor/unrolls")
        self._m_wave_size = reg.gauge("actor/wave_size")
        self._m_ready_frac = reg.gauge("actor/ready_fraction_achieved")
        self._m_grace_ms = reg.gauge("actor/grace_window_ms")
        self._m_unroll = reg.timer("actor/unroll")
        # Flight recorder + lineage (telemetry/tracing.py): one lineage
        # ID per unroll cycle, stamped with the acting param version and
        # threaded through the pool waves, the queue/ring, and the
        # learner — so a trace names exactly which unrolls each learner
        # batch consumed.
        self._tracer = tracer if tracer is not None else get_recorder()
        self._unroll_seq = 0
        self._lid = ""
        # Chaos seam (resilience/chaos.py): called with actor_id at each
        # unroll start; a raise_in_actor fault raises ChaosError here —
        # the error records on this actor and the supervisor restarts the
        # slot, exactly the real-crash path.
        self._chaos = chaos

        if hasattr(envs, "step_all"):  # batched env (ProcessEnvPool)
            self._pool = envs
            self._envs = []
            self._pool_async = getattr(envs, "mode", "lockstep") == "async"
            E = self._pool.num_envs
            self._tasks = (
                [int(t) for t in tasks]
                if tasks is not None
                else [int(t) for t in self._pool.task_ids]
            )
            self._obs = self._pool.reset_all()
        else:
            if not envs:
                raise ValueError("VectorActor needs at least one env")
            self._pool = None
            self._pool_async = False
            self._envs = list(envs)
            E = len(self._envs)
            self._tasks = (
                [int(t) for t in tasks]
                if tasks is not None
                else [int(getattr(e, "task_id", 0)) for e in self._envs]
            )
            obs0 = []
            for i, env in enumerate(self._envs):
                obs, _ = env.reset(seed=seed + i)
                obs0.append(np.asarray(obs))
            self._obs = np.stack(obs0)  # [E, ...]
        if len(self._tasks) != E:
            raise ValueError("tasks must have one entry per env")
        self._ring = traj_ring
        if traj_ring is not None:
            # Startup spec check (mirrors doctor's ring check): a
            # shape/dtype drift between env and ring buffers must fail
            # here, not as silently garbled batches mid-run.
            if self._obs.shape[1:] != traj_ring.obs_shape:
                raise ValueError(
                    f"traj_ring obs shape {traj_ring.obs_shape} != env "
                    f"obs shape {self._obs.shape[1:]}"
                )
            if self._obs.dtype != traj_ring.obs_dtype:
                raise ValueError(
                    f"traj_ring obs dtype {traj_ring.obs_dtype} != env "
                    f"obs dtype {self._obs.dtype}"
                )
            if unroll_length != traj_ring.unroll_length:
                raise ValueError(
                    f"traj_ring unroll_length {traj_ring.unroll_length} "
                    f"!= actor unroll_length {unroll_length}"
                )
            if E > traj_ring.batch_size or traj_ring.batch_size % E:
                raise ValueError(
                    f"actor env count {E} must divide traj_ring "
                    f"batch_size {traj_ring.batch_size}"
                )
        # Reused [E] scratch the pool's done lane folds into (lockstep
        # step_all out_dones=); rewards fold straight into the unroll
        # buffers, but `cont`/`first` are computed FROM dones, so dones
        # need one stable row outside the trajectory arrays.
        self._dones_scratch = np.zeros((E,), np.bool_)
        self._first = np.ones((E,), np.bool_)
        self._state = agent.initial_state(E)
        if device is not None:
            # Keep the recurrent carry on the inference device from step 0;
            # initial_state materializes on the default backend otherwise.
            self._state = jax.device_put(self._state, device)
        self._episode_return = np.zeros((E,), np.float64)
        self._episode_len = np.zeros((E,), np.int64)

    @property
    def num_envs(self) -> int:
        return self._pool.num_envs if self._pool is not None else len(
            self._envs
        )

    def _record_wave(
        self, t0: float, rows: int, ready_frac: float
    ) -> None:
        """One inference wave completed: latency histogram, wave-shape
        gauges, a flight-recorder span carrying the unroll's lineage ID,
        and the liveness heartbeat the stall watchdog reads."""
        now = time.monotonic()
        self._m_wave_ms.observe((now - t0) * 1e3)
        self._m_waves.inc()
        self._m_wave_size.set(rows)
        self._m_ready_frac.set(ready_frac)
        self._tracer.complete(
            "actor/wave",
            int(t0 * 1e9),
            int((now - t0) * 1e9),
            {"lid": self._lid, "rows": rows},
        )
        self._telemetry.heartbeat("actor")

    def _unroll_buffers(self, T: int, E: int):
        """(ring_block, obs, first, actions, rewards, cont, logits).

        Ring mode: the buffers are VIEWS of E columns of a shared learner
        batch slot — every write below lands directly in the batch the
        train step will consume (the zero-copy path; acquire blocks on
        ring backpressure and raises QueueClosed after learner stop).
        Queue mode: fresh per-unroll arrays that become the E emitted
        `Trajectory`s; logits allocate lazily (the width is only known
        after the first inference)."""
        if self._ring is not None:
            block = self._ring.acquire(E, lineage_id=self._lid)
            return (
                block,
                block.obs,
                block.first,
                block.actions,
                block.rewards,
                block.cont,
                block.behaviour_logits,
            )
        obs_buf = np.empty((T + 1, E, *self._obs.shape[1:]), self._obs.dtype)
        first_buf = np.empty((T + 1, E), np.bool_)
        actions = np.empty((T, E), np.int32)
        rewards = np.empty((T, E), np.float32)
        cont = np.empty((T, E), np.float32)
        return None, obs_buf, first_buf, actions, rewards, cont, None

    def _finish_unroll(
        self,
        block,
        obs_buf,
        first_buf,
        actions,
        rewards,
        cont,
        logits_buf,
        start_state,
        param_version: int,
    ) -> List[Trajectory]:
        """Commit a ring block (returns []) or slice the unroll buffers
        into E single-env `Trajectory`s (queue mode)."""
        if block is not None:
            block.task[:] = self._tasks
            if block.agent_state != ():
                jax.tree.map(
                    lambda dst, src: np.copyto(dst, np.asarray(src)),
                    block.agent_state,
                    start_state,
                )
            self._ring.commit(
                block, param_version, lineage_id=self._lid
            )
            return []
        return [
            Trajectory(
                obs=obs_buf[:, i],
                first=first_buf[:, i],
                actions=actions[:, i],
                behaviour_logits=logits_buf[:, i],
                rewards=rewards[:, i],
                cont=cont[:, i],
                agent_state=jax.tree.map(
                    lambda x: x[i : i + 1], start_state
                ),
                actor_id=self._id,
                param_version=param_version,
                task=self._tasks[i],
                lineage_id=self._lid,
            )
            for i in range(self.num_envs)
        ]

    def unroll(self, params, param_version: int = 0) -> List[Trajectory]:
        """Step all E envs for T steps; return E single-env trajectories
        (an empty list in trajectory-ring mode — the unroll was committed
        straight into a shared learner batch slot).

        Mints this cycle's lineage ID (`a<actor>u<seq>`) and records the
        whole cycle as an `actor/unroll` flight-recorder span stamped
        with the acting param version; every downstream stage that
        touches the unroll's bytes reuses the ID."""
        if self._chaos is not None:
            self._chaos(self._id)
        self._lid = lid = mint_lineage_id(self._id, self._unroll_seq)
        self._unroll_seq += 1
        if self._pool is not None:
            # The pool's parent-side trace events (submit->ack worker
            # steps) tag themselves with the driving unroll's lineage.
            self._pool.trace_lineage = lid
        t0_ns = time.monotonic_ns()
        try:
            return self._unroll_cycle(params, param_version)
        finally:
            self._tracer.complete(
                "actor/unroll",
                t0_ns,
                time.monotonic_ns() - t0_ns,
                {
                    "lid": lid,
                    "param_version": param_version,
                    "envs": self.num_envs,
                },
            )

    def _unroll_cycle(self, params, param_version: int) -> List[Trajectory]:
        if self._pool_async:
            return self._unroll_async(params, param_version)
        T, E = self._unroll_length, self.num_envs
        if self._device is not None:
            params = jax.device_put(params, self._device)
        (
            block,
            obs_buf,
            first_buf,
            actions,
            rewards,
            cont,
            logits_buf,
        ) = self._unroll_buffers(T, E)
        try:
            return self._unroll_lockstep_body(
                params, param_version, T, E, block, obs_buf, first_buf,
                actions, rewards, cont, logits_buf,
            )
        except BaseException:
            # A crashed unroll must not wedge the ring: the reserved
            # columns hold garbage, so surrender them (the slot recycles
            # instead of delivering; see TrajectoryRing.abort).
            if block is not None:
                self._ring.abort(block)
            raise

    def _unroll_lockstep_body(  # lint: hot-loop
        self, params, param_version, T, E, block, obs_buf, first_buf,
        actions, rewards, cont, logits_buf,
    ) -> List[Trajectory]:
        # host_snapshot, not bare np.asarray: the snapshot outlives
        # self._state (it rides the Trajectory through the learner queue),
        # and an np.asarray VIEW of a dropped jax CPU array can morph when
        # the allocator reuses the buffer (types.host_snapshot).
        start_state = host_snapshot(self._state)

        for t in range(T):
            wave_t0 = time.monotonic()
            obs_buf[t] = self._obs
            first_buf[t] = self._first
            # Pass obs/first as host numpy: jit placement then follows the
            # committed params/key (the pinned inference device). A bare
            # `jnp.asarray` here would materialize them on the DEFAULT
            # device first — on a TPU host that is an H2D put and a D2H
            # fetch per env step before execution even starts. Pass host
            # numpy into jits.
            self._key, out = self._step_fn(
                params,
                self._key,
                self._obs,
                self._first,
                self._state,
            )
            self._state = out.state
            acts = np.asarray(out.action)
            if logits_buf is None:
                logits_buf = np.empty(
                    (T, E, out.policy_logits.shape[-1]), np.float32
                )
            logits_buf[t] = np.asarray(out.policy_logits)

            if self._pool is not None:
                # Env stepping happens in the worker processes; the pool
                # auto-resets finished envs and reports completed episodes.
                # The reward lane folds STRAIGHT into the unroll buffer
                # row (out_rewards= — in ring mode that row IS the
                # learner's stacking buffer) and the done lane into the
                # reused scratch, skipping one copy per step each.
                actions[t] = acts
                next_obs, _, dones, events = self._pool.step_all(
                    acts,
                    out_rewards=rewards[t],
                    out_dones=self._dones_scratch,
                )
                cont[t] = np.where(dones, 0.0, 1.0)
                self._obs = next_obs
                self._first = dones.copy()
                if self._on_episode_return is not None:
                    for _, ret, length in events:
                        self._on_episode_return(self._id, ret, length)
                self._record_wave(wave_t0, E, 1.0)
                continue

            # The host-side env loop: the only per-env Python work left.
            for i, env in enumerate(self._envs):
                next_obs, reward, terminated, truncated, _ = env.step(
                    int(acts[i])
                )
                # Truncation is treated as termination (standard for these
                # frameworks; CartPole's 500-step cap etc.).
                done = bool(terminated or truncated)
                actions[t, i] = acts[i]
                rewards[t, i] = float(reward)
                cont[t, i] = 0.0 if done else 1.0
                self._episode_return[i] += float(reward)
                self._episode_len[i] += 1
                if done:
                    if self._on_episode_return is not None:
                        self._on_episode_return(
                            self._id,
                            float(self._episode_return[i]),
                            int(self._episode_len[i]),
                        )
                    self._episode_return[i] = 0.0
                    self._episode_len[i] = 0
                    next_obs, _ = env.reset()
                self._obs[i] = np.asarray(next_obs)
                self._first[i] = done
            self._record_wave(wave_t0, E, 1.0)

        obs_buf[T] = self._obs
        first_buf[T] = self._first

        return self._finish_unroll(
            block, obs_buf, first_buf, actions, rewards, cont,
            logits_buf, start_state, param_version,
        )

    def _unroll_async(self, params, param_version: int) -> List[Trajectory]:
        """Ready-set unroll against an async `ProcessEnvPool`.

        Every worker carries its own time index `t_w` into the shared
        `[T+1, E]` unroll buffers; a wave gathers the first `wave_k` ready
        workers (FIFO by ack arrival — stragglers are served as soon as
        they report, so no worker starves), runs ONE batched inference
        over their rows, and writes their actions back through the pool's
        shm action lane. The unroll ends when every worker reaches T; the
        only synchronization with stragglers is that (short) tail, not
        every timestep. Emitted trajectories are bit-compatible with the
        lockstep path per env row: obs/action/reward/first/cont all share
        one per-worker time index, so rows stay time-contiguous and
        `first[t+1]` still mirrors `done[t]`."""
        T, E = self._unroll_length, self.num_envs
        pool = self._pool
        W, Ew = pool.num_workers, pool.envs_per_worker
        wave_k = max(1, math.ceil(pool.ready_fraction * W))
        if self._device is not None:
            params = jax.device_put(params, self._device)
        (
            block,
            obs_buf,
            first_buf,
            actions,
            rewards,
            cont,
            logits_buf,
        ) = self._unroll_buffers(T, E)
        try:
            return self._unroll_async_body(
                params, param_version, T, E, W, Ew, wave_k, block,
                obs_buf, first_buf, actions, rewards, cont, logits_buf,
            )
        except BaseException:
            if block is not None:
                self._ring.abort(block)
            raise

    def _unroll_async_body(  # lint: hot-loop
        self, params, param_version, T, E, W, Ew, wave_k, block,
        obs_buf, first_buf, actions, rewards, cont, logits_buf,
    ) -> List[Trajectory]:
        pool = self._pool
        start_state = host_snapshot(self._state)
        obs_buf[0] = self._obs
        first_buf[0] = self._first

        def slc(w: int) -> slice:
            return slice(w * Ew, (w + 1) * Ew)

        def advance(w: int, step_rewards, dones, events, timed=True) -> None:
            # Record worker w's completed step t_w[w] and move it to
            # t_w[w] + 1 (its rows' next obs/first are now current).
            nonlocal completed, ewma_step
            if timed:
                dur = time.monotonic() - submit_t[w]
                if ewma_step is None:
                    ewma_step = dur
                elif dur < 2.0 * ewma_step:
                    # Track the NORMAL step time only: straggler stalls
                    # must not inflate the grace window that exists to
                    # absorb sub-stall arrival jitter (a stall-inflated
                    # grace would re-serialize the pool on its stragglers).
                    ewma_step = 0.8 * ewma_step + 0.2 * dur
            t = int(t_w[w])
            sl = slc(w)
            rewards[t, sl] = step_rewards
            cont[t, sl] = np.where(dones, 0.0, 1.0)
            obs = pool.read_obs(w)
            obs_buf[t + 1, sl] = obs
            first_buf[t + 1, sl] = dones
            self._obs[sl] = obs
            self._first[sl] = dones
            t_w[w] = t + 1
            if self._on_episode_return is not None:
                for _, ret, length in events:
                    self._on_episode_return(self._id, ret, length)
            if t + 1 >= T:
                completed += 1
            else:
                actionable.append(w)

        t_w = np.zeros((W,), np.int64)
        submit_t = np.zeros((W,), np.float64)
        ewma_step = None  # EWMA of submit->ack worker step seconds
        # No step is ever in flight between unrolls (the previous cycle's
        # tail drained every ack), so all workers start actionable at t=0.
        actionable = collections.deque(range(W))
        completed = 0
        while completed < W:
            # The ready-set gate: wait for acks only until the FIRST
            # `wave_k` workers (or every straggler left below T) are
            # ready — never for the whole pool.
            target = min(wave_k, W - completed)
            while len(actionable) < target:
                # copy=False: rewards/dones arrive as shm-lane views and
                # advance() copies them once, straight into the unroll
                # (ring) buffers — the lane fold skipping the per-ack
                # intermediate copy. Views stay valid until the worker's
                # next submit, which only happens after advance() ran.
                for w, rw, dn, events, _ok in pool.wait_any(copy=False):
                    advance(w, rw, dn, events)
                target = min(wave_k, W - completed)
            # Grace window: once the ready fraction is met, wait one short
            # self-tuned beat (a fraction of the EWMA worker step time)
            # for the nearly-done rest. A pool with NO stragglers then
            # coalesces into ONE full-batch call per timestep — lockstep-
            # parity throughput instead of fragmenting into wave_k pieces
            # — while a genuine straggler costs its wave only the grace,
            # never its full stall. wait_any with an explicit timeout is a
            # bounded poll (no repair sweep), so an expired grace just
            # launches the partial wave.
            if ewma_step is not None:
                deadline = time.monotonic() + 0.25 * ewma_step
                while completed + len(actionable) < W:
                    budget = deadline - time.monotonic()
                    if budget <= 0:
                        break
                    acks = pool.wait_any(timeout=budget, copy=False)
                    if not acks:
                        break
                    for w, rw, dn, events, _ok in acks:
                        advance(w, rw, dn, events)
            else:
                for w, rw, dn, events, _ok in pool.wait_any(
                    timeout=0, copy=False
                ):
                    advance(w, rw, dn, events)
            remaining = W - completed
            if remaining == 0:
                break
            # Full wave when EVERY remaining worker is ready (one extra
            # compiled shape); otherwise exactly wave_k so the jitted step
            # sees a bounded shape set while stragglers catch up.
            ready_now = len(actionable)
            take = (
                ready_now
                if ready_now == remaining
                else min(wave_k, ready_now)
            )
            wave_t0 = time.monotonic()
            if ewma_step is not None:
                self._m_grace_ms.set(0.25 * ewma_step * 1e3)
            wave = [actionable.popleft() for _ in range(take)]
            rows = np.concatenate([np.arange(w * Ew, (w + 1) * Ew)
                                   for w in wave])
            wave_state = jax.tree.map(lambda x: x[rows], self._state)
            self._key, out = self._step_fn(
                params,
                self._key,
                self._obs[rows],
                self._first[rows],
                wave_state,
            )
            self._state = jax.tree.map(
                lambda full, new: full.at[rows].set(new),
                self._state,
                out.state,
            )
            acts = np.asarray(out.action)
            if logits_buf is None:
                logits_buf = np.empty(
                    (T, E, out.policy_logits.shape[-1]), np.float32
                )
            wave_logits = np.asarray(out.policy_logits)
            for j, w in enumerate(wave):
                t, sl = int(t_w[w]), slc(w)
                seg = slice(j * Ew, (j + 1) * Ew)
                actions[t, sl] = acts[seg]
                logits_buf[t, sl] = wave_logits[seg]
                submit_t[w] = time.monotonic()
                if not pool.submit(w, acts[seg]):
                    # Dead worker, repaired by the pool: its envs were
                    # reset, so the submitted action resolves as a crash
                    # episode boundary instead of a stalled wave.
                    advance(
                        w,
                        np.zeros((Ew,), np.float32),
                        np.ones((Ew,), np.bool_),
                        [],
                        timed=False,
                    )
            # ready_fraction_achieved: how much of the still-running pool
            # this wave actually served (1.0 = coalesced full batch — the
            # grace window doing its job; ~ready_fraction = partial waves
            # with stragglers catching up elsewhere).
            self._record_wave(wave_t0, len(rows), take / remaining)

        return self._finish_unroll(
            block, obs_buf, first_buf, actions, rewards, cont,
            logits_buf, start_state, param_version,
        )

    def unroll_and_push(self) -> None:
        version, params = self._param_store.get()
        with self._m_unroll.time():
            trajs = self.unroll(params, version)
        if self._ring is not None:
            # The unroll was committed into the ring in place — no
            # Trajectory objects, no enqueue. Same accounting surface:
            # one cycle still produced E unrolls.
            self.num_unrolls += self.num_envs
            self._m_unrolls.inc(self.num_envs)
            return
        for traj in trajs:
            self._enqueue(traj)
            self.num_unrolls += 1
            self._m_unrolls.inc()

    def run(
        self,
        stop_event: threading.Event,
        max_unrolls: Optional[int] = None,
    ) -> None:
        """Actor loop; same contract as `Actor.run` (supervisor-compatible)."""
        try:
            while not stop_event.is_set():
                if max_unrolls is not None and self.num_unrolls >= max_unrolls:
                    return
                try:
                    self.unroll_and_push()
                except QueueClosed:
                    return
        except BaseException as e:  # noqa: BLE001 — watchdog needs any error
            self.error = e
            raise
