"""Pure-JAX environments: steppable inside jit, vmap, and shard_map.

The TPU-native counterpart to host emulators (SURVEY.md §2 Environments
row): where the reference pays a Python/emulator boundary per env step
(`gym.make` + C emulators on actor CPUs), a JaxEnv's dynamics are jax
functions, so the WHOLE actor loop — policy, env, trajectory assembly —
fuses into one XLA program with zero host↔device traffic (see
runtime/anakin.py). This is the fast path for envs with expressible
dynamics; Atari/Procgen/DMLab keep the host-actor path (envs/factory.py).

Protocol (functional, batch-free — batch via `jax.vmap`):
    reset(key)               -> state
    observe(state)           -> obs
    step(state, action, key) -> (state, reward, done)
Observations are DERIVED from state, never carried alongside it — that
keeps the training carry free of aliased buffers (obs==state.physics for
CartPole would be donated twice by the fused train program otherwise)
and the protocol minimal. `done` folds termination AND truncation (the
framework treats truncation as termination everywhere;
runtime/vector_actor.py does the same for host envs). Auto-reset is the
caller's job (runtime/anakin.py resets inside its scan) so a single
`step` stays a pure transition.

`JaxCartPole` reproduces gymnasium CartPole-v1 exactly (same constants,
Euler integrator, reward-on-every-step including the terminal one, 500-step
time limit, uniform(-0.05, 0.05) resets) — pinned by a step-for-step parity
test against gymnasium in tests/test_jax_envs.py.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class CartPoleState(NamedTuple):
    physics: jax.Array  # [4] float32: x, x_dot, theta, theta_dot
    t: jax.Array  # [] int32 steps taken this episode


@dataclasses.dataclass(frozen=True)
class JaxCartPole:
    """gymnasium CartPole-v1 dynamics as pure jax. Hashable/static."""

    gravity: float = 9.8
    masscart: float = 1.0
    masspole: float = 0.1
    length: float = 0.5  # half the pole's length
    force_mag: float = 10.0
    tau: float = 0.02
    x_threshold: float = 2.4
    theta_threshold: float = 12 * 2 * jnp.pi / 360
    max_steps: int = 500

    num_actions: int = 2
    obs_shape: tuple = (4,)
    obs_dtype = jnp.float32

    def reset(self, key: jax.Array) -> CartPoleState:
        physics = jax.random.uniform(
            key, (4,), jnp.float32, minval=-0.05, maxval=0.05
        )
        return CartPoleState(physics, jnp.zeros((), jnp.int32))

    def observe(self, state: CartPoleState) -> jax.Array:
        return state.physics

    def step(
        self, state: CartPoleState, action: jax.Array, key: jax.Array
    ) -> tuple[CartPoleState, jax.Array, jax.Array]:
        del key  # deterministic dynamics
        x, x_dot, theta, theta_dot = state.physics
        force = jnp.where(action == 1, self.force_mag, -self.force_mag)
        costheta = jnp.cos(theta)
        sintheta = jnp.sin(theta)
        total_mass = self.masspole + self.masscart
        polemass_length = self.masspole * self.length

        temp = (
            force + polemass_length * theta_dot**2 * sintheta
        ) / total_mass
        thetaacc = (self.gravity * sintheta - costheta * temp) / (
            self.length
            * (4.0 / 3.0 - self.masspole * costheta**2 / total_mass)
        )
        xacc = temp - polemass_length * thetaacc * costheta / total_mass

        # gymnasium's default Euler integrator, same update order.
        x = x + self.tau * x_dot
        x_dot = x_dot + self.tau * xacc
        theta = theta + self.tau * theta_dot
        theta_dot = theta_dot + self.tau * thetaacc

        physics = jnp.stack([x, x_dot, theta, theta_dot])
        t = state.t + 1
        terminated = (
            (jnp.abs(x) > self.x_threshold)
            | (jnp.abs(theta) > self.theta_threshold)
        )
        truncated = t >= self.max_steps
        done = terminated | truncated
        # CartPole-v1 pays +1 for every step taken, terminal included.
        reward = jnp.float32(1.0)
        return CartPoleState(physics, t), reward, done


class PixelSignalState(NamedTuple):
    target: jax.Array  # [] int32 quadrant whose action pays reward
    t: jax.Array  # [] int32 steps taken this episode


@dataclasses.dataclass(frozen=True)
class JaxPixelSignal:
    """Pure-JAX port of envs/fake.SignalEnv: a lit quadrant encodes the
    rewarded action, fresh target every step, fixed-length episodes. Gives
    the ON-DEVICE (Anakin) path a conv-pipeline learning signal at
    Atari-like pixel shapes — random policy averages episode_len/4 return,
    a policy that reads the pixels approaches episode_len."""

    size: int = 84
    channels: int = 4
    episode_len: int = 20

    num_actions: int = 4
    obs_dtype = jnp.uint8

    def __post_init__(self):
        # Targets are encoded as 2x2 quadrants (same constraint as the
        # numpy SignalEnv); more actions would render invisible targets.
        if self.num_actions > 4:
            raise ValueError(
                f"num_actions {self.num_actions} > 4: targets are encoded "
                "as 2x2 quadrants"
            )

    @property
    def obs_shape(self) -> tuple:
        return (self.size, self.size, self.channels)

    def reset(self, key: jax.Array) -> PixelSignalState:
        return PixelSignalState(
            target=jax.random.randint(key, (), 0, self.num_actions).astype(
                jnp.int32
            ),
            t=jnp.zeros((), jnp.int32),
        )

    def observe(self, state: PixelSignalState) -> jax.Array:
        h = self.size // 2
        r, c = state.target // 2, state.target % 2
        rows = jnp.arange(self.size)[:, None]
        cols = jnp.arange(self.size)[None, :]
        lit = (
            (rows >= r * h)
            & (rows < (r + 1) * h)
            & (cols >= c * h)
            & (cols < (c + 1) * h)
        )
        frame = jnp.where(lit, jnp.uint8(255), jnp.uint8(0))
        return jnp.broadcast_to(
            frame[:, :, None], (self.size, self.size, self.channels)
        )

    def step(
        self, state: PixelSignalState, action: jax.Array, key: jax.Array
    ) -> tuple[PixelSignalState, jax.Array, jax.Array]:
        reward = (action.astype(jnp.int32) == state.target).astype(
            jnp.float32
        )
        t = state.t + 1
        new_target = jax.random.randint(
            key, (), 0, self.num_actions
        ).astype(jnp.int32)
        return (
            PixelSignalState(target=new_target, t=t),
            reward,
            t >= self.episode_len,
        )


class DelayedCueState(NamedTuple):
    cue: jax.Array  # [] int32: the action that pays at the recall step
    t: jax.Array  # [] int32 steps taken this episode


@dataclasses.dataclass(frozen=True)
class JaxDelayedCue:
    """Memory probe: the cue is visible ONLY at t=0; the action taken at
    the recall step (`delay` steps later, marked by a flag) pays +1 iff it
    matches the cue. All intermediate observations carry no cue
    information, so a memoryless policy earns 1/num_actions in expectation
    at best, while a policy with temporal memory (transformer/LSTM core
    spanning the delay) earns 1.0 — the discriminative bar
    tests/test_memory_task.py trains both sides of (SURVEY.md §6
    long-context row; VERDICT r3 item 7).

    Observation `[num_actions + 2]` f32: one-hot cue (zeros after t=0),
    episode phase t/(delay+1), and the recall flag (1 at t == delay).
    Episodes last exactly delay + 1 steps."""

    num_actions: int = 4
    delay: int = 6

    obs_dtype = jnp.float32

    @property
    def obs_shape(self) -> tuple:
        return (self.num_actions + 2,)

    def reset(self, key: jax.Array) -> DelayedCueState:
        return DelayedCueState(
            cue=jax.random.randint(key, (), 0, self.num_actions).astype(
                jnp.int32
            ),
            t=jnp.zeros((), jnp.int32),
        )

    def observe(self, state: DelayedCueState) -> jax.Array:
        cue_onehot = jnp.where(
            state.t == 0,
            jax.nn.one_hot(state.cue, self.num_actions, dtype=jnp.float32),
            jnp.zeros((self.num_actions,), jnp.float32),
        )
        phase = state.t.astype(jnp.float32) / float(self.delay + 1)
        recall = (state.t == self.delay).astype(jnp.float32)
        return jnp.concatenate(
            [cue_onehot, phase[None], recall[None]]
        )

    def step(
        self, state: DelayedCueState, action: jax.Array, key: jax.Array
    ) -> tuple[DelayedCueState, jax.Array, jax.Array]:
        del key  # deterministic given the reset-time cue
        at_recall = state.t == self.delay
        reward = (
            at_recall & (action.astype(jnp.int32) == state.cue)
        ).astype(jnp.float32)
        t = state.t + 1
        return DelayedCueState(state.cue, t), reward, t > self.delay


class JaxEnvGymWrapper:
    """gymnasium-API adapter over any JaxEnv: host-side stepping for the
    eval runner and the host-actor path, so an Anakin-trained policy can be
    evaluated (and even trained) through the exact same runtime surface as
    emulator envs. State/key are committed to a host CPU device when one is
    available so per-step calls never dispatch to the accelerator the
    learner owns."""

    def __init__(self, env, seed: int = 0) -> None:
        self._env = env
        self._step = jax.jit(env.step)
        self._reset = jax.jit(env.reset)
        self._observe = jax.jit(env.observe)
        try:
            self._device = jax.devices("cpu")[0]
        except RuntimeError:
            self._device = None
        self._key = self._make_key(seed)
        self._state = None
        self.num_actions = env.num_actions

    def _make_key(self, seed):
        # Create ON the host device (default_device keeps the materializing
        # op off the accelerator) and then COMMIT it (device_put) —
        # an uncommitted array leaves per-call device selection to the
        # default backend, so every subsequent split/reset/step would still
        # dispatch to the TPU (see vector_actor.py on the cost). A
        # committed key makes the whole per-step chain follow it to CPU.
        if self._device is None:
            return jax.random.key(seed)
        with jax.default_device(self._device):
            key = jax.random.key(seed)
        return jax.device_put(key, self._device)

    def _split(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def reset(self, seed=None):
        if seed is not None:
            self._key = self._make_key(seed)
        self._state = self._reset(self._split())
        return np.asarray(self._observe(self._state)), {}

    def step(self, action):
        self._state, reward, done = self._step(
            self._state, np.asarray(action, np.int32), self._split()
        )
        # The framework folds truncation into termination everywhere, so
        # the gym 5-tuple reports done as `terminated`.
        return (
            np.asarray(self._observe(self._state)),
            float(reward),
            bool(done),
            False,
            {},
        )


class CatchState(NamedTuple):
    ball_x: jax.Array  # [] int32
    ball_y: jax.Array  # [] int32
    paddle_x: jax.Array  # [] int32


@dataclasses.dataclass(frozen=True)
class JaxCatch:
    """bsuite-style Catch (the analog's toy env, `run_catch.py:49`): a ball
    falls down a rows x cols board; move the paddle on the bottom row to
    catch it. Reward +-1 only on the terminal step. Episodes last exactly
    `rows - 1` steps, making return dynamics easy to reason about in tests.
    """

    rows: int = 10
    cols: int = 5

    num_actions: int = 3  # left, stay, right

    @property
    def obs_shape(self) -> tuple:
        return (self.rows * self.cols,)

    obs_dtype = jnp.float32

    def observe(self, state: CatchState) -> jax.Array:
        board = jnp.zeros((self.rows, self.cols), jnp.float32)
        board = board.at[state.ball_y, state.ball_x].set(1.0)
        board = board.at[self.rows - 1, state.paddle_x].set(1.0)
        return board.reshape(-1)

    def reset(self, key: jax.Array) -> CatchState:
        ball_x = jax.random.randint(key, (), 0, self.cols)
        return CatchState(
            ball_x=ball_x.astype(jnp.int32),
            ball_y=jnp.zeros((), jnp.int32),
            paddle_x=jnp.asarray(self.cols // 2, jnp.int32),
        )

    def step(
        self, state: CatchState, action: jax.Array, key: jax.Array
    ) -> tuple[CatchState, jax.Array, jax.Array]:
        del key
        dx = action.astype(jnp.int32) - 1  # {0,1,2} -> {-1,0,+1}
        paddle_x = jnp.clip(state.paddle_x + dx, 0, self.cols - 1)
        ball_y = state.ball_y + 1
        s = CatchState(state.ball_x, ball_y, paddle_x)
        done = ball_y >= self.rows - 1
        reward = jnp.where(
            done,
            jnp.where(paddle_x == state.ball_x, 1.0, -1.0),
            0.0,
        ).astype(jnp.float32)
        return s, reward, done
