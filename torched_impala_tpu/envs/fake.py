"""Deterministic fake environments for tests and benches.

The env-factory interface must be pluggable because ALE/Procgen/DMLab are not
installed on every host (SURVEY.md Appendix B); these fakes provide the same
observation/action contracts for shape tests and throughput benches without
the emulators.
"""

from __future__ import annotations

import time

import numpy as np


class ScriptedEnv:
    """Gymnasium-API env with scripted episode lengths and rewards.

    Observation is a float32 vector encoding (step_in_episode, episode_idx);
    reward is +1 on every step; episodes last `episode_len` steps. Useful for
    asserting trajectory alignment (first flags, bootstrapping, returns).
    """

    def __init__(self, episode_len: int = 5, obs_size: int = 4):
        self._episode_len = episode_len
        self._obs_size = obs_size
        self._t = 0
        self._episode = 0

    @property
    def action_space_n(self) -> int:
        return 2

    def _obs(self) -> np.ndarray:
        obs = np.zeros((self._obs_size,), np.float32)
        obs[0] = self._t
        obs[1] = self._episode
        return obs

    def reset(self, seed=None):
        self._t = 0
        return self._obs(), {}

    def step(self, action):
        self._t += 1
        terminated = self._t >= self._episode_len
        if terminated:
            self._episode += 1
        return self._obs(), 1.0, terminated, False, {}


class FakeAtariEnv:
    """84x84x4 uint8 random-pixel env with geometric episode ends — stands in
    for ALE in throughput benches and pixel-pipeline tests."""

    def __init__(self, episode_len: int = 1000, num_actions: int = 6, seed=0):
        self._rng = np.random.default_rng(seed)
        self._episode_len = episode_len
        self._num_actions = num_actions
        self._t = 0

    @property
    def action_space_n(self) -> int:
        return self._num_actions

    def _obs(self) -> np.ndarray:
        return self._rng.integers(0, 256, size=(84, 84, 4), dtype=np.uint8)

    def reset(self, seed=None):
        self._t = 0
        return self._obs(), {}

    def step(self, action):
        self._t += 1
        terminated = self._t >= self._episode_len
        if terminated:
            self._t = 0
        reward = float(self._rng.uniform() < 0.05)
        return self._obs(), reward, terminated, False, {}


class FakeDiscreteEnv:
    """Random vector-obs env with configurable reward scale and task id.

    Stands in for one task of a multi-task suite (DMLab-30-style): each
    instance carries a `task_id` and a per-task `reward_scale`, so PopArt
    tests can exercise cross-task normalization without the real emulators.
    """

    def __init__(
        self,
        obs_shape=(8,),
        num_actions: int = 4,
        episode_len: int = 10,
        reward_scale: float = 1.0,
        task_id: int = 0,
        seed: int = 0,
    ):
        self._rng = np.random.default_rng(seed)
        self._obs_shape = tuple(obs_shape)
        self._num_actions = num_actions
        self._episode_len = episode_len
        self._reward_scale = reward_scale
        self.task_id = task_id
        self._t = 0

    @property
    def action_space_n(self) -> int:
        return self._num_actions

    def _obs(self) -> np.ndarray:
        return self._rng.normal(size=self._obs_shape).astype(np.float32)

    def reset(self, seed=None):
        self._t = 0
        return self._obs(), {}

    def step(self, action):
        self._t += 1
        terminated = self._t >= self._episode_len
        if terminated:
            self._t = 0
        reward = float(self._rng.normal()) * self._reward_scale
        return self._obs(), reward, terminated, False, {}


class SignalEnv:
    """Learnable pixel env: the rewarded action is encoded in the pixels.

    One quadrant of the frame is lit; the matching action (quadrant index)
    pays reward 1, everything else 0, and a fresh target is drawn every
    step. Random policy averages episode_len/num_actions per episode, a
    policy that reads the pixels approaches episode_len — so this gives the
    full conv pipeline an end-to-end *learning* signal (unlike the
    random-pixel fakes, which only exercise shapes/throughput).
    """

    def __init__(
        self,
        size: int = 24,
        num_actions: int = 4,
        episode_len: int = 20,
        seed: int = 0,
    ):
        assert num_actions <= 4, "targets are encoded as 2x2 quadrants"
        self._rng = np.random.default_rng(seed)
        self._size = size
        self._num_actions = num_actions
        self._episode_len = episode_len
        self._t = 0
        self._target = 0

    @property
    def action_space_n(self) -> int:
        return self._num_actions

    def _obs(self) -> np.ndarray:
        s = self._size
        h = s // 2
        obs = np.zeros((s, s, 1), np.uint8)
        r, c = divmod(self._target, 2)
        obs[r * h : (r + 1) * h, c * h : (c + 1) * h, :] = 255
        return obs

    def reset(self, seed=None):
        self._t = 0
        self._target = int(self._rng.integers(self._num_actions))
        return self._obs(), {}

    def step(self, action):
        reward = 1.0 if int(action) == self._target else 0.0
        self._t += 1
        self._target = int(self._rng.integers(self._num_actions))
        return self._obs(), reward, self._t >= self._episode_len, False, {}


class VectorSignalEnv:
    """Vector cousin of `SignalEnv`: the rewarded action IS the one-hot obs.

    Same contract — match the target for reward 1, fresh target every
    step, random policy averages episode_len/num_actions per episode —
    but the observation is a float32 one-hot vector, so an MLP torso
    learns it in a handful of SGD steps. This is the cheapest env with a
    genuine learning signal, which makes it the return-target probe for
    CPU-budget recovery scenarios (runtime/distributed.py's `signal`
    env: prove a resumed run still LEARNS, not merely that it steps).
    """

    def __init__(self, num_actions: int = 2, episode_len: int = 8, seed: int = 0):
        self._rng = np.random.default_rng(seed)
        self._num_actions = num_actions
        self._episode_len = episode_len
        self._t = 0
        self._target = 0

    @property
    def action_space_n(self) -> int:
        return self._num_actions

    def _obs(self) -> np.ndarray:
        obs = np.zeros((self._num_actions,), np.float32)
        obs[self._target] = 1.0
        return obs

    def reset(self, seed=None):
        self._t = 0
        self._target = int(self._rng.integers(self._num_actions))
        return self._obs(), {}

    def step(self, action):
        reward = 1.0 if int(action) == self._target else 0.0
        self._t += 1
        self._target = int(self._rng.integers(self._num_actions))
        return self._obs(), reward, self._t >= self._episode_len, False, {}


class TaskSignalEnv:
    """Learnable MULTI-task env: per-task action mapping and reward scale.

    Observation is `[one_hot(target, A); one_hot(task, num_tasks)]`
    (float32). The rewarded action is `(target + task_id) % A`, so a
    policy must condition on the task bits — the tasks are genuinely
    different, not one policy graded twice. Reward is `reward_scale` on a
    hit, 0 otherwise; with scales ~100x apart, an unnormalized baseline is
    dominated by the big-reward task's gradients — exactly the failure
    PopArt's per-task normalization exists to fix (DMLab-30 preset,
    BASELINE config 5), which the end-to-end test in tests/test_popart.py
    exploits.
    """

    def __init__(
        self,
        num_actions: int = 4,
        num_tasks: int = 2,
        task_id: int = 0,
        reward_scale: float = 1.0,
        episode_len: int = 16,
        seed: int = 0,
    ):
        self._rng = np.random.default_rng(seed)
        self._num_actions = num_actions
        self._num_tasks = num_tasks
        self.task_id = task_id
        self._reward_scale = reward_scale
        self._episode_len = episode_len
        self._t = 0
        self._target = 0

    @property
    def action_space_n(self) -> int:
        return self._num_actions

    def _obs(self) -> np.ndarray:
        obs = np.zeros((self._num_actions + self._num_tasks,), np.float32)
        obs[self._target] = 1.0
        obs[self._num_actions + self.task_id] = 1.0
        return obs

    def reset(self, seed=None):
        self._t = 0
        self._target = int(self._rng.integers(self._num_actions))
        return self._obs(), {}

    def step(self, action):
        hit = int(action) == (self._target + self.task_id) % self._num_actions
        reward = self._reward_scale if hit else 0.0
        self._t += 1
        self._target = int(self._rng.integers(self._num_actions))
        return self._obs(), reward, self._t >= self._episode_len, False, {}


class StragglerEnv:
    """Wraps another env and injects per-step delays.

    Every step sleeps `base_delay_s` (emulator-cost stand-in), plus
    `straggler_delay_s` with probability `straggler_prob` — the long-tail
    stall (GC pause, auto-reset, slow emulator frame) that lockstep env
    pools serialize onto every wave. tests/test_env_pool.py uses it to
    hold a worker inside a step long enough to kill it there.
    """

    def __init__(
        self,
        inner,
        base_delay_s: float = 0.0,
        straggler_delay_s: float = 0.0,
        straggler_prob: float = 0.0,
        seed: int = 0,
    ):
        self._inner = inner
        self._base_delay_s = base_delay_s
        self._straggler_delay_s = straggler_delay_s
        self._straggler_prob = straggler_prob
        self._rng = np.random.default_rng(seed)
        self.task_id = getattr(inner, "task_id", 0)

    @property
    def action_space_n(self) -> int:
        return self._inner.action_space_n

    def reset(self, seed=None):
        return self._inner.reset(seed=seed)

    def step(self, action):
        delay = self._base_delay_s
        if (
            self._straggler_delay_s > 0.0
            and self._rng.uniform() < self._straggler_prob
        ):
            delay += self._straggler_delay_s
        if delay > 0.0:
            time.sleep(delay)
        return self._inner.step(action)


class StragglerFactory:
    """Picklable env factory that wraps another factory's envs in
    `StragglerEnv` — delay injection for both thread and process actors."""

    def __init__(
        self,
        inner,
        base_delay_s: float = 0.0,
        straggler_delay_s: float = 0.0,
        straggler_prob: float = 0.0,
    ):
        self.inner = inner
        self.base_delay_s = base_delay_s
        self.straggler_delay_s = straggler_delay_s
        self.straggler_prob = straggler_prob

    def __call__(self, seed: int, env_index=None):
        from torched_impala_tpu.envs.factory import call_env_factory

        env = call_env_factory(self.inner, seed, env_index)
        return StragglerEnv(
            env,
            base_delay_s=self.base_delay_s,
            straggler_delay_s=self.straggler_delay_s,
            straggler_prob=self.straggler_prob,
            seed=seed + 17,
        )


class CrashingFactory:
    """Picklable env factory that wraps another factory's envs in
    `CrashingEnv` — chaos mode for both thread and process actors."""

    def __init__(self, inner, crash_after: int):
        self.inner = inner
        self.crash_after = crash_after

    def __call__(self, seed: int, env_index=None):

        from torched_impala_tpu.envs.factory import call_env_factory

        env = call_env_factory(self.inner, seed, env_index)
        return CrashingEnv(env, crash_after=self.crash_after)


class CrashingEnv:
    """Wraps another env and raises after `crash_after` total steps.

    Chaos-testing helper (SURVEY.md §6 failure detection): a fleet of these
    exercises the actor supervisor's restart path — each fresh instance
    crashes again after its own `crash_after` steps.
    """

    def __init__(self, inner, crash_after: int):
        self._inner = inner
        self._crash_after = crash_after
        self._steps = 0
        self.task_id = getattr(inner, "task_id", 0)

    @property
    def action_space_n(self) -> int:
        return self._inner.action_space_n

    def reset(self, seed=None):
        return self._inner.reset(seed=seed)

    def step(self, action):
        self._steps += 1
        if self._steps >= self._crash_after:
            raise RuntimeError(
                f"chaos: env crashed after {self._steps} steps"
            )
        return self._inner.step(action)
