"""Learner tests: single-process integration (one push → one step) and the
full threaded CartPole smoke showing learning (SURVEY.md §5 items 4).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torched_impala_tpu.envs import ScriptedEnv, make_cartpole
from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
from torched_impala_tpu.ops import ImpalaLossConfig
from torched_impala_tpu.runtime import (
    Actor,
    Learner,
    LearnerConfig,
    stack_trajectories,
    train,
)


def _agent(obs_size=4, num_actions=2, use_lstm=False):
    return Agent(
        ImpalaNet(
            num_actions=num_actions,
            torso=MLPTorso(hidden_sizes=(16,)),
            use_lstm=use_lstm,
            lstm_size=8,
        )
    )


@pytest.mark.parametrize("use_lstm", [False, True])
def test_integration_one_push_one_step(use_lstm):
    """The minimum end-to-end slice: real env, real agent, one unroll pushed,
    one learner SGD step taken (shape of `learner_test.py:29-56`)."""
    T, B = 6, 2
    agent = _agent(use_lstm=use_lstm)
    learner = Learner(
        agent=agent,
        optimizer=optax.sgd(1e-2),
        config=LearnerConfig(batch_size=B, unroll_length=T),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
    )
    _, params = learner.param_store.get()
    actor = Actor(
        actor_id=0,
        env=ScriptedEnv(episode_len=4),
        agent=agent,
        param_store=learner.param_store,
        enqueue=learner.enqueue,
        unroll_length=T,
        seed=0,
    )
    for _ in range(B):
        actor.unroll_and_push()
    learner.start()
    logs = learner.step_once(timeout=30)
    # One step stays in flight: its version is out a call later, or when
    # the loop is drained (stop() does).
    assert learner.param_store.version == 0
    learner.stop()
    assert learner.param_store.version == T * B

    assert np.isfinite(logs["total_loss"])
    assert logs["num_frames"] == T * B
    # Acted with version-0 params, trained after counting this batch's
    # frames: lag is exactly one batch.
    assert logs["param_lag_frames"] == T * B
    # Params actually moved.
    _, new_params = learner.param_store.get()
    diffs = jax.tree.map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).sum()),
        params,
        new_params,
    )
    assert sum(jax.tree.leaves(diffs)) > 0


def test_stack_trajectories_shapes():
    agent = _agent(use_lstm=True)
    params = agent.init_params(jax.random.key(0), jnp.zeros((4,)))
    from torched_impala_tpu.runtime import ParamStore

    store = ParamStore()
    store.publish(7, params)
    actor = Actor(
        actor_id=0,
        env=ScriptedEnv(),
        agent=agent,
        param_store=store,
        enqueue=lambda t: None,
        unroll_length=5,
        seed=0,
    )
    trajs = [actor.unroll(params, 7) for _ in range(3)]
    batch = stack_trajectories(trajs)
    assert batch.obs.shape == (6, 3, 4)
    assert batch.behaviour_logits.shape == (5, 3, 2)
    assert batch.agent_state[0].shape == (3, 8)
    assert batch.param_version == 7


def test_backpressure_and_queue_closed():
    """Bounded queue blocks producers; stop() releases them with QueueClosed."""
    from torched_impala_tpu.runtime import QueueClosed

    agent = _agent()
    learner = Learner(
        agent=agent,
        optimizer=optax.sgd(1e-2),
        config=LearnerConfig(batch_size=1, unroll_length=2, queue_capacity=1),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
    )
    _, params = learner.param_store.get()
    actor = Actor(
        actor_id=0,
        env=ScriptedEnv(),
        agent=agent,
        param_store=learner.param_store,
        enqueue=learner.enqueue,
        unroll_length=2,
        seed=0,
    )
    actor.unroll_and_push()  # fills the queue (capacity 1)
    blocked = threading.Event()
    raised = threading.Event()

    def push_again():
        blocked.set()
        try:
            actor.unroll_and_push()
        except QueueClosed:
            raised.set()

    t = threading.Thread(target=push_again, daemon=True)
    t.start()
    assert blocked.wait(5)
    learner.stop()
    t.join(timeout=5)
    assert raised.is_set()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_watchdog_raises_when_all_actors_die():
    """SURVEY.md §6 failure detection: a job whose producers all crashed must
    fail loudly, not hang. (Actor threads re-raise by design — hence the
    unhandled-thread-exception filter.)"""

    class ExplodingEnv:
        def reset(self, seed=None):
            return np.zeros(4, np.float32), {}

        def step(self, action):
            raise RuntimeError("env exploded")

    agent = _agent()
    with pytest.raises(RuntimeError, match="all actor threads are dead"):
        train(
            agent=agent,
            env_factory=lambda seed: ExplodingEnv(),
            example_obs=np.zeros((4,), np.float32),
            num_actors=2,
            learner_config=LearnerConfig(batch_size=2, unroll_length=4),
            optimizer=optax.sgd(1e-2),
            total_steps=5,
            seed=0,
            # 1 restart proves the recover-then-give-up path; the default
            # 10-restart budget spends ~2min in exponential backoff.
            max_actor_restarts=1,
        )


def test_cartpole_smoke_learns():
    """CartPole-v1, MLP, threaded actors, jit learner: return must rise
    (BASELINE config 1). Thresholds are loose — this is a smoke test, not a
    convergence benchmark."""
    agent = _agent(obs_size=4, num_actions=2)
    result = train(
        agent=agent,
        env_factory=lambda seed: make_cartpole(seed)[0],
        example_obs=np.zeros((4,), np.float32),
        num_actors=2,
        learner_config=LearnerConfig(
            batch_size=4,
            unroll_length=20,
            loss=ImpalaLossConfig(
                discount=0.99, entropy_coef=0.01, reduction="mean"
            ),
        ),
        optimizer=optax.rmsprop(5e-3, decay=0.99, eps=1e-7),
        total_steps=250,
        seed=0,
    )
    returns = [r for _, r, _ in result.episode_returns]
    assert len(returns) >= 20, "too few episodes completed"
    early = np.mean(returns[: len(returns) // 4])
    late = np.mean(returns[-len(returns) // 4 :])
    assert late > early * 1.3, (
        f"no learning signal: early={early:.1f} late={late:.1f}"
    )
    assert result.num_frames == 250 * 4 * 20


def test_pixel_policy_learns_from_signal_env():
    """The FULL conv pipeline learns end-to-end: SignalEnv encodes the
    rewarded action in the pixels, so rising return proves obs transport,
    conv torso, V-trace, and the optimizer are wired correctly at pixel
    shapes (not just CartPole's 4-vector)."""
    import flax.linen as nn

    from torched_impala_tpu.envs.fake import SignalEnv

    class TinyConv(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = x.astype(jnp.float32) / 255.0
            x = nn.relu(nn.Conv(8, (5, 5), strides=(3, 3))(x))
            x = x.reshape(x.shape[0], -1)
            return nn.relu(nn.Dense(64)(x))

    agent = Agent(ImpalaNet(num_actions=4, torso=TinyConv()))
    result = train(
        agent=agent,
        env_factory=lambda seed, idx=None: SignalEnv(seed=seed),
        example_obs=np.zeros((24, 24, 1), np.uint8),
        num_actors=2,
        learner_config=LearnerConfig(batch_size=4, unroll_length=10),
        optimizer=optax.rmsprop(2e-3, decay=0.99, eps=1e-7),
        total_steps=250,
        actor_device=None,
        seed=0,
    )
    returns = [r for _, r, _ in result.episode_returns]
    assert len(returns) >= 100, "too few episodes completed"
    late = np.mean(returns[-50:])
    # Random policy averages 5.0 (20 steps x 1/4); reading the pixels
    # should roughly double that well within 250 learner steps.
    assert late > 9.0, f"conv pipeline failed to learn: late={late:.1f}"


def test_batcher_thread_failure_surfaces():
    """A dead batcher thread must fail the learner loudly, not hang it
    (code-review finding: watchdog only monitored actor threads)."""
    T, B = 3, 2
    agent = _agent()
    learner = Learner(
        agent=agent,
        optimizer=optax.sgd(1e-2),
        config=LearnerConfig(batch_size=B, unroll_length=T),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
    )
    actor = Actor(
        actor_id=0,
        env=ScriptedEnv(episode_len=4),
        agent=agent,
        param_store=learner.param_store,
        enqueue=learner.enqueue,
        unroll_length=T,
        seed=0,
    )
    good = actor.unroll(learner.param_store.get()[1])
    bad = good._replace(obs=good.obs[:, :2])  # mismatched obs shape
    learner.enqueue(good)
    learner.enqueue(bad)
    learner.start()
    deadline = 30.0
    with pytest.raises(RuntimeError, match="batcher thread died"):
        import time

        t0 = time.monotonic()
        while time.monotonic() - t0 < deadline:
            try:
                learner.step_once(timeout=0.5)
            except Exception as e:
                if isinstance(e, RuntimeError):
                    raise
        raise AssertionError("batcher failure never surfaced")
    learner.stop()


def test_vtrace_auto_resolves_to_devices_not_default_backend():
    """'auto' must resolve against the learner's actual compute devices at
    construction (a CPU mesh in a TPU-default process would otherwise lower
    the compiled Pallas kernel for CPU and fail)."""
    from torched_impala_tpu.parallel import make_mesh

    agent = _agent()
    for mesh in (None, make_mesh(num_data=2, devices=jax.devices("cpu")[:2])):
        learner = Learner(
            agent=agent,
            optimizer=optax.sgd(1e-3),
            config=LearnerConfig(
                batch_size=2,
                unroll_length=3,
                loss=ImpalaLossConfig(),  # vtrace_implementation='auto'
            ),
            example_obs=np.zeros((4,), np.float32),
            rng=jax.random.key(0),
            mesh=mesh,
        )
        # Test env forces the CPU platform, so 'auto' must become 'scan'.
        assert learner._config.loss.vtrace_implementation == "scan"


def test_multihost_actor_seeds_offset_by_process_index(monkeypatch):
    """Every controller runs train() with the same --seed; actor seeds and
    env indices must fold in jax.process_index() or all hosts produce
    identical trajectories (review finding: global batch held n copies)."""
    import optax

    from torched_impala_tpu.runtime.loop import train

    seen = {}

    def recording_factory(seed, env_index=None):
        seen[seed] = env_index
        return ScriptedEnv(episode_len=3)

    def run_as_host(idx):
        seen.clear()
        monkeypatch.setattr(jax, "process_index", lambda: idx)
        train(
            agent=_agent(),
            env_factory=recording_factory,
            example_obs=np.zeros((4,), np.float32),
            num_actors=2,
            envs_per_actor=2,
            learner_config=LearnerConfig(batch_size=2, unroll_length=3),
            optimizer=optax.sgd(1e-3),
            total_steps=1,
            seed=7,
        )
        return dict(seen)

    host0, host1 = run_as_host(0), run_as_host(1)
    # Disjoint seed sets and disjoint global env indices across hosts.
    assert not (set(host0) & set(host1)), (host0, host1)
    assert not (set(host0.values()) & set(host1.values())), (host0, host1)


# ---- fused multi-step dispatch (steps_per_dispatch > 1) ----------------


def _push_unrolls(learner, agent, n, T, episode_len=4, seed=0):
    actor = Actor(
        actor_id=0,
        env=ScriptedEnv(episode_len=episode_len),
        agent=agent,
        param_store=learner.param_store,
        enqueue=learner.enqueue,
        unroll_length=T,
        seed=seed,
    )
    for _ in range(n):
        actor.unroll_and_push()


@pytest.mark.parametrize("use_lstm", [False, True])
def test_fused_dispatch_matches_sequential_steps(use_lstm):
    """One K=2 fused dispatch == two unfused step_once calls on the same
    trajectories: same params, same frame/step accounting."""
    T, B, K = 5, 2, 2
    results = {}
    for k in (1, K):
        agent = _agent(use_lstm=use_lstm)
        learner = Learner(
            agent=agent,
            optimizer=optax.sgd(1e-2),
            config=LearnerConfig(
                batch_size=B,
                unroll_length=T,
                steps_per_dispatch=k,
                queue_capacity=K * B,
            ),
            example_obs=np.zeros((4,), np.float32),
            rng=jax.random.key(0),
        )
        # Identical trajectory stream for both learners: same init params
        # (same rng), same actor seed, same scripted env.
        _push_unrolls(learner, agent, K * B, T)
        learner.start()
        for _ in range(K // k):
            logs = learner.step_once(timeout=60)
        learner.stop()
        results[k] = (
            jax.tree.map(np.asarray, learner.params),
            learner.num_frames,
            learner.num_steps,
            float(logs["total_loss"]),
        )

    p1, frames1, steps1, loss1 = results[1]
    pk, framesk, stepsk, lossk = results[K]
    assert frames1 == framesk == K * B * T
    assert steps1 == stepsk == K
    # The fused program's LAST step saw the same (params, batch) as the
    # unfused path's second step.
    np.testing.assert_allclose(loss1, lossk, rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        p1,
        pk,
    )


def test_fused_fallback_chunked_matches_full_dispatch():
    """The learner_fused K8 layout-crash fix (ISSUE 10 satellite): when a
    K>4 superbatch is refused at the jit boundary the learner falls back
    to chunked K<=4 dispatch through the same scan body. The chunked
    path must be numerically identical to the one-shot K=8 dispatch
    (state threads through the chunks exactly as through one scan),
    keep the frame/step accounting, and count on perf/fused_fallbacks."""
    T, B, K = 5, 2, 8
    results = {}
    for forced in (False, True):
        agent = _agent()
        learner = Learner(
            agent=agent,
            optimizer=optax.sgd(1e-2),
            config=LearnerConfig(
                batch_size=B,
                unroll_length=T,
                steps_per_dispatch=K,
                queue_capacity=K * B,
            ),
            example_obs=np.zeros((4,), np.float32),
            rng=jax.random.key(0),
        )
        _push_unrolls(learner, agent, K * B, T)
        if forced:
            # What the jit-boundary ValueError handler sets on a real
            # layout refusal (exercised end to end on TPU backends
            # only; the chunked execution path itself is backend-free).
            learner._fused_fallback_k = 4
        before = learner._m_fused_fallbacks.value
        learner.start()
        logs = learner.step_once(timeout=60)
        learner.stop()
        assert learner.num_frames == K * B * T
        assert learner.num_steps == K
        assert learner._m_fused_fallbacks.value == before + (
            1 if forced else 0
        )
        results[forced] = (
            jax.tree.map(np.asarray, learner.params),
            float(logs["total_loss"]),
        )
    np.testing.assert_allclose(
        results[False][1], results[True][1], rtol=1e-5, atol=1e-6
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        results[False][0],
        results[True][0],
    )


def test_fused_dispatch_sharded():
    """Fused K=3 dispatch over the 8-device data mesh: superbatch leading
    axis unsharded, batch axis sharded, params replicated throughout."""
    from torched_impala_tpu.parallel import make_mesh

    cpu_mesh = make_mesh(num_data=8)
    T, B, K = 4, 8, 3
    agent = _agent()
    learner = Learner(
        agent=agent,
        optimizer=optax.rmsprop(1e-3, decay=0.99, eps=1e-7),
        config=LearnerConfig(
            batch_size=B,
            unroll_length=T,
            steps_per_dispatch=K,
            queue_capacity=K * B,
        ),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
        mesh=cpu_mesh,
    )
    _push_unrolls(learner, agent, K * B, T)
    learner.start()
    logs = learner.step_once(timeout=120)
    learner.stop()
    assert np.isfinite(float(logs["total_loss"]))
    assert learner.num_steps == K
    assert learner.num_frames == K * B * T
    for leaf in jax.tree.leaves(learner.params):
        assert leaf.sharding.is_fully_replicated


def test_fused_dispatch_interval_crossing():
    """publish/log intervals fire on crossings even when K doesn't divide
    them (interval=3, K=2 must log on the dispatch that crosses step 3)."""
    T, B, K = 3, 1, 2
    seen = []
    agent = _agent()
    learner = Learner(
        agent=agent,
        optimizer=optax.sgd(1e-2),
        config=LearnerConfig(
            batch_size=B,
            unroll_length=T,
            steps_per_dispatch=K,
            log_interval=3,
            publish_interval=3,
            queue_capacity=3 * K * B,
        ),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
        logger=lambda logs: seen.append(logs["num_steps"]),
    )
    _push_unrolls(learner, agent, 3 * K * B, T)
    learner.start()
    for _ in range(3):  # num_steps: 2, 4, 6
        learner.step_once(timeout=60)
    learner.stop()
    # Crossings of 3 and 6 happen at num_steps 4 and 6.
    assert seen == [4, 6]
    # Params published on the same crossings: version is frames at step 6.
    version, _ = learner.param_store.get()
    assert version == learner.num_frames


def test_superbatch_inplace_matches_reference():
    """The batcher's in-place superbatch assembly (stack_trajectories with
    out= views) is bit-identical to the stack_superbatch oracle."""
    from torched_impala_tpu.runtime import stack_superbatch

    T, B, K = 4, 3, 2
    agent = _agent(use_lstm=True)
    learner = Learner(
        agent=agent,
        optimizer=optax.sgd(1e-2),
        config=LearnerConfig(
            batch_size=B,
            unroll_length=T,
            steps_per_dispatch=K,
            queue_capacity=K * B,
        ),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
    )
    _, params = learner.param_store.get()
    actor = Actor(
        actor_id=0,
        env=ScriptedEnv(episode_len=3),
        agent=agent,
        param_store=learner.param_store,
        enqueue=learner.enqueue,
        unroll_length=T,
        seed=0,
    )
    trajs = []
    for _ in range(K * B):
        actor.unroll_and_push()
    # Keep handles to the exact queued trajectories for the oracle.
    trajs = list(learner._traj_q.queue)

    sb = learner._assemble_superbatch(K)
    ref = stack_superbatch(
        [stack_trajectories(trajs[k * B : (k + 1) * B]) for k in range(K)]
    )
    jax.tree.map(
        np.testing.assert_array_equal,
        (sb.obs, sb.first, sb.actions, sb.behaviour_logits, sb.rewards,
         sb.cont, sb.task, sb.agent_state),
        (ref.obs, ref.first, ref.actions, ref.behaviour_logits, ref.rewards,
         ref.cont, ref.task, ref.agent_state),
    )
    assert sb.param_version == ref.param_version


class TestStackBufferReuse:
    """The ring-reuse stacking path (LearnerConfig.stack_buffer_reuse):
    batches assembled into reused preallocated buffers must be
    content-identical to fresh stacking, INCLUDING after the ring wraps
    (the regime where a bug would silently serve a previous batch's
    data), for both the plain-batch and superbatch assembly paths."""

    RELAID = (0, 3)  # the batch's frames and behaviour logits

    @classmethod
    def _relay(cls, learner, monkeypatch, K):
        """Have the step take the frames and the behaviour logits in a
        layout of their own (the last two axes swapped), as a TPU's AUTO
        layouts do, so their put goes in two stages; and keep every
        transfer a copy, as on a TPU. The CPU backend's `device_put`
        aliases a 64-byte-aligned host buffer instead of copying it,
        which would make the ring give itself up (the alignment lottery
        below), so the ring's buffers start 16 bytes past such a line."""
        from jax.experimental.layout import Format, Layout
        from jax.sharding import SingleDeviceSharding

        from torched_impala_tpu.runtime import learner as learner_mod

        nd = 3 if K == 1 else 4
        swapped = Format(
            Layout(major_to_minor=tuple(range(nd - 2)) + (nd - 1, nd - 2)),
            SingleDeviceSharding(jax.devices()[0]),
        )
        auto = Format(Layout.AUTO)
        fmts = [auto] * 11  # params, opt, popart, then the batch's 8
        for i in cls.RELAID:
            fmts[3 + i] = swapped
        learner._auto_jit = jax.jit(
            learner._train_step.__wrapped__,
            donate_argnums=(0, 1, 2),
            in_shardings=tuple(fmts),
            out_shardings=auto,
        )

        def off_line(x):
            if not isinstance(x, np.ndarray):
                return x
            raw = np.empty(x.nbytes + 80, np.uint8)
            start = (16 - raw.ctypes.data) % 64
            return raw[start : start + x.nbytes].view(x.dtype).reshape(
                x.shape
            )

        alloc = learner_mod.alloc_stack_buffers
        monkeypatch.setattr(
            learner_mod,
            "alloc_stack_buffers",
            lambda *a: jax.tree.map(off_line, alloc(*a)),
        )

    def _drain_batches(self, K, reuse, n_batches, T=4, B=3,
                       use_lstm=True, relay=None, auto_layouts=True,
                       telemetry=None):
        learner = Learner(
            agent=_agent(use_lstm=use_lstm),
            optimizer=optax.sgd(1e-2),
            config=LearnerConfig(
                batch_size=B,
                unroll_length=T,
                steps_per_dispatch=K,
                queue_capacity=n_batches * K * B,
                stack_buffer_reuse=reuse,
                auto_layouts=auto_layouts,
            ),
            example_obs=np.zeros((4,), np.float32),
            rng=jax.random.key(0),
            telemetry=telemetry,
        )
        if relay is not None:
            self._relay(learner, relay, K)
        _push_unrolls(
            learner, learner._agent, n_batches * K * B, T
        )
        trajs = list(learner._traj_q.queue)
        learner.start()
        drained = []
        try:
            for _ in range(n_batches):
                arrays, _, _ = learner._batch_q.get(timeout=60)
                # Copy to host IMMEDIATELY, and FORCE the copy:
                # np.asarray of a jax CPU array can be a zero-copy VIEW
                # of the device buffer, which dangles once jax frees the
                # buffer and the allocator recycles it for a later batch
                # (observed: "copies" silently morphing into batch i+4's
                # data). The real consumer — the jitted train step —
                # reads device arrays it holds references to, so this is
                # purely a host-inspection concern.
                drained.append(
                    jax.tree.map(lambda x: np.array(x, copy=True), arrays)
                )
        finally:
            learner.stop()
        return trajs, drained, learner

    @pytest.mark.parametrize(
        "K, relaid",
        [
            pytest.param(1, False, id="1"),
            pytest.param(2, False, id="2"),
            pytest.param(1, True, id="1-relaid"),
            pytest.param(2, True, id="2-relaid"),
        ],
    )
    def test_matches_fresh_stacking_through_ring_wrap(
        self, K, relaid, monkeypatch
    ):
        # 6 batches > the double-buffer ring: it wraps and every buffer
        # is restacked at least twice. Relaid: the frames and logits
        # take the two-stage put, so a slot is restacked as soon as its
        # transfer is done, before its batch's re-layout has to be.
        T, B, n = 4, 3, 6
        trajs, drained, learner = self._drain_batches(
            K, "on", n, T=T, B=B, relay=monkeypatch if relaid else None
        )
        if relaid:  # every transfer a copy: the ring must hold
            assert learner._stack_reuse
            assert learner._ring_idx >= 3 * len(learner._ring)
        if learner._stack_reuse:
            assert any(b is not None for b in learner._ring), (
                "ring never engaged"
            )
            assert learner._ring_idx > len(learner._ring), (
                "ring never wrapped"
            )
        # else: the one-time aliasing safety net surrendered the ring
        # (alignment lottery on the CPU backend) — the parity checks below
        # still validate the fresh-allocation fallback.
        for i, arrays in enumerate(drained):
            group = trajs[i * K * B : (i + 1) * K * B]
            if K == 1:
                ref = stack_trajectories(group)
            else:
                from torched_impala_tpu.runtime import stack_superbatch

                ref = stack_superbatch(
                    [
                        stack_trajectories(group[k * B : (k + 1) * B])
                        for k in range(K)
                    ]
                )
            obs, first, actions, logits, rewards, cont, task, state = (
                arrays
            )
            np.testing.assert_array_equal(obs, ref.obs, err_msg=f"batch {i}")
            np.testing.assert_array_equal(actions, ref.actions)
            np.testing.assert_array_equal(task, ref.task)
            jax.tree.map(
                np.testing.assert_array_equal, state, ref.agent_state
            )

    def test_slot_waits_for_the_transfer_not_the_relayout(
        self, monkeypatch
    ):
        """A slot's pending set is what its host buffers were copied into:
        for a leaf the step takes in a layout of its own, the transfer in
        the default layout, not the re-laid-out array the step receives
        (a device program queued behind the steps in flight); for every
        other leaf, the pushed array itself."""
        from torched_impala_tpu.telemetry import Registry

        T, B = 4, 3
        reg = Registry()
        learner = Learner(
            agent=_agent(use_lstm=True),
            optimizer=optax.sgd(1e-2),
            config=LearnerConfig(
                batch_size=B, unroll_length=T, stack_buffer_reuse="on",
            ),
            example_obs=np.zeros((4,), np.float32),
            rng=jax.random.key(0),
            telemetry=reg,
        )
        self._relay(learner, monkeypatch, 1)
        _push_unrolls(learner, learner._agent, B, T)
        learner.start()
        try:
            # One batch's unrolls only: the batcher records the slot's
            # transfers before it pushes, then waits for more unrolls.
            arrays, _, _ = learner._batch_q.get(timeout=60)
            pending = learner._ring_pending[0]
        finally:
            learner.stop()
        assert learner._stack_reuse
        pushed = jax.tree.leaves(arrays)
        fmts = jax.tree.leaves(learner._batch_formats)
        assert len(pending) == len(pushed) == len(fmts)
        relaid = [i in self.RELAID for i in range(len(pushed))]
        assert [p is not t for p, t in zip(pushed, pending)] == relaid
        for p, t, fmt in zip(pushed, pending, fmts):
            # (a leaf the step does not read has no layout: a plain put)
            assert fmt.layout is None or p.format == fmt
            assert t.format.layout.major_to_minor == tuple(range(t.ndim))
            np.testing.assert_array_equal(np.asarray(p), np.asarray(t))
        assert pushed[0].format.layout.major_to_minor == (0, 2, 1)
        assert reg.counter("learner/relayout_puts").value == 2

    def test_a_relayout_into_another_layout_raises_at_the_put(
        self, monkeypatch
    ):
        """Where the put into the step's format hands back another layout
        (seen on a TPU with the frames), the put raises and names the
        leaf and both layouts, instead of queueing a batch that the
        compiled step refuses later."""
        from jax.experimental.layout import Format, Layout
        from jax.sharding import SingleDeviceSharding

        from torched_impala_tpu.runtime import learner as learner_mod

        x = np.arange(24, dtype=np.uint8).reshape(2, 3, 4)
        # the format as a compiled step states it: XLA's layout, tiling
        # included
        fmt = jax.device_put(
            x,
            Format(
                Layout(major_to_minor=(0, 2, 1)),
                SingleDeviceSharding(jax.devices()[0]),
            ),
        ).format
        placed, transfer = learner_mod._put_in_two(x, fmt)
        assert placed is not transfer and placed.format == fmt
        put = jax.device_put
        monkeypatch.setattr(
            learner_mod.jax,
            "device_put",
            lambda a, *d: a if d and isinstance(d[0], Format) else put(a, *d),
        )
        with pytest.raises(RuntimeError, match=r"uint8\[2, 3, 4\].*came back"):
            learner_mod._put_in_two(x, fmt)

    @pytest.mark.parametrize(
        "layouts, per_batch", [("relaid", 2), ("auto", 0), ("off", 0)]
    )
    def test_relayout_puts_counts_the_two_stage_leaves(
        self, layouts, per_batch, monkeypatch
    ):
        """`learner/relayout_puts` counts, per batch, the leaves re-laid
        after their transfer: the two the step takes in layouts of their
        own; none where AUTO chose the default layout for every leaf (the
        CPU's choice), whose transfer is what the step takes; none with
        `auto_layouts=False`."""
        from torched_impala_tpu.telemetry import Registry

        reg, n = Registry(), 3
        _, drained, _ = self._drain_batches(
            1, "on", n,
            relay=monkeypatch if layouts == "relaid" else None,
            auto_layouts=layouts != "off",
            telemetry=reg,
        )
        assert len(drained) == n
        assert reg.counter("learner/relayout_puts").value == per_batch * n

    def test_off_mode_never_allocates_ring(self):
        _, drained, learner = self._drain_batches(1, "off", 3)
        assert len(drained) == 3
        assert all(b is None for b in learner._ring)

    def test_auto_mode_resolves_via_probe(self):
        learner = Learner(
            agent=_agent(),
            optimizer=optax.sgd(1e-2),
            config=LearnerConfig(batch_size=2, unroll_length=3),
            example_obs=np.zeros((4,), np.float32),
            rng=jax.random.key(0),
        )
        assert isinstance(learner._stack_reuse_enabled(), bool)

    def test_rejects_bad_mode(self):
        with pytest.raises(ValueError, match="stack_buffer_reuse"):
            Learner(
                agent=_agent(),
                optimizer=optax.sgd(1e-2),
                config=LearnerConfig(
                    batch_size=2, unroll_length=3,
                    stack_buffer_reuse="maybe",
                ),
                example_obs=np.zeros((4,), np.float32),
                rng=jax.random.key(0),
            )


class TestStackReuseAutoProbe:
    """Both branches of the "auto" aliasing probe in
    `Learner._stack_reuse_enabled` (previously only exercised by
    whichever way THIS backend's alignment lottery happened to fall):
    an aliasing-capable device_put must disable reuse, a copying one
    must enable it, and the probe's verdict must be cached."""

    def _learner(self):
        return Learner(
            agent=_agent(),
            optimizer=optax.sgd(1e-2),
            config=LearnerConfig(batch_size=2, unroll_length=3),
            example_obs=np.zeros((4,), np.float32),
            rng=jax.random.key(0),
        )

    @staticmethod
    def _trajs(learner, n=2, T=3):
        _push_unrolls(learner, learner._agent, n, T)
        return list(learner._traj_q.queue)

    def test_aliasing_backend_disables_reuse(self, monkeypatch):
        learner = self._learner()
        monkeypatch.setattr(np, "shares_memory", lambda *a, **k: True)
        assert learner._stack_reuse_enabled() is False
        # Consequence: the batcher stacks into fresh allocations — no
        # ring buffer is ever handed out or allocated.
        trajs = self._trajs(learner)
        assert learner._stack_out(trajs) is None
        assert all(b is None for b in learner._ring)
        # The verdict is cached: a later (different) probe result must
        # not flip it mid-run under queued batches.
        monkeypatch.setattr(np, "shares_memory", lambda *a, **k: False)
        assert learner._stack_reuse_enabled() is False

    def test_copying_backend_enables_reuse(self, monkeypatch):
        learner = self._learner()
        monkeypatch.setattr(np, "shares_memory", lambda *a, **k: False)
        assert learner._stack_reuse_enabled() is True
        trajs = self._trajs(learner)
        out = learner._stack_out(trajs)
        assert out is not None  # ring buffer allocated and handed out
        batch = stack_trajectories(trajs, out=out)
        ref = stack_trajectories(trajs)
        np.testing.assert_array_equal(batch.obs, ref.obs)
        monkeypatch.setattr(np, "shares_memory", lambda *a, **k: True)
        assert learner._stack_reuse_enabled() is True  # cached

    def test_probe_runs_at_most_once(self, monkeypatch):
        learner = self._learner()
        calls = []

        def counting_shares_memory(*a, **k):
            calls.append(1)
            return False

        monkeypatch.setattr(np, "shares_memory", counting_shares_memory)
        learner._stack_reuse_enabled()
        n = len(calls)
        assert n >= 1  # the probe actually consulted the backend
        learner._stack_reuse_enabled()
        assert len(calls) == n


def test_fused_dispatch_never_overshoots_budget():
    """run(max_steps) with K>1 stops at the largest multiple of K <=
    max_steps and warns about the unspent remainder."""
    import warnings as _warnings

    T, B, K = 3, 1, 2
    agent = _agent()
    learner = Learner(
        agent=agent,
        optimizer=optax.sgd(1e-2),
        config=LearnerConfig(
            batch_size=B,
            unroll_length=T,
            steps_per_dispatch=K,
            queue_capacity=4 * K * B,
        ),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
    )
    _push_unrolls(learner, agent, 4 * K * B, T)
    with _warnings.catch_warnings(record=True) as caught:
        _warnings.simplefilter("always")
        learner.run(max_steps=3)
    assert learner.num_steps == 2  # largest multiple of K=2 within 3
    assert any("not a multiple" in str(w.message) for w in caught)


class TestGradAccum:
    """grad_accum=G must produce the FULL-batch update exactly: same
    params after one step as G=1 on the same trajectories, for both loss
    reductions and with recurrent state; composes with fused dispatch
    and the DP mesh; PopArt is rejected."""

    @staticmethod
    def _collect(agent, params, T, B):
        from torched_impala_tpu.runtime import ParamStore

        store = ParamStore()
        store.publish(0, params)
        actor = Actor(
            actor_id=0,
            env=ScriptedEnv(episode_len=4),
            agent=agent,
            param_store=store,
            enqueue=lambda t: None,
            unroll_length=T,
            seed=0,
        )
        return [actor.unroll(params) for _ in range(B)]

    def _step(self, agent, trajs, T, B, G, reduction="sum", mesh=None,
              steps_per_dispatch=1):
        learner = Learner(
            agent=agent,
            optimizer=optax.sgd(1e-2),
            config=LearnerConfig(
                batch_size=B,
                unroll_length=T,
                loss=ImpalaLossConfig(reduction=reduction),
                grad_accum=G,
                steps_per_dispatch=steps_per_dispatch,
            ),
            example_obs=np.zeros((4,), np.float32),
            rng=jax.random.key(0),
            mesh=mesh,
        )
        for t in trajs * steps_per_dispatch:
            learner.enqueue(t)
        learner.start()
        logs = learner.step_once(timeout=120)
        learner.stop()
        return learner, logs

    @pytest.mark.parametrize("reduction", ["sum", "mean"])
    @pytest.mark.parametrize("use_lstm", [False, True])
    def test_matches_full_batch(self, reduction, use_lstm):
        T, B = 5, 8
        agent = _agent(use_lstm=use_lstm)
        params0 = agent.init_params(jax.random.key(0), jnp.zeros((4,)))
        trajs = self._collect(agent, params0, T, B)
        full, logs_full = self._step(agent, list(trajs), T, B, 1, reduction)
        acc, logs_acc = self._step(agent, list(trajs), T, B, 4, reduction)
        np.testing.assert_allclose(
            float(logs_full["total_loss"]), float(logs_acc["total_loss"]),
            rtol=1e-5,
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            full.params,
            acc.params,
        )

    def test_composes_with_fused_dispatch_and_mesh(self):
        from torched_impala_tpu.parallel import make_mesh

        T, B = 4, 8
        agent = _agent()
        params0 = agent.init_params(jax.random.key(0), jnp.zeros((4,)))
        trajs = self._collect(agent, params0, T, B)
        plain, _ = self._step(agent, list(trajs), T, B, 1)
        combo, _ = self._step(
            agent, list(trajs), T, B, 2,
            mesh=make_mesh(num_data=4), steps_per_dispatch=1,
        )
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
            ),
            plain.params,
            combo.params,
        )
        # Fused x accum NUMERICS: K=2 fused steps each accumulating G=2
        # microbatches must equal two sequential G=2 steps on the same two
        # batches (FIFO order makes the batch split identical) — catches
        # e.g. the inner scan accumulating against stale params.
        two_batches = self._collect(agent, params0, T, 2 * B)
        seq = Learner(
            agent=agent,
            optimizer=optax.sgd(1e-2),
            config=LearnerConfig(
                batch_size=B, unroll_length=T, grad_accum=2
            ),
            example_obs=np.zeros((4,), np.float32),
            rng=jax.random.key(0),
        )
        for t in two_batches:
            seq.enqueue(t)
        seq.start()
        seq.step_once(timeout=120)
        seq.step_once(timeout=120)
        seq.stop()
        fused, _ = self._step(
            agent, list(two_batches), T, B, 2, steps_per_dispatch=2
        )
        assert fused.num_steps == 2
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6
            ),
            seq.params,
            fused.params,
        )

    def test_validation(self):
        from torched_impala_tpu.ops.popart import PopArtConfig

        agent = _agent()
        with pytest.raises(ValueError, match="not divisible by"):
            Learner(
                agent=agent,
                optimizer=optax.sgd(1e-2),
                config=LearnerConfig(batch_size=6, unroll_length=4,
                                     grad_accum=4),
                example_obs=np.zeros((4,), np.float32),
                rng=jax.random.key(0),
            )
        # PopArt x grad_accum is SUPPORTED (batch-end statistics update;
        # parity pinned in tests/test_popart.py::TestGradAccumPopArt) —
        # construction must succeed.
        Learner(
            agent=Agent(
                ImpalaNet(
                    num_actions=2,
                    torso=MLPTorso(hidden_sizes=(16,)),
                    num_values=2,
                )
            ),
            optimizer=optax.sgd(1e-2),
            config=LearnerConfig(
                batch_size=8, unroll_length=4, grad_accum=2,
                popart=PopArtConfig(num_values=2),
            ),
            example_obs=np.zeros((4,), np.float32),
            rng=jax.random.key(0),
        )
