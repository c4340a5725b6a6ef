"""Trajectory-ring tests (ISSUE 3 tentpole): the zero-copy actor->learner
data path must be semantically invisible — batches bit-identical to the
queue path on fixed seeds — while recycling slots safely (free-list +
generation counters, commit-after-crash protection, backpressure).
"""

import threading
import time

import jax
import numpy as np
import optax
import pytest

from torched_impala_tpu.envs.fake import ScriptedEnv
from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
from torched_impala_tpu.runtime import (
    Learner,
    LearnerConfig,
    QueueClosed,
    TrajectoryRing,
    VectorActor,
    train,
)


def _agent(use_lstm=False):
    return Agent(
        ImpalaNet(
            num_actions=2,
            torso=MLPTorso(hidden_sizes=(16,)),
            use_lstm=use_lstm,
            lstm_size=8,
        )
    )


def _ring(T=3, B=4, obs_shape=(4,), num_actions=2, num_slots=2, state=()):
    return TrajectoryRing(
        num_slots=num_slots,
        unroll_length=T,
        batch_size=B,
        example_obs=np.zeros(obs_shape, np.float32),
        num_actions=num_actions,
        agent_state_example=state,
    )


class TestRingMechanics:
    def test_slot_buffers_mirror_alloc_stack_shapes(self):
        ring = _ring(T=5, B=3, obs_shape=(4, 2), num_actions=6)
        buf = ring._slots[0].buffers
        assert buf.obs.shape == (6, 3, 4, 2)
        assert buf.first.shape == (6, 3) and buf.first.dtype == np.bool_
        assert buf.actions.shape == (5, 3) and buf.actions.dtype == np.int32
        assert buf.behaviour_logits.shape == (5, 3, 6)
        assert buf.rewards.shape == (5, 3)
        assert buf.task.shape == (3,)
        assert ring.validate_env_spec(
            np.zeros((4, 2), np.float32), 6
        ) == []

    def test_validate_env_spec_catches_mismatches(self):
        ring = _ring(obs_shape=(4,), num_actions=2)
        problems = ring.validate_env_spec(np.zeros((5,), np.float32), 3)
        assert any("obs slot shape" in p for p in problems)
        assert any("logits slot shape" in p for p in problems)
        problems = ring.validate_env_spec(np.zeros((4,), np.uint8), 2)
        assert any("obs slot dtype" in p for p in problems)

    def test_acquire_commit_pop_release_roundtrip(self):
        ring = _ring(T=2, B=4)
        a = ring.acquire(2)
        b = ring.acquire(2)
        assert a.slot == b.slot and a.cols == slice(0, 2)
        assert b.cols == slice(2, 4)
        a.rewards[...] = 1.0
        b.rewards[...] = 2.0
        ring.commit(a, param_version=10)
        assert ring.pop_ready(timeout=0.05) is None  # half committed
        ring.commit(b, param_version=7)
        view = ring.pop_ready(timeout=1.0)
        assert view is not None
        # Batch version = min over columns (stack_trajectories parity).
        assert view.param_version == 7
        np.testing.assert_array_equal(view.arrays[4][:, :2], 1.0)
        np.testing.assert_array_equal(view.arrays[4][:, 2:], 2.0)
        ring.release(view.slot)
        # The freed slot is reusable and its generation advanced.
        c = ring.acquire(4)
        assert c.gen >= 1 or c.slot != view.slot

    def test_block_must_divide_batch(self):
        ring = _ring(B=4)
        with pytest.raises(ValueError, match="divide batch_size"):
            ring.acquire(3)

    def test_stale_commit_raises_after_recycle(self):
        ring = _ring(B=2, num_slots=2)
        block = ring.acquire(2)
        stale = block
        ring.commit(block, 0)
        view = ring.pop_ready(timeout=1.0)
        ring.release(view.slot)
        # The slot recycled: a writer that held its block across the
        # recycle must fail loudly, not corrupt the next batch.
        with pytest.raises(RuntimeError, match="stale ring block"):
            ring.commit(stale, 1)

    def test_discard_torn_reclaims_half_committed_slot(self):
        """ISSUE 18 satellite: a writer SIGKILLed mid-commit (kill_host
        chaos) leaves a slot with partial progress — neither free nor
        ready. Restore-time discard_torn() must reclaim it, never
        deliver it, and fence the dead writer's block via the
        generation bump."""
        ring = _ring(B=4, num_slots=2)
        # Nothing in flight: nothing to discard.
        assert ring.discard_torn() == 0
        a = ring.acquire(2)
        zombie = ring.acquire(2)
        ring.commit(a, param_version=3)
        # Half committed: not ready, not free — torn if the writer of
        # `zombie` never comes back.
        assert ring.pop_ready(timeout=0.05) is None
        assert ring.discard_torn() == 1
        # The torn slot went straight back to the free list and its
        # partial contents are never delivered.
        assert len(ring._free) == 2
        assert ring.pop_ready(timeout=0.05) is None
        # The dead writer's commit arriving after the discard (a zombie
        # process that hadn't died yet) hits the generation fence.
        with pytest.raises(RuntimeError, match="stale ring block"):
            ring.commit(zombie, 1)
        # A READY slot is not torn: full commit survives a discard pass.
        c = ring.acquire(4)
        ring.commit(c, 5)
        assert ring.discard_torn() == 0
        view = ring.pop_ready(timeout=1.0)
        assert view is not None and view.param_version == 5
        ring.release(view.slot)

    def test_abort_recycles_slot_without_delivering(self):
        ring = _ring(B=4, num_slots=2)
        a = ring.acquire(2)
        b = ring.acquire(2)
        ring.commit(a, 3)
        ring.abort(b)  # writer crash: slot drops, never delivered
        assert ring.pop_ready(timeout=0.05) is None
        assert len(ring._free) == 2  # recycled straight back
        # And the ring keeps working afterwards.
        c = ring.acquire(4)
        ring.commit(c, 1)
        assert ring.pop_ready(timeout=1.0) is not None

    def test_acquire_blocks_until_release_and_close_wakes(self):
        ring = _ring(B=2, num_slots=2)
        blocks = [ring.acquire(2), ring.acquire(2)]  # exhaust both slots
        got = []
        err = []

        def blocked_acquire():
            try:
                got.append(ring.acquire(2))
            except QueueClosed:
                err.append("closed")

        t = threading.Thread(target=blocked_acquire, daemon=True)
        t.start()
        time.sleep(0.1)
        assert not got  # backpressure: no free slot
        ring.commit(blocks[0], 0)
        view = ring.pop_ready(timeout=1.0)
        ring.release(view.slot)
        t.join(timeout=5)
        assert len(got) == 1  # release unblocked the writer
        t2 = threading.Thread(target=blocked_acquire, daemon=True)
        t2.start()
        time.sleep(0.05)
        ring.close()
        t2.join(timeout=5)
        assert err == ["closed"]


class TestRingPipeline:
    """Ring vs queue path parity through the REAL VectorActor + Learner
    batcher on deterministic envs."""

    def _drain(
        self,
        use_ring,
        use_lstm=False,
        T=5,
        E=2,
        B=4,
        n=3,
        agent=None,
        envs=None,
        example_obs=None,
        telemetry=None,
    ):
        agent = agent if agent is not None else _agent(use_lstm=use_lstm)
        if envs is None:
            envs = [ScriptedEnv(episode_len=4) for _ in range(E)]
        if example_obs is None:
            example_obs = np.zeros((4,), np.float32)
        learner = Learner(
            agent=agent,
            optimizer=optax.sgd(1e-2),
            config=LearnerConfig(
                batch_size=B, unroll_length=T, traj_ring=use_ring
            ),
            example_obs=example_obs,
            rng=jax.random.key(0),
            telemetry=telemetry,
        )
        actor = VectorActor(
            actor_id=0,
            envs=envs,
            agent=agent,
            param_store=learner.param_store,
            enqueue=learner.enqueue,
            unroll_length=T,
            seed=3,
            telemetry=telemetry,
            traj_ring=learner.traj_ring,
        )
        learner.start()
        batches = []
        try:
            for _ in range(n):
                for _ in range(B // E):
                    actor.unroll_and_push()
                arrays, version, _meta = learner._batch_q.get(timeout=120)
                batches.append(
                    (
                        jax.tree.map(
                            lambda x: np.array(x, copy=True), arrays
                        ),
                        version,
                    )
                )
        finally:
            learner.stop()
        return batches, actor

    @pytest.mark.parametrize("use_lstm", [False, True])
    def test_ring_batches_bit_identical_to_queue_path(self, use_lstm):
        queue_b, _ = self._drain(False, use_lstm=use_lstm)
        ring_b, actor = self._drain(True, use_lstm=use_lstm)
        assert len(queue_b) == len(ring_b) == 3
        for (bq, vq), (br, vr) in zip(queue_b, ring_b):
            assert vq == vr
            jax.tree.map(np.testing.assert_array_equal, bq, br)
        # Unroll accounting unchanged: E per cycle, counted without
        # Trajectory objects.
        assert actor.num_unrolls == 3 * 4

    def test_ring_copies_nothing_at_stack_stage_on_pixel_unrolls(self):
        """Fake Pong unrolls (84x84x4 uint8, T=4, E=4, B=4, 3 batches)
        through both paths: the batches are bit-identical, the queue
        path copies every unroll at stack time
        (`learner/host_stack_bytes`) and the ring path copies nothing
        there; what the ring stages before the transfer on a backend
        whose device_put may alias host memory (this CPU) never exceeds
        what the queue path copied."""
        from torched_impala_tpu import configs
        from torched_impala_tpu.models import AtariShallowTorso
        from torched_impala_tpu.telemetry import Registry

        T, E, B, n = 4, 4, 4, 3
        cfg = configs.ExperimentConfig(
            name="ring_pixels",
            env_family="atari",
            env_id="PongNoFrameskip-v4",
            obs_shape=(84, 84, 4),
            obs_dtype="uint8",
            num_actions=6,
        )
        factory = configs.make_env_factory(cfg, fake=True)
        agent = Agent(ImpalaNet(num_actions=6, torso=AtariShallowTorso()))

        def drain(use_ring):
            reg = Registry()
            batches, _ = self._drain(
                use_ring,
                T=T,
                E=E,
                B=B,
                n=n,
                agent=agent,
                envs=[factory(1000 + j, j) for j in range(E)],
                example_obs=configs.example_obs(cfg),
                telemetry=reg,
            )
            per_unroll = {
                name: reg.counter(f"learner/{name}").value / (n * B)
                for name in ("host_stack_bytes", "ring_stage_bytes")
            }
            return batches, per_unroll

        queue_b, queue_bytes = drain(False)
        ring_b, ring_bytes = drain(True)
        assert len(queue_b) == len(ring_b) == n
        for (bq, vq), (br, vr) in zip(queue_b, ring_b):
            assert vq == vr
            jax.tree.map(np.testing.assert_array_equal, bq, br)
        # One unroll holds (T+1) * 84*84*4 bytes of frames alone.
        assert queue_bytes["host_stack_bytes"] > (T + 1) * 84 * 84 * 4
        assert ring_bytes["host_stack_bytes"] == 0
        assert queue_bytes["ring_stage_bytes"] == 0
        assert (
            ring_bytes["ring_stage_bytes"]
            <= queue_bytes["host_stack_bytes"]
        )

    def test_ring_slots_recycle_across_many_batches(self):
        # More batches than slots: every slot is recycled at least once
        # (the regime where a stale-generation bug would serve a
        # previous batch's data — bit-parity above would catch content,
        # this pins the free-list actually cycling).
        batches, _ = self._drain(True, n=6)
        assert len(batches) == 6

    def test_train_e2e_with_ring_thread_mode(self):
        agent = _agent()
        result = train(
            agent=agent,
            env_factory=lambda seed, env_index=None: ScriptedEnv(
                episode_len=4
            ),
            example_obs=np.zeros((4,), np.float32),
            num_actors=2,
            learner_config=LearnerConfig(
                batch_size=4, unroll_length=3, traj_ring=True
            ),
            optimizer=optax.sgd(1e-3),
            total_steps=3,
            envs_per_actor=2,
            actor_device=None,
            log_every=1,
        )
        assert result.learner.num_steps == 3
        assert result.num_frames == 3 * 4 * 3
        assert np.isfinite(result.final_logs.get("total_loss", np.nan))

    def test_train_e2e_with_ring_single_env_actors(self):
        """envs_per_actor=1 + ring rides VectorActor with E=1 (the
        scalar-Actor path has no ring writer)."""
        agent = _agent()
        result = train(
            agent=agent,
            env_factory=lambda seed, env_index=None: ScriptedEnv(
                episode_len=4
            ),
            example_obs=np.zeros((4,), np.float32),
            num_actors=2,
            learner_config=LearnerConfig(
                batch_size=2, unroll_length=3, traj_ring=True
            ),
            optimizer=optax.sgd(1e-3),
            total_steps=2,
            envs_per_actor=1,
            actor_device=None,
            log_every=1,
        )
        assert result.learner.num_steps == 2

    def test_env_count_must_divide_batch_size(self):
        agent = _agent()
        with pytest.raises(ValueError, match="divide"):
            train(
                agent=agent,
                env_factory=lambda seed, env_index=None: ScriptedEnv(),
                example_obs=np.zeros((4,), np.float32),
                num_actors=1,
                learner_config=LearnerConfig(
                    batch_size=4, unroll_length=3, traj_ring=True
                ),
                optimizer=optax.sgd(1e-3),
                total_steps=1,
                envs_per_actor=3,  # 3 does not divide 4
                actor_device=None,
            )

    def test_unsupported_learner_combos_rejected(self):
        from torched_impala_tpu.parallel import make_mesh

        agent = _agent()
        common = dict(
            agent=agent,
            optimizer=optax.sgd(1e-2),
            example_obs=np.zeros((4,), np.float32),
            rng=jax.random.key(0),
        )
        # Superbatch ring (ISSUE 13): traj_ring + steps_per_dispatch>1
        # is now the fused feed path — the ring allocates [K, ...] slots.
        sb = Learner(
            config=LearnerConfig(
                batch_size=2,
                unroll_length=3,
                traj_ring=True,
                steps_per_dispatch=2,
            ),
            **common,
        )
        assert sb.traj_ring.superbatch_k == 2
        assert sb.traj_ring._slots[0].buffers.obs.shape == (2, 4, 2, 4)
        # Mesh + ring (ISSUE 15): the single-device carve-out is lifted
        # — the learner builds the ring and the table-driven feed
        # shardings instead of refusing.
        meshed = Learner(
            config=LearnerConfig(
                batch_size=2, unroll_length=3, traj_ring=True
            ),
            mesh=make_mesh(num_data=2),
            **common,
        )
        assert meshed.traj_ring is not None
        assert len(meshed._batch_shardings) == 8
