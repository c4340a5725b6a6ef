"""Replay subsystem tests (ISSUE 9 tentpole): IMPACT-style circular
replay on the trajectory ring (torched_impala_tpu/replay/,
docs/REPLAY.md).

Pins the three contracts the subsystem lives or dies by:

- ring replay semantics — fresh-first ordering, seeded deterministic
  sampling, the `replay_mix` cap, staleness expiry, eviction under
  free-list pressure (actors never block on replayed data), and the
  torn-read guard (a delivered slot is never an eviction candidate, so
  its generation/contents cannot change mid-consumption);
- the target store — pinned on-device snapshot refreshed on a step
  cadence, lag accounting, and the max-lag refusal;
- the loss — `impact_loss` gradients coincide with `impala_loss` at
  learner == target, and a DISABLED ReplayConfig is bit-identical to no
  config at all (structural parity: same code path, same telemetry key
  set, same losses on fixed seeds).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torched_impala_tpu.envs.fake import ScriptedEnv
from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
from torched_impala_tpu.ops.losses import (
    ImpalaLossConfig,
    impact_loss,
    impala_loss,
)
from torched_impala_tpu.replay import ReplayConfig, TargetParamStore
from torched_impala_tpu.runtime import (
    Learner,
    LearnerConfig,
    ParamStore,
    TrajectoryRing,
    VectorActor,
)
from torched_impala_tpu.telemetry.registry import Registry


def _ring(
    T=2,
    B=2,
    num_slots=3,
    max_reuse=2,
    replay_mix=1.0,
    staleness_frames=0,
    sampler_seed=0,
    telemetry=None,
):
    return TrajectoryRing(
        num_slots=num_slots,
        unroll_length=T,
        batch_size=B,
        example_obs=np.zeros((4,), np.float32),
        num_actions=2,
        telemetry=telemetry,
        max_reuse=max_reuse,
        replay_mix=replay_mix,
        staleness_frames=staleness_frames,
        sampler_seed=sampler_seed,
    )


def _fill(ring, value, param_version=0):
    """Write one full slot (rewards = `value`) and commit it."""
    block = ring.acquire(ring.batch_size)
    block.obs[...] = 0.0
    block.first[...] = False
    block.actions[...] = 0
    block.behaviour_logits[...] = 0.0
    block.rewards[...] = value
    block.cont[...] = 1.0
    block.task[...] = 0
    ring.commit(block, param_version=param_version)


class TestReplayRing:
    def test_fresh_first_then_replay_then_exhausted(self):
        ring = _ring(max_reuse=2)
        _fill(ring, 1.0, param_version=5)
        _fill(ring, 2.0, param_version=6)
        # Both fresh deliveries come first even though slot 0 is already
        # retained (released with budget) before slot 1 pops.
        a = ring.pop_ready(timeout=1.0)
        assert (a.reuse_count, a.param_version) == (1, 5)
        ring.release(a.slot)
        b = ring.pop_ready(timeout=1.0)
        assert (b.reuse_count, b.param_version) == (1, 6)
        ring.release(b.slot)
        # Then the retained pair replays (reuse_count == 2)...
        replays = []
        for _ in range(2):
            v = ring.pop_ready(timeout=1.0)
            assert v.reuse_count == 2
            replays.append(float(v.arrays[4][0, 0]))
            ring.release(v.slot)
        assert sorted(replays) == [1.0, 2.0]
        # ...and the budget is spent: nothing left to deliver.
        assert ring.pop_ready(timeout=0.05) is None

    def test_reuse_one_is_inert_and_registers_no_replay_metrics(self):
        reg = Registry()
        ring = _ring(max_reuse=1, telemetry=reg)
        _fill(ring, 1.0)
        v = ring.pop_ready(timeout=1.0)
        assert v.reuse_count == 1
        ring.release(v.slot)
        # Slot recycled, never retained; no replay/* series exists (the
        # disabled ring's snapshot key set is exactly today's — the
        # parity contract).
        assert ring._retained == []
        assert ring.pop_ready(timeout=0.05) is None
        assert not any(
            k.startswith("telemetry/replay/") for k in reg.snapshot()
        )

    def test_replay_metrics_registered_and_counted(self):
        reg = Registry()
        ring = _ring(max_reuse=2, telemetry=reg)
        _fill(ring, 1.0)
        v = ring.pop_ready(timeout=1.0)
        ring.release(v.slot)
        v = ring.pop_ready(timeout=1.0)
        assert v.reuse_count == 2
        ring.release(v.slot)  # budget spent -> recycled + histogram
        snap = reg.snapshot()
        assert snap["telemetry/replay/reuse_delivered"] == 1
        assert snap["telemetry/replay/reuse_count_mean"] == 2.0
        assert snap["telemetry/replay/evict_pressure"] == 0

    def test_sampler_is_seeded_deterministic(self):
        def order(seed):
            ring = _ring(B=1, num_slots=6, max_reuse=2, sampler_seed=seed)
            for i in range(4):
                _fill(ring, float(i), param_version=i)
            for _ in range(4):  # drain fresh, retaining all four
                ring.release(ring.pop_ready(timeout=1.0).slot)
            out = []
            for _ in range(4):  # replay order = sampler draws
                v = ring.pop_ready(timeout=1.0)
                out.append(float(v.arrays[4][0, 0]))
                ring.release(v.slot)
            return out

        assert order(7) == order(7)

    def test_replay_mix_caps_replay_fraction(self):
        # mix=0.34: at most ~1/3 of deliveries may be replays, so after
        # one fresh delivery the retained slot must NOT replay yet.
        ring = _ring(max_reuse=3, replay_mix=0.34)
        _fill(ring, 1.0)
        ring.release(ring.pop_ready(timeout=1.0).slot)
        assert ring.pop_ready(timeout=0.05) is None  # cap binds
        _fill(ring, 2.0)
        ring.release(ring.pop_ready(timeout=1.0).slot)
        # 2 fresh delivered: one replay now fits under the cap.
        v = ring.pop_ready(timeout=1.0)
        assert v is not None and v.reuse_count == 2
        ring.release(v.slot)
        assert ring.pop_ready(timeout=0.05) is None  # cap binds again

    def test_staleness_bound_expires_retained_slots(self):
        reg = Registry()
        ring = _ring(
            max_reuse=3, staleness_frames=10, telemetry=reg
        )
        _fill(ring, 1.0, param_version=100)
        ring.release(ring.pop_ready(timeout=1.0).slot)
        assert len(ring._retained) == 1
        ring.note_version(105)  # within bound: still retained
        assert len(ring._retained) == 1
        ring.note_version(111)  # 11 > 10: expired eagerly
        assert ring._retained == []
        assert ring.pop_ready(timeout=0.05) is None
        assert reg.snapshot()["telemetry/replay/staleness_expired"] == 1

    def test_eviction_under_pressure_unblocks_acquire(self):
        # 2-slot ring, both retained after fresh delivery: a writer
        # acquiring a third unroll must NOT block — the stalest retained
        # slot (oldest param version) is evicted to free it.
        reg = Registry()
        ring = _ring(num_slots=2, max_reuse=5, telemetry=reg)
        _fill(ring, 1.0, param_version=1)
        _fill(ring, 2.0, param_version=9)
        for _ in range(2):
            ring.release(ring.pop_ready(timeout=1.0).slot)
        assert len(ring._retained) == 2 and not ring._free
        _fill(ring, 3.0, param_version=10)  # acquire() must not block
        assert reg.snapshot()["telemetry/replay/evict_pressure"] == 1
        # The survivor is the fresher retained slot (version 9, not 1).
        [kept] = ring._retained
        assert int(ring._slots[kept].versions.min()) == 9

    def test_delivered_slot_is_never_an_eviction_candidate(self):
        # Torn-read guard: while the batcher consumes a replayed slot,
        # free-list pressure must evict some OTHER retained slot — the
        # delivered slot's generation (and therefore its buffers) stay
        # untouched until release.
        ring = _ring(num_slots=2, max_reuse=5)
        _fill(ring, 1.0, param_version=1)
        _fill(ring, 2.0, param_version=2)
        for _ in range(2):
            ring.release(ring.pop_ready(timeout=1.0).slot)
        v = ring.pop_ready(timeout=1.0)  # replay: now delivered
        assert v.reuse_count == 2
        assert v.slot not in ring._retained
        _fill(ring, 3.0, param_version=3)  # evicts the OTHER slot
        assert ring._slots[v.slot].gen == v.gen
        np.testing.assert_array_equal(
            v.arrays[4], np.full_like(v.arrays[4], v.arrays[4][0, 0])
        )
        ring.release(v.slot)

    def test_stale_writer_commit_still_raises_in_replay_mode(self):
        # The generation counter stays the torn-WRITE guard: a writer
        # holding a block across an eviction-recycle fails loudly.
        ring = _ring(num_slots=2, max_reuse=5)
        _fill(ring, 1.0, param_version=1)
        stale = ring.acquire(ring.batch_size)  # second slot, unfinished
        v = ring.pop_ready(timeout=1.0)
        ring.release(v.slot)  # retained
        # Pressure: the retained slot is evicted for this acquire...
        block = ring.acquire(ring.batch_size)
        block.rewards[...] = 9.0
        ring.commit(block, param_version=2)
        # ...while the old writer's block (same slot, pre-recycle
        # generation in the worst case) commits fine only if its slot
        # was untouched; the evicted slot's generation DID advance.
        evicted = v.slot
        assert ring._slots[evicted].gen == v.gen + 1
        ring.commit(stale, param_version=2)  # its slot was never recycled


class TestTargetParamStore:
    def _store(self, **kw):
        store = ParamStore()
        store.publish(0, {"w": jnp.ones((2,))})
        kw.setdefault("update_interval", 4)
        return TargetParamStore(store, **kw), store

    def test_current_before_first_update_raises(self):
        tps, _ = self._store()
        with pytest.raises(RuntimeError, match="before the first update"):
            tps.current()

    def test_update_pins_a_hard_copy(self):
        tps, _ = self._store()
        params = {"w": jnp.arange(2.0)}
        tps.update(params, version=10, step=0)
        ver, pinned = tps.current()
        assert ver == 10
        np.testing.assert_array_equal(np.asarray(pinned["w"]), [0.0, 1.0])
        # Hard copy: the pinned tree is distinct buffers, not aliases.
        assert pinned["w"] is not params["w"]

    def test_maybe_update_honors_step_cadence_and_tracks_lag(self):
        tps, _ = self._store(update_interval=4)
        tps.update({"w": jnp.zeros(2)}, version=0, step=0)
        assert tps.lag() == 0
        # Steps 1-3: watermark advances, target does not.
        for step, version in ((1, 8), (2, 16), (3, 24)):
            tps.maybe_update(step, {"w": jnp.ones(2)}, version)
        assert tps.current()[0] == 0 and tps.lag() == 24
        # Step 4 crosses the interval: refresh, lag collapses.
        tps.maybe_update(4, {"w": jnp.ones(2)}, 32)
        assert tps.current()[0] == 32 and tps.lag() == 0

    def test_max_lag_refusal(self):
        tps, _ = self._store(update_interval=100, max_lag_frames=5)
        tps.update({"w": jnp.zeros(2)}, version=0, step=0)
        tps.maybe_update(1, {"w": jnp.ones(2)}, 4)  # lag 4: fine
        tps.current()
        tps.maybe_update(2, {"w": jnp.ones(2)}, 6)  # lag 6 > 5
        with pytest.raises(RuntimeError, match="target params are"):
            tps.current()

    def test_ctor_validation(self):
        store = ParamStore()
        with pytest.raises(ValueError):
            TargetParamStore(store, update_interval=0)
        with pytest.raises(ValueError):
            TargetParamStore(store, update_interval=1, max_lag_frames=-1)


class TestReplayConfig:
    def test_disabled_by_default_enabled_by_either_knob(self):
        assert not ReplayConfig().enabled
        assert ReplayConfig(
            max_reuse=2, target_update_interval=1
        ).enabled
        assert ReplayConfig(target_update_interval=4).enabled

    def test_validate_rejects_reuse_without_target(self):
        with pytest.raises(ValueError, match="target_update_interval"):
            ReplayConfig(max_reuse=2).validate()
        with pytest.raises(ValueError):
            ReplayConfig(max_reuse=0).validate()
        with pytest.raises(ValueError):
            ReplayConfig(replay_mix=0.0).validate()
        with pytest.raises(ValueError):
            ReplayConfig(target_clip_epsilon=0.0).validate()
        ReplayConfig(max_reuse=2, target_update_interval=4).validate()


class TestImpactLoss:
    def _batch(self, seed=0, T=5, B=3, A=4):
        rng = np.random.default_rng(seed)
        return dict(
            logits=jnp.asarray(
                rng.normal(size=(T, B, A)).astype(np.float32)
            ),
            behaviour=jnp.asarray(
                rng.normal(size=(T, B, A)).astype(np.float32)
            ),
            values=jnp.asarray(rng.normal(size=(T, B)).astype(np.float32)),
            bootstrap=jnp.asarray(rng.normal(size=(B,)).astype(np.float32)),
            actions=jnp.asarray(rng.integers(0, A, size=(T, B)), jnp.int32),
            rewards=jnp.asarray(rng.normal(size=(T, B)).astype(np.float32)),
            discounts=jnp.full((T, B), 0.99, jnp.float32),
        )

    def test_gradients_match_impala_at_learner_equals_target(self):
        """At pi_theta == pi_target the surrogate's gradient reduces to
        the IMPALA policy-gradient (d/dtheta exp(lp - stop(lp)) == d lp),
        so every parameter gradient must coincide — the guarantee that
        turning replay on does not change the learning signal until the
        policies actually separate."""
        b = self._batch()
        cfg = ImpalaLossConfig()

        def impala_total(logits, values, bootstrap):
            return impala_loss(
                target_logits=logits,
                behaviour_logits=b["behaviour"],
                values=values,
                bootstrap_value=bootstrap,
                actions=b["actions"],
                rewards=b["rewards"],
                discounts=b["discounts"],
                config=cfg,
            ).total

        def impact_total(logits, values, bootstrap):
            return impact_loss(
                learner_logits=logits,
                target_logits=b["logits"],  # same values, no gradient
                behaviour_logits=b["behaviour"],
                values=values,
                bootstrap_value=bootstrap,
                actions=b["actions"],
                rewards=b["rewards"],
                discounts=b["discounts"],
                clip_epsilon=0.2,
                config=cfg,
            ).total

        args = (b["logits"], b["values"], b["bootstrap"])
        g_impala = jax.grad(impala_total, argnums=(0, 1, 2))(*args)
        g_impact = jax.grad(impact_total, argnums=(0, 1, 2))(*args)
        for gi, gt in zip(g_impala, g_impact):
            np.testing.assert_allclose(
                np.asarray(gi), np.asarray(gt), rtol=1e-5, atol=1e-6
            )

    def test_ratio_logs_and_clip_activity(self):
        b = self._batch()
        out = impact_loss(
            learner_logits=b["logits"],
            target_logits=b["logits"],
            behaviour_logits=b["behaviour"],
            values=b["values"],
            bootstrap_value=b["bootstrap"],
            actions=b["actions"],
            rewards=b["rewards"],
            discounts=b["discounts"],
        )
        assert float(out.logs["impact_ratio"]) == pytest.approx(1.0)
        assert float(out.logs["impact_clip_frac"]) == 0.0
        # A separated learner policy activates the clip.
        far = impact_loss(
            learner_logits=b["logits"] * 3.0,
            target_logits=b["logits"],
            behaviour_logits=b["behaviour"],
            values=b["values"],
            bootstrap_value=b["bootstrap"],
            actions=b["actions"],
            rewards=b["rewards"],
            discounts=b["discounts"],
        )
        assert float(far.logs["impact_clip_frac"]) > 0.0

    def test_no_gradient_flows_into_target_logits(self):
        b = self._batch()

        def total(target_logits):
            return impact_loss(
                learner_logits=b["logits"],
                target_logits=target_logits,
                behaviour_logits=b["behaviour"],
                values=b["values"],
                bootstrap_value=b["bootstrap"],
                actions=b["actions"],
                rewards=b["rewards"],
                discounts=b["discounts"],
            ).total

        g = jax.grad(total)(b["logits"] + 0.1)
        np.testing.assert_array_equal(np.asarray(g), 0.0)


def _agent():
    return Agent(
        ImpalaNet(num_actions=2, torso=MLPTorso(hidden_sizes=(16,)))
    )


def _run_pipeline(replay, *, T=3, E=2, B=4, n=3, lstm=False):
    """Drive the full ring pipeline for `n` learner steps; return
    (per-step total_loss floats, final host params)."""
    agent = Agent(
        ImpalaNet(
            num_actions=2,
            torso=MLPTorso(hidden_sizes=(16,)),
            use_lstm=lstm,
            lstm_size=8,
        )
    )
    learner = Learner(
        agent=agent,
        optimizer=optax.sgd(1e-2),
        config=LearnerConfig(
            batch_size=B,
            unroll_length=T,
            traj_ring=True,
            replay=replay,
            publish_interval=1,
        ),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
    )
    envs = [ScriptedEnv(episode_len=4) for _ in range(E)]
    actor = VectorActor(
        actor_id=0,
        envs=envs,
        agent=agent,
        param_store=learner.param_store,
        enqueue=learner.enqueue,
        unroll_length=T,
        seed=3,
        traj_ring=learner.traj_ring,
    )
    learner.start()
    losses = []
    try:
        for _ in range(n):
            for _ in range(B // E):
                actor.unroll_and_push()
            logs = learner.step_once(timeout=60)
            losses.append(float(logs["total_loss"]))
    finally:
        learner.stop()
    params = jax.tree.map(np.asarray, learner.params)
    return losses, params


class TestStructuralParity:
    @pytest.mark.slow
    def test_disabled_replay_config_is_bit_identical(self):
        """LearnerConfig(replay=ReplayConfig()) — max_reuse 1, no target
        — must take EXACTLY the existing code path: same per-step losses
        bit-for-bit and same final params on fixed seeds as replay=None.
        """
        base_losses, base_params = _run_pipeline(None)
        off_losses, off_params = _run_pipeline(ReplayConfig())
        assert base_losses == off_losses  # float equality, not approx
        jax.tree.map(
            np.testing.assert_array_equal, base_params, off_params
        )

    @pytest.mark.slow
    def test_enabled_replay_multiplies_updates_per_env_frame(self):
        """max_reuse=2 on the same env stream: every fresh batch is
        re-delivered once, so the learner takes 2x the SGD steps for the
        same env frames — the ISSUE's >= 1.8x acceptance mechanism."""
        agent = _agent()
        reg = Registry()
        learner = Learner(
            agent=agent,
            optimizer=optax.sgd(1e-2),
            config=LearnerConfig(
                batch_size=4,
                unroll_length=3,
                traj_ring=True,
                replay=ReplayConfig(max_reuse=2, target_update_interval=2),
                publish_interval=1,
            ),
            example_obs=np.zeros((4,), np.float32),
            rng=jax.random.key(0),
            telemetry=reg,
        )
        envs = [ScriptedEnv(episode_len=4) for _ in range(2)]
        actor = VectorActor(
            actor_id=0,
            envs=envs,
            agent=agent,
            param_store=learner.param_store,
            enqueue=learner.enqueue,
            unroll_length=3,
            seed=3,
            traj_ring=learner.traj_ring,
        )
        learner.start()
        steps = 0
        try:
            for _ in range(3):  # 3 fresh batches pushed
                for _ in range(2):
                    actor.unroll_and_push()
            import queue as _q

            while True:
                try:
                    logs = learner.step_once(timeout=2.0)
                except _q.Empty:
                    break
                steps += 1
                assert "impact_ratio" in logs
        finally:
            learner.stop()
        assert steps == 6  # 3 fresh + 3 replayed
        snap = reg.snapshot()
        assert snap["telemetry/replay/reuse_delivered"] == 3
        assert snap["telemetry/replay/target_updates"] >= 2

    def test_replay_on_and_off_over_the_same_env_frames(self):
        """The same fresh unroll stream (T=4, E=4, B=4, 3 batches) into
        a learner without replay and one with max_reuse=2: equal env
        frames, and every update the replay arm takes beyond the plain
        arm's is a counted re-delivery through a live target."""
        import queue as queue_mod

        T, E, B, n = 4, 4, 4, 3
        agent = Agent(
            ImpalaNet(num_actions=2, torso=MLPTorso(hidden_sizes=(32,)))
        )

        def run(replay):
            reg = Registry()
            learner = Learner(
                agent=agent,
                optimizer=optax.sgd(1e-2),
                config=LearnerConfig(
                    batch_size=B,
                    unroll_length=T,
                    publish_interval=1,
                    traj_ring=True,
                    replay=replay,
                    auto_layouts=False,
                ),
                example_obs=np.zeros((4,), np.float32),
                rng=jax.random.key(0),
                telemetry=reg,
            )
            actor = VectorActor(
                actor_id=0,
                envs=[ScriptedEnv(episode_len=5) for _ in range(E)],
                agent=agent,
                param_store=learner.param_store,
                enqueue=learner.enqueue,
                unroll_length=T,
                seed=7,
                telemetry=reg,
                traj_ring=learner.traj_ring,
            )
            learner.start()
            updates = 0
            try:
                # Pushes interleaved with steps, so neither the ring nor
                # the device queue backs up into a blocked actor; then
                # the replay tail is drained until nothing is delivered.
                for _ in range(n):
                    for _ in range(B // E):
                        actor.unroll_and_push()
                    learner.step_once(timeout=60)
                    updates += 1
                while True:
                    try:
                        learner.step_once(timeout=3.0)
                    except queue_mod.Empty:
                        break
                    updates += 1
            finally:
                learner.stop()
            snap = reg.snapshot()
            return {
                "updates": updates,
                "env_frames": actor.num_unrolls * T,
                "reuse_delivered": int(
                    snap.get("telemetry/replay/reuse_delivered", 0)
                ),
                "target_updates": int(
                    snap.get("telemetry/replay/target_updates", 0)
                ),
            }

        off = run(None)
        on = run(ReplayConfig(max_reuse=2, target_update_interval=4))
        assert on["env_frames"] == off["env_frames"] == n * B * T
        assert off["updates"] == n
        assert off["reuse_delivered"] == 0 and off["target_updates"] == 0
        assert on["reuse_delivered"] >= 2
        assert on["updates"] == off["updates"] + on["reuse_delivered"]
        assert on["target_updates"] >= 1
