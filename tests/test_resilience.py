"""Resilience subsystem (ISSUE 5): async checkpointing, crash-consistent
resume, chaos fault injection, and the satellite hardening — atomic
checkpoint writes with clear corruption errors, supervisor backoff
jitter + restart telemetry, and ParamStore timeout semantics.
"""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torched_impala_tpu.envs.fake import ScriptedEnv
from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
from torched_impala_tpu.resilience import (
    AsyncCheckpointer,
    ChaosError,
    ChaosInjector,
    ChaosPlan,
    Fault,
    ResumeConfigMismatch,
    config_fingerprint,
    corrupt_file,
    load_manifest,
    restore_latest,
    write_manifest,
)
from torched_impala_tpu.resilience import recovery
from torched_impala_tpu.runtime import (
    Actor,
    ActorSupervisor,
    Learner,
    LearnerConfig,
    ParamStore,
)
from torched_impala_tpu.telemetry import Registry
from torched_impala_tpu.utils.checkpoint import (
    CheckpointCorruptError,
    atomic_write_bytes,
    load_state_file,
    save_state_file,
)


def _state(seed=0.0):
    return {
        "params": {
            "dense": {"kernel": np.full((4, 3), seed, np.float32)},
            "bias": np.arange(3.0, dtype=np.float32) + seed,
        },
        "num_frames": np.asarray(480, np.int64),
        "num_steps": np.asarray(3, np.int64),
        "rng": np.asarray([5, 9], np.uint32),
    }


# ---- atomic state files (satellite: utils/checkpoint.py) ----------------


class TestAtomicStateFile:
    def test_roundtrip(self, tmp_path):
        path = str(tmp_path / "ck.npz")
        nbytes = save_state_file(path, _state(2.0))
        assert nbytes == os.path.getsize(path)
        restored = load_state_file(path, _state(0.0))
        jax.tree.map(
            np.testing.assert_array_equal, restored, _state(2.0)
        )

    def test_no_tmp_residue(self, tmp_path):
        save_state_file(str(tmp_path / "ck.npz"), _state())
        assert [p.name for p in tmp_path.iterdir()] == ["ck.npz"]

    def test_truncated_file_raises_clear_error(self, tmp_path):
        path = str(tmp_path / "ck.npz")
        save_state_file(path, _state())
        data = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(data[: len(data) // 2])  # torn write
        with pytest.raises(CheckpointCorruptError) as ei:
            load_state_file(path, _state())
        msg = str(ei.value)
        assert path in msg and "corrupt" in msg

    def test_bitrot_caught_by_crc(self, tmp_path):
        path = str(tmp_path / "ck.npz")
        save_state_file(path, _state())
        corrupt_file(path)
        with pytest.raises(CheckpointCorruptError):
            load_state_file(path, _state())

    def test_missing_entry_names_the_key(self, tmp_path):
        path = str(tmp_path / "ck.npz")
        save_state_file(path, {"params": np.zeros(3)})
        with pytest.raises(CheckpointCorruptError) as ei:
            load_state_file(path, {"params": np.zeros(3), "extra": np.zeros(2)})
        assert "extra" in str(ei.value)

    def test_shape_mismatch_rejected(self, tmp_path):
        path = str(tmp_path / "ck.npz")
        save_state_file(path, {"params": np.zeros((4, 3))})
        with pytest.raises(ValueError):
            load_state_file(path, {"params": np.zeros((7, 3))})

    def test_atomic_write_bytes_cleans_tmp_on_failure(self, tmp_path):
        target = tmp_path / "sub" / "blob.bin"
        atomic_write_bytes(str(target), b"hello")
        assert target.read_bytes() == b"hello"
        assert [p.name for p in target.parent.iterdir()] == ["blob.bin"]


# ---- manifests + recovery scan -----------------------------------------


class TestRecovery:
    def test_manifest_roundtrip(self, tmp_path):
        m = recovery.RunManifest(
            step=7,
            param_version=560,
            checkpoint="ckpt-000000000007.npz",
            config_hash="abc123",
            rng=[5, 9],
            saved_at=123.5,
        )
        path = write_manifest(str(tmp_path), m)
        assert load_manifest(path) == m
        # The latest-pointer copy matches too.
        latest = load_manifest(str(tmp_path / recovery.LATEST_MANIFEST))
        assert latest == m

    def test_restore_latest_empty_dir_is_none(self, tmp_path):
        assert restore_latest(str(tmp_path), _state()) is None

    def test_restore_latest_picks_newest(self, tmp_path):
        d = str(tmp_path)
        for step, seed in ((2, 1.0), (5, 2.0)):
            save_state_file(recovery.checkpoint_path(d, step), _state(seed))
            write_manifest(
                d,
                recovery.RunManifest(
                    step=step,
                    param_version=step * 10,
                    checkpoint=os.path.basename(
                        recovery.checkpoint_path(d, step)
                    ),
                ),
            )
        manifest, state = restore_latest(d, _state())
        assert manifest.step == 5
        np.testing.assert_array_equal(
            state["params"]["bias"], _state(2.0)["params"]["bias"]
        )

    def _write_ck(self, d, host_count, step=4):
        save_state_file(recovery.checkpoint_path(d, step), _state(3.0))
        write_manifest(
            d,
            recovery.RunManifest(
                step=step,
                param_version=40,
                checkpoint=os.path.basename(
                    recovery.checkpoint_path(d, step)
                ),
                host_count=host_count,
            ),
        )

    def test_restore_under_host_turnover_reshards(self, tmp_path, capsys):
        """ISSUE 18 satellite: an N-host checkpoint restores into an
        M-host run when the global batch still divides — params are
        replicated, so they reshard through the SpecLayout placement
        tables — and says so loudly."""
        d = str(tmp_path)
        self._write_ck(d, host_count=2)
        # 2-host checkpoint -> 1-host run (scale down).
        manifest, state = restore_latest(
            d, _state(), host_count=1, global_batch_size=8
        )
        assert manifest.step == 4 and manifest.host_count == 2
        err = capsys.readouterr().err
        assert "2-host" in err and "1-host" in err
        # 1-host checkpoint -> 2-host run (scale up), other direction.
        d2 = str(tmp_path / "up")
        os.makedirs(d2)
        self._write_ck(d2, host_count=1)
        manifest, state = restore_latest(
            d2, _state(), host_count=2, global_batch_size=8
        )
        assert manifest.host_count == 1
        err = capsys.readouterr().err
        assert "1-host" in err and "2-host" in err
        # Same host count: silent, no turnover notice.
        manifest, state = restore_latest(
            d, _state(), host_count=2, global_batch_size=8
        )
        assert "reshard" not in capsys.readouterr().err

    def test_restore_host_turnover_indivisible_refuses(self, tmp_path):
        """When the global batch does NOT divide over the new host
        count, restore refuses loudly, naming both counts — silently
        changing batch semantics mid-run is worse than dying."""
        from torched_impala_tpu.resilience import HostCountMismatch

        d = str(tmp_path)
        self._write_ck(d, host_count=2)
        with pytest.raises(HostCountMismatch) as ei:
            restore_latest(d, _state(), host_count=3, global_batch_size=8)
        msg = str(ei.value)
        assert "2-host" in msg and "3 hosts" in msg and "8" in msg

    def test_manifest_host_count_default_backcompat(self, tmp_path):
        """Manifests written before host_count existed load as 1-host."""
        blob = recovery.RunManifest(
            step=1, param_version=1, checkpoint="ck.npz"
        ).to_json()
        obj = json.loads(blob)
        assert obj["host_count"] == 1
        del obj["host_count"]
        m = recovery.RunManifest.from_json(json.dumps(obj))
        assert m.host_count == 1

    def test_corrupt_newest_falls_back(self, tmp_path, capsys):
        d = str(tmp_path)
        for step, seed in ((2, 1.0), (5, 2.0)):
            save_state_file(recovery.checkpoint_path(d, step), _state(seed))
            write_manifest(
                d,
                recovery.RunManifest(
                    step=step,
                    param_version=0,
                    checkpoint=os.path.basename(
                        recovery.checkpoint_path(d, step)
                    ),
                ),
            )
        corrupt_file(recovery.checkpoint_path(d, 5))
        manifest, state = restore_latest(d, _state())
        assert manifest.step == 2
        np.testing.assert_array_equal(
            state["params"]["bias"], _state(1.0)["params"]["bias"]
        )
        assert "falling back" in capsys.readouterr().err

    def test_all_corrupt_raises(self, tmp_path):
        d = str(tmp_path)
        save_state_file(recovery.checkpoint_path(d, 2), _state())
        write_manifest(
            d,
            recovery.RunManifest(
                step=2, param_version=0, checkpoint="ckpt-000000000002.npz"
            ),
        )
        corrupt_file(recovery.checkpoint_path(d, 2))
        with pytest.raises(CheckpointCorruptError):
            restore_latest(d, _state())

    def test_config_hash_mismatch_refused(self, tmp_path):
        d = str(tmp_path)
        save_state_file(recovery.checkpoint_path(d, 2), _state())
        write_manifest(
            d,
            recovery.RunManifest(
                step=2,
                param_version=0,
                checkpoint="ckpt-000000000002.npz",
                config_hash=config_fingerprint({"lr": 1e-3}),
            ),
        )
        with pytest.raises(ResumeConfigMismatch) as ei:
            restore_latest(
                d, _state(), config_hash=config_fingerprint({"lr": 5e-4})
            )
        assert "Refusing to resume" in str(ei.value)

    def test_mismatch_still_refused_past_corrupt_newest_manifest(
        self, tmp_path
    ):
        """The hash check rides the first LOADABLE manifest: garbling the
        newest manifest file must not smuggle a wrong-config resume in
        through the fallback."""
        d = str(tmp_path)
        for step in (2, 5):
            save_state_file(recovery.checkpoint_path(d, step), _state())
            write_manifest(
                d,
                recovery.RunManifest(
                    step=step,
                    param_version=0,
                    checkpoint=os.path.basename(
                        recovery.checkpoint_path(d, step)
                    ),
                    config_hash=config_fingerprint({"lr": 1e-3}),
                ),
            )
        with open(recovery.manifest_path(d, 5), "w") as f:
            f.write("{not json")
        with pytest.raises(ResumeConfigMismatch):
            restore_latest(
                d, _state(), config_hash=config_fingerprint({"lr": 9e-9})
            )

    def test_config_fingerprint_stability(self):
        from torched_impala_tpu import configs

        a = config_fingerprint(configs.CARTPOLE)
        b = config_fingerprint(configs.CARTPOLE)
        assert a == b and len(a) == 16
        assert a != config_fingerprint(configs.PONG)
        import dataclasses

        assert a != config_fingerprint(
            dataclasses.replace(configs.CARTPOLE, lr=1e-5)
        )


# ---- AsyncCheckpointer --------------------------------------------------


class TestAsyncCheckpointer:
    def test_interval_cadence_and_retention(self, tmp_path):
        reg = Registry()
        ck = AsyncCheckpointer(
            str(tmp_path), keep=2, interval_steps=2, telemetry=reg
        )
        try:
            for step in range(1, 8):
                fired = ck.maybe_save(step, lambda: _state(float(step)))
                if fired:
                    ck.wait()  # serialize so the cadence is exact
            ck.wait()
            # First call always fires, then every 2 steps: 1, 3, 5, 7;
            # retention keeps the newest 2.
            assert ck.all_steps() == [5, 7]
            assert ck.saves == 4
            snap = reg.snapshot()
            assert snap["telemetry/resilience/checkpoint_saves"] == 4
            assert snap["telemetry/resilience/checkpoint_bytes"] > 0
            assert snap["telemetry/resilience/checkpoint_staleness_s"] >= 0
        finally:
            ck.close()

    def test_seconds_cadence(self, tmp_path):
        ck = AsyncCheckpointer(
            str(tmp_path), interval_seconds=0.05, telemetry=Registry()
        )
        try:
            assert not ck.maybe_save(1, _state)  # clock starts at init
            time.sleep(0.06)
            assert ck.maybe_save(2, _state)  # wall-clock due
            ck.wait()
            assert not ck.maybe_save(3, _state)  # too soon again
            time.sleep(0.06)
            assert ck.maybe_save(4, _state)
            ck.wait()
            assert ck.all_steps() == [2, 4]
        finally:
            ck.close()

    def test_busy_writer_skips_instead_of_queueing(self, tmp_path):
        gate = threading.Event()
        reg = Registry()
        ck = AsyncCheckpointer(
            str(tmp_path),
            interval_steps=1,
            telemetry=reg,
            post_save=lambda path, step: gate.wait(5.0),
        )
        try:
            assert ck.maybe_save(1, _state)  # writer now wedged in post_save
            time.sleep(0.05)
            assert not ck.maybe_save(2, _state)  # skipped, not queued
            assert ck.skipped == 1
            gate.set()
            ck.wait()
            assert ck.all_steps() == [1]
            assert (
                reg.snapshot()["telemetry/resilience/checkpoint_skipped"]
                == 1
            )
        finally:
            gate.set()
            ck.close()

    def test_manifest_carries_param_version_and_hash(self, tmp_path):
        fp = config_fingerprint({"x": 1})
        ck = AsyncCheckpointer(
            str(tmp_path), config_hash=fp, telemetry=Registry()
        )
        try:
            ck.save_now(3, _state(), param_version=480)
            ck.wait()
        finally:
            ck.close()
        m = load_manifest(recovery.manifest_path(str(tmp_path), 3))
        assert m.param_version == 480
        assert m.config_hash == fp
        assert m.rng == [5, 9]  # the state's packed key data, audit copy

    def test_writer_error_surfaces(self, tmp_path):
        class _Unserializable:
            def __array__(self, dtype=None, copy=None):
                raise TypeError("cannot materialize")

        ck = AsyncCheckpointer(str(tmp_path), telemetry=Registry())
        try:
            # A state tree numpy cannot materialize kills the save; the
            # NEXT learner-thread call must raise, not hang silently.
            ck.save_now(1, {"bad": _Unserializable()})
            with pytest.raises(RuntimeError):
                ck.wait()
            with pytest.raises(RuntimeError):
                ck.maybe_save(2, _state)
        finally:
            ck.close()


# ---- kill-and-resume round trip (satellite) -----------------------------


def _build_learner(seed=0):
    return Learner(
        agent=Agent(ImpalaNet(num_actions=2, torso=MLPTorso())),
        optimizer=optax.sgd(1e-2),
        config=LearnerConfig(batch_size=1, unroll_length=5),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(seed),
        telemetry=Registry(),
    )


class TestKillAndResume:
    def test_roundtrip_restores_step_version_and_rng(self, tmp_path):
        """Kill-and-resume: train, interval-save through the async
        writer, 'crash' (no final save), restore a FRESH learner from
        the newest manifest — step count, actor-visible param version,
        and the learner rng stream must all continue exactly."""
        fp = config_fingerprint({"exp": "resume"})
        learner = _build_learner(seed=3)
        actor = Actor(
            actor_id=0,
            env=ScriptedEnv(episode_len=7),
            agent=learner._agent,
            param_store=learner.param_store,
            enqueue=learner.enqueue,
            unroll_length=5,
            seed=42,
        )
        ck = AsyncCheckpointer(
            str(tmp_path),
            keep=3,
            interval_steps=2,
            config_hash=fp,
            telemetry=Registry(),
        )
        learner.post_step = lambda n: ck.maybe_save(
            n, learner.get_state_device, param_version=learner.num_frames
        )
        learner.start()
        try:
            for _ in range(4):
                actor.unroll_and_push()
                learner.step_once(timeout=60)
        finally:
            learner.stop()
        ck.wait()
        saved_steps = ck.all_steps()
        ck.close()
        assert saved_steps, "no interval save landed"
        rng_at_kill = np.asarray(jax.random.key_data(learner._rng))

        fresh = _build_learner(seed=99)  # different init, different rng
        found = restore_latest(
            str(tmp_path), fresh.get_state(), config_hash=fp
        )
        assert found is not None
        manifest, state = found
        fresh.set_state(state)
        assert fresh.num_steps == manifest.step == saved_steps[-1]
        assert fresh.num_frames == manifest.param_version
        # Resume restored the ACTOR-VISIBLE param version: the store
        # republished at the restored frame count with the restored
        # params, so actors resynchronize without any extra signal.
        version, params = fresh.param_store.get(timeout=1.0)
        assert version == fresh.num_frames
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            params,
            state["params"],
        )
        # rng continuity: the checkpointed stream, not the fresh seed.
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(fresh._rng)), rng_at_kill
        )


# ---- ParamStore timeout semantics (satellite) ---------------------------


class TestParamStoreTimeout:
    def test_timeout_expiry_raises(self):
        store = ParamStore()
        t0 = time.monotonic()
        with pytest.raises(TimeoutError):
            store.get(timeout=0.05)
        assert time.monotonic() - t0 < 5.0

    def test_publish_after_wait_wakes_blocked_getter(self):
        """A get() already blocked in wait() must wake on the publish
        and observe that publish's (version, params) — the wakeup
        ordering a respawned actor depends on at startup."""
        store = ParamStore()
        got = []
        waiting = threading.Event()

        def getter():
            waiting.set()
            got.append(store.get(timeout=5.0))

        t = threading.Thread(target=getter)
        t.start()
        assert waiting.wait(1.0)
        time.sleep(0.05)  # the getter is inside wait() now
        store.publish(7, {"w": 1})
        t.join(timeout=5.0)
        assert not t.is_alive()
        assert got == [(7, {"w": 1})]
        # Later publishes win for later getters.
        store.publish(9, {"w": 2})
        assert store.get(timeout=0.1) == (9, {"w": 2})


# ---- supervisor backoff jitter + telemetry (satellite) ------------------


class _InstantCrashActor:
    def __init__(self):
        self.error = None
        self.num_unrolls = 0

    def run(self, stop_event, max_unrolls=None):
        self.error = RuntimeError("boom")
        raise self.error


class TestSupervisorBackoffJitter:
    def _crashy_supervisor(self, reg, jitter, seed=0):
        stop = threading.Event()
        sup = ActorSupervisor(
            make_actor=lambda slot: _InstantCrashActor(),
            num_actors=1,
            stop_event=stop,
            check_interval=0.01,
            backoff_base=0.05,
            backoff_max=100.0,
            backoff_jitter=jitter,
            jitter_seed=seed,
            max_restarts_per_actor=3,
            telemetry=reg,
        )
        return sup, stop

    def test_backoff_grows_and_jitter_widens(self):
        reg = Registry()
        sup, stop = self._crashy_supervisor(reg, jitter=1.0)
        sup.start()
        try:
            deadline = time.monotonic() + 10.0
            while sup.restarts < 3 and time.monotonic() < deadline:
                time.sleep(0.01)
        finally:
            stop.set()
            sup.join()
        assert sup.restarts == 3
        assert (
            reg.snapshot()["telemetry/resilience/supervisor_restarts"] == 3
        )

    def test_jitter_streams_decorrelate(self):
        """Two supervisors with different jitter seeds schedule different
        backoffs for the same restart sequence (the thundering-herd
        decorrelation); jitter=0 reproduces the deterministic delays."""

        def delays(jitter, seed):
            sup, stop = self._crashy_supervisor(
                Registry(), jitter=jitter, seed=seed
            )
            out = []
            base = time.monotonic()
            # Drive _maybe_restart by hand for determinism: spawn the
            # first actor, then observe the scheduled next_restart_at.
            with sup._lock:
                sup._spawn_locked(0, sup._make_actor(0))
            for _ in range(3):
                sup._threads[0].join(timeout=1.0)
                sup._next_restart_at[0] = 0.0  # skip the wall-clock wait
                sup._maybe_restart(0)
                out.append(sup._next_restart_at[0] - time.monotonic())
            stop.set()
            return np.asarray(out)

        d_a = delays(1.0, seed=1)
        d_b = delays(1.0, seed=2)
        d_plain = delays(0.0, seed=1)
        # Exponential growth in every stream...
        assert (np.diff(d_plain) > 0).all(), d_plain
        # ...deterministic when jitter is off (0.05 * 2^k, scheduling
        # slop only)...
        np.testing.assert_allclose(
            d_plain, [0.05, 0.1, 0.2], atol=0.02
        )
        # ...and seed-dependent (decorrelated) when jitter is on, always
        # at or above the deterministic floor.
        assert not np.allclose(d_a, d_b)
        assert (d_a >= d_plain - 0.02).all() and (
            d_b >= d_plain - 0.02
        ).all()

    def test_negative_jitter_rejected(self):
        with pytest.raises(ValueError):
            ActorSupervisor(
                make_actor=lambda slot: _InstantCrashActor(),
                num_actors=1,
                stop_event=threading.Event(),
                backoff_jitter=-0.1,
                telemetry=Registry(),
            )


# ---- chaos harness ------------------------------------------------------


class TestChaosPlan:
    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError) as ei:
            Fault(kind="set_on_fire", at=1)
        assert "unknown fault kind" in str(ei.value)

    def test_at_counts_from_one(self):
        with pytest.raises(ValueError):
            Fault(kind="crash_learner", at=0)

    def test_unknown_field_rejected(self):
        with pytest.raises(ValueError) as ei:
            ChaosPlan.from_dicts([{"kind": "crash_learner", "when": 3}])
        assert "unknown field" in str(ei.value)

    def test_from_json_roundtrip(self, tmp_path):
        path = tmp_path / "plan.json"
        path.write_text(
            json.dumps(
                [
                    {"kind": "kill_env_worker", "at": 5, "target": 2},
                    {"kind": "wedge_queue", "at": 3, "duration_s": 0.5},
                ]
            )
        )
        plan = ChaosPlan.from_json(str(path))
        assert [f.kind for f in plan.faults] == [
            "kill_env_worker",
            "wedge_queue",
        ]
        assert plan.faults[0].site == "pool"
        assert plan.faults[1].duration_s == 0.5

    def test_non_list_json_rejected(self, tmp_path):
        path = tmp_path / "plan.json"
        path.write_text('{"kind": "crash_learner", "at": 1}')
        with pytest.raises(ValueError):
            ChaosPlan.from_json(str(path))


class TestChaosInjector:
    def test_actor_fault_targets_and_counts(self):
        reg = Registry()
        inj = ChaosInjector(
            ChaosPlan([Fault(kind="raise_in_actor", at=2, target=1)]),
            telemetry=reg,
        )
        inj.actor_hook(1)  # event 1: before `at`
        inj.actor_hook(0)  # event 2: at count, wrong target
        with pytest.raises(ChaosError):
            inj.actor_hook(1)  # event 3: count reached AND target match
        inj.actor_hook(1)  # one-shot: no re-fire
        assert [f.kind for f in inj.fired] == ["raise_in_actor"]
        assert inj.pending == 0
        assert reg.snapshot()["telemetry/resilience/chaos_faults"] == 1

    def test_wedge_queue_blocks_one_enqueue(self):
        inj = ChaosInjector(
            ChaosPlan([Fault(kind="wedge_queue", at=2, duration_s=0.15)]),
            telemetry=Registry(),
        )
        seen = []
        enqueue = inj.wrap_enqueue(seen.append)
        t0 = time.monotonic()
        enqueue("a")
        fast = time.monotonic() - t0
        t0 = time.monotonic()
        enqueue("b")
        wedged = time.monotonic() - t0
        assert seen == ["a", "b"]
        assert wedged >= 0.15 and fast < 0.1

    def test_corrupt_checkpoint_forces_fallback(self, tmp_path):
        """The corrupt_checkpoint fault rides the writer's post_save
        hook; the recovery scan must reject the damaged newest file and
        fall back one retained step."""
        inj = ChaosInjector(
            ChaosPlan([Fault(kind="corrupt_checkpoint", at=2)]),
            telemetry=Registry(),
        )
        ck = AsyncCheckpointer(
            str(tmp_path),
            keep=3,
            telemetry=Registry(),
            post_save=inj.checkpoint_hook,
        )
        try:
            ck.save_now(1, _state(1.0))
            ck.wait()
            ck.save_now(2, _state(2.0))  # this save gets corrupted
            ck.wait()
        finally:
            ck.close()
        assert [f.kind for f in inj.fired] == ["corrupt_checkpoint"]
        manifest, state = restore_latest(str(tmp_path), _state())
        assert manifest.step == 1
        np.testing.assert_array_equal(
            state["params"]["bias"], _state(1.0)["params"]["bias"]
        )


# ---- end-to-end: chaos + async checkpoint + resume through loop.train ---


class TestTrainResilienceIntegration:
    def _common(self, batch_size=2):
        import dataclasses

        from torched_impala_tpu import configs

        cfg = configs.CARTPOLE
        agent = configs.make_agent(cfg)
        return cfg, dict(
            agent=agent,
            env_factory=configs.make_env_factory(cfg, fake=True),
            example_obs=configs.example_obs(cfg),
            num_actors=2,
            learner_config=dataclasses.replace(
                configs.make_learner_config(cfg), batch_size=batch_size
            ),
            optimizer=configs.make_optimizer(cfg),
            seed=0,
            log_every=1,
        )

    def test_crash_resume_reaches_target(self, tmp_path):
        from torched_impala_tpu.runtime.loop import train

        cfg, common = self._common()
        fp = config_fingerprint(cfg)
        plan = ChaosPlan(
            [
                Fault(kind="raise_in_actor", at=2),
                Fault(kind="crash_learner", at=3),
            ]
        )
        ck = AsyncCheckpointer(
            str(tmp_path), keep=3, interval_steps=1, config_hash=fp
        )
        with pytest.raises(ChaosError):
            train(
                total_steps=8,
                async_checkpointer=ck,
                chaos=plan,
                config_hash=fp,
                **common,
            )
        ck.wait()
        saved = ck.all_steps()
        ck.close()
        assert saved and saved[-1] < 8  # crashed before the target

        ck2 = AsyncCheckpointer(
            str(tmp_path), keep=3, interval_steps=2, config_hash=fp
        )
        result = train(
            total_steps=8,
            async_checkpointer=ck2,
            resume="auto",
            config_hash=fp,
            **common,
        )
        ck2.close()
        assert result.learner.num_steps == 8
        # Clean finish wrote the final manifest at the target step.
        assert ck2.all_steps()[-1] == 8

    def test_scripted_faults_then_resume_is_deterministic(self, tmp_path):
        """One env worker SIGKILLed, one actor thread crashed and the
        learner crashed at step 4 of 8, process actors, a checkpoint
        every 2 steps: every fault fires as scripted and the first two
        are absorbed before the learner's crash ends the run with a
        save on disk; two resumes of the newest manifest assemble
        bit-identical first batches; the resumed run ends on the target
        step."""
        from torched_impala_tpu import configs
        from torched_impala_tpu.runtime import VectorActor
        from torched_impala_tpu.runtime.loop import train

        target, crash_at, interval = 8, 4, 2
        cfg, common = self._common()
        fp = config_fingerprint(cfg)
        injector = ChaosInjector(
            ChaosPlan.from_dicts(
                [
                    {"kind": "kill_env_worker", "at": 4, "target": 0},
                    {"kind": "raise_in_actor", "at": 3},
                    {"kind": "crash_learner", "at": crash_at},
                ]
            )
        )
        ck = AsyncCheckpointer(
            str(tmp_path), keep=3, interval_steps=interval, config_hash=fp
        )
        with pytest.raises(ChaosError):
            train(
                total_steps=target,
                async_checkpointer=ck,
                chaos=injector,
                actor_mode="process",
                envs_per_actor=2,
                config_hash=fp,
                **common,
            )
        ck.wait()
        saved = ck.all_steps()
        ck.close()
        assert sorted(f.kind for f in injector.fired) == [
            "crash_learner", "kill_env_worker", "raise_in_actor",
        ]
        assert saved and saved[-1] <= crash_at

        lcfg = common["learner_config"]
        factory = common["env_factory"]

        def first_batch_after_resume():
            learner = Learner(
                agent=common["agent"],
                optimizer=configs.make_optimizer(cfg),
                config=lcfg,
                example_obs=common["example_obs"],
                rng=jax.random.key(0),
                telemetry=Registry(),
            )
            manifest, state = restore_latest(
                str(tmp_path), learner.get_state(), config_hash=fp
            )
            learner.set_state(state)
            actor = VectorActor(
                actor_id=0,
                envs=[factory(1000 + j, j) for j in range(2)],
                agent=common["agent"],
                param_store=learner.param_store,
                enqueue=learner.enqueue,
                unroll_length=lcfg.unroll_length,
                seed=7,
                telemetry=Registry(),
            )
            learner.start()
            try:
                actor.unroll_and_push()
                arrays, _, _ = learner._batch_q.get(timeout=120)
                return manifest.step, jax.tree.map(
                    lambda x: np.array(x, copy=True), arrays
                )
            finally:
                learner.stop()

        step_a, batch_a = first_batch_after_resume()
        step_b, batch_b = first_batch_after_resume()
        assert step_a == step_b == saved[-1]
        jax.tree.map(np.testing.assert_array_equal, batch_a, batch_b)

        ck2 = AsyncCheckpointer(
            str(tmp_path), keep=3, interval_steps=interval, config_hash=fp
        )
        result = train(
            total_steps=target,
            async_checkpointer=ck2,
            resume="auto",
            config_hash=fp,
            **common,
        )
        ck2.close()
        assert result.learner.num_steps == target

    def test_resume_refuses_config_mismatch(self, tmp_path):
        from torched_impala_tpu.runtime.loop import train

        cfg, common = self._common()
        fp = config_fingerprint(cfg)
        ck = AsyncCheckpointer(
            str(tmp_path), interval_steps=1, config_hash=fp
        )
        train(
            total_steps=1,
            async_checkpointer=ck,
            config_hash=fp,
            **common,
        )
        ck.close()
        ck2 = AsyncCheckpointer(
            str(tmp_path), interval_steps=1, config_hash="f00d"
        )
        try:
            with pytest.raises(ResumeConfigMismatch):
                train(
                    total_steps=2,
                    async_checkpointer=ck2,
                    resume="auto",
                    config_hash="f00d",
                    **common,
                )
        finally:
            ck2.close()


# ---- CLI surface --------------------------------------------------------


class TestResilienceCLI:
    def test_async_checkpoint_resume_roundtrip(self, tmp_path):
        """--async-checkpoint + --resume end-to-end through run.py: the
        first run leaves manifests; the resumed run does only the
        remaining steps and the final manifest lands at the total."""
        from torched_impala_tpu.run import main as cli_main

        ck = str(tmp_path / "ck")
        base = [
            "--config", "cartpole",
            "--num-actors", "2",
            "--batch-size", "2",
            "--logger", "null",
            "--checkpoint-dir", ck,
            "--async-checkpoint",
            "--checkpoint-interval", "1",
        ]
        assert cli_main(base + ["--total-steps", "2"]) == 0
        assert recovery.list_manifest_steps(ck)[-1] == 2
        assert cli_main(base + ["--total-steps", "4", "--resume"]) == 0
        assert recovery.list_manifest_steps(ck)[-1] == 4

    def test_async_checkpoint_requires_dir(self):
        from torched_impala_tpu.run import main as cli_main

        with pytest.raises(SystemExit):
            cli_main(
                ["--config", "cartpole", "--async-checkpoint",
                 "--total-steps", "1", "--logger", "null"]
            )

    def test_chaos_plan_flag_parses_and_runs(self, tmp_path):
        from torched_impala_tpu.run import main as cli_main

        plan = tmp_path / "plan.json"
        plan.write_text(
            json.dumps([{"kind": "raise_in_actor", "at": 2}])
        )
        rc = cli_main([
            "--config", "cartpole",
            "--num-actors", "2",
            "--batch-size", "2",
            "--total-steps", "2",
            "--logger", "null",
            "--chaos-plan", str(plan),
        ])
        assert rc == 0


# ---- doctor + metric-name lint ------------------------------------------


def test_doctor_resilience_selfcheck_passes():
    from torched_impala_tpu.doctor import _check_resilience

    status, detail = _check_resilience()
    assert status == "ok", detail


def test_lint_flags_unprefixed_resilience_names(tmp_path):
    """impala-lint telemetry rule 3b (the former check_metric_names):
    resilience/* metrics must pick a sub-family prefix
    (checkpoint_/supervisor_/chaos_/recovery_). Migrated to the
    tools.lint framework entrypoint (ISSUE 7)."""
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from tools.lint.metrics import legacy_check

    pkg = tmp_path / "torched_impala_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        'reg.counter("resilience/orphan_series")\n'
        'reg.counter("resilience/checkpoint_bytes")\n'  # prefixed: clean
    )
    errors = legacy_check(str(tmp_path))
    assert len(errors) == 1 and "sub-family prefix" in errors[0]
