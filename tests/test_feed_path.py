"""Zero-copy feed path tests (ISSUE 13): the donated superbatch ring and
the fused V-trace+loss epilogue must be semantically invisible — donated
batches train to bit-identical params vs the copy path, the fused
epilogue matches the separate one to float tolerance at f32 and within a
documented gate at bf16, and disabled flags take the exact pre-existing
path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torched_impala_tpu.envs.fake import ScriptedEnv
from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
from torched_impala_tpu.ops import losses as losses_lib
from torched_impala_tpu.ops.losses import ImpalaLossConfig
from torched_impala_tpu.runtime import Learner, LearnerConfig, VectorActor
from torched_impala_tpu.telemetry.registry import Registry


def _agent(num_values=1):
    return Agent(
        ImpalaNet(
            num_actions=2,
            torso=MLPTorso(hidden_sizes=(16,)),
            num_values=num_values,
        )
    )


def _run_ring(
    donate, K=1, n=4, T=3, B=4, E=2, mesh=None, inspect=None, **cfg_kwargs
):
    """Train `n` learner steps through the trajectory ring and return
    (final params, telemetry registry, per-step losses). `inspect`, when
    given, is shown the first placed batch (the 8-tuple of device
    arrays) before the step takes it."""
    reg = Registry()
    num_values = (
        cfg_kwargs["popart"].num_values
        if cfg_kwargs.get("popart") is not None
        else 1
    )
    agent = _agent(num_values)
    learner = Learner(
        agent=agent,
        optimizer=optax.sgd(1e-2),
        config=LearnerConfig(
            batch_size=B,
            unroll_length=T,
            traj_ring=True,
            steps_per_dispatch=K,
            donate_batch=donate,
            **cfg_kwargs,
        ),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
        telemetry=reg,
        mesh=mesh,
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
        for i in range(n):
            for _ in range(K * B // E):
                actor.unroll_and_push()
            if inspect is not None and i == 0:
                item = learner._batch_q.get(timeout=60)
                inspect(item[0])
                learner._batch_q.put(item)
            logs = learner.step_once(timeout=60)
            assert np.isfinite(logs["total_loss"])
            losses.append(float(logs["total_loss"]))
    finally:
        learner.stop()
    params = jax.tree.map(
        lambda x: np.array(x, copy=True), learner.params
    )
    return params, reg, losses


class TestDonatedRing:
    def test_params_bit_identical_to_copy_path(self):
        """Donation is pure aliasing: same batches, same math, same
        bits — and zero host staging copies."""
        p_copy, reg_copy, _ = _run_ring(donate=False)
        p_don, reg_don, _ = _run_ring(donate=True)
        jax.tree.map(np.testing.assert_array_equal, p_copy, p_don)
        # The copy path stages every batch through host memory; the
        # donated path must stage NOTHING.
        assert reg_copy.counter("learner/ring_stage_bytes").value > 0
        assert reg_don.counter("learner/ring_stage_bytes").value == 0
        assert reg_don.counter("learner/donated_batches").value == 4

    @pytest.mark.parametrize("K", [2, 9])
    def test_superbatch_donated_parity(self, K):
        """K superbatch slots feed the fused dispatch directly (K=9 is
        one past the K=8 ceiling the fused dispatch once had); donation
        must not change the training trajectory, stages nothing where
        the copy path stages every superbatch, and every batch fed is
        counted as donated with its H2D dispatch timed."""
        p_copy, reg_copy, _ = _run_ring(donate=False, K=K, n=3)
        p_don, reg, _ = _run_ring(donate=True, K=K, n=3)
        jax.tree.map(np.testing.assert_array_equal, p_copy, p_don)
        assert reg_copy.counter("learner/ring_stage_bytes").value > 0
        assert reg.counter("learner/ring_stage_bytes").value == 0
        assert reg.counter("learner/donated_batches").value == 3
        assert reg.timer("learner/device_put").calls == 3

    def test_h2d_overlap_telemetry_populated(self):
        """The donated path's H2D dispatch is a `learner/h2d` span a batch,
        which `perf/report.py` scores against compute; each version the
        step loop publishes observes the landing's three timers once."""
        import time

        from torched_impala_tpu.perf import analyze_records
        from torched_impala_tpu.telemetry.tracing import get_recorder

        since = time.monotonic_ns()
        _, reg, _ = _run_ring(donate=True)
        records = [r for r in get_recorder().tail() if r[0] >= since]
        assert sum(r[3] == "learner/h2d" for r in records) == 4
        learner = analyze_records(records)["learner"]
        assert learner["h2d_total_s"] > 0
        assert 0.0 <= learner["h2d_overlap_frac"] <= 1.0
        for name in ("landing_wait", "landing_read", "landing_write"):
            assert reg.timer(f"learner/{name}").calls == 4

    def test_donate_rejects_unsupported_combos(self):
        from torched_impala_tpu.replay import ReplayConfig

        common = dict(
            agent=_agent(),
            optimizer=optax.sgd(1e-2),
            example_obs=np.zeros((4,), np.float32),
            rng=jax.random.key(0),
        )
        with pytest.raises(ValueError, match="donate_batch"):
            Learner(
                config=LearnerConfig(
                    batch_size=2,
                    unroll_length=3,
                    traj_ring=True,
                    donate_batch=True,
                    replay=ReplayConfig(
                        max_reuse=2, target_update_interval=1
                    ),
                ),
                **common,
            )

    def test_mesh_feed_parity_with_single_device(self):
        """Sharded-vs-single-device feed parity (ISSUE 15): the same
        seeded run through a 2-device CPU mesh produces allclose losses
        for 3 steps — the per-shard placement is the same batch, same
        math, just partitioned."""
        from torched_impala_tpu.parallel import make_mesh

        _, _, single = _run_ring(donate=False, n=3)
        mesh = make_mesh(num_data=2, devices=jax.devices("cpu")[:2])
        _, reg, meshed = _run_ring(donate=False, n=3, mesh=mesh)
        np.testing.assert_allclose(single, meshed, rtol=1e-4)
        # the mesh learner's versions land as any other's: once a
        # version, each part timed
        assert reg.timer("learner/device_put").calls == 3
        for name in ("landing_wait", "landing_read", "landing_write"):
            assert reg.timer(f"learner/{name}").calls == 3

    def test_mesh_donated_ring_zero_staging(self):
        """Under the mesh learner the donated ring path stages ZERO
        bytes host-side (the acceptance gauge: learner/ring_stage_bytes
        == 0) and every batch is donated into the pjit step."""
        from torched_impala_tpu.parallel import make_mesh

        mesh = make_mesh(num_data=2, devices=jax.devices("cpu")[:2])
        _, reg, losses = _run_ring(donate=True, n=3, mesh=mesh)
        assert len(losses) == 3
        assert reg.counter("learner/ring_stage_bytes").value == 0
        assert reg.counter("learner/donated_batches").value == 3

    def test_mesh_ring_places_every_shard_on_its_own_device(self):
        """All 8 virtual devices as one data mesh, donated ring, B=8:
        nothing is staged host-side, every batch fed is donated, and the
        batch the step is handed has one row on each device."""
        from torched_impala_tpu.parallel import make_mesh

        devices = jax.devices("cpu")
        assert len(devices) == 8  # tests/conftest.py
        T, B = 3, 8

        def one_row_per_device(arrays):
            obs = arrays[0]  # [T+1, B, ...]
            assert obs.shape == (T + 1, B, 4)
            shards = obs.addressable_shards
            assert {s.device for s in shards} == set(devices)
            assert all(s.data.shape == (T + 1, 1, 4) for s in shards)
            assert sorted(s.index[1].start for s in shards) == list(
                range(B)
            )

        _, reg, losses = _run_ring(
            donate=True,
            n=3,
            T=T,
            B=B,
            E=4,
            mesh=make_mesh(num_data=8, devices=devices),
            inspect=one_row_per_device,
        )
        assert len(losses) == 3
        assert reg.counter("learner/ring_stage_bytes").value == 0
        assert reg.counter("learner/donated_batches").value == 3
        assert reg.timer("learner/device_put").calls == 3

    def test_mesh_donation_reuses_slot_backing_stores(self):
        """Donation aliasing under pjit: a sharded batch assembled by
        place_batch from per-shard puts is consumed by the donating
        step — the global array's buffers are handed to XLA (deleted
        after the call), so ring slot backing stores feed the step with
        no intermediate copy and recycle for the next batch."""
        from torched_impala_tpu.parallel import make_mesh
        from torched_impala_tpu.parallel import multihost, spec_layout

        mesh = make_mesh(num_data=2, devices=jax.devices("cpu")[:2])
        sh = spec_layout.feed_shardings(mesh)[0]  # obs: [T+1, B, ...]
        slot = np.ones((4, 2, 3), np.float32)  # stands in for a ring slot
        placed = multihost.place_batch(sh, slot)
        assert len(placed.sharding.device_set) == 2

        step = jax.jit(
            lambda x: x * 2.0,
            donate_argnums=(0,),
            in_shardings=sh,
            out_shardings=sh,
        )
        out = step(placed)
        assert placed.is_deleted()  # buffers donated into the step
        np.testing.assert_array_equal(np.asarray(out), slot * 2.0)
        # The ring slot itself (host numpy) is untouched and reusable.
        np.testing.assert_array_equal(slot, np.ones((4, 2, 3)))

    def test_mesh_replay_and_popart_compose(self):
        """The lifted carve-outs (ISSUE 15): mesh+replay and
        mesh+PopArt+replay train end-to-end instead of being refused at
        config validation."""
        from torched_impala_tpu.ops.popart import PopArtConfig
        from torched_impala_tpu.parallel import make_mesh
        from torched_impala_tpu.replay import ReplayConfig

        mesh = make_mesh(num_data=2, devices=jax.devices("cpu")[:2])
        _, _, l_replay = _run_ring(
            donate=False,
            n=3,
            mesh=mesh,
            replay=ReplayConfig(max_reuse=2, target_update_interval=1),
        )
        assert len(l_replay) == 3 and all(np.isfinite(l_replay))

        _, _, l_both = _run_ring(
            donate=False,
            n=3,
            mesh=mesh,
            replay=ReplayConfig(max_reuse=2, target_update_interval=1),
            popart=PopArtConfig(num_values=2),
        )
        assert len(l_both) == 3 and all(np.isfinite(l_both))

    def test_popart_replay_mesh_matches_single_device(self):
        """PopArt+replay parity across the mesh boundary: the composed
        step is the same math sharded, so the seeded loss trajectory
        matches the single-device run."""
        from torched_impala_tpu.ops.popart import PopArtConfig
        from torched_impala_tpu.parallel import make_mesh
        from torched_impala_tpu.replay import ReplayConfig

        kwargs = dict(
            donate=False,
            n=3,
            replay=ReplayConfig(max_reuse=2, target_update_interval=1),
            popart=PopArtConfig(num_values=2),
        )
        _, _, single = _run_ring(**kwargs)
        mesh = make_mesh(num_data=2, devices=jax.devices("cpu")[:2])
        _, _, meshed = _run_ring(mesh=mesh, **kwargs)
        np.testing.assert_allclose(single, meshed, rtol=1e-4)

    def test_fused_epilogue_popart_guard(self):
        from torched_impala_tpu.ops.popart import PopArtConfig

        agent = Agent(
            ImpalaNet(
                num_actions=2,
                torso=MLPTorso(hidden_sizes=(16,)),
                num_values=2,
            )
        )
        with pytest.raises(ValueError, match="fused_epilogue"):
            Learner(
                agent=agent,
                optimizer=optax.sgd(1e-2),
                config=LearnerConfig(
                    batch_size=2,
                    unroll_length=3,
                    popart=PopArtConfig(num_values=2),
                    loss=ImpalaLossConfig(fused_epilogue=True),
                ),
                example_obs=np.zeros((4,), np.float32),
                rng=jax.random.key(0),
            )


def _loss_inputs(T=6, B=4, A=5, seed=0):
    rng = np.random.default_rng(seed)
    return dict(
        target_logits=jnp.asarray(
            rng.normal(size=(T, B, A)), dtype=jnp.float32
        ),
        behaviour_logits=jnp.asarray(
            rng.normal(size=(T, B, A)), dtype=jnp.float32
        ),
        values=jnp.asarray(rng.normal(size=(T, B)), dtype=jnp.float32),
        bootstrap_value=jnp.asarray(
            rng.normal(size=(B,)), dtype=jnp.float32
        ),
        actions=jnp.asarray(rng.integers(0, A, size=(T, B))),
        rewards=jnp.asarray(rng.normal(size=(T, B)), dtype=jnp.float32),
        discounts=jnp.full((T, B), 0.99, dtype=jnp.float32),
        mask=jnp.asarray(
            (rng.random((T, B)) > 0.2).astype(np.float32)
        ),
    )


def _value_and_grads(config, inputs):
    def f(tl, v):
        out = losses_lib.impala_loss(
            **{**inputs, "target_logits": tl, "values": v}, config=config
        )
        return out.total, out.logs

    (total, logs), grads = jax.jit(
        jax.value_and_grad(f, argnums=(0, 1), has_aux=True)
    )(inputs["target_logits"], inputs["values"])
    return total, logs, grads


class TestFusedEpilogue:
    @pytest.mark.parametrize("reduction", ["sum", "mean"])
    def test_f32_parity_with_separate_path(self, reduction):
        """At f32 the fused epilogue is the same math reassociated:
        loss, both gradients, and every log key match to float
        tolerance."""
        inputs = _loss_inputs()
        ts, logs_s, gs = _value_and_grads(
            ImpalaLossConfig(reduction=reduction), inputs
        )
        tf, logs_f, gf = _value_and_grads(
            ImpalaLossConfig(reduction=reduction, fused_epilogue=True),
            inputs,
        )
        np.testing.assert_allclose(float(ts), float(tf), rtol=1e-5)
        for a, b in zip(gs, gf):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6
            )
        assert set(logs_s) == set(logs_f)
        for k in logs_s:
            np.testing.assert_allclose(
                float(logs_s[k]), float(logs_f[k]), rtol=1e-4, atol=1e-5
            )

    def test_kernel_interpret_matches_xla(self):
        from torched_impala_tpu.ops.vtrace_pallas import fused_vtrace_loss

        inputs = _loss_inputs(seed=1)
        cfg = ImpalaLossConfig(fused_epilogue=True)
        out_x = fused_vtrace_loss(**inputs, config=cfg, implementation="xla")
        out_k = fused_vtrace_loss(
            **inputs, config=cfg, implementation="kernel"
        )
        np.testing.assert_allclose(
            float(out_x.total), float(out_k.total), rtol=1e-5
        )
        for k in out_x.logs:
            np.testing.assert_allclose(
                float(out_x.logs[k]),
                float(out_k.logs[k]),
                rtol=1e-4,
                atol=1e-5,
            )

    def test_bf16_parity_gate(self):
        """bf16 runs only the [T, B, A] softmax/elementwise phase at
        half precision (recursion + reductions stay f32). Gate: loss
        within 2e-2 relative of the f32 separate path, and the greedy
        action after one SGD step on the logits is unchanged for >= 99%
        of (t, b) positions."""
        inputs = _loss_inputs(T=16, B=8, A=6, seed=2)
        ts, _, gs = _value_and_grads(ImpalaLossConfig(), inputs)
        t16, _, g16 = _value_and_grads(
            ImpalaLossConfig(
                fused_epilogue=True, train_dtype="bfloat16"
            ),
            inputs,
        )
        rel = abs(float(t16) - float(ts)) / max(abs(float(ts)), 1e-8)
        assert rel < 2e-2, rel
        lr = 0.1
        z_f32 = np.asarray(inputs["target_logits"] - lr * gs[0])
        z_b16 = np.asarray(inputs["target_logits"] - lr * g16[0])
        agree = np.mean(z_f32.argmax(-1) == z_b16.argmax(-1))
        assert agree >= 0.99, agree

    def test_flag_off_never_enters_fused_path(self, monkeypatch):
        """fused_epilogue=False must take the exact pre-existing code
        path — it may not even import the fused entry point."""
        import torched_impala_tpu.ops.vtrace_pallas as vp

        def boom(**kwargs):
            raise AssertionError("fused path entered with flag off")

        monkeypatch.setattr(vp, "fused_vtrace_loss", boom)
        inputs = _loss_inputs(seed=3)
        total, logs, _ = _value_and_grads(ImpalaLossConfig(), inputs)
        assert np.isfinite(float(total)) and "pg_loss" in logs

    def test_validates_dtype_and_implementation(self):
        inputs = _loss_inputs(seed=4)
        with pytest.raises(ValueError, match="train_dtype"):
            _value_and_grads(
                ImpalaLossConfig(
                    fused_epilogue=True, train_dtype="float16"
                ),
                inputs,
            )
        from torched_impala_tpu.ops.vtrace_pallas import fused_vtrace_loss

        with pytest.raises(ValueError, match="implementation"):
            fused_vtrace_loss(
                **inputs,
                config=ImpalaLossConfig(fused_epilogue=True),
                implementation="cuda",
            )
