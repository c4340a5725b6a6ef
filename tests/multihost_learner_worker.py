"""Worker for tests/test_multihost.py: one 'host' of a 2-process learner.

Launched twice (process_id 0 and 1). Each process gets 4 virtual CPU
devices; jax.distributed joins them into one 8-device global mesh. Each
host contributes its local half of the global batch; the donated pjit train
step then runs as one SPMD program across both processes — the gradient
all-reduce crosses the process boundary exactly the way it crosses hosts
on a real pod. Both processes must print the identical global loss.

Usage: python tests/multihost_learner_worker.py <process_id> <port>
"""

import os
import sys

# Scripts get their own dir (tests/) on sys.path, not the repo root; add it
# (sys.path, so the worker needs nothing from the caller's environment).
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

os.environ["JAX_PLATFORMS"] = "cpu"
# FORCE 4 devices per process, replacing any inherited count (pytest's
# conftest exports ...device_count=8 into the environment it spawns from).
_flags = [
    f
    for f in os.environ.get("XLA_FLAGS", "").split()
    if "xla_force_host_platform_device_count" not in f
]
os.environ["XLA_FLAGS"] = " ".join(
    _flags + ["--xla_force_host_platform_device_count=4"]
)

import jax

jax.config.update("jax_platforms", "cpu")


def main() -> None:
    process_id, port = int(sys.argv[1]), int(sys.argv[2])

    from torched_impala_tpu.parallel import multihost

    multihost.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=2,
        process_id=process_id,
    )
    assert multihost.process_count() == 2
    assert len(jax.devices()) == 8 and len(jax.local_devices()) == 4

    import numpy as np
    import optax

    from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
    from torched_impala_tpu.parallel import make_mesh
    from torched_impala_tpu.runtime.learner import Learner, LearnerConfig
    from torched_impala_tpu.runtime.types import Trajectory

    T, B_global = 5, 8
    mesh = make_mesh(num_data=8)
    agent = Agent(ImpalaNet(num_actions=3, torso=MLPTorso()))
    learner = Learner(
        agent=agent,
        optimizer=optax.sgd(1e-2),
        config=LearnerConfig(batch_size=B_global, unroll_length=T),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
        mesh=mesh,
    )
    assert learner._local_batch_size == 4

    # Each host contributes 4 deterministic, host-distinct unrolls.
    for i in range(4):
        rng = np.random.default_rng(1000 * process_id + i)
        learner.enqueue(
            Trajectory(
                obs=rng.normal(size=(T + 1, 4)).astype(np.float32),
                first=np.zeros((T + 1,), np.bool_),
                actions=rng.integers(0, 3, size=(T,)).astype(np.int32),
                behaviour_logits=rng.normal(size=(T, 3)).astype(np.float32),
                rewards=rng.normal(size=(T,)).astype(np.float32),
                cont=np.ones((T,), np.float32),
                agent_state=(),
                actor_id=process_id,
                param_version=0,
                task=0,
            )
        )
    learner.start()
    logs = learner.step_once(timeout=300)
    learner.stop()
    loss = float(logs["total_loss"])
    assert np.isfinite(loss)
    for leaf in jax.tree.leaves(learner.params):
        assert leaf.sharding.is_fully_replicated
    print(f"RESULT process={process_id} loss={loss:.10f}", flush=True)

    # Phase 2: the FULL train() loop across both controllers — each host
    # runs its own actor fleet (seeds offset by jax.process_index(), so the
    # hosts contribute DISTINCT trajectories to the global batch), its own
    # batcher, and the shared SPMD learner program. Both controllers must
    # report the same global loss.
    from torched_impala_tpu.envs import FakeDiscreteEnv
    from torched_impala_tpu.runtime.loop import train

    def env_factory(seed, env_index=None):
        return FakeDiscreteEnv(obs_shape=(4,), num_actions=3, seed=seed)

    seen_seeds = []

    def recording_factory(seed, env_index=None):
        seen_seeds.append(seed)
        return env_factory(seed, env_index)

    step_losses = []

    def logger(logs):
        step_losses.append(float(logs["total_loss"]))

    result = train(
        agent=Agent(ImpalaNet(num_actions=3, torso=MLPTorso())),
        env_factory=recording_factory,
        example_obs=np.zeros((4,), np.float32),
        num_actors=2,
        learner_config=LearnerConfig(batch_size=B_global, unroll_length=T),
        optimizer=optax.sgd(1e-2),
        total_steps=3,
        seed=0,
        logger=logger,
        log_every=1,
        mesh=mesh,
    )
    assert result.learner.num_steps == 3
    # Host-distinct actor seeds (the multi-host duplicate-data fix).
    expected_base = 1000 * (2 * process_id + 1)
    assert all(s >= expected_base for s in seen_seeds), (
        process_id,
        seen_seeds,
    )
    print(
        f"RESULT2 process={process_id} loss={step_losses[-1]:.10f} "
        f"seeds={sorted(set(seen_seeds))}",
        flush=True,
    )

    # Phase 3: fused dispatch across controllers — each host feeds K=2
    # local batch slices; multihost.place_batch assembles the [K, T+1,
    # B_global, ...] superbatch from host-local [K, T+1, B_local, ...]
    # slices and ONE SPMD program scans both SGD steps. Same global loss
    # on both controllers, num_steps advances by K.
    K = 2
    fused = Learner(
        agent=Agent(ImpalaNet(num_actions=3, torso=MLPTorso())),
        optimizer=optax.sgd(1e-2),
        config=LearnerConfig(
            batch_size=B_global,
            unroll_length=T,
            steps_per_dispatch=K,
            queue_capacity=K * 4,
        ),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
        mesh=mesh,
    )
    for i in range(K * 4):
        rng = np.random.default_rng(1000 * process_id + i)
        fused.enqueue(
            Trajectory(
                obs=rng.normal(size=(T + 1, 4)).astype(np.float32),
                first=np.zeros((T + 1,), np.bool_),
                actions=rng.integers(0, 3, size=(T,)).astype(np.int32),
                behaviour_logits=rng.normal(size=(T, 3)).astype(np.float32),
                rewards=rng.normal(size=(T,)).astype(np.float32),
                cont=np.ones((T,), np.float32),
                agent_state=(),
                actor_id=process_id,
                param_version=0,
                task=0,
            )
        )
    fused.start()
    fused_logs = fused.step_once(timeout=300)
    fused.stop()
    assert fused.num_steps == K
    print(
        f"RESULT3 process={process_id} "
        f"loss={float(fused_logs['total_loss']):.10f}",
        flush=True,
    )

    # Phase 4: DP x TP across controllers (VERDICT r3 item 9) — a global
    # (data=4, model=2) mesh over the same 8 devices. Device order is
    # process-major, so reshape(4, 2) keeps each model pair process-LOCAL
    # (rows 0-1 on process 0, rows 2-3 on process 1): TP collectives stay
    # intra-host the way they ride intra-host ICI on a pod, while the DP
    # gradient all-reduce crosses the process boundary. Weight matrices
    # must come out genuinely model-sharded, and the loss must match the
    # phase-1 DP-only run on the identical global batch (the same
    # single-host invariance test_parallel pins, now under
    # jax.distributed).
    tp_mesh = make_mesh(num_data=4, num_model=2)
    tp = Learner(
        agent=Agent(ImpalaNet(num_actions=3, torso=MLPTorso())),
        optimizer=optax.sgd(1e-2),
        config=LearnerConfig(batch_size=B_global, unroll_length=T),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
        mesh=tp_mesh,
    )
    assert tp._local_batch_size == 4
    sharded = sum(
        1
        for leaf in jax.tree.leaves(tp.params)
        if leaf.ndim >= 2 and not leaf.sharding.is_fully_replicated
    )
    assert sharded > 0, "no weight leaf is model-sharded on the 4x2 mesh"
    for i in range(4):
        rng = np.random.default_rng(1000 * process_id + i)
        tp.enqueue(
            Trajectory(
                obs=rng.normal(size=(T + 1, 4)).astype(np.float32),
                first=np.zeros((T + 1,), np.bool_),
                actions=rng.integers(0, 3, size=(T,)).astype(np.int32),
                behaviour_logits=rng.normal(size=(T, 3)).astype(np.float32),
                rewards=rng.normal(size=(T,)).astype(np.float32),
                cont=np.ones((T,), np.float32),
                agent_state=(),
                actor_id=process_id,
                param_version=0,
                task=0,
            )
        )
    tp.start()
    tp_logs = tp.step_once(timeout=300)
    tp.stop()
    print(
        f"RESULT4 process={process_id} "
        f"loss={float(tp_logs['total_loss']):.10f} sharded={sharded}",
        flush=True,
    )


if __name__ == "__main__":
    main()
