"""Process-count-agnostic distributed training harness.

One spec, N controllers: `DistSpec` is a JSON-serializable description
of a small end-to-end training run (env, model, learner knobs, chaos
plan, checkpoint policy). `run_host` executes it inside ONE process —
whatever `jax.process_count()` says, it builds the global mesh through
`multihost.global_mesh`, runs its own actor fleet + env pool +
(optionally) traj_ring, feeds only its addressable shards via
`place_batch`, and reports a structured result line. `launch_cluster`
runs the same spec as an N-process simulated pod on CPU
(parallel/simhost.py), and `launch_with_recovery` adds the pod failure
model on top: when any host dies (e.g. the `kill_host` chaos fault's
SIGKILL), the survivors are torn down and the WHOLE cluster restarts
from the newest async checkpoint — host-granular failure, job-granular
recovery, which is how jax multi-controller pods actually fail
(docs/MULTIHOST.md "failure model").

The same module doubles as the worker entrypoint:

    python -m torched_impala_tpu.runtime.distributed --spec run.json

with host identity carried by the IMPALA_COORDINATOR/NUM_HOSTS/HOST_ID
environment triple (`multihost.bootstrap`). Single-process invocation
(no triple in the env) runs the identical program on one host — the
process-count-agnostic property the tier-1 parity test pins.

Used by: tests/test_multihost.py (2-process vs 1-process loss-trajectory
parity, the kill_host recovery scenario) and doctor's multihost row.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile
from typing import Any, Dict, List, Optional, Tuple


@dataclasses.dataclass
class DistSpec:
    """One distributed training run, JSON round-trippable.

    `batch_size` is GLOBAL: each host contributes
    batch_size / num_hosts unrolls per step regardless of N — holding
    this fixed while varying `num_hosts` is what makes 1-vs-2-host loss
    trajectories comparable (same global batch semantics).
    """

    num_hosts: int = 2
    devices_per_host: int = 1
    num_data: Optional[int] = None  # mesh data-axis size; None = all devices
    num_model: int = 1
    total_steps: int = 4
    batch_size: int = 4  # GLOBAL batch, split across hosts
    unroll_length: int = 5
    num_actors: int = 1
    envs_per_actor: int = 1
    seed: int = 0
    # Model: ImpalaNet over an MLP torso (vector obs).
    obs_dim: int = 4
    num_actions: int = 3
    hidden_sizes: Tuple[int, ...] = (16,)
    # Env: "fake" (FakeDiscreteEnv, shape/throughput only) or "signal"
    # (VectorSignalEnv, genuine learning signal for return targets).
    env: str = "fake"
    episode_len: int = 8
    # Optimizer.
    optimizer: str = "sgd"
    learning_rate: float = 1e-2
    entropy_cost: Optional[float] = None
    # Learner knobs forwarded into LearnerConfig via dataclasses.replace
    # (e.g. {"traj_ring": true, "donate_batch": true}).
    learner_overrides: Dict[str, Any] = dataclasses.field(
        default_factory=dict
    )
    # Resilience.
    checkpoint_dir: str = ""
    checkpoint_interval: int = 0
    resume: bool = False
    chaos: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    chaos_host: int = 0  # only this host arms the chaos plan
    log_every: int = 1
    actor_mode: str = "thread"
    # "train" = full actor/env/learner path (run_host). "feed_parity" =
    # actorless deterministic feed (run_feed_parity): every trajectory is
    # a pure function of (step, global_slot), so the global batch a step
    # consumes is bit-identical at ANY host count — the lever behind the
    # tier-1 1-vs-2-process loss-trajectory parity test.
    mode: str = "train"

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "DistSpec":
        raw = json.loads(text)
        known = {f.name for f in dataclasses.fields(cls)}
        raw = {k: v for k, v in raw.items() if k in known}
        if "hidden_sizes" in raw:
            raw["hidden_sizes"] = tuple(raw["hidden_sizes"])
        return cls(**raw)

    def fingerprint(self) -> str:
        """Stable config hash for manifest-guarded resume. Host count is
        EXCLUDED on purpose: an N-host checkpoint must be restorable into
        an M-host run of the same training config (resume-under-host-
        turnover); the manifest's own host_count field carries the
        topology for the divisibility check instead."""
        import hashlib

        core = dataclasses.asdict(self)
        for topo_key in ("num_hosts", "devices_per_host", "chaos",
                         "chaos_host", "resume"):
            core.pop(topo_key, None)
        return hashlib.sha256(
            json.dumps(core, sort_keys=True).encode()
        ).hexdigest()[:16]


class SpecEnvFactory:
    """Picklable seed -> env factory (process actors cross a pickle
    boundary; loop.train offsets seeds per host, so no host logic here)."""

    def __init__(self, env: str, obs_dim: int, num_actions: int,
                 episode_len: int):
        self.env = env
        self.obs_dim = obs_dim
        self.num_actions = num_actions
        self.episode_len = episode_len

    def __call__(self, seed: int, env_index=None):
        from torched_impala_tpu.envs import FakeDiscreteEnv, VectorSignalEnv

        if self.env == "signal":
            return VectorSignalEnv(
                num_actions=self.num_actions,
                episode_len=self.episode_len,
                seed=seed,
            )
        return FakeDiscreteEnv(
            obs_shape=(self.obs_dim,),
            num_actions=self.num_actions,
            seed=seed,
        )


def example_obs(spec: DistSpec):
    import numpy as np

    dim = spec.num_actions if spec.env == "signal" else spec.obs_dim
    return np.zeros((dim,), np.float32)


def run_host(spec: DistSpec) -> Dict[str, Any]:
    """Execute the spec in THIS process (one host of process_count()).

    Returns the structured payload that the worker main prints as a
    SIMHOST_RESULT line: per-step losses, steps/frames, publish version,
    per-host telemetry labels, episode returns — what the cluster-side
    callers assert on.
    """
    import dataclasses as _dc

    import jax
    import numpy as np
    import optax

    from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
    from torched_impala_tpu.parallel import multihost
    from torched_impala_tpu.runtime.learner import LearnerConfig
    from torched_impala_tpu.runtime.loop import train

    topo = multihost.topology()
    mesh = multihost.global_mesh(
        num_data=spec.num_data, num_model=spec.num_model
    )
    agent = Agent(
        ImpalaNet(
            num_actions=spec.num_actions,
            torso=MLPTorso(hidden_sizes=tuple(spec.hidden_sizes)),
        )
    )
    lcfg = LearnerConfig(
        batch_size=spec.batch_size,
        unroll_length=spec.unroll_length,
    )
    if spec.entropy_cost is not None:
        lcfg = _dc.replace(
            lcfg,
            loss=_dc.replace(lcfg.loss, entropy_coef=spec.entropy_cost),
        )
    if spec.learner_overrides:
        lcfg = _dc.replace(lcfg, **spec.learner_overrides)
    optimizer = (
        optax.adam(spec.learning_rate)
        if spec.optimizer == "adam"
        else optax.sgd(spec.learning_rate)
    )

    async_ck = None
    if spec.checkpoint_dir:
        from torched_impala_tpu.resilience import AsyncCheckpointer

        async_ck = AsyncCheckpointer(
            spec.checkpoint_dir,
            keep=3,
            interval_steps=max(1, spec.checkpoint_interval),
            config_hash=spec.fingerprint(),
        )

    chaos = None
    if spec.chaos and topo.process_index == spec.chaos_host:
        from torched_impala_tpu.resilience import ChaosInjector, ChaosPlan

        chaos = ChaosInjector(ChaosPlan.from_dicts(spec.chaos))

    from torched_impala_tpu.telemetry import get_aggregator

    losses: List[float] = []
    versions: List[int] = []
    proc_labels: set = set()

    def logger(logs):
        if "total_loss" in logs:
            losses.append(float(logs["total_loss"]))
        if "param_version" in logs:
            versions.append(int(logs["param_version"]))
        # Sample the fan-in lanes while the pool is alive: aggregated
        # keys carry the per-host label grammar proc<h>w<w>/ whose h
        # must be THIS host's process index (the multi-host telemetry
        # satellite's observable).
        for key in get_aggregator().aggregated_snapshot({}):
            parts = key.split("/")
            if len(parts) >= 2 and parts[0] == "telemetry":
                if parts[1].startswith("proc"):
                    proc_labels.add(parts[1])

    result = train(
        agent=agent,
        env_factory=SpecEnvFactory(
            spec.env, spec.obs_dim, spec.num_actions, spec.episode_len
        ),
        example_obs=example_obs(spec),
        num_actors=spec.num_actors,
        learner_config=lcfg,
        optimizer=optimizer,
        total_steps=spec.total_steps,
        seed=spec.seed,
        logger=logger,
        log_every=spec.log_every,
        mesh=mesh,
        async_checkpointer=async_ck,
        resume="auto" if spec.resume else False,
        config_hash=spec.fingerprint(),
        chaos=chaos,
        envs_per_actor=spec.envs_per_actor,
        actor_mode=spec.actor_mode,
    )
    if async_ck is not None:
        async_ck.wait()
        async_ck.close()

    returns = [r for _, r, _ in result.episode_returns]
    payload: Dict[str, Any] = {
        "host": topo.process_index,
        "process_count": topo.process_count,
        "local_devices": topo.local_device_count,
        "global_devices": topo.global_device_count,
        "steps": int(result.learner.num_steps),
        "num_frames": int(result.num_frames),
        "losses": [round(x, 10) for x in losses],
        "publish_version": int(result.learner.param_store.version),
        "local_batch_size": int(result.learner._local_batch_size),
        "episode_return_mean_tail": (
            float(np.mean(returns[-20:])) if returns else None
        ),
        "episodes": len(returns),
        "proc_labels": sorted(proc_labels),
    }
    return payload


def run_feed_parity(spec: DistSpec) -> Dict[str, Any]:
    """Actorless deterministic feed: the process-count-agnostic proof.

    Each host builds the same global mesh and learner as `run_host`, but
    instead of actors it enqueues synthetic trajectories that are pure
    functions of (step, global_slot), covering ONLY its own slots
    [h*B_local, (h+1)*B_local). The global batch assembled on the mesh
    data axis at step s is therefore identical whether one process owns
    all slots or N processes own B/N each — so the per-step loss
    trajectories must agree across host counts up to collective
    summation order (the tier-1 parity test's rtol gate). Divergence
    here means the feed plane is NOT topology-transparent: wrong shard
    placement, wrong slot->host mapping, or a gradient reduction that
    isn't averaging over the full global batch.
    """
    import jax
    import numpy as np
    import optax

    from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
    from torched_impala_tpu.parallel import multihost
    from torched_impala_tpu.runtime.learner import Learner, LearnerConfig
    from torched_impala_tpu.runtime.types import Trajectory

    topo = multihost.topology()
    mesh = multihost.global_mesh(
        num_data=spec.num_data, num_model=spec.num_model
    )
    T, B = spec.unroll_length, spec.batch_size
    if B % topo.process_count:
        raise ValueError(
            f"global batch {B} not divisible by {topo.process_count} hosts"
        )
    b_local = B // topo.process_count
    dim = spec.obs_dim
    acts = spec.num_actions

    def traj(step: int, slot: int) -> Trajectory:
        rng = np.random.default_rng(100_000 + 1_000 * step + slot)
        return Trajectory(
            obs=rng.normal(size=(T + 1, dim)).astype(np.float32),
            first=np.zeros((T + 1,), np.bool_),
            actions=rng.integers(0, acts, size=(T,)).astype(np.int32),
            behaviour_logits=rng.normal(size=(T, acts)).astype(np.float32),
            rewards=rng.normal(size=(T,)).astype(np.float32),
            cont=np.ones((T,), np.float32),
            agent_state=(),
            actor_id=topo.process_index,
            param_version=0,
            task=0,
        )

    losses: List[float] = []

    def logger(logs):
        if "total_loss" in logs:
            losses.append(float(logs["total_loss"]))

    learner = Learner(
        agent=Agent(
            ImpalaNet(
                num_actions=acts,
                torso=MLPTorso(hidden_sizes=tuple(spec.hidden_sizes)),
            )
        ),
        optimizer=optax.sgd(spec.learning_rate),
        config=LearnerConfig(
            batch_size=B, unroll_length=T, log_interval=1
        ),
        example_obs=np.zeros((dim,), np.float32),
        rng=jax.random.key(spec.seed),
        mesh=mesh,
        logger=logger,
    )
    learner.start()
    try:
        for step in range(spec.total_steps):
            for i in range(b_local):
                learner.enqueue(traj(step, topo.process_index * b_local + i))
            learner.step_once(timeout=120)
    finally:
        learner.stop()

    return {
        "host": topo.process_index,
        "process_count": topo.process_count,
        "mode": "feed_parity",
        "steps": spec.total_steps,
        "losses": [round(x, 6) for x in losses],
    }


# ---------------------------------------------------------------- cluster


def launch_cluster(spec: DistSpec, *, timeout: float = 300.0):
    """Run the spec as `spec.num_hosts` simulated host processes.

    Returns the simhost ClusterResult; per-host payloads via
    `[h.results()[-1] for h in res.hosts]` when `res.ok`.
    """
    from torched_impala_tpu.parallel import simhost

    with tempfile.NamedTemporaryFile(
        "w", suffix=".json", prefix="distspec_", delete=False
    ) as f:
        f.write(spec.to_json())
        path = f.name
    try:
        return simhost.launch(
            [
                sys.executable,
                "-m",
                "torched_impala_tpu.runtime.distributed",
                "--spec",
                path,
            ],
            spec.num_hosts,
            devices_per_host=spec.devices_per_host,
            timeout=timeout,
        )
    finally:
        try:
            os.unlink(path)
        except OSError:
            pass


def launch_with_recovery(
    spec: DistSpec, *, max_restarts: int = 2, timeout: float = 300.0
):
    """Pod failure model: restart the whole cluster until a clean finish.

    Requires `spec.checkpoint_dir` (the survivors' progress lives in the
    async checkpoints; everything in dead processes' memory — including
    any traj_ring slot that was mid-commit when the SIGKILL landed — is
    gone, which is precisely why torn-slot discard on restart matters).
    Restarted attempts run with resume=True and the chaos plan DISARMED
    (the fault already fired; a real operator doesn't re-inject it).
    Returns (final ClusterResult, attempts list).
    """
    if not spec.checkpoint_dir:
        raise ValueError("launch_with_recovery needs spec.checkpoint_dir")
    attempts = []
    current = spec
    for attempt in range(max_restarts + 1):
        res = launch_cluster(current, timeout=timeout)
        attempts.append(res)
        if res.ok:
            return res, attempts
        current = dataclasses.replace(current, resume=True, chaos=[])
    return attempts[-1], attempts


def main(argv: Optional[List[str]] = None) -> int:
    """Worker entrypoint (one simulated or real host)."""
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--spec", required=True, help="path to DistSpec json")
    args = parser.parse_args(argv)

    with open(args.spec) as f:
        spec = DistSpec.from_json(f.read())

    from torched_impala_tpu.parallel import multihost, simhost

    multihost.bootstrap()
    if spec.mode == "feed_parity":
        payload = run_feed_parity(spec)
    else:
        payload = run_host(spec)
    simhost.emit_result(payload)
    return 0


if __name__ == "__main__":
    sys.exit(main())
