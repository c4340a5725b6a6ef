"""Experiment wiring: N actor threads + one learner (SURVEY.md §4.1).

`train()` is the single-host orchestration entry: build agent + learner,
spawn actor threads against an env factory, run the learner for a step
budget, and return learning statistics. The CLI (`run.py`) and the smoke
tests both drive this function.
"""

from __future__ import annotations

import collections
import dataclasses
import sys
import threading
from typing import Any, Callable, Mapping, Optional

import jax
import numpy as np
import optax

from torched_impala_tpu.models.agent import Agent
from torched_impala_tpu.runtime.actor import Actor
from torched_impala_tpu.runtime.learner import Learner, LearnerConfig
from torched_impala_tpu.runtime.supervisor import ActorSupervisor
from torched_impala_tpu.runtime.vector_actor import VectorActor
from torched_impala_tpu.telemetry import (
    AlertEngine,
    MetricsExporter,
    StallWatchdog,
    default_slo_specs,
    export_merged_trace,
    get_aggregator,
    get_recorder,
    get_registry,
    install_thread_excepthook,
)


@dataclasses.dataclass
class TrainResult:
    episode_returns: list  # (actor_id, return, length) in completion order
    final_logs: Mapping[str, Any]
    learner: Learner
    num_frames: int
    actor_restarts: int = 0
    actor_device: str = ""  # where actor inference ran (str(Device))


def resolve_actor_device(actor_device: Optional[str] = "cpu"):
    """`(device, note)` for actor inference. `device` is the first LOCAL
    device of the `actor_device` platform; when that platform is not
    enabled in this process (e.g. JAX_PLATFORMS=tpu hides the host CPU)
    or `actor_device` is None, actors share the default backend with the
    learner — `device` is then None and `note` says so, for the start-up
    line: an actor stepping on the learner's chip is a different system
    from one stepping on the host, and must not be entered silently."""
    default = jax.local_devices()[0]
    if actor_device is None:
        return None, f"{default} (default backend; shared with the learner)"
    try:
        # LOCAL devices: under multi-controller (jax.distributed),
        # jax.devices()[0] is GLOBAL device 0 — non-addressable from
        # every other process, so actor inference there dies with
        # "spans non-addressable devices".
        device = jax.local_devices(backend=actor_device)[0]
    except RuntimeError:
        return None, (
            f"{default} (platform {actor_device!r} is not enabled; "
            "actors share the default backend with the learner)"
        )
    return device, str(device)


def train(
    *,
    agent: Agent,
    env_factory: Callable[[int], Any],  # seed -> env (gymnasium API)
    example_obs: np.ndarray,
    num_actors: int,
    learner_config: LearnerConfig,
    optimizer: optax.GradientTransformation,
    total_steps: int,
    seed: int = 0,
    logger: Optional[Callable[[Mapping[str, Any]], None]] = None,
    log_every: int = 50,
    actor_device: Optional[str] = "cpu",
    mesh=None,
    checkpointer=None,
    checkpoint_interval: int = 0,
    resume=False,
    async_checkpointer=None,
    config_hash: Optional[str] = None,
    chaos=None,
    max_actor_restarts: Optional[int] = 10,
    envs_per_actor: int = 1,
    actor_mode: str = "thread",
    pool_mode: str = "lockstep",
    pool_ready_fraction: float = 0.5,
    telemetry_interval: int = 1,
    stall_timeout: float = 0.0,
    on_learner_step: Optional[Callable[[int], None]] = None,
    trace_path: Optional[str] = None,
    perf_report_path: Optional[str] = None,
    control=None,
    metrics_port: Optional[int] = None,
    metrics_file: str = "",
    slo_specs=None,
    postmortem_dir: str = "postmortems",
) -> TrainResult:
    """Run the actor-learner loop until `total_steps` TOTAL learner updates.

    `total_steps` counts from step 0 including any restored progress: a
    resumed run performs only the remainder, keeping the lr schedule and the
    frame budget aligned with a single uninterrupted run.

    `actor_device="cpu"` pins actor inference to a host CPU device, so
    env-paced single-step policy calls don't pay per-step dispatch latency to
    the accelerator the learner owns. Where that platform is not enabled the
    actors share the default backend — `resolve_actor_device` names the
    outcome and `TrainResult.actor_device` records it.

    `mesh` shards the learner over a device mesh (DP; SURVEY.md §3b).
    `checkpointer` (a `utils.Checkpointer`) saves learner state every
    `checkpoint_interval` learner steps and at the end; `resume=True`
    restores the latest checkpoint before training (restoring the
    actor-visible param version too, SURVEY.md §6 checkpoint row).

    Resilience (docs/RESILIENCE.md):
    - `async_checkpointer` (a `resilience.AsyncCheckpointer`) takes over
      the INTERVAL saves: the post-step hook hands an on-device state
      clone to its background writer (atomic tmp+fsync+rename + run
      manifest + retention) so the train loop never blocks on disk; a
      final manifest save lands at clean shutdown. May combine with
      `checkpointer` (orbax then only writes the final checkpoint, which
      keeps `--mode eval` readable).
    - `resume="auto"` (or True) restores the newest state available:
      the async checkpointer's manifests and the orbax dir are compared
      by step and the newer wins. Manifest resume verifies `config_hash`
      (resilience.config_fingerprint of the experiment config) and
      REFUSES a mismatch with a clear error; the learner's `set_state`
      then republishes params at the restored version so actors and the
      trajectory ring resynchronize cleanly.
    - `chaos` (a `resilience.ChaosPlan` or `ChaosInjector`) arms the
      fault-injection harness: its hooks ride the env pools, the actors'
      unroll starts, the trajectory enqueue, the learner post-step, and
      the checkpoint writer (resilience/chaos.py fault table).

    `actor_mode` selects how env stepping escapes Python:
    - "thread": `num_actors` actor threads in this process, each stepping
      `envs_per_actor` envs (fine for tests and small runs; the GIL caps
      env throughput at scale).
    - "process": `num_actors` worker *processes* (runtime/env_pool.py),
      each hosting `envs_per_actor` envs, feeding ONE batched-inference
      actor thread — the reference's multiprocess-actor capability in its
      TPU-native (central-inference) shape. Requires a picklable
      `env_factory`.

    `pool_mode` (process mode only) schedules the worker pool:
    - "lockstep" (default): every inference wave gates on EVERY worker —
      one slow env step stalls the whole pool.
    - "async": ready-set batching — inference runs over whichever
      `pool_ready_fraction` of workers has reported, stragglers catch up
      on the next wave (runtime/env_pool.py async protocol).
      `pool_ready_fraction="auto"` arms the pool's EWMA straggler-rate
      tuner (the fraction tracks the measured straggler rate between
      unrolls; env_pool.AUTO_FRACTION_* constants).

    `learner_config.traj_ring=True` switches the actor->learner edge to
    the zero-copy trajectory ring (runtime/traj_ring.py): actors write
    unrolls straight into shared `[T+1, B, ...]` batch slots, the
    batcher device_puts completed slots with no host stacking — under a
    mesh, one device_put per data-parallel shard sliced straight from
    the slot (parallel/multihost.place_batch; no gather/reshard hop).
    Needs a vectorized actor fleet whose env counts divide batch_size
    (checked at startup).

    Observability (docs/OBSERVABILITY.md):
    - `telemetry_interval=N` merges the global telemetry registry's
      snapshot (`telemetry/actor|pool|queue|learner/*` keys) into every
      Nth logger write; 0 disables the merge (registry still records).
    - `stall_timeout=S` (seconds, 0 = off) arms a stall watchdog: if no
      learner step or actor wave completes for S seconds it dumps every
      thread's stack + the registry snapshot to stderr and emits a
      `telemetry/watchdog/stall` event through the logger, instead of
      letting a wedged run hang silently.
    - `on_learner_step(num_steps)` is called after every learner step
      (and once at startup with the restored step count) — run.py's
      `--profile-steps` window hooks in here.
    - `trace_path="out.json"` exports the flight recorder's retained
      events (telemetry/tracing.py: per-unroll lineage IDs threaded
      env→pool→queue/ring→learner, exact per-batch param lag) as
      Chrome-trace JSON when the run ends — crash- and stop-safe via
      the same finally that tears the pipeline down. Load it in
      Perfetto (docs/OBSERVABILITY.md).
    - `metrics_port` (TCP port, None = off, 0 = ephemeral) serves the
      run-wide AGGREGATED snapshot — local registry + every env-pool
      worker's fan-in under `proc<h>w<w>/` prefixes — as an
      OpenMetrics/Prometheus text endpoint (telemetry/export.py);
      `metrics_file` atomic-writes the same payload for sandboxed runs.
      Either one also arms the SLO burn-rate alert engine
      (telemetry/alerts.py; `slo_specs` overrides the default table),
      whose `alerts/*` gauges ride the same snapshot and whose state
      control policies can consume via `control.AlertSignal`.
    - `learner_config.loss.health_diagnostics=True` stands up the
      training-health plane (telemetry/health.py): in-jit learning
      diagnostics surface as `health/*` gauges, the burn-rate health
      alerts (entropy collapse, rho saturation, EV collapse, grad
      spike) ride the same engine shape, and each alert firing or
      learner crash writes a postmortem bundle under `postmortem_dir`
      (tools/postmortem.py renders the triage report).
    - `perf_report_path="out.json"` runs the performance observatory
      (perf/report.py) over the same retained events at run end:
      inter-train_step gap attribution (feed/H2D/publish/compile/
      unattributed), fresh vs replayed compute, and the cost model's
      roofline — JSON plus a human-readable `.txt` sibling, written in
      the same teardown finally.
    """
    if actor_mode not in ("thread", "process"):
        raise ValueError(f"unknown actor_mode {actor_mode!r}")
    if pool_mode not in ("lockstep", "async"):
        raise ValueError(f"unknown pool_mode {pool_mode!r}")
    # Backstop for thread bodies that (against convention) don't record
    # their own errors: an uncaught background-thread crash lands in
    # telemetry/runtime/thread_crashes + stderr instead of dying silently
    # (telemetry/excepthook.py; idempotent, process-wide).
    install_thread_excepthook()
    device, actor_device_note = resolve_actor_device(actor_device)

    episode_returns: collections.deque = collections.deque(maxlen=10_000)
    returns_lock = threading.Lock()

    def on_episode_return(actor_id: int, ret: float, length: int) -> None:
        with returns_lock:
            episode_returns.append((actor_id, ret, length))

    step_logs: dict = {}
    # Bound after the Learner exists (the supervisor needs the learner's
    # queue); the logger callback may fire before then (e.g. on resume), so
    # guard the reference instead of closing over an unbound name.
    supervisor: Optional[ActorSupervisor] = None
    registry = get_registry()
    # Two writers may now reach `logger`: the learner's log stream (below)
    # and the stall watchdog's event (a stalled run has no learner writes,
    # so the event cannot ride that stream). Loggers are not assumed
    # thread-safe, so both writers serialize on this lock.
    logger_lock = threading.Lock()
    telemetry_writes = [0]

    def learner_logger(logs: Mapping[str, Any]) -> None:
        # Called by the learner every `log_interval` steps with host floats.
        # Schema-dependent loggers (CSV) need a stable key set, so restart
        # telemetry rides this stream instead of the monitor thread's.
        step_logs.update(logs)
        if logger is not None:
            with returns_lock:
                recent = [r for _, r, _ in list(episode_returns)[-100:]]
            merged = dict(logs)
            merged["episode_return_mean"] = (
                float(np.mean(recent)) if recent else float("nan")
            )
            merged["actor_restarts"] = (
                supervisor.restarts if supervisor is not None else 0
            )
            if telemetry_interval > 0:
                telemetry_writes[0] += 1
                if telemetry_writes[0] % telemetry_interval == 0:
                    # The registry snapshot rides the existing write(dict)
                    # surface: every logger backend gets the namespaced
                    # telemetry/<component>/<name> series for free.
                    merged.update(registry.snapshot())
            with logger_lock:
                logger(merged)

    # Chaos harness (resilience/chaos.py): accept a plan or a prebuilt
    # injector; hooks attach to every stage built below.
    injector = None
    if chaos is not None:
        from torched_impala_tpu.resilience.chaos import (
            ChaosInjector,
            ChaosPlan,
        )

        if isinstance(chaos, ChaosInjector):
            injector = chaos
        else:
            plan = chaos if isinstance(chaos, ChaosPlan) else ChaosPlan(chaos)
            injector = ChaosInjector(plan)
        if async_checkpointer is not None:
            async_checkpointer._post_save = injector.checkpoint_hook

    learner = Learner(
        agent=agent,
        optimizer=optimizer,
        config=dataclasses.replace(learner_config, log_interval=log_every),
        example_obs=example_obs,
        rng=jax.random.key(seed),
        logger=learner_logger,
        mesh=mesh,
    )

    # Training-health plane (telemetry/health.py): only stood up when the
    # loss closure actually compiles the health_* diagnostics — otherwise
    # the learner keeps its exact pre-health code path (self._health is
    # None and _finish_step never branches).
    health_monitor = None
    if getattr(learner_config.loss, "health_diagnostics", False):
        from torched_impala_tpu.telemetry.health import (
            HealthMonitor,
            PostmortemWriter,
        )

        health_monitor = HealthMonitor(
            registry=registry,
            postmortem=PostmortemWriter(postmortem_dir or "postmortems"),
        )
        learner.attach_health(health_monitor)
    if resume:
        # Newest state wins across backends: the async checkpointer's
        # manifests (crash-consistent interval saves) vs the orbax dir
        # (final saves of completed runs). Manifest resume is config-
        # hash-guarded (resilience/recovery.py refuses a mismatch).
        restored = None
        restored_step = -1
        if async_checkpointer is not None:
            from torched_impala_tpu.resilience import recovery

            found = recovery.restore_latest(
                async_checkpointer.directory,
                learner.get_state(),
                config_hash=config_hash,
                # Host turnover: an N-host checkpoint restores into this
                # M-host run only while the global batch still divides
                # (recovery.HostCountMismatch names both counts if not).
                host_count=jax.process_count(),
                global_batch_size=learner_config.batch_size,
            )
            if found is not None:
                manifest, restored = found
                restored_step = manifest.step
                print(
                    f"[resume] manifest @ step {manifest.step} "
                    f"(param_version {manifest.param_version}) from "
                    f"{async_checkpointer.directory}",
                    file=sys.stderr,
                    flush=True,
                )
        if checkpointer is not None:
            orbax_step = checkpointer.latest_step()
            if orbax_step is not None and orbax_step > restored_step:
                orbax_restored = checkpointer.restore(learner.get_state())
                if orbax_restored is not None:
                    restored = orbax_restored
        if restored is not None:
            learner.set_state(restored)

    post_hooks: list = []
    if async_checkpointer is not None:
        # Interval saves go through the background writer: the post-step
        # hook only clones state on-device (get_state_device, no host
        # sync) when a save is due — the train loop never blocks on disk.
        def _async_checkpoint_hook(num_steps: int) -> None:
            async_checkpointer.maybe_save(
                num_steps,
                learner.get_state_device,
                param_version=learner.num_frames,
            )

        post_hooks.append(_async_checkpoint_hook)
    elif checkpointer is not None and checkpoint_interval > 0:
        last_saved = [learner.num_steps]

        def _checkpoint_hook(num_steps: int) -> None:
            # Runs on the learner thread, so get_state() sees a consistent
            # (params, opt_state, counters) snapshot.
            if num_steps - last_saved[0] >= checkpoint_interval:
                checkpointer.save(num_steps, learner.get_state())
                last_saved[0] = num_steps

        post_hooks.append(_checkpoint_hook)
    if injector is not None:
        post_hooks.append(injector.learner_hook)
    if on_learner_step is not None:
        post_hooks.append(on_learner_step)
        # Fire once with the CURRENT (possibly restored) step count so a
        # profile window whose start step is already behind us opens at
        # the run's first step instead of never.
        on_learner_step(learner.num_steps)
    if post_hooks:

        def _post_step(num_steps: int) -> None:
            for hook in post_hooks:
                hook(num_steps)

        learner.post_step = _post_step

    # `total_steps` is the TOTAL step budget: a resumed run does only the
    # remainder, so the optax schedule and the frame budget line up.
    remaining_steps = max(0, total_steps - learner.num_steps)

    stop_event = threading.Event()

    # Factories that accept (seed, env_index) get the global env slot so
    # multi-task families can cover every task — task selection must NOT be
    # derived from the seed (seeds stride by 1000 per actor, and
    # gcd(1000, num_tasks) > 1 silently drops tasks).
    from torched_impala_tpu.envs.factory import call_env_factory

    def build_env(seed_: int, env_index: int):
        return call_env_factory(env_factory, seed_, env_index)

    # Multi-host: every controller runs this same function with the same
    # --seed, so actor slots must be offset by the process index or all
    # hosts step IDENTICAL env streams and the global batch holds n copies
    # of the same data (effective batch / n, corrupted gradients).
    # jax.process_index() is 0 when jax.distributed was never initialized.
    host_slot0 = jax.process_index() * num_actors

    env_pools: list = []
    if actor_mode == "process":
        from torched_impala_tpu.runtime.env_pool import ProcessEnvPool

        # Two pools (when there are >= 2 workers), each driven by its own
        # batched-inference thread: while one thread waits on its workers'
        # env steps, the other runs its policy batch — inference and env
        # stepping overlap instead of serializing. Worker slot w keeps
        # global env indices regardless of the split.
        groups = (
            [list(range(num_actors))]
            if num_actors < 2
            else [
                list(range(0, num_actors // 2)),
                list(range(num_actors // 2, num_actors)),
            ]
        )
        try:
            for gi, group in enumerate(groups):
                env_pools.append(
                    ProcessEnvPool(
                        env_factory=env_factory,
                        num_workers=len(group),
                        envs_per_worker=envs_per_actor,
                        obs_shape=example_obs.shape,
                        obs_dtype=example_obs.dtype,
                        base_seed=seed + 1000 * (host_slot0 + group[0]),
                        first_env_index=(host_slot0 + group[0])
                        * envs_per_actor,
                        max_restarts=(
                            max_actor_restarts * len(group)
                            if max_actor_restarts is not None
                            else 1_000_000
                        ),
                        mode=pool_mode,
                        ready_fraction=pool_ready_fraction,
                        # proc<h>w<w> fan-in labels: h = this host's
                        # controller index, w = global worker slot (the
                        # pool derives it from first_env_index).
                        label_host=jax.process_index(),
                    )
                )
        except BaseException:
            # A failed later pool must not leak the earlier pools' worker
            # processes and SharedMemory segments.
            for pool in env_pools:
                pool.close()
            raise

    # Zero-copy trajectory ring (LearnerConfig.traj_ring): actors write
    # unrolls straight into shared learner batch slots instead of
    # enqueueing Trajectories. With LearnerConfig.replay the same ring
    # retains released slots for IMPACT-style reuse (replay/ package) —
    # the divisibility contract below is unchanged because replay only
    # re-delivers already-committed slots. Every actor's env-column
    # block must divide the batch so blocks never straddle a slot — checked HERE, where the
    # actual fleet shapes are known, so a bad combination fails at
    # startup instead of deadlocking the ring.
    traj_ring = learner.traj_ring
    if traj_ring is not None:
        B = learner_config.batch_size
        env_counts = (
            {pool.num_envs for pool in env_pools}
            if env_pools
            else {max(1, envs_per_actor)}
        )
        for E in sorted(env_counts):
            if E > B or B % E:
                raise ValueError(
                    f"traj_ring: actor env count {E} must divide "
                    f"batch_size {B} (each unroll cycle fills whole "
                    f"column blocks of one batch slot)"
                )

    # Chaos wiring: the enqueue seam (wedge_queue) and the per-unroll
    # actor seam ride every actor; the pool seam rides every pool.
    enqueue = learner.enqueue
    actor_chaos = None
    if injector is not None:
        enqueue = injector.wrap_enqueue(learner.enqueue)
        actor_chaos = injector.actor_hook
        for pool in env_pools:
            pool.chaos_hook = injector.pool_hook
        if traj_ring is not None:
            # kill_host seam: commit-time SIGKILL of this simulated host
            # (resilience/chaos.py fault table).
            traj_ring.chaos_hook = injector.ring_commit_hook

    def make_actor(slot: int):
        # Fresh env(s) per (re)spawn: actors are stateless up to the
        # published params, so restart-after-crash just rebuilds the envs.
        base_seed = seed + 1000 * (host_slot0 + slot + 1)
        common = dict(
            actor_id=slot,
            agent=agent,
            param_store=learner.param_store,
            enqueue=enqueue,
            unroll_length=learner_config.unroll_length,
            seed=base_seed,
            on_episode_return=on_episode_return,
            device=device,
            chaos=actor_chaos,
        )
        if env_pools:
            # One batched-inference actor per pool; pools repair their own
            # dead workers, so a supervisor respawn of this actor just
            # re-attaches to the live pool.
            return VectorActor(
                envs=env_pools[slot], traj_ring=traj_ring, **common
            )
        if envs_per_actor > 1 or traj_ring is not None:
            # The ring path needs the vectorized (column-block) writer,
            # so a 1-env thread actor rides VectorActor with E=1.
            return VectorActor(
                envs=[
                    build_env(
                        base_seed + j,
                        (host_slot0 + slot) * envs_per_actor + j,
                    )
                    for j in range(max(1, envs_per_actor))
                ],
                traj_ring=traj_ring,
                **common,
            )
        return Actor(
            env=build_env(base_seed, host_slot0 + slot), **common
        )

    def on_restart(slot: int, error: BaseException) -> None:
        # stderr, not the metrics logger: this runs on the monitor thread.
        print(
            f"[supervisor] restarting actor {slot} "
            f"(restart #{supervisor.restarts}): {error!r}",
            file=sys.stderr,
            flush=True,
        )

    supervisor = ActorSupervisor(
        make_actor=make_actor,
        # Process mode runs one batched-inference thread per pool.
        num_actors=len(env_pools) if env_pools else num_actors,
        stop_event=stop_event,
        max_restarts_per_actor=max_actor_restarts,
        on_restart=on_restart,
    )
    supervisor.start()

    def watchdog() -> None:
        # Called by the learner when no batch arrives for a second. The
        # supervisor restarts crashed actors; fail loudly only when every
        # slot is dead AND no restart can ever revive one (budget spent or
        # clean exits).
        if supervisor.alive_count() == 0 and not supervisor.can_recover():
            errors = supervisor.errors()
            detail = (
                f"first actor error: {errors[0]!r}"
                if errors
                else "no recorded errors"
            )
            raise RuntimeError(
                f"all actor threads are dead and unrecoverable "
                f"({supervisor.restarts} restarts performed); {detail}"
            )

    # Closed-loop control plane (torched_impala_tpu/control/): tunes the
    # hot-applicable runtime knobs from live telemetry on a background
    # thread, every decision audited as control/* telemetry plus a
    # control/decision flight-recorder event. Strictly optional: with
    # `control` None or mode "off" nothing is built and the run is
    # byte-identical to a pre-control-plane run.
    control_loop = None
    if control is not None and getattr(control, "mode", "off") == "auto":
        from torched_impala_tpu.control import build_train_control

        control_loop = build_train_control(
            learner=learner,
            traj_ring=traj_ring,
            checkpointer=async_checkpointer,
            batch_size=learner_config.batch_size,
            steps_per_dispatch=getattr(
                learner_config, "steps_per_dispatch", 1
            ),
            # Per-shard-aware B grid: proposals stay divisible by the
            # mesh's data axis (1 when unmeshed — grid unchanged).
            data_shards=(
                dict(mesh.shape).get("data", 1) if mesh is not None else 1
            ),
            interval_s=control.interval_s,
            tolerance=control.tolerance,
            hysteresis=control.hysteresis,
            cooldown_s=control.cooldown_s,
            checkpoint_overhead_budget=control.checkpoint_overhead_budget,
            allow_recompile=control.allow_recompile,
            recompile_cadence_s=getattr(
                control, "recompile_cadence_s", 300.0
            ),
        )
        control_loop.start()

    # Observability plane (docs/OBSERVABILITY.md): the aggregator folds
    # every env-pool worker's published snapshot into the run-wide view;
    # the exporter serves/writes it as OpenMetrics and ticks the SLO
    # burn-rate alert engine on a steady cadence.
    aggregator = get_aggregator()

    def aggregated_snapshot() -> dict:
        return aggregator.aggregated_snapshot(registry.snapshot())

    alert_engine = None
    metrics_exporter = None
    if metrics_port is not None or metrics_file:
        alert_engine = AlertEngine(
            default_slo_specs() if slo_specs is None else slo_specs,
            registry,
        )
        metrics_exporter = MetricsExporter(
            aggregated_snapshot,
            port=metrics_port,
            path=metrics_file or "",
            alert_engine=alert_engine,
        ).start()
        if metrics_port is not None:
            print(
                f"[metrics] OpenMetrics endpoint on "
                f"http://localhost:{metrics_exporter.port}/metrics",
                file=sys.stderr,
                flush=True,
            )

    stall_watchdog: Optional[StallWatchdog] = None
    if stall_timeout > 0:

        def _on_stall(event: Mapping[str, Any]) -> None:
            # The stack dump already went to stderr (watchdog thread);
            # this pushes the machine-readable event into the metrics
            # stream so dashboards/log scrapers see the stall too.
            if logger is not None:
                with logger_lock:
                    logger(dict(event))

        stall_watchdog = StallWatchdog(
            registry,
            deadline_s=stall_timeout,
            on_stall=_on_stall,
            aggregator=aggregator,
            alert_engine=alert_engine,
        ).start()

    try:
        learner.run(remaining_steps, stop_event, watchdog=watchdog)
    finally:
        if control_loop is not None:
            control_loop.stop()
        if stall_watchdog is not None:
            stall_watchdog.stop()
        if metrics_exporter is not None:
            metrics_exporter.stop()
        stop_event.set()
        learner.stop()
        if perf_report_path:
            try:
                from torched_impala_tpu.perf import generate_report

                cm = getattr(learner, "_cost_model", None)
                generate_report(
                    perf_report_path,
                    roofline=cm.snapshot() if cm is not None else None,
                )
                print(
                    f"[perf-report] -> {perf_report_path}",
                    file=sys.stderr,
                    flush=True,
                )
            except Exception as e:  # noqa: BLE001 — teardown must finish
                print(
                    f"[perf-report] generation failed: {e!r}",
                    file=sys.stderr,
                    flush=True,
                )
        # Drain the trajectory queue so actor threads blocked on a full
        # queue can observe the stop event and exit.
        try:
            while True:
                learner._traj_q.get_nowait()
        except Exception:
            pass
        supervisor.join()
        for pool in env_pools:
            pool.close()
        # Merged trace export runs AFTER pool close: closing a pool
        # harvests every worker's final published payload (their exit
        # paths dump the full trace ring through the snapshot lane), so
        # the timeline gets one row per worker process with
        # pool/worker_step spans nested under the parent's submit->ack
        # spans by lineage ID.
        if trace_path:
            try:
                n = export_merged_trace(
                    trace_path, get_recorder(), aggregator
                )
                print(
                    f"[flight-recorder] {n} events (merged) -> "
                    f"{trace_path}",
                    file=sys.stderr,
                    flush=True,
                )
            except Exception as e:  # noqa: BLE001 — teardown must finish
                print(
                    f"[flight-recorder] export failed: {e!r}",
                    file=sys.stderr,
                    flush=True,
                )

    # Final saves land only on a CLEAN finish — an exception above (a real
    # crash or a chaos crash_learner fault) propagates past this point, so
    # resume starts from the last INTERVAL checkpoint, exactly like a
    # process death.
    if async_checkpointer is not None:
        async_checkpointer.save_now(
            learner.num_steps,
            learner.get_state(),
            param_version=learner.num_frames,
        )
        async_checkpointer.wait()
    if checkpointer is not None:
        checkpointer.save(learner.num_steps, learner.get_state())
        checkpointer.wait()

    with returns_lock:
        returns = list(episode_returns)
    return TrainResult(
        episode_returns=returns,
        final_logs=dict(step_logs),
        learner=learner,
        num_frames=learner.num_frames,
        actor_device=actor_device_note,
        actor_restarts=supervisor.restarts
        + sum(pool.restarts for pool in env_pools),
    )
