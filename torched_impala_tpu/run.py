"""CLI entry point: `python -m torched_impala_tpu.run --config <preset>`.

The experiment/CLI layer (SURVEY.md §2 top row): pick a preset from
`configs.REGISTRY`, apply flag overrides, and run training or greedy
evaluation. One registry entry exists per BASELINE.json config; presets
whose emulators are missing on this host run with `--fake-envs`.

Examples:
  python -m torched_impala_tpu.run --config cartpole
  python -m torched_impala_tpu.run --config pong --fake-envs --total-steps 50
  python -m torched_impala_tpu.run --config cartpole --mode eval \
      --checkpoint-dir /tmp/ck --eval-episodes 20
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import numpy as np


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default=None, help="preset name")
    p.add_argument("--doctor", action="store_true",
                   help="validate this host's env/emulator stack and exit: "
                        "dependency inventory, accelerator jit, per-family "
                        "env contracts (missing emulators reported, not "
                        "failed), and — with --config — a 2-step real-env "
                        "train probe (<1 min total)")
    p.add_argument("--mode", choices=("train", "eval"), default="train")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--platform", default=None,
                   help="jax platform list, e.g. 'cpu' or 'tpu,cpu' "
                        "('<accel>,cpu' enables CPU-pinned actor inference; "
                        "set before any backend is initialized)")
    # Scale overrides.
    p.add_argument("--num-actors", type=int, default=None)
    p.add_argument("--envs-per-actor", type=int, default=None,
                   help="envs stepped per actor thread with one batched "
                        "policy dispatch per timestep")
    p.add_argument("--actor-mode", choices=("thread", "process"),
                   default=None,
                   help="'process' runs env workers as OS processes "
                        "(GIL escape) feeding one batched-inference actor")
    p.add_argument("--pool-mode", choices=("lockstep", "async"),
                   default=None,
                   help="process-pool scheduling: 'async' batches "
                        "inference over the ready fraction of workers "
                        "instead of gating every wave on stragglers "
                        "(runtime/env_pool.py)")
    p.add_argument("--pool-ready-fraction", default=None,
                   type=lambda s: s if s == "auto" else float(s),
                   help="async pool wave size as a fraction of workers "
                        "(0 < f <= 1; default 0.5), or 'auto' to let "
                        "the pool retune it from an EWMA of its own "
                        "straggler rate (runtime/env_pool.py)")
    p.add_argument("--traj-ring", action="store_true",
                   help="zero-copy trajectory ring: actors write unrolls "
                        "straight into preallocated learner batch slots "
                        "(no per-env Trajectory arrays, no np.stack); "
                        "needs vectorized actors whose env counts divide "
                        "batch-size; composes with --dp meshes "
                        "(runtime/traj_ring.py)")
    p.add_argument("--max-reuse", type=int, default=None,
                   help="replay: deliver each committed unroll up to N "
                        "times from the trajectory ring before recycling "
                        "its slot (IMPACT-style circular replay; needs "
                        "--traj-ring and --target-update-interval; "
                        "torched_impala_tpu/replay/, docs/REPLAY.md)")
    p.add_argument("--replay-mix", type=float, default=None,
                   help="replay: cap on the replayed fraction of delivered "
                        "batches (0 < f <= 1; fresh batches always take "
                        "priority regardless)")
    p.add_argument("--replay-staleness-frames", type=int, default=None,
                   help="replay: expire retained unrolls once the learner "
                        "frame watermark moves more than N frames past "
                        "their oldest transition (0 = no bound)")
    p.add_argument("--target-update-interval", type=int, default=None,
                   help="replay: refresh the on-device target-policy "
                        "snapshot every N learner steps (the clipped "
                        "surrogate anchors to it; required when "
                        "--max-reuse > 1)")
    p.add_argument("--target-clip-epsilon", type=float, default=None,
                   help="replay: PPO-style clip radius for the "
                        "learner/target policy ratio in the surrogate "
                        "loss (default 0.2)")
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--unroll-length", type=int, default=None)
    p.add_argument("--steps-per-dispatch", type=int, default=None,
                   help="fuse K SGD steps into one dispatched XLA program "
                        "(amortizes host dispatch latency; params publish "
                        "every K steps — see LearnerConfig)")
    p.add_argument("--superbatch-k", type=int, default=None, metavar="K",
                   help="zero-copy feed path bundle: trajectory ring with "
                        "[K, ...] superbatch slots donated straight into "
                        "the fused K-step dispatch (sets --traj-ring, "
                        "--steps-per-dispatch K, and buffer donation)")
    p.add_argument("--fused-epilogue", action="store_true",
                   help="run the V-trace recursion and the pg/value/"
                        "entropy loss epilogue in one fused pass with an "
                        "analytic VJP (ops/vtrace_pallas.py)")
    p.add_argument("--train-dtype", choices=("float32", "bfloat16"),
                   default=None,
                   help="train-step compute dtype: bfloat16 runs the FULL "
                        "step (params+activations cast inside the loss "
                        "closure; optimizer/PopArt/V-trace accumulators "
                        "stay f32 — ops/precision.py policy) and also "
                        "selects the fused epilogue's [T, B, A] phase "
                        "dtype under --fused-epilogue; gated by a "
                        "greedy-action parity probe that falls back to "
                        "float32 on failure")
    p.add_argument("--grad-accum", type=int, default=None,
                   help="accumulate gradients over G microbatches before "
                        "one optimizer update (same numbers as the full "
                        "batch, ~G-fold smaller activation footprint)")
    p.add_argument("--total-steps", type=int, default=None,
                   help="learner updates (default: total_env_frames/T*B)")
    p.add_argument("--total-env-frames", type=int, default=None)
    p.add_argument("--lr", type=float, default=None)
    # Parallelism.
    p.add_argument("--dp", type=int, default=None,
                   help="shard learner batch over N devices (-1 = all)")
    p.add_argument("--tp", type=int, default=None,
                   help="tensor-parallel 'model' mesh axis of N devices "
                        "(weight matrices shard by output features; "
                        "composes with --dp as a ('data','model') mesh)")
    p.add_argument("--sp", type=int, default=None,
                   help="shard the transformer unroll's time axis over N "
                        "devices (('data','seq') mesh with --dp; needs "
                        "--transformer-attention ring|ulysses; the "
                        "learner forwards unroll_length+1 steps, so pick "
                        "unroll-length = k*N - 1)")
    p.add_argument("--transformer-attention",
                   choices=("dense", "ring", "ulysses"), default=None,
                   help="route the transformer core's attention through "
                        "the sequence-parallel ops")
    p.add_argument("--transformer-dtype",
                   choices=("float32", "bfloat16"), default=None,
                   help="transformer core matmul compute dtype (opt-in "
                        "lever, separate from the torso's compute_dtype: "
                        "pays at d_model>=512 or T>=256 — docs/SCALING.md)")
    p.add_argument("--coordinator", default=None,
                   help="multi-host: coordinator host:port "
                        "(jax.distributed); every host runs this same "
                        "command with its own --host-id")
    p.add_argument("--num-hosts", type=int, default=None)
    p.add_argument("--host-id", type=int, default=None)
    p.add_argument("--simulate-hosts", type=int, default=None, metavar="N",
                   help="multi-host without a pod: re-exec this same "
                        "command as N CPU processes (one jax controller "
                        "each, gloo collectives, loopback coordinator) "
                        "and run it as an N-host cluster — the "
                        "parallel/simhost.py harness behind the tier-1 "
                        "multi-host tests (docs/MULTIHOST.md)")
    # Environments.
    p.add_argument("--env-id", default=None,
                   help="override the preset's env id (e.g. a different "
                        "ALE game for an Atari-57 sweep over the pong/"
                        "breakout presets)")
    p.add_argument("--fake-envs", action="store_true",
                   help="substitute shape-faithful fake envs (no emulators)")
    p.add_argument("--chaos", type=int, default=0, metavar="N",
                   help="fault injection: crash each actor's env every ~N "
                        "env steps to exercise supervisor restarts")
    p.add_argument("--max-actor-restarts", type=int, default=10,
                   help="per-actor supervisor restart budget")
    p.add_argument("--remat-torso", action="store_true",
                   help="rematerialize the torso in the backward pass "
                        "(trades an extra forward for not storing its "
                        "activations; for HBM-bound batch sizes)")
    p.add_argument("--fused-conv", action="store_true",
                   help="run deep-ResNet residual blocks as one fused "
                        "Pallas kernel each (ops/conv_pallas.py); "
                        "deep_resnet only, param-tree compatible")
    p.add_argument("--stack-buffer-reuse", choices=("auto", "on", "off"),
                   default="auto",
                   help="stack batches into a ring of reused preallocated "
                        "host buffers (measured 3.6-4.9x feed-path win at "
                        "large B; see LearnerConfig.stack_buffer_reuse)")
    # Logging / checkpointing.
    p.add_argument("--logger", choices=("print", "csv", "tb", "jsonl", "null"),
                   default="print")
    p.add_argument("--logdir", default="/tmp/torched_impala_tpu")
    p.add_argument("--log-every", type=int, default=50)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-interval", type=int, default=None,
                   help="learner steps between checkpoint saves "
                        "(default: preset's checkpoint_interval, 1000)")
    p.add_argument("--checkpoint-keep", type=int, default=None,
                   help="retained checkpoints, both backends (default: "
                        "preset's checkpoint_keep, 3)")
    p.add_argument("--checkpoint-seconds", type=float, default=None,
                   help="async backend: also save when this much wall "
                        "time passed since the last save (0 = step "
                        "cadence only; default: preset)")
    p.add_argument("--async-checkpoint", action="store_true",
                   help="resilience backend for interval saves: a "
                        "background thread writes atomic checkpoints + "
                        "JSON run manifests under --checkpoint-dir and "
                        "the train loop never blocks on disk "
                        "(resilience/checkpointer.py; the final save "
                        "still lands in orbax so --mode eval works)")
    p.add_argument("--resume", nargs="?", const="auto", default=None,
                   choices=("auto",),
                   help="restore the newest checkpoint before training "
                        "(bare flag = 'auto': async-checkpoint manifests "
                        "and the orbax dir compared by step, newest "
                        "wins; manifest resume refuses a mismatched "
                        "config hash)")
    p.add_argument("--chaos-plan", default=None, metavar="PLAN.json",
                   help="resilience fault-injection plan (JSON list of "
                        "{kind, at, target, duration_s} — "
                        "resilience/chaos.py fault table; composes with "
                        "--chaos N env crashes)")
    # Eval.
    p.add_argument("--eval-episodes", type=int, default=10)
    p.add_argument("--eval-serving", action="store_true",
                   help="route eval inference through the serving tier "
                        "(PolicyServer + in-process client, "
                        "torched_impala_tpu/serving/): continuous-batched "
                        "waves, versioned params, serving/* telemetry — "
                        "greedy eval returns are identical to the direct "
                        "path (docs/SERVING.md)")
    p.add_argument("--serve-dtype",
                   choices=("float32", "bfloat16", "int8"),
                   default=None,
                   help="serving-path param dtype (default: preset's "
                        "serving_dtype). bfloat16 and int8 (per-channel "
                        "weight quantization, serving/quant.py) are "
                        "refused unless the f32 greedy-action parity "
                        "gate passes on this checkpoint (docs/SERVING.md "
                        "reduced-precision policy)")
    p.add_argument("--serve-replicas", type=int, default=None, metavar="N",
                   help="serve eval through an N-replica ServingFleet "
                        "(least-loaded router + draining rollouts, "
                        "serving/fleet.py) instead of one PolicyServer "
                        "(default: preset's serving_replicas)")
    p.add_argument("--eval-stochastic", action="store_true",
                   help="sample actions instead of argmax")
    p.add_argument("--eval-max-steps", type=int, default=108_000,
                   help="per-episode env-step cap during eval (guards "
                        "against never-terminating policies); <=0 disables")
    p.add_argument("--eval-parallel", type=int, default=1, metavar="E",
                   help="step E eval envs in lockstep with one batched "
                        "policy dispatch per timestep (E-fold fewer "
                        "dispatches; episode seeding differs from the "
                        "serial protocol — see runtime/evaluator.py)")
    # Profiling (SURVEY.md §6 tracing row).
    p.add_argument("--profile-dir", default=None,
                   help="capture a jax.profiler trace of the WHOLE learner "
                        "loop (includes compile time; for a bounded "
                        "steady-state window use --profile-steps)")
    p.add_argument("--profile-steps", default=None, metavar="A:B",
                   help="capture a jax.profiler trace window: open after "
                        "learner step A completes, close after step B, "
                        "written under --trace-dir (telemetry/profiling.py)")
    p.add_argument("--trace-dir", default="traces",
                   help="directory for --profile-steps / SIGUSR1 trace "
                        "captures (one subdirectory per capture) and "
                        "SIGUSR2 flight-recorder dumps")
    p.add_argument("--trace", default=None, metavar="OUT.json",
                   help="export the flight recorder (telemetry/"
                        "tracing.py: per-unroll lineage env→pool→queue/"
                        "ring→learner, exact per-batch param lag) as "
                        "Chrome-trace JSON at run end; load in Perfetto "
                        "(docs/OBSERVABILITY.md). SIGUSR2 dumps the "
                        "recorder on a live run regardless of this flag")
    p.add_argument("--perf-report", default=None, metavar="OUT.json",
                   help="performance observatory (perf/report.py): "
                        "analyze the flight recorder at run end — "
                        "inter-step gap attribution (feed/H2D/publish/"
                        "compile/unattributed), fresh vs replayed "
                        "compute, roofline from the cost model — into "
                        "OUT.json plus a human-readable .txt sibling; "
                        "SIGUSR2 also dumps a numbered live report")
    # Observability (telemetry/, docs/OBSERVABILITY.md). SIGUSR1 on a
    # live train run toggles a profiler capture into --trace-dir.
    p.add_argument("--telemetry-every", type=int, default=None,
                   help="merge the telemetry registry snapshot "
                        "(telemetry/<component>/<name> keys) into every "
                        "Nth metrics write (default: preset's "
                        "telemetry_interval, normally 1; 0 disables)")
    p.add_argument("--stall-timeout", type=float, default=None,
                   help="stall watchdog deadline in seconds: no learner "
                        "step or actor wave for this long dumps all "
                        "thread stacks + telemetry to stderr and emits a "
                        "telemetry/watchdog/stall event (default: "
                        "preset's stall_timeout_s, normally 300; 0 off)")
    p.add_argument("--metrics-port", type=int, default=None,
                   metavar="PORT",
                   help="serve the run-wide AGGREGATED telemetry "
                        "snapshot (local registry + proc<h>w<w>/ env-"
                        "pool worker fan-in + alerts/* burn-rate "
                        "gauges) as an OpenMetrics/Prometheus endpoint "
                        "on http://localhost:PORT/metrics "
                        "(telemetry/export.py; 0 = off; tools/dash.py "
                        "renders a live dashboard over it)")
    p.add_argument("--metrics-file", default=None, metavar="OUT.prom",
                   help="atomic-write the same OpenMetrics payload to "
                        "this file every exposition tick — the "
                        "sandboxed-run fallback when no port can be "
                        "bound (tools/dash.py --file reads it)")
    p.add_argument("--health", action="store_true",
                   help="training-health diagnostics (telemetry/"
                        "health.py): compile learning-health gauges "
                        "(V-trace rho/c clip fractions + IS-weight "
                        "histogram, entropy, behaviour->learner KL, "
                        "value explained variance, per-layer-group grad "
                        "norms, PopArt drift) into the train step as "
                        "health/* telemetry, arm the burn-rate health "
                        "alerts (entropy collapse, rho saturation, EV "
                        "collapse, grad spike), and write a postmortem "
                        "bundle on each alert firing or learner crash "
                        "(tools/postmortem.py renders them)")
    p.add_argument("--postmortem-dir", default=None, metavar="DIR",
                   help="where --health anomaly bundles land (default: "
                        "preset's postmortem_dir, 'postmortems')")
    # Control plane (torched_impala_tpu/control/, docs/CONTROL.md).
    p.add_argument("--control", choices=("auto", "off"), default=None,
                   help="closed-loop control plane: 'auto' starts a "
                        "ControlLoop that tunes runtime knobs (fused-K "
                        "chunking, replay max_reuse, checkpoint cadence; "
                        "serving latency knobs under --eval-serving) from "
                        "live telemetry, with every decision audited as "
                        "control/* telemetry and control/decision trace "
                        "events (default: preset's control.mode, 'off')")
    p.add_argument("--control-interval", type=float, default=None,
                   metavar="S",
                   help="ControlLoop tick period in seconds (default: "
                        "preset's control.interval_s, 5.0)")
    return p.parse_args(argv)


def build_config(args: argparse.Namespace):
    from torched_impala_tpu.configs import REGISTRY

    if args.config not in REGISTRY:
        raise SystemExit(
            f"unknown config {args.config!r}; have {sorted(REGISTRY)}"
        )
    cfg = REGISTRY[args.config]
    overrides = {}
    for flag, field in (
        ("num_actors", "num_actors"),
        ("envs_per_actor", "envs_per_actor"),
        ("actor_mode", "actor_mode"),
        ("pool_mode", "pool_mode"),
        ("pool_ready_fraction", "pool_ready_fraction"),
        ("max_reuse", "max_reuse"),
        ("replay_mix", "replay_mix"),
        ("replay_staleness_frames", "replay_staleness_frames"),
        ("target_update_interval", "target_update_interval"),
        ("target_clip_epsilon", "target_clip_epsilon"),
        ("batch_size", "batch_size"),
        ("unroll_length", "unroll_length"),
        ("steps_per_dispatch", "steps_per_dispatch"),
        ("total_env_frames", "total_env_frames"),
        ("lr", "lr"),
        ("dp", "dp_devices"),
        ("tp", "tp_devices"),
        ("sp", "sp_devices"),
        ("transformer_attention", "transformer_attention"),
        ("transformer_dtype", "transformer_dtype"),
        ("env_id", "env_id"),
        ("train_dtype", "train_dtype"),
        ("trace", "trace_path"),
        ("perf_report", "perf_report"),
        ("metrics_port", "metrics_port"),
        ("metrics_file", "metrics_file"),
        ("postmortem_dir", "postmortem_dir"),
    ):
        v = getattr(args, flag)
        if v is not None:
            overrides[field] = v
    if args.remat_torso:
        overrides["remat_torso"] = True
    if args.fused_conv:
        overrides["fused_conv"] = True
    if args.traj_ring:
        overrides["traj_ring"] = True
    if args.fused_epilogue:
        overrides["fused_epilogue"] = True
    if args.health:
        overrides["health_diagnostics"] = True
    if args.superbatch_k:
        # The one-flag zero-copy bundle: superbatch ring slots donated
        # into the fused K-step dispatch.
        overrides["traj_ring"] = True
        overrides["steps_per_dispatch"] = args.superbatch_k
        overrides["donate_batch"] = True
    control_overrides = {}
    if args.control is not None:
        control_overrides["mode"] = args.control
    if args.control_interval is not None:
        control_overrides["interval_s"] = args.control_interval
    if control_overrides:
        overrides["control"] = dataclasses.replace(
            cfg.control, **control_overrides
        )
    cfg = dataclasses.replace(cfg, **overrides) if overrides else cfg
    cfg.control.validate()
    if args.env_id is not None and not args.fake_envs:
        # The preset's num_actions describes its ORIGINAL env; a
        # substituted game's action space can differ (pong 6 vs breakout
        # 4), and the policy head must match the env the actors step.
        from torched_impala_tpu.configs import probe_num_actions

        real = probe_num_actions(cfg)
        if real != cfg.num_actions:
            print(
                f"--env-id {args.env_id}: num_actions {cfg.num_actions} "
                f"(preset) -> {real} (probed from the env)",
                file=sys.stderr,
            )
            cfg = dataclasses.replace(cfg, num_actions=real)
    return cfg


def make_profiler(args: argparse.Namespace):
    """(capture, window) for on-demand jax.profiler traces: SIGUSR1 on
    the live process toggles a capture into --trace-dir (best-effort
    install), SIGUSR2 dumps the flight recorder there, and
    --profile-steps A:B drives a bounded learner-step window. `window`
    is None without --profile-steps."""
    from torched_impala_tpu.telemetry import (
        ProfilerCapture,
        StepWindowProfiler,
        install_sigusr2,
        parse_profile_steps,
    )

    capture = ProfilerCapture(args.trace_dir)
    capture.install_sigusr1()
    install_sigusr2(args.trace_dir)
    window = None
    if args.profile_steps:
        try:
            start, stop = parse_profile_steps(args.profile_steps)
        except ValueError as e:
            raise SystemExit(str(e)) from e
        window = StepWindowProfiler(capture, start, stop)
    return capture, window


def make_logger(args: argparse.Namespace):
    from torched_impala_tpu.utils import loggers

    if args.logger == "print":
        return loggers.PrintLogger()
    if args.logger == "csv":
        return loggers.CSVLogger(f"{args.logdir}/{args.config}.csv")
    if args.logger == "tb":
        return loggers.TensorBoardLogger(f"{args.logdir}/{args.config}")
    if args.logger == "jsonl":
        return loggers.JSONLinesLogger(f"{args.logdir}/{args.config}.jsonl")
    return loggers.NullLogger()


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from torched_impala_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()
    if args.doctor:
        from torched_impala_tpu.doctor import run_doctor

        return run_doctor(args.config)
    if args.config is None:
        raise SystemExit("--config is required (unless --doctor)")
    if args.simulate_hosts:
        import os

        from torched_impala_tpu.parallel import multihost, simhost

        if os.environ.get(multihost.ENV_HOST_ID) is None:
            # Parent: re-exec this exact command as N simulated host
            # processes (simhost sets the IMPALA_* triple per child; the
            # children fall through to bootstrap() below).
            res = simhost.launch(
                [sys.executable, "-m", "torched_impala_tpu.run"]
                + list(argv if argv is not None else sys.argv[1:]),
                args.simulate_hosts,
            )
            for h in res.hosts:
                tail = "\n".join(
                    (h.stdout + "\n" + h.stderr).strip().splitlines()[-6:]
                )
                print(
                    f"[simulate-hosts] host {h.host_id} "
                    f"rc={h.returncode}\n{tail}"
                )
            print(
                f"[simulate-hosts] cluster "
                f"{'ok' if res.ok else 'FAILED'} in {res.duration_s:.1f}s"
            )
            return 0 if res.ok else 1
        multihost.bootstrap()
    if args.coordinator or args.num_hosts or args.host_id is not None:
        from torched_impala_tpu.parallel import multihost

        multihost.initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_hosts,
            process_id=args.host_id,
        )
    from torched_impala_tpu import configs
    from torched_impala_tpu.parallel import make_mesh
    from torched_impala_tpu.runtime.loop import resolve_actor_device, train
    from torched_impala_tpu.utils.checkpoint import Checkpointer

    cfg = build_config(args)

    # The SP flags only make sense together: ring/ulysses attention with
    # no seq axis silently runs dense, and a seq axis with dense
    # attention reserves devices that never do anything — reject both.
    if (cfg.transformer_attention != "dense") != bool(cfg.sp_devices):
        raise SystemExit(
            "--transformer-attention ring|ulysses and --sp N go together "
            f"(got attention={cfg.transformer_attention!r}, "
            f"sp={cfg.sp_devices})"
        )
    if cfg.sp_devices and cfg.core != "transformer":
        raise SystemExit(
            "--sp shards the transformer core's unroll attention; the "
            f"config's core is {cfg.core!r}"
        )
    if cfg.sp_devices and (cfg.unroll_length + 1) % cfg.sp_devices != 0:
        # Without this the core only WARNS at trace time and silently runs
        # dense attention on an N-times larger mesh whose seq devices
        # duplicate work (ADVICE r2). The learner forwards T+1 steps, so
        # the shardable length is unroll_length + 1.
        raise SystemExit(
            f"--sp {cfg.sp_devices} needs (unroll_length+1) divisible by "
            f"it; got unroll_length={cfg.unroll_length} "
            f"({cfg.unroll_length + 1} % {cfg.sp_devices} = "
            f"{(cfg.unroll_length + 1) % cfg.sp_devices}). "
            f"Pick unroll-length = k*{cfg.sp_devices} - 1."
        )

    if cfg.tp_devices and cfg.tp_devices < 0:
        # No '-1 = all' for tp (unlike --dp): the model axis size changes
        # the weight layouts, so it must be chosen, not inferred — and
        # silently ignoring a negative would fake a TP run (ADVICE-class
        # footgun).
        raise SystemExit(
            f"--tp must be a concrete axis size >= 2, got {cfg.tp_devices}"
        )
    if cfg.sp_devices and cfg.tp_devices and cfg.tp_devices > 1:
        raise SystemExit(
            "--tp and --sp build different meshes (('data','model') vs "
            "('data','seq')); combine tp with dp only"
        )

    mesh = None
    if cfg.sp_devices:
        # Combined data+sequence parallelism: ('data','seq') mesh; the
        # learner shards the batch over 'data' (its existing shardings),
        # the transformer core's attention shards the unroll over 'seq'.
        from torched_impala_tpu.parallel import data_seq_mesh

        if cfg.sp_devices < 2:
            raise SystemExit(f"--sp must be >= 2, got {cfg.sp_devices}")
        dp = (
            max(1, len(jax.devices()) // cfg.sp_devices)
            if cfg.dp_devices == -1
            else max(1, cfg.dp_devices)
        )
        try:
            mesh = data_seq_mesh(dp, cfg.sp_devices)
        except ValueError as e:
            raise SystemExit(str(e)) from e
    elif cfg.tp_devices and cfg.tp_devices > 1:
        # ('data','model') mesh: batch over data, weight matrices over
        # model (parallel.model_shardings). --dp sizes the data axis
        # (-1/0 = whatever the device count allows).
        tp = cfg.tp_devices
        dp = (
            max(1, len(jax.devices()) // tp)
            if cfg.dp_devices in (0, -1)
            else cfg.dp_devices
        )
        mesh = make_mesh(num_data=dp, num_model=tp)
    elif cfg.dp_devices:  # 0 = single-device; -1 = all; N = N devices
        n = len(jax.devices()) if cfg.dp_devices == -1 else cfg.dp_devices
        mesh = make_mesh(num_data=n)
    elif jax.process_count() > 1:
        # Multi-controller run (--simulate-hosts / --coordinator) with no
        # explicit mesh flags: a mesh is NOT optional — without one each
        # controller would train its own independent copy. Default to
        # data-parallel over every device in the pod.
        from torched_impala_tpu.parallel import multihost

        mesh = multihost.global_mesh()

    agent = configs.make_agent(cfg, mesh=mesh)

    if args.mode == "train" and cfg.train_dtype != "float32":
        # The train-side parity gate (ISSUE 16; the serving bf16/int8
        # gate's idiom): the reduced-precision train forward must agree
        # with f32 on greedy actions over a fixed probe. Unlike serving
        # (which exits rc=5 — the caller picked an explicit serve
        # dtype), training REFUSES the half dtype and falls back to the
        # exact f32 step: the run proceeds, just without the speedup.
        ok, mismatches = configs.check_train_dtype_parity(
            cfg, mesh=mesh, seed=args.seed
        )
        if not ok:
            print(
                f"warning: --train-dtype {cfg.train_dtype} refused — "
                f"greedy-action parity gate failed ({mismatches} probe "
                "actions differ from f32); falling back to float32 "
                "(docs/OBSERVABILITY.md mixed-precision policy)",
                file=sys.stderr,
            )
            cfg = dataclasses.replace(cfg, train_dtype="float32")
            agent = configs.make_agent(cfg, mesh=mesh)

    # Checkpoint cadence/retention: flags override the preset fields
    # (configs.ExperimentConfig resilience block).
    if args.checkpoint_interval is None:
        args.checkpoint_interval = cfg.checkpoint_interval
    ck_keep = (
        args.checkpoint_keep
        if args.checkpoint_keep is not None
        else cfg.checkpoint_keep
    )
    ck_seconds = (
        args.checkpoint_seconds
        if args.checkpoint_seconds is not None
        else cfg.checkpoint_seconds
    )

    checkpointer = (
        Checkpointer(args.checkpoint_dir, max_to_keep=ck_keep)
        if args.checkpoint_dir is not None
        else None
    )

    if args.mode == "eval":
        try:
            return run_eval(args, cfg, agent, checkpointer)
        finally:
            if checkpointer is not None:
                checkpointer.close()

    if cfg.runtime == "anakin":
        if args.coordinator or args.num_hosts:
            raise SystemExit(
                "runtime='anakin' is single-controller (multi-host needs "
                "the actor runtime); drop --coordinator/--num-hosts"
            )
        if args.grad_accum is not None:
            # Silently ignoring it would fake the documented HBM lever
            # (anakin fuses rollout+update; it has no microbatch path).
            raise SystemExit(
                "--grad-accum applies to the actor-runtime learner only; "
                "runtime='anakin' has no microbatch path"
            )
        return run_anakin(args, cfg, agent, mesh, checkpointer)

    learner_config = configs.make_learner_config(cfg)
    if args.stack_buffer_reuse != "auto":
        learner_config = dataclasses.replace(
            learner_config, stack_buffer_reuse=args.stack_buffer_reuse
        )
    if args.grad_accum is not None:
        # No truthiness filter: 0 must reach the Learner's own >= 1
        # validation and fail loudly.
        learner_config = dataclasses.replace(
            learner_config, grad_accum=args.grad_accum
        )

    env_factory = configs.make_env_factory(cfg, fake=args.fake_envs)
    if args.chaos:
        from torched_impala_tpu.envs.fake import CrashingFactory

        env_factory = CrashingFactory(env_factory, crash_after=args.chaos)

    # Resilience wiring (docs/RESILIENCE.md): the async checkpoint writer
    # (crash-consistent interval saves + run manifests) and the chaos
    # fault plan.
    async_checkpointer = None
    config_hash = None
    if args.async_checkpoint:
        if args.checkpoint_dir is None:
            raise SystemExit("--async-checkpoint needs --checkpoint-dir")
        from torched_impala_tpu.resilience import (
            AsyncCheckpointer,
            config_fingerprint,
        )

        config_hash = config_fingerprint(cfg)
        async_checkpointer = AsyncCheckpointer(
            args.checkpoint_dir,
            keep=ck_keep,
            interval_steps=args.checkpoint_interval,
            interval_seconds=ck_seconds,
            config_hash=config_hash,
        )
    chaos_plan = None
    if args.chaos_plan:
        from torched_impala_tpu.resilience import ChaosPlan

        chaos_plan = ChaosPlan.from_json(args.chaos_plan)

    total_steps = (
        args.total_steps
        if args.total_steps is not None
        else cfg.total_learner_steps
    )
    logger = make_logger(args)
    print(
        f"config={cfg.name} actors={cfg.num_actors} T={cfg.unroll_length} "
        f"B={cfg.batch_size} steps={total_steps} "
        f"mesh={None if mesh is None else dict(mesh.shape)} "
        f"backend={jax.default_backend()} "
        f"actor_device={resolve_actor_device()[1]}",
        file=sys.stderr,
    )

    capture, profile_window = make_profiler(args)
    if cfg.perf_report:
        # Chained after the flight-recorder handler make_profiler
        # installed: one SIGUSR2 yields both the raw trace dump and a
        # numbered live perf report.
        from torched_impala_tpu.perf import install_sigusr2_report

        install_sigusr2_report(cfg.perf_report)
    profile_ctx = None
    if args.profile_dir:
        profile_ctx = jax.profiler.trace(
            args.profile_dir, create_perfetto_link=False
        )
        profile_ctx.__enter__()
    try:
        result = train(
            agent=agent,
            env_factory=env_factory,
            example_obs=configs.example_obs(cfg),
            num_actors=cfg.num_actors,
            learner_config=learner_config,
            optimizer=configs.make_optimizer(cfg),
            total_steps=total_steps,
            seed=args.seed,
            logger=logger,
            log_every=args.log_every,
            mesh=mesh,
            checkpointer=checkpointer,
            checkpoint_interval=args.checkpoint_interval,
            resume=args.resume,
            async_checkpointer=async_checkpointer,
            config_hash=config_hash,
            chaos=chaos_plan,
            max_actor_restarts=args.max_actor_restarts,
            envs_per_actor=cfg.envs_per_actor,
            actor_mode=cfg.actor_mode,
            pool_mode=cfg.pool_mode,
            pool_ready_fraction=cfg.pool_ready_fraction,
            telemetry_interval=(
                args.telemetry_every
                if args.telemetry_every is not None
                else cfg.telemetry_interval
            ),
            stall_timeout=(
                args.stall_timeout
                if args.stall_timeout is not None
                else cfg.stall_timeout_s
            ),
            on_learner_step=(
                profile_window.on_step if profile_window else None
            ),
            trace_path=cfg.trace_path or None,
            perf_report_path=cfg.perf_report or None,
            control=cfg.control,
            metrics_port=(
                cfg.metrics_port if cfg.metrics_port > 0 else None
            ),
            metrics_file=cfg.metrics_file,
            postmortem_dir=cfg.postmortem_dir,
        )
    finally:
        if profile_window is not None:
            profile_window.close()  # flush a still-open step window
        capture.stop()  # flush a SIGUSR1 capture left running
        if profile_ctx is not None:
            profile_ctx.__exit__(*sys.exc_info())
        logger.close()
        if checkpointer is not None:
            checkpointer.close()
        if async_checkpointer is not None:
            async_checkpointer.close()

    recent = [r for _, r, _ in result.episode_returns[-100:]]
    mean_ret = float(np.mean(recent)) if recent else float("nan")
    print(
        f"done: steps={result.learner.num_steps} "
        f"frames={result.num_frames} episodes={len(result.episode_returns)} "
        f"recent_return_mean={mean_ret:.2f} "
        f"actor_restarts={result.actor_restarts}",
        file=sys.stderr,
    )
    return 0


def run_anakin(args, cfg, agent, mesh, checkpointer) -> int:
    """Train with the fully on-device runtime (runtime/anakin.py).

    total-steps counts ITERATIONS here (each = unroll_length steps of
    batch_size on-device envs = cfg.frames_per_step frames, same frame
    accounting as a learner step on the actor runtime). Honors --resume,
    --checkpoint-interval (plus a final save, crash-safe via finally), and
    --profile-dir like the actor runtime; env states are not checkpointed
    (envs restart fresh on resume, exactly as host envs do)."""
    from torched_impala_tpu import configs
    from torched_impala_tpu.runtime import AnakinConfig, AnakinRunner

    total_steps = (
        args.total_steps
        if args.total_steps is not None
        else cfg.total_learner_steps
    )
    logger = make_logger(args)
    print(
        f"config={cfg.name} runtime=anakin E={cfg.batch_size} "
        f"T={cfg.unroll_length} iters={total_steps} "
        f"mesh={None if mesh is None else dict(mesh.shape)} "
        f"backend={jax.default_backend()}",
        file=sys.stderr,
    )
    runner = AnakinRunner(
        agent=agent,
        env=configs.make_jax_env(cfg),
        optimizer=configs.make_optimizer(cfg),
        config=AnakinConfig(
            num_envs=cfg.batch_size,
            unroll_length=cfg.unroll_length,
            loss=configs.make_learner_config(cfg).loss,
            updates_per_dispatch=cfg.steps_per_dispatch,
        ),
        rng=jax.random.key(args.seed),
        mesh=mesh,
    )
    if args.resume and checkpointer is not None:
        restored = checkpointer.restore(runner.get_state())
        if restored is not None:
            runner.set_state(restored)
            print(
                f"resumed @ step {runner.num_steps} "
                f"({runner.num_frames} frames)",
                file=sys.stderr,
            )
    # Budget semantics match the actor runtime: total_steps is the TOTAL
    # budget; a resumed run performs only the remainder. With fused
    # dispatch (steps_per_dispatch > 1) the loop never overshoots: it runs
    # the largest multiple of N that fits (Learner.run semantics).
    N = cfg.steps_per_dispatch
    remaining_updates = max(0, total_steps - runner.num_steps)
    if remaining_updates % N:
        print(
            f"warning: step budget remainder {remaining_updates % N} < "
            f"steps_per_dispatch={N} will not run",
            file=sys.stderr,
        )
    remaining = remaining_updates // N

    capture, profile_window = make_profiler(args)
    profile_ctx = None
    if args.profile_dir:
        profile_ctx = jax.profiler.trace(
            args.profile_dir, create_perfetto_link=False
        )
        profile_ctx.__enter__()
    logs = {}
    start_frames = runner.num_frames
    t0 = time.perf_counter()
    try:
        from torched_impala_tpu.runtime import crossed_interval

        def crossed(interval: int) -> bool:
            return crossed_interval(runner.num_steps, N, interval)

        if profile_window is not None:
            # Same contract as the actor runtime: a window whose start is
            # already behind the restored step opens on the first step.
            profile_window.on_step(runner.num_steps)
        for _ in range(remaining):
            logs = runner.step()
            if profile_window is not None:
                profile_window.on_step(runner.num_steps)
            if args.log_every and crossed(args.log_every):
                host_logs = {k: float(v) for k, v in logs.items()}
                host_logs["num_steps"] = runner.num_steps
                host_logs["num_frames"] = runner.num_frames
                logger(host_logs)
            if (
                checkpointer is not None
                and args.checkpoint_interval
                and crossed(args.checkpoint_interval)
            ):
                checkpointer.save(runner.num_steps, runner.get_state())
    finally:
        if profile_window is not None:
            profile_window.close()
        capture.stop()
        if profile_ctx is not None:
            profile_ctx.__exit__(*sys.exc_info())
        if checkpointer is not None:
            if checkpointer.latest_step() != runner.num_steps:
                checkpointer.save(runner.num_steps, runner.get_state())
            checkpointer.close()
        if cfg.trace_path:
            # Anakin records no host lineage (rollouts fuse into the XLA
            # program), but whatever reached the recorder still exports.
            from torched_impala_tpu.telemetry import get_recorder

            try:
                get_recorder().export(cfg.trace_path)
            except Exception as e:  # noqa: BLE001 — teardown must finish
                print(
                    f"[flight-recorder] export failed: {e!r}",
                    file=sys.stderr,
                )
        if cfg.perf_report:
            # Same caveat as the trace export: anakin's fused program
            # emits no learner/train_step spans, so the report mostly
            # documents that fact — but the artifact contract holds.
            from torched_impala_tpu.perf import generate_report

            try:
                generate_report(cfg.perf_report)
            except Exception as e:  # noqa: BLE001 — teardown must finish
                print(
                    f"[perf-report] generation failed: {e!r}",
                    file=sys.stderr,
                )
        logger.close()
    jax.block_until_ready(jax.tree.leaves(runner.params)[0])
    dt = time.perf_counter() - t0
    fps = (runner.num_frames - start_frames) / dt if dt > 0 else 0.0
    ret = float(logs.get("episode_return_mean", float("nan")))
    print(
        f"done: steps={runner.num_steps} frames={runner.num_frames} "
        f"frames_per_sec={fps:,.0f} episode_return_mean={ret:.2f}",
        file=sys.stderr,
    )
    return 0


def run_eval(args, cfg, agent, checkpointer) -> int:
    from torched_impala_tpu import configs
    from torched_impala_tpu.runtime.evaluator import run_episodes

    params = agent.init_params(
        jax.random.key(args.seed),
        jax.numpy.asarray(configs.example_obs(cfg)),
    )
    if checkpointer is not None:
        # Restore just the params subtree from the latest checkpoint.
        target = {
            "params": params,
            "opt_state": configs.make_optimizer(cfg).init(params),
            "num_frames": np.asarray(0, np.int64),
            "num_steps": np.asarray(0, np.int64),
            "rng": np.asarray(
                jax.random.key_data(jax.random.key(args.seed))
            ),
        }
        if cfg.num_tasks > 1:
            from torched_impala_tpu.ops import popart as popart_ops

            target["popart_state"] = popart_ops.init(cfg.num_tasks)
        restored = checkpointer.restore(target)
        if restored is None:
            # Distinct nonzero rc: an explicitly requested checkpoint that
            # does not exist must not be silently replaced by fresh params
            # — a sweep would record the random policy's return as the
            # game's result forever (ADVICE r2). Evaluating fresh params
            # is still available by omitting --checkpoint-dir.
            print(
                f"error: --checkpoint-dir {args.checkpoint_dir} holds no "
                "checkpoint (omit the flag to eval fresh params)",
                file=sys.stderr,
            )
            return 4
        else:
            params = restored["params"]
            print(
                f"restored checkpoint @ step {checkpointer.latest_step()}",
                file=sys.stderr,
            )

    env_factory = configs.make_env_factory(cfg, fake=args.fake_envs)
    max_steps = args.eval_max_steps if args.eval_max_steps > 0 else None
    if args.eval_serving:
        # Serving-tier eval (docs/SERVING.md): the evaluator is the
        # serving tier's first client — identical greedy returns to the
        # direct path, but the inference rides PolicyServer waves with
        # serving/* telemetry and versioned provenance.
        if args.eval_parallel > 1:
            raise SystemExit(
                "--eval-serving batches inside the server; it composes "
                "with the serial evaluator only (drop --eval-parallel)"
            )
        from torched_impala_tpu.runtime.param_store import ParamStore
        from torched_impala_tpu.serving import (
            FleetClient,
            InProcessClient,
            PolicyServer,
            ServingFleet,
            VersionRegistry,
            greedy_action_parity,
        )

        serve_dtype = args.serve_dtype or cfg.serving_dtype
        if serve_dtype in ("bfloat16", "int8"):
            rng = np.random.default_rng(args.seed)
            example = configs.example_obs(cfg)
            if example.dtype == np.uint8:
                probe = rng.integers(
                    0, 256, size=(8, *example.shape), dtype=np.uint8
                )
            else:
                probe = rng.normal(size=(8, *example.shape)).astype(
                    example.dtype
                )
            ok, mismatches = greedy_action_parity(
                agent, params, probe, dtype=serve_dtype
            )
            if not ok:
                print(
                    f"error: {serve_dtype} serving refused — "
                    f"greedy-action parity gate failed ({mismatches}/8 "
                    "probe actions differ from f32); serve in float32 "
                    "or retrain (docs/SERVING.md reduced-precision "
                    "policy)",
                    file=sys.stderr,
                )
                return 5
        serve_replicas = (
            args.serve_replicas
            if args.serve_replicas is not None
            else cfg.serving_replicas
        )
        if serve_replicas < 1:
            raise SystemExit(
                f"--serve-replicas must be >= 1, got {serve_replicas}"
            )
        store = ParamStore()
        store.publish(0, params)
        fleet = None
        if serve_replicas > 1:
            fleet = ServingFleet(
                agent=agent,
                store=store,
                example_obs=configs.example_obs(cfg),
                replicas=serve_replicas,
                max_clients=4,
                max_batch=min(4, cfg.serving_max_batch),
                max_wait_s=cfg.serving_wait_ms / 1e3,
                dtype=serve_dtype,
                seed=args.seed,
            ).start()
            server = None
        else:
            registry = VersionRegistry.serving_latest(store)
            server = PolicyServer(
                agent=agent,
                registry=registry,
                example_obs=configs.example_obs(cfg),
                max_clients=4,
                max_batch=min(4, cfg.serving_max_batch),
                max_wait_s=cfg.serving_wait_ms / 1e3,
                dtype=serve_dtype,
                seed=args.seed,
            ).start()
        control_loop = None
        if cfg.control.mode == "auto":
            from torched_impala_tpu.control import build_serving_control

            control_target = (
                {"fleet": fleet} if fleet is not None else {"server": server}
            )
            control_loop = build_serving_control(
                slo_ms=cfg.control.serving_slo_ms,
                interval_s=min(1.0, cfg.control.interval_s),
                **control_target,
            )
            control_loop.start()
        env = env_factory(args.seed + 777_000)
        try:
            if fleet is not None:
                client_cm = FleetClient(
                    fleet, greedy=not args.eval_stochastic
                )
            else:
                client_cm = InProcessClient(
                    server, greedy=not args.eval_stochastic
                )
            with client_cm as client:
                result = run_episodes(
                    env=env,
                    num_episodes=args.eval_episodes,
                    greedy=not args.eval_stochastic,
                    seed=args.seed,
                    max_steps_per_episode=max_steps,
                    client=client,
                )
        finally:
            if control_loop is not None:
                control_loop.stop()
            if fleet is not None:
                fleet.close()
            if server is not None:
                server.close()
            close = getattr(env, "close", None)
            if close is not None:
                close()
        print(
            f"eval: episodes={len(result.returns)} "
            f"mean_return={result.mean_return:.2f} "
            f"mean_length={result.mean_length:.1f} "
            f"(serving path, dtype={serve_dtype}, "
            f"replicas={serve_replicas})"
        )
        return 0
    if args.eval_parallel > 1:
        from torched_impala_tpu.runtime.evaluator import (
            run_episodes_batched,
        )

        # Factory passed straight through: the evaluator forwards each
        # env's slot index, so multi-task presets cover tasks 0..E-1.
        result = run_episodes_batched(
            agent=agent,
            params=params,
            env_factory=env_factory,
            num_episodes=args.eval_episodes,
            parallel_envs=args.eval_parallel,
            greedy=not args.eval_stochastic,
            seed=args.seed + 777_000,
            max_steps_per_episode=max_steps,
        )
    else:
        env = env_factory(args.seed + 777_000)
        try:
            result = run_episodes(
                agent=agent,
                params=params,
                env=env,
                num_episodes=args.eval_episodes,
                greedy=not args.eval_stochastic,
                seed=args.seed,
                max_steps_per_episode=max_steps,
            )
        finally:
            close = getattr(env, "close", None)
            if close is not None:
                close()
    print(
        f"eval: episodes={len(result.returns)} "
        f"mean_return={result.mean_return:.2f} "
        f"mean_length={result.mean_length:.1f}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
