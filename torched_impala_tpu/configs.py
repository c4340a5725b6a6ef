"""Experiment presets: one registry entry per BASELINE.json config.

The reference exposes a CLI entry point with flags per experiment
(SURVEY.md §1 item 7, reconstructed); here each experiment is a typed,
frozen `ExperimentConfig` (SURVEY.md §6 config row: "typed dataclass
configs, one registered preset per BASELINE.json:6-12 config") plus pure
builder functions that turn a config into the framework objects (agent,
optimizer, env factory, learner config).

Envs whose emulators are absent on a host (ale-py/procgen/dmlab,
SURVEY.md Appendix B) still have complete presets: the agent/optimizer/
learner build everywhere, and `make_env_factory(cfg, fake=True)` substitutes
shape-faithful fakes so throughput and integration runs work on any host.

Hyper-parameter provenance: IMPALA paper (PAPERS.md:5) — RMSProp with
linear lr anneal to 0 over total frames, entropy 0.01, baseline 0.5,
global-norm grad clip 40; the analog's CartPole-scale settings for the
smoke config (run_catch.py:29-36,59).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax.numpy as jnp
import numpy as np
import optax

from torched_impala_tpu.models import (
    Agent,
    AtariDeepTorso,
    AtariShallowTorso,
    ImpalaNet,
    MLPTorso,
)
from torched_impala_tpu.ops.losses import ImpalaLossConfig
from torched_impala_tpu.ops.popart import PopArtConfig
from torched_impala_tpu.runtime.learner import LearnerConfig


@dataclasses.dataclass(frozen=True)
class ControlConfig:
    """Closed-loop control plane (torched_impala_tpu/control/,
    docs/CONTROL.md): an online controller that tunes runtime knobs from
    live telemetry. `mode` is "off" (default — identical behavior to
    every run before the control plane existed) or "auto" (start a
    ControlLoop alongside the learner, and a second one inside serving
    eval). The remaining fields parameterize the standard policies:
    objective-regression tolerance for the guardrail revert, hysteresis
    band for hill climbs, post-revert/refusal cooldown, the serving p99
    SLO budget, the checkpoint wall-clock overhead budget, and whether
    the recompile gate may ever permit a live re-jit (default no: B/K
    proposals are audited but refused)."""

    mode: str = "off"  # "off" | "auto"
    interval_s: float = 5.0
    tolerance: float = 0.05
    hysteresis: float = 0.01
    cooldown_s: float = 30.0
    serving_slo_ms: float = 25.0
    checkpoint_overhead_budget: float = 0.01
    allow_recompile: bool = False
    # Minimum spacing between permitted live re-jits (the RecompileGate's
    # min_interval_s): with allow_recompile the B/K hill-climb on perf/mfu
    # may take at most one recompiling step per cadence window, so the
    # ~30s re-jit stall always has a full window to amortize (ISSUE 16).
    recompile_cadence_s: float = 300.0

    def validate(self) -> None:
        if self.mode not in ("off", "auto"):
            raise ValueError(
                f"control mode must be 'off' or 'auto', got {self.mode!r}"
            )
        if self.interval_s <= 0:
            raise ValueError("control interval_s must be > 0")
        if self.recompile_cadence_s <= 0:
            raise ValueError("control recompile_cadence_s must be > 0")


@dataclasses.dataclass(frozen=True)
class ExperimentConfig:
    """Everything needed to reproduce one experiment, statically typed."""

    name: str
    # Environment.
    env_family: str  # key into envs.FACTORIES
    env_id: str = ""
    obs_shape: tuple = ()  # nominal; used for agent init and fakes
    obs_dtype: str = "float32"
    num_actions: int = 2
    num_tasks: int = 1  # >1 => multi-task (PopArt) preset
    # Model.
    model: str = "mlp"  # mlp | shallow_cnn | deep_resnet
    use_lstm: bool = False
    lstm_size: int = 256
    # Temporal core: "auto" resolves to lstm/none via use_lstm; "transformer"
    # selects the sliding-window-KV causal core (models/transformer.py).
    core: str = "auto"
    transformer_d_model: int = 256
    transformer_layers: int = 2
    transformer_heads: int = 4
    transformer_window: int = 128
    # "dense" | "ring" | "ulysses": route the transformer core's
    # attention through the sequence-parallel ops (needs a ('data','seq')
    # mesh — run.py builds one from --dp/--sp; models/transformer.py).
    transformer_attention: str = "dense"
    # Compute dtype for the transformer CORE's dense-path matmuls —
    # deliberately separate from compute_dtype (the torso lever):
    # bfloat16 measured +9-14% at d_model>=512 or T>=256 but -9% at the
    # small pong_transformer shapes (cast overhead dominates a d256/T20
    # core; an earlier rig's readings), so it is opt-in, not inherited. Ignored (f32
    # forced, with a warning) on the sequence-parallel path.
    transformer_dtype: str = "float32"
    # Dense-attention kernel: "auto" picks pallas-vs-einsum from the
    # measured PALLAS_MIN_SCORE_ELEMS crossover; "pallas"/"einsum" force
    # (the retuning affordance for non-v5e TPU generations).
    transformer_dense_kernel: str = "auto"
    # core="hybrid" (models/hybrid.py): state-space, window- and
    # full-attention layers, one kind a layer in `hybrid_layers`, each
    # followed by a gated MLP. `hybrid_window` is the sliding window and
    # the window layers' cache length, `hybrid_full_cache` the full
    # layers'. `hybrid_dtype` is the operands' precision in the core's
    # matrix products (float32 accumulation; LayerNorm, softmax, the scan
    # and the residual stream stay float32). `hybrid_remat` recomputes
    # each block in the backward pass. Attention takes the fused kernel
    # and the scan its Pallas kernels whenever the step runs on one TPU
    # device (make_agent; `transformer_dense_kernel` still forces).
    hybrid_layers: tuple = ("mamba", "window", "mamba", "full")
    hybrid_d_model: int = 2560
    hybrid_heads: int = 40
    hybrid_kv_heads: int = 20
    hybrid_head_dim: int = 64
    hybrid_window: int = 512
    hybrid_full_cache: int = 2048
    hybrid_d_intermediate: int = 10240
    hybrid_d_inner: int = 5120
    hybrid_d_state: int = 16
    hybrid_d_conv: int = 4
    hybrid_dt_rank: int = 160
    hybrid_dtype: str = "bfloat16"
    hybrid_remat: bool = True
    # `hybrid_norm` "layer" | "rms"; `hybrid_mlp` "gated" | "moe": a router
    # over `hybrid_experts` experts of width `hybrid_expert_width`,
    # `hybrid_experts_per_token` a token, of which this learner holds
    # `hybrid_experts_held` = (first, count).
    hybrid_norm: str = "layer"
    hybrid_mlp: str = "gated"
    hybrid_experts: int = 32
    hybrid_experts_per_token: int = 4
    hybrid_experts_held: tuple = (0, 8)
    hybrid_expert_width: int = 1792
    # A routed core's other choices: the first `hybrid_dense_layers` blocks
    # take the gated MLP (`hybrid_d_intermediate`); an expert of width
    # `hybrid_shared_expert` (0: none) that every token takes; the chosen
    # scores over their sum, times `hybrid_routed_scale`.
    hybrid_dense_layers: int = 0
    hybrid_shared_expert: int = 0
    hybrid_routed_scale: float = 1.0
    # Layers of kind "latent" (multi-head latent attention): (q_rank,
    # kv_rank, nope_dim, rope_dim, v_dim); `hybrid_rope_theta` is the
    # rotary base of "full_rope" and "latent" layers, `hybrid_eps` every
    # norm's epsilon.
    hybrid_latent: tuple = ()
    hybrid_rope_theta: float = 1e6
    hybrid_eps: float = 1e-5
    # Shard the unroll's time axis over this many devices (the 'seq' mesh
    # axis); 0 = off. Combined with dp_devices as a ('data','seq') mesh.
    sp_devices: int = 0
    # Atari preprocessing options (standard DeepMind stack extras).
    episodic_life: bool = False
    fire_reset: bool = False
    # Torso compute dtype ("float32" | "bfloat16"). bf16 keeps the conv
    # FLOPs on the MXU's fast path; params, LSTM core, heads, and all loss
    # math stay float32.
    compute_dtype: str = "float32"
    # Rematerialize the torso in the backward pass (jax.checkpoint via
    # nn.remat): trades one extra torso forward for not storing its
    # activations between passes — the standard lever when HBM, not MXU,
    # bounds the batch size (deep ResNet at large B/T; SURVEY.md §7).
    remat_torso: bool = False
    # Run the deep-ResNet residual blocks through the fused Pallas block
    # kernel (ops/conv_pallas.py): relu→conv→relu→conv→skip in one VMEM
    # pass per image. Param-tree compatible with the unfused path;
    # deep_resnet only. Opt-in (CPU interpret mode is strictly slower).
    fused_conv: bool = False
    # Runtime: "actors" = host actor fleet feeding the device learner (the
    # reference's architecture); "anakin" = fully on-device actor-learner
    # for pure-JAX env families (runtime/anakin.py; env stepping fused into
    # the train program, batch_size = number of on-device envs).
    runtime: str = "actors"
    # Loss reduction over [T, B]: "sum" matches the reference; "mean"
    # decouples lr from unroll/batch size (the sane default at anakin env
    # counts, where T*B is in the thousands).
    loss_reduction: str = "sum"
    # Scale. `num_actors` is actor threads (actor_mode="thread") or env
    # worker *processes* (actor_mode="process"); each steps
    # `envs_per_actor` envs. Thread mode batches policy dispatch per actor
    # (VectorActor); process mode escapes the GIL and batches inference
    # over the whole pool (runtime/env_pool.py).
    num_actors: int = 4
    envs_per_actor: int = 1
    actor_mode: str = "thread"
    # Process-pool scheduling (actor_mode="process" only). "lockstep"
    # gates every inference wave on every worker; "async" is the
    # ready-set protocol: inference batches over whichever
    # `pool_ready_fraction` of workers has reported and lets stragglers
    # catch up on the next wave (runtime/env_pool.py). Lockstep stays the
    # default and the test baseline; async is opt-in per preset.
    # `pool_ready_fraction` also accepts "auto": the pool retunes the
    # fraction from an EWMA of its own straggler flags (the
    # rate->fraction line of env_pool.AUTO_FRACTION_*).
    pool_mode: str = "lockstep"
    pool_ready_fraction: float | str = 0.5
    # Zero-copy trajectory ring (runtime/traj_ring.py): actors write
    # unrolls straight into preallocated learner batch slots — the
    # shm-lane -> Trajectory -> np.stack copy chain collapses to one
    # write. Opt-in; needs vectorized actors whose env counts divide
    # batch_size. Composes with the mesh learner (slots are sliced
    # per-shard at device_put; parallel/multihost.place_batch) and with
    # the fused K>1 dispatch (LearnerConfig docs).
    traj_ring: bool = False
    # IMPACT replay (torched_impala_tpu/replay/, docs/REPLAY.md): train
    # on each ring slot up to `max_reuse` times with the clipped
    # target-network surrogate. max_reuse > 1 requires traj_ring=True
    # and target_update_interval >= 1 (ReplayConfig.validate); the
    # defaults keep replay off (and the learner on the exact pre-replay
    # code path).
    max_reuse: int = 1
    replay_mix: float = 1.0
    replay_staleness_frames: int = 0
    target_update_interval: int = 0
    target_clip_epsilon: float = 0.2
    unroll_length: int = 20
    batch_size: int = 8
    # Fuse K SGD steps into one dispatched XLA program (lax.scan over a
    # [K, ...] superbatch) — amortizes per-dispatch host latency at the
    # cost of params publish landing every K steps (LearnerConfig docs).
    steps_per_dispatch: int = 1
    # Zero-copy feed path (ISSUE 13, `--superbatch-k` bundles all
    # three pieces): donate ring slots straight into the compiled train
    # step (no host staging copy, slot released one step behind), run
    # the loss epilogue fused with the V-trace recursion.
    donate_batch: bool = False
    fused_epilogue: bool = False
    # Train-step compute dtype (ISSUE 16; ops/precision.py policy role
    # "train_step"): 'bfloat16' runs the FULL step — params and
    # activations — in bf16 (params cast inside the loss closure, so
    # the optimizer updates f32 master weights) and also selects the
    # fused epilogue's [T, B, A] elementwise phase dtype when
    # fused_epilogue is on. Optimizer moments, PopArt stats and the
    # V-trace recursion stay f32 regardless; run.py gates bf16 behind
    # a greedy-action parity probe and falls back to f32 on failure.
    train_dtype: str = "float32"
    total_env_frames: int = 1_000_000
    # Optimization.
    lr: float = 6e-4
    lr_anneal: bool = True  # linear anneal to 0 over total_env_frames
    # Large-batch operating point (ISSUE 16; arxiv 1803.02811's
    # linear-scaling playbook): when lr_scale_ref_batch > 0, the base
    # lr is cfg.lr * (B*K / lr_scale_ref_batch) with B*K the effective
    # batch (batch_size * steps_per_dispatch), and lr_warmup_steps
    # learner steps ramp linearly 0 -> base before the anneal begins.
    # Resume-mid-warmup is correct by construction: optax schedules
    # index the restored optimizer step count.
    lr_scale_ref_batch: int = 0
    lr_warmup_steps: int = 0
    rmsprop_decay: float = 0.99
    rmsprop_eps: float = 1e-7  # paper uses 0.1 for Atari; analog 1e-7
    max_grad_norm: float = 40.0
    # Loss.
    discount: float = 0.99
    entropy_coef: float = 0.01
    vf_coef: float = 0.5
    # Observability (telemetry/, docs/OBSERVABILITY.md): merge the
    # telemetry registry snapshot into every Nth metrics write (0 = keep
    # recording but never merge), and arm the stall watchdog with this
    # deadline in seconds (0 = off). 300s is comfortably above any sane
    # step/wave period on every preset yet turns an overnight silent hang
    # into a same-minute stack dump.
    telemetry_interval: int = 1
    stall_timeout_s: float = 300.0
    # Training-health diagnostics plane (telemetry/health.py):
    # `health_diagnostics` compiles the learning-health gauges — V-trace
    # rho/c clip fractions + pre-clip IS-weight histogram, entropy,
    # behaviour->learner KL, value explained variance, per-layer-group
    # grad norms / update ratios, PopArt drift — into the train step
    # (they ride the existing log-interval materialization; off = bit-
    # identical step) and arms the HealthMonitor -> burn-rate health
    # alerts -> postmortem-bundle chain. Anomaly bundles land under
    # `postmortem_dir` (tools/postmortem.py renders them). run.py:
    # `--health` / `--postmortem-dir`.
    health_diagnostics: bool = False
    postmortem_dir: str = "postmortems"
    # Closed-loop control plane (ControlConfig above; `--control
    # auto|off` / `--control-interval` in run.py).
    control: ControlConfig = ControlConfig()
    # Resilience (torched_impala_tpu/resilience/, docs/RESILIENCE.md):
    # checkpoint cadence and retention, wired through `--checkpoint-
    # interval` / `--checkpoint-keep` / `--checkpoint-seconds`.
    # `checkpoint_interval` is learner steps between saves;
    # `checkpoint_seconds` (async backend only, 0 = off) additionally
    # triggers a save when that much wall time passed — whichever comes
    # first. `checkpoint_keep` bounds retained checkpoints in BOTH
    # backends (orbax max_to_keep / async retention prune).
    checkpoint_interval: int = 1000
    checkpoint_keep: int = 3
    checkpoint_seconds: float = 0.0
    # Serving tier (torched_impala_tpu/serving/, docs/SERVING.md): the
    # batched-inference service parameters used when eval (or a serving
    # fleet) routes policy requests through a PolicyServer.
    # `serving_max_batch` is the padded wave width (ONE compiled shape);
    # `serving_wait_ms` the coalescing window (a wave launches when
    # max_batch distinct clients wait OR the oldest request ages this
    # much); `serving_dtype` opts serving into bf16-cast or int8
    # per-channel-quantized params — both gated on the f32 greedy-action
    # parity check (serving.greedy_action_parity);
    # `serving_replicas` > 1 serves through a ServingFleet (replicated
    # PolicyServers + least-loaded router, serving/fleet.py).
    serving_max_batch: int = 32
    serving_wait_ms: float = 2.0
    serving_dtype: str = "float32"
    serving_replicas: int = 1
    # Flight-recorder export (telemetry/tracing.py): write the retained
    # trace events — per-unroll lineage IDs threaded env→pool→queue/
    # ring→learner with exact per-batch param lag — as Chrome-trace
    # JSON at this path when the run ends ("" = no export; the recorder
    # itself is always on, and SIGUSR2 dumps it on demand). run.py's
    # `--trace out.json` overrides per run.
    trace_path: str = ""
    # Performance observatory (perf/report.py): analyze the flight
    # recorder at run end into a roofline + pipeline-attribution report
    # (JSON at this path, human-readable .txt sibling; "" = off).
    # run.py's `--perf-report out.json` overrides per run, and SIGUSR2
    # also dumps a live report when enabled.
    perf_report: str = ""
    # Observability plane exposition (telemetry/export.py): serve the
    # run-wide AGGREGATED snapshot (local registry + proc<h>w<w>/
    # worker fan-in) as an OpenMetrics endpoint on this TCP port
    # (0 = off), and/or atomic-write it to this file path ("" = off;
    # the sandboxed-run fallback). Either one also arms the SLO
    # burn-rate alert engine (telemetry/alerts.py). run.py's
    # `--metrics-port` / `--metrics-file` override per run.
    metrics_port: int = 0
    metrics_file: str = ""
    # Parallelism: shard the learner batch over this many devices (DP);
    # 0 = single device. SURVEY.md §3b DP row.
    dp_devices: int = 0
    # Tensor parallelism: widen the mesh's 'model' axis to this many
    # devices — weight matrices shard by output features
    # (parallel.model_shardings), composing with DP as a ('data','model')
    # mesh. 0/1 = off.
    tp_devices: int = 0
    popart_step_size: float = 3e-4

    @property
    def frames_per_step(self) -> int:
        return self.unroll_length * self.batch_size

    @property
    def total_learner_steps(self) -> int:
        return max(1, self.total_env_frames // self.frames_per_step)


# Dense-attention 'auto' crossover: use the Pallas flash kernel only when
# the learner's score matrix reaches this many elements. The constant
# dates from an earlier rig's v5e readings (kernel ahead from T*S ~ 1M,
# behind XLA's fused einsum at the pong_transformer preset's T=21/S=149,
# where kernel-launch overhead covers a 3k-element tile); it has NOT been
# re-measured on the current chip (PERF.md). Retune by editing this
# constant or
# force per-experiment via ExperimentConfig.transformer_dense_kernel.
PALLAS_MIN_SCORE_ELEMS = 1 << 18


def make_agent(cfg: ExperimentConfig, mesh=None) -> Agent:
    """Build the policy agent for a config.

    `mesh` is required when `cfg.transformer_attention != "dense"`: the
    transformer core's sequence-parallel attention runs over it (a
    ('data','seq') mesh from `run.py --dp N --sp M`, batch over 'data',
    unroll over 'seq'; see models/transformer.py)."""
    if cfg.compute_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"unknown compute_dtype {cfg.compute_dtype!r}; "
            "expected 'float32' or 'bfloat16'"
        )
    if cfg.transformer_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"unknown transformer_dtype {cfg.transformer_dtype!r}; "
            "expected 'float32' or 'bfloat16'"
        )
    if cfg.transformer_dense_kernel not in ("auto", "pallas", "einsum"):
        raise ValueError(
            f"unknown transformer_dense_kernel "
            f"{cfg.transformer_dense_kernel!r}; "
            "expected 'auto', 'pallas' or 'einsum'"
        )
    from torched_impala_tpu.ops import precision

    precision.validate_compute_dtype("train_step", cfg.train_dtype)
    dtype = jnp.dtype(cfg.compute_dtype)
    if cfg.train_dtype == "bfloat16":
        # Full-bf16 train step (ISSUE 16): activations follow the train
        # compute dtype end-to-end. The heads and the recurrent core
        # still cast to f32 (models/nets.py), matching the policy's
        # lstm_carry / loss_reductions accumulator roles.
        dtype = jnp.dtype("bfloat16")
    torso_cls = {
        "mlp": MLPTorso,
        "shallow_cnn": AtariShallowTorso,
        "deep_resnet": AtariDeepTorso,
    }.get(cfg.model)
    if torso_cls is None:
        raise ValueError(f"unknown model {cfg.model!r}")
    if cfg.remat_torso:
        # nn.remat is parameter-transparent: the wrapped class produces an
        # identical param tree (checkpoints interchange with the unwrapped
        # net) and identical outputs/grads — pinned in tests/test_models.py.
        import flax.linen as nn

        torso_cls = nn.remat(torso_cls)
    torso_kwargs = {"dtype": dtype}
    if cfg.model == "deep_resnet":
        # Only the ResNet torso has residual blocks to fuse; the flag is
        # a no-op (and rejected) elsewhere.
        torso_kwargs["fused_blocks"] = cfg.fused_conv
    elif cfg.fused_conv:
        raise ValueError(
            "fused_conv requires model='deep_resnet' "
            f"(got model={cfg.model!r})"
        )
    torso = torso_cls(**torso_kwargs)
    # Dense-path attention math, resolved HERE against the actual compute
    # devices (mesh when given, default backend otherwise), mirroring the
    # learner's V-trace 'auto' resolution; the core itself refuses 'auto'.
    from torched_impala_tpu.ops.vtrace import resolve_implementation

    devices = None if mesh is None else list(mesh.devices.flat)
    t_learner = cfg.unroll_length + 1
    score_elems = t_learner * (cfg.transformer_window + t_learner)
    if cfg.transformer_dense_kernel != "auto":
        dense_kernel = cfg.transformer_dense_kernel
    else:
        dense_kernel = (
            "pallas"
            if resolve_implementation("auto", devices) == "pallas"
            and score_elems >= PALLAS_MIN_SCORE_ELEMS
            else "einsum"
        )
    # The hybrid core takes the fused attention whenever it runs on a TPU,
    # whatever PALLAS_MIN_SCORE_ELEMS says: with 40 query heads an einsum's
    # scores over (cache + unroll) are 40 x 2048 x 4096 x 4 B = 2.7 GB a
    # layer at the preset's sizes, and grouped heads and the window live
    # in the kernel. 'einsum' still forces the written-out mask.
    on_tpu = resolve_implementation("auto", devices) == "pallas"
    if cfg.hybrid_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"unknown hybrid_dtype {cfg.hybrid_dtype!r}; "
            "expected 'float32' or 'bfloat16'"
        )
    hybrid = ()
    if cfg.core == "hybrid":
        hybrid = (
            ("d_model", cfg.hybrid_d_model),
            ("layers", tuple(cfg.hybrid_layers)),
            ("num_heads", cfg.hybrid_heads),
            ("num_kv_heads", cfg.hybrid_kv_heads),
            ("head_dim", cfg.hybrid_head_dim),
            ("window", cfg.hybrid_window),
            ("full_cache", cfg.hybrid_full_cache),
            ("d_intermediate", cfg.hybrid_d_intermediate),
            ("d_inner", cfg.hybrid_d_inner),
            ("d_state", cfg.hybrid_d_state),
            ("d_conv", cfg.hybrid_d_conv),
            ("dt_rank", cfg.hybrid_dt_rank),
            ("dtype", jnp.dtype(cfg.hybrid_dtype)),
            ("remat", cfg.hybrid_remat),
            (
                "attention_kernel",
                cfg.transformer_dense_kernel
                if cfg.transformer_dense_kernel != "auto"
                else ("pallas" if on_tpu else "einsum"),
            ),
            ("norm", cfg.hybrid_norm),
            ("mlp", cfg.hybrid_mlp),
            ("num_experts", cfg.hybrid_experts),
            ("experts_per_token", cfg.hybrid_experts_per_token),
            ("experts_held", tuple(cfg.hybrid_experts_held)),
            ("expert_width", cfg.hybrid_expert_width),
            ("dense_layers", cfg.hybrid_dense_layers),
            ("shared_expert", cfg.hybrid_shared_expert),
            ("routed_scale", cfg.hybrid_routed_scale),
            ("latent", tuple(cfg.hybrid_latent)),
            ("rope_theta", cfg.hybrid_rope_theta),
            ("ln_eps", cfg.hybrid_eps),
        )
    transformer = (
        ("d_model", cfg.transformer_d_model),
        ("num_layers", cfg.transformer_layers),
        ("num_heads", cfg.transformer_heads),
        ("window", cfg.transformer_window),
        ("dense_kernel", dense_kernel),
        # Opt-in core compute dtype (cfg.transformer_dtype, NOT
        # compute_dtype: the small-preset measurement says the torso
        # lever and the core lever want independent settings).
        ("dtype", jnp.dtype(cfg.transformer_dtype)),
    )
    if cfg.transformer_attention != "dense":
        if mesh is None:
            raise ValueError(
                f"transformer_attention={cfg.transformer_attention!r} "
                "needs a ('data','seq') mesh (run.py builds one from "
                "--dp/--sp)"
            )
        transformer += (
            ("attention", cfg.transformer_attention),
            ("sp_mesh", mesh),
            ("sp_batch_axis", "data"),
        )
    net = ImpalaNet(
        num_actions=cfg.num_actions,
        torso=torso,
        use_lstm=cfg.use_lstm,
        core=cfg.core,
        lstm_size=cfg.lstm_size,
        transformer=transformer,
        hybrid=hybrid,
        num_values=cfg.num_tasks,
    )
    return Agent(net)


def check_train_dtype_parity(
    cfg: ExperimentConfig,
    mesh=None,
    *,
    seed: int = 0,
    batch: int = 8,
    unroll: int = 4,
) -> tuple[bool, int]:
    """Train-side greedy-action parity gate for `train_dtype` (ISSUE
    16; the serving gate's idiom — serving.greedy_action_parity):
    argmax actions of the reduced-precision train forward (the bf16
    agent unrolling bf16-cast params, exactly what the full-bf16 loss
    closure runs) must equal the f32 reference on a fixed `[T, B]`
    probe. Returns (ok, mismatches over T*B probe actions). Callers
    refuse the half dtype and fall back to f32 on failure (run.py's
    warning path; doctor's "mixed precision" row), mirroring how
    serving refuses a failing bf16/int8 cast. Deterministic: argmax
    needs no sampling key."""
    import jax

    from torched_impala_tpu.ops import precision

    if cfg.train_dtype == "float32":
        return True, 0
    agent_ref = make_agent(
        dataclasses.replace(cfg, train_dtype="float32"), mesh=mesh
    )
    agent_half = make_agent(cfg, mesh=mesh)
    example = example_obs(cfg)
    rng = np.random.default_rng(seed)
    shape = (unroll, batch, *example.shape)
    if example.dtype == np.uint8:
        probe = rng.integers(0, 256, size=shape, dtype=np.uint8)
    else:
        probe = rng.normal(size=shape).astype(example.dtype)
    probe = jnp.asarray(probe)
    first = jnp.zeros((unroll, batch), jnp.bool_).at[0].set(True)
    params = agent_ref.init_params(
        jax.random.key(seed), jnp.asarray(example)
    )

    def greedy(agent, p):
        out, _ = agent.unroll(p, probe, first, agent.initial_state(batch))
        return np.asarray(jnp.argmax(out.policy_logits, axis=-1))

    a_ref = greedy(agent_ref, params)
    a_half = greedy(
        agent_half, precision.cast_to_compute(params, cfg.train_dtype)
    )
    mismatches = int(np.sum(a_ref != a_half))
    return mismatches == 0, mismatches


def scaled_base_lr(cfg: ExperimentConfig) -> float:
    """cfg.lr linearly scaled by effective batch (B*K) against the
    reference batch, per the large-batch playbook (arxiv 1803.02811).
    `lr_scale_ref_batch == 0` disables scaling."""
    if cfg.lr_scale_ref_batch <= 0:
        return cfg.lr
    effective_batch = cfg.batch_size * max(1, cfg.steps_per_dispatch)
    return cfg.lr * (effective_batch / cfg.lr_scale_ref_batch)


def make_lr_schedule(cfg: ExperimentConfig):
    """The learning-rate schedule (or constant): optional linear warmup
    over `lr_warmup_steps` learner steps from 0 to the (batch-scaled)
    base lr, then the paper's linear anneal-to-zero over the remaining
    steps (or a constant tail with lr_anneal=False). Schedules are
    indexed by the optimizer's step count, so a checkpoint restored
    mid-warmup resumes at the right point on the ramp."""
    base_lr = scaled_base_lr(cfg)
    warmup = max(0, cfg.lr_warmup_steps)
    if cfg.lr_anneal:
        tail = optax.linear_schedule(
            init_value=base_lr,
            end_value=0.0,
            transition_steps=max(1, cfg.total_learner_steps - warmup),
        )
    elif warmup:
        tail = optax.constant_schedule(base_lr)
    else:
        return base_lr
    if warmup:
        return optax.join_schedules(
            [
                optax.linear_schedule(
                    init_value=0.0,
                    end_value=base_lr,
                    transition_steps=warmup,
                ),
                tail,
            ],
            [warmup],
        )
    return tail


def make_optimizer(cfg: ExperimentConfig) -> optax.GradientTransformation:
    """RMSProp under `make_lr_schedule` (warmup + linear-scaled base lr
    when configured, the paper's linear anneal-to-zero either way)."""
    return optax.rmsprop(
        make_lr_schedule(cfg),
        decay=cfg.rmsprop_decay,
        eps=cfg.rmsprop_eps,
    )


def make_learner_config(cfg: ExperimentConfig) -> LearnerConfig:
    replay = None
    if cfg.max_reuse > 1 or cfg.target_update_interval > 0:
        from torched_impala_tpu.replay import ReplayConfig

        replay = ReplayConfig(
            max_reuse=cfg.max_reuse,
            replay_mix=cfg.replay_mix,
            staleness_frames=cfg.replay_staleness_frames,
            target_update_interval=cfg.target_update_interval,
            target_clip_epsilon=cfg.target_clip_epsilon,
        )
    return LearnerConfig(
        batch_size=cfg.batch_size,
        unroll_length=cfg.unroll_length,
        loss=ImpalaLossConfig(
            discount=cfg.discount,
            vf_coef=cfg.vf_coef,
            entropy_coef=cfg.entropy_coef,
            reduction=cfg.loss_reduction,
            fused_epilogue=cfg.fused_epilogue,
            health_diagnostics=cfg.health_diagnostics,
            train_dtype=cfg.train_dtype,
        ),
        max_grad_norm=cfg.max_grad_norm,
        steps_per_dispatch=cfg.steps_per_dispatch,
        traj_ring=cfg.traj_ring,
        donate_batch=cfg.donate_batch,
        train_dtype=cfg.train_dtype,
        replay=replay,
        popart=(
            PopArtConfig(
                num_values=cfg.num_tasks, step_size=cfg.popart_step_size
            )
            if cfg.num_tasks > 1
            else None
        ),
    )


def example_obs(cfg: ExperimentConfig) -> np.ndarray:
    return np.zeros(cfg.obs_shape, np.dtype(cfg.obs_dtype))


@dataclasses.dataclass(frozen=True)
class _EnvFactory:
    """Picklable (seed, env_index=None) -> env factory for one preset.

    A module-level class (not a closure) so process-mode actors can ship it
    across the multiprocessing spawn boundary (runtime/env_pool.py).

    Multi-task presets assign `task = env_index % num_tasks`: the explicit
    env index (global env slot, passed by the runtime) guarantees every task
    is instantiated. Deriving tasks from the seed is WRONG — the runtime
    strides seeds by 1000 per actor and gcd(1000, num_tasks) > 1 silently
    drops tasks (round-1 advisor finding). The seed fallback exists only for
    legacy single-task callers.
    """

    cfg: ExperimentConfig
    fake: bool

    def _task_of(self, seed: int, env_index) -> int:
        idx = env_index if env_index is not None else seed
        return idx % max(1, self.cfg.num_tasks)

    def __call__(self, seed: int, env_index=None):
        cfg = self.cfg
        task = self._task_of(seed, env_index)
        if cfg.env_family.startswith("jax_"):
            # Pure-JAX envs are their own host fallback: the gym adapter
            # steps the identical dynamics on CPU, so eval and thread/
            # process actors see the same MDP as the on-device path.
            from torched_impala_tpu.envs.jax_envs import JaxEnvGymWrapper

            env = JaxEnvGymWrapper(make_jax_env(cfg), seed=seed)
            env.task_id = task
            return env
        if self.fake:
            return self._fake(seed, task)
        from torched_impala_tpu.envs import FACTORIES

        family = FACTORIES[cfg.env_family]
        if cfg.env_family == "cartpole":
            env, _, _ = family(seed=seed)
        elif cfg.env_family == "atari":
            env, _, _ = family(
                cfg.env_id,
                seed=seed,
                task=task,
                episodic_life=cfg.episodic_life,
                fire_reset=cfg.fire_reset,
            )
        else:
            env, _, _ = family(cfg.env_id, seed=seed, task=task)
        env.task_id = task
        return env

    def _fake(self, seed: int, task: int):
        from torched_impala_tpu.envs.fake import (
            FakeAtariEnv,
            FakeDiscreteEnv,
        )

        cfg = self.cfg
        if cfg.obs_dtype == "uint8":
            shape = cfg.obs_shape

            class _ShapedPixels(FakeAtariEnv):
                def _obs(self):
                    return self._rng.integers(
                        0, 256, size=shape, dtype=np.uint8
                    )

            pixel_cls = (
                FakeAtariEnv if shape == (84, 84, 4) else _ShapedPixels
            )
            env = pixel_cls(num_actions=cfg.num_actions, seed=seed)
            env.task_id = task
            return env
        return FakeDiscreteEnv(
            obs_shape=cfg.obs_shape,
            num_actions=cfg.num_actions,
            task_id=task,
            seed=seed,
        )


def make_jax_env(cfg: ExperimentConfig):
    """Build the pure-JAX env for `runtime="anakin"` presets."""
    from torched_impala_tpu.envs import JaxCartPole, JaxCatch, JaxPixelSignal

    if cfg.env_family == "jax_cartpole":
        return JaxCartPole()
    if cfg.env_family == "jax_catch":
        return JaxCatch()
    if cfg.env_family == "jax_pixels":
        return JaxPixelSignal(
            size=cfg.obs_shape[0],
            channels=cfg.obs_shape[-1],
            num_actions=cfg.num_actions,
        )
    raise ValueError(
        f"env_family {cfg.env_family!r} has no pure-JAX implementation "
        "(anakin runtime needs one of: jax_cartpole, jax_catch, jax_pixels)"
    )


def make_env_factory(
    cfg: ExperimentConfig, *, fake: bool = False
) -> Callable[..., object]:
    """(seed, env_index=None) -> env. `fake=True` substitutes shape-faithful
    fakes for env families whose emulators aren't installed
    (throughput/integration runs on any host). The returned factory is
    picklable — required for `actor_mode="process"`."""
    return _EnvFactory(cfg, fake)


def probe_num_actions(cfg: ExperimentConfig) -> int:
    """Construct ONE real env for `cfg` and return its action-space size.

    Needed when `--env-id` overrides a preset's env: the preset's
    `num_actions` constant describes the ORIGINAL game, and building the
    policy head from it would sample out-of-range (or unreachable)
    actions for the substituted one (e.g. pong's 6 vs Breakout's 4)."""
    env = _EnvFactory(cfg, fake=False)(seed=0, env_index=0)
    try:
        return int(env.action_space.n)
    finally:
        close = getattr(env, "close", None)
        if close is not None:
            close()


# ---- the five BASELINE.json presets ------------------------------------

CARTPOLE = ExperimentConfig(
    name="cartpole",
    env_family="cartpole",
    obs_shape=(4,),
    num_actions=2,
    model="mlp",
    num_actors=4,
    unroll_length=20,
    batch_size=8,
    total_env_frames=200_000,
    lr=5e-3,
    lr_anneal=False,
)

PONG = ExperimentConfig(
    name="pong",
    env_family="atari",
    env_id="PongNoFrameskip-v4",
    obs_shape=(84, 84, 4),
    obs_dtype="uint8",
    num_actions=6,
    model="shallow_cnn",
    compute_dtype="bfloat16",
    episodic_life=True,
    fire_reset=True,
    actor_mode="process",
    num_actors=32,
    unroll_length=20,
    batch_size=32,
    total_env_frames=200_000_000,
)

BREAKOUT = ExperimentConfig(
    name="breakout",
    env_family="atari",
    env_id="BreakoutNoFrameskip-v4",
    obs_shape=(84, 84, 4),
    obs_dtype="uint8",
    num_actions=4,
    model="deep_resnet",
    compute_dtype="bfloat16",
    episodic_life=True,
    fire_reset=True,
    use_lstm=True,
    actor_mode="process",
    num_actors=256,
    unroll_length=20,
    batch_size=32,
    total_env_frames=200_000_000,
)

PROCGEN = ExperimentConfig(
    name="procgen",
    env_family="procgen",
    env_id="coinrun",
    obs_shape=(64, 64, 3),
    obs_dtype="uint8",
    num_actions=15,
    model="deep_resnet",
    compute_dtype="bfloat16",
    actor_mode="process",
    # The largest fleet is where one straggler gates 512 envs in lockstep:
    # ready-set batching over the first 75% of workers (the gain is
    # not measured on the chip's host: no cell drives an env pool).
    pool_mode="async",
    num_actors=512,
    unroll_length=20,
    batch_size=64,
    total_env_frames=200_000_000,
    dp_devices=-1,  # -1 = all available devices (DP learner preset)
)

DMLAB30 = ExperimentConfig(
    name="dmlab30",
    env_family="dmlab",
    env_id="dmlab30",
    obs_shape=(72, 96, 3),
    obs_dtype="uint8",
    num_actions=15,
    num_tasks=30,
    model="deep_resnet",
    compute_dtype="bfloat16",
    use_lstm=True,
    actor_mode="process",
    num_actors=256,
    unroll_length=100,
    batch_size=32,
    total_env_frames=10_000_000_000,
)

# Experimental (beyond the five BASELINE presets): the transformer temporal
# core on Pong shapes — exercises core="transformer" end-to-end
# (models/transformer.py; VERDICT round 1 item 7). Runs with --fake-envs on
# emulator-less hosts like any Atari preset.
PONG_TRANSFORMER = ExperimentConfig(
    name="pong_transformer",
    env_family="atari",
    env_id="PongNoFrameskip-v4",
    obs_shape=(84, 84, 4),
    obs_dtype="uint8",
    num_actions=6,
    model="shallow_cnn",
    compute_dtype="bfloat16",
    episodic_life=True,
    fire_reset=True,
    core="transformer",
    transformer_d_model=256,
    transformer_layers=2,
    transformer_heads=4,
    transformer_window=128,
    actor_mode="process",
    num_actors=32,
    unroll_length=20,
    batch_size=32,
    total_env_frames=200_000_000,
)

# The hybrid temporal core at the published widths of
# Phi-4-mini-flash-reasoning's self-decoder (arXiv:2507.06607; layers
# 14-17 of its 32: Mamba, window attention, Mamba, full attention) on
# Pong's shapes: a long-memory agent, few long unrolls a chip. 439M
# parameters; benchmark/configs/pong_phi4flash_core.json states the cut.
# Every step publishes a version of 1.76 GB: the host's copy of it, not
# the chip's 0.2 s step, sets the pace (PERF.md section 6, PR 34).
PONG_PHI4FLASH = ExperimentConfig(
    name="pong_phi4flash",
    env_family="atari",
    env_id="PongNoFrameskip-v4",
    obs_shape=(84, 84, 4),
    obs_dtype="uint8",
    num_actions=6,
    model="shallow_cnn",
    compute_dtype="bfloat16",
    episodic_life=True,
    fire_reset=True,
    core="hybrid",
    actor_mode="process",
    num_actors=2,
    unroll_length=2047,
    batch_size=2,
    total_env_frames=200_000_000,
)

# Layers 2-5 of LiquidAI's LFM2-8B-A1B (full attention with rotary
# embeddings and per-head q/k norms, then three gated short convolutions),
# every one with its routed experts, as the temporal core on Pong's shapes,
# at the published widths. The learner is one of the four chips that share
# each layer's 32 experts: it holds 8 of them (413M parameters in the
# core), routes over all 32 and computes its own experts' part;
# benchmark/configs/pong_lfm2_moe_core.json states the cut.
PONG_LFM2_MOE = dataclasses.replace(
    PONG_PHI4FLASH,
    name="pong_lfm2_moe",
    hybrid_layers=("full_rope", "short_conv", "short_conv", "short_conv"),
    hybrid_d_model=2048,
    hybrid_heads=32,
    hybrid_kv_heads=8,
    hybrid_head_dim=64,
    hybrid_full_cache=2048,
    hybrid_norm="rms",
    hybrid_mlp="moe",
    hybrid_experts=32,
    hybrid_experts_per_token=4,
    hybrid_experts_held=(0, 8),
    hybrid_expert_width=1792,
)

# JoyAI-LLM-Flash's leading dense layer and its first four expert layers
# (a DeepSeek-V3 decoder: latent attention of 32 heads, query/key width 192
# = 128 + a rotary part of 64 shared by every head, values of 128; a
# latent cache of c_kv 512 and k_pe 64 a position) as the temporal core on
# Pong's shapes, at the published widths. The learner is one of the 32
# chips that share each layer's 256 routed experts: it holds 8 of them
# (top-8 over all 256, sigmoid scores, scale 2.5) beside the shared
# expert; 347.8M parameters in the core.
# benchmark/configs/pong_joyai_flash_core.json states the cut.
PONG_JOYAI_FLASH = dataclasses.replace(
    PONG_LFM2_MOE,
    name="pong_joyai_flash",
    hybrid_layers=("latent",) * 5,
    hybrid_heads=32,
    hybrid_latent=(1536, 512, 128, 64, 128),
    hybrid_rope_theta=32_000_000.0,
    hybrid_eps=1e-6,
    hybrid_d_intermediate=7168,
    hybrid_dense_layers=1,
    hybrid_experts=256,
    hybrid_experts_per_token=8,
    hybrid_experts_held=(0, 8),
    hybrid_expert_width=768,
    hybrid_shared_expert=768,
    hybrid_routed_scale=2.5,
)

# On-device (Anakin) presets: the whole actor-learner is one XLA program
# over pure-JAX envs (runtime/anakin.py). batch_size = on-device env count.
# Same MDPs as their host counterparts (envs/jax_envs.py parity tests), so
# eval-mode and host-actor runs of these presets use the identical dynamics
# through the gym adapter.
CARTPOLE_ANAKIN = ExperimentConfig(
    name="cartpole_anakin",
    env_family="jax_cartpole",
    obs_shape=(4,),
    num_actions=2,
    model="mlp",
    runtime="anakin",
    loss_reduction="mean",
    unroll_length=32,
    batch_size=256,
    total_env_frames=4_000_000,
    lr=3e-3,
    lr_anneal=False,
)

CATCH_ANAKIN = ExperimentConfig(
    name="catch_anakin",
    env_family="jax_catch",
    obs_shape=(50,),
    num_actions=3,
    model="mlp",
    runtime="anakin",
    loss_reduction="mean",
    unroll_length=16,
    batch_size=128,
    total_env_frames=1_000_000,
    lr=5e-3,
    lr_anneal=False,
)

# Atari-shaped pixels fully on-device: the bf16 Nature-CNN learns the
# JaxPixelSignal quadrant->action signal with env stepping fused into the
# train program — the closest on-device analog of the Pong pipeline.
PIXELS_ANAKIN = ExperimentConfig(
    name="pixels_anakin",
    env_family="jax_pixels",
    obs_shape=(84, 84, 4),
    obs_dtype="uint8",
    num_actions=4,
    model="shallow_cnn",
    compute_dtype="bfloat16",
    runtime="anakin",
    loss_reduction="mean",
    unroll_length=20,
    batch_size=128,
    total_env_frames=50_000_000,
    lr=1e-3,
    lr_anneal=False,
)

REGISTRY: dict[str, ExperimentConfig] = {
    c.name: c
    for c in (
        CARTPOLE,
        PONG,
        BREAKOUT,
        PROCGEN,
        DMLAB30,
        PONG_TRANSFORMER,
        PONG_PHI4FLASH,
        PONG_LFM2_MOE,
        PONG_JOYAI_FLASH,
        CARTPOLE_ANAKIN,
        CATCH_ANAKIN,
        PIXELS_ANAKIN,
    )
}
