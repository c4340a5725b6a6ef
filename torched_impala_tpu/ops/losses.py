"""IMPALA actor-critic loss: policy gradient + baseline + entropy, time-major.

Loss = pg + vf_coef * baseline + entropy_coef * (negative entropy), summed over
the `[T, B]` unroll with an optional validity mask (episode-boundary steps can
be masked out). Semantics follow the IMPALA paper and the reference's loss
composition (SURVEY.md §1 item 3; default coefficients 1 / 0.5 / 0.01, where
`baseline_loss` itself carries a 0.5 factor so the *effective* squared-error
weight is vf_coef * 0.5 = 0.25 — matching the analog's double-0.5
composition, SURVEY.md §1 item 3 note).

All functions are pure and jit-safe; the categorical distribution math is
inlined (log_softmax) rather than pulled from a distributions library so the
whole loss fuses into the learner's single XLA program.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping, NamedTuple

import jax
import jax.numpy as jnp

from torched_impala_tpu.ops.vtrace import clipped_surrogate as _clipped_surrogate
from torched_impala_tpu.ops.vtrace import vtrace as _vtrace


@dataclasses.dataclass(frozen=True)
class ImpalaLossConfig:
    """Static hyper-parameters of the IMPALA loss (hashable; safe as a jit static)."""

    discount: float = 0.99
    vf_coef: float = 0.5
    entropy_coef: float = 0.01
    clip_rho_threshold: float = 1.0
    clip_c_threshold: float = 1.0
    clip_pg_rho_threshold: float = 1.0
    lambda_: float = 1.0
    # 'sum' matches the reference (losses summed over [T, B]); 'mean' divides
    # by the number of valid steps, decoupling lr from unroll/batch size.
    reduction: str = "sum"
    # 'auto' = fused Pallas kernel on TPU, lax.scan elsewhere. A perf
    # NON-LEVER either way: both sit at the dispatch floor (~0.2% of a
    # train step) on a real v5e — see ops/vtrace.py:vtrace.
    vtrace_implementation: str = "auto"
    # Fused V-trace + loss epilogue (ops/vtrace_pallas.fused_vtrace_loss):
    # ONE log_softmax serves ratios + policy gradient + entropy, the
    # recursion and the three masked reductions run next to each other
    # (inside the Pallas kernel on TPU), and the backward pass is an
    # analytic elementwise VJP. False = the exact pre-existing separate
    # epilogue, op for op.
    fused_epilogue: bool = False
    # In-jit training-health diagnostics (ISSUE 19): when True the loss
    # adds `health_`-prefixed scalar reductions over tensors already
    # live in the step (rho/c clip fractions, the pre-clip IS-weight
    # log-histogram, entropy, behaviour->learner KL, value explained
    # variance — see health_diagnostics_logs) to its logs;
    # telemetry/health.py republishes them as health/* gauges. False =
    # the exact pre-existing log set, op for op (the bit-parity
    # contract tests/test_health.py pins).
    health_diagnostics: bool = False
    # Train compute dtype ('float32' or 'bfloat16'; the ops/precision.py
    # "train_step"/"fused_epilogue_elementwise" policy roles). Here it
    # selects the fused epilogue's [T, B, A] softmax/elementwise phase
    # dtype when fused_epilogue is on; the SAME config value drives the
    # full-bf16 step's params/activations cast in the Learner
    # (LearnerConfig.train_dtype — one consistent surface via
    # configs.make_learner_config). Recursion, reductions, and PopArt
    # stats stay f32 regardless (the accumulator contract tools/lint
    # polices).
    train_dtype: str = "float32"


class LossOutput(NamedTuple):
    total: jax.Array
    logs: Mapping[str, jax.Array]


def _reduce(x: jax.Array, mask: jax.Array, reduction: str) -> jax.Array:
    total = jnp.sum(x * mask)
    if reduction == "sum":
        return total
    if reduction == "mean":
        return total / jnp.maximum(jnp.sum(mask), 1.0)
    raise ValueError(f"unknown reduction: {reduction!r}")


def action_log_probs(logits: jax.Array, actions: jax.Array) -> jax.Array:
    """log pi(a|x) of taken actions. logits `[..., A]`, actions `[...]` int."""
    log_pi = jax.nn.log_softmax(logits, axis=-1)
    return jnp.take_along_axis(log_pi, actions[..., None], axis=-1)[..., 0]


def entropy(logits: jax.Array) -> jax.Array:
    """Categorical entropy per step, `[...]` from logits `[..., A]`."""
    log_pi = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.exp(log_pi) * log_pi, axis=-1)


def policy_gradient_loss(
    logits: jax.Array,
    actions: jax.Array,
    advantages: jax.Array,
    mask: jax.Array,
    reduction: str = "sum",
) -> jax.Array:
    """-sum(A_t * log pi(a_t|x_t)); advantages are stop-gradiented."""
    log_probs = action_log_probs(logits, actions)
    return _reduce(
        -jax.lax.stop_gradient(advantages) * log_probs, mask, reduction
    )


def baseline_loss(
    errors: jax.Array, mask: jax.Array, reduction: str = "sum"
) -> jax.Array:
    """0.5 * sum((vs - V)^2). `errors` must carry gradient through V.

    Note: callers pass ``vs - values`` recomputed with live `values` (the
    VTraceOutput.errors field is stop-gradiented).
    """
    return 0.5 * _reduce(jnp.square(errors), mask, reduction)


def entropy_loss(
    logits: jax.Array, mask: jax.Array, reduction: str = "sum"
) -> jax.Array:
    """Negative entropy — *adding* this with a positive coef is an entropy bonus."""
    return _reduce(-entropy(logits), mask, reduction)


# Fixed log-space bin edges for the pre-clip IS-weight histogram
# (health diagnostics): log(rho) in (-inf,-2), [-2,-1), [-1,-0.5),
# [-0.5,0), [0,0.5), [0.5,1), [1,2), [2,inf). Exactly on-policy data
# piles into bin 4 (log rho = 0); mass drifting into the outer bins is
# the off-policy shift V-trace is about to clip away.
HEALTH_LOGRHO_EDGES = (-2.0, -1.0, -0.5, 0.0, 0.5, 1.0, 2.0)


def health_diagnostics_logs(
    *,
    learner_logits: jax.Array,
    behaviour_logits: jax.Array,
    log_rhos: jax.Array,
    values: jax.Array,
    vs: jax.Array,
    mask: jax.Array,
    config: ImpalaLossConfig,
) -> dict:
    """In-jit training-health diagnostics (ISSUE 19): one pass of
    masked scalar reductions over tensors the loss already computed —
    no new matmuls, no host syncs, everything under stop_gradient so
    the backward pass is untouched.

    Emits (all as masked per-step means, `health_` log-key prefix —
    telemetry/health.py maps these to `health/*` gauges):
      clip_rho_frac / clip_c_frac — fraction of valid steps whose
        pre-clip importance weight exp(log_rhos) exceeds the rho / c
        clip threshold (V-trace saturation, IMPALA arXiv:1802.01561
        sec. 4.1; IMPACT arXiv:1912.00167 reads this as the off-policy
        distance gauge);
      clip_logrho_mean / clip_logrho_std — moments of the pre-clip
        log-IS-weight;
      clip_logrho_bin0..7 — fixed-bin log-histogram fractions
        (HEALTH_LOGRHO_EDGES);
      entropy_mean — policy entropy of the optimized logits;
      kl_behaviour_learner — KL(mu || pi), behaviour->learner policy
        divergence per step;
      ev_value — explained variance of the baseline against its
        V-trace targets: 1 - Var(vs - V) / Var(vs).
    """
    sg = jax.lax.stop_gradient
    learner_logits = sg(learner_logits)
    behaviour_logits = sg(behaviour_logits)
    log_rhos = sg(log_rhos)
    values = sg(values)
    vs = sg(vs)
    mask = sg(mask)
    n = jnp.maximum(jnp.sum(mask), 1.0)

    def masked_mean(x):
        return jnp.sum(x * mask) / n

    rhos = jnp.exp(log_rhos)
    logrho_mean = masked_mean(log_rhos)
    logrho_var = masked_mean(jnp.square(log_rhos)) - jnp.square(logrho_mean)
    logs = {
        "health_clip_rho_frac": masked_mean(
            (rhos > config.clip_rho_threshold).astype(values.dtype)
        ),
        "health_clip_c_frac": masked_mean(
            (rhos > config.clip_c_threshold).astype(values.dtype)
        ),
        "health_clip_logrho_mean": logrho_mean,
        "health_clip_logrho_std": jnp.sqrt(jnp.maximum(logrho_var, 0.0)),
    }
    lo_edges = (-jnp.inf,) + HEALTH_LOGRHO_EDGES
    hi_edges = HEALTH_LOGRHO_EDGES + (jnp.inf,)
    for i, (lo, hi) in enumerate(zip(lo_edges, hi_edges)):
        in_bin = (log_rhos >= lo) & (log_rhos < hi)
        logs[f"health_clip_logrho_bin{i}"] = masked_mean(
            in_bin.astype(values.dtype)
        )
    logs["health_entropy_mean"] = masked_mean(entropy(learner_logits))
    log_pi = jax.nn.log_softmax(learner_logits, axis=-1)
    log_mu = jax.nn.log_softmax(behaviour_logits, axis=-1)
    kl = jnp.sum(jnp.exp(log_mu) * (log_mu - log_pi), axis=-1)
    logs["health_kl_behaviour_learner"] = masked_mean(kl)
    vs_mean = masked_mean(vs)
    vs_var = masked_mean(jnp.square(vs - vs_mean))
    err = vs - values
    err_mean = masked_mean(err)
    err_var = masked_mean(jnp.square(err - err_mean))
    logs["health_ev_value"] = 1.0 - err_var / jnp.maximum(vs_var, 1e-8)
    return logs


# Log keys that assemble_loss emits as SUMS over the batch when
# reduction="sum" (everything else it emits is a per-step mean).
# Consumers that combine logs across microbatches (Learner.grad_accum)
# key off this set, so it must stay next to the code that owns the
# reduction semantics.
SUM_REDUCED_LOG_KEYS = frozenset(
    {"pg_loss", "baseline_loss", "entropy_loss", "total_loss"}
)


def assemble_loss(
    *,
    pg: jax.Array,
    bl: jax.Array,
    ent: jax.Array,
    mask: jax.Array,
    config: ImpalaLossConfig,
    extra_logs: Mapping[str, jax.Array] | None = None,
) -> LossOutput:
    """Combine the three loss components and build the standard log dict.

    Shared by `impala_loss` and `ops.popart.popart_impala_loss` so the
    weighting and the entropy metric cannot drift between the two.
    """
    total = pg + config.vf_coef * bl + config.entropy_coef * ent
    logs = {
        "pg_loss": pg,
        "baseline_loss": bl,
        "entropy_loss": ent,
        "total_loss": total,
        "entropy": -ent / jnp.maximum(jnp.sum(mask), 1.0)
        if config.reduction == "sum"
        else -ent,
    }
    if extra_logs:
        logs.update(extra_logs)
    return LossOutput(total=total, logs=logs)


def impala_loss(
    *,
    target_logits: jax.Array,
    behaviour_logits: jax.Array,
    values: jax.Array,
    bootstrap_value: jax.Array,
    actions: jax.Array,
    rewards: jax.Array,
    discounts: jax.Array,
    mask: jax.Array | None = None,
    config: ImpalaLossConfig = ImpalaLossConfig(),
    devices=None,
) -> LossOutput:
    """Full IMPALA loss over a time-major unroll.

    Args:
      target_logits: `[T, B, A]` learner-policy logits at x_t.
      behaviour_logits: `[T, B, A]` actor-policy logits recorded at act time.
      values: `[T, B]` learner baseline V(x_t) — must carry gradient.
      bootstrap_value: `[B]` V(x_T).
      actions: `[T, B]` int actions taken.
      rewards: `[T, B]` rewards (already clipped upstream if configured).
      discounts: `[T, B]` per-step discounts `gamma * (1 - done)`.
      mask: `[T, B]` validity mask (1 = train on this step); defaults to ones.
      config: loss hyper-parameters.
      devices: the devices this loss will run on, used to resolve
        `config.vtrace_implementation == 'auto'` (e.g. `mesh.devices.flat`).
        None consults the default backend — wrong for a non-default-backend
        mesh, so meshed callers must pass it (VERDICT r2 weak #6).

    Returns:
      LossOutput(total, logs) where logs holds the per-component scalars the
      learner publishes (SURVEY.md §6 metrics set).
    """
    if config.fused_epilogue:
        from torched_impala_tpu.ops.vtrace_pallas import fused_vtrace_loss

        out = fused_vtrace_loss(
            target_logits=target_logits,
            behaviour_logits=behaviour_logits,
            values=values,
            bootstrap_value=bootstrap_value,
            actions=actions,
            rewards=rewards,
            discounts=discounts,
            mask=mask,
            config=config,
            # The fused path follows the same resolution as the separate
            # V-trace: the Learner turns 'auto' into 'scan' on a
            # multi-device mesh, and the kernel must give way with it.
            implementation={"scan": "xla", "pallas": "kernel"}.get(
                config.vtrace_implementation, "auto"
            ),
        )
        if not config.health_diagnostics:
            return out
        # Diagnostics under the fused epilogue: the kernel keeps no
        # intermediate (log_rhos, vs) outputs, so a supplementary
        # stop-gradient V-trace pass recomputes them — gradient-free
        # and elementwise-cheap, but not the zero-marginal-cost path;
        # the default separate epilogue folds diagnostics into tensors
        # it already holds.
        diag_mask = (
            jnp.ones_like(rewards) if mask is None else mask
        ).astype(values.dtype)
        log_rhos = action_log_probs(
            jax.lax.stop_gradient(target_logits), actions
        ) - action_log_probs(behaviour_logits, actions)
        vt = _vtrace(
            log_rhos=log_rhos,
            discounts=discounts,
            rewards=rewards,
            values=jax.lax.stop_gradient(values),
            bootstrap_value=jax.lax.stop_gradient(bootstrap_value),
            clip_rho_threshold=config.clip_rho_threshold,
            clip_c_threshold=config.clip_c_threshold,
            clip_pg_rho_threshold=config.clip_pg_rho_threshold,
            lambda_=config.lambda_,
            implementation=config.vtrace_implementation,
            devices=devices,
        )
        logs = dict(out.logs)
        logs.update(
            health_diagnostics_logs(
                learner_logits=target_logits,
                behaviour_logits=behaviour_logits,
                log_rhos=log_rhos,
                values=values,
                vs=vt.vs,
                mask=diag_mask,
                config=config,
            )
        )
        return LossOutput(total=out.total, logs=logs)
    if mask is None:
        mask = jnp.ones_like(rewards)
    mask = mask.astype(values.dtype)

    log_rhos = action_log_probs(target_logits, actions) - action_log_probs(
        behaviour_logits, actions
    )
    vt = _vtrace(
        log_rhos=log_rhos,
        discounts=discounts,
        rewards=rewards,
        values=jax.lax.stop_gradient(values),
        bootstrap_value=jax.lax.stop_gradient(bootstrap_value),
        clip_rho_threshold=config.clip_rho_threshold,
        clip_c_threshold=config.clip_c_threshold,
        clip_pg_rho_threshold=config.clip_pg_rho_threshold,
        lambda_=config.lambda_,
        implementation=config.vtrace_implementation,
        devices=devices,
    )

    pg = policy_gradient_loss(
        target_logits, actions, vt.pg_advantages, mask, config.reduction
    )
    # Baseline regresses live values towards the (constant) vs targets.
    bl = baseline_loss(vt.vs - values, mask, config.reduction)
    ent = entropy_loss(target_logits, mask, config.reduction)
    extra = {
        "mean_vtrace_target": jnp.mean(vt.vs),
        "mean_advantage": jnp.mean(vt.pg_advantages),
    }
    if config.health_diagnostics:
        extra.update(
            health_diagnostics_logs(
                learner_logits=target_logits,
                behaviour_logits=behaviour_logits,
                log_rhos=log_rhos,
                values=values,
                vs=vt.vs,
                mask=mask,
                config=config,
            )
        )
    return assemble_loss(
        pg=pg,
        bl=bl,
        ent=ent,
        mask=mask,
        config=config,
        extra_logs=extra,
    )


def impact_loss(
    *,
    learner_logits: jax.Array,
    target_logits: jax.Array,
    behaviour_logits: jax.Array,
    values: jax.Array,
    bootstrap_value: jax.Array,
    actions: jax.Array,
    rewards: jax.Array,
    discounts: jax.Array,
    mask: jax.Array | None = None,
    clip_epsilon: float = 0.2,
    config: ImpalaLossConfig = ImpalaLossConfig(),
    devices=None,
) -> LossOutput:
    """IMPACT clipped-target surrogate loss (arXiv:1912.00167), time-major.

    The replay-safe sibling of `impala_loss` (replay/ subsystem,
    docs/REPLAY.md "Loss math"). Three policies are in play:

      mu        — behaviour policy (actor logits recorded at act time)
      pi_target — the pinned target network (replay.TargetParamStore),
                  STALE BY CONSTRUCTION and held constant
      pi_theta  — the live learner policy being optimized

    V-trace corrections (rho, c, and the pg advantage) use
    pi_target / mu — the target policy is the stable anchor the replayed
    data is corrected towards — while the optimized term is the
    PPO-style clipped surrogate on r = pi_theta / pi_target
    (`ops.vtrace.clipped_surrogate`), so a slot replayed `reuse_count`
    times cannot drag pi_theta more than ~epsilon per step from the
    anchor regardless of how stale it has become.

    The baseline and entropy terms mirror `impala_loss` exactly: the
    baseline regresses the LIVE values onto the target-policy V-trace
    targets; entropy is of the live learner policy.

    Note this is deliberately NOT a generalization of `impala_loss`:
    at clip_epsilon→inf and target==learner the surrogate's VALUE is
    sum(A_t) rather than sum(-A_t log pi) (the gradients coincide at
    r=1, the objectives don't), so the replay-disabled learner takes
    the `impala_loss` code path unchanged — bit-identity by structure,
    pinned by tests/test_replay.py.

    Args:
      learner_logits: `[T, B, A]` live-policy logits — carry gradient.
      target_logits: `[T, B, A]` pinned-target logits — stop-gradiented
        here (belt and braces: the learner also stops them at unroll).
      behaviour_logits: `[T, B, A]` actor logits recorded at act time.
      values, bootstrap_value: live baseline V(x_t) `[T, B]` / V(x_T) `[B]`.
      actions, rewards, discounts, mask: as in `impala_loss`.
      clip_epsilon: surrogate clip radius (ReplayConfig.target_clip_epsilon).
      config, devices: as in `impala_loss`.

    Returns:
      LossOutput whose logs add `impact_ratio` (mean learner/target
      ratio, drift gauge) and `impact_clip_frac` (fraction of valid
      steps where the clip is active) to the standard set.
    """
    if mask is None:
        mask = jnp.ones_like(rewards)
    mask = mask.astype(values.dtype)

    target_logits = jax.lax.stop_gradient(target_logits)
    target_lp = action_log_probs(target_logits, actions)
    log_rhos = target_lp - action_log_probs(behaviour_logits, actions)
    vt = _vtrace(
        log_rhos=log_rhos,
        discounts=discounts,
        rewards=rewards,
        values=jax.lax.stop_gradient(values),
        bootstrap_value=jax.lax.stop_gradient(bootstrap_value),
        clip_rho_threshold=config.clip_rho_threshold,
        clip_c_threshold=config.clip_c_threshold,
        clip_pg_rho_threshold=config.clip_pg_rho_threshold,
        lambda_=config.lambda_,
        implementation=config.vtrace_implementation,
        devices=devices,
    )

    log_ratio = action_log_probs(learner_logits, actions) - target_lp
    surrogate, ratio = _clipped_surrogate(
        log_ratio, vt.pg_advantages, clip_epsilon
    )
    pg = _reduce(-surrogate, mask, config.reduction)
    bl = baseline_loss(vt.vs - values, mask, config.reduction)
    ent = entropy_loss(learner_logits, mask, config.reduction)
    n_valid = jnp.maximum(jnp.sum(mask), 1.0)
    clipped = jnp.abs(ratio - 1.0) > clip_epsilon
    extra = {
        "mean_vtrace_target": jnp.mean(vt.vs),
        "mean_advantage": jnp.mean(vt.pg_advantages),
        "impact_ratio": jnp.sum(ratio * mask) / n_valid,
        "impact_clip_frac": jnp.sum(clipped * mask) / n_valid,
    }
    if config.health_diagnostics:
        # log_rhos here are the V-trace correction weights
        # (pi_target / mu); entropy/KL diagnose the LIVE learner policy
        # — the distribution actually being optimized.
        extra.update(
            health_diagnostics_logs(
                learner_logits=learner_logits,
                behaviour_logits=behaviour_logits,
                log_rhos=log_rhos,
                values=values,
                vs=vt.vs,
                mask=mask,
                config=config,
            )
        )
    return assemble_loss(
        pg=pg,
        bl=bl,
        ent=ent,
        mask=mask,
        config=config,
        extra_logs=extra,
    )
