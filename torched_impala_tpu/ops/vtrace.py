"""V-trace off-policy correction (IMPALA, arXiv:1802.01561 §4.1), time-major.

The clipped-rho/c importance-weight recursion over the time axis:

    delta_t = rho_t * (r_t + gamma_t * V(x_{t+1}) - V(x_t))
    vs_t - V(x_t) = delta_t + gamma_t * c_t * (vs_{t+1} - V(x_{t+1}))
    rho_t = min(rho_bar, pi(a_t|x_t) / mu(a_t|x_t))
    c_t   = lambda * min(c_bar, pi(a_t|x_t) / mu(a_t|x_t))

and the policy-gradient advantage

    A_t = rho'_t * (r_t + gamma_t * vs_{t+1} - V(x_t)),
    rho'_t = min(rho_pg_bar, pi/mu).

Everything is time-major `[T, B]` so the recursion is a single
`jax.lax.scan(reverse=True)` — one fused XLA loop over T with all [B] lanes
vectorized on the VPU. A Pallas TPU kernel variant of the same recursion lives
in `vtrace_pallas.py`; `vtrace(..., implementation=...)` selects between them
behind one API.

Capability parity: reference `torched_impala` implements this recursion in
torch over the time axis (SURVEY.md §1 item 2, reconstructed from
BASELINE.json:5 — the reference mount was empty, see SURVEY.md §0).
"""

from __future__ import annotations

from typing import NamedTuple

import chex
import jax
import jax.numpy as jnp


class VTraceOutput(NamedTuple):
    """V-trace targets and policy-gradient advantages, both `[T, B]`.

    Attributes:
      vs: V-trace value targets for V(x_s); train the baseline towards these.
      pg_advantages: clipped-rho-weighted advantages for the policy gradient.
      errors: ``vs - values`` (the TD-like error the baseline loss regresses).
    """

    vs: jax.Array
    pg_advantages: jax.Array
    errors: jax.Array


def resolve_implementation(implementation: str, devices=None) -> str:
    """Resolve 'auto' to 'pallas'/'scan' for the given compute devices.

    Keyed off `Device.platform` rather than the backend *name* (TPU
    plugins have registered under other names). `devices=None` means the
    default backend's devices — callers that know their actual compute
    devices (Learner/AnakinRunner pass mesh devices) should pass them. A
    backend that fails to initialise raises: a quiet 'scan' would hide
    that the run is not on the device it was asked for.
    """
    if implementation != "auto":
        return implementation
    if devices is None:
        devices = jax.devices()
    first = next(iter(devices), None)
    if first is None:
        # An explicit empty iterable is a caller bug; a bare
        # StopIteration here could be swallowed by iterator-protocol
        # frames in the caller's caller.
        raise ValueError("resolve_implementation: `devices` is empty")
    return "pallas" if first.platform == "tpu" else "scan"


def importance_ratios(
    target_log_probs: jax.Array, behaviour_log_probs: jax.Array
) -> jax.Array:
    """pi/mu ratios of the taken actions from log-probs, shape-preserving."""
    return jnp.exp(target_log_probs - behaviour_log_probs)


def clipped_surrogate(
    log_ratio: jax.Array, advantages: jax.Array, clip_epsilon: float
) -> tuple[jax.Array, jax.Array]:
    """PPO-style clipped surrogate term, the IMPACT objective's core
    (arXiv:1912.00167 eq. 2; consumed by `ops.losses.impact_loss`).

        surrogate_t = min(r_t * A_t, clip(r_t, 1-eps, 1+eps) * A_t)
        r_t = pi_learner(a_t|x_t) / pi_target(a_t|x_t)

    Args:
      log_ratio: `[T, B]` log(pi_learner / pi_target) of taken actions —
        must carry gradient through the learner log-probs.
      advantages: `[T, B]` V-trace pg advantages (stop-gradiented here;
        they are targets, not a gradient path).
      clip_epsilon: the clip radius around r = 1.

    Returns:
      (surrogate, ratio), both `[T, B]`. Maximize the surrogate (the loss
      negates it). `ratio` is returned for clip-fraction telemetry.
    """
    advantages = jax.lax.stop_gradient(advantages)
    ratio = jnp.exp(log_ratio)
    clipped = jnp.clip(ratio, 1.0 - clip_epsilon, 1.0 + clip_epsilon)
    return jnp.minimum(ratio * advantages, clipped * advantages), ratio


def vtrace_scan(
    *,
    log_rhos: jax.Array,
    discounts: jax.Array,
    rewards: jax.Array,
    values: jax.Array,
    bootstrap_value: jax.Array,
    clip_rho_threshold: float = 1.0,
    clip_c_threshold: float = 1.0,
    clip_pg_rho_threshold: float = 1.0,
    lambda_: float = 1.0,
) -> VTraceOutput:
    """V-trace via `lax.scan(reverse=True)` over the time axis.

    Args:
      log_rhos: `[T, B]` log importance ratios log(pi(a|x)) - log(mu(a|x)) of
        the actions actually taken.
      discounts: `[T, B]` per-step discounts, typically `gamma * (1 - done)`.
      rewards: `[T, B]` rewards r_t received after acting at step t.
      values: `[T, B]` baseline V(x_t) under the *target* (learner) params.
      bootstrap_value: `[B]` V(x_T) bootstrap under the target params.
      clip_rho_threshold: rho_bar; None/inf disables clipping.
      clip_c_threshold: c_bar.
      clip_pg_rho_threshold: rho_bar for the policy-gradient advantage.
      lambda_: optional Peng's-Q(lambda)-style mixing on the c weights.

    Returns:
      VTraceOutput with `vs`, `pg_advantages`, `errors`, all `[T, B]`, with
      gradients stopped — V-trace targets are treated as constants by both the
      policy and baseline losses.
    """
    chex.assert_equal_shape([log_rhos, discounts, rewards, values])
    chex.assert_equal_shape([values[0], bootstrap_value])
    clip_rho_threshold = (
        jnp.inf if clip_rho_threshold is None else clip_rho_threshold
    )
    clip_c_threshold = jnp.inf if clip_c_threshold is None else clip_c_threshold
    clip_pg_rho_threshold = (
        jnp.inf if clip_pg_rho_threshold is None else clip_pg_rho_threshold
    )
    rhos = jnp.exp(log_rhos)
    clipped_rhos = jnp.minimum(clip_rho_threshold, rhos)
    cs = lambda_ * jnp.minimum(clip_c_threshold, rhos)
    # V(x_{t+1}) with the bootstrap appended for the final step.
    values_tp1 = jnp.concatenate([values[1:], bootstrap_value[None]], axis=0)
    deltas = clipped_rhos * (rewards + discounts * values_tp1 - values)

    def body(acc, inputs):
        delta_t, discount_t, c_t = inputs
        acc = delta_t + discount_t * c_t * acc
        return acc, acc

    _, errors = jax.lax.scan(
        body,
        jnp.zeros_like(bootstrap_value),
        (deltas, discounts, cs),
        reverse=True,
    )
    vs = values + errors
    vs_tp1 = jnp.concatenate([vs[1:], bootstrap_value[None]], axis=0)
    clipped_pg_rhos = jnp.minimum(clip_pg_rho_threshold, rhos)
    pg_advantages = clipped_pg_rhos * (rewards + discounts * vs_tp1 - values)
    return VTraceOutput(
        vs=jax.lax.stop_gradient(vs),
        pg_advantages=jax.lax.stop_gradient(pg_advantages),
        errors=jax.lax.stop_gradient(errors),
    )


def vtrace(
    *,
    log_rhos: jax.Array,
    discounts: jax.Array,
    rewards: jax.Array,
    values: jax.Array,
    bootstrap_value: jax.Array,
    clip_rho_threshold: float = 1.0,
    clip_c_threshold: float = 1.0,
    clip_pg_rho_threshold: float = 1.0,
    lambda_: float = 1.0,
    implementation: str = "auto",
    devices=None,
) -> VTraceOutput:
    """V-trace with a selectable backend: 'auto', 'scan' (XLA), or 'pallas'
    (TPU kernel).

    Both backends compute identical math; 'pallas' fuses the whole recursion
    (ratio clipping, delta computation, reverse scan, pg advantage) into one
    VMEM-resident kernel. See `vtrace_pallas.py`.

    'auto' resolves against `devices` — pass the devices this computation
    will actually run on (e.g. `mesh.devices.flat`); runtime.Learner and
    AnakinRunner do, so a CPU mesh built in a TPU-default process still
    gets the scan. `devices=None` falls back to the default backend's
    devices (correct for un-meshed callers only).

    Performance: expected to be a non-lever at trained shapes — an
    earlier rig's grid over (T, B) found both implementations at the
    dispatch-latency floor, a fraction of a percent of a train step. On
    the current chip: not measured (PERF.md).
    """
    kwargs = dict(
        log_rhos=log_rhos,
        discounts=discounts,
        rewards=rewards,
        values=values,
        bootstrap_value=bootstrap_value,
        clip_rho_threshold=clip_rho_threshold,
        clip_c_threshold=clip_c_threshold,
        clip_pg_rho_threshold=clip_pg_rho_threshold,
        lambda_=lambda_,
    )
    implementation = resolve_implementation(implementation, devices)
    if implementation == "scan":
        return vtrace_scan(**kwargs)
    if implementation == "pallas":
        from torched_impala_tpu.ops import vtrace_pallas

        return vtrace_pallas.vtrace_pallas(**kwargs)
    raise ValueError(f"unknown vtrace implementation: {implementation!r}")
