"""Pallas TPU kernel for the V-trace recursion (BASELINE.json:5's "Pallas
fallback for the time-major inner loop").

One fused VMEM-resident kernel computes, per 128-lane batch tile:
ratio clipping → deltas → the reverse-time linear recurrence → vs targets →
policy-gradient advantages. The grid runs over the batch axis (the recursion
is sequential in T but embarrassingly parallel in B); each program keeps its
whole `[T, 128]` tile in VMEM, so the T-loop never touches HBM.

Semantically identical to `vtrace.vtrace_scan` (asserted in
tests/test_pallas_vtrace.py); both sit behind `vtrace.vtrace(...,
implementation=...)`.

Outputs are V-trace *targets* — constants w.r.t. all inputs (stop_gradient
semantics), so the kernel needs no custom VJP; the wrapper blocks gradient
flow explicitly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torched_impala_tpu.ops import precision
from torched_impala_tpu.ops.pallas_util import pallas_call
from torched_impala_tpu.ops.vtrace import VTraceOutput

_LANES = 128


def _vtrace_kernel(
    log_rhos_ref,
    discounts_ref,
    rewards_ref,
    values_ref,
    bootstrap_ref,
    vs_ref,
    pg_ref,
    err_ref,
    a_scratch,
    *,
    clip_rho: float,
    clip_c: float,
    clip_pg_rho: float,
    lambda_: float,
    T: int,
):
    rhos = jnp.exp(log_rhos_ref[:])  # [T, 128]
    discounts = discounts_ref[:]
    values = values_ref[:]
    bootstrap = bootstrap_ref[0, :]  # [128]

    clipped_rhos = jnp.minimum(clip_rho, rhos)
    cs = lambda_ * jnp.minimum(clip_c, rhos)
    values_tp1 = jnp.concatenate([values[1:], bootstrap[None]], axis=0)
    deltas = clipped_rhos * (rewards_ref[:] + discounts * values_tp1 - values)

    # Stage the recursion operands in refs so the T-loop uses dynamic-slice
    # reads/writes on memory instead of gathers on traced arrays.
    err_ref[:] = deltas
    a_scratch[:] = discounts * cs

    def body(i, acc):
        t = T - 1 - i
        acc = err_ref[pl.ds(t, 1), :] + a_scratch[pl.ds(t, 1), :] * acc
        err_ref[pl.ds(t, 1), :] = acc
        return acc

    jax.lax.fori_loop(0, T, body, jnp.zeros((1, _LANES), values.dtype))

    vs = values + err_ref[:]
    vs_ref[:] = vs
    vs_tp1 = jnp.concatenate([vs[1:], bootstrap[None]], axis=0)
    clipped_pg_rhos = jnp.minimum(clip_pg_rho, rhos)
    pg_ref[:] = clipped_pg_rhos * (rewards_ref[:] + discounts * vs_tp1 - values)


@functools.partial(
    jax.jit,
    static_argnames=(
        "clip_rho_threshold",
        "clip_c_threshold",
        "clip_pg_rho_threshold",
        "lambda_",
        "interpret",
    ),
)
def vtrace_pallas(
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
    interpret: bool | None = None,
) -> VTraceOutput:
    """V-trace via the fused Pallas TPU kernel. Same contract as `vtrace_scan`.

    `interpret=None` compiles the kernel where the call is lowered for a
    TPU and interprets it anywhere else (ops/pallas_util.py), so tests, CPU
    meshes and a CPU-placed call in a TPU-default process run the same code
    path.
    """
    T, B = rewards.shape
    f32 = jnp.float32

    def prep(x):
        # V-trace outputs are targets (constants); stopping gradients on the
        # *inputs* keeps jax.grad from tracing a (nonexistent) JVP rule
        # through pallas_call.
        return jax.lax.stop_gradient(jnp.asarray(x, f32))

    log_rhos, discounts, rewards, values = map(
        prep, (log_rhos, discounts, rewards, values)
    )
    bootstrap = prep(bootstrap_value)[None, :]  # [1, B]

    # Pad the batch axis to full 128-wide lanes; lanes beyond B compute
    # garbage independently and are sliced off (no cross-lane ops).
    Bp = max(_LANES, ((B + _LANES - 1) // _LANES) * _LANES)
    pad = Bp - B
    if pad:
        padding = ((0, 0), (0, pad))
        log_rhos, discounts, rewards, values, bootstrap = (
            jnp.pad(x, padding)
            for x in (log_rhos, discounts, rewards, values, bootstrap)
        )

    kernel = functools.partial(
        _vtrace_kernel,
        clip_rho=float("inf")
        if clip_rho_threshold is None
        else clip_rho_threshold,
        clip_c=float("inf") if clip_c_threshold is None else clip_c_threshold,
        clip_pg_rho=float("inf")
        if clip_pg_rho_threshold is None
        else clip_pg_rho_threshold,
        lambda_=lambda_,
        T=T,
    )
    tb_spec = pl.BlockSpec((T, _LANES), lambda i: (0, i), memory_space=pltpu.VMEM)
    boot_spec = pl.BlockSpec(
        (1, _LANES), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    out_struct = jax.ShapeDtypeStruct((T, Bp), f32)
    vs, pg, err = pallas_call(
        kernel,
        grid=(Bp // _LANES,),
        in_specs=[tb_spec, tb_spec, tb_spec, tb_spec, boot_spec],
        out_specs=(tb_spec, tb_spec, tb_spec),
        out_shape=(out_struct, out_struct, out_struct),
        scratch_shapes=[pltpu.VMEM((T, _LANES), f32)],
        interpret=interpret,
    )(log_rhos, discounts, rewards, values, bootstrap)

    vs, pg, err = (x[:, :B] for x in (vs, pg, err))
    return VTraceOutput(
        vs=jax.lax.stop_gradient(vs),
        pg_advantages=jax.lax.stop_gradient(pg),
        errors=jax.lax.stop_gradient(err),
    )


# ---- fused V-trace + loss epilogue (ISSUE 13 tentpole) -----------------
#
# The separate epilogue materializes log_softmax over [T, B, A] three
# times (log_rhos, policy-gradient, entropy) and lets autodiff rebuild
# two softmax backward chains over the cube. The fused path computes ONE
# log_softmax, feeds scalars [T, B] into the recursion, reduces the
# three loss terms next to it (inside the Pallas kernel on TPU), and
# backpropagates through a single analytic VJP over the whole epilogue:
# with p = softmax and plp = p * log_p saved from the forward, the
# logits gradient is
#
#   dL/dz = p * c1[..., None] - coef_ent[..., None] * plp
#           + scatter_add(coef_pg at actions)
#
# (c1 = -coef_pg - coef_ent * H) — three elementwise passes plus one
# scatter, versus the two full softmax-VJP chains autodiff builds for
# the separate path. NB: sharing one log_softmax between take_along_axis
# and the entropy reduction under autodiff is a measured pessimization
# (the joint backward is ~2x slower than two CSE'd log_softmax calls on
# CPU XLA); the analytic VJP sidesteps that entirely.

# Compute dtypes the fused epilogue accepts for its softmax/elementwise
# phase, drawn from the declarative mixed-precision policy table
# (ops/precision.py, ISSUE 16 — the single source of truth the dtype
# lint validates): ONLY the [T, B, A] elementwise phase may run in
# bf16 — the V-trace recursion, loss reductions, and PopArt stats
# stay f32 (the accumulator contract the lint rule polices).
_FUSED_COMPUTE_DTYPES = precision.compute_dtypes(
    "fused_epilogue_elementwise"
)


def _fused_loss_kernel(
    log_rhos_ref,
    discounts_ref,
    rewards_ref,
    values_ref,
    bootstrap_ref,
    log_pi_a_ref,
    entropy_ref,
    mask_ref,
    vs_ref,
    adv_ref,
    pg_sum_ref,
    bl_sum_ref,
    ent_sum_ref,
    err_ref,
    a_scratch,
    *,
    clip_rho: float,
    clip_c: float,
    clip_pg_rho: float,
    lambda_: float,
    T: int,
):
    """`_vtrace_kernel` + the loss epilogue in one VMEM-resident pass:
    after the recursion, the per-tile policy-gradient / baseline /
    entropy partial sums are reduced in place (padded lanes carry
    mask 0, so they contribute nothing). The three `(grid, 1)` sum
    arrays sit whole in SMEM; each grid step writes its own row."""
    rhos = jnp.exp(log_rhos_ref[:])  # [T, 128]
    discounts = discounts_ref[:]
    values = values_ref[:]
    bootstrap = bootstrap_ref[0, :]  # [128]

    clipped_rhos = jnp.minimum(clip_rho, rhos)
    cs = lambda_ * jnp.minimum(clip_c, rhos)
    values_tp1 = jnp.concatenate([values[1:], bootstrap[None]], axis=0)
    deltas = clipped_rhos * (rewards_ref[:] + discounts * values_tp1 - values)

    err_ref[:] = deltas
    a_scratch[:] = discounts * cs

    def body(i, acc):
        t = T - 1 - i
        acc = err_ref[pl.ds(t, 1), :] + a_scratch[pl.ds(t, 1), :] * acc
        err_ref[pl.ds(t, 1), :] = acc
        return acc

    jax.lax.fori_loop(0, T, body, jnp.zeros((1, _LANES), values.dtype))

    vs = values + err_ref[:]
    vs_ref[:] = vs
    vs_tp1 = jnp.concatenate([vs[1:], bootstrap[None]], axis=0)
    clipped_pg_rhos = jnp.minimum(clip_pg_rho, rhos)
    adv = clipped_pg_rhos * (rewards_ref[:] + discounts * vs_tp1 - values)
    adv_ref[:] = adv

    m = mask_ref[:]
    tile = pl.program_id(0)
    pg_sum_ref[tile, 0] = jnp.sum(-adv * log_pi_a_ref[:] * m)
    bl_sum_ref[tile, 0] = 0.5 * jnp.sum(jnp.square(vs - values) * m)
    ent_sum_ref[tile, 0] = jnp.sum(-entropy_ref[:] * m)


def _fused_sums_kernel_call(
    log_pi_a, ent, values, bootstrap, log_rhos, discounts, rewards, mask,
    *, clip_rho, clip_c, clip_pg_rho, lambda_, interpret,
):
    """Run the fused kernel over 128-lane tiles; returns (pg, bl, ent
    sums, vs, adv) with the padding sliced off."""
    T, B = rewards.shape
    f32 = jnp.float32
    Bp = max(_LANES, ((B + _LANES - 1) // _LANES) * _LANES)
    pad = Bp - B
    boot2d = bootstrap[None, :]
    if pad:
        padding = ((0, 0), (0, pad))
        (log_pi_a, ent, values, log_rhos, discounts, rewards, mask) = (
            jnp.pad(x, padding)
            for x in (
                log_pi_a, ent, values, log_rhos, discounts, rewards, mask
            )
        )
        boot2d = jnp.pad(boot2d, padding)
    grid = Bp // _LANES
    kernel = functools.partial(
        _fused_loss_kernel,
        clip_rho=clip_rho,
        clip_c=clip_c,
        clip_pg_rho=clip_pg_rho,
        lambda_=lambda_,
        T=T,
    )
    tb_spec = pl.BlockSpec(
        (T, _LANES), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    boot_spec = pl.BlockSpec(
        (1, _LANES), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    # Whole (grid, 1) array resident in SMEM across the (sequential)
    # grid: a (1, 1) block per tile is refused by the TPU lowering as
    # soon as the grid exceeds one tile (B > 128).
    sum_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    tb_struct = jax.ShapeDtypeStruct((T, Bp), f32)
    sum_struct = jax.ShapeDtypeStruct((grid, 1), f32)
    vs, adv, pg_p, bl_p, ent_p = pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            tb_spec, tb_spec, tb_spec, tb_spec, boot_spec,
            tb_spec, tb_spec, tb_spec,
        ],
        out_specs=(tb_spec, tb_spec, sum_spec, sum_spec, sum_spec),
        out_shape=(
            tb_struct, tb_struct, sum_struct, sum_struct, sum_struct
        ),
        scratch_shapes=[
            pltpu.VMEM((T, _LANES), f32),
            pltpu.VMEM((T, _LANES), f32),
        ],
        interpret=interpret,
    )(log_rhos, discounts, rewards, values, boot2d, log_pi_a, ent, mask)
    return (
        jnp.sum(pg_p),
        jnp.sum(bl_p),
        jnp.sum(ent_p),
        vs[:, :B],
        adv[:, :B],
    )


def _fused_core_fwd(
    statics, target_logits, actions, values, bootstrap, log_mu_a,
    discounts, rewards, mask,
):
    clip_rho, clip_c, clip_pg_rho, lambda_, implementation = statics
    f32 = jnp.float32
    log_p = jax.nn.log_softmax(target_logits, axis=-1)  # [T, B, A]
    p = jnp.exp(log_p)
    plp = p * log_p
    log_pi_a = jnp.take_along_axis(
        log_p, actions[..., None], axis=-1
    )[..., 0].astype(f32)
    ent = -jnp.sum(plp, axis=-1).astype(f32)
    # The [T, B] scalars feeding the recursion are f32 from here on —
    # only the [T, B, A] cube above ran at compute_dtype.
    log_rhos = log_pi_a - log_mu_a
    clips = dict(
        clip_rho=clip_rho,
        clip_c=clip_c,
        clip_pg_rho=clip_pg_rho,
        lambda_=lambda_,
    )

    def kernel_path(interpret, *operands):
        return _fused_sums_kernel_call(
            *operands, **clips, interpret=interpret
        )

    def xla_path(
        log_pi_a, ent, values, bootstrap, log_rhos, discounts, rewards,
        mask,
    ):
        # XLA fuses the same math around a lax.scan recursion. Same
        # reductions, same analytic VJP below.
        from torched_impala_tpu.ops.vtrace import vtrace_scan

        vt = vtrace_scan(
            log_rhos=log_rhos,
            discounts=discounts,
            rewards=rewards,
            values=values,
            bootstrap_value=bootstrap,
            clip_rho_threshold=clip_rho,
            clip_c_threshold=clip_c,
            clip_pg_rho_threshold=clip_pg_rho,
            lambda_=lambda_,
        )
        vs, adv = vt.vs, vt.pg_advantages
        pg = jnp.sum(-adv * log_pi_a * mask)
        bl = 0.5 * jnp.sum(jnp.square(vs - values) * mask)
        en = jnp.sum(-ent * mask)
        return pg, bl, en, vs, adv

    operands = (
        log_pi_a, ent, values, bootstrap, log_rhos, discounts, rewards,
        mask,
    )
    if implementation == "kernel":
        # Compiled where lowered for a TPU, interpreted elsewhere
        # (ops/pallas_util.py).
        pg, bl, en, vs, adv = kernel_path(None, *operands)
    elif implementation == "xla":
        pg, bl, en, vs, adv = xla_path(*operands)
    else:
        # 'auto': the lowering platform picks — the compiled kernel on
        # a TPU, the scan epilogue anywhere else (the interpreter would
        # crawl there).
        pg, bl, en, vs, adv = jax.lax.platform_dependent(
            *operands,
            tpu=functools.partial(kernel_path, False),
            default=xla_path,
        )
    out = (pg, bl, en, jnp.mean(vs), jnp.mean(adv))
    return out, (p, plp, ent, adv, vs, values, mask, actions)


def _fused_core_bwd(statics, res, g):
    """Analytic VJP of the fused epilogue. The V-trace targets (vs, adv)
    are constants by contract (stop_gradient in the separate path), so
    the live derivatives are:

      dL/dz     = coef_pg * (onehot(a) - p) - coef_ent * (plp + p * H)
      dL/dvalues = (values - vs) * mask * g_bl

    with coef_pg = -adv * mask * g_pg and coef_ent = -mask * g_ent.
    Grouping by the saved residuals p and plp makes the cube backward
    three elementwise passes plus one scatter_add. Cotangents for the
    vs/adv mean logs are deliberately dropped — they are diagnostics of
    stop-gradient targets, exactly as in the separate epilogue."""
    del statics
    p, plp, ent, adv, vs, values, mask, actions = res
    g_pg, g_bl, g_ent, _g_vs_mean, _g_adv_mean = g
    cd = p.dtype
    coef_pg = -adv * mask * g_pg  # [T, B] f32
    coef_ent = -mask * g_ent  # [T, B] f32
    c1 = (-coef_pg - coef_ent * ent).astype(cd)
    g_z = p * c1[..., None] - coef_ent.astype(cd)[..., None] * plp
    t_idx = jnp.arange(p.shape[0])[:, None]
    b_idx = jnp.arange(p.shape[1])[None, :]
    g_z = g_z.at[t_idx, b_idx, actions].add(coef_pg.astype(cd))
    zero_tb = jnp.zeros_like(values)
    return (
        g_z,  # target_logits
        np.zeros(actions.shape, jax.dtypes.float0),  # actions (int)
        (values - vs) * mask * g_bl,  # values
        jnp.zeros(mask.shape[1:], values.dtype),  # bootstrap
        zero_tb,  # log_mu_a
        zero_tb,  # discounts
        zero_tb,  # rewards
        jnp.zeros_like(mask),  # mask
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_core(
    statics, target_logits, actions, values, bootstrap, log_mu_a,
    discounts, rewards, mask,
):
    """(pg_sum, bl_sum, ent_sum, vs_mean, adv_mean) of the V-trace loss
    epilogue; `statics` = (clip_rho, clip_c, clip_pg_rho, lambda_,
    implementation)."""
    out, _ = _fused_core_fwd(
        statics, target_logits, actions, values, bootstrap, log_mu_a,
        discounts, rewards, mask,
    )
    return out


_fused_core.defvjp(_fused_core_fwd, _fused_core_bwd)


def fused_vtrace_loss(
    *,
    target_logits: jax.Array,
    behaviour_logits: jax.Array,
    values: jax.Array,
    bootstrap_value: jax.Array,
    actions: jax.Array,
    rewards: jax.Array,
    discounts: jax.Array,
    mask: jax.Array | None = None,
    config,
    implementation: str = "auto",
):
    """IMPALA loss with the V-trace recursion AND the loss epilogue in
    one fused pass (ImpalaLossConfig.fused_epilogue routes here).

    Same contract and log dict as `ops.losses.impala_loss`. ONE
    log_softmax over `[T, B, A]` serves the importance ratios, the
    policy-gradient term, and the entropy term; the recursion plus the
    three masked reductions run inside the Pallas kernel
    (`implementation='kernel'`, interpreted where not lowered for a
    TPU) or a lax.scan epilogue (`'xla'`) behind one analytic-VJP
    custom_vjp. `'auto'` lets the lowering platform pick: kernel on
    TPU, scan elsewhere. A multi-device mesh must use `'xla'` (Mosaic
    kernels are not auto-partitioned; the Learner resolves that).

    `config.train_dtype='bfloat16'` runs the `[T, B, A]` softmax /
    elementwise phase in bf16 (the allow-listed half entry point —
    see _FUSED_COMPUTE_DTYPES); scalars entering the recursion and
    every reduction are cast back to f32. Greedy actions and losses
    stay within the parity gate pinned in tests/test_losses.py.
    """
    from torched_impala_tpu.ops.losses import assemble_loss

    compute_dtype = getattr(config, "train_dtype", "float32")
    if compute_dtype not in _FUSED_COMPUTE_DTYPES:
        raise ValueError(
            f"train_dtype {compute_dtype!r} not in "
            f"{_FUSED_COMPUTE_DTYPES}"
        )
    if implementation not in ("auto", "kernel", "xla"):
        raise ValueError(f"unknown implementation: {implementation!r}")

    f32 = jnp.float32
    if mask is None:
        mask = jnp.ones_like(rewards, dtype=f32)
    mask = mask.astype(f32)

    cd = jnp.dtype(compute_dtype)
    # The behaviour policy is pure data (stop-grad by contract); its
    # log-prob per taken action is all the recursion needs.
    log_mu = jax.nn.log_softmax(
        jax.lax.stop_gradient(behaviour_logits).astype(cd), axis=-1
    )
    log_mu_a = jnp.take_along_axis(
        log_mu, actions[..., None], axis=-1
    )[..., 0].astype(f32)

    statics = (
        float("inf")
        if config.clip_rho_threshold is None
        else float(config.clip_rho_threshold),
        float("inf")
        if config.clip_c_threshold is None
        else float(config.clip_c_threshold),
        float("inf")
        if config.clip_pg_rho_threshold is None
        else float(config.clip_pg_rho_threshold),
        float(config.lambda_),
        implementation,
    )
    # ONE log_softmax inside the core serves ratios + pg + entropy; the
    # astype here puts the whole [T, B, A] cube phase (forward AND the
    # analytic backward) at compute_dtype, with the cotangent cast back
    # to the caller's dtype by convert_element_type's transpose.
    pg, bl, en, vs_mean, adv_mean = _fused_core(
        statics,
        target_logits.astype(cd),
        actions,
        values.astype(f32),
        jax.lax.stop_gradient(bootstrap_value).astype(f32),
        log_mu_a,
        discounts.astype(f32),
        rewards.astype(f32),
        mask,
    )
    if config.reduction == "mean":
        n_valid = jnp.maximum(jnp.sum(mask), 1.0)
        pg, bl, en = pg / n_valid, bl / n_valid, en / n_valid
    elif config.reduction != "sum":
        raise ValueError(f"unknown reduction: {config.reduction!r}")
    return assemble_loss(
        pg=pg,
        bl=bl,
        ent=en,
        mask=mask,
        config=config,
        extra_logs={
            "mean_vtrace_target": vs_mean,
            "mean_advantage": adv_mean,
        },
    )
