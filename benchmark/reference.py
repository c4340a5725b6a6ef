"""Plain reference of one IMPALA learner step, kept with the benchmark:
the part that every configuration shares.

Straightforward `jax.numpy` in float32 under
`jax.default_matmul_precision("highest")`: V-trace targets, the IMPALA
loss, PopArt (arXiv:1809.04474) where the configuration has more than one
task, the gradient by `jax.grad`, global-norm clipping and RMSProp under a
linear learning-rate anneal. The network itself (its weights from the seed
and its forward pass over an unroll) is the configuration's network file
under `networks/`, handed in as `net`; its tree carries the value head
under `value` (`w` `[F, K]`, `b` `[K]`), which PopArt rescales.

It imports nothing of the program and takes nothing the program made: the
weights come from the network file's `init_params(seed, config)` and are
handed TO the program (`benchmark/program.py`). Every hyper-parameter comes
from the configuration's own file under `configs/`.

The batch is processed in blocks of rows so that a full-size step fits a
chip beside nothing else: the loss sums over rows, so gradients add; PopArt
needs the whole batch's value-target moments first, hence its forward-only
first pass.

Departure from the publications, stated: RMSProp epsilon 1e-7 inside the
square root (the program's optax settings), where the paper uses 0.1.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


# ---- what every network's file uses -------------------------------------


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


def draw_leaves(key, shapes):
    """A tree of weights for a tree of shapes (traced inside the network
    file's one jitted call)."""
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_shape)
    keys = jax.random.split(key, len(leaves))

    def leaf(k, shape):
        # Variance-scaling normal over the fan-in for every matrix and
        # filter; small normal biases rather than zeros, so that every
        # leaf has a gradient AND a value the comparison can see move.
        if len(shape) == 1:
            return 0.01 * jax.random.normal(k, shape, F32)
        fan_in = int(np.prod(shape[:-1]))
        return jax.random.normal(k, shape, F32) / np.sqrt(fan_in)

    return jax.tree.unflatten(
        treedef, [leaf(k, sh) for k, sh in zip(keys, leaves)]
    )


def seed_key(seed: int):
    """`seed` may exceed 32 bits: it is folded into two words."""
    return jax.random.fold_in(
        jax.random.key(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def rounded(x, dtype):
    """`x` stored in `dtype`. An 8-bit float is held in bfloat16 after the
    rounding, so that the arithmetic between two roundings is the same on
    every backend."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize == 1:
        top = float(jnp.finfo(dtype).max)  # saturate: e4m3 has no infinity
        return jnp.clip(x, -top, top).astype(dtype).astype(jnp.bfloat16)
    return x.astype(dtype)


# ---- V-trace, loss, PopArt ---------------------------------------------


def vtrace(log_rhos, discounts, rewards, values, bootstrap, hp):
    """V-trace targets `vs` and policy-gradient advantages, `[T, B]`
    (IMPALA eq. 1 and sec. 4.2), computed backwards over time."""
    rhos = jnp.exp(log_rhos)
    clipped = jnp.minimum(hp["clip_rho"], rhos)
    cs = hp["lambda"] * jnp.minimum(hp["clip_c"], rhos)
    v_tp1 = jnp.concatenate([values[1:], bootstrap[None]], 0)
    deltas = clipped * (rewards + discounts * v_tp1 - values)

    def back(acc, xs):
        delta, disc, c = xs
        acc = delta + disc * c * acc
        return acc, acc

    _, errs = jax.lax.scan(
        back, jnp.zeros_like(bootstrap), (deltas, discounts, cs), reverse=True
    )
    vs = values + errs
    vs_tp1 = jnp.concatenate([vs[1:], bootstrap[None]], 0)
    adv = jnp.minimum(hp["clip_pg_rho"], rhos) * (
        rewards + discounts * vs_tp1 - values
    )
    return vs, adv


def _log_prob(logits, actions):
    lp = jax.nn.log_softmax(logits, -1)
    return jnp.take_along_axis(lp, actions[..., None], -1)[..., 0]


def popart_sigma(pa, hp):
    var = jnp.maximum(pa["nu"] - jnp.square(pa["mu"]), 0.0)
    return jnp.clip(jnp.sqrt(var), hp["sigma_min"], hp["sigma_max"])


class Batch(NamedTuple):
    """Time-major arrays of B unrolls, as the learner's step receives."""

    obs: Any  # [T+1, B, H, W, C] uint8
    first: Any  # [T+1, B] bool
    actions: Any  # [T, B] int32
    behaviour_logits: Any  # [T, B, A]
    rewards: Any  # [T, B]
    cont: Any  # [T, B]
    tasks: Any  # [B] int32
    state: Any  # the network's recurrent state: a tuple of `[B, ...]` arrays

    def rows(self, lo: int, hi: int) -> "Batch":
        return Batch(
            *(x[:, lo:hi] for x in self[:6]),
            self.tasks[lo:hi],
            tuple(s[lo:hi] for s in self.state),
        )


def _targets(net, params, pa_old, batch: Batch, hp, popart, dtypes):
    """Forward pass, then V-trace on constants: (logits[:-1], values that
    carry gradient `[T+1, B]`, vs, advantages). `net` is the network file's
    `(forward, sizes)`. `dtypes` other than float32 make the control:
    weights and activations of the network rounded to them, the loss,
    V-trace and PopArt still in float32."""
    forward, sizes = net
    logits, values = forward(
        sizes, params, batch.obs, batch.first, batch.state, dtypes
    )
    if popart:
        values = jnp.take_along_axis(
            values, batch.tasks[None, :, None], -1
        )[..., 0]
        s_old = popart_sigma(pa_old, hp["popart"])[batch.tasks]
        mu_old = pa_old["mu"][batch.tasks]
        v_un = s_old * values + mu_old  # unnormalised, pre-update stats
    else:
        values = values[..., 0]
        v_un = values
    v_un = jax.lax.stop_gradient(v_un)
    log_rhos = _log_prob(logits[:-1], batch.actions) - _log_prob(
        batch.behaviour_logits, batch.actions
    )
    vs, adv = vtrace(
        jax.lax.stop_gradient(log_rhos),
        hp["discount"] * batch.cont,
        batch.rewards,
        v_un[:-1],
        v_un[-1],
        hp,
    )
    return logits[:-1], values, vs, adv


def _block_loss(params, net, pa_old, pa_new, batch: Batch, hp, popart, dtypes):
    """Summed loss of a block of rows (total, parts)."""
    logits, values, vs, adv = _targets(
        net, params, pa_old, batch, hp, popart, dtypes
    )
    vs, adv = jax.lax.stop_gradient((vs, adv))
    if popart:
        # Baseline on normalised values and targets, both under the
        # POST-update statistics; advantages divided by the new sigma.
        s_old = popart_sigma(pa_old, hp["popart"])[batch.tasks]
        mu_old = pa_old["mu"][batch.tasks]
        s_new = popart_sigma(pa_new, hp["popart"])[batch.tasks]
        mu_new = pa_new["mu"][batch.tasks]
        err = (vs - mu_new) / s_new - (
            s_old * values[:-1] + mu_old - mu_new
        ) / s_new
        adv = adv / s_new
    else:
        err = vs - values[:-1]
    pg = -jnp.sum(adv * _log_prob(logits, batch.actions))
    bl = 0.5 * jnp.sum(jnp.square(err))
    lp = jax.nn.log_softmax(logits, -1)
    ent = jnp.sum(jnp.exp(lp) * lp)  # negative entropy
    total = pg + hp["vf_coef"] * bl + hp["entropy_coef"] * ent
    return total, {"pg": pg, "baseline": bl, "neg_entropy": ent}


@functools.partial(jax.jit, static_argnames=("net", "popart", "dtypes"))
def _block_grad(params, net, pa_old, pa_new, batch, hp, popart, dtypes):
    (total, parts), grads = jax.value_and_grad(_block_loss, has_aux=True)(
        params, net, pa_old, pa_new, batch, hp, popart, dtypes
    )
    return total, parts, grads


@functools.partial(jax.jit, static_argnames=("net", "num_values", "dtypes"))
def _block_moments(params, net, pa_old, batch, hp, num_values, dtypes):
    """Per-task count, sum and sum of squares of a block's V-trace
    targets (PopArt's first pass; additive over blocks)."""
    _, _, vs, _ = _targets(net, params, pa_old, batch, hp, True, dtypes)
    zero = jnp.zeros((num_values,), F32)
    cnt = zero.at[batch.tasks].add(jnp.full(batch.tasks.shape, vs.shape[0], F32))
    tot = zero.at[batch.tasks].add(jnp.sum(vs, 0))
    tot_sq = zero.at[batch.tasks].add(jnp.sum(jnp.square(vs), 0))
    return cnt, tot, tot_sq


def _popart_update(pa, cnt, tot, tot_sq, hp):
    present = cnt > 0
    denom = jnp.maximum(cnt, 1.0)
    step = hp["step_size"]
    mu = jnp.where(present, pa["mu"] + step * (tot / denom - pa["mu"]), pa["mu"])
    nu = jnp.where(
        present, pa["nu"] + step * (tot_sq / denom - pa["nu"]), pa["nu"]
    )
    return {"mu": mu, "nu": nu}


@functools.partial(jax.jit, donate_argnums=(1, 2))
def _apply(params, nu, grads, k, hp):
    """Clip by global norm, RMSProp, linear anneal; step index `k` from 0.
    The second moments and the gradient are updated in their own buffers
    (8 bytes a parameter less on the device); the parameters are not, since
    the first step's are the weights every later reading starts from."""
    gnorm = jnp.sqrt(
        sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    )
    scale = jnp.minimum(1.0, hp["max_grad_norm"] / (gnorm + 1e-8))
    grads = jax.tree.map(lambda g: g * scale, grads)
    d = hp["rmsprop_decay"]
    nu = jax.tree.map(lambda n, g: d * n + (1.0 - d) * g * g, nu, grads)
    frac = jnp.clip(k / hp["anneal_steps"], 0.0, 1.0)
    lr = hp["lr"] * (1.0 - frac)
    params = jax.tree.map(
        lambda p, g, n: p - lr * g * jax.lax.rsqrt(n + hp["rmsprop_eps"]),
        params,
        grads,
        nu,
    )
    return params, nu, grads, gnorm


@jax.jit
def _popart_rescale(value, pa_old, pa_new, hp):
    """Keep the unnormalised value outputs where they were (PopArt's
    'preserve outputs precisely')."""
    s_old, s_new = popart_sigma(pa_old, hp), popart_sigma(pa_new, hp)
    return {
        "w": value["w"] * (s_old / s_new)[None, :],
        "b": (s_old * value["b"] + pa_old["mu"] - pa_new["mu"]) / s_new,
    }


class StepOut(NamedTuple):
    params: Any
    nu: Any
    popart: Optional[dict]
    loss: float
    loss_scale: float  # |pg| + vf_coef |baseline| + entropy_coef |entropy|
    grads: Any  # as the optimizer gets them: after clipping
    grad_norm_unclipped: float


def learner_step(
    net,
    params,
    nu,
    popart: Optional[dict],
    batch: Batch,
    k: int,
    hp: dict,
    block_rows: int,
    dtypes: tuple,
) -> StepOut:
    """One reference step on host arrays `batch`, in blocks of rows, of the
    network `net`: its file's `(forward, sizes(config))`, both hashable."""
    n = batch.tasks.shape[0]
    if n % block_rows:
        raise ValueError(f"{n} rows do not divide into blocks of {block_rows}")
    blocks = [
        jax.device_put(batch.rows(lo, lo + block_rows))
        for lo in range(0, n, block_rows)
    ]
    use_pa = popart is not None
    with jax.default_matmul_precision("highest"):
        pa_new = popart
        if use_pa:
            mom = None
            for blk in blocks:
                m = _block_moments(
                    params, net, popart, blk, hp,
                    num_values=popart["mu"].shape[0], dtypes=dtypes,
                )
                mom = m if mom is None else jax.tree.map(jnp.add, mom, m)
            pa_new = _popart_update(popart, *mom, hp["popart"])
        loss, parts, grads = 0.0, None, None
        for blk in blocks:
            total, prt, g = _block_grad(
                params, net, popart, pa_new, blk, hp,
                popart=use_pa, dtypes=dtypes,
            )
            loss = loss + total
            parts = prt if parts is None else jax.tree.map(jnp.add, parts, prt)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        # The loss is a sum of three terms of either sign; a gap is judged
        # against their magnitudes, not against a total that may cancel.
        scale = (
            abs(float(parts["pg"]))
            + float(hp["vf_coef"]) * abs(float(parts["baseline"]))
            + float(hp["entropy_coef"]) * abs(float(parts["neg_entropy"]))
        )
        params, nu, grads, gnorm = _apply(params, nu, grads, k, hp)
        if use_pa:
            params = dict(
                params,
                value=_popart_rescale(
                    params["value"], popart, pa_new, hp["popart"]
                ),
            )
    return StepOut(
        params, nu, pa_new, float(loss), scale, grads, float(gnorm)
    )


def hyper_params(config: dict) -> dict:
    """The numbers of the configuration's file, as the step reads them."""
    loss, opt = config["loss"], config["optimizer"]
    if loss["reduction"] != "sum":
        raise ValueError("the reference sums the loss over [T, B]")
    frames_per_step = config["batch_size"] * config["unroll_length"]
    hp = {
        "discount": loss["discount"],
        "vf_coef": loss["vf_coef"],
        "entropy_coef": loss["entropy_coef"],
        "clip_rho": loss["clip_rho_threshold"],
        "clip_c": loss["clip_c_threshold"],
        "clip_pg_rho": loss["clip_pg_rho_threshold"],
        "lambda": loss["lambda"],
        "max_grad_norm": opt["max_grad_norm"],
        "lr": opt["lr"],
        "rmsprop_decay": opt["rmsprop_decay"],
        "rmsprop_eps": opt["rmsprop_eps"],
        "anneal_steps": float(
            max(1, opt["total_env_frames"] // frames_per_step)
        ),
    }
    if config["model"]["num_tasks"] > 1:
        hp["popart"] = dict(config["popart"])
    return jax.tree.map(lambda x: np.float32(x), hp)


def init_popart(seed: int, config: dict) -> Optional[dict]:
    """PopArt's statistics at the start of a run, from the seed, or None
    with one task. Not the identity (mu 0, sigma 1), under which a learner
    that skipped the normalisation would read the same: each task's mean is
    drawn N(0, 1) and its sigma log-normally about 1, so the normalised
    baseline loss and the rescaled value head are live from the first step."""
    k = int(config["model"]["num_tasks"])
    if k <= 1:
        return None
    rng = np.random.default_rng([abs(int(seed)), 2])
    mu = rng.standard_normal(k).astype(np.float32)
    sigma = np.exp(0.5 * rng.standard_normal(k)).astype(np.float32)
    return {"mu": mu, "nu": np.square(sigma) + np.square(mu)}


def init_state(params, popart: Optional[dict]):
    """(RMSProp second moments, PopArt statistics or None) at step 0."""
    nu = jax.tree.map(jnp.zeros_like, params)
    if popart is not None:
        popart = {k: jnp.asarray(v, F32) for k, v in popart.items()}
    return nu, popart
