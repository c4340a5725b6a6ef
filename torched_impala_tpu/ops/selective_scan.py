"""Selective scan (Mamba-1, arXiv:2312.00752) with episode resets, forward
and backward as chunked Pallas TPU kernels.

    a_t = keep_t * exp(dt_t A)          keep_t = 0 where first[t], else 1
    s_t = a_t * s_{t-1} + (dt_t x_t) B_t
    y_t = sum_n s_t[:, n] C_t[n]

over `[B, T, Di]` inputs with a state `[Di, N]` per row (the hybrid core,
models/hybrid.py: Di 5120, N 16). The skip `D x_t` and the gate
`y * silu(z)` are elementwise and stay with XLA.

What the kernels are built around:

- The state is never written out per step (T x Di x N x 4 B = 1.3 GB a
  layer at T=4096 rows): it lives in VMEM scratch across the time chunks
  of a row, and the forward keeps only its value at each chunk's start
  (`[B, T/chunk, N, Di]`) for the backward, which recomputes a chunk's
  states into scratch before it walks the chunk backwards.
- Layout `[N, Di]`: the state dimension on sublanes, channels on lanes,
  so a step's work on a block of channels is a handful of full vregs and
  `y_t` is a sublane reduction. `B_t` and `C_t` come in already broadcast
  along 128 lanes (`[B, T, N, 128]`, made by XLA), so no step moves data
  across lanes; their gradients leave the same way, as per-lane partial
  sums that XLA adds up.
- The reset is inside the decay: `exp(dt_t A + r_t)` with `r_t` 0 or
  -1e30, so a chunk needs no branch and a reset may fall anywhere in it,
  chunk boundaries included.
- Grid `(B, T/chunk, Di/block_d)`, channel blocks innermost: the
  per-chunk blocks of `B`, `C` and their gradients stay resident while
  the channel blocks go by and accumulate into them.

Off a TPU (CPU actors, tests, a mesh) the op is the plain `lax.scan`
under autodiff, `selective_scan_xla`; tests reach the kernel bodies with
`interpret=True`. Formulations that lost on the chip are in PERF.md
section 6 (PR 34).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torched_impala_tpu.ops.pallas_util import pallas_call

F32 = jnp.float32
LANES = 128
GROUP = 8  # steps per loop iteration: one aligned tile of rows
RESET = -1e30  # added inside exp() at a reset: the decay becomes exactly 0
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
    vmem_limit_bytes=48 << 20,
)


def selective_scan_xla(x, dt, a, b, c, first, s0):
    """The scan one step at a time (`lax.scan` over T), differentiated by
    autodiff. Shapes as `selective_scan`."""
    keep = 1.0 - first.astype(F32)  # [B, T]

    def step(s, xs):
        x_t, dt_t, b_t, c_t, k_t = xs
        decay = jnp.exp(dt_t[:, :, None] * a) * k_t[:, None, None]
        s = decay * s + (dt_t * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("bdn,bn->bd", s, c_t)

    tm = lambda v: jnp.swapaxes(v, 0, 1)  # noqa: E731
    s_last, y = jax.lax.scan(
        step, s0, (tm(x), tm(dt), tm(b), tm(c), tm(keep))
    )
    return tm(y), s_last


# ---- kernels ---------------------------------------------------------------


def _lanes(v, block_d: int):
    """`[R, 128]` repeated along lanes to `[R, block_d]`."""
    return jnp.concatenate([v] * (block_d // LANES), axis=1)


def _fold(v, block_d: int):
    """`[R, block_d]` -> `[R, 128]`: the sum of its 128-lane pieces."""
    out = v[:, :LANES]
    for j in range(1, block_d // LANES):
        out = out + v[:, j * LANES : (j + 1) * LANES]
    return out


def _fwd_kernel(
    x_ref, dt_ref,  # [1, Tc, Db]
    bb_ref, cb_ref,  # [1, Tc, N, 128]
    r_ref,  # [1, Tc, 1, 128]
    a_ref,  # [N, Db]
    s0_ref,  # [1, N, Db]
    y_ref,  # [1, Tc, Db]
    starts_ref,  # [1, 1, N, Db] the state as this chunk begins
    last_ref,  # [1, N, Db]
    state,  # scratch [nD, N, Db]
    *,
    chunk: int,
    block_d: int,
):
    t, d = pl.program_id(1), pl.program_id(2)

    @pl.when(t == 0)
    def _load():
        state[d] = s0_ref[0]

    a = a_ref[...]
    s = state[d]
    starts_ref[0, 0] = s

    def group(g, s):
        rows = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
        dt8, x8 = dt_ref[0, rows, :], x_ref[0, rows, :]  # [8, Db]
        ys = []
        for k in range(GROUP):
            i = g * GROUP + k
            dt_t, x_t = dt8[k : k + 1], x8[k : k + 1]  # [1, Db]
            decay = jnp.exp(dt_t * a + _lanes(r_ref[0, i], block_d))
            s = decay * s + (dt_t * x_t) * _lanes(bb_ref[0, i], block_d)
            ys.append(
                jnp.sum(
                    s * _lanes(cb_ref[0, i], block_d), axis=0, keepdims=True
                )
            )
        y_ref[0, rows, :] = jnp.concatenate(ys, axis=0)
        return s

    s = jax.lax.fori_loop(0, chunk // GROUP, group, s)
    state[d] = s
    last_ref[0] = s


def _bwd_kernel(
    x_ref, dt_ref, dy_ref,  # [1, Tc, Db]
    bb_ref, cb_ref,  # [1, Tc, N, 128]
    r_ref,  # [1, Tc, 1, 128]
    a_ref,  # [N, Db]
    starts_ref,  # [1, 1, N, Db]
    dlast_ref,  # [1, N, Db] cotangent of the last state
    dx_ref, ddt_ref,  # [1, Tc, Db]
    dbb_ref, dcb_ref,  # [1, Tc, N, 128] per-lane partial sums
    da_ref,  # [1, N, Db]
    ds0_ref,  # [1, N, Db]
    hist,  # scratch [Tc, N, Db]: this chunk's states, recomputed
    adj,  # scratch [nD, N, Db]: decay_{t+1} * dL/ds_{t+1}
    da_acc,  # scratch [nD, N, Db]
    *,
    chunk: int,
    block_d: int,
):
    t, d = pl.program_id(1), pl.program_id(2)  # t counts chunks from the end

    @pl.when(t == 0)
    def _load():
        adj[d] = dlast_ref[0]
        da_acc[d] = jnp.zeros_like(da_acc[d])

    @pl.when(d == 0)
    def _clear():
        dbb_ref[...] = jnp.zeros_like(dbb_ref)
        dcb_ref[...] = jnp.zeros_like(dcb_ref)

    a = a_ref[...]
    start = starts_ref[0, 0]

    def decay_of(dt_t, i):
        return jnp.exp(dt_t * a + _lanes(r_ref[0, i], block_d))

    def forward(g, s):
        rows = pl.ds(pl.multiple_of(g * GROUP, GROUP), GROUP)
        dt8, x8 = dt_ref[0, rows, :], x_ref[0, rows, :]
        for k in range(GROUP):
            i = g * GROUP + k
            dt_t, x_t = dt8[k : k + 1], x8[k : k + 1]
            s = decay_of(dt_t, i) * s + (dt_t * x_t) * _lanes(
                bb_ref[0, i], block_d
            )
            hist[i] = s
        return s

    jax.lax.fori_loop(0, chunk // GROUP, forward, start)

    def backward(j, carry):
        ga, da = carry
        g0 = (chunk // GROUP - 1 - j) * GROUP
        rows = pl.ds(pl.multiple_of(g0, GROUP), GROUP)
        dt8, x8, dy8 = dt_ref[0, rows, :], x_ref[0, rows, :], dy_ref[0, rows, :]
        dxs, ddts = [None] * GROUP, [None] * GROUP
        for k in reversed(range(GROUP)):
            i = g0 + k
            dt_t, x_t, dy_t = dt8[k : k + 1], x8[k : k + 1], dy8[k : k + 1]
            s_t = hist[i]
            s_prev = jnp.where(i > 0, hist[jnp.maximum(i - 1, 0)], start)
            g = dy_t * _lanes(cb_ref[0, i], block_d) + ga  # dL/ds_t
            dcb_ref[0, i] += _fold(dy_t * s_t, block_d)
            dbb_ref[0, i] += _fold(g * (dt_t * x_t), block_d)
            du = jnp.sum(
                g * _lanes(bb_ref[0, i], block_d), axis=0, keepdims=True
            )
            decay = decay_of(dt_t, i)
            ddecay = g * s_prev * decay  # dL/d(dt_t A), elementwise
            ddts[k] = jnp.sum(ddecay * a, axis=0, keepdims=True) + du * x_t
            dxs[k] = du * dt_t
            ga, da = g * decay, da + ddecay * dt_t
        ddt_ref[0, rows, :] = jnp.concatenate(ddts, axis=0)
        dx_ref[0, rows, :] = jnp.concatenate(dxs, axis=0)
        return ga, da

    ga, da = jax.lax.fori_loop(
        0, chunk // GROUP, backward, (adj[d], da_acc[d])
    )
    adj[d] = ga
    da_acc[d] = da
    # Written at every visit; the last chunk's (the unroll's first) stands.
    ds0_ref[0] = ga
    da_ref[0] = da


# ---- wrappers --------------------------------------------------------------


def _sizes(t: int, di: int, chunk: int, block_d: int):
    chunk = -(-min(chunk, t) // GROUP) * GROUP
    block_d = min(block_d, di)
    if di % block_d or block_d % LANES:
        raise ValueError(
            f"selective_scan: {di} channels do not divide into blocks of "
            f"{block_d} (a multiple of {LANES})"
        )
    return chunk, -(-t // chunk) * chunk, block_d


def _prepare(x, dt, b, c, first, chunk, block_d):
    """Pad T to whole chunks (dt 0, x 0, C 0: the state stands still and
    y reads 0) and lay B, C and the reset out for the kernels."""
    t, di = x.shape[1:]
    chunk, tp, block_d = _sizes(t, di, chunk, block_d)
    pad = lambda v: jnp.pad(  # noqa: E731
        v, ((0, 0), (0, tp - t)) + ((0, 0),) * (v.ndim - 2)
    )
    wide = lambda v: jnp.broadcast_to(  # noqa: E731
        pad(v)[..., None], (*v.shape[:1], tp, v.shape[2], LANES)
    )
    r = jnp.where(pad(first), RESET, 0.0).astype(F32)
    r = jnp.broadcast_to(r[:, :, None, None], (*r.shape, 1, LANES))
    return pad(x), pad(dt), wide(b), wide(c), r, chunk, tp, block_d


def _specs(chunk: int, n: int, block_d: int, rev_t=None):
    """BlockSpecs by kind, for the grid (row, chunk, channel block);
    `rev_t` (the number of chunks) makes the chunk axis run backwards."""
    tt = (lambda t: t) if rev_t is None else (lambda t: rev_t - 1 - t)
    return {
        "td": pl.BlockSpec((1, chunk, block_d), lambda b, t, d: (b, tt(t), d)),
        "tn": pl.BlockSpec(
            (1, chunk, n, LANES), lambda b, t, d: (b, tt(t), 0, 0)
        ),
        "t1": pl.BlockSpec(
            (1, chunk, 1, LANES), lambda b, t, d: (b, tt(t), 0, 0)
        ),
        "nd": pl.BlockSpec((n, block_d), lambda b, t, d: (0, d)),
        "bnd": pl.BlockSpec((1, n, block_d), lambda b, t, d: (b, 0, d)),
        "starts": pl.BlockSpec(
            (1, 1, n, block_d), lambda b, t, d: (b, tt(t), 0, d)
        ),
    }


def _forward(x, dt, a_t, b, c, first, s0_t, chunk, block_d, interpret):
    """`a_t` `[N, Di]`, `s0_t` `[B, N, Di]`: (y, last state `[B, N, Di]`,
    chunk-start states `[B, T/chunk, N, Di]`)."""
    rows, t, di = x.shape
    n = a_t.shape[0]
    xp, dtp, bb, cb, r, chunk, tp, block_d = _prepare(
        x, dt, b, c, first, chunk, block_d
    )
    nt, nd = tp // chunk, di // block_d
    sp = _specs(chunk, n, block_d)
    y, starts, last = pallas_call(
        functools.partial(
            _fwd_kernel, chunk=chunk, block_d=block_d
        ),
        name="selective_scan_forward",
        interpret=interpret,
        grid=(rows, nt, nd),
        in_specs=[
            sp["td"], sp["td"], sp["tn"], sp["tn"], sp["t1"], sp["nd"],
            sp["bnd"],
        ],
        out_specs=(sp["td"], sp["starts"], sp["bnd"]),
        out_shape=(
            jax.ShapeDtypeStruct((rows, tp, di), F32),
            jax.ShapeDtypeStruct((rows, nt, n, di), F32),
            jax.ShapeDtypeStruct((rows, n, di), F32),
        ),
        scratch_shapes=[pltpu.VMEM((nd, n, block_d), F32)],
        compiler_params=_PARAMS,
    )(xp, dtp, bb, cb, r, a_t, s0_t)
    return y[:, :t], last, starts


def _backward(x, dt, a_t, b, c, first, starts, dy, dlast, chunk, block_d,
              interpret):
    rows, t, di = x.shape
    n = a_t.shape[0]
    xp, dtp, bb, cb, r, chunk, tp, block_d = _prepare(
        x, dt, b, c, first, chunk, block_d
    )
    dyp = jnp.pad(dy, ((0, 0), (0, tp - t), (0, 0)))
    nt, nd = tp // chunk, di // block_d
    sp = _specs(chunk, n, block_d, rev_t=nt)
    state = pltpu.VMEM((nd, n, block_d), F32)
    dx, ddt, dbb, dcb, da, ds0 = pallas_call(
        functools.partial(
            _bwd_kernel, chunk=chunk, block_d=block_d
        ),
        name="selective_scan_backward",
        interpret=interpret,
        grid=(rows, nt, nd),
        in_specs=[
            sp["td"], sp["td"], sp["td"], sp["tn"], sp["tn"], sp["t1"],
            sp["nd"], sp["starts"], sp["bnd"],
        ],
        out_specs=(
            sp["td"], sp["td"], sp["tn"], sp["tn"], sp["bnd"], sp["bnd"]
        ),
        out_shape=(
            jax.ShapeDtypeStruct((rows, tp, di), F32),
            jax.ShapeDtypeStruct((rows, tp, di), F32),
            jax.ShapeDtypeStruct((rows, tp, n, LANES), F32),
            jax.ShapeDtypeStruct((rows, tp, n, LANES), F32),
            jax.ShapeDtypeStruct((rows, n, di), F32),
            jax.ShapeDtypeStruct((rows, n, di), F32),
        ),
        scratch_shapes=[pltpu.VMEM((chunk, n, block_d), F32), state, state],
        compiler_params=_PARAMS,
    )(xp, dtp, dyp, bb, cb, r, a_t, starts, dlast)
    return (
        dx[:, :t], ddt[:, :t], jnp.sum(da, axis=0),
        jnp.sum(dbb[:, :t], axis=-1), jnp.sum(dcb[:, :t], axis=-1), ds0,
    )


@functools.lru_cache(maxsize=None)
def scan_kernels(chunk: int = 64, block_d: int = 512, interpret=None):
    """The scan with the kernels as its forward and backward, on the
    kernels' own layout of `a` (`[N, Di]`) and the states (`[B, N, Di]`).
    `interpret` as in `pallas_util.pallas_call`."""

    @jax.custom_vjp
    def scan(x, dt, a_t, b, c, first, s0_t):
        y, last, _ = _forward(
            x, dt, a_t, b, c, first, s0_t, chunk, block_d, interpret
        )
        return y, last

    def forward(x, dt, a_t, b, c, first, s0_t):
        y, last, starts = _forward(
            x, dt, a_t, b, c, first, s0_t, chunk, block_d, interpret
        )
        return (y, last), (x, dt, a_t, b, c, first, starts)

    def backward(res, g):
        x, dt, a_t, b, c, first, starts = res
        dy, dlast = g
        dx, ddt, da, db, dc, ds0 = _backward(
            x, dt, a_t, b, c, first, starts, dy, dlast, chunk, block_d,
            interpret,
        )
        return dx, ddt, da, db, dc, None, ds0

    scan.defvjp(forward, backward)
    return scan


def selective_scan(
    x, dt, a, b, c, first, s0, *, chunk: int = 64, block_d: int = 512,
    kernel: bool = True, interpret=None,
):
    """Reset-aware selective scan.

    Args:
      x, dt: `[B, T, Di]` float32 (after the convolution and its silu;
        after the softplus).
      a: `[Di, N]` float32, negative.
      b, c: `[B, T, N]` float32.
      first: `[B, T]` bool: the state before step t is zero where set.
      s0: `[B, Di, N]` float32, the state before the first step.
      kernel: False is `selective_scan_xla` everywhere (a mesh). True
        takes the Pallas kernels in a program lowered for a TPU and the
        plain scan elsewhere; `interpret=True` forces the interpreted
        kernels (the tests).

    Returns (y `[B, T, Di]` without the skip, last state `[B, Di, N]`).
    """
    if not kernel:
        return selective_scan_xla(x, dt, a, b, c, first, s0)

    def kernels(x, dt, a, b, c, first, s0):
        run = scan_kernels(
            chunk, block_d, False if interpret is None else interpret
        )
        y, last = run(
            x, dt, a.T, b, c, first, jnp.swapaxes(s0, 1, 2)
        )
        return y, jnp.swapaxes(last, 1, 2)

    if interpret is not None:
        return kernels(x, dt, a, b, c, first, s0)
    return jax.lax.platform_dependent(
        x, dt, a, b, c, first, s0, tpu=kernels, default=selective_scan_xla
    )
