"""Fused Pallas LSTM cell: one pass over the gate matmuls + elementwise
gates (ISSUE 16 — the Pallas footprint beyond the V-trace epilogue).

The flax `OptimizedLSTMCell` inside `ImpalaNet._core_step` lowers to a
chain of XLA ops per scan step: two gate matmuls, a bias add, four
splits, three sigmoids, two tanhs, and the carry arithmetic — each a
separate HBM round-trip at `[B, 4H]`/`[B, H]`. This kernel computes the
whole cell in one `pallas_call` per step: the `[B, F]@[F, 4H]` and
`[B, H]@[H, 4H]` gate matmuls accumulate in f32 on the MXU and every
elementwise op runs on the still-resident VMEM tile.

Numerics follow the flax cell op-for-op: same concat layout (i, f, g,
o along the 4H axis), same add order ((h@Wh + b) + x@Wi — flax adds
the bias to the recurrent half before summing the input half), same
activations. Outputs agree to ~1 ulp in f32 (XLA fuses/reassociates
the reference's adds differently); tests/test_pallas_lstm.py pins the
documented tolerance (<= 1e-6 absolute on unit-scale probes).

`vtrace_pallas`-style analytic VJP: the forward saves the activated
gates, the backward is closed-form elementwise algebra plus four plain
matmuls (jnp — the XLA fallback precedent from `_fused_core_bwd`), so
autodiff never differentiates through the kernel. Lowered for anything
but a TPU the kernel runs in interpret mode (ops/pallas_util.py; no
`fori_loop` inside, so interpretation is a plain jnp evaluation) —
tier-1 exercises the exact kernel body on CPU, and an actor pinned to
the host CPU beside a TPU learner runs the same body.

Fast memory: the grid runs over blocks of `_BLOCK_ROWS` batch rows with
the weights resident, so the VMEM footprint is bounded by the block and
not by B (a single whole-array call was refused by the v5e compiler at
B=1024, F=512: 18.8M scoped vs the 16M limit).

Accumulator contract (ops/precision.py): the carry is the policy's
"lstm_carry" role — f32 only. Inputs are promoted to f32 on entry.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from torched_impala_tpu.ops.pallas_util import pallas_call

# Batch rows per grid step. 256 rows of [rows, 4H] f32 gates at H=256
# are 1 MiB per temporary; with both weight matrices resident the block
# stays well inside the 16 MiB scoped-VMEM limit of a v5e
# (tests/test_tpu_compile.py compiles B=1024 at F=512 and F=256).
_BLOCK_ROWS = 256


def _lstm_cell_kernel(
    x_ref,
    h_ref,
    c_ref,
    wi_ref,
    wh_ref,
    b_ref,
    new_c_ref,
    new_h_ref,
    acts_ref,
    *,
    hidden: int,
):
    """One LSTM cell step, whole-tile resident.

    Gate layout along the 4H axis is (i, f, g, o), matching the flax
    OptimizedLSTMCell's concat order; the pre-activation sum keeps
    flax's exact grouping, (h@Wh + b) + x@Wi.
    """
    h = h_ref[:]
    gates = (
        jnp.dot(h, wh_ref[:], preferred_element_type=jnp.float32)
        + b_ref[:]
    ) + jnp.dot(x_ref[:], wi_ref[:], preferred_element_type=jnp.float32)
    i = jax.nn.sigmoid(gates[:, :hidden])
    f = jax.nn.sigmoid(gates[:, hidden : 2 * hidden])
    g = jnp.tanh(gates[:, 2 * hidden : 3 * hidden])
    o = jax.nn.sigmoid(gates[:, 3 * hidden :])
    new_c = f * c_ref[:] + i * g
    new_h = o * jnp.tanh(new_c)
    new_c_ref[:] = new_c
    new_h_ref[:] = new_h
    # Activated gates, saved for the analytic backward (recomputing
    # them would repeat both gate matmuls).
    acts_ref[:] = jnp.concatenate([i, f, g, o], axis=-1)


def _lstm_forward(x, h, c, wi, wh, b):
    """(new_c, new_h, acts) via the Pallas kernel, gridded over batch
    rows (weights broadcast to every block)."""
    batch, hidden = c.shape
    feat = x.shape[-1]
    f32 = jnp.float32
    x, h, c, wi, wh, b = (
        a.astype(f32) for a in (x, h, c, wi, wh, b)
    )
    # One block when the batch fits (a block equal to the array needs no
    # row alignment); otherwise pad the rows to whole blocks — rows are
    # independent, the padding is sliced off.
    rows = batch if batch <= _BLOCK_ROWS else _BLOCK_ROWS
    padded = -(-batch // rows) * rows
    if padded != batch:
        pad = ((0, padded - batch), (0, 0))
        x, h, c = (jnp.pad(a, pad) for a in (x, h, c))

    def row_block(width):
        return pl.BlockSpec((rows, width), lambda i: (i, 0))

    def whole(shape):
        return pl.BlockSpec(shape, lambda i: (0, 0))

    kernel = functools.partial(_lstm_cell_kernel, hidden=hidden)
    new_c, new_h, acts = pallas_call(
        kernel,
        grid=(padded // rows,),
        in_specs=[
            row_block(feat),
            row_block(hidden),
            row_block(hidden),
            whole((feat, 4 * hidden)),
            whole((hidden, 4 * hidden)),
            whole((1, 4 * hidden)),
        ],
        out_specs=(
            row_block(hidden),
            row_block(hidden),
            row_block(4 * hidden),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((padded, hidden), f32),
            jax.ShapeDtypeStruct((padded, hidden), f32),
            jax.ShapeDtypeStruct((padded, 4 * hidden), f32),
        ),
    )(x, h, c, wi, wh, b.reshape(1, -1))
    if padded != batch:
        new_c, new_h, acts = (a[:batch] for a in (new_c, new_h, acts))
    return new_c, new_h, acts


@jax.custom_vjp
def lstm_cell_fused(x, h, c, wi, wh, b):
    """Fused LSTM cell step.

    Args:
      x: `[B, F]` inputs for this step.
      h: `[B, H]` previous hidden state.
      c: `[B, H]` previous cell state.
      wi: `[F, 4H]` input kernel, gates concatenated (i, f, g, o).
      wh: `[H, 4H]` recurrent kernel, same layout.
      b: `[4H]` bias (flax keeps it on the recurrent half).

    Returns:
      (new_c, new_h), each `[B, H]` float32.
    """
    new_c, new_h, _ = _lstm_forward(x, h, c, wi, wh, b)
    return new_c, new_h


def _lstm_fwd(x, h, c, wi, wh, b):
    new_c, new_h, acts = _lstm_forward(x, h, c, wi, wh, b)
    return (new_c, new_h), (x, h, c, wi, wh, acts, new_c)


def _lstm_bwd(res, grads):
    """Closed-form cell backward (plain jnp, the vtrace_pallas bwd
    precedent): elementwise gate algebra + four matmuls. With
    s = sigmoid gates, tc = tanh(new_c):

      d_pre_o = dh' * tc * o(1-o)
      dcp     = dc' + dh' * o * (1 - tc^2)     (cell-state chain)
      d_pre_i = dcp * g * i(1-i)
      d_pre_f = dcp * c * f(1-f)
      d_pre_g = dcp * i * (1 - g^2)
      dc      = dcp * f

    and the matmul transposes dA@Wi^T, dA@Wh^T, x^T@dA, h^T@dA.
    """
    x, h, c, wi, wh, acts, new_c = res
    d_new_c, d_new_h = grads
    hidden = c.shape[-1]
    i = acts[:, :hidden]
    f = acts[:, hidden : 2 * hidden]
    g = acts[:, 2 * hidden : 3 * hidden]
    o = acts[:, 3 * hidden :]
    tc = jnp.tanh(new_c)
    dcp = d_new_c + d_new_h * o * (1.0 - tc * tc)
    d_pre = jnp.concatenate(
        [
            dcp * g * i * (1.0 - i),
            dcp * c * f * (1.0 - f),
            dcp * i * (1.0 - g * g),
            d_new_h * tc * o * (1.0 - o),
        ],
        axis=-1,
    )
    dx = d_pre @ wi.T
    dh = d_pre @ wh.T
    dc = dcp * f
    dwi = x.T @ d_pre
    dwh = h.T @ d_pre
    db = jnp.sum(d_pre, axis=0)
    return dx, dh, dc, dwi, dwh, db


lstm_cell_fused.defvjp(_lstm_fwd, _lstm_bwd)
