"""Fused Pallas residual conv block for the ResNet torso (ISSUE 16).

`ResidualBlock` (models/torsos.py) is relu → conv3x3 SAME → relu →
conv3x3 SAME → +skip. XLA materializes each stage to HBM; this kernel
computes the whole block per batch image in one `pallas_call`, with the
intermediate activation living only in VMEM.

Formulation: a 3x3 SAME conv over `[H, W, C]` is nine shifted
`[H*W, C] @ [C, F]` matmuls over the zero-padded input — MXU-shaped
work with static slices, no gather. The kernel runs the nine-shift
matmul for conv1 over the pre-padded relu(x), applies bias+relu, embeds
the result in a zero VMEM scratch ring (conv2's SAME padding pads
*conv1's output* with zeros — evaluating conv1 outside the image would
be wrong), runs the nine-shift matmul again for conv2, and adds the
skip. Matmuls accumulate in f32 (`preferred_element_type`) with
operands in the block's compute dtype — the same bf16-in/f32-acc
contract XLA's TPU conv emitters use.

`vtrace_pallas`-style analytic VJP in plain jnp: conv transposes are
the same nine-shift matmuls with flipped shifts and transposed kernels
(`_bwd` derives them in closed form), so autodiff never sees the Pallas
call. Lowered for anything but a TPU the kernel runs in interpret mode
(ops/pallas_util.py; statically unrolled shifts, no `fori_loop`) —
tier-1 exercises the kernel body on CPU.
Parity against the flax reference block is pinned in
tests/test_pallas_conv.py (f32 ulp-level tolerance).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torched_impala_tpu.ops.pallas_util import pallas_call


def _nine_shift(xp, k, h, w):
    """Sum of nine shifted matmuls == 3x3 SAME conv over the padded
    input `xp` `[H+2, W+2, C]` with kernel `k` `[3, 3, C, F]`."""
    c = xp.shape[-1]
    f = k.shape[-1]
    acc = jnp.zeros((h * w, f), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            # Flatten in f32: Mosaic has no [H, W, C] -> [H*W, C] shape
            # cast for packed (bf16) vectors when W is not a multiple of
            # the sublane tile; the round trip through f32 is exact.
            patch = (
                xp[dy : dy + h, dx : dx + w, :]
                .astype(jnp.float32)
                .reshape(h * w, c)
                .astype(xp.dtype)
            )
            acc = acc + jnp.dot(
                patch, k[dy, dx], preferred_element_type=jnp.float32
            )
    return acc


def _residual_block_kernel(
    x_ref, xp_ref, k1_ref, b1_ref, k2_ref, b2_ref, out_ref, y1p_ref
):
    """One image's full residual block; `y1p_ref` is the VMEM scratch
    holding conv1's activated output inside a zero ring (conv2's SAME
    zero padding)."""
    h, w = x_ref.shape[1], x_ref.shape[2]
    dtype = x_ref.dtype
    a1 = _nine_shift(xp_ref[0], k1_ref[:], h, w) + b1_ref[:]
    y1 = jnp.maximum(a1, 0.0).reshape(h, w, -1).astype(dtype)
    y1p_ref[:] = jnp.zeros_like(y1p_ref)
    y1p_ref[1 : h + 1, 1 : w + 1, :] = y1
    a2 = _nine_shift(y1p_ref[:], k2_ref[:], h, w) + b2_ref[:]
    out_ref[0] = (
        x_ref[0].astype(jnp.float32) + a2.reshape(h, w, -1)
    ).astype(dtype)


def _pad1(x):
    """Zero-pad the two spatial axes of `[N, H, W, C]` by 1."""
    return jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))


def _block_forward(x, k1, b1, k2, b2):
    """Pallas forward: grid over batch, weights broadcast."""
    n, h, w, c = x.shape
    dtype = x.dtype
    xp = _pad1(jnp.maximum(x, 0))
    grid = (n,)
    img = lambda i: (i, 0, 0, 0)  # noqa: E731
    rep = lambda *_: (0,) * 4  # noqa: E731
    vec = lambda *_: (0,)  # noqa: E731
    return pallas_call(
        _residual_block_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, h, w, c), img),
            pl.BlockSpec((1, h + 2, w + 2, c), img),
            pl.BlockSpec((3, 3, c, c), rep),
            pl.BlockSpec((c,), vec),
            pl.BlockSpec((3, 3, c, c), rep),
            pl.BlockSpec((c,), vec),
        ],
        out_specs=pl.BlockSpec((1, h, w, c), img),
        out_shape=jax.ShapeDtypeStruct((n, h, w, c), dtype),
        scratch_shapes=[pltpu.VMEM((h + 2, w + 2, c), dtype)],
        # C=16/32 channels occupy 128-lane tiles, so one 42x42x16 image
        # with its nine shifted patches needs ~21M of VMEM in f32 — more
        # than the 16M a kernel is scoped to by default on a v5e (128M
        # physical).
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=64 << 20),
    )(x, xp, k1.astype(dtype), b1, k2.astype(dtype), b2)


def _reference_intermediates(x, k1, b1, k2, b2):
    """(xp1, a1) recomputed for the backward — cheaper to rebuild conv1's
    pre-activation than to stream `[N, H, W, C]` residuals out of VMEM."""
    n, h, w, _ = x.shape
    xp1 = _pad1(jnp.maximum(x, 0))
    a1 = (
        jax.vmap(lambda img: _nine_shift(img, k1, h, w))(xp1).reshape(
            n, h, w, -1
        )
        + b1
    )
    return xp1, a1


@jax.custom_vjp
def fused_residual_block(x, k1, b1, k2, b2):
    """relu → conv3x3 SAME → relu → conv3x3 SAME → +skip, fused.

    Args:
      x: `[N, H, W, C]` input (the block's compute dtype).
      k1/k2: `[3, 3, C, C]` conv kernels (f32 params; cast in-kernel).
      b1/b2: `[C]` biases.

    Returns:
      `[N, H, W, C]`, same dtype as `x`.
    """
    return _block_forward(x, k1, b1, k2, b2)


def _block_fwd(x, k1, b1, k2, b2):
    return _block_forward(x, k1, b1, k2, b2), (x, k1, b1, k2, b2)


def _block_bwd(res, dout):
    """Closed-form block backward (plain jnp). With xr = relu(x),
    a1 = conv1(xr)+b1, y1 = relu(a1), out = x + conv2(y1)+b2:

      db2 = Σ dout                 dk2[s] = patchᵀ(y1p, s) @ dout
      dy1 = conv2ᵀ(dout)          (nine flipped shifts, kernel
                                   transposed on channels)
      da1 = dy1 · [a1 > 0]
      db1 = Σ da1                  dk1[s] = patchᵀ(xp1, s) @ da1
      dx  = dout + conv1ᵀ(da1) · [x > 0]
    """
    x, k1, b1, k2, b2 = res
    n, h, w, c = x.shape
    f32 = jnp.float32
    dout = dout.astype(f32)
    xp1, a1 = _reference_intermediates(
        x.astype(f32), k1.astype(f32), b1, k2.astype(f32), b2
    )
    y1 = jnp.maximum(a1, 0.0)
    y1p = _pad1(y1)

    def conv_t(dyy, k):
        """Transposed 3x3 SAME conv: d input from d output."""
        dp = _pad1(dyy)
        acc = jnp.zeros((n, h, w, c), f32)
        for dy in range(3):
            for dx in range(3):
                sl = dp[:, 2 - dy : 2 - dy + h, 2 - dx : 2 - dx + w, :]
                acc = acc + jnp.einsum("nhwd,cd->nhwc", sl, k[dy, dx])
        return acc

    def kernel_grad(src_p, dyy):
        """dk[dy, dx] = Σ_nhw src_p[n, h+dy, w+dx, :]ᵀ dyy[n, h, w, :]."""
        rows = []
        for dy in range(3):
            cols = []
            for dx in range(3):
                sl = src_p[:, dy : dy + h, dx : dx + w, :]
                cols.append(jnp.einsum("nhwc,nhwd->cd", sl, dyy))
            rows.append(jnp.stack(cols))
        return jnp.stack(rows)

    db2 = jnp.sum(dout, axis=(0, 1, 2))
    dk2 = kernel_grad(y1p, dout)
    dy1 = conv_t(dout, k2.astype(f32))
    da1 = dy1 * (a1 > 0)
    db1 = jnp.sum(da1, axis=(0, 1, 2))
    dk1 = kernel_grad(xp1, da1)
    dxr = conv_t(da1, k1.astype(f32))
    dx = dout + dxr * (x > 0)
    return (
        dx.astype(x.dtype),
        dk1.astype(k1.dtype),
        db1.astype(b1.dtype),
        dk2.astype(k2.dtype),
        db2.astype(b2.dtype),
    )


fused_residual_block.defvjp(_block_fwd, _block_bwd)
