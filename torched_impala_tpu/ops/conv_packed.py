"""The residual blocks' 3x3 convolution with a W-packed weight gradient
(ISSUE 31): `p` adjacent pixels of a row are one product.

XLA lays the deep torso's activations out batch on the lanes, channels on
the sublanes. Forward and input gradient of a 16- or 32-channel 3x3
convolution run at the chip's memory bandwidth that way (about 615 GB/s
for what each must read and write; my chip runs, PR 31) and stay as they
are. The weight gradient does not: per pixel and 128 frames it is a
`[C, 128] @ [128, 9*C]` product, 16 (32) rows against a 128-row operand,
and takes twice the time its bytes need. Packed, the cotangent is read as
`[N, H, W/p, p*C]` (the same bytes: in XLA's layout the reshape is a
bitcast), the convolution that contracts frames, rows and groups has a
`3 x (p+2)` result window with `p*C` output features, and the 3x3
gradient is that block-Toeplitz gradient with its diagonals summed:
`p*C` rows a product where there were C, at the bandwidth's time (1.81 ->
0.89 ms at 42x42x16 over 5376 frames; PERF.md section 6, PR 31). Every
term of the plain sum is in the packed one once, accumulated in float32
and rounded once, as XLA's own weight gradient does.

The packed gradient is taken where `conv3x3` is asked for it (a step
that `runtime/learner.resolve_kernels` builds for a TPU) and some `p`
above 1 divides W with `p*C` under 128. Anywhere else the function
traces the plain convolution and nothing of this file: a CPU step would
pay `(p+2)/3` times the arithmetic. Forward and input gradient are
`nn.Conv`'s either way, so what is not differentiated compiles what it
compiled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_ROWS = 128  # the MXU's rows: `p * C` output rows stay under them


def pack_width(w: int, c: int) -> int:
    """Pixels a product: the largest `p` that divides W with `p * C`
    under 128 rows (7 at 42x42x16, 6 at 36x48x16, 3 at 21x21x32 and
    18x24x32: the fastest of those tried on the chip, PERF.md section 6,
    PR 31; at `p * C` = 128 XLA re-lays the cotangent out and the pass
    takes twice the plain one's time). 1, XLA's own weight gradient,
    where nothing divides."""
    return max(
        (p for p in range(2, w + 1) if w % p == 0 and p * c < _ROWS),
        default=1,
    )


def _diagonals(p: int) -> np.ndarray:
    """`[3, p+2, p]`: 1 where column `jj` of a group's `p + 2` input
    columns is tap `kw` of its output pixel `j`."""
    kw, jj, j = np.ogrid[:3, : p + 2, :p]
    return jj == j + kw


def unpack_kernel_sum(packed: jax.Array, p: int) -> jax.Array:
    """A block-Toeplitz kernel's gradient `[3, p+2, C_in, p*C_out]` ->
    `[3, 3, C_in, C_out]`, diagonals summed: output pixel `j` of a group
    of `p` reads input columns `j .. j+2` of the group's `p + 2`, so tap
    `kw` of the 3x3 kernel collects `[:, j + kw, :, j, :]` over `j`."""
    _, _, c_in, m = packed.shape
    on = jnp.asarray(_diagonals(p), packed.dtype)[None, :, :, None, :, None]
    packed = packed.reshape(3, 1, p + 2, c_in, p, m // p)
    return (packed * on).sum((2, 4))


def plain_conv(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """3x3 / stride 1 / SAME over `[N, H, W, C]`, as `nn.Conv` calls it."""
    return lax.conv_general_dilated(
        x, kernel, (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def packed_weight_gradient(
    x: jax.Array, dy: jax.Array, kernel: jax.Array, p: int
) -> jax.Array:
    """The same gradient from products of `p*C` rows of `dy` against
    `(p+2)*C` of `x`, float32 until its diagonals are summed."""
    n, h, w, c_out = dy.shape
    packed = lax.conv_general_dilated(
        x, dy.reshape(n, h, w // p, p * c_out), (1, 1), ((1, 1), (1, 1)),
        rhs_dilation=(1, p),
        dimension_numbers=("CHWN", "IHWO", "HWNC"),
        preferred_element_type=jnp.float32,
    )
    return unpack_kernel_sum(packed, p).astype(kernel.dtype)


@functools.lru_cache(maxsize=None)
def conv_with_packed_gradient(p: int):
    """`plain_conv` whose weight gradient is the packed one."""

    @jax.custom_vjp
    def conv(x, kernel):
        return plain_conv(x, kernel)

    def forward(x, kernel):
        return plain_conv(x, kernel), (x, kernel)

    def backward(residuals, dy):
        x, kernel = residuals
        dx = jax.vjp(lambda x: plain_conv(x, kernel), x)[1](dy)[0]
        return dx, packed_weight_gradient(x, dy, kernel, p)

    conv.defvjp(forward, backward)
    return conv


def conv3x3(
    x: jax.Array, kernel: jax.Array, bias: jax.Array, *, packed: bool
) -> jax.Array:
    """`nn.Conv(C, (3, 3))` over `[..., H, W, C]` from its parameters,
    already in the compute dtype; `packed`: with the packed weight
    gradient where `pack_width` finds a `p`."""
    lead = x.shape[:-3]
    if len(lead) != 1:
        x = x.reshape(-1, *x.shape[-3:])
    p = pack_width(x.shape[2], x.shape[3]) if packed else 1
    conv = plain_conv if p == 1 else conv_with_packed_gradient(p)
    # bias and shapes handled as `nn.Conv` does, equation for equation
    y = conv(x, kernel) + bias.reshape(1, 1, 1, -1)
    return y if len(lead) == 1 else y.reshape(*lead, *y.shape[1:])
