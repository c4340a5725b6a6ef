"""Observation torsos: MLP, Nature-CNN, IMPALA deep ResNet (Flax).

Capability parity with the reference's policy-network zoo (SURVEY.md §1
item 4, reconstructed from BASELINE.json:7-11): 2-layer MLP (CartPole),
Nature-CNN "shallow torso" (Pong), IMPALA deep ResNet ((16,32,32) channel
sections, 2 residual blocks each) for Breakout/Procgen/DMLab. Mirrors the
analog's `haiku_nets.py:26,57,79,104` decomposition but written Flax-first.

TPU notes: convs/matmuls run on the MXU; `dtype` selects the compute dtype
(bfloat16 halves HBM traffic and doubles MXU throughput) while parameters
stay float32. Pixel observations arrive uint8 `[..., H, W, C]` and are
scaled inside the torso so the host→device transfer stays 1 byte/pixel.

Four TPU-shaped rewrites live here, none of which changes a parameter
tree: the first pixel convolution's kernel-side 1/255 fold and its
space-to-depth form (`_FirstPixelConv`), the deep torso's max-pool,
whose backward routes gradients from a one-byte winner index saved by
the forward instead of XLA's `select-and-scatter` over the kept
convolution output (`ops/maxpool_pallas.py`; not differentiated it is
`nn.max_pool` as before), and the deep torso's 3x3 convolutions, the
section-entry ones and the residual blocks', whose weight gradient in a
step built for a TPU takes several adjacent pixels a product so that 16
or 32 output channels fill the MXU's rows (`ops/conv_packed.py`; the
first one's from the uint8 frames; forward and input gradient are
`nn.Conv`'s everywhere, and so is the weight gradient off a TPU).
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen.dtypes import promote_dtype

from torched_impala_tpu.ops.conv_packed import conv3x3, pack_width
from torched_impala_tpu.ops.maxpool_pallas import max_pool


class _FirstPixelConv(nn.Module):
    """First conv over pixel observations, with two TPU-shaped rewrites.
    Parameter tree is bit-identical to the `nn.Conv` it replaces (same
    `kernel`/`bias` names, shapes, f32 param dtype, and initializers), so
    checkpoints and the TP `model_shardings` are unaffected.

    1. **Kernel-side 1/255 fold** (uint8 inputs only):
       `conv(x/255, w) == conv(x, w/255)`, so the normalize is one f32
       multiply on the 8 KB kernel instead of a pass over the obs batch.
       The bare uint8->dtype convert then sinks into the conv's input
       fusion and XLA's obs layout transpose (the r4 headline trace's
       copy.8 — 12% of the train step) runs on 1-byte elements.
       Activations stay in the normalized range, so bf16 rounding is
       normal (the r4 output-side fold ran the conv on 0..255 inputs and
       needed 0.08-loose pinning; this fold is tight — tests/test_models).

    2. **Space-to-depth** (strided first conv, `kernel % stride == 0`):
       a kh x kw / stride-s conv over C channels is algebraically the
       same sum as a (kh/s x kw/s) / stride-1 conv over s*s*C channels
       of s x s pixel blocks. For the Nature-CNN 8x8/4 first layer this
       turns a C_in=4 contraction (4/128 MXU lane utilization; the dW
       pass alone was 22% of the r5 headline trace) into C_in=64.
       Input repack is a pure reshape/transpose on uint8 bytes; kernel
       repack is free (8 KB, constant-folded).

    With `packed_gradient` (the deep torso's, set where `resolve_kernels`
    builds a step for a TPU) a 3x3 / stride 1 / SAME convolution takes
    the W-packed weight and bias gradients of `ops/conv_packed.py`, whose
    residual is the uint8 frames themselves; the 1/255 fold stays outside
    it, so the kernel's gradient flows through the fold as before.
    """

    features: int
    kernel_size: tuple
    strides: tuple = (1, 1)
    padding: str = "SAME"
    dtype: jnp.dtype = jnp.float32
    packed_gradient: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        kh, kw = self.kernel_size
        sh, sw = self.strides
        cin = x.shape[-1]
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (kh, kw, cin, self.features),
            jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.features,), jnp.float32
        )
        if x.dtype == jnp.uint8:
            kernel = kernel * (1.0 / 255.0)
        lead, (h, w) = x.shape[:-3], x.shape[-3:-1]
        xb = x.reshape(-1, h, w, cin)
        if self.packed_gradient and (kh, kw, sh, sw, self.padding) == (
            3, 3, 1, 1, "SAME"
        ):
            y = conv3x3(
                xb,
                kernel.astype(self.dtype),
                bias.astype(self.dtype),
                packed=True,
            )
            return y.reshape(*lead, *y.shape[1:])
        # Space-to-depth only understands the two string conventions; an
        # explicit pad-pair (or CIRCULAR etc.) routes to the plain conv.
        s2d = (
            self.padding in ("SAME", "VALID")
            and sh == sw
            and sh > 1
            and kh % sh == 0
            and kw % sw == 0
        )
        if s2d:
            y = self._s2d_conv(xb, kernel)
        else:
            y = jax.lax.conv_general_dilated(
                xb.astype(self.dtype),
                kernel.astype(self.dtype),
                (sh, sw),
                self.padding,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
            )
        y = y + bias.astype(self.dtype)
        return y.reshape(*lead, *y.shape[1:])

    def _s2d_conv(self, x: jax.Array, kernel: jax.Array) -> jax.Array:
        """Strided conv as a stride-1 conv over pixel blocks.

        VALID windows start at multiples of s, so the used input extent
        (out-1)*s + kh is block-aligned (s | kh) — no pixel movement
        beyond an edge trim. SAME needs an explicit low/high pad first
        (XLA's split: low = total // 2); the padded extent is likewise
        always a multiple of s.
        """
        n, h, w, cin = x.shape
        kh, kw = self.kernel_size
        s = self.strides[0]
        if self.padding != "VALID":
            # SAME: explicit low/high pad to the block-aligned extent
            # first (XLA's split: low = total // 2), then the same
            # reshape applies.
            out_h, out_w = -(-h // s), -(-w // s)
            pad_h = max((out_h - 1) * s + kh - h, 0)
            pad_w = max((out_w - 1) * s + kw - w, 0)
            x = jnp.pad(
                x,
                (
                    (0, 0),
                    (pad_h // 2, pad_h - pad_h // 2),
                    (pad_w // 2, pad_w - pad_w // 2),
                    (0, 0),
                ),
            )
        else:
            # VALID: trim the unused remainder so the extent is
            # block-aligned (windows start at multiples of s and
            # (out-1)*s + kh is a multiple of s).
            out_h, out_w = (h - kh) // s + 1, (w - kw) // s + 1
            x = x[:, : (out_h - 1) * s + kh, : (out_w - 1) * s + kw, :]
        hp, wp = x.shape[1:3]
        # Splitting each spatial axis into (blocks, s) is a PURE RESHAPE
        # (row-major split) — no transpose, no data movement. The conv
        # then runs with FOUR spatial dims: (block_h, in_h, block_w,
        # in_w) with window (kh/s, s, kw/s, s) and stride 1; the two
        # intra-block dims contract to extent 1. Output position
        # (I, J) covers pixels (s*I + ki, s*J + kj), ki = s*pi + bi —
        # exactly the strided conv. XLA's TPU conv emitters handle the
        # blocked layout internally; the r5 trace showed the explicit
        # blocks-to-channels transpose costing 2.4 ms/step of pure u8
        # data movement that this formulation deletes.
        xs = x.reshape(n, hp // s, s, wp // s, s, cin)
        ws = kernel.reshape(kh // s, s, kw // s, s, cin, self.features)
        dn = jax.lax.conv_dimension_numbers(
            xs.shape, ws.shape, ("NHXWYC", "HXWYIO", "NHXWYC")
        )
        y = jax.lax.conv_general_dilated(
            xs.astype(self.dtype),
            ws.astype(self.dtype),
            (1, 1, 1, 1),
            "VALID",
            dimension_numbers=dn,
        )
        return y.reshape(n, out_h, out_w, self.features)


class MLPTorso(nn.Module):
    """2-layer MLP for vector observations (CartPole smoke config)."""

    hidden_sizes: Sequence[int] = (64, 64)
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = x.astype(self.dtype)
        x = x.reshape(*x.shape[:-1], -1) if x.ndim > 2 else x
        for size in self.hidden_sizes:
            x = nn.relu(nn.Dense(size, dtype=self.dtype)(x))
        return x


class AtariShallowTorso(nn.Module):
    """Nature-CNN: 3 VALID convs + Dense(512) (analog `haiku_nets.py:57-76`,
    which pins `padding='VALID'` per the DQN paper: 84 -> 20 -> 9 -> 7,
    flatten 3136). Rounds 1-4 ran flax's default SAME here (21 -> 11 ->
    11, flatten 7744) — a silent 2x over-compute vs the cited spec;
    fixed in r5 (param shapes changed: Dense_0 kernel 7744 -> 3136)."""

    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        x = nn.relu(
            _FirstPixelConv(
                32,
                (8, 8),
                strides=(4, 4),
                padding="VALID",
                dtype=self.dtype,
                name="Conv_0",
            )(x)
        )
        x = nn.relu(
            nn.Conv(
                64,
                (4, 4),
                strides=(2, 2),
                padding="VALID",
                dtype=self.dtype,
                name="Conv_1",
            )(x)
        )
        x = nn.relu(
            nn.Conv(
                64,
                (3, 3),
                strides=(1, 1),
                padding="VALID",
                dtype=self.dtype,
                name="Conv_2",
            )(x)
        )
        x = x.reshape(*x.shape[:-3], -1)
        return nn.relu(nn.Dense(512, dtype=self.dtype)(x))


class _ConvParams(nn.Module):
    """Param-only 3x3 conv holder: same param names, shapes, and default
    initializers as `nn.Conv(features, (3, 3))`, so a `ResidualBlock`
    has the param tree of two `nn.Conv` whichever way it computes (the
    submodule is named `Conv_0`/`Conv_1`, matching flax's auto-naming —
    same RNG paths at init, same checkpoint layout)."""

    features: int

    @nn.compact
    def __call__(self, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (3, 3, x.shape[-1], self.features),
            jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros_init(), (self.features,), jnp.float32
        )
        return kernel, bias


def _conv(x, kernel, bias, dtype, packed: bool) -> jax.Array:
    """`nn.Conv(dtype=dtype)`'s 3x3 convolution from its parameters
    (`promote_dtype`: what it does with its input and parameters)."""
    return conv3x3(*promote_dtype(x, kernel, bias, dtype=dtype), packed=packed)


class ResidualBlock(nn.Module):
    """Two 3x3 convs with a skip connection (analog `haiku_nets.py:79-101`):
    relu, conv, relu, conv, add, the convolutions computed by
    `ops/conv_packed.py` from the parameters of two `nn.Conv` (with
    `packed_gradient` their weight gradient is the W-packed one,
    everything else `nn.Conv`'s).

    With `fused=True` the whole block — relu, both convs, the skip add —
    runs as one Pallas kernel per image (`ops/conv_pallas.py`), keeping
    the intermediate activation in VMEM instead of round-tripping each
    stage through HBM. Same param tree either way; outputs agree to
    ulp-level f32 tolerance (tests/test_pallas_conv.py)."""

    channels: int
    dtype: jnp.dtype = jnp.float32
    fused: bool = False
    packed_gradient: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        k1, b1 = _ConvParams(self.channels, name="Conv_0")(x)
        k2, b2 = _ConvParams(self.channels, name="Conv_1")(x)
        if self.fused:
            from torched_impala_tpu.ops.conv_pallas import (
                fused_residual_block,
            )

            return fused_residual_block(x.astype(self.dtype), k1, b1, k2, b2)

        out = _conv(nn.relu(x), k1, b1, self.dtype, self.packed_gradient)
        out = _conv(nn.relu(out), k2, b2, self.dtype, self.packed_gradient)
        return x + out


class AtariDeepTorso(nn.Module):
    """IMPALA deep ResNet: sections of (conv, maxpool, 2 residual blocks)
    with (16, 32, 32) channels, then Dense(256) (analog
    `haiku_nets.py:104-130`; IMPALA paper fig. 3). The section-entry
    convolutions after the first are computed by `ops/conv_packed.py`
    from the parameters of the `nn.Conv` they stand for (same names,
    shapes, initialisers and checkpoints)."""

    channel_sections: Sequence[int] = (16, 32, 32)
    blocks_per_section: int = 2
    hidden_size: int = 256
    dtype: jnp.dtype = jnp.float32
    # Route residual blocks through the fused Pallas block kernel
    # (ops/conv_pallas.py; `--fused-conv`). Param-tree compatible with
    # the unfused path — opt-in because the win is TPU memory-bandwidth
    # bound and CPU interpret mode is strictly slower.
    fused_blocks: bool = False
    # The pool's own backward (ops/maxpool_pallas.py). Not a choice of
    # the user's: `resolve_kernels` clears it for a learner on a mesh of
    # several TPU devices, where a Mosaic kernel cannot be partitioned.
    pool_kernel: bool = True
    # The W-packed weight gradient of every 3x3 convolution where a `p`
    # divides (ops/conv_packed.py). Not a choice of the user's either:
    # `resolve_kernels` sets it for a step that runs on a TPU; off it the
    # torso traces `nn.Conv`'s own program.
    packed_gradients: bool = False

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        for i, channels in enumerate(self.channel_sections):
            if i == 0:
                # Stride-1 3x3: no space-to-depth; still gets the
                # kernel-side 1/255 fold for uint8 pixels.
                x = _FirstPixelConv(
                    channels,
                    (3, 3),
                    dtype=self.dtype,
                    packed_gradient=self.packed_gradients,
                    name="Conv_0",
                )(x)
            else:
                kernel, bias = _ConvParams(channels, name=f"Conv_{i}")(x)
                x = _conv(x, kernel, bias, self.dtype, self.packed_gradients)
            x = max_pool(x, kernel=self.pool_kernel)
            for _ in range(self.blocks_per_section):
                x = ResidualBlock(
                    channels,
                    dtype=self.dtype,
                    fused=self.fused_blocks,
                    packed_gradient=self.packed_gradients,
                )(x)
        x = nn.relu(x)
        x = x.reshape(*x.shape[:-3], -1)
        return nn.relu(nn.Dense(self.hidden_size, dtype=self.dtype)(x))

    def packed_convs(self, obs_shape: Sequence[int]) -> list:
        """`(C_in, C_out, H, W, p)` of each convolution over `[..., H, W,
        C]` observations whose weight gradient is the packed one
        (ops/conv_packed.py), from the shapes alone: a section's entry
        convolution at its input's size, then its blocks' (none where the
        blocks are fused) at the pool's, which halves H and W, rounded
        up."""
        if not self.packed_gradients:
            return []
        h, w, c_in = obs_shape[-3:]
        taken = []
        for channels in self.channel_sections:
            p = pack_width(w, channels)
            if p > 1:
                taken.append((c_in, channels, h, w, p))
            h, w, c_in = -(-h // 2), -(-w // 2), channels
            p = pack_width(w, channels)
            if p > 1 and not self.fused_blocks:
                block = (channels, channels, h, w, p)
                taken += [block] * (2 * self.blocks_per_section)
        return taken
