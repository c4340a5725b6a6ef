"""The deep torso's W-packed weight gradients (
ops/conv_packed.py), on the CPU: XLA computes the packed convolutions
here as it computes any other, so tier-1 holds the identities the chip's
program rests on.

Claims: `unpack_kernel_sum` is the transpose of the block-Toeplitz
packing `pack_kernel` below builds; the packed weight gradient is XLA's
own to float32 rounding, and in bfloat16 within two ulps, for every
`(C_in, C_out, W, p)` the rule can choose, the rule keyed on the output
channels; the rule takes p = 1, XLA's gradient and no `custom_vjp`,
where nothing divides; `ResidualBlock` has the parameter tree of two
`nn.Conv`, and a checkpoint written from that tree loads, as the deep
torso loads one written from its tree of `nn.Conv` section-entry
convolutions; the first convolution takes the packed gradient from the
uint8 frames, which are its residual, and its space-to-depth path is
left as it was; the packed products a torso's gradient holds are the
ones `AtariDeepTorso.packed_convs` lists from the shapes; a torso nobody
asked (`resolve_kernels` asks on a TPU only) traces the program of the
`nn.Conv`s, `custom_vjp` and all left out.
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from torched_impala_tpu.models.torsos import (
    AtariDeepTorso,
    ResidualBlock,
    _FirstPixelConv,
)
from torched_impala_tpu.ops import conv_packed
from torched_impala_tpu.ops.conv_packed import (
    conv3x3,
    pack_width,
    packed_weight_gradient,
    plain_conv,
    unpack_kernel_sum,
)
from torched_impala_tpu.ops.maxpool_pallas import max_pool
from torched_impala_tpu.utils.checkpoint import (
    load_state_file,
    save_state_file,
)

# (C, H, W): what the presets' residual blocks meet (84x84 Atari, 72x96
# DMLab, 64x64 Procgen), a W that only 2 divides, and two nothing does;
# (C_in, C_out, H, W): what their section-entry convolutions meet, the
# first over the frames, and one where nothing divides. The rule keys on
# C_out: 16 output channels take 7 pixels a product whatever C_in is.
RULE = {
    (16, 42, 42): 7, (32, 21, 21): 3, (32, 11, 11): 1,
    (16, 36, 48): 6, (32, 18, 24): 3, (32, 9, 12): 3,
    (16, 32, 32): 4, (32, 16, 16): 2, (32, 8, 8): 2,
    (16, 5, 22): 2, (16, 6, 13): 1, (64, 7, 7): 1,
    (4, 16, 84, 84): 7, (3, 16, 72, 96): 6, (3, 16, 64, 64): 4,
    (16, 32, 42, 42): 3, (16, 32, 36, 48): 3, (16, 32, 32, 32): 2,
    (16, 32, 5, 11): 1,
}

PACKED = sorted((*key, p) for key, p in RULE.items() if len(key) == 3 and p > 1)
ENTRY = sorted((*key, p) for key, p in RULE.items() if len(key) == 4 and p > 1)
DTYPES = [jnp.float32, jnp.bfloat16]


def pack_kernel(kernel, p):
    """`[3, 3, C_in, C_out]` -> block-Toeplitz `[3, p+2, C_in, p*C_out]`
    (ISSUE 31's own code)."""
    packed = jnp.zeros(
        (3, p + 2, kernel.shape[2], p, kernel.shape[3]), kernel.dtype
    )
    for j in range(p):
        packed = packed.at[:, j : j + 3, :, j, :].set(kernel)
    return packed.reshape(3, p + 2, kernel.shape[2], p * kernel.shape[3])


def packed_conv(x, kernel, p):
    n, h, w, _ = x.shape
    y = lax.conv_general_dilated(
        x, pack_kernel(kernel, p), (1, p), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return y.reshape(n, h, w, kernel.shape[3])


def plain_weight_gradient(x, dy, kernel):
    """XLA's own: what autodiff of the plain convolution gives."""
    return jax.vjp(lambda k: plain_conv(x, k), kernel)[1](dy)[0]


def _draw(c, h, w, dtype, n=3, seed=0, c_out=None):
    """Inputs whose values bfloat16 holds exactly, so that both dtypes
    multiply the same numbers; `c` channels in, `c_out` (`c`) out."""
    rng = np.random.default_rng(seed + 1000 * h + w)
    c_out = c_out or c

    def make(*shape, scale=1.0):
        a = jnp.asarray(rng.normal(size=shape) * scale, jnp.bfloat16)
        return a.astype(dtype)

    return (
        make(n, h, w, c),
        make(3, 3, c, c_out, scale=0.2),
        make(n, h, w, c_out),
    )


def _close(got, want, dtype):
    """float32: 1e-5 of the largest element. bfloat16: two ulps of each
    element (an ulp is at most 2**-7 of it), with the same room for
    elements that cancel to almost nothing."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = np.abs(want).max()
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * scale)
    else:
        np.testing.assert_allclose(
            got, want, rtol=2 * 2.0**-7, atol=2.0**-7 * 1e-2 * scale
        )


@pytest.mark.parametrize("key", sorted(RULE), ids=str)
def test_rule(key):
    """`(C, H, W)` or `(C_in, C_out, H, W)`: the output channels decide."""
    c_out, w = key[-3], key[-1]
    p = pack_width(w, c_out)
    assert p == RULE[key]
    assert w % p == 0 and p * c_out < 128


@pytest.mark.parametrize("p", [2, 3, 4, 6, 7])
def test_unpack_is_the_transpose_of_pack(p):
    rng = np.random.default_rng(p)
    k = jnp.asarray(rng.normal(size=(3, 3, 5, 4)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(3, p + 2, 5, p * 4)), jnp.float32)
    (want,) = jax.vjp(lambda k: pack_kernel(k, p), k)[1](g)
    np.testing.assert_allclose(
        unpack_kernel_sum(g, p), want, rtol=1e-6, atol=1e-6
    )
    # and it undoes a packing but for the count of diagonals
    np.testing.assert_allclose(
        unpack_kernel_sum(pack_kernel(k, p), p), p * k, rtol=1e-6
    )


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("c,h,w,p", PACKED)
def test_packed_weight_gradient_is_xlas(c, h, w, p, dtype):
    _packed_against_xla(c, c, h, w, p, dtype)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("c_in,c_out,h,w,p", ENTRY)
def test_packed_entry_gradient_is_xlas(c_in, c_out, h, w, p, dtype):
    """The section-entry convolutions' shapes, C_in != C_out."""
    _packed_against_xla(c_in, c_out, h, w, p, dtype)


def _packed_against_xla(c_in, c_out, h, w, p, dtype):
    x, k, dy = _draw(c_in, h, w, dtype, c_out=c_out)
    got = packed_weight_gradient(x, dy, k, p)
    assert got.dtype == dtype and got.shape == k.shape
    # float32 truth from the same (bfloat16-exact) numbers
    truth = plain_weight_gradient(
        *(a.astype(jnp.float32) for a in (x, dy, k))
    )
    _close(got, truth, dtype)
    _close(got, plain_weight_gradient(x, dy, k), dtype)
    if dtype == jnp.float32:
        # and the gradient of the packed product through the packing
        (auto,) = jax.vjp(lambda k: packed_conv(x, k, p), k)[1](dy)
        _close(got, auto, dtype)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("c,h,w,p", PACKED[:4])
def test_conv3x3_under_vjp(c, h, w, p, dtype):
    """`conv3x3` with the packed gradient, leading dimensions and bias
    included, against `nn.Conv` under `jax.vjp`."""
    x, k, dy = _draw(c, h, w, dtype, n=4)
    x, dy = x.reshape(2, 2, h, w, c), dy.reshape(2, 2, h, w, c)
    b = jnp.linspace(-1, 1, c).astype(dtype)
    conv = nn.Conv(c, (3, 3), dtype=dtype, param_dtype=dtype)
    params = {"params": {"kernel": k, "bias": b}}
    want, vjp = jax.vjp(lambda x, p: conv.apply(p, x), x, params)
    got, vjp_got = jax.vjp(
        functools.partial(conv3x3, packed=True), x, k, b
    )
    np.testing.assert_array_equal(got, want)
    dx, dparams = vjp(dy)
    dx_got, dk_got, db_got = vjp_got(dy)
    np.testing.assert_array_equal(dx_got, dx)
    _close(dk_got, dparams["params"]["kernel"], dtype)
    np.testing.assert_array_equal(db_got, dparams["params"]["bias"])


def _gradient_jaxpr(c, h, w, packed, c_out=None):
    x, k, _ = _draw(c, h, w, jnp.float32, c_out=c_out)
    b = jnp.zeros((c_out or c,))
    return str(
        jax.make_jaxpr(
            jax.grad(lambda k: conv3x3(x, k, b, packed=packed).sum())
        )(k)
    )


def test_unasked_or_undivided_is_the_plain_convolution():
    """p = 1, and any W where the packed gradient was not asked for: no
    `custom_vjp`, the jaxpr of `nn.Conv`'s convolution; asked and
    divided (W = 22, p = 2), the packed product."""
    plain = _gradient_jaxpr(16, 6, 13, packed=False)
    assert "custom_vjp" not in plain and "f32[3,4,16,32]" not in plain
    assert _gradient_jaxpr(16, 6, 13, packed=True) == plain
    unasked = _gradient_jaxpr(16, 5, 22, packed=False)
    assert "custom_vjp" not in unasked and "f32[3,4,16,32]" not in unasked
    assert "f32[3,4,16,32]" in _gradient_jaxpr(16, 5, 22, packed=True)
    # 16 -> 32 channels over W = 11: asked, and nothing divides
    entry = _gradient_jaxpr(16, 5, 11, packed=True, c_out=32)
    assert "custom_vjp" not in entry
    assert entry == _gradient_jaxpr(16, 5, 11, packed=False, c_out=32)


class _TwoConvBlock(nn.Module):
    """`ResidualBlock` as it stood before ISSUE 31."""

    channels: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        out = nn.relu(x)
        out = nn.Conv(self.channels, (3, 3), dtype=self.dtype)(out)
        out = nn.relu(out)
        out = nn.Conv(self.channels, (3, 3), dtype=self.dtype)(out)
        return x + out


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("c,h,w", [(16, 6, 14), (16, 6, 13)], ids=str)
def test_block_has_the_tree_of_two_convs_and_loads_their_checkpoint(
    c, h, w, dtype, tmp_path
):
    """Names, shapes, dtypes and the draw from one key, packed (W = 14)
    or not (13); the block's own output from a checkpoint the two-conv
    block wrote is that block's output."""
    x = _draw(c, h, w, jnp.float32)[0]
    old = _TwoConvBlock(c, dtype)
    new = ResidualBlock(c, dtype, packed_gradient=True)
    p_old = old.init(jax.random.key(7), x)
    p_new = new.init(jax.random.key(7), x)
    assert jax.tree.structure(p_old) == jax.tree.structure(p_new)
    assert sorted(p_new["params"]) == ["Conv_0", "Conv_1"]
    for a, b in zip(jax.tree.leaves(p_old), jax.tree.leaves(p_new)):
        assert a.dtype == b.dtype == jnp.float32 and a.shape == b.shape
        np.testing.assert_array_equal(a, b)
    # biases off zero, so that the file's values matter
    p_old = jax.tree.map(lambda a: a + 0.25, p_old)
    path = str(tmp_path / "two_convs.npz")
    save_state_file(path, p_old)
    loaded = load_state_file(path, p_new)
    np.testing.assert_array_equal(
        new.apply(loaded, x), old.apply(p_old, x)
    )


class _ParentDeepTorso(nn.Module):
    """`AtariDeepTorso` as it stood before its section-entry convolutions
    took the packed gradient: `nn.Conv` after the first."""

    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        for i, channels in enumerate((16, 32, 32)):
            if i == 0:
                x = _FirstPixelConv(
                    channels, (3, 3), dtype=self.dtype, name="Conv_0"
                )(x)
            else:
                x = nn.Conv(
                    channels, (3, 3), dtype=self.dtype, name=f"Conv_{i}"
                )(x)
            x = max_pool(x, kernel=False)
            for _ in range(2):
                x = ResidualBlock(channels, dtype=self.dtype)(x)
        x = nn.relu(x)
        x = x.reshape(*x.shape[:-3], -1)
        return nn.relu(nn.Dense(256, dtype=self.dtype)(x))


def _frames(frame, lead=(2,), seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).integers(0, 256, (*lead, *frame)),
        jnp.uint8,
    )


@pytest.mark.parametrize("frame", [(84, 84, 4), (72, 96, 3)], ids=str)
def test_deep_torso_loads_the_parent_tree_and_traces_its_program(
    frame, tmp_path
):
    """The deep torso has its parent's tree (`nn.Conv`'s names, shapes,
    dtypes and draws from one key), loads a checkpoint written from it
    and computes that torso's output from it, packed or not; unasked, its
    gradient's jaxpr is the parent's equation for equation."""
    obs = _frames(frame)
    old = _ParentDeepTorso()
    new = AtariDeepTorso(dtype=jnp.bfloat16, pool_kernel=False)
    p_old = old.init(jax.random.key(3), obs)
    p_new = new.init(jax.random.key(3), obs)
    assert jax.tree.structure(p_old) == jax.tree.structure(p_new)
    for a, b in zip(jax.tree.leaves(p_old), jax.tree.leaves(p_new)):
        assert a.dtype == b.dtype == jnp.float32 and a.shape == b.shape
        np.testing.assert_array_equal(a, b)
    p_old = jax.tree.map(lambda a: a + 0.25, p_old)
    path = str(tmp_path / "deep_torso.npz")
    save_state_file(path, p_old)
    loaded = load_state_file(path, p_new)
    want = old.apply(p_old, obs)
    np.testing.assert_array_equal(new.apply(loaded, obs), want)
    packed = new.clone(packed_gradients=True)
    np.testing.assert_array_equal(packed.apply(loaded, obs), want)

    def jaxpr(torso):
        return str(
            jax.make_jaxpr(
                jax.grad(
                    lambda p: jnp.sum(torso.apply(p, obs).astype(jnp.float32))
                )
            )(p_old)
        )

    assert jaxpr(new) == jaxpr(old)
    h, w, c = frame
    p = pack_width(w, 16)
    # the frames and a channel of ones, for the bias's gradient
    assert f"f32[3,{p + 2},{c + 1},{16 * p}]" in jaxpr(packed)


# The presets' frames: Atari, DMLab, Procgen. `(C_in, C_out, H, W, p)` of
# each packed product, section by section: the entry convolution at the
# section's input size, then its four block convolutions at the pool's.
FRAMES = {
    (84, 84, 4): (
        [(4, 16, 84, 84, 7)]
        + [(16, 16, 42, 42, 7)] * 4
        + [(16, 32, 42, 42, 3)]
        + [(32, 32, 21, 21, 3)] * 4
        + [(32, 32, 21, 21, 3)]
    ),
    (72, 96, 3): (
        [(3, 16, 72, 96, 6)]
        + [(16, 16, 36, 48, 6)] * 4
        + [(16, 32, 36, 48, 3)]
        + [(32, 32, 18, 24, 3)] * 4
        + [(32, 32, 18, 24, 3)]
        + [(32, 32, 9, 12, 3)] * 4
    ),
    (64, 64, 3): (
        [(3, 16, 64, 64, 4)]
        + [(16, 16, 32, 32, 4)] * 4
        + [(16, 32, 32, 32, 2)]
        + [(32, 32, 16, 16, 2)] * 4
        + [(32, 32, 16, 16, 2)]
        + [(32, 32, 8, 8, 2)] * 4
    ),
}


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("frame", sorted(FRAMES), ids=str)
def test_first_convolution_packed_from_the_uint8_frames(frame, dtype):
    """The deep torso's `Conv_0` (`_FirstPixelConv`, 3x3 / stride 1 over
    uint8 frames, the 1/255 fold on the kernel) with the packed gradient
    against its plain program: the output to the bit, the kernel's
    gradient through the fold within `_close`'s rounding, the bias's
    (from the same product) the cotangent's sum within it; and the only
    copy of the frames the backward keeps is the uint8 frames
    themselves."""
    h, w, c = frame
    obs = _frames(frame, lead=(2, 3))
    plain = _FirstPixelConv(16, (3, 3), dtype=dtype)
    packed = plain.clone(packed_gradient=True)
    params = jax.tree.map(
        lambda a: a + 0.25, plain.init(jax.random.key(1), obs)
    )
    want, vjp = jax.vjp(lambda p: plain.apply(p, obs), params)
    got, vjp_got = jax.vjp(lambda p: packed.apply(p, obs), params)
    np.testing.assert_array_equal(got, want)
    dy = _draw(16, h, w, dtype, n=6)[2].reshape(want.shape)
    (g,), (g_got,) = vjp(dy), vjp_got(dy)
    _close(g_got["params"]["kernel"], g["params"]["kernel"], dtype)
    # the bias's gradient is the cotangent's sum, here in float32 and
    # rounded once (the CPU's bfloat16 reduction of the plain program
    # rounds as it goes, by up to 40% at these sizes)
    total = np.asarray(dy, np.float32).sum(axis=(0, 1, 2, 3))
    _close(g_got["params"]["bias"], total, dtype)
    kept = [a for a in jax.tree.leaves(vjp_got) if a.shape[-3:] == frame]
    assert kept and all(a.dtype == jnp.uint8 for a in kept)
    assert any(
        a.dtype != jnp.uint8
        for a in jax.tree.leaves(vjp)
        if a.shape[-3:] == frame
    )


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_space_to_depth_path_is_untouched(dtype):
    """The Nature CNN's first convolution (8x8 / 4, space-to-depth; the
    shallow torso's) traces the gradient it traced, asked for the packed
    gradient or not."""
    obs = _frames((84, 84, 4))
    conv = _FirstPixelConv(
        32, (8, 8), strides=(4, 4), padding="VALID", dtype=dtype
    )
    params = conv.init(jax.random.key(2), obs)

    def jaxpr(module):
        return str(
            jax.make_jaxpr(
                jax.grad(
                    lambda p: jnp.sum(
                        jnp.sin(module.apply(p, obs).astype(jnp.float32))
                    )
                )
            )(params)
        )

    plain = jaxpr(conv)
    assert "custom_vjp" not in plain
    assert jaxpr(conv.clone(packed_gradient=True)) == plain


@pytest.mark.parametrize("frame", sorted(FRAMES), ids=str)
def test_torso_gradients_packed_against_plain(frame, monkeypatch):
    """The bfloat16 deep torso, every leaf's gradient with the packed
    weight gradients against XLA's: the packed kernels (the section-entry
    convolutions' among them) within bfloat16 rounding by L2 norm, the
    biases likewise, everything above equal; and the products the
    gradient holds are the ones `packed_convs` lists from the shapes."""
    plain = AtariDeepTorso(dtype=jnp.bfloat16, pool_kernel=False)
    packed = plain.clone(packed_gradients=True)
    obs = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (2, *frame)), jnp.uint8
    )
    params = plain.init(jax.random.key(0), obs)
    taken, packed_gradient = [], conv_packed.packed_weight_gradient

    def listed(x, dy, kernel, p):
        taken.append((*kernel.shape[2:], *x.shape[1:3], p))
        return packed_gradient(x, dy, kernel, p)

    monkeypatch.setattr(conv_packed, "packed_weight_gradient", listed)

    def grads(torso):
        return jax.grad(
            lambda p: jnp.sum(jnp.sin(torso.apply(p, obs).astype(jnp.float32)))
        )(params)

    want, got = grads(plain), grads(packed)
    # `Conv_0`'s bias gradient, from its product, is the sum of its
    # cotangent in float32 rounded once; the plain program's bfloat16
    # reduction on the CPU rounds as it goes (by 1-2% here), so the sum
    # of the cotangent itself stands in for it
    h, w, _ = frame

    def with_probe(probe):
        def add(call, args, kwargs, context):
            y = call(*args, **kwargs)
            first = isinstance(context.module, _FirstPixelConv)
            return y + probe if first else y

        with nn.intercept_methods(add):
            out = plain.apply(params, obs)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    dy = jax.grad(with_probe)(jnp.zeros((2, h, w, 16), jnp.bfloat16))
    want["params"]["Conv_0"]["bias"] = dy.astype(jnp.float32).sum((0, 1, 2))
    # the backward meets them last section first
    assert taken[::-1] == packed.packed_convs(obs.shape) == FRAMES[frame]
    assert plain.packed_convs(obs.shape) == []
    # fused blocks are Pallas kernels: the entry convolutions alone
    assert packed.clone(fused_blocks=True).packed_convs(obs.shape) == [
        FRAMES[frame][i] for i in (0, 5, 10)
    ]
    for (path, a), b in zip(
        jax.tree.leaves_with_path(want), jax.tree.leaves(got)
    ):
        gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
        assert gap < 2.0**-8, (jax.tree_util.keystr(path), gap)
        if "Dense" in jax.tree_util.keystr(path):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_unasked_block_traces_the_two_convs_program(dtype):
    """`ResidualBlock` as `nn.Module`s build it by default (every
    program not built by `resolve_kernels` for a TPU): the gradient's
    jaxpr is the two `nn.Conv`s' block's, equation for equation."""
    x = _draw(16, 6, 14, jnp.float32)[0]
    old, new = _TwoConvBlock(16, dtype), ResidualBlock(16, dtype)
    params = old.init(jax.random.key(0), x)

    def jaxpr(block):
        return jax.make_jaxpr(
            jax.grad(lambda p: jnp.sum(jnp.sin(block.apply(p, x))))
        )(params)

    assert str(jaxpr(new)) == str(jaxpr(old))
    assert "f32[3,9,16,112]" in str(
        jaxpr(ResidualBlock(16, dtype, packed_gradient=True))
    )


@pytest.mark.parametrize("platform,devices", [("tpu", 1), ("tpu", 4), ("cpu", 1)])
def test_resolve_kernels_asks_on_a_tpu(platform, devices):
    """`resolve_kernels` decides by the devices the step runs on
    (stand-ins with a `platform` are all it reads): the packed gradient
    on a TPU, one device or a mesh of several, and not elsewhere."""
    import dataclasses

    from torched_impala_tpu import configs
    from torched_impala_tpu.ops.losses import ImpalaLossConfig
    from torched_impala_tpu.runtime.learner import resolve_kernels

    @dataclasses.dataclass(frozen=True)
    class FakeDevice:
        platform: str

    @dataclasses.dataclass(frozen=True)
    class FakeMesh:
        devices: np.ndarray

    cfg = configs.REGISTRY["breakout"]
    mesh = FakeMesh(np.array([FakeDevice(platform)] * devices, dtype=object))
    agent, _, _ = resolve_kernels(
        configs.make_agent(cfg), ImpalaLossConfig(), mesh
    )
    torso = agent.net.torso
    assert torso.packed_gradients == (platform == "tpu")
    assert torso.packed_convs(cfg.obs_shape) == (
        FRAMES[84, 84, 4] if platform == "tpu" else []
    )


def test_learner_lists_its_packed_convolutions(monkeypatch):
    """`Learner.kernels["packed_convs"]`: the breakout preset's eleven
    where `resolve_kernels` asked for them (none on the CPU:
    tests/test_pallas_maxpool.py's learner holds that)."""
    import dataclasses

    from torched_impala_tpu import configs
    from torched_impala_tpu.runtime import learner as learner_module

    cfg = dataclasses.replace(
        configs.REGISTRY["breakout"], batch_size=2, unroll_length=2
    )
    resolve = learner_module.resolve_kernels

    def as_on_a_tpu(*args):
        agent, loss, resolved = resolve(*args)
        net = agent.net
        assert not net.torso.packed_gradients
        net = net.clone(torso=net.torso.clone(packed_gradients=True))
        return dataclasses.replace(agent, net=net), loss, resolved

    monkeypatch.setattr(learner_module, "resolve_kernels", as_on_a_tpu)
    learner = learner_module.Learner(
        agent=configs.make_agent(cfg),
        optimizer=configs.make_optimizer(cfg),
        config=configs.make_learner_config(cfg),
        example_obs=configs.example_obs(cfg),
        rng=jax.random.key(0),
    )
    learner.stop()
    assert learner.kernels["packed_convs"] == FRAMES[84, 84, 4]
