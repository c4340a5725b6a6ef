"""Compile rehearsal for the v5e: the main path's Pallas kernels, lowered
and compiled by the real TPU compiler for a DESCRIBED chip (no device is
attached; nothing runs). Guards what interpret-mode tests cannot see:
block shapes the Mosaic lowering refuses, scoped-VMEM overflow, and
kernels that cannot be partitioned under a mesh.

Rules this file keeps (the `on-chip-measurement` guide, section 2): the
topology is described inside a module-scoped fixture, after a test of
this file has started — never at import, in a `skipif`, or in
`conftest.py` — because only one process may load the TPU library; every
compile happens in this process; all cases live in this one file.

A compile that passes is not a chip run. `chip_smoke.py` is the chip run.
"""

from __future__ import annotations

import collections
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from torched_impala_tpu.models.torsos import AtariDeepTorso
from torched_impala_tpu.ops import lstm_pallas, vtrace_pallas
from torched_impala_tpu.ops.attention_pallas import windowed_attention
from torched_impala_tpu.ops.conv_pallas import fused_residual_block
from torched_impala_tpu.ops.losses import ImpalaLossConfig
from torched_impala_tpu.parallel import spec_layout

F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # any failure to describe it means: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip (the next run would warn
    # and recompile): keep these compiles out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def data_mesh(topo):
    return Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))


def _compile(fn, *structs) -> str:
    return jax.jit(fn).lower(*structs).compile().as_text()


def _shape(sharding, shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# ---- one chip: every kernel compiles to a Mosaic custom call -----------


def _vtrace(log_rhos, discounts, rewards, values, bootstrap_value):
    return vtrace_pallas.vtrace_pallas(
        log_rhos=log_rhos, discounts=discounts, rewards=rewards,
        values=values, bootstrap_value=bootstrap_value,
    )


def test_vtrace_kernel(one_chip):
    tb = _shape(one_chip, (20, 32))
    text = _compile(_vtrace, tb, tb, tb, tb, _shape(one_chip, (32,)))
    assert "tpu_custom_call" in text


def _lstm_structs(sharding, batch, feat, hidden=256):
    return (
        _shape(sharding, (batch, feat)),
        _shape(sharding, (batch, hidden)),
        _shape(sharding, (batch, hidden)),
        _shape(sharding, (feat, 4 * hidden)),
        _shape(sharding, (hidden, 4 * hidden)),
        _shape(sharding, (4 * hidden,)),
    )


# (B, F): the breakout preset's learner batch and torso width; the
# documented B=1024 operating point at that width; and the B=1024, F=512
# shape the un-gridded kernel was refused at (18.83M scoped VMEM > 16M).
_LSTM_SHAPES = [(32, 256), (1024, 256), (1024, 512)]


@pytest.mark.parametrize("batch,feat", _LSTM_SHAPES)
def test_lstm_forward(one_chip, batch, feat):
    text = _compile(
        lstm_pallas.lstm_cell_fused, *_lstm_structs(one_chip, batch, feat)
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch,feat", _LSTM_SHAPES)
def test_lstm_vjp(one_chip, batch, feat):
    def loss(*args):
        new_c, new_h = lstm_pallas.lstm_cell_fused(*args)
        return jnp.sum(new_c) + jnp.sum(new_h)

    text = _compile(
        jax.grad(loss, argnums=tuple(range(6))),
        *_lstm_structs(one_chip, batch, feat),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [32, 1024])
def test_fused_loss_kernel(one_chip, batch):
    """Forward and analytic VJP of `--fused-epilogue` with the kernel
    forced; B=1024 is eight 128-lane tiles (refused before this file
    existed: `(1, 1)` SMEM blocks over a `(grid, 1)` array)."""
    T, A = 20, 4
    config = ImpalaLossConfig(fused_epilogue=True)

    def loss(logits, behaviour, values, bootstrap, actions, rewards, disc):
        return vtrace_pallas.fused_vtrace_loss(
            target_logits=logits,
            behaviour_logits=behaviour,
            values=values,
            bootstrap_value=bootstrap,
            actions=actions,
            rewards=rewards,
            discounts=disc,
            config=config,
            implementation="kernel",
        ).total

    tb = _shape(one_chip, (T, batch))
    tba = _shape(one_chip, (T, batch, A))
    text = _compile(
        jax.value_and_grad(loss, argnums=(0, 2)),
        tba, tba, tb, _shape(one_chip, (batch,)),
        _shape(one_chip, (T, batch), jnp.int32), tb, tb,
    )
    assert "tpu_custom_call" in text


def test_conv_block(one_chip):
    """`--fused-conv` at the breakout preset's last residual section:
    (T+1)*B = 672 images of 11x11x32, bf16 torso (refused before PR 21:
    no bf16 [H, W, C] -> [H*W, C] shape cast). The 21x21x32 and 42x42x16
    sections take 13 s and 27 s to compile — too long for here;
    `chip_smoke.py` compiles and checks all three on the chip."""
    n, hw, c = 21 * 32, 11, 32
    kernel = _shape(one_chip, (3, 3, c, c))
    bias = _shape(one_chip, (c,))
    text = _compile(
        fused_residual_block,
        _shape(one_chip, (n, hw, hw, c), jnp.bfloat16),
        kernel, bias, kernel, bias,
    )
    assert "tpu_custom_call" in text


# Per cell: the packed weight gradients the first two (DMLab: all three)
# sections' blocks take, as `(p + 2, C, p * C)` of the packed product's
# `f32[3, p+2, C, p*C]` result -> how many.
@pytest.mark.parametrize(
    "n,obs,packed",
    [
        (21 * 256, (84, 84, 4), {(9, 16, 112): 4, (5, 32, 96): 4}),
        (101 * 64, (72, 96, 3), {(8, 16, 96): 4, (5, 32, 96): 8}),
    ],
    ids=["breakout_cell", "dmlab_cell"],
)
def test_deep_torso_pool_gradient(one_chip, n, obs, packed):
    """The bf16 deep torso's gradient at the benchmark cells' (T+1)*B
    images (a compile takes as long at 128, and there both versions fit
    without temporaries worth comparing): each pool is two
    Mosaic calls (forward with the winner index, backward from it), no
    `select-and-scatter` is left, the `[H, W, C, N]` views around the
    kernels are bitcasts — no `copy` or `transpose` of anything the size
    of an activation anywhere in the module, but for the uint8
    observations' own re-layout (the learner's AUTO input layouts are
    what removes that one) — and the step needs fewer
    temporaries than with XLA's pool compiled beside it (the convolution
    outputs are no longer kept for the backward).

    ISSUE 31, in the same compile: with `packed_gradients`, as
    `resolve_kernels` builds a TPU's torso, the residual blocks' weight
    gradients are the packed products (`rhs_dilate=1xp`, `p*C` output
    features, float32), one per convolution the torso's `packed_convs`
    lists, XLA's own `[3,3,C,C]` weight gradient is left only where
    nothing divides, and the reshape of the cotangent beside each is no
    `copy` either (the same `moved` list)."""
    torsos = {
        kernel: AtariDeepTorso(
            dtype=jnp.bfloat16, pool_kernel=kernel, packed_gradients=True
        )
        for kernel in (True, False)
    }

    def compiled(pool_kernel):
        torso = torsos[pool_kernel]
        params = jax.eval_shape(
            torso.init, jax.random.key(0), jnp.zeros((1, *obs), jnp.uint8)
        )

        def loss(p, x):
            return jnp.sum(torso.apply(p, x).astype(F32))

        return (
            jax.jit(jax.grad(loss))
            .lower(
                jax.tree.map(
                    lambda a: _shape(one_chip, a.shape, a.dtype), params
                ),
                _shape(one_chip, (n, *obs), jnp.uint8),
            )
            .compile()
        )

    kernel, xla = compiled(True), compiled(False)
    text = kernel.as_text()
    products = collections.Counter(
        tuple(map(int, m.group(1).split(",")))
        for m in re.finditer(
            r"= f32\[3,(\d+,\d+,\d+)\]\S* convolution\([^\n]*rhs_dilate=1x",
            text,
        )
    )
    assert products == packed
    assert packed == collections.Counter(
        (p + 2, c, p * c) for c, _, _, p in torsos[True].packed_convs(obs)
    )
    plain = len(re.findall(r"= \w+\[3,3,\d+,\d+\]\S* convolution\(", text))
    assert plain == 3 + 4 * 3 - sum(packed.values())
    assert text.count('custom_call_target="tpu_custom_call"') == 6
    assert "max_pool_forward" in text and "max_pool_backward" in text
    assert "select-and-scatter(" not in text
    assert xla.as_text().count("select-and-scatter(") == 3
    # The smallest activation a pool touches: the last section's output.
    smallest = n * 32 * math.prod(-(-s // 8) for s in obs[:2])
    moved = [
        line.strip()[:120]
        for line in text.splitlines()
        for m in [re.search(r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(", line)]
        if m
        and (dims := tuple(map(int, m.group(1).split(",")))) != (n, *obs)
        and math.prod(dims) >= smallest
    ]
    assert not moved, moved
    assert (
        kernel.memory_analysis().temp_size_in_bytes
        < xla.memory_analysis().temp_size_in_bytes
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_windowed_attention(one_chip, dtype):
    """Forward and both backward kernels at `pong_transformer` widths:
    B=32, T=21 learner tokens, W=128 cache slots, 4 heads x 64."""
    B, T, W, H, dh = 32, 21, 128, 4, 64
    S = W + T

    def loss(q, k, v, seg_q, seg_ctx):
        return jnp.sum(
            windowed_attention(q, k, v, seg_q, seg_ctx, W).astype(F32)
        )

    text = _compile(
        jax.grad(loss, argnums=(0, 1, 2)),
        _shape(one_chip, (B, T, H, dh), dtype),
        _shape(one_chip, (B, S, H, dh), dtype),
        _shape(one_chip, (B, S, H, dh), dtype),
        _shape(one_chip, (B, T), jnp.int32),
        _shape(one_chip, (B, S), jnp.int32),
    )
    # forward + dQ sweep + dK/dV sweep
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("kind,cache,window", [("window", 512, 512),
                                               ("full", 2048, None)])
def test_hybrid_attention(one_chip, kind, cache, window):
    """The hybrid core's two attention layers at the published widths
    (`pong_phi4flash`): 40 query heads on 20 key/value heads of 64,
    bfloat16, B=2, 2,048 positions behind a cache of 512 under a window
    of 512, and behind a cache of 2,048 with none."""
    B, T, H, Hkv, dh = 2, 2048, 40, 20, 64
    S = cache + T

    def loss(q, k, v, seg_q, seg_ctx):
        return jnp.sum(
            windowed_attention(
                q, k, v, seg_q, seg_ctx, cache, None, window,
                f"attention_{kind}",
            ).astype(F32)
        )

    text = _compile(
        jax.grad(loss, argnums=(0, 1, 2)),
        _shape(one_chip, (B, T, H, dh), jnp.bfloat16),
        _shape(one_chip, (B, S, Hkv, dh), jnp.bfloat16),
        _shape(one_chip, (B, S, Hkv, dh), jnp.bfloat16),
        _shape(one_chip, (B, T), jnp.int32),
        _shape(one_chip, (B, S), jnp.int32),
    )
    for part in ("forward", "backward_dq", "backward_dkv"):
        assert f"attention_{kind}_{part}" in text, part


def test_selective_scan(one_chip):
    """The scan's forward and backward kernels at the published widths:
    B=2, 2,048 positions, d_inner 5120, d_state 16; the state is never
    written out per step (that would be 1.3 GB)."""
    from torched_impala_tpu.ops.selective_scan import selective_scan

    B, T, DI, N = 2, 2048, 5120, 16

    def loss(x, dt, a, b, c, first, s0):
        y, last = selective_scan(x, dt, a, b, c, first, s0)
        return jnp.sum(y) + jnp.sum(last)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        _shape(one_chip, (B, T, DI)), _shape(one_chip, (B, T, DI)),
        _shape(one_chip, (DI, N)), _shape(one_chip, (B, T, N)),
        _shape(one_chip, (B, T, N)), _shape(one_chip, (B, T), jnp.bool_),
        _shape(one_chip, (B, DI, N)),
    ).compile()
    text = compiled.as_text()
    assert "selective_scan_forward" in text
    assert "selective_scan_backward" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 600e6


# ---- four chips: the mesh path resolves to XLA by construction ---------


def _lstm_agent():
    from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso

    return Agent(
        ImpalaNet(num_actions=4, torso=MLPTorso(), use_lstm=True)
    )


def test_mesh_resolves_kernels_to_xla(data_mesh):
    """On a mesh of more than one TPU device `resolve_kernels` hands the
    learner the XLA implementations (Mosaic kernels cannot be
    auto-partitioned), and says which."""
    from torched_impala_tpu.runtime.learner import resolve_kernels

    agent, loss, resolved = resolve_kernels(
        _lstm_agent(), ImpalaLossConfig(), data_mesh
    )
    assert loss.vtrace_implementation == "scan"
    assert agent.net.lstm_impl == "flax"
    assert resolved["vtrace"] == "scan" and resolved["lstm"] == "flax"
    # One TPU device (or no mesh on a TPU) keeps the kernels.
    one = Mesh(
        np.array(data_mesh.devices.flat[:1]).reshape(1, 1),
        ("data", "model"),
    )
    agent, loss, resolved = resolve_kernels(
        _lstm_agent(), ImpalaLossConfig(), one
    )
    assert loss.vtrace_implementation == "pallas"
    assert agent.net.lstm_impl == "fused"
    # An explicit kernel request cannot be honoured there: refuse it by
    # name instead of failing inside the lowering.
    with pytest.raises(ValueError, match="auto-partition"):
        resolve_kernels(
            _lstm_agent(),
            ImpalaLossConfig(vtrace_implementation="pallas"),
            data_mesh,
        )


def test_mesh_resolves_the_hybrid_cores_kernels_to_xla(data_mesh):
    """The hybrid core under a mesh: the written-out attention mask and
    the scan one step at a time; on one TPU device both kernels."""
    from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
    from torched_impala_tpu.runtime.learner import resolve_kernels

    def agent():
        return Agent(ImpalaNet(
            num_actions=4, torso=MLPTorso(), core="hybrid",
            hybrid=(("d_model", 64), ("attention_kernel", "pallas")),
        ))

    resolved_agent, _, resolved = resolve_kernels(
        agent(), ImpalaLossConfig(), data_mesh
    )
    core = dict(resolved_agent.net.hybrid)
    assert core["attention_kernel"] == "einsum"
    assert core["scan_kernel"] is False and core["d_model"] == 64
    assert resolved["attention"] == "einsum"
    assert resolved["selective_scan"] == "xla_scan"
    one = Mesh(
        np.array(data_mesh.devices.flat[:1]).reshape(1, 1),
        ("data", "model"),
    )
    _, _, resolved = resolve_kernels(agent(), ImpalaLossConfig(), one)
    assert resolved["attention"] == "pallas"
    assert resolved["selective_scan"] == "pallas"


def test_vtrace_and_lstm_under_data_mesh(data_mesh):
    """What the resolved learner traces — V-trace loss over an LSTM
    unroll, batch sharded over `data` — compiles for four chips, with no
    Mosaic call in it."""
    from torched_impala_tpu.ops.losses import impala_loss
    from torched_impala_tpu.runtime.learner import resolve_kernels

    agent, loss_config, _ = resolve_kernels(
        _lstm_agent(), ImpalaLossConfig(), data_mesh
    )
    T, B, obs_dim = 20, 64, 16
    params = jax.eval_shape(
        agent.init_params, jax.random.key(0), jnp.zeros((obs_dim,))
    )

    def loss(params, obs, first, state, actions, behaviour, rewards, disc):
        out, _ = agent.unroll(params, obs, first, state)
        values = out.values[..., 0]
        return impala_loss(
            target_logits=out.policy_logits[:-1],
            behaviour_logits=behaviour,
            values=values[:-1],
            bootstrap_value=values[-1],
            actions=actions,
            rewards=rewards,
            discounts=disc,
            config=loss_config,
            devices=data_mesh.devices.flat,
        ).total

    rep = NamedSharding(data_mesh, spec_layout.replicated_spec())
    tb = NamedSharding(data_mesh, spec_layout.batch_spec())
    b = NamedSharding(data_mesh, spec_layout.state_spec())
    text = _compile(
        jax.grad(loss),
        jax.tree.map(lambda x: _shape(rep, x.shape, x.dtype), params),
        _shape(tb, (T + 1, B, obs_dim)),
        _shape(tb, (T + 1, B), jnp.bool_),
        (_shape(b, (B, 256)), _shape(b, (B, 256))),
        _shape(tb, (T, B), jnp.int32),
        _shape(tb, (T, B, 4)),
        _shape(tb, (T, B)),
        _shape(tb, (T, B)),
    )
    assert "tpu_custom_call" not in text
    assert "all-reduce" in text  # gradients are reduced over `data`


def test_mosaic_kernel_is_refused_under_a_mesh(data_mesh):
    """The reason for the rule above, kept where a JAX upgrade that
    lifts the restriction will be noticed."""
    tb = _shape(
        NamedSharding(data_mesh, spec_layout.batch_spec()), (20, 512)
    )
    boot = _shape(
        NamedSharding(data_mesh, spec_layout.state_spec()), (512,)
    )
    with pytest.raises(NotImplementedError, match="shard_map"):
        _compile(_vtrace, tb, tb, tb, tb, boot)


def test_packed_weight_gradients_partition_under_data_mesh(data_mesh):
    """The residual blocks' packed weight gradients are XLA convolutions
    and partition like any other (the batch they contract is sharded
    over `data`, so the gradients' all-reduce follows them): a block's
    gradient at 42x42x16 compiles for four chips with both packed
    products in it and no Mosaic call."""
    from torched_impala_tpu.models.torsos import ResidualBlock

    block = ResidualBlock(16, dtype=jnp.bfloat16, packed_gradient=True)
    shape = (4 * 64, 42, 42, 16)
    params = jax.eval_shape(
        block.init, jax.random.key(0), jnp.zeros((1, *shape[1:]), jnp.bfloat16)
    )
    rep = NamedSharding(data_mesh, spec_layout.replicated_spec())
    rows = NamedSharding(data_mesh, spec_layout.state_spec())

    def loss(p, x):
        return jnp.sum(block.apply(p, x).astype(F32))

    text = _compile(
        jax.grad(loss),
        jax.tree.map(lambda a: _shape(rep, a.shape, a.dtype), params),
        _shape(rows, shape, jnp.bfloat16),
    )
    assert "tpu_custom_call" not in text
    assert "all-reduce" in text
    assert len(re.findall(r"convolution\([^\n]*rhs_dilate=1x7", text)) == 2
