"""The deep torso's max-pool with its own backward (ISSUE 29): interpret
mode on CPU, so tier-1 exercises the exact kernel bodies (`kernel_pool`
below is `pool_with_index`, what `max_pool` takes in a program lowered
for a TPU; lowered for the CPU `max_pool` is XLA's pool).

Claims (ops/maxpool_pallas.py): the pooled values are `nn.max_pool`'s;
every gradient element is routed where `jax.grad` through `nn.max_pool`
routes it (first maximal element in row-major window order, padding
never wins), which integer-valued cotangents show exactly in float32 and
bfloat16 alike; overlapping contributions are summed in float32 and
rounded once. A second, kernel-free reference (the parity-plane
formulation) computes winner and gradient in plain jnp."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torched_impala_tpu.models.torsos import AtariDeepTorso
from torched_impala_tpu.ops import maxpool_pallas
from torched_impala_tpu.ops.maxpool_pallas import max_pool, pool_with_index

# What the presets' three sections meet (84x84 Atari, 72x96 DMLab,
# 64x64 Procgen) and one odd pair.
SIZES = [
    (84, 84), (42, 42), (21, 21),
    (72, 96), (36, 48), (18, 24),
    (64, 64), (32, 32), (16, 16),
    (7, 5),
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _xla_pool(x):
    return nn.max_pool(x, window_shape=(3, 3), strides=(2, 2), padding="SAME")


def kernel_pool(x):
    return pool_with_index(x.shape[-3], x.shape[-2])(x)


def _out(size):
    return -(-size // 2)


def _inputs(h, w, dtype, kind, n=3, c=8, seed=0):
    """`ties`: four distinct values, so most windows hold their maximum
    several times; `neg_inf`: normal draws with -inf among them.
    The cotangent holds small integers: sums of up to four are exact in
    bfloat16, so equal gradients mean equal routing."""
    rng = np.random.default_rng(seed + 1000 * h + w)
    if kind == "ties":
        x = rng.integers(0, 4, size=(n, h, w, c)).astype(np.float32) - 1.5
    else:
        x = rng.normal(size=(n, h, w, c)).astype(np.float32)
        # Sparse, and both corners: a window's first valid position may
        # hold -inf, but no window is -inf throughout (where nothing
        # exceeds -inf the kernel keeps the first position inside the
        # image; XLA's CPU expansion of `select-and-scatter` lets the
        # padding win there and drops the gradient).
        x[rng.random(x.shape) < 0.03] = -np.inf
        x[:, 0, 0] = x[:, -1, -1] = -np.inf
    g = rng.integers(1, 9, size=(n, _out(h), _out(w), c)).astype(np.float32)
    return jnp.asarray(x, dtype), jnp.asarray(g, dtype)


def _vjp(pool, x, g):
    y, pull = jax.vjp(pool, x)
    return y, pull(g)[0]


def parity_plane_pool_grad(x, g):
    """Pooled values and input gradient in plain jnp, no kernel and no
    `select-and-scatter`: nine strided candidate planes, the first valid
    maximum's index, and the nine masked gradients added into the four
    parity planes of the padded input, `[N, Ho+1, 2, Wo+1, 2, C]`."""
    n, h, w, c = x.shape
    ho, wo = _out(h), _out(w)
    lo_h, lo_w = h % 2, w % 2
    pad = (
        (0, 0),
        (lo_h, 2 * ho + 2 - h - lo_h),
        (lo_w, 2 * wo + 2 - w - lo_w),
        (0, 0),
    )
    xp = jnp.pad(x.astype(jnp.float32), pad, constant_values=-jnp.inf)
    inside = jnp.pad(jnp.ones((1, h, w, 1), bool), pad)
    taps = [(dy, dx) for dy in range(3) for dx in range(3)]

    def planes(a):
        return jnp.stack(
            [a[:, dy : dy + 2 * ho : 2, dx : dx + 2 * wo : 2] for dy, dx in taps]
        )

    cands, valid = planes(xp), planes(inside)
    best = jnp.max(cands, axis=0)
    winner = jnp.argmax((cands == best) & valid, axis=0)  # first True
    gp = [[jnp.zeros((n, ho + 1, wo + 1, c), jnp.float32)] * 2 for _ in "ab"]
    for k, (dy, dx) in enumerate(taps):
        part = jnp.where(winner == k, g.astype(jnp.float32), 0.0)
        part = jnp.pad(
            part,
            (
                (0, 0),
                (dy // 2, 1 - dy // 2),
                (dx // 2, 1 - dx // 2),
                (0, 0),
            ),
        )
        gp[dy % 2][dx % 2] = gp[dy % 2][dx % 2] + part
    full = jnp.stack(
        [jnp.stack(row, axis=3) for row in gp], axis=2
    ).reshape(n, 2 * ho + 2, 2 * wo + 2, c)
    dx_ = full[:, lo_h : lo_h + h, lo_w : lo_w + w]
    return best.astype(x.dtype), dx_.astype(x.dtype)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize("hw", SIZES, ids=lambda hw: f"{hw[0]}x{hw[1]}")
def test_forward_and_routing_match_xla_pool_on_ties(hw, dtype):
    x, g = _inputs(*hw, dtype, "ties")
    y, dx = _vjp(kernel_pool, x, g)
    y_ref, dx_ref = _vjp(_xla_pool, x, g)
    assert y.dtype == dtype and dx.dtype == dtype
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
    np.testing.assert_array_equal(
        np.asarray(dx, np.float32), np.asarray(dx_ref, np.float32)
    )


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
@pytest.mark.parametrize(
    "hw", [(84, 84), (21, 21), (18, 24), (7, 5)],
    ids=lambda hw: f"{hw[0]}x{hw[1]}",
)
def test_routing_with_neg_inf_inputs(hw, dtype):
    x, g = _inputs(*hw, dtype, "neg_inf")
    y, dx = _vjp(kernel_pool, x, g)
    y_ref, dx_ref = _vjp(_xla_pool, x, g)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
    np.testing.assert_array_equal(
        np.asarray(dx, np.float32), np.asarray(dx_ref, np.float32)
    )


@pytest.mark.parametrize("kind", ["ties", "neg_inf"])
@pytest.mark.parametrize(
    "hw", [(42, 42), (21, 21), (7, 5)], ids=lambda hw: f"{hw[0]}x{hw[1]}"
)
def test_parity_plane_reference_agrees(hw, kind):
    """The kernel-free formulation is a second witness: it agrees with
    XLA's pool and with the kernels."""
    x, g = _inputs(*hw, jnp.float32, kind)
    y_ref, dx_ref = parity_plane_pool_grad(x, g)
    for pool in (kernel_pool, _xla_pool):
        y, dx = _vjp(pool, x, g)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
        np.testing.assert_array_equal(np.asarray(dx), np.asarray(dx_ref))


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_overlap_sums_are_float32_rounded_once(dtype):
    """Real-valued cotangents: float32 agrees with `jax.grad` to 1e-6;
    bfloat16 is within one ulp of the float32 sum rounded once (XLA's
    own bfloat16 `select-and-scatter` adds in bfloat16)."""
    rng = np.random.default_rng(7)
    x, _ = _inputs(42, 42, dtype, "ties")
    g = jnp.asarray(rng.normal(size=(3, 21, 21, 8)), dtype)
    _, dx = _vjp(kernel_pool, x, g)
    _, exact = _vjp(
        _xla_pool, x.astype(jnp.float32), g.astype(jnp.float32)
    )
    dx, exact = np.asarray(dx, np.float32), np.asarray(exact)
    if dtype == jnp.float32:
        np.testing.assert_allclose(dx, exact, atol=1e-6, rtol=1e-6)
    else:
        assert np.all(np.abs(dx - exact) <= 2.0**-7 * np.abs(exact))


def test_batch_not_a_multiple_of_the_lane_chunk():
    """N = 131 leaves a last block of three lanes."""
    x, g = _inputs(8, 6, jnp.float32, "ties", n=131, c=4)
    y, dx = _vjp(kernel_pool, x, g)
    y_ref, dx_ref = _vjp(_xla_pool, x, g)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
    np.testing.assert_array_equal(np.asarray(dx), np.asarray(dx_ref))


@pytest.mark.parametrize("budget,rows", [(1, 1), (2100, 2)])
def test_several_row_blocks(monkeypatch, budget, rows):
    """A small block budget cuts the rows into blocks, so halo rows and
    their masks at the image's edge are exercised (even and odd H)."""
    monkeypatch.setattr(maxpool_pallas, "_BLOCK_BYTES", budget)
    # The kernels' wrappers are jitted: a trace under another budget at
    # the same shapes must not be found again.
    maxpool_pallas._pool_forward.clear_cache()
    maxpool_pallas._pool_backward.clear_cache()
    assert maxpool_pallas._blocks(12, 8, 4, 3, 4) == (rows, 3)
    for hw in [(12, 8), (9, 7)]:
        for kind in ("ties", "neg_inf"):
            x, g = _inputs(*hw, jnp.float32, kind, c=4)
            y, dx = _vjp(kernel_pool, x, g)
            y_ref, dx_ref = _vjp(_xla_pool, x, g)
            np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
            np.testing.assert_array_equal(np.asarray(dx), np.asarray(dx_ref))


def test_leading_time_and_batch_axes():
    x, g = _inputs(16, 16, jnp.float32, "ties", n=6)
    x5, g5 = x.reshape(2, 3, *x.shape[1:]), g.reshape(2, 3, *g.shape[1:])
    y, dx = _vjp(kernel_pool, x5, g5)
    y_ref, dx_ref = _vjp(_xla_pool, x, g)
    assert y.shape == (2, 3, 8, 8, 8) and dx.shape == x5.shape
    np.testing.assert_array_equal(np.asarray(y).reshape(y_ref.shape), y_ref)
    np.testing.assert_array_equal(
        np.asarray(dx).reshape(dx_ref.shape), dx_ref
    )


def test_under_checkpoint_and_jit():
    x, g = _inputs(16, 16, jnp.bfloat16, "ties")

    def loss(pool, x):
        return jnp.sum(pool(x).astype(jnp.float32) * g.astype(jnp.float32))

    want = jax.grad(lambda x: loss(_xla_pool, x))(x)
    got = jax.jit(jax.grad(lambda x: loss(jax.checkpoint(kernel_pool), x)))(x)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )


def test_under_vmap():
    x, g = _inputs(8, 8, jnp.float32, "ties", n=4)
    x5, g5 = x.reshape(2, 2, *x.shape[1:]), g.reshape(2, 2, *g.shape[1:])

    def one(x, g):
        return _vjp(kernel_pool, x, g)

    y, dx = jax.vmap(one)(x5, g5)
    y_ref, dx_ref = _vjp(_xla_pool, x, g)
    np.testing.assert_array_equal(np.asarray(y).reshape(y_ref.shape), y_ref)
    np.testing.assert_array_equal(
        np.asarray(dx).reshape(dx_ref.shape), dx_ref
    )


def test_not_differentiated_it_is_xlas_pool():
    """Actor inference, serving and rollouts compile the program they
    compiled before: no kernel, no index, on any platform."""
    x, _ = _inputs(16, 16, jnp.bfloat16, "ties")
    for platform in ("cpu", "tpu"):
        for pool in (max_pool, kernel_pool):
            text = (
                jax.jit(pool)
                .trace(x)
                .lower(lowering_platforms=(platform,))
                .as_text()
            )
            assert "reduce_window" in text
            assert "while" not in text and "custom_call" not in text
    for pool in (max_pool, kernel_pool, lambda x: max_pool(x, kernel=False)):
        np.testing.assert_array_equal(
            np.asarray(pool(x)), np.asarray(_xla_pool(x))
        )


def _lowered_grad(pool, x, platform):
    def loss(x):
        return jnp.sum(pool(x).astype(jnp.float32))

    return (
        jax.jit(jax.grad(loss))
        .trace(x)
        .lower(lowering_platforms=(platform,))
        .as_text()
    )


def test_max_pool_takes_the_kernels_only_when_lowered_for_a_tpu():
    """`max_pool` under differentiation: the two Mosaic kernels and no
    `select_and_scatter` in a program lowered for a TPU; XLA's pool, as
    before, in one lowered for the CPU (where it also runs) and with
    `kernel=False` (the mesh path)."""
    x, g = _inputs(16, 16, jnp.bfloat16, "ties")
    tpu = _lowered_grad(max_pool, x, "tpu")
    assert tpu.count("tpu_custom_call") == 2
    assert "max_pool_forward" in tpu and "max_pool_backward" in tpu
    assert "select_and_scatter" not in tpu
    for text in (
        _lowered_grad(max_pool, x, "cpu"),
        _lowered_grad(lambda x: max_pool(x, kernel=False), x, "tpu"),
    ):
        assert "select_and_scatter" in text
        assert "tpu_custom_call" not in text and "while" not in text
    np.testing.assert_array_equal(
        np.asarray(_vjp(max_pool, x, g)[1], np.float32),
        np.asarray(_vjp(_xla_pool, x, g)[1], np.float32),
    )


def test_the_only_residual_is_a_uint8_index():
    x, _ = _inputs(16, 16, jnp.bfloat16, "ties")
    _, pull = jax.vjp(kernel_pool, x)
    residuals = jax.tree.leaves(pull)
    assert [(r.dtype, r.shape) for r in residuals] == [
        (jnp.uint8, (8, 8, 8, 3))
    ]
    assert int(jnp.max(residuals[0])) <= 8


def test_deep_torso_has_one_param_tree_and_six_kernels_on_a_tpu():
    """`pool_kernel` changes no parameter; the torso's gradient lowered
    for a TPU holds a forward and a backward kernel per section."""
    x = jnp.zeros((2, 21, 21, 4), jnp.uint8)
    kernel_torso = AtariDeepTorso(dtype=jnp.bfloat16)
    xla_torso = AtariDeepTorso(dtype=jnp.bfloat16, pool_kernel=False)
    params = jax.eval_shape(kernel_torso.init, jax.random.key(0), x)
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(xla_torso.init, jax.random.key(0), x)
    )

    def lowered(torso):
        def loss(p):
            return jnp.sum(torso.apply(p, x).astype(jnp.float32))

        return (
            jax.jit(jax.grad(loss))
            .trace(params)
            .lower(lowering_platforms=("tpu",))
            .as_text()
        )

    text = lowered(kernel_torso)
    assert text.count("tpu_custom_call") == 6
    assert "select_and_scatter" not in text
    text = lowered(xla_torso)
    assert text.count("select_and_scatter") == 3
    assert "tpu_custom_call" not in text


def _tiny_breakout_learner(mesh=None):
    from torched_impala_tpu import configs
    from torched_impala_tpu.runtime.learner import Learner

    cfg = dataclasses.replace(
        configs.REGISTRY["breakout"], batch_size=2, unroll_length=2
    )
    return cfg, Learner(
        agent=configs.make_agent(cfg),
        optimizer=configs.make_optimizer(cfg),
        config=configs.make_learner_config(cfg),
        example_obs=configs.example_obs(cfg),
        rng=jax.random.key(0),
        mesh=mesh,
    )


def _traced_step(cfg, learner):
    T, B = cfg.unroll_length, cfg.batch_size
    state = learner._agent.initial_state(B)
    batch = (
        jnp.zeros((T + 1, B) + tuple(cfg.obs_shape), cfg.obs_dtype),
        jnp.zeros((T + 1, B), bool),
        jnp.zeros((T, B), jnp.int32),
        jnp.zeros((T, B, cfg.num_actions), jnp.float32),
        jnp.zeros((T, B), jnp.float32),
        jnp.ones((T, B), jnp.float32),
        jnp.zeros((B,), jnp.int32),
        state,
    )
    return learner._train_step.trace(
        learner._params, learner._opt_state, learner._popart_state, *batch
    )


def test_learner_step_has_no_select_and_scatter_and_mesh_compiles():
    """The breakout preset's learner step at tiny size, lowered for a
    TPU: each pool's forward and backward are the kernels and no
    `select_and_scatter` is left. On a mesh of two (CPU) devices the
    same step still compiles."""
    from torched_impala_tpu.parallel.mesh import make_mesh

    cfg, learner = _tiny_breakout_learner()
    try:
        assert learner.kernels["max_pool"] == "kernel"
        # a step lowered for the CPU takes no packed weight gradient
        assert learner.kernels["packed_convs"] == []
        text = (
            _traced_step(cfg, learner)
            .lower(lowering_platforms=("tpu",))
            .as_text()
        )
        assert "select_and_scatter" not in text
        assert text.count("max_pool_forward") >= 3
        assert text.count("max_pool_backward") >= 3
    finally:
        learner.stop()
    cfg, learner = _tiny_breakout_learner(make_mesh(num_data=2))
    try:
        _traced_step(cfg, learner).lower().compile()
    finally:
        learner.stop()


def test_mesh_of_tpu_devices_takes_xlas_pool():
    """`resolve_kernels` decides by the mesh's devices; stand-ins with a
    `platform` are all it reads."""
    from torched_impala_tpu.models import Agent, ImpalaNet
    from torched_impala_tpu.ops.losses import ImpalaLossConfig
    from torched_impala_tpu.runtime.learner import resolve_kernels

    @dataclasses.dataclass(frozen=True)
    class FakeDevice:
        platform: str

    @dataclasses.dataclass(frozen=True)
    class FakeMesh:
        devices: np.ndarray

    agent = Agent(ImpalaNet(num_actions=4, torso=AtariDeepTorso()))
    mesh = FakeMesh(np.array([FakeDevice("tpu")] * 2, dtype=object))
    resolved_agent, _, resolved = resolve_kernels(
        agent, ImpalaLossConfig(), mesh
    )
    assert resolved["max_pool"] == "xla"
    assert resolved_agent.net.torso.pool_kernel is False
    one = FakeMesh(np.array([FakeDevice("tpu")], dtype=object))
    resolved_agent, _, resolved = resolve_kernels(
        agent, ImpalaLossConfig(), one
    )
    assert resolved["max_pool"] == "kernel"
    assert resolved_agent.net.torso.pool_kernel is True
