"""The selective-scan kernels (ops/selective_scan.py), interpreted on the
CPU, against the scan written one step at a time: forward, every
gradient, chunks that do and do not divide T, resets on and off chunk
boundaries. float32 throughout."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torched_impala_tpu.ops.selective_scan import (
    selective_scan,
    selective_scan_xla,
)

B, T, DI, N = 2, 11, 128, 16


def _inputs(seed, t=T, di=DI, resets=()):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    first = np.zeros((B, t), bool)
    for row, step in resets:
        first[row, step] = True
    return dict(
        x=f(B, t, di),
        dt=jax.nn.softplus(f(B, t, di)),
        a=-jnp.exp(0.5 * f(di, N)),
        b=f(B, t, N),
        c=f(B, t, N),
        first=jnp.asarray(first),
        s0=f(B, di, N),
    )


def _step_by_step(x, dt, a, b, c, first, s0):
    """The recurrence as the docstring writes it, a Python loop."""
    s, ys = s0, []
    for t in range(x.shape[1]):
        s = jnp.where(first[:, t, None, None], 0.0, s)
        s = jnp.exp(dt[:, t, :, None] * a) * s + (
            dt[:, t] * x[:, t]
        )[:, :, None] * b[:, t, None, :]
        ys.append(jnp.einsum("bdn,bn->bd", s, c[:, t]))
    return jnp.stack(ys, 1), s


CASES = {
    "no_reset_chunk_divides": dict(t=12, chunk=4, resets=()),
    "no_reset_chunk_does_not_divide": dict(t=11, chunk=4, resets=()),
    "one_chunk": dict(t=11, chunk=64, resets=((0, 5),)),
    "reset_first_middle_last": dict(
        t=11, chunk=4, resets=((0, 0), (0, 5), (1, 10))
    ),
    "reset_on_a_chunk_boundary": dict(t=11, chunk=4, resets=((0, 4), (1, 8))),
    "reset_before_a_chunk_boundary": dict(t=11, chunk=4, resets=((1, 3),)),
    "every_step_resets": dict(
        t=8, chunk=4, resets=tuple((0, i) for i in range(8))
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_forward_matches_the_step_by_step_scan(case):
    spec = CASES[case]
    args = _inputs(1, t=spec["t"], resets=spec["resets"])
    want_y, want_s = _step_by_step(**args)
    y, s = selective_scan(**args, chunk=spec["chunk"], interpret=True)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=1e-5)
    y, s = selective_scan_xla(**args)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", sorted(CASES))
def test_backward_matches_autodiff_of_the_step_by_step_scan(case):
    spec = CASES[case]
    args = _inputs(2, t=spec["t"], resets=spec["resets"])
    first = args.pop("first")
    rng = np.random.default_rng(3)
    wy = jnp.asarray(rng.standard_normal((B, spec["t"], DI)), jnp.float32)
    ws = jnp.asarray(rng.standard_normal((B, DI, N)), jnp.float32)

    def loss(scan, kw):
        y, s = scan(first=first, **kw)
        return jnp.sum(y * wy) + jnp.sum(s * ws)

    want = jax.grad(lambda kw: loss(_step_by_step, kw))(args)
    got = jax.grad(
        lambda kw: loss(
            lambda **k: selective_scan(
                **k, chunk=spec["chunk"], interpret=True
            ),
            kw,
        )
    )(args)
    for k in want:
        scale = float(jnp.max(jnp.abs(want[k]))) + 1e-6
        np.testing.assert_allclose(
            got[k] / scale, want[k] / scale, rtol=0, atol=2e-5, err_msg=k
        )


def test_two_channel_blocks_accumulate_the_shared_gradients():
    """B's and C's gradients add up over the channel blocks."""
    args = _inputs(4, di=256, resets=((0, 3),))
    first = args.pop("first")

    def loss(scan, kw):
        y, s = scan(first=first, **kw)
        return jnp.sum(jnp.sin(y)) + jnp.sum(s)

    want = jax.grad(lambda kw: loss(selective_scan_xla, kw))(args)
    got = jax.grad(
        lambda kw: loss(
            lambda **k: selective_scan(
                **k, chunk=4, block_d=128, interpret=True
            ),
            kw,
        )
    )(args)
    for k in ("b", "c", "a"):
        np.testing.assert_allclose(got[k], want[k], rtol=2e-4, atol=2e-4)


def test_off_a_tpu_the_op_is_the_plain_scan():
    """Lowered for the CPU, `selective_scan` holds no Pallas call."""
    args = _inputs(5)
    text = jax.jit(selective_scan).lower(**args).as_text()
    assert "selective_scan_forward" not in text
    y, _ = jax.jit(selective_scan)(**args)
    np.testing.assert_allclose(
        y, selective_scan_xla(**args)[0], rtol=1e-6, atol=1e-6
    )


def test_channels_must_divide_into_blocks():
    args = _inputs(6, di=192)
    with pytest.raises(ValueError, match="do not divide"):
        selective_scan(**args, block_d=128, interpret=True)
