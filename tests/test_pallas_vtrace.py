"""Pallas V-trace kernel parity vs the scan implementation (interpret mode on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from torched_impala_tpu.ops import vtrace as vtrace_lib
from torched_impala_tpu.ops import vtrace_pallas as vp


def _inputs(rng, T, B):
    return dict(
        log_rhos=jnp.asarray(rng.normal(size=(T, B)) * 0.4, dtype=jnp.float32),
        discounts=jnp.asarray(
            0.99 * (rng.uniform(size=(T, B)) > 0.15), dtype=jnp.float32
        ),
        rewards=jnp.asarray(rng.normal(size=(T, B)), dtype=jnp.float32),
        values=jnp.asarray(rng.normal(size=(T, B)), dtype=jnp.float32),
        bootstrap_value=jnp.asarray(rng.normal(size=(B,)), dtype=jnp.float32),
    )


@pytest.mark.parametrize("T,B", [(1, 1), (7, 3), (20, 32), (20, 128), (9, 130)])
def test_pallas_matches_scan(T, B):
    rng = np.random.default_rng(seed=T * 1000 + B)
    kwargs = _inputs(rng, T, B)
    ref = vtrace_lib.vtrace_scan(**kwargs)
    out = vp.vtrace_pallas(**kwargs)
    np.testing.assert_allclose(out.vs, ref.vs, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        out.pg_advantages, ref.pg_advantages, rtol=1e-5, atol=1e-5
    )
    np.testing.assert_allclose(out.errors, ref.errors, rtol=1e-5, atol=1e-5)


def test_pallas_clip_thresholds():
    rng = np.random.default_rng(seed=5)
    kwargs = _inputs(rng, 11, 17)
    common = dict(
        clip_rho_threshold=0.7, clip_c_threshold=0.9, clip_pg_rho_threshold=2.0,
        lambda_=0.9,
    )
    ref = vtrace_lib.vtrace_scan(**kwargs, **common)
    out = vp.vtrace_pallas(**kwargs, **common)
    np.testing.assert_allclose(out.vs, ref.vs, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        out.pg_advantages, ref.pg_advantages, rtol=1e-5, atol=1e-5
    )


def test_pallas_backend_under_grad():
    """Regression: jax.grad through impala_loss(pallas) must not trace a JVP
    through pallas_call (inputs are stop-gradiented inside the wrapper)."""
    import jax

    from torched_impala_tpu.ops import losses as losses_lib

    T, B, A = 4, 3, 2
    cfg = losses_lib.ImpalaLossConfig(vtrace_implementation="pallas")

    def f(logits, values):
        return losses_lib.impala_loss(
            target_logits=logits,
            behaviour_logits=jnp.zeros((T, B, A)),
            values=values,
            bootstrap_value=jnp.zeros((B,)),
            actions=jnp.zeros((T, B), dtype=jnp.int32),
            rewards=jnp.ones((T, B)),
            discounts=jnp.full((T, B), 0.9),
            config=cfg,
        ).total

    gl, gv = jax.grad(f, argnums=(0, 1))(jnp.zeros((T, B, A)), jnp.zeros((T, B)))
    assert np.abs(np.asarray(gl)).sum() > 0
    assert np.abs(np.asarray(gv)).sum() > 0


@pytest.mark.tpu
def test_pallas_compiled_on_tpu_matches_scan():
    """Compiled (Mosaic, interpret=False) kernel parity on a real chip.

    The test-suite conftest forces the CPU backend, so under `pytest tests/`
    this always skips; it runs when invoked with a TPU backend — e.g.
    `python -m pytest tests/test_pallas_vtrace.py -k compiled -p no:cacheprovider`
    with a tpu-forcing conftest override (`chip_smoke.py`'s kernels
    phase holds the same parity on the chip).
    """
    import jax

    if jax.default_backend() != "tpu":
        pytest.skip("requires a TPU backend (conftest pins tests to CPU)")
    rng = np.random.default_rng(seed=11)
    for T, B in ((20, 256), (100, 32)):
        kwargs = _inputs(rng, T, B)
        ref = vtrace_lib.vtrace_scan(**kwargs)
        out = vp.vtrace_pallas(**kwargs, interpret=False)
        np.testing.assert_allclose(out.vs, ref.vs, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            out.pg_advantages, ref.pg_advantages, rtol=1e-5, atol=1e-5
        )


def test_dispatch_via_vtrace_api():
    rng = np.random.default_rng(seed=6)
    kwargs = _inputs(rng, 5, 4)
    ref = vtrace_lib.vtrace(**kwargs, implementation="scan")
    out = vtrace_lib.vtrace(**kwargs, implementation="pallas")
    np.testing.assert_allclose(out.vs, ref.vs, rtol=1e-5, atol=1e-5)
