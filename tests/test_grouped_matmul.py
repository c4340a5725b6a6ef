"""The held experts' grouped products (ops/grouped_matmul.py) on the CPU:
the grouped layout and its capacity, and the way in (`dispatch`), XLA's
ragged product and the way out (`combine`) against a plain per-row
product, forward and the gradients of the tokens, the experts and the
slots' weights, under balanced, one-sided and empty routing, with float32
and bfloat16 operands; and the gathers' own transposes against autodiff's
scatters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torched_impala_tpu.ops.grouped_matmul import (
    capacity,
    combine,
    dispatch,
    group_rows,
    grouped_matmul,
    row_slots,
)

N, K, HELD, E, Q, P = 200, 4, 3, 8, 64, 48
ROUTINGS = ["balanced", "all_held_first", "none_held"]


def _routing(kind, rng):
    """Each token's k distinct experts of E; `held` the first HELD."""
    if kind == "balanced":
        sel = np.stack([rng.permutation(E)[:K] for _ in range(N)])
    elif kind == "all_held_first":  # every token picks expert 0 and 3 unheld
        sel = np.stack([[0, 3, 4, 5]] * N)
    else:  # "none_held"
        sel = np.stack([rng.permutation(np.arange(HELD, E))[:K] for _ in range(N)])
    return np.where(sel < HELD, sel, HELD)


@pytest.mark.parametrize("routing", ROUTINGS)
def test_the_layout_groups_rows_by_expert_in_the_tokens_order(routing):
    local = _routing(routing, np.random.default_rng(0))
    pos, rows = group_rows(jnp.asarray(local), HELD)
    pos, rows = np.asarray(pos), np.asarray(rows)
    m = capacity(N, K, HELD)
    assert rows.tolist() == [int((local == g).sum()) for g in range(HELD)]
    held = local < HELD
    assert (pos[~held] == m).all()
    taken = pos[held]
    assert len(set(taken.tolist())) == taken.size  # one row each
    starts = np.concatenate([[0], np.cumsum(rows)[:-1]])
    for g in range(HELD):
        mine = pos[local == g]  # in the tokens' order, row-major
        # a group's rows are consecutive from its start, in the tokens' order
        assert mine.tolist() == list(range(starts[g], starts[g] + rows[g]))


@pytest.mark.parametrize("k,held", [(4, 8), (4, 3), (4, 1)])
def test_the_capacity_is_the_most_rows_the_routing_can_send(k, held):
    """Every token's picks on held experts as far as they go: the groups
    fill the buffer to its last row, and no pick falls outside it."""
    n = 50
    local = np.stack([np.arange(k)] * n)
    local = np.where(local < held, local, held)
    pos, rows = group_rows(jnp.asarray(local), held)
    m = capacity(n, k, held)
    assert int(np.asarray(rows).sum()) == m == n * min(k, held)
    assert sorted(np.asarray(pos)[local < held].tolist()) == list(range(m))


def _product(x, w, weight, local):
    pos, rows = group_rows(jnp.asarray(local), HELD)
    slot, filled = row_slots(pos, capacity(N, K, HELD))
    y = grouped_matmul(dispatch(x, pos, slot, w.dtype), w, rows)
    return combine(y, weight, pos, slot, filled)


def _plain(x, w, weight, local):
    out = 0.0
    for j in range(K):
        e = local[:, j]
        y = jnp.einsum(
            "nq,nqp->np", x, w[np.minimum(e, HELD - 1)],
            preferred_element_type=jnp.float32,
        )
        out = out + jnp.where((e < HELD)[:, None], y * weight[:, j, None], 0.0)
    return out


# Against the plain product in float32 of the same operands. float32: the
# order of the sums alone; bfloat16 operands: exact products summed in
# float32, the gradients rounded once to bfloat16 (2**-8 of an element).
TOLERANCES = {"float32": (1e-4, 1e-5), "bfloat16": (1e-4, 1e-2)}


@pytest.mark.parametrize("dtype", sorted(TOLERANCES))
@pytest.mark.parametrize("routing", ROUTINGS)
def test_a_product_and_its_gradients_match_the_plain_one(routing, dtype):
    """The tokens in float32 (the layer's normed inputs), the experts'
    matrices in `dtype`, each slot's weight float32 and zero where its
    expert is not held (as the layer gives it)."""
    rng = np.random.default_rng(1)
    local = _routing(routing, rng)
    x = jnp.asarray(rng.standard_normal((N, Q)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((HELD, Q, P)), dtype)
    weight = jnp.asarray(rng.random((N, K)) * (local < HELD), jnp.float32)
    out_tol, grad_tol = TOLERANCES[dtype]

    def loss(f, w32=False):
        return lambda x, w, a: jnp.sum(
            jnp.square(f(x, w.astype(jnp.float32) if w32 else w, a, local))
        )

    # the plain product's operands rounded to `dtype` as the grouped one's
    # are: only the order of the sums and the gradients' dtype differ
    xr = x.astype(dtype).astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = _product(x, w, weight, local)
        want = _plain(xr, w.astype(jnp.float32), weight, local)
        assert got.dtype == jnp.float32
        scale = float(jnp.max(jnp.abs(want))) + 1e-6
        np.testing.assert_allclose(got / scale, want / scale, rtol=0, atol=out_tol)
        grads = jax.grad(loss(_product), (0, 1, 2))(x, w, weight)
        wants = jax.grad(loss(_plain, True), (0, 1, 2))(xr, w, weight)
    for g, r in zip(grads, wants):
        g, r = np.asarray(g, np.float32), np.asarray(r, np.float32)
        scale = float(np.max(np.abs(r))) + 1e-6
        np.testing.assert_allclose(g / scale, r / scale, rtol=0, atol=grad_tol)
        assert np.all(np.isfinite(g))
    assert not np.any(np.asarray(grads[2])[local == HELD])  # slots not held
    if routing != "balanced":  # experts no row reached get no gradient
        empty = [g for g in range(HELD) if not (local == g).any()]
        assert empty and not np.any(np.asarray(grads[1], np.float32)[empty])


def _by_autodiff(x, y, weight, local):
    """The same gathers in and out, transposed by autodiff (scatters); the
    way in masks the rows past the groups, whose cotangent is undefined."""
    pos, _ = group_rows(jnp.asarray(local), HELD)
    m = capacity(N, K, HELD)
    slot, filled = row_slots(pos, m)
    buf = jnp.where(filled[:, None], x[slot // K], 0.0)
    picked = jnp.where((pos < m)[..., None], y[jnp.minimum(pos, m - 1)], 0.0)
    return buf, jnp.sum(picked * weight[..., None], axis=1)


@pytest.mark.parametrize("routing", ROUTINGS)
def test_the_gathers_transposes_are_autodiffs(routing):
    """`dispatch` and `combine` carry their own transposes (gathers by the
    layout's map and its inverse); they give what autodiff's scatters give,
    to float32's rounding of a token's sum over its k rows. A row past the
    groups holding anything, infinity even, changes no gradient."""
    rng = np.random.default_rng(2)
    local = _routing(routing, rng)
    m = capacity(N, K, HELD)
    x = jnp.asarray(rng.standard_normal((N, Q)), jnp.float32)
    y = rng.standard_normal((m, P)).astype(np.float32)
    pos, rows = group_rows(jnp.asarray(local), HELD)
    y[int(jnp.sum(rows)) :] = np.inf
    y = jnp.asarray(y)
    weight = jnp.asarray(rng.random((N, K)) * (local < HELD), jnp.float32)
    slot, filled = row_slots(pos, m)
    d_buf = rng.standard_normal((m, Q)).astype(np.float32)
    d_buf[int(jnp.sum(rows)) :] = np.inf
    d_buf = jnp.asarray(d_buf)
    d_out = jnp.asarray(rng.standard_normal((N, P)), jnp.float32)

    def ours(x, y, weight):
        return dispatch(x, pos, slot, jnp.float32), combine(
            y, weight, pos, slot, filled
        )

    def theirs(x, y, weight):
        return _by_autodiff(x, jnp.where(filled[:, None], y, 0.0), weight, local)

    (got_buf, got_out), vjp = jax.vjp(ours, x, y, weight)
    (want_buf, want_out), want_vjp = jax.vjp(theirs, x, y, weight)
    held_rows = int(jnp.sum(rows))
    np.testing.assert_array_equal(got_buf[:held_rows], want_buf[:held_rows])
    np.testing.assert_array_equal(got_out, want_out)
    for g, r in zip(vjp((d_buf, d_out)), want_vjp((d_buf, d_out))):
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(g, r, rtol=1e-6, atol=1e-6)
