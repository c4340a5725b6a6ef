"""Grouped matrix products of a mixture-of-experts layer's held experts.

The rows routed to the experts a layer holds are laid out grouped by
expert, one group after another in the tokens' order (`group_rows`), and
each group is multiplied by its own expert's matrix with XLA's ragged
product, `jax.lax.ragged_dot`:

    out[r] = lhs[r] @ rhs[g(r)]

No token is dropped at any load (dropless): the buffer is sized for the
most rows the routing can send here, `N * min(k, G)` (`capacity`), while
the product works through the rows that were sent. Rows past the groups
belong to no token and hold no defined value on a TPU; no caller may read
them. On a TPU the ragged product and both its gradients are Mosaic
kernels of XLA's own (`%ragged-dot-*` custom calls on the `XLA Ops` line);
on the CPU and under a mesh it is XLA's plain lowering.

Operands in the matrix products' precision (bfloat16 on the learner's
path), accumulation and the output in float32.

The rows go into the buffer (`dispatch`) and come back out to their tokens
(`combine`) by gathers, and each one's transpose is a gather too: the
grouped layout is a one-to-one map between the held (token, slot) pairs
and the buffer's first rows, so either side can read the other by the map
or by its inverse (`row_slots`). Autodiff's transpose of a gather is a
scatter-add: on a TPU v5e that cost a layer's backward pass about 2 ms on
the combine's `[16384, 2048]` float32 rows alone (B=2 x 2,048 positions,
4 of 32 experts a position, 8 held).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def capacity(num_tokens: int, k: int, held: int) -> int:
    """Rows of the grouped buffer: every held row the routing can send
    (each token picks k distinct experts)."""
    return num_tokens * min(k, held)


def group_rows(local: jax.Array, held: int):
    """`local` `[N, k]`: each (token, slot)'s held expert in [0, held), or
    `held` where the slot's expert is not held here. Returns (the row of
    each (token, slot) in the grouped buffer, `capacity` where not held;
    the rows routed to each held expert `[held]` int32). A group's rows
    keep the tokens' order."""
    n, k = local.shape
    flat = local.reshape(-1)
    onehot = (flat[:, None] == jnp.arange(held)[None, :]).astype(jnp.int32)
    rows = jnp.sum(onehot, axis=0)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    start = (jnp.cumsum(rows) - rows)[jnp.minimum(flat, held - 1)]
    pos = jnp.where(flat < held, start + rank, capacity(n, k, held))
    return pos.reshape(n, k).astype(jnp.int32), rows.astype(jnp.int32)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, rows: jax.Array) -> jax.Array:
    """`lhs` `[M, Q]` in the grouped layout of `group_rows`, `rhs`
    `[G, Q, P]`, `rows` `[G]`: `[M, P]` float32, the rows of each group
    times its expert's matrix."""
    return jax.lax.ragged_dot(lhs, rhs, rows, preferred_element_type=F32)


def row_slots(pos: jax.Array, m: int):
    """The inverse of `group_rows`' map: for each of the `m` rows, the flat
    (token, slot) index `n * k + j` that fills it `[m]` int32 (0 where
    none does) and whether one does `[m]` bool."""
    flat = pos.reshape(-1)
    slot = jnp.zeros((m,), jnp.int32).at[flat].set(
        jnp.arange(flat.size, dtype=jnp.int32), mode="drop"
    )
    filled = jnp.zeros((m,), bool).at[flat].set(True, mode="drop")
    return slot, filled


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def dispatch(x, pos, slot, dtype):
    """The grouped buffer `[m, D]` in `dtype` of the tokens `x` `[N, D]`
    float32: row r holds token `slot[r] // k`'s row. A row past the groups
    holds token 0's, which neither the products nor their gradients read.
    Its transpose reads each token's held rows back by `pos` and sums them
    in float32."""
    return x.astype(dtype)[slot // pos.shape[1]]


def _dispatch_fwd(x, pos, slot, dtype):
    return dispatch(x, pos, slot, dtype), pos


def _dispatch_bwd(dtype, pos, g):
    m = g.shape[0]
    rows = jnp.where(
        (pos < m)[..., None], g[jnp.minimum(pos, m - 1)].astype(F32), 0.0
    )
    return jnp.sum(rows, axis=1), None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(y, weight, pos, slot, filled):
    """Each token's held rows of the products `y` `[m, D]` float32, times
    their slots' `weight` `[N, k]`, summed: `[N, D]`. A row past the groups
    is never read. Its transpose gives each filled row its token's
    cotangent times its slot's weight, by `slot`."""
    m = y.shape[0]
    picked = jnp.where((pos < m)[..., None], y[jnp.minimum(pos, m - 1)], 0.0)
    return jnp.sum(picked * weight[..., None], axis=1)


def _combine_fwd(y, weight, pos, slot, filled):
    return combine(y, weight, pos, slot, filled), (y, weight, pos, slot, filled)


def _combine_bwd(res, g):
    y, weight, pos, slot, filled = res
    m, k = y.shape[0], pos.shape[1]
    g_rows = g[slot // k]  # each row's token's cotangent
    d_y = jnp.where(
        filled[:, None], g_rows * weight.reshape(-1)[slot][:, None], 0.0
    )
    # a row past the groups may hold anything, infinity even: `where` drops it
    d_row = jnp.where(filled, jnp.sum(g_rows * y, axis=1), 0.0)
    d_weight = jnp.where(pos < m, d_row[jnp.minimum(pos, m - 1)], 0.0)
    return d_y, d_weight, None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)
