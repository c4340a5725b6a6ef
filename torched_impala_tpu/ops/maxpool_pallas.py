"""The deep torso's 3x3 / stride-2 / SAME max-pool with its own backward
(ISSUE 29): the forward saves each window's winner as one byte, the
backward routes the gradient from that byte.

Autodiff of `nn.max_pool` leaves XLA a `select-and-scatter`, which
finds every window's winner again from the pool's whole *input*: the
full-resolution convolution output is kept from forward to backward for
that one reader, and the op reads it, reads the gradient and writes the
input gradient (2.73 GB for the breakout cell's first pool, the largest
single op of the step). Here the forward kernel reads the convolution
output once and writes the pooled values plus a `uint8` index 0..8 of
the winner inside its window; the backward kernel reads gradient and
index and writes the input gradient once. The convolution output is no
residual any more.

Same pool: the pooled values are a max, so exact; the winner is the
FIRST maximal element in row-major window order (dy, then dx) and a
SAME-padding position never wins — `select-and-scatter`'s `ge` rule —
so every gradient element lands where it lands today. Where windows
overlap (up to four contributions per input position) the sum is taken
in float32 and rounded once.

Layout: XLA's TPU layout for the torso's NHWC activations is batch on
the lanes, channels on the sublanes, H and W major — physically the
row-major order of a logical `[H, W, C, N]` array, so the transposes
around the kernels are bitcasts (tests/test_tpu_compile.py holds the
compiled module to that). In that view a kernel block is
`[rows, W, C, lanes]`: every window offset is an index on an untiled
leading dimension and the unit of work is one `(C, lanes)` tile. The
grid runs over lane chunks and blocks of output rows; the one extra row
a block's windows reach into (stride 2, window 3) arrives as a second,
one-row block of the same array.

`max_pool` takes the kernels only in a program lowered for a TPU; for
any other platform it is XLA's pool under autodiff, as before. The
kernels themselves follow the package's rule (ops/pallas_util.py:
compiled for a TPU, the interpreted body elsewhere), so tier-1 exercises
the exact kernel bodies by calling `pool_with_index` directly. Under a
mesh of several devices the torso takes XLA's pool (`kernel=False`,
runtime/learner.resolve_kernels), as the other Mosaic kernels do.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from torched_impala_tpu.ops.pallas_util import pallas_call

_LANES = 128
# Bytes of one grid step's blocks. The pipeline double-buffers each, so
# VMEM holds twice this plus the halo rows, inside the 16 MiB of scoped
# VMEM a kernel gets without asking. Larger blocks are a little faster
# (the halo row is a smaller share of what a step reads: 20 MiB blocks
# took 0.2 ms off a 56 ms step) but need a raised limit, which comes out
# of what XLA keeps resident in VMEM for the rest of the step (my chip
# runs and sandbox compiles, PR 29).
_BLOCK_BYTES = 7 << 20
# An index no window position has: masks a halo row outside the array.
_NO_WINNER = 255


def _out(size: int) -> int:
    """Pooled extent of an input extent (stride 2, SAME)."""
    return -(-size // 2)


def _sources(parity: int, lo: int):
    """Which windows reach input position `2*o + parity` along one
    axis: `(d, k)` pairs meaning window `o + d` at window offset `k`
    (`2*(o+d) + k - lo == 2*o + parity`)."""
    return [
        ((parity + lo - k) // 2, k)
        for k in range(3)
        if (parity + lo - k) % 2 == 0
    ]


def _edges_and_interior(body, count: int, first: bool, last: bool):
    """Run `body(i)` for `i` in `range(count)`. An edge whose windows
    reach outside the block or the image (`first`, `last`) gets a Python
    int, so that what lies outside drops statically or comes from the
    halo row; everything else runs in one loop."""
    start, stop = int(first), count - int(last)
    if first:
        body(0)
    if stop > start:
        jax.lax.fori_loop(start, stop, lambda i, c: (body(i), c)[1], 0)
    if last and not (first and count == 1):
        body(count - 1)


def _f32(x):
    return jax.lax.convert_element_type(x, jnp.float32)


def _forward_kernel(x_ref, halo_ref, y_ref, idx_ref, *, h: int, w: int):
    """Pooled values and winner index for one block of output rows.

    `x_ref` `[2*rows, W, C, n]` holds input rows `2*top ..`; `halo_ref`
    `[W, C, n]` the one further row the windows reach: the row above
    when H is odd (SAME pads one row on top), the row below otherwise.

    (Here and below `lax` primitives stand where `jnp` would read
    better: every `jnp` call is traced as a nested `jit`, and these
    bodies are traced for every pool of every trace of the step, which
    showed as seconds of set-up; my chip runs, PR 29.)
    """
    rows, wo = y_ref.shape[:2]
    lo_h, lo_w = h % 2, w % 2
    top = pl.program_id(1) * rows
    lax = jax.lax

    def output_row(r):
        # Per window row: (local input row, scalar "inside the image" or
        # None where that is certain). Only a block's first and last
        # output row reach the halo row or the padding.
        taps = []
        for dy in range(3):
            lr = 2 * r + dy - lo_h
            inside = None
            if isinstance(r, int) and (lr < 0 or lr >= 2 * rows - lo_h):
                row = 2 * top + lr
                inside = (row >= 0) & (row < h)
            taps.append((lr, inside))
        first_dy = 0 if taps[0][1] is None else lax.select(taps[0][1], 0, 1)

        def cell(j):
            dxs = [
                dx for dx in range(3)
                if not isinstance(j, int) or 0 <= 2 * j + dx - lo_w < w
            ]
            best = idx = None
            for dy, (lr, inside) in enumerate(taps):
                in_block = not isinstance(lr, int) or 0 <= lr < 2 * rows
                for dx in dxs:
                    col = 2 * j + dx - lo_w
                    v = _f32(x_ref[lr, col] if in_block else halo_ref[col])
                    if inside is not None:
                        v = lax.select(inside, v, lax.full_like(v, -jnp.inf))
                    if best is None:
                        # The first position inside the image keeps the
                        # window when nothing exceeds -inf.
                        best = lax.full_like(v, -jnp.inf)
                        idx = lax.full_like(
                            v, 3 * first_dy + dx, dtype=jnp.int32
                        )
                    better = lax.gt(v, best)
                    best = lax.select(better, v, best)
                    idx = lax.select(
                        better, lax.full_like(idx, 3 * dy + dx), idx
                    )
            y_ref[r, j] = lax.convert_element_type(best, y_ref.dtype)
            idx_ref[r, j] = lax.convert_element_type(idx, idx_ref.dtype)

        _edges_and_interior(cell, wo, first=lo_w == 1, last=True)

    # A window reaches one row or column past the last; with an odd
    # extent (SAME pads both sides) also one before the first.
    _edges_and_interior(output_row, rows, first=lo_h == 1, last=True)


def _backward_kernel(
    g_ref, g_halo_ref, idx_ref, idx_halo_ref, dx_ref, *, h: int, w: int
):
    """Input gradient rows `2*top .. 2*top + 2*rows` from the block's
    output rows of gradient and index plus one halo row: the row above
    when H is even (its windows' third row is this block's first), the
    row below when H is odd."""
    rows, wo = g_ref.shape[:2]
    ho = _out(h)
    lo_h, lo_w = h % 2, w % 2
    top = pl.program_id(1) * rows
    lax = jax.lax

    def output_row(r):
        def cell(j):
            static = isinstance(j, int)
            tiles = {}

            def tile(di, dj):
                if (di, dj) not in tiles:
                    lr, col = r + di, j + dj
                    if not isinstance(lr, int) or 0 <= lr < rows:
                        g = g_ref[lr, col]
                        ix = lax.convert_element_type(
                            idx_ref[lr, col], jnp.int32
                        )
                    else:
                        g = g_halo_ref[col]
                        ix = lax.convert_element_type(
                            idx_halo_ref[col], jnp.int32
                        )
                        row = top + lr
                        ix = lax.select(
                            (row >= 0) & (row < ho),
                            ix,
                            lax.full_like(ix, _NO_WINNER),
                        )
                    tiles[di, dj] = _f32(g), ix
                return tiles[di, dj]

            for p in range(2):
                for q in range(2):
                    if static and 2 * j + q >= w:
                        continue
                    acc = None
                    for di, dy in _sources(p, lo_h):
                        for dj, dx in _sources(q, lo_w):
                            if static and not 0 <= j + dj < wo:
                                continue
                            g, ix = tile(di, dj)
                            won = lax.eq(ix, lax.full_like(ix, 3 * dy + dx))
                            part = lax.select(won, g, lax.full_like(g, 0))
                            acc = part if acc is None else lax.add(acc, part)
                    dx_ref[2 * r + p, 2 * j + q] = lax.convert_element_type(
                        acc, dx_ref.dtype
                    )

        _edges_and_interior(cell, wo, first=lo_w == 0, last=lo_w == 1)

    # An even extent is reached by the window before (the halo row above
    # the first row, nothing left of the first column), an odd one by
    # the window after.
    _edges_and_interior(output_row, rows, first=lo_h == 0, last=lo_h == 1)


def _blocks(h: int, w: int, c: int, n: int, itemsize: int):
    """(output rows per block, lanes per block). The rows divide the
    output height, so only a halo row can lie outside the image."""
    ho, wo = _out(h), _out(w)
    lanes = min(n, _LANES)
    # Per output row: two full-resolution rows, one pooled row, one
    # index row (forward and backward move the same bytes).
    per_row = c * lanes * (2 * w * itemsize + wo * (itemsize + 1))
    rows = max(
        r for r in range(1, ho + 1)
        if ho % r == 0 and (r == 1 or r * per_row <= _BLOCK_BYTES)
    )
    return rows, lanes


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "parallel"))


def _cost(h: int, w: int, c: int, n: int, itemsize: int):
    """What either kernel moves and computes, for XLA's scheduler: the
    full-resolution array once, the pooled array and the index once,
    some thirty vector operations per pooled element."""
    pooled = _out(h) * _out(w) * c * n
    return pl.CostEstimate(
        flops=30 * pooled,
        transcendentals=0,
        bytes_accessed=h * w * c * n * itemsize + pooled * (itemsize + 1),
    )


# Jitted so that a second trace with the same shapes (the transposition
# of the platform switch traces each backward twice, and a learner
# traces its step more than once) finds the kernel already traced.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _pool_forward(xt: jax.Array, interpret: bool | None = None):
    """`[H, W, C, N]` -> pooled `[Ho, Wo, C, N]`, winner index `uint8`."""
    h, w, c, n = xt.shape
    ho, wo = _out(h), _out(w)
    rows, lanes = _blocks(h, w, c, n, xt.dtype.itemsize)

    def halo_row(i):  # clamped: the kernel masks a row outside the image
        if h % 2:
            return jnp.maximum(2 * rows * i - 1, 0)
        return jnp.minimum(2 * rows * (i + 1), h - 1)

    pooled = pl.BlockSpec((rows, wo, c, lanes), lambda b, i: (i, 0, 0, b))
    return pallas_call(
        functools.partial(_forward_kernel, h=h, w=w),
        name="max_pool_forward",
        interpret=interpret,
        grid=(pl.cdiv(n, lanes), ho // rows),
        in_specs=[
            pl.BlockSpec(
                (2 * rows, w, c, lanes), lambda b, i: (i, 0, 0, b)
            ),
            pl.BlockSpec(
                (None, w, c, lanes), lambda b, i: (halo_row(i), 0, 0, b)
            ),
        ],
        out_specs=(pooled, pooled),
        out_shape=(
            jax.ShapeDtypeStruct((ho, wo, c, n), xt.dtype),
            jax.ShapeDtypeStruct((ho, wo, c, n), jnp.uint8),
        ),
        compiler_params=_PARAMS,
        cost_estimate=_cost(h, w, c, n, xt.dtype.itemsize),
    )(xt, xt)


@functools.partial(jax.jit, static_argnames=("h", "w", "interpret"))
def _pool_backward(
    gt: jax.Array, idx: jax.Array, h: int, w: int,
    interpret: bool | None = None,
):
    """Gradient `[Ho, Wo, C, N]` and winner index -> `[H, W, C, N]`."""
    ho, wo, c, n = gt.shape
    rows, lanes = _blocks(h, w, c, n, gt.dtype.itemsize)

    def halo_row(i):  # clamped: the kernel masks a row outside the image
        if h % 2:
            return jnp.minimum(rows * (i + 1), ho - 1)
        return jnp.maximum(rows * i - 1, 0)

    pooled = pl.BlockSpec((rows, wo, c, lanes), lambda b, i: (i, 0, 0, b))
    halo = pl.BlockSpec(
        (None, wo, c, lanes), lambda b, i: (halo_row(i), 0, 0, b)
    )
    return pallas_call(
        functools.partial(_backward_kernel, h=h, w=w),
        name="max_pool_backward",
        interpret=interpret,
        grid=(pl.cdiv(n, lanes), ho // rows),
        in_specs=[pooled, halo, pooled, halo],
        out_specs=pl.BlockSpec(
            (2 * rows, w, c, lanes), lambda b, i: (i, 0, 0, b)
        ),
        out_shape=jax.ShapeDtypeStruct((h, w, c, n), gt.dtype),
        compiler_params=_PARAMS,
        cost_estimate=_cost(h, w, c, n, gt.dtype.itemsize),
    )(gt, gt, idx, idx)


def _xla_pool(x: jax.Array) -> jax.Array:
    return nn.max_pool(
        x, window_shape=(3, 3), strides=(2, 2), padding="SAME"
    )


def _batch_last(x: jax.Array) -> jax.Array:
    """`[..., H, W, C]` -> `[H, W, C, N]`, a bitcast in XLA's TPU layout.

    The barrier keeps the transposition out of the producer: XLA would
    otherwise fold it into the convolution's output dimensions, regroup
    the fusions around the kernels and move 1.7 GB more a step than it
    saved (same step time as the parent; my chip run, PR 29). What a
    kernel reads is materialised in HBM either way."""
    x = jax.lax.optimization_barrier(x)
    return jnp.transpose(x.reshape(-1, *x.shape[-3:]), (1, 2, 3, 0))


def _batch_first(xt: jax.Array, lead: tuple) -> jax.Array:
    """`[H, W, C, N]` -> `[*lead, H, W, C]`, behind the same barrier."""
    x = jnp.transpose(xt, (3, 0, 1, 2)).reshape(*lead, *xt.shape[:3])
    return jax.lax.optimization_barrier(x)


@functools.lru_cache(maxsize=None)
def pool_with_index(h: int, w: int, interpret: bool | None = None):
    """The pool over `[..., h, w, C]` inputs with the kernels as its
    forward and backward under differentiation (the backward needs the
    input's H and W, which the pooled shape does not determine).
    `interpret` as in `pallas_util.pallas_call`: `None` chooses by the
    platform a program is lowered for."""

    @jax.custom_vjp
    def pool(x):
        return _xla_pool(x)

    def forward(x):
        with jax.named_scope("torso/max_pool"):
            y, idx = _pool_forward(_batch_last(x), interpret)
            return _batch_first(y, x.shape[:-3]), idx

    def backward(idx, g):
        with jax.named_scope("torso/max_pool"):
            dx = _pool_backward(_batch_last(g), idx, h, w, interpret)
            return (_batch_first(dx, g.shape[:-3]),)

    pool.defvjp(forward, backward)
    return pool


def max_pool(x: jax.Array, *, kernel: bool = True) -> jax.Array:
    """3x3 / stride-2 / SAME max-pool over `[..., H, W, C]`.

    Not differentiated (actor inference, serving, rollouts) this is
    `nn.max_pool` everywhere. Differentiated in a program lowered for a
    TPU, the forward is a Pallas kernel whose only residual is the
    `uint8` winner index and the backward a Pallas kernel that routes
    the gradient from it. Lowered for anything else, and with
    `kernel=False` (the mesh path), autodiff goes through XLA's pool as
    before: the interpreted kernels walk the image a tile at a time,
    which costs a CPU step 25 times what XLA's pool does (sandbox,
    PR 29), so they run interpreted only where a test calls
    `pool_with_index` itself."""
    if not kernel:
        return _xla_pool(x)
    # Already inside the TPU's branch: the compiled kernels, no second
    # choice by platform.
    kernels = pool_with_index(x.shape[-3], x.shape[-2], interpret=False)
    return jax.lax.platform_dependent(x, tpu=kernels, default=_xla_pool)
