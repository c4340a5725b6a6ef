"""Hybrid temporal core: state-space (Mamba-1), sliding-window attention
and full attention layers in one stack, each followed by a gated MLP —
the self-decoder of SambaY (arXiv:2507.06607; Phi-4-mini-flash-reasoning)
as an agent's recurrence.

Every block is `h = x + Mixer(LN(x)); y = h + MLP(LN(h))` with
`MLP(u) = W_down(silu(W_gate u) * W_up u)`, no biases in the projections.
`layers` names each block's mixer:

- `"mamba"`: `[x, z] = W_in u`; `x = silu(conv1d_causal(x) + b)`;
  `(d, B_t, C_t) = W_x x`; `dt = softplus(W_dt d + b_dt)`;
  `s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) B_t`, `A = -exp(A_log)`;
  `y_t = s_t . C_t + D x_t`; out `= W_out(y * silu(z))`
  (ops/selective_scan.py). Where `first[t]` is set, `s_{t-1}` and the
  convolution's remembered inputs are zero: the reset sits inside the
  scan's decay and the convolution's taps, not around a step loop.
- `"window"`: softmax attention of `num_heads` query heads on
  `num_kv_heads` key/value heads, no positional encoding (the state-space
  layers carry position); the query at position p sees positions
  p-window+1..p of its own episode, whether the cache or the unroll
  holds them (ops/attention_pallas.py `window=`). Plain softmax: the
  paper's differential attention is not implemented, a stated departure
  (benchmark/configs/pong_phi4flash_core.json).
- `"full"`: the same over every earlier-or-same position of its own
  episode that the cache (`full_cache` slots) or the unroll holds.

The carry holds state of three kinds and two cache lengths, per row
(batch-major, so the learner's batcher concatenates it leaf by leaf like
any recurrent state; float32/int32):

  conv   `[B, Lm, d_conv-1, Di]` the convolution's remembered inputs
  ssm    `[B, Lm, N, Di]`        the scan state (the scan kernels' layout)
  k_win, v_win   `[B, Lw, W, Hkv*dh]`, win_seg/win_pos `[B, W]`
  k_full, v_full `[B, Lf, F, Hkv*dh]`, full_seg/full_pos `[B, F]`
  pos `[B]` next absolute position, seg `[B]` running episode counter

Every large leaf ends in a dimension that is a multiple of 128 (channels,
or a position's key/value heads side by side), so that the learner's
`device_put` of a stacked carry is a straight copy: the chip's tiled
layout would pad a trailing `[20, 64]` or `[5120, 16]` and the host would
re-lay 54 MB a batch (0.33 s a batch; my chip run, PR 34).

Cache slots are ordered oldest first and hold consecutive positions (slot
j of a cache of W holds position `pos - W + j` once written; `*_seg` is -1
for an empty slot), so the window test runs on indices: context index s
of (cache + unroll) stands `s - W` steps from the unroll's start.
Convolution windows and scan states are those of the running episode
`seg`, or zero.

Precision: parameters float32; matrix products in `dtype` (bfloat16 on
the learner's path) with float32 accumulation; LayerNorm, softmax, `dt`,
`exp(dt A)`, the scan state and the residual stream float32.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torched_impala_tpu.ops.attention_pallas import (
    _visibility,
    windowed_attention,
)
from torched_impala_tpu.ops.selective_scan import selective_scan

F32 = jnp.float32
NEG_INF = -1e30
KINDS = ("mamba", "window", "full")


class HybridCoreState(NamedTuple):
    conv: jax.Array
    ssm: jax.Array
    k_win: jax.Array
    v_win: jax.Array
    win_seg: jax.Array
    win_pos: jax.Array
    k_full: jax.Array
    v_full: jax.Array
    full_seg: jax.Array
    full_pos: jax.Array
    pos: jax.Array
    seg: jax.Array


def _a_log_init(key, shape, dtype=F32):
    """Mamba's S4D-real start: A[d, n] = -(n + 1)."""
    del key
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[1] + 1, dtype=dtype)), shape
    )


def _dt_bias_init(key, shape, dtype=F32):
    """softplus(bias) log-uniform in [1e-3, 1e-1] (Mamba's default)."""
    dt = jnp.exp(
        jax.random.uniform(key, shape, dtype)
        * (jnp.log(0.1) - jnp.log(1e-3))
        + jnp.log(1e-3)
    )
    return dt + jnp.log(-jnp.expm1(-dt))


class _Linear(nn.Module):
    """`x @ kernel` with operands in `dtype`, accumulated in float32 and
    returned in `out_dtype`; no bias unless asked for."""

    features: int
    dtype: Any = F32
    use_bias: bool = False

    @nn.compact
    def __call__(self, x, out_dtype=None):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], self.features), F32,
        )
        y = jnp.dot(
            x.astype(self.dtype), kernel.astype(self.dtype),
            preferred_element_type=F32,
        )
        if self.use_bias:
            y = y + self.param(
                "bias", nn.initializers.zeros, (self.features,), F32
            )
        return y.astype(out_dtype or self.dtype)


class _MLP(nn.Module):
    d_intermediate: int
    dtype: Any = F32

    @nn.compact
    def __call__(self, u):
        with jax.named_scope("core/mlp"):
            gate = _Linear(self.d_intermediate, self.dtype, name="gate")(u)
            up = _Linear(self.d_intermediate, self.dtype, name="up")(u)
            return _Linear(u.shape[-1], self.dtype, name="down")(
                nn.silu(gate) * up, F32
            )


class _Mamba(nn.Module):
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int
    dtype: Any = F32
    scan_kernel: bool = True
    scan_chunk: int = 64

    @nn.compact
    def __call__(self, u, conv0, ssm0, first, seg_q, seg0):
        """u `[B, T, D]` (after LN); conv0 `[B, K-1, Di]`, ssm0
        `[B, N, Di]` of episode `seg0` `[B]`; first/seg_q `[B, T]`.
        Returns (out `[B, T, D]` float32, new conv, new ssm)."""
        B, T, D = u.shape
        di, n, k = self.d_inner, self.d_state, self.d_conv
        xz = _Linear(2 * di, self.dtype, name="in_proj")(u, F32)
        x, z = xz[..., :di], xz[..., di:]
        # Causal depthwise convolution over (remembered inputs + unroll);
        # a tap reaches only inputs of the query step's own episode.
        w = self.param(
            "conv_kernel", nn.initializers.lecun_normal(), (k, di), F32
        )
        b = self.param("conv_bias", nn.initializers.zeros, (di,), F32)
        xpad = jnp.concatenate([conv0, x], axis=1)  # [B, K-1+T, Di]
        segpad = jnp.concatenate(
            [jnp.broadcast_to(seg0[:, None], (B, k - 1)), seg_q], axis=1
        )
        xc = x * w[k - 1] + b
        for j in range(k - 1):
            same = segpad[:, j : j + T] == seg_q
            xc = xc + jnp.where(same[..., None], xpad[:, j : j + T], 0.0) * w[j]
        x = nn.silu(xc)
        tail = segpad[:, -(k - 1) :] == seg_q[:, -1:]
        new_conv = jnp.where(tail[..., None], xpad[:, -(k - 1) :], 0.0)

        dbc = _Linear(self.dt_rank + 2 * n, self.dtype, name="x_proj")(x, F32)
        dt = nn.softplus(
            _Linear(di, self.dtype, name="dt_proj")(
                dbc[..., : self.dt_rank], F32
            )
            + self.param("dt_bias", _dt_bias_init, (di,), F32)
        )
        a = -jnp.exp(self.param("A_log", _a_log_init, (di, n), F32))
        with jax.named_scope("core/mamba_scan"):
            y, new_ssm = selective_scan(
                x, dt, a,
                dbc[..., self.dt_rank : self.dt_rank + n],
                dbc[..., self.dt_rank + n :],
                first, jnp.swapaxes(ssm0, 1, 2),
                chunk=self.scan_chunk, kernel=self.scan_kernel,
            )
            new_ssm = jnp.swapaxes(new_ssm, 1, 2)
        y = y + x * self.param("D", nn.initializers.ones, (di,), F32)
        out = _Linear(D, self.dtype, name="out_proj")(y * nn.silu(z), F32)
        return out, new_conv, new_ssm


class _Attention(nn.Module):
    kind: str  # "window" | "full"
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int  # the sliding window (kind "window" only)
    dtype: Any = F32
    kernel: str = "einsum"

    @nn.compact
    def __call__(self, u, k_cache, v_cache, cache_seg, seg_q):
        """u `[B, T, D]`; caches `[B, W, Hkv*dh]` (float32), cache_seg
        `[B, W]`, seg_q `[B, T]`. Returns (out `[B, T, D]` float32, new
        key cache, new value cache)."""
        B, T, D = u.shape
        H, Hkv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        W = k_cache.shape[1]
        q = _Linear(H * dh, self.dtype, name="q_proj")(u).reshape(B, T, H, dh)
        k = _Linear(Hkv * dh, self.dtype, name="k_proj")(u)
        v = _Linear(Hkv * dh, self.dtype, name="v_proj")(u)
        k_ctx = jnp.concatenate([k_cache.astype(self.dtype), k], axis=1)
        v_ctx = jnp.concatenate([v_cache.astype(self.dtype), v], axis=1)
        new_k, new_v = k_ctx[:, -W:].astype(F32), v_ctx[:, -W:].astype(F32)
        k_ctx = k_ctx.reshape(B, W + T, Hkv, dh)
        v_ctx = v_ctx.reshape(B, W + T, Hkv, dh)
        seg_ctx = jnp.concatenate([cache_seg, seg_q], axis=1)
        window = self.window if self.kind == "window" else None
        with jax.named_scope(f"core/attention_{self.kind}"):
            if self.kernel == "pallas" and T > 1:
                out = windowed_attention(
                    q, k_ctx, v_ctx, seg_q, seg_ctx, W, None, window,
                    f"attention_{self.kind}",
                )
            else:
                out = _masked_attention(
                    q, k_ctx, v_ctx, seg_q, seg_ctx, W, window
                )
        out = _Linear(D, self.dtype, name="o_proj")(
            out.reshape(B, T, H * dh), F32
        )
        return out, new_k, new_v


def _masked_attention(q, k_ctx, v_ctx, seg_q, seg_ctx, W, window):
    """Attention under the written-out mask (step mode, the CPU, a mesh):
    scores and softmax in float32."""
    B, T, H, dh = q.shape
    S, Hkv = k_ctx.shape[1:3]
    mask = _visibility(seg_q, seg_ctx, T, S, W, window)  # the kernels' own
    qg = q.reshape(B, T, Hkv, H // Hkv, dh)
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", qg, k_ctx, preferred_element_type=F32
    ) / jnp.sqrt(float(dh))
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bkgts,bskd->btkgd", probs.astype(q.dtype), v_ctx,
        preferred_element_type=F32,
    )
    return out.reshape(B, T, H, dh).astype(q.dtype)


class _Block(nn.Module):
    """One layer: mixer and MLP, each behind its LayerNorm, on the
    float32 residual stream. `state` and the return's second element are
    the mixer's own part of the carry."""

    kind: str
    mixer: dict
    d_intermediate: int
    ln_eps: float
    dtype: Any = F32

    @nn.compact
    def __call__(self, x, state, first, seg_q, seg0):
        ln = lambda name: nn.LayerNorm(  # noqa: E731
            epsilon=self.ln_eps, dtype=F32, name=name
        )
        u = ln(f"ln_{self.kind}")(x)
        if self.kind == "mamba":
            out, *new = _Mamba(dtype=self.dtype, name="mamba", **self.mixer)(
                u, *state, first, seg_q, seg0
            )
        else:
            k_cache, v_cache, cache_seg = state
            out, *new = _Attention(
                kind=self.kind, dtype=self.dtype,
                name=f"{self.kind}_attention", **self.mixer,
            )(u, k_cache, v_cache, cache_seg, seg_q)
        x = x + out
        x = x + _MLP(self.d_intermediate, self.dtype, name="mlp")(
            ln("ln_mlp")(x)
        )
        return x, tuple(new)


class HybridCore(nn.Module):
    """Call with features `[T, B, F]` (time-major, like the other cores),
    `first` `[T, B]` and a `HybridCoreState`; returns (`[T, B, d_model]`
    float32, new state). Step mode is T=1."""

    d_model: int = 2560
    layers: tuple = ("mamba", "window", "mamba", "full")
    num_heads: int = 40
    num_kv_heads: int = 20
    head_dim: int = 64
    window: int = 512  # sliding window = the window layers' cache length
    full_cache: int = 2048
    d_intermediate: int = 10240
    d_inner: int = 5120
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    ln_eps: float = 1e-5
    dtype: Any = F32  # the matrix products' operands
    # Recompute each block in the backward pass (nn.remat): a block's
    # activations (the MLP's hidden layer, the scan's inputs) are then
    # alive for one block at a time.
    remat: bool = False
    # "pallas" | "einsum"; resolved by the caller against the compute
    # devices (configs.make_agent), like TransformerCore.dense_kernel.
    attention_kernel: str = "einsum"
    scan_kernel: bool = True
    scan_chunk: int = 64

    def _count(self, kind: str) -> int:
        return sum(k == kind for k in self.layers)

    def initial_state(self, batch_size: int) -> HybridCoreState:
        bad = [k for k in self.layers if k not in KINDS]
        if bad:
            raise ValueError(f"unknown layer kinds {bad}; expected {KINDS}")
        B, kv = batch_size, self.num_kv_heads * self.head_dim
        lm, lw, lf = (self._count(k) for k in KINDS)
        zeros = lambda *s: jnp.zeros(s, F32)  # noqa: E731
        return HybridCoreState(
            conv=zeros(B, lm, self.d_conv - 1, self.d_inner),
            ssm=zeros(B, lm, self.d_state, self.d_inner),
            k_win=zeros(B, lw, self.window, kv),
            v_win=zeros(B, lw, self.window, kv),
            win_seg=jnp.full((B, self.window), -1, jnp.int32),
            win_pos=jnp.zeros((B, self.window), jnp.int32),
            k_full=zeros(B, lf, self.full_cache, kv),
            v_full=zeros(B, lf, self.full_cache, kv),
            full_seg=jnp.full((B, self.full_cache), -1, jnp.int32),
            full_pos=jnp.zeros((B, self.full_cache), jnp.int32),
            pos=jnp.zeros((B,), jnp.int32),
            seg=jnp.zeros((B,), jnp.int32),
        )

    def state_bytes_per_row(self) -> int:
        """Bytes of one row's carry."""
        return sum(
            x.size * x.dtype.itemsize
            for x in jax.tree.leaves(
                jax.eval_shape(lambda: self.initial_state(1))
            )
        )

    @nn.compact
    def __call__(self, features, first, state: HybridCoreState):
        T, B, _ = features.shape
        first = first.transpose(1, 0)  # [B, T]
        seg_q = state.seg[:, None] + jnp.cumsum(first.astype(jnp.int32), 1)
        pos_q = state.pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        x = _Linear(self.d_model, self.dtype, use_bias=True, name="in_proj")(
            features, F32
        ).transpose(1, 0, 2)  # [B, T, D]

        mixers = {
            "mamba": dict(
                d_inner=self.d_inner, d_state=self.d_state,
                d_conv=self.d_conv, dt_rank=self.dt_rank,
                scan_kernel=self.scan_kernel, scan_chunk=self.scan_chunk,
            ),
            "attention": dict(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, window=self.window,
                kernel=self.attention_kernel,
            ),
        }
        block_cls = nn.remat(_Block) if self.remat else _Block
        seen = dict.fromkeys(KINDS, 0)
        new = {k: [] for k in KINDS}
        for i, kind in enumerate(self.layers):
            j = seen[kind]
            seen[kind] += 1
            if kind == "mamba":
                part = (state.conv[:, j], state.ssm[:, j])
            elif kind == "window":
                part = (state.k_win[:, j], state.v_win[:, j], state.win_seg)
            else:
                part = (state.k_full[:, j], state.v_full[:, j], state.full_seg)
            x, out = block_cls(
                kind=kind,
                mixer=mixers["mamba" if kind == "mamba" else "attention"],
                d_intermediate=self.d_intermediate,
                ln_eps=self.ln_eps,
                dtype=self.dtype,
                name=f"block_{i}",
            )(x, part, first, seg_q, state.seg)
            new[kind].append(out)
        out = nn.LayerNorm(epsilon=self.ln_eps, dtype=F32, name="ln_out")(x)

        def stacked(kind, part, old):
            if not new[kind]:
                return old
            return jnp.stack([o[part] for o in new[kind]], axis=1)

        def slots(old, fresh, width):
            return jnp.concatenate([old, fresh], axis=1)[:, -width:]

        new_state = HybridCoreState(
            conv=stacked("mamba", 0, state.conv),
            ssm=stacked("mamba", 1, state.ssm),
            k_win=stacked("window", 0, state.k_win),
            v_win=stacked("window", 1, state.v_win),
            win_seg=slots(state.win_seg, seg_q, self.window),
            win_pos=slots(state.win_pos, pos_q, self.window),
            k_full=stacked("full", 0, state.k_full),
            v_full=stacked("full", 1, state.v_full),
            full_seg=slots(state.full_seg, seg_q, self.full_cache),
            full_pos=slots(state.full_pos, pos_q, self.full_cache),
            pos=state.pos + T,
            seg=seg_q[:, -1],
        )
        return out.transpose(1, 0, 2), new_state
