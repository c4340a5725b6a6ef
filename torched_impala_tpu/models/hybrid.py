"""Hybrid temporal core: state-space (Mamba-1), gated short-convolution,
sliding-window, full and latent attention layers in one stack, each
followed by a gated MLP or a mixture of experts — the self-decoder of
SambaY (arXiv:2507.06607; Phi-4-mini-flash-reasoning), LFM2-8B-A1B's hybrid
(huggingface.co/LiquidAI/LFM2-8B-A1B) and a DeepSeek-V3 decoder
(arXiv:2412.19437 section 2.1; JoyAI-LLM-Flash) as an agent's recurrence.

Every block is `h = x + Mixer(N(x)); y = h + MLP(N(h))`, `N` a LayerNorm
(`norm="layer"`) or an RMSNorm (`norm="rms"`), no biases in the
projections. `mlp="gated"`: `MLP(u) = W_down(silu(W_gate u) * W_up u)`.
`mlp="moe"`: a routed mixture of such MLPs (`_MoE`): `s = sigmoid(W_r u)`
over all `num_experts` experts of the layer, the top `experts_per_token`
of `s + expert_bias` chosen (the bias only chooses), their `s` divided by
their sum plus 1e-6 and times `routed_scale`; the layer holds the
experts `experts_held = (first, count)` of them and computes their part of
the sum alone, as a chip of an expert-parallel deployment does (no
exchange, and nothing stands in for the experts held elsewhere), and adds
a gated MLP of width `shared_expert` that every token takes, whole on
every chip (none where 0). Dropless: the rows routed to the held experts
go through ops/grouped_matmul.py whatever the load. With `mlp="moe"` the
first `dense_layers` blocks take the gated MLP instead. `layers` names
each block's mixer:

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
- `"full_rope"`: the same, with an RMSNorm over each head of q and of k
  and then rotary position embeddings (`rope_theta`, rotate-half) at each
  position's absolute step; the cache holds keys as rotated. It shares the
  full layers' cache length.
- `"short_conv"` (LFM2's double-gated short convolution):
  `[B, C, x] = W_in u`; `y = C * conv1d_causal(B * x)` (depthwise,
  `conv_taps` taps, no bias); out `= W_out y`. Its carry is the last
  `conv_taps - 1` gated inputs `B * x`, of the running episode or zero.
- `"latent"` (multi-head latent attention), `latent = (q_rank, kv_rank,
  nope, rope, v)`: `c_q = RMSNorm(W_qa u)`, `[q_nope, q_pe]_h = (W_qb
  c_q)_h`; `[c_kv, k_pe] = W_kva u`, `c_kv = RMSNorm(c_kv)`; `q_pe` and
  `k_pe` (one head, shared by every head) take rotary embeddings on
  interleaved pairs `(x_2i, x_2i+1)` at each position's absolute step;
  `[k_nope, v]_h = (W_kvb c_kv)_h` over cache and unroll alike; attention
  of `[q_nope, q_pe]` on `[k_nope, k_pe]` (width nope + rope, scaled by
  it) over values of width `v`, the full layers' visibility; out `= W_o
  o`. The cache holds per position `c_kv` after its norm and `k_pe` after
  its rotation (`kv_rank + rope` numbers), and is up-projected once an
  unroll, into the fused kernels' operands.

The carry holds state of up to five kinds and two cache lengths, per row
(batch-major, so the learner's batcher concatenates it leaf by leaf like
any recurrent state; float32/int32); a kind no layer has is `None`:

  conv   `[B, Lm, d_conv-1, Di]` the Mamba convolution's remembered inputs
  ssm    `[B, Lm, N, Di]`        the scan state (the scan kernels' layout)
  k_win, v_win   `[B, Lw, W, Hkv*dh]`, win_seg/win_pos `[B, W]`
  k_full, v_full `[B, Lf, F, Hkv*dh]`, full_seg/full_pos `[B, F]`
                                 (Lf: the "full" and "full_rope" layers;
                                 the slots' counters are the latent
                                 layers' too)
  pos `[B]` next absolute position, seg `[B]` running episode counter
  sconv  `[B, Ls, conv_taps-1, D]` the short convolutions' gated inputs
  lat_ckv `[B, Ll, F, kv_rank]`  the latent layers' normalised `c_kv`
  lat_kpe `[B, Ll, F*rope/128, 128]` their rotated `k_pe`, 128/rope
                                 positions to a row

Every large leaf ends in a dimension that is a multiple of 128 (channels,
or a position's key/value heads side by side), so that the learner's
`device_put` of a stacked carry is a straight copy: the chip's tiled
layout would pad a trailing `[20, 64]` or `[5120, 16]` and the host would
re-lay 54 MB a batch (0.33 s a batch, measured on one v5e chip). So a latent
cache keeps `c_kv` and `k_pe` apart, and lays `k_pe` (64 numbers a
position at the published widths) two positions to a row: no padded
lanes, where one leaf of `kv_rank + rope` = 576 padded to 640 would carry
11% of zeros through every batch.

Cache slots are ordered oldest first and hold consecutive positions (slot
j of a cache of W holds position `pos - W + j` once written; `*_seg` is -1
for an empty slot), so the window test runs on indices: context index s
of (cache + unroll) stands `s - W` steps from the unroll's start.
Convolution windows and scan states are those of the running episode
`seg`, or zero.

Precision: parameters float32; matrix products in `dtype` (bfloat16 on
the learner's path) with float32 accumulation, the experts' included;
the norms, softmax, `dt`, `exp(dt A)`, the scan state, the residual
stream, the router's scores and its choice float32 (the router's product
at `highest` precision, so that which experts a token gets is not a
matter of the products' rounding).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from torched_impala_tpu.ops.attention_pallas import (
    _visibility,
    windowed_attention,
)
from torched_impala_tpu.ops.grouped_matmul import (
    capacity,
    combine,
    dispatch,
    group_rows,
    grouped_matmul,
    row_slots,
)
from torched_impala_tpu.ops.selective_scan import selective_scan

F32 = jnp.float32
NEG_INF = -1e30
KINDS = ("mamba", "window", "full", "full_rope", "short_conv", "latent")


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
    sconv: Any = None
    lat_ckv: Any = None
    lat_kpe: Any = None


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
    trace_name: str = "core/mlp"

    @nn.compact
    def __call__(self, u):
        with jax.named_scope(self.trace_name):
            gate = _Linear(self.d_intermediate, self.dtype, name="gate")(u)
            up = _Linear(self.d_intermediate, self.dtype, name="up")(u)
            return _Linear(u.shape[-1], self.dtype, name="down")(
                nn.silu(gate) * up, F32
            )


class _RMSNorm(nn.Module):
    """`x / rms(x) * scale` over the last axis, in float32."""

    eps: float

    @nn.compact
    def __call__(self, x):
        x = x.astype(F32)
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), F32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps
        ) * scale


def _rope(x, pos, theta: float):
    """Rotary embedding, rotate-half: `x` `[B, T, H, dh]` float32 at
    absolute positions `pos` `[B, T]`."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[..., None, None] * inv  # [B, T, 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _rope_pairs(x, pos, theta: float):
    """Rotary embedding on interleaved pairs: `(x[2i], x[2i+1])` of `x`
    `[B, T, H, d]` float32 turns by `pos * theta^(-2i/d)`, `pos` `[B, T]`."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[..., None, None] * inv  # [B, T, 1, d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def _causal_conv(x, window0, w, seg_q, seg0, bias=0.0):
    """Causal depthwise convolution of `x` `[B, T, C]` over (the window of
    remembered inputs `[B, K-1, C]` of episode `seg0` `[B]` + the unroll),
    taps `w` `[K, C]` oldest first: a tap reaches only inputs of the query
    step's own episode (`seg_q` `[B, T]`). Returns (out, new window: the
    last K-1 inputs of the running episode, or zero)."""
    B, T, _ = x.shape
    k = w.shape[0]
    xpad = jnp.concatenate([window0, x], axis=1)  # [B, K-1+T, C]
    segpad = jnp.concatenate(
        [jnp.broadcast_to(seg0[:, None], (B, k - 1)), seg_q], axis=1
    )
    out = x * w[k - 1] + bias
    for j in range(k - 1):
        same = segpad[:, j : j + T] == seg_q
        out = out + jnp.where(same[..., None], xpad[:, j : j + T], 0.0) * w[j]
    tail = segpad[:, -(k - 1) :] == seg_q[:, -1:]
    return out, jnp.where(tail[..., None], xpad[:, -(k - 1) :], 0.0)


class _ShortConv(nn.Module):
    """LFM2's gated short convolution (module docstring)."""

    taps: int
    dtype: Any = F32

    @nn.compact
    def __call__(self, u, window0, seg_q, seg0):
        """u `[B, T, D]` (after the norm); window0 `[B, taps-1, D]` the gated
        inputs of episode `seg0` `[B]`; seg_q `[B, T]`. Returns (out
        `[B, T, D]` float32, new window)."""
        D = u.shape[-1]
        bcx = _Linear(3 * D, self.dtype, name="in_proj")(u, F32)
        gate_b, gate_c, x = bcx[..., :D], bcx[..., D : 2 * D], bcx[..., 2 * D :]
        w = self.param(
            "conv_kernel", nn.initializers.lecun_normal(), (self.taps, D), F32
        )
        with jax.named_scope("core/short_conv"):
            conv, new_window = _causal_conv(gate_b * x, window0, w, seg_q, seg0)
            y = gate_c * conv
        return _Linear(D, self.dtype, name="out_proj")(y, F32), new_window


class _MoE(nn.Module):
    """The routed mixture of gated MLPs of one layer, its held experts'
    part (module docstring). Returns (out `[B, T, D]` float32, rows routed
    to each held expert `[held]`)."""

    num_experts: int
    experts_per_token: int
    experts_held: tuple  # (first, count)
    d_ff: int
    dtype: Any = F32
    shared: int = 0  # width of the expert every token takes; 0: none
    routed_scale: float = 1.0

    @nn.compact
    def __call__(self, u):
        B, T, D = u.shape
        first, held = self.experts_held
        e, k = self.num_experts, self.experts_per_token
        x = u.reshape(B * T, D)
        with jax.named_scope("core/moe_route"):
            router = self.param(
                "router", nn.initializers.lecun_normal(), (D, e), F32
            )
            bias = self.param("expert_bias", nn.initializers.zeros, (e,), F32)
            scores = jax.nn.sigmoid(
                jnp.dot(
                    x.astype(F32), router,
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=F32,
                )
            )
            _, chosen = jax.lax.top_k(jax.lax.stop_gradient(scores + bias), k)
            weight = jnp.take_along_axis(scores, chosen, axis=1)
            weight = weight / (jnp.sum(weight, 1, keepdims=True) + 1e-6)
            if self.routed_scale != 1.0:
                weight = weight * self.routed_scale
            local = chosen - first
            mine = (local >= 0) & (local < held)
            weight = jnp.where(mine, weight, 0.0)
            pos, rows = group_rows(jnp.where(mine, local, held), held)
            slot, filled = row_slots(pos, capacity(B * T, k, held))
        with jax.named_scope("core/moe_experts"):
            # each expert's matrix over its own fan-in
            init = nn.initializers.lecun_normal(batch_axis=(0,))
            w = lambda name, shape: self.param(  # noqa: E731
                name, init, (held, *shape), F32
            ).astype(self.dtype)
            buf = dispatch(x.astype(F32), pos, slot, self.dtype)
            mm = functools.partial(grouped_matmul, rows=rows)
            gate = mm(buf, w("gate", (D, self.d_ff)))
            up = mm(buf, w("up", (D, self.d_ff)))
            hidden = (nn.silu(gate) * up).astype(self.dtype)
            y = mm(hidden, w("down", (self.d_ff, D)))
            # a slot not held here reads no row: the buffer's rows past
            # the groups hold no defined value (ops/grouped_matmul.py)
            out = combine(y, weight, pos, slot, filled)
        out = out.reshape(B, T, D)
        if self.shared:
            out = out + _MLP(
                self.shared, self.dtype, "core/moe_shared", name="shared"
            )(u)
        return out, rows


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
        D = u.shape[-1]
        di, n, k = self.d_inner, self.d_state, self.d_conv
        xz = _Linear(2 * di, self.dtype, name="in_proj")(u, F32)
        x, z = xz[..., :di], xz[..., di:]
        w = self.param(
            "conv_kernel", nn.initializers.lecun_normal(), (k, di), F32
        )
        b = self.param("conv_bias", nn.initializers.zeros, (di,), F32)
        xc, new_conv = _causal_conv(x, conv0, w, seg_q, seg0, b)
        x = nn.silu(xc)

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
    kind: str  # "window" | "full" | "full_rope"
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int  # the sliding window (kind "window" only)
    dtype: Any = F32
    kernel: str = "einsum"
    rope_theta: float = 1e6  # kind "full_rope" only
    eps: float = 1e-5  # the per-head norms of kind "full_rope"

    @nn.compact
    def __call__(self, u, k_cache, v_cache, cache_seg, seg_q, pos_q):
        """u `[B, T, D]`; caches `[B, W, Hkv*dh]` (float32), cache_seg
        `[B, W]`, seg_q and pos_q `[B, T]`. Returns (out `[B, T, D]`
        float32, new key cache, new value cache)."""
        B, T, D = u.shape
        H, Hkv, dh = self.num_heads, self.num_kv_heads, self.head_dim
        W = k_cache.shape[1]
        if self.kind == "full_rope":
            def rotated(name, heads):
                x = _Linear(heads * dh, self.dtype, name=f"{name}_proj")(u, F32)
                x = _RMSNorm(self.eps, name=f"{name}_norm")(
                    x.reshape(B, T, heads, dh)
                )
                return _rope(x, pos_q, self.rope_theta).astype(self.dtype)

            q = rotated("q", H)
            k = rotated("k", Hkv).reshape(B, T, Hkv * dh)
        else:
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
        scope = "window" if self.kind == "window" else "full"
        with jax.named_scope(f"core/attention_{scope}"):
            if self.kernel == "pallas" and T > 1:
                out = windowed_attention(
                    q, k_ctx, v_ctx, seg_q, seg_ctx, W, None, window,
                    f"attention_{scope}",
                )
            else:
                out = _masked_attention(
                    q, k_ctx, v_ctx, seg_q, seg_ctx, W, window
                )
        out = _Linear(D, self.dtype, name="o_proj")(
            out.reshape(B, T, H * dh), F32
        )
        return out, new_k, new_v


class _Latent(nn.Module):
    """Multi-head latent attention (module docstring, kind `"latent"`)."""

    num_heads: int
    latent: tuple  # (q_rank, kv_rank, nope, rope, v)
    rope_theta: float
    dtype: Any = F32
    kernel: str = "einsum"
    eps: float = 1e-6  # the latent norms

    @nn.compact
    def __call__(self, u, ckv_cache, kpe_cache, cache_seg, seg_q, pos_q):
        """u `[B, T, D]`; the cache's `c_kv` `[B, F, kv_rank]` and rotated
        `k_pe` `[B, F*rope/128, 128]` (float32), cache_seg `[B, F]`, seg_q
        and pos_q `[B, T]`. Returns (out `[B, T, D]` float32, new `c_kv`
        cache, new `k_pe` cache)."""
        B, T, D = u.shape
        H = self.num_heads
        q_rank, kv_rank, nope, rope, dv = self.latent
        F = ckv_cache.shape[1]
        c_q = _RMSNorm(self.eps, name="q_a_norm")(
            _Linear(q_rank, self.dtype, name="q_a_proj")(u, F32)
        )
        q = _Linear(H * (nope + rope), self.dtype, name="q_b_proj")(
            c_q, F32
        ).reshape(B, T, H, nope + rope)
        q = jnp.concatenate(
            [q[..., :nope], _rope_pairs(q[..., nope:], pos_q, self.rope_theta)],
            -1,
        ).astype(self.dtype)
        kv = _Linear(kv_rank + rope, self.dtype, name="kv_a_proj")(u, F32)
        c_kv = _RMSNorm(self.eps, name="kv_a_norm")(kv[..., :kv_rank])
        k_pe = _rope_pairs(kv[..., None, kv_rank:], pos_q, self.rope_theta)
        ckv_ctx = jnp.concatenate([ckv_cache, c_kv], axis=1)  # [B, S, kv_rank]
        kpe_ctx = jnp.concatenate(
            [kpe_cache.reshape(B, F, rope), k_pe[:, :, 0]], axis=1
        )
        S = F + T
        with jax.named_scope("core/latent_kv"):
            kv = _Linear(H * (nope + dv), self.dtype, name="kv_b_proj")(
                ckv_ctx
            ).reshape(B, S, H, nope + dv)
            k = jnp.concatenate(
                [
                    kv[..., :nope],
                    jnp.broadcast_to(
                        kpe_ctx.astype(self.dtype)[:, :, None], (B, S, H, rope)
                    ),
                ],
                -1,
            )
            v = kv[..., nope:]
        seg_ctx = jnp.concatenate([cache_seg, seg_q], axis=1)
        with jax.named_scope("core/attention_latent"):
            if self.kernel == "pallas" and T > 1:
                out = windowed_attention(
                    q, k, v, seg_q, seg_ctx, F, None, None, "attention_latent"
                )
            else:
                out = _masked_attention(q, k, v, seg_q, seg_ctx, F, None)
        out = _Linear(D, self.dtype, name="o_proj")(
            out.reshape(B, T, H * dv), F32
        )
        return out, ckv_ctx[:, -F:], kpe_ctx[:, -F:].reshape(kpe_cache.shape)


def _masked_attention(q, k_ctx, v_ctx, seg_q, seg_ctx, W, window):
    """Attention under the written-out mask (step mode, the CPU, a mesh):
    scores and softmax in float32, scaled by the query/key width."""
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
    return out.reshape(B, T, H, v_ctx.shape[-1]).astype(q.dtype)


class _Block(nn.Module):
    """One layer: mixer and MLP, each behind its norm, on the float32
    residual stream. `state` and the return's second element are the
    mixer's own part of the carry; the third is the rows routed to each
    held expert (`mlp="moe"`), or None."""

    kind: str
    mixer: dict
    mlp: dict
    norm: str
    eps: float
    dtype: Any = F32

    @nn.compact
    def __call__(self, x, state, first, seg_q, seg0, pos_q):
        def norm(name):
            if self.norm == "rms":
                return _RMSNorm(self.eps, name=name)
            return nn.LayerNorm(epsilon=self.eps, dtype=F32, name=name)

        u = norm(f"ln_{self.kind}")(x)
        if self.kind == "mamba":
            out, *new = _Mamba(dtype=self.dtype, name="mamba", **self.mixer)(
                u, *state, first, seg_q, seg0
            )
        elif self.kind == "short_conv":
            out, *new = _ShortConv(
                dtype=self.dtype, name="short_conv", **self.mixer
            )(u, *state, seg_q, seg0)
        elif self.kind == "latent":
            out, *new = _Latent(
                dtype=self.dtype, eps=self.eps, name="latent_attention",
                **self.mixer,
            )(u, *state, seg_q, pos_q)
        else:
            k_cache, v_cache, cache_seg = state
            out, *new = _Attention(
                kind=self.kind, dtype=self.dtype, eps=self.eps,
                name=f"{self.kind}_attention", **self.mixer,
            )(u, k_cache, v_cache, cache_seg, seg_q, pos_q)
        x = x + out
        u = norm("ln_mlp")(x)
        rows = None
        if "num_experts" in self.mlp:
            out, rows = _MoE(dtype=self.dtype, name="moe", **self.mlp)(u)
        else:
            out = _MLP(self.mlp["d_intermediate"], self.dtype, name="mlp")(u)
        return x + out, tuple(new), rows


class HybridCore(nn.Module):
    """Call with features `[T, B, F]` (time-major, like the other cores),
    `first` `[T, B]` and a `HybridCoreState`; returns (`[T, B, d_model]`
    float32, new state). Step mode is T=1. With `mlp="moe"` the unroll
    also sows, into the `intermediates` collection, `moe_held_rows` (rows
    routed to the held experts, summed over the layers) and
    `moe_load_max_share` (the largest held expert's rows in any layer over
    the share a balanced router gives it, `B*T*k / num_experts`)."""

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
    norm: str = "layer"  # "layer" | "rms"
    rope_theta: float = 1e6  # "full_rope" layers
    conv_taps: int = 3  # "short_conv" layers
    mlp: str = "gated"  # "gated" | "moe"
    num_experts: int = 32  # the router's width (mlp="moe")
    experts_per_token: int = 4
    experts_held: tuple = (0, 8)  # (first, count) of the experts held here
    expert_width: int = 1792
    dense_layers: int = 0  # mlp="moe": the leading blocks with a gated MLP
    shared_expert: int = 0  # mlp="moe": its width; 0 = none
    routed_scale: float = 1.0
    # "latent" layers: (q_rank, kv_rank, nope, rope, v); `num_heads` heads,
    # a cache of `full_cache` positions, rotary base `rope_theta`
    latent: tuple = ()
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

    def _count(self, *kinds: str) -> int:
        return sum(k in kinds for k in self.layers)

    def initial_state(self, batch_size: int) -> HybridCoreState:
        bad = [k for k in self.layers if k not in KINDS]
        if bad:
            raise ValueError(f"unknown layer kinds {bad}; expected {KINDS}")
        B, kv = batch_size, self.num_kv_heads * self.head_dim
        lm, lw = self._count("mamba"), self._count("window")
        lf, ls = self._count("full", "full_rope"), self._count("short_conv")
        ll = self._count("latent")
        kv_rank = rope = 0
        if ll:
            _, kv_rank, _, rope, _ = self.latent
            if self.full_cache * rope % 128:
                raise ValueError(
                    f"a latent cache of {self.full_cache} positions of "
                    f"{rope} rotary numbers does not fill rows of 128"
                )

        def zeros(n, *s):
            return jnp.zeros((B, n, *s), F32) if n else None

        def slots(n, width, fill):
            return jnp.full((B, width), fill, jnp.int32) if n else None

        return HybridCoreState(
            conv=zeros(lm, self.d_conv - 1, self.d_inner),
            ssm=zeros(lm, self.d_state, self.d_inner),
            k_win=zeros(lw, self.window, kv),
            v_win=zeros(lw, self.window, kv),
            win_seg=slots(lw, self.window, -1),
            win_pos=slots(lw, self.window, 0),
            k_full=zeros(lf, self.full_cache, kv),
            v_full=zeros(lf, self.full_cache, kv),
            full_seg=slots(lf + ll, self.full_cache, -1),
            full_pos=slots(lf + ll, self.full_cache, 0),
            pos=jnp.zeros((B,), jnp.int32),
            seg=jnp.zeros((B,), jnp.int32),
            sconv=zeros(ls, self.conv_taps - 1, self.d_model),
            lat_ckv=zeros(ll, self.full_cache, kv_rank),
            lat_kpe=zeros(ll, self.full_cache * rope // 128, 128),
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

        attention = dict(
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim, window=self.window,
            kernel=self.attention_kernel,
        )
        mixers = {
            "mamba": dict(
                d_inner=self.d_inner, d_state=self.d_state,
                d_conv=self.d_conv, dt_rank=self.dt_rank,
                scan_kernel=self.scan_kernel, scan_chunk=self.scan_chunk,
            ),
            "window": attention,
            "full": attention,
            "full_rope": dict(attention, rope_theta=self.rope_theta),
            "short_conv": dict(taps=self.conv_taps),
            "latent": dict(
                num_heads=self.num_heads, latent=tuple(self.latent),
                rope_theta=self.rope_theta, kernel=self.attention_kernel,
            ),
        }
        if self.mlp == "moe":
            mlp = dict(
                num_experts=self.num_experts,
                experts_per_token=self.experts_per_token,
                experts_held=tuple(self.experts_held),
                d_ff=self.expert_width,
                shared=self.shared_expert,
                routed_scale=self.routed_scale,
            )
        elif self.mlp == "gated":
            mlp = dict(d_intermediate=self.d_intermediate)
        else:
            raise ValueError(f"unknown mlp {self.mlp!r}; expected 'gated' or 'moe'")
        dense = dict(d_intermediate=self.d_intermediate)
        block_cls = nn.remat(_Block) if self.remat else _Block
        cache = {
            "mamba": "mamba", "window": "window", "full": "full",
            "full_rope": "full", "short_conv": "short_conv", "latent": "latent",
        }
        seen = dict.fromkeys(cache.values(), 0)
        new = {k: [] for k in seen}
        routed = []
        for i, kind in enumerate(self.layers):
            j = seen[cache[kind]]
            seen[cache[kind]] += 1
            if kind == "mamba":
                part = (state.conv[:, j], state.ssm[:, j])
            elif kind == "window":
                part = (state.k_win[:, j], state.v_win[:, j], state.win_seg)
            elif kind == "short_conv":
                part = (state.sconv[:, j],)
            elif kind == "latent":
                part = (state.lat_ckv[:, j], state.lat_kpe[:, j], state.full_seg)
            else:
                part = (state.k_full[:, j], state.v_full[:, j], state.full_seg)
            x, out, rows = block_cls(
                kind=kind,
                mixer=mixers[kind],
                mlp=dense if i < self.dense_layers else mlp,
                norm=self.norm,
                eps=self.ln_eps,
                dtype=self.dtype,
                name=f"block_{i}",
            )(x, part, first, seg_q, state.seg, pos_q)
            new[cache[kind]].append(out)
            if rows is not None:
                routed.append(rows)
        if self.norm == "rms":
            out = _RMSNorm(self.ln_eps, name="ln_out")(x)
        else:
            out = nn.LayerNorm(epsilon=self.ln_eps, dtype=F32, name="ln_out")(x)
        if routed:
            rows = jnp.stack(routed).astype(F32)  # [layers, held]
            fair = B * T * self.experts_per_token / self.num_experts
            self.sow("intermediates", "moe_held_rows", jnp.sum(rows))
            self.sow(
                "intermediates", "moe_load_max_share", jnp.max(rows) / fair
            )

        def stacked(kind, part, old):
            if not new[kind]:
                return old
            return jnp.stack([o[part] for o in new[kind]], axis=1)

        def slots(old, fresh, width):
            if old is None:
                return None
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
            sconv=stacked("short_conv", 0, state.sconv),
            lat_ckv=stacked("latent", 0, state.lat_ckv),
            lat_kpe=stacked("latent", 1, state.lat_kpe),
        )
        return out.transpose(1, 0, 2), new_state
