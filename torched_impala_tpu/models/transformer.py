"""Transformer policy core: causal attention over the unroll time axis.

An alternative temporal core to the LSTM (the reference's recurrence is an
LSTM; SURVEY.md §6 notes that if a transformer policy were added, sharding
the time axis with collective-permute ring attention is the natural TPU
path — `parallel/ring_attention.py` and `parallel/ulysses.py` provide
those ops with this core's full attention semantics, and this core can
USE them: `attention="ring"|"ulysses"` with `sp_mesh=seq_mesh(n)`
computes the same attention (same params, same outputs — pinned by
tests/test_transformer.py) over a sequence-sharded unroll, the KV cache
riding along as the ops' replicated segment-gated `prefix_*` block;
rotary positions are applied at projection time, before attention.
Combined data+sequence parallelism works end-to-end: `sp_mesh` with
('data','seq') axes and `sp_batch_axis="data"` shards the batch and the
unroll simultaneously, and the unmodified Learner composes with it —
its data shardings + this core's internal seq shard_map produce the
identical loss/params as the dense single-device learner
(tests/test_transformer.py), reachable from the CLI via
`--dp N --sp M --transformer-attention ring`. When T isn't shardable —
param init, the actors' T=1 step mode — the core falls back to the
identical-output dense path). This core makes long-context policies
first-class:

- **unroll mode** processes the whole `[T, B]` unroll in parallel (no
  sequential scan — attention is the transformer's advantage on the MXU);
- **step mode** is the same code path with T=1, carrying a sliding-window
  KV cache as the recurrent state, so actors pay one cached-attention step
  per env step;
- **episode boundaries** are handled with segment ids: each row carries a
  running episode counter, queries attend only to cache/unroll entries
  from the same episode (the transformer analog of `hk.ResetCore`
  zeroing the LSTM carry);
- **positions** are rotary with absolute per-row step indices — relative
  offsets are what matters, caches store post-rotary keys.

State layout (all float32/int32, batch-major so the DP learner shards it
on axis 0 like any recurrent state):
  k_cache/v_cache `[B, L, W, D]`, kv_seg/kv_pos `[B, W]`,
  pos `[B]` next absolute index, seg `[B]` episode counter.
Fresh state has kv_seg = -1 (matches no real segment => empty context).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

NEG_INF = -1e30


class TransformerCoreState(NamedTuple):
    k_cache: jax.Array  # [B, L, W, D]
    v_cache: jax.Array  # [B, L, W, D]
    kv_seg: jax.Array  # [B, W] int32, -1 = empty slot
    kv_pos: jax.Array  # [B, W] int32 absolute positions
    pos: jax.Array  # [B] int32 next absolute position
    seg: jax.Array  # [B] int32 current episode counter


def rotary(x: jax.Array, positions: jax.Array) -> jax.Array:
    """Apply rotary embeddings. x `[..., H, Dh]`, positions broadcastable to
    x's leading dims (`[...]`). Angle math in f32; result in x's dtype (a
    bf16 x must not silently promote the whole K path to f32)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None, None] * freqs  # [...,1,half]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


class _Block(nn.Module):
    """Pre-LN transformer block; attention consumes explicit K/V + mask.

    `sp_ctx=None` computes dense attention over the pre-concatenated
    context with the explicit mask. With `sp_ctx` (a dict from
    TransformerCore) the SAME parameters compute the SAME attention
    through the sequence-parallel ops: the current-token KV becomes the
    sharded sequence, the cache becomes the replicated prefix block, and
    the core's visibility rules map onto the ops' causal + segment +
    prefix-segment masking exactly."""

    d_model: int
    num_heads: int
    mlp_factor: int = 4
    # Activation/matmul compute dtype (params stay f32; LayerNorms and
    # softmax run f32 regardless — see TransformerCore.dtype).
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self, x, k_ctx, v_ctx, mask, q_pos, sp_ctx=None, pallas_ctx=None
    ):
        """x `[B, T, D]` queries; k_ctx/v_ctx `[B, S, D]` context (cache +
        current tokens, already projected by THIS block's kv projections —
        see TransformerCore); mask `[B, T, S]` bool; q_pos `[B, T]` int32.

        `pallas_ctx` (dict with seg_q `[B, T]`, seg_ctx `[B, S]`, W)
        routes the dense path through the fused Pallas kernel
        (ops/attention_pallas.py) — same parameters, same outputs, the
        mask derived in-kernel from the segment ids instead of being
        materialized."""
        B, T, D = x.shape
        H = self.num_heads
        dh = D // H
        # LN stats in f32 for stability; output back in compute dtype.
        h = nn.LayerNorm(name="ln_attn")(
            x.astype(jnp.float32)
        ).astype(self.dtype)
        q = nn.Dense(D, dtype=self.dtype, name="q_proj")(h).reshape(
            B, T, H, dh
        )
        q = rotary(q, q_pos)
        if sp_ctx is not None:
            from torched_impala_tpu.parallel import (
                ring_attention_sharded,
                ulysses_attention_sharded,
            )

            op = {
                "ring": ring_attention_sharded,
                "ulysses": ulysses_attention_sharded,
            }[sp_ctx["kind"]]
            to_tb = lambda a: a.reshape(B, -1, H, dh).transpose(  # noqa: E731
                1, 0, 2, 3
            )
            out = op(
                q.transpose(1, 0, 2, 3),  # [T, B, H, dh]
                to_tb(sp_ctx["k_new"]),
                to_tb(sp_ctx["v_new"]),
                sp_ctx["mesh"],
                causal=True,
                segment_ids=sp_ctx["seg_q"].transpose(1, 0),  # [T, B]
                prefix_k=to_tb(sp_ctx["k_cache"]),  # [W, B, H, dh]
                prefix_v=to_tb(sp_ctx["v_cache"]),
                prefix_seg=sp_ctx["kv_seg"].transpose(1, 0),  # [W, B]
                batch_axis=sp_ctx["batch_axis"],
            )
            out = out.transpose(1, 0, 2, 3).reshape(B, T, D)
        elif pallas_ctx is not None:
            from torched_impala_tpu.ops.attention_pallas import (
                windowed_attention,
            )

            out = windowed_attention(
                q,
                k_ctx.reshape(B, -1, H, dh),
                v_ctx.reshape(B, -1, H, dh),
                pallas_ctx["seg_q"],
                pallas_ctx["seg_ctx"],
                pallas_ctx["W"],
            ).reshape(B, T, D)
        else:
            k = k_ctx.reshape(B, -1, H, dh)  # rotary'd at projection
            v = v_ctx.reshape(B, -1, H, dh)
            # bf16 operands ride the MXU fast path; logits accumulate and
            # softmax in f32 (identical math when dtype is f32).
            logits = jnp.einsum(
                "bthd,bshd->bhts",
                q,
                k,
                preferred_element_type=jnp.float32,
            ) / jnp.sqrt(float(dh))
            logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)
            attn = jax.nn.softmax(logits, axis=-1)
            # Fully-masked rows (empty context can't happen: self always
            # visible) — no special case needed.
            out = jnp.einsum(
                "bhts,bshd->bthd", attn.astype(self.dtype), v
            ).reshape(B, T, D)
        x = x + nn.Dense(D, dtype=self.dtype, name="o_proj")(
            out.astype(self.dtype)
        )
        h = nn.LayerNorm(name="ln_mlp")(
            x.astype(jnp.float32)
        ).astype(self.dtype)
        h = nn.Dense(self.mlp_factor * D, dtype=self.dtype, name="mlp_in")(h)
        h = nn.gelu(h)
        x = x + nn.Dense(D, dtype=self.dtype, name="mlp_out")(h)
        return x


class TransformerCore(nn.Module):
    """L pre-LN blocks over time with sliding-window KV cache.

    Call with features `[T, B, F]` (time-major, like the LSTM core),
    `first` `[T, B]`, and a `TransformerCoreState`; returns
    (`[T, B, d_model]`, new state). Step mode = T=1.
    """

    d_model: int = 256
    num_layers: int = 2
    num_heads: int = 4
    window: int = 128
    mlp_factor: int = 4
    # "dense" computes attention locally; "ring"/"ulysses" compute the
    # SAME attention (same params, same outputs) through the
    # sequence-parallel ops over `sp_mesh` — a ('seq',) mesh, or a
    # ('data','seq') mesh with sp_batch_axis="data" for combined DP+SP:
    # the unroll's T axis is sharded, the KV cache rides along as the
    # replicated prefix block. The 'seq' axis size must divide T
    # ("ulysses" also needs it to divide num_heads).
    attention: str = "dense"
    sp_mesh: Any = None
    # Optional second mesh axis to shard the BATCH over (combined
    # data+sequence parallelism: sp_mesh has ('data','seq') axes, the
    # unroll shards over 'seq' and the batch over sp_batch_axis='data').
    sp_batch_axis: Any = None
    # Dense-path attention math: "einsum" (XLA) or "pallas" (fused TPU
    # kernel, ops/attention_pallas.py — same params, same outputs, pinned
    # by tests/test_attention_pallas.py). Resolve 'auto' in the CALLER
    # against the actual compute devices (configs.make_agent does, like
    # the learner's V-trace resolution) — the core only accepts the two
    # concrete values. Step mode (T=1) always uses einsum: one cached-
    # attention step is too small to pay a kernel launch for.
    dense_kernel: str = "einsum"
    # Activation/matmul compute dtype for DENSE-configured cores
    # (bfloat16 puts every projection/MLP/attention matmul on the MXU
    # fast path, the same lever as the torsos' dtype). Params, LayerNorm
    # statistics, softmax, the KV-cache STATE, and the core's output stay
    # f32 — so state layout, checkpoints, and the value/policy heads are
    # dtype-independent. An SP-configured core (attention="ring"|
    # "ulysses") IGNORES this and computes f32 on EVERY path — including
    # its T=1 dense actor-step fallback, so actor and learner numerics
    # match — and warns if bf16 was requested.
    dtype: Any = jnp.float32

    def initial_state(self, batch_size: int) -> TransformerCoreState:
        B, L, W, D = batch_size, self.num_layers, self.window, self.d_model
        return TransformerCoreState(
            k_cache=jnp.zeros((B, L, W, D), jnp.float32),
            v_cache=jnp.zeros((B, L, W, D), jnp.float32),
            kv_seg=jnp.full((B, W), -1, jnp.int32),
            kv_pos=jnp.zeros((B, W), jnp.int32),
            pos=jnp.zeros((B,), jnp.int32),
            seg=jnp.zeros((B,), jnp.int32),
        )

    @nn.compact
    def __call__(self, features, first, state: TransformerCoreState):
        T, B, _ = features.shape
        W, L, D = self.window, self.num_layers, self.d_model

        first = first.transpose(1, 0)  # [B, T]
        # Segment id of each query step: running episode counter + starts
        # seen so far in this unroll (a step flagged `first` begins a NEW
        # segment, so the cumsum includes it).
        seg_q = state.seg[:, None] + jnp.cumsum(
            first.astype(jnp.int32), axis=1
        )  # [B, T]
        pos_q = state.pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]

        if self.attention not in ("dense", "ring", "ulysses"):
            raise ValueError(
                f"attention={self.attention!r}; expected 'dense', "
                "'ring', or 'ulysses'"
            )
        sp = self.attention != "dense"
        if sp and self.sp_mesh is None:
            raise ValueError(
                f"attention={self.attention!r} needs sp_mesh — a "
                "('seq',) mesh (parallel.seq_mesh) or a ('data','seq') "
                "mesh with sp_batch_axis='data'"
            )
        if sp:
            # SP shards the unroll's T axis; when T isn't shardable —
            # param init and the actors' step mode run this core at T=1 —
            # fall back to the (identical-output) dense path. SP only
            # pays off on long unrolls anyway. NOTE the learner re-forward
            # runs this core at T = unroll_length + 1 (the bootstrap
            # step), so choose unroll_length ≡ -1 (mod seq axis size).
            n_seq = dict(self.sp_mesh.shape).get("seq", 1)
            sp = T % n_seq == 0 and T >= n_seq > 1
            if not sp and T > 1:
                # A silent fallback on a long unroll means the seq devices
                # idle while the user believes SP is on — say so (once per
                # trace).
                import warnings

                warnings.warn(
                    f"attention={self.attention!r} requested but T={T} "
                    f"is not shardable over seq={n_seq} (learner T is "
                    "unroll_length+1); running the dense path",
                    stacklevel=2,
                )
        # Compute dtype keys off the CONFIGURED attention mode, not this
        # call's sp fallback: the SP ops run f32 (their collectives and
        # tests are pinned there), and if the T=1 actor-step fallback of
        # an SP-configured core ran bf16 while the learner's SP unroll
        # ran f32, behaviour and target logits would skew by bf16
        # rounding inside the V-trace ratios. So an SP-configured core is
        # f32 EVERYWHERE; the dtype lever applies to dense-configured
        # cores only. Like the T-shardability fallback above, a silent
        # override would leave the user believing bf16 is on — warn.
        sp_configured = self.attention != "dense"
        cdtype = jnp.float32 if sp_configured else self.dtype
        if sp_configured and jnp.dtype(self.dtype) != jnp.float32:
            import warnings

            warnings.warn(
                f"dtype={jnp.dtype(self.dtype).name} requested but "
                f"attention={self.attention!r} computes f32 on every "
                "path (incl. the T=1 dense fallback, so actor and "
                "learner numerics match); the bf16 lever applies to "
                "dense-configured cores only",
                stacklevel=2,
            )
        x = nn.Dense(D, dtype=cdtype, name="in_proj")(
            features.astype(cdtype)
        ).transpose(1, 0, 2)  # [B, T, D]

        if self.dense_kernel not in ("einsum", "pallas"):
            raise ValueError(
                f"dense_kernel={self.dense_kernel!r}; expected 'einsum' or "
                "'pallas' ('auto' must be resolved by the caller against "
                "its compute devices)"
            )
        use_pallas = self.dense_kernel == "pallas" and not sp and T > 1
        pallas_ctx = None
        if use_pallas:
            # Loop-invariant (every layer sees the same segments/window),
            # so build it once like the einsum mask below.
            pallas_ctx = {
                "seg_q": seg_q,
                "seg_ctx": jnp.concatenate([state.kv_seg, seg_q], axis=1),
                "W": W,
            }
        mask = None
        if not sp and not use_pallas:
            # Visibility masks (dense path; the SP ops derive the same
            # visibility from causal + segment + prefix-segment inputs).
            cache_vis = (
                seg_q[:, :, None] == state.kv_seg[:, None, :]
            )  # [B,T,W]
            causal = (
                jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
            )  # [T, T'] queries attend to earlier-or-self unroll steps
            intra_vis = (
                (seg_q[:, :, None] == seg_q[:, None, :])
                & causal[None, :, :]
            )  # [B, T, T]
            mask = jnp.concatenate(
                [cache_vis, intra_vis], axis=2
            )  # [B,T,W+T]

        new_k_layers = []
        new_v_layers = []
        for layer in range(L):
            # K/V of current tokens for this layer (cache stores post-
            # rotary keys; values raw).
            kv_in = nn.LayerNorm(name=f"ln_kv_{layer}")(
                x.astype(jnp.float32)
            ).astype(cdtype)
            k_new = nn.Dense(D, dtype=cdtype, name=f"k_proj_{layer}")(kv_in)
            k_new = rotary(
                k_new.reshape(B, T, self.num_heads, D // self.num_heads),
                pos_q,
            ).reshape(B, T, D)
            v_new = nn.Dense(D, dtype=cdtype, name=f"v_proj_{layer}")(kv_in)
            # Cache STATE stays f32 (layout contract above); cast the
            # read side into the compute dtype, the write side back.
            k_ctx = jnp.concatenate(
                [state.k_cache[:, layer].astype(cdtype), k_new], axis=1
            )  # [B, W+T, D]
            v_ctx = jnp.concatenate(
                [state.v_cache[:, layer].astype(cdtype), v_new], axis=1
            )
            sp_ctx = None
            if sp:
                sp_ctx = {
                    "kind": self.attention,
                    "mesh": self.sp_mesh,
                    "batch_axis": self.sp_batch_axis,
                    "k_new": k_new,
                    "v_new": v_new,
                    "k_cache": state.k_cache[:, layer],
                    "v_cache": state.v_cache[:, layer],
                    "seg_q": seg_q,
                    "kv_seg": state.kv_seg,
                }
            x = _Block(
                d_model=D,
                num_heads=self.num_heads,
                mlp_factor=self.mlp_factor,
                dtype=cdtype,
                name=f"block_{layer}",
            )(x, k_ctx, v_ctx, mask, pos_q, sp_ctx=sp_ctx,
              pallas_ctx=pallas_ctx)
            new_k_layers.append(k_ctx[:, -W:].astype(jnp.float32))
            new_v_layers.append(v_ctx[:, -W:].astype(jnp.float32))

        out = nn.LayerNorm(name="ln_out")(x.astype(jnp.float32))

        combined_seg = jnp.concatenate(
            [state.kv_seg, seg_q], axis=1
        )[:, -W:]
        combined_pos = jnp.concatenate(
            [state.kv_pos, pos_q], axis=1
        )[:, -W:]
        new_state = TransformerCoreState(
            k_cache=jnp.stack(new_k_layers, axis=1),
            v_cache=jnp.stack(new_v_layers, axis=1),
            kv_seg=combined_seg,
            kv_pos=combined_pos,
            pos=state.pos + T,
            seg=seg_q[:, -1],
        )
        return out.transpose(1, 0, 2), new_state
