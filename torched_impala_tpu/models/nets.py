"""ImpalaNet: torso + optional LSTM reset core + policy/value heads (Flax).

Two-mode API mirroring the analog's `AtariNet` (`haiku_nets.py:133-172`) and
the reference's `nn.Module.forward(obs, core_state)` (SURVEY.md §2 Agent row):

- step:   `[B, ...]` single timestep for actors;
- unroll: `[T, B, ...]` time-major re-forward for the learner, with the
  recurrent core driven by `lax.scan` (via `nn.scan` so both modes share
  parameters) and episode-start resets applied to the carry *inside* the scan
  — the `hk.ResetCore` semantics (`haiku_nets.py:141,159-161`).

TPU notes: the torso is applied to the whole `[T*B, ...]` batch in one call
(one big MXU-friendly conv/matmul batch, no per-step Python loop); only the
LSTM recurrence is sequential, as a single fused XLA while-loop.

The value head is always a `num_values`-wide Dense named "value_head" so the
PopArt rescaling in `ops/popart.py` can address its kernel/bias by a stable
path; with PopArt enabled its outputs are *normalized* values.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp

NetState = Any  # LSTM carry tuple, or () for feedforward nets.


class NetOutput(NamedTuple):
    """Policy logits `[..., A]` and values `[..., num_values]` (float32)."""

    policy_logits: jax.Array
    values: jax.Array


def _reset_carry(carry, initial_carry, first: jax.Array):
    """Replace carry rows with the initial carry where `first` is set."""

    def sel(c, c0):
        m = first.reshape(first.shape + (1,) * (c.ndim - first.ndim))
        return jnp.where(m, c0, c)

    return jax.tree.map(sel, carry, initial_carry)


def _core_step(cell: nn.Module, carry, inputs):
    """One recurrent step with episode-boundary reset; scanned over time."""
    x, first = inputs
    zero_carry = jax.tree.map(jnp.zeros_like, carry)
    carry = _reset_carry(carry, zero_carry, first)
    carry, out = cell(carry, x)
    return carry, out


class ImpalaNet(nn.Module):
    """Policy network: `torso` feature extractor, optional temporal core,
    heads.

    Attributes:
      num_actions: size of the categorical action space.
      torso: a Flax module mapping `[N, ...obs]` → `[N, F]` features.
      use_lstm: insert an LSTM(lstm_size) core between torso and heads
        (equivalent to core="lstm"; kept for the reference-parity surface).
      core: "none" | "lstm" | "transformer" | "hybrid" — the temporal
        core. The transformer core (models/transformer.py) attends causally
        over the unroll with a sliding-window KV cache as its recurrent state
        (long-context policies; SP-ready, see parallel/ring_attention.py).
        The hybrid core (models/hybrid.py) stacks state-space, window- and
        full-attention layers; its carry holds scan states, convolution
        windows and two lengths of key/value cache.
      lstm_size: LSTM hidden width (reference uses 256, SURVEY.md §1 item 4).
      transformer: TransformerCore hyper-parameters, used when
        core="transformer" (a dict so the module stays hashable; keys are
        TransformerCore fields).
      hybrid: HybridCore hyper-parameters, used when core="hybrid" (the
        same kind of tuple; keys are HybridCore fields).
      lstm_impl: "fused" (default) computes the LSTM cell with the
        single-pass Pallas kernel (ops/lstm_pallas.py; interpret mode
        off-TPU), "flax" keeps nn.OptimizedLSTMCell. Both produce a
        bitwise-identical param tree and outputs within ~1 ulp in f32 —
        an escape hatch, not a checkpoint fork (tests/test_pallas_lstm.py
        pins the tolerance).
      num_values: width of the value head (1, or num_tasks under PopArt).
    """

    num_actions: int
    torso: nn.Module
    use_lstm: bool = False
    core: str = "auto"  # "auto" resolves via use_lstm for back-compat
    lstm_size: int = 256
    transformer: tuple = ()  # e.g. (("d_model", 128), ("num_layers", 2))
    hybrid: tuple = ()  # e.g. (("d_model", 64), ("layers", ("mamba", "full")))
    lstm_impl: str = "fused"
    num_values: int = 1

    def _core_kind(self) -> str:
        if self.core != "auto":
            return self.core
        return "lstm" if self.use_lstm else "none"

    def _transformer_core(self, *, bound: bool):
        """`bound=True` names the submodule (only legal inside apply);
        `bound=False` builds an anonymous instance for pure config-only
        methods like initial_state (flax forbids `name=` outside a parent
        module context)."""
        from torched_impala_tpu.models.transformer import TransformerCore

        kwargs = dict(self.transformer)
        if bound:
            return TransformerCore(name="transformer", **kwargs)
        # parent=None detaches the instance from the calling module context
        # (initial_state runs inside a flax-wrapped method, which would
        # otherwise try to adopt the child into a scopeless parent).
        return TransformerCore(parent=None, **kwargs)

    def _hybrid_core(self, *, bound: bool):
        """As `_transformer_core`, for core="hybrid"."""
        from torched_impala_tpu.models.hybrid import HybridCore

        kwargs = dict(self.hybrid)
        if bound:
            return HybridCore(name="hybrid", **kwargs)
        return HybridCore(parent=None, **kwargs)

    def initial_state(self, batch_size: int) -> NetState:
        """Zero recurrent state; a pure function of the config (no params)."""
        kind = self._core_kind()
        if kind == "none":
            return ()
        if kind == "transformer":
            return self._transformer_core(bound=False).initial_state(
                batch_size
            )
        if kind == "hybrid":
            return self._hybrid_core(bound=False).initial_state(batch_size)
        shape = (batch_size, self.lstm_size)
        return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))

    def _heads(self, core_out: jax.Array) -> NetOutput:
        core_out = core_out.astype(jnp.float32)
        logits = nn.Dense(self.num_actions, name="policy_head")(core_out)
        values = nn.Dense(self.num_values, name="value_head")(core_out)
        return NetOutput(policy_logits=logits, values=values)

    @nn.compact
    def __call__(
        self,
        obs: jax.Array,
        first: jax.Array,
        state: NetState,
        *,
        unroll: bool = False,
    ) -> tuple[NetOutput, NetState]:
        """Apply the net.

        Args:
          obs: `[B, ...]` (step mode) or `[T, B, ...]` (unroll mode).
          first: bool `[B]` / `[T, B]` episode-start flags; resets the core.
          state: recurrent carry from `initial_state` or a previous call.
          unroll: static mode switch (two jit specializations, shared params).

        Returns:
          (NetOutput, new_state) with leading dims matching the mode.
        """
        if unroll:
            t, b = obs.shape[:2]
            features = self.torso(obs.reshape(t * b, *obs.shape[2:]))
            features = features.reshape(t, b, -1)
        else:
            features = self.torso(obs)

        kind = self._core_kind()
        if kind in ("transformer", "hybrid"):
            core = (
                self._transformer_core(bound=True)
                if kind == "transformer"
                else self._hybrid_core(bound=True)
            )
            if unroll:
                core_out, state = core(features, first, state)
            else:
                # Step mode is the T=1 unroll; the caches (and the hybrid
                # core's scan states and convolution windows) are the carry.
                core_out, state = core(
                    features[None], first[None], state
                )
                core_out = core_out[0]
        elif kind == "lstm":
            # The recurrent core runs in float32 regardless of the torso's
            # compute dtype (bf16 torsos feed f32 features): the scan carry
            # dtype must be stable across steps, and the LSTM is a
            # negligible share of the FLOPs next to the convs on the MXU.
            features = features.astype(jnp.float32)
            if self.lstm_impl == "flax":
                cell = nn.OptimizedLSTMCell(self.lstm_size, name="lstm")
            elif self.lstm_impl == "fused":
                from torched_impala_tpu.models.lstm import PallasLSTMCell

                cell = PallasLSTMCell(self.lstm_size, name="lstm")
            else:
                raise ValueError(
                    f"unknown lstm_impl {self.lstm_impl!r}; "
                    "expected 'fused' or 'flax'"
                )
            if unroll:
                scan = nn.scan(
                    _core_step,
                    variable_broadcast="params",
                    split_rngs={"params": False},
                    in_axes=0,
                    out_axes=0,
                )
                state, core_out = scan(cell, state, (features, first))
            else:
                state, core_out = _core_step(cell, state, (features, first))
        else:
            core_out = features

        return self._heads(core_out), state
