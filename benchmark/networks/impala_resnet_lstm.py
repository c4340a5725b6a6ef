"""The network `impala_resnet_lstm`: everything the benchmark knows about
the IMPALA deep ResNet torso (arXiv:1802.01561 fig. 3, large architecture),
an LSTM core with episode resets inside the unroll, and policy and value
heads. One flat set of functions of the configuration's file, which
`driver.Spec.network` finds by name and loads by path (`benchmark/README.md`
lists what a network file holds and what the harness calls when).

The reference's half is plain `jax.numpy` in float32 and imports nothing of
the program; the program's half (`to_program_params`, `leaf_groups`,
`stated`) reads plain trees and attributes handed to it.

Departure from the publication, stated: no language-instruction LSTM in the
DMLab-30 model (the program has none).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops, reference
from benchmark.reference import F32, rounded

REQUIRED_MODEL_KEYS = (
    "obs_shape", "num_actions", "num_tasks", "torso", "torso_dtype",
    "train_dtype", "channel_sections", "blocks_per_section", "fc_size",
    "use_lstm", "lstm_size",
)
STATE_STD = 0.5  # spread of an unroll's initial LSTM state


class Shapes(NamedTuple):
    """The sizes of one configuration, read from its file."""

    obs_shape: tuple
    num_actions: int
    num_values: int
    channel_sections: tuple
    blocks_per_section: int
    fc_size: int
    lstm_size: int  # 0: no recurrent core


def sizes(config: dict) -> Shapes:
    m = config["model"]
    return Shapes(
        obs_shape=tuple(m["obs_shape"]),
        num_actions=int(m["num_actions"]),
        num_values=int(m["num_tasks"]),
        channel_sections=tuple(m["channel_sections"]),
        blocks_per_section=int(m["blocks_per_section"]),
        fc_size=int(m["fc_size"]),
        lstm_size=int(m["lstm_size"]) if m["use_lstm"] else 0,
    )


def pooled(n: int) -> int:
    """Output extent of the 3x3 / stride-2 SAME max-pool."""
    return -(-n // 2)


def flat_features(s: Shapes) -> int:
    h, w, _ = s.obs_shape
    for _ in s.channel_sections:
        h, w = pooled(h), pooled(w)
    return h * w * s.channel_sections[-1]


# ---- weights from the seed ---------------------------------------------


def _param_shapes(s: Shapes) -> dict:
    def conv(cin, cout):
        return {"w": (3, 3, cin, cout), "b": (cout,)}

    sections, cin = [], s.obs_shape[-1]
    for ch in s.channel_sections:
        sections.append(
            {
                "conv": conv(cin, ch),
                "blocks": [
                    {"conv1": conv(ch, ch), "conv2": conv(ch, ch)}
                    for _ in range(s.blocks_per_section)
                ],
            }
        )
        cin = ch
    core = s.lstm_size or s.fc_size
    tree = {
        "sections": sections,
        "fc": {"w": (flat_features(s), s.fc_size), "b": (s.fc_size,)},
        "policy": {"w": (core, s.num_actions), "b": (s.num_actions,)},
        "value": {"w": (core, s.num_values), "b": (s.num_values,)},
    }
    if s.lstm_size:
        h = s.lstm_size
        tree["lstm"] = {
            "wi": (s.fc_size, 4 * h),
            "wh": (h, 4 * h),
            "b": (4 * h,),
        }
    return tree


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, s: Shapes):
    return reference.draw_leaves(key, _param_shapes(s))


def init_params(seed: int, config: dict) -> dict:
    """The reference's weights, made on the default device in one jitted
    call."""
    return _init(reference.seed_key(seed), sizes(config))


# ---- forward pass -------------------------------------------------------


def _conv(x, p):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )
    return y + p["b"]


def _max_pool(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )


def torso(params, obs, dtype=F32):
    """`[N, H, W, C]` pixels (uint8 scaled by 1/255) -> `[N, fc]`; weights
    and every layer's output stored in `dtype` (float32: the reference)."""
    q = functools.partial(rounded, dtype=dtype)
    params = jax.tree.map(q, params)
    x = obs.astype(F32)
    if obs.dtype == jnp.uint8:
        x = x / 255.0
    x = q(x)
    for sec in params["sections"]:
        x = q(_max_pool(_conv(x, sec["conv"])))
        for blk in sec["blocks"]:
            y = q(_conv(jax.nn.relu(x), blk["conv1"]))
            y = q(_conv(jax.nn.relu(y), blk["conv2"]))
            x = q(x + y)
    x = jax.nn.relu(x).reshape(x.shape[0], -1)
    return q(jax.nn.relu(x @ params["fc"]["w"] + params["fc"]["b"]))


def lstm_unroll(p, feats, first, c0, h0):
    """LSTM over `[T, B, F]` with the carry zeroed where `first` is set,
    BEFORE the cell sees that step. Gates along 4H are (i, f, g, o)."""
    hid = c0.shape[-1]

    def step(carry, xs):
        c, h = carry
        x, fst = xs
        keep = 1.0 - fst.astype(c.dtype)[:, None]
        c, h = c * keep, h * keep
        gates = (h @ p["wh"] + p["b"]) + x @ p["wi"]
        i = jax.nn.sigmoid(gates[:, :hid])
        f = jax.nn.sigmoid(gates[:, hid : 2 * hid])
        g = jnp.tanh(gates[:, 2 * hid : 3 * hid])
        o = jax.nn.sigmoid(gates[:, 3 * hid :])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        return (c, h), h

    _, out = jax.lax.scan(step, (c0, h0), (feats, first))
    return out


def forward(s: Shapes, params, obs, first, state, dtypes=(F32, F32)):
    """Unroll over `[T+1, B, ...]`: (policy logits `[T+1, B, A]`, values
    `[T+1, B, K]`). `state` is `(c, h)` at obs[0], or `()` with no core.
    `dtypes`: what the torso, and the core with the heads, are stored in.
    Every size is read off the weights; `s` is unused."""
    t, b = obs.shape[:2]
    torso_params = {k: params[k] for k in ("sections", "fc")}
    feats = torso(torso_params, obs.reshape(t * b, *obs.shape[2:]), dtypes[0])
    feats = feats.reshape(t, b, -1).astype(dtypes[1])
    params = jax.tree.map(
        lambda a: a.astype(dtypes[1]),
        {k: v for k, v in params.items() if k not in torso_params},
    )
    if "lstm" in params:
        c0, h0 = (x.astype(feats.dtype) for x in state)
        feats = lstm_unroll(params["lstm"], feats, first, c0, h0)
    logits = feats @ params["policy"]["w"] + params["policy"]["b"]
    values = feats @ params["value"]["w"] + params["value"]["b"]
    return logits.astype(F32), values.astype(F32)


# ---- the recurrent state of a generated unroll ---------------------------


def draw_state(rng, n: int, config: dict) -> tuple:
    """The states of `n` unrolls at their first observation, from the
    generator's `rng`: `(c0, h0)`, each `[n, 1, H]`, or `()` with no core."""
    m = config["model"]
    if not m["use_lstm"]:
        return ()
    h = int(m["lstm_size"])
    c0 = rng.standard_normal((n, 1, h), dtype=np.float32) * STATE_STD
    h0 = np.tanh(rng.standard_normal((n, 1, h), dtype=np.float32) * STATE_STD)
    return c0, h0


# ---- the program's side of the same tree ---------------------------------


def to_program_params(ref: dict) -> dict:
    """The reference's parameter tree in the program's leaf names (flax
    auto-names of `AtariDeepTorso`, `PallasLSTMCell`, the two heads)."""

    def named(p):
        return {"kernel": p["w"], "bias": p["b"]}

    torso: dict = {}
    block = 0
    for i, sec in enumerate(ref["sections"]):
        torso[f"Conv_{i}"] = named(sec["conv"])
        for blk in sec["blocks"]:
            torso[f"ResidualBlock_{block}"] = {
                "Conv_0": named(blk["conv1"]),
                "Conv_1": named(blk["conv2"]),
            }
            block += 1
    torso["Dense_0"] = named(ref["fc"])
    out = {
        "torso": torso,
        "policy_head": named(ref["policy"]),
        "value_head": named(ref["value"]),
    }
    if "lstm" in ref:
        hid = ref["lstm"]["wh"].shape[0]
        lstm = {}
        for j, gate in enumerate("ifgo"):
            cols = slice(j * hid, (j + 1) * hid)
            lstm[f"i{gate}"] = {"kernel": ref["lstm"]["wi"][:, cols]}
            lstm[f"h{gate}"] = {
                "kernel": ref["lstm"]["wh"][:, cols],
                "bias": ref["lstm"]["b"][cols],
            }
        out["lstm"] = lstm
    return {"params": out}


def leaf_groups(leaf_name: str) -> tuple:
    """The parts of the model a parameter leaf belongs to. As far as the
    configuration states precisions apart: the `torso` (its `torso_dtype`)
    or the `core` (recurrent core and heads, float32); within the core, the
    `lstm` (its gradient comes back through the whole unroll) or the
    `heads` (theirs does not)."""
    if "['torso']" in leaf_name:
        return ("torso",)
    return ("core", "lstm" if "['lstm']" in leaf_name else "heads")


def stated(config: dict, exp, net) -> dict:
    """{size: (the file's, the program's)} for what this network's file
    states: `exp` is the program's preset, `net` the network it built."""
    m, torso = config["model"], net.torso
    pairs = {
        "torso": (m["torso"], exp.model),
        "torso_dtype": (m["torso_dtype"], exp.compute_dtype),
        "use_lstm": (m["use_lstm"], exp.use_lstm),
        "channel_sections": (
            tuple(m["channel_sections"]),
            tuple(torso.channel_sections),
        ),
        "blocks_per_section": (
            m["blocks_per_section"],
            torso.blocks_per_section,
        ),
        "fc_size": (m["fc_size"], torso.hidden_size),
    }
    if m["use_lstm"]:
        pairs["lstm_size"] = (m["lstm_size"], exp.lstm_size)
    return pairs


# ---- precisions -----------------------------------------------------------


def stated_dtypes(config: dict) -> tuple:
    """What `forward`'s `dtypes` are as the configuration states them: the
    torso's, and the core's with the heads."""
    return config["model"]["torso_dtype"], config["model"]["train_dtype"]


# Which of `stated_dtypes` a control stores one precision below; the others
# are the reference's float32. `control` is the contract's: each part one
# below. `control_core` is the step that tempts most (a bfloat16 LSTM
# kernel or train step beside the torso as it is); no number separates it
# from a sound run, whose core is already fed by a bfloat16 torso (PERF.md
# section 2).
CONTROLS = {"control": (True, True), "control_core": (False, True)}


# ---- operations and bytes -------------------------------------------------


def taps_3x3_same(h: int, w: int) -> int:
    """Filter taps of a 3x3 / stride-1 SAME convolution that fall on the
    image, summed over its h x w outputs: the taps on the zero padding need
    no operation and are not counted (as XLA's own cost analysis does not)."""
    return (3 * h - 2) * (3 * w - 2)


def forward_macs_per_obs(s: Shapes) -> dict:
    """Multiply-accumulates of one observation's forward pass, by layer."""
    h, w, cin = s.obs_shape
    macs = {}
    for i, ch in enumerate(s.channel_sections):
        macs[f"section{i}.conv"] = taps_3x3_same(h, w) * cin * ch
        h, w = pooled(h), pooled(w)
        macs[f"section{i}.blocks"] = (
            s.blocks_per_section * 2 * taps_3x3_same(h, w) * ch * ch
        )
        cin = ch
    macs["fc"] = h * w * cin * s.fc_size
    core = s.fc_size
    if s.lstm_size:
        macs["lstm"] = (s.fc_size + s.lstm_size) * 4 * s.lstm_size
        core = s.lstm_size
    macs["heads"] = core * (s.num_actions + s.num_values)
    return macs


def step_flops(config: dict) -> float:
    """Model FLOPs of one learner step (`flops.step_flops`'s rule); the
    first convolution's input is data and needs no gradient."""
    macs = forward_macs_per_obs(sizes(config))
    return flops.step_flops(
        sum(macs.values()), macs["section0.conv"],
        config["unroll_length"], config["batch_size"],
    )


def lstm_unroll_forward(config: dict, chips: int) -> tuple:
    """(FLOPs, bytes) of one forward unroll of the LSTM core over the T+1
    observations of a batch's share of one chip.

    FLOPs: per step the two gate products, `[B, F] x [F, 4H]` and
    `[B, H] x [H, 4H]`. Bytes, float32, the least the unroll has to move
    to and from the chip's main memory: both weight matrices and the bias
    once, the features of every step in, the hidden state of every step
    out. (What an implementation saves for its backward pass is its own
    choice and is not counted; XLA keeps all of it in on-chip memory here.)
    At the benchmark's sizes the FLOPs are the larger bound."""
    m = config["model"]
    rows = int(config["batch_size"]) // chips
    steps = int(config["unroll_length"]) + 1
    feat, hid = int(m["fc_size"]), int(m["lstm_size"])
    n_flops = steps * 2.0 * rows * (feat + hid) * 4 * hid
    words = (feat + hid + 1) * 4 * hid + steps * rows * (feat + hid)
    return n_flops, 4.0 * words


OPS_AND_BYTES = {"lstm_unroll_forward": lstm_unroll_forward}
