"""The network `nature_cnn_transformer`, as files alone: the Nature CNN
torso (three VALID convolutions and a 512-wide layer) and the program's
pre-LN transformer core over the unroll (preset `pong_transformer`: d_model
256, 2 layers, 4 heads, a cache of 128 steps), with policy and value heads.

It exists to prove the seam (`test_benchmark_second_network.py`): a second
network enters the harness as this file, a configuration, a mix, a cell
and limits, with no harness file touched. No cell of `BENCHMARK.json` runs
it.

The reference's half follows what `models/transformer.py` documents and
imports nothing of it. Per unroll of T+1 steps, each row carries: a cache of
the last W steps' keys (rotated at projection time) and values per layer,
each slot's episode counter (`-1`: empty) and absolute position, the next
absolute position and the running episode counter. A step flagged `first`
starts a new episode: the counter goes up at that step, and a query sees a
cache slot or an earlier-or-same step of the unroll only where the episode
counters are equal. Every cache slot of the query's episode is visible and
the unroll is causal: the window bounds the cache, not the unroll. Blocks
are pre-LN: x + attention(LN(x)), then x + MLP(LN(x)) with a tanh GELU and
a factor of 4; keys and values of a layer come from their own LN of that
layer's input; rotary positions over half the head's width, base 10000; a
last LN before the heads.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops, reference
from benchmark.reference import F32, rounded

REQUIRED_MODEL_KEYS = (
    "obs_shape", "num_actions", "num_tasks", "torso", "torso_dtype",
    "train_dtype", "fc_size", "core", "core_dtype", "d_model", "num_layers",
    "num_heads", "window", "mlp_factor",
)
CONVS = ((8, 4, 32), (4, 2, 64), (3, 1, 64))  # (filter, stride, channels), VALID
CACHE_STD = 0.5  # spread of the cached keys and values of a generated unroll
NEG_INF = -1e30
LN_EPS = 1e-6


class Sizes(NamedTuple):
    obs_shape: tuple
    num_actions: int
    num_values: int
    fc_size: int
    d_model: int
    num_layers: int
    num_heads: int
    window: int
    mlp_factor: int


def sizes(config: dict) -> Sizes:
    m = config["model"]
    return Sizes(
        tuple(m["obs_shape"]), int(m["num_actions"]), int(m["num_tasks"]),
        *(int(m[k]) for k in Sizes._fields[3:]),
    )


def conv_extents(s: Sizes) -> list:
    """(h, w) of each convolution's output."""
    (h, w, _), out = s.obs_shape, []
    for k, stride, _ in CONVS:
        h, w = (h - k) // stride + 1, (w - k) // stride + 1
        out.append((h, w))
    return out


# ---- weights from the seed ---------------------------------------------


def _param_shapes(s: Sizes) -> dict:
    def dense(n_in, n_out):
        return {"w": (n_in, n_out), "b": (n_out,)}

    d = s.d_model
    ln = {"g": (d,), "b": (d,)}
    convs, cin = [], s.obs_shape[-1]
    for k, _, ch in CONVS:
        convs.append({"w": (k, k, cin, ch), "b": (ch,)})
        cin = ch
    h, w = conv_extents(s)[-1]
    layer = {
        "ln_kv": ln, "k": dense(d, d), "v": dense(d, d),
        "ln_attn": ln, "q": dense(d, d), "o": dense(d, d),
        "ln_mlp": ln, "mlp_in": dense(d, s.mlp_factor * d),
        "mlp_out": dense(s.mlp_factor * d, d),
    }
    return {
        "convs": convs,
        "fc": dense(h * w * cin, s.fc_size),
        "core": {
            "in": dense(s.fc_size, d),
            "layers": [layer for _ in range(s.num_layers)],
            "ln_out": ln,
        },
        "policy": dense(d, s.num_actions),
        "value": dense(d, s.num_values),
    }


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, s: Sizes):
    params = reference.draw_leaves(key, _param_shapes(s))

    def about_one(path, leaf):  # a LayerNorm's scale: 1 + the small draw
        return 1.0 + leaf if path[-1].key == "g" else leaf

    return jax.tree_util.tree_map_with_path(about_one, params)


def init_params(seed: int, config: dict) -> dict:
    return _init(reference.seed_key(seed), sizes(config))


# ---- forward pass -------------------------------------------------------


def torso(params, obs, dtype=F32):
    """`[N, H, W, C]` pixels (uint8 scaled by 1/255) -> `[N, fc]`."""
    q = functools.partial(rounded, dtype=dtype)
    params = jax.tree.map(q, params)
    x = obs.astype(F32)
    if obs.dtype == jnp.uint8:
        x = x / 255.0
    x = q(x)
    for p, (_, stride, _) in zip(params["convs"], CONVS):
        y = jax.lax.conv_general_dilated(
            x, p["w"], (stride, stride), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        x = q(jax.nn.relu(y + p["b"]))
    x = x.reshape(x.shape[0], -1)
    return q(jax.nn.relu(x @ params["fc"]["w"] + params["fc"]["b"]))


def _dense(p, x):
    return x @ p["w"] + p["b"]


def _ln(p, x):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["g"] + p["b"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x * x * x)
    ))


def rotary(x, positions):
    """`x` `[B, T, H, Dh]` turned by its row's absolute positions `[B, T]`."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (10000.0 ** (jnp.arange(half, dtype=F32) / half))
    angles = positions.astype(F32)[..., None, None] * freqs
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half], x[..., half:]
    turned = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return turned.astype(x.dtype)


def core_unroll(s: Sizes, p, feats, first, state):
    """`[T, B, F]` features -> `[T, B, D]`, from the cache in `state`."""
    k_cache, v_cache, kv_seg, _, pos, seg = state
    t, b = feats.shape[:2]
    heads = (b, -1, s.num_heads, s.d_model // s.num_heads)
    seg_q = seg[:, None] + jnp.cumsum(first.T.astype(jnp.int32), axis=1)
    pos_q = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    in_cache = seg_q[:, :, None] == kv_seg[:, None, :]  # [B, T, W]
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    in_unroll = (seg_q[:, :, None] == seg_q[:, None, :]) & causal[None]
    mask = jnp.concatenate([in_cache, in_unroll], axis=2)[:, None]
    x = _dense(p["in"], feats.transpose(1, 0, 2))  # [B, T, D]
    for i, layer in enumerate(p["layers"]):
        kv_in = _ln(layer["ln_kv"], x)
        k_new = rotary(_dense(layer["k"], kv_in).reshape(heads), pos_q)
        v_new = _dense(layer["v"], kv_in).reshape(heads)
        keys = jnp.concatenate(
            [k_cache[:, i].astype(x.dtype).reshape(heads), k_new], axis=1
        )
        values = jnp.concatenate(
            [v_cache[:, i].astype(x.dtype).reshape(heads), v_new], axis=1
        )
        q = rotary(_dense(layer["q"], _ln(layer["ln_attn"], x)).reshape(heads), pos_q)
        scores = jnp.einsum("bthd,bshd->bhts", q, keys) / math.sqrt(heads[-1])
        attn = jax.nn.softmax(jnp.where(mask, scores, NEG_INF), axis=-1)
        out = jnp.einsum("bhts,bshd->bthd", attn, values).reshape(b, t, -1)
        x = x + _dense(layer["o"], out)
        hidden = _gelu(_dense(layer["mlp_in"], _ln(layer["ln_mlp"], x)))
        x = x + _dense(layer["mlp_out"], hidden)
    return _ln(p["ln_out"], x).transpose(1, 0, 2)


def forward(s: Sizes, params, obs, first, state, dtypes=(F32, F32)):
    """Unroll over `[T+1, B, ...]`: (policy logits `[T+1, B, A]`, values
    `[T+1, B, K]`). `dtypes`: what the torso, and the core with the heads,
    are stored in."""
    t, b = obs.shape[:2]
    feats = torso(
        {k: params[k] for k in ("convs", "fc")},
        obs.reshape(t * b, *obs.shape[2:]), dtypes[0],
    )
    feats = feats.reshape(t, b, -1).astype(dtypes[1])
    rest = jax.tree.map(
        lambda a: a.astype(dtypes[1]),
        {k: params[k] for k in ("core", "policy", "value")},
    )
    out = core_unroll(s, rest["core"], feats, first, state)
    return (
        _dense(rest["policy"], out).astype(F32),
        _dense(rest["value"], out).astype(F32),
    )


# ---- the recurrent state of a generated unroll ---------------------------


def draw_state(rng, n: int, config: dict) -> tuple:
    """The caches of `n` unrolls at their first observation, each array
    with a row axis of one after the unroll's: keys and values
    `[n, 1, L, W, D]`, the slots' episode counters and positions
    `[n, 1, W]`, next position and episode counter `[n, 1]`. The newest
    `m` slots (0 to W, by unroll) hold the running episode; older slots
    are empty or hold the episode before it, which no query may see."""
    s = sizes(config)
    w = s.window
    shape = (n, 1, s.num_layers, w, s.d_model)
    k_cache = rng.standard_normal(shape, dtype=np.float32) * CACHE_STD
    v_cache = rng.standard_normal(shape, dtype=np.float32) * CACHE_STD
    held = rng.integers(0, w + 1, size=(n, 1, 1))
    seg = rng.integers(1, 1000, size=(n, 1)).astype(np.int32)
    older = np.where(rng.random((n, 1, 1)) < 0.5, -1, seg[..., None] - 1)
    pos = (held[..., 0] + rng.integers(0, 1000, size=(n, 1))).astype(np.int32)
    slot = np.arange(w)[None, None, :]
    running = slot >= w - held
    kv_seg = np.where(running, seg[..., None], older).astype(np.int32)
    kv_pos = np.where(running, pos[..., None] - (w - slot), 0).astype(np.int32)
    return k_cache, v_cache, kv_seg, kv_pos, pos, seg


# ---- the program's side of the same tree ---------------------------------


def to_program_params(ref: dict) -> dict:
    """The reference's tree in the program's leaf names (flax names of
    `AtariShallowTorso`, `TransformerCore` and its blocks, the heads)."""

    def dense(p):
        return {"kernel": p["w"], "bias": p["b"]}

    def ln(p):
        return {"scale": p["g"], "bias": p["b"]}

    torso = {f"Conv_{i}": dense(p) for i, p in enumerate(ref["convs"])}
    torso["Dense_0"] = dense(ref["fc"])
    core = {"in_proj": dense(ref["core"]["in"]), "ln_out": ln(ref["core"]["ln_out"])}
    for i, layer in enumerate(ref["core"]["layers"]):
        core[f"ln_kv_{i}"] = ln(layer["ln_kv"])
        core[f"k_proj_{i}"] = dense(layer["k"])
        core[f"v_proj_{i}"] = dense(layer["v"])
        core[f"block_{i}"] = {
            "ln_attn": ln(layer["ln_attn"]),
            "q_proj": dense(layer["q"]),
            "o_proj": dense(layer["o"]),
            "ln_mlp": ln(layer["ln_mlp"]),
            "mlp_in": dense(layer["mlp_in"]),
            "mlp_out": dense(layer["mlp_out"]),
        }
    return {
        "params": {
            "torso": torso,
            "transformer": core,
            "policy_head": dense(ref["policy"]),
            "value_head": dense(ref["value"]),
        }
    }


def leaf_groups(leaf_name: str) -> tuple:
    if "['torso']" in leaf_name:
        return ("torso",)
    return ("core", "blocks" if "['transformer']" in leaf_name else "heads")


def stated(config: dict, exp, net) -> dict:
    m, core = config["model"], dict(net.transformer)
    return {
        "torso": (m["torso"], exp.model),
        "torso_dtype": (m["torso_dtype"], exp.compute_dtype),
        "core": (m["core"], exp.core),
        "core_dtype": (m["core_dtype"], exp.transformer_dtype),
        "attention": ("dense", exp.transformer_attention),
        "d_model": (m["d_model"], core["d_model"]),
        "num_layers": (m["num_layers"], core["num_layers"]),
        "num_heads": (m["num_heads"], core["num_heads"]),
        "window": (m["window"], core["window"]),
        "mlp_factor": (m["mlp_factor"], 4),  # the program's default; no preset sets it
    }


def stated_dtypes(config: dict) -> tuple:
    return config["model"]["torso_dtype"], config["model"]["core_dtype"]


CONTROLS = {"control": (True, True)}


# ---- operations and bytes -------------------------------------------------


def forward_macs_per_obs(s: Sizes, unroll_length: int) -> dict:
    """Multiply-accumulates of one observation's forward pass, by layer.
    Attention, per query and layer: scores and the weighted sum over the W
    cache slots and the causal half of the T+1 steps of its unroll, a mean
    of (T+2)/2 (every slot counted as visible: the most a query needs)."""
    d, cin, macs = s.d_model, s.obs_shape[-1], {}
    for i, ((k, _, ch), (h, w)) in enumerate(zip(CONVS, conv_extents(s))):
        macs[f"conv{i}"] = h * w * k * k * cin * ch
        cin = ch
    macs["fc"] = h * w * cin * s.fc_size
    macs["core.in"] = s.fc_size * d
    macs["core.projections"] = s.num_layers * 4 * d * d
    macs["core.mlp"] = s.num_layers * 2 * s.mlp_factor * d * d
    macs["core.attention"] = (
        s.num_layers * 2 * d * (s.window + (unroll_length + 2) / 2)
    )
    macs["heads"] = d * (s.num_actions + s.num_values)
    return macs


def step_flops(config: dict) -> float:
    macs = forward_macs_per_obs(sizes(config), config["unroll_length"])
    return flops.step_flops(
        sum(macs.values()), macs["conv0"],
        config["unroll_length"], config["batch_size"],
    )


OPS_AND_BYTES = {}  # the einsum path has no kernel of its own to time
