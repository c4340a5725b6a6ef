"""The network `nature_cnn_lfm2moe`: the Nature CNN torso (three VALID
convolutions and a 512-wide layer) under a temporal core cut from LiquidAI's
LFM2-8B-A1B (huggingface.co/LiquidAI/LFM2-8B-A1B `config.json`,
`model_type` `lfm2_moe`), with policy and value heads. Preset
`pong_lfm2_moe`; configuration `pong_lfm2_moe_core`.

What the reference computes, written from the published configuration and
the family's description, importing nothing of the program. The core takes
the torso's features through a `[fc, hidden]` projection (no token
embedding, no LM head: the decoder is an agent's recurrence) and runs
`layers` (the published layers 2-5: full attention, then three short
convolutions) on a float32 residual stream; every block is

    h = x + Mixer(RMSNorm(x));  y = h + MoE(RMSNorm(h))

with RMSNorm `x / sqrt(mean(x^2) + norm_eps) * g`, then a last RMSNorm.

- `full_rope`: softmax attention over `q.k / sqrt(head_dim)`, 32 query
  heads on 8 key/value heads of 64 (query head h reads key/value head
  h // 4), no projection biases; q and k each pass an RMSNorm over the
  head (*assumed*: the family's `q_layernorm` / `k_layernorm`; the config
  has no key for them) and then rotary embeddings of base `rope_theta`,
  rotate-half, at each position's absolute step. The cache holds keys as
  rotated. A key is seen where its episode counter equals the query's and
  its position is not after the query's, in the cache or the unroll.
- `short_conv` (the family's gated short convolution): `[B, C, x] = W_in u`
  in that order (*assumed*: the config does not say), `y = C * conv(B * x)`
  with a causal depthwise convolution of `conv_L_cache` taps and no bias
  (`conv_bias` false), out `= W_out y`. A plain `lax.scan` over time whose
  carry is the last `conv_L_cache - 1` gated inputs; where `first[t]` is set
  they are zeroed before the step.
- the mixture of experts, every layer of the cut (each follows the
  `num_dense_layers` leading dense layers): `s = sigmoid(W_r u)` over all
  `router_experts` experts (*assumed*: sigmoid scoring), the top
  `num_experts_per_tok` of `s + expert_bias` chosen (*assumed*: the bias
  only chooses, `use_expert_bias`), their `s` divided by their sum plus
  1e-6 (`norm_topk_prob`; the 1e-6 *assumed*) and times
  `routed_scaling_factor`; each expert `W2 (silu(W1 u) * W3 u)` of width
  `moe_intermediate_size`. **The chip's share**: the layer holds the
  experts `experts_held_first .. + num_experts - 1` of the 32 and adds only
  their part, a dense sum over the tokens with each held expert's weight
  (zero where it was not chosen); what the experts held on the other three
  chips would add is left out, here and in the program alike.

State of a generated unroll (`draw_state`, the order of the program's
`HybridCoreState` leaves): the full layer's keys and values
`[full_cache, kv * head_dim]` with the slots' episode counters and
absolute positions, the next position, the running episode counter, and
per short-convolution layer its last `conv_L_cache - 1` gated inputs
`[2, hidden]`.

`dtypes` of `forward`: what the torso is stored in, what the operands of
the core's matrix products (the experts' among them) are stored in, and
what the rest of the core (the norms, rotary embeddings, softmax, the
router's scores and choice, the residual stream) is stored in. The
configuration states bfloat16, bfloat16, float32; the reference runs all
three in float32; the control lowers each by one step.

Memory of the reference at the cell's size (one row, 2,048 positions):
every block is under `jax.checkpoint`, attention goes one key/value head
at a time and the experts one at a time.
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
    "train_dtype", "fc_size", "core", "core_dtype", "core_state_dtype",
    "hidden_size", "layers", "num_attention_heads", "num_key_value_heads",
    "head_dim", "full_cache", "conv_L_cache", "rope_theta", "norm_eps",
    "router_experts", "num_experts", "experts_held_first",
    "num_experts_per_tok", "moe_intermediate_size", "routed_scaling_factor",
)
CONVS = ((8, 4, 32), (4, 2, 64), (3, 1, 64))  # (filter, stride, channels), VALID
CACHE_STD = 0.5  # spread of the cached keys, values and inputs of an unroll
NEG_INF = -1e30
SCAN_UNROLL = 8  # steps of the convolution's scan traced into one iteration
# The cut's layer kinds by block (`stated` holds the file to them).
LAYERS = ("full_rope", "short_conv", "short_conv", "short_conv")


class Sizes(NamedTuple):
    obs_shape: tuple
    num_actions: int
    num_values: int
    layers: tuple
    norm_eps: float
    rope_theta: float
    routed_scaling_factor: float
    fc_size: int
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    full_cache: int
    conv_L_cache: int
    router_experts: int
    num_experts: int  # held here
    experts_held_first: int
    num_experts_per_tok: int
    moe_intermediate_size: int


def sizes(config: dict) -> Sizes:
    m = config["model"]
    return Sizes(
        tuple(m["obs_shape"]), int(m["num_actions"]), int(m["num_tasks"]),
        tuple(m["layers"]), float(m["norm_eps"]), float(m["rope_theta"]),
        float(m["routed_scaling_factor"]),
        *(int(m[k]) for k in Sizes._fields[7:]),
    )


def conv_extents(s: Sizes) -> list:
    """(h, w) of each convolution's output."""
    (h, w, _), out = s.obs_shape, []
    for k, stride, _ in CONVS:
        h, w = (h - k) // stride + 1, (w - k) // stride + 1
        out.append((h, w))
    return out


def _count(s: Sizes, kind: str) -> int:
    return sum(k == kind for k in s.layers)


# ---- weights from the seed ---------------------------------------------


def _param_shapes(s: Sizes) -> dict:
    def dense(n_in, n_out):
        return {"w": (n_in, n_out), "b": (n_out,)}

    def matrix(n_in, n_out):
        return {"w": (n_in, n_out)}

    d, f, e = s.hidden_size, s.moe_intermediate_size, s.num_experts
    norm = {"g": (d,)}
    convs, cin = [], s.obs_shape[-1]
    for k, _, ch in CONVS:
        convs.append({"w": (k, k, cin, ch), "b": (ch,)})
        cin = ch
    h, w = conv_extents(s)[-1]
    moe = {
        "ffn_norm": norm, "router": matrix(d, s.router_experts),
        "expert_bias": (s.router_experts,),
        "gate": (e, d, f), "up": (e, d, f), "down": (e, f, d),
    }
    hq = s.num_attention_heads * s.head_dim
    hkv = s.num_key_value_heads * s.head_dim
    attention = {
        "norm": norm, "q": matrix(d, hq), "k": matrix(d, hkv),
        "v": matrix(d, hkv), "o": matrix(hq, d),
        "q_norm": {"g": (s.head_dim,)}, "k_norm": {"g": (s.head_dim,)}, **moe,
    }
    short_conv = {
        "norm": norm, "in": matrix(d, 3 * d), "conv": {"w": (s.conv_L_cache, d)},
        "out": matrix(d, d), **moe,
    }
    return {
        "convs": convs,
        "fc": dense(h * w * cin, s.fc_size),
        "core": {
            "in": dense(s.fc_size, d),
            "layers": [
                attention if k == "full_rope" else short_conv for k in s.layers
            ],
            "norm_out": norm,
        },
        "policy": dense(d, s.num_actions),
        "value": dense(d, s.num_values),
    }


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, s: Sizes):
    params = reference.draw_leaves(key, _param_shapes(s))

    def placed(path, leaf):
        """An RMSNorm's scale about 1, where a draw about zero would switch
        the layer off; each expert's matrices scaled over their own fan-in
        (the draw divides by the fan-in of all the experts stacked)."""
        name = getattr(path[-1], "key", None)
        if name == "g":
            return 1.0 + leaf
        if name in ("gate", "up", "down"):
            return leaf * math.sqrt(s.num_experts)
        return leaf

    return jax.tree_util.tree_map_with_path(placed, params)


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


class _Precision(NamedTuple):
    """`mm`: a product whose operands are stored in the products' precision,
    accumulated in float32; `keep`: a value stored in the precision of the
    rest of the core."""

    products: object
    rest: object

    def mm(self, x, w):
        return rounded(x, self.products).astype(F32) @ rounded(
            w, self.products
        ).astype(F32)

    def keep(self, x):
        return rounded(x, self.rest).astype(F32)


def _rms(p, x, eps, pr: _Precision):
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return pr.keep(y * pr.keep(p["g"]))


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rope(x, pos, theta):
    """Rotate-half rotary embedding of `x` `[B, T, H, dh]` at `pos` `[B, T]`."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[..., None, None] * inv
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang)],
        -1,
    )


def routing(s: Sizes, p, u, pr: _Precision):
    """`u` `[N, D]` -> each held expert's weight for each token `[N, held]`
    (zero where the expert was not chosen)."""
    scores = jax.nn.sigmoid(pr.keep(u) @ pr.keep(p["router"]["w"]))
    _, chosen = jax.lax.top_k(
        jax.lax.stop_gradient(scores + pr.keep(p["expert_bias"])),
        s.num_experts_per_tok,
    )
    weight = jnp.take_along_axis(scores, chosen, axis=1)
    weight = weight / (jnp.sum(weight, 1, keepdims=True) + 1e-6)
    weight = pr.keep(weight * s.routed_scaling_factor)
    held = s.experts_held_first + jnp.arange(s.num_experts)
    return jnp.sum(
        jnp.where(chosen[:, :, None] == held[None, None, :], weight[..., None], 0.0),
        axis=1,
    )


def moe(s: Sizes, p, u, pr: _Precision):
    """`u` `[B, T, D]` -> the held experts' part `[B, T, D]`: every token
    through every held expert, weighted by `routing`; one expert at a time."""
    b, t, d = u.shape
    x = u.reshape(b * t, d)
    coef = routing(s, p, x, pr)  # [N, held]

    @jax.checkpoint
    def one_expert(acc, xs):
        w1, w3, w2, c = xs
        y = pr.mm(pr.keep(_silu(pr.mm(x, w1)) * pr.mm(x, w3)), w2)
        return acc + c[:, None] * y, None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x), (p["gate"], p["up"], p["down"], coef.T)
    )
    return out.reshape(b, t, d)


def short_conv(s: Sizes, p, u, first, window0, pr: _Precision):
    """`u` `[B, T, D]` (after the norm), `first` `[B, T]`, the last gated
    inputs `[B, L-1, D]` -> `[B, T, D]`."""
    d = s.hidden_size
    bcx = pr.mm(u, p["in"]["w"])
    bx = pr.keep(bcx[..., :d] * bcx[..., 2 * d :])
    w = pr.keep(p["conv"]["w"])
    tm = lambda v: jnp.swapaxes(v, 0, 1)  # noqa: E731

    def conv_step(window, xs):
        x_t, first_t = xs  # [B, D], [B]
        window = jnp.where(first_t[:, None, None], 0.0, window)
        taps = jnp.concatenate([window, x_t[:, None]], axis=1)  # [B, L, D]
        return taps[:, 1:], jnp.sum(taps * w[None], axis=1)

    _, conv = jax.lax.scan(
        conv_step, window0, (tm(bx), tm(first)), unroll=SCAN_UNROLL
    )
    y = pr.keep(bcx[..., d : 2 * d] * tm(conv))
    return pr.mm(y, p["out"]["w"])


def attention(s: Sizes, p, u, pos_q, k_cache, v_cache, mask, pr: _Precision):
    """`u` `[B, T, D]`; caches `[B, F, Hkv*dh]` (keys as rotated); `mask`
    `[B, T, F + T]` over (cache + unroll). One key/value head, with its
    group of query heads, at a time."""
    b, t, _ = u.shape
    h, hkv, dh = s.num_attention_heads, s.num_key_value_heads, s.head_dim

    def rotated(name, heads):
        x = pr.mm(u, p[name]["w"]).reshape(b, t, heads, dh)
        return pr.keep(
            _rope(_rms(p[f"{name}_norm"], x, s.norm_eps, pr), pos_q, s.rope_theta)
        )

    q = rotated("q", h).reshape(b, t, hkv, h // hkv, dh)
    keys = jnp.concatenate([k_cache, rotated("k", hkv).reshape(b, t, -1)], axis=1)
    values = jnp.concatenate([v_cache, pr.mm(u, p["v"]["w"])], axis=1)
    keys = keys.reshape(b, -1, hkv, dh)
    values = values.reshape(b, -1, hkv, dh)

    @jax.checkpoint
    def one_head(xs):
        q_h, k_h, v_h = xs  # [B, T, G, dh], [B, S, dh], [B, S, dh]
        scores = pr.mm(
            q_h.transpose(0, 2, 1, 3), k_h[:, None].transpose(0, 1, 3, 2)
        ) / math.sqrt(dh)  # [B, G, T, S]
        probs = pr.keep(
            jax.nn.softmax(jnp.where(mask[:, None], scores, NEG_INF), -1)
        )
        return pr.mm(probs, v_h[:, None]).transpose(0, 2, 1, 3)

    out = jax.lax.map(
        one_head,
        (
            q.transpose(2, 0, 1, 3, 4),
            keys.transpose(2, 0, 1, 3),
            values.transpose(2, 0, 1, 3),
        ),
    )  # [Hkv, B, T, G, dh]
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, t, h * dh)
    return pr.mm(pr.keep(out), p["o"]["w"])


def core_unroll(s: Sizes, p, feats, first, state, pr: _Precision):
    """`[T, B, F]` features -> `[T, B, D]`, from the carry in `state`."""
    k_full, v_full, full_seg, full_pos, pos, seg, sconv = state
    t, b = feats.shape[:2]
    first = first.T  # [B, T]
    seg_q = seg[:, None] + jnp.cumsum(first.astype(jnp.int32), axis=1)
    pos_q = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    seg_ctx = jnp.concatenate([full_seg, seg_q], axis=1)
    pos_ctx = jnp.concatenate([full_pos, pos_q], axis=1)
    mask = (seg_q[:, :, None] == seg_ctx[:, None, :]) & (
        pos_q[:, :, None] >= pos_ctx[:, None, :]
    )
    x = pr.keep(
        pr.mm(feats.transpose(1, 0, 2), p["in"]["w"]) + pr.keep(p["in"]["b"])
    )
    seen = dict.fromkeys(LAYERS, 0)
    for kind, layer in zip(s.layers, p["layers"]):
        j = seen[kind]
        seen[kind] += 1

        @jax.checkpoint
        def block(x, layer, kind=kind, j=j):
            u = _rms(layer["norm"], x, s.norm_eps, pr)
            if kind == "full_rope":
                mixed = attention(
                    s, layer, u, pos_q, pr.keep(k_full[:, j]),
                    pr.keep(v_full[:, j]), mask, pr,
                )
            else:
                mixed = short_conv(s, layer, u, first, pr.keep(sconv[:, j]), pr)
            x = pr.keep(x + mixed)
            u = _rms(layer["ffn_norm"], x, s.norm_eps, pr)
            return pr.keep(x + moe(s, layer, u, pr))

        x = block(x, layer)
    return _rms(p["norm_out"], x, s.norm_eps, pr).transpose(1, 0, 2)


def forward(s: Sizes, params, obs, first, state, dtypes=(F32, F32, F32)):
    """Unroll over `[T+1, B, ...]`: (policy logits `[T+1, B, A]`, values
    `[T+1, B, K]`). `dtypes`: see the module's docstring."""
    t, b = obs.shape[:2]
    feats = torso(
        {k: params[k] for k in ("convs", "fc")},
        obs.reshape(t * b, *obs.shape[2:]), dtypes[0],
    )
    pr = _Precision(jnp.dtype(dtypes[1]), jnp.dtype(dtypes[2]))
    feats = feats.reshape(t, b, -1).astype(F32)
    out = core_unroll(s, params["core"], feats, first, state, pr)
    heads = jax.tree.map(pr.keep, {k: params[k] for k in ("policy", "value")})

    def head(p):
        return (out @ p["w"] + p["b"]).astype(F32)

    return head(heads["policy"]), head(heads["value"])


# ---- the recurrent state of a generated unroll ---------------------------


def draw_state(rng, n: int, config: dict) -> tuple:
    """The carries of `n` unrolls at their first observation, each array
    with a row axis of one after the unroll's, in the order of the
    program's `HybridCoreState` leaves. The running episode has lasted `m`
    steps (0 to a quarter past the cache, by unroll): the newest
    min(m, cache) slots hold it at consecutive positions, older ones the
    episode before it (which no query may see) or nothing (`-1`)."""
    s = sizes(config)
    kv = s.num_key_value_heads * s.head_dim

    def normal(*shape):
        return rng.standard_normal((n, 1, *shape), dtype=np.float32) * CACHE_STD

    k_full, v_full = (normal(1, s.full_cache, kv) for _ in range(2))
    sconv = normal(_count(s, "short_conv"), s.conv_L_cache - 1, s.hidden_size)
    lasted = rng.integers(0, s.full_cache + s.full_cache // 4, size=(n, 1))
    seg = rng.integers(1, 1000, size=(n, 1)).astype(np.int32)
    pos = (lasted + rng.integers(0, 100_000, size=(n, 1))).astype(np.int32)
    held = np.minimum(lasted, s.full_cache)[..., None]
    older = np.where(rng.random((n, 1, 1)) < 0.5, -1, seg[..., None] - 1)
    slot = np.arange(s.full_cache)[None, None, :]
    full_seg = np.where(
        slot >= s.full_cache - held, seg[..., None], older
    ).astype(np.int32)
    full_pos = (pos[..., None] - (s.full_cache - slot)).astype(np.int32)
    return k_full, v_full, full_seg, full_pos, pos, seg, sconv


# ---- the program's side of the same tree ---------------------------------


def to_program_params(ref: dict) -> dict:
    """The reference's tree in the program's leaf names (flax names of
    `AtariShallowTorso`, `HybridCore` and its blocks, the heads)."""

    def dense(p):
        return {"kernel": p["w"], "bias": p["b"]}

    def matrix(p):
        return {"kernel": p["w"]}

    def norm(p):
        return {"scale": p["g"]}

    torso_ = {f"Conv_{i}": dense(p) for i, p in enumerate(ref["convs"])}
    torso_["Dense_0"] = dense(ref["fc"])
    core = {
        "in_proj": dense(ref["core"]["in"]),
        "ln_out": norm(ref["core"]["norm_out"]),
    }
    for i, layer in enumerate(ref["core"]["layers"]):
        block = {
            "ln_mlp": norm(layer["ffn_norm"]),
            "moe": {
                "router": layer["router"]["w"],
                "expert_bias": layer["expert_bias"],
                **{k: layer[k] for k in ("gate", "up", "down")},
            },
        }
        if "conv" in layer:
            block["ln_short_conv"] = norm(layer["norm"])
            block["short_conv"] = {
                "in_proj": matrix(layer["in"]),
                "conv_kernel": layer["conv"]["w"],
                "out_proj": matrix(layer["out"]),
            }
        else:
            block["ln_full_rope"] = norm(layer["norm"])
            block["full_rope_attention"] = {
                **{f"{k}_proj": matrix(layer[k]) for k in "qkvo"},
                "q_norm": norm(layer["q_norm"]),
                "k_norm": norm(layer["k_norm"]),
            }
        core[f"block_{i}"] = block
    return {
        "params": {
            "torso": torso_,
            "hybrid": core,
            "policy_head": dense(ref["policy"]),
            "value_head": dense(ref["value"]),
        }
    }


def leaf_groups(leaf_name: str) -> tuple:
    if "['torso']" in leaf_name:
        return ("torso",)
    if "['hybrid']" not in leaf_name:
        return ("core", "heads")
    for part, group in (
        ("['moe']", "core.moe"), ("short_conv", "core.short_conv"),
        ("attention", "core.attention"),
    ):
        if part in leaf_name:
            return ("core", group)
    return ("core", "core.io")  # projection, norms before mixers, last norm


def stated(config: dict, exp, net) -> dict:
    m, core = config["model"], net._hybrid_core(bound=False)
    first, held = core.experts_held
    return {
        "torso": (m["torso"], exp.model),
        "torso_dtype": (m["torso_dtype"], exp.compute_dtype),
        "core": (m["core"], exp.core),
        "core_dtype": (m["core_dtype"], jnp.dtype(core.dtype).name),
        "core_state_dtype": (m["core_state_dtype"], "float32"),
        "layers": (tuple(m["layers"]), tuple(core.layers)),
        "layers_of_this_file": (tuple(m["layers"]), LAYERS),
        "norm": ("rms", core.norm),
        "mlp": ("moe", core.mlp),
        "hidden_size": (m["hidden_size"], core.d_model),
        "num_attention_heads": (m["num_attention_heads"], core.num_heads),
        "num_key_value_heads": (m["num_key_value_heads"], core.num_kv_heads),
        "head_dim": (m["head_dim"], core.head_dim),
        "full_cache": (m["full_cache"], core.full_cache),
        "conv_L_cache": (m["conv_L_cache"], core.conv_taps),
        "rope_theta": (m["rope_theta"], core.rope_theta),
        "norm_eps": (m["norm_eps"], core.ln_eps),
        "router_experts": (m["router_experts"], core.num_experts),
        "num_experts": (m["num_experts"], held),
        "experts_held_first": (m["experts_held_first"], first),
        "num_experts_per_tok": (m["num_experts_per_tok"], core.experts_per_token),
        "moe_intermediate_size": (m["moe_intermediate_size"], core.expert_width),
        # the program weights the chosen experts by their normalised scores
        # alone: a routed scaling factor of 1
        "routed_scaling_factor": (m["routed_scaling_factor"], 1.0),
        "fc_size": (m["fc_size"], 512),  # AtariShallowTorso's
    }


def stated_dtypes(config: dict) -> tuple:
    m = config["model"]
    return m["torso_dtype"], m["core_dtype"], m["core_state_dtype"]


CONTROLS = {"control": (True, True, True)}


# ---- operations and bytes -------------------------------------------------


def held_rows(config: dict) -> float:
    """Rows routed to the held experts in a layer of one step at a balanced
    router's share: each of the B * (T+1) positions picks k of the router's
    experts, and this chip holds `num_experts` of them."""
    s = sizes(config)
    positions = config["batch_size"] * (config["unroll_length"] + 1)
    return positions * s.num_experts_per_tok * s.num_experts / s.router_experts


def forward_macs_per_obs(s: Sizes, unroll_length: int) -> dict:
    """Multiply-accumulates of one observation's forward pass, by layer:
    convolutions and matrix products alone (benchmark/flops.py). The held
    experts at a balanced router's share: a position's k picks land on a
    held expert `k * held / router_experts` times (once here). Attention
    over the `full_cache` slots and the causal half of the T+1 steps of its
    unroll (every slot counted as seen: the most a query needs)."""
    d, cin, macs = s.hidden_size, s.obs_shape[-1], {}
    for i, ((k, _, ch), (h, w)) in enumerate(zip(CONVS, conv_extents(s))):
        macs[f"conv{i}"] = h * w * k * k * cin * ch
        cin = ch
    macs["fc"] = h * w * cin * s.fc_size
    macs["core.in"] = s.fc_size * d
    hq = s.num_attention_heads * s.head_dim
    hkv = s.num_key_value_heads * s.head_dim
    attn, conv = _count(s, "full_rope"), _count(s, "short_conv")
    macs["core.projections"] = attn * (2 * d * hq + 2 * d * hkv)
    macs["core.attention_full"] = (
        attn * 2 * hq * (s.full_cache + (unroll_length + 2) / 2)
    )
    macs["core.short_conv"] = conv * (3 * d * d + d * d)
    macs["core.router"] = len(s.layers) * d * s.router_experts
    macs["core.moe_experts"] = len(s.layers) * (
        s.num_experts_per_tok * s.num_experts / s.router_experts
    ) * 3 * d * s.moe_intermediate_size
    macs["heads"] = d * (s.num_actions + s.num_values)
    return macs


def step_flops(config: dict) -> float:
    macs = forward_macs_per_obs(sizes(config), config["unroll_length"])
    return flops.step_flops(
        sum(macs.values()), macs["conv0"],
        config["unroll_length"], config["batch_size"],
    )


def moe_experts_product(config: dict, chips: int):
    """One call of XLA's ragged product over the held experts with a
    two-axis result (the gate's, the up's or the down's product, forward or
    recomputed, or the input's gradient of one of them) over a layer's
    rows at a balanced router's share (`held_rows`), not the rows this
    step's router sent: 2 d f operations a row; the rows read in bfloat16,
    the held experts' matrices once in bfloat16, the product written in
    float32. The least any of these calls moves: the input's gradients read
    a float32 cotangent (up to 16% more bytes), and every one of them is
    bound by the MXU at this share, not by the bytes."""
    s = sizes(config)
    rows = held_rows(config) / chips
    d, f = s.hidden_size, s.moe_intermediate_size
    return (
        2.0 * rows * d * f,
        2.0 * rows * d + 2.0 * s.num_experts * d * f + 4.0 * rows * f,
    )


def attention_full_forward(config: dict, chips: int):
    """Forward of the full attention layer: q.k and p.v over every cache
    slot and the causal half of the unroll; q and the output in the
    products' precision once, keys and values of (cache + unroll) once."""
    s = sizes(config)
    rows = config["batch_size"] / chips
    t = config["unroll_length"] + 1
    hq = s.num_attention_heads * s.head_dim
    hkv = s.num_key_value_heads * s.head_dim
    item = jnp.dtype(config["model"]["core_dtype"]).itemsize
    keys = s.full_cache + (config["unroll_length"] + 2) / 2
    return (
        4.0 * rows * t * hq * keys,
        item * rows * (2 * t * hq + 2 * (s.full_cache + t) * hkv),
    )


OPS_AND_BYTES = {
    "moe_experts": moe_experts_product,
    "attention_full": attention_full_forward,
}
