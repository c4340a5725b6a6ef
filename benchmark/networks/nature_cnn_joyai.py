"""The network `nature_cnn_joyai`: the Nature CNN torso (three VALID
convolutions and a 512-wide layer) under a temporal core cut from
jdopensource's JoyAI-LLM-Flash
(huggingface.co/jdopensource/JoyAI-LLM-Flash `config.json`, `model_type`
`joyai_llm_flash`, whose keys are DeepSeek-V3's), with policy and value
heads. Preset `pong_joyai_flash`; configuration `pong_joyai_flash_core`.

What the reference computes, written from the published configuration and
DeepSeek-V3's description (arXiv:2412.19437 section 2.1), importing
nothing of the program. The core takes the torso's features through a
`[fc, hidden]` projection (no token embedding, no LM head: the decoder is
an agent's recurrence) and runs `layers` (the published layers 0-4) on a
float32 residual stream; every block is

    h = x + MLA(RMSNorm(x));  y = h + FFN(RMSNorm(h))

with RMSNorm `x / sqrt(mean(x^2) + rms_norm_eps) * g`, then a last RMSNorm.
No projection has a bias (`attention_bias` false).

- MLA, `num_attention_heads` heads: `c_q = RMSNorm(W_qa u)` (width
  `q_lora_rank`), `[q_nope, q_pe]_h = (W_qb c_q)_h` (128 + 64);
  `[c_kv, k_pe] = W_kva u` (512 + 64), `c_kv = RMSNorm(c_kv)`; `q_pe` of
  every head and the one `k_pe` take rotary embeddings on interleaved
  pairs (`rope_interleave`): `(x_2i, x_2i+1)` turns by
  `pos * rope_theta^(-2i/64)` at each position's absolute step, with no
  scaling (`rope_scaling` null); `[k_nope, v]_h = (W_kvb c_kv)_h` (128 +
  128) over every position of cache and unroll; `o_h = softmax((q_nope_h .
  k_nope_h + q_pe_h . k_pe) / sqrt(192) + mask) v_h`; out `= W_o [o_1 ..
  o_32]`. The cache holds per position `c_kv` after its norm and `k_pe`
  after its rotation. A key is seen where its episode counter equals the
  query's and its position is not after the query's, in the cache or the
  unroll. Computed one head at a time.
- the FFN of the first `first_k_dense_replace` layers: `W_down(silu(W_gate
  u) * W_up u)`, width `intermediate_size`.
- the FFN of the others (`moe_layer_freq` 1): `s = sigmoid(W_r u)` over all
  `router_experts` experts (`scoring_func`), the top `num_experts_per_tok`
  of `s + expert_bias` chosen (`topk_method` `noaux_tc`, `n_group` =
  `topk_group` = 1: no group limit; the bias only chooses), their `s`
  divided by their sum plus `router_eps` (`norm_topk_prob`; 1e-6, where
  DeepSeek-V3's modeling adds 1e-20: the sum of 8 chosen scores is of order
  1 to 4, so the two differ by under 1e-6 of a weight, below float32's
  rounding) and times `routed_scaling_factor`;
  each expert `W2 (silu(W1 u) * W3 u)` of width `moe_intermediate_size`,
  and beside them `n_shared_experts` shared experts of that width, which
  every token takes with weight 1. **The chip's share**: the layer holds
  the routed experts `experts_held_first .. + num_experts - 1` and adds
  only their part, a dense sum over the tokens with each held expert's
  weight (zero where it was not chosen), and the shared expert's whole
  output; what the experts held on the other chips would add is left out,
  here and in the program alike.

State of a generated unroll (`draw_state`, the order of the program's
`HybridCoreState` leaves): the cache slots' episode counters and absolute
positions, the next position, the running episode counter, and per layer
the cache's `c_kv` `[full_cache, kv_lora_rank]` and rotated `k_pe`, two
positions to a row of 128 (`[full_cache * 64 / 128, 128]`).

`dtypes` of `forward`: what the torso is stored in, what the operands of
the core's matrix products (attention's and the experts' among them) are
stored in, and what the rest of the core (the norms, rotary embeddings,
softmax, the router's scores and choice, the residual stream, the cache)
is stored in. The configuration states bfloat16, bfloat16, float32; the
reference runs all three in float32; the control lowers each by one step.

Memory of the reference at the cell's size (one row, 2,048 positions and
2,048 cached): every block is under `jax.checkpoint`, attention goes one
head at a time and the routed experts one at a time.
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
    "layers", "hidden_size", "num_attention_heads", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
    "full_cache", "rope_theta", "rope_interleave", "rms_norm_eps",
    "intermediate_size", "first_k_dense_replace", "router_experts",
    "num_experts", "experts_held_first", "num_experts_per_tok",
    "moe_intermediate_size", "n_shared_experts", "routed_scaling_factor",
    "router_eps",
)
CONVS = ((8, 4, 32), (4, 2, 64), (3, 1, 64))  # (filter, stride, channels), VALID
NEG_INF = -1e30
LANES = 128  # a row of the rotated keys' cache leaf
# The cut's layer kinds by block (`stated` holds the file to them).
LAYERS = ("latent",) * 5


class Sizes(NamedTuple):
    obs_shape: tuple
    num_actions: int
    num_values: int
    layers: tuple
    rms_norm_eps: float
    rope_theta: float
    routed_scaling_factor: float
    router_eps: float
    fc_size: int
    hidden_size: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    full_cache: int
    intermediate_size: int
    first_k_dense_replace: int
    router_experts: int
    num_experts: int  # held here
    experts_held_first: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    n_shared_experts: int


def sizes(config: dict) -> Sizes:
    m = config["model"]
    return Sizes(
        tuple(m["obs_shape"]), int(m["num_actions"]), int(m["num_tasks"]),
        tuple(m["layers"]), float(m["rms_norm_eps"]), float(m["rope_theta"]),
        float(m["routed_scaling_factor"]), float(m["router_eps"]),
        *(int(m[k]) for k in Sizes._fields[8:]),
    )


def conv_extents(s: Sizes) -> list:
    """(h, w) of each convolution's output."""
    (h, w, _), out = s.obs_shape, []
    for k, stride, _ in CONVS:
        h, w = (h - k) // stride + 1, (w - k) // stride + 1
        out.append((h, w))
    return out


def _dense(s: Sizes, i: int) -> bool:
    """Block `i` takes the dense FFN."""
    return i < s.first_k_dense_replace


# ---- weights from the seed ---------------------------------------------


def _param_shapes(s: Sizes) -> dict:
    def dense(n_in, n_out):
        return {"w": (n_in, n_out), "b": (n_out,)}

    def matrix(n_in, n_out):
        return {"w": (n_in, n_out)}

    def swiglu(d, f):
        return {"gate": matrix(d, f), "up": matrix(d, f), "down": matrix(f, d)}

    d, f, e = s.hidden_size, s.moe_intermediate_size, s.num_experts
    h, nope, rope = s.num_attention_heads, s.qk_nope_head_dim, s.qk_rope_head_dim
    norm = {"g": (d,)}
    convs, cin = [], s.obs_shape[-1]
    for k, _, ch in CONVS:
        convs.append({"w": (k, k, cin, ch), "b": (ch,)})
        cin = ch
    hh, ww = conv_extents(s)[-1]
    mla = {
        "norm": norm,
        "q_a": matrix(d, s.q_lora_rank), "q_a_norm": {"g": (s.q_lora_rank,)},
        "q_b": matrix(s.q_lora_rank, h * (nope + rope)),
        "kv_a": matrix(d, s.kv_lora_rank + rope),
        "kv_a_norm": {"g": (s.kv_lora_rank,)},
        "kv_b": matrix(s.kv_lora_rank, h * (nope + s.v_head_dim)),
        "o": matrix(h * s.v_head_dim, d),
        "ffn_norm": norm,
    }
    moe = {
        "router": matrix(d, s.router_experts),
        "expert_bias": (s.router_experts,),
        "gate": (e, d, f), "up": (e, d, f), "down": (e, f, d),
        "shared": swiglu(d, s.n_shared_experts * f),
    }
    return {
        "convs": convs,
        "fc": dense(hh * ww * cin, s.fc_size),
        "core": {
            "in": dense(s.fc_size, d),
            "layers": [
                {**mla, "mlp": swiglu(d, s.intermediate_size)}
                if _dense(s, i) else {**mla, "moe": moe}
                for i in range(len(s.layers))
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
        the layer off; each routed expert's matrices scaled over their own
        fan-in (the draw divides by the fan-in of all the experts
        stacked)."""
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


def rope_pairs(x, pos, theta):
    """Rotary embedding on interleaved pairs of `x` `[B, T, H, d]` at `pos`
    `[B, T]`: `(x_2i, x_2i+1)` turns by `pos * theta^(-2i/d)`."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[..., None, None] * inv
    a, b = x[..., 0::2], x[..., 1::2]
    turned = (a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang))
    return jnp.stack(turned, -1).reshape(x.shape)


def swiglu(p, u, pr: _Precision):
    return pr.mm(
        pr.keep(_silu(pr.mm(u, p["gate"]["w"])) * pr.mm(u, p["up"]["w"])),
        p["down"]["w"],
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
    weight = weight / (jnp.sum(weight, 1, keepdims=True) + s.router_eps)
    weight = pr.keep(weight * s.routed_scaling_factor)
    held = s.experts_held_first + jnp.arange(s.num_experts)
    return jnp.sum(
        jnp.where(chosen[:, :, None] == held[None, None, :], weight[..., None], 0.0),
        axis=1,
    )


def moe(s: Sizes, p, u, pr: _Precision):
    """`u` `[B, T, D]` -> the held routed experts' part plus the shared
    expert's output `[B, T, D]`: every token through every held expert,
    weighted by `routing`; one expert at a time."""
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
    return (out + swiglu(p["shared"], x, pr)).reshape(b, t, d)


def mla(s: Sizes, p, u, pos_q, ckv_cache, kpe_cache, mask, pr: _Precision):
    """`u` `[B, T, D]`; the cache's `c_kv` `[B, F, kv_lora_rank]` and rotated
    `k_pe` `[B, F, rope]`; `mask` `[B, T, F + T]` over (cache + unroll).
    One head at a time."""
    b, t, _ = u.shape
    h, nope, rope = s.num_attention_heads, s.qk_nope_head_dim, s.qk_rope_head_dim
    r, dv = s.kv_lora_rank, s.v_head_dim
    c_q = _rms(p["q_a_norm"], pr.mm(u, p["q_a"]["w"]), s.rms_norm_eps, pr)
    q = pr.mm(c_q, p["q_b"]["w"]).reshape(b, t, h, nope + rope)
    q_nope = q[..., :nope]
    q_pe = pr.keep(rope_pairs(q[..., nope:], pos_q, s.rope_theta))
    kv = pr.mm(u, p["kv_a"]["w"])
    c_kv = _rms(p["kv_a_norm"], kv[..., :r], s.rms_norm_eps, pr)
    k_pe = pr.keep(rope_pairs(kv[..., None, r:], pos_q, s.rope_theta)[:, :, 0])
    c_kv = jnp.concatenate([ckv_cache, c_kv], axis=1)  # [B, S, r]
    k_pe = jnp.concatenate([kpe_cache, k_pe], axis=1)  # [B, S, rope]
    up = pr.mm(c_kv, p["kv_b"]["w"]).reshape(b, -1, h, nope + dv)

    @jax.checkpoint
    def one_head(xs):
        qn, qp, kn, vh = xs  # [B, T, nope], [B, T, rope], [B, S, nope], [B, S, dv]
        scores = (
            pr.mm(qn, kn.transpose(0, 2, 1)) + pr.mm(qp, k_pe.transpose(0, 2, 1))
        ) / math.sqrt(nope + rope)  # [B, T, S]
        probs = pr.keep(jax.nn.softmax(jnp.where(mask, scores, NEG_INF), -1))
        return pr.mm(probs, vh)

    heads = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    out = jax.lax.map(
        one_head,
        (heads(q_nope), heads(q_pe), heads(up[..., :nope]), heads(up[..., nope:])),
    )  # [H, B, T, dv]
    out = jnp.moveaxis(out, 0, 2).reshape(b, t, h * dv)
    return pr.mm(pr.keep(out), p["o"]["w"])


def core_unroll(s: Sizes, p, feats, first, state, pr: _Precision):
    """`[T, B, F]` features -> `[T, B, D]`, from the carry in `state`."""
    full_seg, full_pos, pos, seg, lat_ckv, lat_kpe = state
    t, b = feats.shape[:2]
    first = first.T  # [B, T]
    seg_q = seg[:, None] + jnp.cumsum(first.astype(jnp.int32), axis=1)
    pos_q = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    seg_ctx = jnp.concatenate([full_seg, seg_q], axis=1)
    pos_ctx = jnp.concatenate([full_pos, pos_q], axis=1)
    mask = (seg_q[:, :, None] == seg_ctx[:, None, :]) & (
        pos_q[:, :, None] >= pos_ctx[:, None, :]
    )
    kpe_all = lat_kpe.reshape(b, len(s.layers), s.full_cache, s.qk_rope_head_dim)
    x = pr.keep(
        pr.mm(feats.transpose(1, 0, 2), p["in"]["w"]) + pr.keep(p["in"]["b"])
    )
    for i, layer in enumerate(p["layers"]):

        @jax.checkpoint
        def block(x, layer, i=i):
            u = _rms(layer["norm"], x, s.rms_norm_eps, pr)
            mixed = mla(
                s, layer, u, pos_q, pr.keep(lat_ckv[:, i]),
                pr.keep(kpe_all[:, i]), mask, pr,
            )
            x = pr.keep(x + mixed)
            u = _rms(layer["ffn_norm"], x, s.rms_norm_eps, pr)
            ffn = swiglu(layer["mlp"], u, pr) if _dense(s, i) else moe(
                s, layer["moe"], u, pr
            )
            return pr.keep(x + ffn)

        x = block(x, layer)
    return _rms(p["norm_out"], x, s.rms_norm_eps, pr).transpose(1, 0, 2)


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
    episode before it (which no query may see) or nothing (`-1`). The
    cached `c_kv` is drawn N(0, 1), as a normalised vector stands, and so
    is `k_pe`, the rotation of a product of such a vector."""
    s = sizes(config)
    f, layers = s.full_cache, len(s.layers)

    def normal(*shape):
        return rng.standard_normal((n, 1, *shape), dtype=np.float32)

    lat_ckv = normal(layers, f, s.kv_lora_rank)
    lat_kpe = normal(layers, f * s.qk_rope_head_dim // LANES, LANES)
    lasted = rng.integers(0, f + f // 4, size=(n, 1))
    seg = rng.integers(1, 1000, size=(n, 1)).astype(np.int32)
    pos = (lasted + rng.integers(0, 100_000, size=(n, 1))).astype(np.int32)
    held = np.minimum(lasted, f)[..., None]
    older = np.where(rng.random((n, 1, 1)) < 0.5, -1, seg[..., None] - 1)
    slot = np.arange(f)[None, None, :]
    full_seg = np.where(slot >= f - held, seg[..., None], older).astype(np.int32)
    full_pos = (pos[..., None] - (f - slot)).astype(np.int32)
    return full_seg, full_pos, pos, seg, lat_ckv, lat_kpe


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

    def swiglu_(p):
        return {k: matrix(p[k]) for k in ("gate", "up", "down")}

    torso_ = {f"Conv_{i}": dense(p) for i, p in enumerate(ref["convs"])}
    torso_["Dense_0"] = dense(ref["fc"])
    core = {
        "in_proj": dense(ref["core"]["in"]),
        "ln_out": norm(ref["core"]["norm_out"]),
    }
    for i, layer in enumerate(ref["core"]["layers"]):
        block = {
            "ln_latent": norm(layer["norm"]),
            "latent_attention": {
                "q_a_proj": matrix(layer["q_a"]),
                "q_a_norm": norm(layer["q_a_norm"]),
                "q_b_proj": matrix(layer["q_b"]),
                "kv_a_proj": matrix(layer["kv_a"]),
                "kv_a_norm": norm(layer["kv_a_norm"]),
                "kv_b_proj": matrix(layer["kv_b"]),
                "o_proj": matrix(layer["o"]),
            },
            "ln_mlp": norm(layer["ffn_norm"]),
        }
        if "mlp" in layer:
            block["mlp"] = swiglu_(layer["mlp"])
        else:
            m = layer["moe"]
            block["moe"] = {
                "router": m["router"]["w"],
                "expert_bias": m["expert_bias"],
                **{k: m[k] for k in ("gate", "up", "down")},
                "shared": swiglu_(m["shared"]),
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
        ("['shared']", "core.moe_shared"), ("['moe']", "core.moe"),
        ("['mlp']", "core.mlp"), ("attention", "core.attention"),
    ):
        if part in leaf_name:
            return ("core", group)
    return ("core", "core.io")  # projection, norms before mixers, last norm


def stated(config: dict, exp, net) -> dict:
    m, core = config["model"], net._hybrid_core(bound=False)
    first, held = core.experts_held
    latent = (
        m["q_lora_rank"], m["kv_lora_rank"], m["qk_nope_head_dim"],
        m["qk_rope_head_dim"], m["v_head_dim"],
    )
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
        "latent": (latent, tuple(core.latent)),
        "full_cache": (m["full_cache"], core.full_cache),
        "rope_theta": (m["rope_theta"], core.rope_theta),
        # the program's latent layers turn interleaved pairs, and only those
        "rope_interleave": (m["rope_interleave"], True),
        "rms_norm_eps": (m["rms_norm_eps"], core.ln_eps),
        "intermediate_size": (m["intermediate_size"], core.d_intermediate),
        "first_k_dense_replace": (m["first_k_dense_replace"], core.dense_layers),
        "router_experts": (m["router_experts"], core.num_experts),
        "num_experts": (m["num_experts"], held),
        "experts_held_first": (m["experts_held_first"], first),
        "num_experts_per_tok": (m["num_experts_per_tok"], core.experts_per_token),
        "moe_intermediate_size": (m["moe_intermediate_size"], core.expert_width),
        "shared_width": (
            m["n_shared_experts"] * m["moe_intermediate_size"], core.shared_expert
        ),
        "routed_scaling_factor": (m["routed_scaling_factor"], core.routed_scale),
        "router_eps": (m["router_eps"], 1e-6),  # models/hybrid.py `_MoE`'s
        "fc_size": (m["fc_size"], 512),  # AtariShallowTorso's
    }


def stated_dtypes(config: dict) -> tuple:
    m = config["model"]
    return m["torso_dtype"], m["core_dtype"], m["core_state_dtype"]


CONTROLS = {"control": (True, True, True)}


# ---- operations and bytes -------------------------------------------------


def _keys(s: Sizes, unroll_length: int) -> float:
    """Keys a query needs: the cache's slots and the causal half of the
    T+1 steps of its unroll (every slot counted as seen: the most a query
    needs)."""
    return s.full_cache + (unroll_length + 2) / 2


def forward_macs_per_obs(s: Sizes, unroll_length: int) -> dict:
    """Multiply-accumulates of one observation's forward pass, by layer:
    convolutions and matrix products alone (benchmark/flops.py). The latent
    up-projection of a position once (its recomputation over the cache each
    unroll is not counted). The held experts at a balanced router's share:
    a position's k picks land on a held expert `k * held / router_experts`
    times (a quarter here)."""
    d, cin, macs = s.hidden_size, s.obs_shape[-1], {}
    for i, ((k, _, ch), (h, w)) in enumerate(zip(CONVS, conv_extents(s))):
        macs[f"conv{i}"] = h * w * k * k * cin * ch
        cin = ch
    macs["fc"] = h * w * cin * s.fc_size
    macs["core.in"] = s.fc_size * d
    n = len(s.layers)
    heads, nope, rope = s.num_attention_heads, s.qk_nope_head_dim, s.qk_rope_head_dim
    dk, dv = nope + rope, s.v_head_dim
    macs["core.projections"] = n * (
        d * s.q_lora_rank + s.q_lora_rank * heads * dk
        + d * (s.kv_lora_rank + rope) + heads * dv * d
    )
    macs["core.latent_kv"] = n * s.kv_lora_rank * heads * (nope + dv)
    macs["core.attention_latent"] = n * heads * (dk + dv) * _keys(s, unroll_length)
    dense = sum(_dense(s, i) for i in range(n))
    macs["core.mlp"] = dense * 3 * d * s.intermediate_size
    routed = n - dense
    macs["core.router"] = routed * d * s.router_experts
    macs["core.moe_shared"] = (
        routed * 3 * d * s.n_shared_experts * s.moe_intermediate_size
    )
    macs["core.moe_experts"] = routed * (
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


def attention_latent_forward(config: dict, chips: int):
    """One call of the kernel `attention_latent_forward` (the forward or
    its recomputation): q.k at the query/key width and p.v at the value
    width over every cache slot and the causal half of the unroll; q, keys
    and values of (cache + unroll) read once in the products' precision,
    the output written once in float32."""
    s = sizes(config)
    rows = config["batch_size"] / chips
    t = config["unroll_length"] + 1
    h, dv = s.num_attention_heads, s.v_head_dim
    dk = s.qk_nope_head_dim + s.qk_rope_head_dim
    item = jnp.dtype(config["model"]["core_dtype"]).itemsize
    n_flops = 2.0 * rows * t * h * (dk + dv) * _keys(s, config["unroll_length"])
    n_bytes = rows * h * (
        item * (t * dk + (s.full_cache + t) * (dk + dv)) + 4 * t * dv
    )
    return n_flops, n_bytes


def attention_latent_backward(config: dict, chips: int):
    """One call of either of the backward's two kernels
    (`attention_latent_backward_dq`, `_dkv`), as half of the backward:
    twice the forward's products (the gradients of q, of the keys and of
    the values, and of the probabilities; the recomputed scores not
    counted), and its bytes: q, keys, values and the output's cotangent
    read, their three gradients written in float32."""
    s = sizes(config)
    f, b = attention_latent_forward(config, chips)
    rows = config["batch_size"] / chips
    t = config["unroll_length"] + 1
    h, dv = s.num_attention_heads, s.v_head_dim
    dk = s.qk_nope_head_dim + s.qk_rope_head_dim
    kv = (s.full_cache + t) * (dk + dv)
    grads = 4.0 * rows * h * (t * dk + kv)
    return f, (b + grads) / 2.0


OPS_AND_BYTES = {
    "attention_latent_forward": attention_latent_forward,
    "attention_latent_backward": attention_latent_backward,
}
