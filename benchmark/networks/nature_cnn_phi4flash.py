"""The network `nature_cnn_phi4flash`: the Nature CNN torso (three VALID
convolutions and a 512-wide layer) under a hybrid temporal core cut from
Phi-4-mini-flash-reasoning's self-decoder (SambaY, arXiv:2507.06607;
huggingface.co/microsoft/Phi-4-mini-flash-reasoning `config.json`,
`model_type` `phi4flash`), with policy and value heads. Preset
`pong_phi4flash`; configuration `pong_phi4flash_core`.

What the reference computes, written from the published description and
importing nothing of the program. The core takes the torso's features
through a `[fc, hidden]` projection (there is no token embedding and no LM
head: the decoder is an agent's recurrence) and runs `layers`, one mixer
kind a layer, on a float32 residual stream; every block is

    h = x + Mixer(LN(x));  y = h + MLP(LN(h))
    MLP(u) = W_down(silu(W_gate u) * W_up u)          no biases

with LayerNorm (scale and bias, `layer_norm_eps`), then a last LayerNorm.

- `mamba` (Mamba-1 selective scan, arXiv:2312.00752; sizes `d_inner`,
  `d_state`, `d_conv`, `dt_rank` are the family's defaults, *assumed*: the
  config does not carry them): `[x, z] = W_in u`;
  `x = silu(conv1d_causal(x) + b)` (depthwise, `d_conv` taps);
  `(d, B_t, C_t) = W_x x`; `dt = softplus(W_dt d + b_dt)`;
  `A = -exp(A_log)`; `s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) B_t`;
  `y_t = s_t . C_t + D x_t`; out `= W_out(y * silu(z))`. Here it is a plain
  `lax.scan` over time whose carry is the scan state and the convolution's
  `d_conv - 1` remembered inputs; where `first[t]` is set both are zeroed
  before the step.
- `window` / `full`: softmax attention over `q.k / sqrt(head_dim)` with
  `num_attention_heads` query heads on `num_key_value_heads` key/value
  heads (query head h reads key/value head h // group), no positional
  encoding and no projection biases (*assumed*: the config has no rotary
  key; the state-space layers carry position), under an explicit mask over
  (cache + unroll): a key is seen where its episode counter equals the
  query's, its position is not after the query's and, in a window layer,
  less than `sliding_window` positions before it. *A departure:* the
  paper describes the model as SambaY "enhanced with Differential
  Attention" (arXiv:2507.06607, abstract); this is plain softmax, here and
  in the program, because the config's keys name no variant and the
  published modeling file is not in the repository (the configuration
  file lists it under `departures`).

State of a generated unroll (`draw_state`, the order of the program's
`HybridCoreState`): per Mamba layer the convolution's remembered inputs
`[d_conv-1, d_inner]` and the scan state `[d_state, d_inner]`; per window
layer keys and values `[sliding_window, kv * head_dim]` (a position's
heads side by side), per full layer `[full_cache, kv * head_dim]`, each
cache length with its slots' episode
counters and absolute positions; the next position and the running episode
counter. Cache slots run oldest first; the newest `m` hold the running
episode at consecutive positions, older ones another episode or nothing.

`dtypes` of `forward`: what the torso is stored in, what the operands of
the core's matrix products are stored in, and what the rest of the core
(LayerNorm, softmax, `dt`, the decay, the scan state, the residual stream)
is stored in. The configuration states bfloat16, bfloat16, float32; the
reference runs all three in float32; the control lowers each by one step.

Memory of the reference at the cell's size (one row, 2,048 positions):
every block is under `jax.checkpoint` and attention goes one key/value
head at a time, so that the backward pass holds one block's activations
(a Mamba block's per-step states: 2.7 GB) beside the weights.
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
    "head_dim", "sliding_window", "full_cache", "intermediate_size",
    "d_inner", "d_state", "d_conv", "dt_rank", "layer_norm_eps",
)
CONVS = ((8, 4, 32), (4, 2, 64), (3, 1, 64))  # (filter, stride, channels), VALID
CACHE_STD = 0.5  # spread of the cached keys, values and states of an unroll
NEG_INF = -1e30
# Steps of a `lax.scan` traced into one iteration of its loop: the same
# steps in the same order; 2,048 single steps cost the chip a loop's
# overhead each, 147k of them a check (my chip run, PR 34).
SCAN_UNROLL = 8


class Sizes(NamedTuple):
    obs_shape: tuple
    num_actions: int
    num_values: int
    layers: tuple
    layer_norm_eps: float
    fc_size: int
    hidden_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    sliding_window: int
    full_cache: int
    intermediate_size: int
    d_inner: int
    d_state: int
    d_conv: int
    dt_rank: int


def sizes(config: dict) -> Sizes:
    m = config["model"]
    return Sizes(
        tuple(m["obs_shape"]), int(m["num_actions"]), int(m["num_tasks"]),
        tuple(m["layers"]), float(m["layer_norm_eps"]),
        *(int(m[k]) for k in Sizes._fields[5:]),
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

    d, di, n = s.hidden_size, s.d_inner, s.d_state
    ln = {"g": (d,), "b": (d,)}
    convs, cin = [], s.obs_shape[-1]
    for k, _, ch in CONVS:
        convs.append({"w": (k, k, cin, ch), "b": (ch,)})
        cin = ch
    h, w = conv_extents(s)[-1]
    mlp = {
        "ln_mlp": ln, "gate": matrix(d, s.intermediate_size),
        "up": matrix(d, s.intermediate_size),
        "down": matrix(s.intermediate_size, d),
    }
    mamba = {
        "ln": ln, "in": matrix(d, 2 * di),
        "conv": {"w": (s.d_conv, di), "b": (di,)},
        "x": matrix(di, s.dt_rank + 2 * n), "dt": dense(s.dt_rank, di),
        "A_log": (di, n), "D": (di,), "out": matrix(di, d), **mlp,
    }
    hq = s.num_attention_heads * s.head_dim
    hkv = s.num_key_value_heads * s.head_dim
    attention = {
        "ln": ln, "q": matrix(d, hq), "k": matrix(d, hkv),
        "v": matrix(d, hkv), "o": matrix(hq, d), **mlp,
    }
    return {
        "convs": convs,
        "fc": dense(h * w * cin, s.fc_size),
        "core": {
            "in": dense(s.fc_size, d),
            "layers": [mamba if k == "mamba" else attention for k in s.layers],
            "ln_out": ln,
        },
        "policy": dense(d, s.num_actions),
        "value": dense(d, s.num_values),
    }


@functools.partial(jax.jit, static_argnums=(1,))
def _init(key, s: Sizes):
    params = reference.draw_leaves(key, _param_shapes(s))

    def placed(path, leaf):
        """Where a draw about zero would switch the layer off: a
        LayerNorm's scale and the skip `D` about 1; `A = -exp(A_log)`
        about -(n + 1), Mamba's start; `softplus(b_dt)` from 1e-3 to 1e-1,
        log-uniform by the channel's draw, so that the state remembers
        tens to hundreds of steps and a reset inside the unroll shows."""
        name = getattr(path[-1], "key", None)
        before = getattr(path[-2], "key", None) if len(path) > 1 else None
        if name == "g" or name == "D":
            return 1.0 + leaf
        if name == "A_log":
            return leaf + jnp.log(jnp.arange(1, leaf.shape[1] + 1, dtype=F32))
        if name == "b" and before == "dt":
            u = jax.nn.sigmoid(100.0 * leaf)  # the draw is 0.01 N(0, 1)
            dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
            return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
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


def _ln(p, x, eps, pr: _Precision):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return pr.keep(y * pr.keep(p["g"]) + pr.keep(p["b"]))


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mlp(p, u, pr: _Precision):
    return pr.mm(pr.keep(_silu(pr.mm(u, p["gate"]["w"])) * pr.mm(u, p["up"]["w"])),
                 p["down"]["w"])


def mamba(s: Sizes, p, u, first, window0, state0, pr: _Precision):
    """`u` `[B, T, D]` (after LN), `first` `[B, T]`, the convolution's
    remembered inputs `[B, d_conv-1, Di]` and the scan state `[B, N, Di]`
    -> `[B, T, D]`. Two plain scans over time, one step at a time: the
    convolution over its remembered inputs, then (after the projections
    of all steps at once) the state's recurrence; a reset zeroes what the
    step remembers before the step."""
    n, r = s.d_state, s.dt_rank
    xz = pr.mm(u, p["in"]["w"])
    x_in, z = xz[..., : s.d_inner], xz[..., s.d_inner :]
    w, b = pr.keep(p["conv"]["w"]), pr.keep(p["conv"]["b"])
    tm = lambda v: jnp.swapaxes(v, 0, 1)  # noqa: E731

    def conv_step(window, xs):
        x_t, first_t = xs  # [B, Di], [B]
        window = jnp.where(first_t[:, None, None], 0.0, window)
        taps = jnp.concatenate([window, x_t[:, None]], axis=1)  # [B, K, Di]
        return taps[:, 1:], jnp.sum(taps * w[None], axis=1) + b

    _, conv = jax.lax.scan(
        conv_step, window0, (tm(x_in), tm(first)), unroll=SCAN_UNROLL
    )
    x = pr.keep(_silu(tm(conv)))  # [B, T, Di]
    dbc = pr.mm(x, p["x"]["w"])
    dt = pr.keep(
        jax.nn.softplus(pr.mm(dbc[..., :r], p["dt"]["w"]) + pr.keep(p["dt"]["b"]))
    )
    a = -jnp.exp(pr.keep(p["A_log"])).T  # [N, Di]

    def state_step(state, xs):
        x_t, dt_t, b_t, c_t, first_t = xs  # [B, Di] x2, [B, N] x2, [B]
        state = jnp.where(first_t[:, None, None], 0.0, state)
        decay = pr.keep(jnp.exp(dt_t[:, None, :] * a[None]))
        state = pr.keep(
            decay * state + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        )
        return state, jnp.sum(state * c_t[:, :, None], axis=1)

    _, y = jax.lax.scan(
        state_step, state0,
        (tm(x), tm(dt), tm(dbc[..., r : r + n]), tm(dbc[..., r + n :]),
         tm(first)),
        unroll=SCAN_UNROLL,
    )
    y = tm(y) + pr.keep(p["D"]) * x
    return pr.mm(pr.keep(y * _silu(z)), p["out"]["w"])


def attention(s: Sizes, p, u, kind, k_cache, v_cache, mask, pr: _Precision):
    """`u` `[B, T, D]`; caches `[B, W, Hkv*dh]`; `mask` `[B, T, W + T]`
    over (cache + unroll). One key/value head, with its group of query
    heads, at a time."""
    b, t, _ = u.shape
    h, hkv, dh = s.num_attention_heads, s.num_key_value_heads, s.head_dim
    q = pr.mm(u, p["q"]["w"]).reshape(b, t, hkv, h // hkv, dh)
    keys = jnp.concatenate([k_cache, pr.mm(u, p["k"]["w"])], axis=1)
    values = jnp.concatenate([v_cache, pr.mm(u, p["v"]["w"])], axis=1)
    keys = keys.reshape(b, -1, hkv, dh)  # [B, S, Hkv, dh]
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
        return pr.mm(probs, v_h[:, None]).transpose(0, 2, 1, 3)  # [B,T,G,dh]

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
    (conv, ssm, k_win, v_win, win_seg, win_pos, k_full, v_full, full_seg,
     full_pos, pos, seg) = state
    t, b = feats.shape[:2]
    first = first.T  # [B, T]
    seg_q = seg[:, None] + jnp.cumsum(first.astype(jnp.int32), axis=1)
    pos_q = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]

    def mask_over(cache_seg, cache_pos, window):
        seg_ctx = jnp.concatenate([cache_seg, seg_q], axis=1)
        pos_ctx = jnp.concatenate([cache_pos, pos_q], axis=1)
        back = pos_q[:, :, None] - pos_ctx[:, None, :]  # [B, T, S]
        seen = (seg_q[:, :, None] == seg_ctx[:, None, :]) & (back >= 0)
        if window is not None:
            seen &= back < window
        return seen

    masks = {
        "window": mask_over(win_seg, win_pos, s.sliding_window),
        "full": mask_over(full_seg, full_pos, None),
    }
    caches = {"window": (k_win, v_win), "full": (k_full, v_full)}
    x = pr.keep(
        pr.mm(feats.transpose(1, 0, 2), p["in"]["w"]) + pr.keep(p["in"]["b"])
    )
    seen = dict.fromkeys(("mamba", "window", "full"), 0)
    for kind, layer in zip(s.layers, p["layers"]):
        j = seen[kind]
        seen[kind] += 1

        @jax.checkpoint
        def block(x, layer, kind=kind, j=j):
            u = _ln(layer["ln"], x, s.layer_norm_eps, pr)
            if kind == "mamba":
                mixed = mamba(s, layer, u, first, conv[:, j], ssm[:, j], pr)
            else:
                k_cache, v_cache = caches[kind]
                mixed = attention(
                    s, layer, u, kind, pr.keep(k_cache[:, j]),
                    pr.keep(v_cache[:, j]), masks[kind], pr,
                )
            x = pr.keep(x + mixed)
            u = _ln(layer["ln_mlp"], x, s.layer_norm_eps, pr)
            return pr.keep(x + _mlp(layer, u, pr))

        x = block(x, layer)
    return _ln(p["ln_out"], x, s.layer_norm_eps, pr).transpose(1, 0, 2)


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
    program's `HybridCoreState`. The running episode has lasted `m` steps
    (0 to a quarter past the full cache, by unroll): the newest
    min(m, length) slots of each cache hold it at consecutive positions,
    older slots hold the episode before it (which no query may see) or
    nothing (`-1`). No array is zero."""
    s = sizes(config)
    lm, lw, lf = (_count(s, k) for k in ("mamba", "window", "full"))
    kv = s.num_key_value_heads * s.head_dim

    def normal(*shape):
        return rng.standard_normal((n, 1, *shape), dtype=np.float32) * CACHE_STD

    conv = normal(lm, s.d_conv - 1, s.d_inner)
    ssm = normal(lm, s.d_state, s.d_inner)
    k_win, v_win = (normal(lw, s.sliding_window, kv) for _ in range(2))
    k_full, v_full = (normal(lf, s.full_cache, kv) for _ in range(2))
    lasted = rng.integers(0, s.full_cache + s.full_cache // 4, size=(n, 1))
    seg = rng.integers(1, 1000, size=(n, 1)).astype(np.int32)
    pos = (lasted + rng.integers(0, 100_000, size=(n, 1))).astype(np.int32)

    def slots(width):
        held = np.minimum(lasted, width)[..., None]  # [n, 1, 1]
        older = np.where(rng.random((n, 1, 1)) < 0.5, -1, seg[..., None] - 1)
        slot = np.arange(width)[None, None, :]
        running = slot >= width - held
        return (
            np.where(running, seg[..., None], older).astype(np.int32),
            # consecutive positions throughout: the episode before ran on
            # the steps before the running one's
            (pos[..., None] - (width - slot)).astype(np.int32),
        )

    win_seg, win_pos = slots(s.sliding_window)
    full_seg, full_pos = slots(s.full_cache)
    return (
        conv, ssm, k_win, v_win, win_seg, win_pos, k_full, v_full, full_seg,
        full_pos, pos, seg,
    )


# ---- the program's side of the same tree ---------------------------------


def to_program_params(ref: dict) -> dict:
    """The reference's tree in the program's leaf names (flax names of
    `AtariShallowTorso`, `HybridCore` and its blocks, the heads)."""

    def dense(p):
        return {"kernel": p["w"], "bias": p["b"]}

    def matrix(p):
        return {"kernel": p["w"]}

    def ln(p):
        return {"scale": p["g"], "bias": p["b"]}

    torso_ = {f"Conv_{i}": dense(p) for i, p in enumerate(ref["convs"])}
    torso_["Dense_0"] = dense(ref["fc"])
    core = {"in_proj": dense(ref["core"]["in"]), "ln_out": ln(ref["core"]["ln_out"])}
    for i, layer in enumerate(ref["core"]["layers"]):
        block = {
            "ln_mlp": ln(layer["ln_mlp"]),
            "mlp": {k: matrix(layer[k]) for k in ("gate", "up", "down")},
        }
        if "A_log" in layer:
            block["ln_mamba"] = ln(layer["ln"])
            block["mamba"] = {
                "in_proj": matrix(layer["in"]),
                "conv_kernel": layer["conv"]["w"],
                "conv_bias": layer["conv"]["b"],
                "x_proj": matrix(layer["x"]),
                "dt_proj": matrix(layer["dt"]),
                "dt_bias": layer["dt"]["b"],
                "A_log": layer["A_log"],
                "D": layer["D"],
                "out_proj": matrix(layer["out"]),
            }
        else:
            kind = _KIND_OF_BLOCK[i]
            block[f"ln_{kind}"] = ln(layer["ln"])
            block[f"{kind}_attention"] = {
                f"{k}_proj": matrix(layer[k]) for k in "qkvo"
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


# The cut's layer kinds by block: the reference's tree carries no kind of
# its own for an attention layer (window and full differ in their mask and
# cache, not in their weights). `sizes(config).layers` must agree
# (`stated`).
LAYERS = ("mamba", "window", "mamba", "full")
_KIND_OF_BLOCK = dict(enumerate(LAYERS))


def leaf_groups(leaf_name: str) -> tuple:
    if "['torso']" in leaf_name:
        return ("torso",)
    if "['hybrid']" not in leaf_name:
        return ("core", "heads")
    for part, group in (
        ("mlp']", "core.mlp"), ("mamba']", "core.mamba"),
        ("window", "core.window"), ("full", "core.full"),
    ):
        if part in leaf_name:
            return ("core", group)
    return ("core", "core.io")  # the input projection and the last LayerNorm


def stated(config: dict, exp, net) -> dict:
    m, core = config["model"], dict(net.hybrid)
    return {
        "torso": (m["torso"], exp.model),
        "torso_dtype": (m["torso_dtype"], exp.compute_dtype),
        "core": (m["core"], exp.core),
        "core_dtype": (m["core_dtype"], jnp.dtype(core["dtype"]).name),
        "core_state_dtype": (m["core_state_dtype"], "float32"),
        "layers": (tuple(m["layers"]), tuple(core["layers"])),
        "layers_of_this_file": (tuple(m["layers"]), LAYERS),
        "hidden_size": (m["hidden_size"], core["d_model"]),
        "num_attention_heads": (m["num_attention_heads"], core["num_heads"]),
        "num_key_value_heads": (m["num_key_value_heads"], core["num_kv_heads"]),
        "head_dim": (m["head_dim"], core["head_dim"]),
        "sliding_window": (m["sliding_window"], core["window"]),
        "full_cache": (m["full_cache"], core["full_cache"]),
        "intermediate_size": (m["intermediate_size"], core["d_intermediate"]),
        "d_inner": (m["d_inner"], core["d_inner"]),
        "d_state": (m["d_state"], core["d_state"]),
        "d_conv": (m["d_conv"], core["d_conv"]),
        "dt_rank": (m["dt_rank"], core["dt_rank"]),
        "layer_norm_eps": (m["layer_norm_eps"], 1e-5),  # HybridCore.ln_eps
        "fc_size": (m["fc_size"], 512),  # AtariShallowTorso's
    }


def stated_dtypes(config: dict) -> tuple:
    m = config["model"]
    return m["torso_dtype"], m["core_dtype"], m["core_state_dtype"]


CONTROLS = {"control": (True, True, True)}


# ---- operations and bytes -------------------------------------------------


def forward_macs_per_obs(s: Sizes, unroll_length: int) -> dict:
    """Multiply-accumulates of one observation's forward pass, by layer:
    convolutions and matrix products alone (benchmark/flops.py); the
    scan's elementwise work, 7 operations a state element, is 0.4% of the
    core's and is left out. Attention, per query: scores and the weighted
    sum over `sliding_window` keys in a window layer (the window is full
    from the cache on), over the `full_cache` slots and the causal half of
    the T+1 steps of its unroll in a full layer (every slot counted as
    seen: the most a query needs)."""
    d, di, cin, macs = s.hidden_size, s.d_inner, s.obs_shape[-1], {}
    for i, ((k, _, ch), (h, w)) in enumerate(zip(CONVS, conv_extents(s))):
        macs[f"conv{i}"] = h * w * k * k * cin * ch
        cin = ch
    macs["fc"] = h * w * cin * s.fc_size
    macs["core.in"] = s.fc_size * d
    hq = s.num_attention_heads * s.head_dim
    hkv = s.num_key_value_heads * s.head_dim
    macs["core.mlp"] = len(s.layers) * 3 * d * s.intermediate_size
    macs["core.mamba"] = _count(s, "mamba") * (
        d * 2 * di + di * (s.dt_rank + 2 * s.d_state) + s.dt_rank * di + di * d
    )
    attn = _count(s, "window") + _count(s, "full")
    macs["core.projections"] = attn * (2 * d * hq + 2 * d * hkv)
    macs["core.attention_window"] = (
        _count(s, "window") * 2 * hq * s.sliding_window
    )
    macs["core.attention_full"] = (
        _count(s, "full") * 2 * hq * (s.full_cache + (unroll_length + 2) / 2)
    )
    macs["heads"] = d * (s.num_actions + s.num_values)
    return macs


def step_flops(config: dict) -> float:
    macs = forward_macs_per_obs(sizes(config), config["unroll_length"])
    return flops.step_flops(
        sum(macs.values()), macs["conv0"],
        config["unroll_length"], config["batch_size"],
    )


def _positions(config: dict) -> int:
    """Rows times the T+1 positions of the learner's forward pass."""
    return config["batch_size"] * (config["unroll_length"] + 1)


def selective_scan_forward(config: dict, chips: int):
    """One call of the scan over a Mamba layer's unroll, forward: the
    algorithm's least. 7 operations a state element and step (dt A, exp,
    times the state, dt x B, add, times C, add up); x and dt read and y
    written once in float32, B_t and C_t read once. Bandwidth-bound."""
    s = sizes(config)
    steps = _positions(config) / chips
    return (
        7.0 * steps * s.d_inner * s.d_state,
        4.0 * steps * (3 * s.d_inner + 2 * s.d_state),
    )


def selective_scan_backward(config: dict, chips: int):
    """The scan's backward pass over the same call: the states recomputed
    (5 operations) and about 14 more a state element; x, dt and dy read,
    dx and ddt written, B, C and their gradients once."""
    s = sizes(config)
    steps = _positions(config) / chips
    return (
        19.0 * steps * s.d_inner * s.d_state,
        4.0 * steps * (5 * s.d_inner + 4 * s.d_state),
    )


def _attention(config: dict, chips: int, keys_per_query: float, cache: int):
    """Forward of one attention layer: q.k and p.v over the keys a query
    needs; q and the output in the products' precision once, keys and
    values of (cache + unroll) once."""
    s = sizes(config)
    rows = config["batch_size"] / chips
    t = config["unroll_length"] + 1
    hq = s.num_attention_heads * s.head_dim
    hkv = s.num_key_value_heads * s.head_dim
    item = jnp.dtype(config["model"]["core_dtype"]).itemsize
    return (
        4.0 * rows * t * hq * keys_per_query,
        item * rows * (2 * t * hq + 2 * (cache + t) * hkv),
    )


def attention_window_forward(config: dict, chips: int):
    """512 keys a query (the window, full from the cache on), not the
    tiles a kernel visits."""
    w = sizes(config).sliding_window
    return _attention(config, chips, w, w)


def attention_full_forward(config: dict, chips: int):
    """Every cache slot and the causal half of the unroll."""
    f = sizes(config).full_cache
    return _attention(
        config, chips, f + (config["unroll_length"] + 2) / 2, f
    )


OPS_AND_BYTES = {
    "selective_scan": selective_scan_forward,
    "selective_scan_backward": selective_scan_backward,
    "attention_window": attention_window_forward,
    "attention_full": attention_full_forward,
}
