"""The latent-attention core (models/hybrid.py kind `latent`; preset
`pong_joyai_flash`) on the CPU at a small size (d_model 64, 4 heads, q rank
24, kv rank 16, query/key width 8 + 8, value width 8, latent cache 16,
dense width 48, expert width 16; the router's 256 experts and 8 a token, 8
held; T 11, B 2), float32: the whole core against the plain forward pass of
the benchmark's network file (outputs and gradients), one latent block
alone, the interleaved rotary embedding against the pairwise rotation, the
latent cache across two unrolls and at an episode's start, and step mode.
The routed layers, the carry's size and the learner's path are in
`tests/test_joyai_routed.py`."""

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torched_impala_tpu import configs
from torched_impala_tpu.models.hybrid import HybridCoreState, _Latent, _rope_pairs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, B, F = 11, 2, 16
LATENT = (24, 16, 8, 8, 8)  # q rank, kv rank, nope, rope, v
SMALL = dict(
    hybrid_d_model=64, hybrid_heads=4, hybrid_latent=LATENT,
    hybrid_full_cache=F, hybrid_d_intermediate=48, hybrid_expert_width=16,
    hybrid_shared_expert=16, hybrid_dtype="float32", compute_dtype="float32",
)
SMALL_FILE = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, full_cache=F,
    intermediate_size=48, moe_intermediate_size=16,
)


def small_cfg(**more):
    return dataclasses.replace(
        configs.REGISTRY["pong_joyai_flash"], unroll_length=T - 1,
        batch_size=B, **{**SMALL, **more},
    )


@pytest.fixture(scope="module")
def network():
    from benchmark import driver

    spec = driver.Spec(ROOT)
    config = spec.config("pong_joyai_flash_core")
    config["model"].update(SMALL_FILE)
    config.update(batch_size=B, unroll_length=T - 1)
    return spec.network(config), config


def _inputs(net, config, seed, firsts, running=5, steps=T):
    """Frames, starts and a drawn carry whose cache holds the running
    episode in its newest `running` slots and the episode before it in the
    rest."""
    rng = np.random.default_rng(seed)
    obs = rng.integers(0, 256, size=(steps, B, 84, 84, 4), dtype=np.uint8)
    first = np.zeros((steps, B), bool)
    for t, b in firsts:
        first[t, b] = True
    state = [np.concatenate(list(p), axis=0) for p in net.draw_state(rng, B, config)]
    seg = state[3]
    state[0] = np.where(
        np.arange(F)[None, :] >= F - running, seg[:, None], seg[:, None] - 1
    ).astype(np.int32)
    return obs, first, tuple(state)


def _carried(agent, state):
    return jax.tree.unflatten(jax.tree.structure(agent.initial_state(B)), state)


def _scale_close(got, want, atol, msg=""):
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    np.testing.assert_allclose(got / scale, want / scale, rtol=0, atol=atol, err_msg=msg)


RESETS = {
    "none": (),
    "first_middle_last": ((0, 0), (5, 0), (T - 1, 0), (6, 1)),
}


@pytest.mark.parametrize(
    "resets,held_first", [("first_middle_last", 0), ("none", 248)]
)
def test_core_matches_the_network_files_forward(network, resets, held_first):
    """Outputs and every parameter's gradient, with the cache partly from
    another episode, for the chip that holds experts 0-7 and for the one
    that holds 248-255."""
    net, config = network
    config = {**config, "model": {**config["model"], "experts_held_first": held_first}}
    obs, first, state = _inputs(net, config, 3, RESETS[resets])
    weights = net.init_params(17, config)
    agent = configs.make_agent(small_cfg(hybrid_experts_held=(held_first, 8)))
    params = net.to_program_params(weights)
    carried = _carried(agent, state)
    rng = np.random.default_rng(5)
    wl = jnp.asarray(rng.standard_normal((T, B, 6)), jnp.float32)
    wv = jnp.asarray(rng.standard_normal((T, B, 1)), jnp.float32)

    def ours(p):
        out, _ = agent.unroll(p, obs, first, carried)
        return out.policy_logits, out.values

    def theirs(w):
        with jax.default_matmul_precision("highest"):
            return net.forward(net.sizes(config), w, obs, first, state)

    def scalar(fn, p):
        logits, values = fn(p)
        return jnp.sum(logits * wl) + jnp.sum(values * wv)

    def outputs_and_grads(fn, p):
        return fn(p), jax.grad(lambda q: scalar(fn, q))(p)

    got, g_ours = jax.jit(functools.partial(outputs_and_grads, ours))(params)
    want, g_theirs = jax.jit(functools.partial(outputs_and_grads, theirs))(weights)
    # float32 both sides, the router's choice the same on every token:
    # what is left is the order of the sums
    np.testing.assert_allclose(got[0], want[0], rtol=0, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=0, atol=1e-5)
    g_theirs = dict(
        jax.tree_util.tree_leaves_with_path(net.to_program_params(g_theirs))
    )
    flat_ours = jax.tree_util.tree_leaves_with_path(g_ours)
    assert len(flat_ours) == len(g_theirs)
    for path, g in flat_ours:
        # 22 positions' terms through five blocks, summed in another order
        # (the expanded heads against one head at a time, grouped rows
        # against dense per-token sums): 5e-5 of the leaf's largest element.
        # The experts' bias chooses and has no gradient.
        name = jax.tree_util.keystr(path)
        _scale_close(g, g_theirs[path], 5e-5, name)
        if "expert_bias" in name:
            assert not np.any(np.asarray(g))


def _latent_params(rng, d=64, heads=4):
    q_rank, kv_rank, nope, rope, dv = LATENT

    def m(n_in, n_out):
        return jnp.asarray(rng.standard_normal((n_in, n_out)) / np.sqrt(n_in), jnp.float32)

    def g(n):
        return jnp.asarray(1.0 + 0.1 * rng.standard_normal(n), jnp.float32)

    return {
        "q_a_proj": {"kernel": m(d, q_rank)}, "q_a_norm": {"scale": g(q_rank)},
        "q_b_proj": {"kernel": m(q_rank, heads * (nope + rope))},
        "kv_a_proj": {"kernel": m(d, kv_rank + rope)},
        "kv_a_norm": {"scale": g(kv_rank)},
        "kv_b_proj": {"kernel": m(kv_rank, heads * (nope + dv))},
        "o_proj": {"kernel": m(heads * dv, d)},
    }


def test_a_latent_block_against_a_plain_float32_mla(network):
    """One `_Latent` mixer (the written-out mask path and, interpreted, the
    fused kernels with a key width of 16 and a value width of 8) against the
    network file's plain MLA, with the cache's `c_kv` and rotated `k_pe`,
    episode starts inside the unroll, and the new cache it hands on: float32
    both, 1e-5 of the output's largest element (the order of the sums)."""
    net, config = network
    s = net.sizes(config)
    rng = np.random.default_rng(11)
    p = _latent_params(rng)
    u = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    ckv = jnp.asarray(rng.standard_normal((B, F, 16)), jnp.float32)
    kpe = jnp.asarray(rng.standard_normal((B, F * 8 // 128, 128)), jnp.float32)
    cache_seg = jnp.asarray(np.where(np.arange(F) >= 6, 4, 3)[None].repeat(B, 0), jnp.int32)
    seg_q = jnp.asarray(4 + np.cumsum(rng.random((B, T)) < 0.2, axis=1), jnp.int32)
    pos_q = jnp.asarray(50_000 + np.arange(T)[None].repeat(B, 0), jnp.int32)
    pos_ctx = jnp.concatenate([pos_q[:, :1] - F + jnp.arange(F)[None], pos_q], 1)
    seg_ctx = jnp.concatenate([cache_seg, seg_q], 1)
    mask = (seg_q[:, :, None] == seg_ctx[:, None, :]) & (
        pos_q[:, :, None] >= pos_ctx[:, None, :]
    )
    ref_p = {
        "q_a": {"w": p["q_a_proj"]["kernel"]}, "q_a_norm": {"g": p["q_a_norm"]["scale"]},
        "q_b": {"w": p["q_b_proj"]["kernel"]}, "kv_a": {"w": p["kv_a_proj"]["kernel"]},
        "kv_a_norm": {"g": p["kv_a_norm"]["scale"]}, "kv_b": {"w": p["kv_b_proj"]["kernel"]},
        "o": {"w": p["o_proj"]["kernel"]},
    }
    pr = net._Precision(jnp.float32, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = net.mla(s, ref_p, u, pos_q, ckv, kpe.reshape(B, F, 8), mask, pr)
    for kernel in ("einsum", "pallas"):
        layer = _Latent(num_heads=4, latent=LATENT, rope_theta=s.rope_theta,
                        kernel=kernel, eps=s.rms_norm_eps)
        out, new_ckv, new_kpe = layer.apply(
            {"params": p}, u, ckv, kpe, cache_seg, seg_q, pos_q
        )
        _scale_close(out, want, 1e-5, kernel)
        # the cache handed on: the newest F positions' normalised c_kv and
        # rotated k_pe, 16 positions to a row of 128
        assert new_ckv.shape == (B, F, 16) and new_kpe.shape == kpe.shape
        kv = u @ p["kv_a_proj"]["kernel"]
        c = kv[..., :16] * jax.lax.rsqrt(jnp.mean(kv[..., :16] ** 2, -1, keepdims=True) + 1e-6)
        c = c * p["kv_a_norm"]["scale"]
        np.testing.assert_allclose(new_ckv[:, F - T:], c[:, T - F:] if T > F else c, atol=1e-5)
        np.testing.assert_array_equal(new_ckv[:, : F - T], ckv[:, T:])
        k_pe = net.rope_pairs(kv[..., None, 16:], pos_q, s.rope_theta)[:, :, 0]
        np.testing.assert_allclose(
            new_kpe.reshape(B, F, 8)[:, F - T:], k_pe, rtol=0, atol=1e-5
        )


def test_interleaved_rope_against_the_pairwise_rotation():
    """`(x[2i], x[2i+1])` turned by `pos * theta^(-2i/d)`, each pair by its
    own 2x2 rotation in float64, at positions up to 4,096 (float32 angles:
    2e-4 of a pair's length); and what a rotary embedding is for: the
    product of a rotated query and key depends on their distance alone."""
    rng = np.random.default_rng(0)
    d, theta = 64, 32_000_000.0
    x = rng.standard_normal((2, 5, 3, d)).astype(np.float32)
    pos = rng.integers(0, 4096, size=(2, 5)).astype(np.int32)
    got = np.asarray(_rope_pairs(jnp.asarray(x), jnp.asarray(pos), theta))
    want = np.empty_like(x, dtype=np.float64)
    for i in range(d // 2):
        ang = pos.astype(np.float64)[..., None] * theta ** (-2.0 * i / d)
        c, s = np.cos(ang), np.sin(ang)
        a, b = x[..., 2 * i].astype(np.float64), x[..., 2 * i + 1].astype(np.float64)
        want[..., 2 * i] = c * a - s * b
        want[..., 2 * i + 1] = s * a + c * b
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-4 * np.abs(x).max())
    q, k = (jnp.asarray(rng.standard_normal((1, 1, 1, d)), jnp.float32) for _ in "qk")
    dot = lambda pq, pk: float(jnp.sum(  # noqa: E731
        _rope_pairs(q, jnp.full((1, 1), pq), theta)
        * _rope_pairs(k, jnp.full((1, 1), pk), theta)
    ))
    assert dot(130, 100) == pytest.approx(dot(30, 0), abs=1e-4)
    assert dot(130, 100) != pytest.approx(dot(30, 20), abs=1e-3)


def test_two_unrolls_through_the_latent_cache_equal_one_of_twice_the_length(network):
    """An unroll of 2T = 16 steps against two of 8 through the carry, with
    an episode start in each half: outputs and the carry at the end. The
    first half's 8 steps push the cache's 8 oldest slots out, which hold
    another episode and were never seen."""
    net, config = network
    obs, first, state = _inputs(net, config, 9, ((3, 0), (12, 1)), running=8, steps=16)
    agent = configs.make_agent(small_cfg())
    params = net.to_program_params(net.init_params(29, config))
    apply = jax.jit(lambda o, f, s: agent.net.apply(params, o, f, s, unroll=True))
    whole, end = apply(obs, first, _carried(agent, state))
    a, mid = apply(obs[:8], first[:8], _carried(agent, state))
    b, end2 = apply(obs[8:], first[8:], mid)
    np.testing.assert_allclose(
        np.concatenate([a.policy_logits, b.policy_logits]), whole.policy_logits,
        rtol=0, atol=2e-5,
    )
    for name, x, y in zip(end._fields, end, end2):
        if x is None:
            assert y is None, name
            continue
        np.testing.assert_allclose(x, y, rtol=0, atol=2e-5, err_msg=name)
    assert end.lat_ckv.shape == (B, 5, F, 16) and end.lat_kpe.shape == (B, 5, 1, 128)
    np.testing.assert_array_equal(end.full_pos[:, -1], state[2] + 15)


def test_an_episode_start_cuts_what_the_latent_cache_shows(network):
    """Everything the cache holds (its `c_kv` and `k_pe`) and the frames
    before a start at step 4 change nothing from the start on."""
    net, config = network
    obs, first, state = _inputs(net, config, 4, ((4, 0), (4, 1)))
    agent = configs.make_agent(small_cfg())
    params = net.to_program_params(net.init_params(7, config))
    out, _ = agent.unroll(params, obs, first, _carried(agent, state))
    changed = list(state)
    changed[4] = state[4] + 3.0
    changed[5] = state[5] - 2.0
    obs2 = obs.copy()
    obs2[:4] = 255 - obs2[:4]
    out2, _ = agent.unroll(params, obs2, first, _carried(agent, changed))
    np.testing.assert_allclose(
        out.policy_logits[4:], out2.policy_logits[4:], rtol=0, atol=1e-5
    )
    assert not np.allclose(out.policy_logits[:4], out2.policy_logits[:4], atol=1e-3)


def test_step_mode_equals_the_unroll(network):
    """T=1, eleven times through the carry (as actors step): outputs and
    the final state, an episode start among them."""
    net, config = network
    obs, first, state = _inputs(net, config, 6, ((5, 0), (0, 1), (6, 1)))
    agent = configs.make_agent(small_cfg())
    params = net.to_program_params(net.init_params(23, config))
    carried = _carried(agent, state)
    out, final = agent.unroll(params, obs, first, carried)
    step = jax.jit(lambda s, o, f: agent.net.apply(params, o, f, s, unroll=False))
    s, logits = carried, []
    for t in range(T):
        o, s = step(s, obs[t], first[t])
        logits.append(o.policy_logits)
    np.testing.assert_allclose(np.stack(logits), out.policy_logits, rtol=0, atol=2e-5)
    assert isinstance(s, HybridCoreState)
    for name, a, b in zip(s._fields, s, final):
        if a is None:
            assert b is None, name
            continue
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5, err_msg=name)
