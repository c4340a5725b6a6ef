"""The routed hybrid core (models/hybrid.py with `full_rope`, `short_conv`
and `mlp="moe"`; preset `pong_lfm2_moe`) on the CPU at a small size
(d_model 64, 4 query heads on 2 key/value heads of 16, full cache 8,
expert width 32, the router's 32 experts and 4 a token, 8 held; T 11,
B 2), float32: against the plain forward pass of the benchmark's network
file (outputs and gradients) for two chips' shares of the experts, the
chip's share of a layer against the uncut layer, the dropless path under a routing that
sends every token to the same experts, the short convolution's carry at an
episode's start, step mode against the unroll, and the carry through the
learner's batcher."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torched_impala_tpu import configs
from torched_impala_tpu.models.hybrid import HybridCoreState, _MoE

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, B = 11, 2
SMALL = dict(
    hybrid_d_model=64, hybrid_heads=4, hybrid_kv_heads=2, hybrid_head_dim=16,
    hybrid_full_cache=8, hybrid_expert_width=32, hybrid_dtype="float32",
    compute_dtype="float32",
)
SMALL_FILE = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, full_cache=8, moe_intermediate_size=32,
)


def small_cfg(**more):
    return dataclasses.replace(
        configs.REGISTRY["pong_lfm2_moe"], unroll_length=T - 1,
        batch_size=B, **SMALL, **more,
    )


@pytest.fixture(scope="module")
def network():
    from benchmark import driver

    spec = driver.Spec(ROOT)
    config = spec.config("pong_lfm2_moe_core")
    config["model"].update(SMALL_FILE)
    config.update(batch_size=B, unroll_length=T - 1)
    return spec.network(config), config


def _inputs(net, config, seed, firsts):
    """Frames, starts and a drawn carry whose full cache holds the running
    episode in its newest 3 slots and the episode before it in the rest."""
    rng = np.random.default_rng(seed)
    obs = rng.integers(0, 256, size=(T, B, 84, 84, 4), dtype=np.uint8)
    first = np.zeros((T, B), bool)
    for t, b in firsts:
        first[t, b] = True
    state = [np.concatenate(list(p), axis=0) for p in net.draw_state(rng, B, config)]
    seg = state[5]
    state[2] = np.where(
        np.arange(8)[None, :] >= 5, seg[:, None], seg[:, None] - 1
    ).astype(np.int32)
    return obs, first, tuple(state)


def _carried(agent, state):
    return jax.tree.unflatten(jax.tree.structure(agent.initial_state(B)), state)


RESETS = {
    "none": (),
    "first_middle_last": ((0, 0), (5, 0), (T - 1, 0), (6, 1)),
    "two_in_a_row": ((4, 1), (5, 1)),
}


@pytest.mark.parametrize("held_first", [0, 24])
@pytest.mark.parametrize("resets", sorted(RESETS))
def test_core_matches_the_network_files_forward(network, resets, held_first):
    """Outputs and every parameter's gradient, with the cache partly from
    another episode, for the chip that holds experts 0-7 and for the one
    that holds 24-31."""
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

    got, want = ours(params), theirs(weights)
    # float32 both sides, the router's choice the same on every token:
    # what is left is the order of the sums
    np.testing.assert_allclose(got[0], want[0], rtol=0, atol=1e-5)
    np.testing.assert_allclose(got[1], want[1], rtol=0, atol=1e-5)
    g_ours = jax.grad(lambda p: scalar(ours, p))(params)
    g_theirs = net.to_program_params(
        jax.grad(lambda w: scalar(theirs, w))(weights)
    )
    flat_ours = jax.tree_util.tree_leaves_with_path(g_ours)
    flat_theirs = dict(jax.tree_util.tree_leaves_with_path(g_theirs))
    assert len(flat_ours) == len(flat_theirs)
    for path, g in flat_ours:
        w = flat_theirs[path]
        # 22 positions' terms through four blocks, summed in another order
        # (grouped rows against dense per-token sums): 5e-5 of the leaf's
        # largest element. The experts' bias chooses and has no gradient.
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        np.testing.assert_allclose(
            g / scale, w / scale, rtol=0, atol=5e-5,
            err_msg=jax.tree_util.keystr(path),
        )
        if "expert_bias" in jax.tree_util.keystr(path):
            assert not np.any(np.asarray(g))


def _moe_params(rng, d, f, experts, bias=None):
    return {
        "router": jnp.asarray(rng.standard_normal((d, experts)) / np.sqrt(d), jnp.float32),
        "expert_bias": jnp.asarray(
            0.01 * rng.standard_normal(experts) if bias is None else bias, jnp.float32
        ),
        "gate": jnp.asarray(rng.standard_normal((experts, d, f)) / np.sqrt(d), jnp.float32),
        "up": jnp.asarray(rng.standard_normal((experts, d, f)) / np.sqrt(d), jnp.float32),
        "down": jnp.asarray(rng.standard_normal((experts, f, d)) / np.sqrt(f), jnp.float32),
    }


def _share(p, first, count):
    return {
        **p, **{k: p[k][first : first + count] for k in ("gate", "up", "down")}
    }


def _program_moe(p, u, first, count):
    moe = _MoE(
        num_experts=32, experts_per_token=4, experts_held=(first, count),
        d_ff=p["gate"].shape[-1],
    )
    return moe.apply({"params": p}, u)


def _reference_moe(net, config, p, u, first, count):
    s = net.sizes(config)._replace(num_experts=count, experts_held_first=first)
    pr = net._Precision(jnp.float32, jnp.float32)
    p = {**p, "router": {"w": p["router"]}}  # the reference's leaf names
    with jax.default_matmul_precision("highest"):
        return net.moe(s, p, u, pr)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_four_shares_of_eight_experts_add_up_to_the_uncut_layer(network, side):
    """Each chip of the deployment holds 8 of the 32 experts and computes
    their part for every token; nothing is computed alike on every chip
    (no shared expert), so the four parts sum to the layer with all 32."""
    net, config = network
    rng = np.random.default_rng(0)
    p = _moe_params(rng, 64, 32, 32)
    u = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    if side == "program":
        whole, rows = _program_moe(p, u, 0, 32)
        parts = [_program_moe(_share(p, 8 * i, 8), u, 8 * i, 8) for i in range(4)]
        assert int(rows.sum()) == B * T * 4  # every pick is held by the uncut layer
        assert sum(int(r.sum()) for _, r in parts) == B * T * 4
        parts = [out for out, _ in parts]
    else:
        whole = _reference_moe(net, config, p, u, 0, 32)
        parts = [
            _reference_moe(net, config, _share(p, 8 * i, 8), u, 8 * i, 8)
            for i in range(4)
        ]
    np.testing.assert_allclose(sum(parts), whole, rtol=0, atol=1e-5)
    assert all(float(jnp.max(jnp.abs(x))) > 1e-3 for x in parts)


@pytest.mark.parametrize("planted", [(0, 1, 2, 3), (6, 7, 8, 9)])
def test_dropless_when_every_token_routes_to_the_same_experts(network, planted):
    """The experts' bias planted so that every token picks the same four
    experts, all held here or two of them: every such group gets every
    token (the most rows a held expert can get), the others get none, and
    the output and its gradients are the dense per-token sums of the
    reference."""
    net, config = network
    rng = np.random.default_rng(1)
    bias = np.zeros(32)
    bias[list(planted)] = 10.0
    p = _moe_params(rng, 64, 32, 32, bias=bias)
    p8 = _share(p, 0, 8)
    u = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    out, rows = _program_moe(p8, u, 0, 8)
    assert np.asarray(rows).tolist() == [B * T * (e in planted) for e in range(8)]
    want = _reference_moe(net, config, p8, u, 0, 8)
    np.testing.assert_allclose(out, want, rtol=0, atol=1e-5)
    g = jax.grad(lambda q: jnp.sum(_program_moe(q, u, 0, 8)[0] ** 2))(p8)
    g_ref = jax.grad(
        lambda q: jnp.sum(_reference_moe(net, config, q, u, 0, 8) ** 2)
    )(p8)  # the router's gradient under the program's leaf name
    for k in ("router", "gate", "up", "down"):
        scale = float(jnp.max(jnp.abs(g_ref[k]))) + 1e-6
        np.testing.assert_allclose(g[k] / scale, g_ref[k] / scale, atol=1e-5, err_msg=k)
    idle = [e for e in range(8) if e not in planted]  # experts no token reached
    assert not np.any(np.asarray(g["gate"])[idle])


def test_the_short_convolutions_carry_ends_at_an_episodes_start(network):
    """What the convolutions remember (and the episode before, and the
    frames before the start) changes nothing from the start on; the carry
    after the unroll is the last two gated inputs of the running episode."""
    net, config = network
    obs, first, state = _inputs(net, config, 4, ((4, 0), (4, 1)))
    agent = configs.make_agent(small_cfg())
    params = net.to_program_params(net.init_params(7, config))
    out, new = agent.unroll(params, obs, first, _carried(agent, state))
    changed = list(state)
    changed[6] = state[6] + 3.0  # the remembered gated inputs
    changed[0] = state[0] - 2.0  # keys of the cache
    obs2 = obs.copy()
    obs2[:4] = 255 - obs2[:4]
    out2, new2 = agent.unroll(params, obs2, first, _carried(agent, changed))
    np.testing.assert_allclose(
        out.policy_logits[4:], out2.policy_logits[4:], rtol=0, atol=1e-5
    )
    assert not np.allclose(out.policy_logits[:4], out2.policy_logits[:4], atol=1e-3)
    np.testing.assert_allclose(new.sconv, new2.sconv, rtol=0, atol=1e-5)
    assert new.sconv.shape == (B, 3, 2, 64) and np.abs(new.sconv).max() > 0
    # a start at the last position leaves only that position's input
    last = np.zeros((T, B), bool)
    last[T - 1] = True
    _, ended = agent.unroll(params, obs, last, _carried(agent, state))
    assert not np.any(np.asarray(ended.sconv[:, :, 0]))
    assert np.all(np.any(np.asarray(ended.sconv[:, :, 1]) != 0, axis=-1))


STEP_RESETS = {
    "middle": ((5, 0), (5, 1)),
    "first_middle_last": ((0, 0), (5, 0), (T - 1, 0), (0, 1), (6, 1)),
}


@pytest.mark.parametrize("resets", sorted(STEP_RESETS))
def test_step_mode_equals_the_unroll(network, resets):
    """T=1, eleven times through the carry (as actors step): outputs and
    the final state. Episodes here stay within the full cache's 8 steps."""
    net, config = network
    obs, first, state = _inputs(net, config, 6, STEP_RESETS[resets])
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


def test_the_carry_of_a_fresh_state_and_its_size():
    core = configs.make_agent(configs.REGISTRY["pong_lfm2_moe"]).net._hybrid_core(
        bound=False
    )
    one = jax.eval_shape(lambda: core.initial_state(1))
    assert (one.conv, one.ssm, one.k_win, one.win_seg) == (None,) * 4
    assert one.k_full.shape == (1, 1, 2048, 512)
    assert one.full_seg.shape == (1, 2048)
    assert one.sconv.shape == (1, 3, 2, 2048)
    assert core.state_bytes_per_row() == 4 * (
        2 * 2048 * 512 + 2 * 2048 + 2 + 3 * 2 * 2048
    )


def test_the_learners_batcher_stacks_the_carry_and_reads_the_load(
    network, monkeypatch
):
    """Two unrolls through `Learner.enqueue` -> batcher -> one step: the
    batch that reaches the device holds every leaf of both carries, the
    gauges read the carry's size and the last step's routing, and under a
    small bound on outstanding copies the held experts' matrices (three
    axes) travel cut by expert and are published bit for bit."""
    from torched_impala_tpu.runtime import Learner
    from torched_impala_tpu.runtime import learner as learner_mod
    from torched_impala_tpu.runtime.learner import stack_trajectories
    from torched_impala_tpu.runtime.types import Trajectory
    from torched_impala_tpu.telemetry.registry import Registry

    net, config = network
    cfg = small_cfg()
    agent = configs.make_agent(cfg)
    obs, first, state = _inputs(net, config, 8, ((0, 0), (4, 0), (9, 1)))
    structure = jax.tree.structure(agent.initial_state(1))
    rng = np.random.default_rng(0)
    trajs = [
        Trajectory(
            obs=obs[:, i], first=first[:, i],
            actions=rng.integers(0, 6, T - 1).astype(np.int32),
            behaviour_logits=rng.standard_normal((T - 1, 6)).astype(np.float32),
            rewards=rng.standard_normal(T - 1).astype(np.float32),
            cont=np.ones(T - 1, np.float32),
            agent_state=jax.tree.unflatten(
                structure, [leaf[i : i + 1] for leaf in state]
            ),
            task=0,
        )
        for i in range(B)
    ]
    stacked = stack_trajectories(trajs)
    assert isinstance(stacked.agent_state, HybridCoreState)
    for leaf, want in zip(jax.tree.leaves(stacked.agent_state), state):
        np.testing.assert_array_equal(leaf, want)
    reg = Registry()
    # pieces of 2 KB: an expert's [64, 32] matrix (8 KB) is a piece of its own
    monkeypatch.setattr(learner_mod, "SNAPSHOT_COPY_AHEAD_BYTES", 8192)
    learner = Learner(
        agent=agent, optimizer=configs.make_optimizer(cfg),
        config=configs.make_learner_config(cfg),
        example_obs=configs.example_obs(cfg), rng=jax.random.key(0),
        telemetry=reg,
    )
    assert learner.kernels["moe"] == "ragged_dot"
    params = learner.params
    learner.start()
    try:
        for t in trajs:
            learner.enqueue(t)
        logs = learner.step_once(timeout=300)
        own = [np.array(x) for x in jax.tree.leaves(learner.params)]
        learner.drain()
        assert np.isfinite(float(logs["total_loss"]))
        published = jax.tree.leaves(learner.param_store.get_version(B * (T - 1)))
    finally:
        learner.stop()
    assert [x.tobytes() for x in published] == [x.tobytes() for x in own]
    experts = [x for x in published if x.ndim == 3]
    assert len(experts) == 12 and not any(x.flags.owndata for x in experts)
    gauges = {m.name: m.value for m in reg.metrics() if hasattr(m, "value")}
    _, _, stats = agent.unroll_with_stats(
        params, stacked.obs, stacked.first,
        jax.tree.map(jnp.asarray, stacked.agent_state),
    )
    held = gauges["core/moe_held_rows"]
    assert held == float(stats["moe_held_rows"]) > 0
    # four layers, at most 4 of a position's picks held: at most 4 T B rows
    assert held <= 4 * 4 * T * B
    assert gauges["core/moe_load_max_share"] == pytest.approx(
        float(stats["moe_load_max_share"])
    )
    assert gauges["core/moe_load_max_share"] >= 1.0
    assert gauges["core/state_bytes_per_row"] == (
        agent.net._hybrid_core(bound=False).state_bytes_per_row()
    )
    # every expert of every layer's three matrices is a piece of its own
    assert gauges["learner/publish_pieces"] >= len(own) + 12 * 7


def test_other_cores_have_no_load_gauges():
    from torched_impala_tpu.runtime import Learner
    from torched_impala_tpu.telemetry.registry import Registry

    cfg = dataclasses.replace(
        configs.REGISTRY["pong_phi4flash"], hybrid_d_model=32, hybrid_heads=2,
        hybrid_kv_heads=1, hybrid_head_dim=16, hybrid_window=4,
        hybrid_full_cache=8, hybrid_d_intermediate=32, hybrid_d_inner=128,
        hybrid_dt_rank=2, unroll_length=3, batch_size=2,
    )
    reg = Registry()
    learner = Learner(
        agent=configs.make_agent(cfg), optimizer=configs.make_optimizer(cfg),
        config=configs.make_learner_config(cfg),
        example_obs=configs.example_obs(cfg), rng=jax.random.key(0),
        telemetry=reg,
    )
    learner.stop()
    names = {m.name for m in reg.metrics()}
    assert "core/state_bytes_per_row" in names
    assert not {n for n in names if n.startswith("core/moe_")}
    assert learner.kernels["moe"] is None


def test_the_preset_states_the_published_widths():
    cfg = configs.REGISTRY["pong_lfm2_moe"]
    net = configs.make_agent(cfg).net
    core = dict(net.hybrid)
    module = net._hybrid_core(bound=False)  # its published constants too
    with open(os.path.join(
        ROOT, "benchmark", "configs", "pong_lfm2_moe_core.json"
    )) as f:
        stated = json.load(f)
    assert core["d_model"] == stated["hidden_size"] == 2048
    assert core["num_heads"] == stated["num_attention_heads"] == 32
    assert core["num_kv_heads"] == stated["num_key_value_heads"] == 8
    assert core["expert_width"] == stated["moe_intermediate_size"] == 1792
    assert core["experts_per_token"] == stated["num_experts_per_tok"] == 4
    assert core["num_experts"] == stated["published"]["num_experts"] == 32
    assert core["experts_held"] == (0, stated["num_experts"]) == (0, 8)
    assert module.conv_taps == stated["conv_L_cache"] == 3
    assert module.rope_theta == stated["rope_theta"] == 1e6
    assert stated["routed_scaling_factor"] == 1  # the program has no other
    assert core["layers"] == ("full_rope", "short_conv", "short_conv", "short_conv")
    assert [stated["layer_types"][i] for i in range(2, 6)] == [
        "full_attention", "conv", "conv", "conv"
    ]
    assert (core["norm"], core["mlp"], core["remat"]) == ("rms", "moe", True)
    assert jnp.dtype(core["dtype"]) == jnp.bfloat16
    assert (cfg.unroll_length, cfg.batch_size) == (2047, 2)
    # 413.4M parameters in the four blocks at these widths
    shapes = jax.eval_shape(
        lambda: configs.make_agent(cfg).init_params(
            jax.random.key(0), jnp.zeros((84, 84, 4), jnp.uint8)
        )
    )
    blocks = shapes["params"]["hybrid"]
    count = sum(
        int(np.prod(x.shape))
        for k, v in blocks.items() if k.startswith("block_")
        for x in jax.tree.leaves(v)
    )
    assert round(count / 1e6, 1) == 413.4


def test_routing_takes_its_choice_on_float32_scores():
    """The router's product is float32 at the highest precision whatever
    the products' precision: its scores are those of a float32 layer."""
    rng = np.random.default_rng(2)
    p = _moe_params(rng, 64, 32, 32)
    u = jnp.asarray(rng.standard_normal((1, 16, 64)), jnp.float32)
    moe = _MoE(
        num_experts=32, experts_per_token=4, experts_held=(0, 32), d_ff=32,
        dtype=jnp.bfloat16,
    )
    _, rows = moe.apply({"params": p}, u)
    scores = jax.nn.sigmoid(np.asarray(u[0], np.float64) @ np.asarray(p["router"], np.float64))
    chosen = np.argsort(-(scores + np.asarray(p["expert_bias"])), axis=1)[:, :4]
    want = np.bincount(chosen.ravel(), minlength=32)
    assert np.asarray(rows).tolist() == want.tolist()
