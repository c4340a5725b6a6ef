"""The routed layers of the latent-attention core (models/hybrid.py
`mlp="moe"` with a leading dense layer, a shared expert and a routed scale
of 2.5; preset `pong_joyai_flash`) on the CPU at the
small size of `tests/test_joyai_core.py`: the chip's share of a layer
against the uncut layer, the routed scale, the dense leading layer, the
latent carry's size, the carry and the load through the learner's batcher,
and the preset's published widths."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_joyai_core import (  # noqa: F401 (a fixture)
    B,
    F,
    LATENT,
    ROOT,
    T,
    _carried,
    _inputs,
    _latent_params,
    network,
    small_cfg,
)

from torched_impala_tpu import configs
from torched_impala_tpu.models.hybrid import HybridCore, _Block, _MoE


def _moe_params(rng, d=64, f=16, experts=256):
    def m(*shape):
        return jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[-2]), jnp.float32)

    return {
        "router": m(d, experts),
        "expert_bias": jnp.asarray(0.01 * rng.standard_normal(experts), jnp.float32),
        "gate": m(experts, d, f), "up": m(experts, d, f), "down": m(experts, f, d),
        "shared": {k: {"kernel": m(*s)} for k, s in
                   (("gate", (d, f)), ("up", (d, f)), ("down", (f, d)))},
    }


def _share(p, first, count):
    return {**p, **{k: p[k][first : first + count] for k in ("gate", "up", "down")}}


MOE = dict(num_experts=256, experts_per_token=8, d_ff=16, shared=16,
           routed_scale=2.5)


def _block_params(rng, moe):
    def g(n=64):
        return {"scale": jnp.asarray(1.0 + 0.1 * rng.standard_normal(n), jnp.float32)}

    return {"ln_latent": g(), "ln_mlp": g(), "latent_attention": _latent_params(rng), "moe": moe}


@pytest.mark.parametrize("side", ["program", "reference"])
def test_32_shares_of_256_experts_add_up_to_the_uncut_layer(network, side):
    """Each chip of the deployment holds 8 of the 256 routed experts and
    computes their part for every token; attention, the shared expert and
    the residual are computed alike on every chip. So the 32 shares' layers,
    with what every chip computes alike counted once, add up to the layer
    that holds all 256 (the program: whole blocks; the reference: the
    expert layer). The program's uncut layer is also the reference's."""
    net, config = network
    rng = np.random.default_rng(0)
    p = _moe_params(rng)
    s = net.sizes(config)
    if side == "reference":
        u = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
        pr = net._Precision(jnp.float32, jnp.float32)

        def layer(q, first, count):
            sz = s._replace(num_experts=count, experts_held_first=first)
            ref = {**q, "router": {"w": q["router"]},
                   "shared": {k: {"w": v["kernel"]} for k, v in q["shared"].items()}}
            with jax.default_matmul_precision("highest"):
                return net.moe(sz, ref, u, pr)

        whole = layer(p, 0, 256)
        zeroed = {**_share(p, 0, 8), **{k: 0 * p[k][:8] for k in ("gate", "up", "down")}}
        alike = layer(zeroed, 0, 8)  # the shared expert alone
        parts = [layer(_share(p, 8 * i, 8), 8 * i, 8) for i in range(32)]
        moe = _MoE(experts_held=(0, 256), **MOE)
        np.testing.assert_allclose(moe.apply({"params": p}, u)[0], whole, rtol=0, atol=1e-5)
    else:
        x = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
        bp = _block_params(rng, p)
        ckv = jnp.asarray(rng.standard_normal((B, F, 16)), jnp.float32)
        kpe = jnp.asarray(rng.standard_normal((B, 1, 128)), jnp.float32)
        seg0 = jnp.zeros((B,), jnp.int32)
        seg_q = jnp.zeros((B, T), jnp.int32)
        pos_q = jnp.asarray(F + np.arange(T)[None].repeat(B, 0), jnp.int32)
        cache_seg = jnp.zeros((B, F), jnp.int32)

        def layer(q, first, count):
            block = _Block(
                kind="latent",
                mixer=dict(num_heads=4, latent=LATENT, rope_theta=s.rope_theta),
                mlp=dict(experts_held=(first, count), **MOE), norm="rms", eps=1e-6,
            )
            out, _, _ = block.apply(
                {"params": {**bp, "moe": q}}, x, (ckv, kpe, cache_seg),
                jnp.zeros((B, T), bool), seg_q, seg0, pos_q,
            )
            return out

        whole = layer(p, 0, 256)
        zeroed = {**_share(p, 0, 8), **{k: 0 * p[k][:8] for k in ("gate", "up", "down")}}
        alike = layer(zeroed, 0, 8)  # residual, attention and shared expert
        parts = [layer(_share(p, 8 * i, 8), 8 * i, 8) for i in range(32)]
    np.testing.assert_allclose(
        sum(x - alike for x in parts) + alike, whole, rtol=0, atol=1e-4
    )
    routed = [float(jnp.max(jnp.abs(x - alike))) for x in parts]
    assert sum(r > 1e-3 for r in routed) >= 16  # most shares route something


def test_the_routed_scale_and_the_normaliser():
    """The chosen experts' weights: their scores over their sum plus the
    epsilon, times the scale, so the routed part at 2.5 is 2.5 times the
    routed part at 1, and the shared expert's is not scaled."""
    rng = np.random.default_rng(3)
    p = _moe_params(rng)
    u = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)

    def out(scale, shared=16, q=p):
        moe = _MoE(experts_held=(0, 256), **{**MOE, "routed_scale": scale, "shared": shared})
        return moe.apply({"params": q}, u)[0]

    routed = {k: v for k, v in p.items() if k != "shared"}
    np.testing.assert_allclose(
        out(2.5, 0, routed), 2.5 * out(1.0, 0, routed), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        out(2.5) - out(2.5, 0, routed), out(1.0) - out(1.0, 0, routed),
        rtol=0, atol=1e-5,
    )


def test_the_dense_leading_layer(network):
    """The first `dense_layers` blocks take the gated MLP of width
    `d_intermediate` and route nothing; the others take the routed experts
    and the shared expert, and only they count rows."""
    net, config = network
    agent = configs.make_agent(small_cfg())
    params = net.to_program_params(net.init_params(3, config))
    shapes = jax.eval_shape(
        lambda: agent.init_params(jax.random.key(0), jnp.zeros((84, 84, 4), jnp.uint8))
    )["params"]["hybrid"]
    assert set(shapes["block_0"]) == {"ln_latent", "latent_attention", "ln_mlp", "mlp"}
    assert shapes["block_0"]["mlp"]["gate"]["kernel"].shape == (64, 48)
    for i in range(1, 5):
        assert set(shapes[f"block_{i}"]) == {"ln_latent", "latent_attention", "ln_mlp", "moe"}
        assert shapes[f"block_{i}"]["moe"]["shared"]["down"]["kernel"].shape == (16, 64)
    obs, first, state = _inputs(net, config, 2, ())
    _, _, stats = agent.unroll_with_stats(params, obs, first, _carried(agent, state))
    # the four routed layers: each position's 8 picks over 256, 8 held
    assert 0 <= float(stats["moe_held_rows"]) <= 4 * 8 * T * B
    core = HybridCore(**{**dict(agent.net.hybrid), "dense_layers": 0})
    every = jax.eval_shape(
        lambda: core.init(
            jax.random.key(0), jnp.zeros((T, B, 512)), jnp.zeros((T, B), bool),
            core.initial_state(B),
        )
    )["params"]
    assert "moe" in every["block_0"] and "mlp" not in every["block_0"]


def test_the_carry_of_a_fresh_state_and_its_size():
    core = configs.make_agent(configs.REGISTRY["pong_joyai_flash"]).net._hybrid_core(
        bound=False
    )
    one = jax.eval_shape(lambda: core.initial_state(1))
    assert (one.conv, one.ssm, one.k_win, one.k_full, one.sconv) == (None,) * 5
    assert one.lat_ckv.shape == (1, 5, 2048, 512)
    assert one.lat_kpe.shape == (1, 5, 1024, 128)  # two positions a row
    assert one.full_seg.shape == one.full_pos.shape == (1, 2048)
    # 576 numbers a position a layer, against 10,240 expanded
    assert core.state_bytes_per_row() == 4 * (5 * 2048 * (512 + 64) + 2 * 2048 + 2)
    bad = HybridCore(layers=("latent",), latent=(24, 16, 8, 6, 8), full_cache=16)
    with pytest.raises(ValueError, match="rows of 128"):
        bad.initial_state(1)


def test_the_learners_batcher_stacks_the_carry_and_reads_the_load(network):
    """Two unrolls through `Learner.enqueue` -> batcher -> one step: the
    batch holds every leaf of both carries, the gauges read the carry's
    size and the routed layers' load, and `kernels` names the latent
    attention's path."""
    from torched_impala_tpu.runtime import Learner
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
            agent_state=jax.tree.unflatten(structure, [leaf[i : i + 1] for leaf in state]),
            task=0,
        )
        for i in range(B)
    ]
    stacked = stack_trajectories(trajs)
    for leaf, want in zip(jax.tree.leaves(stacked.agent_state), state):
        np.testing.assert_array_equal(leaf, want)
    reg = Registry()
    learner = Learner(
        agent=agent, optimizer=configs.make_optimizer(cfg),
        config=configs.make_learner_config(cfg),
        example_obs=configs.example_obs(cfg), rng=jax.random.key(0),
        telemetry=reg,
    )
    assert learner.kernels["attention_latent"] == "einsum"  # no TPU here
    assert learner.kernels["moe"] == "ragged_dot"
    params = learner.params
    learner.start()
    try:
        for t in trajs:
            learner.enqueue(t)
        logs = learner.step_once(timeout=300)
        learner.drain()
    finally:
        learner.stop()
    assert np.isfinite(float(logs["total_loss"]))
    gauges = {m.name: m.value for m in reg.metrics() if hasattr(m, "value")}
    _, _, stats = agent.unroll_with_stats(
        params, stacked.obs, stacked.first,
        jax.tree.map(jnp.asarray, stacked.agent_state),
    )
    assert gauges["core/moe_held_rows"] == float(stats["moe_held_rows"])
    assert gauges["core/moe_load_max_share"] == pytest.approx(
        float(stats["moe_load_max_share"])
    )
    assert gauges["core/state_bytes_per_row"] == 4 * (5 * F * 24 + 2 * F + 2)


def test_the_preset_states_the_published_widths():
    cfg = configs.REGISTRY["pong_joyai_flash"]
    net = configs.make_agent(cfg).net
    core = dict(net.hybrid)
    with open(os.path.join(
        ROOT, "benchmark", "configs", "pong_joyai_flash_core.json"
    )) as f:
        stated = json.load(f)
    assert core["layers"] == ("latent",) * stated["num_hidden_layers"]
    assert core["d_model"] == stated["hidden_size"] == 2048
    assert core["num_heads"] == stated["num_attention_heads"] == 32
    assert core["latent"] == (
        stated["q_lora_rank"], stated["kv_lora_rank"], stated["qk_nope_head_dim"],
        stated["qk_rope_head_dim"], stated["v_head_dim"],
    ) == (1536, 512, 128, 64, 128)
    assert core["rope_theta"] == stated["rope_theta"] == 32_000_000
    assert core["ln_eps"] == stated["rms_norm_eps"] == 1e-6
    assert core["d_intermediate"] == stated["intermediate_size"] == 7168
    assert core["dense_layers"] == stated["first_k_dense_replace"] == 1
    assert core["num_experts"] == stated["published"]["n_routed_experts"] == 256
    assert core["experts_held"] == (0, stated["n_routed_experts"]) == (0, 8)
    assert core["experts_per_token"] == stated["num_experts_per_tok"] == 8
    assert core["expert_width"] == stated["moe_intermediate_size"] == 768
    assert core["shared_expert"] == stated["n_shared_experts"] * 768
    assert core["routed_scale"] == stated["routed_scaling_factor"] == 2.5
    # the normaliser's epsilon is the program's one (`_MoE`'s), as stated
    assert "router_eps" not in core and stated["model"]["router_eps"] == 1e-6
    assert (core["norm"], core["mlp"], core["remat"]) == ("rms", "moe", True)
    assert jnp.dtype(core["dtype"]) == jnp.bfloat16
    assert (cfg.unroll_length, cfg.batch_size, cfg.hybrid_full_cache) == (2047, 2, 2048)
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
    assert count == 70_391_808 + 4 * 69_343_488  # 347.8M
