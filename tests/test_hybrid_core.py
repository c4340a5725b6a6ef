"""The hybrid temporal core (models/hybrid.py) on the CPU at a small size
(d_model 64, d_inner 128, d_state 16, 4 query heads on 2 key/value heads,
window 4, full cache 8, T 11, B 2), float32: against the plain forward
pass of the benchmark's network file (outputs and gradients), step mode
against the unroll, the mixed carry through the learner's batcher, the
actor and the server."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torched_impala_tpu import configs
from torched_impala_tpu.models.hybrid import HybridCore, HybridCoreState

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T, B = 11, 2
SMALL = dict(
    hybrid_d_model=64, hybrid_heads=4, hybrid_kv_heads=2, hybrid_head_dim=16,
    hybrid_window=4, hybrid_full_cache=8, hybrid_d_intermediate=128,
    hybrid_d_inner=128, hybrid_d_state=16, hybrid_dt_rank=4,
    hybrid_dtype="float32", compute_dtype="float32",
)


def small_cfg(**more):
    return dataclasses.replace(
        configs.REGISTRY["pong_phi4flash"], unroll_length=T - 1,
        batch_size=B, **SMALL, **more,
    )


@pytest.fixture(scope="module")
def network():
    """The benchmark's network file and its configuration at the test's
    sizes: the plain float32 forward pass."""
    from benchmark import driver

    spec = driver.Spec(ROOT)
    config = spec.config("pong_phi4flash_core")
    config["model"].update(
        hidden_size=64, intermediate_size=128, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, sliding_window=4, full_cache=8,
        d_inner=128, d_state=16, dt_rank=4,
    )
    config.update(batch_size=B, unroll_length=T - 1)
    return spec.network(config), config


def _inputs(net, config, seed, firsts, other_episode=True):
    rng = np.random.default_rng(seed)
    obs = rng.integers(0, 256, size=(T, B, 84, 84, 4), dtype=np.uint8)
    first = np.zeros((T, B), bool)
    for t, b in firsts:
        first[t, b] = True
    parts = net.draw_state(rng, B, config)
    state = [np.concatenate(list(p), axis=0) for p in parts]
    if other_episode:
        # the running episode holds the newest 3 slots of the full cache
        # (and of the window's), the rest is the episode before it
        seg = state[11]
        for slots, width in ((4, 4), (8, 8)):
            state[slots] = np.where(
                np.arange(width)[None, :] >= width - 3,
                seg[:, None], seg[:, None] - 1,
            ).astype(np.int32)
    return obs, first, tuple(state)


RESETS = {
    "none": (),
    "first_position": ((0, 0), (0, 1)),
    "middle_position": ((5, 0),),
    "last_position": ((T - 1, 1),),
    "first_middle_last": ((0, 0), (5, 0), (T - 1, 0), (6, 1)),
    # window 4: the query at step 7 sees steps 4..7; a reset at step 4 is
    # exactly one window behind it, one at step 3 just outside
    "one_window_behind_a_query": ((4, 0), (3, 1)),
    "two_in_one_window": ((5, 0), (7, 0)),
    "every_position": tuple((t, 0) for t in range(T)),
}


@pytest.mark.parametrize("resets", sorted(RESETS))
def test_core_matches_the_network_files_forward(network, resets):
    """Outputs and every parameter's gradient, with the caches partly
    from another episode."""
    net, config = network
    obs, first, state = _inputs(net, config, 3, RESETS[resets])
    weights = net.init_params(17, config)
    agent = configs.make_agent(small_cfg())
    params = net.to_program_params(weights)
    carried = jax.tree.unflatten(
        jax.tree.structure(agent.initial_state(B)), state
    )
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
        # a gradient adds up 22 positions' terms through four blocks in
        # another order than the plain pass: 1e-5 on the outputs, 5e-5 of
        # the leaf's largest element here (the widest read 2e-5)
        scale = float(jnp.max(jnp.abs(w))) + 1e-6
        np.testing.assert_allclose(
            g / scale, w / scale, rtol=0, atol=5e-5,
            err_msg=jax.tree_util.keystr(path),
        )


@pytest.mark.parametrize("kernel", ["pallas", "einsum"])
def test_attention_kernel_and_written_out_mask_agree(network, kernel):
    """The fused attention (interpreted here) and the mask written out
    give the network file's outputs alike; so does the scan's kernel."""
    net, config = network
    obs, first, state = _inputs(net, config, 4, RESETS["first_middle_last"])
    weights = net.init_params(19, config)
    agent = configs.make_agent(small_cfg(transformer_dense_kernel=kernel))
    assert dict(agent.net.hybrid)["attention_kernel"] == kernel
    carried = jax.tree.unflatten(
        jax.tree.structure(agent.initial_state(B)), state
    )
    out, _ = agent.unroll(net.to_program_params(weights), obs, first, carried)
    with jax.default_matmul_precision("highest"):
        want, _ = net.forward(net.sizes(config), weights, obs, first, state)
    np.testing.assert_allclose(out.policy_logits, want, rtol=0, atol=1e-5)


# Step mode sees what the caches hold: the full layer 8 positions, where
# the unroll sees its cache and the unroll. The two agree as long as an
# episode's history fits the full cache, so these keep every episode at 8
# steps or fewer (3 of them in the cache at the start).
STEP_RESETS = {
    "middle": ((5, 0), (5, 1)),
    "first_middle_last": ((0, 0), (5, 0), (T - 1, 0), (0, 1), (6, 1)),
    "one_window_behind_a_query": ((4, 0), (3, 1)),
}


@pytest.mark.parametrize("resets", sorted(STEP_RESETS))
def test_step_mode_equals_the_unroll(network, resets):
    """T=1, eleven times through the carry: outputs and the final state
    (scan states, convolution windows, both caches, counters)."""
    net, config = network
    obs, first, state = _inputs(net, config, 6, STEP_RESETS[resets])
    agent = configs.make_agent(small_cfg())
    params = net.to_program_params(net.init_params(23, config))
    carried = jax.tree.unflatten(
        jax.tree.structure(agent.initial_state(B)), state
    )
    out, final = agent.unroll(params, obs, first, carried)
    step = jax.jit(
        lambda s, o, f: agent.net.apply(params, o, f, s, unroll=False)
    )
    s, logits = carried, []
    for t in range(T):
        o, s = step(s, obs[t], first[t])
        logits.append(o.policy_logits)
    np.testing.assert_allclose(
        np.stack(logits), out.policy_logits, rtol=0, atol=2e-5
    )
    assert isinstance(s, HybridCoreState)
    for name, a, b in zip(s._fields, s, final):
        np.testing.assert_allclose(a, b, rtol=0, atol=2e-5, err_msg=name)


def test_the_carry_of_a_fresh_state_and_its_size():
    core = HybridCore(parent=None)
    one = jax.eval_shape(lambda: core.initial_state(1))
    assert one.conv.shape == (1, 2, 3, 5120)
    assert one.ssm.shape == (1, 2, 16, 5120)
    assert one.k_win.shape == (1, 1, 512, 1280)
    assert one.k_full.shape == (1, 1, 2048, 1280)
    assert one.win_seg.shape == (1, 512) and one.full_pos.shape == (1, 2048)
    # 2 x (61 KB + 328 KB) + 2 x 2.6 MB + 2 x 10.5 MB + counters
    assert core.state_bytes_per_row() == 4 * (
        2 * (3 * 5120 + 5120 * 16) + 2 * 512 * 1280 + 2 * 2048 * 1280
        + 2 * 512 + 2 * 2048 + 2
    )
    fresh = HybridCore(parent=None, window=4, full_cache=8).initial_state(3)
    assert (np.asarray(fresh.win_seg) == -1).all()
    assert (np.asarray(fresh.full_seg) == -1).all()
    with pytest.raises(ValueError, match="unknown layer kinds"):
        HybridCore(parent=None, layers=("mamba", "gmu")).initial_state(1)


def test_the_learners_batcher_stacks_the_mixed_carry_leaf_by_leaf(network):
    """Two unrolls through `Learner.enqueue` -> batcher -> one step; the
    batch that reaches the device holds every leaf of both carries, and
    the gauges read the carry's size and the episode starts."""
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
            agent_state=jax.tree.unflatten(
                structure, [leaf[i : i + 1] for leaf in state]
            ),
            task=0,
        )
        for i in range(B)
    ]
    stacked = stack_trajectories(trajs)
    assert isinstance(stacked.agent_state, HybridCoreState)
    for leaf, want in zip(stacked.agent_state, state):
        np.testing.assert_array_equal(leaf, want)
    reg = Registry()
    learner = Learner(
        agent=agent, optimizer=configs.make_optimizer(cfg),
        config=configs.make_learner_config(cfg),
        example_obs=configs.example_obs(cfg), rng=jax.random.key(0),
        telemetry=reg,
    )
    assert learner.kernels["attention"] == "einsum"  # no TPU here
    assert learner.kernels["selective_scan"] == "xla_scan"
    learner.start()
    try:
        for t in trajs:
            learner.enqueue(t)
        logs = learner.step_once(timeout=300)
        learner.drain()
        assert np.isfinite(float(logs["total_loss"]))
    finally:
        learner.stop()
    gauges = {m.name: m.value for m in reg.metrics() if hasattr(m, "value")}
    assert gauges["core/resets_in_batch"] == 3
    assert gauges["core/state_bytes_per_row"] == (
        agent.net._hybrid_core(bound=False).state_bytes_per_row()
    )


def test_other_cores_have_no_core_gauges():
    from torched_impala_tpu.runtime import Learner
    from torched_impala_tpu.telemetry.registry import Registry

    cfg = configs.REGISTRY["cartpole"]
    reg = Registry()
    learner = Learner(
        agent=configs.make_agent(cfg), optimizer=configs.make_optimizer(cfg),
        config=configs.make_learner_config(cfg),
        example_obs=configs.example_obs(cfg), rng=jax.random.key(0),
        telemetry=reg,
    )
    learner.stop()
    assert not [m.name for m in reg.metrics() if m.name.startswith("core/")]
    assert learner.kernels["selective_scan"] is None


def test_the_preset_states_the_published_widths():
    cfg = configs.REGISTRY["pong_phi4flash"]
    core = dict(configs.make_agent(cfg).net.hybrid)
    with open(os.path.join(
        ROOT, "benchmark", "configs", "pong_phi4flash_core.json"
    )) as f:
        stated = json.load(f)
    assert core["d_model"] == stated["hidden_size"] == 2560
    assert core["d_intermediate"] == stated["intermediate_size"] == 10240
    assert core["num_heads"] == stated["num_attention_heads"] == 40
    assert core["num_kv_heads"] == stated["num_key_value_heads"] == 20
    assert core["window"] == stated["sliding_window"] == 512
    assert core["head_dim"] == 64 and core["full_cache"] == 2048
    assert (core["d_inner"], core["d_state"], core["d_conv"]) == (5120, 16, 4)
    assert core["dt_rank"] == 160 and core["remat"] is True
    assert core["layers"] == ("mamba", "window", "mamba", "full")
    assert jnp.dtype(core["dtype"]) == jnp.bfloat16
    assert (cfg.unroll_length, cfg.batch_size) == (2047, 2)
    assert cfg.obs_shape == (84, 84, 4) and cfg.num_actions == 6


@pytest.mark.parametrize("forced,want", [("auto", "einsum"),
                                         ("pallas", "pallas"),
                                         ("einsum", "einsum")])
def test_attention_kernel_resolution(forced, want):
    """Off a TPU 'auto' keeps the written-out mask; on one the hybrid core
    takes the fused kernel whatever PALLAS_MIN_SCORE_ELEMS says (the small
    unroll here is far under it)."""
    cfg = small_cfg(transformer_dense_kernel=forced)
    assert (T * (4 + T)) < configs.PALLAS_MIN_SCORE_ELEMS
    assert dict(configs.make_agent(cfg).net.hybrid)["attention_kernel"] == want


def _mlp_agent():
    from torched_impala_tpu.models import Agent, ImpalaNet
    from torched_impala_tpu.models.torsos import MLPTorso

    return Agent(ImpalaNet(
        num_actions=3, torso=MLPTorso(hidden_sizes=(16,)), core="hybrid",
        hybrid=(
            ("d_model", 32), ("num_heads", 4), ("num_kv_heads", 2),
            ("head_dim", 8), ("window", 4), ("full_cache", 8),
            ("d_intermediate", 64), ("d_inner", 128), ("dt_rank", 2),
        ),
    ))


def test_the_actors_step_threads_the_mixed_carry():
    """Step mode (T=1) as the actor calls it: positions and episode
    counters advance, the newest cache slot is the step's own, the scan
    state moves, and a start resets it."""
    agent = _mlp_agent()
    obs = np.random.default_rng(0).normal(size=(3, 5)).astype(np.float32)
    params = agent.init_params(jax.random.key(1), obs[0])
    step = lambda first, state: agent.step(  # noqa: E731
        params, jax.random.key(0), obs, jnp.asarray(first), state
    )
    one = step([True] * 3, agent.initial_state(3)).state
    assert isinstance(one, HybridCoreState)
    assert np.asarray(one.pos).tolist() == [1, 1, 1]
    assert np.asarray(one.seg).tolist() == [1, 1, 1]
    assert (np.asarray(one.win_seg)[:, -1] == 1).all()
    assert (np.asarray(one.win_seg)[:, :-1] == -1).all()
    two = step([False, False, True], one).state
    assert np.asarray(two.pos).tolist() == [2, 2, 2]
    assert np.asarray(two.seg).tolist() == [1, 1, 2]
    assert (np.asarray(two.full_seg)[:2, -2:] == 1).all()
    ssm1, ssm2 = np.asarray(one.ssm), np.asarray(two.ssm)
    assert np.abs(ssm1).max() > 0
    assert not np.allclose(ssm2[0], ssm1[0])  # went on from the first step
    np.testing.assert_allclose(ssm2[2], ssm1[2], atol=1e-6)  # started anew


def test_the_mixed_carry_lives_on_the_policy_server():
    """Per-client slots of the carry, leaf by leaf: a client stepping a
    sequence through the server gets exactly the actions of a direct
    `agent.step` loop chaining its own carry (tests/test_serving.py's
    LSTM case, with scan states, convolution windows and two caches)."""
    from torched_impala_tpu.runtime.param_store import ParamStore
    from torched_impala_tpu.serving import InProcessClient, PolicyServer
    from torched_impala_tpu.serving.registry import VersionRegistry
    from torched_impala_tpu.telemetry.registry import Registry

    agent = _mlp_agent()
    seq = np.random.default_rng(3).normal(size=(10, 5)).astype(np.float32)
    params = agent.init_params(jax.random.key(0), seq[0])
    store = ParamStore()
    store.publish(0, params)
    server = PolicyServer(
        agent=agent,
        registry=VersionRegistry.serving_latest(store, telemetry=Registry()),
        example_obs=np.zeros((5,), np.float32),
        telemetry=Registry(), max_clients=4, max_batch=2, max_wait_s=0.0,
    ).start()
    try:
        starts = {0, 6}  # the second episode starts inside the sequence
        ref, state = [], agent.initial_state(1)
        for t in range(seq.shape[0]):
            out = agent.step(
                params, jax.random.key(0), seq[t][None],
                np.asarray([t in starts]), state,
            )
            ref.append(int(np.argmax(np.asarray(out.policy_logits))))
            state = out.state
        client = InProcessClient(server)
        got = [client.act(seq[t], t in starts) for t in range(seq.shape[0])]
        assert got == ref
    finally:
        server.close()


class _Walk:
    """What a learner asks of the runtime while it publishes: every
    `copy_to_host_async` (`("ask", id, shape, bytes)`) and every piece
    read (`("read", id, shape, bytes)`, as its bytes have landed, on
    whichever thread), in order."""

    def __init__(self, monkeypatch, bound=None):
        from torched_impala_tpu.runtime import learner as learner_mod

        if bound is not None:
            monkeypatch.setattr(
                learner_mod, "SNAPSHOT_COPY_AHEAD_BYTES", bound
            )
        self.events = events = []
        impl = type(jnp.zeros(1))
        ask = impl.copy_to_host_async
        monkeypatch.setattr(
            impl, "copy_to_host_async",
            lambda self: (
                events.append(("ask", id(self), self.shape, self.nbytes)),
                ask(self),
            )[1],
        )
        read = learner_mod.owned_array
        monkeypatch.setattr(
            learner_mod, "owned_array",
            lambda piece, out=None: (
                read(piece, out),
                events.append(("read", id(piece), piece.shape, piece.nbytes)),
            )[0],
        )

    def outstanding(self):
        """`(bytes, pieces)` requested and unread after each event, and
        whatever was asked for and never read."""
        unread, series = {}, []
        for kind, key, _, nbytes in self.events:
            if kind == "ask":
                unread[key] = nbytes
            else:
                unread.pop(key, None)
            series.append((sum(unread.values()), len(unread)))
        return series, unread


def _unrolls(network, agent):
    from torched_impala_tpu.runtime.types import Trajectory

    net, config = network
    obs, first, state = _inputs(net, config, 9, ((4, 0),))
    structure = jax.tree.structure(agent.initial_state(1))
    return [
        Trajectory(
            obs=obs[:, i], first=first[:, i],
            actions=np.zeros(T - 1, np.int32),
            behaviour_logits=np.zeros((T - 1, 6), np.float32),
            rewards=np.ones(T - 1, np.float32),
            cont=np.ones(T - 1, np.float32),
            agent_state=jax.tree.unflatten(
                structure, [leaf[i : i + 1] for leaf in state]
            ),
            task=0,
        )
        for i in range(B)
    ]


_RUNS: dict = {}


def _publishing_run(network, bound):
    """Construction and two steps of the small hybrid learner under
    `bound` (run once a bound): the walk, the three versions as
    published, the learner's own parameters at each, the gauges after
    the last."""
    from torched_impala_tpu.runtime import Learner
    from torched_impala_tpu.telemetry.registry import Registry

    if bound in _RUNS:
        return _RUNS[bound]
    with pytest.MonkeyPatch.context() as patch:
        walk = _Walk(patch, bound)
        cfg = small_cfg()
        agent = configs.make_agent(cfg)
        reg = Registry()
        learner = Learner(
            agent=agent, optimizer=configs.make_optimizer(cfg),
            config=configs.make_learner_config(cfg),
            example_obs=configs.example_obs(cfg), rng=jax.random.key(0),
            telemetry=reg,
        )
        events = list(walk.events)  # the reads below are not the learner's
        own = {0: [np.array(x) for x in jax.tree.leaves(learner.params)]}
        learner.start()
        try:
            for step in (1, 2):
                for unroll in _unrolls(network, agent):
                    learner.enqueue(unroll)
                del walk.events[:]
                learner.step_once(timeout=300)
                events += walk.events
                own[step * B * (T - 1)] = [
                    np.array(x) for x in jax.tree.leaves(learner.params)
                ]
            del walk.events[:]
            learner.drain()
            events += walk.events
            assert learner.param_store.versions() == sorted(own)
            published = {
                version: jax.tree.leaves(
                    learner.param_store.get_version(version)
                )
                for version in own
            }
        finally:
            learner.stop()
        walk.events[:] = events
    gauges = {m.name: m.value for m in reg.metrics() if hasattr(m, "value")}
    _RUNS[bound] = walk, published, own, gauges
    return _RUNS[bound]


def _rows(leaf, bound):
    """`learner._piece_rows` of a host leaf as if on one device, under
    `bound` (`test_piece_rows` holds the function itself to numbers)."""
    import types

    from torched_impala_tpu.runtime import learner as learner_mod

    one_device = types.SimpleNamespace(is_fully_replicated=True)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(learner_mod, "SNAPSHOT_COPY_AHEAD_BYTES", bound)
        return learner_mod._piece_rows(types.SimpleNamespace(
            shape=leaf.shape, nbytes=leaf.nbytes, sharding=one_device
        ))


CUT = 65536  # a bound whose pieces cut the 128 KB input projection in 8


def _alone(versions):
    """Every leaf of every version is one C-contiguous array whose bytes
    are its own, or its version's slab's (a cut leaf: a view, whose slab
    no other version's leaves lie in): nobody else writes them while a
    reader holds them. Returns the slabs."""
    slabs = []
    for leaves in versions:
        views = [x for x in leaves if not x.flags.owndata]
        assert all(x.flags.c_contiguous and x.flags.aligned for x in leaves)
        if views:
            (slab,) = {id(x.base): x.base for x in views}.values()
            assert isinstance(slab, np.ndarray) and slab.dtype == np.uint8
            assert all(np.shares_memory(x, slab) for x in views)
            slabs.append(slab)
    assert not any(
        np.shares_memory(a, b) for a in slabs for b in slabs if a is not b
    )
    return slabs


@pytest.mark.parametrize("bound", [64 << 20, 8192])
def test_a_snapshots_copies_are_requested_within_a_bound(network, bound):
    """The copies of a version to the host stand requested and unread by
    `SNAPSHOT_COPY_AHEAD_BYTES` at the most (at 1.76 GB a version, all
    requested at once made the runtime's host side grow without bound):
    a tree that fits is requested whole as it is queued; a larger one
    travels in pieces of a quarter of the bound, none of them ever over
    it; what is published is the same either way, every version, the
    step's own parameters in bytes no other version shares."""
    walk, published, own, gauges = _publishing_run(network, bound)
    leaves = len(own[0])
    version = 2 * B * (T - 1)
    for got, want in zip(published[version], own[version]):
        np.testing.assert_array_equal(got, want)
    slabs = _alone(published.values())
    # a slab a step's version with a cut leaf; the construction's comes
    # whole from the live parameters, and so does a tree that fits
    assert len(slabs) == (0 if bound == 64 << 20 else 2)
    # three versions: the construction's read leaf by leaf from the live
    # parameters, the two steps' piece by piece, each piece once
    pieces = int(gauges["learner/publish_pieces"])
    assert sum(e[0] == "read" for e in walk.events) == leaves + 2 * pieces
    series, unread = walk.outstanding()
    assert 0 < max(held for held, _ in series) <= bound and not unread
    asked = {e[2] for e in walk.events if e[0] == "ask"}
    # the core's input projection, 128 KB: requested whole where it fits
    # a piece, in blocks of 8 rows (2 KB) under the small bound
    assert ((512, 64) in asked) is (bound > 512 * 64 * 4)
    if bound == 64 << 20:
        # a tree that fits: every leaf requested before the first is
        # read, and the pieces are the leaves
        assert [e[0] for e in walk.events].index("read") == leaves
        assert pieces == leaves
    else:
        assert (8, 64) in asked and pieces > leaves


@pytest.mark.parametrize("version", [0, 1, 2])
def test_a_version_cut_in_pieces_is_published_whole(network, version):
    """(i) With one leaf cut in eight and others in two, every published
    leaf is the learner's own parameter of that version bit for bit, one
    C-contiguous array a leaf, a cut one a view of its version's slab:
    the construction's version and each step's."""
    walk, published, own, _ = _publishing_run(network, CUT)
    cut = [x for x in own[0] if _rows(x, CUT)]
    assert max(-(-x.shape[0] // _rows(x, CUT)) for x in cut) >= 3
    version *= B * (T - 1)
    assert len(published[version]) == len(own[version])
    for got, want in zip(published[version], own[version]):
        assert isinstance(got, np.ndarray) and got.dtype == want.dtype
        assert got.shape == want.shape and got.tobytes() == want.tobytes()
        assert got.flags.owndata is not (version > 0 and bool(_rows(want, CUT)))
    _alone([published[version]])
    # the steps moved the parameters: the versions are not one tree
    assert any(
        a.tobytes() != b.tobytes()
        for a, b in zip(published[0], published[2 * B * (T - 1)])
    )


def test_the_next_pieces_stand_requested_while_one_is_read(network):
    """(ii) Requested-and-unread bytes never pass the bound, and at some
    moment more than one piece stood requested: the link moves the next
    pieces while the host writes this one into its leaf."""
    walk, _, own, _ = _publishing_run(network, CUT)
    series, unread = walk.outstanding()
    assert not unread
    assert max(held for held, _ in series) <= CUT
    # four pieces of 16 KB fill the bound
    assert max(count for _, count in series) >= 4
    # the torso's dense kernel, [3136, 512], travels in 392 blocks of 8
    # rows: as one of them lands the next three stand requested or are
    # being read (on two threads: fewer where a later one landed
    # first), never more, which a walk over whole leaves cannot do with
    # a leaf over the bound
    ahead, unread = [], {}
    for kind, key, shape, _ in walk.events:
        if kind == "ask":
            unread[key] = shape
        else:
            unread.pop(key, None)
            if shape == (8, 512):
                ahead.append(list(unread.values()).count((8, 512)))
    assert len(ahead) == 2 * 392
    assert max(ahead) == 3 and sorted(ahead)[len(ahead) // 2] >= 2


def test_the_gauges_count_pieces_and_outstanding_bytes(network):
    """(iii) `learner/publish_pieces` is the last version's pieces (its
    leaves where nothing is cut), `learner/publish_outstanding_peak_bytes`
    the most that stood requested and unread, within the bound."""
    walk, _, own, gauges = _publishing_run(network, CUT)
    leaves = own[0]
    want = sum(
        -(-x.shape[0] // _rows(x, CUT)) if _rows(x, CUT) else 1
        for x in leaves
    )
    assert gauges["learner/publish_pieces"] == want > len(leaves)
    series, _ = walk.outstanding()
    peak = gauges["learner/publish_outstanding_peak_bytes"]
    assert 0 < peak <= CUT
    # the walk saw two versions' requests at once (step k's queued before
    # step k-1's are read), the gauge counts one version's
    assert peak <= max(held for held, _ in series) <= 2 * CUT
    assert gauges["learner/publish_gb_per_s"] > 0
    _, _, _, whole = _publishing_run(network, 64 << 20)
    assert whole["learner/publish_pieces"] == len(leaves)
    assert whole["learner/publish_outstanding_peak_bytes"] == sum(
        x.nbytes for x in leaves
    )


def test_an_lstm_presets_copies_are_all_asked_then_all_read(monkeypatch):
    """(iv) At the real bound a 6 MB tree (the breakout preset's, 48
    leaves, none over a piece) is published call for call as before the
    pieces: every leaf asked for as the snapshot is queued, every leaf
    read after the wait on the loop's own thread, in the tree's order
    (the readers' threads take only the pieces of cut leaves)."""
    from torched_impala_tpu.runtime import Learner
    from torched_impala_tpu.runtime.types import Trajectory

    cfg = dataclasses.replace(
        configs.REGISTRY["breakout"], batch_size=2, unroll_length=2
    )
    walk = _Walk(monkeypatch)
    agent = configs.make_agent(cfg)
    learner = Learner(
        agent=agent, optimizer=configs.make_optimizer(cfg),
        config=configs.make_learner_config(cfg),
        example_obs=configs.example_obs(cfg), rng=jax.random.key(0),
    )
    shapes = [x.shape for x in jax.tree.leaves(learner.params)]
    assert len(shapes) == 48
    sequences = [list(walk.events)]
    learner.start()
    try:
        state = jax.tree.map(np.asarray, agent.initial_state(1))
        for _ in range(2):
            learner.enqueue(Trajectory(
                obs=np.zeros((3, 84, 84, 4), np.uint8),
                first=np.zeros(3, bool), actions=np.zeros(2, np.int32),
                behaviour_logits=np.zeros((2, 4), np.float32),
                rewards=np.ones(2, np.float32), cont=np.ones(2, np.float32),
                agent_state=state, task=0,
            ))
        del walk.events[:]
        learner.step_once(timeout=300)
        learner.drain()
        sequences.append(list(walk.events))
    finally:
        learner.stop()
    for events in sequences:  # the construction's version, the step's
        assert [(e[0], e[2]) for e in events] == (
            [("ask", shape) for shape in shapes]
            + [("read", shape) for shape in shapes]
        )
        assert [e[1] for e in events[:48]] == [e[1] for e in events[48:]]


@pytest.mark.parametrize("shape,sharded,want", [
    ((2560, 10240), False, 368),   # 105 MB: 7 pieces of 15.1 MB
    ((10240, 2560), False, 1464),  # 105 MB: 6 x 1464 rows and 1456
    ((5120, 2560), False, 1280),   # 52 MB: 4 even pieces
    ((2560, 2560), False, 1280),   # 26 MB: 2
    ((3136, 512), False, 0),       # 6.4 MB fits a piece: whole
    ((8 << 20,), False, 4 << 20),  # one axis, 32 MiB: 2 pieces
    ((16, 1 << 20), False, 8),     # 8 rows alone are 32 MiB: cut to 8 rows
    ((4, 8 << 20), False, 0),      # under 8 rows there is nothing to cut
    ((2560, 10240), True, 0),      # sharded over a mesh: one piece
])
def test_piece_rows(shape, sharded, want):
    """A piece is a quarter of the bound, its rows a multiple of 8 and
    the pieces of a leaf as even as that allows; a leaf that fits, has
    under 8 rows a piece or is sharded is one piece."""
    import types

    from torched_impala_tpu.runtime.learner import (
        SNAPSHOT_COPY_AHEAD_BYTES, _piece_rows,
    )

    leaf = types.SimpleNamespace(
        shape=shape, nbytes=4 * int(np.prod(shape)),
        sharding=types.SimpleNamespace(is_fully_replicated=not sharded),
    )
    rows = _piece_rows(leaf)
    assert rows == want
    piece, row_bytes = SNAPSHOT_COPY_AHEAD_BYTES // 4, leaf.nbytes // shape[0]
    if rows:
        assert rows % 8 == 0 and rows < shape[0]
        assert rows * row_bytes <= max(piece, 8 * row_bytes)


def test_a_leaf_sharded_over_the_mesh_is_never_cut(monkeypatch):
    """On four devices (data 2 x model 2) under a bound of 512 bytes: a
    replicated leaf over a piece travels in blocks of rows, a leaf that
    is sharded over the model axis is one piece whatever its size, and
    both are published bit for bit."""
    import optax

    from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
    from torched_impala_tpu.parallel import make_mesh
    from torched_impala_tpu.runtime.learner import Learner, LearnerConfig
    from torched_impala_tpu.runtime.types import Trajectory

    walk = _Walk(monkeypatch, 512)
    agent = Agent(
        ImpalaNet(num_actions=3, torso=MLPTorso(hidden_sizes=(32, 32)))
    )
    learner = Learner(
        agent=agent, optimizer=optax.sgd(1e-2),
        config=LearnerConfig(batch_size=4, unroll_length=3),
        example_obs=np.zeros((8,), np.float32), rng=jax.random.key(0),
        mesh=make_mesh(num_data=2, num_model=2),
    )
    sharded = {
        x.shape for x in jax.tree.leaves(learner.params)
        if not x.sharding.is_fully_replicated
    }
    replicated = {
        x.shape for x in jax.tree.leaves(learner.params)
        if x.sharding.is_fully_replicated and x.nbytes > 128
    }
    assert (32, 32) in sharded and replicated == {(32, 3)}
    learner.start()
    try:
        for i in range(4):
            learner.enqueue(Trajectory(
                obs=np.full((4, 8), i, np.float32), first=np.zeros(4, bool),
                actions=np.zeros(3, np.int32),
                behaviour_logits=np.zeros((3, 3), np.float32),
                rewards=np.ones(3, np.float32), cont=np.ones(3, np.float32),
                agent_state=(), task=0,
            ))
        del walk.events[:]
        learner.step_once(timeout=120)
        learner.drain()
        read = [e[2] for e in walk.events if e[0] == "read"]
        version, published = learner.param_store.get()
        assert version == 12
        for got, own in zip(
            jax.tree.leaves(published), jax.tree.leaves(learner.params)
        ):
            assert got.tobytes() == np.asarray(own).tobytes()
            assert got.flags.owndata is (got.shape != (32, 3))
        _alone([jax.tree.leaves(published)])
    finally:
        learner.stop()
    for shape in sharded:
        assert shape in read
    assert (32, 3) not in read and read.count((8, 3)) == 4


def test_a_held_version_keeps_its_bytes(network):
    """The readers' guarantee: a tree taken from `param_store.get()` and
    held while `keep_versions + 3` later versions are published still
    reads its own version's bytes: no later landing writes where a
    reader may still look."""
    from torched_impala_tpu.runtime import Learner, learner as learner_mod

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(learner_mod, "SNAPSHOT_COPY_AHEAD_BYTES", CUT)
        cfg = small_cfg()
        agent = configs.make_agent(cfg)
        learner = Learner(
            agent=agent, optimizer=configs.make_optimizer(cfg),
            config=configs.make_learner_config(cfg),
            example_obs=configs.example_obs(cfg), rng=jax.random.key(0),
        )
        learner.start()
        try:
            unrolls = _unrolls(network, agent)
            for unroll in unrolls:
                learner.enqueue(unroll)
            learner.step_once(timeout=300)
            learner.drain()
            version, held = learner.param_store.get()
            assert version == B * (T - 1)
            then = [x.tobytes() for x in jax.tree.leaves(held)]
            later = learner.param_store.keep_versions + 3
            for _ in range(later):
                for unroll in unrolls:
                    learner.enqueue(unroll)
                learner.step_once(timeout=300)
            learner.drain()
            assert learner.param_store.version == (later + 1) * version
            assert version not in learner.param_store.versions()
            newest = jax.tree.leaves(learner.param_store.get()[1])
        finally:
            learner.stop()
    now = [x.tobytes() for x in jax.tree.leaves(held)]
    assert now == then
    assert any(a != b.tobytes() for a, b in zip(then, newest))
    # the held version's slab was never handed out again: a landing
    # writes where no reader can be looking
    assert len(_alone([jax.tree.leaves(held), newest])) == 2


def test_a_slab_is_used_again_only_after_its_version_died():
    """`_Slabs` hands a slab out again when the last view of the version
    that had it is gone, whoever held it (a leaf, or a view of a leaf's
    view), and not before."""
    from torched_impala_tpu.runtime.learner import _Slabs

    slabs = _Slabs()
    first = slabs.take(4096)
    where = first.ctypes.data
    leaf = first[64:128].view(np.float32).reshape(4, 4)
    inner = leaf[1:3][:, :2]  # a reader's view of a view
    second = slabs.take(4096)
    assert second.ctypes.data != where
    del first, leaf
    assert slabs.take(4096).ctypes.data not in (where, second.ctypes.data)
    inner[:] = 7.0
    del inner  # the last view of the first version
    again = slabs.take(4096)
    assert again.ctypes.data == where
    del again  # a tree of another size does not get it
    other = slabs.take(8192)
    assert other.nbytes == 8192 and other.ctypes.data != where


def test_slabs_taken_and_dropped_on_many_threads_are_never_shared():
    """More threads than cores take slabs, stamp them, hold them a moment
    and drop them (finalizers run on whichever thread lets the last view
    go): a slab in someone's hands never shows another's stamp."""
    import sys
    import threading
    import time

    from torched_impala_tpu.runtime.learner import _Slabs

    slabs, wrong, deadline = _Slabs(), [], time.monotonic() + 2.0
    interval = sys.getswitchinterval()

    def worker(stamp):
        held = []
        while time.monotonic() < deadline and not wrong:
            slab = slabs.take(1 << 12)
            slab[:] = stamp
            held.append(slab[8:72].view(np.float32))  # a reader's view
            del slab
            if len(held) > 3:
                view = held.pop(0)
                if (view.view(np.uint8) != stamp).any():
                    wrong.append(stamp)

    sys.setswitchinterval(1e-5)
    try:
        threads = [
            threading.Thread(target=worker, args=(i + 1,)) for i in range(24)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads) and not wrong


def test_the_vector_actor_hands_each_env_its_row_of_the_mixed_carry():
    """Unrolls collected in step mode: every trajectory starts from its
    own env's row of every leaf, and the learner's stacking puts E of them
    back together; the second cycle starts from the carried state."""
    from torched_impala_tpu.envs.fake import ScriptedEnv
    from torched_impala_tpu.runtime import ParamStore, VectorActor
    from torched_impala_tpu.runtime.learner import stack_trajectories

    steps, envs = 4, 3
    agent = _mlp_agent()
    params = agent.init_params(jax.random.key(0), jnp.zeros((4,), jnp.float32))
    store = ParamStore()
    store.publish(0, params)
    pushed = []
    actor = VectorActor(
        actor_id=0, envs=[ScriptedEnv(episode_len=3) for _ in range(envs)],
        agent=agent, param_store=store, enqueue=pushed.append,
        unroll_length=steps, seed=0,
    )
    actor.unroll_and_push()
    actor.unroll_and_push()
    assert len(pushed) == 2 * envs
    fresh = agent.initial_state(1)
    for traj in pushed:
        assert isinstance(traj.agent_state, HybridCoreState)
        for leaf, like in zip(traj.agent_state, fresh):
            assert leaf.shape == like.shape and leaf.dtype == like.dtype
    second = pushed[envs:]
    assert all(int(t.agent_state.pos[0]) == steps for t in second)
    assert all(np.abs(np.asarray(t.agent_state.ssm)).max() > 0 for t in second)
    assert all((np.asarray(t.agent_state.full_seg)[0, -steps:] >= 1).all()
               for t in second)
    stacked = stack_trajectories(second).agent_state
    assert stacked.k_full.shape == (envs, 1, 8, 16)
    assert stacked.conv.shape == (envs, 2, 3, 128)

