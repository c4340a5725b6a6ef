"""The plain float32 reference against the program on CPU at a tiny size,
for both cells' configurations, episode resets and PopArt included: so that
a disagreement on the chip is about the chip. The program's torso is run in
float32 here (the preset's bfloat16 torso is held to the same reference in
the rehearsal, with bfloat16's room)."""

import dataclasses

import numpy as np
import pytest

from benchmark import check, driver, program, reference, traffic


def _records(checkout, cell_name, unroll, seed, monkeypatch):
    checkout.shrink(batch=4, unroll=unroll, block=2)
    for w in checkout.doc["workloads"]:
        rel = f"benchmark/traffic/{w['traffic']}.json"
        checkout.write(rel, dict(checkout.read(rel), p_first=0.3))
    for c in checkout.doc["configs"]:  # the file says what the program runs
        cfg = checkout.read(c["file"])
        cfg["model"]["torso_dtype"] = "float32"
        checkout.write(c["file"], cfg)
    as_stated = program.experiment_config
    monkeypatch.setattr(
        program,
        "experiment_config",
        lambda c: dataclasses.replace(as_stated(c), compute_dtype="float32"),
    )
    spec = driver.Spec(checkout.root)
    prep = driver.prepare(spec, spec.cell(cell_name), seed)
    assert any(u["first"][1:].any() for u in prep.pool[:12]), "no reset inside"
    learner, _ = program.build_learner(
        prep.net, prep.config, prep.chips, prep.weights, prep.popart
    )
    learner.start()
    try:
        got = driver.first_steps(learner, prep)
    finally:
        program.release(learner)
    batches = driver.check_batches(prep)
    want = check.reference_record(prep, batches)
    return prep, got, want


@pytest.mark.parametrize(
    "cell_name,unroll",
    [("breakout_b256_feed_sat", 5), ("dmlab30_t100_b64_feed_sat", 6)],
)
def test_reference_agrees_with_the_program(checkout, cell_name, unroll, monkeypatch):
    prep, got, want = _records(checkout, cell_name, unroll, 11, monkeypatch)
    numbers = check.compare(got, want, 0.99, prep.net.leaf_groups)["numbers"]
    # float32 both sides: rounding, and the odd ReLU or max-pool tie
    assert numbers["loss_gap_step1"] < 2e-5
    assert numbers["grad_norm_gap"] < 5e-3
    # two RMSProp steps on (sign-like updates of 6e-3 a weight) amplify it
    assert numbers["loss_gap_step2"] < 5e-3
    assert numbers["delta_norm_gap"] < 0.05
    if prep.config["model"]["num_tasks"] > 1:
        # from statistics that are not the identity, so that a learner
        # which skipped the normalisation would not read the same
        assert np.abs(prep.popart["mu"]).min() > 0
        for k in ("mu", "nu"):
            np.testing.assert_allclose(
                got["popart1"][k], want["popart1"][k], rtol=0, atol=1e-6
            )
            moved = np.abs(want["popart1"][k] - prep.popart[k]).max()
            assert moved > 1e-4, "PopArt never moved"
        assert numbers["popart_gap"] < 1e-3
    else:
        assert got["popart1"] is None and want["popart1"] is None
    assert numbers["update_wrong_way"] < 1e-3


def test_vtrace_against_a_loop_written_by_hand():
    rng = np.random.default_rng(0)
    t, b = 7, 3
    log_rhos = rng.normal(size=(t, b)).astype(np.float32)
    disc = (0.9 * (rng.random((t, b)) > 0.2)).astype(np.float32)
    rew = rng.normal(size=(t, b)).astype(np.float32)
    val = rng.normal(size=(t, b)).astype(np.float32)
    boot = rng.normal(size=(b,)).astype(np.float32)
    hp = {"clip_rho": 1.0, "clip_c": 1.0, "clip_pg_rho": 1.0, "lambda": 1.0}
    vs, adv = reference.vtrace(log_rhos, disc, rew, val, boot, hp)
    rho = np.minimum(1.0, np.exp(log_rhos))
    want = np.zeros((t, b))
    acc = np.zeros(b)
    nxt = boot
    for i in reversed(range(t)):
        delta = rho[i] * (rew[i] + disc[i] * nxt - val[i])
        acc = delta + disc[i] * rho[i] * acc
        want[i] = val[i] + acc
        nxt = val[i]
    np.testing.assert_allclose(vs, want, rtol=1e-5, atol=1e-5)
    vs_next = np.concatenate([want[1:], boot[None]], 0)
    np.testing.assert_allclose(
        adv, rho * (rew + disc * vs_next - val), rtol=1e-5, atol=1e-5
    )


def test_lstm_reset_zeroes_the_carry_before_the_step(network_of, tiny_config):
    net = network_of(tiny_config())
    p = net.init_params(3, tiny_config())["lstm"]
    feats = np.ones((3, 2, 8), np.float32)
    c0 = h0 = np.full((2, 8), 0.7, np.float32)
    first = np.array([[False, True], [False, False], [True, False]])
    out = np.asarray(net.lstm_unroll(p, feats, first, c0, h0))
    fresh = np.asarray(
        net.lstm_unroll(p, feats[:1], first[:1] | True, c0, h0)
    )
    # row 1 starts an episode at t=0: its carry-in does not matter
    np.testing.assert_allclose(out[0, 1], fresh[0, 1], rtol=1e-6)
    assert not np.allclose(out[0, 0], fresh[0, 0])
    # row 0 resets at t=2: the same output as a fresh start on that input
    np.testing.assert_allclose(out[2, 0], fresh[0, 0], rtol=1e-6)


def test_weights_are_a_function_of_the_seed_alone(network_of, tiny_config):
    cfg = tiny_config()
    net = network_of(cfg)
    a, b = net.init_params(2**31 + 5, cfg), net.init_params(2**31 + 5, cfg)
    c = net.init_params(5, cfg)
    assert np.array_equal(a["fc"]["w"], b["fc"]["w"])
    assert not np.array_equal(a["fc"]["w"], c["fc"]["w"])
    tree = net.to_program_params(a)["params"]
    assert sorted(tree) == ["lstm", "policy_head", "torso", "value_head"]
    np.testing.assert_array_equal(
        tree["lstm"]["hg"]["kernel"], np.asarray(a["lstm"]["wh"])[:, 16:24]
    )


def test_the_pool_is_the_same_work_for_every_seed(checkout):
    spec = driver.Spec(checkout.root)
    cell = spec.cell("dmlab30_t100_b64_feed_sat")
    config = dict(spec.config(cell["config"]), batch_size=2, unroll_length=3)
    mix = spec.find("traffic", cell["traffic"])
    draw = spec.network(config).draw_state
    a = traffic.make_pool(2**31 + 7, config, mix, draw)
    b = traffic.make_pool(2**31 + 7, config, mix, draw)
    c = traffic.make_pool(8, config, mix, draw)
    assert len(a) == len(c) == traffic.POOL_BATCHES * 2
    assert np.array_equal(a[0]["obs"], b[0]["obs"])
    assert not np.array_equal(a[0]["obs"], c[0]["obs"])
    assert a[0]["obs"].shape == (4, 72, 96, 3) and a[0]["obs"].dtype == np.uint8
    assert {k: v.shape for k, v in a[1].items() if hasattr(v, "shape")} == {
        k: v.shape for k, v in c[1].items() if hasattr(v, "shape")
    }
    assert a[0]["state"][0].shape == (1, 256)
    orders = traffic.feeder_orders(9, mix, len(a))
    assert len(orders) == mix["feeders"]
    assert all(sorted(o) == list(range(len(a))) for o in orders)
    with pytest.raises(ValueError, match="tasks must be one of"):
        traffic.make_pool(1, config, dict(mix, tasks="by_turns"), draw)
