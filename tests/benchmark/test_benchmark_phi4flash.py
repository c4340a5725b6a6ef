"""The cell `phi4flash_t2047_b2_feed_sat` as its files stand in the repo
(configuration `pong_phi4flash_core`, network `nature_cnn_phi4flash`, mix
`feed_sat_ep1000`), run through `run.main` on a checkout with the core cut
to a size the CPU holds: d_model 64, d_inner 128, 4 query heads on 2
key/value heads, window 4, full cache 8, B=2, T=5. The layer kinds, the
state's kinds and the harness are the cell's own; the widths are the
test's (the preset's are 0.44 billion parameters). Nothing here is a
device number."""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from benchmark import check, driver, program

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "phi4flash_t2047_b2_feed_sat"
CONFIG = "benchmark/configs/pong_phi4flash_core.json"
SMALL = dict(
    hidden_size=64, intermediate_size=128, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, sliding_window=4, full_cache=8,
    d_inner=128, d_state=16, d_conv=4, dt_rank=4,
)
PRESET_FIELDS = dict(
    hidden_size="hybrid_d_model", intermediate_size="hybrid_d_intermediate",
    num_attention_heads="hybrid_heads", num_key_value_heads="hybrid_kv_heads",
    head_dim="hybrid_head_dim", sliding_window="hybrid_window",
    full_cache="hybrid_full_cache", d_inner="hybrid_d_inner",
    d_state="hybrid_d_state", d_conv="hybrid_d_conv", dt_rank="hybrid_dt_rank",
)
# The first step's loss as every cell holds it; the others with the room
# the committed cells have. The cell's own limits come from `calibrate.py`
# at its own size on the chip.
LIMITS = {
    "loss_gap_step1": 0.02,
    "grad_norm_gap": 0.5,
    "delta_norm_gap": 0.6,
    "update_wrong_way": 0.25,
}


@pytest.fixture
def small(checkout, monkeypatch):
    """The checkout with the cell at the test's widths: the file states
    them, and the preset the harness builds is cut to the same."""

    def cut(**more):
        cfg = checkout.read(CONFIG)
        cfg.update(batch_size=2, unroll_length=5, reference_block_rows=1)
        cfg["model"].update(SMALL, **more)
        checkout.write(CONFIG, cfg)
        checkout.write(f"benchmark/limits/{CELL}.json", LIMITS)
        preset = {PRESET_FIELDS[k]: v for k, v in SMALL.items()}
        if "core_dtype" in more:
            preset["hybrid_dtype"] = more["core_dtype"]
        if "torso_dtype" in more:
            preset["compute_dtype"] = more["torso_dtype"]
        as_stated = program.experiment_config
        monkeypatch.setattr(
            program, "experiment_config",
            lambda c: dataclasses.replace(as_stated(c), **preset),
        )
        return checkout

    return cut


def _numbers(checkout, seed, **planted):
    spec = driver.Spec(checkout.root)
    prep = driver.prepare(spec, spec.cell(CELL), seed)
    decay = prep.config["optimizer"]["rmsprop_decay"]
    batches = driver.check_batches(prep)
    want = check.reference_record(prep, batches)
    if planted:
        got = check.as_program_record(
            check.reference_record(prep, batches, **planted), decay
        )
    else:
        learner, _ = program.build_learner(
            prep.net, prep.config, prep.chips, prep.weights, prep.popart
        )
        learner.start()
        try:
            got = driver.first_steps(learner, prep)
        finally:
            program.release(learner)
    return prep, check.compare(got, want, decay, prep.net.leaf_groups)["numbers"]


def test_the_cell_runs_to_correct_as_its_files_stand(small):
    checkout = small()
    # On 10 frames the loss's three terms can all but cancel: of six seeds
    # tried one read 0.0296 on `loss_gap_step1` (the others 0.0004-0.0124);
    # the run's seed is fixed.
    rc, result, err = checkout.run(CELL, seed=11)
    assert rc == 0, err
    assert set(result["metrics"]) == {"frames_per_s", "setup_s"}
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["checks"]) == set(LIMITS)
    assert result["correct"] is True, result["checks"]
    # the numbers come by this network's own parts
    for group in ("torso", "core.mamba", "core.window", "core.full",
                  "core.mlp", "heads"):
        assert f"grad_elem_gap.{group}" in result["all_numbers"], group
    assert "grad_elem_gap.lstm" not in result["all_numbers"]
    # the committed limits (the small run is held to the test's) name
    # numbers the check computes: one it lacks would fail every run
    with open(os.path.join(ROOT, f"benchmark/limits/{CELL}.json")) as f:
        assert set(json.load(f)) <= set(result["all_numbers"])


def test_the_control_is_not_correct(small):
    """Each part stored one precision below what the file states (the
    torso and the products in 8-bit floats, the rest of the core in
    bfloat16) fails the limits that the sound program passes."""
    checkout = small()
    spec = driver.Spec(checkout.root)
    config = spec.config("pong_phi4flash_core")
    lowered = check.control_dtypes(spec.network(config), config, "control")
    assert lowered == ("float8_e4m3fn", "float8_e4m3fn", "bfloat16")
    _, numbers = _numbers(checkout, 3_000_000_019, dtypes=lowered)
    correct, table = check.verdict(numbers, LIMITS)
    assert correct is False, table


def test_its_reference_agrees_with_the_program_in_float32(small):
    """Torso and products in float32, as the file then says: what is left
    between the two is rounding, on scan, convolution window, both caches,
    the episode starts and every layer's gradients alike."""
    checkout = small(torso_dtype="float32", core_dtype="float32")
    _, numbers = _numbers(checkout, 11)
    assert numbers["loss_gap_step1"] < 2e-5
    assert numbers["loss_gap_step3"] < 1e-3
    assert numbers["grad_norm_gap"] < 1e-3
    assert numbers["grad_elem_gap"] < 1e-3
    assert numbers["delta_norm_gap"] < 1e-3
    assert numbers["update_wrong_way"] < 1e-3


def test_its_state_is_of_three_kinds_and_two_cache_lengths(small):
    checkout = small()
    spec = driver.Spec(checkout.root)
    prep = driver.prepare(spec, spec.cell(CELL), 3_000_000_019)
    assert prep.net.__name__ == "nature_cnn_phi4flash"
    state = prep.pool[0]["state"]
    assert [s.shape for s in state] == [
        (1, 2, 3, 128), (1, 2, 16, 128),  # convolution windows, scan states
        (1, 1, 4, 32), (1, 1, 4, 32), (1, 4), (1, 4),  # window cache
        (1, 1, 8, 32), (1, 1, 8, 32), (1, 8), (1, 8),  # full cache
        (1,), (1,),
    ]
    assert all(np.any(s != 0) for s in state), "a state array is all zero"
    # the program gets it in the type its core carries, the same arrays
    carried = prep.trajs[0].agent_state
    assert type(carried).__name__ == "HybridCoreState"
    assert carried.conv is state[0] and carried.seg is state[11]
    # B unrolls' states concatenate on their row axis, for the reference
    # and in the learner's own stacking, leaf by leaf
    batch = driver.check_batches(prep)[0]
    assert [s.shape[0] for s in batch["state"]] == [2] * 12
    from torched_impala_tpu.runtime.learner import stack_trajectories

    stacked = stack_trajectories(prep.trajs[:2]).agent_state
    for ours, theirs in zip(jax.tree.leaves(stacked), batch["state"]):
        np.testing.assert_array_equal(ours, theirs)
    # some cache slots belong to another episode, and some unrolls start
    # with less than a full cache of their own
    pool = prep.pool
    other = [int((u["state"][8] != u["state"][11][:, None]).sum()) for u in pool]
    assert max(other) > 0 and min(other) < 8


def test_what_a_query_may_not_see_changes_nothing(small):
    """The episode before the running one in either cache, the cache
    beyond the window, and everything before a start inside the unroll
    (scan state, convolution window, keys and values alike)."""
    checkout = small()
    spec = driver.Spec(checkout.root)
    prep = driver.prepare(spec, spec.cell(CELL), 5)
    net, config = prep.net, prep.config
    sizes, params = net.sizes(config), prep.weights
    batch = driver.check_batches(prep)[0]
    obs, first = batch["obs"], batch["first"].copy()
    first[3, :] = True
    logits, _ = net.forward(sizes, params, obs, first, batch["state"])
    state = list(batch["state"])
    seg = state[11]
    for k, v, slot_seg in ((2, 3, 4), (6, 7, 8)):
        hidden = (state[slot_seg] != seg[:, None])[:, None, :, None]
        state[k] = np.where(hidden, 7.0, state[k])
        state[v] = np.where(hidden, -7.0, state[v])
    state[0] = state[0] + 3.0  # the convolution's window: reset at step 3
    state[1] = state[1] - 3.0  # the scan state: reset at step 3
    obs2 = obs.copy()
    obs2[:3] = 255 - obs2[:3]
    logits2, _ = net.forward(sizes, params, obs2, first, tuple(state))
    np.testing.assert_allclose(logits[3:], logits2[3:], rtol=0, atol=1e-5)
    assert not np.allclose(logits[:3], logits2[:3], atol=1e-3)


def test_its_counts_by_hand():
    """At the published widths, from the files as they stand."""
    spec = driver.Spec(ROOT)
    config = spec.config("pong_phi4flash_core")
    net = spec.network(config)
    s = net.sizes(config)
    macs = net.forward_macs_per_obs(s, 2047)
    assert macs["conv0"] == 20 * 20 * 8 * 8 * 4 * 32
    assert macs["fc"] == 3136 * 512
    assert macs["core.mlp"] == 4 * 3 * 2560 * 10240
    assert macs["core.mamba"] == 2 * (
        2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560
    )
    assert macs["core.projections"] == 2 * (2 * 2560 * 2560 + 2 * 2560 * 1280)
    assert macs["core.attention_window"] == 2 * 2560 * 512
    assert macs["core.attention_full"] == 2 * 2560 * (2048 + 1024.5)
    core = sum(v for k, v in macs.items() if k.startswith("core"))
    assert 0.975 < core / sum(macs.values()) < 0.985
    fwd = 2 * sum(macs.values())
    assert net.step_flops(config) == 2 * (
        2048 * fwd + 2047 * (2 * fwd - 2 * macs["conv0"])
    )
    # parameters of the cut: 436.4M in the four blocks
    shapes = jax.eval_shape(lambda: net.init_params(0, config))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))  # noqa: E731
    assert round(count(shapes["core"]["layers"]) / 1e6, 1) == 436.4
    # one call of each kernel: the scan is bandwidth-bound, attention not
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    f, b = net.OPS_AND_BYTES["selective_scan"](config, 1)
    assert (f, b) == (7.0 * 4096 * 5120 * 16, 4.0 * 4096 * (3 * 5120 + 32))
    assert b / peaks["hbm_bytes_per_s"] > 10 * f / peaks["bf16_flops_per_s"]
    f, b = net.OPS_AND_BYTES["attention_window"](config, 1)
    assert f == 4.0 * 2 * 2048 * 2560 * 512
    assert f / peaks["bf16_flops_per_s"] > b / peaks["hbm_bytes_per_s"]
    f_full, _ = net.OPS_AND_BYTES["attention_full"](config, 1)
    assert f_full == 4.0 * 2 * 2048 * 2560 * (2048 + 1024.5)


KERNEL_METRICS = {
    "kernels.selective_scan_roofline": "selective_scan",
    "kernels.selective_scan_backward_roofline": "selective_scan_backward",
    "kernels.attention_window_roofline": "attention_window",
    "kernels.attention_full_roofline": "attention_full",
}


def test_the_cell_reports_the_accepted_per_layer_metrics():
    spec = driver.Spec(ROOT)
    reported = {m["name"] for m in spec.metrics("per_layer", CELL)}
    assert "kernels.lstm_roofline" not in reported  # no LSTM to read
    assert {"train_step.mfu", "train_step.device_ms", "device.idle_share",
            "host.publish_copy_ms", "host.loop_overhead_ms",
            "host.bookkeeping_ms", "feed.batch_wait_share"} <= reported


def test_its_kernel_metric_files_read_a_trace_once_declared(checkout, monkeypatch):
    """The four roofline files are in the repo; their `per_layer` entries
    are not: `test_benchmark_step_loop_metrics.py` holds the list's last
    three entries to the step loop's, and no file of the benchmark may be
    edited by this kind of PR (PERF.md section 7). Declared in a checkout,
    each reads its kernel's calls by name from the `XLA Ops` line against
    the network file's count; here on a trace written by hand."""
    from benchmark import readers, trace
    from benchmark.trace import Event

    spec = driver.Spec(ROOT)
    config = spec.config("pong_phi4flash_core")
    net = spec.network(config)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    tuple_of = "(f32[2,2048,5120]{2,1,0}, f32[2,32,16,5120]{3,2,1,0})"
    ops = [
        Event(f"%selective_scan_forward.3 = {tuple_of} custom-call(...)", 0.0, 1e-3),
        Event(f"%selective_scan_forward.5 = {tuple_of} custom-call(...)", 0.1, 1e-3),
        Event("%selective_scan_backward.7 = (f32[2,2048,5120]) custom-call(...)", 0.2, 3e-3),
        Event("%attention_window_forward.9 = bf16[2,40,2048,64] custom-call(...)", 0.3, 5e-4),
        Event("%attention_window_backward_dq.2 = f32[2,40,2048,64] custom-call(...)", 0.4, 1.0),
        Event("%attention_full_forward = bf16[2,40,2048,64] custom-call(...)", 0.5, 2e-3),
        Event("%fusion.1 = f32[8] fusion(...)", 0.6, 0.5),
    ]
    ctx = readers.Context(
        trace=trace.Trace({"/device:TPU:0": ops}, {}), timers={},
        host_window_s=1.0, steps=1, config=config, chips=1, peaks=peaks,
        net=net,
    )
    want_seconds = {
        "kernels.selective_scan_roofline": 1e-3,
        "kernels.selective_scan_backward_roofline": 3e-3,
        "kernels.attention_window_roofline": 5e-4,
        "kernels.attention_full_roofline": 2e-3,
    }
    for name, count in KERNEL_METRICS.items():
        file = spec.find("metrics", name)
        assert file["reader"] == "roofline"
        assert file["params"]["ops_and_bytes"] == count
        f, b = net.OPS_AND_BYTES[count](config, 1)
        least = max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
        got = readers.read(ctx, file)
        assert got == pytest.approx(100 * least / want_seconds[name]), name
        assert 0 < got < 100, name
    # a program without the kernels (the parent): nothing to read, no raise
    empty = ctx._replace(trace=trace.Trace({"/device:TPU:0": ops[-1:]}, {}))
    assert all(
        readers.read(empty, spec.find("metrics", n)) is None
        for n in KERNEL_METRICS
    )
    # declared in a checkout as later PRs may, they are the cell's alone
    doc = checkout.doc
    for name in KERNEL_METRICS:
        doc["per_layer"].append(
            {"name": name, "unit": "%", "better": "higher",
             "source": "device_trace", "layer": "kernels",
             "moves": "frames_per_s", "workloads": [CELL]}
        )
    checkout.write("BENCHMARK.json", doc)
    there = driver.Spec(checkout.root)
    assert set(KERNEL_METRICS) <= {
        m["name"] for m in there.metrics("per_layer", CELL)
    }
    assert not set(KERNEL_METRICS) & {
        m["name"] for m in there.metrics("per_layer", "breakout_b256_feed_sat")
    }
