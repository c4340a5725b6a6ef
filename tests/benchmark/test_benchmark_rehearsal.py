"""The driver end to end on CPU at a tiny size, the look for a chip stood
in for by the test: once on one device, once on four virtual devices with
a `chips: 4` configuration that exists only as new files. Nothing here is a
device number."""

import os

import pytest

from benchmark import driver, readers, run, trace
from benchmark.trace import Event

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_no_accelerator_means_no_result(checkout, capsys):
    rc = run.main(
        ["--workload", "breakout_b256_feed_sat", "--seed", "1", "--seconds", "1"],
        root=checkout.root,
    )
    captured = capsys.readouterr()
    assert rc == 2 and captured.out == ""
    assert "no CPU fallback" in captured.err


def window_s(result):
    return result["window"]["window_s"] + 1.0  # the window's two ends


def test_one_device_rehearsal(checkout):
    checkout.shrink(batch=4, unroll=5, block=2)
    checkout.loosen()
    rc, result, err = checkout.run("breakout_b256_feed_sat")
    assert rc == 0
    assert KEYS <= result.keys() and list(result)[-1] != "metrics"
    assert set(result["metrics"]) == {m["name"] for m in checkout.doc["end_to_end"]}
    assert {"frames_per_s", "setup_s"} <= set(result["metrics"])
    assert {"step_ms_p50", "step_ms_p95", "step_ms_p99"} <= set(result["window"])
    assert result["metrics"]["frames_per_s"]["unit"] == "frames/s"
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert result["device"]["count"] == 1
    assert "memory_peak_bytes" in result["device"]
    seconds, calls = result["timers"]["learner/batch_wait"]
    assert calls >= result["attempted"] and 0 <= seconds <= window_s(result)
    window = result["window"]
    assert window["frames_per_s"] == pytest.approx(
        4 * 5 * window["steps"] / window["window_s"]
    )
    # every number compared stands beside its limit, in the line and as
    # the last lines of standard error
    checks = result["checks"]
    assert set(checks) == {
        "loss_gap_step1", "grad_elem_gap.core.median_leaf", "grad_norm_gap",
        "delta_norm_gap",
        "update_wrong_way",
    }
    assert result["correct"] is True
    # the bfloat16 torso against the float32 reference, 24 frames
    assert checks["loss_gap_step1"]["value"] < 2e-2
    assert checks["grad_norm_gap"]["value"] < 0.5
    tail = err.strip().splitlines()[-len(checks):]
    assert all(line.startswith("check ") and "limit" in line for line in tail)


PROCGEN_DP4 = {
    "name": "procgen_deep_dp4",
    "preset": "procgen",
    "source": "IMPALA large architecture on Procgen frames (arXiv:1912.01588)",
    "reduced": [],
    "assumed": {"batch_size": "a CPU-sized stand-in"},
    "chips": 4,
    "batch_size": 8,
    "unroll_length": 5,
    "model": {
        "torso": "deep_resnet", "torso_dtype": "bfloat16",
        "train_dtype": "float32", "obs_shape": [64, 64, 3],
        "obs_dtype": "uint8", "num_actions": 15, "num_tasks": 1,
        "channel_sections": [16, 32, 32], "blocks_per_section": 2,
        "fc_size": 256, "use_lstm": False, "lstm_size": 0,
    },
    "loss": {
        "discount": 0.99, "vf_coef": 0.5, "entropy_coef": 0.01,
        "reduction": "sum", "clip_rho_threshold": 1.0,
        "clip_c_threshold": 1.0, "clip_pg_rho_threshold": 1.0, "lambda": 1.0,
    },
    "optimizer": {
        "name": "rmsprop", "lr": 0.0006, "lr_anneal": True,
        "rmsprop_decay": 0.99, "rmsprop_eps": 1e-07, "max_grad_norm": 40.0,
        "total_env_frames": 200000000,
    },
    "reference_block_rows": 4,
}


def add_dp4_cell(checkout):
    """A configuration, a traffic mix, a cell and a per-layer metric, each
    as a NEW file under a NEW directory of `paths` plus an entry: no file
    that was there is edited (BENCHMARK.json only gains entries)."""
    before = {
        rel: open(checkout.path(rel)).read()
        for rel in _files(checkout.root, "benchmark")
    }
    new = "bench_dp"
    checkout.write(f"{new}/configs/procgen_deep_dp4.json", PROCGEN_DP4)
    mix = checkout.read("benchmark/traffic/feed_sat_ep100.json")
    checkout.write(f"{new}/traffic/feed_sat_ep100_dp.json", dict(mix, feeders=3))
    checkout.write(
        f"{new}/metrics/mesh.collective_exposed_ms.json",
        {"reader": "exposed_time", "params": {"patterns": ["all-reduce"]}},
    )
    checkout.write(
        f"{new}/limits/procgen_dp4_b8_feed_sat.json",
        {"loss_gap_step1": 1e9, "grad_norm_gap": 1e9},
    )
    doc = checkout.doc
    doc["paths"].append(new)
    doc["configs"].append(
        {"name": "procgen_deep_dp4", "source": PROCGEN_DP4["source"],
         "file": f"{new}/configs/procgen_deep_dp4.json", "reduced": [],
         "why": "data parallel over four chips"}
    )
    doc["workloads"].append(
        {"name": "procgen_dp4_b8_feed_sat", "config": "procgen_deep_dp4",
         "traffic": "feed_sat_ep100_dp", "chips": 4, "why": "sharded feed"}
    )
    doc["per_layer"].append(
        {"name": "mesh.collective_exposed_ms", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "mesh", "moves": "frames_per_s",
         "workloads": ["procgen_dp4_b8_feed_sat"]}
    )
    checkout.write("BENCHMARK.json", doc)
    after = {
        rel: open(checkout.path(rel)).read()
        for rel in _files(checkout.root, "benchmark")
    }
    assert before == after


def _files(root, sub):
    out = []
    for d, _, names in os.walk(os.path.join(root, sub)):
        out += [os.path.relpath(os.path.join(d, n), root) for n in names]
    return sorted(out)


def test_four_virtual_devices_with_a_cell_added_as_files(checkout):
    add_dp4_cell(checkout)
    rc, result, err = checkout.run("procgen_dp4_b8_feed_sat")
    assert rc == 0, err
    assert result["device"]["count"] == 4
    assert set(result["metrics"]) == {m["name"] for m in checkout.doc["end_to_end"]}
    # the cell's own limits file names two numbers: those are what decide
    assert set(result["checks"]) == {"loss_gap_step1", "grad_norm_gap"}
    assert result["correct"] is True
    assert result["checks"]["loss_gap_step1"]["value"] < 2e-2


def test_a_metric_added_as_a_file_reads_a_four_chip_trace(checkout):
    add_dp4_cell(checkout)
    spec = driver.Spec(checkout.root)
    names = [m["name"] for m in spec.metrics("per_layer", "procgen_dp4_b8_feed_sat")]
    assert "mesh.collective_exposed_ms" in names
    assert "mesh.collective_exposed_ms" not in [
        m["name"] for m in spec.metrics("per_layer", "breakout_b256_feed_sat")
    ]
    planes = [f"/device:TPU:{i}" for i in range(4)]
    ops = [
        Event("fusion.1", 0.0, 0.6), Event("all-reduce.2", 0.5, 0.3),
        Event("fusion.1", 1.0, 0.6), Event("all-reduce.2", 1.5, 0.3),
        Event("fusion.1", 2.0, 0.6),
    ]
    modules = [Event("jit__train_step_impl", float(t), 0.8) for t in range(3)]
    ctx = readers.Context(
        trace=trace.Trace({p: ops for p in planes}, {p: modules for p in planes}),
        timers={}, host_window_s=2.0, steps=2,
        config=spec.config("procgen_deep_dp4"), chips=4,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
    )
    value = readers.read(ctx, spec.find("metrics", "mesh.collective_exposed_ms"))
    # 0.2 s of each all-reduce sticks out past the fusion; two per window
    assert value == pytest.approx(1e3 * 0.4 / 2)
    # the same file finds nothing on a one-chip trace and says so
    lonely = ctx._replace(
        trace=trace.Trace({planes[0]: ops[::2]}, {planes[0]: modules})
    )
    assert readers.read(lonely, spec.find("metrics", "mesh.collective_exposed_ms")) is None


def test_every_entry_of_benchmark_json_has_its_file(checkout):
    spec = driver.Spec(checkout.root)
    for cell in spec.doc["workloads"]:
        config = spec.config(cell["config"])
        assert config["chips"] == cell["chips"]
        assert spec.network(config).__name__ == config.get(
            "network", driver.DEFAULT_NETWORK
        )
        spec.find("traffic", cell["traffic"])
        assert spec.find("limits", cell["name"])
        for m in spec.metrics("per_layer", cell["name"]):
            assert spec.find("metrics", m["name"])["reader"] in readers.READERS
    with pytest.raises(FileNotFoundError):
        spec.find("metrics", "no.such.metric")
    with pytest.raises(KeyError):
        spec.cell("no_such_cell")
