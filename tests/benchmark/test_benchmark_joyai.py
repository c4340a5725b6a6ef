"""The cell `joyai_t2047_b2_feed_sat` as its files stand in the repo
(configuration `pong_joyai_flash_core`, network `nature_cnn_joyai`, mix
`feed_sat_ep1000`), run through `run.main` on a checkout with the core cut
to a size the CPU holds: d_model 64, 4 heads, q rank 24, kv rank 16,
query/key width 8 + 8 and value width 8, latent cache 16, dense width 48,
expert width 16, B=2, T=5; the router's 256 experts, 8 a token, the 8 held,
the shared expert, the scale of 2.5, the dense leading layer, the layer
kinds, the state's kinds and the harness are the cell's own. Nothing here
is a device number."""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from benchmark import check, driver, program

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "joyai_t2047_b2_feed_sat"
CONFIG = "benchmark/configs/pong_joyai_flash_core.json"
SMALL = dict(
    hidden_size=64, num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, full_cache=16,
    intermediate_size=48, moe_intermediate_size=16,
)
PRESET = dict(
    hybrid_d_model=64, hybrid_heads=4, hybrid_latent=(24, 16, 8, 8, 8),
    hybrid_full_cache=16, hybrid_d_intermediate=48, hybrid_expert_width=16,
    hybrid_shared_expert=16,
)
# The first step's loss as every cell holds it; the others with the room
# the committed cells have; the routed layers' median leaves of the three
# steps' change and of the first step's direction as the cell holds them.
# The cell's own limits come from `calibrate.py` at its own size on the
# chip.
LIMITS = {
    "loss_gap_step1": 0.02,
    "grad_norm_gap": 0.5,
    "delta_norm_gap": 0.6,
    "update_wrong_way": 0.25,
    "delta_norm_gap.core.moe.median_leaf": 0.04,
    "update_wrong_way.core.moe.median_leaf": 0.024,
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
        preset = dict(PRESET)
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


# The sound reference's record by seed, shared by the cases that set a
# program or a control against it: the reference sees nothing they plant.
_WANT = {}


def _numbers(checkout, seed, **planted):
    spec = driver.Spec(checkout.root)
    prep = driver.prepare(spec, spec.cell(CELL), seed)
    decay = prep.config["optimizer"]["rmsprop_decay"]
    batches = driver.check_batches(prep)
    if seed not in _WANT:
        _WANT[seed] = check.reference_record(prep, batches)
    want = _WANT[seed]
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
    rc, result, err = checkout.run(CELL, seed=17)
    assert rc == 0, err
    assert set(result["metrics"]) == {"frames_per_s", "setup_s"}
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert set(result["checks"]) == set(LIMITS)
    assert result["correct"] is True, result["checks"]
    # the numbers come by this network's own parts
    for group in ("torso", "core.attention", "core.mlp", "core.moe",
                  "core.moe_shared", "core.io", "heads"):
        assert f"grad_elem_gap.{group}" in result["all_numbers"], group
    # the committed limits (the small run is held to the test's) name
    # numbers the check computes: one it lacks would fail every run
    with open(os.path.join(ROOT, f"benchmark/limits/{CELL}.json")) as f:
        assert set(json.load(f)) <= set(result["all_numbers"])


def test_the_control_is_not_correct(small):
    """Each part stored one precision below what the file states (the
    torso and the products in 8-bit floats, the rest of the core, the
    router's scores and the latent cache among it, in bfloat16) fails the
    limits that the sound program passes."""
    checkout = small()
    spec = driver.Spec(checkout.root)
    config = spec.config("pong_joyai_flash_core")
    lowered = check.control_dtypes(spec.network(config), config, "control")
    assert lowered == ("float8_e4m3fn", "float8_e4m3fn", "bfloat16")
    _, numbers = _numbers(checkout, 3_000_000_019, dtypes=lowered)
    correct, table = check.verdict(numbers, LIMITS)
    assert correct is False, table


def _rotate_half(x, pos, theta):
    """The rotary embedding of the other layout: the halves, not the
    interleaved pairs, turned together."""
    from torched_impala_tpu.models.hybrid import _rope

    return _rope(x, pos, theta)


def _plant(monkeypatch, fault):
    """The fault in the program's core, with its parameters and what it
    states as they were (so the harness builds it and only the check can
    tell)."""
    import flax.linen as nn
    import jax.numpy as jnp

    from torched_impala_tpu.models import hybrid

    if fault == "rope_not_interleaved":
        monkeypatch.setattr(hybrid, "_rope_pairs", _rotate_half)
    elif fault == "routed_scale_1":  # the chosen weights left summing to 1
        moe = hybrid._MoE
        monkeypatch.setattr(
            hybrid, "_MoE", lambda *a, **kw: moe(*a, **{**kw, "routed_scale": 1.0})
        )
    elif fault == "experts_frozen":  # no gradient reaches the held experts
        product = hybrid.grouped_matmul
        monkeypatch.setattr(
            hybrid, "grouped_matmul",
            lambda x, w, rows: product(x, jax.lax.stop_gradient(w), rows=rows),
        )
    else:  # the latent norms' scale kept, their normalisation left out

        class ScaleOnly(hybrid._RMSNorm):
            @nn.compact
            def __call__(self, x):
                x = x.astype(jnp.float32)
                return x * self.param(
                    "scale", nn.initializers.ones, (x.shape[-1],), jnp.float32
                )

        real_norm = hybrid._RMSNorm
        monkeypatch.setattr(
            hybrid, "_RMSNorm",
            lambda eps, name=None: (
                ScaleOnly if name in ("q_a_norm", "kv_a_norm") else real_norm
            )(eps, name=name),
        )


@pytest.mark.parametrize(
    "fault", ["rope_not_interleaved", "routed_scale_1", "experts_frozen"]
)
def test_a_planted_fault_is_not_correct(small, monkeypatch, fault):
    """A fault planted in the program (the reference as it stands) fails
    the limits on the seed the sound run passes (a second fault:
    `test_benchmark_joyai_reference.py`). The two faults of the routed
    path fail the routed layers' limits: at this size a token's 8 picks
    land on a held expert a quarter of a time, so neither moves the
    whole network's numbers past theirs. The first step is RMSProp's
    sign-like step, so the routed scale keeps its direction: only the
    frozen experts, which take no step, fail the direction's limit."""
    checkout = small()
    _plant(monkeypatch, fault)
    _, numbers = _numbers(checkout, 17)
    correct, table = check.verdict(numbers, LIMITS)
    assert correct is False, table
    routed = {
        "routed_scale_1": ["delta_norm_gap.core.moe.median_leaf"],
        "experts_frozen": [
            "delta_norm_gap.core.moe.median_leaf",
            "update_wrong_way.core.moe.median_leaf",
        ],
    }
    for moe in routed.get(fault, []):
        assert table[moe]["value"] > LIMITS[moe], table


def test_the_configuration_holds_the_catalog_row():
    """Every number of the published configuration is in the file under
    its own key, changed only where `reduced` names it."""
    spec = driver.Spec(ROOT)
    config = spec.config("pong_joyai_flash_core")
    entry = next(
        c for c in spec.doc["configs"] if c["name"] == "pong_joyai_flash_core"
    )
    published = {**config, **config["published"]}
    assert config["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert config[key] != published[key], key
    assert config["num_hidden_layers"] == len(config["model"]["layers"]) == 5
    assert config["n_routed_experts"] == config["model"]["num_experts"] == 8
    m = config["model"]
    for key in ("hidden_size", "num_attention_heads", "q_lora_rank",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "intermediate_size", "first_k_dense_replace",
                "num_experts_per_tok", "moe_intermediate_size",
                "n_shared_experts", "routed_scaling_factor", "rope_theta",
                "rope_interleave", "rms_norm_eps"):
        assert m[key] == config[key], key
    assert m["router_experts"] == config["published"]["n_routed_experts"] == 256
    assert config["qk_head_dim"] == m["qk_nope_head_dim"] + m["qk_rope_head_dim"]


def test_its_counts_by_hand():
    """At the published widths, from the files as they stand."""
    spec = driver.Spec(ROOT)
    config = spec.config("pong_joyai_flash_core")
    net = spec.network(config)
    s = net.sizes(config)
    macs = net.forward_macs_per_obs(s, 2047)
    assert macs["conv0"] == 20 * 20 * 8 * 8 * 4 * 32
    assert macs["core.projections"] == 5 * (
        2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 32 * 128 * 2048
    )
    assert macs["core.latent_kv"] == 5 * 512 * 32 * 256
    assert macs["core.attention_latent"] == 5 * 32 * 320 * (2048 + 1024.5)
    assert macs["core.mlp"] == 3 * 2048 * 7168
    assert macs["core.moe_shared"] == 4 * 3 * 2048 * 768
    # a position's 8 picks land on a held expert a quarter of a time
    assert macs["core.moe_experts"] == 4 * 0.25 * 3 * 2048 * 768
    assert macs["core.router"] == 4 * 2048 * 256
    fwd = 2 * sum(macs.values())
    assert net.step_flops(config) == 2 * (
        2048 * fwd + 2047 * (2 * fwd - 2 * macs["conv0"])
    )
    # latent attention (projections, up-projection, attention) is about
    # four fifths of the core's forward products, the held experts 1%
    core = sum(v for k, v in macs.items() if k.startswith("core."))
    mla = macs["core.projections"] + macs["core.latent_kv"] + macs["core.attention_latent"]
    assert 0.75 < mla / core < 0.85
    assert macs["core.moe_experts"] / core < 0.015
    # parameters of the cut: 347.8M in the five blocks
    shapes = jax.eval_shape(lambda: net.init_params(0, config))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))  # noqa: E731
    layers = shapes["core"]["layers"]
    assert [count(x) for x in layers] == [70_391_808] + [69_343_488] * 4
    assert round(count(layers) / 1e6, 1) == 347.8
    # one call of each latent kernel: compute-bound
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    for name in ("attention_latent_forward", "attention_latent_backward"):
        f, b = net.OPS_AND_BYTES[name](config, 1)
        assert f == 2.0 * 2 * 2048 * 32 * 320 * (2048 + 1024.5), name
        assert f / peaks["bf16_flops_per_s"] > b / peaks["hbm_bytes_per_s"], name


def test_the_cell_reports_the_accepted_per_layer_metrics():
    spec = driver.Spec(ROOT)
    reported = {m["name"] for m in spec.metrics("per_layer", CELL)}
    assert "kernels.lstm_roofline" not in reported  # no LSTM to read
    assert {"train_step.mfu", "train_step.device_ms", "device.idle_share",
            "host.publish_copy_ms", "host.loop_overhead_ms",
            "host.bookkeeping_ms", "feed.batch_wait_share",
            "feed.host_stack_ms", "step_ms_p50", "step_ms_p95"} == reported


@pytest.mark.parametrize(
    "name,kernels",
    [
        ("kernels.attention_latent_roofline", ("forward",)),
        ("kernels.attention_latent_backward_roofline", ("backward_dq", "backward_dkv")),
    ],
)
def test_a_latent_roofline_file_reads_a_trace_once_declared(checkout, name, kernels):
    """The files are in the repo and their `per_layer` entries are not
    (PERF.md section 7, row 16). Declared in a checkout, each reads its
    kernels' calls by name from the `XLA Ops` line against the network
    file's count; here on a trace written by hand. The full layers'
    kernels are not read. A program without the kernels (the parent)
    gives nothing to read and no error."""
    from benchmark import readers, trace
    from benchmark.trace import Event

    spec = driver.Spec(ROOT)
    config = spec.config("pong_joyai_flash_core")
    net = spec.network(config)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ops = [
        Event(f"%attention_latent_{k}.{i} = (f32[2,32,2048,128]) custom-call(...)", 0.1 * i, 5e-3)
        for i, k in enumerate(kernels)
    ] + [
        Event("%attention_full_forward.2 = (f32[2,32,2048,64]) custom-call(...)", 0.5, 1.0),
        Event("%fusion.1 = f32[8] fusion(...)", 0.6, 0.5),
    ]
    ctx = readers.Context(
        trace=trace.Trace({"/device:TPU:0": ops}, {}), timers={},
        host_window_s=1.0, steps=1, config=config, chips=1, peaks=peaks,
        net=net,
    )
    file = spec.find("metrics", name)
    assert file["reader"] == "roofline"
    f, b = net.OPS_AND_BYTES[file["params"]["ops_and_bytes"]](config, 1)
    least = max(f / peaks["bf16_flops_per_s"], b / peaks["hbm_bytes_per_s"])
    got = readers.read(ctx, file)
    assert got == pytest.approx(100 * least / 5e-3)
    assert 0 < got < 100
    empty = ctx._replace(trace=trace.Trace({"/device:TPU:0": ops[-2:]}, {}))
    assert readers.read(empty, file) is None
    doc = checkout.doc
    doc["per_layer"].append(
        {"name": name, "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "kernels",
         "moves": "frames_per_s", "workloads": [CELL]}
    )
    checkout.write("BENCHMARK.json", doc)
    there = driver.Spec(checkout.root)
    assert name in {m["name"] for m in there.metrics("per_layer", CELL)}


def test_the_parent_refuses_the_cell_at_once(checkout, monkeypatch):
    """A program without the preset (the parent of the change that adds
    it) stops before any device work, with the configuration's mismatch."""
    from torched_impala_tpu import configs

    monkeypatch.delitem(configs.REGISTRY, "pong_joyai_flash")
    with pytest.raises(program.ConfigMismatch, match="no preset"):
        program.experiment_config(
            driver.Spec(checkout.root).config("pong_joyai_flash_core")
        )
