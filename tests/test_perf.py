"""Performance observatory units (ISSUE 10): cost model + fallback,
overlap-analyzer interval arithmetic on synthetic flight-recorder
traces, and report rendering/writing.

Synthetic traces use the recorder's own record shape — the
`(ts_ns, dur_ns, phase, name, tid, args)` 6-tuples of
`FlightRecorder.tail()` — so the analyzer is tested against the real
interface, not a private fixture format.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from torched_impala_tpu.perf import (  # noqa: E402
    CostModel,
    RootCost,
    analyze_records,
    categorize_span,
    extract_compiled_cost,
    generate_report,
    measure,
    render_report,
    static_flops_estimate,
    subtract,
    union,
    write_report,
)
from torched_impala_tpu.telemetry import Registry  # noqa: E402

MS = 1_000_000  # ns


def _span(t0_ms, dur_ms, name, args=None, tid=1):
    return (t0_ms * MS, dur_ms * MS, "X", name, tid, args)


# ---- cost model ----------------------------------------------------------


def test_static_flops_estimate():
    # 6 FLOPs per param per frame: 10 params x 4 frames.
    assert static_flops_estimate(10, 4) == 240.0


def test_extract_compiled_cost_never_raises():
    class Broken:
        def cost_analysis(self):
            raise RuntimeError("no backend")

    out = extract_compiled_cost(Broken())
    assert out == {"flops": 0.0, "bytes_accessed": 0.0, "temp_bytes": 0.0}


def test_extract_compiled_cost_handles_both_shapes():
    class ListShaped:
        def cost_analysis(self):
            return [{"flops": 7.0, "bytes accessed": 3.0}]

    class DictShaped:
        def cost_analysis(self):
            return {"flops": 7.0, "bytes accessed": 3.0}

    for compiled in (ListShaped(), DictShaped()):
        out = extract_compiled_cost(compiled)
        assert out["flops"] == 7.0 and out["bytes_accessed"] == 3.0


def test_extract_compiled_cost_on_real_executable():
    import jax
    import jax.numpy as jnp

    x = jnp.ones((32, 32), jnp.float32)
    compiled = jax.jit(lambda a: (a @ a).sum()).lower(x).compile()
    out = extract_compiled_cost(compiled)
    # The CPU backend may or may not report costs; the contract is only
    # "well-formed and non-negative" — the fallback test below pins the
    # nonzero path.
    assert out["flops"] >= 0.0 and out["bytes_accessed"] >= 0.0


def test_cost_model_static_fallback_and_gauges():
    import jax.numpy as jnp

    reg = Registry()
    cm = CostModel.for_device("TPU v5 lite", registry=reg)

    class NoCosts:
        def cost_analysis(self):
            return []

    root = cm.register_root(
        "train_step",
        compiled=NoCosts(),
        fallback_params={"w": jnp.ones((8, 8)), "b": jnp.ones((8,))},
        frames_per_call=100,
        steps_per_call=2,
    )
    assert isinstance(root, RootCost)
    assert root.source == "static"
    assert root.flops == 6.0 * 72 * 100
    mfu = cm.observe_call("train_step", dt_seconds=1e-3)
    assert mfu > 0.0
    snap = reg.snapshot()
    assert snap["telemetry/perf/mfu"] == pytest.approx(mfu)
    # Per-SGD-step gauge divides the per-call count by steps_per_call.
    assert snap["telemetry/perf/flops_per_step"] == pytest.approx(
        root.flops / 2
    )


def test_cost_model_flops_scale_corrects_scan_bodies():
    reg = Registry()
    cm = CostModel(registry=reg)

    class BodyOnce:
        def cost_analysis(self):
            return [{"flops": 1000.0, "bytes accessed": 10.0}]

    root = cm.register_root(
        "train_step", compiled=BodyOnce(), flops_scale=4.0
    )
    assert root.source == "cost_analysis"
    assert root.flops == 4000.0


def test_cost_model_roofline_bound():
    cm = CostModel(
        registry=Registry(), peak_flops=100.0, peak_bytes_per_s=10.0
    )

    class C:
        def __init__(self, flops, b):
            self._c = {"flops": flops, "bytes accessed": b}

        def cost_analysis(self):
            return self._c

    cm.register_root("hot", compiled=C(1000.0, 10.0))  # AI 100 > ridge 10
    cm.register_root("cold", compiled=C(10.0, 10.0))  # AI 1 < ridge 10
    assert cm.roofline("hot")["bound"] == "compute"
    assert cm.roofline("cold")["bound"] == "memory"
    assert cm.roofline("missing") == {}
    assert set(cm.snapshot()) == {"hot", "cold"}


def test_observe_call_unknown_root_is_zero():
    cm = CostModel(registry=Registry())
    assert cm.observe_call("nope", 1.0) == 0.0


# ---- interval arithmetic -------------------------------------------------


def test_union_merges_and_drops_empty():
    assert union([(5, 7), (0, 2), (1, 3), (9, 9)]) == [(0, 3), (5, 7)]


def test_subtract_partial_overlaps():
    removed, remaining = subtract([(0, 10)], [(2, 4), (6, 8)])
    assert removed == 4
    assert remaining == [(0, 2), (4, 6), (8, 10)]
    assert measure(remaining) == 6


def test_subtract_no_overlap():
    removed, remaining = subtract([(0, 5)], [(10, 20)])
    assert removed == 0 and remaining == [(0, 5)]


# ---- overlap analyzer ----------------------------------------------------


def test_categorize_span_priority_families():
    assert categorize_span("learner/publish") == "publish"
    assert categorize_span("learner/device_put") == "h2d"
    assert categorize_span("learner/host_stack") == "feed"
    assert categorize_span("queue/enqueue") == "feed"
    assert categorize_span("ring/commit") == "feed"
    assert categorize_span("learner/compile_wait") == "compile"
    assert categorize_span("learner/train_step") is None
    assert categorize_span("watchdog/stall") is None


def test_analyze_empty_and_no_steps():
    assert analyze_records([])["learner"] == {"steps": 0}
    rep = analyze_records([_span(0, 5, "queue/enqueue")])
    assert rep["learner"] == {"steps": 0}
    assert rep["span_counts"] == {"queue/enqueue": 1}


def test_analyze_attributes_gaps_by_priority():
    # Two steps with a 10ms gap; publish and feed BOTH cover [10,14):
    # publish (higher priority) wins the disputed interval, feed only
    # charges its uncontested [14, 18), and [18, 20) is unattributed.
    records = [
        _span(0, 10, "learner/train_step", {}),
        _span(10, 4, "learner/publish"),
        _span(10, 8, "learner/host_stack"),
        _span(20, 10, "learner/train_step", {}),
    ]
    learner = analyze_records(records)["learner"]
    assert learner["steps"] == 2
    assert learner["wall_clock_s"] == pytest.approx(0.030)
    assert learner["compute_s"] == pytest.approx(0.020)
    assert learner["gap_total_s"] == pytest.approx(0.010)
    assert learner["gaps_s"]["publish"] == pytest.approx(0.004)
    assert learner["gaps_s"]["feed"] == pytest.approx(0.004)
    assert learner["gaps_s"]["unattributed"] == pytest.approx(0.002)
    assert learner["coverage_frac"] == pytest.approx(1.0)
    assert learner["attributed_frac"] == pytest.approx(28 / 30)


def test_analyze_pipelined_feeder_only_charges_gap_portion():
    # The feeder span [5, 15) overlaps step one (healthy pipelining);
    # only its in-gap part [10, 12) may be charged.
    records = [
        _span(0, 10, "learner/train_step", {}),
        _span(5, 10, "learner/host_stack"),
        _span(12, 10, "learner/train_step", {}),
    ]
    learner = analyze_records(records)["learner"]
    assert learner["gaps_s"]["feed"] == pytest.approx(0.002)
    assert learner["gaps_s"]["unattributed"] == 0.0
    assert learner["coverage_frac"] == pytest.approx(1.0)


def test_in_flight_bracket_is_the_compute_not_the_dispatch():
    """On an asynchronous backend `learner/train_step` is the dispatch: a
    2 ms dispatch inside a 60 ms in-flight bracket is 60 ms of compute,
    and `publish` is charged the copy alone, not the wait for the device
    that `learner/publish` also holds."""
    records = []
    for k, t0 in enumerate((0, 70), start=1):
        tag = {"step": k}
        records += [
            _span(t0, 2, "learner/train_step", dict(tag, batch=k)),
            _span(t0 + 2, 1, "learner/bookkeeping", tag),
            _span(t0 + 3, 57, "learner/step_wait", tag),
            _span(t0, 60, "learner/step_in_flight", tag),
            _span(t0 + 60, 4, "learner/publish_copy", tag),
            _span(t0 + 3, 61, "learner/publish", {"version": k}),
            _span(t0 + 64, 1, "learner/bookkeeping", tag),
            _span(t0 + 65, 3, "learner/outside_step", tag),
            _span(t0 + 68, 2, "learner/batch_wait", tag),
        ]
    learner = analyze_records(records)["learner"]
    assert learner["compute_source"] == "step_in_flight"
    assert learner["steps"] == 2
    assert learner["compute_s"] == pytest.approx(0.120)
    assert learner["wall_clock_s"] == pytest.approx(0.130)
    assert learner["gaps_s"]["publish"] == pytest.approx(0.004)
    assert learner["gaps_s"]["bookkeeping"] == pytest.approx(0.001)
    assert learner["gaps_s"]["outside_step"] == pytest.approx(0.003)
    # the batch wait with no feeder span beside it
    assert learner["gaps_s"]["unattributed"] == pytest.approx(0.002)
    assert learner["coverage_frac"] == pytest.approx(1.0)
    assert "dispatch to device done" in render_report({"learner": learner})


def test_overlapping_brackets_of_a_pipelined_loop_add_up_to_the_window():
    """One step in flight: the bracket of step k+1 opens at its dispatch,
    59 ms before the wait for step k returns. Each step's compute is its
    own bracket from where the one before ended, so the three brackets
    (119 + 119 + 63 ms) count as the 183 ms of wall clock they cover."""
    records = []
    for k in range(1, 4):
        t0, tag, prev = 60 * (k - 1), {"step": k}, {"step": k - 1}
        records += [  # the call for step k
            _span(t0 + 1, 2, "learner/train_step", tag),
            _span(t0 + 3, 1, "learner/publish_copy", tag),  # queued
        ]
        if k > 1:
            records += [  # ... settles step k-1 behind that dispatch
                _span(t0 + 4, 56, "learner/step_wait", prev),
                _span(t0 - 59, 119, "learner/step_in_flight", prev),
                _span(t0 + 60, 1, "learner/publish_copy", prev),  # landed
            ]
    records += [  # the drain settles step 3
        _span(183, 1, "learner/step_wait", {"step": 3}),
        _span(121, 63, "learner/step_in_flight", {"step": 3}),
    ]
    learner = analyze_records(records)["learner"]
    assert learner["compute_source"] == "step_in_flight"
    assert learner["steps"] == 3
    assert learner["wall_clock_s"] == pytest.approx(0.183)
    assert learner["compute_s"] == pytest.approx(0.183)
    assert learner["compute_frac"] == pytest.approx(1.0)
    assert learner["gap_total_s"] == 0.0
    assert learner["coverage_frac"] == pytest.approx(1.0)
    assert learner["fresh"]["compute_s"] == pytest.approx(0.183)


def test_without_a_bracket_the_dispatch_stands_in_and_the_report_says_so():
    # publish_interval 2: only step 2 waited for the device
    records = [
        _span(0, 2, "learner/train_step", {"step": 1}),
        _span(2, 1, "learner/bookkeeping", {"step": 1}),
        _span(3, 2, "learner/train_step", {"step": 2}),
        _span(3, 60, "learner/step_in_flight", {"step": 2}),
        _span(63, 5, "learner/publish_copy", {"step": 2}),
        _span(5, 63, "learner/publish", {"version": 2}),
        _span(68, 2, "learner/train_step", {"step": 3}),
    ]
    learner = analyze_records(records)["learner"]
    assert learner["compute_source"] == "mixed"
    assert learner["compute_s"] == pytest.approx(0.064)
    assert learner["gaps_s"]["publish"] == pytest.approx(0.005)
    assert learner["gaps_s"]["bookkeeping"] == pytest.approx(0.001)
    none = analyze_records(
        [
            _span(0, 10, "learner/train_step", {"step": 1}),
            _span(10, 4, "learner/publish"),
            _span(14, 10, "learner/train_step", {"step": 2}),
        ]
    )["learner"]
    assert none["compute_source"] == "train_step"
    assert none["gaps_s"]["publish"] == pytest.approx(0.004)
    assert "DISPATCH only" in render_report({"learner": none})


def test_step_loop_phases_are_gap_categories():
    assert categorize_span("learner/publish_copy") == "publish"
    assert categorize_span("learner/bookkeeping") == "bookkeeping"
    assert categorize_span("learner/outside_step") == "outside_step"
    assert categorize_span("learner/step_in_flight") is None
    assert categorize_span("learner/compile") == "compile"
    # the waits are not causes: what overlaps them is
    assert categorize_span("learner/batch_wait") is None
    assert categorize_span("learner/step_wait") is None


def test_categorize_donated_h2d_span():
    # The donated-ring staging span is H2D time like device_put
    # (ISSUE 13 zero-copy feed path).
    assert categorize_span("learner/h2d") == "h2d"


def test_analyze_overlapped_h2d_not_charged_and_frac_reported():
    # Step N's H2D rides entirely inside step N-1's compute: it must
    # charge NO gap anywhere, and the report's h2d_overlap_frac says
    # 1.0 — the double-buffered staging win, measured.
    records = [
        _span(0, 10, "learner/train_step", {}),
        _span(2, 6, "learner/h2d", {"batch": 1}),
        _span(10, 10, "learner/train_step", {}),
        _span(12, 4, "learner/h2d", {"batch": 2}),
        _span(20, 10, "learner/train_step", {}),
    ]
    learner = analyze_records(records)["learner"]
    assert learner["gap_total_s"] == 0.0
    assert learner["gaps_s"]["h2d"] == 0.0
    assert learner["h2d_total_s"] == pytest.approx(0.010)
    assert learner["h2d_overlap_frac"] == pytest.approx(1.0)
    assert learner["compute_frac"] == pytest.approx(1.0)


def test_analyze_partially_overlapped_h2d_charges_only_gap_part():
    # H2D [8, 14) spans the step boundary at 10: the overlapped [8, 10)
    # is free, only the in-gap [10, 14) is charged as h2d, and the
    # fraction reports the 2/6 that hid under compute.
    records = [
        _span(0, 10, "learner/train_step", {}),
        _span(8, 6, "learner/h2d", {}),
        _span(16, 10, "learner/train_step", {}),
    ]
    learner = analyze_records(records)["learner"]
    assert learner["gaps_s"]["h2d"] == pytest.approx(0.004)
    assert learner["gaps_s"]["unattributed"] == pytest.approx(0.002)
    assert learner["h2d_overlap_frac"] == pytest.approx(2 / 6)
    assert learner["coverage_frac"] == pytest.approx(1.0)


def test_analyze_splits_fresh_from_replayed():
    # BatchLineage convention: reuse_count 1 == fresh first delivery;
    # only re-deliveries (> 1) count as replayed.
    records = [
        _span(0, 10, "learner/train_step", {"reuse_max": 1}),
        _span(12, 10, "learner/train_step", {"reuse_max": 3, "staleness": 640}),
        _span(24, 10, "learner/train_step", {"reuse_max": 2, "staleness": 320}),
        _span(36, 10, "learner/train_step", {}),  # no lineage: fresh
    ]
    learner = analyze_records(records)["learner"]
    assert learner["fresh"]["steps"] == 2
    assert learner["replayed"]["steps"] == 2
    assert learner["replayed"]["compute_s"] == pytest.approx(0.020)
    assert learner["replayed"]["reuse_mean"] == pytest.approx(2.5)
    assert learner["replayed"]["staleness_mean"] == pytest.approx(480.0)


def test_analyze_skips_non_complete_phases():
    records = [
        _span(0, 10, "learner/train_step", {}),
        (5 * MS, 0, "i", "ring/commit", 1, None),  # instant: ignored
        _span(12, 10, "learner/train_step", {}),
        None,  # empty ring slot
    ]
    learner = analyze_records(records)["learner"]
    assert learner["steps"] == 2


# ---- report rendering / writing ------------------------------------------


def test_render_and_write_report(tmp_path):
    records = [
        _span(0, 10, "learner/train_step", {}),
        _span(10, 2, "learner/device_put"),
        _span(12, 10, "learner/train_step", {"reuse_max": 2}),
    ]
    roofline = {
        "train_step": {
            "root": "train_step",
            "source": "static",
            "flops_per_step": 2e9,
            "arithmetic_intensity": 300.0,
            "ridge_intensity": 240.5,
            "bound": "compute",
        }
    }
    path = str(tmp_path / "perf.json")
    report = generate_report(path, records=records, roofline=roofline)
    text = render_report(report)
    assert "2 steps" in text
    assert "gap:h2d" in text
    assert "replayed: 1/2 steps" in text
    assert "compute-bound" in text
    with open(path) as f:
        on_disk = json.load(f)
    assert on_disk["learner"]["steps"] == 2
    assert on_disk["roofline"] == roofline
    with open(str(tmp_path / "perf.txt")) as f:
        assert f.read() == text


def test_write_report_non_json_suffix(tmp_path):
    path = str(tmp_path / "perf.out")
    txt = write_report({"schema": 1, "span_counts": {}}, path)
    assert txt == path + ".txt"
    assert os.path.exists(path) and os.path.exists(txt)
