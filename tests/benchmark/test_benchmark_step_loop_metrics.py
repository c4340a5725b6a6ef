"""The three per-layer metrics of the step loop (ISSUE 24) are data: each
file is read by the `timer` reader from the program's timers over the
traced window. Values from timers written by hand; nothing where the
program has no such timer (the parent of the PR that added them)."""

import json
import os

import pytest

from benchmark import readers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
METRICS = {
    "host.loop_overhead_ms": "learner/loop_overhead",
    "host.publish_copy_ms": "learner/publish_copy",
    "host.bookkeeping_ms": "learner/bookkeeping",
}


def _context(timers):
    return readers.Context(
        trace=None,
        timers=timers,
        host_window_s=5.0,
        steps=50,
        config={},
        chips=1,
        peaks={},
    )


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_step_loop_metric_reads_its_timer(name):
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        file = json.load(f)
    assert file["params"] == {"key": METRICS[name], "stat": "mean_ms"}
    # 0.35 s over 50 calls: 7 ms a call
    ctx = _context({METRICS[name]: (0.35, 50), "learner/publish": (3.0, 50)})
    assert readers.read(ctx, file) == pytest.approx(7.0)
    # a program without the timer, or one that never observed it
    assert readers.read(_context({"learner/publish": (3.0, 50)}), file) is None
    assert readers.read(_context({METRICS[name]: (0.0, 0)}), file) is None


def test_the_step_loop_metrics_are_declared_for_both_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cells = [w["name"] for w in doc["workloads"]]
    declared = {m["name"]: m for m in doc["per_layer"]}
    assert list(declared)[-3:] == list(METRICS)
    for name in METRICS:
        entry = declared[name]
        assert entry["workloads"] == cells
        assert (entry["unit"], entry["better"]) == ("ms", "lower")
        assert (entry["source"], entry["layer"]) == ("program_span", "step loop")
        assert entry["moves"] == "frames_per_s"
