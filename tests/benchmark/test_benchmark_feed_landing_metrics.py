"""Five per-layer metrics of the feed and the step loop are data: each
file is read by the `timer` reader from the program's timers over the
traced window, as the step loop's three are. The feed's stacking split
into the wait for its reused buffer and the copy into it, and a version's
landing into the loop's wait, the pieces' reads and their writes. Values
from timers written by hand; nothing where the program has no such timer
(a parent that lacks it, or a run that never observed it)."""

import json
import os

import pytest

from benchmark import readers

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
METRICS = {
    "feed.slot_wait_ms": "learner/stack_slot_wait",
    "feed.stack_copy_ms": "learner/stack_copy",
    "host.landing_wait_ms": "learner/landing_wait",
    "host.landing_read_ms": "learner/landing_read",
    "host.landing_write_ms": "learner/landing_write",
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


def _file(name):
    with open(os.path.join(ROOT, "benchmark", "metrics", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_feed_or_landing_metric_reads_its_timer(name):
    file = _file(name)
    assert file["reader"] == "timer"
    assert file["params"] == {"key": METRICS[name], "stat": "mean_ms"}
    # 0.35 s over 50 calls: 7 ms a call (a batch, or a version)
    ctx = _context({METRICS[name]: (0.35, 50), "learner/host_stack": (3.0, 50)})
    assert readers.read(ctx, file) == pytest.approx(7.0)
    # a program without the timer, or one that never observed it
    assert readers.read(_context({"learner/host_stack": (3.0, 50)}), file) is None
    assert readers.read(_context({METRICS[name]: (0.0, 0)}), file) is None


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_program_registers_the_timer_a_metric_reads(name):
    """The learner the benchmark builds registers each timer at once, in
    the registry the benchmark hands it (its totals hold the key from the
    start, so a window that observed it carries it)."""
    import jax
    import numpy as np
    import optax

    from benchmark import program
    from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
    from torched_impala_tpu.runtime import Learner, LearnerConfig

    reg = program.make_registry()
    Learner(
        agent=Agent(ImpalaNet(num_actions=2, torso=MLPTorso(hidden_sizes=(8,)))),
        optimizer=optax.sgd(1e-2),
        config=LearnerConfig(batch_size=2, unroll_length=3),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
        telemetry=reg,
    )
    assert METRICS[name] in reg.timer_totals()
