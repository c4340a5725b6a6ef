"""The learner's step loop tells its own time (ISSUE 24, ISSUE 25).

One period of the loop (an entry of `step_once` to the next) is cut into
phases that do not overlap and add up to it; each is a registry timer and
a flight-recorder span carrying the number of the step it belongs to. The
call for step k dispatches step k and then settles step k-1, so a period
holds spans of both. CPU, tiny sizes: the arithmetic is what is tested,
not a speed.
"""

import queue
import threading
import time

import jax
import numpy as np
import optax
import pytest

from torched_impala_tpu.runtime.learner import Learner, LearnerConfig
from torched_impala_tpu.runtime.types import Trajectory
from torched_impala_tpu.telemetry import FlightRecorder, Registry

PHASES = (
    "learner/batch_wait",
    "learner/train_step",
    "learner/bookkeeping",
    "learner/step_wait",
    "learner/publish_copy",
    "learner/outside_step",
)
T, B = 4, 4


def _agent():
    from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso

    return Agent(ImpalaNet(num_actions=2, torso=MLPTorso(hidden_sizes=(16,))))


def _trajectory(i: int) -> Trajectory:
    rng = np.random.default_rng(i)
    return Trajectory(
        obs=rng.normal(size=(T + 1, 4)).astype(np.float32),
        first=np.zeros((T + 1,), bool),
        actions=rng.integers(0, 2, size=(T,)).astype(np.int32),
        behaviour_logits=rng.normal(size=(T, 2)).astype(np.float32),
        rewards=rng.normal(size=(T,)).astype(np.float32),
        cont=np.ones((T,), np.float32),
        agent_state=(),
    )


def _learner(reg, rec, publish_interval: int = 1) -> Learner:
    return Learner(
        agent=_agent(),
        optimizer=optax.sgd(1e-2),
        config=LearnerConfig(
            batch_size=B, unroll_length=T, publish_interval=publish_interval
        ),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
        telemetry=reg,
        tracer=rec,
    )


def _drive(publish_interval: int, steps: int):
    """`steps` steps of a tiny learner fed from a thread, with a pause
    between calls so that `outside_step` is not empty. Returns (registry,
    recorder, window seconds, timer seconds in the window): the window
    opens and closes where `step_once` returned by the loop's own stamp
    (`_returned_ns`, where `outside_step` starts), not by a clock read
    after the return: between the two the thread can lose the processor
    for milliseconds (six xdist workers, a feeder and a batcher thread on
    the GIL), which a window of 20 tiny CPU steps (80-200 ms) cannot carry
    under a relative tolerance, and which is nobody's phase."""
    reg, rec = Registry(), FlightRecorder(capacity=1 << 14)
    learner = _learner(reg, rec, publish_interval)

    def feed():
        try:
            for i in range((steps + 2) * B):
                learner.enqueue(_trajectory(i))
        except Exception:  # QueueClosed at the end
            pass

    feeder = threading.Thread(target=feed, daemon=True)
    learner.start()
    feeder.start()

    def totals():
        return {name: reg.timer(name).seconds for name in PHASES}

    try:
        learner.step_once(timeout=60)  # the compile
        t_open, before = learner._returned_ns, totals()
        for _ in range(steps):
            time.sleep(0.002)
            learner.step_once(timeout=60)
        t_close, after = learner._returned_ns, totals()
    finally:
        learner.stop()
        feeder.join(timeout=30)
    spent = {name: after[name] - before[name] for name in PHASES}
    return reg, rec, (t_close - t_open) / 1e9, spent


def _spans(rec, name):
    return [r for r in rec.tail() if r[3] == name and r[2] == "X"]


@pytest.mark.parametrize("publish_interval", [1, 4])
def test_phases_add_up_to_the_window(publish_interval):
    reg, rec, window, spent = _drive(publish_interval, steps=20)
    # The phases tile the window: nothing of a period goes uncounted or
    # is counted twice (the timers add float seconds, the stamps are ns).
    assert sum(spent.values()) == pytest.approx(window, rel=1e-6)
    assert spent["learner/outside_step"] >= 20 * 0.002
    # Only a step that publishes is waited for, one call later (the 21st
    # by stop()'s drain): once each.
    published = 21 // publish_interval
    assert reg.timer("learner/step_wait").calls == published
    # publish_copy is observed once per call that queued a snapshot or
    # landed one; with publish_interval 1 every call does both.
    assert reg.timer("learner/publish_copy").calls == (
        21 + 1 if publish_interval == 1 else 2 * published
    )
    # once per call of step_once, and once more where the drain settled
    assert reg.timer("learner/bookkeeping").calls == 21 + (
        21 % publish_interval == 0
    )
    assert reg.timer("learner/outside_step").calls == 20
    # publish is the wait and the landing of one version: the two phase
    # timers less the queueing. __init__'s blocking publish is in the
    # timer besides and in no phase (it waits for the parameters' init,
    # milliseconds on a busy machine), so its span is taken off.
    publish = reg.timer("learner/publish")
    assert publish.calls == published + 1
    (constructed,) = [
        s for s in _spans(rec, "learner/publish") if s[5] == {"version": 0}
    ]
    settled = publish.seconds - constructed[1] / 1e9
    assert reg.timer("learner/step_wait").seconds <= settled + 1e-9
    assert settled <= (
        reg.timer("learner/step_wait").seconds
        + reg.timer("learner/publish_copy").seconds
        + 1e-9
    )


@pytest.mark.parametrize("publish_interval", [1, 4])
def test_every_span_of_a_step_carries_its_number_and_they_tile(
    publish_interval,
):
    _, rec, _, _ = _drive(publish_interval, steps=20)
    spans = [
        (ts, dur, name, args)
        for ts, dur, _ph, name, _tid, args in rec.tail()
        if name in PHASES + ("learner/step_in_flight", "learner/loop_overhead")
    ]
    by_step: dict = {}
    for span in spans:
        by_step.setdefault(span[3]["step"], []).append(span)
    assert sorted(by_step) == list(range(1, 22))
    # The phases tile the loop's thread from the first entry to the last
    # return, whichever step each belongs to: each starts where the last
    # ended. (stop()'s drain comes after the last return.)
    tiles = sorted(s for s in spans if s[2] in PHASES)
    drained = [s[0] for s in by_step[21] if s[2] == "learner/step_wait"]
    tiles = [s for s in tiles if not drained or s[0] < drained[0]]
    for a, b in zip(tiles, tiles[1:]):
        assert a[0] + a[1] == b[0], (a[2:], b[2:])
    for step in range(1, 22):
        names = [s[2] for s in by_step[step]]
        published = step % publish_interval == 0
        for name in PHASES + ("learner/loop_overhead",):
            if name in ("learner/step_wait", "learner/publish_copy"):
                want = published
            else:  # the last period was not closed by a next entry
                want = step < 21 or name not in (
                    "learner/outside_step", "learner/loop_overhead"
                )
            assert (name in names) == want, (step, name)
        assert ("learner/step_in_flight" in names) == published
        if published:
            # two pieces of copy: the snapshot queued in the step's own
            # call, and landed in the next, after the wait
            assert names.count("learner/publish_copy") == 2
            (flight,) = [
                s for s in by_step[step] if s[2] == "learner/step_in_flight"
            ]
            (dispatch,) = [
                s for s in by_step[step] if s[2] == "learner/train_step"
            ]
            (wait,) = [s for s in by_step[step] if s[2] == "learner/step_wait"]
            assert flight[0] == dispatch[0]
            assert flight[0] + flight[1] == wait[0] + wait[1]
            if step < 21:
                # settled in the next call: behind that step's dispatch,
                # or at its entry where no batch was there to dispatch
                (period,) = [
                    s for s in by_step[step] if s[2] == "learner/loop_overhead"
                ]
                (ahead,) = [
                    s for s in by_step[step + 1] if s[2] == "learner/train_step"
                ]
                assert wait[0] in (period[0] + period[1], ahead[0] + ahead[1]) or (
                    wait[0] > ahead[0] + ahead[1]
                )
    # A period is the tiles between two entries, whatever their numbers.
    for period in (s for s in spans if s[2] == "learner/loop_overhead"):
        inside = [
            s for s in tiles if period[0] <= s[0] < period[0] + period[1]
        ]
        assert inside[0][0] == period[0]
        assert inside[0][2] in ("learner/step_wait", "learner/batch_wait")
        assert period[1] == sum(s[1] for s in inside)
        waits = sum(
            s[1]
            for s in inside
            if s[2] in ("learner/batch_wait", "learner/step_wait")
        )
        assert period[3]["overhead_ns"] == period[1] - waits


def test_loop_overhead_timer_is_the_period_less_the_waits():
    reg, rec, _, _ = _drive(1, steps=20)
    overheads = [s[5]["overhead_ns"] for s in _spans(rec, "learner/loop_overhead")]
    timer = reg.timer("learner/loop_overhead")
    assert timer.calls == len(overheads) == 20
    assert timer.seconds == pytest.approx(sum(overheads) / 1e9, rel=1e-9)


def test_a_timed_out_wait_is_counted_and_leaves_the_period_open():
    reg, rec = Registry(), FlightRecorder(capacity=256)
    learner = _learner(reg, rec)
    learner.start()
    try:
        for _ in range(2):
            with pytest.raises(queue.Empty):
                learner.step_once(timeout=0.01)
        assert reg.timer("learner/batch_wait").calls == 2
        assert reg.timer("learner/batch_wait").seconds >= 0.02
        assert reg.timer("learner/outside_step").calls == 1
        assert reg.timer("learner/loop_overhead").calls == 0
        for i in range(B):
            learner.enqueue(_trajectory(i))
        learner.step_once(timeout=60)
        with pytest.raises(queue.Empty):
            learner.step_once(timeout=0.01)
    finally:
        learner.stop()
    # one period: from the first entry to the entry after the step
    assert reg.timer("learner/loop_overhead").calls == 1
    (period,) = _spans(rec, "learner/loop_overhead")
    waits = sum(s[1] for s in _spans(rec, "learner/batch_wait")[:3])
    assert period[5]["overhead_ns"] == period[1] - waits
    # The entry after the step found no batch to dispatch ahead of the
    # device, so it settled the step in flight before it waited for one:
    # the version is out although no further step was ever dispatched.
    (wait,) = _spans(rec, "learner/step_wait")
    assert wait[0] == period[0] + period[1]
    assert wait[5] == {"step": 1}
    assert _spans(rec, "learner/batch_wait")[3][0] > wait[0] + wait[1]
    assert learner.param_store.version == T * B
