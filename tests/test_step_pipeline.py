"""One train step stays in flight (ISSUE 25).

`step_once` for step k dispatches step k and only then settles step k-1:
waits for it, copies its parameters to the host, publishes them. What the
device computes, and every version the actors get, is as with a loop that
waits after every step; only when the host does its part has changed.
CPU, tiny sizes: what is tested is the order, the versions and the
bytes, not a speed.
"""

import threading
import time

import jax
import numpy as np
import optax
import pytest

from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
from torched_impala_tpu.ops.popart import PopArtConfig
from torched_impala_tpu.runtime.learner import Learner, LearnerConfig
from torched_impala_tpu.runtime.types import Trajectory
from torched_impala_tpu.telemetry import FlightRecorder, Registry

T, B, TASKS, ACTIONS, LSTM = 4, 4, 2, 3, 8
FRAMES = T * B


def _trajectory(i: int, use_lstm: bool) -> Trajectory:
    rng = np.random.default_rng(i)
    state = ()
    if use_lstm:
        state = (
            rng.normal(size=(1, LSTM)).astype(np.float32),
            rng.normal(size=(1, LSTM)).astype(np.float32),
        )
    return Trajectory(
        obs=rng.normal(size=(T + 1, 4)).astype(np.float32),
        first=rng.uniform(size=(T + 1,)) < 0.2,
        actions=rng.integers(0, ACTIONS, size=(T,)).astype(np.int32),
        behaviour_logits=rng.normal(size=(T, ACTIONS)).astype(np.float32),
        rewards=(10.0 * rng.normal(size=(T,))).astype(np.float32),
        cont=np.ones((T,), np.float32),
        agent_state=state,
        task=i % TASKS,
    )


def _learner(publish_interval=1, use_lstm=False, **kwargs) -> Learner:
    """A PopArt learner with RMSProp, so that parameters, optimizer state
    and statistics all move; its own registry and recorder."""
    agent = Agent(
        ImpalaNet(
            num_actions=ACTIONS,
            torso=MLPTorso(hidden_sizes=(16,)),
            use_lstm=use_lstm,
            lstm_size=LSTM,
            num_values=TASKS,
        )
    )
    return Learner(
        agent=agent,
        optimizer=optax.rmsprop(1e-2),
        config=LearnerConfig(
            batch_size=B,
            unroll_length=T,
            publish_interval=publish_interval,
            popart=PopArtConfig(num_values=TASKS, step_size=0.1),
        ),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
        telemetry=kwargs.pop("telemetry", None) or Registry(),
        tracer=kwargs.pop("tracer", None) or FlightRecorder(capacity=1 << 12),
        **kwargs,
    )


def _feed(learner, step: int, use_lstm=False) -> None:
    for i in range(B):
        learner.enqueue(_trajectory(step * B + i, use_lstm))


def _state(learner) -> list:
    """Host copies of everything a step changes."""
    return [
        np.array(x, copy=True)
        for x in jax.tree.leaves(
            (learner.params, learner.opt_state, learner.popart_state)
        )
    ]


class _Versions:
    """Every publish as the `ParamStore` announced it, with what it held
    at that moment (copies, so a later change of the bytes would show)."""

    def __init__(self, learner):
        self.seen: list = []
        self._store = learner.param_store
        self._store.add_publish_listener(self._on_publish)

    def _on_publish(self, version: int) -> None:
        got, params = self._store.get()
        assert got == version
        self.seen.append(
            (version, params, [np.array(x) for x in jax.tree.leaves(params)])
        )


# ---- (a) the same parameters, bit for bit -------------------------------


@pytest.mark.parametrize("use_lstm", [False, True], ids=["mlp", "lstm"])
@pytest.mark.parametrize("publish_interval", [1, 4])
def test_state_after_n_steps_is_bitwise_that_of_a_drained_loop(
    publish_interval, use_lstm
):
    steps = 9
    finals = []
    for drain_each_step in (False, True):
        learner = _learner(publish_interval, use_lstm)
        learner.start()
        try:
            for step in range(steps):
                _feed(learner, step, use_lstm)
                learner.step_once(timeout=120)
                if drain_each_step:
                    learner.drain()
            learner.drain()
            finals.append(_state(learner))
            assert learner.num_steps == steps
            assert learner.param_store.version == FRAMES * (
                steps // publish_interval * publish_interval
            )
        finally:
            learner.stop()
    piped, drained = finals
    assert len(piped) == len(drained) > 6
    for a, b in zip(piped, drained):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


# ---- (b) every version once, in order, with its own bytes ---------------


@pytest.mark.parametrize("publish_interval", [1, 4])
def test_every_crossing_version_is_published_once_in_order_with_its_own_bytes(
    publish_interval,
):
    steps = 9
    learner = _learner(publish_interval)
    versions = _Versions(learner)
    params_after: dict = {}
    learner.start()
    try:
        for step in range(1, steps + 1):
            _feed(learner, step)
            learner.step_once(timeout=120)
            # `params` is the newest state: that of the step just
            # dispatched, whatever has been published so far.
            params_after[step * FRAMES] = [
                np.array(x) for x in jax.tree.leaves(learner.params)
            ]
        learner.drain()
    finally:
        learner.stop()
    want = [
        k * FRAMES for k in range(1, steps + 1) if k % publish_interval == 0
    ]
    assert [v for v, _, _ in versions.seen] == want
    for version, params, at_publish in versions.seen:
        # the parameters of that step, not of the step after it ...
        for got, own in zip(at_publish, params_after[version]):
            assert got.tobytes() == own.tobytes()
        # ... in memory of their own (no leaf of this tree is over a
        # piece: a cut leaf would be a view of its version's slab), which
        # no later step's donation has deleted or written over since
        for leaf, then in zip(jax.tree.leaves(params), at_publish):
            assert isinstance(leaf, np.ndarray) and leaf.flags.owndata
            assert leaf.tobytes() == then.tobytes()
    if len(want) > 1:
        first, last = versions.seen[0][2], versions.seen[-1][2]
        assert any(a.tobytes() != b.tobytes() for a, b in zip(first, last))


def test_the_snapshot_is_a_copy_that_the_next_step_cannot_reach():
    """The published bytes come from fresh device buffers: the step that
    follows donates the live parameters and deletes them."""
    learner = _learner()
    learner.start()
    try:
        _feed(learner, 0)
        learner.step_once(timeout=120)
        flight = learner._in_flight
        live = jax.tree.leaves(learner.params)
        held = jax.tree.leaves(flight.snapshot)
        assert flight.version == FRAMES and flight.step == 1
        assert all(a is not b for a, b in zip(held, live))
        assert {a.unsafe_buffer_pointer() for a in held}.isdisjoint(
            b.unsafe_buffer_pointer() for b in live
        )
        want = [np.array(x) for x in live]
        _feed(learner, 1)
        learner.step_once(timeout=120)
        assert all(x.is_deleted() for x in live)  # donated to step 2
        assert not any(x.is_deleted() for x in held)
        version, params = learner.param_store.get()
        assert version == FRAMES
        for got, own in zip(jax.tree.leaves(params), want):
            assert got.tobytes() == own.tobytes()
    finally:
        learner.stop()


# ---- (c) one step behind, and never left behind -------------------------


@pytest.mark.parametrize("settle", ["drain", "stop", "set_state"])
def test_step_k_minus_1_is_out_when_step_k_returns_and_step_k_by(settle):
    learner = _learner()
    learner.start()
    try:
        # Both batches wait on the device queue (depth 2), so the second
        # call has a batch to dispatch ahead of the first step.
        _feed(learner, 0)
        _feed(learner, 1)
        _wait_for(lambda: learner._batch_q.full())
        logs = learner.step_once(timeout=120)
        assert logs["num_frames"] == FRAMES and logs["num_steps"] == 1
        assert learner.param_store.version == 0
        assert learner._in_flight.version == FRAMES
        logs = learner.step_once(timeout=120)
        assert logs["num_frames"] == 2 * FRAMES
        assert learner.param_store.version == FRAMES
        if settle == "set_state":
            # The step in flight is settled before the state is replaced,
            # then the restored state is published at its own count.
            state = learner.get_state()
            state["num_frames"] = np.asarray(7 * FRAMES, np.int64)
            versions = _Versions(learner)
            learner.set_state(state)
            assert [v for v, _, _ in versions.seen] == [2 * FRAMES, 7 * FRAMES]
        else:
            getattr(learner, settle)()
            assert learner.param_store.version == 2 * FRAMES
        assert learner._in_flight is None
        learner.drain()  # nothing left: a second one does nothing
    finally:
        learner.stop()


def test_run_publishes_its_last_step_before_it_returns():
    learner = _learner()
    versions = _Versions(learner)
    steps = 5

    def feed():
        try:
            for step in range(steps):
                _feed(learner, step)
        except Exception:  # QueueClosed once the run is over
            pass

    feeder = threading.Thread(target=feed, daemon=True)
    feeder.start()
    learner.run(steps)
    feeder.join(timeout=30)
    assert not feeder.is_alive()
    assert learner.num_steps == steps
    assert [v for v, _, _ in versions.seen] == [
        k * FRAMES for k in range(1, steps + 1)
    ]
    assert learner._in_flight is None


def test_the_logger_gets_each_step_once_a_call_later_with_its_own_numbers():
    rows: list = []
    learner = _learner(logger=rows.append)
    learner.start()
    try:
        returned = []
        for step in range(3):
            _feed(learner, step)
            logs = learner.step_once(timeout=120)
            returned.append(float(logs["total_loss"]))
            assert [r["num_steps"] for r in rows][-1:] != [step + 1]
        learner.drain()
    finally:
        learner.stop()
    assert [r["num_steps"] for r in rows] == [1, 2, 3]
    assert [r["num_frames"] for r in rows] == [FRAMES, 2 * FRAMES, 3 * FRAMES]
    assert [r["total_loss"] for r in rows] == returned
    assert all(isinstance(r["total_loss"], float) for r in rows)


# ---- (d) the counter that says it engages -------------------------------


class _Leaf:
    """A device leaf whose readiness the test decides."""

    def __init__(self, leaf, ready: bool):
        self._leaf, self._ready = leaf, ready

    def is_ready(self) -> bool:
        return self._ready

    def block_until_ready(self):
        self._leaf.block_until_ready()
        return self


@pytest.mark.parametrize("still_in_flight", [True, False])
def test_dispatch_lead_is_observed_only_when_the_step_before_was_in_flight(
    still_in_flight,
):
    reg = Registry()
    learner = _learner(telemetry=reg)
    lead = reg.timer("learner/dispatch_lead")
    learner.start()
    try:
        _feed(learner, 0)
        learner.step_once(timeout=120)
        assert lead.calls == 0  # nothing was in flight before the first
        for step in (1, 2):
            flight = learner._in_flight
            learner._in_flight = flight._replace(
                probe=[_Leaf(flight.probe[0], ready=not still_in_flight)]
            )
            _feed(learner, step)
            _wait_for(lambda: not learner._batch_q.empty())
            learner.step_once(timeout=120)
            assert lead.calls == (step if still_in_flight else 0)
        learner.drain()  # a drain launches nothing ahead of anything
        assert lead.calls == (2 if still_in_flight else 0)
        assert reg.timer("learner/train_step").calls == 3
        if still_in_flight:
            # from the dispatch's return to the step being ready: inside
            # the wait, and the copy queued before it
            assert 0 < lead.seconds <= (
                reg.timer("learner/step_wait").seconds
                + reg.timer("learner/publish_copy").seconds
                + reg.timer("learner/bookkeeping").seconds
            )
    finally:
        learner.stop()


def test_a_starved_loop_settles_before_it_waits_and_leads_nothing():
    """No batch to dispatch ahead of the device: the step in flight is
    settled at the entry, so its version does not wait for the feed."""
    reg = Registry()
    learner = _learner(telemetry=reg)
    learner.start()
    try:
        _feed(learner, 0)
        learner.step_once(timeout=120)
        assert learner.param_store.version == 0

        def late_feed():
            _wait_for(lambda: learner.param_store.version == FRAMES)
            _feed(learner, 1)

        feeder = threading.Thread(target=late_feed, daemon=True)
        feeder.start()
        learner.step_once(timeout=120)  # returns only if step 1 got out first
        feeder.join(timeout=30)
        assert not feeder.is_alive()
        assert reg.timer("learner/dispatch_lead").calls == 0
    finally:
        learner.stop()


def _wait_for(condition, timeout: float = 60.0) -> None:
    deadline = time.monotonic() + timeout
    while not condition():
        assert time.monotonic() < deadline, "condition not met in time"
        time.sleep(0.001)
