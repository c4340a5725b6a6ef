"""Flight recorder + cross-stage batch lineage tracing (ISSUE 4).

Covers the recorder itself (ring semantics, Chrome-trace export), the
watchdog ↔ recorder interplay (a wedged pipeline's stall dump must show
the stuck lineage ID), lineage threading through the trajectory ring and
the learner's batch queue, and the CLI acceptance path: a smoke run with
`--trace` emits valid Chrome-trace JSON in which every consumed learner
batch reconstructs its full env→queue/ring→learner lineage with exact
per-batch policy-version lag.
"""

import io
import json
import os
import queue
import signal
import threading
import time

import jax
import numpy as np
import optax
import pytest

from torched_impala_tpu.telemetry import (
    FlightRecorder,
    Registry,
    StallWatchdog,
    get_recorder,
    install_sigusr2,
    mint_lineage_id,
    validate_chrome_trace,
)


# ---- recorder unit behavior ---------------------------------------------


def test_record_kinds_and_tail_order():
    rec = FlightRecorder(capacity=64)
    rec.begin("actor/unroll", {"lid": "a0u0"})
    rec.instant("queue/enqueue", {"lid": "a0u0"})
    rec.end("actor/unroll", {"lid": "a0u0"})
    with rec.span("learner/host_stack", {"batch": 0}):
        pass
    assert len(rec) == 4
    tail = rec.tail()
    assert [r[2] for r in tail] == ["B", "i", "E", "X"]
    # Timestamps are monotone in record order.
    ts = [r[0] for r in tail]
    assert ts == sorted(ts)
    # The complete record carries its measured duration.
    assert tail[-1][1] >= 0
    # Lineage rides each record untouched.
    assert tail[0][5] == {"lid": "a0u0"}


def test_ring_wraps_keeping_newest():
    rec = FlightRecorder(capacity=8)
    for i in range(20):
        rec.instant("test/evt", {"i": i})
    assert len(rec) == 8
    assert rec.total_recorded == 20
    kept = [r[5]["i"] for r in rec.tail()]
    assert kept == list(range(12, 20))
    # tail(n) returns the newest n, oldest first.
    assert [r[5]["i"] for r in rec.tail(3)] == [17, 18, 19]


def test_capacity_rounds_up_to_power_of_two():
    assert FlightRecorder(capacity=100).capacity == 128
    with pytest.raises(ValueError):
        FlightRecorder(capacity=1)


def test_trace_name_grammar_enforced():
    rec = FlightRecorder(capacity=8)
    for bad in ("noslash", "Upper/case", "a/b/c", "a b/c"):
        with pytest.raises(ValueError, match="trace event name"):
            rec.instant(bad)


def test_disabled_recorder_is_noop():
    rec = FlightRecorder(capacity=8)
    rec.enabled = False
    rec.instant("test/evt")
    with rec.span("test/blk"):
        pass
    assert len(rec) == 0
    rec.enabled = True
    rec.instant("test/evt")
    assert len(rec) == 1


def test_concurrent_writers_never_lose_ring_shape():
    rec = FlightRecorder(capacity=256)

    def hammer(k):
        for i in range(5_000):
            rec.instant("test/spin", {"k": k, "i": i})

    threads = [
        threading.Thread(target=hammer, args=(k,)) for k in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert rec.total_recorded == 20_000
    tail = rec.tail()
    assert len(tail) == 256
    assert all(r is not None for r in tail)


def test_mint_lineage_id_format():
    assert mint_lineage_id(3, 17) == "a3u17"


# ---- Chrome-trace export -------------------------------------------------


def test_export_valid_chrome_trace(tmp_path):
    rec = FlightRecorder(capacity=64)
    with rec.span("actor/unroll", {"lid": "a0u0", "param_version": 0}):
        rec.instant("ring/commit", {"lid": "a0u0", "slot": 1})
    rec.instant("learner/publish", {"version": 160})
    path = str(tmp_path / "out" / "trace.json")  # parent dir created
    n = rec.export(path)
    assert n == 3
    obj = json.load(open(path))
    assert validate_chrome_trace(obj) == []
    events = obj["traceEvents"]
    # Components become Perfetto process rows via metadata events.
    proc_names = {
        e["args"]["name"]
        for e in events
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert proc_names == {"actor", "ring", "learner"}
    # Complete events carry dur in microseconds; instants a thread scope.
    x = [e for e in events if e["ph"] == "X"]
    assert x and all("dur" in e for e in x)
    i = [e for e in events if e["ph"] == "i"]
    assert i and all(e["s"] == "t" for e in i)
    # args survive the round trip.
    assert any(
        e.get("args", {}).get("lid") == "a0u0" for e in events
    )


@pytest.mark.parametrize(
    "n_records, capacity", [(3 * 500, 4096), (4 * 5000, 16384)]
)
def test_export_holds_every_record_up_to_the_ring_size(
    tmp_path, n_records, capacity
):
    """A scripted run of instants, pre-timed spans and span contexts,
    with and without lineage: the export is a valid Chrome trace of
    exactly the records written while they fit the ring, and of the
    ring's size once they do not (the newest are kept)."""
    rec = FlightRecorder(capacity=capacity)
    lineage = {"lid": "a0u0", "worker": 3}
    t_ns = time.monotonic_ns()
    for i in range(n_records):
        kind = i % 4
        if kind == 0:
            rec.instant("script/evt", lineage)
        elif kind == 1:
            rec.complete("script/span", t_ns + i, 1000, lineage)
        elif kind == 2:
            with rec.span("script/ctx", lineage):
                pass
        else:
            rec.instant("script/bare")
    path = str(tmp_path / "trace.json")
    n = rec.export(path)
    assert n == min(n_records, rec.capacity)
    obj = json.load(open(path))
    assert validate_chrome_trace(obj) == []
    events = [e for e in obj["traceEvents"] if e["ph"] != "M"]
    assert len(events) == n
    assert {e["ph"] for e in events} == {"i", "X"}


def test_validate_chrome_trace_catches_violations():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"foo": 1}) != []
    assert validate_chrome_trace({"traceEvents": "nope"}) != []
    missing_ts = {"traceEvents": [{"name": "x", "ph": "i",
                                   "pid": 1, "tid": 1}]}
    assert any("ts" in p for p in validate_chrome_trace(missing_ts))
    no_dur = {"traceEvents": [{"name": "x", "ph": "X", "ts": 1,
                               "pid": 1, "tid": 1}]}
    assert any("dur" in p for p in validate_chrome_trace(no_dur))
    ok = {"traceEvents": [{"name": "x", "ph": "X", "ts": 1, "dur": 2,
                           "pid": 1, "tid": 1}]}
    assert validate_chrome_trace(ok) == []


def test_format_tail_readable_with_lineage():
    rec = FlightRecorder(capacity=16)
    rec.instant("queue/enqueue", {"lid": "a7u3"})
    text = rec.format_tail()
    assert "queue/enqueue" in text and "a7u3" in text
    assert FlightRecorder(capacity=16).format_tail() == (
        "  (flight recorder empty)\n"
    )


@pytest.mark.skipif(
    not hasattr(signal, "SIGUSR2"), reason="platform without SIGUSR2"
)
def test_sigusr2_dumps_recorder(tmp_path):
    rec = FlightRecorder(capacity=32)
    rec.instant("test/evt", {"lid": "a1u2"})
    assert install_sigusr2(str(tmp_path), recorder=rec)
    try:
        os.kill(os.getpid(), signal.SIGUSR2)
        path = tmp_path / "flight_001.json"
        deadline = time.time() + 5
        while not path.exists() and time.time() < deadline:
            time.sleep(0.01)
        obj = json.load(open(path))
        assert validate_chrome_trace(obj) == []
        assert any(
            e.get("args", {}).get("lid") == "a1u2"
            for e in obj["traceEvents"]
        )
    finally:
        signal.signal(signal.SIGUSR2, signal.SIG_DFL)


# ---- watchdog ↔ flight recorder (ISSUE 4 satellite) ----------------------


def test_stall_dump_contains_recorder_tail_with_stuck_lineage():
    """A deliberately wedged queue: the producer records its unroll's
    lineage, then blocks forever on a full queue. The watchdog's stall
    dump must contain the flight-recorder tail with the stuck lineage
    ID visible — the dump names WHICH unroll wedged, not just where."""
    reg = Registry()
    rec = FlightRecorder(capacity=64)
    reg.heartbeat("actor")  # one beat, then silence = the wedge

    wedged_q: queue.Queue = queue.Queue(maxsize=1)
    wedged_q.put("full")
    release = threading.Event()
    stuck_lid = mint_lineage_id(4, 9)  # "a4u9"

    def wedged_producer():
        rec.begin("actor/unroll", {"lid": stuck_lid})
        rec.instant("queue/enqueue", {"lid": stuck_lid})
        while not release.is_set():
            try:
                wedged_q.put("next", timeout=0.1)
                return
            except queue.Full:
                continue

    producer = threading.Thread(
        target=wedged_producer, name="wedged-producer"
    )
    producer.start()
    stream = io.StringIO()
    dog = StallWatchdog(
        reg, deadline_s=0.3, poll_s=0.05, stream=stream, recorder=rec
    )
    try:
        dog.start()
        assert dog.fired.wait(timeout=5.0), "watchdog never fired"
    finally:
        dog.stop()
        release.set()
        wedged_q.get_nowait()
        producer.join()
    dump = stream.getvalue()
    assert "flight recorder tail" in dump
    assert stuck_lid in dump  # the wedged unroll is named
    assert "queue/enqueue" in dump  # ... at the stage it wedged
    assert "wedged-producer" in dump  # thread stacks still present


# ---- lineage through the trajectory ring ---------------------------------


def test_ring_carries_block_lineage_to_ready_slot():
    from torched_impala_tpu.runtime.traj_ring import TrajectoryRing

    rec = FlightRecorder(capacity=128)
    ring = TrajectoryRing(
        num_slots=2,
        unroll_length=3,
        batch_size=4,
        example_obs=np.zeros((4,), np.float32),
        num_actions=2,
        telemetry=Registry(),
        tracer=rec,
    )
    a = ring.acquire(2, lineage_id="a0u0")
    b = ring.acquire(2, lineage_id="a1u0")
    for blk in (a, b):
        for arr in (blk.obs, blk.first, blk.actions,
                    blk.behaviour_logits, blk.rewards, blk.cont,
                    blk.task):
            arr[...] = np.zeros_like(arr)
    # Commit out of order: lineage must come back in COLUMN order.
    ring.commit(b, param_version=7, lineage_id="a1u0")
    ring.commit(a, param_version=10, lineage_id="a0u0")
    view = ring.pop_ready(timeout=1.0)
    assert view is not None
    assert view.lineage == ("a0u0", "a1u0")
    assert view.versions == (10, 7)
    assert view.param_version == 7
    ring.release(view.slot)
    # Recycled slot starts a fresh lineage record.
    c = ring.acquire(4, lineage_id="a0u1")
    ring.commit(c, param_version=12, lineage_id="a0u1")
    view2 = ring.pop_ready(timeout=1.0)
    assert view2.lineage == ("a0u1",)
    names = {r[3] for r in rec.tail()}
    assert {"ring/acquire", "ring/commit", "ring/release"} <= names


# ---- lineage through the learner -----------------------------------------


class _ScriptedEnv:
    """Deterministic 4-dim obs env (gymnasium API surface)."""

    def __init__(self, episode_len=5):
        self._n = 0
        self._len = episode_len

    def reset(self, seed=None):
        self._n = 0
        return np.full((4,), 0.1, np.float32), {}

    def step(self, action):
        self._n += 1
        done = self._n >= self._len
        return (
            np.full((4,), 0.1 * (self._n + 1), np.float32),
            1.0,
            done,
            False,
            {},
        )


def _agent():
    from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso

    return Agent(
        ImpalaNet(num_actions=2, torso=MLPTorso(hidden_sizes=(16,)))
    )


@pytest.mark.parametrize("use_ring", [False, True])
def test_learner_step_names_exact_unrolls_and_lags(use_ring):
    """The tentpole invariant, queue and ring paths: the train-step
    trace span lists exactly the consumed unrolls' lineage IDs and the
    EXACT per-unroll param lag (num_frames after the update minus each
    unroll's acting version)."""
    from torched_impala_tpu.runtime.learner import Learner, LearnerConfig
    from torched_impala_tpu.runtime.vector_actor import VectorActor

    T, E, B = 4, 2, 4
    rec = FlightRecorder(capacity=1024)
    agent = _agent()
    learner = Learner(
        agent=agent,
        optimizer=optax.sgd(1e-2),
        config=LearnerConfig(
            batch_size=B, unroll_length=T, traj_ring=use_ring
        ),
        example_obs=np.zeros((4,), np.float32),
        rng=jax.random.key(0),
        telemetry=Registry(),
        tracer=rec,
    )
    actor = VectorActor(
        actor_id=0,
        envs=[_ScriptedEnv() for _ in range(E)],
        agent=agent,
        param_store=learner.param_store,
        enqueue=learner.enqueue,
        unroll_length=T,
        seed=0,
        telemetry=Registry(),
        traj_ring=learner.traj_ring,
        tracer=rec,
    )
    learner.start()
    try:
        for step in range(2):
            for _ in range(B // E):
                actor.unroll_and_push()
            learner.step_once(timeout=60)
    finally:
        learner.stop()

    tail = rec.tail()
    steps = [r for r in tail if r[3] == "learner/train_step"]
    unrolls = [r for r in tail if r[3] == "actor/unroll"]
    assert len(steps) == 2
    # Each version under its own frame count, in order, and settled
    # after its own step's call (the last by stop()'s drain): not under
    # the counter's value at that time.
    published = [r for r in tail if r[3] == "learner/publish"]
    assert [r[5]["version"] for r in published] == [0, T * B, 2 * T * B]
    for step, publish in zip(steps, published[1:]):
        assert publish[0] >= step[0] + step[1]
    minted = {r[5]["lid"]: r[5]["param_version"] for r in unrolls}
    frames_per_step = T * B
    for k, rec_step in enumerate(steps, start=1):
        args = rec_step[5]
        assert args["batch"] == k - 1
        lids = args["lineage"]
        # Ring mode: one lid per E-column block; queue mode: one per
        # trajectory (each cycle emits E of them, same cycle lid).
        expected_unrolls = B // E if use_ring else B
        assert len(lids) == expected_unrolls
        assert set(lids) <= set(minted)
        # Exact per-batch staleness: frames after this update minus the
        # acting version each unroll recorded at mint time.
        num_frames = k * frames_per_step
        for lid, version, lag in zip(
            lids, args["param_versions"], args["param_lag_frames"]
        ):
            assert version == minted[lid]
            assert lag == num_frames - version
        assert args["param_lag_min"] == min(args["param_lag_frames"])
        assert args["param_lag_max"] == max(args["param_lag_frames"])
    # The full chain exists: unroll -> queue/ring hop -> host_stack ->
    # device_put -> train_step -> publish.
    names = {r[3] for r in tail}
    hop = "ring/commit" if use_ring else "queue/enqueue"
    assert {
        "actor/unroll", hop, "learner/host_stack",
        "learner/device_put", "learner/train_step", "learner/publish",
    } <= names


def test_pool_worker_steps_tagged_with_driving_unroll():
    """Process-pool path: parent-observed submit->ack spans carry the
    lineage ID of the unroll the driving actor is filling."""
    from torched_impala_tpu.runtime.env_pool import ProcessEnvPool
    from torched_impala_tpu.runtime.param_store import ParamStore
    from torched_impala_tpu.runtime.vector_actor import VectorActor
    from torched_impala_tpu import configs

    rec = FlightRecorder(capacity=2048)
    factory = configs.make_env_factory(
        configs.ExperimentConfig(
            name="tracing_pool",
            env_family="cartpole",
            obs_shape=(4,),
            num_actions=2,
        ),
        fake=True,
    )
    agent = _agent()
    pool = ProcessEnvPool(
        env_factory=factory,
        num_workers=2,
        envs_per_worker=2,
        obs_shape=(4,),
        obs_dtype=np.float32,
        mode="async",
        ready_fraction=0.5,
        telemetry=Registry(),
        tracer=rec,
    )
    try:
        store = ParamStore()
        store.publish(0, agent.init_params(
            jax.random.key(0), np.zeros((4,), np.float32)
        ))
        actor = VectorActor(
            actor_id=0,
            envs=pool,
            agent=agent,
            param_store=store,
            enqueue=lambda t: None,
            unroll_length=3,
            seed=0,
            telemetry=Registry(),
            tracer=rec,
        )
        actor.unroll_and_push()
        actor.unroll_and_push()
    finally:
        pool.close()
    tail = rec.tail()
    worker_steps = [r for r in tail if r[3] == "pool/worker_step"]
    unroll_lids = {r[5]["lid"] for r in tail if r[3] == "actor/unroll"}
    assert unroll_lids == {"a0u0", "a0u1"}
    assert worker_steps
    assert {r[5]["lid"] for r in worker_steps} <= unroll_lids
    assert all("worker" in r[5] for r in worker_steps)


# ---- CLI acceptance: --trace emits a lineage-complete Chrome trace -------


def _load_trace(path):
    obj = json.load(open(path))
    assert validate_chrome_trace(obj) == [], validate_chrome_trace(obj)
    return [e for e in obj["traceEvents"] if e["ph"] != "M"]


@pytest.mark.parametrize("ring_flag", [[], ["--traj-ring"]])
def test_cli_trace_reconstructs_batch_lineage(tmp_path, ring_flag):
    """Acceptance: a smoke run with `--trace` emits valid Chrome-trace
    JSON in which every consumed learner batch's spans reconstruct the
    full env→queue/ring→learner lineage, including exact per-batch
    policy-version lag."""
    from torched_impala_tpu.run import main

    get_recorder().clear()
    out = str(tmp_path / "trace.json")
    rc = main(
        [
            "--config", "cartpole",
            "--fake-envs",
            "--total-steps", "4",
            "--log-every", "2",
            "--logger", "null",
            "--num-actors", "1",
            "--envs-per-actor", "2",
            "--trace", out,
        ]
        + ring_flag
    )
    assert rc == 0
    events = _load_trace(out)
    steps = [e for e in events if e["name"] == "learner/train_step"]
    assert len(steps) == 4
    minted = {
        e["args"]["lid"]: e["args"]["param_version"]
        for e in events
        if e["name"] == "actor/unroll"
    }
    hop = "ring/commit" if ring_flag else "queue/enqueue"
    hop_lids = {
        e["args"]["lid"] for e in events if e["name"] == hop
    }
    frames_per_step = 20 * 8  # cartpole preset: T=20, B=8
    for e in steps:
        args = e["args"]
        lids = args["lineage"]
        assert lids, "train step consumed no named unrolls"
        # Every consumed unroll traces back to an actor mint AND to its
        # queue/ring hop — the full env→...→learner chain.
        assert set(lids) <= set(minted)
        assert set(lids) <= hop_lids
        # Exact policy-version lag per consumed unroll.
        num_frames = args["step"] * frames_per_step
        for lid, version, lag in zip(
            lids, args["param_versions"], args["param_lag_frames"]
        ):
            assert version == minted[lid]
            assert lag == num_frames - version
    # Stage spans all present for the timeline view.
    names = {e["name"] for e in events}
    assert {
        "actor/unroll", "actor/wave", hop, "learner/host_stack",
        "learner/device_put", "learner/train_step", "learner/publish",
    } <= names
