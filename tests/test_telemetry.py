"""Telemetry subsystem: registry, watchdog, profiler capture, pipeline
integration (ISSUE 2)."""

import io
import importlib.util
import json
import math
import os
import queue
import signal
import threading
import time

import numpy as np
import pytest

from torched_impala_tpu.telemetry import (
    FlightRecorder,
    ProfilerCapture,
    Registry,
    StallWatchdog,
    StepWindowProfiler,
    parse_profile_steps,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---- registry -----------------------------------------------------------


def test_counter_concurrent_increments():
    reg = Registry()
    c = reg.counter("test/hits")
    threads = [
        threading.Thread(
            target=lambda: [c.inc() for _ in range(10_000)]
        )
        for _ in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 80_000
    assert reg.snapshot()["telemetry/test/hits"] == 80_000


def test_histogram_bucket_edges():
    reg = Registry()
    h = reg.histogram("test/lat_ms", buckets=(1.0, 2.0, 5.0))
    # Upper edges are inclusive: 1.0 lands in the first bucket, 1.0001 in
    # the second, 5.0 in the third, 7.0 in the +inf tail.
    for v in (0.5, 1.0, 1.5, 2.0, 5.0, 7.0):
        h.observe(v)
    assert h.count == 6
    assert h._counts == [2, 2, 1, 1]
    snap = reg.snapshot()
    assert snap["telemetry/test/lat_ms_count"] == 6
    assert snap["telemetry/test/lat_ms_max"] == 7.0
    assert snap["telemetry/test/lat_ms_mean"] == pytest.approx(17.0 / 6)
    # p50: rank 3 of 6 falls at the top of bucket 2 (upper edge 2.0).
    assert 1.0 <= snap["telemetry/test/lat_ms_p50"] <= 2.0
    # p95/p99: ranks 5.7 and 5.94 of 6 fall in the +inf bucket, which
    # reports max.
    assert snap["telemetry/test/lat_ms_p95"] == 7.0
    assert snap["telemetry/test/lat_ms_p99"] == 7.0


def test_histogram_quantile_ordering_and_interpolation():
    """p50 <= p95 <= p99 <= max, each linearly interpolated inside its
    bucket when the rank lands below the +inf tail."""
    reg = Registry()
    h = reg.histogram("test/quant_ms", buckets=(10.0, 100.0, 1000.0))
    for _ in range(98):
        h.observe(5.0)  # bucket [0, 10]
    h.observe(500.0)  # bucket (100, 1000]
    h.observe(500.0)
    snap = reg.snapshot()
    p50 = snap["telemetry/test/quant_ms_p50"]
    p95 = snap["telemetry/test/quant_ms_p95"]
    p99 = snap["telemetry/test/quant_ms_p99"]
    assert 0.0 < p50 <= 10.0
    assert 0.0 < p95 <= 10.0  # rank 95 of 100 still in the first bucket
    # rank 99 of 100 lands in the (100, 1000] bucket: interpolated
    # there, clamped to the observed max (no real quantile exceeds it).
    assert 100.0 <= p99 <= 500.0
    assert p50 <= p95 <= p99 <= snap["telemetry/test/quant_ms_max"]


def test_histogram_single_bucket_edge_case():
    """One configured edge: two real buckets ([0, e] and +inf). The
    quantile estimator must interpolate in the only finite bucket and
    report the observed max from the tail — not crash or divide by a
    missing lower edge."""
    reg = Registry()
    h = reg.histogram("test/single_ms", buckets=(5.0,))
    h.observe(1.0)
    h.observe(6.0)  # +inf tail
    snap = reg.snapshot()
    assert snap["telemetry/test/single_ms_count"] == 2
    # rank 1 of 2: top of the finite bucket, interpolated within [0, 5].
    assert 0.0 < snap["telemetry/test/single_ms_p50"] <= 5.0
    # ranks 1.9/1.98 of 2: the +inf bucket reports the max.
    assert snap["telemetry/test/single_ms_p95"] == 6.0
    assert snap["telemetry/test/single_ms_p99"] == 6.0
    # All observations in the single finite bucket: quantiles stay
    # inside it.
    h2 = reg.histogram("test/single2_ms", buckets=(5.0,))
    h2.observe(2.0)
    snap = reg.snapshot()
    assert 0.0 < snap["telemetry/test/single2_ms_p99"] <= 5.0


def test_timer_keeps_a_total_beside_the_average():
    """A share of a window is a difference of two sums: `<name>_total_s`
    is the sum of every observation, under the timer's own lock, and goes
    through the exposition like `_calls`."""
    from torched_impala_tpu.telemetry import parse_openmetrics, to_openmetrics

    reg = Registry()
    t = reg.timer("test/stage")
    assert t.seconds == 0.0
    for seconds in (0.25, 0.5, 1.0):
        t.observe(seconds)
    assert t.seconds == pytest.approx(1.75)
    snap = reg.snapshot()
    assert snap["telemetry/test/stage_total_s"] == pytest.approx(1.75)
    assert snap["telemetry/test/stage_calls"] == 3
    scraped = parse_openmetrics(to_openmetrics(snap))
    assert scraped["impala_test_stage_total_s"] == pytest.approx(1.75)
    assert scraped["impala_test_stage_calls"] == 3
    # frozen while the registry is disabled, like every other record
    reg.enabled = False
    t.observe(5.0)
    assert t.seconds == pytest.approx(1.75)
    # concurrent observers lose nothing
    reg2 = Registry()
    t2 = reg2.timer("test/stage")
    threads = [
        threading.Thread(target=lambda: [t2.observe(0.5) for _ in range(2000)])
        for _ in range(4)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert t2.seconds == 4000.0 and t2.calls == 8000


def test_the_benchmarks_summing_timer_does_not_count_twice():
    """`benchmark/program.py::SummingTimer` adds to its own `total_s`
    after `EwmaTimer.observe`; the program's total must stay its own."""
    from benchmark import program

    reg = program.make_registry()
    t = reg.timer("learner/publish")
    t.observe(1.0)
    assert reg.timer_totals() == {"learner/publish": (1.0, 1)}
    assert t.seconds == 1.0
    assert reg.snapshot()["telemetry/learner/publish_total_s"] == 1.0


def test_histogram_empty_is_nan_not_crash():
    reg = Registry()
    reg.histogram("test/empty_ms")
    snap = reg.snapshot()
    assert snap["telemetry/test/empty_ms_count"] == 0
    assert math.isnan(snap["telemetry/test/empty_ms_p95"])
    assert math.isnan(snap["telemetry/test/empty_ms_p99"])
    assert math.isnan(snap["telemetry/test/empty_ms_mean"])


def test_histogram_rejects_bad_buckets():
    reg = Registry()
    with pytest.raises(ValueError):
        reg.histogram("test/bad", buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        reg.histogram("test/bad2", buckets=())


def test_snapshot_while_writing():
    reg = Registry()
    c = reg.counter("test/spins")
    h = reg.histogram("test/spin_ms")
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            c.inc()
            h.observe(1.5)
            reg.gauge("test/depth").set(3.0)
            reg.heartbeat("hammer")

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    try:
        last = -1
        for _ in range(200):
            snap = reg.snapshot()
            v = snap["telemetry/test/spins"]
            assert v >= last  # counter is monotone, never torn
            last = v
            assert (
                snap["telemetry/test/spin_ms_count"] >= 0
            )
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert reg.last_heartbeat() is not None


def test_same_name_same_type_shares_metric():
    reg = Registry()
    assert reg.counter("a/b") is reg.counter("a/b")


def test_type_conflict_raises():
    reg = Registry()
    reg.counter("test/thing")
    with pytest.raises(TypeError, match="already registered as counter"):
        reg.gauge("test/thing")
    # span() registers a timer under the hood: same-name timer is fine,
    # but a histogram is a conflict.
    with reg.span("test/block"):
        pass
    assert reg.timer("test/block") is not None
    with pytest.raises(TypeError):
        reg.histogram("test/block")


@pytest.mark.parametrize(
    "bad", ["noslash", "Upper/case", "a/b/c", "a/", "/b", "a b/c"]
)
def test_malformed_names_rejected(bad):
    with pytest.raises(ValueError):
        Registry().counter(bad)


def test_gauge_fn_reads_lazily():
    reg = Registry()
    q: queue.Queue = queue.Queue()
    reg.gauge("test/qdepth", fn=q.qsize)
    assert reg.snapshot()["telemetry/test/qdepth"] == 0
    q.put(1)
    q.put(2)
    assert reg.snapshot()["telemetry/test/qdepth"] == 2


def test_span_times_block():
    reg = Registry()
    with reg.span("test/sleepy"):
        time.sleep(0.02)
    snap = reg.snapshot()
    assert snap["telemetry/test/sleepy_calls"] == 1
    assert snap["telemetry/test/sleepy_ms"] >= 15.0


def test_disabled_registry_is_noop():
    reg = Registry(enabled=False)
    c = reg.counter("test/hits")
    c.inc()
    reg.heartbeat("x")
    assert c.value == 0
    assert reg.last_heartbeat() is None
    reg.enabled = True
    c.inc()
    assert c.value == 1


# ---- stall watchdog -----------------------------------------------------


def test_watchdog_fires_on_wedged_queue():
    """The acceptance scenario: a producer wedged on a full queue whose
    consumer never drains it. The watchdog must dump thread stacks (the
    wedged frame visible), dump the snapshot, count the stall, and emit
    the event through on_stall."""
    reg = Registry()
    reg.counter("test/progress").inc()
    reg.heartbeat("learner")  # one beat, then silence = the wedge

    wedged_q: queue.Queue = queue.Queue(maxsize=1)
    wedged_q.put("full")
    release = threading.Event()

    def wedged_enqueue_producer():
        # Blocks forever on the full queue (until the test releases it).
        while not release.is_set():
            try:
                wedged_q.put("next", timeout=0.1)
                return
            except queue.Full:
                continue

    producer = threading.Thread(
        target=wedged_enqueue_producer, name="wedged-producer"
    )
    producer.start()
    events = []
    stream = io.StringIO()
    dog = StallWatchdog(
        reg,
        deadline_s=0.3,
        poll_s=0.05,
        on_stall=events.append,
        stream=stream,
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
    assert "STALL" in dump and "no pipeline heartbeat" in dump
    assert "learner=" in dump  # the last-beats report
    assert "thread stacks" in dump
    assert "wedged-producer" in dump  # the wedged thread is visible
    assert "wedged_enqueue_producer" in dump  # ... down to its frame
    assert "registry snapshot" in dump
    assert "telemetry/test/progress=1" in dump
    assert reg.snapshot()["telemetry/watchdog/stall"] == 1
    assert len(events) == 1
    assert events[0]["telemetry/watchdog/stall"] == 1
    assert events[0]["telemetry/watchdog/stalled_for_s"] >= 0.3


def test_watchdog_quiet_while_heartbeats_flow_then_rearms():
    reg = Registry()
    stream = io.StringIO()
    dog = StallWatchdog(reg, deadline_s=0.4, poll_s=0.05, stream=stream)
    try:
        dog.start()
        for _ in range(8):  # healthy phase: beats inside the deadline
            reg.heartbeat("actor")
            time.sleep(0.05)
        assert not dog.fired.is_set()
        assert dog.fired.wait(timeout=5.0)  # silence -> first stall
        assert stream.getvalue().count("STALL") == 1
        time.sleep(0.3)  # still silent: must NOT re-dump the same stall
        assert stream.getvalue().count("STALL") == 1
        reg.heartbeat("actor")  # progress resumes -> re-arms
        time.sleep(0.15)
        assert dog._stall_active is False
    finally:
        dog.stop()


def test_watchdog_rejects_nonpositive_deadline():
    with pytest.raises(ValueError):
        StallWatchdog(Registry(), deadline_s=0.0)


# ---- profiler capture ---------------------------------------------------


def test_parse_profile_steps():
    assert parse_profile_steps("0:3") == (0, 3)
    assert parse_profile_steps("100:250") == (100, 250)
    for bad in ("3", "a:b", "5:5", "7:3", "-1:4", "1:2:3"):
        with pytest.raises(ValueError):
            parse_profile_steps(bad)


class _FakeCapture:
    def __init__(self):
        self.calls = []

    def start(self, tag=None):
        self.calls.append(("start", tag))

    def stop(self):
        self.calls.append(("stop", None))


def test_step_window_opens_and_closes_on_edges():
    cap = _FakeCapture()
    win = StepWindowProfiler(cap, start_step=2, stop_step=5)
    for s in (1, 2, 3, 4, 5, 6, 7):
        win.on_step(s)
    assert cap.calls == [("start", "steps_2_5"), ("stop", None)]


def test_step_window_opens_immediately_when_start_is_past():
    # A resumed run restored beyond start_step: the initial callback
    # (loop.py fires one with the restored count) opens the window.
    cap = _FakeCapture()
    win = StepWindowProfiler(cap, start_step=2, stop_step=10)
    win.on_step(7)
    assert cap.calls == [("start", "steps_2_10")]
    win.close()  # budget ended before stop_step: flush, don't lose it
    assert cap.calls[-1] == ("stop", None)


def test_step_window_validates_range():
    with pytest.raises(ValueError):
        StepWindowProfiler(_FakeCapture(), 5, 5)


def test_profiler_capture_writes_trace(tmp_path):
    cap = ProfilerCapture(str(tmp_path / "traces"))
    import jax
    import jax.numpy as jnp

    path = cap.start(tag="t")
    assert cap.active and path.endswith("/t")
    assert cap.start() is None  # single global trace at a time
    jax.jit(lambda x: x * 2)(jnp.ones((8,))).block_until_ready()
    assert cap.stop() == path
    assert not cap.active
    assert cap.stop() is None
    files = [
        os.path.join(dp, f)
        for dp, _, fs in os.walk(path)
        for f in fs
    ]
    assert files, "trace directory is empty"


def test_capture_options_reach_the_profiler(tmp_path, monkeypatch):
    """An operator's capture runs with the Python tracer off and the host
    tracer at `HOST_TRACER_LEVEL`: the default options make a DMLab step
    take 3.7 s instead of 96 ms (PERF.md)."""
    import jax

    from torched_impala_tpu.telemetry import profiling

    seen = {}

    def fake_start(path, **kwargs):
        seen["path"], seen["kwargs"] = path, kwargs

    monkeypatch.setattr(jax.profiler, "start_trace", fake_start)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    cap = ProfilerCapture(
        str(tmp_path / "traces"), registry=Registry(), recorder=FlightRecorder(64)
    )
    path = cap.start(tag="t")
    options = seen["kwargs"]["profiler_options"]
    assert seen["path"] == path
    assert options.python_tracer_level == profiling.PYTHON_TRACER_LEVEL == 0
    assert options.host_tracer_level == profiling.HOST_TRACER_LEVEL
    assert profiling.HOST_TRACER_LEVEL in (0, 1)
    cap.stop()


def test_capture_leaves_the_recorders_spans_beside_the_xplane(tmp_path):
    """`stop()` writes `host_spans.json` next to the `.xplane.pb`: a valid
    Chrome trace on the epoch clock that holds the records of the capture
    and none from before it."""
    import glob

    import jax
    import jax.numpy as jnp

    from torched_impala_tpu.telemetry import validate_chrome_trace
    from torched_impala_tpu.telemetry.profiling import HOST_SPANS_FILE

    rec = FlightRecorder(capacity=64)
    cap = ProfilerCapture(
        str(tmp_path / "traces"), registry=Registry(), recorder=rec
    )
    t_old = time.monotonic_ns()
    rec.complete("learner/train_step", t_old, 1000, {"step": 1})
    time.sleep(0.002)
    wall_before = time.time_ns()
    path = cap.start(tag="t")
    jax.jit(lambda x: x * 2)(jnp.ones((8,))).block_until_ready()
    t_in = time.monotonic_ns()
    rec.complete("learner/step_in_flight", t_in, 2_000_000, {"step": 2})
    rec.instant("queue/enqueue", {"lid": "a0u0"})
    cap.stop()
    wall_after = time.time_ns()
    (pb,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    with open(os.path.join(os.path.dirname(pb), HOST_SPANS_FILE)) as f:
        doc = json.load(f)
    assert validate_chrome_trace(doc) == []
    events = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert [e["name"] for e in events] == [
        "learner/step_in_flight",
        "queue/enqueue",
    ]
    assert events[0]["args"] == {"step": 2}
    assert events[0]["dur"] == pytest.approx(2000.0)
    # on the epoch clock, in microseconds
    for e in events:
        assert wall_before / 1e3 <= e["ts"] <= wall_after / 1e3
    pair = doc["metadata"]["clock_pair_ns"]
    assert events[0]["ts"] * 1e3 == pytest.approx(
        t_in - pair["monotonic"] + pair["epoch"], abs=1.0
    )
    assert "epoch" in doc["metadata"]["clock"]


@pytest.mark.parametrize("written, capacity", [(3, 64), (20, 8)])
def test_a_capture_states_the_records_its_ring_lost(tmp_path, written, capacity):
    """`host_spans.json` says how many of the capture's records the ring
    had overwritten by its end (`dropped`): the oldest ones, so what it
    holds is the newest `capacity`."""
    import glob

    from torched_impala_tpu.telemetry.profiling import HOST_SPANS_FILE

    rec = FlightRecorder(capacity=capacity)
    for i in range(5):  # before the capture: not the capture's
        rec.instant("queue/enqueue", {"lid": f"old{i}"})
    cap = ProfilerCapture(
        str(tmp_path / "traces"), registry=Registry(), recorder=rec
    )
    path = cap.start(tag="t")
    for i in range(written):
        rec.instant("queue/enqueue", {"lid": f"a0u{i}"})
    cap.stop()
    found = glob.glob(os.path.join(path, "**", HOST_SPANS_FILE), recursive=True)
    with open(found[0]) as f:
        doc = json.load(f)
    assert doc["metadata"]["dropped"] == max(0, written - capacity)
    lids = [e["args"]["lid"] for e in doc["traceEvents"] if e["ph"] != "M"]
    assert lids == [f"a0u{i}" for i in range(written)][-capacity:]


def test_recorder_export_since_and_epoch(tmp_path):
    rec = FlightRecorder(capacity=64)
    mono, wall = rec.clock_pair
    assert abs((time.time_ns() - wall) - (time.monotonic_ns() - mono)) < 50e6
    rec.complete("test/old", 1_000, 500)
    rec.complete("test/straddles", 1_800, 400)  # ends at 2_200
    rec.complete("test/new", 3_000, 100)
    kept = rec.to_chrome_events(since_ns=2_000)
    assert [e["name"] for e in kept if e["ph"] == "X"] == [
        "test/straddles",
        "test/new",
    ]
    plain = [e for e in rec.to_chrome_events() if e["ph"] == "X"]
    shifted = [e for e in rec.to_chrome_events(epoch=True) if e["ph"] == "X"]
    for a, b in zip(plain, shifted):
        assert b["ts"] - a["ts"] == pytest.approx((wall - mono) / 1e3)
        assert a["dur"] == b["dur"]
    out = str(tmp_path / "t.json")
    assert rec.export(out, since_ns=2_900, metadata={"k": 1}) == 1
    with open(out) as f:
        doc = json.load(f)
    assert doc["metadata"] == {"k": 1}
    assert rec.sync_clock() == rec.clock_pair != (mono, wall)


def test_a_compile_shows_in_the_registrys_timers():
    """`jit/backend_compile` and `jit/trace` count this process's compiles
    with their seconds: a fresh jit bumps them, its second call does not."""
    import jax
    import jax.numpy as jnp

    from torched_impala_tpu.telemetry.profiling import watch_compiles

    x = jnp.ones((7, 3))  # before the watch: making it compiles too
    reg = Registry()
    watch_compiles(reg)
    watch_compiles(reg)  # asking twice listens once
    compiles, traces = reg.timer("jit/backend_compile"), reg.timer("jit/trace")
    assert (compiles.calls, traces.calls) == (0, 0)
    fresh = jax.jit(lambda x: jnp.tanh(x) * 3.0 + 1.0)
    fresh(x).block_until_ready()
    first = (compiles.calls, traces.calls)
    assert first[0] == 1 and first[1] >= 1
    assert compiles.seconds > 0.0
    fresh(x).block_until_ready()
    assert (compiles.calls, traces.calls) == first
    snap = reg.snapshot()
    assert snap["telemetry/jit/backend_compile_calls"] == 1
    assert snap["telemetry/jit/backend_compile_total_s"] == compiles.seconds
    # a registry nobody holds any more is dropped, not fed for ever
    import gc
    import weakref

    ref = weakref.ref(reg)
    del reg, compiles, traces
    gc.collect()
    assert ref() is None
    jax.jit(lambda x: x - 1.0)(x).block_until_ready()


@pytest.mark.skipif(
    not hasattr(signal, "SIGUSR1"), reason="platform without SIGUSR1"
)
def test_sigusr1_toggles_capture(tmp_path):
    cap = ProfilerCapture(str(tmp_path / "traces"))
    assert cap.install_sigusr1()
    try:
        os.kill(os.getpid(), signal.SIGUSR1)
        deadline = time.time() + 5
        while not cap.active and time.time() < deadline:
            time.sleep(0.01)
        assert cap.active
        os.kill(os.getpid(), signal.SIGUSR1)
        while cap.active and time.time() < deadline:
            time.sleep(0.01)
        assert not cap.active
    finally:
        signal.signal(signal.SIGUSR1, signal.SIG_DFL)
        if cap.active:
            cap.stop()


# ---- metric-name lint (tools/lint telemetry checker) --------------------
#
# Migrated to the impala-lint framework entrypoint (ISSUE 7); the
# legacy tools/check_metric_names.py CLI shim is covered by
# tests/test_lint.py. `legacy_check` keeps the historical list-of-
# strings surface these tests were written against.


def _load_lint():
    import sys

    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.lint import metrics

    class _Shim:
        check = staticmethod(metrics.legacy_check)

    return _Shim


def test_metric_name_lint_clean():
    lint = _load_lint()
    errors = lint.check(REPO)
    assert errors == [], "\n".join(errors)


def test_metric_name_lint_catches_violations(tmp_path):
    lint = _load_lint()
    pkg = tmp_path / "torched_impala_tpu"
    pkg.mkdir()
    (pkg / "bad.py").write_text(
        'reg.counter("NoSlash")\n'
        'reg.gauge("pool/depth")\n'
        'reg.timer("pool/depth")\n'  # type fork with the gauge above
        'x = "telemetry/bad key here"\n'  # prose, must NOT flag
        'y = "telemetry/bad/Key"\n'  # malformed literal, not flagged
        'z = "telemetry/ok/key"\n'
        'rec.instant("Bad.Trace")\n'  # trace grammar violation
        'rec.complete("pool/worker_step", t0, dur)\n'  # valid trace
        'rec.instant("ring/commit", {"lid": lid})\n'  # valid trace
    )
    errors = lint.check(str(tmp_path))
    joined = "\n".join(errors)
    assert "NoSlash" in joined
    assert "registered it as gauge" in joined
    assert "Bad.Trace" in joined and "trace instant" in joined
    assert len(errors) == 3


# ---- pipeline integration ----------------------------------------------


def _jsonl_keys(path):
    keys = set()
    with open(path) as f:
        for line in f:
            keys.update(json.loads(line).keys())
    return keys


def test_train_emits_telemetry_through_jsonl(tmp_path):
    """Acceptance: a CPU fake-env run emits telemetry/pool/*, actor/*,
    queue/*, and learner/* keys through JSONLinesLogger (process-mode
    pool so all four stages exist)."""
    import optax

    from torched_impala_tpu import configs
    from torched_impala_tpu.runtime.loop import train
    from torched_impala_tpu.utils.loggers import JSONLinesLogger

    cfg = configs.ExperimentConfig(
        name="telemetry_it",
        env_family="cartpole",
        obs_shape=(4,),
        num_actions=2,
        num_actors=2,
        envs_per_actor=2,
        actor_mode="process",
        pool_mode="async",
        pool_ready_fraction=0.5,
        unroll_length=5,
        batch_size=4,
        lr=1e-3,
        lr_anneal=False,
    )
    path = str(tmp_path / "telemetry.jsonl")
    logger = JSONLinesLogger(path)
    try:
        result = train(
            agent=configs.make_agent(cfg),
            env_factory=configs.make_env_factory(cfg, fake=True),
            example_obs=configs.example_obs(cfg),
            num_actors=cfg.num_actors,
            learner_config=configs.make_learner_config(cfg),
            optimizer=optax.sgd(1e-3),
            total_steps=4,
            logger=logger,
            log_every=2,
            envs_per_actor=cfg.envs_per_actor,
            actor_mode="process",
            pool_mode="async",
            telemetry_interval=1,
            stall_timeout=120.0,
        )
    finally:
        logger.close()
    assert result.learner.num_steps == 4
    keys = _jsonl_keys(path)
    for ns in ("pool", "actor", "queue", "learner"):
        assert any(
            k.startswith(f"telemetry/{ns}/") for k in keys
        ), f"missing telemetry/{ns}/* in {sorted(keys)}"
    # The load-bearing series from the ISSUE are all present.
    for key in (
        "telemetry/pool/worker_step_ms_p95",
        "telemetry/pool/restarts",
        "telemetry/pool/lane_occupancy",
        "telemetry/actor/wave_latency_ms_p95",
        "telemetry/actor/ready_fraction_achieved",
        "telemetry/queue/depth",
        "telemetry/queue/enqueue_block_ms_p95",
        "telemetry/learner/train_step_ms",
        "telemetry/learner/param_lag_frames",
        "telemetry/watchdog/stall",
    ):
        assert key in keys, f"{key} missing from {sorted(keys)}"


def test_telemetry_interval_throttles_merge(tmp_path):
    """telemetry_interval=0 disables the snapshot merge entirely."""
    import optax

    from torched_impala_tpu import configs
    from torched_impala_tpu.runtime.loop import train
    from torched_impala_tpu.utils.loggers import JSONLinesLogger

    cfg = configs.CARTPOLE
    path = str(tmp_path / "quiet.jsonl")
    logger = JSONLinesLogger(path)
    try:
        train(
            agent=configs.make_agent(cfg),
            env_factory=configs.make_env_factory(cfg, fake=True),
            example_obs=configs.example_obs(cfg),
            num_actors=1,
            learner_config=configs.make_learner_config(cfg),
            optimizer=optax.sgd(1e-3),
            total_steps=2,
            logger=logger,
            log_every=1,
            telemetry_interval=0,
        )
    finally:
        logger.close()
    keys = _jsonl_keys(path)
    assert keys and not any(k.startswith("telemetry/") for k in keys)


def test_cli_profile_steps_writes_trace(tmp_path):
    """Acceptance: --profile-steps produces a non-empty trace directory
    on CPU."""
    from torched_impala_tpu.run import main

    trace_dir = str(tmp_path / "traces")
    rc = main(
        [
            "--config", "cartpole",
            "--fake-envs",
            "--total-steps", "4",
            "--log-every", "2",
            "--logger", "null",
            "--num-actors", "1",
            "--profile-steps", "1:3",
            "--trace-dir", trace_dir,
        ]
    )
    assert rc == 0
    window = os.path.join(trace_dir, "steps_1_3")
    files = [
        os.path.join(dp, f)
        for dp, _, fs in os.walk(window)
        for f in fs
    ]
    assert files, f"no trace files under {window}"


def test_cli_rejects_bad_profile_steps():
    from torched_impala_tpu.run import main

    with pytest.raises(SystemExit, match="profile-steps"):
        main(
            [
                "--config", "cartpole", "--fake-envs",
                "--logger", "null", "--profile-steps", "9:2",
            ]
        )
