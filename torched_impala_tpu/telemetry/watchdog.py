"""Stall watchdog: turn a silently wedged run into a loud, diagnosable one.

A distributed actor-learner pipeline has many ways to deadlock quietly —
a full trajectory queue with a dead consumer, an env worker stuck in a
native emulator call, a device hanging a `device_put` — and
the symptom is always the same: the process sits at 0% progress forever.
The watchdog closes that gap: pipeline stages record liveness via
`Registry.heartbeat(component)` (the learner after every SGD step, the
actor after every inference wave), and when NO component heartbeats
within `deadline_s`, the watchdog

1. dumps every Python thread's stack to stderr (the wedged frame is
   almost always visible there),
2. dumps the flight recorder's tail (telemetry/tracing.py) — the last
   few dozen trace events, lineage IDs included, so the dump names
   WHICH unroll/batch the pipeline wedged on, not just where,
3. dumps the latest registry snapshot (which stage's counters froze tells
   you WHERE the pipeline wedged),
4. increments `telemetry/watchdog/stall` and calls `on_stall(event)` so
   the stall reaches the metrics log as an event, not just stderr.

It fires ONCE per stall and re-arms when progress resumes, so a long
wedge doesn't spam a dump per poll interval.
"""

from __future__ import annotations

import sys
import threading
import time
import traceback
from typing import Callable, Dict, Optional

from torched_impala_tpu.telemetry.registry import PREFIX, Registry
from torched_impala_tpu.telemetry.tracing import (
    FlightRecorder,
    get_recorder,
)


def dump_thread_stacks(file=None) -> None:
    """Write every live Python thread's current stack to `file`
    (default stderr) — the portable, in-process subset of what
    `faulthandler` gives you, with thread names attached."""
    file = file or sys.stderr
    names = {t.ident: t.name for t in threading.enumerate()}
    frames = sys._current_frames()
    print(
        f"==== thread stacks ({len(frames)} threads) ====",
        file=file,
    )
    for ident, frame in frames.items():
        name = names.get(ident, "?")
        print(f"-- thread {name} (ident {ident}) --", file=file)
        for line in traceback.format_stack(frame):
            file.write(line)
    print("==== end thread stacks ====", file=file, flush=True)


class StallWatchdog:
    """Background thread that watches `registry` heartbeats.

    `deadline_s`: no heartbeat from ANY component for this long => stall.
    Before the first heartbeat the clock runs from `start()` (a pipeline
    that never comes up at all is also a stall).
    `on_stall(event)`: optional callback receiving a small dict
    (`{"telemetry/watchdog/stall": n, "telemetry/watchdog/stalled_for_s":
    age}`) — the run loop forwards it to the metrics logger.
    """

    def __init__(
        self,
        registry: Registry,
        deadline_s: float = 300.0,
        on_stall: Optional[Callable[[Dict[str, float]], None]] = None,
        poll_s: Optional[float] = None,
        stream=None,
        recorder: Optional[FlightRecorder] = None,
        tail_records: int = 48,
        aggregator=None,
        alert_engine=None,
    ):
        if deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        self._registry = registry
        # Observability plane hooks (telemetry/aggregate.py/alerts.py):
        # with an aggregator the stall dump shows the CROSS-PROCESS
        # snapshot — a wedged env-pool worker's own frozen counters are
        # visible in the dump that fires about it — and with an alert
        # engine it names the currently-firing alerts.
        self._aggregator = aggregator
        self._alert_engine = alert_engine
        # The flight recorder whose tail rides the stall dump (None =
        # the process-global one every pipeline stage records into).
        self._recorder = recorder if recorder is not None else get_recorder()
        self._tail_records = tail_records
        self._deadline_s = deadline_s
        self._on_stall = on_stall
        self._poll_s = (
            poll_s if poll_s is not None else max(0.05, deadline_s / 10.0)
        )
        self._stream = stream  # None = sys.stderr at dump time
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Written once in start() BEFORE the watchdog thread exists
        # (Thread.start is the happens-before edge); read-only after.
        self._t_start = 0.0  # lint: guarded-by(gil)
        self._stall_active = False
        self._stalls = registry.counter("watchdog/stall")
        self.fired = threading.Event()  # latched on first stall (tests)

    # -- lifecycle -------------------------------------------------------

    def start(self) -> "StallWatchdog":
        if self._thread is None:
            self._t_start = time.monotonic()
            self._thread = threading.Thread(
                target=self._run, name="stall-watchdog", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self._poll_s * 4 + 1.0)
            self._thread = None

    def __enter__(self) -> "StallWatchdog":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- the watch loop --------------------------------------------------

    def _age(self) -> float:
        last = self._registry.last_heartbeat()
        if last is None:
            last = self._t_start
        return time.monotonic() - last

    def _run(self) -> None:
        while not self._stop.wait(self._poll_s):
            age = self._age()
            if age <= self._deadline_s:
                self._stall_active = False  # progress resumed: re-arm
                continue
            if self._stall_active:
                continue  # one dump per stall
            self._stall_active = True
            self._fire(age)

    def _fire(self, age: float) -> None:
        self._stalls.inc()
        stream = self._stream or sys.stderr
        beats = self._registry.heartbeats()
        now = time.monotonic()
        print(
            f"[stall-watchdog] STALL: no pipeline heartbeat for "
            f"{age:.1f}s (deadline {self._deadline_s:.1f}s); "
            f"last beats: "
            + (
                ", ".join(
                    f"{k}={now - t:.1f}s ago"
                    for k, t in sorted(beats.items())
                )
                or "none ever"
            ),
            file=stream,
            flush=True,
        )
        dump_thread_stacks(stream)
        # The forensic timeline: which unrolls/batches (lineage IDs) were
        # in flight when the pipeline went quiet.
        print(
            f"[stall-watchdog] flight recorder tail "
            f"(last {self._tail_records} of "
            f"{self._recorder.total_recorded} events):",
            file=stream,
        )
        stream.write(self._recorder.format_tail(self._tail_records))
        stream.flush()
        snap = self._registry.snapshot()
        label = "registry snapshot"
        if self._aggregator is not None:
            try:
                snap = self._aggregator.aggregated_snapshot(snap)
                label = "aggregated snapshot (all processes)"
            except Exception:
                pass  # fall back to the local view
        print(
            f"[stall-watchdog] {label}: "
            + " ".join(f"{k}={v}" for k, v in sorted(snap.items())),
            file=stream,
            flush=True,
        )
        if self._alert_engine is not None:
            try:
                print(
                    "[stall-watchdog] "
                    + self._alert_engine.format_status(),
                    file=stream,
                    flush=True,
                )
            except Exception:
                pass
        self.fired.set()
        if self._on_stall is not None:
            try:
                self._on_stall(
                    {
                        f"{PREFIX}/watchdog/stall": self._stalls.value,
                        f"{PREFIX}/watchdog/stalled_for_s": age,
                    }
                )
            except Exception:
                # The watchdog must never die on a broken logger — the
                # stderr dump above already happened.
                traceback.print_exc(file=stream)
