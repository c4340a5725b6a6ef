"""On-demand `jax.profiler` capture for live runs.

Two entry points, both writing standard XPlane traces under a `traces/`
directory (open with TensorBoard's profile plugin or Perfetto):

- `--profile-steps A:B` (run.py): a `StepWindowProfiler` hooked into the
  learner's post-step callback opens the trace once learner step A has
  completed and closes it after step B — a bounded window around exactly
  the steps you care about, instead of a whole-run trace that buries the
  steady state under compile time.
- SIGUSR1: `ProfilerCapture.install_sigusr1()` toggles capture on a LIVE
  run (`kill -USR1 <pid>` starts a trace, a second one stops and writes
  it) — the "why is it slow right now" affordance, no restart needed.

Each capture writes into a fresh `<trace_dir>/<tag>` subdirectory so
repeated captures never clobber each other. Beside the capture's
`.xplane.pb`, `stop()` leaves `host_spans.json`: the flight recorder's
records of the capture on the epoch clock, so that the program's own spans
(the learner's step phases, `learner/step_in_flight`) can be laid beside
the device's operations. The profiler's host tracer cannot supply them:
see `HOST_TRACER_LEVEL`. Capture state is guarded by a
lock: the signal handler, the learner thread, and test code may all
toggle; `jax.profiler.start_trace` is process-global, so exactly one
capture can be active at a time.
"""

from __future__ import annotations

import glob
import os
import signal
import sys
import threading
import time
import weakref
from typing import Optional, Tuple

from torched_impala_tpu.telemetry.registry import Registry, get_registry
from torched_impala_tpu.telemetry.tracing import (
    FlightRecorder,
    get_recorder,
)

HOST_SPANS_FILE = "host_spans.json"
CLOCK_NOTE = (
    "ts: microseconds since the Unix epoch (time.time_ns). The .xplane.pb "
    "beside this file counts start_ns from the profile_start_time of its "
    "'Task Environment' plane, itself epoch ns: subtract it to lay the two "
    "side by side."
)

# What a capture asks of the profiler's host side: nothing. Chip readings in
# the DMLab-30 cell's shapes (T=100, B=64; PERF.md, my chip runs, PR 24): the
# step period is 98.8 ms untraced and 99.7 ms inside a capture at level 0;
# at level 1 it is 1,818 ms (14 million host events in 2.5 s from the
# runtime threads that transpose the observation batch on its way to the
# device, a 467 MB capture that takes 64 s to write); at the default, 2, PR
# 23 read 3.7 s a step. So the host's side of a capture is the flight
# recorder's own spans, written beside it (`host_spans.json`).
HOST_TRACER_LEVEL = 0
PYTHON_TRACER_LEVEL = 0


def profile_options():
    """`jax.profiler.ProfileOptions` of every capture this module starts."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = PYTHON_TRACER_LEVEL
    options.host_tracer_level = HOST_TRACER_LEVEL
    return options


# ---- compiles, seen ------------------------------------------------------

# jax.monitoring duration events -> the timer each feeds. The backend-compile
# event brackets `compile_or_get_cached`, so a hit in the persistent cache
# counts too (with the seconds the retrieval took).
COMPILE_EVENT_TIMERS = {
    "/jax/core/compile/backend_compile_duration": "jit/backend_compile",
    "/jax/core/compile/jaxpr_trace_duration": "jit/trace",
}
_compile_watchers: "weakref.WeakSet[Registry]" = weakref.WeakSet()
_compile_lock = threading.Lock()
_compile_listening = False


def _on_jax_duration(event: str, duration_secs: float, **_kwargs) -> None:
    name = COMPILE_EVENT_TIMERS.get(event)
    if name is None:
        return
    for reg in tuple(_compile_watchers):
        reg.timer(name).observe(duration_secs)


def watch_compiles(registry: Registry) -> None:
    """Feed every jit trace and backend compile of this process into
    `registry`'s `jit/trace` and `jit/backend_compile` timers, so that a
    compile inside a measured window shows there with its seconds. One
    `jax.monitoring` listener per process, however many registries ask;
    a registry is dropped when nothing else holds it."""
    global _compile_listening
    import jax

    with _compile_lock:
        for name in COMPILE_EVENT_TIMERS.values():
            registry.timer(name)  # the series exists before the first compile
        _compile_watchers.add(registry)
        if not _compile_listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_jax_duration
            )
            _compile_listening = True


def parse_profile_steps(spec: str) -> Tuple[int, int]:
    """Parse `--profile-steps A:B` into (start, stop) learner steps.

    The trace opens once step A has completed and closes after step B, so
    it contains steps A+1..B (B > A >= 0). `"0:3"` traces the first three
    steps of the run (window opens before any step when the run starts at
    step 0 — resumed runs count from their restored step)."""
    try:
        a_str, b_str = spec.split(":")
        a, b = int(a_str), int(b_str)
    except ValueError as e:
        raise ValueError(
            f"--profile-steps expects A:B (two integers), got {spec!r}"
        ) from e
    if a < 0 or b <= a:
        raise ValueError(
            f"--profile-steps needs 0 <= A < B, got {a}:{b}"
        )
    return a, b


class ProfilerCapture:
    """Start/stop `jax.profiler` traces under `trace_dir`, one
    subdirectory per capture."""

    def __init__(
        self,
        trace_dir: str = "traces",
        registry: Optional[Registry] = None,
        recorder: Optional[FlightRecorder] = None,
    ):
        self.trace_dir = trace_dir
        self._recorder = recorder if recorder is not None else get_recorder()
        self._lock = threading.Lock()
        self._active_dir: Optional[str] = None
        self._since_ns = 0
        self._since_records = 0
        self._captures = 0
        reg = registry if registry is not None else get_registry()
        self._capture_counter = reg.counter("profiler/captures")
        self._active_gauge = reg.gauge(
            "profiler/active", fn=lambda: 1.0 if self.active else 0.0
        )

    @property
    def active(self) -> bool:
        return self._active_dir is not None

    def start(self, tag: Optional[str] = None) -> Optional[str]:
        """Begin a capture; returns its directory (None if one was
        already running — jax allows a single global trace)."""
        import jax

        with self._lock:
            if self._active_dir is not None:
                return None
            self._captures += 1
            tag = tag or f"capture_{self._captures:03d}_{int(time.time())}"
            path = os.path.join(self.trace_dir, tag)
            os.makedirs(path, exist_ok=True)
            self._since_ns, _ = self._recorder.sync_clock()
            self._since_records = self._recorder.total_recorded
            jax.profiler.start_trace(
                path, profiler_options=profile_options()
            )
            self._active_dir = path
            self._capture_counter.inc()
            print(
                f"[profiler] trace started -> {path}",
                file=sys.stderr,
                flush=True,
            )
            return path

    def stop(self) -> Optional[str]:
        """End the active capture; returns its directory (None if no
        capture was running)."""
        import jax

        with self._lock:
            if self._active_dir is None:
                return None
            path, self._active_dir = self._active_dir, None
            try:
                jax.profiler.stop_trace()
                self._write_host_spans(path)
            finally:
                print(
                    f"[profiler] trace written -> {path}",
                    file=sys.stderr,
                    flush=True,
                )
            return path

    def _write_host_spans(self, path: str) -> None:
        """The recorder's records of this capture, as a Chrome trace on the
        epoch clock, beside the capture's `.xplane.pb` (in `path` itself
        when the profiler wrote none). `dropped` in its metadata: records
        written during the capture that the ring had overwritten by its
        end, the oldest ones (`perf/report.py` marks the stretch they
        covered as having no records)."""
        written = glob.glob(
            os.path.join(path, "**", "*.xplane.pb"), recursive=True
        )
        where = (
            os.path.dirname(max(written, key=os.path.getmtime))
            if written
            else path
        )
        mono_ns, wall_ns = self._recorder.clock_pair
        written = self._recorder.total_recorded - self._since_records
        self._recorder.export(
            os.path.join(where, HOST_SPANS_FILE),
            since_ns=self._since_ns,
            epoch=True,
            metadata={
                "clock": CLOCK_NOTE,
                "clock_pair_ns": {"monotonic": mono_ns, "epoch": wall_ns},
                "host_tracer_level": HOST_TRACER_LEVEL,
                "dropped": max(0, written - self._recorder.capacity),
            },
        )

    def toggle(self) -> None:
        if self.active:
            self.stop()
        else:
            self.start()

    def install_sigusr1(self) -> bool:
        """SIGUSR1 toggles capture on a live run. Main-thread only (signal
        module restriction); returns False when it cannot install (not the
        main thread, or no SIGUSR1 on this platform) instead of raising —
        the CLI treats the handler as best-effort."""
        if not hasattr(signal, "SIGUSR1"):
            return False
        if threading.current_thread() is not threading.main_thread():
            return False

        def _handler(signum, frame):
            # start_trace/stop_trace do I/O; a signal handler interrupting
            # arbitrary bytecode must keep its own work minimal and
            # exception-free.
            try:
                self.toggle()
            except Exception as e:  # noqa: BLE001 — never kill the run
                print(
                    f"[profiler] SIGUSR1 toggle failed: {e!r}",
                    file=sys.stderr,
                    flush=True,
                )

        signal.signal(signal.SIGUSR1, _handler)
        return True


class StepWindowProfiler:
    """Drive a `ProfilerCapture` from learner-step callbacks.

    `on_step(num_steps)` is called after every learner step (and once at
    startup with the initial step count): the window opens when
    `num_steps >= start_step` and closes once `num_steps >= stop_step`.
    With fused dispatch (steps_per_dispatch=K) steps advance K at a time;
    the window still opens/closes at the first callback past each edge.
    """

    def __init__(
        self, capture: ProfilerCapture, start_step: int, stop_step: int
    ):
        if not 0 <= start_step < stop_step:
            raise ValueError(
                f"need 0 <= start_step < stop_step, got "
                f"{start_step}:{stop_step}"
            )
        self._capture = capture
        self.start_step = start_step
        self.stop_step = stop_step
        self._opened = False
        self._closed = False

    def on_step(self, num_steps: int) -> None:
        if self._closed:
            return
        if not self._opened and num_steps >= self.start_step:
            self._opened = True
            self._capture.start(
                tag=f"steps_{self.start_step}_{self.stop_step}"
            )
        if self._opened and num_steps >= self.stop_step:
            self._closed = True
            self._capture.stop()

    def close(self) -> None:
        """Flush a window still open at run end (budget shorter than
        stop_step) so the partial trace is written, not lost."""
        if self._opened and not self._closed:
            self._closed = True
            self._capture.stop()
