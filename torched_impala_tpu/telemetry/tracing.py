"""Flight recorder: an always-on, fixed-size ring buffer of structured
trace events, plus cross-stage batch lineage.

The registry (registry.py) answers "which stage is slow ON AVERAGE";
this module answers "what happened to THIS batch": every unroll minted
by a `VectorActor` carries a lineage ID (`a<actor>u<seq>`, stamped with
the actor's param version at act time), which rides the env pool's
submit→ack edges, the trajectory queue (`Trajectory.lineage_id`) or the
trajectory ring (`commit(lineage_id=...)`), and the learner's
host-stack / device-put / train-step / publish spans — so a learner
step can name exactly which unrolls it consumed and at what exact
policy-version lag (per-batch, not the EWMA gauge). TorchBeast's
platform lesson (arxiv 1910.03552 §3) is that actor-learner debugging
lives or dies on seeing where ONE unroll stalls between processes;
V-trace's correctness story (arxiv 1802.01561) makes the per-batch
staleness distribution a first-class observable, not an average.

Design constraints, in order:

- ALWAYS ON at negligible cost (on the chip, all telemetry on against
  off reads about 0.4 ms a DMLab step, PERF.md section 6, PR 24): one
  record is a tuple build + a short
  lock for the ring index + a slot store — no allocation beyond the
  record itself, no I/O, no formatting. A disabled recorder
  short-circuits to one attribute load + branch.
- FIXED memory: `capacity` records (power of two), oldest overwritten.
  A wedged run's recorder tail is a forensic timeline of the last few
  thousand events — the `StallWatchdog` dumps it next to the thread
  stacks.
- STANDARD output: `export()` writes Chrome-trace JSON (open in
  Perfetto / chrome://tracing / TensorBoard's trace viewer). Each
  pipeline component becomes a trace "process" row; threads nest under
  it; lineage dicts ride the event `args`.

Event names follow the SAME `<component>/<name>` slug grammar as
metric names (`tools/check_metric_names.py` lints both; the registry's
NAME_RE is the single source of truth). Phases mirror Chrome's:
`begin`/`end` ("B"/"E") bracket a named region, `instant` ("i") marks
a point, `complete` ("X") is a pre-timed span — the `span()` context
manager records ONE complete event at exit (half the records of a B/E
pair, and immune to torn pairs at ring wraparound).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from torched_impala_tpu.telemetry.registry import NAME_RE

# Chrome trace event phases (the subset the recorder emits).
PH_BEGIN = "B"
PH_END = "E"
PH_INSTANT = "i"
PH_COMPLETE = "X"

DEFAULT_CAPACITY = 1 << 14  # ~16k records, ~2 MB — minutes of pipeline


def _check_trace_name(name: str, _seen=set()) -> None:  # noqa: B006
    """Validate `<component>/<name>` once per distinct name (the cache
    keeps the hot path at one set lookup)."""
    if name in _seen:
        return
    if not NAME_RE.match(name):
        raise ValueError(
            f"trace event name {name!r} must match <component>/<name> "
            f"({NAME_RE.pattern})"
        )
    _seen.add(name)


class _TraceSpan:
    """`with recorder.span("learner/train_step", {...}):` — one complete
    ("X") record at exit. Allocate-per-with by design (the only per-span
    allocation besides the record tuple)."""

    __slots__ = ("_rec", "_name", "_lineage", "_t0")

    def __init__(self, rec: "FlightRecorder", name: str, lineage):
        self._rec = rec
        self._name = name
        self._lineage = lineage
        self._t0 = 0

    def __enter__(self) -> "_TraceSpan":
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> None:
        self._rec.complete(
            self._name,
            self._t0,
            time.monotonic_ns() - self._t0,
            self._lineage,
        )


class FlightRecorder:
    """Fixed-size ring of `(ts_ns, dur_ns, phase, name, tid, lineage)`
    records. Thread-safe; writers take one short lock per record (the
    ring index + slot store), readers copy under the same lock."""

    def __init__(
        self, capacity: int = DEFAULT_CAPACITY, process_label: str = ""
    ):
        if capacity < 2:
            raise ValueError(f"capacity must be >= 2, got {capacity}")
        # (pid, process label) stamp: worker-process recorders carry
        # their pool label (proc<h>w<w>) so the cross-process trace
        # merge (telemetry/aggregate.py) can name per-process rows; the
        # parent's global recorder keeps the default empty label.
        self.process_label = process_label
        self.pid = os.getpid()
        # Round up to a power of two so the ring index is one AND.
        cap = 1
        while cap < capacity:
            cap <<= 1
        self.capacity = cap
        self._mask = cap - 1
        self._buf: List[Optional[tuple]] = [None] * cap
        self._n = 0  # total records ever written
        self._lock = threading.Lock()
        self.enabled = True
        # tid -> thread name, filled lazily on first record per thread
        # (export emits them as Chrome thread_name metadata).
        self._thread_names: Dict[int, str] = {}
        self.clock_pair = self.sync_clock()

    def sync_clock(self) -> Tuple[int, int]:
        """Read the two clocks together: records are stamped
        `time.monotonic_ns()`, a profiler capture counts from the epoch,
        and this pair relates them (`epoch=True` in the exports).
        `ProfilerCapture.start` reads it again, so that a capture does
        not carry the drift since the recorder was made."""
        self.clock_pair = (time.monotonic_ns(), time.time_ns())
        return self.clock_pair

    # -- recording (the hot path) -----------------------------------------

    def _record(
        self,
        phase: str,
        name: str,
        lineage: Optional[dict],
        ts_ns: int,
        dur_ns: int = 0,
    ) -> None:
        if not self.enabled:
            return
        _check_trace_name(name)
        tid = threading.get_ident()
        if tid not in self._thread_names:
            self._thread_names[tid] = threading.current_thread().name
        rec = (ts_ns, dur_ns, phase, name, tid, lineage)
        with self._lock:
            self._buf[self._n & self._mask] = rec
            self._n += 1

    def instant(self, name: str, lineage: Optional[dict] = None) -> None:
        """A point event (e.g. `queue/enqueue` with the unroll's lid)."""
        self._record(PH_INSTANT, name, lineage, time.monotonic_ns())

    def begin(self, name: str, lineage: Optional[dict] = None) -> None:
        self._record(PH_BEGIN, name, lineage, time.monotonic_ns())

    def end(self, name: str, lineage: Optional[dict] = None) -> None:
        self._record(PH_END, name, lineage, time.monotonic_ns())

    def complete(
        self,
        name: str,
        t0_ns: int,
        dur_ns: int,
        lineage: Optional[dict] = None,
    ) -> None:
        """A pre-timed span (phase "X"): the caller measured
        `t0_ns`/`dur_ns` itself (`time.monotonic_ns()` clock — the same
        clock `time.monotonic()` reads in seconds)."""
        self._record(PH_COMPLETE, name, lineage, t0_ns, dur_ns)

    def span(
        self, name: str, lineage: Optional[dict] = None
    ) -> _TraceSpan:
        return _TraceSpan(self, name, lineage)

    # -- reading -----------------------------------------------------------

    def __len__(self) -> int:
        return min(self._n, self.capacity)

    @property
    def total_recorded(self) -> int:
        """Records ever written (>= len() once the ring has wrapped)."""
        return self._n

    def tail(self, n: Optional[int] = None) -> List[tuple]:
        """The last `n` records (default: everything retained), oldest
        first. Safe against concurrent writers."""
        with self._lock:
            count = min(self._n, self.capacity)
            if n is not None:
                count = min(n, count)
            start = self._n - count
            return [
                self._buf[i & self._mask]
                for i in range(start, self._n)
            ]

    def clear(self) -> None:
        with self._lock:
            self._n = 0
            self._buf = [None] * self.capacity

    # -- export ------------------------------------------------------------

    def to_chrome_events(
        self,
        records: Optional[List[tuple]] = None,
        *,
        since_ns: Optional[int] = None,
        epoch: bool = False,
    ) -> List[dict]:
        """Chrome-trace event dicts: components map to trace 'processes'
        (one row per pipeline stage in Perfetto), threads nest under
        them, lineage rides `args`. `since_ns` (a `time.monotonic_ns()`
        reading) keeps only what ended at or after it; `epoch` writes
        `ts` on the epoch clock (`clock_pair`) instead of the monotonic
        one."""
        records = self.tail() if records is None else records
        mono_ns, wall_ns = self.clock_pair
        shift_ns = wall_ns - mono_ns if epoch else 0
        pids: Dict[str, int] = {}
        events: List[dict] = []
        thread_names = dict(self._thread_names)
        seen_tids = set()
        for ts_ns, dur_ns, phase, name, tid, lineage in records:
            if since_ns is not None and ts_ns + dur_ns < since_ns:
                continue
            ts_ns += shift_ns
            component = name.split("/", 1)[0]
            pid = pids.setdefault(component, len(pids) + 1)
            ev: Dict[str, Any] = {
                "name": name,
                "cat": component,
                "ph": phase,
                "ts": ts_ns / 1e3,  # Chrome trace wants microseconds
                "pid": pid,
                "tid": tid,
            }
            if phase == PH_COMPLETE:
                ev["dur"] = dur_ns / 1e3
            elif phase == PH_INSTANT:
                ev["s"] = "t"  # thread-scoped instant
            if lineage:
                ev["args"] = dict(lineage)
            events.append(ev)
            seen_tids.add((pid, tid))
        meta: List[dict] = []
        label = self.process_label
        for component, pid in pids.items():
            meta.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": 0,
                    "args": {
                        "name": (
                            f"{label}/{component}" if label else component
                        )
                    },
                }
            )
        for pid, tid in sorted(seen_tids):
            tname = thread_names.get(tid)
            if tname:
                meta.append(
                    {
                        "name": "thread_name",
                        "ph": "M",
                        "pid": pid,
                        "tid": tid,
                        "args": {"name": tname},
                    }
                )
        return meta + events

    def export(
        self,
        path: str,
        *,
        since_ns: Optional[int] = None,
        epoch: bool = False,
        metadata: Optional[dict] = None,
    ) -> int:
        """Write the retained records as Chrome-trace JSON (`{"traceEvents":
        [...]}`); returns the number of non-metadata events written. Load
        in Perfetto (ui.perfetto.dev → Open trace file) or
        chrome://tracing. `since_ns` and `epoch` as in `to_chrome_events`;
        `metadata` is written beside the events."""
        events = self.to_chrome_events(since_ns=since_ns, epoch=epoch)
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        if metadata:
            doc["metadata"] = metadata
        with open(path, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        return sum(1 for e in events if e["ph"] != "M")

    def format_tail(self, n: int = 48) -> str:
        """Human-readable tail for stall dumps: one line per record,
        timestamps relative to the newest record."""
        records = self.tail(n)
        if not records:
            return "  (flight recorder empty)\n"
        newest = records[-1][0]
        names = dict(self._thread_names)
        lines = []
        for ts_ns, dur_ns, phase, name, tid, lineage in records:
            rel_ms = (ts_ns - newest) / 1e6
            line = (
                f"  {rel_ms:+10.3f}ms {phase} {name}"
                f" [{names.get(tid, tid)}]"
            )
            if phase == PH_COMPLETE:
                line += f" dur={dur_ns / 1e6:.3f}ms"
            if lineage:
                line += f" {lineage}"
            lines.append(line)
        return "\n".join(lines) + "\n"


def validate_chrome_trace(obj: Any) -> List[str]:
    """Schema problems of a loaded Chrome-trace JSON object (empty =
    valid). The contract Perfetto/chrome://tracing require: a
    `traceEvents` list whose entries carry name/ph/ts/pid/tid, with
    `dur` on complete ("X") events. Doctor's trace self-check and the
    tests share this single validator."""
    problems: List[str] = []
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return ["top level must be a dict with a 'traceEvents' list"]
    events = obj["traceEvents"]
    if not isinstance(events, list):
        return ["'traceEvents' must be a list"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event {i} is not a dict")
            continue
        missing = [
            k for k in ("name", "ph", "pid", "tid") if k not in ev
        ]
        if ev.get("ph") != "M" and "ts" not in ev:
            missing.append("ts")
        if missing:
            problems.append(f"event {i} missing {missing}")
        if ev.get("ph") == PH_COMPLETE and "dur" not in ev:
            problems.append(f"event {i}: complete event without 'dur'")
    return problems


_GLOBAL = FlightRecorder()


def get_recorder() -> FlightRecorder:
    """The process-global flight recorder every pipeline stage records
    into (mirrors `registry.get_registry`)."""
    return _GLOBAL


def install_sigusr2(
    trace_dir: str = "traces",
    recorder: Optional[FlightRecorder] = None,
) -> bool:
    """SIGUSR2 on a live run dumps the flight recorder to
    `<trace_dir>/flight_<n>.json` — the "what was the pipeline doing
    just now" affordance, no restart needed (SIGUSR1 toggles the
    jax.profiler capture; see telemetry/profiling.py). Main-thread
    only; returns False when it cannot install, like
    `ProfilerCapture.install_sigusr1`."""
    if not hasattr(signal, "SIGUSR2"):
        return False
    if threading.current_thread() is not threading.main_thread():
        return False
    rec = recorder if recorder is not None else get_recorder()
    count = [0]

    def _handler(signum, frame):
        # Keep signal-context work minimal and exception-free: one
        # export, one stderr line.
        try:
            count[0] += 1
            path = os.path.join(trace_dir, f"flight_{count[0]:03d}.json")
            n = rec.export(path)
            print(
                f"[flight-recorder] {n} events -> {path}",
                file=sys.stderr,
                flush=True,
            )
        except Exception as e:  # noqa: BLE001 — never kill the run
            print(
                f"[flight-recorder] SIGUSR2 dump failed: {e!r}",
                file=sys.stderr,
                flush=True,
            )

    signal.signal(signal.SIGUSR2, _handler)
    return True


def mint_lineage_id(actor_id: int, seq: int) -> str:
    """The unroll lineage ID format — `a<actor>u<seq>` — minted once
    per unroll cycle in `VectorActor.unroll` and threaded through every
    stage that touches the unroll's bytes."""
    return f"a{actor_id}u{seq}"
