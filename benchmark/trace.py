"""Reduction of a profiler trace to device busy time, idle gaps and the
time of named operations. Kept with the benchmark: every PR reads the same
number the same way.

The arithmetic works on plain lists of `Event(name, start, dur)` in
seconds, so that it can be checked on a list written by hand. `load` fills
those lists from the `.xplane.pb` that `jax.profiler` writes, read with
`jax.profiler.ProfileData` alone.

What a TPU trace holds (looked at by hand, PERF.md section 5): one plane per
chip named `/device:TPU:<n>`; on it the line `XLA Modules` carries one
event per executed program, `XLA Ops` one per operation of it (a `while`
spans the operations of its body, so times by name are self times; an
operation's name is its whole HLO line). The host plane would carry
`TraceAnnotation` spans on the same clock, but the host tracer is off: with
it on, the host-side transposition of the DMLab observation batch emits 2.4
million events a batch and a step takes 3.7 s instead of 96 ms.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable, NamedTuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


class Trace(NamedTuple):
    ops: dict  # device plane name -> [Event] of operations
    modules: dict  # device plane name -> [Event] of whole programs


def newest_capture(trace_dir: str) -> str:
    """The newest `.xplane.pb` under `trace_dir`."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(trace_dir: str) -> Trace:
    """Read the newest capture under `trace_dir`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(newest_capture(trace_dir))
    ops, modules = {}, {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    into = ops if line.name == OPS_LINE else modules
                    into[plane.name] = [_event(e) for e in line.events]
    return Trace(ops, modules)


def _event(e) -> Event:
    return Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)


def short_name(name: str, width: int = 96) -> str:
    """An operation's event name is its whole HLO line: keep the
    instruction's name, the operation and the result's type (no layout)."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:width]
    end = 0
    if rest.startswith("("):  # a tuple result: up to its closing bracket
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
    end = rest.find(" ", end)
    result, call = rest[:end], rest[end + 1 :]
    result = re.sub(r"\{[^}]*\}", "", result)
    return f"{head} {call.split('(')[0]} {result}"[:width]


# ---- interval arithmetic ------------------------------------------------


def merged(intervals: Iterable) -> list:
    """Sorted, disjoint (start, end) pairs covering the same time."""
    out: list = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def clipped(events: Iterable, t0: float, t1: float) -> list:
    """(start, end) of each event, cut to the window; empty ones dropped."""
    return [
        (max(e.start, t0), min(e.end, t1))
        for e in events
        if e.end > t0 and e.start < t1
    ]


def busy_seconds(events: Iterable, t0: float, t1: float) -> float:
    """Union of the intervals in which some operation runs, in [t0, t1]."""
    return sum(b - a for a, b in merged(clipped(events, t0, t1)))


def matches(name: str, patterns: Iterable) -> bool:
    return any(re.search(p, name) for p in patterns)


def matching(events: Iterable, patterns: Iterable) -> list:
    patterns = list(patterns)
    return [e for e in events if matches(e.name, patterns)]


def enclosing(events: Iterable, inner: Iterable, patterns: Iterable) -> list:
    """For each event of `inner`, the shortest event matching `patterns`
    that spans it (a kernel's own `while` loop, not an outer one); each such
    event once."""
    outer = sorted(matching(events, patterns), key=lambda e: e.dur)
    found = {}
    for k in inner:
        for o in outer:
            if o.start <= k.start and k.end <= o.end:
                found[(o.name, o.start)] = o
                break
    return list(found.values())


def exposed_seconds(
    events: Iterable, patterns: Iterable, t0: float, t1: float
) -> float:
    """Time in [t0, t1] during which an operation matching `patterns` runs
    and no other operation does: what a collective adds to the step when
    compute does not hide it."""
    events, patterns = list(events), list(patterns)
    mine = merged(clipped(matching(events, patterns), t0, t1))
    others = merged(
        clipped((e for e in events if not matches(e.name, patterns)), t0, t1)
    )
    covered = 0.0
    j = 0
    for a, b in mine:
        while j < len(others) and others[j][1] <= a:
            j += 1
        k = j
        while k < len(others) and others[k][0] < b:
            covered += min(b, others[k][1]) - max(a, others[k][0])
            k += 1
    return sum(b - a for a, b in mine) - covered


def self_seconds(events: Iterable) -> dict:
    """Seconds by operation name, each event less the events nested in it
    (a `while` does not count its body twice)."""
    out: dict = {}
    stack: list = []  # [event, seconds of children]

    def close():
        ev, inner = stack.pop()
        out[ev.name] = out.get(ev.name, 0.0) + max(ev.dur - inner, 0.0)
        if stack:
            stack[-1][1] += ev.dur

    for ev in sorted(events, key=lambda e: (e.start, -e.dur)):
        while stack and stack[-1][0].end <= ev.start:
            close()
        if stack and ev.end > stack[-1][0].end:
            # Overlaps without being nested (an asynchronous collective
            # beside compute): a sibling, counted whole.
            out[ev.name] = out.get(ev.name, 0.0) + ev.dur
            continue
        stack.append([ev, 0.0])
    while stack:
        close()
    return out


def step_window(modules: Iterable):
    """The traced window, cut to whole periods of the program that runs
    most: from the start of its first execution in the trace to the start
    of its last. Returns (t0, t1, periods) or None with fewer than two
    executions. Both ends are on the device's clock, so the edges of the
    capture (the profiler starting and stopping) are left out."""
    by_name: dict = {}
    for e in modules:
        by_name.setdefault(e.name, []).append(e)
    if not by_name:
        return None
    runs = max(by_name.values(), key=lambda es: sum(e.dur for e in es))
    if len(runs) < 2:
        return None
    starts = sorted(e.start for e in runs)
    return starts[0], starts[-1], len(starts) - 1


def program_name(module_event_name: str) -> str:
    """`jit__train_step_impl(7363486381379749291)` -> `jit__train_step_impl`."""
    return module_event_name.split("(")[0]


def idle_gaps(events: Iterable, modules: Iterable, t0: float, t1: float) -> list:
    """Idle seconds of one device in [t0, t1] by where they fall among the
    programs the host launched: `inside <program>` (between two of its
    operations) or `after <program>, before <program>` (the device waits
    for the host's next launch). Sorted, largest first: [[label, seconds]].

    The host's own spans are not in the capture: the host tracer has to
    stay off (PERF.md section 5), so the gaps are named from the device's
    side; the program's timers say what the host was doing meanwhile."""
    busy = merged(clipped(events, t0, t1))
    edges = [t0] + [x for ab in busy for x in ab] + [t1]
    gaps = [
        (edges[i], edges[i + 1])
        for i in range(0, len(edges), 2)
        if edges[i + 1] > edges[i]
    ]
    runs = sorted(modules, key=lambda e: e.start)
    out: dict = {}
    j = 0  # first program that ends after the gap's start
    for a, b in gaps:
        while j < len(runs) and runs[j].end <= a:
            j += 1
        if j < len(runs) and runs[j].start <= a and b <= runs[j].end:
            label = f"inside {program_name(runs[j].name)}"
        else:
            before = program_name(runs[j - 1].name) if j > 0 else "(start)"
            after = program_name(runs[j].name) if j < len(runs) else "(end)"
            label = f"after {before}, before {after}"
        out[label] = out.get(label, 0.0) + (b - a)
    return sorted(([k, v] for k, v in out.items()), key=lambda kv: -kv[1])
