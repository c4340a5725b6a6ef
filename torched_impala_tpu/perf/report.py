"""Overlap analyzer: attribute inter-train_step gaps from the flight
recorder, emit a per-run roofline + pipeline-attribution report.

The flight recorder (telemetry/tracing.py) already holds the answer to
"where did the step time go" — host_stack / device_put / publish spans
from the feeder threads interleaved with the learner's step spans
— but nobody was doing the interval arithmetic. This module replays the
ring: the learner wall-clock is tiled into compute plus the gaps between
consecutive steps, and each gap is attributed to the highest-priority
pipeline activity that overlapped it:

    publish > bookkeeping > outside_step (the step loop's own thread)
    > h2d (device_put) > feed (host_stack/queue/ring/pool/actor)
    > compile > unattributed

Read from a profiler capture (`analyze_capture`, `python -m
torched_impala_tpu.perf.report <capture dir>`), compute is the device's
own: the union of its operations over whole periods of the step program,
and the device's idle time is put down to the step loop's and the
batcher's phases on one clock (`attribute_idle`). Where no capture exists
(`--perf-report`, SIGUSR2) compute is the host's bracket around the
device's work on a step, `learner/step_in_flight` (dispatch start to the
moment the wait for the device returns), where the step has one.
`learner/train_step` times only the DISPATCH of the step: on an
asynchronous backend it is a few ms of a 60 ms step, and tiling the wall
clock with it would book the device's compute as a `publish` gap. A step without the bracket (it owed the host
nothing, so the host never waited for it) falls back to its
`learner/train_step` span; `compute_source` says which was used.

The step loop keeps one step in flight: step k+1 is dispatched before
the wait for step k returns, so two brackets overlap by design. The
device runs the steps one after the other, so a step's compute is its own
bracket from where the bracket before it ended: the overlap is time step
k+1 sat queued behind step k and is step k's compute, counted once. The
computes then never add up to more than the wall clock.

Attribution is by interval union-and-subtract, so a feeder span that
overlaps a train_step (healthy pipelining) only charges the part that
falls inside a gap — exactly the non-overlapped remainder the MFU push
needs to shrink. Batches with `reuse_count > 1` lineage (IMPACT replay
re-deliveries; 1 = fresh first delivery) are split out from fresh ones
so replay's extra SGD steps don't read as free compute.

Output is JSON plus a human-readable text rendering, wired to
``--perf-report`` in run.py and a SIGUSR2 live dump (chained after the
flight-recorder export so one signal yields both artifacts).
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import signal
import sys
import threading
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from torched_impala_tpu.telemetry.tracing import (
    PH_COMPLETE,
    FlightRecorder,
    get_recorder,
)

SCHEMA_VERSION = 1

TRAIN_STEP = "learner/train_step"
STEP_IN_FLIGHT = "learner/step_in_flight"

# Gap categories in attribution priority order (first match wins a
# disputed interval): the step loop's own phases first, since that thread
# is what launches the next step. "compile" is matched by name substring
# so future explicit compile spans land without a code change here.
GAP_CATEGORIES = (
    "publish", "bookkeeping", "outside_step", "h2d", "feed", "compile",
)
_FEED_COMPONENTS = frozenset(
    {"actor", "pool", "queue", "ring", "env", "replay"}
)


def categorize_span(name: str) -> Optional[str]:
    """Gap category for one trace-span name (None = not attributable,
    e.g. the train_step spans themselves)."""
    if name in (TRAIN_STEP, STEP_IN_FLIGHT):
        return None
    component, _, sub = name.partition("/")
    if name in ("learner/publish", "learner/publish_copy"):
        # `learner/publish` also holds the wait for the device; where the
        # step has an in-flight bracket that wait lies inside compute, so
        # only the copy (`learner/publish_copy`) is ever left in a gap.
        return "publish"
    if name in ("learner/bookkeeping", "learner/outside_step"):
        return sub
    if name in ("learner/device_put", "learner/h2d"):
        # learner/h2d is the donated-ring staging span (zero-copy feed
        # path); learner/device_put the copying one. Same category: both
        # are host->device transfer time, and the union-and-subtract
        # below charges only the part NOT overlapped by a train_step.
        return "h2d"
    if "compile" in sub:
        return "compile"
    if component in _FEED_COMPONENTS or name == "learner/host_stack":
        return "feed"
    return None


# ---- interval arithmetic -------------------------------------------------


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge possibly-overlapping [start, end) intervals."""
    out: List[Tuple[int, int]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def subtract(
    uncovered: List[Tuple[int, int]], cover: List[Tuple[int, int]]
) -> Tuple[int, List[Tuple[int, int]]]:
    """Remove `cover` (a merged union) from `uncovered` (disjoint,
    sorted); returns (measure removed, remaining intervals)."""
    removed = 0
    remaining: List[Tuple[int, int]] = []
    for s, e in uncovered:
        pos = s
        for cs, ce in cover:
            if ce <= pos or cs >= e:
                continue
            lo, hi = max(cs, pos), min(ce, e)
            if lo > pos:
                remaining.append((pos, lo))
            removed += hi - lo
            pos = hi
            if pos >= e:
                break
        if pos < e:
            remaining.append((pos, e))
    return removed, remaining


def measure(intervals: List[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


# ---- analysis ------------------------------------------------------------


def analyze_records(
    records: List[tuple],
    *,
    roofline: Optional[Dict[str, Any]] = None,
    device: Optional["DeviceTimeline"] = None,
    clock_pair: Tuple[int, int] = (0, 0),
    records_dropped: int = 0,
) -> Dict[str, Any]:
    """Attribute the learner timeline of one flight-recorder record list
    (the `(ts_ns, dur_ns, phase, name, tid, args)` 6-tuples of
    `FlightRecorder.tail()`).

    Returns the report dict; `roofline` (e.g. `CostModel.snapshot()`
    or a single root's `CostModel.roofline()`) rides along verbatim so
    the report pairs "where the time went" with "what the FLOPs cost".

    With `device` (a profiler capture's device timeline, `load_capture`)
    compute is the device's own: the union of its operations over whole
    periods of the step program, and the report gains `idle`, the
    device's idle time put down to the host's phases (`attribute_idle`).
    The records are then laid on the device's clock by `clock_pair`
    (`(monotonic_ns, epoch_ns)` read together, as the recorder keeps
    it); `records_dropped` is how many records of the capture the ring
    had overwritten.
    """
    shift = clock_pair[1] - clock_pair[0] if device is not None else 0
    spans: List[Tuple[int, int, str, Optional[dict]]] = []
    span_counts: Dict[str, int] = {}
    for rec in records:
        if rec is None:
            continue
        ts_ns, dur_ns, phase, name, _tid, args = rec
        if phase != PH_COMPLETE:
            continue
        spans.append((ts_ns + shift, ts_ns + shift + dur_ns, name, args))
        span_counts[name] = span_counts.get(name, 0) + 1
    report: Dict[str, Any] = {
        "schema": SCHEMA_VERSION,
        "span_counts": dict(sorted(span_counts.items())),
    }
    if roofline:
        report["roofline"] = roofline
    window = step_window(device.modules) if device is not None else None
    if window is not None:
        t0, t1, periods = window
        busy = union(
            [(max(s, t0), min(e, t1)) for s, e in device.ops if e > t0 and s < t1]
        )
        _, idle = subtract([(t0, t1)], busy)
        steps = [
            (s, e, args)
            for s, e, name, args in spans
            if name == TRAIN_STEP and t0 <= s < t1
        ]
        report["learner"] = _learner_section(
            spans, steps, busy, idle, t1 - t0, "device", periods
        )
        report["idle"] = attribute_idle(
            spans, device.modules, window, busy, idle,
            records_dropped=records_dropped,
        )
        return report

    # One compute interval per step: its in-flight bracket, else its
    # dispatch; the lineage args ride the dispatch span either way.
    in_flight = {
        (args or {}).get("step"): (s, e)
        for s, e, name, args in spans
        if name == STEP_IN_FLIGHT
    }
    bracketed = 0
    steps = []
    for s, e, name, args in spans:
        if name != TRAIN_STEP:
            continue
        step = (args or {}).get("step")
        if step is not None and step in in_flight:
            s, e = in_flight[step]
            bracketed += 1
        steps.append((s, e, args))
    steps.sort(key=lambda step: step[:2])
    if len(steps) == 0:
        report["learner"] = {"steps": 0}
        return report

    # One step in flight: clip each bracket to where the one before ended.
    done = steps[0][0]
    for i, (s, e, args) in enumerate(steps):
        steps[i] = (min(max(s, done), e), e, args)
        done = max(done, e)
    # The gaps: wall-clock minus the union of the steps' compute.
    busy = union([(s, e) for s, e, _ in steps])
    _, gaps = subtract([(steps[0][0], done)], busy)
    compute_source = (
        "step_in_flight" if bracketed == len(steps)
        else "train_step" if bracketed == 0
        else "mixed"
    )
    report["learner"] = _learner_section(
        spans, steps, busy, gaps, done - steps[0][0], compute_source,
        len(steps),
    )
    return report


def _learner_section(
    spans, steps, busy, uncovered, wall_ns, compute_source, n_steps
) -> Dict[str, Any]:
    """The learner's wall clock tiled into compute (`busy`, merged) and
    its gaps (`uncovered`), each gap put down to a category; `steps` are
    the steps' `(start, end, args)` whose lineage splits fresh from
    replayed compute."""
    compute_ns = measure(busy)
    total_gap_ns = measure(uncovered)

    # Fresh vs replayed compute (IMPACT lineage rides the span args;
    # BatchLineage convention: reuse_count 1 = first delivery = fresh,
    # > 1 = a replay re-delivery of a retained slot). Under a device
    # timeline the device's compute is shared out by count of steps.
    fresh = {"steps": 0, "compute_ns": 0}
    replayed = {
        "steps": 0,
        "compute_ns": 0,
        "reuse_total": 0,
        "staleness_total": 0.0,
    }
    for s, e, args in steps:
        args = args or {}
        if int(args.get("reuse_max") or 0) > 1:
            replayed["steps"] += 1
            replayed["compute_ns"] += e - s
            replayed["reuse_total"] += int(args.get("reuse_max") or 0)
            replayed["staleness_total"] += float(
                args.get("staleness") or 0.0
            )
        else:
            fresh["steps"] += 1
            fresh["compute_ns"] += e - s
    if compute_source == "device":
        counted = max(fresh["steps"] + replayed["steps"], 1)
        for part in (fresh, replayed):
            part["compute_ns"] = compute_ns * part["steps"] // counted

    by_category = {
        cat: union(
            [
                (s, e)
                for s, e, name, _ in spans
                if categorize_span(name) == cat
            ]
        )
        for cat in GAP_CATEGORIES
    }
    gaps: Dict[str, int] = {}
    for cat in GAP_CATEGORIES:
        got, uncovered = subtract(uncovered, by_category[cat])
        gaps[cat] = got
    gaps["unattributed"] = measure(uncovered)

    # How much of the H2D transfer time hid under compute: the double-
    # buffered staging win. Overlapped H2D is charged to NOTHING (it is
    # not a gap), so this fraction is the report's proof that the feed
    # path actually pipelines — 1.0 means every transfer rode a step.
    h2d_total_ns = measure(by_category["h2d"])
    h2d_overlapped_ns, _ = subtract(list(by_category["h2d"]), busy)

    def _s(ns: int) -> float:
        return ns / 1e9

    return {
        "steps": n_steps,
        "wall_clock_s": _s(wall_ns),
        "compute_source": compute_source,
        "compute_s": _s(compute_ns),
        "compute_frac": compute_ns / wall_ns if wall_ns else 0.0,
        "gap_total_s": _s(total_gap_ns),
        "gaps_s": {k: _s(v) for k, v in gaps.items()},
        "gap_frac": {
            k: (v / wall_ns if wall_ns else 0.0) for k, v in gaps.items()
        },
        "h2d_total_s": _s(h2d_total_ns),
        "h2d_overlap_frac": (
            h2d_overlapped_ns / h2d_total_ns if h2d_total_ns else 0.0
        ),
        # compute + every attributed category + unattributed remainder:
        # the acceptance coverage (tiles the wall-clock by construction,
        # modulo clock skew between threads).
        "coverage_frac": (
            (compute_ns + sum(gaps.values())) / wall_ns if wall_ns else 0.0
        ),
        # how much of the wall-clock we can NAME (excludes the
        # unattributed remainder) — the honest attribution number.
        "attributed_frac": (
            (compute_ns + sum(gaps.values()) - gaps["unattributed"])
            / wall_ns
            if wall_ns
            else 0.0
        ),
        "fresh": {
            "steps": fresh["steps"],
            "compute_s": _s(fresh["compute_ns"]),
        },
        "replayed": {
            "steps": replayed["steps"],
            "compute_s": _s(replayed["compute_ns"]),
            "reuse_mean": (
                replayed["reuse_total"] / replayed["steps"]
                if replayed["steps"]
                else 0.0
            ),
            "staleness_mean": (
                replayed["staleness_total"] / replayed["steps"]
                if replayed["steps"]
                else 0.0
            ),
        },
    }


# ---- the device's idle, put down to the host's phases --------------------

# The step loop's phases (one thread; they tile its period) and the
# batcher's spans, first match wins where two of one side overlap.
LOOP_PHASES = (
    "batch_wait", "train_step", "bookkeeping", "publish_copy", "step_wait",
    "outside_step",
)
_BATCHER_SPANS = (
    ("learner/stack_slot_wait", "stack_slot_wait"),
    ("learner/stack_copy", "stack_copy"),
    ("learner/device_put", "device_put"),
    ("learner/h2d", "device_put"),
    # the trajectory ring's handoff, and captures older than the split
    ("learner/host_stack", "host_stack"),
)
UNATTRIBUTED = "unattributed"
# The batcher outside its spans, between its first and last: blocked
# putting a batch on the full device-batch queue, or collecting the next
# batch's unrolls.
BATCHER_QUEUE = "queue"
# A stretch of the capture whose records the ring overwrote.
NO_RECORDS = "no_records"


class DeviceTimeline(NamedTuple):
    """One device's side of a profiler capture, epoch ns: its operations
    as `(start, end)` (the `XLA Ops` line) and its programs as
    `(name, start, end)` (the `XLA Modules` line)."""

    ops: List[Tuple[int, int]]
    modules: List[Tuple[str, int, int]]


def program_name(name: str) -> str:
    """`jit__train_step_impl(7363486381379749291)` -> `jit__train_step_impl`."""
    return name.split("(")[0]


def step_window(modules) -> Optional[Tuple[int, int, int]]:
    """Whole periods of the program that runs longest in all: from the
    start of its first execution to the start of its last, so the
    capture's edges are left out. `(t0, t1, periods)`, or None with fewer
    than two executions."""
    runs: Dict[str, List[int]] = {}
    total: Dict[str, int] = {}
    for name, s, e in modules:
        key = program_name(name)
        runs.setdefault(key, []).append(s)
        total[key] = total.get(key, 0) + e - s
    if not runs:
        return None
    starts = sorted(runs[max(total, key=total.get)])
    if len(starts) < 2:
        return None
    return starts[0], starts[-1], len(starts) - 1


class _Programs:
    """The device's programs in order of start, to name where each idle
    interval falls among them, the intervals taken in order: `inside
    <program>` or `after <program>, before <program>`, by the rule of the
    benchmark's `breakdown.idle_gaps` (`benchmark/trace.py`), on integer
    ns where that reads float seconds."""

    def __init__(self, modules):
        self.runs = sorted(modules, key=lambda m: m[1])
        self.j = 0  # the first program that ends after the last interval's start

    def label(self, a: int, b: int) -> str:
        runs = self.runs
        while self.j < len(runs) and runs[self.j][2] <= a:
            self.j += 1
        j = self.j
        if j < len(runs) and runs[j][1] <= a and b <= runs[j][2]:
            return f"inside {program_name(runs[j][0])}"
        before = program_name(runs[j - 1][0]) if j > 0 else "(start)"
        after = program_name(runs[j][0]) if j < len(runs) else "(end)"
        return f"after {before}, before {after}"


class _Cover:
    """Merged intervals of one label, found by bisection."""

    def __init__(self, intervals):
        self.intervals = union(intervals)
        self.starts = [s for s, _ in self.intervals]

    def at(self, t: int) -> bool:
        i = bisect.bisect_right(self.starts, t) - 1
        return i >= 0 and t < self.intervals[i][1]

    def edges(self, a: int, b: int) -> List[int]:
        """Edges of its intervals strictly inside (a, b)."""
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        out = []
        while i < len(self.intervals) and self.intervals[i][0] < b:
            out += [x for x in self.intervals[i] if a < x < b]
            i += 1
        return out


def attribute_idle(
    spans, modules, window, busy, idle, *, records_dropped: int = 0
) -> Dict[str, Any]:
    """Every idle interval of the device (`idle`, the complement of `busy`
    over `window`: whole periods of the step program, `step_window`), cut
    where a host span begins or ends, and each piece put down to the step
    loop's phase open across it, the batcher's, and the device's own label
    for the interval (its neighbouring programs among `modules`). `spans`
    are `(start, end, name, args)` on the device's clock. Nothing is
    dropped: a piece that no span covers is `unattributed` (the batcher's
    `queue` between its spans), and where the ring overwrote records
    (`records_dropped`) the stretch before the first record kept is
    `no_records`; the pieces add up to the device's idle time."""
    t0, t1, periods = window
    loop = [
        (phase, _Cover([(s, e) for s, e, n, _ in spans if n == "learner/" + phase]))
        for phase in LOOP_PHASES
    ]
    batcher_spans: Dict[str, list] = {}
    for name, label in _BATCHER_SPANS:
        batcher_spans.setdefault(label, []).extend(
            (s, e) for s, e, n, _ in spans if n == name
        )
    batcher = [(label, _Cover(iv)) for label, iv in batcher_spans.items()]
    edges = [x for _, c in batcher for iv in c.intervals for x in iv]
    extent = (min(edges), max(edges)) if edges else (0, 0)
    records_from = (
        min((s for s, _, _, _ in spans), default=t1) if records_dropped else t0
    )
    programs = _Programs(modules)
    rows: Dict[Tuple[str, str, str], int] = {}
    for a, b in idle:
        label = programs.label(a, b)
        cuts = {a, b, records_from} if a < records_from < b else {a, b}
        for _, cover in loop + batcher:
            cuts.update(cover.edges(a, b))
        cuts = sorted(cuts)
        for p, q in zip(cuts, cuts[1:]):
            if p < records_from:
                key = (label, NO_RECORDS, NO_RECORDS)
            else:
                phase = next((n for n, c in loop if c.at(p)), UNATTRIBUTED)
                feed = next((n for n, c in batcher if c.at(p)), None)
                if feed is None:
                    feed = (
                        BATCHER_QUEUE if extent[0] <= p < extent[1]
                        else UNATTRIBUTED
                    )
                key = (label, phase, feed)
            rows[key] = rows.get(key, 0) + q - p

    def _sum(pick) -> List[list]:
        out: Dict[Any, int] = {}
        for key, ns in rows.items():
            out[pick(key)] = out.get(pick(key), 0) + ns
        return sorted(
            ([*(k if isinstance(k, tuple) else (k,)), v / 1e9]
             for k, v in out.items()),
            key=lambda row: -row[-1],
        )

    idle_ns = measure(idle)
    by_pair = _sum(lambda k: (k[1], k[2]))
    return {
        "window_s": (t1 - t0) / 1e9,
        "periods": periods,
        "busy_s": measure(busy) / 1e9,
        "idle_s": idle_ns / 1e9,
        "attributed_s": sum(rows.values()) / 1e9,
        "records_dropped": records_dropped,
        "by_pair": by_pair,
        "by_loop": _sum(lambda k: k[1]),
        "by_batcher": _sum(lambda k: k[2]),
        "by_device_label": _sum(lambda k: k[0]),
        "rows": _sum(lambda k: k),
        # the largest cell that names a phase on either side
        "largest": next(
            (
                row for row in by_pair
                if {row[0], row[1]} - {UNATTRIBUTED, NO_RECORDS}
            ),
            None,
        ),
    }


# ---- a profiler capture ---------------------------------------------------

_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")


def load_capture(path: str):
    """A `ProfilerCapture` directory (or any directory holding one
    capture): `(DeviceTimeline, records, clock_pair, records_dropped)`.
    The device side is the first device plane of the newest `.xplane.pb`
    under `path`, read with `jax.profiler.ProfileData`; its events count
    from the capture's `profile_start_time` (the `Task Environment`
    plane, epoch ns), which is added where they are not epoch readings
    already. The host side is `host_spans.json`
    beside it (`telemetry/profiling.HOST_SPANS_FILE`): its events, on the
    epoch clock, turned back into the recorder's records by the clock
    pair the file carries."""
    from jax.profiler import ProfileData

    from torched_impala_tpu.telemetry.profiling import HOST_SPANS_FILE

    captures = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not captures:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    newest = max(captures, key=os.path.getmtime)
    data = ProfileData.from_file(newest)
    start, planes = 0, []
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = int(dict(plane.stats).get("profile_start_time", 0))
        hit = _DEVICE_PLANE.match(plane.name)
        if hit:
            planes.append((int(hit.group(2)), plane))
    if not planes:
        raise ValueError(f"no device plane in {newest}")
    ops, modules = [], []
    for line in min(planes, key=lambda p: p[0])[1].lines:
        if line.name == "XLA Ops":
            ops = [(e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
        elif line.name == "XLA Modules":
            modules = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events
            ]
    if ops and ops[0][0] < start:  # counted from the capture's start
        ops = [(s + start, e + start) for s, e in ops]
        modules = [(n, s + start, e + start) for n, s, e in modules]
    with open(os.path.join(os.path.dirname(newest), HOST_SPANS_FILE)) as f:
        return (DeviceTimeline(ops, modules), *host_records(json.load(f)))


def host_records(doc: dict):
    """A capture's `host_spans.json` (loaded): `(records, clock_pair,
    records_dropped)`, its complete spans as the recorder held them, on
    the monotonic clock (the file's `ts` is on the epoch clock, shifted
    by the pair it carries)."""
    meta = doc.get("metadata") or {}
    pair = meta.get("clock_pair_ns") or {"monotonic": 0, "epoch": 0}
    clock_pair = (int(pair["monotonic"]), int(pair["epoch"]))
    shift = clock_pair[1] - clock_pair[0]
    records = [
        (
            round(ev["ts"] * 1e3) - shift,
            round(ev.get("dur", 0.0) * 1e3),
            ev["ph"],
            ev["name"],
            ev.get("tid", 0),
            ev.get("args"),
        )
        for ev in doc["traceEvents"]
        if ev.get("ph") == PH_COMPLETE
    ]
    return records, clock_pair, int(meta.get("dropped", 0))


def analyze_capture(
    path: str, *, roofline: Optional[Dict[str, Any]] = None
) -> Dict[str, Any]:
    """`analyze_records` of a profiler capture directory (`load_capture`):
    compute from the device, its idle put down to the host's phases."""
    device, records, clock_pair, dropped = load_capture(path)
    return analyze_records(
        records, roofline=roofline, device=device, clock_pair=clock_pair,
        records_dropped=dropped,
    )


_COMPUTE_SOURCE_NOTE = {
    "step_in_flight": "learner/step_in_flight: dispatch to device done",
    "train_step": (
        "learner/train_step: the DISPATCH only, no step waited for the "
        "device"
    ),
    "mixed": (
        "learner/step_in_flight where a step has one, else its dispatch"
    ),
    "device": "the device's operations, whole periods of the step program",
}


def render_report(report: Dict[str, Any]) -> str:
    """Human-readable rendering (the .txt sibling of the JSON)."""
    lines = ["== perf report =="]
    learner = report.get("learner") or {}
    steps = learner.get("steps", 0)
    if not steps:
        lines.append("no learner/train_step spans in the flight recorder")
    else:
        wall = learner["wall_clock_s"]
        lines.append(
            f"learner: {steps} steps over {wall:.3f}s wall-clock "
            f"({learner['compute_frac']:.1%} compute)"
        )
        lines.append(
            f"  compute         {learner['compute_s']:9.3f}s  "
            f"{learner['compute_frac']:6.1%}  "
            f"({_COMPUTE_SOURCE_NOTE[learner['compute_source']]})"
        )
        for cat in (*GAP_CATEGORIES, "unattributed"):
            lines.append(
                f"  gap:{cat:<12s}{learner['gaps_s'][cat]:9.3f}s  "
                f"{learner['gap_frac'][cat]:6.1%}"
            )
        lines.append(
            f"  coverage {learner['coverage_frac']:.1%} "
            f"(attributed {learner['attributed_frac']:.1%})"
        )
        if learner.get("h2d_total_s"):
            lines.append(
                f"  h2d: {learner['h2d_total_s']:.3f}s total, "
                f"{learner['h2d_overlap_frac']:.1%} overlapped with "
                "compute"
            )
        rep = learner.get("replayed") or {}
        if rep.get("steps"):
            lines.append(
                f"  replayed: {rep['steps']}/{steps} steps, "
                f"{rep['compute_s']:.3f}s compute, "
                f"mean reuse {rep['reuse_mean']:.2f}, "
                f"mean staleness {rep['staleness_mean']:.0f} frames"
            )
    idle = report.get("idle")
    if idle:
        lines.append(
            f"device idle {idle['idle_s']:.6f}s of {idle['window_s']:.6f}s "
            f"({idle['periods']} periods; put down to the host's phases "
            f"{idle['attributed_s']:.6f}s; records dropped "
            f"{idle['records_dropped']})"
        )
        lines.append(f"  {'loop phase':<14s}{'batcher':<17s}  seconds  share")
        for phase, feed, sec in idle["by_pair"]:
            share = sec / idle["idle_s"] if idle["idle_s"] else 0.0
            lines.append(f"  {phase:<14s}{feed:<17s}{sec:9.6f} {share:6.1%}")
        lines.append("  by the device's neighbouring programs:")
        for label, phase, feed, sec in idle["rows"][:12]:
            lines.append(f"    {sec:9.6f}s  {label} | {phase} | {feed}")
    roof = report.get("roofline") or {}
    # Accept either a single root's roofline or a {name: roofline} map.
    roots = (
        roof.values()
        if roof and all(isinstance(v, dict) for v in roof.values())
        else [roof]
    )
    for r in roots:
        if not isinstance(r, dict) or not r.get("flops_per_step"):
            continue
        line = (
            f"roofline[{r.get('root', '?')}] "
            f"{r['flops_per_step'] / 1e9:.1f} GFLOP/step "
            f"({r.get('source', '?')})"
        )
        if r.get("arithmetic_intensity"):
            line += (
                f", AI {r['arithmetic_intensity']:.1f} flop/byte "
                f"(ridge {r['ridge_intensity']:.1f}) -> "
                f"{r.get('bound', '?')}-bound"
            )
        lines.append(line)
    spans = report.get("span_counts") or {}
    if spans:
        lines.append(
            "spans: "
            + ", ".join(f"{k}x{v}" for k, v in sorted(spans.items()))
        )
    return "\n".join(lines) + "\n"


def write_report(report: Dict[str, Any], path: str) -> str:
    """Write `path` (JSON) and its human-readable `.txt` sibling;
    returns the text path."""
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=1)
    txt = (path[:-5] if path.endswith(".json") else path) + ".txt"
    with open(txt, "w", encoding="utf-8") as f:
        f.write(render_report(report))
    return txt


def generate_report(
    path: Optional[str] = None,
    *,
    recorder: Optional[FlightRecorder] = None,
    records: Optional[List[tuple]] = None,
    roofline: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Analyze the (given or global) flight recorder and optionally
    persist the JSON + text pair at `path`."""
    if records is None:
        rec = recorder if recorder is not None else get_recorder()
        records = rec.tail()
    report = analyze_records(records, roofline=roofline)
    if path:
        write_report(report, path)
    return report


def install_sigusr2_report(
    path: str,
    *,
    roofline_fn=None,
) -> bool:
    """Chain a perf-report dump onto SIGUSR2: the flight recorder's own
    handler (tracing.install_sigusr2) keeps firing first, then the
    current ring is analyzed into `<path>` stamped with a sequence
    number. Main-thread only; returns False when it cannot install."""
    if not hasattr(signal, "SIGUSR2"):
        return False
    if threading.current_thread() is not threading.main_thread():
        return False
    prev = signal.getsignal(signal.SIGUSR2)
    count = [0]

    def _handler(signum, frame):
        if callable(prev):
            try:
                prev(signum, frame)
            except Exception:
                pass
        try:
            count[0] += 1
            base = path[:-5] if path.endswith(".json") else path
            out = f"{base}_{count[0]:03d}.json"
            roofline = roofline_fn() if roofline_fn is not None else None
            generate_report(out, roofline=roofline)
            print(
                f"[perf-report] -> {out}", file=sys.stderr, flush=True
            )
        except Exception as e:  # noqa: BLE001 — never kill the run
            print(
                f"[perf-report] SIGUSR2 dump failed: {e!r}",
                file=sys.stderr,
                flush=True,
            )

    signal.signal(signal.SIGUSR2, _handler)
    return True


def main(argv: Optional[List[str]] = None) -> int:
    """`python -m torched_impala_tpu.perf.report <capture dir>`: the
    report of one profiler capture (`--profile-steps`, SIGUSR1), the
    device's idle put down to the host's phases."""
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(
            "usage: python -m torched_impala_tpu.perf.report <capture dir>",
            file=sys.stderr,
        )
        return 2
    sys.stdout.write(render_report(analyze_capture(args[0])))
    return 0


if __name__ == "__main__":
    sys.exit(main())
