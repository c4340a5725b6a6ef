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

Compute is the host's bracket around the device's work on a step,
`learner/step_in_flight` (dispatch start to the moment the wait for the
device returns), where the step has one. `learner/train_step` times only
the DISPATCH of the step: on an asynchronous backend it is a few ms of a
60 ms step, and tiling the wall clock with it would book the device's
compute as a `publish` gap. A step without the bracket (it owed the host
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

import json
import os
import signal
import sys
import threading
from typing import Any, Dict, List, Optional, Tuple

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
) -> Dict[str, Any]:
    """Attribute the learner timeline of one flight-recorder record list
    (the `(ts_ns, dur_ns, phase, name, tid, args)` 6-tuples of
    `FlightRecorder.tail()`).

    Returns the report dict; `roofline` (e.g. `CostModel.snapshot()`
    or a single root's `CostModel.roofline()`) rides along verbatim so
    the report pairs "where the time went" with "what the FLOPs cost".
    """
    spans: List[Tuple[int, int, str, Optional[dict]]] = []
    span_counts: Dict[str, int] = {}
    for rec in records:
        if rec is None:
            continue
        ts_ns, dur_ns, phase, name, _tid, args = rec
        if phase != PH_COMPLETE:
            continue
        spans.append((ts_ns, ts_ns + dur_ns, name, args))
        span_counts[name] = span_counts.get(name, 0) + 1

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
    report: Dict[str, Any] = {
        "schema": SCHEMA_VERSION,
        "span_counts": dict(sorted(span_counts.items())),
    }
    if roofline:
        report["roofline"] = roofline
    if len(steps) == 0:
        report["learner"] = {"steps": 0}
        return report

    # One step in flight: clip each bracket to where the one before ended.
    done = steps[0][0]
    for i, (s, e, args) in enumerate(steps):
        steps[i] = (min(max(s, done), e), e, args)
        done = max(done, e)
    wall_ns = done - steps[0][0]
    compute_ns = sum(e - s for s, e, _ in steps)

    # Fresh vs replayed compute (IMPACT lineage rides the span args;
    # BatchLineage convention: reuse_count 1 = first delivery = fresh,
    # > 1 = a replay re-delivery of a retained slot).
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

    # The gaps: wall-clock minus the union of train_step spans.
    gap_intervals = union([(s, e) for s, e, _ in steps])
    uncovered: List[Tuple[int, int]] = []
    pos = steps[0][0]
    for s, e in gap_intervals:
        if s > pos:
            uncovered.append((pos, s))
        pos = max(pos, e)
    total_gap_ns = measure(uncovered)

    compute_source = (
        "step_in_flight" if bracketed == len(steps)
        else "train_step" if bracketed == 0
        else "mixed"
    )
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
    h2d_overlapped_ns, _ = subtract(
        list(by_category["h2d"]), gap_intervals
    )

    def _s(ns: int) -> float:
        return ns / 1e9

    learner: Dict[str, Any] = {
        "steps": len(steps),
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
    report["learner"] = learner
    return report


_COMPUTE_SOURCE_NOTE = {
    "step_in_flight": "learner/step_in_flight: dispatch to device done",
    "train_step": (
        "learner/train_step: the DISPATCH only, no step waited for the "
        "device"
    ),
    "mixed": (
        "learner/step_in_flight where a step has one, else its dispatch"
    ),
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
