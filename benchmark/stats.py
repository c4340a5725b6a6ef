"""Window arithmetic: the rate and the tail of step completions."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) with linear interpolation between
    closest ranks, as numpy's default; no numpy so that it can be checked
    by hand."""
    if not values:
        raise ValueError("no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def window_metrics(t_open: float, completions, frames_per_step: int) -> dict:
    """End-to-end numbers of one window.

    `t_open` is the completion stamp of the last warm-up step and
    `completions` the stamps of every step completed after it, the last
    one closing the window. So the window starts and ends on a step
    boundary, and the rate is all the work over all the time: no step is
    cut, and a stall anywhere lowers it."""
    if len(completions) < 2:
        raise ValueError("a window needs at least two completed steps")
    stamps = [t_open, *completions]
    gaps_ms = [(b - a) * 1e3 for a, b in zip(stamps, stamps[1:])]
    wall = completions[-1] - t_open
    return {
        "steps": len(completions),
        "window_s": wall,
        "frames_per_s": frames_per_step * len(completions) / wall,
        "step_ms_p95": percentile(gaps_ms, 95.0),
        "step_ms_p50": percentile(gaps_ms, 50.0),
        "step_ms_p90": percentile(gaps_ms, 90.0),
        "step_ms_p99": percentile(gaps_ms, 99.0),
        "step_ms_max": max(gaps_ms),
    }
