"""The few generic readers behind the per-layer metrics.

A per-layer metric is a file `metrics/<name>.json` under one of the
benchmark's `paths`: `{"reader": <one of READERS>, "params": {...}}` and a
line on what it is. A reader takes the run's context and the file's
`params`, and returns the value, or None when it finds nothing to read:
the harness then leaves the metric out of the line. It never returns 0 for
a share of a roofline or of a peak that it could not read.

The context (`Context`) holds what one traced run produced: the reduced
trace, the timers' totals over the traced window, the steps completed in
it, the configuration with its network file, and the peaks of the device.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

from benchmark import flops, trace as tr


class Context(NamedTuple):
    trace: Optional[tr.Trace]
    timers: dict  # name -> (seconds, calls) observed in the traced window
    host_window_s: float  # the traced window by the host's clock
    steps: int  # steps completed in it
    config: dict
    chips: int
    peaks: dict  # {"bf16_flops_per_s", "hbm_bytes_per_s", ...}
    window: dict = {}  # stats.window_metrics of the traced window
    net: Any = None  # the configuration's network file: its counts

    @property
    def device_window(self):
        """(t0, t1, periods) on the device's clock, or None."""
        if self.trace is None or not self.trace.modules:
            return None
        return tr.step_window(next(iter(self.trace.modules.values())))


def timer(ctx: Context, key: str, stat: str):
    """A program timer over the traced window: `share_of_window` in %, or
    `mean_ms` per call."""
    seconds, calls = ctx.timers.get(key, (0.0, 0))
    if calls == 0:
        return None
    if stat == "share_of_window":
        return 100.0 * seconds / ctx.host_window_s
    if stat == "mean_ms":
        return 1e3 * seconds / calls
    raise ValueError(f"unknown stat {stat!r}")


def window_stat(ctx: Context, key: str):
    """A statistic of the traced window's step completions by the host's
    clock (`stats.window_metrics`), such as `step_ms_p95`."""
    return ctx.window.get(key)


def _per_device(ctx: Context, line: str) -> list:
    if ctx.trace is None:
        return []
    return list(getattr(ctx.trace, line).values())


def device_time(ctx: Context, patterns: list):
    """Device milliseconds of one execution of the programs whose name
    matches (the `XLA Modules` line), mean over the traced window's
    executions and over the chips."""
    win = ctx.device_window
    means = []
    for events in _per_device(ctx, "modules"):
        hits = tr.matching(events, patterns)
        if win is not None:
            hits = [e for e in hits if win[0] <= e.start < win[1]]
        if hits:
            means.append(sum(e.dur for e in hits) / len(hits))
    if not means:
        return None
    return 1e3 * sum(means) / len(means)


def roofline(ctx: Context, patterns: list, within: list, ops_and_bytes: str):
    """A kernel's share of its roofline, in %: the least time the chip could
    take (`flops.least_seconds` of the FLOPs and bytes that the network
    file's `OPS_AND_BYTES[ops_and_bytes]` counts) over the device time
    measured. What is timed: the events matching `patterns` or, with
    `within`, the shortest event matching `within` around each of them (the
    loop that a kernel's calls make up), each counted as one call of the
    function."""
    n_flops, n_bytes = ctx.net.OPS_AND_BYTES[ops_and_bytes](ctx.config, ctx.chips)
    least = flops.least_seconds(n_flops, n_bytes, ctx.peaks)
    calls, seconds = 0, 0.0
    for events in _per_device(ctx, "ops"):
        hits = tr.matching(events, patterns)
        if within:
            hits = tr.enclosing(events, hits, within)
        calls += len(hits)
        seconds += sum(e.dur for e in hits)
    if calls == 0 or seconds <= 0.0:
        return None
    return 100.0 * least * calls / seconds


def exposed_time(ctx: Context, patterns: list):
    """Milliseconds per step during which a matching operation (a
    collective) runs on a chip and nothing else does there; mean over the
    chips."""
    win = ctx.device_window
    per_chip = []
    for events in _per_device(ctx, "ops"):
        if win is None or not tr.matching(events, patterns):
            continue
        per_chip.append(
            tr.exposed_seconds(events, patterns, win[0], win[1]) / win[2]
        )
    if not per_chip:
        return None
    return 1e3 * sum(per_chip) / len(per_chip)


def busy_and_window(ctx: Context):
    """(busy seconds averaged over the chips, window seconds), or None."""
    win = ctx.device_window
    per_chip = _per_device(ctx, "ops")
    if win is None or not per_chip:
        return None
    busy = sum(tr.busy_seconds(ev, win[0], win[1]) for ev in per_chip)
    return busy / len(per_chip), win[1] - win[0]


def idle_share(ctx: Context):
    """1 - (union of device-operation intervals) / (traced window), in %."""
    bw = busy_and_window(ctx)
    if bw is None or bw[0] <= 0.0:
        return None
    return 100.0 * (1.0 - bw[0] / bw[1])


def mfu(ctx: Context):
    """Model FLOPs (the network file's `step_flops`) of the steps completed
    in the traced window, over the window and the chips' bf16 peak, in %."""
    if ctx.steps < 1 or ctx.host_window_s <= 0.0:
        return None
    rate = ctx.net.step_flops(ctx.config) * ctx.steps / ctx.host_window_s
    return 100.0 * rate / (ctx.chips * ctx.peaks["bf16_flops_per_s"])


READERS = {
    "timer": timer,
    "window_stat": window_stat,
    "device_time": device_time,
    "roofline": roofline,
    "exposed_time": exposed_time,
    "idle_share": idle_share,
    "mfu": mfu,
}


def read(ctx: Context, metric_file: dict):
    """Value of one metric file, or None."""
    reader = READERS[metric_file["reader"]]
    return reader(ctx, **metric_file.get("params", {}))
