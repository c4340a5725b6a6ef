"""Open-loop load generator for the serving fleet (docs/SERVING.md).

Closed-loop drivers (submit, wait, submit) measure a flattering lie:
when the server slows down, the driver offers less load, and the
latency histogram quietly omits every request that WOULD have arrived.
This harness is open-loop: arrival times are drawn up front from a
traffic shape (Poisson / bursty / diurnal), worker threads sleep until
each scheduled instant, and latency is measured FROM THE SCHEDULED
ARRIVAL — a late start counts against the server (the standard
coordinated-omission correction).

Chaos riders: a sampled fraction of arrivals are SLOW CLIENTS (stall
after claiming their slot — the straggler a wave must not wait for) or
DISCONNECTS (submit, then hang up before reading the answer — the
cleanup path a fleet sees constantly at scale). Both are deterministic
per seed.

The verdict is a `LoadReport`: p50/p99 latency, achieved vs offered
rate, and GOODPUT — completed requests per second that landed within
the SLO. Goodput-at-SLO is the fleet's headline number (no benchmark
cell drives serving yet, PERF.md section 7, row 4):
past the saturation knee, raw throughput keeps climbing while goodput
collapses, which is exactly the regression a latency gate must catch.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from torched_impala_tpu.serving.fleet import FleetClient, ServingFleet
from torched_impala_tpu.serving.server import (
    ClientDisconnected,
    DeadlineExpired,
    ServingError,
)

_SHAPES = ("poisson", "bursty", "diurnal")


@dataclasses.dataclass(frozen=True)
class TrafficShape:
    """An open-loop arrival process over a bounded window.

    - `poisson`: memoryless arrivals at `rate_rps`.
    - `bursty`: square-wave modulation — `burst_duty` of every
      `period_s` runs at `burst_rps` (default 4x), the rest at whatever
      keeps the MEAN at `rate_rps` (clamped at 0 when bursts alone
      exceed it).
    - `diurnal`: sinusoidal modulation, `rate_rps * (1 + amplitude *
      sin(2*pi*t / period_s))` — the day/night envelope compressed to
      seconds.

    Modulated shapes sample by thinning a `max rate` Poisson process,
    so all three are exact (no time-bucketing artifacts).
    """

    kind: str = "poisson"
    rate_rps: float = 100.0
    duration_s: float = 2.0
    burst_rps: float = 0.0  # 0 -> 4 * rate_rps
    burst_duty: float = 0.25
    period_s: float = 1.0
    amplitude: float = 0.5

    def __post_init__(self) -> None:
        if self.kind not in _SHAPES:
            raise ValueError(
                f"unknown traffic shape {self.kind!r}; expected one of "
                f"{_SHAPES}"
            )
        if self.rate_rps <= 0 or self.duration_s <= 0:
            raise ValueError("need rate_rps > 0 and duration_s > 0")
        if not 0.0 < self.burst_duty < 1.0:
            raise ValueError(
                f"burst_duty must be in (0, 1), got {self.burst_duty}"
            )
        if self.period_s <= 0:
            raise ValueError(f"period_s must be > 0, got {self.period_s}")
        if not 0.0 <= self.amplitude < 1.0:
            raise ValueError(
                f"amplitude must be in [0, 1), got {self.amplitude}"
            )

    def _rate_at(self, t: np.ndarray) -> np.ndarray:
        """Instantaneous rate lambda(t), vectorized."""
        if self.kind == "poisson":
            return np.full_like(t, self.rate_rps, dtype=np.float64)
        if self.kind == "bursty":
            hi = self.burst_rps if self.burst_rps > 0 else 4.0 * self.rate_rps
            lo = max(
                0.0,
                (self.rate_rps - hi * self.burst_duty)
                / (1.0 - self.burst_duty),
            )
            phase = np.mod(t, self.period_s) / self.period_s
            return np.where(phase < self.burst_duty, hi, lo)
        # diurnal
        return self.rate_rps * (
            1.0 + self.amplitude * np.sin(2.0 * np.pi * t / self.period_s)
        )

    def peak_rate(self) -> float:
        if self.kind == "poisson":
            return self.rate_rps
        if self.kind == "bursty":
            return (
                self.burst_rps if self.burst_rps > 0 else 4.0 * self.rate_rps
            )
        return self.rate_rps * (1.0 + self.amplitude)

    def arrival_times(self, rng: np.random.Generator) -> np.ndarray:
        """Sorted arrival offsets (seconds) in [0, duration_s)."""
        peak = self.peak_rate()
        # Draw a homogeneous Poisson stream at the peak rate, then thin.
        n = rng.poisson(peak * self.duration_s)
        t = np.sort(rng.uniform(0.0, self.duration_s, size=n))
        keep = rng.uniform(0.0, 1.0, size=n) * peak < self._rate_at(t)
        return t[keep]


@dataclasses.dataclass
class LoadReport:
    """What one load run measured (all latency in ms, from SCHEDULED
    arrival — see module docstring)."""

    shape: TrafficShape
    slo_ms: float
    clients: int
    offered: int  # scheduled arrivals
    ok: int  # completed with an action
    ok_within_slo: int  # ... within the SLO
    expired: int  # DeadlineExpired
    disconnected: int  # disconnect-chaos arrivals (by design)
    failed: int  # any other error (MUST be 0 in a healthy run)
    retried: int  # answered via the one failover retry
    p50_ms: float
    p90_ms: float
    p99_ms: float
    max_ms: float
    offered_rps: float
    completed_rps: float
    goodput_rps: float  # ok_within_slo / duration — the headline
    latencies_ms: np.ndarray = dataclasses.field(repr=False, default=None)

    def summary(self) -> Dict[str, Any]:
        return {
            "offered": self.offered,
            "ok": self.ok,
            "ok_within_slo": self.ok_within_slo,
            "expired": self.expired,
            "disconnected": self.disconnected,
            "failed": self.failed,
            "retried": self.retried,
            "p50_ms": self.p50_ms,
            "p99_ms": self.p99_ms,
            "goodput_rps": self.goodput_rps,
            "completed_rps": self.completed_rps,
            "offered_rps": self.offered_rps,
        }


def run_load(
    *,
    fleet: ServingFleet,
    shape: TrafficShape,
    slo_ms: float,
    example_obs: np.ndarray,
    obs_pool: Optional[np.ndarray] = None,
    clients: int = 8,
    seed: int = 0,
    greedy: bool = True,
    deadline_s: Optional[float] = None,
    disconnect_frac: float = 0.0,
    slow_frac: float = 0.0,
    slow_hold_ms: float = 20.0,
    timeout_s: float = 30.0,
) -> LoadReport:
    """Drive `fleet` with `shape` arrivals from `clients` worker threads
    and return the `LoadReport`.

    Workers share one global arrival index: each claims the next
    scheduled arrival, sleeps until its instant, and issues a blocking
    request — so the OFFERED process is `shape` regardless of how slow
    the fleet answers (until all workers are stuck in flight, which the
    report exposes as offered-vs-achieved divergence plus fat tails)."""
    if slo_ms <= 0:
        raise ValueError(f"slo_ms must be > 0, got {slo_ms}")
    if clients < 1:
        raise ValueError(f"need clients >= 1, got {clients}")
    rng = np.random.default_rng(seed)
    arrivals = shape.arrival_times(rng)
    n = len(arrivals)
    disconnect_mask = rng.uniform(size=n) < disconnect_frac
    slow_mask = rng.uniform(size=n) < slow_frac
    if obs_pool is None:
        obs_pool = np.stack([np.asarray(example_obs)] * 4)
    pool_n = len(obs_pool)

    lock = threading.Lock()
    next_idx = [0]
    lat_ms = np.full(n, np.nan)
    outcome = np.zeros(n, np.int8)  # 0 pending, 1 ok, 2 expired,
    # 3 disconnected (chaos), 4 failed
    retried = np.zeros(n, np.bool_)

    start = time.monotonic()

    def worker(wid: int) -> None:
        client = FleetClient(
            fleet,
            greedy=greedy,
            timeout_s=timeout_s,
            client_id=wid,
        )
        try:
            while True:
                with lock:
                    i = next_idx[0]
                    if i >= n:
                        return
                    next_idx[0] += 1
                t_sched = start + float(arrivals[i])
                delay = t_sched - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                if slow_mask[i]:
                    # A straggling client: claims its arrival, then
                    # stalls before submitting.
                    time.sleep(slow_hold_ms / 1e3)
                obs = obs_pool[i % pool_n]
                try:
                    if disconnect_mask[i]:
                        client.act_abandon(obs, first=True)
                        outcome[i] = 3
                        continue
                    res = client.act_full(
                        obs, first=True, deadline_s=deadline_s
                    )
                except DeadlineExpired:
                    outcome[i] = 2
                except (ServingError, TimeoutError, ClientDisconnected):
                    outcome[i] = 4
                else:
                    lat_ms[i] = (time.monotonic() - t_sched) * 1e3
                    outcome[i] = 1
                    retried[i] = res.retried
        finally:
            client.close()

    threads = [
        threading.Thread(
            target=worker, args=(w,), name=f"loadgen-{w}", daemon=True
        )
        for w in range(clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    ok_lat = lat_ms[outcome == 1]
    ok = int(np.sum(outcome == 1))
    ok_within = int(np.sum(ok_lat <= slo_ms)) if ok else 0
    duration = float(shape.duration_s)
    pct = (
        np.percentile(ok_lat, [50, 90, 99])
        if ok
        else np.array([np.inf, np.inf, np.inf])
    )
    return LoadReport(
        shape=shape,
        slo_ms=float(slo_ms),
        clients=clients,
        offered=n,
        ok=ok,
        ok_within_slo=ok_within,
        expired=int(np.sum(outcome == 2)),
        disconnected=int(np.sum(outcome == 3)),
        failed=int(np.sum(outcome == 4)),
        retried=int(np.sum(retried)),
        p50_ms=float(pct[0]),
        p90_ms=float(pct[1]),
        p99_ms=float(pct[2]),
        max_ms=float(np.max(ok_lat)) if ok else float("inf"),
        offered_rps=n / duration,
        completed_rps=ok / duration,
        goodput_rps=ok_within / duration,
        latencies_ms=ok_lat,
    )
