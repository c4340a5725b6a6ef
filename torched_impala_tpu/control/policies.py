"""Controller policies: one small feedback rule per knob.

Three rule shapes cover every knob the runtime exposes today:

- :class:`HillClimbPolicy` — generalized hill climb on a measured
  objective (MFU, throughput): step the knob, wait out its settle
  window, keep the direction while the objective improves beyond the
  hysteresis margin, reverse when it stops paying, and **revert** any
  change that regresses the objective beyond the tolerance within the
  settle window (the guardrail). A cooldown after reverts and refused
  recompiles stops the climb from hammering a wall.
- :class:`TargetMapPolicy` — a direct measured-line feedback law:
  ``value = base - slope * signal``. The env_pool EWMA auto
  ready-fraction tuner is the first instance (the slope is
  env_pool.AUTO_FRACTION_SLOPE).
- :class:`SloPolicy` — budgeted-headroom bang-bang with a hysteresis
  band: shrink the knob while the SLO is violated, relax it back while
  there is ample headroom, hold in between. Serves the serving-tier
  latency knobs and (with ``grow_on_violation=True``) the checkpoint
  cadence knob, where *violation* means overhead too high and the fix
  is a LONGER interval.

Policies are pure deciders: ``tick`` returns a :class:`Proposal` (or
None to hold); the ControlLoop owns applying it through the knob and
reports back via ``observe_result`` so the policy can settle/cool down.
Every policy reads only Signal adapters — no direct runtime access.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

from torched_impala_tpu.control.knobs import Knob
from torched_impala_tpu.control.signals import Signal

# Relative thresholds turn degenerate near a zero objective; fall back
# to absolute comparisons below this magnitude.
_EPS = 1e-9


@dataclasses.dataclass(frozen=True)
class Proposal:
    """One decision a policy wants taken on its knob."""

    kind: str  # "set" | "revert"
    target: float = 0.0
    reason: str = ""


class Policy:
    """Base: ``tick(snap, now, knob) -> Proposal | None`` plus the
    apply-outcome callback."""

    def tick(
        self, snap, now: float, knob: Knob
    ) -> Optional[Proposal]:
        raise NotImplementedError

    def observe_result(self, status: str, now: float) -> None:
        """Called by the loop after acting on this policy's proposal
        with status "applied" | "noop" | "refused" | "reverted"."""


class HillClimbPolicy(Policy):
    """Hill climb with hysteresis, settle windows, guardrail reverts,
    and post-revert/post-refusal cooldown. See the module docstring."""

    def __init__(
        self,
        objective: Signal,
        *,
        tolerance: float = 0.05,
        hysteresis: float = 0.01,
        cooldown_s: float = 30.0,
        direction: int = 1,
    ) -> None:
        if tolerance <= 0 or hysteresis < 0:
            raise ValueError("need tolerance > 0 and hysteresis >= 0")
        self.objective = objective
        self.tolerance = tolerance
        self.hysteresis = hysteresis
        self.cooldown_s = cooldown_s
        self._direction = 1 if direction >= 0 else -1
        self._phase = "idle"  # "idle" | "settling"
        self._changed_t = 0.0
        self._pre_obj: Optional[float] = None
        self._cooldown_until = float("-inf")
        # Exposed for the control/objective_delta gauge: the judged
        # objective change of the last settled step (None until one).
        self.last_objective_delta: Optional[float] = None

    def tick(self, snap, now, knob):
        obj = self.objective.read(snap, now)
        if obj is None:
            return None
        if now < self._cooldown_until:
            return None
        if self._phase == "settling":
            if now - self._changed_t < knob.spec.settle_s:
                return None
            return self._judge(obj)
        return self._climb(obj, knob)

    def _judge(self, obj: float) -> Optional[Proposal]:
        """Settle window elapsed: compare against the pre-change
        objective; revert on regression beyond tolerance, otherwise
        commit and pick the next direction."""
        pre = self._pre_obj
        self._phase = "idle"
        if pre is None:
            return None
        scale = max(abs(pre), _EPS)
        self.last_objective_delta = obj - pre
        if obj < pre - self.tolerance * scale:
            self._direction *= -1
            return Proposal(
                "revert",
                reason=(
                    f"objective {obj:.4g} regressed beyond "
                    f"{self.tolerance:.0%} of {pre:.4g}"
                ),
            )
        if obj <= pre + self.hysteresis * scale:
            # Within the hysteresis band: the move didn't pay. Keep it
            # (no regression) but try the other direction next.
            self._direction *= -1
        return None

    def _climb(self, obj: float, knob: Knob) -> Optional[Proposal]:
        step = knob.spec.default_step()
        current = knob.value
        target = current + self._direction * step
        if knob.spec.clamp(target) == current:
            self._direction *= -1  # at a bound: turn around
            target = current + self._direction * step
            if knob.spec.clamp(target) == current:
                return None  # degenerate range
        self._pre_obj = obj
        return Proposal(
            "set",
            target,
            reason=f"hill-climb {'+' if self._direction > 0 else '-'}"
            f"{step:g} at objective {obj:.4g}",
        )

    def observe_result(self, status, now):
        if status == "applied":
            self._phase = "settling"
            self._changed_t = now
        elif status in ("refused", "reverted"):
            self._phase = "idle"
            self._cooldown_until = now + self.cooldown_s


class TargetMapPolicy(Policy):
    """Direct feedback law ``value = base - slope * signal`` (clamped by
    the knob's bounds). Stateless between ticks — the smoothing lives in
    the signal (EWMA), exactly like the env_pool prototype it
    generalizes."""

    def __init__(
        self, signal: Signal, *, slope: float, base: float = 1.0
    ) -> None:
        self.signal = signal
        self.slope = slope
        self.base = base

    def target_for(self, x: float) -> float:
        return self.base - self.slope * x

    def tick(self, snap, now, knob):
        x = self.signal.read(snap, now)
        if x is None:
            return None
        target = self.target_for(x)
        if knob.spec.clamp(target) == knob.value:
            return None
        return Proposal(
            "set", target, reason=f"target map: signal {x:.4g}"
        )


class SloPolicy(Policy):
    """Budgeted-headroom rule. ``signal`` must be a normalized headroom
    ((budget - value) / budget): negative = violating. While violating,
    move one step toward ``lo`` (or ``hi`` with
    ``grow_on_violation=True`` — the checkpoint-cadence shape, where
    the cure for overhead is a longer interval); while headroom exceeds
    ``relax_headroom``, move one step back; hold in the band between.
    A per-move cooldown keeps the knob from slewing faster than the
    percentile windows it reads can react."""

    def __init__(
        self,
        signal: Signal,
        *,
        grow_on_violation: bool = False,
        relax_headroom: float = 0.5,
        cooldown_s: float = 5.0,
    ) -> None:
        if not 0.0 < relax_headroom < 1.0:
            raise ValueError("relax_headroom must be in (0, 1)")
        self.signal = signal
        self.grow_on_violation = grow_on_violation
        self.relax_headroom = relax_headroom
        self.cooldown_s = cooldown_s
        self._cooldown_until = float("-inf")

    def tick(self, snap, now, knob):
        h = self.signal.read(snap, now)
        if h is None or now < self._cooldown_until:
            return None
        step = knob.spec.default_step()
        current = knob.value
        if h < 0.0:
            delta = step if self.grow_on_violation else -step
            reason = f"slo violated (headroom {h:.2f})"
        elif h > self.relax_headroom:
            delta = -step if self.grow_on_violation else step
            reason = f"slo headroom {h:.2f} > {self.relax_headroom:.2f}"
        else:
            return None
        target = current + delta
        if knob.spec.clamp(target) == current:
            return None
        return Proposal("set", target, reason=reason)

    def observe_result(self, status, now):
        if status == "applied":
            self._cooldown_until = now + self.cooldown_s


class AlertGatedPolicy(Policy):
    """Wrap an inner policy with a health-alert gate (ISSUE 19): while
    ``gate`` (typically :class:`control.signals.AlertSignal` over a
    ``health_slo_specs`` row) reads firing, the inner policy's
    proposals are discarded — growth is frozen — and, with
    ``shrink_on_alert``, the knob steps toward its floor instead (the
    rho-saturation -> replay ``max_reuse`` binding: when most
    importance weights clip, more reuse is buying bias, not
    throughput). When the gate reads 0 or has no data (no health plane
    attached), ticks pass through to the inner policy untouched, so
    wrapping is behavior-neutral for runs without health monitoring.
    """

    def __init__(
        self,
        inner: Policy,
        gate: Signal,
        *,
        shrink_on_alert: bool = True,
        cooldown_s: float = 5.0,
    ) -> None:
        self.inner = inner
        self.gate = gate
        self.shrink_on_alert = shrink_on_alert
        self.cooldown_s = cooldown_s
        self._cooldown_until = float("-inf")
        self._last_was_gate = False

    def tick(self, snap, now, knob):
        firing = self.gate.read(snap, now)
        if firing is None or firing < 1.0:
            self._last_was_gate = False
            return self.inner.tick(snap, now, knob)
        self._last_was_gate = True
        if not self.shrink_on_alert or now < self._cooldown_until:
            return None
        step = knob.spec.default_step()
        target = knob.value - step
        if knob.spec.clamp(target) == knob.value:
            return None  # already at the floor
        return Proposal(
            "set",
            target,
            reason=f"health alert {getattr(self.gate, 'key', '?')} firing",
        )

    def observe_result(self, status, now):
        if self._last_was_gate:
            # Our own shrink proposal — only pace ourselves; the inner
            # policy never proposed, so its settle/cooldown state must
            # not move.
            if status == "applied":
                self._cooldown_until = now + self.cooldown_s
            return
        self.inner.observe_result(status, now)


def monotonic() -> float:
    """Indirection point so tests can monkeypatch one clock."""
    return time.monotonic()
