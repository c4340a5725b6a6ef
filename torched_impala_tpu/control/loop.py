"""ControlLoop: the background thread that closes the loop.

One loop owns a set of (knob, policy) bindings. Every tick it takes a
single telemetry snapshot, lets each policy propose against it, and acts
through the knob — the only mutation path. Every acted-on change emits a
``control/decision`` flight-recorder instant (knob, kind, from, to,
reason) so the whole adaptation history replays in Perfetto next to the
learner/actor spans it affected, plus ``control/*`` counters for
dashboards:

- ``control/decision_total``   — applied changes
- ``control/decision_refused`` — proposals the recompile gate rejected
- ``control/revert_total``     — guardrail reverts
- ``control/objective_delta``  — judged objective change of the last
  settled hill-climb step
- ``control/knob_<name>``      — live value of each knob (from knobs.py)

``build_train_control`` / ``build_serving_control`` assemble the
standard knob sets for the training runtime and the PolicyServer; the
loop itself is engine, not policy.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import List, Optional

from torched_impala_tpu.control.knobs import (
    Knob,
    KnobSet,
    KnobSpec,
    RecompileGate,
)
from torched_impala_tpu.control.policies import (
    AlertGatedPolicy,
    HillClimbPolicy,
    Policy,
    SloPolicy,
)
from torched_impala_tpu.control.signals import (
    AlertSignal,
    CheckpointOverheadSignal,
    EwmaSignal,
    GaugeSignal,
    HeadroomSignal,
    SloHeadroomSignal,
)
from torched_impala_tpu.telemetry import get_recorder, get_registry

DECISION_EVENT = "control/decision"

# Largest fused-dispatch K the superbatch trajectory ring is sized for
# (runtime/traj_ring.py [K, T+1, B, ...] slots; ISSUE 13). Knob ceilings
# below derive from this so the controller can explore past the old K=8
# fused ceiling without outrunning what the feed path can actually
# deliver.
SUPERBATCH_MAX_K = 16


@dataclasses.dataclass
class _Binding:
    knob: Knob
    policy: Policy


class ControlLoop:
    """Ticks the bound policies at a fixed interval on a daemon thread.

    ``tick`` is also public and side-effect-complete so tests and
    doctor drive the loop deterministically without threads or
    sleeps (pass an explicit ``now`` for a synthetic clock).
    """

    def __init__(
        self,
        *,
        interval_s: float = 5.0,
        telemetry=None,
        tracer=None,
        name: str = "control-loop",
    ) -> None:
        if interval_s <= 0:
            raise ValueError("control interval must be > 0")
        self.interval_s = interval_s
        self.knobs = KnobSet()
        self._bindings: List[_Binding] = []
        self._registry = (
            telemetry if telemetry is not None else get_registry()
        )
        self._tracer = tracer if tracer is not None else get_recorder()
        self._name = name
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        reg = self._registry
        self._m_decisions = reg.counter("control/decision_total")
        self._m_refused = reg.counter("control/decision_refused")
        self._m_reverts = reg.counter("control/revert_total")
        self._m_obj_delta = reg.gauge("control/objective_delta")
        self._m_ticks = reg.counter("control/decision_ticks")

    def add_knob(self, knob: Knob) -> Knob:
        """Register a knob with no policy: hot-apply surface only,
        still audited and exported (the gated B/K knobs live here)."""
        return self.knobs.register(knob)

    def bind(self, knob: Knob, policy: Policy) -> Knob:
        if knob.spec.name not in self.knobs:
            self.knobs.register(knob)
        self._bindings.append(_Binding(knob, policy))
        return knob

    # -- the loop body -------------------------------------------------

    def tick(self, now: Optional[float] = None) -> int:
        """Run one control cycle; returns the number of applied changes
        + reverts (i.e. audited decisions) this tick."""
        now = time.monotonic() if now is None else now
        self._m_ticks.inc()
        snap = self._registry.snapshot()
        acted = 0
        for b in self._bindings:
            try:
                proposal = b.policy.tick(snap, now, b.knob)
            except Exception:
                # A broken policy must not take down its siblings or
                # the runtime; the knob simply stops moving.
                continue
            if proposal is None:
                continue
            if proposal.kind == "revert":
                status = self._do_revert(b.knob, proposal, now)
            else:
                status = self._do_set(b.knob, proposal, now)
            if status in ("applied", "reverted"):
                acted += 1
            b.policy.observe_result(status, now)
            delta = getattr(b.policy, "last_objective_delta", None)
            if delta is not None:
                self._m_obj_delta.set(delta)
        return acted

    def _do_set(self, knob: Knob, proposal, now: float) -> str:
        before = knob.value
        status, detail = knob.propose(proposal.target, now)
        if status == "applied":
            self._m_decisions.inc()
            self._trace(
                knob, "set", before, knob.value, proposal.reason
            )
        elif status == "refused":
            self._m_refused.inc()
            self._trace(knob, "refused", before, before, detail)
        return status

    def _do_revert(self, knob: Knob, proposal, now: float) -> str:
        before = knob.value
        restored = knob.revert(now)
        if restored is None:
            return "noop"
        self._m_reverts.inc()
        self._trace(knob, "revert", before, restored, proposal.reason)
        return "reverted"

    def _trace(
        self, knob: Knob, kind: str, frm: float, to: float, reason: str
    ) -> None:
        self._tracer.instant(
            DECISION_EVENT,
            {
                "knob": knob.spec.name,
                "kind": kind,
                "from": frm,
                "to": to,
                "reason": reason,
            },
        )

    # -- thread lifecycle ----------------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name=self._name, daemon=True
        )
        self._thread.start()

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=timeout)
            self._thread = None

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.tick()
            except Exception:
                # Same contract as the per-binding guard: the control
                # plane is strictly optional and must never crash a run.
                continue


# -- standard knob sets ------------------------------------------------


def build_train_control(
    *,
    learner=None,
    traj_ring=None,
    checkpointer=None,
    batch_size: Optional[int] = None,
    steps_per_dispatch: Optional[int] = None,
    data_shards: int = 1,
    interval_s: float = 5.0,
    tolerance: float = 0.05,
    hysteresis: float = 0.01,
    cooldown_s: float = 30.0,
    checkpoint_overhead_budget: float = 0.01,
    staleness_budget_frames: float = 0.0,
    health_alert_gate: Optional[str] = "rho_saturation",
    allow_recompile: bool = False,
    recompile_cadence_s: float = 300.0,
    telemetry=None,
    tracer=None,
) -> ControlLoop:
    """The training-side loop: fused-K chunking hill-climbs on MFU,
    replay ``max_reuse`` tracks its staleness budget, checkpoint cadence
    tracks its overhead budget, ``replay_mix`` is a registered hot-apply
    surface (no default policy), and B/K hill-climb on the same MFU
    signal behind the recompile gate: with ``allow_recompile`` the gate
    opens at most once per ``recompile_cadence_s`` (the re-jit stall
    gets a full window to amortize); default-deny keeps every proposal
    audited but refused, exactly the pre-ISSUE-16 behavior.

    Every collaborator is optional: pass only the pieces a given run
    actually has and the rest of the knob set is simply absent.
    """
    loop = ControlLoop(
        interval_s=interval_s, telemetry=telemetry, tracer=tracer
    )
    settle = 2.0 * interval_s

    fused_k = int(steps_per_dispatch or 1)
    if learner is not None and fused_k > 1:
        # The chunked fused-dispatch fallback only exists for K > 1
        # learners (the [K, ...] superbatch axis it slices is absent at
        # K=1), so the knob is simply not offered below that.
        def _apply_chunk(v: float) -> None:
            learner._fused_fallback_k = int(v)

        loop.bind(
            Knob(
                KnobSpec(
                    "learner_fused_chunk",
                    lo=0,
                    hi=fused_k,
                    step=max(1, fused_k // 2),
                    settle_s=settle,
                    kind="int",
                    apply=_apply_chunk,
                    read=lambda: learner._fused_fallback_k,
                ),
                telemetry=telemetry,
            ),
            HillClimbPolicy(
                EwmaSignal(GaugeSignal("perf/mfu")),
                tolerance=tolerance,
                hysteresis=hysteresis,
                cooldown_s=cooldown_s,
            ),
        )

    if traj_ring is not None and getattr(traj_ring, "max_reuse", 0):
        hi_reuse = max(2, int(traj_ring.max_reuse))
        budget = staleness_budget_frames or 64.0 * hi_reuse

        def _apply_reuse(v: float) -> None:
            traj_ring.max_reuse = int(v)

        reuse_policy: Policy = SloPolicy(
            SloHeadroomSignal("replay/staleness_frames", budget),
            cooldown_s=cooldown_s,
        )
        if health_alert_gate:
            # Health-gated flywheel (ISSUE 19): while the named health
            # alert burns (rho saturation by default — most importance
            # weights clipping means extra reuse buys bias, not
            # progress), freeze the staleness policy and step reuse
            # toward 1. AlertSignal reads None when no health plane is
            # attached, which passes ticks straight through — wrapping
            # is free for runs without a HealthMonitor.
            reuse_policy = AlertGatedPolicy(
                reuse_policy,
                AlertSignal(health_alert_gate),
                cooldown_s=cooldown_s,
            )

        loop.bind(
            Knob(
                KnobSpec(
                    "replay_max_reuse",
                    lo=1,
                    hi=hi_reuse,
                    step=1,
                    settle_s=settle,
                    kind="int",
                    apply=_apply_reuse,
                    read=lambda: traj_ring.max_reuse,
                ),
                telemetry=telemetry,
            ),
            reuse_policy,
        )

        def _apply_mix(v: float) -> None:
            traj_ring.replay_mix = float(v)

        loop.add_knob(
            Knob(
                KnobSpec(
                    "replay_mix",
                    lo=0.0,
                    hi=1.0,
                    settle_s=settle,
                    apply=_apply_mix,
                    read=lambda: traj_ring.replay_mix,
                ),
                telemetry=telemetry,
            )
        )

    if checkpointer is not None and getattr(
        checkpointer, "_interval_steps", 0
    ):
        base = int(checkpointer._interval_steps)

        def _apply_ckpt(v: float) -> None:
            checkpointer._interval_steps = int(v)

        loop.bind(
            Knob(
                KnobSpec(
                    "checkpoint_interval_steps",
                    lo=base,
                    hi=10 * base,
                    step=base,
                    settle_s=settle,
                    kind="int",
                    apply=_apply_ckpt,
                    read=lambda: checkpointer._interval_steps,
                ),
                telemetry=telemetry,
            ),
            SloPolicy(
                HeadroomSignal(
                    CheckpointOverheadSignal(),
                    checkpoint_overhead_budget,
                ),
                grow_on_violation=True,
                cooldown_s=cooldown_s,
            ),
        )

    gate = RecompileGate(
        allow=allow_recompile, min_interval_s=recompile_cadence_s
    )
    # The B/K knobs share the MFU objective with the fused-chunk climb
    # (one signal, consistent direction) but each binding keeps its own
    # EWMA/cooldown state. The knobs carry recompile=True, so every
    # proposed move still runs through `gate` inside Knob.propose —
    # binding a policy changes who *proposes*, not what is *permitted*.
    if batch_size:
        # Under a data-parallel mesh every proposed B must stay
        # divisible by the data-axis size (the learner refuses a
        # non-divisible batch at construction), so the grid anchors and
        # steps in multiples of `data_shards` — per-shard-aware knob
        # grids (ISSUE 15). data_shards=1 reproduces the old grid.
        n = max(1, int(data_shards))

        def _q(v: int) -> int:  # round up to a shard multiple, >= n
            return max(n, ((int(v) + n - 1) // n) * n)

        loop.bind(
            Knob(
                KnobSpec(
                    "batch_size",
                    # Grid anchored at B/2 so the live B is a grid
                    # point (lo=1 + step=B/2 quantized 8 -> 9).
                    lo=_q(max(1, batch_size // 2)),
                    hi=max(2.0 * n, 4.0 * batch_size),
                    step=_q(max(1, batch_size // 2)),
                    # Recompiles need the full cadence window to judge,
                    # not the hot-apply settle.
                    settle_s=recompile_cadence_s,
                    kind="int",
                    recompile=True,
                ),
                gate=gate,
                initial=batch_size,
                telemetry=telemetry,
            ),
            HillClimbPolicy(
                EwmaSignal(GaugeSignal("perf/mfu")),
                tolerance=tolerance,
                hysteresis=hysteresis,
                cooldown_s=max(cooldown_s, recompile_cadence_s),
            ),
        )
    if steps_per_dispatch:
        loop.bind(
            Knob(
                KnobSpec(
                    "steps_per_dispatch",
                    lo=1,
                    # Ceiling tracks the superbatch ring's sizing, not a
                    # multiple of the configured K: the feed path can
                    # deliver up to SUPERBATCH_MAX_K per dispatch.
                    hi=float(
                        max(SUPERBATCH_MAX_K, 2 * steps_per_dispatch)
                    ),
                    step=1,
                    settle_s=recompile_cadence_s,
                    kind="int",
                    recompile=True,
                ),
                gate=gate,
                initial=steps_per_dispatch,
                telemetry=telemetry,
            ),
            HillClimbPolicy(
                EwmaSignal(GaugeSignal("perf/mfu")),
                tolerance=tolerance,
                hysteresis=hysteresis,
                cooldown_s=max(cooldown_s, recompile_cadence_s),
            ),
        )
    return loop


def build_serving_control(
    *,
    server=None,
    fleet=None,
    slo_ms: float = 25.0,
    interval_s: float = 1.0,
    cooldown_s: float = 2.0,
    telemetry=None,
    tracer=None,
) -> ControlLoop:
    """The serving-side loop: both latency knobs track the request-wait
    p99 against the SLO budget. Under violation the coalescing window
    shrinks and the wave-formation cap shrinks (smaller, sooner waves);
    with ample headroom they relax back toward the configured maxima for
    better batching efficiency. ``max_batch`` here is the wave-formation
    cap only — padding stays at the fixed ``pad_batch``, so no value the
    controller picks can trigger a re-jit.

    Pass `server` for the single-replica shape (knob names unchanged:
    `serving_max_wait_ms` / `serving_max_batch`), or `fleet` to bind the
    same pair PER REPLICA (`serving_max_wait_ms_r0`, ...). Per-replica
    binding is deliberate: replicas drain/die independently, so one
    shared knob would keep retuning a replica that is not taking
    traffic. All replicas track the shared request-wait p99 signal (the
    wave path aggregates across replicas into one registry)."""
    if (server is None) == (fleet is None):
        raise ValueError(
            "build_serving_control needs exactly one of server= / fleet="
        )
    loop = ControlLoop(
        interval_s=interval_s, telemetry=telemetry, tracer=tracer
    )
    targets = (
        [(server, "")]
        if fleet is None
        else [(rep.server, f"_{rep.name}") for rep in fleet.replicas()]
    )
    for srv, suffix in targets:
        _bind_serving_knobs(
            loop,
            srv,
            suffix,
            slo_ms=slo_ms,
            interval_s=interval_s,
            cooldown_s=cooldown_s,
            telemetry=telemetry,
        )
    return loop


def _bind_serving_knobs(
    loop: ControlLoop,
    server,
    suffix: str,
    *,
    slo_ms: float,
    interval_s: float,
    cooldown_s: float,
    telemetry,
) -> None:
    pad = server.pad_batch
    wait0 = server.max_wait_s

    loop.bind(
        Knob(
            KnobSpec(
                f"serving_max_wait_ms{suffix}",
                lo=0.0,
                hi=max(1e-3, wait0) * 1e3,
                step=max(1e-3, wait0) * 1e3 / 4.0,
                settle_s=interval_s,
                apply=lambda v: server.set_max_wait_s(v * 1e-3),
                read=lambda: server.max_wait_s * 1e3,
            ),
            telemetry=telemetry,
        ),
        SloPolicy(
            SloHeadroomSignal("serving/request_wait_ms_p99", slo_ms),
            cooldown_s=cooldown_s,
        ),
    )
    if pad > 1:
        loop.bind(
            Knob(
                KnobSpec(
                    f"serving_max_batch{suffix}",
                    lo=1,
                    hi=pad,
                    step=max(1, pad // 4),
                    settle_s=interval_s,
                    kind="int",
                    apply=server.set_max_batch,
                    read=lambda: server.max_batch,
                ),
                telemetry=telemetry,
            ),
            SloPolicy(
                SloHeadroomSignal(
                    "serving/request_wait_ms_p99", slo_ms
                ),
                cooldown_s=cooldown_s,
            ),
        )
