"""Control plane tests (ISSUE 12): knob bounds/quantization, the
recompile gate, signal adapters, the three policy shapes (hill climb
with guardrail reverts, target map, SLO bang-bang), the ControlLoop's
decision accounting + flight-recorder audit trail, the standard
train/serving knob sets, and the --control CLI roundtrip.

Everything here drives ``ControlLoop.tick(now=...)`` with a synthetic
clock — no control thread, no sleeps — matching the doctor self-check's
deterministic style; the standing-scenarios test at the end puts a real
env pool and a threaded server under the same clock.
"""

import pytest

from torched_impala_tpu.control import (
    ControlLoop,
    DECISION_EVENT,
    CheckpointOverheadSignal,
    EwmaSignal,
    FnSignal,
    GaugeSignal,
    HillClimbPolicy,
    Knob,
    KnobSet,
    KnobSpec,
    Proposal,
    RateSignal,
    RecompileGate,
    SloHeadroomSignal,
    SloPolicy,
    TargetMapPolicy,
    build_serving_control,
    build_train_control,
)
from torched_impala_tpu.telemetry import FlightRecorder, Registry


def _spec(name="k", lo=0.0, hi=8.0, **kw):
    return KnobSpec(name, lo=lo, hi=hi, **kw)


def _knob(reg=None, **kw):
    kw.setdefault("initial", 4.0)
    spec_kw = {
        k: kw.pop(k)
        for k in ("name", "lo", "hi", "step", "settle_s", "kind",
                  "recompile", "apply", "read")
        if k in kw
    }
    return Knob(
        _spec(**spec_kw),
        telemetry=reg if reg is not None else Registry(),
        **kw,
    )


def _decisions(rec):
    """Oldest-first (kind, lineage) of control/decision instants."""
    return [
        (r[5].get("kind"), r[5])
        for r in rec.tail()
        if r[3] == DECISION_EVENT
    ]


# ---- KnobSpec ---------------------------------------------------------


class TestKnobSpec:
    def test_name_grammar_enforced(self):
        for bad in ("Bad", "9lead", "has-dash", "has/slash", ""):
            with pytest.raises(ValueError):
                _spec(name=bad)
        _spec(name="ok_name_2")  # and the happy path parses

    def test_bounds_step_kind_validation(self):
        with pytest.raises(ValueError):
            _spec(lo=4.0, hi=4.0)
        with pytest.raises(ValueError):
            _spec(lo=5.0, hi=1.0)
        with pytest.raises(ValueError):
            _spec(step=-1.0)
        with pytest.raises(ValueError):
            _spec(kind="bool")

    def test_clamp_quantizes_to_grid_and_bounds(self):
        s = _spec(lo=1.0, hi=9.0, step=2.0)
        assert s.clamp(4.2) == 5.0  # nearest grid point 1+2k
        assert s.clamp(3.9) == 3.0
        assert s.clamp(100.0) == 9.0
        assert s.clamp(-100.0) == 1.0
        si = _spec(lo=0, hi=10, kind="int")
        assert si.clamp(3.6) == 4.0
        assert isinstance(si.clamp(3.6), float)

    def test_default_step(self):
        assert _spec(step=2.0).default_step() == 2.0
        assert _spec(lo=0.0, hi=8.0).default_step() == 1.0  # range/8
        # int knobs always move by at least 1
        assert _spec(lo=0, hi=4, kind="int").default_step() == 1.0


# ---- RecompileGate ----------------------------------------------------


class TestRecompileGate:
    def test_default_deny(self):
        ok, reason = RecompileGate().check(now=0.0)
        assert not ok and "disabled" in reason

    def test_min_interval_amortization(self):
        g = RecompileGate(allow=True, min_interval_s=300.0)
        ok, _ = g.check(now=0.0)
        assert ok
        g.record(now=0.0)
        ok, reason = g.check(now=100.0)
        assert not ok and "min interval" in reason
        ok, _ = g.check(now=301.0)
        assert ok


# ---- Knob -------------------------------------------------------------


class TestKnob:
    def test_needs_initial_or_read(self):
        with pytest.raises(ValueError):
            Knob(_spec(), telemetry=Registry())

    def test_propose_applies_then_noops(self):
        reg = Registry()
        applied = []
        k = _knob(reg, apply=applied.append)
        status, detail = k.propose(6.0, now=1.0)
        assert status == "applied" and applied == [6.0]
        assert k.value == 6.0
        assert reg.snapshot()["telemetry/control/knob_k"] == 6.0
        status, _ = k.propose(6.0, now=2.0)
        assert status == "noop" and applied == [6.0]

    def test_int_apply_receives_int(self):
        applied = []
        k = _knob(kind="int", apply=applied.append)
        k.propose(6.4, now=0.0)
        assert applied == [6] and isinstance(applied[0], int)

    def test_revert_is_one_level(self):
        k = _knob()
        k.propose(6.0, now=0.0)
        assert k.revert(now=1.0) == 4.0
        assert k.value == 4.0
        assert k.revert(now=2.0) is None  # nothing left to undo

    def test_recompile_knob_refused_by_default(self):
        k = _knob(recompile=True)
        status, reason = k.propose(8.0, now=0.0)
        assert status == "refused" and "recompile-gated" in reason
        assert k.value == 4.0

    def test_recompile_knob_applies_when_allowed(self):
        k = Knob(
            _spec(recompile=True),
            gate=RecompileGate(allow=True),
            initial=4.0,
            telemetry=Registry(),
        )
        assert k.propose(8.0, now=0.0)[0] == "applied"
        # gate recorded the re-jit: immediate second move refused
        assert k.propose(2.0, now=1.0)[0] == "refused"

    def test_value_rereads_live_object(self):
        box = {"v": 4.0}
        k = _knob(read=lambda: box["v"], initial=None)
        box["v"] = 7.0  # some other actor moved the live value
        assert k.value == 7.0


class TestKnobSet:
    def test_registry_semantics(self):
        ks = KnobSet()
        a = ks.register(_knob(name="a"))
        ks.register(_knob(name="b", initial=1.0))
        assert ks["a"] is a and "a" in ks and len(ks) == 2
        assert ks.names() == ["a", "b"]
        assert ks.snapshot() == {"a": 4.0, "b": 1.0}
        with pytest.raises(ValueError):
            ks.register(_knob(name="a"))


# ---- Signals ----------------------------------------------------------


class TestSignals:
    def test_gauge_signal_reads_snapshot_key(self):
        s = GaugeSignal("perf/mfu", scale=100.0)
        assert s.read({"telemetry/perf/mfu": 0.42}, 0.0) == 42.0
        assert s.read({}, 0.0) is None
        assert s.read({"telemetry/perf/mfu": float("nan")}, 0.0) is None

    def test_fn_signal(self):
        assert FnSignal(lambda: 3.0).read({}, 0.0) == 3.0
        assert FnSignal(lambda: None).read({}, 0.0) is None
        assert FnSignal(lambda: float("nan")).read({}, 0.0) is None

    def test_ewma_signal_smooths_and_holds(self):
        s = EwmaSignal(GaugeSignal("perf/mfu"), alpha=0.5)
        assert s.read({"telemetry/perf/mfu": 1.0}, 0.0) == 1.0
        assert s.read({"telemetry/perf/mfu": 3.0}, 1.0) == 2.0
        # missing sample: hold the smoothed value instead of None
        assert s.read({}, 2.0) == 2.0

    def test_rate_signal_primes_then_rates(self):
        s = RateSignal("learner/steps")
        assert s.read({"telemetry/learner/steps": 10.0}, 0.0) is None
        assert s.read({"telemetry/learner/steps": 30.0}, 2.0) == 10.0

    def test_slo_headroom_sign_and_validation(self):
        s = SloHeadroomSignal("serving/request_wait_ms_p99", 20.0)
        assert s.read(
            {"telemetry/serving/request_wait_ms_p99": 10.0}, 0.0
        ) == pytest.approx(0.5)
        assert s.read(
            {"telemetry/serving/request_wait_ms_p99": 30.0}, 0.0
        ) == pytest.approx(-0.5)
        with pytest.raises(ValueError):
            SloHeadroomSignal("x/y", 0.0)

    def test_checkpoint_overhead_fraction(self):
        s = CheckpointOverheadSignal()
        snap1 = {
            "telemetry/resilience/checkpoint_save_ms_ms": 100.0,
            "telemetry/resilience/checkpoint_saves": 1.0,
        }
        assert s.read(snap1, 0.0) is None  # rate still priming
        snap2 = dict(snap1, **{
            "telemetry/resilience/checkpoint_saves": 3.0,
        })
        # 2 saves over 10 s at 100 ms each = 2% of wall-clock
        assert s.read(snap2, 10.0) == pytest.approx(0.02)


# ---- Policies ---------------------------------------------------------


def _hill(signal_box, **kw):
    kw.setdefault("tolerance", 0.05)
    kw.setdefault("hysteresis", 0.01)
    kw.setdefault("cooldown_s", 10.0)
    return HillClimbPolicy(FnSignal(lambda: signal_box["obj"]), **kw)


class TestHillClimbPolicy:
    def test_climbs_then_waits_out_settle(self):
        box = {"obj": 1.0}
        pol = _hill(box)
        knob = _knob(step=1.0, settle_s=5.0)
        p = pol.tick({}, 0.0, knob)
        assert p is not None and p.kind == "set" and p.target == 5.0
        knob.propose(p.target, now=0.0)
        pol.observe_result("applied", 0.0)
        assert pol.tick({}, 2.0, knob) is None  # inside settle window
        # judging tick: obj unchanged -> commit, flip direction
        assert pol.tick({}, 6.0, knob) is None
        p2 = pol.tick({}, 7.0, knob)
        assert p2 is not None and p2.target == 4.0  # now climbing down

    def test_guardrail_reverts_regression_and_cools_down(self):
        box = {"obj": 1.0}
        pol = _hill(box)
        knob = _knob(step=1.0, settle_s=2.0)
        p = pol.tick({}, 0.0, knob)
        knob.propose(p.target, now=0.0)
        pol.observe_result("applied", 0.0)
        box["obj"] = 0.5  # >5% regression within the settle window
        p = pol.tick({}, 3.0, knob)
        assert p is not None and p.kind == "revert"
        assert pol.last_objective_delta == pytest.approx(-0.5)
        knob.revert(3.0)
        pol.observe_result("reverted", 3.0)
        assert pol.tick({}, 4.0, knob) is None  # cooling down
        assert pol.tick({}, 14.0, knob) is not None  # cooldown over

    def test_hysteresis_band_flips_direction(self):
        box = {"obj": 1.0}
        pol = _hill(box)
        knob = _knob(step=1.0, settle_s=1.0)
        p = pol.tick({}, 0.0, knob)
        assert p.target == 5.0  # first move is upward
        knob.propose(p.target, now=0.0)
        pol.observe_result("applied", 0.0)
        box["obj"] = 1.001  # inside the 1% hysteresis band: didn't pay
        assert pol.tick({}, 2.0, knob) is None  # commit (no revert)
        p = pol.tick({}, 3.0, knob)
        assert p.kind == "set" and p.target == 4.0  # flipped downward

    def test_turns_around_at_bounds(self):
        box = {"obj": 1.0}
        pol = _hill(box)
        knob = _knob(lo=0.0, hi=4.0, step=1.0, initial=4.0)
        p = pol.tick({}, 0.0, knob)
        assert p is not None and p.target == 3.0  # +1 clamps: went -1

    def test_holds_without_signal(self):
        pol = HillClimbPolicy(FnSignal(lambda: None))
        assert pol.tick({}, 0.0, _knob()) is None

    def test_parameter_validation(self):
        with pytest.raises(ValueError):
            HillClimbPolicy(FnSignal(lambda: 0.0), tolerance=0.0)
        with pytest.raises(ValueError):
            HillClimbPolicy(FnSignal(lambda: 0.0), hysteresis=-0.1)


class TestTargetMapPolicy:
    def test_maps_signal_through_line(self):
        pol = TargetMapPolicy(
            FnSignal(lambda: 0.04), slope=7.5, base=1.0
        )
        knob = _knob(lo=0.25, hi=1.0, initial=1.0)
        p = pol.tick({}, 0.0, knob)
        assert p is not None and p.target == pytest.approx(0.7)
        knob.propose(p.target)
        # same signal again: clamped target == current -> hold
        assert pol.tick({}, 1.0, knob) is None

    def test_clamps_into_knob_bounds(self):
        pol = TargetMapPolicy(
            FnSignal(lambda: 1.0), slope=7.5, base=1.0
        )
        knob = _knob(lo=0.25, hi=1.0, initial=1.0)
        p = pol.tick({}, 0.0, knob)
        knob.propose(p.target)
        assert knob.value == 0.25  # floor, not -6.5


class TestSloPolicy:
    def _h(self, value):
        return FnSignal(lambda: value)

    def test_bang_bang_with_hold_band(self):
        knob = _knob(lo=0.0, hi=8.0, step=2.0)
        shrink = SloPolicy(self._h(-0.2)).tick({}, 0.0, knob)
        assert shrink.target == 2.0  # violating: one step down
        relax = SloPolicy(self._h(0.9)).tick({}, 0.0, knob)
        assert relax.target == 6.0  # ample headroom: one step up
        assert SloPolicy(self._h(0.3)).tick({}, 0.0, knob) is None

    def test_grow_on_violation_inverts(self):
        knob = _knob(lo=0.0, hi=8.0, step=2.0)
        grow = SloPolicy(self._h(-0.2), grow_on_violation=True)
        assert grow.tick({}, 0.0, knob).target == 6.0
        back = SloPolicy(self._h(0.9), grow_on_violation=True)
        assert back.tick({}, 0.0, knob).target == 2.0

    def test_cooldown_after_apply(self):
        pol = SloPolicy(self._h(-0.2), cooldown_s=5.0)
        knob = _knob(step=2.0)
        assert pol.tick({}, 0.0, knob) is not None
        pol.observe_result("applied", 0.0)
        assert pol.tick({}, 2.0, knob) is None
        assert pol.tick({}, 6.0, knob) is not None

    def test_holds_at_bound(self):
        pol = SloPolicy(self._h(-0.5))
        knob = _knob(lo=0.0, hi=8.0, step=2.0, initial=0.0)
        assert pol.tick({}, 0.0, knob) is None  # already at the floor

    def test_relax_headroom_validation(self):
        with pytest.raises(ValueError):
            SloPolicy(self._h(0.0), relax_headroom=1.5)


# ---- ControlLoop ------------------------------------------------------


class TestControlLoop:
    def _loop(self, interval_s=1.0):
        reg = Registry()
        rec = FlightRecorder(capacity=256)
        return ControlLoop(
            interval_s=interval_s, telemetry=reg, tracer=rec
        ), reg, rec

    def test_interval_validation(self):
        with pytest.raises(ValueError):
            ControlLoop(interval_s=0.0, telemetry=Registry(),
                        tracer=FlightRecorder(capacity=64))

    def test_applied_decision_audited(self):
        loop, reg, rec = self._loop()
        box = {"obj": 1.0}
        loop.bind(
            _knob(reg, step=1.0, settle_s=2.0),
            _hill(box),
        )
        assert loop.tick(now=0.0) == 1
        snap = reg.snapshot()
        assert snap["telemetry/control/decision_total"] == 1
        assert snap["telemetry/control/decision_ticks"] == 1
        assert snap["telemetry/control/knob_k"] == 5.0
        (kind, args), = _decisions(rec)
        assert kind == "set"
        assert (args["knob"], args["from"], args["to"]) == ("k", 4.0, 5.0)
        assert "hill-climb" in args["reason"]

    def test_guardrail_revert_full_cycle(self):
        """Seeded regression: apply at t=0, objective tanks, the judging
        tick reverts, and every leg lands in counters + the recorder."""
        loop, reg, rec = self._loop()
        box = {"obj": 1.0}
        loop.bind(_knob(reg, step=1.0, settle_s=2.0), _hill(box))
        loop.tick(now=0.0)  # 4 -> 5
        box["obj"] = 0.5
        assert loop.tick(now=3.0) == 1  # judged: revert 5 -> 4
        snap = reg.snapshot()
        assert snap["telemetry/control/decision_total"] == 1
        assert snap["telemetry/control/revert_total"] == 1
        assert snap["telemetry/control/knob_k"] == 4.0
        assert snap["telemetry/control/objective_delta"] == pytest.approx(
            -0.5
        )
        kinds = [k for k, _ in _decisions(rec)]
        assert kinds == ["set", "revert"]
        assert _decisions(rec)[-1][1]["to"] == 4.0

    def test_refused_recompile_audited(self):
        loop, reg, rec = self._loop()
        gated = Knob(
            _spec(name="batch", lo=1, hi=64, step=1, kind="int",
                  recompile=True),
            gate=RecompileGate(allow=False),
            initial=8,
            telemetry=reg,
        )
        loop.bind(gated, SloPolicy(FnSignal(lambda: -1.0),
                                   grow_on_violation=True))
        assert loop.tick(now=0.0) == 0  # refused counts as not-acted
        snap = reg.snapshot()
        assert snap["telemetry/control/decision_refused"] == 1
        assert snap["telemetry/control/decision_total"] == 0
        assert snap["telemetry/control/knob_batch"] == 8.0
        (kind, args), = _decisions(rec)
        assert kind == "refused" and args["from"] == args["to"] == 8.0
        assert "recompile-gated" in args["reason"]

    def test_broken_policy_does_not_take_down_siblings(self):
        loop, reg, _ = self._loop()

        class Exploding(SloPolicy):
            def tick(self, snap, now, knob):
                raise RuntimeError("boom")

        loop.bind(_knob(reg, name="bad"),
                  Exploding(FnSignal(lambda: -1.0)))
        loop.bind(_knob(reg, name="good", step=2.0),
                  SloPolicy(FnSignal(lambda: -1.0)))
        assert loop.tick(now=0.0) == 1  # sibling still acted
        assert reg.snapshot()["telemetry/control/knob_good"] == 2.0

    def test_add_knob_is_audit_only_surface(self):
        loop, reg, _ = self._loop()
        loop.add_knob(_knob(reg, name="surface"))
        assert "surface" in loop.knobs
        assert loop.tick(now=0.0) == 0  # no binding: nothing moves
        assert reg.snapshot()["telemetry/control/knob_surface"] == 4.0

    def test_thread_start_stop_idempotent(self):
        loop, _, _ = self._loop(interval_s=0.01)
        loop.start()
        loop.start()  # second start is a no-op
        loop.stop()
        assert loop._thread is None
        loop.stop()  # stop after stop is safe


# ---- standard knob sets ----------------------------------------------


class _FakeRing:
    max_reuse = 4
    replay_mix = 0.25


class _FakeCkpt:
    _interval_steps = 50


class _FakeLearner:
    _fused_fallback_k = 0


class TestBuildTrainControl:
    def test_full_composition(self):
        loop = build_train_control(
            learner=_FakeLearner(),
            traj_ring=_FakeRing(),
            checkpointer=_FakeCkpt(),
            batch_size=32,
            steps_per_dispatch=4,
            telemetry=Registry(),
            tracer=FlightRecorder(capacity=64),
        )
        assert loop.knobs.names() == [
            "batch_size",
            "checkpoint_interval_steps",
            "learner_fused_chunk",
            "replay_max_reuse",
            "replay_mix",
            "steps_per_dispatch",
        ]

    def test_fused_chunk_absent_for_k1_learner(self):
        # A K=1 learner has no [K, ...] superbatch axis to chunk —
        # binding the knob there once sliced the time axis mid-run
        # (caught live: --control auto + --traj-ring crashed the
        # learner with a broadcast shape mismatch).
        loop = build_train_control(
            learner=_FakeLearner(),
            steps_per_dispatch=1,
            telemetry=Registry(),
            tracer=FlightRecorder(capacity=64),
        )
        assert "learner_fused_chunk" not in loop.knobs.names()
        assert "steps_per_dispatch" in loop.knobs.names()

    def test_fused_chunk_bounded_by_k(self):
        loop = build_train_control(
            learner=_FakeLearner(),
            steps_per_dispatch=4,
            telemetry=Registry(),
            tracer=FlightRecorder(capacity=64),
        )
        spec = loop.knobs["learner_fused_chunk"].spec
        assert (spec.lo, spec.hi, spec.step) == (0, 4, 2)
        assert spec.clamp(8) == 4.0

    def test_steps_per_dispatch_ceiling_tracks_superbatch_max(self):
        # ISSUE 13: the superbatch ring delivers up to SUPERBATCH_MAX_K
        # per dispatch, so the gated K knob's ceiling derives from it —
        # not from a multiple of the configured K (which pinned the old
        # fused ceiling at 4*K=8 for the default K=2).
        from torched_impala_tpu.control.loop import SUPERBATCH_MAX_K

        reg = Registry()
        loop = build_train_control(
            steps_per_dispatch=2,
            allow_recompile=True,
            cooldown_s=0.0,
            telemetry=reg,
            tracer=FlightRecorder(capacity=256),
        )
        knob = loop.knobs["steps_per_dispatch"]
        assert knob.spec.hi == float(SUPERBATCH_MAX_K) > 8.0

        # With recompiles allowed, a hill climb on a monotone objective
        # must actually reach past the old K=8 ceiling.
        box = {"obj": 1.0}
        loop.bind(
            knob,
            HillClimbPolicy(
                FnSignal(lambda: box["obj"]),
                tolerance=0.05,
                hysteresis=0.01,
                cooldown_s=0.0,
            ),
        )
        now, peak = 0.0, 0.0
        for _ in range(60):
            loop.tick(now=now)
            # Outwait the recompile gate's 300s amortization window and
            # keep the objective visibly improving after every apply.
            now += 301.0
            box["obj"] *= 1.5
            peak = max(peak, knob.value)
        # The climb tops out at the new ceiling (then probes back down —
        # a monotone objective judges every move a win).
        assert peak == float(SUPERBATCH_MAX_K) > 8.0

    def test_fused_chunk_hill_climbs_past_old_k8_ceiling(self):
        # A SUPERBATCH_MAX_K learner's chunk knob spans (0, 16, 8): the
        # built-in MFU hill climb reaches full-K chunking (> 8) when the
        # signal rewards it.
        from torched_impala_tpu.control.loop import SUPERBATCH_MAX_K

        lr = _FakeLearner()
        reg = Registry()
        mfu = reg.gauge("perf/mfu")
        loop = build_train_control(
            learner=lr,
            steps_per_dispatch=SUPERBATCH_MAX_K,
            cooldown_s=0.0,
            telemetry=reg,
            tracer=FlightRecorder(capacity=256),
        )
        spec = loop.knobs["learner_fused_chunk"].spec
        assert (spec.lo, spec.hi, spec.step) == (0, 16, 8)
        now, obj, peak = 0.0, 0.1, 0
        for _ in range(50):
            mfu.set(obj)
            loop.tick(now=now)
            now += 60.0
            obj *= 1.5  # every probe judged a clear win
            peak = max(peak, lr._fused_fallback_k)
        assert peak == SUPERBATCH_MAX_K > 8

    def test_collaborators_optional(self):
        loop = build_train_control(
            telemetry=Registry(), tracer=FlightRecorder(capacity=64)
        )
        assert len(loop.knobs) == 0

    def test_shape_knobs_default_deny(self):
        loop = build_train_control(
            batch_size=32,
            telemetry=Registry(),
            tracer=FlightRecorder(capacity=64),
        )
        status, reason = loop.knobs["batch_size"].propose(64, now=0.0)
        assert status == "refused" and "recompile-gated" in reason

    def test_reuse_knob_applies_to_ring(self):
        ring = _FakeRing()
        loop = build_train_control(
            traj_ring=ring,
            telemetry=Registry(),
            tracer=FlightRecorder(capacity=64),
        )
        loop.knobs["replay_max_reuse"].propose(2, now=0.0)
        assert ring.max_reuse == 2
        loop.knobs["replay_mix"].propose(0.5, now=0.0)
        assert ring.replay_mix == 0.5


class TestBuildServingControl:
    def _server(self):
        jax = pytest.importorskip("jax")
        jax.config.update("jax_platforms", "cpu")
        import numpy as np

        from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
        from torched_impala_tpu.runtime.param_store import ParamStore
        from torched_impala_tpu.serving import (
            PolicyServer,
            VersionRegistry,
        )

        agent = Agent(
            ImpalaNet(num_actions=3, torso=MLPTorso(hidden_sizes=(8,)))
        )
        params = agent.init_params(
            jax.random.key(0), np.zeros((4,), np.float32)
        )
        store = ParamStore()
        store.publish(0, params)
        registry = VersionRegistry.serving_latest(
            store, telemetry=Registry()
        )
        return PolicyServer(
            agent=agent,
            registry=registry,
            example_obs=np.zeros((4,), np.float32),
            max_clients=8,
            max_batch=4,
            max_wait_s=0.004,
            telemetry=Registry(),
        )

    def test_serving_knobs_over_real_server(self):
        server = self._server()
        reg = Registry()
        loop = build_serving_control(
            server=server,
            slo_ms=25.0,
            telemetry=reg,
            tracer=FlightRecorder(capacity=64),
        )
        assert loop.knobs.names() == [
            "serving_max_batch",
            "serving_max_wait_ms",
        ]
        # wait knob round-trips through the server in ms
        loop.knobs["serving_max_wait_ms"].propose(2.0, now=0.0)
        assert server.max_wait_s == pytest.approx(2e-3)
        # batch knob moves the wave cap but NEVER the jit pad width
        pad0 = server.pad_batch
        loop.knobs["serving_max_batch"].propose(1, now=0.0)
        assert server.max_batch == 1 and server.pad_batch == pad0

    def test_set_max_batch_clamps_to_pad(self):
        server = self._server()
        server.set_max_batch(999)
        assert server.max_batch == server.pad_batch
        server.set_max_batch(0)
        assert server.max_batch == 1

    def test_slo_violation_shrinks_wait_window(self):
        server = self._server()
        reg = Registry()
        wait_p99 = reg.gauge("serving/request_wait_ms_p99")
        wait_p99.set(40.0)  # violating the 25 ms SLO
        loop = build_serving_control(
            server=server,
            slo_ms=25.0,
            telemetry=reg,
            tracer=FlightRecorder(capacity=64),
        )
        wait0 = server.max_wait_s
        assert loop.tick(now=0.0) >= 1
        assert server.max_wait_s < wait0


# ---- the two standing scenarios, end to end ---------------------------


def _scripted_env(seed, env_index=None):
    from torched_impala_tpu.envs.fake import ScriptedEnv

    return ScriptedEnv(episode_len=5)


class TestStandingScenarios:
    def test_stragglers_shrink_waves_and_bursts_shrink_the_window(self):
        """Scenario 1: a 4x2 async env pool with ready_fraction="auto"
        is shown one stall of 25 ms in every ten worker steps (scripted:
        the submit stamp is backdated, nothing sleeps); its knob moves
        off the 0.5 default and ends under it. Scenario 2: bursts of 4
        clients against a server whose coalescing window is 10 ms and
        whose wave holds 16, so every burst waits the whole window; the
        serving controller, ticked on a synthetic clock between bursts
        against a 2 ms SLO on the request wait, takes decisions and ends
        with a window under the configured one."""
        import time

        import jax
        import numpy as np

        from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
        from torched_impala_tpu.runtime.env_pool import ProcessEnvPool
        from torched_impala_tpu.runtime.param_store import ParamStore
        from torched_impala_tpu.serving import (
            InProcessClient,
            PolicyServer,
            VersionRegistry,
        )

        reg = Registry()
        pool = ProcessEnvPool(
            env_factory=_scripted_env,
            num_workers=4,
            envs_per_worker=2,
            obs_shape=(4,),
            obs_dtype=np.float32,
            mode="async",
            ready_fraction="auto",
            telemetry=reg,
        )
        try:
            assert pool.ready_fraction == 0.5
            for i in range(32 + 320):
                stall = i >= 32 and i % 10 == 0
                w = i % 4
                pool._submit_t[w] = time.monotonic() - (
                    0.025 if stall else 1e-3
                )
                pool._observe_step(w)
            assert pool.AUTO_FRACTION_MIN <= pool.ready_fraction < 0.5
            assert reg.gauge(
                "control/knob_pool_ready_fraction"
            ).value == pytest.approx(pool.ready_fraction)
        finally:
            pool.close()

        burst, cap, wait0_s, slo_ms, rounds = 4, 16, 0.010, 2.0, 12
        obs_dim = 8
        agent = Agent(
            ImpalaNet(num_actions=4, torso=MLPTorso(hidden_sizes=(64,)))
        )
        params = agent.init_params(
            jax.random.key(0), np.zeros((obs_dim,), np.float32)
        )
        sreg = Registry()
        store = ParamStore()
        store.publish(0, params)
        server = PolicyServer(
            agent=agent,
            registry=VersionRegistry.serving_latest(store, telemetry=sreg),
            example_obs=np.zeros((obs_dim,), np.float32),
            max_clients=cap,
            max_batch=cap,
            max_wait_s=wait0_s,
            telemetry=sreg,
        ).start()
        loop = build_serving_control(
            server=server,
            slo_ms=slo_ms,
            telemetry=sreg,
            tracer=FlightRecorder(capacity=256),
        )
        try:
            clients = [
                InProcessClient(server, greedy=True) for _ in range(burst)
            ]
            obs = np.random.default_rng(0).normal(
                size=(burst, obs_dim)
            ).astype(np.float32)
            for r in range(rounds + 1):
                cells = [
                    c.act_async(obs[i], r == 0)
                    for i, c in enumerate(clients)
                ]
                for cell in cells:
                    cell.result(timeout=120.0)
                # The clock strides past the policy's cooldown, so every
                # burst's evidence can move the knobs.
                loop.tick(now=10.0 * (r + 1))
            for c in clients:
                c.close()
        finally:
            server.close()
        assert sreg.counter("control/decision_total").value > 0
        assert server.max_wait_s < wait0_s


# ---- CLI / config roundtrip ------------------------------------------


class TestControlConfig:
    def test_cli_roundtrip(self):
        from torched_impala_tpu.run import build_config, parse_args

        args = parse_args(
            [
                "--config", "cartpole",
                "--control", "auto",
                "--control-interval", "2.5",
                "--fake-envs",
            ]
        )
        cfg = build_config(args)
        assert cfg.control.mode == "auto"
        assert cfg.control.interval_s == 2.5

    def test_preset_default_is_off(self):
        from torched_impala_tpu.run import build_config, parse_args

        cfg = build_config(
            parse_args(["--config", "cartpole", "--fake-envs"])
        )
        assert cfg.control.mode == "off"

    def test_validate_rejects_bad_values(self):
        import dataclasses

        from torched_impala_tpu.configs import ControlConfig

        with pytest.raises(ValueError):
            dataclasses.replace(
                ControlConfig(), mode="sometimes"
            ).validate()
        with pytest.raises(ValueError):
            dataclasses.replace(
                ControlConfig(), interval_s=0.0
            ).validate()
        ControlConfig().validate()
