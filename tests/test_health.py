"""Training-health diagnostics plane tests (ISSUE 19): closed-form
fixtures for the in-jit loss diagnostics, bit-parity of the
diagnostics-off path, the HealthMonitor's derived series + alert
firing + postmortem bundles round-tripped through tools/postmortem.py,
the AlertGatedPolicy flywheel gate, and the serving shadow-mismatch
windowed rate.

Everything time-dependent drives observe()/tick() with a synthetic
clock — no sleeps — matching the control and alert suites.
"""

import collections
import math
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torched_impala_tpu.control import (
    AlertGatedPolicy,
    AlertSignal,
    Knob,
    KnobSpec,
    Policy,
    Proposal,
)
from torched_impala_tpu.ops import losses as losses_lib
from torched_impala_tpu.ops.losses import ImpalaLossConfig
from torched_impala_tpu.runtime.learner import (
    BatchLineage,
    _health_param_groups,
)
from torched_impala_tpu.telemetry import FlightRecorder, Registry
from torched_impala_tpu.telemetry.health import (
    HealthMonitor,
    PostmortemWriter,
    health_slo_specs,
)


def _softmax(x):
    e = np.exp(x - x.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)


# ---- in-jit loss diagnostics: closed-form fixtures --------------------


class TestHealthDiagnosticsLogs:
    def _logs(self, **kw):
        T, B, A = 2, 2, 4
        defaults = dict(
            learner_logits=jnp.zeros((T, B, A)),
            behaviour_logits=jnp.zeros((T, B, A)),
            log_rhos=jnp.zeros((T, B)),
            values=jnp.zeros((T, B)),
            vs=jnp.zeros((T, B)),
            mask=jnp.ones((T, B)),
            config=ImpalaLossConfig(health_diagnostics=True),
        )
        defaults.update(kw)
        return {
            k: np.asarray(v)
            for k, v in losses_lib.health_diagnostics_logs(
                **defaults
            ).items()
        }

    def test_uniform_policy_entropy_and_zero_kl(self):
        logs = self._logs()
        np.testing.assert_allclose(
            logs["health_entropy_mean"], np.log(4.0), rtol=1e-6
        )
        np.testing.assert_allclose(
            logs["health_kl_behaviour_learner"], 0.0, atol=1e-6
        )

    def test_clip_fractions_and_logrho_moments(self):
        # rho > 1 exactly where log_rho > 0: entries 0.3 and 2.5.
        log_rhos = jnp.asarray([[0.0, 0.3], [-1.5, 2.5]])
        logs = self._logs(log_rhos=log_rhos)
        np.testing.assert_allclose(logs["health_clip_rho_frac"], 0.5)
        np.testing.assert_allclose(logs["health_clip_c_frac"], 0.5)
        lr = np.asarray(log_rhos).ravel()
        np.testing.assert_allclose(
            logs["health_clip_logrho_mean"], lr.mean(), rtol=1e-6
        )
        np.testing.assert_allclose(
            logs["health_clip_logrho_std"], lr.std(), rtol=1e-5
        )

    def test_logrho_histogram_bins_and_unit_mass(self):
        # Edges (-2,-1,-0.5,0,0.5,1,2): 0.0 and 0.3 -> bin4 [0,0.5),
        # -1.5 -> bin1 [-2,-1), 2.5 -> bin7 [2,inf).
        logs = self._logs(
            log_rhos=jnp.asarray([[0.0, 0.3], [-1.5, 2.5]])
        )
        bins = [
            float(logs[f"health_clip_logrho_bin{i}"]) for i in range(8)
        ]
        np.testing.assert_allclose(
            bins, [0.0, 0.25, 0.0, 0.0, 0.5, 0.0, 0.0, 0.25]
        )
        np.testing.assert_allclose(sum(bins), 1.0, rtol=1e-6)

    def test_explained_variance_closed_form(self):
        values = jnp.asarray([[1.0, 2.0], [3.0, 4.0]])
        vs = jnp.asarray([[2.0, 2.0], [3.0, 6.0]])
        logs = self._logs(values=values, vs=vs)
        err = np.asarray(vs - values).ravel()
        ref = 1.0 - err.var() / np.asarray(vs).ravel().var()
        np.testing.assert_allclose(logs["health_ev_value"], ref, rtol=1e-6)
        # Perfect baseline: values == vs -> EV = 1 exactly.
        perfect = self._logs(values=vs, vs=vs)
        np.testing.assert_allclose(perfect["health_ev_value"], 1.0)

    def test_masked_steps_are_excluded(self):
        # Garbage in the masked column must not move any statistic.
        mask = jnp.asarray([[1.0, 0.0], [1.0, 0.0]])
        garbage = jnp.asarray([[0.0, 1e6], [-1.0, -1e6]])
        logs = self._logs(log_rhos=garbage, mask=mask)
        valid = np.asarray([0.0, -1.0])
        np.testing.assert_allclose(
            logs["health_clip_logrho_mean"], valid.mean(), rtol=1e-6
        )
        np.testing.assert_allclose(logs["health_clip_rho_frac"], 0.0)


# ---- the loss entry point: presence, count, bit-parity ----------------


def _loss_inputs(seed=0, T=6, B=4, A=3):
    rng = np.random.default_rng(seed)
    return dict(
        target_logits=jnp.asarray(
            rng.normal(size=(T, B, A)), dtype=jnp.float32
        ),
        behaviour_logits=jnp.asarray(
            rng.normal(size=(T, B, A)), dtype=jnp.float32
        ),
        values=jnp.asarray(rng.normal(size=(T, B)), dtype=jnp.float32),
        bootstrap_value=jnp.asarray(
            rng.normal(size=(B,)), dtype=jnp.float32
        ),
        actions=jnp.asarray(rng.integers(0, A, size=(T, B))),
        rewards=jnp.asarray(rng.normal(size=(T, B)), dtype=jnp.float32),
        discounts=jnp.full((T, B), 0.99, dtype=jnp.float32),
    )


class TestImpalaLossHealthFamily:
    def test_on_emits_family_off_emits_none(self):
        inputs = _loss_inputs()
        on = losses_lib.impala_loss(
            config=ImpalaLossConfig(health_diagnostics=True), **inputs
        )
        keys = sorted(k for k in on.logs if k.startswith("health_"))
        # 4 clip stats + 8 histogram bins + entropy + KL + EV.
        assert len(keys) == 15, keys
        assert all(np.isfinite(float(on.logs[k])) for k in keys)
        mass = sum(
            float(on.logs[f"health_clip_logrho_bin{i}"]) for i in range(8)
        )
        assert mass == pytest.approx(1.0, rel=1e-5)
        off = losses_lib.impala_loss(
            config=ImpalaLossConfig(health_diagnostics=False), **inputs
        )
        assert not any(k.startswith("health_") for k in off.logs)

    def test_diagnostics_off_path_is_bit_identical(self):
        """The ISSUE 19 parity contract: the diagnostics are pure
        stop-gradient log extras — total loss and gradients are
        bit-identical with the flag on and off."""
        inputs = _loss_inputs(seed=1)

        def total(values, logits, cfg):
            kw = dict(inputs)
            kw["values"] = values
            kw["target_logits"] = logits
            return losses_lib.impala_loss(config=cfg, **kw).total

        grad = jax.jit(
            jax.value_and_grad(total, argnums=(0, 1)),
            static_argnums=(2,),
        )
        on_t, on_g = grad(
            inputs["values"],
            inputs["target_logits"],
            ImpalaLossConfig(health_diagnostics=True),
        )
        off_t, off_g = grad(
            inputs["values"],
            inputs["target_logits"],
            ImpalaLossConfig(health_diagnostics=False),
        )
        np.testing.assert_array_equal(np.asarray(on_t), np.asarray(off_t))
        for a, b in zip(on_g, off_g):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_health_param_groups_flax_tree_and_fallback():
    tree = {
        "params": {
            "Conv_0": {"kernel": np.ones(2)},
            "Dense_1": {"bias": np.ones(1)},
        }
    }
    groups = _health_param_groups(tree)
    assert set(groups) == {"conv_0", "dense_1"}
    assert groups["conv_0"] is tree["params"]["Conv_0"]
    # Non-flax containers fall back to one 'all' group.
    assert set(_health_param_groups(np.ones(3))) == {"all"}
    assert set(_health_param_groups({})) == {"all"}


# ---- HealthMonitor: derived series, firing, bundles -------------------


def _monitor(tmp_path, fast_window_s=5.0):
    reg = Registry()
    rec = FlightRecorder(capacity=64)
    pm = PostmortemWriter(str(tmp_path), recorder=rec)
    mon = HealthMonitor(
        specs=health_slo_specs(
            fast_window_s=fast_window_s, slow_window_s=10 * fast_window_s
        ),
        registry=reg,
        recorder=rec,
        postmortem=pm,
    )
    return mon, reg, rec


class TestHealthMonitor:
    def test_grad_spike_ratio_is_norm_over_ewma(self, tmp_path):
        mon, reg, _ = _monitor(tmp_path)
        for i in range(5):
            mon.observe({"grad_norm_unclipped": 1.0}, now=100.0 + i)
        mon.observe({"grad_norm_unclipped": 64.0}, now=105.0)
        snap = reg.snapshot()
        assert snap["telemetry/health/grad_spike_ratio"] == pytest.approx(
            64.0
        )

    def test_staleness_clip_correlation(self, tmp_path):
        mon, reg, _ = _monitor(tmp_path)
        for i in range(10):
            mon.observe(
                {"health_clip_rho_frac": 0.01 * i},
                lineage=types.SimpleNamespace(staleness=i),
                now=100.0 + i,
            )
        snap = reg.snapshot()
        assert snap["telemetry/health/staleness_clip_corr"] == (
            pytest.approx(1.0)
        )

    def test_entropy_collapse_fires_after_coverage_gate_and_bundles(
        self, tmp_path
    ):
        """The e2e acceptance scenario: a seeded entropy collapse
        sustains a breach, the alert fires exactly when the retained
        sample span reaches the fast window (never instantly), one
        bundle is published, and tools/postmortem.py round-trips it
        with the correct first-breach signal and lineage."""
        mon, reg, _ = _monitor(tmp_path, fast_window_s=5.0)
        lineage = BatchLineage(
            batch=7,
            lineage=("a0u12",),
            versions=(41,),
            reuse_count=2,
            staleness=12,
            ring_slot=5,
        )
        fired_at = None
        for i in range(14):
            fired = mon.observe(
                {"health_entropy_mean": 0.01, "num_steps": 100 + i},
                lineage=lineage,
                now=1000.0 + 0.5 * i,
            )
            if fired and fired_at is None:
                fired_at = i
                assert fired == ["entropy_collapse"]
        # Coverage gate: span >= fast_window_s first holds at sample 10
        # (t = 1005.0), so the sustained breach fires there — not on
        # the very first bad sample.
        assert fired_at == 10
        snap = reg.snapshot()
        assert snap["telemetry/alerts/firing_entropy_collapse"] == 1.0
        assert snap["telemetry/alerts/burn_rate_entropy_collapse"] > 1.0
        assert snap["telemetry/health/entropy_mean"] == pytest.approx(0.01)
        # First breach is the very first observation of the bad value.
        fb = mon.first_breach["entropy_collapse"]
        assert fb["t"] == 1000.0
        assert fb["key"] == "health/entropy_mean"
        assert fb["step"] == 100
        # One 0->1 transition -> exactly one bundle.
        assert len(mon.bundles) == 1

        from tools import postmortem as pm_tool

        bundles = pm_tool.list_bundles(str(tmp_path))
        assert bundles == mon.bundles
        bundle = pm_tool.load_bundle(bundles[0])
        m = bundle["manifest"]
        assert m["reason"] == "alert_entropy_collapse"
        assert m["firing"] == ["entropy_collapse"]
        assert pm_tool.first_breach_signal(m) == "entropy_collapse"
        assert m["lineage"]["reuse_count"] == 2
        assert m["lineage"]["staleness"] == 12
        # Snapshot rows carry the health gauge series.
        assert bundle["snapshots"], "bundle has no snapshot rows"
        assert any(
            "telemetry/health/entropy_mean" in row
            for row in bundle["snapshots"]
        )
        report = pm_tool.render_report(bundle)
        assert "FIRST BREACH: entropy_collapse" in report
        assert "health/entropy_mean" in report
        assert "reuse_count: 2" in report
        assert "staleness: 12" in report
        assert "Perfetto" in report

    def test_healthy_run_never_fires_or_bundles(self, tmp_path):
        mon, reg, _ = _monitor(tmp_path)
        for i in range(20):
            fired = mon.observe(
                {
                    "health_entropy_mean": 1.2,
                    "health_clip_rho_frac": 0.05,
                    "health_ev_value": 0.8,
                },
                now=1000.0 + 0.5 * i,
            )
            assert fired == []
        assert mon.bundles == []
        assert os.listdir(str(tmp_path)) == []

    def test_crash_bundle_written_once(self, tmp_path):
        mon, _, _ = _monitor(tmp_path)
        mon.observe({"health_entropy_mean": 0.8}, now=50.0)
        err = ValueError("boom in train step")
        path = mon.on_crash(err)
        assert path is not None and os.path.isdir(path)
        # One bundle per monitor lifetime: a teardown crash storm must
        # not spam bundles for the same root cause.
        assert mon.on_crash(ValueError("again")) is None

        from tools import postmortem as pm_tool

        bundle = pm_tool.load_bundle(path)
        assert bundle["manifest"]["reason"] == "crash"
        assert "boom in train step" in bundle["manifest"]["error"]
        report = pm_tool.render_report(bundle)
        assert "crash traceback:" in report
        assert "ValueError: boom in train step" in report

    def test_monitor_without_postmortem_is_safe(self):
        mon = HealthMonitor(registry=Registry(), postmortem=None)
        mon.observe({"health_entropy_mean": 0.5}, now=1.0)
        assert mon.on_crash(RuntimeError("x")) is None


def test_health_slo_spec_table_pinned():
    specs = {s.name: s for s in health_slo_specs()}
    assert set(specs) == {
        "entropy_collapse",
        "rho_saturation",
        "ev_collapse",
        "grad_norm_spike",
        "shadow_mismatch",
    }
    assert specs["entropy_collapse"].key == "health/entropy_mean"
    assert specs["entropy_collapse"].kind == "lower"
    assert specs["rho_saturation"].key == "health/clip_rho_frac"
    assert specs["shadow_mismatch"].key == "serving/shadow_mismatch_rate"


# ---- AlertGatedPolicy: the health-gated flywheel signal ---------------


class _InnerStub(Policy):
    def __init__(self):
        self.ticks = 0
        self.results = []

    def tick(self, snap, now, knob):
        self.ticks += 1
        return Proposal("set", 99.0, reason="inner")

    def observe_result(self, status, now):
        self.results.append(status)


def _reuse_knob(initial=3):
    return Knob(
        KnobSpec("replay_max_reuse", lo=1, hi=4, step=1, kind="int"),
        telemetry=Registry(),
        initial=initial,
    )


_FIRING = {"telemetry/alerts/firing_rho_saturation": 1.0}
_CLEAR = {"telemetry/alerts/firing_rho_saturation": 0.0}


class TestAlertGatedPolicy:
    def test_passthrough_without_gauge_or_while_clear(self):
        """No health plane attached (gauge absent) and alert-clear both
        pass straight through — wrapping is behavior-neutral."""
        inner = _InnerStub()
        pol = AlertGatedPolicy(inner, AlertSignal("rho_saturation"))
        knob = _reuse_knob()
        assert pol.tick({}, 0.0, knob).reason == "inner"
        assert pol.tick(_CLEAR, 1.0, knob).reason == "inner"
        assert inner.ticks == 2
        pol.observe_result("applied", 1.0)
        assert inner.results == ["applied"]

    def test_firing_freezes_inner_and_shrinks(self):
        inner = _InnerStub()
        pol = AlertGatedPolicy(inner, AlertSignal("rho_saturation"))
        knob = _reuse_knob(initial=3)
        p = pol.tick(_FIRING, 0.0, knob)
        assert inner.ticks == 0  # growth frozen: inner never consulted
        assert p.kind == "set" and p.target == 2.0
        assert "rho_saturation" in p.reason
        # The gate's own apply outcome must NOT leak into the inner
        # policy's cooldown/settle bookkeeping.
        pol.observe_result("applied", 0.0)
        assert inner.results == []

    def test_firing_at_floor_holds(self):
        pol = AlertGatedPolicy(_InnerStub(), AlertSignal("rho_saturation"))
        assert pol.tick(_FIRING, 0.0, _reuse_knob(initial=1)) is None

    def test_shrink_disabled_just_freezes(self):
        inner = _InnerStub()
        pol = AlertGatedPolicy(
            inner, AlertSignal("rho_saturation"), shrink_on_alert=False
        )
        assert pol.tick(_FIRING, 0.0, _reuse_knob()) is None
        assert inner.ticks == 0

    def test_shrink_paced_by_cooldown(self):
        pol = AlertGatedPolicy(
            _InnerStub(), AlertSignal("rho_saturation"), cooldown_s=10.0
        )
        knob = _reuse_knob(initial=4)
        assert pol.tick(_FIRING, 0.0, knob) is not None
        pol.observe_result("applied", 0.0)
        assert pol.tick(_FIRING, 5.0, knob) is None  # inside cooldown
        assert pol.tick(_FIRING, 11.0, knob) is not None


# ---- serving: windowed shadow mismatch rate ---------------------------


class TestShadowMismatchRate:
    def _stub(self):
        return types.SimpleNamespace(
            _shadow_rate_window=collections.deque()
        )

    def test_nan_with_no_recent_waves(self):
        from torched_impala_tpu.serving.server import PolicyServer

        assert math.isnan(PolicyServer._shadow_mismatch_rate(self._stub()))

    def test_rate_over_window_and_stale_rows_pruned(self):
        from torched_impala_tpu.serving import server as server_mod

        stub = self._stub()
        now = time.monotonic()
        stale = now - server_mod.SHADOW_RATE_WINDOW_S - 5.0
        stub._shadow_rate_window.append((stale, 10, 10))  # outside window
        stub._shadow_rate_window.append((now - 1.0, 8, 2))
        stub._shadow_rate_window.append((now, 2, 1))
        rate = server_mod.PolicyServer._shadow_mismatch_rate(stub)
        assert rate == pytest.approx(3.0 / 10.0)
        # The all-mismatch stale wave was pruned, not averaged in.
        assert len(stub._shadow_rate_window) == 2

    def test_gauge_is_registered_on_the_server(self):
        # The health plane's shadow_mismatch SloSpec reads this exact
        # key; pin the registration (server construction is covered by
        # test_serving — here we only check the spec/gauge agreement).
        spec = {
            s.name: s for s in health_slo_specs()
        }["shadow_mismatch"]
        assert spec.key == "serving/shadow_mismatch_rate"


def test_one_learner_step_emits_health_series_and_same_parameters():
    """A learner on fake Pong frames (shallow torso, T=5, B=8) with
    `health_diagnostics` on and a monitor attached: one settled step
    leaves at least 10 `health/*` series in the registry, all from the
    step's own logs, and the parameters after that step are bit for bit
    those of the same step with the diagnostics off, which emits no
    `health_*` log at all."""
    import optax

    from torched_impala_tpu import configs
    from torched_impala_tpu.models import Agent, AtariShallowTorso, ImpalaNet
    from torched_impala_tpu.runtime import Learner, LearnerConfig, VectorActor

    T, B, E = 5, 8, 4
    cfg = configs.ExperimentConfig(
        name="health_step",
        env_family="atari",
        env_id="PongNoFrameskip-v4",
        obs_shape=(84, 84, 4),
        obs_dtype="uint8",
        num_actions=6,
    )
    factory = configs.make_env_factory(cfg, fake=True)
    agent = Agent(ImpalaNet(num_actions=6, torso=AtariShallowTorso()))

    def one_step(diagnostics):
        reg = Registry()
        learner = Learner(
            agent=agent,
            optimizer=optax.rmsprop(6e-4, decay=0.99, eps=1e-7),
            config=LearnerConfig(
                batch_size=B,
                unroll_length=T,
                log_interval=1,
                loss=ImpalaLossConfig(health_diagnostics=diagnostics),
            ),
            example_obs=configs.example_obs(cfg),
            rng=jax.random.key(0),
            telemetry=reg,
        )
        learner.attach_health(HealthMonitor(registry=reg))
        actor = VectorActor(
            actor_id=0,
            envs=[factory(1000 + j, j) for j in range(E)],
            agent=agent,
            param_store=learner.param_store,
            enqueue=learner.enqueue,
            unroll_length=T,
            seed=7,
            telemetry=reg,
        )
        learner.start()
        try:
            for _ in range(B // E):
                actor.unroll_and_push()
            logs = learner.step_once(timeout=120)
            learner.drain()
        finally:
            learner.stop()
        params = jax.tree.map(lambda x: np.array(x, copy=True), learner.params)
        series = sorted(
            k for k in reg.snapshot() if k.startswith("telemetry/health/")
        )
        return params, logs, series

    p_on, logs_on, series_on = one_step(True)
    p_off, logs_off, series_off = one_step(False)
    in_step = sorted(k for k in logs_on if k.startswith("health_"))
    assert len(in_step) >= 10, in_step
    assert len(series_on) >= 10, series_on
    assert {
        "telemetry/health/" + k[len("health_"):] for k in in_step
    } <= set(series_on)
    assert not any(k.startswith("health_") for k in logs_off)
    # Host-derived only (the grad spike ratio), nothing from the step.
    assert len(series_off) < len(series_on)
    jax.tree.map(np.testing.assert_array_equal, p_on, p_off)
