"""Fleet serving tests (ISSUE 14): weighted least-loaded routing edge
cases (exact weighted split, death mid-request with exactly-one retry,
draining blocks vs. dead raises), rollout-during-burst per-wave version
uniformity, int8 quantization + the parity gate end to end through a
fleet, the serving chaos faults (kill_server_mid_wave failover,
corrupt_pinned_version bounded retry, wedge_shm_ring), the load
generator's arrival sampling + accounting closure, ParamStore publish
listeners, and the per-replica control-plane binding."""

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jax.config.update("jax_platforms", "cpu")

from torched_impala_tpu.control.loop import build_serving_control  # noqa: E402
from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso  # noqa: E402
from torched_impala_tpu.resilience.chaos import (  # noqa: E402
    ChaosInjector,
    ChaosPlan,
    Fault,
)
from torched_impala_tpu.runtime.param_store import ParamStore  # noqa: E402
from torched_impala_tpu.serving import (  # noqa: E402
    FleetClient,
    PolicyServer,
    ServerClosed,
    ServingFleet,
    ShmRingClient,
    ShmRingPump,
    ShmServingRing,
    TrafficShape,
    VersionRegistry,
    corrupt_scales,
    dequantize_params,
    greedy_action_parity,
    quantize_params,
    run_load,
)
from torched_impala_tpu.serving.fleet import ACTIVE, DEAD, DRAINING  # noqa: E402
from torched_impala_tpu.serving.quant import (  # noqa: E402
    quant_axis_for,
    quantization_report,
)
from torched_impala_tpu.telemetry import Registry  # noqa: E402

OBS_DIM = 6
NUM_ACTIONS = 5


def make_agent() -> Agent:
    return Agent(
        ImpalaNet(
            num_actions=NUM_ACTIONS,
            torso=MLPTorso(hidden_sizes=(16,)),
        )
    )


@pytest.fixture(scope="module")
def agent():
    return make_agent()


@pytest.fixture(scope="module")
def params(agent):
    return agent.init_params(
        jax.random.key(0), np.zeros((OBS_DIM,), np.float32)
    )


def make_fleet(agent, params, replicas=2, versions=1, start=False, **kw):
    """Fresh (fleet, store) with v0..versions-1 published and the fleet
    label pinned to the LATEST. Servers are NOT started unless asked —
    routing tests exercise acquire/release without serve threads."""
    store = ParamStore()
    for v in range(versions):
        store.publish(v, params)
    kw.setdefault("telemetry", Registry())
    kw.setdefault("max_clients", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_wait_s", 0.0)
    fleet = ServingFleet(
        agent=agent,
        store=store,
        example_obs=np.zeros((OBS_DIM,), np.float32),
        replicas=replicas,
        version=versions - 1,
        **kw,
    )
    if start:
        fleet.start()
    return fleet, store


def obs_batch(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, OBS_DIM)).astype(np.float32)


def direct_greedy(agent, params, obs):
    out = agent.step(
        params,
        jax.random.key(0),
        obs,
        np.ones((obs.shape[0],), np.bool_),
        agent.initial_state(obs.shape[0]),
    )
    return np.argmax(np.asarray(out.policy_logits), axis=-1)


# ---- router: weighted least-loaded picks -------------------------------


class TestRouter:
    def test_weighted_split_is_exact(self, agent, params):
        """40 acquires with weights (3, 1) and no releases must split
        exactly 30/10: the min-key ((inflight+1)/weight, -weight, name)
        is deterministic water-filling, not a sampling approximation."""
        fleet, _ = make_fleet(agent, params, weights=(3.0, 1.0))
        try:
            picks = [fleet.acquire().name for _ in range(40)]
            assert picks.count("r0") == 30
            assert picks.count("r1") == 10
        finally:
            fleet.close()

    def test_equal_weights_alternate_least_loaded(self, agent, params):
        fleet, _ = make_fleet(agent, params)
        try:
            # Ties (equal score, equal weight) break on name: r0 first.
            assert [fleet.acquire().name for _ in range(4)] == [
                "r0", "r1", "r0", "r1",
            ]
        finally:
            fleet.close()

    def test_release_restores_inflight_and_feeds_ewma(self, agent, params):
        fleet, _ = make_fleet(agent, params, replicas=1)
        try:
            rep = fleet.acquire()
            assert rep.inflight == 1 and rep.ewma_ms is None
            fleet.release(rep, latency_ms=10.0)
            assert rep.inflight == 0
            assert rep.ewma_ms == 10.0  # first sample seeds the EWMA
            rep = fleet.acquire()
            fleet.release(rep, latency_ms=20.0)
            # alpha=0.2 default: 0.2*20 + 0.8*10
            assert rep.ewma_ms == pytest.approx(12.0)
            # Failed releases never pollute the latency estimate.
            rep = fleet.acquire()
            fleet.release(rep, latency_ms=999.0, ok=False)
            assert rep.ewma_ms == pytest.approx(12.0)
        finally:
            fleet.close()

    def test_exclude_and_prefer(self, agent, params):
        fleet, _ = make_fleet(agent, params)
        try:
            assert fleet.acquire(exclude=("r0",)).name == "r1"
            assert fleet.acquire(prefer="r1").name == "r1"
        finally:
            fleet.close()

    def test_acquire_blocks_through_draining_then_resumes(
        self, agent, params
    ):
        """DRAINING is temporary by contract — the router parks the
        caller instead of failing over, and wakes it on return."""
        fleet, _ = make_fleet(agent, params, replicas=1)
        try:
            rep = fleet.replica("r0")
            with fleet._cond:
                rep.state = DRAINING

            def restore():
                time.sleep(0.1)
                with fleet._cond:
                    rep.state = ACTIVE
                    fleet._cond.notify_all()

            t = threading.Thread(target=restore, daemon=True)
            t.start()
            assert fleet.acquire(timeout_s=5.0).name == "r0"
            t.join()
        finally:
            fleet.close()

    def test_acquire_times_out_while_draining(self, agent, params):
        fleet, _ = make_fleet(agent, params, replicas=1)
        try:
            with fleet._cond:
                fleet.replica("r0").state = DRAINING
            with pytest.raises(TimeoutError, match="no ACTIVE replica"):
                fleet.acquire(timeout_s=0.05)
        finally:
            fleet.close()

    def test_acquire_raises_when_all_dead(self, agent, params):
        fleet, _ = make_fleet(agent, params)
        try:
            for rep in fleet.replicas():
                fleet.mark_dead(rep, reason="test")
            assert fleet.states() == {"r0": DEAD, "r1": DEAD}
            with pytest.raises(ServerClosed, match="no live replica"):
                fleet.acquire()
        finally:
            fleet.close()

    def test_replica_lookup_raises_on_unknown_name(self, agent, params):
        fleet, _ = make_fleet(agent, params)
        try:
            with pytest.raises(KeyError):
                fleet.replica("r9")
        finally:
            fleet.close()


# ---- failover: replica death mid-request -------------------------------


class TestFailover:
    def test_death_mid_request_retries_exactly_once(self, agent, params):
        """r0 dies under the router's nose: the client's first attempt
        lands on it, fails ServerClosed, marks it dead, and retries ON A
        DIFFERENT replica — exactly once, observably (FleetResult.retried
        + the retry counter)."""
        reg = Registry()
        fleet, _ = make_fleet(
            agent, params, start=True, telemetry=reg
        )
        try:
            # The fleet still believes r0 is ACTIVE; kill its server
            # out-of-band, the way a crashed process would look.
            fleet.replica("r0").server.kill(reason="test crash")
            with FleetClient(fleet) as client:
                res = client.act_full(obs_batch(1)[0], True)
                assert res.retried is True
                assert res.replica == "r1"
                assert 0 <= res.action < NUM_ACTIONS
                assert fleet.states()["r0"] == DEAD
                assert reg.counter("serving/route_retry_total").value == 1
                # The survivor serves the next request with no retry.
                res2 = client.act_full(obs_batch(1, seed=1)[0], True)
                assert res2.retried is False and res2.replica == "r1"
                assert reg.counter("serving/route_retry_total").value == 1
        finally:
            fleet.close()

    def test_second_failure_propagates(self, agent, params):
        """One retry is the whole budget: with every replica dead the
        client surfaces ServerClosed instead of spinning."""
        fleet, _ = make_fleet(agent, params, start=True)
        try:
            for rep in fleet.replicas():
                rep.server.kill(reason="test crash")
            with FleetClient(fleet) as client:
                with pytest.raises(ServerClosed):
                    client.act_full(obs_batch(1)[0], True)
                assert all(s == DEAD for s in fleet.states().values())
                # Fast-fail from then on: the router refuses up front.
                with pytest.raises(ServerClosed, match="no live replica"):
                    client.act_full(obs_batch(1)[0], True)
        finally:
            fleet.close()


# ---- draining rollouts under live load ---------------------------------


class TestRollout:
    def test_rollout_during_burst_keeps_waves_version_uniform(
        self, agent, params
    ):
        """The acceptance property at test scale: a rollout lands while
        client threads hammer the fleet; every (replica, wave) pair must
        serve exactly one version and nothing may error or drop."""
        fleet, store = make_fleet(agent, params, start=True)
        results = []
        errors = []
        lock = threading.Lock()

        def worker(seed):
            obs = obs_batch(40, seed=seed)
            try:
                with FleetClient(fleet) as client:
                    for i in range(40):
                        r = client.act_full(obs[i], True)
                        with lock:
                            results.append(r)
            except Exception as e:  # pragma: no cover - failure detail
                with lock:
                    errors.append(e)

        try:
            threads = [
                threading.Thread(target=worker, args=(s,)) for s in range(3)
            ]
            for t in threads:
                t.start()
            time.sleep(0.02)
            store.publish(1, params)
            out = fleet.rollout(1, timeout_s=30.0)
            for t in threads:
                t.join()
            assert errors == []
            assert out == {"version": 1, "replicas": ["r0", "r1"]}
            assert len(results) == 120
            by_wave = {}
            for r in results:
                by_wave.setdefault((r.replica, r.wave), set()).add(r.version)
            assert all(len(v) == 1 for v in by_wave.values())
            versions = {r.version for r in results}
            assert versions <= {0, 1}
            # Post-rollout traffic is on the new version.
            with FleetClient(fleet) as client:
                assert client.act_full(obs_batch(1)[0], True).version == 1
        finally:
            fleet.close()

    def test_rollout_unknown_version_raises_before_draining(
        self, agent, params
    ):
        fleet, _ = make_fleet(agent, params, start=True)
        try:
            with pytest.raises(KeyError):
                fleet.rollout(99)
            assert fleet.states() == {"r0": ACTIVE, "r1": ACTIVE}
        finally:
            fleet.close()

    def test_rollout_skips_dead_replica(self, agent, params):
        fleet, store = make_fleet(agent, params, start=True)
        try:
            fleet.replica("r1").server.kill(reason="test")
            fleet.mark_dead(fleet.replica("r1"), reason="test")
            store.publish(1, params)
            out = fleet.rollout(1, timeout_s=30.0)
            assert out == {"version": 1, "replicas": ["r0"]}
        finally:
            fleet.close()

    def test_warm_precaches_the_serving_dtype(self, agent, params):
        """rollout()'s WARM phase: quantization happens off-rotation, so
        the first post-pin wave reuses the cache instead of paying it."""
        fleet, _ = make_fleet(
            agent, params, replicas=1, versions=2, dtype="int8"
        )
        try:
            server = fleet.replica("r0").server
            assert 0 not in server._cast_cache
            server.warm(0)
            assert 0 in server._cast_cache
        finally:
            fleet.close()
        # float32 serving has nothing to pre-resolve: warm is a no-op.
        fleet, _ = make_fleet(agent, params, replicas=1)
        try:
            server = fleet.replica("r0").server
            server.warm(0)
            assert len(server._cast_cache) == 0
        finally:
            fleet.close()


# ---- int8 quantization + the parity gate -------------------------------


class TestQuant:
    def test_layout_globs_select_channel_axes(self):
        assert quant_axis_for("params/Dense_0/kernel") == -1
        assert quant_axis_for("params/embed/embedding") == -1
        assert quant_axis_for("params/Dense_0/bias") is None
        assert quant_axis_for("params/LayerNorm_0/scale") is None
        assert quant_axis_for("opt_state/count") is None  # no match

    def test_roundtrip_error_bounded_by_half_scale(self):
        rng = np.random.default_rng(3)
        w = rng.normal(size=(8, 4)).astype(np.float32)
        qp = quantize_params({"m": {"kernel": w}})
        q = np.asarray(qp.q["m"]["kernel"])
        scale = np.asarray(qp.scale["m"]["kernel"])
        assert q.dtype == np.int8
        assert scale.shape == (1, 4)  # per-output-channel, keepdims
        np.testing.assert_allclose(
            scale[0], np.abs(w).max(axis=0) / 127.0, rtol=1e-6
        )
        dq = np.asarray(dequantize_params(qp)["m"]["kernel"])
        # Symmetric round-to-nearest: error <= scale/2 per channel.
        assert np.all(np.abs(dq - w) <= scale / 2 + 1e-7)

    def test_pass_through_leaves_survive_untouched(self, params):
        qp = quantize_params(params)
        rpt = quantization_report(qp)
        assert rpt["quantized_leaves"] >= 1
        assert rpt["quantized_leaves"] < rpt["leaves"]  # biases pass through
        assert rpt["int8_bytes"] > 0 and rpt["scale_bytes"] > 0
        flat = jax.tree_util.tree_flatten_with_path(qp.q)[0]
        for path, leaf in flat:
            path_s = "/".join(str(getattr(p, "key", p)) for p in path)
            if path_s.endswith("bias"):
                assert leaf.dtype == np.float32

    def test_parity_gate_passes_and_seeded_corruption_fails(
        self, agent, params
    ):
        obs = obs_batch(16, seed=7)
        ok, mismatches = greedy_action_parity(
            agent, params, obs, dtype="int8"
        )
        assert ok and mismatches == 0
        bad = lambda p: dequantize_params(  # noqa: E731
            corrupt_scales(quantize_params(p))
        )
        ok, mismatches = greedy_action_parity(
            agent, params, obs, cast_fn=bad
        )
        assert not ok and mismatches > 0

    def test_int8_fleet_serves_parity_actions(self, agent, params):
        """End to end through routing: an int8 fleet's greedy actions
        equal the f32 direct actions (the gate's promise)."""
        fleet, _ = make_fleet(
            agent, params, replicas=1, dtype="int8", start=True
        )
        try:
            obs = obs_batch(5, seed=21)
            expected = direct_greedy(agent, params, obs)
            with FleetClient(fleet) as client:
                got = [client.act(obs[i], True) for i in range(5)]
            assert np.array_equal(np.asarray(got), expected)
        finally:
            fleet.close()


# ---- chaos: the serving fault kinds ------------------------------------


class TestServingChaos:
    def test_kill_server_mid_wave_fails_over(self, agent, params):
        """The harness fault, not a hand-rolled kill: the first wave's
        replica dies between dequeue and compute; the request must still
        be answered by the survivor, retried exactly once."""
        fleet, _ = make_fleet(agent, params, start=True)
        injector = ChaosInjector(
            ChaosPlan([Fault(kind="kill_server_mid_wave", at=1)]),
            telemetry=Registry(),
        )
        injector.install(fleets=[fleet])
        try:
            with FleetClient(fleet) as client:
                res = client.act_full(obs_batch(1)[0], True)
            assert res.retried is True
            assert len(injector.fired) == 1
            states = fleet.states()
            assert sorted(states.values()) == [ACTIVE, DEAD]
        finally:
            fleet.close()

    def test_corrupt_pinned_version_is_a_bounded_outage(self, agent, params):
        """Corrupting the SHARED store poisons every replica: the wave
        fails at trace time, each server kills itself rather than wedge,
        and the client surfaces ServerClosed after its single retry —
        correlated failure must cost one retry, not a retry storm."""
        fleet, _ = make_fleet(agent, params, start=True)
        injector = ChaosInjector(
            ChaosPlan([Fault(kind="corrupt_pinned_version", at=1)]),
            telemetry=Registry(),
        )
        injector.install(fleets=[fleet])
        try:
            with FleetClient(fleet) as client:
                with pytest.raises(ServerClosed):
                    client.act_full(obs_batch(1)[0], True)
            assert len(injector.fired) == 1
            assert all(s == DEAD for s in fleet.states().values())
        finally:
            fleet.close()

    def test_wedge_shm_ring_is_latency_not_errors(self, agent, params):
        """A wedged pump stalls the scan for duration_s; the client sees
        a slow answer, never a wrong or failed one."""
        store = ParamStore()
        store.publish(0, params)
        registry = VersionRegistry.serving_latest(
            store, telemetry=Registry()
        )
        server = PolicyServer(
            agent=agent,
            registry=registry,
            example_obs=np.zeros((OBS_DIM,), np.float32),
            telemetry=Registry(),
            max_clients=8,
            max_batch=4,
            max_wait_s=0.0,
        )
        server.start()
        ring = ShmServingRing(
            capacity=4, obs_shape=(OBS_DIM,), obs_dtype=np.float32
        )
        pump = ShmRingPump(server)
        injector = ChaosInjector(
            ChaosPlan(
                [Fault(kind="wedge_shm_ring", at=1, duration_s=0.3)]
            ),
            telemetry=Registry(),
        )
        injector.install(pumps=[pump])
        try:
            pump.attach(ring, greedy=True)
            obs = obs_batch(2, seed=9)
            expected = direct_greedy(agent, params, obs)
            rc = ShmRingClient(ring)
            # Submit BEFORE the pump starts: its very first scan fires
            # the wedge, so the queued request waits out the full stall.
            rc.submit(obs[0], True)
            t0 = time.monotonic()
            pump.start()
            got0 = rc.result(timeout_s=30.0)[0]
            assert time.monotonic() - t0 >= 0.25  # absorbed the stall
            got1 = rc.act(obs[1], True)  # recovered: fault is one-shot
            assert np.array_equal(
                np.asarray([got0, got1]), expected
            )
            assert len(injector.fired) == 1
            assert rc.outstanding == 0
        finally:
            pump.stop()
            server.close()
            ring.close()


# ---- load generator: arrivals + accounting -----------------------------


class TestLoadgen:
    def test_shape_validation(self):
        with pytest.raises(ValueError):
            TrafficShape(kind="sawtooth")
        with pytest.raises(ValueError):
            TrafficShape(rate_rps=0.0)
        with pytest.raises(ValueError):
            TrafficShape(kind="diurnal", amplitude=1.0)
        with pytest.raises(ValueError):
            TrafficShape(kind="bursty", burst_duty=1.0)
        with pytest.raises(ValueError):
            TrafficShape(kind="diurnal", period_s=0.0)

    def test_poisson_arrivals_match_rate(self):
        shape = TrafficShape(kind="poisson", rate_rps=500.0, duration_s=2.0)
        ts = shape.arrival_times(np.random.default_rng(0))
        # Poisson(1000): 3 sigma is ~±95 arrivals.
        assert 850 <= len(ts) <= 1150
        assert np.all(np.diff(ts) >= 0)
        assert ts[0] >= 0.0 and ts[-1] < shape.duration_s
        assert shape.peak_rate() == 500.0

    def test_bursty_and_diurnal_mean_rates(self):
        bursty = TrafficShape(
            kind="bursty", rate_rps=300.0, duration_s=4.0, period_s=1.0
        )
        assert bursty.peak_rate() == 1200.0  # default burst = 4x base
        n = len(bursty.arrival_times(np.random.default_rng(1)))
        assert abs(n - 1200) <= 300  # mean preserved across the duty cycle
        diurnal = TrafficShape(
            kind="diurnal",
            rate_rps=300.0,
            duration_s=4.0,
            period_s=2.0,
            amplitude=0.5,
        )
        assert diurnal.peak_rate() == pytest.approx(450.0)
        n = len(diurnal.arrival_times(np.random.default_rng(2)))
        assert abs(n - 1200) <= 300

    def test_run_load_accounting_closes(self, agent, params):
        """Every offered arrival lands in exactly one outcome bucket and
        the headline rates are recomputable from the buckets."""
        fleet, _ = make_fleet(agent, params, replicas=1, start=True)
        try:
            shape = TrafficShape(
                kind="poisson", rate_rps=300.0, duration_s=0.5
            )
            report = run_load(
                fleet=fleet,
                shape=shape,
                slo_ms=100.0,
                example_obs=np.zeros((OBS_DIM,), np.float32),
                clients=4,
                seed=5,
                disconnect_frac=0.25,
            )
            assert report.offered > 0
            assert report.offered == (
                report.ok
                + report.expired
                + report.disconnected
                + report.failed
            )
            assert report.failed == 0 and report.expired == 0
            assert report.disconnected > 0  # chaos clients hung up
            assert report.ok_within_slo <= report.ok
            assert len(report.latencies_ms) == report.ok
            assert report.goodput_rps == pytest.approx(
                report.ok_within_slo / shape.duration_s
            )
            summary = report.summary()
            for key in ("offered", "ok", "goodput_rps", "p99_ms"):
                assert key in summary
        finally:
            fleet.close()


    @pytest.mark.parametrize("replicas", [1, 2])
    def test_killed_replica_under_load_single_fails_fleet_absorbs(
        self, agent, params, replicas
    ):
        """The same open-loop stream (16 clients, int8 behind the parity
        gate) with one server killed mid-wave at its 10th wave: a single
        server has nowhere to fail over and its later requests fail; a
        2-replica fleet marks exactly one replica dead, answers the
        orphaned requests by the one retry and fails none."""
        obs_pool = obs_batch(64, seed=3)
        ok, mismatches = greedy_action_parity(
            agent, params, obs_pool[:16], dtype="int8"
        )
        assert ok and mismatches == 0
        clients = 16
        fleet, _ = make_fleet(
            agent,
            params,
            replicas=replicas,
            start=True,
            dtype="int8",
            max_clients=clients + 2,
            max_batch=8,
            max_wait_s=1e-3,
        )
        injector = ChaosInjector(
            ChaosPlan([Fault(kind="kill_server_mid_wave", at=10)]),
            telemetry=Registry(),
        )
        injector.install(fleets=[fleet])
        try:
            report = run_load(
                fleet=fleet,
                shape=TrafficShape(
                    kind="poisson", rate_rps=300.0, duration_s=1.0
                ),
                slo_ms=50.0,
                example_obs=np.zeros((OBS_DIM,), np.float32),
                obs_pool=obs_pool,
                clients=clients,
                seed=3,
            )
            states = sorted(fleet.states().values())
        finally:
            fleet.close()
        assert len(injector.fired) == 1
        assert report.offered == (
            report.ok + report.expired + report.disconnected + report.failed
        )
        if replicas == 1:
            assert states == [DEAD]
            assert report.failed > 0
        else:
            assert states == [ACTIVE, DEAD]
            assert report.failed == 0
            assert report.retried >= 1
            assert report.ok == report.offered


# ---- ParamStore publish listeners (the rollout feed) -------------------


class TestPublishListeners:
    def test_listener_add_remove_and_error_isolation(self):
        store = ParamStore()
        seen = []
        fn = store.add_publish_listener(seen.append)

        def broken(_v):
            raise RuntimeError("observer bug")

        store.add_publish_listener(broken)
        store.publish(1, {"w": 1})  # broken listener must not stall this
        assert seen == [1]
        store.remove_publish_listener(fn)
        store.publish(2, {"w": 2})
        assert seen == [1]

    def test_fleet_tracks_latest_published(self, agent, params):
        reg = Registry()
        fleet, store = make_fleet(agent, params, telemetry=reg)
        try:
            gauge = reg.gauge("serving/fleet_latest_published")
            assert gauge.value == 0
            store.publish(7, params)
            assert gauge.value == 7
        finally:
            fleet.close()
        # close() detaches the listener: later publishes are not seen.
        store.publish(9, params)
        assert gauge.value == 7


# ---- control plane: per-replica knob binding ---------------------------


class TestFleetSloSpecs:
    def test_objective_table_shape(self, agent, params):
        """The fleet's SloSpec table (ISSUE 17) plugs into the
        burn-rate engine: a dead replica held past the alert windows
        must burn the active-floor budget, while a healthy fleet burns
        nothing."""
        from torched_impala_tpu.telemetry import AlertEngine
        from torched_impala_tpu.telemetry.tracing import FlightRecorder

        fleet, _ = make_fleet(agent, params)
        try:
            specs = fleet.slo_specs(slo_ms=40.0)
            by_name = {s.name: s for s in specs}
            assert by_name["fleet_route_p99"].key == (
                "serving/route_latency_ms_p99"
            )
            assert by_name["fleet_route_p99"].objective == 40.0
            floor = by_name["fleet_active_floor"]
            assert floor.kind == "lower"
            assert floor.is_bad(1.0)  # one of two replicas: degraded
            assert not floor.is_bad(2.0)
            reg = Registry()
            eng = AlertEngine(
                [
                    type(floor)(
                        **{
                            **floor.__dict__,
                            "fast_window_s": 0.5,
                            "slow_window_s": 1.0,
                        }
                    )
                ],
                registry=reg,
                recorder=FlightRecorder(capacity=16),
            )
            t, fired = 0.0, False
            while t <= 2.0:
                if eng.evaluate(
                    {"telemetry/serving/fleet_active": 1.0}, now=t
                ):
                    fired = True
                t += 0.1
            assert fired
        finally:
            fleet.close()


class TestFleetControl:
    def test_per_replica_knob_names(self, agent, params):
        fleet, _ = make_fleet(agent, params)
        try:
            loop = build_serving_control(fleet=fleet, telemetry=Registry())
            assert loop.knobs.names() == [
                "serving_max_batch_r0",
                "serving_max_batch_r1",
                "serving_max_wait_ms_r0",
                "serving_max_wait_ms_r1",
            ]
        finally:
            fleet.close()

    def test_exactly_one_of_server_or_fleet(self, agent, params):
        fleet, _ = make_fleet(agent, params)
        try:
            with pytest.raises(ValueError, match="exactly one"):
                build_serving_control()
            with pytest.raises(ValueError, match="exactly one"):
                build_serving_control(
                    server=fleet.replica("r0").server, fleet=fleet
                )
        finally:
            fleet.close()
