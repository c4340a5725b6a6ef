"""Serving tier tests (ISSUE 6): ParamStore pinned versions, the
VersionRegistry, PolicyServer wave semantics + edge cases (disconnect
mid-wave, deadline expiry, version swap mid-wave, shm-ring wraparound
under backpressure), the evaluator's serving-client path, the bf16
greedy-parity gate, and the evaluator jit-cache leak regression."""

import gc
import threading
import weakref

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jax.config.update("jax_platforms", "cpu")

from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso  # noqa: E402
from torched_impala_tpu.runtime.param_store import ParamStore  # noqa: E402
from torched_impala_tpu.serving import (  # noqa: E402
    ClientDisconnected,
    DeadlineExpired,
    InProcessClient,
    PolicyServer,
    RingBackpressure,
    ServerClosed,
    ShmRingClient,
    ShmRingPump,
    ShmServingRing,
    VersionRegistry,
    cast_params,
    greedy_action_parity,
    mint_request_lid,
)
from torched_impala_tpu.telemetry import Registry  # noqa: E402

OBS_DIM = 6
NUM_ACTIONS = 5


def make_agent(lstm: bool = False) -> Agent:
    return Agent(
        ImpalaNet(
            num_actions=NUM_ACTIONS,
            torso=MLPTorso(hidden_sizes=(16,)),
            use_lstm=lstm,
            lstm_size=8,
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


def make_server(agent, params, versions=1, **kwargs):
    """Fresh (store, registry, server) with `versions` sequential
    publishes (v = 0..versions-1) and a single 'live' label pinned to
    the LATEST."""
    store = ParamStore()
    for v in range(versions):
        store.publish(v, params)
    registry = VersionRegistry.serving_latest(
        store, telemetry=kwargs.pop("registry_telemetry", Registry())
    )
    kwargs.setdefault("telemetry", Registry())
    kwargs.setdefault("max_clients", 8)
    kwargs.setdefault("max_batch", 4)
    kwargs.setdefault("max_wait_s", 0.0)
    server = PolicyServer(
        agent=agent,
        registry=registry,
        example_obs=np.zeros((OBS_DIM,), np.float32),
        **kwargs,
    )
    return store, registry, server


def obs_batch(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, OBS_DIM)).astype(np.float32)


def direct_greedy(agent, params, obs):
    """Reference greedy actions: direct agent.step argmax, fresh state,
    first=True rows."""
    out = agent.step(
        params,
        jax.random.key(0),
        obs,
        np.ones((obs.shape[0],), np.bool_),
        agent.initial_state(obs.shape[0]),
    )
    return np.argmax(np.asarray(out.policy_logits), axis=-1)


# ---- ParamStore: pinned versions + sharing contract (satellite) ---------


class TestParamStore:
    def test_get_version_roundtrip(self):
        store = ParamStore()
        store.publish(10, {"w": 1})
        store.publish(20, {"w": 2})
        assert store.get_version(10) == {"w": 1}
        assert store.get_version(20) == {"w": 2}
        assert store.get() == (20, {"w": 2})

    def test_keep_last_k_evicts_oldest(self):
        store = ParamStore(keep_versions=2)
        for v in range(4):
            store.publish(v, {"v": v})
        assert store.versions() == [2, 3]
        with pytest.raises(KeyError, match="not retained"):
            store.get_version(0)
        # The error names what IS retained (operator affordance).
        with pytest.raises(KeyError, match=r"\[2, 3\]"):
            store.get_version(1)

    def test_get_returns_shared_reference(self):
        """The documented sharing contract: get()/get_version() hand back
        the PUBLISHED object, not a copy — actors and the serving tier
        rely on zero-copy reads, and the learner publishes host
        snapshots precisely so this is safe."""
        store = ParamStore()
        tree = {"w": np.arange(4.0)}
        store.publish(7, tree)
        assert store.get()[1] is tree
        assert store.get_version(7) is tree

    def test_republish_same_version_updates(self):
        store = ParamStore(keep_versions=2)
        store.publish(1, "a")
        store.publish(1, "b")
        assert store.versions() == [1]
        assert store.get_version(1) == "b"

    def test_keep_versions_validated(self):
        with pytest.raises(ValueError, match="keep_versions"):
            ParamStore(keep_versions=0)


# ---- VersionRegistry ----------------------------------------------------


class TestVersionRegistry:
    def test_serving_latest_routes_everyone(self):
        store = ParamStore()
        store.publish(3, "p3")
        reg = VersionRegistry.serving_latest(
            store, telemetry=Registry()
        )
        for cid in range(20):
            assert reg.route(cid) == "live"
        assert reg.resolve("live") == (3, "p3")

    def test_pin_validates_retention(self):
        store = ParamStore(keep_versions=1)
        store.publish(0, "p0")
        store.publish(1, "p1")
        reg = VersionRegistry(store, telemetry=Registry())
        with pytest.raises(KeyError, match="not retained"):
            reg.pin("old", version=0)
        assert reg.pin("live") == 1

    def test_pin_is_sticky_across_publishes(self):
        """A label resolves to its PINNED version even after the learner
        publishes newer params — deploys happen at pin time only."""
        store = ParamStore()
        store.publish(0, "p0")
        reg = VersionRegistry(store, telemetry=Registry())
        reg.pin("stable", 0)
        store.publish(1, "p1")
        assert reg.resolve("stable") == (0, "p0")
        reg.pin("stable")  # re-pin to latest = the deploy
        assert reg.resolve("stable") == (1, "p1")

    def test_repin_counts_version_swap(self):
        telemetry = Registry()
        store = ParamStore()
        store.publish(0, "p0")
        store.publish(1, "p1")
        reg = VersionRegistry(store, telemetry=telemetry)
        reg.pin("live", 0)
        reg.pin("live", 0)  # same version: not a swap
        assert telemetry.counter("serving/version_swaps").value == 0
        reg.pin("live", 1)
        assert telemetry.counter("serving/version_swaps").value == 1

    def test_route_deterministic_and_weighted(self):
        store = ParamStore()
        store.publish(0, "p")
        reg = VersionRegistry(store, telemetry=Registry())
        reg.pin("a", 0)
        reg.pin("b", 0)
        reg.set_routing({"a": 0.8, "b": 0.2})
        routes = [reg.route(cid) for cid in range(400)]
        assert routes == [reg.route(cid) for cid in range(400)]  # sticky
        frac_b = routes.count("b") / len(routes)
        # blake2b-uniform over 400 ids: generous band around 0.2.
        assert 0.08 < frac_b < 0.35, frac_b

    def test_set_routing_validation(self):
        store = ParamStore()
        store.publish(0, "p")
        reg = VersionRegistry(store, telemetry=Registry())
        reg.pin("live", 0)
        with pytest.raises(ValueError, match="unpinned"):
            reg.set_routing({"ghost": 1.0})
        with pytest.raises(ValueError, match="unpinned"):
            reg.set_routing({"live": 1.0}, shadow="ghost")
        with pytest.raises(ValueError, match="must be > 0"):
            reg.set_routing({"live": 0.0})
        with pytest.raises(ValueError, match="shadow_fraction"):
            reg.set_routing({"live": 1.0}, shadow_fraction=0.0)
        with pytest.raises(RuntimeError, match="no routing"):
            VersionRegistry(store, telemetry=Registry()).route(0)

    def test_unpin_refuses_routed_label(self):
        store = ParamStore()
        store.publish(0, "p")
        reg = VersionRegistry(store, telemetry=Registry())
        reg.pin("live", 0)
        reg.set_routing({"live": 1.0})
        with pytest.raises(ValueError, match="still routed"):
            reg.unpin("live")


# ---- evaluator jit-cache leak regression (satellite) --------------------


class TestEvalStepCache:
    def test_cache_is_bounded_evicted_agents_collect(self):
        """The old unbounded lru_cache kept every Agent (and its jitted
        executables) alive forever; the bounded cache must evict —
        and an evicted agent must actually become collectable (nothing
        else pins it)."""
        from torched_impala_tpu.runtime.evaluator import (
            _EVAL_STEP_CACHE_SIZE,
            _jitted_eval_step,
        )

        # Distinct static config so no other test shares this entry.
        doomed = Agent(
            ImpalaNet(
                num_actions=NUM_ACTIONS,
                torso=MLPTorso(hidden_sizes=(7, 7)),
            )
        )
        _jitted_eval_step(doomed, True)
        ref = weakref.ref(doomed)
        del doomed
        # Flood the LRU with distinct configs to push the entry out.
        for i in range(_EVAL_STEP_CACHE_SIZE + 1):
            _jitted_eval_step(
                Agent(
                    ImpalaNet(
                        num_actions=NUM_ACTIONS,
                        torso=MLPTorso(hidden_sizes=(32 + i,)),
                    )
                ),
                True,
            )
        gc.collect()
        assert ref() is None, "evicted agent still referenced"
        info = _jitted_eval_step.cache_info()
        assert info.maxsize == _EVAL_STEP_CACHE_SIZE
        assert info.currsize <= _EVAL_STEP_CACHE_SIZE

    def test_same_agent_shares_compiled_fn(self, agent):
        from torched_impala_tpu.runtime.evaluator import _jitted_eval_step

        assert _jitted_eval_step(agent, True) is _jitted_eval_step(
            agent, True
        )
        assert _jitted_eval_step(agent, True) is not _jitted_eval_step(
            agent, False
        )


# ---- PolicyServer core --------------------------------------------------


class TestPolicyServer:
    def test_wave_matches_direct_greedy(self, agent, params):
        _, _, server = make_server(agent, params)
        try:
            obs = obs_batch(3)
            clients = [InProcessClient(server) for _ in range(3)]
            cells = [
                c.act_async(obs[i], True) for i, c in enumerate(clients)
            ]
            assert server.service_once() == 3
            got = np.asarray(
                [cell.result(timeout=10.0).action for cell in cells]
            )
            assert np.array_equal(got, direct_greedy(agent, params, obs))
        finally:
            server.close()

    def test_coalesced_requests_share_one_wave(self, agent, params):
        telemetry = Registry()
        _, _, server = make_server(
            agent, params, max_batch=4, telemetry=telemetry
        )
        try:
            clients = [InProcessClient(server) for _ in range(4)]
            obs = obs_batch(4)
            cells = [
                c.act_async(obs[i], True) for i, c in enumerate(clients)
            ]
            assert server.service_once() == 4
            waves = {cell.result(1.0).wave for cell in cells}
            assert len(waves) == 1, waves
            snap = telemetry.snapshot()
            assert snap["telemetry/serving/wave_total"] == 1
            assert snap["telemetry/serving/request_total"] == 4
        finally:
            server.close()

    def test_one_request_per_client_per_wave(self, agent, params):
        """A pipelining client's second request must ride the NEXT wave —
        the recurrent-state chain advances one step per wave."""
        _, _, server = make_server(agent, params, max_batch=4)
        try:
            client = InProcessClient(server)
            obs = obs_batch(2)
            c1 = client.act_async(obs[0], True)
            c2 = client.act_async(obs[1], False)
            assert server.service_once() == 1
            assert c1.done() and not c2.done()
            assert server.service_once() == 1
            assert c2.result(1.0).wave == c1.result(1.0).wave + 1
        finally:
            server.close()

    def test_sampled_mode_returns_valid_actions(self, agent, params):
        _, _, server = make_server(agent, params)
        try:
            client = InProcessClient(server, greedy=False)
            cell = client.act_async(obs_batch(1)[0], True)
            server.service_once()
            assert 0 <= cell.result(1.0).action < NUM_ACTIONS
        finally:
            server.close()

    def test_lstm_state_lives_on_server(self, params):
        """Per-client recurrent-state slots: a client stepping a sequence
        through the server gets EXACTLY the actions of a direct
        agent.step loop chaining its own carry — state never visits the
        client."""
        lstm_agent = make_agent(lstm=True)
        lstm_params = lstm_agent.init_params(
            jax.random.key(0), np.zeros((OBS_DIM,), np.float32)
        )
        _, _, server = make_server(lstm_agent, lstm_params)
        server.start()
        try:
            seq = obs_batch(6, seed=3)
            ref, state, first = [], lstm_agent.initial_state(1), True
            for t in range(seq.shape[0]):
                out = lstm_agent.step(
                    lstm_params,
                    jax.random.key(0),
                    seq[t][None],
                    np.asarray([first]),
                    state,
                )
                ref.append(int(np.argmax(np.asarray(out.policy_logits))))
                state = out.state
                first = False
            client = InProcessClient(server)
            got, first = [], True
            for t in range(seq.shape[0]):
                got.append(client.act(seq[t], first))
                first = False
            assert got == ref
        finally:
            server.close()

    def test_obs_shape_validated(self, agent, params):
        _, _, server = make_server(agent, params)
        try:
            client = InProcessClient(server)
            with pytest.raises(ValueError, match="obs shape"):
                client.act_async(np.zeros((OBS_DIM + 1,), np.float32), True)
        finally:
            server.close()

    def test_max_clients_enforced(self, agent, params):
        _, _, server = make_server(agent, params, max_clients=2)
        try:
            a = InProcessClient(server)
            InProcessClient(server)
            with pytest.raises(RuntimeError, match="max_clients"):
                InProcessClient(server)
            a.close()  # freeing a slot re-admits
            InProcessClient(server)
        finally:
            server.close()

    def test_close_fails_outstanding_requests(self, agent, params):
        _, _, server = make_server(agent, params)
        client = InProcessClient(server)
        cell = client.act_async(obs_batch(1)[0], True)
        server.close()
        with pytest.raises(ServerClosed):
            cell.result(1.0)
        with pytest.raises(ServerClosed):
            server.connect()

    def test_threaded_serve_loop_end_to_end(self, agent, params):
        """The production drive: started server thread, coalescing window
        honored, many clients in flight concurrently."""
        _, _, server = make_server(
            agent, params, max_batch=4, max_wait_s=2e-3
        )
        server.start()
        try:
            obs = obs_batch(4)
            expected = direct_greedy(agent, params, obs)
            clients = [InProcessClient(server) for _ in range(4)]
            results = [None] * 4

            def drive(i):
                results[i] = clients[i].act(obs[i], True)

            threads = [
                threading.Thread(target=drive, args=(i,))
                for i in range(4)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert np.array_equal(np.asarray(results), expected)
        finally:
            server.close()


# ---- serving edge cases (satellite) -------------------------------------


class TestServingEdgeCases:
    def test_client_disconnect_mid_wave(self, agent, params):
        """A request whose client disconnects while queued must fail
        ClientDisconnected, never crash the wave, and must not consume
        wave capacity; the freed slot is reusable."""
        telemetry = Registry()
        _, _, server = make_server(agent, params, telemetry=telemetry)
        try:
            doomed = InProcessClient(server)
            survivor = InProcessClient(server)
            obs = obs_batch(2)
            doomed_cell = doomed.act_async(obs[0], True)
            survivor_cell = survivor.act_async(obs[1], True)
            doomed.close()  # disconnect with the request pending
            assert server.service_once() == 1  # only the survivor waved
            with pytest.raises(ClientDisconnected):
                doomed_cell.result(1.0)
            assert survivor_cell.result(1.0).action >= 0
            snap = telemetry.snapshot()
            assert snap["telemetry/serving/request_dropped"] == 1
            # Slot is reusable after the disconnect.
            again = InProcessClient(server)
            cell = again.act_async(obs[0], True)
            server.service_once()
            assert cell.result(1.0).action >= 0
        finally:
            server.close()

    def test_request_deadline_expiry(self, agent, params):
        """A request older than its deadline when the wave forms fails
        DeadlineExpired instead of receiving a stale action."""
        import time

        telemetry = Registry()
        _, _, server = make_server(agent, params, telemetry=telemetry)
        try:
            client = InProcessClient(server)
            obs = obs_batch(2)
            expired = client.act_async(obs[0], True, deadline_s=0.01)
            time.sleep(0.05)  # server idle past the deadline
            fresh = client.act_async(obs[1], True, deadline_s=30.0)
            assert server.service_once() == 1
            with pytest.raises(DeadlineExpired):
                expired.result(1.0)
            assert fresh.result(1.0).action >= 0
            assert (
                telemetry.snapshot()["telemetry/serving/request_expired"]
                == 1
            )
        finally:
            server.close()

    def test_version_swap_between_submits_is_wave_consistent(
        self, agent, params
    ):
        """Deterministic interleaving: a re-pin landing BETWEEN two
        submits of one wave must not split the wave across versions —
        the wave resolves its label once."""
        store, registry, server = make_server(agent, params, versions=2)
        try:
            registry.pin("live", 0)
            a = InProcessClient(server)
            b = InProcessClient(server)
            obs = obs_batch(2)
            cell_a = a.act_async(obs[0], True)
            registry.pin("live", 1)  # swap lands mid-queue
            cell_b = b.act_async(obs[1], True)
            assert server.service_once() == 2
            ra, rb = cell_a.result(1.0), cell_b.result(1.0)
            assert ra.wave == rb.wave
            assert ra.version == rb.version == 1  # resolved at wave time
        finally:
            server.close()

    def test_version_swap_hammer_never_mixes_a_wave(self, agent, params):
        """Concurrent re-pin hammer: across many waves with a thread
        flipping the live pin as fast as it can, every wave's responses
        still share ONE version."""
        store, registry, server = make_server(
            agent, params, versions=2, max_batch=4
        )
        stop = threading.Event()

        def hammer():
            v = 0
            while not stop.is_set():
                registry.pin("live", v)
                v ^= 1

        t = threading.Thread(target=hammer)
        t.start()
        try:
            clients = [InProcessClient(server) for _ in range(4)]
            obs = obs_batch(4)
            by_wave = {}
            for _round in range(25):
                cells = [
                    c.act_async(obs[i], _round == 0)
                    for i, c in enumerate(clients)
                ]
                server.service_once()
                for cell in cells:
                    r = cell.result(5.0)
                    by_wave.setdefault(r.wave, set()).add(r.version)
            assert by_wave, "no waves served"
            mixed = {w: vs for w, vs in by_wave.items() if len(vs) > 1}
            assert not mixed, f"waves mixing versions: {mixed}"
        finally:
            stop.set()
            t.join(timeout=10)
            server.close()

    def test_shadow_scores_without_touching_primary(self, agent, params):
        """Shadow traffic: computed + counted, never returned; identical
        shadow params never mismatch the primary actions."""
        import time

        telemetry = Registry()
        store = ParamStore()
        store.publish(0, params)
        registry = VersionRegistry(store, telemetry=Registry())
        registry.pin("live", 0)
        registry.pin("shadow", 0)
        registry.set_routing(
            {"live": 1.0}, shadow="shadow", shadow_fraction=1.0
        )
        server = PolicyServer(
            agent=agent,
            registry=registry,
            example_obs=np.zeros((OBS_DIM,), np.float32),
            max_clients=4,
            max_batch=4,
            max_wait_s=0.0,
            telemetry=telemetry,
        ).start()
        try:
            obs = obs_batch(2)
            expected = direct_greedy(agent, params, obs)
            clients = [InProcessClient(server) for _ in range(2)]
            got = [
                clients[i].act(obs[i], True) for i in range(2)
            ]
            assert np.array_equal(np.asarray(got), expected)
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                snap = telemetry.snapshot()
                if snap["telemetry/serving/shadow_total"] >= 2:
                    break
                time.sleep(0.01)
            snap = telemetry.snapshot()
            assert snap["telemetry/serving/shadow_total"] >= 2
            assert snap["telemetry/serving/shadow_mismatch"] == 0
        finally:
            server.close()


    def test_64_clients_coalesce_under_shadow_with_bf16_parity(
        self, agent, params
    ):
        """64 clients, one request each a round for 5 rounds, against a
        threaded server that may put all of them into one wave: the
        requests are served in fewer waves than there are requests, the
        actions are the direct greedy ones, every sampled wave is scored
        by a shadow label holding the same parameters without one
        mismatch, and the bfloat16 cast passes the greedy-action gate on
        the same 64 observations."""
        import time

        C, rounds = 64, 5
        telemetry = Registry()
        store = ParamStore()
        store.publish(0, params)
        registry = VersionRegistry(store, telemetry=Registry())
        registry.pin("live", 0)
        registry.pin("shadow", 0)
        registry.set_routing(
            {"live": 1.0}, shadow="shadow", shadow_fraction=1.0
        )
        server = PolicyServer(
            agent=agent,
            registry=registry,
            example_obs=np.zeros((OBS_DIM,), np.float32),
            max_clients=C,
            max_batch=C,
            max_wait_s=5e-3,
            telemetry=telemetry,
        ).start()
        obs = obs_batch(C, seed=4)
        try:
            clients = [InProcessClient(server, greedy=True) for _ in range(C)]
            for r in range(rounds):
                cells = [
                    c.act_async(obs[i], r == 0)
                    for i, c in enumerate(clients)
                ]
                got = [cell.result(timeout=120.0).action for cell in cells]
                if r == 0:
                    assert np.array_equal(
                        np.asarray(got), direct_greedy(agent, params, obs)
                    )
            for c in clients:
                c.close()
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if telemetry.snapshot()["telemetry/serving/shadow_total"]:
                    break
                time.sleep(0.01)
        finally:
            server.close()
        snap = telemetry.snapshot()
        assert snap["telemetry/serving/request_total"] == C * rounds
        assert (
            rounds
            <= snap["telemetry/serving/wave_total"]
            < snap["telemetry/serving/request_total"]
        )
        assert snap["telemetry/serving/shadow_total"] > 0
        assert snap["telemetry/serving/shadow_mismatch"] == 0
        ok, mismatches = greedy_action_parity(agent, params, obs)
        assert ok and mismatches == 0


# ---- shm request ring ---------------------------------------------------


class TestShmRing:
    def test_roundtrip_matches_in_process(self, agent, params):
        _, _, server = make_server(agent, params, max_batch=2)
        server.start()
        ring = ShmServingRing(
            capacity=4, obs_shape=(OBS_DIM,), obs_dtype=np.float32
        )
        pump = ShmRingPump(server).start()
        try:
            pump.attach(ring, greedy=True)
            obs = obs_batch(5, seed=9)
            expected = direct_greedy(agent, params, obs)
            rc = ShmRingClient(ring)
            # first=True every request: fresh-state rows, comparable to
            # the direct batch above.
            got = [rc.act(obs[i], True) for i in range(5)]
            assert np.array_equal(np.asarray(got), expected)
        finally:
            pump.stop()
            server.close()
            ring.close()

    def test_wraparound_under_backpressure(self, agent, params):
        """More requests than ring slots with the server initially DOWN:
        submit blocks at capacity (RingBackpressure), then the started
        server drains the ring and every response lands FIFO-correct
        across >2 wraparounds."""
        _, _, server = make_server(agent, params, max_batch=2)
        ring = ShmServingRing(
            capacity=3, obs_shape=(OBS_DIM,), obs_dtype=np.float32
        )
        pump = ShmRingPump(server)
        try:
            pump.attach(ring, greedy=True)
            n = 10  # > 3x capacity: the ring wraps at least 3 times
            obs = obs_batch(n, seed=11)
            expected = direct_greedy(agent, params, obs)
            rc = ShmRingClient(ring)
            for i in range(ring.capacity):
                rc.submit(obs[i], True)
            # Ring full, server down: backpressure must be a bounded
            # timeout, not a deadlock.
            with pytest.raises(RingBackpressure):
                rc.submit(obs[ring.capacity], True, timeout_s=0.05)
            assert rc.full_waits == 1
            server.start()
            pump.start()
            got = []
            submitted = ring.capacity
            while len(got) < n:
                got.append(rc.result(timeout_s=30.0)[0])
                if submitted < n:
                    rc.submit(obs[submitted], True, timeout_s=30.0)
                    submitted += 1
            assert np.array_equal(np.asarray(got), expected)
            assert rc.outstanding == 0
        finally:
            pump.stop()
            server.close()
            ring.close()

    def test_descriptor_attach(self):
        ring = ShmServingRing(
            capacity=2, obs_shape=(3,), obs_dtype=np.uint8
        )
        try:
            other = ShmServingRing.attach(ring.descriptor())
            other.obs[1] = np.asarray([1, 2, 3], np.uint8)
            other.status[1] = 1
            assert np.array_equal(ring.obs[1], [1, 2, 3])
            assert ring.status[1] == 1
            other.close()
        finally:
            ring.close()


# ---- bf16 serving + parity gate -----------------------------------------


class TestBf16Serving:
    def test_cast_params_touches_only_floats(self, params):
        cast = cast_params(params, jax.numpy.bfloat16)
        for ref, leaf in zip(
            jax.tree.leaves(params), jax.tree.leaves(cast)
        ):
            if jax.numpy.issubdtype(
                jax.numpy.result_type(ref), jax.numpy.floating
            ):
                assert leaf.dtype == jax.numpy.bfloat16
            else:
                assert leaf.dtype == ref.dtype

    def test_parity_gate_passes_on_mlp(self, agent, params):
        ok, mismatches = greedy_action_parity(
            agent, params, obs_batch(32)
        )
        assert ok and mismatches == 0

    def test_parity_gate_detects_divergence(self, agent, params):
        """The gate must actually FAIL when the cast policy argmaxes
        differently — not return a constant True. Casting to int8
        truncates the small random-init weights to zero (constant
        logits, argmax 0 everywhere), which provably diverges from the
        f32 argmaxes on a 64-row probe."""
        import jax.numpy as jnp

        ref = direct_greedy(agent, params, obs_batch(64))
        assert (ref != 0).any(), "degenerate policy; probe is vacuous"
        ok, mismatches = greedy_action_parity(
            agent, params, obs_batch(64), dtype=jnp.int8
        )
        assert not ok and mismatches > 0

    def test_bf16_server_serves_parity_actions(self, agent, params):
        """A dtype='bfloat16' server's greedy actions equal the f32
        direct actions on this model (the gate's promise, end-to-end)."""
        _, _, server = make_server(agent, params, dtype="bfloat16")
        try:
            obs = obs_batch(3, seed=21)
            expected = direct_greedy(agent, params, obs)
            clients = [InProcessClient(server) for _ in range(3)]
            cells = [
                c.act_async(obs[i], True) for i, c in enumerate(clients)
            ]
            server.service_once()
            got = np.asarray([c.result(1.0).action for c in cells])
            assert np.array_equal(got, expected)
        finally:
            server.close()


# ---- evaluator through the serving client (acceptance) ------------------


class _ActionRewardEnv:
    """Deterministic env whose RETURN depends on the action sequence:
    reward 1 when the action matches `t % NUM_ACTIONS`, else 0 —
    identical returns across two eval paths implies identical actions."""

    def __init__(self, seed=0, episode_len=8):
        self._rng_seed = seed
        self._episode_len = episode_len
        self._t = 0
        self.actions = []

    def _obs(self):
        rng = np.random.default_rng(self._rng_seed * 1000 + self._t)
        return rng.normal(size=(OBS_DIM,)).astype(np.float32)

    def reset(self, seed=None):
        if seed is not None:
            self._rng_seed = seed
        self._t = 0
        return self._obs(), {}

    def step(self, action):
        self.actions.append(int(action))
        reward = 1.0 if action == self._t % NUM_ACTIONS else 0.0
        self._t += 1
        done = self._t >= self._episode_len
        return self._obs(), reward, done, False, {}


class TestServingEvaluator:
    def test_client_path_identical_to_direct(self, agent, params):
        """ISSUE 6 acceptance: run_episodes through the serving client
        produces IDENTICAL episode returns (and the same action
        sequences) as the direct agent.step path at the same
        params/seed."""
        from torched_impala_tpu.runtime.evaluator import run_episodes

        env_direct = _ActionRewardEnv()
        direct = run_episodes(
            agent=agent,
            params=params,
            env=env_direct,
            num_episodes=3,
            greedy=True,
            seed=5,
        )
        _, _, server = make_server(agent, params, max_wait_s=0.0)
        server.start()
        try:
            env_served = _ActionRewardEnv()
            with InProcessClient(server, greedy=True) as client:
                served = run_episodes(
                    env=env_served,
                    num_episodes=3,
                    greedy=True,
                    seed=5,
                    client=client,
                )
        finally:
            server.close()
        assert served.returns == direct.returns
        assert served.lengths == direct.lengths
        assert env_served.actions == env_direct.actions

    def test_direct_path_requires_agent_and_params(self):
        from torched_impala_tpu.runtime.evaluator import run_episodes

        with pytest.raises(ValueError, match="agent"):
            run_episodes(env=_ActionRewardEnv(), num_episodes=1)


# ---- CLI wiring ---------------------------------------------------------


class TestServingCLI:
    def test_eval_serving_flag_end_to_end(self, capsys):
        """`--mode eval --eval-serving` runs the evaluator through a real
        PolicyServer (fresh params, fake envs) and reports the serving
        path in its summary line."""
        from torched_impala_tpu.run import main as cli_main

        rc = cli_main([
            "--config", "cartpole",
            "--mode", "eval",
            "--fake-envs",
            "--eval-serving",
            "--eval-episodes", "2",
        ])
        assert rc == 0
        out = capsys.readouterr().out
        assert "serving path, dtype=float32" in out

    def test_eval_serving_rejects_eval_parallel(self):
        from torched_impala_tpu.run import main as cli_main

        with pytest.raises(SystemExit, match="eval-serving"):
            cli_main([
                "--config", "cartpole",
                "--mode", "eval",
                "--fake-envs",
                "--eval-serving",
                "--eval-parallel", "4",
            ])


# ---- misc ---------------------------------------------------------------


def test_mint_request_lid_format():
    assert mint_request_lid(3, 17) == "c3r17"
