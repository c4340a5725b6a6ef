"""PolicyServer: one device, many clients, continuous-batched inference.

The Sebulba/Podracer decomposition (arxiv 2104.06272) applied as a
standalone service: CPU-side clients (env steppers, evaluators, request
rings) send `(obs, first)` and get actions back, while ONE server thread
owns the device and answers every outstanding request with a single
jitted forward per WAVE — the production policy-serving shape for a fleet
where per-client inference would drown in dispatch overhead.

Core mechanics (docs/SERVING.md has the diagrams):

- CONTINUOUS BATCHING: requests land in a pending queue; a wave forms
  when `max_batch` distinct clients are waiting OR the oldest request
  has aged `max_wait_s` (deadline + max-batch coalescing). Waves are
  padded to a FIXED `max_batch` so the jitted step compiles exactly once
  per policy-tree structure — padded rows gather a clipped state row and
  scatter with `mode="drop"`, so they are pure throwaway compute.
- PER-CLIENT RECURRENT STATE: the server holds the `[max_clients, ...]`
  LSTM carry and gathers/scatters the wave's rows inside the jitted
  step. Clients never see (or round-trip) recurrent state; `first=True`
  resets a row via the net's reset-core semantics, exactly as in the
  actor runtime. One request per client per wave keeps the carry chain
  causal even when a client pipelines requests (shm ring transport).
- VERSIONED ROUTING: each client is stickily routed to a registry label
  at connect; each wave resolves its label's `(version, params)` ONCE,
  so every action in a wave comes from a single consistent version even
  while labels are re-pinned concurrently (pinned by
  tests/test_serving.py::TestVersionSwapMidWave).
- SHADOW TRAFFIC: when the registry names a shadow label, a sampled
  fraction of primary waves is re-scored under the shadow version on a
  best-effort background thread (bounded queue, drop-when-busy) — actions
  are logged (`serving/shadow_mismatch`) and NEVER returned, and the
  primary wave path never blocks on shadow compute.
- REDUCED-PRECISION SERVING: `dtype="bfloat16"` casts each pinned
  version's floating params once (cached per version); `dtype="int8"`
  quantizes them per-channel (serving/quant.py) and dequantizes inside
  the jitted wave — the actor-side speed/memory levers. Policy: both
  must pass the f32 greedy-action parity gate (`greedy_action_parity`,
  run by doctor/tests/run.py) before a fleet trusts them.

Every request carries a lineage ID (`c<slot>r<seq>`) recorded on the
`serving/request` span; waves record `serving/wave` with the exact
(label, version, fill) — so flight-recorder traces tie a served action
to the policy version that produced it, the same provenance chain the
training pipeline has.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from torched_impala_tpu.models.agent import Agent
from torched_impala_tpu.serving.quant import (
    Int8Params,
    dequantize_params,
    quantize_params,
)
from torched_impala_tpu.serving.registry import VersionRegistry
from torched_impala_tpu.telemetry.registry import Registry, get_registry
from torched_impala_tpu.telemetry.tracing import (
    FlightRecorder,
    get_recorder,
)


# Sliding window for `serving/shadow_mismatch_rate`: the raw mismatch
# counter only ever grows, so "is the candidate diverging NOW" needs a
# windowed rate — this is the health plane's shadow_mismatch SloSpec
# input (telemetry/health.py:health_slo_specs), sized to a couple of
# alert fast-windows so the gauge and the burn computation agree about
# "recent".
SHADOW_RATE_WINDOW_S = 60.0


class ServingError(RuntimeError):
    """Base class for request-path failures."""


class DeadlineExpired(ServingError):
    """The request's deadline passed before a wave picked it up."""


class ClientDisconnected(ServingError):
    """The client disconnected while the request was pending."""


class ServerClosed(ServingError):
    """The server shut down with the request outstanding."""


class ServeResult(NamedTuple):
    """One answered request: the action plus its exact provenance."""

    action: int
    version: int  # policy version the action was computed from
    label: str  # registry label that version was resolved through
    wave: int  # server wave sequence number that answered it


class _ResultCell:
    """Write-once result slot (the cross-thread response handoff).

    First finish/fail wins; later calls are no-ops — so a disconnect
    racing a wave completion can never raise, unlike stdlib futures.
    """

    __slots__ = ("_event", "_result", "_error")

    def __init__(self) -> None:
        self._event = threading.Event()
        self._result: Optional[ServeResult] = None
        self._error: Optional[BaseException] = None

    def finish(self, result: ServeResult) -> None:
        if not self._event.is_set():
            self._result = result
            self._event.set()

    def fail(self, error: BaseException) -> None:
        if not self._event.is_set():
            self._error = error
            self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> ServeResult:
        if not self._event.wait(timeout=timeout):
            raise TimeoutError("no response within timeout")
        if self._error is not None:
            raise self._error
        assert self._result is not None
        return self._result


class _Request:
    __slots__ = (
        "slot", "obs", "first", "deadline", "cell", "lid", "t_submit_ns",
        # Stamped at wave formation (under the lock), read at execution.
        "greedy_flag", "label",
    )

    def __init__(self, slot, obs, first, deadline, cell, lid, t_submit_ns):
        self.slot = slot
        self.obs = obs
        self.first = first
        self.deadline = deadline
        self.cell = cell
        self.lid = lid
        self.t_submit_ns = t_submit_ns
        self.greedy_flag = True
        self.label = ""


class _Slot:
    __slots__ = ("greedy", "label", "requests")

    def __init__(self, greedy: bool, label: str):
        self.greedy = greedy
        self.label = label
        self.requests = 0  # per-slot sequence for lineage IDs


def mint_request_lid(slot: int, seq: int) -> str:
    """Serving lineage ID format — `c<client-slot>r<seq>` — the serving
    analog of the actor runtime's `a<actor>u<seq>` unroll IDs."""
    return f"c{slot}r{seq}"


def cast_params(params: Any, dtype) -> Any:
    """Cast every floating leaf of a param tree to `dtype` (non-float
    leaves — int counters, PRNG keys — pass through untouched)."""
    dtype = jnp.dtype(dtype)

    def leaf(a):
        if jnp.issubdtype(jnp.result_type(a), jnp.floating):
            return jnp.asarray(a, dtype)
        return a

    return jax.tree.map(leaf, params)


def greedy_action_parity(
    agent: Agent,
    params: Any,
    obs_batch: np.ndarray,
    dtype="bfloat16",
    cast_fn=None,
) -> tuple[bool, int]:
    """The reduced-precision parity gate (docs/SERVING.md): greedy
    (argmax) actions from the `dtype` serving representation must equal
    the f32 actions on `obs_batch` (fresh initial state, first=True
    rows). Returns (ok, mismatches). `dtype="int8"` gates the
    quantize→dequantize roundtrip (serving/quant.py) through the SAME
    comparison bf16 uses; `cast_fn` overrides the representation
    entirely (doctor seeds corrupted scales through it). RNG-free by
    construction — argmax needs no key, so the gate is deterministic."""
    B = int(obs_batch.shape[0])
    first = jnp.ones((B,), jnp.bool_)
    state = agent.initial_state(B)
    key = jax.random.key(0)  # unused by argmax; step() wants one

    @jax.jit
    def _greedy(p):
        out = agent.step(p, key, obs_batch, first, state)
        return jnp.argmax(out.policy_logits, axis=-1)

    if cast_fn is None:
        if dtype == "int8":
            cast_fn = lambda p: dequantize_params(quantize_params(p))  # noqa: E731
        else:
            cast_fn = lambda p: cast_params(p, dtype)  # noqa: E731
    a_ref = np.asarray(_greedy(params))
    a_cast = np.asarray(_greedy(cast_fn(params)))
    mismatches = int(np.sum(a_ref != a_cast))
    return mismatches == 0, mismatches


class PolicyServer:
    """Batched inference service over a `VersionRegistry`.

    Lifecycle: construct, `start()` the serving thread (or drive
    `service_once()` deterministically from tests), `connect()` clients,
    `submit()` requests, `close()`. The in-process client
    (serving/client.py) and the shm request ring (serving/shm_ring.py)
    wrap the connect/submit surface.
    """

    def __init__(
        self,
        *,
        agent: Agent,
        registry: VersionRegistry,
        example_obs: np.ndarray,
        max_clients: int = 64,
        max_batch: int = 32,
        max_wait_s: float = 2e-3,
        dtype: str = "float32",
        seed: int = 0,
        telemetry: Optional[Registry] = None,
        tracer: Optional[FlightRecorder] = None,
    ) -> None:
        if max_clients < 1 or max_batch < 1:
            raise ValueError("need max_clients >= 1 and max_batch >= 1")
        if max_wait_s < 0:
            raise ValueError(f"max_wait_s must be >= 0, got {max_wait_s}")
        if dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError(
                f"unknown serving dtype {dtype!r}; expected 'float32', "
                "'bfloat16' or 'int8'"
            )
        self._agent = agent
        self._registry = registry
        self._max_clients = max_clients
        self._max_batch = min(max_batch, max_clients)
        # Waves are always padded to `_pad_batch` so the jitted wave fn
        # sees ONE shape for the server's lifetime; `_max_batch` is only
        # the wave-formation cap and may be tuned down (never up past
        # the pad) online by the control plane without a re-jit.
        self._pad_batch = self._max_batch
        self._max_wait_s = float(max_wait_s)
        self._dtype = dtype
        self._example_obs = np.asarray(example_obs)

        self._cond = threading.Condition()
        self._pending: "collections.deque[_Request]" = collections.deque()
        self._slots: Dict[int, _Slot] = {}
        self._free_slots = list(range(max_clients - 1, -1, -1))
        self._pending_resets: List[int] = []
        self._closed = False
        self._killed = False
        # Chaos/fleet hook: called (with the server) at the top of every
        # wave execution; the injector wires faults through it.
        self.chaos_hook = None  # lint: guarded-by(gil)
        # One servicer at a time: the serve thread normally, a test's
        # service_once() otherwise — the recurrent-state pytree and the
        # wave RNG key are only ever touched under this lock.
        self._service_lock = threading.Lock()

        self._key = jax.random.key(seed)
        self._state = agent.initial_state(max_clients)
        self._has_state = bool(jax.tree.leaves(self._state))
        self._init_row = agent.initial_state(1)
        self._wave_fn = self._build_wave_fn()
        self._wave_seq = 0
        # version -> cast/quantized params (bfloat16/int8 only); bounded
        # like the store's retention ring so dead versions don't pin
        # host/HBM. Own lock: `warm()` must be able to populate it while
        # the serve thread idles inside `_form_wave` holding
        # `_service_lock`.
        self._cast_cache: "collections.OrderedDict[int, Any]" = (
            collections.OrderedDict()
        )
        self._cast_lock = threading.Lock()

        # Shadow scoring: bounded handoff + one best-effort thread. The
        # primary path only ever does a non-blocking put.
        self._shadow_q: "collections.deque" = collections.deque(maxlen=2)
        self._shadow_evt = threading.Event()
        self._shadow_key = jax.random.key(seed + 1)
        self._shadow_acc = 0.0

        reg = telemetry if telemetry is not None else get_registry()
        self._m_request_total = reg.counter("serving/request_total")
        self._m_request_expired = reg.counter("serving/request_expired")
        self._m_request_dropped = reg.counter("serving/request_dropped")
        self._m_request_wait = reg.histogram("serving/request_wait_ms")
        self._m_wave_total = reg.counter("serving/wave_total")
        self._m_wave_failed = reg.counter("serving/wave_failed")
        self._m_wave_ms = reg.histogram("serving/wave_ms")
        self._m_wave_size = reg.histogram(
            "serving/wave_size",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512),
        )
        self._m_shadow_total = reg.counter("serving/shadow_total")
        self._m_shadow_skipped = reg.counter("serving/shadow_skipped")
        self._m_shadow_mismatch = reg.counter("serving/shadow_mismatch")
        self._m_shadow_ms = reg.histogram("serving/shadow_ms")
        # (t, scored, mismatched) per shadow wave; appended by the
        # shadow thread, pruned at read time by the gauge fn (deque ops
        # are individually atomic, and only the gauge ever pops).
        self._shadow_rate_window: "collections.deque" = collections.deque()
        reg.gauge(
            "serving/shadow_mismatch_rate", fn=self._shadow_mismatch_rate
        )
        self._registry_ref = reg
        reg.gauge(
            "serving/client_connected", fn=lambda: len(self._slots)
        )
        self._tracer = tracer if tracer is not None else get_recorder()

        self._thread: Optional[threading.Thread] = None
        self._shadow_thread: Optional[threading.Thread] = None

    # -- public surface ----------------------------------------------------

    @property
    def max_batch(self) -> int:
        return self._max_batch

    @property
    def pad_batch(self) -> int:
        """Fixed padded wave width (the jit shape). Never tunable."""
        return self._pad_batch

    @property
    def max_wait_s(self) -> float:
        return self._max_wait_s

    def set_max_batch(self, n: int) -> None:
        """Hot-apply path for the control plane: retune the
        wave-formation cap within [1, pad_batch]. The pad width is
        untouched, so this can never force a recompile."""
        n = max(1, min(int(n), self._pad_batch))
        with self._cond:
            self._max_batch = n
            self._cond.notify_all()

    def set_max_wait_s(self, s: float) -> None:
        """Hot-apply path for the control plane: retune the coalescing
        window (clamped to >= 0)."""
        s = max(0.0, float(s))
        with self._cond:
            self._max_wait_s = s
            self._cond.notify_all()

    @property
    def registry(self) -> VersionRegistry:
        return self._registry

    @property
    def dtype(self) -> str:
        return self._dtype

    @property
    def closed(self) -> bool:
        return self._closed

    @property
    def killed(self) -> bool:
        return self._killed

    @property
    def pending_count(self) -> int:
        """Requests queued but not yet taken into a wave (the fleet's
        drain loop polls this alongside its own in-flight count)."""
        with self._cond:
            return len(self._pending)

    def start(self) -> "PolicyServer":
        """Spawn the serving thread (idempotent)."""
        from torched_impala_tpu.telemetry import install_thread_excepthook

        # Server startup is a thread-spawning entrypoint of its own
        # (serving runs without loop.train): arm the same process-wide
        # crash-to-telemetry backstop before the first wave thread.
        install_thread_excepthook()
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._serve_loop, name="policy-server", daemon=True
            )
            self._thread.start()
        if self._shadow_thread is None:
            self._shadow_thread = threading.Thread(
                target=self._shadow_loop, name="policy-shadow", daemon=True
            )
            self._shadow_thread.start()
        return self

    def connect(
        self, greedy: bool = True, client_id: Optional[int] = None
    ) -> int:
        """Claim a client slot; returns the slot id (the submit handle).

        Routing is resolved HERE and stays sticky for the connection
        (`client_id` overrides the hash key — default: the slot id).
        The slot's recurrent-state row is scheduled for reset before the
        next wave, so a fresh connection never inherits a predecessor's
        carry even if it (wrongly) skips `first=True`."""
        with self._cond:
            if self._closed:
                raise ServerClosed("server is closed")
            if not self._free_slots:
                raise RuntimeError(
                    f"server is at max_clients={self._max_clients}"
                )
            slot = self._free_slots.pop()
            label = self._registry.route(
                slot if client_id is None else client_id
            )
            self._slots[slot] = _Slot(greedy=greedy, label=label)
            if self._has_state:
                self._pending_resets.append(slot)
        return slot

    def disconnect(self, slot: int) -> None:
        """Release a slot. Pending (not-yet-waved) requests from it fail
        with ClientDisconnected; an in-flight wave finishes harmlessly
        (its write lands in a write-once cell nobody reads)."""
        with self._cond:
            if slot not in self._slots:
                return
            del self._slots[slot]
            self._free_slots.append(slot)
            kept: List[_Request] = []
            for req in self._pending:
                if req.slot == slot:
                    self._m_request_dropped.inc()
                    req.cell.fail(
                        ClientDisconnected(f"slot {slot} disconnected")
                    )
                else:
                    kept.append(req)
            self._pending = collections.deque(kept)

    def submit(
        self,
        slot: int,
        obs: np.ndarray,
        first: bool,
        deadline_s: Optional[float] = None,
    ) -> _ResultCell:
        """Queue one action request for `slot`; returns the result cell.

        `deadline_s` (relative seconds) bounds how long the request may
        WAIT for a wave: a wave formed after the deadline fails the cell
        with DeadlineExpired instead of computing a stale action."""
        obs = np.asarray(obs)
        if obs.shape != self._example_obs.shape:
            raise ValueError(
                f"obs shape {obs.shape} != serving shape "
                f"{self._example_obs.shape}"
            )
        cell = _ResultCell()
        now = time.monotonic()
        with self._cond:
            if self._closed:
                cell.fail(ServerClosed("server is closed"))
                return cell
            sl = self._slots.get(slot)
            if sl is None:
                cell.fail(ClientDisconnected(f"slot {slot} not connected"))
                return cell
            lid = mint_request_lid(slot, sl.requests)
            sl.requests += 1
            self._pending.append(
                _Request(
                    slot=slot,
                    obs=obs,
                    first=bool(first),
                    deadline=(
                        None if deadline_s is None else now + deadline_s
                    ),
                    cell=cell,
                    lid=lid,
                    t_submit_ns=time.monotonic_ns(),
                )
            )
            self._m_request_total.inc()
            self._cond.notify_all()
        return cell

    def service_once(self) -> int:
        """Form and run AT MOST one wave from the current pending set,
        without waiting out the coalescing window — the deterministic
        drive for tests and the doctor. Returns requests answered."""
        with self._service_lock:
            reqs = self._form_wave(flush=True)
            if not reqs:
                return 0
            return self._run_wave(reqs)

    def kill(self, reason: str = "killed") -> None:
        """Abrupt death (chaos `kill_server_mid_wave`, failed waves):
        fail everything pending and stop, WITHOUT joining threads — so
        it is safe to call from the serve thread itself mid-wave. The
        fleet router sees ServerClosed surface on the clients and fails
        the replica over; `close()` afterwards still joins cleanly."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._killed = True
            pending = list(self._pending)
            self._pending.clear()
            self._cond.notify_all()
        for req in pending:
            req.cell.fail(ServerClosed(f"server killed: {reason}"))
        self._shadow_evt.set()

    def close(self) -> None:
        """Stop serving; every outstanding request fails ServerClosed."""
        with self._cond:
            already = self._closed
            self._closed = True
            pending = [] if already else list(self._pending)
            self._pending.clear()
            self._cond.notify_all()
        for req in pending:
            req.cell.fail(ServerClosed("server closed"))
        self._shadow_evt.set()
        cur = threading.current_thread()
        if self._thread is not None and self._thread is not cur:
            self._thread.join(timeout=10)
        if self._shadow_thread is not None and self._shadow_thread is not cur:
            self._shadow_thread.join(timeout=10)

    # -- wave formation ----------------------------------------------------

    def _form_wave(self, flush: bool) -> List[_Request]:
        """Pop up to `max_batch` serviceable requests — first request per
        distinct slot, FIFO; duplicates stay queued for the next wave
        (the per-client carry chain must advance one step per wave).
        Expired/disconnected requests are failed in place. `flush=False`
        honors the coalescing window (deadline + max-batch)."""
        with self._cond:
            if not flush:
                while not self._closed and not self._pending:
                    self._cond.wait(0.1)
                if self._pending:
                    window_end = (
                        self._pending[0].t_submit_ns * 1e-9
                        + self._max_wait_s
                    )
                    while not self._closed:
                        distinct = len({r.slot for r in self._pending})
                        if distinct >= self._max_batch:
                            break
                        remaining = window_end - time.monotonic()
                        if remaining <= 0:
                            break
                        self._cond.wait(remaining)
            if self._closed or not self._pending:
                return []
            now = time.monotonic()
            taken: List[_Request] = []
            taken_slots: set = set()
            leftover: List[_Request] = []
            for req in self._pending:
                if req.cell.done():
                    continue
                if req.slot not in self._slots:
                    self._m_request_dropped.inc()
                    req.cell.fail(
                        ClientDisconnected(
                            f"slot {req.slot} disconnected mid-queue"
                        )
                    )
                    continue
                if req.deadline is not None and now > req.deadline:
                    self._m_request_expired.inc()
                    req.cell.fail(
                        DeadlineExpired(
                            f"request {req.lid} expired before a wave "
                            f"formed"
                        )
                    )
                    continue
                if (
                    req.slot in taken_slots
                    or len(taken) >= self._max_batch
                ):
                    leftover.append(req)
                    continue
                taken.append(req)
                taken_slots.add(req.slot)
            self._pending = collections.deque(leftover)
            resets = self._pending_resets
            self._pending_resets = []
            greedy = {r.slot: self._slots[r.slot].greedy for r in taken}
            labels = {r.slot: self._slots[r.slot].label for r in taken}
        self._apply_resets(resets)
        for req in taken:
            req.greedy_flag = greedy[req.slot]
            req.label = labels[req.slot]
        return taken

    def _apply_resets(self, slots: Sequence[int]) -> None:  # lint: guarded-by(_service_lock)
        if not self._has_state or not slots:
            return
        idx = jnp.asarray(sorted(set(slots)), jnp.int32)
        n = int(idx.shape[0])
        self._state = jax.tree.map(
            lambda full, one: full.at[idx].set(
                jnp.broadcast_to(one, (n,) + tuple(one.shape[1:]))
            ),
            self._state,
            self._init_row,
        )

    # -- wave execution ----------------------------------------------------

    def _build_wave_fn(self):
        agent = self._agent
        max_clients = self._max_clients

        def _wave(params, key, obs, first, idx, state):
            if isinstance(params, Int8Params):
                # Python-level branch: jit retraces once for the int8
                # pytree structure; the device holds int8 + f32 scales
                # and reconstructs f32 weights inside the compiled wave.
                params = dequantize_params(params)
            key, sub = jax.random.split(key)
            gather = jnp.minimum(idx, max_clients - 1)
            rows = jax.tree.map(lambda a: a[gather], state)
            out = agent.step(params, sub, obs, first, rows)
            greedy = jnp.argmax(out.policy_logits, axis=-1).astype(
                jnp.int32
            )
            # Padded rows carry idx == max_clients: out of range, so the
            # scatter drops them and the full state stays untouched.
            new_state = jax.tree.map(
                lambda full, new: full.at[idx].set(new, mode="drop"),
                state,
                out.state,
            )
            return key, out.action, greedy, new_state

        return jax.jit(_wave)

    def _params_for(self, version: int, params: Any) -> Any:  # lint: guarded-by(_cast_lock)
        if self._dtype == "float32":
            return params
        with self._cast_lock:
            cached = self._cast_cache.get(version)
            if cached is None:
                if self._dtype == "int8":
                    cached = quantize_params(params)
                else:
                    cached = cast_params(params, jnp.bfloat16)
                self._cast_cache[version] = cached
                while len(self._cast_cache) > 4:
                    self._cast_cache.popitem(last=False)
            return cached

    def warm(self, version: int) -> None:
        """Pre-resolve `version`'s serving-dtype params into the cast
        cache, so the quantize/cast cost lands NOW instead of inside
        the first wave at the new version. Draining rollouts
        (fleet.rollout) call this while the replica is still out of
        rotation: with a second replica carrying traffic the warm is
        free, with one replica it is downtime. No-op for float32."""
        if self._dtype == "float32":
            return
        params = self._registry.store.get_version(version)
        self._params_for(version, params)

    def _run_wave(self, reqs: List[_Request]) -> int:
        """Execute one wave per label group in `reqs`; returns requests
        answered. Must be called with `_service_lock` held.

        A wave that RAISES (corrupted pinned params, device loss, chaos)
        must not wedge its clients on cells nobody will ever write: the
        group's cells fail with ServerClosed and the server kills itself
        so the fleet router fails the replica over instead of feeding it
        more traffic."""
        hook = self.chaos_hook
        if hook is not None:
            try:
                hook(self)
            except Exception:
                pass  # chaos acts through explicit effects, never raises
        groups: Dict[str, List[_Request]] = {}
        for req in reqs:
            groups.setdefault(req.label, []).append(req)
        served = 0
        for label, group in groups.items():
            if self._closed:
                for req in group:
                    req.cell.fail(ServerClosed("server killed mid-wave"))
                continue
            try:
                served += self._run_label_wave(label, group)
            except Exception as e:
                self._m_wave_failed.inc()
                for req in group:
                    req.cell.fail(ServerClosed(f"wave failed: {e!r}"))
                self.kill(reason=f"wave execution failed: {e!r}")
        return served

    def _run_label_wave(self, label: str, group: List[_Request]) -> int:  # lint: guarded-by(_service_lock)
        B = self._pad_batch
        n = len(group)
        # Resolve ONCE: every action in this wave comes from this exact
        # (version, params) snapshot, re-pins land on the next wave.
        version, params = self._registry.resolve(label)
        params = self._params_for(version, params)
        obs = np.zeros((B,) + self._example_obs.shape,
                       self._example_obs.dtype)
        first = np.ones((B,), np.bool_)
        idx = np.full((B,), self._max_clients, np.int32)  # pad: dropped
        for i, req in enumerate(group):
            obs[i] = req.obs
            first[i] = req.first
            idx[i] = req.slot
        t0_ns = time.monotonic_ns()
        self._key, sampled, greedy, self._state = self._wave_fn(
            params, self._key, obs, first, idx, self._state
        )
        sampled = np.asarray(sampled)
        greedy = np.asarray(greedy)
        dur_ns = time.monotonic_ns() - t0_ns
        self._wave_seq += 1
        wave = self._wave_seq
        self._m_wave_total.inc()
        self._m_wave_ms.observe(dur_ns / 1e6)
        self._m_wave_size.observe(n)
        self._tracer.complete(
            "serving/wave",
            t0_ns,
            dur_ns,
            {"wave": wave, "label": label, "version": version, "n": n},
        )
        end_ns = time.monotonic_ns()
        for i, req in enumerate(group):
            action = int(greedy[i] if req.greedy_flag else sampled[i])
            self._m_request_wait.observe(
                (end_ns - req.t_submit_ns) / 1e6
            )
            self._tracer.complete(
                "serving/request",
                req.t_submit_ns,
                end_ns - req.t_submit_ns,
                {"lid": req.lid, "version": version, "wave": wave},
            )
            req.cell.finish(
                ServeResult(
                    action=action, version=version, label=label, wave=wave
                )
            )
        self._maybe_shadow(obs, first, idx, n, greedy)
        return n

    # -- shadow scoring ----------------------------------------------------

    def _maybe_shadow(self, obs, first, idx, n, primary_greedy) -> None:  # lint: guarded-by(_service_lock)
        shadow_label = self._registry.shadow
        if shadow_label is None:
            return
        self._shadow_acc += self._registry.shadow_fraction
        if self._shadow_acc < 1.0:
            return
        self._shadow_acc -= 1.0
        if len(self._shadow_q) == self._shadow_q.maxlen:
            # Best-effort by design: a busy shadow scorer drops samples
            # rather than backpressuring the primary path.
            self._m_shadow_skipped.inc()
            return
        try:
            version, params = self._registry.resolve(shadow_label)
        except KeyError:
            self._m_shadow_skipped.inc()
            return
        self._shadow_q.append(
            (obs, first, idx, n, primary_greedy.copy(), version,
             self._params_for(version, params), self._state)
        )
        self._shadow_evt.set()

    def _shadow_mismatch_rate(self) -> float:
        """Mismatched / scored actions over the last
        SHADOW_RATE_WINDOW_S seconds; NaN with no recent shadow wave
        (the alert engine skips NaN samples, so an idle shadow path
        never burns the shadow_mismatch SLO's budget)."""
        cutoff = time.monotonic() - SHADOW_RATE_WINDOW_S
        win = self._shadow_rate_window
        while win and win[0][0] < cutoff:
            win.popleft()
        rows = list(win)
        scored = sum(n for _, n, _ in rows)
        if scored == 0:
            return float("nan")
        return sum(m for _, _, m in rows) / scored

    def _shadow_loop(self) -> None:
        while True:
            self._shadow_evt.wait(timeout=0.2)
            if self._closed and not self._shadow_q:
                return
            try:
                item = self._shadow_q.popleft()
            except IndexError:
                self._shadow_evt.clear()
                continue
            obs, first, idx, n, primary_greedy, version, params, state = (
                item
            )
            t0_ns = time.monotonic_ns()
            self._shadow_key, _, shadow_greedy, _ = self._wave_fn(
                params, self._shadow_key, obs, first, idx, state
            )
            shadow_greedy = np.asarray(shadow_greedy)
            dur_ns = time.monotonic_ns() - t0_ns
            self._m_shadow_ms.observe(dur_ns / 1e6)
            self._m_shadow_total.inc(n)
            mismatched = int(
                np.sum(shadow_greedy[:n] != primary_greedy[:n])
            )
            self._m_shadow_mismatch.inc(mismatched)
            self._shadow_rate_window.append(
                (time.monotonic(), n, mismatched)
            )
            self._tracer.complete(
                "serving/shadow",
                t0_ns,
                dur_ns,
                {"version": version, "n": n},
            )

    # -- serve loop --------------------------------------------------------

    def _serve_loop(self) -> None:  # lint: hot-loop
        while True:
            with self._service_lock:
                reqs = self._form_wave(flush=False)
                if reqs:
                    self._run_wave(reqs)
            if self._closed:
                return
