"""Chaos harness: declarative fault plans injected into the live pipeline.

`ActorSupervisor`, the env-pool restart repair, the stall watchdog, and
the resume path all CLAIM to handle failure; this module exercises those
claims on demand instead of waiting for production to. A `ChaosPlan` is a
list of `Fault`s — each names a KIND, an injection SITE counter value
(`at` = the Nth event observed at that site), and an optional target —
parsed from JSON (`--chaos-plan plan.json`) or built in code (tests).

Fault kinds and the hook site each rides:

  kind                site      effect
  ------------------  --------  ------------------------------------------
  kill_env_worker     pool      SIGKILL worker `target`'s OS process mid-
                                run; the pool's send/recv repair respawns
                                it and reports a clean episode boundary
  delay_lane          pool      sleep `duration_s` in the parent's lane
                                path — a wedged/slow shm lane
  raise_in_actor      actor     raise ChaosError inside actor `target`'s
                                unroll; the supervisor must restart it
  wedge_queue         enqueue   block one trajectory enqueue for
                                `duration_s` — starves the learner, the
                                stall watchdog's trigger condition
  crash_learner       learner   raise ChaosError from the post-step hook:
                                the run dies WITHOUT a final checkpoint,
                                exactly like SIGKILL on the learner host
  corrupt_checkpoint  save      overwrite bytes inside the just-written
                                checkpoint file; the recovery scan must
                                reject it and fall back one step
  kill_server_mid_wave serving  abrupt PolicyServer death at the top of
                                a wave (pending requests fail
                                ServerClosed); the fleet router must
                                mark the replica DEAD and retry each
                                in-flight request elsewhere exactly once
  corrupt_pinned_version serving swap the params a replica's label is
                                pinned to for a shape-truncated tree in
                                the store ring; the next wave raises,
                                the server fails the group cleanly and
                                kills itself, the fleet fails over
  wedge_shm_ring      pump      stall the shm request-ring pump for
                                `duration_s` — a wedged cross-process
                                transport under live clients
  kill_host           ring_commit  SIGKILL THIS whole OS process at the
                                top of a trajectory-ring block commit —
                                a simulated pod host dying mid-write
                                (parallel/simhost.py clusters). No
                                teardown, no final checkpoint, the slot
                                left torn; the survivor-driven restart
                                must discard it (`discard_torn`) and
                                resume from the last durable checkpoint

Sites count monotonically from 1; a fault fires when its site's counter
reaches `at` (once — every fault is one-shot). The injector is
thread-safe: sites are hit from actor threads, the batcher, the learner
thread, and the checkpoint writer concurrently.
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import sys
import threading
import time
from typing import Callable, List, Optional, Sequence

from torched_impala_tpu.telemetry.registry import Registry, get_registry

KINDS = (
    "kill_env_worker",
    "delay_lane",
    "raise_in_actor",
    "wedge_queue",
    "crash_learner",
    "corrupt_checkpoint",
    "kill_server_mid_wave",
    "corrupt_pinned_version",
    "wedge_shm_ring",
    "kill_host",
)

_SITE_OF = {
    "kill_env_worker": "pool",
    "delay_lane": "pool",
    "raise_in_actor": "actor",
    "wedge_queue": "enqueue",
    "crash_learner": "learner",
    "corrupt_checkpoint": "save",
    "kill_server_mid_wave": "serving",
    "corrupt_pinned_version": "serving",
    "wedge_shm_ring": "pump",
    "kill_host": "ring_commit",
}


class ChaosError(RuntimeError):
    """An injected fault (not a real bug) — recognizable in logs/tests."""


@dataclasses.dataclass(frozen=True)
class Fault:
    """One scheduled fault: fire at the `at`-th event on `kind`'s site."""

    kind: str
    at: int
    target: int = -1  # worker index / actor slot; -1 = any
    duration_s: float = 0.0  # delay_lane / wedge_queue only

    def __post_init__(self) -> None:
        if self.kind not in KINDS:
            raise ValueError(
                f"unknown fault kind {self.kind!r}; expected one of "
                f"{KINDS}"
            )
        if self.at < 1:
            raise ValueError(
                f"fault {self.kind}: `at` counts site events from 1, "
                f"got {self.at}"
            )
        if self.duration_s < 0:
            raise ValueError(f"fault {self.kind}: negative duration_s")

    @property
    def site(self) -> str:
        return _SITE_OF[self.kind]


@dataclasses.dataclass(frozen=True)
class ChaosPlan:
    """An ordered fault list; the declarative artifact tests assert
    against."""

    faults: tuple

    def __init__(self, faults: Sequence[Fault]) -> None:
        object.__setattr__(self, "faults", tuple(faults))

    @classmethod
    def from_dicts(cls, objs: Sequence[dict]) -> "ChaosPlan":
        faults = []
        for i, obj in enumerate(objs):
            unknown = set(obj) - {f.name for f in dataclasses.fields(Fault)}
            if unknown:
                raise ValueError(
                    f"fault #{i}: unknown field(s) {sorted(unknown)}; "
                    f"schema is kind/at/target/duration_s "
                    "(docs/RESILIENCE.md)"
                )
            faults.append(Fault(**obj))
        return cls(faults)

    @classmethod
    def from_json(cls, path: str) -> "ChaosPlan":
        with open(path, encoding="utf-8") as f:
            objs = json.load(f)
        if not isinstance(objs, list):
            raise ValueError(
                f"chaos plan {path} must be a JSON list of fault objects"
            )
        return cls.from_dicts(objs)


class ChaosInjector:
    """Executes a `ChaosPlan` through the pipeline's chaos hooks.

    The runtime attaches one bound hook per site (`loop.train` does the
    wiring): hooks are no-ops costing one attribute check when no plan
    targets their site, and every fired fault increments the
    `resilience/chaos_faults` counter plus a stderr breadcrumb so a chaos
    run's log explains its own weirdness."""

    def __init__(
        self,
        plan: ChaosPlan,
        *,
        telemetry: Optional[Registry] = None,
    ) -> None:
        self.plan = plan
        self._lock = threading.Lock()
        self._counts = {site: 0 for site in set(_SITE_OF.values())}
        self._armed: List[Fault] = list(plan.faults)
        self.fired: List[Fault] = []
        reg = telemetry if telemetry is not None else get_registry()
        self._m_faults = reg.counter("resilience/chaos_faults")

    def _trigger(self, site: str, target: int = -1) -> List[Fault]:
        """Advance `site`'s counter; pop every armed fault due now (match
        on site, count, and — when both sides specify one — target)."""
        with self._lock:
            self._counts[site] += 1
            n = self._counts[site]
            due, rest = [], []
            for f in self._armed:
                if (
                    f.site == site
                    and n >= f.at
                    and (f.target < 0 or target < 0 or f.target == target)
                ):
                    due.append(f)
                else:
                    rest.append(f)
            self._armed = rest
            for f in due:
                self.fired.append(f)
        for f in due:
            self._m_faults.inc()
            print(
                f"[chaos] firing {f.kind} (site={site} event #{n} "
                f"target={target})",
                file=sys.stderr,
                flush=True,
            )
        return due

    @property
    def pending(self) -> int:
        with self._lock:
            return len(self._armed)

    # ---- site hooks ----------------------------------------------------

    def pool_hook(self, pool) -> None:
        """Attach as `pool.chaos_hook`; called once per dispatch wave.
        kill_env_worker SIGKILLs a live worker process (abrupt death —
        no cleanup, the exact failure the pool's repair path claims to
        absorb); delay_lane stalls the parent's lane path."""
        for f in self._trigger("pool"):
            if f.kind == "kill_env_worker":
                w = f.target if f.target >= 0 else 0
                w = min(w, pool.num_workers - 1)
                proc = pool._procs[w]
                if proc is not None and proc.pid and proc.is_alive():
                    os.kill(proc.pid, signal.SIGKILL)
            elif f.kind == "delay_lane":
                time.sleep(f.duration_s)

    def actor_hook(self, actor_id: int) -> None:
        """Attach as the actor's `chaos_hook`; called at each unroll
        start. raise_in_actor kills this unroll with ChaosError — the
        supervisor must restart the slot."""
        for f in self._trigger("actor", target=actor_id):
            if f.kind == "raise_in_actor":
                raise ChaosError(
                    f"injected actor crash (actor {actor_id})"
                )

    def wrap_enqueue(self, enqueue: Callable) -> Callable:
        """Wrap the learner's enqueue; wedge_queue blocks ONE enqueue for
        duration_s (trajectory starvation upstream of the batcher)."""

        def chaotic_enqueue(traj):
            for f in self._trigger("enqueue"):
                if f.kind == "wedge_queue":
                    time.sleep(f.duration_s)
            return enqueue(traj)

        return chaotic_enqueue

    def learner_hook(self, num_steps: int) -> None:
        """Attach as a post-step hook. crash_learner aborts the run with
        ChaosError — teardown runs, the FINAL checkpoint save does not
        (exactly a mid-run process death for the resume path)."""
        for f in self._trigger("learner"):
            if f.kind == "crash_learner":
                raise ChaosError(
                    f"injected learner crash at step {num_steps}"
                )

    def checkpoint_hook(self, path: str, step: int) -> None:
        """Attach as AsyncCheckpointer's post_save. corrupt_checkpoint
        scribbles over bytes mid-file: the zip CRCs must catch it and the
        recovery scan must fall back to the previous retained step."""
        for f in self._trigger("save"):
            if f.kind == "corrupt_checkpoint":
                corrupt_file(path)

    def serving_hook(self, server, replica: int = -1) -> None:
        """Attach as `PolicyServer.chaos_hook` (install binds one per
        fleet replica with its index as the target); called at the top
        of every wave execution, before any label group runs.

        kill_server_mid_wave: abrupt `server.kill()` — the wave's
        requests fail ServerClosed without an answer, exactly a replica
        process dying between dequeue and compute. corrupt_pinned_version:
        bit-rot the pinned snapshot in the store ring (below) so the
        wave itself raises and the server's fail-the-group path runs."""
        for f in self._trigger("serving", target=replica):
            if f.kind == "kill_server_mid_wave":
                server.kill(reason="chaos kill_server_mid_wave")
            elif f.kind == "corrupt_pinned_version":
                corrupt_pinned_params(server.registry)

    def ring_commit_hook(self, slot: int = -1) -> None:
        """Attach as `TrajectoryRing.chaos_hook`; called with the slot
        index at the top of every block commit. kill_host SIGKILLs THIS
        process while the slot is torn (columns handed out, commit not
        counted) — the abrupt death of one simulated pod host. The
        multi-host launcher (parallel/simhost.py) reaps the corpse and
        kills the survivors blocked in collectives; recovery relaunches
        the cluster with resume=True and the chaos plan disarmed."""
        for f in self._trigger("ring_commit", target=slot):
            if f.kind == "kill_host":
                os.kill(os.getpid(), signal.SIGKILL)

    def pump_hook(self, pump=None) -> None:
        """Attach as `ShmRingPump.chaos_hook`; wedge_shm_ring stalls one
        pump scan for duration_s — clients see latency, never errors."""
        for f in self._trigger("pump"):
            if f.kind == "wedge_shm_ring":
                time.sleep(f.duration_s)

    def install(
        self,
        *,
        pools: Sequence = (),
        checkpointer=None,
        fleets: Sequence = (),
        servers: Sequence = (),
        pumps: Sequence = (),
    ) -> None:
        """Convenience wiring for the hookable objects that take
        attributes (actors/enqueue/post-step hooks are wired where those
        callables are built — see loop.train)."""
        for pool in pools:
            pool.chaos_hook = self.pool_hook
        if checkpointer is not None:
            checkpointer._post_save = self.checkpoint_hook
        for fleet in fleets:
            for i, rep in enumerate(fleet.replicas()):
                rep.server.chaos_hook = (
                    lambda srv, _i=i: self.serving_hook(srv, replica=_i)
                )
        for server in servers:
            server.chaos_hook = self.serving_hook
        for pump in pumps:
            pump.chaos_hook = self.pump_hook


def corrupt_pinned_params(registry) -> int:
    """Bit-rot the snapshot a registry's first pinned label resolves to:
    swap the params in the store's retention ring for a copy whose first
    multi-row leaf is TRUNCATED along axis 0 (reaching into `_ring` the
    way pool_hook reaches into `_procs` — chaos simulates damage the
    public API exists to prevent). The next wave that resolves the label
    fails at trace time with a shape error; the server must fail that
    group with ServerClosed and kill itself rather than wedge clients.
    Returns the corrupted version."""
    import jax

    pinned = registry.pinned()
    label = sorted(pinned)[0]
    version = pinned[label]
    store = registry.store
    params = store.get_version(version)
    leaves, treedef = jax.tree.flatten(params)
    for i, leaf in enumerate(leaves):
        if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] >= 2:
            leaves[i] = leaf[:-1]
            break
    corrupted = jax.tree.unflatten(treedef, leaves)
    with store._lock:
        store._ring[version] = corrupted
        if store._version == version:
            store._params = corrupted
    return version


def corrupt_file(path: str, offset_frac: float = 0.5, nbytes: int = 64) -> None:
    """Overwrite `nbytes` bytes in the middle of `path` in place (no
    rename — simulating bit rot / a torn write, NOT an atomic writer)."""
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(max(0, int(size * offset_frac) - nbytes // 2))
        f.write(b"\xde\xad\xbe\xef" * (nbytes // 4 + 1))
