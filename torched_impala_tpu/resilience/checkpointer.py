"""AsyncCheckpointer: the train loop never blocks on checkpoint disk I/O.

The learner-thread half of a save is two cheap operations: an interval
check (integer compare) and, when due, an on-device clone of the state
tree (`Learner.get_state_device` — async dispatch, no host sync). The
clone rides a depth-1 queue to a background writer thread that:

1. `device_get`s the clone into one of TWO reusable host buffers (the
   double buffer: capture into slot B can start while slot A's bytes are
   still streaming to disk on a slow store);
2. writes the state file atomically — tmp + fsync + os.replace
   (utils/checkpoint.save_state_file), so a crash mid-save never leaves a
   half-written checkpoint;
3. writes the run manifest (resilience/recovery.py) AFTER the state file
   — a manifest on disk always points at a complete checkpoint;
4. prunes retention beyond `keep`.

A save triggers every `interval_steps` learner steps OR `interval_seconds`
wall seconds, whichever comes first; a trigger that lands while the writer
is still busy is skipped (NOT queued — the next step re-triggers, so the
train loop can never back up behind a slow disk). Telemetry rides the
registry as `resilience/checkpoint_*`: the save_ms span, bytes written,
save/skip counters, and a staleness gauge (seconds since the last
completed save — the recovery-point-objective a dashboard alarms on).
"""

from __future__ import annotations

import os
import sys
import threading
import time
import weakref
from typing import Any, Callable, Mapping, Optional

import jax
import numpy as np

from torched_impala_tpu.resilience import recovery
from torched_impala_tpu.telemetry.registry import Registry, get_registry
from torched_impala_tpu.utils.checkpoint import save_state_file


class AsyncCheckpointer:
    """Background atomic checkpoint writer with manifests + retention.

    `state_fn` passed to `maybe_save` must return the state tree WITHOUT
    blocking on the device (on-device clones are fine; the writer thread
    does the only host transfer). `wait()` before reading files or
    exiting; `close()` is idempotent."""

    def __init__(
        self,
        directory: str,
        *,
        keep: int = 3,
        interval_steps: int = 0,
        interval_seconds: float = 0.0,
        config_hash: Optional[str] = None,
        telemetry: Optional[Registry] = None,
        post_save: Optional[Callable[[str, int], None]] = None,
        host_count: Optional[int] = None,
    ) -> None:
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._keep = keep
        self._interval_steps = interval_steps
        self._interval_seconds = interval_seconds
        self._config_hash = config_hash
        # Stamped into every manifest so resume can validate restoring
        # into a different topology (recovery.HostCountMismatch).
        self._host_count = (
            int(host_count) if host_count is not None else jax.process_count()
        )
        # Chaos hook: called (checkpoint_path, step) after each completed
        # save — the fault-injection seam `corrupt_checkpoint` uses.
        self._post_save = post_save
        # Single-writer atomic reference rebind (writer thread sets it,
        # the learner thread only reads) — no lock by design.
        self.error: Optional[BaseException] = None  # lint: guarded-by(gil)

        self._last_step = -(10**18)  # first maybe_save always fires
        self._last_time = time.monotonic()
        self._last_completed = time.monotonic()
        # Depth-1 handoff: at most one capture in flight; a busy writer
        # makes the NEXT trigger retry instead of queueing work.
        self._pending: Optional[tuple] = None
        self._pending_lock = threading.Lock()
        self._kick = threading.Event()
        self._idle = threading.Event()
        self._idle.set()
        self._stop = threading.Event()
        # Double host buffer: slot i is a pytree of owned numpy arrays
        # matching the state tree, allocated on first use.
        self._buffers: list = [None, None]
        self._buf_idx = 0
        self.saves = 0
        self.skipped = 0

        reg = telemetry if telemetry is not None else get_registry()
        self._m_save_ms = reg.timer("resilience/checkpoint_save_ms")
        self._m_bytes = reg.counter("resilience/checkpoint_bytes")
        self._m_saves = reg.counter("resilience/checkpoint_saves")
        self._m_skipped = reg.counter("resilience/checkpoint_skipped")
        # Staleness = the recovery-point objective: how many seconds of
        # training a crash RIGHT NOW would lose. Lazy fn + weakref so the
        # global registry never keeps a closed checkpointer alive.
        self_ref = weakref.ref(self)

        def _staleness() -> float:
            ck = self_ref()
            if ck is None:
                return float("nan")
            return time.monotonic() - ck._last_completed

        reg.gauge("resilience/checkpoint_staleness_s", fn=_staleness)

        self._thread = threading.Thread(
            target=self._writer_loop, name="async-checkpointer", daemon=True
        )
        self._thread.start()

    # ---- learner-thread surface ---------------------------------------

    def due(self, step: int) -> bool:
        """Does the retention policy want a save at this step? True when
        `interval_steps` learner steps or `interval_seconds` wall seconds
        elapsed since the last trigger (whichever comes first); False
        when neither interval is configured."""
        if self._interval_steps > 0 and (
            step - self._last_step >= self._interval_steps
        ):
            return True
        return self._interval_seconds > 0 and (
            time.monotonic() - self._last_time >= self._interval_seconds
        )

    def maybe_save(
        self,
        step: int,
        state_fn: Callable[[], Mapping[str, Any]],
        *,
        param_version: Optional[int] = None,
    ) -> bool:
        """Interval-triggered async save; call after every learner step
        (cheap when not due). Returns True when a save was handed to the
        writer. A due trigger that finds the writer busy is SKIPPED (and
        counted) — the next due step retries — so this call never blocks
        on disk."""
        if self.error is not None:
            raise RuntimeError(
                "async checkpointer writer thread failed"
            ) from self.error
        if not self.due(step):
            return False
        if not self._idle.is_set():
            self.skipped += 1
            self._m_skipped.inc()
            return False
        self._submit(step, state_fn(), param_version)
        return True

    def save_now(
        self,
        step: int,
        state: Mapping[str, Any],
        *,
        param_version: Optional[int] = None,
    ) -> None:
        """Unconditional save (final checkpoint, tests); still async —
        `wait()` to block until it is on disk."""
        self._idle.wait()
        self._submit(step, state, param_version)

    def _submit(self, step, state, param_version) -> None:
        self._last_step = step
        self._last_time = time.monotonic()
        with self._pending_lock:
            self._pending = (step, state, param_version)
            self._idle.clear()
        self._kick.set()

    def wait(self, timeout: Optional[float] = None) -> None:
        """Block until the writer drains (the last submitted save is on
        disk, manifest included)."""
        self._idle.wait(timeout=timeout)
        if self.error is not None:
            raise RuntimeError(
                "async checkpointer writer thread failed"
            ) from self.error

    def latest_step(self) -> Optional[int]:
        steps = recovery.list_manifest_steps(self.directory)
        return steps[-1] if steps else None

    def all_steps(self) -> list:
        return recovery.list_manifest_steps(self.directory)

    def close(self) -> None:
        if self._stop.is_set():
            return
        self._idle.wait(timeout=60.0)
        self._stop.set()
        self._kick.set()
        self._thread.join(timeout=60.0)

    # ---- writer thread -------------------------------------------------

    def _capture(self, state) -> Any:
        """device_get the (on-device) state clone into the next host
        double-buffer slot; allocates the slot on first use, reuses its
        arrays afterwards (no per-save large allocations)."""
        i = self._buf_idx
        self._buf_idx = (self._buf_idx + 1) % len(self._buffers)
        # Kick off every D2H before materializing any (the copies
        # overlap instead of serializing one transfer per leaf).
        for leaf in jax.tree.leaves(state):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        if self._buffers[i] is None:
            self._buffers[i] = jax.tree.map(
                lambda x: np.array(np.asarray(x), copy=True), state
            )
            return self._buffers[i]

        def into(dst, src):
            src = np.asarray(src)
            if (
                isinstance(dst, np.ndarray)
                and dst.shape == src.shape
                and dst.dtype == src.dtype
            ):
                np.copyto(dst, src)
                return dst
            return np.array(src, copy=True)  # shape drift: reallocate

        self._buffers[i] = jax.tree.map(into, self._buffers[i], state)
        return self._buffers[i]

    def _writer_loop(self) -> None:
        while True:
            self._kick.wait()
            self._kick.clear()
            if self._stop.is_set():
                return
            with self._pending_lock:
                item = self._pending
                self._pending = None
            if item is None:
                continue
            step, state, param_version = item
            try:
                self._write_one(step, state, param_version)
            except BaseException as e:  # noqa: BLE001 — surfaced via .error
                self.error = e
                print(
                    f"[async-checkpointer] save @ step {step} failed: "
                    f"{e!r}",
                    file=sys.stderr,
                    flush=True,
                )
            finally:
                self._idle.set()

    def _write_one(self, step, state, param_version) -> None:
        with self._m_save_ms.time():
            host_state = self._capture(state)
            ckpt = recovery.checkpoint_path(self.directory, step)
            nbytes = save_state_file(ckpt, host_state)
            if isinstance(host_state, Mapping):
                rng = recovery.manifest_rng(host_state.get("rng"))
            else:
                rng = None
            if param_version is None and isinstance(host_state, Mapping):
                v = host_state.get("num_frames")
                param_version = int(v) if v is not None else step
            recovery.write_manifest(
                self.directory,
                recovery.RunManifest(
                    step=int(step),
                    param_version=int(
                        param_version if param_version is not None else step
                    ),
                    checkpoint=os.path.basename(ckpt),
                    config_hash=self._config_hash,
                    rng=rng,
                    saved_at=time.time(),
                    host_count=self._host_count,
                ),
            )
            recovery.prune(self.directory, self._keep)
        self._m_bytes.inc(nbytes)
        self._m_saves.inc()
        self.saves += 1
        self._last_completed = time.monotonic()
        if self._post_save is not None:
            self._post_save(ckpt, int(step))
