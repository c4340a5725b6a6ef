"""Simulated multi-host cluster launcher (CPU, one box, N OS processes).

The multi-host code path is process-count-agnostic: every host runs THE
SAME program and `jax.distributed` joins them. That means the whole pod
story is testable on one CPU box by launching N OS processes, each with
its own virtual CPU devices (`--xla_force_host_platform_device_count`),
wired together through a loopback coordinator. This module owns that
launch: build each child's environment (`child_env`), start the
processes, babysit them (`launch`), and parse their structured result
lines (`parse_results`).

Used by tests/test_multihost.py (tier-1 2-process parity), doctor's
`multihost` row, and the `kill_host` chaos scenario — the launcher is
also the survivor-side failure detector: when one host dies (e.g.
SIGKILL mid-collective), the surviving processes are blocked inside the
broken collective forever, so `launch` kills them after a grace period and reports the wreck; callers
restart the whole cluster from the newest checkpoint, which is exactly
the real-pod failure model (docs/MULTIHOST.md).

No jax import here — the launcher must stay usable before/without
backend init, and children configure their own backends from the env.
"""

from __future__ import annotations

import dataclasses
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence

# Keep in sync with parallel/multihost.py (not imported: see module
# docstring — this file must not pull in jax).
ENV_COORDINATOR = "IMPALA_COORDINATOR"
ENV_NUM_HOSTS = "IMPALA_NUM_HOSTS"
ENV_HOST_ID = "IMPALA_HOST_ID"

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

RESULT_TAG = "SIMHOST_RESULT"


def find_free_port() -> int:
    s = socket.socket()
    try:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
    finally:
        s.close()


def child_env(
    host_id: int,
    num_hosts: int,
    port: int,
    *,
    devices_per_host: int = 1,
    extra: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    """Environment for one simulated host process.

    Starts from the parent's environment and forces the CPU backend
    (simulated hosts are CPU-only by definition: they never load the TPU
    library, so a parent may hold a chip) with `devices_per_host`
    virtual devices (replacing any
    inherited count: pytest's conftest exports 8), and sets the
    IMPALA_COORDINATOR/NUM_HOSTS/HOST_ID triple that
    `multihost.bootstrap()` reads before first backend touch.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = [
        f
        for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    ]
    env["XLA_FLAGS"] = " ".join(
        flags
        + [f"--xla_force_host_platform_device_count={devices_per_host}"]
    )
    env[ENV_COORDINATOR] = f"127.0.0.1:{port}"
    env[ENV_NUM_HOSTS] = str(num_hosts)
    env[ENV_HOST_ID] = str(host_id)
    if extra:
        env.update(extra)
    return env


@dataclasses.dataclass
class HostProc:
    """One finished (or killed) simulated host."""

    host_id: int
    returncode: Optional[int]  # negative = died by signal; None = killed by us
    stdout: str
    stderr: str

    @property
    def ok(self) -> bool:
        return self.returncode == 0

    def results(self, tag: str = RESULT_TAG) -> List[dict]:
        """Parse `<tag> {json}` lines from this host's stdout."""
        out = []
        for line in self.stdout.splitlines():
            line = line.strip()
            if line.startswith(tag + " "):
                out.append(json.loads(line[len(tag) + 1 :]))
        return out


@dataclasses.dataclass
class ClusterResult:
    hosts: List[HostProc]
    duration_s: float
    port: int

    @property
    def ok(self) -> bool:
        return all(h.ok for h in self.hosts)

    @property
    def dead(self) -> List[HostProc]:
        return [h for h in self.hosts if not h.ok]

    def describe(self) -> str:
        lines = [f"cluster({len(self.hosts)} hosts, {self.duration_s:.1f}s)"]
        for h in self.hosts:
            tail = "\n".join(
                (h.stdout + "\n" + h.stderr).strip().splitlines()[-15:]
            )
            lines.append(f"-- host {h.host_id} rc={h.returncode}\n{tail}")
        return "\n".join(lines)


def launch(
    argv: Sequence[str],
    num_hosts: int,
    *,
    devices_per_host: int = 1,
    timeout: float = 300.0,
    grace_s: float = 10.0,
    extra_env: Optional[Dict[str, str]] = None,
    per_host_env: Optional[Dict[int, Dict[str, str]]] = None,
    cwd: str = REPO_ROOT,
) -> ClusterResult:
    """Run `argv` as `num_hosts` coordinated processes and wait.

    All hosts execute the same argv (the SPMD contract); host identity
    rides the IMPALA_* env triple. If any host exits nonzero (or is
    signal-killed), the survivors get `grace_s` to notice and exit on
    their own — they usually can't, because a dead peer leaves them
    blocked inside a cross-host collective — and are then SIGKILLed.
    On `timeout`, everything is killed and returncodes report whatever
    the OS saw. stdout/stderr are captured via temp files (no pipe
    drain threads, no deadlock at large outputs).
    """
    port = find_free_port()
    t0 = time.monotonic()
    procs = []
    files = []
    try:
        for h in range(num_hosts):
            env = child_env(
                h,
                num_hosts,
                port,
                devices_per_host=devices_per_host,
                extra=extra_env,
            )
            if per_host_env and h in per_host_env:
                env.update(per_host_env[h])
            out_f = tempfile.TemporaryFile(mode="w+")
            err_f = tempfile.TemporaryFile(mode="w+")
            files.append((out_f, err_f))
            procs.append(
                subprocess.Popen(
                    list(argv),
                    stdout=out_f,
                    stderr=err_f,
                    env=env,
                    cwd=cwd,
                    text=True,
                )
            )
        deadline = t0 + timeout
        kill_at = None  # set once a host has died abnormally
        while True:
            codes = [p.poll() for p in procs]
            if all(c is not None for c in codes):
                break
            now = time.monotonic()
            if kill_at is None and any(
                c is not None and c != 0 for c in codes
            ):
                kill_at = now + grace_s
            if (kill_at is not None and now >= kill_at) or now >= deadline:
                for p in procs:
                    if p.poll() is None:
                        try:
                            p.send_signal(signal.SIGKILL)
                        except OSError:
                            pass
                for p in procs:
                    try:
                        p.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        pass
                break
            time.sleep(0.05)
        hosts = []
        for h, (p, (out_f, err_f)) in enumerate(zip(procs, files)):
            out_f.seek(0)
            err_f.seek(0)
            hosts.append(
                HostProc(
                    host_id=h,
                    returncode=p.poll(),
                    stdout=out_f.read(),
                    stderr=err_f.read(),
                )
            )
        return ClusterResult(
            hosts=hosts, duration_s=time.monotonic() - t0, port=port
        )
    finally:
        for p in procs:
            if p.poll() is None:
                try:
                    p.kill()
                except OSError:
                    pass
        for out_f, err_f in files:
            out_f.close()
            err_f.close()


def worker_preamble(devices_per_host: Optional[int] = None) -> None:
    """Standard prologue for a simulated-host worker SCRIPT (not needed
    for `-m` module workers launched with cwd=REPO_ROOT): repo root on
    sys.path (sys.path, not PYTHONPATH) and the CPU backend forced
    before the first jax import. `child_env` already sets both in the
    environment; this is the belt-and-braces version for workers that
    can also be run by hand."""
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if devices_per_host is not None:
        flags = [
            f
            for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f
        ]
        os.environ["XLA_FLAGS"] = " ".join(
            flags
            + [
                "--xla_force_host_platform_device_count="
                f"{devices_per_host}"
            ]
        )


def emit_result(payload: dict, tag: str = RESULT_TAG) -> None:
    """Worker side: print one structured result line for `HostProc.results`."""
    print(tag + " " + json.dumps(payload), flush=True)
