"""Resilience subsystem: async checkpointing, crash-consistent resume,
and the chaos fault-injection harness.

Three pillars (docs/RESILIENCE.md):
- `checkpointer.AsyncCheckpointer` — interval-triggered background
  checkpoint writes (atomic tmp+fsync+rename, double-buffered D2H,
  retention) that never block the train loop;
- `recovery` — JSON run manifests next to every checkpoint, a
  config-hash-guarded newest-first recovery scan, and corrupt-checkpoint
  fallback;
- `chaos` — declarative fault plans (SIGKILL env workers, crash actors,
  wedge the trajectory queue, delay shm lanes, corrupt checkpoints,
  crash the learner) injected through runtime hooks; exercised by
  tests/test_resilience.py.
"""

from torched_impala_tpu.resilience.checkpointer import AsyncCheckpointer
from torched_impala_tpu.resilience.chaos import (
    ChaosError,
    ChaosInjector,
    ChaosPlan,
    Fault,
    corrupt_file,
)
from torched_impala_tpu.resilience.recovery import (
    HostCountMismatch,
    RunManifest,
    ResumeConfigMismatch,
    config_fingerprint,
    list_manifest_steps,
    load_manifest,
    restore_latest,
    write_manifest,
)

__all__ = [
    "AsyncCheckpointer",
    "ChaosError",
    "ChaosInjector",
    "ChaosPlan",
    "Fault",
    "corrupt_file",
    "HostCountMismatch",
    "RunManifest",
    "ResumeConfigMismatch",
    "config_fingerprint",
    "list_manifest_steps",
    "load_manifest",
    "restore_latest",
    "write_manifest",
]
