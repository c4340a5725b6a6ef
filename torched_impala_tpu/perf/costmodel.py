"""Cost model: FLOPs / bytes-accessed per jitted root, live MFU gauges.

The benchmark computes `train_step.mfu` offline, from shapes and a device
trace (`benchmark/flops.py`, `benchmark/readers.py`). This module is the
LIVE estimate a running job exports: every jitted root
(train_step, replay step, serving wave, fused K-step) registers its
compiled cost here, and the learner's step cadence turns them into
`perf/mfu`, `perf/membw_util`, and `perf/flops_per_step` gauges through
the ordinary telemetry registry.

Two sources, in preference order:

- ``cost_analysis`` — XLA's algebraic per-program count, read off a
  compiled executable (``jax.jit(f).lower(...).compile()`` or an AOT
  handle). Caveat (PERF.md section 6, PR 23): XLA counts every
  `lax.scan`/`while` BODY once, not x trip count, so grad-accum
  programs under-count by ~accum (pass ``steps_per_call``/``flops_scale``
  to correct) while a fused-K body IS one full SGD step already.
- ``static`` — the classic dense-training estimate
  ``6 * params * frames`` (2 forward + 4 backward) when the backend
  reports nothing (CPU CI). Order-of-magnitude only for conv nets
  (convs reuse params), but it keeps the gauges and the doctor
  self-check alive off-TPU.

Peaks come from ONE table keyed by `device_kind` (DEVICE_PEAKS below,
with its source). A device that is not in the table has no peaks: the
utilisation gauges then stay unset (`CostModel.for_device`) — no device
is a v5e by default.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from torched_impala_tpu.telemetry.registry import Registry, get_registry

@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks for one `device_kind`."""

    flops_per_s: float  # dense bf16
    hbm_bytes_per_s: float
    # Ring all-reduce bandwidth per chip over the interconnect, for the
    # data-axis gradient all-reduce estimate (allreduce_ns).
    allreduce_bytes_per_s: float
    source: str


# Keyed by `jax.devices()[0].device_kind`. Add a device WITH its source;
# never let a lookup default to some other device's numbers.
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        flops_per_s=197e12,
        hbm_bytes_per_s=819e9,
        # v5e ICI links run ~45 GB/s per direction; a bidirectional
        # ring sustains ~9e10 B/s of all-reduce bandwidth per chip (of
        # the 1,600 Gbit/s published per-chip interconnect total).
        allreduce_bytes_per_s=9e10,
        source=(
            'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
            "16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip"
        ),
    ),
}

# Simulated CPU pods (parallel/simhost.py) move gradients over loopback
# gloo TCP; 4 GB/s is the measured order of magnitude. Not a device
# peak: it only feeds the all-reduce OVERLAP estimate of a CPU mesh.
LOOPBACK_BYTES_PER_S = 4e9


def allreduce_ns(nbytes: float, n_shards: int, bytes_per_s: float) -> int:
    """Ring all-reduce wall-time estimate: 2(n-1)/n * bytes / bandwidth.

    The standard bidirectional-ring cost (scaling-book collective
    table): each of n shards sends/receives 2(n-1)/n of the payload.
    Returns 0 when there is nothing to reduce (n<=1 or empty)."""
    if n_shards <= 1 or nbytes <= 0 or bytes_per_s <= 0:
        return 0
    return int(2 * (n_shards - 1) / n_shards * nbytes / bytes_per_s * 1e9)


@dataclasses.dataclass
class RootCost:
    """Per-compiled-program cost: one entry per jitted root."""

    name: str
    flops: float = 0.0  # per CALL, after flops_scale correction
    bytes_accessed: float = 0.0
    temp_bytes: int = 0
    steps_per_call: int = 1  # fused K: SGD steps per dispatch
    frames_per_call: int = 0  # env frames consumed per dispatch
    source: str = "none"  # "cost_analysis" | "static" | "none"


def extract_compiled_cost(compiled: Any) -> Dict[str, float]:
    """FLOPs / bytes-accessed / temp HBM from a compiled executable.

    Handles the two shapes ``cost_analysis()`` has shipped as (a dict,
    or a list/tuple of one dict) and returns zeros — never raises — when
    the backend reports nothing (CPU CI).
    """
    out = {"flops": 0.0, "bytes_accessed": 0.0, "temp_bytes": 0.0}
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        out["flops"] = max(float(cost.get("flops", 0.0)), 0.0)
        out["bytes_accessed"] = max(
            float(cost.get("bytes accessed", 0.0)), 0.0
        )
    except Exception:
        pass
    try:
        out["temp_bytes"] = float(
            compiled.memory_analysis().temp_size_in_bytes
        )
    except Exception:
        pass
    return out


def static_flops_estimate(param_count: int, frames: int) -> float:
    """Dense-training fallback: 6 FLOPs per parameter per frame
    (2 forward + 4 backward). Used when cost_analysis reports nothing."""
    return 6.0 * float(param_count) * float(frames)


def param_count(params: Any) -> int:
    """Total scalar count of a params pytree (jax is imported lazily so
    report-side tooling can load this module without a backend)."""
    import jax

    return sum(
        int(getattr(leaf, "size", 0)) for leaf in jax.tree.leaves(params)
    )


class CostModel:
    """Registry of jitted-root costs + the live `perf/*` gauges.

    Usage::

        cm = CostModel()
        cm.register_root("train_step", compiled=executable,
                         frames_per_call=T * B * K, steps_per_call=K)
        # ... each learner step:
        cm.observe_call("train_step", dt_seconds)

    ``observe_call`` folds the root's per-call FLOPs and bytes over the
    measured wall-clock into `perf/mfu` / `perf/membw_util`;
    `perf/flops_per_step` carries the per-SGD-step FLOP count of the
    most recently registered root.
    """

    def __init__(
        self,
        *,
        peak_flops: Optional[float] = None,
        peak_bytes_per_s: Optional[float] = None,
        registry: Optional[Registry] = None,
    ):
        """Without peaks (`None`) the model still counts FLOPs and bytes
        (`perf/flops_per_step`, `roofline`'s raw counts) but sets no
        utilisation gauge: a ratio against another device's peak would
        be a number about nothing."""
        reg = registry if registry is not None else get_registry()
        self.peak_flops = peak_flops
        self.peak_bytes_per_s = peak_bytes_per_s
        self.roots: Dict[str, RootCost] = {}
        self._g_mfu = reg.gauge("perf/mfu")
        self._g_membw = reg.gauge("perf/membw_util")
        self._g_flops = reg.gauge("perf/flops_per_step")

    @classmethod
    def for_device(
        cls, device_kind: str, *, registry: Optional[Registry] = None
    ) -> "CostModel":
        """Peaks from DEVICE_PEAKS for `device_kind`; none (utilisation
        gauges stay unset) for a device the table does not know."""
        peaks = DEVICE_PEAKS.get(device_kind)
        if peaks is None:
            return cls(registry=registry)
        return cls(
            peak_flops=peaks.flops_per_s,
            peak_bytes_per_s=peaks.hbm_bytes_per_s,
            registry=registry,
        )

    def register_root(
        self,
        name: str,
        *,
        compiled: Any = None,
        fallback_params: Any = None,
        frames_per_call: int = 0,
        steps_per_call: int = 1,
        flops_scale: float = 1.0,
    ) -> RootCost:
        """Record one jitted root's cost. Prefers ``compiled``'s
        cost_analysis; falls back to the static estimate from
        ``fallback_params`` x ``frames_per_call``. ``flops_scale``
        corrects scan-body-counted-once programs (grad_accum)."""
        root = RootCost(
            name=name,
            steps_per_call=max(int(steps_per_call), 1),
            frames_per_call=int(frames_per_call),
        )
        if compiled is not None:
            c = extract_compiled_cost(compiled)
            if c["flops"] > 0:
                root.flops = c["flops"] * flops_scale
                root.bytes_accessed = c["bytes_accessed"] * flops_scale
                root.temp_bytes = int(c["temp_bytes"])
                root.source = "cost_analysis"
        if root.flops <= 0 and fallback_params is not None:
            root.flops = static_flops_estimate(
                param_count(fallback_params), max(frames_per_call, 1)
            )
            root.source = "static" if root.flops > 0 else "none"
        self.roots[name] = root
        if root.flops > 0:
            self._g_flops.set(root.flops / root.steps_per_call)
        return root

    def observe_call(self, name: str, dt_seconds: float) -> float:
        """One completed dispatch of root ``name`` took ``dt_seconds``;
        update the live gauges and return the instantaneous MFU (0.0
        when the root is unknown or costless)."""
        root = self.roots.get(name)
        if (
            root is None
            or root.flops <= 0
            or dt_seconds <= 0
            or not self.peak_flops
        ):
            return 0.0
        mfu = (root.flops / dt_seconds) / self.peak_flops
        self._g_mfu.set(mfu)
        if root.bytes_accessed > 0 and self.peak_bytes_per_s:
            self._g_membw.set(
                (root.bytes_accessed / dt_seconds) / self.peak_bytes_per_s
            )
        return mfu

    def roofline(self, name: str) -> Dict[str, Any]:
        """Roofline coordinates for one root: arithmetic intensity vs
        the machine's ridge point, and which side it sits on."""
        root = self.roots.get(name)
        if root is None:
            return {}
        out: Dict[str, Any] = {
            "root": name,
            "source": root.source,
            "flops_per_call": root.flops,
            "flops_per_step": (
                root.flops / root.steps_per_call if root.flops else 0.0
            ),
            "bytes_per_call": root.bytes_accessed,
            "temp_bytes": root.temp_bytes,
            "peak_flops": self.peak_flops,
            "peak_bytes_per_s": self.peak_bytes_per_s,
        }
        if not (self.peak_flops and self.peak_bytes_per_s):
            return out  # unknown device: counts only, no ridge/bound
        ridge = self.peak_flops / self.peak_bytes_per_s
        out["ridge_intensity"] = ridge
        if root.bytes_accessed > 0 and root.flops > 0:
            ai = root.flops / root.bytes_accessed
            out["arithmetic_intensity"] = ai
            out["bound"] = "compute" if ai >= ridge else "memory"
        return out

    def snapshot(self) -> Dict[str, Dict[str, Any]]:
        return {name: self.roofline(name) for name in self.roots}
