"""perfgate: the BENCH_HISTORY.jsonl regression gate.

``python -m tools.perfgate [--history PATH] [--drop 0.2] [--window 8]``

bench.py (and its tiny variants under tests/test_bench_units.py) append
one structured record per headline metric to ``BENCH_HISTORY.jsonl``:

    {"ts": ..., "sha": "<git sha>", "section": "headline",
     "metric": "learner_frames_per_sec_per_chip_pong",
     "value": 707462.3, "unit": "frames/s/chip",
     "direction": "higher", "fingerprint": "<host|arch|cpuN|backend>"}

The gate checks, for the NEWEST record of every (metric, fingerprint)
group:

- **pinned budgets** (`BUDGETS` below): absolute floors for the
  load-bearing numbers, applied only when the record's fingerprint
  matches the budget's backend (a CPU smoke run must not trip a TPU
  floor);
- **relative drop vs. the trailing median**: with at least
  ``--min-prior`` earlier records in the same group, the newest value
  must not sit more than ``--drop`` below (above, for lower-is-better
  metrics) the median of the trailing ``--window`` records.

Exit codes mirror impala-lint: 0 clean, 1 regression found, 2
usage/framework error (including a missing or empty history file).
Grouping by machine fingerprint means laptops, CI boxes, and a TPU
host each gate against their own trajectory — values are never
compared across machines.
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import statistics
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_HISTORY = os.path.join(REPO, "BENCH_HISTORY.jsonl")

# Absolute floors for the load-bearing full-bench numbers (frames/s/chip
# on a v5e; pinned on an earlier rig and not re-measured on the current
# chip — BENCH_HISTORY.jsonl holds no TPU row, see PERF.md).
# `fingerprint_contains` scopes each floor to the backend it was pinned
# on — tiny CPU-CI records use their own `tiny_*` metric names and are
# gated by the relative-drop check only (except entries below that set
# `no_drop_check`: dispatch-noise-dominated quotients keep just their
# absolute budget).
BUDGETS: Dict[str, Dict[str, Any]] = {
    "learner_frames_per_sec_per_chip_pong": {
        "min": 500_000.0,
        "fingerprint_contains": "tpu",
    },
    "anakin_cartpole_frames_per_sec": {
        "min": 1_000_000.0,
        "fingerprint_contains": "tpu",
    },
    # ISSUE 13 zero-copy feed path. Backend-agnostic floors (empty
    # fingerprint scope): both numbers are RATIOS of same-backend
    # quantities, so the claim holds wherever the bench runs — the
    # donated put must overwhelmingly overlap in-flight compute, and
    # the fused V-trace+loss epilogue must beat the separate path by
    # >= 10% (measured ~0.70x at the full bench shape on CPU; the
    # analytic VJP that buys this is backend-independent).
    "h2d_overlap_frac": {
        "min": 0.8,
        "fingerprint_contains": "",
    },
    "fused_epilogue_step_ratio": {
        "max": 0.9,
        "fingerprint_contains": "",
    },
    # ISSUE 15 mesh-native feed. Backend-agnostic: staged bytes under
    # the 2-device data mesh must be EXACTLY zero (the tentpole claim —
    # ring slots shard straight to per-device memory with no host
    # gather/stage hop), and per-batch sharded placement must be no
    # slower than the explicit stage-on-one-device-then-reshard hop it
    # replaces (same-box quotient; the hop moves every byte over H2D
    # twice, measured ~0.6x on CPU).
    "mesh_ring_stage_bytes": {
        "max": 0.0,
        "fingerprint_contains": "",
    },
    "mesh_feed_step_ratio": {
        "max": 1.0,
        "fingerprint_contains": "",
    },
    # ISSUE 14 fleet serving. Backend-agnostic: the goodput ratio is a
    # same-box quotient (2-replica fleet vs single server across an
    # incident window with a mid-wave server kill — measured ~1.99x,
    # the fleet keeps the whole window, the single arm loses half), and
    # serving_p99_ms is gated against the SLO BUDGET itself (50 ms):
    # the fleet arm must absorb rollouts + failover without blowing the
    # latency objective, on any box that runs the full bench.
    "fleet_goodput_ratio": {
        "min": 1.5,
        "fingerprint_contains": "",
    },
    "serving_p99_ms": {
        "max": 50.0,
        "fingerprint_contains": "",
    },
    # ISSUE 16 compute-side MFU. TPU-scoped, unlike the other ratio
    # budgets: bf16 is software-emulated on CPU and the Pallas kernels
    # run in interpret mode there, so the speedup claims only hold on
    # real MXUs (the CPU bench appends tiny_-prefixed rows instead).
    # The full-bf16 step must beat f32 by >= 5%, the fused LSTM unroll
    # must be no slower than the flax cell, and the B=1024 default
    # operating point must clear 0.15 MFU on the v5e.
    "train_dtype_step_ratio": {
        "max": 0.95,
        "fingerprint_contains": "tpu",
    },
    "lstm_fused_step_ratio": {
        "max": 1.0,
        "fingerprint_contains": "tpu",
    },
    "mfu_b1024": {
        "min": 0.15,
        "fingerprint_contains": "tpu",
    },
    # ISSUE 17 observability plane. Backend-agnostic: exposition is
    # pure host-side work, so the overhead fraction (env-pool steps/s
    # with the OpenMetrics endpoint scraped at 20 Hz vs without the
    # exporter) must stay under the 1% acceptance bound wherever the
    # full bench runs, and the shared-memory fan-in lane's
    # publish->read roundtrip for a worker-sized payload must stay
    # well under the 250 ms publish interval it rides (measured
    # ~100 us; 10 ms is two orders of magnitude of headroom).
    # `no_drop_check` on the overhead: it divides two noisy host
    # throughputs whose true delta is < 1%, so the trailing-median
    # comparison would gate on scheduler noise — the absolute ceiling
    # IS the claim.
    "export_overhead_frac": {
        "max": 0.01,
        "fingerprint_contains": "",
        "no_drop_check": True,
    },
    "fanin_roundtrip_us": {
        "max": 10_000.0,
        "fingerprint_contains": "",
        "no_drop_check": True,
    },
    # ISSUE 19 learning-health diagnostics: the in-step health_* family
    # (clip fractions, IS-weight histogram, entropy/KL/EV, grad-group
    # norms and update ratios) rides the existing train-step dispatch
    # and must cost <= 1% of step time. Same shape as the export
    # overhead: a quotient of two noisy host wall-clocks whose true
    # delta is under 1%, so the absolute ceiling IS the claim and the
    # trailing-median drop check would gate on scheduler noise.
    "health_overhead_frac": {
        "max": 0.01,
        "fingerprint_contains": "",
        "no_drop_check": True,
    },
    # Dispatch-noise carve-out: the tiny mesh placement ratio divides
    # two sub-millisecond host puts, so run-to-run it swings 0.55-1.1x
    # on a shared CI box — a 20% median gate on it is a coin flip (the
    # full-shape row keeps the normal drop check). `no_drop_check`
    # skips the trailing-median comparison; the loose absolute ceiling
    # still catches the direct-placement path genuinely losing to the
    # reshard hop it replaced.
    "tiny_mesh_feed_step_ratio": {
        "max": 2.0,
        "fingerprint_contains": "",
        "no_drop_check": True,
    },
    # ISSUE 18 multi-host pod-slice training. The simulated cluster is
    # CPU-by-construction (even on a TPU box the harness pins child
    # processes to JAX_PLATFORMS=cpu), so the tiny CI floors are the
    # acceptance numbers: 2 simulated hosts must deliver >= 0.8x the
    # frames/s of 2x one host on the env-paced weak-scaling scenario,
    # and the learner's gradient all-reduce must hide >= 0.8 of its
    # cost-model estimate behind the step (perf/allreduce_overlap_frac).
    # `no_drop_check`: both are quotients of second-scale wall times on
    # a contended 1-core CI box — the absolute floor IS the claim; the
    # full-bench rows keep the same floors.
    "tiny_multihost_weak_scaling_eff": {
        "min": 0.8,
        "fingerprint_contains": "cpu",
        "no_drop_check": True,
    },
    "tiny_allreduce_overlap_frac": {
        "min": 0.8,
        "fingerprint_contains": "cpu",
        "no_drop_check": True,
    },
    "multihost_weak_scaling_eff": {
        "min": 0.8,
        "fingerprint_contains": "",
        "no_drop_check": True,
    },
    "allreduce_overlap_frac": {
        "min": 0.8,
        "fingerprint_contains": "",
        "no_drop_check": True,
    },
}


def git_sha(repo: str = REPO) -> str:
    try:
        return (
            subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                cwd=repo,
                capture_output=True,
                text=True,
                timeout=10,
            ).stdout.strip()
            or "unknown"
        )
    except Exception:
        return "unknown"


def machine_fingerprint(backend: str = "") -> str:
    """Stable-enough identity of the measuring machine: history records
    only compare against records with an identical fingerprint."""
    parts = [
        platform.node() or "unknown-host",
        platform.machine() or "unknown-arch",
        f"cpu{os.cpu_count() or 0}",
    ]
    if backend:
        parts.append(backend)
    return "|".join(parts)


def append_history(
    section: str,
    metric: str,
    value: float,
    *,
    path: Optional[str] = None,
    unit: str = "",
    direction: str = "higher",
    backend: str = "",
    sha: Optional[str] = None,
    fingerprint: Optional[str] = None,
) -> Dict[str, Any]:
    """Append one record to the history file (created on first write).
    The `BENCH_HISTORY_PATH` env var overrides the default location so
    tests can write to a scratch file."""
    path = path or os.environ.get("BENCH_HISTORY_PATH") or DEFAULT_HISTORY
    record = {
        "ts": time.time(),
        "sha": sha if sha is not None else git_sha(),
        "section": section,
        "metric": metric,
        "value": float(value),
        "unit": unit,
        "direction": direction,
        "fingerprint": (
            fingerprint
            if fingerprint is not None
            else machine_fingerprint(backend)
        ),
    }
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps(record) + "\n")
    return record


def load_history(path: str) -> List[Dict[str, Any]]:
    """Parse the JSONL history; raises FileNotFoundError when absent.
    Unparseable or schema-less lines are skipped — a half-written tail
    from a killed bench run must not wedge the gate."""
    records: List[Dict[str, Any]] = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if (
                isinstance(rec, dict)
                and "metric" in rec
                and isinstance(rec.get("value"), (int, float))
            ):
                records.append(rec)
    return records


def check_records(
    records: List[Dict[str, Any]],
    *,
    drop: float = 0.2,
    window: int = 8,
    min_prior: int = 3,
    budgets: Optional[Dict[str, Dict[str, Any]]] = None,
) -> List[str]:
    """The gate proper: findings (empty = pass) for the newest record of
    every (metric, fingerprint) group, in file order."""
    budgets = BUDGETS if budgets is None else budgets
    groups: Dict[tuple, List[Dict[str, Any]]] = {}
    for rec in records:
        key = (rec["metric"], rec.get("fingerprint", ""))
        groups.setdefault(key, []).append(rec)
    findings: List[str] = []
    for (metric, fingerprint), group in groups.items():
        newest = group[-1]
        value = float(newest["value"])
        higher = newest.get("direction", "higher") != "lower"
        budget = budgets.get(metric)
        budget_applies = budget is not None and budget.get(
            "fingerprint_contains", ""
        ) in fingerprint
        if budget_applies:
            floor = budget.get("min")
            ceil = budget.get("max")
            if floor is not None and value < floor:
                findings.append(
                    f"{metric} [{fingerprint}]: {value:g} below pinned "
                    f"budget min {floor:g} (sha {newest.get('sha')})"
                )
            if ceil is not None and value > ceil:
                findings.append(
                    f"{metric} [{fingerprint}]: {value:g} above pinned "
                    f"budget max {ceil:g} (sha {newest.get('sha')})"
                )
        if budget_applies and budget.get("no_drop_check"):
            # Dispatch-noise-dominated metric: the absolute budget above
            # is the whole gate for it.
            continue
        prior = [float(r["value"]) for r in group[:-1][-window:]]
        if len(prior) < min_prior:
            continue
        med = statistics.median(prior)
        if med <= 0:
            continue
        # >= so a drop of exactly the threshold is flagged (the
        # acceptance bar: a seeded 20% regression must exit nonzero at
        # the default --drop 0.2).
        if higher and med - value >= drop * med:
            findings.append(
                f"{metric} [{fingerprint}]: {value:g} is "
                f"{1.0 - value / med:.1%} below the trailing median "
                f"{med:g} over {len(prior)} records "
                f"(threshold {drop:.0%}, sha {newest.get('sha')})"
            )
        elif not higher and value - med >= drop * med:
            findings.append(
                f"{metric} [{fingerprint}]: {value:g} is "
                f"{value / med - 1.0:.1%} above the trailing median "
                f"{med:g} over {len(prior)} records "
                f"(threshold {drop:.0%}, sha {newest.get('sha')})"
            )
    return findings


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="perfgate",
        description=(
            "bench-history regression gate: pinned budgets + relative "
            "drop vs. trailing median per (metric, machine) group"
        ),
    )
    parser.add_argument(
        "--history",
        default=os.environ.get("BENCH_HISTORY_PATH") or DEFAULT_HISTORY,
        help="BENCH_HISTORY.jsonl path (default: repo root, or "
        "$BENCH_HISTORY_PATH)",
    )
    parser.add_argument(
        "--drop",
        type=float,
        default=0.2,
        help="max relative drop vs. the trailing median (default 0.2)",
    )
    parser.add_argument(
        "--window",
        type=int,
        default=8,
        help="trailing records per group for the median (default 8)",
    )
    parser.add_argument(
        "--min-prior",
        type=int,
        default=3,
        help="priors required before the relative check arms (default 3)",
    )
    parser.add_argument(
        "--verbose", action="store_true", help="print every group checked"
    )
    args = parser.parse_args(argv)
    if args.drop <= 0 or args.drop >= 1:
        print(
            f"perfgate: error: --drop must be in (0, 1), got {args.drop}",
            file=sys.stderr,
        )
        return 2
    try:
        records = load_history(args.history)
    except FileNotFoundError:
        print(
            f"perfgate: error: no history at {args.history} — run "
            "bench.py (or the tiny variants) to create it",
            file=sys.stderr,
        )
        return 2
    if not records:
        print(
            f"perfgate: error: history at {args.history} holds no "
            "parseable records",
            file=sys.stderr,
        )
        return 2
    findings = check_records(
        records,
        drop=args.drop,
        window=args.window,
        min_prior=args.min_prior,
    )
    if args.verbose:
        groups = {
            (r["metric"], r.get("fingerprint", "")) for r in records
        }
        for metric, fp in sorted(groups):
            print(f"perfgate: checked {metric} [{fp}]", file=sys.stderr)
    for finding in findings:
        print(f"perfgate: REGRESSION: {finding}", file=sys.stderr)
    n = len(findings)
    print(
        f"perfgate: {'FAIL' if n else 'OK'} ({n} regression"
        f"{'s' if n != 1 else ''}, {len(records)} records, "
        f"{len({(r['metric'], r.get('fingerprint', '')) for r in records})}"
        " groups)",
        file=sys.stderr,
    )
    return 1 if findings else 0


if __name__ == "__main__":
    raise SystemExit(main())
