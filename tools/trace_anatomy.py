"""Per-op device-time anatomy of a jax.profiler trace.

An early headline anatomy was parsed by hand; this makes the method
repeatable: point it at a profiler
trace dir (the newest `plugins/profile/<ts>/` capture inside), and it
prints mean device time per XLA op per step, sorted, with the step count
inferred from the top-level module activity.

Usage:
    python tools/trace_anatomy.py traces/bench [--steps N] [--top K]

The trace.json.gz "traceEvents" carry one event per op execution with
`dur` in microseconds; device-stream events are identified by their PID's
process name containing "TPU" / "/device:". Ops are aggregated by name
across the capture and divided by the step count (events of the
outermost jit program).
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import sys


def newest_capture(trace_dir: str) -> str:
    pats = sorted(
        glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*", "*trace.json.gz")
        )
    )
    if not pats:
        raise FileNotFoundError(f"no trace.json.gz under {trace_dir}")
    return max(pats, key=os.path.getmtime)


def load_events(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def device_pids(doc: dict) -> set:
    """PIDs whose process_name metadata looks like a device stream."""
    pids = set()
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            name = (ev.get("args") or {}).get("name", "")
            low = name.lower()
            if "tpu" in low or "/device:" in low or "xla" in low:
                pids.add(ev["pid"])
    return pids


def anatomy(path: str):
    """Returns (per_op dur-sums us, per_op counts, module dur-sum us,
    module count). Container events — the outermost jit module (name
    starts with "jit") and the pid-level numbered step rows (bare
    integers, one per step) — are split out of per_op: counting them as
    ops double-counts the total and deflates every real op's share."""
    doc = load_events(path)
    pids = device_pids(doc)
    per_op = collections.Counter()
    per_op_n = collections.Counter()
    modules = collections.defaultdict(lambda: [0.0, 0])
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") != "X" or ev.get("pid") not in pids:
            continue
        name = ev.get("name", "?")
        dur = float(ev.get("dur", 0.0))
        if name.startswith("jit"):
            modules[name][0] += dur
            modules[name][1] += 1
            continue
        if name.isdigit():  # per-step marker rows, not ops
            continue
        per_op[name] += dur
        per_op_n[name] += 1
    # A capture can contain several jitted programs (or the same module
    # on several device streams); the OUTER step module is the one with
    # the most total device time — counting all jit* events as steps
    # would deflate every ms/step figure.
    if modules:
        module_us, module_n = max(modules.values(), key=lambda v: v[0])
    else:
        module_us, module_n = 0.0, 0
    return per_op, per_op_n, module_us, module_n


def hlo_attribution(hlo_path: str) -> dict:
    """op name -> (result type+shape, source op_name metadata) from an
    HLO text dump (`compiled.as_text()`): automates the by-hand greps
    that mapped trace ops to model code in rounds 4-5."""
    import re

    attr = {}
    text = open(hlo_path).read()
    for m in re.finditer(
        r"^\s*(?:ROOT\s+)?%(?P<name>[\w.\-]+) = (?P<ty>\S+)"
        r"(?:.*?op_name=\"(?P<op>[^\"]+)\")?",
        text,
        re.M,
    ):
        ty = m.group("ty")
        # Trim layout/tiling annotations out of the type for brevity.
        ty = ty.split("{")[0]
        attr[m.group("name")] = (ty, m.group("op") or "")
    return attr


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("--steps", type=int, default=None,
                    help="steps in the capture (default: modal op count)")
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--hlo", default=None,
                    help="HLO text dump (compiled.as_text()) to attribute "
                         "each op to its result shape + source op_name")
    args = ap.parse_args(argv)

    path = newest_capture(args.trace_dir)
    print(f"# capture: {path}")
    per_op, per_op_n, module_us, module_n = anatomy(path)
    if not per_op:
        print("no device events found", file=sys.stderr)
        return 1

    steps = args.steps
    if steps is None:
        # The module (outer jit program) runs exactly once per step;
        # fall back to the modal op count if no module event exists.
        if module_n:
            steps = module_n
        else:
            counts = [per_op_n[k] for k, _ in per_op.most_common(20)]
            steps = collections.Counter(counts).most_common(1)[0][0]
    total_us = sum(per_op.values())
    if module_n:
        print(f"# module (outer jit): {module_us / module_n / 1e3:.3f} "
              f"ms/step over {module_n} steps")
    print(f"# per-op sum {total_us / 1e3:.2f} ms -> "
          f"{total_us / steps / 1e3:.3f} ms/step "
          f"(shares below are of the per-op sum)")
    attr = hlo_attribution(args.hlo) if args.hlo else {}
    print(f"{'op':36s} {'ms/step':>9s} {'share':>7s} {'n':>5s}")
    for name, us in per_op.most_common(args.top):
        line = (
            f"{name[:36]:36s} {us / steps / 1e3:9.3f} "
            f"{us / total_us:6.1%} {per_op_n[name]:5d}"
        )
        if attr:
            ty, op = attr.get(name, ("?", ""))
            # Keep the informative tail of the op_name (module path).
            op_short = "/".join(op.split("/")[-3:]) if op else ""
            line += f"  {ty:28s} {op_short}"
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
