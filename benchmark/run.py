"""One run of one cell:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics with
`--trace 0`, its per-layer metrics with `--trace 1`), `device`, with
`--trace 1` also `breakdown`, and last `checks`: every number compared
beside its limit. No accelerator, or another count of chips than the cell
asks for: no result and exit code 2.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_SECONDS = 5.0  # a traced run captures this much and then stops


class NoChip(RuntimeError):
    pass


def probe_devices(chips: int) -> dict:
    """What JAX runs on; refuses anything but `chips` TPU devices."""
    import jax

    devices = jax.devices()
    found = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if found["platform"] != "tpu" or found["count"] != chips:
        raise NoChip(
            f"this cell needs {chips} TPU device(s); JAX found {found}. "
            "The benchmark measures only on the chip: no CPU fallback."
        )
    return found


def main(argv=None, probe=probe_devices, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import check, driver, program, readers, trace

    spec = driver.Spec(root)
    cell = spec.cell(args.workload)
    program.configure_compile_cache()
    try:
        device = probe(int(cell["chips"]))
    except NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        peaks = json.load(f)
    if device["kind"] not in peaks:
        print(
            f"benchmark: no published peaks for {device['kind']!r} in "
            "benchmark/peaks.json; add the device with its source",
            file=sys.stderr,
        )
        return 2
    limits = spec.find("limits", cell["name"])

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(root, ".bench_trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    prep = driver.prepare(spec, cell, args.seed)
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    try:
        got = driver.measure(prep, seconds, T_START, trace_dir)
    except driver.MemoryMismatch as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 4
    device.update(got.memory)

    result = {
        "correct": False,
        "attempted": got.window["steps"],
        "failed": 0,
        "metrics": {},
        "device": device,
    }
    if args.trace:
        ctx = readers.Context(
            trace=trace.load(trace_dir),
            timers=got.timers,
            host_window_s=got.window["window_s"],
            steps=got.window["steps"],
            config=prep.config,
            chips=prep.chips,
            peaks=peaks[device["kind"]],
            window=got.window,
            net=prep.net,
        )
        shutil.rmtree(trace_dir, ignore_errors=True)
        for m in spec.metrics("per_layer", cell["name"]):
            value = readers.read(ctx, spec.find("metrics", m["name"]))
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        bw = readers.busy_and_window(ctx)
        if bw is None or bw[0] <= 0.0:
            print("benchmark: no device operation in the trace", file=sys.stderr)
            return 3
        device["busy_s"], device["window_s"] = bw
        result["breakdown"] = _breakdown(ctx, trace)
    else:
        # any statistic of the window can be named as an end-to-end metric
        values = dict(got.window, setup_s=got.setup_s)
        for m in spec.metrics("end_to_end", cell["name"]):
            result["metrics"][m["name"]] = {
                "value": values[m["name"]],
                "unit": m["unit"],
            }
    result["window"] = got.window
    # the program's timers over the window, [seconds, calls]: where a stall
    # of the step loop was spent (PERF.md section 2)
    result["timers"] = {k: list(v) for k, v in sorted(got.timers.items())}
    result["setup_parts"] = got.setup_parts

    # The reference runs last: the window is closed, the peak is read and
    # the learner's state is freed.
    gc.collect()
    t_ref = time.monotonic()
    ref_record = check.reference_record(prep, driver.check_batches(prep))
    compared = check.compare(
        got.program_record, ref_record,
        prep.config["optimizer"]["rmsprop_decay"], prep.net.leaf_groups,
    )
    result["reference_s"] = time.monotonic() - t_ref
    result["all_numbers"] = compared["numbers"]
    result["worst_leaf"] = compared["worst_leaf"]
    result["correct"], result["checks"] = check.verdict(
        compared["numbers"], limits
    )
    lines = [
        f"check {name}: {row['value']:.6g} (limit {row['limit']:.6g})"
        for name, row in result["checks"].items()
    ]
    print(json.dumps(result))
    sys.stdout.flush()
    print("\n".join(lines), file=sys.stderr)
    return 0


def _breakdown(ctx, trace) -> dict:
    """Top device operations by self time and idle time by where it falls
    among the launched programs, both of the first chip, in seconds over
    the traced window."""
    ops = next(iter(ctx.trace.ops.values()))
    t0, t1, _ = ctx.device_window
    inside = [e for e in ops if t0 <= e.start < t1]
    by_name = sorted(
        trace.self_seconds(inside).items(), key=lambda kv: -kv[1]
    )
    return {
        "device_ops": [[trace.short_name(k), v] for k, v in by_name[:10]],
        "idle_gaps": trace.idle_gaps(
            ops, next(iter(ctx.trace.modules.values())), t0, t1
        )[:10],
    }


if __name__ == "__main__":
    sys.exit(main())
