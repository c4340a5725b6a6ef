"""Readings that the limits of a cell's output check are set from.

    python3 benchmark/calibrate.py --workload <cell> --seeds 12 \
        --control-seeds 3 --fault-seeds 3 --out chiprun_out/<file>.json

In one process, at the cell's own size, on the chip:

  sound          the program as the configuration states it, against the
                 reference, on every seed: the largest reading is a
                 number's lower reading.
  control        the reference put in the program's place with each part of
                 the network, weights and activations, in the precision
                 below the one the configuration states for it (bfloat16 in
                 8-bit floats, e4m3; float32 in bfloat16). (The program's
                 own lower-precision path, `train_dtype`, does not trace
                 with the fused LSTM: PERF.md.)
  control_...    the other entries of the network file's `CONTROLS`: only
                 some parts lowered, the rest in the reference's float32.
  half           the fault 'half of the batch left out', planted in the
                 reference put in the program's place: the reference on the
                 first half of each batch's rows against the reference on
                 all.
  popart_ignored (PopArt cells) the reference started from the identity
                 statistics, mu 0 and sigma 1, in the program's place: a
                 learner that took no notice of the statistics it was given.

No window is measured: a training cell's readings need none. Not run by
the benchmark's own runs; `limits/<cell>.json` holds what was set from it
and PERF.md the readings.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def program_record(prep) -> dict:
    from benchmark import driver, program

    learner, _ = program.build_learner(
        prep.net, prep.config, prep.chips, prep.weights, prep.popart
    )
    learner.start()
    try:
        return driver.first_steps(learner, prep)
    finally:
        program.release(learner)


def main(argv=None, probe=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_500_000_001)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from benchmark import check, driver, program, run

    spec = driver.Spec(root)
    cell = spec.cell(args.workload)
    program.configure_compile_cache()
    try:
        device = (probe or run.probe_devices)(int(cell["chips"]))
    except run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    out = {"workload": cell["name"], "device": device, "seeds": []}
    for i in range(args.seeds):
        seed = args.first_seed + 7_919 * i
        t0 = time.monotonic()
        prep = driver.prepare(spec, cell, seed)
        t_prep = time.monotonic() - t0
        decay = prep.config["optimizer"]["rmsprop_decay"]
        row = {"seed": seed}
        sound = program_record(prep)
        gc.collect()
        batches = driver.check_batches(prep)
        ref = check.reference_record(prep, batches)
        row["sound"] = check.compare(sound, ref, decay, prep.net.leaf_groups)
        row["losses"] = {"program": sound["losses"], "reference": ref["losses"]}
        row["grad_norm_unclipped"] = ref["grad_norm_unclipped"]
        del sound  # a record is 5 bytes a parameter: two at a time from here

        def in_place(prep=prep, **planted) -> dict:
            rec = check.reference_record(prep, batches, **planted)
            return check.compare(
                check.as_program_record(rec, decay), ref, decay,
                prep.net.leaf_groups,
            )

        if i < args.control_seeds:
            for which in prep.net.CONTROLS:
                row[which] = in_place(
                    dtypes=check.control_dtypes(prep.net, prep.config, which)
                )
        if i < args.fault_seeds:
            row["half"] = in_place(
                rows=slice(0, int(prep.config["batch_size"]) // 2)
            )
            if prep.popart is not None:
                mu = prep.popart["mu"]
                identity = {"mu": 0.0 * mu, "nu": 1.0 + 0.0 * mu}
                row["popart_ignored"] = in_place(
                    prep=prep._replace(popart=identity)
                )
        row["seconds"] = {"prepare": t_prep, "all": time.monotonic() - t0}
        out["seeds"].append(row)
        print(json.dumps(row), flush=True)
        del prep, ref, batches
        gc.collect()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
