"""The one driver loop for every cell: the learner's ingest path, timed.

feeder threads -> `Learner.enqueue` -> host queue -> batcher thread
(`np.stack` into reused buffers) -> `device_put` -> `Learner.step_once`
(the jitted train step with the kernels the program picks). A closed loop
at saturation: a feeder blocks while the learner's bounded queue is full.

The same `Learner` object takes, in this order: the three steps the output
check reads (fed in a known order from the main thread, through the same
`enqueue` and `step_once`), the warm-up steps with the feeders running,
and the window.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import threading
import time
from typing import Any, NamedTuple, Optional

from benchmark import check, program, reference, stats, traffic

WARMUP_STEPS = 8  # after the check's three: queues and both stack buffers full
STEP_TIMEOUT_S = 120.0
# The very first step waits for the step's compile on an empty cache and for
# the first `device_put`: a step of 0.4 billion parameters took 124 s to its
# third step cold (my chip runs, PR 32).
FIRST_STEP_TIMEOUT_S = 600.0
CHECK_STEPS = 3
DEFAULT_NETWORK = "impala_resnet_lstm"  # of a configuration that names none
# What a network file holds (benchmark/README.md, "A network").
NETWORK_API = (
    "REQUIRED_MODEL_KEYS", "sizes", "init_params", "forward", "draw_state",
    "to_program_params", "leaf_groups", "stated", "stated_dtypes", "CONTROLS",
    "step_flops", "OPS_AND_BYTES",
)


class Spec:
    """`BENCHMARK.json` at `root` and the files it names. Whatever belongs
    to one configuration, one network, one traffic mix or one per-layer
    metric is a file of its own, found by name under any of `paths`."""

    def __init__(self, root: str):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        self._networks: dict = {}

    def cell(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.doc["workloads"]]
        raise KeyError(f"no workload {name!r} in BENCHMARK.json: {known}")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def _file(self, kind: str, name: str) -> str:
        """`<path>/<kind>/<name>` under the first of `paths` that has it."""
        for path in self.doc["paths"]:
            file = os.path.join(self.root, path, kind, name)
            if os.path.exists(file):
                return file
        raise FileNotFoundError(f"no {kind}/{name} under {self.doc['paths']}")

    def find(self, kind: str, name: str) -> dict:
        """The data file `<kind>/<name>.json`."""
        with open(self._file(kind, name + ".json")) as f:
            return json.load(f)

    def network(self, config: dict):
        """The module `networks/<name>.py` that the configuration names
        under `network`, loaded by its path, once."""
        name = config.get("network", DEFAULT_NETWORK)
        if name not in self._networks:
            try:
                file = self._file("networks", name + ".py")
            except FileNotFoundError as e:
                raise program.ConfigMismatch(
                    f"{config.get('name')}: network {name!r}: {e}"
                ) from None
            module_spec = importlib.util.spec_from_file_location(name, file)
            module = importlib.util.module_from_spec(module_spec)
            module_spec.loader.exec_module(module)
            missing = [k for k in NETWORK_API if not hasattr(module, k)]
            if missing:
                raise program.ConfigMismatch(f"network {name!r} lacks {missing}")
            self._networks[name] = module
        module = self._networks[name]
        lacking = [
            k for k in module.REQUIRED_MODEL_KEYS if k not in config["model"]
        ]
        if lacking:
            raise program.ConfigMismatch(
                f"{config.get('name')}: network {name!r} needs {lacking} "
                "in the configuration's `model` group"
            )
        return module

    def metrics(self, section: str, cell: str) -> list:
        """Entries of `end_to_end` or `per_layer` that this cell reports."""
        return [
            m
            for m in self.doc[section]
            if "workloads" not in m or cell in m["workloads"]
        ]


class Prepared(NamedTuple):
    config: dict
    net: Any  # the configuration's network file, a module
    mix: dict
    chips: int
    weights: dict  # the reference's tree, on the device
    popart: Optional[dict]  # PopArt's statistics at the start, or None
    pool: list  # generated unrolls (dicts of numpy)
    trajs: list  # the same, as the program's Trajectory
    orders: list  # one permutation of the pool per feeder


def prepare(spec: Spec, cell: dict, seed: int) -> Prepared:
    config = spec.config(cell["config"])
    net = spec.network(config)
    mix = spec.find("traffic", cell["traffic"])
    seed = abs(int(seed))
    pool = traffic.make_pool(seed, config, mix, net.draw_state)
    return Prepared(
        config=config,
        net=net,
        mix=mix,
        chips=int(cell["chips"]),
        weights=net.init_params(seed, config),
        popart=reference.init_popart(seed, config),
        pool=pool,
        trajs=program.trajectories(config, pool),
        orders=traffic.feeder_orders(seed, mix, len(pool)),
    )


def check_batches(prep: Prepared) -> list:
    """The first three batches of the pool, stacked by the benchmark itself:
    what `first_steps` feeds, for the reference."""
    b = int(prep.config["batch_size"])
    return [
        traffic.stack(prep.pool[s * b : (s + 1) * b]) for s in range(CHECK_STEPS)
    ]


def first_steps(learner, prep: Prepared) -> dict:
    """Drive the learner through its first three steps on the first three
    batches of the pool, in order, and record what the check compares
    (`check.compare`'s program record): of a network of P parameters the
    record keeps 5 P bytes on the host through the window."""
    b = int(prep.config["batch_size"])
    start = check.leaves(prep.net.to_program_params(prep.weights))
    record = {"losses": [], "popart0": prep.popart}
    for s in range(CHECK_STEPS):
        for traj in prep.trajs[s * b : (s + 1) * b]:
            learner.enqueue(traj)
        logs = learner.step_once(
            timeout=FIRST_STEP_TIMEOUT_S if s == 0 else STEP_TIMEOUT_S
        )
        record["losses"].append(float(logs["total_loss"]))
        if s == 0:
            after_one = program.read_state(learner)
            record["nu1"] = program.host(check.leaves(after_one["nu"]))
            record["moved1"] = check.step_signs(
                start, check.leaves(after_one["params"])
            )
            record["popart1"] = after_one["popart"]
            del after_one  # the next step donates these arrays
    record["delta3"] = check.change_norms(
        start, check.leaves(program.read_state(learner)["params"])
    )
    return record


class Feeders:
    """Threads that offer the pool to `enqueue` for ever, each in its own
    order, until the learner stops (enqueue then raises QueueClosed)."""

    def __init__(self, learner, prep: Prepared):
        closed = program.queue_closed_error()
        self.errors: list = []

        def feed(order):
            try:
                while True:
                    for i in order:
                        learner.enqueue(prep.trajs[i])
            except closed:
                return
            except BaseException as e:  # surfaced by the run as a failure
                self.errors.append(e)

        self.threads = [
            threading.Thread(target=feed, args=(o,), name=f"feeder{i}", daemon=True)
            for i, o in enumerate(prep.orders)
        ]

    def start(self) -> None:
        for t in self.threads:
            t.start()

    def join(self) -> None:
        for t in self.threads:
            if t.ident is not None:  # started: the check's steps may have raised first
                t.join(timeout=30)
        alive = [t.name for t in self.threads if t.is_alive()]
        if alive:
            raise RuntimeError(f"feeder threads did not stop: {alive}")


class StepLoop:
    """`step_once` with one step in flight: after dispatching step k it
    blocks on step k-1's loss and stamps that step complete."""

    def __init__(self, learner):
        import jax

        self._jax = jax
        self._learner = learner
        self._in_flight = None
        self.stamps: list = []

    def step(self) -> None:
        logs = self._learner.step_once(timeout=STEP_TIMEOUT_S)
        if self._in_flight is not None:
            self._jax.block_until_ready(self._in_flight["total_loss"])
            self.stamps.append(time.monotonic())
        self._in_flight = logs

    def run_for(self, seconds: float) -> tuple:
        """(stamp that opens the window, stamps of the steps completed in
        it). Opens on the next completion and closes on the first one at or
        after `seconds` later (and not before two steps: a gap needs two)."""
        self.step()
        t_open = self.stamps[-1]
        first = len(self.stamps)
        while (
            self.stamps[-1] - t_open < seconds or len(self.stamps) < first + 2
        ):
            self.step()
        return t_open, self.stamps[first:]

    def drain(self) -> None:
        if self._in_flight is not None:
            self._jax.block_until_ready(self._in_flight["total_loss"])
            self._in_flight = None


class Measured(NamedTuple):
    setup_s: float
    setup_parts: dict  # seconds since process start at each stage of set-up
    window: dict  # stats.window_metrics of the measured (or traced) window
    timers: dict  # name -> (seconds, calls) inside that window
    program_record: dict
    memory: dict  # memory_reading of the fullest chip


def measure(
    prep: Prepared,
    seconds: float,
    t_start: float,
    trace_dir: Optional[str] = None,
) -> Measured:
    """Build, check-steps, warm up, measure, stop. With `trace_dir` the
    window is captured by the profiler (python tracer off)."""
    import jax

    parts = {"inputs_made": time.monotonic() - t_start}
    learner, registry = program.build_learner(
        prep.net, prep.config, prep.chips, prep.weights, prep.popart
    )
    parts["learner_built"] = time.monotonic() - t_start
    learner.start()
    feeders = Feeders(learner, prep)
    try:
        record = first_steps(learner, prep)
        parts["first_steps_done"] = time.monotonic() - t_start
        feeders.start()
        loop = StepLoop(learner)
        for _ in range(WARMUP_STEPS):
            loop.step()
        parts["warmed_up"] = time.monotonic() - t_start
        # The pool is thousands of long-lived objects of the benchmark's
        # own; a full collection inside the window would walk them all.
        gc.collect()
        gc.freeze()
        with profiler(trace_dir):
            before = registry.timer_totals()
            setup_s = time.monotonic() - t_start
            t_open, stamps = loop.run_for(seconds)
            after = registry.timer_totals()
        loop.drain()
        memory = memory_reading(jax.devices(), program.step_memory(learner))
    finally:
        gc.unfreeze()
        program.release(learner)
        feeders.join()
    if feeders.errors:
        raise RuntimeError("a feeder thread failed") from feeders.errors[0]
    if learner.error is not None:
        raise RuntimeError("the batcher thread failed") from learner.error
    frames = int(prep.config["batch_size"]) * int(prep.config["unroll_length"])
    timers = {
        k: (after[k][0] - before.get(k, (0.0, 0))[0],
            after[k][1] - before.get(k, (0, 0))[1])
        for k in after
    }
    return Measured(
        setup_s=setup_s,
        setup_parts=parts,
        window=stats.window_metrics(t_open, stamps, frames),
        timers=timers,
        program_record=record,
        memory=memory,
    )


class MemoryMismatch(RuntimeError):
    """The allocator's readings do not add up to a peak that can be trusted."""


# `peak_bytes_reserved` against the compiled step's temporaries. Over them
# by more than 2% another program's scratch is counted in: the side that
# would overstate `memory_peak_bytes`. Under them it can only understate
# it, and a large step stands under: RESERVED_UNDER_TEMP_AT_MOST of the
# temporaries at the most, or it is not the step that was compiled.
RESERVED_OVER_TEMP_AT_MOST = 0.02
RESERVED_UNDER_TEMP_AT_MOST = 0.15


def memory_reading(devices, step_memory: Optional[dict]) -> dict:
    """The fullest chip's memory, read while the learner is still loaded.

    The TPU runtime keeps the scratch of loaded programs apart from the
    buffers: `bytes_reserved` (the train step's temporaries live there and
    stay reserved while it is loaded) is left out of `bytes_in_use`, and
    the allocator takes both off what is available (its own events in a
    trace: limit - reserved - allocated = available). So the chip's peak is
    `peak_bytes_in_use` + `peak_bytes_reserved`, as long as the scratch
    stood reserved when the buffers peaked. Two cross-checks, or no result:
    the scratch reserved now, as the window closes, is the peak reserved;
    and where the program hands out its compiled step (`step_memory`, from
    `memory_analysis()`), the peak reserved is that step's temporaries, as
    far as the two can agree. `reserved_under_temp`, given beside them, is
    the share of the temporaries by which the reservation stands under
    them (negative: over). Over by more than 2% another program's scratch
    is counted in, and the peak is overstated. Under is the runtime's own
    doing and can only understate it: every step read so far stands under,
    by 6 to 55 MB on this tree's steps (0.2-0.9%; my chip runs, PR 31 and
    PR 33) and by 2.4%, 4.6% and 7.6% on three steps of trees that were
    not merged (PERF.md section 7). `memory_analysis()` counts what the
    compiler assigns to the chip's alternate memory (`S(1)` in the compiled
    layouts, 128 MiB, buffers of up to 95 MB each) among the temporaries
    and the reservation is of HBM; that covers the gaps under 128 MiB and
    not the two widest, whose cause stayed open. The runtime also drops
    the reservation of an idle step when the chip is full (16.86 of 16.91
    GB: `bytes_reserved` read 0 as a traced window closed, in one run of
    four): the first cross-check then refuses the run, as it should."""
    best = None
    for d in devices:
        s = d.memory_stats()
        if s is None:  # a backend that keeps none (the CPU, in the tests)
            return {"memory_peak_bytes": 0}
        got = {
            k: int(s.get(k, 0))
            for k in ("peak_bytes_in_use", "peak_bytes_reserved", "bytes_reserved")
        }
        got["memory_peak_bytes"] = (
            got["peak_bytes_in_use"] + got["peak_bytes_reserved"]
        )
        if best is None or got["memory_peak_bytes"] > best["memory_peak_bytes"]:
            best = got
    if best["bytes_reserved"] != best["peak_bytes_reserved"]:
        raise MemoryMismatch(
            "program scratch reserved at the window's close is not the peak "
            f"reserved: {best}; in_use + reserved may overstate the peak"
        )
    if step_memory is not None:
        temp = step_memory["temp_bytes"]
        under = (temp - best["peak_bytes_reserved"]) / temp
        if not -RESERVED_OVER_TEMP_AT_MOST <= under <= RESERVED_UNDER_TEMP_AT_MOST:
            raise MemoryMismatch(
                f"peak reserved {best['peak_bytes_reserved']} stands "
                f"{abs(under):.2%} {'under' if under > 0 else 'over'} the "
                f"compiled step's temporaries {temp}: it may stand at most "
                f"{RESERVED_OVER_TEMP_AT_MOST:.0%} over them (another "
                "program's scratch is counted in) and at most "
                f"{RESERVED_UNDER_TEMP_AT_MOST:.0%} under (not this step)"
            )
        best.update({f"step_{k}": v for k, v in step_memory.items()})
        best["reserved_under_temp"] = under
    return best


@contextlib.contextmanager
def profiler(trace_dir: Optional[str]):
    """Capture device events into `trace_dir` (None: no capture)."""
    if trace_dir is None:
        yield
        return
    import jax

    # Device events only. The host tracer would time every block of the
    # host-side transposition that the DMLab observation batch goes
    # through on its way to the device: 2.4 million events a batch, and a
    # step of 96 ms takes 3.7 s (my chip runs, PR 23).
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
