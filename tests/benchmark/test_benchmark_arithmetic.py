"""The benchmark's own arithmetic, on CPU: the trace reduction on event
lists written by hand and on a small recorded capture, the FLOP counter
against XLA's count of a scan-free model, the LSTM cell's operations and
bytes by hand, and the window's rate and tail."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops, readers, stats, trace
from benchmark.trace import Event

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ---- trace reduction ----------------------------------------------------

OPS = [
    Event("fusion.1", 0.0, 2.0),
    Event("all-reduce.7", 1.5, 2.0),  # 1.5..3.5: 0.5 hidden, 1.5 exposed
    Event("fusion.2", 5.0, 1.0),
    Event("while.3", 7.0, 3.0),  # spans its body
    Event("lstm_cell", 7.5, 1.0),
    Event("fusion.4", 9.0, 0.5),
]


def test_busy_union_counts_overlap_once():
    assert trace.busy_seconds(OPS, 0.0, 10.0) == pytest.approx(3.5 + 1.0 + 3.0)
    # cut to a window: 1..6 holds 1..3.5 and 5..6
    assert trace.busy_seconds(OPS, 1.0, 6.0) == pytest.approx(3.5)
    assert trace.busy_seconds([], 0.0, 1.0) == 0.0


def test_matching_events_time_and_self_time():
    hits = trace.matching(OPS, [r"^fusion"])
    assert sum(e.dur for e in hits) == pytest.approx(3.5)
    own = trace.self_seconds(OPS)
    assert own["while.3"] == pytest.approx(3.0 - 1.0 - 0.5)
    assert own["lstm_cell"] == pytest.approx(1.0)
    assert sum(own.values()) == pytest.approx(sum(e.dur for e in OPS) - 1.5)


def test_exposed_time_is_the_part_nothing_else_covers():
    assert trace.exposed_seconds(OPS, ["all-reduce"], 0.0, 10.0) == pytest.approx(1.5)
    assert trace.exposed_seconds(OPS, ["all-reduce"], 0.0, 3.0) == pytest.approx(1.0)
    assert trace.exposed_seconds(OPS, ["no-such-op"], 0.0, 10.0) == 0.0


def test_step_window_and_idle_gaps():
    modules = [Event("jit_train_step", t, 0.8) for t in (0.0, 1.0, 2.0, 3.0)]
    modules.append(Event("jit_other", 0.9, 0.05))
    assert trace.step_window(modules) == (0.0, 3.0, 3)
    assert trace.step_window(modules[:1]) is None
    # idle time is named from the device's side: between which programs,
    # or inside which one
    ops = [Event("fusion", t, 0.8) for t in (0.0, 1.0, 2.0)]
    ops += [Event("copy", 0.85, 0.1), Event("copy", 1.1, 0.1)]
    runs = [Event("jit_step(12)", float(t), 0.8) for t in range(3)]
    runs.insert(1, Event("jit_put(7)", 0.85, 0.1))
    gaps = dict(map(tuple, trace.idle_gaps(ops, runs, 0.0, 3.0)))
    assert gaps["after jit_step, before jit_put"] == pytest.approx(0.05)
    assert gaps["after jit_put, before jit_step"] == pytest.approx(0.05)
    assert gaps["after jit_step, before jit_step"] == pytest.approx(0.2)
    assert gaps["after jit_step, before (end)"] == pytest.approx(0.2)
    assert sum(gaps.values()) == pytest.approx(3.0 - 2.4 - 0.1)
    inside = trace.idle_gaps(
        [Event("a", 0.0, 0.3), Event("b", 0.5, 0.3)], [Event("jit_step(1)", 0.0, 0.8)], 0.0, 0.8
    )
    assert inside == [["inside jit_step", pytest.approx(0.2)]]


def _context(ops, modules, **kw):
    from benchmark import driver

    cfg = json.load(open(os.path.join(ROOT, "benchmark/configs/breakout_deep_lstm.json")))
    base = dict(
        trace=trace.Trace({"/device:TPU:0": ops}, {"/device:TPU:0": modules}),
        timers={"learner/batch_wait": (0.3, 3), "learner/host_stack": (0.12, 4)},
        host_window_s=3.0,
        steps=3,
        config=cfg,
        chips=1,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        net=driver.Spec(ROOT).network(cfg),
    )
    base.update(kw)
    return readers.Context(**base)


KERNEL = (
    '%branch_0_fun.10 = (f32[256,256]{1,0:T(8,128)S(1)}, f32[256,1024]{1,0:T(8,128)S(1)}) '
    'custom-call(f32[256,256]{1,0} %x), custom_call_target="tpu_custom_call"'
)
LSTM_METRIC = {
    "patterns": [r"f32\[\d+,1024\]\S*\) custom-call\(.*tpu_custom_call"],
    "within": ["^%while"],
    "ops_and_bytes": "lstm_unroll_forward",
}


def test_readers_on_a_hand_made_trace():
    starts = (0.0, 1.0, 2.0, 3.0)
    modules = [Event("jit__train_step_impl", t, 0.8) for t in starts]
    ops = [Event("%fusion.1 = bf16[8] fusion(...)", t, 0.6) for t in starts]
    # the forward unroll: a loop of 0.2 s around two kernel calls, inside an
    # outer loop that must not be taken for it
    ops += [Event("%while.9 = (...) while(...)", t + 0.6, 0.2) for t in starts]
    ops += [Event("%while.2 = (...) while(...)", t + 0.6, 0.15) for t in starts]
    ops += [Event(KERNEL, t + 0.6 + d, 0.01) for t in starts for d in (0.01, 0.08)]
    ctx = _context(ops, modules)
    assert readers.idle_share(ctx) == pytest.approx(100 * (1 - 2.4 / 3.0))
    assert readers.busy_and_window(ctx) == pytest.approx((2.4, 3.0))
    assert readers.timer(ctx, "learner/batch_wait", "share_of_window") == pytest.approx(10.0)
    assert readers.timer(ctx, "learner/host_stack", "mean_ms") == pytest.approx(30.0)
    assert readers.timer(ctx, "learner/absent", "mean_ms") is None
    assert readers.device_time(ctx, ["train_step"]) == pytest.approx(800.0)
    assert readers.device_time(ctx, ["nothing"]) is None
    assert readers.exposed_time(ctx, ["all-reduce"]) is None
    n_flops, n_bytes = ctx.net.lstm_unroll_forward(ctx.config, 1)
    least = max(n_flops / 197e12, n_bytes / 819e9)
    assert flops.least_seconds(n_flops, n_bytes, ctx.peaks) == least
    # four unrolls, each timed by its own (the shorter) loop: 0.15 s
    assert readers.roofline(ctx, **LSTM_METRIC) == pytest.approx(100 * least / 0.15)
    # without `within` the kernel's own events are timed, each one a call
    alone = dict(LSTM_METRIC, within=[])
    assert readers.roofline(ctx, **alone) == pytest.approx(100 * least / 0.01)
    # A reader that finds nothing returns nothing, never 0.
    empty = _context([], [])
    assert readers.idle_share(empty) is None
    assert readers.roofline(empty, **LSTM_METRIC) is None
    per_step = ctx.net.step_flops(ctx.config)
    assert readers.mfu(ctx) == pytest.approx(100 * per_step * 3 / 3.0 / 197e12)
    shipped = json.load(open(os.path.join(ROOT, "benchmark/metrics/kernels.lstm_roofline.json")))
    assert shipped["params"] == LSTM_METRIC


@pytest.mark.parametrize("name", ["step_ms_p50", "step_ms_p95"])
def test_a_window_statistic_as_a_per_layer_metric(name):
    """The tail of the step gaps swings too widely for a bound (PERF.md
    section 2), so it is read from the traced window as a per-layer metric."""
    file = json.load(open(os.path.join(ROOT, f"benchmark/metrics/{name}.json")))
    gaps = [0.1 * (i + 1) for i in range(40)] + [4.5]
    ctx = _context([], [], window=stats.window_metrics(0.0, gaps, 5120))
    assert readers.read(ctx, file) == pytest.approx(ctx.window[name])
    assert readers.read(ctx, file) >= 100.0
    # no window, nothing to read
    assert readers.read(_context([], []), file) is None


def test_short_names_for_the_breakdown():
    assert trace.short_name(KERNEL) == (
        "%branch_0_fun.10 custom-call (f32[256,256], f32[256,1024])"
    )
    assert trace.short_name("jit__train_step_impl(123)") == "jit__train_step_impl(123)"


def test_load_reads_a_recorded_capture(tmp_path):
    """A small capture recorded here, with the options a traced run uses:
    it loads, and a CPU has no device plane, so no device operations (a
    traced run then ends without a result rather than report an idle
    share of nothing)."""
    from benchmark import driver

    with driver.profiler(str(tmp_path)):
        jnp.sum(jnp.ones((64, 64)) @ jnp.ones((64, 64))).block_until_ready()
    got = trace.load(str(tmp_path))
    assert got.ops == {} and got.modules == {}
    assert readers.busy_and_window(_context([], [])._replace(trace=got)) is None
    with pytest.raises(FileNotFoundError):
        trace.load(str(tmp_path / "nothing_here"))


# ---- operations and bytes ----------------------------------------------


def test_lstm_unroll_ops_and_bytes_by_hand(network_of):
    cfg = json.load(open(os.path.join(ROOT, "benchmark/configs/breakout_deep_lstm.json")))
    # 21 steps of [256,512] x [512,1024]
    count = network_of(cfg).OPS_AND_BYTES["lstm_unroll_forward"]
    n_flops, n_bytes = count(cfg, 1)
    assert n_flops == 21 * 2 * 256 * 512 * 1024 == 5_637_144_576
    words = 513 * 1024 + 21 * 256 * 512
    assert n_bytes == 4 * words == 13_111_296
    # compute-bound on a v5e: 28.6 us of FLOPs against 16.0 us of bytes
    assert n_flops / 197e12 > n_bytes / 819e9
    # four chips: a quarter of the rows each, the weights whole
    quarter = count(cfg, 4)
    assert quarter[0] == n_flops / 4
    assert quarter[1] == 4 * (513 * 1024 + 21 * 64 * 512)


def test_forward_macs_by_hand_for_breakout(network_of):
    cfg = json.load(open(os.path.join(ROOT, "benchmark/configs/breakout_deep_lstm.json")))
    net = network_of(cfg)
    macs = net.forward_macs_per_obs(net.sizes(cfg))
    # taps on the zero padding are not counted: (3h-2)(3w-2) of 9hw
    assert macs["section0.conv"] == 250 * 250 * 4 * 16
    assert macs["section0.blocks"] == 4 * 124 * 124 * 16 * 16
    assert macs["section2.blocks"] == 4 * 31 * 31 * 32 * 32
    assert net.taps_3x3_same(1, 1) == 1 and net.taps_3x3_same(2, 2) == 16
    assert macs["fc"] == 11 * 11 * 32 * 256 == 3872 * 256
    assert macs["lstm"] == 512 * 1024
    assert macs["heads"] == 256 * 5


def test_flop_counter_against_xla_on_a_scan_free_model(network_of, tiny_config):
    """XLA's `cost_analysis` counts a scan's body once, so the check is on
    the part without one: torso and heads, forward and backward. It also
    counts the elementwise work, hence the 10% of room above."""
    TINY = tiny_config(
        obs_shape=[16, 20, 3], num_actions=5, num_tasks=3,
        channel_sections=[8, 16], blocks_per_section=1, fc_size=32,
        use_lstm=False, lstm_size=0,
    )
    net = network_of(TINY)
    sizes = net.sizes(TINY)
    params = net.init_params(7, TINY)
    n = 6
    obs = jax.random.randint(
        jax.random.key(1), (1, n, *sizes.obs_shape), 0, 256
    ).astype(jnp.uint8)

    def loss(p, x):
        logits, values = net.forward(sizes, p, x, None, ())
        return jnp.sum(jnp.square(logits)) + jnp.sum(jnp.square(values))

    cost = jax.jit(jax.grad(loss)).lower(params, obs).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    # one observation per unroll, all of them trained on: T+1 = T = 1 here
    macs = net.forward_macs_per_obs(sizes)
    fwd = 2.0 * sum(macs.values())
    counted = n * (fwd + 2.0 * fwd - 2.0 * macs["section0.conv"])
    assert counted <= cost["flops"] <= 1.10 * counted
    step = dict(TINY, unroll_length=1, batch_size=n)
    assert net.step_flops(step) == pytest.approx(n * (2 * fwd + 2 * fwd - 2 * macs["section0.conv"]))


# ---- the window's rate and tail ----------------------------------------


def test_percentile_by_hand():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    assert stats.percentile([7.0], 95) == 7.0
    xs = list(np.random.default_rng(0).random(37))
    assert stats.percentile(xs, 95) == pytest.approx(np.percentile(xs, 95))


def test_a_stall_lowers_the_rate_and_raises_the_tail():
    steady = [0.1 * (i + 1) for i in range(100)]
    a = stats.window_metrics(0.0, steady, frames_per_step=5120)
    assert a["frames_per_s"] == pytest.approx(51200.0)
    assert a["step_ms_p95"] == pytest.approx(100.0)
    # six steps in a hundred take 0.5 s instead of 0.1 s
    t, stalled = 0.0, []
    for i in range(100):
        t += 0.5 if i % 17 == 3 else 0.1
        stalled.append(t)
    b = stats.window_metrics(0.0, stalled, frames_per_step=5120)
    assert b["steps"] == 100 and b["window_s"] == pytest.approx(t)
    assert b["frames_per_s"] == pytest.approx(5120 * 100 / t)
    assert b["frames_per_s"] < a["frames_per_s"]
    assert b["step_ms_p95"] > 4 * a["step_ms_p95"]
    assert b["step_ms_p50"] == pytest.approx(100.0)
    with pytest.raises(ValueError):
        stats.window_metrics(0.0, [0.1], 1)


class _Chip:
    def __init__(self, in_use, reserved_peak, reserved_now):
        self._stats = {
            "peak_bytes_in_use": in_use,
            "peak_bytes_reserved": reserved_peak,
            "bytes_reserved": reserved_now,
        }

    def memory_stats(self):
        return self._stats


STEP = {"temp_bytes": 4000, "argument_bytes": 170, "output_bytes": 3, "alias_bytes": 2}


@pytest.mark.parametrize(
    "chips,step,want",
    [
        # buffers + the loaded programs' scratch, of the fullest chip
        ([_Chip(1000, 3990, 3990), _Chip(1100, 4050, 4050)], STEP, 5150),
        # no compiled step at hand (a mesh): the first cross-check alone
        ([_Chip(1000, 4000, 4000)], None, 5000),
        # the scratch no longer stood when the window closed
        ([_Chip(1000, 4000, 2500)], STEP, None),
        # the reserved peak is not this step's temporaries: far over them,
        # and so far under that it is not this step (2.5% under is: since
        # PR 33 that side is read and admitted, test_benchmark_check_at_size)
        ([_Chip(1000, 9000, 9000)], STEP, None),
        ([_Chip(1000, 2900, 2900)], STEP, None),
    ],
    ids=["fullest_chip", "no_step_at_hand", "scratch_released", "far_over", "far_under"],
)
def test_memory_peak_is_buffers_plus_scratch_and_is_cross_checked(chips, step, want):
    from benchmark import driver

    if want is None:
        with pytest.raises(driver.MemoryMismatch):
            driver.memory_reading(chips, step)
        return
    got = driver.memory_reading(chips, step)
    assert got["memory_peak_bytes"] == want
    assert got["peak_bytes_in_use"] + got["peak_bytes_reserved"] == want
    if step is not None:
        assert got["step_temp_bytes"] == 4000
