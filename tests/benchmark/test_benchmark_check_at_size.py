"""What lets the harness take a network of hundreds of millions of
parameters (PR 33): the output check goes leaf by leaf and keeps 5 bytes a
parameter a side, yet reads what the whole-tree arithmetic it replaced
read, to the last bit; the memory cross-check refuses the side that
overstates the peak and admits a reservation that stands under the compiled
step's temporaries; the first step has a time limit of its own. Nothing
here is a device number: the byte counts are chip readings quoted from
PERF.md (sections 4 and 7)."""

import json
import os
import queue
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, driver, program, reference
from test_benchmark_arithmetic import _Chip
from test_benchmark_second_network import CELL as TRANSFORMER_CELL
from test_benchmark_second_network import add_transformer_cell

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# ---- (a) the numbers are the whole-tree arithmetic's, bit for bit --------


def _whole_tree_compare(prog: dict, ref: dict, decay: float, leaf_groups) -> dict:
    """`check.compare` as it stood before PR 33, on records that hold whole
    float32 trees (params0, params1, params3, nu1 / grads1): every tree's
    differences and magnitudes built at once in float64."""

    def norms(tree):
        return {
            k: float(np.sqrt(np.sum(np.square(np.asarray(v, np.float64)))))
            for k, v in check.leaves(tree).items()
        }

    def diff(a, b):
        return jax.tree.map(
            lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64), a, b
        )

    def shape_gap(m, g, floor):
        r = np.abs(np.asarray(g, np.float64))
        nm, nr = np.linalg.norm(m), np.linalg.norm(r)
        unit_m = m / nm if nm > 0 else m
        unit_r = r / nr if nr > 0 else r
        return float(np.linalg.norm(unit_m - unit_r) * nr / max(nr, floor, 1e-30))

    def wrong_way(dp, dr, g):
        energy = np.square(np.asarray(g, np.float64))
        return float(np.sum(energy * (dp * dr <= 0.0)) / max(np.sum(energy), 1e-300))

    numbers, where = {}, {}
    for i, (lp, lr, scale) in enumerate(
        zip(prog["losses"], ref["losses"], ref["loss_scales"])
    ):
        numbers[f"loss_gap_step{i + 1}"] = abs(lp - lr) / max(scale, 1e-30)
    g_ref = norms(ref["grads1"])
    g_floor = float(np.median(list(g_ref.values())))
    moving = [k for k, v in g_ref.items() if v >= check.NEGLIGIBLE_GRADIENT * g_floor]
    magnitude = jax.tree.map(
        lambda n: np.sqrt(np.maximum(np.asarray(n, np.float64), 0.0) / (1.0 - decay)),
        prog["nu1"],
    )
    d_prog = diff(prog["params3"], prog["params0"])
    d_ref = diff(ref["params3"], ref["params0"])
    per_leaf = {
        "grad_norm_gap": (check.leaf_gaps(norms(magnitude), g_ref), list(g_ref)),
        "grad_elem_gap": (
            check.leaves(
                jax.tree.map(
                    lambda m, g: shape_gap(m, g, g_floor), magnitude, ref["grads1"]
                )
            ),
            list(g_ref),
        ),
        "delta_norm_gap": (check.leaf_gaps(norms(d_prog), norms(d_ref)), moving),
        "update_wrong_way": (
            check.leaves(
                jax.tree.map(
                    wrong_way,
                    diff(prog["params1"], prog["params0"]),
                    diff(ref["params1"], ref["params0"]),
                    ref["grads1"],
                )
            ),
            moving,
        ),
    }
    if ref["popart0"] is not None:
        numbers["popart_gap"] = check.popart_gap(prog, ref)
    for name, (gaps, keep) in per_leaf.items():
        numbers[name], where[name] = check._worst(gaps, keep)
        numbers[f"{name}.median_leaf"] = float(np.median([gaps[k] for k in keep]))
        for group in sorted({g for k in keep for g in leaf_groups(k)}):
            members = [k for k in keep if group in leaf_groups(k)]
            numbers[f"{name}.{group}"], where[f"{name}.{group}"] = check._worst(
                gaps, members
            )
            numbers[f"{name}.{group}.median_leaf"] = float(
                np.median([gaps[k] for k in members])
            )
    return {
        "numbers": numbers,
        "worst_leaf": where,
        "left_out": sorted(set(g_ref) - set(moving)),
        "per_leaf": {k: v[0] for k, v in per_leaf.items()},
    }


def _copies(tree):
    return jax.tree.map(lambda x: np.array(x, copy=True), tree)


def _both_kinds_of_record(prep, monkeypatch):
    """One run of the program's three steps and one of the reference's,
    each recorded twice: as the harness records them, and as whole trees
    copied from the same states as they pass."""
    to_program = prep.net.to_program_params
    params0 = _copies(to_program(prep.weights))
    states, outs = [], []
    learner, _ = program.build_learner(
        prep.net, prep.config, prep.chips, prep.weights, prep.popart
    )
    step_once = learner.step_once

    def stepping(**kw):
        logs = step_once(**kw)
        states.append(_copies((learner.params, learner.opt_state[0].nu)))
        return logs

    learner.step_once = stepping
    learner.start()
    try:
        got = driver.first_steps(learner, prep)
    finally:
        program.release(learner)
    whole_got = {
        "losses": got["losses"], "params0": params0,
        "params1": states[0][0], "nu1": states[0][1], "params3": states[2][0],
        "popart0": got["popart0"], "popart1": got["popart1"],
    }

    learner_step = reference.learner_step

    def stepping_reference(*a, **kw):
        outs.append(learner_step(*a, **kw))
        return outs[-1]

    monkeypatch.setattr(reference, "learner_step", stepping_reference)
    want = check.reference_record(prep, driver.check_batches(prep))
    whole_want = {
        "losses": want["losses"], "loss_scales": want["loss_scales"],
        "params0": params0,
        "grads1": _copies(to_program(outs[0].grads)),
        "params1": _copies(to_program(outs[0].params)),
        "params3": _copies(to_program(outs[2].params)),
        "popart0": want["popart0"], "popart1": want["popart1"],
    }
    return (got, want), (whole_got, whole_want)


@pytest.mark.parametrize(
    "cell_name",
    ["breakout_b256_feed_sat", "dmlab30_t100_b64_feed_sat", TRANSFORMER_CELL],
)
def test_leaf_by_leaf_reads_what_the_whole_trees_read(
    checkout, monkeypatch, cell_name
):
    if cell_name == TRANSFORMER_CELL:
        add_transformer_cell(checkout)
    else:
        checkout.shrink(batch=4, unroll=5, block=2)
    spec = driver.Spec(checkout.root)
    prep = driver.prepare(spec, spec.cell(cell_name), 2_147_483_659)
    decay = prep.config["optimizer"]["rmsprop_decay"]
    (got, want), (whole_got, whole_want) = _both_kinds_of_record(prep, monkeypatch)
    new = check.compare(got, want, decay, prep.net.leaf_groups)
    old = _whole_tree_compare(whole_got, whole_want, decay, prep.net.leaf_groups)
    assert new["numbers"] == old["numbers"]  # equal, not close
    assert new["worst_leaf"] == old["worst_leaf"]
    assert new["left_out"] == old["left_out"]
    assert new["per_leaf"] == old["per_leaf"]
    assert 0 < new["numbers"]["grad_elem_gap"] < 1  # a bfloat16 torso: not all nought
    # the reference in the program's place: (1 - decay) g^2 for nu1
    as_program = dict(
        whole_want,
        nu1=jax.tree.map(lambda g: (1.0 - decay) * np.square(g), whole_want["grads1"]),
    )
    new = check.compare(
        check.as_program_record(want, decay), want, decay, prep.net.leaf_groups
    )
    old = _whole_tree_compare(as_program, whole_want, decay, prep.net.leaf_groups)
    assert new["numbers"] == old["numbers"] and new["per_leaf"] == old["per_leaf"]


# ---- (b) and in a bounded piece of the host's memory ----------------------

_SYNTHETIC = """
import json, sys, tracemalloc
import numpy as np
from benchmark import check

in_place, largest = sys.argv[1] == "1", 5_000_000
rng = np.random.default_rng(33)
grads, nu, moved_p, moved_r, delta = {}, {}, {}, {}, {}
for i, n in enumerate([largest] + [15_000_000 // 39] * 39):
    name = f"['params']['layer_{i:02d}']['kernel']"
    g = rng.standard_normal(n, dtype=np.float32)
    grads[name] = g
    nu[name] = 0.01 * np.square(g * (1 + 0.01 * rng.standard_normal(n, dtype=np.float32)))
    moved_r[name] = -np.sign(g).astype(np.int8)
    moved_p[name] = moved_r[name] * rng.choice(np.array([1, 1, 1, -1], np.int8), size=n)
    delta[name] = float(i + 1)
common = {"losses": [1.0, 1.1, 1.2], "popart0": None, "popart1": None}
got = dict(common, nu1=nu, moved1=moved_p, delta3=delta)
want = dict(common, loss_scales=[1.0, 1.0, 1.0], grads1=grads, moved1=moved_r,
            delta3={k: 1.01 * v for k, v in delta.items()})
if in_place:
    got = check.as_program_record(want, 0.99)
tracemalloc.start()
before = tracemalloc.get_traced_memory()[0]
tracemalloc.reset_peak()
out = check.compare(got, want, 0.99, lambda name: ("core",))
peak = tracemalloc.get_traced_memory()[1] - before
print(json.dumps({"peak": peak, "largest": largest, "numbers": out["numbers"],
                  "leaves": len(out["per_leaf"]["grad_elem_gap"])}))
"""


@pytest.mark.parametrize("in_place", [False, True], ids=["program", "reference_in_place"])
def test_compare_builds_two_leaves_of_float64_at_the_most(in_place):
    """40 leaves, 2e7 elements: the whole-tree arithmetic built five
    float64 trees of them, 800 MB; leaf by leaf it is twice the largest
    leaf (5e6 elements: 80 MB) and a slack for the byte-wide masks. In a
    process of its own (numpy alone: `compare` needs no JAX), so that
    arrays of this size come and go in no test worker's heap."""
    done = subprocess.run(
        [sys.executable, "-c", _SYNTHETIC, str(int(in_place))],
        cwd=ROOT, capture_output=True, text=True, timeout=300, check=True,
    )
    out = json.loads(done.stdout.splitlines()[-1])
    largest, numbers = out["largest"], out["numbers"]
    assert out["peak"] <= 2 * 8 * largest + 2 * largest + (1 << 20), out["peak"]
    assert out["leaves"] == 40
    if in_place:  # the reference against itself
        assert numbers["grad_elem_gap"] < 1e-6
        assert numbers["update_wrong_way"] == 0.0
    else:  # a quarter of the elements moved the other way
        assert numbers["update_wrong_way.median_leaf"] == pytest.approx(0.25, abs=0.01)


# ---- (c) the memory cross-check guards the side that can mislead ----------


def _chip(in_use, reserved_peak, reserved_now=None):
    return _Chip(
        in_use, reserved_peak, reserved_peak if reserved_now is None else reserved_now
    )


@pytest.mark.parametrize(
    "reserved,temp,under",
    [
        # breakout as PR 23 read it, and both cells since PR 29
        (4_670_603_264, 4_682_080_256, 0.00245),
        (3_016_343_552, 3_041_531_392, 0.00828),
        (3_523_117_056, 3_529_344_000, 0.00176),
        # the three witnesses of ISSUE 33: a step of 403M parameters before
        # and after PR 32's review round, and PR 29's try of XLA's pool for
        # the last section (its share alone was written down: 4.6%)
        (5_931_204_608, 6_076_323_840, 0.02388),
        (int(3_041_531_392 * (1 - 0.046)), 3_041_531_392, 0.046),
        (5_621_448_704, 6_085_359_616, 0.07623),
        # the same reservation exactly, and a hair over
        (4_000_000_000, 4_000_000_000, 0.0),
        (4_040_000_000, 4_000_000_000, -0.01),
    ],
)
def test_a_reservation_under_the_temporaries_is_read_and_admitted(reserved, temp, under):
    got = driver.memory_reading(
        [_chip(1_000_000_000, reserved)], {"temp_bytes": temp, "argument_bytes": 1}
    )
    assert got["memory_peak_bytes"] == 1_000_000_000 + reserved
    assert got["step_temp_bytes"] == temp
    assert got["reserved_under_temp"] == pytest.approx(under, abs=5e-5)
    assert got["reserved_under_temp"] == (temp - reserved) / temp


@pytest.mark.parametrize(
    "chip,temp",
    [
        # 3% over: another program's scratch is counted in
        (_chip(1_000_000_000, 4_120_000_000), 4_000_000_000),
        # far under: not the step that was compiled
        (_chip(1_000_000_000, 3_000_000_000), 4_000_000_000),
        (_chip(1_000_000_000, 0), 4_000_000_000),
        # the scratch no longer stood as the window closed, whatever the step
        (_chip(1_000_000_000, 5_931_204_608, 5_000_000_000), 6_076_323_840),
    ],
    ids=["3pct_over", "far_under", "nothing_reserved", "scratch_released"],
)
def test_a_reservation_that_can_mislead_is_refused(chip, temp):
    with pytest.raises(driver.MemoryMismatch):
        driver.memory_reading([chip], {"temp_bytes": temp})


def test_a_reservation_over_the_temporaries_ends_a_run_in_exit_code_4(
    checkout, monkeypatch
):
    """Through `run.main`: no result line, exit code 4."""
    checkout.shrink(batch=4, unroll=5, block=2)
    reading = driver.memory_reading
    monkeypatch.setattr(
        driver, "memory_reading",
        lambda devices, step: reading(
            [_chip(1_000_000_000, 4_120_000_000)], {"temp_bytes": 4_000_000_000}
        ),
    )
    rc, result, err = checkout.run("breakout_b256_feed_sat")
    assert rc == 4 and result is None
    assert "3.00% over" in err, err


# ---- (d) the first step's own time limit -----------------------------------


class _StubLearner:
    """Takes `needs[k]` seconds for its k-th step, by its own account: a
    step given less time than it needs ends as the program's does, in
    `queue.Empty`. Nothing sleeps."""

    def __init__(self, needs):
        self._needs = list(needs)
        self.timeouts = []
        self.params = {"params": {"w": jnp.ones((3,), jnp.float32)}}
        self.opt_state = (type("S", (), {"nu": {"params": {"w": jnp.zeros((3,))}}})(),)
        self.popart_state = ()

    def enqueue(self, traj):
        pass

    def step_once(self, timeout=None):
        self.timeouts.append(timeout)
        if timeout < self._needs[len(self.timeouts) - 1]:
            raise queue.Empty
        self.params = jax.tree.map(lambda x: x - 0.5, self.params)
        return {"total_loss": 1.0}


def _stub_prep():
    net = type("N", (), {"to_program_params": staticmethod(lambda w: {"params": w})})
    return driver.Prepared(
        config={"batch_size": 2}, net=net, mix={}, chips=1,
        weights={"w": jnp.ones((3,), jnp.float32)}, popart=None,
        pool=[], trajs=[object()] * 6, orders=[],
    )


def test_the_first_step_may_wait_for_a_compile_and_no_later_one():
    # a cold compile of a 0.4B-parameter step: the first step took 276 s
    learner = _StubLearner([276.0, 1.0, 1.0, 1.0, 1.0])
    record = driver.first_steps(learner, _stub_prep())
    assert learner.timeouts == [driver.FIRST_STEP_TIMEOUT_S, 120.0, 120.0]
    assert driver.FIRST_STEP_TIMEOUT_S == 600.0 and driver.STEP_TIMEOUT_S == 120.0
    assert record["delta3"] == {"['params']['w']": pytest.approx(1.5 * 3**0.5)}
    assert record["moved1"]["['params']['w']"].tolist() == [-1, -1, -1]
    loop = driver.StepLoop(learner)
    loop.step()
    loop.step()
    assert learner.timeouts[3:] == [120.0, 120.0]


@pytest.mark.parametrize("slow_step", [1, 2], ids=["second", "third"])
def test_a_later_step_over_its_120_s_ends_the_run(slow_step):
    needs = [1.0, 1.0, 1.0]
    needs[slow_step] = 121.0
    with pytest.raises(queue.Empty):
        driver.first_steps(_StubLearner(needs), _stub_prep())


def test_a_window_step_over_its_120_s_ends_the_run():
    loop = driver.StepLoop(_StubLearner([1.0, 121.0]))
    loop.step()
    with pytest.raises(queue.Empty):
        loop.step()


def test_a_first_step_over_its_own_limit_ends_the_run_too():
    with pytest.raises(queue.Empty):
        driver.first_steps(_StubLearner([601.0, 1.0, 1.0]), _stub_prep())


def test_a_failed_check_step_is_what_the_run_reports(checkout, monkeypatch):
    """The feeders have not started when one of the check's three steps
    raises: stopping them may not put another error in its place (a
    publish fault of the program read as "cannot join thread before it is
    started" on the chip; PERF.md section 7)."""
    checkout.shrink(batch=4, unroll=5, block=2)

    def failing(learner, prep):
        raise queue.Empty

    monkeypatch.setattr(driver, "first_steps", failing)
    with pytest.raises(queue.Empty):
        checkout.run("breakout_b256_feed_sat")
