"""`correct` has to come out false when the timed path is broken, and for
the control. Each fault drives a whole run (the look for a chip stood in
for, the rest as on the chip) at a size a CPU holds, under the limits the
cells are committed with; the control is the reference in lower precision put in
the program's place, as `calibrate.py` reads it on the chip."""

import pytest

from benchmark import check, driver

CELL = "breakout_b256_feed_sat"
DMLAB = "dmlab30_t100_b64_feed_sat"
ELEM = "grad_elem_gap.core.median_leaf"  # breakout's; DMLab holds `.heads`


def _state_unchanged(monkeypatch):
    """A step that returns its state as it got it (the logs are real)."""
    from torched_impala_tpu.runtime.learner import Learner

    real = Learner._train_step_impl

    def broken(self, params, opt_state, popart_state, *batch):
        _, _, _, logs = real(self, params, opt_state, popart_state, *batch)
        return params, opt_state, popart_state, logs

    monkeypatch.setattr(Learner, "_train_step_impl", broken)


def _half_the_batch(monkeypatch):
    """Half of the batch's rows left out of the loss and the gradient."""
    from torched_impala_tpu.runtime.learner import Learner

    real = Learner._compute_grads

    def broken(self, params, popart_state, obs, first, actions, logits,
               rewards, cont, tasks, agent_state, **kw):
        import jax

        half = tasks.shape[0] // 2
        return real(
            self, params, popart_state,
            *(x[:, :half] for x in (obs, first, actions, logits, rewards, cont)),
            tasks[:half], jax.tree.map(lambda s: s[:half], agent_state), **kw,
        )

    monkeypatch.setattr(Learner, "_compute_grads", broken)


def _popart_unchanged(monkeypatch):
    """PopArt's statistics returned as they came; everything else real."""
    from torched_impala_tpu.runtime.learner import Learner

    real = Learner._train_step_impl

    def broken(self, params, opt_state, popart_state, *batch):
        params, opt_state, _, logs = real(
            self, params, opt_state, popart_state, *batch
        )
        return params, opt_state, popart_state, logs

    monkeypatch.setattr(Learner, "_train_step_impl", broken)


def _wrong_sign(monkeypatch):
    """The gradient handed to the optimizer with its sign flipped: same
    second moments, same norms, every step taken the wrong way."""
    from torched_impala_tpu.runtime.learner import Learner

    real = Learner._compute_grads

    def broken(self, *args, **kw):
        import jax

        grads, logs, new_popart = real(self, *args, **kw)
        return jax.tree.map(lambda g: -g, grads), logs, new_popart

    monkeypatch.setattr(Learner, "_compute_grads", broken)


@pytest.mark.parametrize(
    "cell_name,plant,failing",
    [
        (
            CELL,
            _state_unchanged,
            {"grad_norm_gap", "delta_norm_gap", ELEM, "update_wrong_way"},
        ),
        # at the chip's size the summed loss gives it away too (PERF.md);
        # on 48 frames the three terms can cancel in both alike
        (CELL, _half_the_batch, {ELEM}),
        # blind to a sign: the norms and the second moments read as sound
        (CELL, _wrong_sign, {"update_wrong_way"}),
        (DMLAB, _popart_unchanged, {"popart_gap"}),
    ],
    ids=["state_unchanged", "half_the_batch", "wrong_sign", "popart_unchanged"],
)
def test_a_broken_timed_path_is_not_correct(
    checkout, monkeypatch, cell_name, plant, failing
):
    checkout.shrink(batch=8, unroll=6, block=4)
    plant(monkeypatch)
    rc, result, err = checkout.run(cell_name)
    assert rc == 0, err
    assert result["correct"] is False
    over = {
        k for k, row in result["checks"].items() if not row["value"] <= row["limit"]
    }
    assert over and failing <= over
    if plant is _state_unchanged:
        # nothing moved: both norms read 1 by the measure
        assert result["checks"]["grad_norm_gap"]["value"] == pytest.approx(1.0)
        assert result["checks"]["delta_norm_gap"]["value"] == pytest.approx(1.0)
        assert result["checks"]["update_wrong_way"]["value"] == pytest.approx(1.0)
    if plant is _wrong_sign:
        assert result["checks"]["update_wrong_way"]["value"] > 0.99
        assert result["checks"][ELEM]["value"] < 0.04
    if plant is _popart_unchanged:
        assert result["checks"]["popart_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("cell_name", [CELL, DMLAB])
def test_the_program_as_it_is_comes_out_correct(checkout, cell_name):
    """The same run with nothing planted, under the same committed limits:
    the faults above are what turns `correct` false, not the small size."""
    checkout.shrink(batch=8, unroll=6, block=4)
    rc, result, err = checkout.run(cell_name)
    assert rc == 0, err
    assert result["correct"] is True, result["checks"]


@pytest.mark.parametrize("cell_name", [CELL, DMLAB])
def test_the_control_is_not_correct(checkout, cell_name):
    """Each part of the network one precision below what the configuration
    states (torso in 8-bit floats, core in bfloat16), against the float32
    reference, under the cell's committed limits. The core lowered alone,
    beside a float32 torso, reads like a sound run (whose core is fed by a
    bfloat16 torso) and passes: PERF.md section 2 says so, and this test
    holds the statement."""
    checkout.shrink(batch=8, unroll=6, block=4)
    spec = driver.Spec(checkout.root)
    prep = driver.prepare(spec, spec.cell(cell_name), 5)
    batches = driver.check_batches(prep)
    limits = spec.find("limits", cell_name)
    want = check.reference_record(prep, batches)
    decay = prep.config["optimizer"]["rmsprop_decay"]

    def verdict_of(record):
        numbers = check.compare(
            check.as_program_record(record, decay), want, decay,
            prep.net.leaf_groups,
        )
        return check.verdict(numbers["numbers"], limits)

    for which, comes_out in (("control", False), ("control_core", True)):
        dtypes = check.control_dtypes(prep.net, prep.config, which)
        correct, table = verdict_of(
            check.reference_record(prep, batches, dtypes=dtypes)
        )
        assert correct is comes_out, (which, table)
    # and the reference against itself is correct, with every gap at nought
    correct, table = verdict_of(want)
    assert correct is True
    assert max(row["value"] for row in table.values()) < 1e-6


def test_a_verdict_needs_every_number_at_or_under_its_limit():
    limits = {"a": 1.0, "b": 0.5}
    assert check.verdict({"a": 1.0, "b": 0.1, "unheld": 9.0}, limits)[0] is True
    assert check.verdict({"a": 1.1, "b": 0.1}, limits)[0] is False
    assert check.verdict({"a": float("nan"), "b": 0.1}, limits)[0] is False
    assert check.verdict({"a": 0.1}, limits)[0] is False  # a number missing
    assert check.verdict({"a": 0.1}, {})[0] is False  # nothing compared
