"""What decides `correct`: the learner's first three steps, as the timed
object took them on the timed feed at the timed sizes, against the plain
reference on the same rows from the same weights.

Numbers compared (each has its own limit in `limits/<cell>.json`):

  loss_gap_step1..3  |program's loss - reference's| over the magnitude of
                     the reference's loss: |pg| + vf_coef |baseline| +
                     entropy_coef |entropy| (the three terms have either
                     sign, and their sum passes through zero).
  grad_norm_gap      the first gradient as the optimizer gets it (after
                     clipping), worked out from RMSProp's second moments
                     after one step, `sqrt(sum(nu) / (1 - decay))` by leaf:
                     the gap between the program's norm and the
                     reference's, over the reference's norm of that leaf or
                     of the median leaf, whichever is larger; worst leaf.
  delta_norm_gap     the same measure on the norm of each leaf's change
                     over the three steps. Leaves whose first gradient in
                     the reference is under a thousandth of the median
                     leaf's move by round-off alone under RMSProp and are
                     left out, by that rule and not by name.

  grad_elem_gap      the first gradient's shape, element by element:
                     RMSProp's second moments after one step give every
                     element's magnitude, `sqrt(nu / (1 - decay))`; by leaf,
                     the norm of the difference between the program's
                     magnitudes and the reference's, each divided by its own
                     norm of that leaf, weighted down where the reference's
                     norm is under the median leaf's; worst leaf. Rounding
                     errors cancel in a norm and in a summed loss, so those
                     barely move when the arithmetic loses bits; they do not
                     cancel here. Each leaf's scale is taken out because the
                     clip by global norm ties every leaf's scale to the
                     largest, noisiest leaves (a bfloat16 torso kernel): one
                     sound seed in 57 read 0.24 on every core leaf alike that
                     way (PERF.md section 2); `grad_norm_gap` holds the scale.

  update_wrong_way   the direction of the first update, element by element:
                     by leaf, the share of the reference's first gradient's
                     energy (sum of squares) on elements that the program's
                     first step moved against the reference's first step, or
                     not at all; worst leaf. The only number that sees a
                     sign: RMSProp's second moments and a norm are blind to
                     it. A step taken the wrong way, or not taken, reads 1.
  popart_gap         where the configuration has PopArt: the change of its
                     statistics over the first step, from the start state
                     that `reference.init_popart` draws (not the identity);
                     the norm over the tasks of (the program's change - the
                     reference's) over the norm of the reference's change,
                     the larger of mu's and nu's. Statistics left unchanged
                     read 1. (After the first step the parameters part, as
                     RMSProp's sign-like steps make them, and with them the
                     value targets: PERF.md section 2.)

The per-leaf numbers are also given by the part of the model whose precision the
configuration states apart: `.torso` (bfloat16 in both presets) and `.core`
(recurrent core and heads, float32), and within the core by `.lstm` and
`.heads`: the LSTM's gradient comes back through the whole unroll, which at
T=100 amplifies the torso's rounding on some seeds (PERF.md section 2); the
heads' does not. `.median_leaf`, of the whole model or of a part, is the
steady reading where the worst leaf is one odd leaf's noise.
"""

from __future__ import annotations

import math

import numpy as np

NEGLIGIBLE_GRADIENT = 1e-3  # of the median leaf's norm


def _norms(tree) -> dict:
    import jax

    return {
        jax.tree_util.keystr(path): float(
            np.sqrt(np.sum(np.square(np.asarray(leaf, np.float64))))
        )
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def _diff(a, b):
    import jax

    return jax.tree.map(
        lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64), a, b
    )


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """|prog - ref| / max(ref, median ref) for every leaf."""
    if prog.keys() != ref.keys():
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))}")
    floor = float(np.median(list(ref.values())))
    return {
        name: abs(prog[name] - r) / max(r, floor, 1e-30)
        for name, r in ref.items()
    }


def _worst(gaps: dict, keep) -> tuple:
    """(largest gap, its leaf) over the leaves in `keep`; NaN wins."""
    worst, where = 0.0, ""
    for name in keep:
        if not gaps[name] <= worst:
            worst, where = gaps[name], name
    return worst, where


def shape_gaps(magnitude, grads, floor: float) -> dict:
    """By leaf, the distance between the program's element magnitudes and
    the reference's, each scaled to unit norm; a leaf whose reference norm
    is under `floor` counts in that proportion. All zeros read 1."""
    import jax

    def gap(m, g):
        r = np.abs(np.asarray(g, np.float64))
        nm, nr = np.linalg.norm(m), np.linalg.norm(r)
        unit_m = m / nm if nm > 0 else m
        unit_r = r / nr if nr > 0 else r
        return float(np.linalg.norm(unit_m - unit_r) * nr / max(nr, floor, 1e-30))

    gaps = jax.tree.map(gap, magnitude, grads)
    return {
        jax.tree_util.keystr(path): v
        for path, v in jax.tree_util.tree_leaves_with_path(gaps)
    }


def wrong_way(d_prog, d_ref, grads) -> dict:
    """By leaf, the share of the reference's first gradient's energy (sum
    of squares) that lies on elements which the program moved against the
    reference, or not at all."""
    import jax

    def share(dp, dr, g):
        energy = np.square(np.asarray(g, np.float64))
        return float(np.sum(energy * (dp * dr <= 0.0)) / max(np.sum(energy), 1e-300))

    shares = jax.tree.map(share, d_prog, d_ref, grads)
    return {
        jax.tree_util.keystr(path): v
        for path, v in jax.tree_util.tree_leaves_with_path(shares)
    }


def popart_gap(program: dict, reference: dict) -> float:
    """The first step's change of PopArt's statistics: the norm of
    (program's - reference's) over the reference's, worse of mu and nu."""
    worst = 0.0
    for k in ("mu", "nu"):
        start = np.asarray(reference["popart0"][k], np.float64)
        want = np.asarray(reference["popart1"][k], np.float64) - start
        got = np.asarray(program["popart1"][k], np.float64) - start
        gap = np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)
        if not gap <= worst:
            worst = float(gap)
    return worst


def compare(
    program: dict, reference: dict, rmsprop_decay: float, leaf_groups
) -> dict:
    """The numbers, from two records in the program's leaf names;
    `leaf_groups` is the network file's.

    program:   losses [3], params0, params1 and nu1 (parameters and second
               moments after step 1), params3, popart0 and popart1 (PopArt's
               statistics at the start and after step 1; None with one task).
    reference: the same with grads1 (clipped) for nu1, and loss_scales."""
    import jax

    numbers, where = {}, {}
    for i, (lp, lr, scale) in enumerate(
        zip(program["losses"], reference["losses"], reference["loss_scales"])
    ):
        numbers[f"loss_gap_step{i + 1}"] = abs(lp - lr) / max(scale, 1e-30)
    g_ref = _norms(reference["grads1"])
    g_floor = float(np.median(list(g_ref.values())))
    moving = [k for k, v in g_ref.items() if v >= NEGLIGIBLE_GRADIENT * g_floor]
    # every element's magnitude, from RMSProp's second moments after a step
    magnitude = jax.tree.map(
        lambda n: np.sqrt(
            np.maximum(np.asarray(n, np.float64), 0.0) / (1.0 - rmsprop_decay)
        ),
        program["nu1"],
    )
    g_prog = _norms(magnitude)
    d_prog = _diff(program["params3"], program["params0"])
    d_ref = _diff(reference["params3"], reference["params0"])
    d_norm = _norms(d_ref)
    per_leaf = {
        "grad_norm_gap": (leaf_gaps(g_prog, g_ref), list(g_ref)),
        "grad_elem_gap": (
            shape_gaps(magnitude, reference["grads1"], g_floor),
            list(g_ref),
        ),
        "delta_norm_gap": (leaf_gaps(_norms(d_prog), d_norm), moving),
        "update_wrong_way": (
            wrong_way(
                _diff(program["params1"], program["params0"]),
                _diff(reference["params1"], reference["params0"]),
                reference["grads1"],
            ),
            moving,
        ),
    }
    if reference["popart0"] is not None:
        numbers["popart_gap"] = popart_gap(program, reference)
    for name, (gaps, keep) in per_leaf.items():
        numbers[name], where[name] = _worst(gaps, keep)
        numbers[f"{name}.median_leaf"] = float(
            np.median([gaps[k] for k in keep])
        )
        for group in sorted({g for k in keep for g in leaf_groups(k)}):
            members = [k for k in keep if group in leaf_groups(k)]
            numbers[f"{name}.{group}"], where[f"{name}.{group}"] = _worst(
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


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}): every number that has a limit
    is at or under it, and is a number."""
    table = {
        name: {"value": numbers.get(name, float("nan")), "limit": limit}
        for name, limit in limits.items()
    }
    ok = all(
        math.isfinite(row["value"]) and row["value"] <= row["limit"]
        for row in table.values()
    )
    return bool(ok) and bool(table), table


# The nearest precision below each one a configuration can state.
PRECISION_BELOW = {
    "float32": "bfloat16",
    "bfloat16": "float8_e4m3fn",
    "float16": "float8_e4m3fn",
}


def control_dtypes(net, config: dict, which: str) -> tuple:
    """What a control stores each part of the network in: one precision
    below what the configuration states for it (`net.stated_dtypes`) where
    `net.CONTROLS[which]` says so, the reference's float32 elsewhere."""
    return tuple(
        PRECISION_BELOW[d] if lower else "float32"
        for d, lower in zip(net.stated_dtypes(config), net.CONTROLS[which])
    )


def reference_record(prep, batches: list, rows=None, dtypes=None) -> dict:
    """Three reference steps from `prep`'s weights and PopArt statistics on
    the stacked host `batches`; the record in the program's leaf names.
    `rows` (a slice) plants the fault 'part of the batch left out' into the
    reference; `dtypes` (see `control_dtypes`) makes it a control: what
    each part of the network is stored in."""
    import jax.numpy as jnp

    from benchmark import program, reference as ref

    config, net = prep.config, prep.net
    to_program = net.to_program_params
    if dtypes is None:
        dtypes = ("float32",) * len(net.stated_dtypes(config))
    dtypes = tuple(jnp.dtype(d) for d in dtypes)
    hp = ref.hyper_params(config)
    params = prep.weights
    nu, popart = ref.init_state(params, prep.popart)
    block = int(config["reference_block_rows"])
    record = {
        "losses": [],
        "loss_scales": [],
        "params0": program.host(to_program(params)),
        "popart0": prep.popart,
    }
    network = (net.forward, net.sizes(config))
    for k, b in enumerate(batches):
        batch = ref.Batch(
            b["obs"], b["first"], b["actions"], b["behaviour_logits"],
            b["rewards"], b["cont"], b["tasks"], b["state"],
        )
        if rows is not None:
            batch = batch.rows(rows.start, rows.stop)
        out = ref.learner_step(
            network, params, nu, popart, batch, k, hp, block, dtypes=dtypes
        )
        params, nu, popart = out.params, out.nu, out.popart
        record["losses"].append(out.loss)
        record["loss_scales"].append(out.loss_scale)
        if k == 0:
            record["grads1"] = program.host(to_program(out.grads))
            record["grad_norm_unclipped"] = out.grad_norm_unclipped
            record["params1"] = program.host(to_program(params))
            record["popart1"] = popart and {
                k: np.asarray(v) for k, v in popart.items()
            }
    record["params3"] = program.host(to_program(params))
    return record


def as_program_record(ref_record: dict, rmsprop_decay: float) -> dict:
    """A reference record put in the program's place: RMSProp's second
    moments after one step are (1 - decay) * g^2."""
    import jax

    return {
        "losses": ref_record["losses"],
        "params0": ref_record["params0"],
        "params1": ref_record["params1"],
        "params3": ref_record["params3"],
        "popart0": ref_record["popart0"],
        "popart1": ref_record["popart1"],
        "nu1": jax.tree.map(
            lambda g: (1.0 - rmsprop_decay) * np.square(g), ref_record["grads1"]
        ),
    }
