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

Host memory. A record holds, by leaf name, only what `compare` reads
element by element: the first step's second moments or gradient (float32)
and which way that step moved every element (one byte); the three steps'
change is kept as its norm by leaf. For a network of P parameters that is
5 P bytes a side, through the window and after it, and every tree of the
device is read one leaf at a time (`step_signs`, `change_norms`), as
`compare` goes through the leaves one at a time: about twice the largest
leaf in float64 beside the records. Before PR 33 the two records held eight
float32 trees and `compare` built five float64 ones beside them, 72 P bytes:
a network of 0.4 billion parameters ended on the host's 40 GiB.
"""

from __future__ import annotations

import collections.abc
import math

import numpy as np

NEGLIGIBLE_GRADIENT = 1e-3  # of the median leaf's norm


def leaves(tree) -> dict:
    """{leaf name: leaf} of a tree, in the tree's own order."""
    import jax

    return {
        jax.tree_util.keystr(path): leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
    }


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def _host_pairs(start: dict, after: dict):
    """(name, host leaf of `start`, host leaf of `after`), one pair of
    leaves on the host at a time; both are {name: device array}."""
    from benchmark import program

    if start.keys() != after.keys():
        raise ValueError(f"leaves differ: {sorted(set(start) ^ set(after))}")
    for name, leaf in start.items():
        yield name, program.host(leaf), program.host(after[name])


def step_signs(start: dict, after: dict) -> dict:
    """By leaf, which way a step moved every element: the sign of
    `after - start` as one byte (-1, 0, 1; a NaN reads 0)."""
    return {
        name: np.greater(b, a).view(np.int8) - np.less(b, a).view(np.int8)
        for name, a, b in _host_pairs(start, after)
    }


def change_norms(start: dict, after: dict) -> dict:
    """By leaf, the norm of `after - start`, worked out in float64."""
    norms = {}
    for name, a, b in _host_pairs(start, after):
        change = np.array(b, np.float64)
        change -= a  # float32 to float64 is exact: float64(b) - float64(a)
        norms[name] = _norm(change)
    return norms


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


def shape_gap(magnitude, grad, floor: float) -> float:
    """One leaf: the distance between the program's element magnitudes
    (float64; overwritten) and the reference's gradient, each scaled to
    unit norm; a leaf whose reference norm is under `floor` counts in that
    proportion. All zeros read 1."""
    m = magnitude
    r = np.array(grad, np.float64)
    np.abs(r, out=r)
    nm, nr = np.linalg.norm(m), np.linalg.norm(r)
    if nm > 0:
        m /= nm
    if nr > 0:
        r /= nr
    m -= r
    return float(np.linalg.norm(m) * nr / max(nr, floor, 1e-30))


def wrong_way(moved_prog, moved_ref, grad) -> float:
    """One leaf: the share of the reference's first gradient's energy (sum
    of squares) that lies on elements which the program moved against the
    reference, or not at all; `moved_*` are `step_signs` of the first step."""
    energy = np.array(grad, np.float64)
    np.square(energy, out=energy)
    total = np.sum(energy)
    energy *= moved_prog * moved_ref <= 0
    return float(np.sum(energy) / max(total, 1e-300))


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
    `leaf_groups` is the network file's. One leaf at a time: what is built
    of a leaf is reduced to that leaf's scalars and dropped.

    program:   losses [3]; by leaf name nu1 (second moments after step 1),
               moved1 (`step_signs` of step 1) and delta3 (`change_norms`
               of the three steps); popart0 and popart1 (PopArt's
               statistics at the start and after step 1; None with one task).
    reference: the same with grads1 (clipped) for nu1, and loss_scales."""
    numbers, where = {}, {}
    for i, (lp, lr, scale) in enumerate(
        zip(program["losses"], reference["losses"], reference["loss_scales"])
    ):
        numbers[f"loss_gap_step{i + 1}"] = abs(lp - lr) / max(scale, 1e-30)
    grads = reference["grads1"]
    g_ref = {name: _norm(g) for name, g in grads.items()}
    g_floor = float(np.median(list(g_ref.values())))
    moving = [k for k, v in g_ref.items() if v >= NEGLIGIBLE_GRADIENT * g_floor]
    g_prog, elem, wrong = {}, {}, {}
    for name, g in grads.items():
        # every element's magnitude, from RMSProp's second moments after a step
        magnitude = np.array(program["nu1"][name], np.float64)
        np.maximum(magnitude, 0.0, out=magnitude)
        magnitude /= 1.0 - rmsprop_decay
        np.sqrt(magnitude, out=magnitude)
        g_prog[name] = _norm(magnitude)
        elem[name] = shape_gap(magnitude, g, g_floor)
        del magnitude  # before the next arrays of this leaf's size
        wrong[name] = wrong_way(
            program["moved1"][name], reference["moved1"][name], g
        )
    per_leaf = {
        "grad_norm_gap": (leaf_gaps(g_prog, g_ref), list(g_ref)),
        "grad_elem_gap": (elem, list(g_ref)),
        "delta_norm_gap": (
            leaf_gaps(program["delta3"], reference["delta3"]),
            moving,
        ),
        "update_wrong_way": (wrong, moving),
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
    if dtypes is None:
        dtypes = ("float32",) * len(net.stated_dtypes(config))
    dtypes = tuple(jnp.dtype(d) for d in dtypes)
    hp = ref.hyper_params(config)
    params = prep.weights
    nu, popart = ref.init_state(params, prep.popart)
    block = int(config["reference_block_rows"])

    def named(tree) -> dict:  # the reference's tree by the program's leaf names
        return leaves(net.to_program_params(tree))

    start = named(prep.weights)
    record = {"losses": [], "loss_scales": [], "popart0": prep.popart}
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
            record["grads1"] = program.host(named(out.grads))
            record["grad_norm_unclipped"] = out.grad_norm_unclipped
            record["moved1"] = step_signs(start, named(params))
            record["popart1"] = popart and {
                k: np.asarray(v) for k, v in popart.items()
            }
        del out  # its gradient leaves the device before the next step's
    record["delta3"] = change_norms(start, named(params))
    return record


class _SecondMoments(collections.abc.Mapping):
    """RMSProp's second moments after one step, (1 - decay) * g^2, worked
    out from the first gradient a leaf at a time as each is asked for."""

    def __init__(self, grads: dict, rmsprop_decay: float):
        self._grads, self._decay = grads, rmsprop_decay

    def __getitem__(self, name):
        return (1.0 - self._decay) * np.square(self._grads[name])

    def __iter__(self):
        return iter(self._grads)

    def __len__(self):
        return len(self._grads)


def as_program_record(ref_record: dict, rmsprop_decay: float) -> dict:
    """A reference record put in the program's place; it shares the
    reference record's leaves and copies none."""
    return {
        "losses": ref_record["losses"],
        "moved1": ref_record["moved1"],
        "delta3": ref_record["delta3"],
        "popart0": ref_record["popart0"],
        "popart1": ref_record["popart1"],
        "nu1": _SecondMoments(ref_record["grads1"], rmsprop_decay),
    }
