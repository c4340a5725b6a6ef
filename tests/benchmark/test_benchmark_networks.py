"""The seam between the harness and a network's file: what moved behind it
reads as before (hashes and counts the parent commit gives), a network that
is missing or incomplete ends in `ConfigMismatch`, and the harness modules
outside `networks/` name no layer."""

import ast
import hashlib
import io
import os
import tokenize

import jax
import numpy as np
import pytest

from benchmark import driver, program, reference, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEED = 2**31 + 7
# What commit 576524e (the parent of the PR that cut the network out) gives
# on the CPU: the pool at B=2, T=3 (every array of every unroll, state
# last), the weights (at their full widths) and PopArt's start statistics.
PARENT = {
    "breakout_deep_lstm": {
        "mix": "feed_sat_ep100",
        "pool": "4bfa0c9a80a97bce22a1cac7178cc81bce5170acbde521d6fc38f91c3e0ed256",
        "weights": "722fd4226e76ab5870fcc96d5350882b4cbe8c8175168d40055bcc34c3eed771",
        "popart": None,
        "step_flops": 1586917801984.0,
        "mflop_per_frame": 310,
        "lstm_unroll_forward": {1: (5637144576.0, 13111296.0), 4: (1409286144.0, 4853760.0)},
    },
    "dmlab30_deep_lstm_popart": {
        "mix": "feed_sat_ep1000_tasks_uniform",
        "pool": "8d993c85135d48310c8f4c36105c056834a16152fc1bc872dbac2813ff420855",
        "weights": "b397740db0e1c3559d1f7287bf87ead253b57ad2eb2e20d717d542f333c15dc1",
        "popart": "9dac8f1c44ba1dbc8d37af3c05561e637ef1b018c93898b3b3fe8f520369ecb6",
        "step_flops": 1873254375424.0,
        "mflop_per_frame": 293,
        "lstm_unroll_forward": {1: (6777995264.0, 15339520.0), 4: (1694498816.0, 5410816.0)},
    },
}
POOL_KEYS = ("obs", "first", "actions", "behaviour_logits", "rewards", "cont", "task")


def digest(arrays):
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(np.asarray(a))
        h.update(str((a.dtype.str, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PARENT))
def test_pool_and_weights_are_the_parents_bit_for_bit(name):
    spec, want = driver.Spec(ROOT), PARENT[name]
    config = spec.config(name)
    net = spec.network(config)
    assert net.__name__ == driver.DEFAULT_NETWORK and "network" not in config
    tiny = dict(config, batch_size=2, unroll_length=3)
    pool = traffic.make_pool(
        SEED, tiny, spec.find("traffic", want["mix"]), net.draw_state
    )
    arrays = [u[k] for u in pool for k in POOL_KEYS]
    arrays += [x for u in pool for x in u["state"]]
    assert digest(arrays) == want["pool"]
    weights = net.init_params(SEED, config)
    assert digest(jax.tree.leaves(weights)) == want["weights"]
    popart = reference.init_popart(SEED, config)
    assert (popart and digest([popart["mu"], popart["nu"]])) == want["popart"]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_flops_and_bytes_through_the_network_file_are_the_parents(name):
    spec, want = driver.Spec(ROOT), PARENT[name]
    config = spec.config(name)
    net = spec.network(config)
    assert net.step_flops(config) == want["step_flops"]
    frames = config["unroll_length"] * config["batch_size"]
    assert round(net.step_flops(config) / frames / 1e6) == want["mflop_per_frame"]
    for chips, counts in want["lstm_unroll_forward"].items():
        assert net.OPS_AND_BYTES["lstm_unroll_forward"](config, chips) == counts


# ---- a network that is not there, or not whole ---------------------------


def _own_network(checkout, edit):
    """The first cell's configuration on a network file of the test's own:
    the repo's, with `edit` applied to its text."""
    cell = checkout.doc["workloads"][0]
    entry = next(c for c in checkout.doc["configs"] if c["name"] == cell["config"])
    checkout.write(entry["file"], dict(checkout.read(entry["file"]), network="mine"))
    with open(checkout.path("benchmark/networks/impala_resnet_lstm.py")) as f:
        text = f.read()
    assert edit[0] in text
    with open(checkout.path("benchmark/networks/mine.py"), "w") as f:
        f.write(text.replace(*edit))
    return cell["name"]


def _no_result(checkout, cell, capsys):
    from benchmark import run

    with pytest.raises(program.ConfigMismatch) as raised:
        run.main(
            ["--workload", cell, "--seed", "5", "--seconds", "0.2"],
            probe=lambda chips: {"platform": "tpu", "kind": "TPU v5 lite", "count": chips},
            root=checkout.root,
        )
    assert capsys.readouterr().out == ""
    return str(raised.value)


def test_a_network_that_no_paths_directory_has(checkout, capsys):
    checkout.shrink(batch=4, unroll=5, block=2)
    cell = _own_network(checkout, ("STATE_STD", "STATE_STD"))
    os.remove(checkout.path("benchmark/networks/mine.py"))
    message = _no_result(checkout, cell, capsys)
    assert "'mine'" in message and "networks/mine.py" in message


def test_a_network_file_that_lacks_a_function(checkout, capsys):
    checkout.shrink(batch=4, unroll=5, block=2)
    cell = _own_network(checkout, ("def draw_state(", "def draw_no_state("))
    message = _no_result(checkout, cell, capsys)
    assert "'mine'" in message and "draw_state" in message


def test_a_network_file_that_needs_a_key_the_configuration_lacks(checkout, capsys):
    checkout.shrink(batch=4, unroll=5, block=2)
    cell = _own_network(checkout, ('"lstm_size",\n)', '"lstm_size", "depth",\n)'))
    message = _no_result(checkout, cell, capsys)
    assert "'mine'" in message and "depth" in message


def test_a_network_file_whose_tree_is_not_the_programs(checkout, capsys):
    checkout.shrink(batch=4, unroll=5, block=2)
    cell = _own_network(checkout, ('torso["Dense_0"]', 'torso["Dense_9"]'))
    message = _no_result(checkout, cell, capsys)
    assert "'mine'" in message
    assert "Dense_0" in message and "Dense_9" in message


# ---- the harness names no layer -------------------------------------------

HARNESS = (
    "driver", "check", "readers", "traffic", "calibrate", "run", "reference",
    "flops", "program", "stats", "trace",
)
LAYER_NAMES = (
    "channel_sections", "blocks_per_section", "fc_size", "lstm_size",
    "use_lstm", "Conv_", "ResidualBlock",
)


def _code_without_comments_and_docstrings(path):
    with open(path) as f:
        source = f.read()
    docstrings = set()
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef)):
            body = node.body
            if body and isinstance(body[0], ast.Expr) and isinstance(
                getattr(body[0], "value", None), ast.Constant
            ) and isinstance(body[0].value.value, str):
                docstrings.add(body[0].value.lineno)
    kept = []
    for tok in tokenize.generate_tokens(io.StringIO(source).readline):
        if tok.type == tokenize.COMMENT:
            continue
        if tok.type == tokenize.STRING and tok.start[0] in docstrings:
            continue
        kept.append(tok.string)
    return " ".join(kept)


@pytest.mark.parametrize("module", HARNESS)
def test_the_harness_outside_networks_names_no_layer(module):
    code = _code_without_comments_and_docstrings(
        os.path.join(ROOT, "benchmark", module + ".py")
    )
    assert [name for name in LAYER_NAMES if name in code] == []
    # and the file that received what they lost does name them
    net = os.path.join(ROOT, "benchmark", "networks", driver.DEFAULT_NETWORK + ".py")
    assert all(name in _code_without_comments_and_docstrings(net) for name in LAYER_NAMES)


# ---- the `zipf` mode of a mix's task ids ----------------------------------


def test_zipf_task_ids_follow_one_over_rank_with_ranks_from_the_seed():
    spec = driver.Spec(ROOT)
    config = dict(
        spec.config("dmlab30_deep_lstm_popart"), batch_size=2000, unroll_length=1
    )
    config["model"] = dict(config["model"], obs_shape=[2, 2, 1])
    mix = dict(spec.find("traffic", "feed_sat_ep1000_tasks_uniform"), tasks="zipf")
    no_state = lambda rng, n, config: ()  # noqa: E731

    def task_ids(seed):
        pool = traffic.make_pool(seed, config, mix, no_state)
        return np.array([u["task"] for u in pool])

    ids = task_ids(11)
    assert ids.dtype == np.int32 and 0 <= ids.min() and ids.max() < 30
    assert np.array_equal(ids, task_ids(11))
    counts = np.sort(np.bincount(ids, minlength=30))[::-1]
    share = 1.0 / np.arange(1, 31)
    share /= share.sum()
    # 6000 draws: the most frequent task has a quarter of them, and every
    # rank's count is its share to within four standard deviations
    sd = np.sqrt(len(ids) * share * (1 - share))
    assert np.all(np.abs(counts - len(ids) * share) < 4 * sd + 1)
    # which task has which rank is the seed's to say
    assert np.bincount(ids, minlength=30).argmax() != np.bincount(
        task_ids(12), minlength=30
    ).argmax() or np.bincount(task_ids(13), minlength=30).argmax() != np.bincount(
        ids, minlength=30
    ).argmax()
    traffic.validate(mix)
    with pytest.raises(ValueError, match="tasks must be one of"):
        traffic.validate(dict(mix, tasks="Zipf"))
