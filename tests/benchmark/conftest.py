"""Helpers of the benchmark's tests: a checkout in `tmp_path` that holds
`BENCHMARK.json` and the benchmark's data files cut to a size a CPU can
run, and a stand-in for the look for a chip (the test steers it; the
harness has no option for it)."""

import io
import json
import os
import shutil
from contextlib import redirect_stderr, redirect_stdout

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA_DIRS = ("configs", "networks", "traffic", "metrics", "limits")


def fake_probe(chips):
    """Says what a v5e host would; nothing is measured under that name."""
    return {"platform": "tpu", "kind": "TPU v5 lite", "count": chips}


@pytest.fixture
def network_of():
    """`network_of(config)`: the network file of the repo's own benchmark
    that `config` names, as the harness loads it."""
    from benchmark import driver

    return driver.Spec(ROOT).network


@pytest.fixture
def tiny_config():
    """`tiny_config(**model)`: a configuration of `impala_resnet_lstm` at
    sizes a test states."""
    base = {
        "obs_shape": [8, 8, 1], "num_actions": 2, "num_tasks": 1,
        "torso": "deep_resnet", "torso_dtype": "bfloat16",
        "train_dtype": "float32", "channel_sections": [4],
        "blocks_per_section": 1, "fc_size": 8, "use_lstm": True,
        "lstm_size": 8,
    }
    return lambda **model: {"name": "tiny", "model": dict(base, **model)}


class TinyCheckout:
    def __init__(self, root):
        self.root = str(root)
        for d in DATA_DIRS:
            shutil.copytree(
                os.path.join(ROOT, "benchmark", d),
                os.path.join(self.root, "benchmark", d),
            )
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), self.root)
        self.doc = self.read("BENCHMARK.json")

    def path(self, rel):
        return os.path.join(self.root, rel)

    def read(self, rel):
        with open(self.path(rel)) as f:
            return json.load(f)

    def write(self, rel, obj):
        os.makedirs(os.path.dirname(self.path(rel)), exist_ok=True)
        with open(self.path(rel), "w") as f:
            json.dump(obj, f)

    def shrink(self, batch, unroll, block):
        """Every configuration at the published widths, B and T cut."""
        for c in self.doc["configs"]:
            cfg = self.read(c["file"])
            cfg.update(
                batch_size=batch, unroll_length=unroll, reference_block_rows=block
            )
            self.write(c["file"], cfg)

    def loosen(self, limit=1e9):
        for w in self.doc["workloads"]:
            rel = f"benchmark/limits/{w['name']}.json"
            self.write(rel, {k: limit for k in self.read(rel)})

    def run(self, workload, trace=0, seconds=0.5, seed=3_000_000_019):
        """`run.main` in this checkout: (exit code, last stdout line as a
        dict or None, stderr)."""
        from benchmark import run

        out, err = io.StringIO(), io.StringIO()
        with redirect_stdout(out), redirect_stderr(err):
            rc = run.main(
                ["--workload", workload, "--seed", str(seed),
                 "--seconds", str(seconds), "--trace", str(trace)],
                probe=fake_probe,
                root=self.root,
            )
        lines = [x for x in out.getvalue().splitlines() if x.strip()]
        result = json.loads(lines[-1]) if lines and rc == 0 else None
        return rc, result, err.getvalue()


@pytest.fixture
def checkout(tmp_path):
    return TinyCheckout(tmp_path)
