"""The one traffic generator: unrolls for the learner's feed, from a seed.

A traffic mix is a data file `traffic/<name>.json` under one of the
benchmark's `paths`. Its parameters:

  feeders  number of feeder threads. Each offers its next unroll as soon
           as the learner's bounded queue takes the last: a closed loop at
           saturation, the only kind of load the learner's feed has.
  p_first  per-step probability that an observation starts an episode (the
           recurrent state is reset there; the step before it ends one).
  tasks    "single" (task 0), "uniform" over the configuration's tasks, or
           "zipf": task ids with probability proportional to 1/rank, the
           tasks' ranks a permutation fixed by the seed.

The same in every mix: a pool of `POOL_BATCHES` batches of distinct unrolls
(the output check's three steps see 3*B rows that all differ, and no batch
repeats the one before it), behaviour logits N(0,1) (so V-trace's clipping
is active), rewards N(0,1), and each unroll's recurrent state at its first
observation as the configuration's network file draws it (`draw_state`, the
generator's last draw): a flat tuple of arrays, each with a leading axis of
one row, which B unrolls concatenate on, as the program's own stacking does.

Every seed gives the same sizes and the same amount of work; only the
values and the order differ.
"""

from __future__ import annotations

import numpy as np

REQUIRED = ("feeders", "p_first", "tasks")
TASKS = ("single", "uniform", "zipf")
POOL_BATCHES = 3


def validate(mix: dict) -> None:
    missing = [k for k in REQUIRED if k not in mix]
    if missing:
        raise ValueError(f"traffic mix lacks {missing}")
    if mix["tasks"] not in TASKS:
        raise ValueError(f"tasks must be one of {TASKS}: {mix['tasks']!r}")
    if mix["feeders"] < 1 or not 0.0 <= mix["p_first"] <= 1.0:
        raise ValueError("feeders >= 1 and p_first a probability")


def make_pool(seed: int, config: dict, mix: dict, draw_state) -> list:
    """`POOL_BATCHES * B` unrolls as dicts of numpy arrays, time-major,
    T+1 observations each. Bulk draws, sliced into views per unroll.
    `draw_state(rng, n, config)` is the network file's."""
    validate(mix)
    m = config["model"]
    t, b = int(config["unroll_length"]), int(config["batch_size"])
    n = POOL_BATCHES * b
    a = int(m["num_actions"])
    rng = np.random.default_rng(seed)
    obs = rng.integers(
        0, 256, size=(n, t + 1, *m["obs_shape"]), dtype=np.uint8
    )
    first = rng.random((n, t + 1)) < mix["p_first"]
    actions = rng.integers(0, a, size=(n, t), dtype=np.int32)
    logits = rng.standard_normal((n, t, a), dtype=np.float32)
    rewards = rng.standard_normal((n, t), dtype=np.float32)
    # An episode that starts at t+1 ended at t.
    cont = 1.0 - first[:, 1:].astype(np.float32)
    k = int(m["num_tasks"])
    if mix["tasks"] == "uniform":
        tasks = rng.integers(0, k, size=n, dtype=np.int32)
    elif mix["tasks"] == "zipf":
        weight = 1.0 / (1.0 + rng.permutation(k))  # 1/rank of each task
        tasks = rng.choice(k, size=n, p=weight / weight.sum()).astype(np.int32)
    else:
        tasks = np.zeros(n, np.int32)
    state = draw_state(rng, n, config)
    pool = []
    for i in range(n):
        pool.append(
            {
                "obs": obs[i],
                "first": first[i],
                "actions": actions[i],
                "behaviour_logits": logits[i],
                "rewards": rewards[i],
                "cont": cont[i],
                "task": tasks[i],
                "state": tuple(s[i] for s in state),
            }
        )
    return pool


def feeder_orders(seed: int, mix: dict, pool_size: int) -> list:
    """One permutation of the pool for each feeder, all from the seed: a
    feeder cycles through its own, so batches mix the pool differently
    every time round."""
    rng = np.random.default_rng([seed, 1])
    return [rng.permutation(pool_size) for _ in range(int(mix["feeders"]))]


def stack(unrolls: list) -> dict:
    """B unrolls as one time-major batch `[T(+1), B, ...]`: the benchmark's
    own stacking, for the reference (the program stacks for itself)."""
    keys = ("obs", "first", "actions", "behaviour_logits", "rewards", "cont")
    out = {k: np.stack([u[k] for u in unrolls], axis=1) for k in keys}
    out["tasks"] = np.asarray([u["task"] for u in unrolls], np.int32)
    out["state"] = tuple(
        np.concatenate([u["state"][j] for u in unrolls], axis=0)
        for j in range(len(unrolls[0]["state"]))
    )
    return out
