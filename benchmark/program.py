"""Everything the benchmark knows about the program under test.

The system under test is built here exactly as `torched_impala_tpu/run.py`
builds it (preset -> `make_agent`, `make_optimizer`, `make_learner_config`
-> `Learner`), and this module is the only one that imports it. The rest of
the benchmark sees: `enqueue`, `step_once`, the telemetry timers, and the
learner's state as plain trees in the program's own leaf names.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any

import numpy as np


class ConfigMismatch(ValueError):
    """The configuration's file and the program's preset disagree."""


def experiment_config(config: dict):
    """The preset named by the configuration's file, with B and T from the
    file. Every hyper-parameter the file states is checked against what the
    program will run (the network's sizes: `_check_net`), so that the FLOP
    count and the reference are of the real model."""
    from torched_impala_tpu import configs

    if config["preset"] not in configs.REGISTRY:
        raise ConfigMismatch(f"no preset {config['preset']!r} in the program")
    exp = dataclasses.replace(
        configs.REGISTRY[config["preset"]],
        batch_size=int(config["batch_size"]),
        unroll_length=int(config["unroll_length"]),
    )
    m, loss, opt = config["model"], config["loss"], config["optimizer"]
    stated = {
        "obs_shape": (tuple(m["obs_shape"]), tuple(exp.obs_shape)),
        "obs_dtype": (m["obs_dtype"], exp.obs_dtype),
        "num_actions": (m["num_actions"], exp.num_actions),
        "num_tasks": (m["num_tasks"], exp.num_tasks),
        "train_dtype": (m["train_dtype"], exp.train_dtype),
        "discount": (loss["discount"], exp.discount),
        "vf_coef": (loss["vf_coef"], exp.vf_coef),
        "entropy_coef": (loss["entropy_coef"], exp.entropy_coef),
        "reduction": (loss["reduction"], exp.loss_reduction),
        "lr": (opt["lr"], exp.lr),
        "lr_anneal": (opt["lr_anneal"], exp.lr_anneal),
        "rmsprop_decay": (opt["rmsprop_decay"], exp.rmsprop_decay),
        "rmsprop_eps": (opt["rmsprop_eps"], exp.rmsprop_eps),
        "max_grad_norm": (opt["max_grad_norm"], exp.max_grad_norm),
        "total_env_frames": (opt["total_env_frames"], exp.total_env_frames),
    }
    if m["num_tasks"] > 1:
        stated["popart_step_size"] = (
            config["popart"]["step_size"],
            exp.popart_step_size,
        )
    wrong = {k: v for k, v in stated.items() if v[0] != v[1]}
    if wrong:
        raise ConfigMismatch(
            f"{config['name']}: file vs preset {config['preset']!r}: {wrong}"
        )
    return exp


def _check_net(net, config: dict, exp, agent, learner_config) -> None:
    """The network file's own pairs of (file, program), and the loss's."""
    loss, lc = config["loss"], learner_config.loss
    stated = {
        **net.stated(config, exp, agent.net),
        "clip_rho_threshold": (
            loss["clip_rho_threshold"],
            lc.clip_rho_threshold,
        ),
        "clip_c_threshold": (loss["clip_c_threshold"], lc.clip_c_threshold),
        "clip_pg_rho_threshold": (
            loss["clip_pg_rho_threshold"],
            lc.clip_pg_rho_threshold,
        ),
        "lambda": (loss["lambda"], lc.lambda_),
    }
    if learner_config.popart is not None:
        pa = learner_config.popart
        stated["sigma_min"] = (config["popart"]["sigma_min"], pa.sigma_min)
        stated["sigma_max"] = (config["popart"]["sigma_max"], pa.sigma_max)
    wrong = {k: v for k, v in stated.items() if v[0] != v[1]}
    if wrong:
        raise ConfigMismatch(f"{config['name']}: file vs program: {wrong}")


def make_registry():
    """A telemetry registry whose timers also keep a running total.

    The program's `EwmaTimer` keeps a moving average and a call count; a
    share of a window needs the sum. The subclass adds only that, through
    the `telemetry=` argument the `Learner` offers for isolating runs."""
    from torched_impala_tpu.telemetry.registry import EwmaTimer, Registry

    class SummingTimer(EwmaTimer):
        def __init__(self, registry, name, alpha=0.2):
            super().__init__(registry, name, alpha)
            self._sum_lock = threading.Lock()
            self.total_s = 0.0

        def observe(self, seconds: float) -> None:
            super().observe(seconds)
            with self._sum_lock:
                self.total_s += seconds

    class SummingRegistry(Registry):
        def timer(self, name: str, alpha: float = 0.2):
            return self._get(SummingTimer, name, alpha)

        def timer_totals(self) -> dict:
            """{timer name: (seconds observed so far, calls so far)}."""
            return {
                m.name: (m.total_s, m.calls)
                for m in self.metrics()
                if isinstance(m, SummingTimer)
            }

    return SummingRegistry()


def _check_tree(net, config: dict, ours, theirs) -> None:
    """The network file's tree in the program's leaf names has to be the
    program's own, leaf for leaf and shape for shape."""
    import jax

    def shapes(tree):
        return {
            jax.tree_util.keystr(path): tuple(leaf.shape)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
        }

    ours, theirs = shapes(ours), shapes(theirs)
    wrong = {
        k: (ours.get(k), theirs.get(k))
        for k in sorted(ours.keys() | theirs.keys())
        if ours.get(k) != theirs.get(k)
    }
    if wrong:
        raise ConfigMismatch(
            f"{config['name']}: network {net.__name__!r} against the program's "
            f"parameters, (file's, program's) shape by leaf: {wrong}"
        )


def build_learner(net, config: dict, chips: int, weights: Any, popart=None):
    """The preset's learner with the benchmark's own weights (the tree of
    the network file `net`), and PopArt statistics where the configuration
    has them, in it."""
    import jax

    from torched_impala_tpu import configs
    from torched_impala_tpu.runtime import Learner

    exp = experiment_config(config)
    mesh = None
    if chips > 1:
        from torched_impala_tpu.parallel import make_mesh

        mesh = make_mesh(num_data=chips)
    agent = configs.make_agent(exp, mesh=mesh)
    learner_config = configs.make_learner_config(exp)
    _check_net(net, config, exp, agent, learner_config)
    registry = make_registry()
    learner = Learner(
        agent=agent,
        optimizer=configs.make_optimizer(exp),
        config=learner_config,
        example_obs=configs.example_obs(exp),
        rng=jax.random.key(0),
        mesh=mesh,
        telemetry=registry,
    )
    state = learner.get_state()
    # Copies: the train step donates its parameters, and `set_state` may
    # hand it these very buffers.
    import jax.numpy as jnp

    params = net.to_program_params(weights)
    _check_tree(net, config, params, state["params"])
    state["params"] = jax.tree.map(jnp.copy, params)
    if (popart is None) != (learner_config.popart is None):
        raise ConfigMismatch(f"{config['name']}: PopArt in one of file, program")
    if popart is not None:
        state["popart_state"] = {k: np.array(v) for k, v in popart.items()}
    learner.set_state(state)
    return learner, registry


def trajectories(config: dict, pool: list) -> list:
    """The generated unrolls as the program's `Trajectory`s, each one's
    state (a flat tuple of arrays) in the type the program's network
    carries."""
    import jax

    from torched_impala_tpu import configs
    from torched_impala_tpu.runtime.types import Trajectory

    agent = configs.make_agent(experiment_config(config))
    carried = jax.tree.structure(agent.initial_state(1))
    return [
        Trajectory(
            obs=u["obs"],
            first=u["first"],
            actions=u["actions"],
            behaviour_logits=u["behaviour_logits"],
            rewards=u["rewards"],
            cont=u["cont"],
            agent_state=jax.tree.unflatten(carried, u["state"]),
            task=int(u["task"]),
        )
        for u in pool
    ]


def queue_closed_error():
    from torched_impala_tpu.runtime.types import QueueClosed

    return QueueClosed


def host(tree: Any) -> Any:
    """Owning host copies of a tree of device arrays (or of one). Each is
    read through a second handle on the array's buffer: a `jax.Array` keeps
    the host copy it was first read into for as long as it lives, and the
    reference's weights live to the end of the run."""
    import jax

    return jax.tree.map(
        lambda x: np.array(x.addressable_data(0), copy=True), tree
    )


def read_state(learner) -> dict:
    """What the check compares, in the program's leaf names: parameters and
    RMSProp's second moments (first link of optax's `rmsprop` chain) as
    they stand on the device, for the check to read a leaf at a time, and
    host copies of PopArt's statistics."""
    popart = learner.popart_state
    return {
        "params": learner.params,
        "nu": learner.opt_state[0].nu,
        "popart": (
            {"mu": np.array(popart.mu), "nu": np.array(popart.nu)}
            if popart != ()
            else None
        ),
    }


def step_memory(learner):
    """`memory_analysis()` of the train step as the learner compiled it
    (its AUTO-layout executable), in bytes, or None where it holds none
    (under a mesh, or before the first batch)."""
    compiled = getattr(learner, "_auto_compiled", None)
    analysis = compiled.memory_analysis() if compiled is not None else None
    if analysis is None:
        return None
    return {
        "temp_bytes": int(analysis.temp_size_in_bytes),
        "argument_bytes": int(analysis.argument_size_in_bytes),
        "output_bytes": int(analysis.output_size_in_bytes),
        "alias_bytes": int(analysis.alias_size_in_bytes),
    }


def configure_compile_cache() -> str:
    from torched_impala_tpu.utils.compile_cache import (
        configure_compile_cache as configure,
    )

    return configure()


def release(learner) -> None:
    """Stop the learner's threads; the caller then drops its reference so
    that the device state is freed before the reference runs."""
    learner.stop()
    thread = getattr(learner, "_batcher_thread", None)
    if thread is not None:
        thread.join(timeout=30)
