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
    file. Every size and hyper-parameter the file states is checked against
    what the program will run, so that the FLOP count and the reference are
    of the real model."""
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
        "torso": (m["torso"], exp.model),
        "torso_dtype": (m["torso_dtype"], exp.compute_dtype),
        "train_dtype": (m["train_dtype"], exp.train_dtype),
        "use_lstm": (m["use_lstm"], exp.use_lstm),
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
    if m["use_lstm"]:
        stated["lstm_size"] = (m["lstm_size"], exp.lstm_size)
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


def _check_net(config: dict, agent, learner_config) -> None:
    m, loss = config["model"], config["loss"]
    torso, lc = agent.net.torso, learner_config.loss
    stated = {
        "channel_sections": (
            tuple(m["channel_sections"]),
            tuple(torso.channel_sections),
        ),
        "blocks_per_section": (
            m["blocks_per_section"],
            torso.blocks_per_section,
        ),
        "fc_size": (m["fc_size"], torso.hidden_size),
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


def build_learner(config: dict, chips: int, weights: Any, popart=None):
    """The preset's learner with the benchmark's own weights, and PopArt
    statistics where the configuration has them, in it."""
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
    _check_net(config, agent, learner_config)
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

    state["params"] = jax.tree.map(jnp.copy, to_program_params(weights))
    if (popart is None) != (learner_config.popart is None):
        raise ConfigMismatch(f"{config['name']}: PopArt in one of file, program")
    if popart is not None:
        state["popart_state"] = {k: np.array(v) for k, v in popart.items()}
    learner.set_state(state)
    return learner, registry


def to_program_params(ref: dict) -> dict:
    """The reference's parameter tree in the program's leaf names (flax
    auto-names of `AtariDeepTorso`, `PallasLSTMCell`, the two heads)."""
    torso: dict = {}
    block = 0
    for i, sec in enumerate(ref["sections"]):
        torso[f"Conv_{i}"] = {
            "kernel": sec["conv"]["w"],
            "bias": sec["conv"]["b"],
        }
        for blk in sec["blocks"]:
            torso[f"ResidualBlock_{block}"] = {
                "Conv_0": {
                    "kernel": blk["conv1"]["w"],
                    "bias": blk["conv1"]["b"],
                },
                "Conv_1": {
                    "kernel": blk["conv2"]["w"],
                    "bias": blk["conv2"]["b"],
                },
            }
            block += 1
    torso["Dense_0"] = {"kernel": ref["fc"]["w"], "bias": ref["fc"]["b"]}
    out = {
        "torso": torso,
        "policy_head": {
            "kernel": ref["policy"]["w"],
            "bias": ref["policy"]["b"],
        },
        "value_head": {
            "kernel": ref["value"]["w"],
            "bias": ref["value"]["b"],
        },
    }
    if "lstm" in ref:
        hid = ref["lstm"]["wh"].shape[0]
        lstm = {}
        for j, gate in enumerate("ifgo"):
            cols = slice(j * hid, (j + 1) * hid)
            lstm[f"i{gate}"] = {"kernel": ref["lstm"]["wi"][:, cols]}
            lstm[f"h{gate}"] = {
                "kernel": ref["lstm"]["wh"][:, cols],
                "bias": ref["lstm"]["b"][cols],
            }
        out["lstm"] = lstm
    return {"params": out}


def leaf_groups(leaf_name: str) -> tuple:
    """The parts of the model a parameter leaf belongs to. As far as the
    configuration states precisions apart: the `torso` (its `torso_dtype`)
    or the `core` (recurrent core and heads, float32); within the core, the
    `lstm` (its gradient comes back through the whole unroll) or the
    `heads` (theirs does not)."""
    if "['torso']" in leaf_name:
        return ("torso",)
    return ("core", "lstm" if "['lstm']" in leaf_name else "heads")


def trajectory(unroll: dict):
    """One generated unroll as the program's `Trajectory`."""
    from torched_impala_tpu.runtime.types import Trajectory

    return Trajectory(
        obs=unroll["obs"],
        first=unroll["first"],
        actions=unroll["actions"],
        behaviour_logits=unroll["behaviour_logits"],
        rewards=unroll["rewards"],
        cont=unroll["cont"],
        agent_state=unroll["state"],
        task=int(unroll["task"]),
    )


def queue_closed_error():
    from torched_impala_tpu.runtime.types import QueueClosed

    return QueueClosed


def host(tree: Any) -> Any:
    """Owning host copies of a tree of device arrays."""
    import jax

    return jax.tree.map(lambda x: np.array(x, copy=True), tree)


def read_state(learner) -> dict:
    """Host copies of what the check compares: parameters, RMSProp's
    second moments (first link of optax's `rmsprop` chain) and PopArt's
    statistics, in the program's leaf names."""
    popart = learner.popart_state
    return {
        "params": host(learner.params),
        "nu": host(learner.opt_state[0].nu),
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
