#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Runs the actor-learner main path ONCE on a TPU, in this one process,
through the entry points a user calls, and checks what comes out by the
repo's own references. Every phase prints one JSON object on its own
line; the LAST line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit code is 0 only if every phase passed. Without a TPU (or
with JAX_PLATFORMS=cpu) the script exits non-zero with "ok": false — a
CPU run is never reported as a pass.

Default (one chip), in order:
  device   jax.devices() must be a TPU; versions and compile cache.
  kernels  every Pallas kernel COMPILED (tpu_custom_call in the program
           text) at preset shapes, against the reference the repo holds.
  train    run.main on --config breakout --fake-envs: T=20, B=32,
           84x84x4 uint8, deep ResNet (bf16) + LSTM 256, process actors;
           only the host-side fleet is cut (printed).
  anakin   run.main on --config pixels_anakin (the on-device runtime).
  serve    a PolicyServer over the breakout agent answers batched waves.
  children every process started on the way (env workers, the fork server,
           the resource tracker) is stopped and gone before the last line.

`--chips 4` runs ONLY the data-parallel phase and what it is compared
with: the procgen preset's learner step on a 4-device `data` mesh
against the same seeded batches through a one-device learner.

Weights and data are random, made from SEED. No child process touches
the chip: env workers are numpy-only, and the train phase shows it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0  # every weight and every batch below is made from it

# Fleet cut for the train phase (the only cut): 4 actor threads x 8 envs
# = 32 envs fill the B=32 learner batch once per 20 env steps; the
# preset's own fleet is 256 actors for a 200M-frame run.
TRAIN_ARGS = (
    "--config", "breakout", "--fake-envs",
    "--num-actors", "4", "--envs-per-actor", "8",
    "--total-steps", "30", "--log-every", "5",
    "--logger", "null", "--stall-timeout", "600",
)
ANAKIN_ARGS = (
    "--config", "pixels_anakin", "--total-steps", "6", "--log-every", "2",
    "--logger", "null",
)
# Four chips against one, on identical seeded batches (tests/
# test_parallel.py holds rtol 1e-4 at f32 on CPU):
# - step 0 starts from the same parameters, so its loss (a sum over the
#   batch, reduced across the four shards) and its gradient norm (after
#   the all-reduce) test the sharded program itself. The torso is bf16,
#   but each image's forward is the same arithmetic on either layout; only
#   the cross-shard sums change order. Measured on four v5e chips: loss
#   identical to the last bit. Held to 1e-4, the CPU test's own bound.
# - later steps are compared loosely. RMSProp's first updates are
#   sign-like (v ~ (1-decay) g^2, so lr*g/sqrt(v) ~ +-lr/sqrt(1-decay)
#   wherever |g| >> eps): a gradient component near zero that rounds to
#   the other side moves its weight by a full step. The runs therefore
#   part ways at the size of the step, not of the rounding: measured
#   1.3e-2, 9.2e-3, 5.0e-2 relative at steps 1-3. 0.25 says "the same
#   trajectory", and a wrong all-reduce (a missing or double-counted
#   shard) moves step 1 by O(1).
DP_STEP0_RTOL = 1e-4
DP_LATER_RTOL = 0.25


# Phases run with stdout redirected to stderr (the program's own prints
# land there); the JSON lines go to the real stdout, captured here.
_STDOUT = sys.stdout


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), file=_STDOUT, flush=True)


class CompileClock:
    """Sums JAX's own backend-compile durations and persistent-cache
    hits, so each phase can say how much of its wall time was compiling."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.compile_s += secs

    def _on_event(self, event: str, **_) -> None:
        if event.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1
        elif event.endswith("compilation_cache/cache_misses"):
            self.cache_misses += 1


def run_phase(name: str, fn, clock: CompileClock) -> bool:
    """Run one phase; print its JSON line; return whether it passed."""
    t0 = time.perf_counter()
    c0 = clock.compile_s
    out = {"phase": name}
    try:
        out.update(fn())
        out["ok"] = bool(out.get("ok", True))
    except Exception as e:  # boundary: report the failure, keep going
        out["ok"] = False
        out["error"] = f"{type(e).__name__}: {e}"[:2000]
        traceback.print_exc(file=sys.stderr)
    out["wall_s"] = round(time.perf_counter() - t0, 2)
    out["compile_s"] = round(clock.compile_s - c0, 2)
    emit(out)
    return out["ok"]


# ---- device ------------------------------------------------------------


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(jax.devices()),
    }


def phase_device(cache_dir: str, want_chips: int) -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    info = device_info()
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    out = {
        **info,
        "devices": [str(d) for d in jax.devices()],
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "compile_cache_dir": cache_dir,
        "compile_cache_from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
        ),
    }
    if info["platform"] != "tpu":
        out["error"] = (
            f"no TPU: jax.devices() reports platform {info['platform']!r} "
            f"({info['kind']}); this script never passes on a CPU"
        )
    elif info["count"] < want_chips:
        out["error"] = (
            f"--chips {want_chips} needs {want_chips} devices, "
            f"found {info['count']}"
        )
    out["ok"] = "error" not in out
    return out


# ---- kernels -----------------------------------------------------------


def _compiled_kernel(fn, *args):
    """AOT-compile `fn` for the chip, insist the Mosaic kernel is in the
    program (a kernel that gave way to a reference fails here), run it."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    if "tpu_custom_call" not in compiled.as_text():
        raise AssertionError("no tpu_custom_call in the compiled program")
    return jax.block_until_ready(compiled(*args))


def _max_err(got, want) -> float:
    import jax
    import numpy as np

    errs = [
        float(np.max(np.abs(np.asarray(a, np.float32)
                            - np.asarray(b, np.float32))))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))
    ]
    return max(errs)


# A float32 matmul on the MXU multiplies in bf16 passes: one pass at the
# default precision, for XLA's ops and for a Mosaic kernel's `jnp.dot`
# alike (measured on the v5e, PR 21: every f32 kernel that multiplies
# sits about 1e-2 to 2e-2 from its reference computed at 'highest' —
# the size of the bf16 variants' error). So such a kernel is compared
# with the reference AS THE CHIP RUNS IT (default precision), and held
# to a bf16-sized bound against the same reference at 'highest'.
VS_EXACT_ATOL = 5e-2


def _check(name, got, want, *, rtol, atol, results, exact=None) -> None:
    """allclose under the tolerance the repo's own test uses; records the
    largest absolute error either way. `exact`: the reference again at
    'highest' matmul precision, held to VS_EXACT_ATOL."""
    import jax
    import numpy as np

    err = _max_err(got, want)
    ok = all(
        np.allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=rtol, atol=atol,
        ) and bool(np.all(np.isfinite(np.asarray(a, np.float32))))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))
    )
    results[name] = {
        "ok": ok, "max_abs_err": err, "rtol": rtol, "atol": atol,
    }
    if exact is not None:
        err_exact = _max_err(got, exact)
        results[name]["max_abs_err_vs_highest"] = err_exact
        results[name]["ok"] = ok and err_exact <= VS_EXACT_ATOL


def phase_kernels(seed: int) -> dict:
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, os.path.join(HERE, "tests"))
    import attention_oracle

    from torched_impala_tpu.ops import vtrace as vtrace_lib
    from torched_impala_tpu.ops import conv_pallas, vtrace_pallas
    from torched_impala_tpu.ops.attention_pallas import windowed_attention
    from torched_impala_tpu.ops.losses import ImpalaLossConfig
    from torched_impala_tpu.ops.lstm_pallas import lstm_cell_fused

    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    results: dict = {}

    def normal(*shape, scale=1.0, dtype=f32):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    def both(ref, *args):
        """The reference as the chip runs it, and at 'highest'."""
        with jax.default_matmul_precision("highest"):
            exact = jax.jit(ref)(*args)
        return jax.jit(ref)(*args), exact

    # -- V-trace kernel vs the scan, breakout T=20 B=32 and B=1024 ------
    for T, B in ((20, 32), (20, 1024)):
        kw = dict(
            log_rhos=normal(T, B, scale=0.5),
            discounts=jnp.asarray(
                0.99 * (rng.random((T, B)) > 0.05), f32
            ),
            rewards=normal(T, B),
            values=normal(T, B),
            bootstrap_value=normal(B),
        )
        names = list(kw)
        got = _compiled_kernel(
            lambda *a: vtrace_pallas.vtrace_pallas(**dict(zip(names, a))),
            *kw.values(),
        )
        want = vtrace_lib.vtrace_scan(**kw)
        _check(f"vtrace_pallas[T={T},B={B}]", got, want,
               rtol=1e-5, atol=1e-5, results=results)  # test_pallas_vtrace

    # -- fused V-trace loss: kernel vs xla, value and grads -------------
    config = ImpalaLossConfig(fused_epilogue=True)
    for T, B, A in ((20, 32, 4), (20, 1024, 4)):
        args = (
            normal(T, B, A), normal(T, B, A), normal(T, B), normal(B),
            jnp.asarray(rng.integers(0, A, (T, B)), jnp.int32),
            normal(T, B),
            jnp.asarray(0.99 * (rng.random((T, B)) > 0.05), f32),
        )

        def loss(impl, logits, behaviour, values, boot, act, rew, disc):
            return vtrace_pallas.fused_vtrace_loss(
                target_logits=logits, behaviour_logits=behaviour,
                values=values, bootstrap_value=boot, actions=act,
                rewards=rew, discounts=disc, config=config,
                implementation=impl,
            ).total

        def vg(impl):
            return lambda *a: jax.value_and_grad(
                lambda *b: loss(impl, *b), argnums=(0, 2)
            )(*a)

        got = _compiled_kernel(vg("kernel"), *args)
        want = jax.jit(vg("xla"))(*args)
        # tests/test_feed_path.py: total rtol 1e-5, grads rtol 1e-4/atol
        # 1e-5; the total is a sum over T*B terms, hence relative.
        _check(f"fused_vtrace_loss[T={T},B={B}]", got, want,
               rtol=1e-4, atol=1e-5, results=results)

    # -- fused LSTM cell vs nn.OptimizedLSTMCell, fwd + VJP -------------
    H = 256
    for B, F in ((32, 256), (1024, 256)):
        cell = nn.OptimizedLSTMCell(H)
        x, h, c = normal(B, F), normal(B, H), normal(B, H)
        params = cell.init(jax.random.key(seed), (c, h), x)
        p = params["params"]
        gates = ("i", "f", "g", "o")
        wi = jnp.concatenate([p[f"i{g}"]["kernel"] for g in gates], -1)
        wh = jnp.concatenate([p[f"h{g}"]["kernel"] for g in gates], -1)
        b = normal(4 * H, scale=0.1)
        for j, g in enumerate(gates):
            p[f"h{g}"]["bias"] = b[j * H:(j + 1) * H]

        def fused(x, h, c, wi, wh, b):
            def f(*a):
                new_c, new_h = lstm_cell_fused(*a)
                return jnp.sum(jnp.sin(new_c)) + jnp.sum(jnp.sin(new_h))

            out = lstm_cell_fused(x, h, c, wi, wh, b)
            return out, jax.grad(f, argnums=(0, 1, 2))(x, h, c, wi, wh, b)

        def flax_ref(x, h, c):
            def f(x, h, c):
                (new_c, new_h), _ = cell.apply(params, (c, h), x)
                return jnp.sum(jnp.sin(new_c)) + jnp.sum(jnp.sin(new_h))

            (new_c, new_h), _ = cell.apply(params, (c, h), x)
            return (new_c, new_h), jax.grad(f, argnums=(0, 1, 2))(x, h, c)

        got = _compiled_kernel(fused, x, h, c, wi, wh, b)
        want, exact = both(flax_ref, x, h, c)
        # tests/test_pallas_lstm.py holds 1e-5 on CPU, where products
        # are exact f32. On the chip the kernel and the flax cell round
        # the same operands to bf16, but their sigmoid/tanh differ by an
        # ulp or so, and where that flips a bf16 rounding of an operand
        # of the backward matmuls the product moves by 2^-9 of it:
        # measured 1.9e-5 (B=32) and 2.1e-4 (B=1024, 1024-term sums).
        # 1e-3 is five times the larger reading and 1/10 of bf16 size.
        _check(f"lstm_cell_fused[B={B},F={F}]", got, want,
               rtol=1e-3, atol=1e-3, results=results, exact=exact)

    # -- fused residual block vs the nine-shift reference ---------------
    # The deep torso's three residual sections on 84x84 frames.
    for hw, ch in ((42, 16), (21, 32), (11, 32)):
        n = 8
        x = normal(n, hw, hw, ch)
        k1, k2 = (normal(3, 3, ch, ch, scale=0.1) for _ in range(2))
        b1, b2 = normal(ch, scale=0.1), normal(ch, scale=0.1)
        got = _compiled_kernel(
            conv_pallas.fused_residual_block, x, k1, b1, k2, b2
        )

        def ref(x, k1, b1, k2, b2):
            _, a1 = conv_pallas._reference_intermediates(x, k1, b1, k2, b2)
            y1p = conv_pallas._pad1(jnp.maximum(a1, 0.0))
            a2 = jax.vmap(
                lambda img: conv_pallas._nine_shift(img, k2, hw, hw)
            )(y1p).reshape(n, hw, hw, -1) + b2
            return x + a2

        want, exact = both(ref, x, k1, b1, k2, b2)
        # tests/test_pallas_conv.py: atol 1e-5 rtol 1e-5 at f32.
        _check(f"fused_residual_block[{hw}x{hw}x{ch},f32]", got, want,
               rtol=1e-5, atol=1e-5, results=results, exact=exact)
        # The preset's torso dtype: bf16 operands, f32 accumulation.
        got16 = _compiled_kernel(
            conv_pallas.fused_residual_block,
            x.astype(jnp.bfloat16), k1, b1, k2, b2,
        )
        _check(f"fused_residual_block[{hw}x{hw}x{ch},bf16]", got16, exact,
               rtol=5e-2, atol=1e-1, results=results)

    # -- windowed attention vs tests/attention_oracle.py ----------------
    # pong_transformer widths: B=32, T=21 learner tokens, W=128, 4 x 64.
    B, T, W, Hh, dh = 32, 21, 128, 4, 64
    S = W + T
    q, k, v = normal(B, T, Hh, dh), normal(B, S, Hh, dh), normal(B, S, Hh, dh)
    seg_t = attention_oracle.make_segments(rng, T, B)  # [T, B]
    first_seg = np.asarray(seg_t[0])
    # Cache slots: empty (-1), an older episode, or the row's first one.
    pre = rng.integers(-1, 1, (W, B)).astype(np.int32)
    pre_seg = jnp.asarray(np.where(pre == 0, first_seg[None, :], pre - 1))
    seg_q = jnp.asarray(seg_t).T.astype(jnp.int32)
    seg_ctx = jnp.concatenate([pre_seg.T.astype(jnp.int32), seg_q], axis=1)

    def attn(q, k, v):
        def f(q, k, v):
            return jnp.sum(jnp.sin(
                windowed_attention(q, k, v, seg_q, seg_ctx, W)
                .astype(f32)
            ))

        out = windowed_attention(q, k, v, seg_q, seg_ctx, W)
        return out, jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    def oracle(q, k, v):
        def fwd(q, k, v):
            tm = lambda a: a.transpose(1, 0, 2, 3)  # noqa: E731
            out = attention_oracle.dense_attention(
                tm(q), tm(k[:, W:]), tm(v[:, W:]), True,
                segment_ids=seg_q.T,
                prefix_k=tm(k[:, :W]), prefix_v=tm(v[:, :W]),
                prefix_seg=seg_ctx[:, :W].T,
            )
            return tm(out)

        f = lambda q, k, v: jnp.sum(jnp.sin(fwd(q, k, v)))  # noqa: E731
        return fwd(q, k, v), jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    got = _compiled_kernel(attn, q, k, v)
    want, exact = both(oracle, q, k, v)
    # tests/test_attention_pallas.py holds 5e-4 on CPU. On the chip the
    # flash kernel and the einsum feed DIFFERENT operands to the bf16
    # pass (unnormalised tile probabilities rescaled afterwards vs the
    # normalised softmax), so their roundings are independent: measured
    # 2.0e-2 between them and 2.0e-2 from each to 'highest' — f32 here
    # is bf16-sized either way, so the bf16 test's bound is the one that
    # can be held, against 'highest'.
    _check("windowed_attention[f32]", got, exact,
           rtol=5e-2, atol=5e-2, results=results)
    results["windowed_attention[f32]"]["max_abs_err_vs_default"] = (
        _max_err(got, want)
    )
    got16 = _compiled_kernel(
        attn, *(a.astype(jnp.bfloat16) for a in (q, k, v))
    )
    _check("windowed_attention[bf16]", got16, exact,
           rtol=5e-2, atol=5e-2, results=results)  # the bf16 test's 0.05

    return {
        "ok": all(r["ok"] for r in results.values()),
        "compiled_on": str(jax.devices()[0]),
        "kernels": results,
    }


# ---- train (run.main, breakout) ----------------------------------------


def _descendants(root: int) -> list[int]:
    parents = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                # pid (comm) state ppid ...; comm may hold spaces
                parents[int(entry)] = int(
                    f.read().rsplit(")", 1)[1].split()[1]
                )
        except (OSError, IndexError, ValueError):
            continue
    out, frontier = [], [root]
    while frontier:
        pid = frontier.pop()
        kids = [p for p, pp in parents.items() if pp == pid]
        out.extend(kids)
        frontier.extend(kids)
    return out


def _children_backend_report() -> dict:
    """What this process's descendants (forkserver + env workers) hold:
    a process that initialised the TPU backend has libtpu.so mapped and
    an accelerator device node open."""
    report = {"children": 0, "with_libtpu": [], "with_accel_fd": []}
    for pid in _descendants(os.getpid()):
        try:
            with open(f"/proc/{pid}/maps") as f:
                maps = f.read()
            fds = [
                os.readlink(f"/proc/{pid}/fd/{fd}")
                for fd in os.listdir(f"/proc/{pid}/fd")
            ]
        except OSError:
            continue  # exited between listing and reading
        report["children"] += 1
        if "libtpu" in maps:
            report["with_libtpu"].append(pid)
        if any(t.startswith(("/dev/accel", "/dev/vfio")) for t in fds):
            report["with_accel_fd"].append(pid)
    return report


def phase_train(seed: int) -> dict:
    import jax
    import numpy as np

    from torched_impala_tpu import configs, run
    from torched_impala_tpu.runtime import loop
    from torched_impala_tpu.telemetry.registry import get_registry

    seen: dict = {"logs": [], "children": []}
    real_train, real_learner = loop.train, loop.Learner

    class SpiedLearner(real_learner):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            seen["params_before"] = jax.tree.map(np.asarray, self._params)

    def spying_train(**kw):
        logger, on_step = kw.get("logger"), kw.get("on_learner_step")

        def tee(logs):
            seen["logs"].append(dict(logs))
            if logger is not None:
                logger(logs)

        def step_hook(n):
            if on_step is not None:
                on_step(n)
            if n in (10, 25):  # workers are mid-run here
                seen["children"].append(_children_backend_report())

        kw.update(logger=tee, on_learner_step=step_hook)
        seen["result"] = real_train(**kw)
        return seen["result"]

    # run.main imports `train` from runtime.loop at call time, and train
    # looks `Learner` up in its module: both are steered from here, not
    # through an option of the program.
    loop.train, loop.Learner = spying_train, SpiedLearner
    try:
        rc = run.main([*TRAIN_ARGS, "--seed", str(seed)])
    finally:
        loop.train, loop.Learner = real_train, real_learner

    result = seen["result"]
    learner = result.learner
    cfg = learner._config
    leaves = jax.tree.leaves(learner._params)
    param_devices = sorted({str(d) for x in leaves for d in x.devices()})
    losses = [float(l["total_loss"]) for l in seen["logs"]]
    changed = any(
        not np.array_equal(np.asarray(a), b)
        for a, b in zip(
            leaves, jax.tree.leaves(seen["params_before"])
        )
    )
    snap = get_registry().snapshot()
    counters = {
        "actor_restarts": result.actor_restarts,
        "thread_crashes": snap.get(
            "telemetry/runtime/thread_crashes", 0
        ),
        "fused_fallbacks": snap.get("telemetry/perf/fused_fallbacks", 0),
    }
    # AUTO input layouts: engaged means the AOT executable was built and
    # is still the step in use; `_auto_jit is None` afterwards means the
    # layout fallback (a refused batch layout) was taken.
    auto_requested = cfg.auto_layouts
    layouts = {
        "requested": auto_requested,
        "engaged": learner._auto_compiled is not None,
        "fallback_taken": bool(
            auto_requested
            and learner._auto_jit is None
            and learner._mesh is None
        ),
    }
    kids = seen["children"]
    workers_clean = bool(kids) and all(
        r["children"] > 0 and not r["with_libtpu"] and not r["with_accel_fd"]
        for r in kids
    )
    out = {
        "argv": list(TRAIN_ARGS),
        "fleet_cut": "num_actors 256 -> 4 actor threads x 8 envs in "
                     "process workers (32 envs = one B=32 batch per 20 "
                     "env steps); nothing else is cut",
        "rc": rc,
        "T": cfg.unroll_length,
        "B": cfg.batch_size,
        "obs": list(configs.REGISTRY["breakout"].obs_shape),
        "learner_steps": learner.num_steps,
        "learner_param_devices": param_devices,
        "actor_device": result.actor_device,
        "kernels_resolved": learner.kernels,
        "losses": losses,
        "losses_finite": bool(losses) and bool(np.all(np.isfinite(losses))),
        "params_changed": changed,
        "counters": counters,
        "auto_layouts": layouts,
        "env_worker_samples": kids,
        "env_workers_never_touched_tpu": workers_clean,
    }
    out["ok"] = bool(
        rc == 0
        and learner.num_steps >= 30
        and all(d.lower().startswith("tpu") for d in param_devices)
        and out["losses_finite"]
        and changed
        and not any(counters.values())
        and not layouts["fallback_taken"]
        and workers_clean
        and learner.kernels["vtrace"] == "pallas"
        and learner.kernels["lstm"] == "fused"
        # breakout: two sections' residual blocks (ops/conv_packed.py)
        and len(learner.kernels["packed_convs"]) == 8
    )
    return out


# ---- anakin (run.main, pixels_anakin) ----------------------------------


def phase_anakin(seed: int) -> dict:
    import jax
    import numpy as np

    from torched_impala_tpu import run, runtime

    box: dict = {"logs": []}
    real_runner = runtime.AnakinRunner

    class SpiedRunner(real_runner):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            box["runner"] = self
            box["before"] = jax.tree.map(np.asarray, self.params)

        def step(self):
            logs = super().step()
            box["logs"].append({k: float(v) for k, v in logs.items()})
            return logs

    runtime.AnakinRunner = SpiedRunner
    try:
        rc = run.main([*ANAKIN_ARGS, "--seed", str(seed)])
    finally:
        runtime.AnakinRunner = real_runner
    runner = box["runner"]
    leaves = jax.tree.leaves(runner.params)
    devices = sorted({str(d) for x in leaves for d in x.devices()})
    losses = [l["total_loss"] for l in box["logs"]]
    changed = any(
        not np.array_equal(np.asarray(a), b)
        for a, b in zip(leaves, jax.tree.leaves(box["before"]))
    )
    out = {
        "argv": list(ANAKIN_ARGS),
        "rc": rc,
        "steps": runner.num_steps,
        "param_devices": devices,
        "kernels_resolved": runner.kernels,
        "losses": losses,
        "losses_finite": bool(losses) and bool(np.all(np.isfinite(losses))),
        "params_changed": changed,
    }
    out["ok"] = bool(
        rc == 0
        and runner.num_steps >= 6
        and all(d.lower().startswith("tpu") for d in devices)
        and out["losses_finite"]
        and changed
    )
    return out


# ---- serve (PolicyServer over the breakout agent) ----------------------


def phase_serve(seed: int) -> dict:
    import jax
    import numpy as np

    from torched_impala_tpu import configs
    from torched_impala_tpu.runtime.param_store import ParamStore
    from torched_impala_tpu.serving import (
        InProcessClient,
        PolicyServer,
        VersionRegistry,
    )

    cfg = configs.REGISTRY["breakout"]
    agent = configs.make_agent(cfg)
    example = configs.example_obs(cfg)
    params = agent.init_params(jax.random.key(seed), example)
    store = ParamStore()
    store.publish(0, params)
    n, waves = 16, 3
    server = PolicyServer(
        agent=agent,
        registry=VersionRegistry.serving_latest(store),
        example_obs=example,
        max_clients=n,
        max_batch=n,
        max_wait_s=0.0,
        dtype="float32",
    )
    rng = np.random.default_rng(seed)
    mismatches, served_total, wave_ids = 0, 0, []
    try:
        clients = [InProcessClient(server, greedy=True) for _ in range(n)]
        state = agent.initial_state(n)
        step = jax.jit(agent.step)
        for w in range(waves):
            obs = rng.integers(0, 256, (n, *example.shape)).astype(
                example.dtype
            )
            first = np.full((n,), w == 0, np.bool_)
            cells = [
                c.act_async(obs[i], bool(first[i]))
                for i, c in enumerate(clients)
            ]
            served_total += server.service_once()
            results = [cell.result(timeout=120.0) for cell in cells]
            wave_ids.append(sorted({r.wave for r in results}))
            direct = step(params, jax.random.key(0), obs, first, state)
            state = direct.state
            want = np.argmax(np.asarray(direct.policy_logits), axis=-1)
            got = np.asarray([r.action for r in results])
            mismatches += int(np.sum(got != want))
        for c in clients:
            c.close()
    finally:
        server.close()
    out = {
        "agent": "breakout (deep ResNet bf16 + LSTM 256), serve dtype f32",
        "clients": n,
        "waves": waves,
        "served": served_total,
        "wave_ids": wave_ids,
        "action_mismatches_vs_direct_step": mismatches,
        "device": str(jax.devices()[0]),
    }
    out["ok"] = bool(
        served_total == n * waves
        and mismatches == 0
        and all(len(w) == 1 for w in wave_ids)
    )
    return out


# ---- --chips 4: data-parallel learner vs one device --------------------


def _shard_report(tree) -> dict:
    """Bytes each device holds of `tree`, from addressable_shards."""
    import jax

    per_device: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            key = str(shard.device)
            per_device[key] = per_device.get(key, 0) + shard.data.nbytes
    return dict(sorted(per_device.items()))


def phase_dp(seed: int) -> dict:
    import jax
    import numpy as np

    from torched_impala_tpu import configs
    from torched_impala_tpu.parallel import make_mesh
    from torched_impala_tpu.runtime import Learner
    from torched_impala_tpu.runtime.types import Trajectory

    cfg = configs.REGISTRY["procgen"]
    T, B = cfg.unroll_length, cfg.batch_size
    steps = 4
    mesh = make_mesh(num_data=4)
    example = configs.example_obs(cfg)
    rng = np.random.default_rng(seed)

    def unroll():
        return Trajectory(
            obs=rng.integers(0, 256, (T + 1, *cfg.obs_shape)).astype(
                np.uint8
            ),
            first=rng.random(T + 1) < 0.05,
            actions=rng.integers(0, cfg.num_actions, T).astype(np.int32),
            behaviour_logits=rng.normal(
                size=(T, cfg.num_actions)
            ).astype(np.float32),
            rewards=(0.01 * rng.normal(size=T)).astype(np.float32),
            cont=(rng.random(T) > 0.02).astype(np.float32),
            agent_state=(),
        )

    batches = [[unroll() for _ in range(B)] for _ in range(steps)]

    def drive(mesh):
        agent = configs.make_agent(cfg, mesh=mesh)
        learner = Learner(
            agent=agent,
            optimizer=configs.make_optimizer(cfg),
            config=configs.make_learner_config(cfg),
            example_obs=example,
            rng=jax.random.key(seed),
            mesh=mesh,
        )
        learner.start()
        losses, grad_norms, batch_bytes = [], [], None
        try:
            for trajs in batches:
                for t in trajs:
                    learner.enqueue(t)
                if batch_bytes is None:
                    # Peek the staged device batch before the step
                    # consumes it: where did the batcher put it?
                    deadline = time.time() + 300
                    while learner._batch_q.empty():
                        if learner.error is not None:
                            raise learner.error
                        if time.time() > deadline:
                            raise TimeoutError("no device batch staged")
                        time.sleep(0.01)
                    arrays = learner._batch_q.queue[0][0]
                    batch_bytes = _shard_report(arrays)
                logs = learner.step_once(timeout=600)
                losses.append(float(logs["total_loss"]))
                grad_norms.append(float(logs["grad_norm_unclipped"]))
        finally:
            learner.stop()
        return learner, losses, grad_norms, batch_bytes

    dp_learner, dp_losses, dp_gnorm, dp_batch = drive(mesh)
    param_bytes = _shard_report(dp_learner._params)
    one_learner, one_losses, one_gnorm, one_batch = drive(None)

    def rel(a, b):
        return [abs(x - y) / max(abs(y), 1e-6) for x, y in zip(a, b)]

    loss_rel, gnorm_rel = rel(dp_losses, one_losses), rel(dp_gnorm, one_gnorm)
    total_param_bytes = sum(
        x.nbytes for x in jax.tree.leaves(one_learner._params)
    )
    out = {
        "preset": "procgen: deep ResNet bf16, T=20, B=64, 64x64x3 uint8",
        "mesh": dict(mesh.shape),
        "kernels_resolved_dp": dp_learner.kernels,
        "kernels_resolved_one": one_learner.kernels,
        "losses_dp": dp_losses,
        "losses_one_device": one_losses,
        "loss_rel_diff": loss_rel,
        "grad_norm_dp": dp_gnorm,
        "grad_norm_one_device": one_gnorm,
        "grad_norm_rel_diff": gnorm_rel,
        "step0_rtol": DP_STEP0_RTOL,
        "later_steps_rtol": DP_LATER_RTOL,
        "batch_bytes_per_device_dp": dp_batch,
        "batch_bytes_per_device_one": one_batch,
        "param_bytes_per_device_dp": param_bytes,
        "param_bytes_total": total_param_bytes,
    }
    even_batch = (
        len(dp_batch) == 4 and len(set(dp_batch.values())) == 1
    )
    replicated = len(param_bytes) == 4 and all(
        v == total_param_bytes for v in param_bytes.values()
    )
    out["batch_sharded_evenly_over_4"] = even_batch
    out["params_replicated_on_4"] = replicated
    out["ok"] = bool(
        np.all(np.isfinite(dp_losses))
        and loss_rel[0] <= DP_STEP0_RTOL
        and gnorm_rel[0] <= DP_STEP0_RTOL
        and max(loss_rel) <= DP_LATER_RTOL
        and even_batch
        and replicated
    )
    return out


# ---- children: nothing this script started outlives it -----------------


def _alive(pid: int) -> bool:
    """Still running: a zombie has stopped and only waits to be reaped."""
    try:
        with open(f"/proc/{pid}/stat") as f:
            return f.read().rsplit(")", 1)[1].split()[0] != "Z"
    except (OSError, IndexError):
        return False


def _sweep(keep: set, grace_s: float) -> list:
    """SIGTERM, then SIGKILL, every live descendant not in `keep`; wait
    until each is gone. Returns what had to be signalled."""
    import signal

    me, signalled = os.getpid(), []
    for sig in (signal.SIGTERM, signal.SIGKILL):
        left = [p for p in _descendants(me) if p not in keep and _alive(p)]
        for pid in left:
            with contextlib.suppress(ProcessLookupError):
                os.kill(pid, sig)
                signalled.append({"pid": pid, "signal": sig.name})
        deadline = time.monotonic() + grace_s
        while left and time.monotonic() < deadline:
            time.sleep(0.05)
            with contextlib.suppress(ChildProcessError):
                while os.waitpid(-1, os.WNOHANG)[0]:
                    pass
            left = [p for p in left if _alive(p)]
    return signalled


def stop_children(grace_s: float = 20.0) -> dict:
    """Stop every process this one started, and wait until it is gone.

    The env pools join their workers when they close, but the two helpers
    multiprocessing starts for them stay by design: the fork server (with
    `torched_impala_tpu.envs` preloaded it takes about half a second to
    tear down) and the resource tracker. Both stop on their own only once
    this process is gone, so for a moment they OUTLIVE it. They are
    stopped here the way the standard library stops them (close the fd
    they watch, wait for the pid). Anything else found among the
    descendants, before or after, is terminated, then killed."""
    import threading
    from multiprocessing import forkserver, resource_tracker

    me = os.getpid()
    found = {}
    for pid in _descendants(me):
        with contextlib.suppress(OSError):
            with open(f"/proc/{pid}/cmdline") as f:
                found[pid] = f.read().replace("\0", " ").strip()[:100]
    # Workers are the fork server's children: once it is gone they are
    # nobody's descendants, so a worker that outlived its pool goes first.
    helpers = {
        forkserver._forkserver._forkserver_pid,
        resource_tracker._resource_tracker._pid,
    }
    signalled = _sweep(helpers, grace_s)

    def stop_helpers():
        # The fork server first: the tracker leaves when the last write
        # end of its pipe closes, and forked workers held copies.
        with contextlib.suppress(ChildProcessError):
            forkserver._forkserver._stop()
        with contextlib.suppress(ChildProcessError):
            resource_tracker._resource_tracker._stop()

    # `_stop` waits for the pid without a limit; a helper that ignored the
    # closed fd is killed by the sweep below, which lets the wait return.
    stopper = threading.Thread(target=stop_helpers, daemon=True)
    stopper.start()
    stopper.join(grace_s)
    signalled += _sweep(set(), grace_s)
    stopper.join(grace_s)
    left = [p for p in _descendants(me) if _alive(p)]
    return {
        "found": found,
        "signalled": signalled,  # empty when each stopped when asked
        "left_running": left,
        "ok": not left,
    }


# ---- main --------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1 (default): the one-chip phases. 4: ONLY the data-parallel "
             "phase on a 4-device mesh and its one-device comparison.",
    )
    args = parser.parse_args(argv)
    t0 = time.perf_counter()

    # The package, not a copy of this file alone, is what gets proven.
    from torched_impala_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    clock = CompileClock()
    ok = run_phase(
        "device", lambda: phase_device(cache_dir, args.chips), clock
    )
    device = device_info()  # as JAX reports it, pass or fail
    if ok:
        if args.chips == 4:
            phases = [("dp", phase_dp)]
        else:
            phases = [
                ("kernels", phase_kernels),
                ("train", phase_train),
                ("anakin", phase_anakin),
                ("serve", phase_serve),
            ]
        for name, fn in phases:
            with contextlib.redirect_stdout(sys.stderr):
                passed = run_phase(name, lambda: fn(SEED), clock)
            ok = passed and ok
    ok = run_phase("children", stop_children, clock) and ok
    emit({
        "phase": "total",
        "wall_s": round(time.perf_counter() - t0, 2),
        "compile_s": round(clock.compile_s, 2),
        "compile_cache_hits": clock.cache_hits,
        "compile_cache_misses": clock.cache_misses,
    })
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
