"""`python -m torched_impala_tpu.run --doctor`: validate THIS host's
environment stack end-to-end in under a minute (SURVEY.md §1 item 5;
VERDICT r4 item 6 — the emulator adapters were written without the real
emulators present, so an equipped host needs a one-command check that
every first-contact assumption holds before launching a long run).

Checks, in order:
1. dependency inventory (jax/gymnasium/cv2 required; ale-py, procgen,
   deepmind_lab optional — reported MISSING, not failed);
2. accelerator: jax backend init + one tiny jit (bounded by the caller's
   --platform choice; a backend that cannot start surfaces here, not
   mid-run);
   then telemetry registry, flight-recorder trace round-trip (a 2-event
   Chrome-trace export under traces/ reloaded + schema-validated),
   trajectory-ring spec checks, the resilience self-check (atomic
   checkpoint + manifest round-trip, corrupted-copy rejection,
   config-hash resume refusal), the serving self-check (PolicyServer
   + in-process clients, one batched wave vs direct agent.step parity,
   bf16 greedy-parity gate), and the impala-lint self-check (each
   static checker catches a seeded violation; the tree itself lints
   clean against the baseline);
3. per-family env contract: construct the REAL factory, reset, step a
   random policy N steps, validate the (obs, reward, terminated,
   truncated, info) surface, dtypes and shapes against the factory's
   example_obs, and episode restart;
4. (--config NAME) a 2-step real train probe through the full runtime on
   that preset with its real envs.

Exit code: 0 = everything present passed; 1 = a PRESENT family failed
its contract (missing optional emulators do not fail the doctor).
"""

from __future__ import annotations

import importlib
import os
import sys
import time
import traceback


def _probe_module(mod_name: str) -> tuple[str, str]:
    """("ok", version) | ("absent", "") | ("broken", error).

    Native-lib packages (ale_py, procgen, deepmind_lab) commonly fail
    import with OSError/RuntimeError (missing .so) rather than
    ImportError — a broken install must diagnose as broken, not crash
    the doctor or masquerade as cleanly absent."""
    try:
        mod = importlib.import_module(mod_name)
    except ImportError:
        return "absent", ""
    except Exception as e:
        return "broken", f"{type(e).__name__}: {e}"
    return "ok", getattr(mod, "__version__", "present")


# Which optional modules gate each env family: an ImportError from a
# family whose modules ALL import fine is a real failure, not "missing".
# cv2 rides with atari (gymnasium's AtariPreprocessing hard-depends on
# it) — it is NOT globally required, so a procgen/dmlab-only host
# without opencv still gets doctor: PASS.
_FAMILY_MODULES = {
    "cartpole": ("gymnasium",),
    "atari": ("ale_py", "cv2"),
    "procgen": ("procgen",),
    "dmlab": ("deepmind_lab",),
}


def _family_gate(name: str) -> tuple[str, str]:
    """("ok"|"absent"|"broken", detail) across the family's modules.

    Families with no gating modules (the pure-JAX Anakin envs —
    jax_cartpole/jax_catch/jax_pixels — need nothing beyond jax) are
    trivially ok."""
    for mod in _FAMILY_MODULES.get(name, ()):
        status, detail = _probe_module(mod)
        if status != "ok":
            return status, f"{mod} {detail}".strip()
    return "ok", ""


def _check_env_contract(name: str) -> tuple[str, str]:
    """Build family `name` via the real factory and exercise the contract.

    Returns (status, detail): status in {"ok", "missing", "FAIL"}.
    """
    import numpy as np

    from torched_impala_tpu.envs import factory as F

    t0 = time.perf_counter()
    gate, gate_detail = _family_gate(name)
    if gate == "broken":
        return "FAIL", f"broken install: {gate_detail}"
    try:
        env, num_actions, example = F.FACTORIES[name]()
    except Exception as e:
        if gate == "absent" and isinstance(e, ImportError):
            return "missing", str(e).split(". ")[0]
        # Every gating module imports fine (or the error isn't the
        # missing-emulator ImportError), so this is a bug — the exact
        # launch-day surprise the doctor exists to catch.
        return "FAIL", f"construction raised:\n{traceback.format_exc()}"
    try:
        rng = np.random.default_rng(0)
        obs, info = env.reset(seed=0)
        obs = np.asarray(obs)
        assert obs.shape == example.shape, (
            f"obs shape {obs.shape} != example {example.shape}"
        )
        assert obs.dtype == example.dtype, (
            f"obs dtype {obs.dtype} != example {example.dtype}"
        )
        assert isinstance(info, dict), type(info)
        episodes = 0
        for _ in range(20):
            a = int(rng.integers(num_actions))
            obs, reward, term, trunc, info = env.step(a)
            obs = np.asarray(obs)
            assert obs.shape == example.shape and obs.dtype == example.dtype
            float(reward)  # must be scalar-coercible
            assert isinstance(bool(term), bool)
            assert isinstance(bool(trunc), bool)
            if term or trunc:
                episodes += 1
                obs, info = env.reset()
        dt = time.perf_counter() - t0
        return "ok", (
            f"{num_actions} actions, obs {example.shape} "
            f"{example.dtype}, 20 steps + {episodes} restarts in {dt:.1f}s"
        )
    except Exception:
        return "FAIL", f"contract violated:\n{traceback.format_exc()}"
    finally:
        try:
            env.close()
        except Exception:
            pass


def _check_telemetry() -> tuple[str, str]:
    """Exercise the telemetry stack in-process: one metric of each kind
    through a fresh registry, snapshot key-grammar validation, and the
    jax.profiler capture surface (`--profile-steps` / SIGUSR1 depend on
    it). Purely local — no threads, pools, or devices."""
    import re

    try:
        import jax

        from torched_impala_tpu.telemetry import Registry

        reg = Registry()
        reg.counter("doctor/count").inc(3)
        reg.gauge("doctor/gauge").set(1.5)
        with reg.span("doctor/span"):
            pass
        reg.histogram("doctor/hist_ms").observe(2.0)
        reg.heartbeat("doctor")
        snap = reg.snapshot()
        assert snap["telemetry/doctor/count"] == 3, snap
        assert snap["telemetry/doctor/hist_ms_count"] == 1, snap
        key_re = re.compile(r"^telemetry/[a-z0-9_]+/[a-z0-9_]+$")
        bad = [k for k in snap if not key_re.match(k)]
        assert not bad, f"malformed snapshot keys: {bad}"
        profiler_ok = hasattr(jax.profiler, "start_trace") and hasattr(
            jax.profiler, "stop_trace"
        )
        return "ok", (
            f"registry roundtrip ({len(snap)} keys), profiler "
            f"{'ok' if profiler_ok else 'MISSING start/stop_trace'}"
        )
    except Exception:
        return "FAIL", f"telemetry stack broken:\n{traceback.format_exc()}"


def _check_tracing() -> tuple[str, str]:
    """Flight-recorder self-check: record a 2-event trace (one span, one
    instant with a lineage ID), export it under `traces/`, reload the
    JSON, and validate the Chrome-trace schema — so `--trace` / SIGUSR2
    dumps are known-loadable in Perfetto BEFORE a long run depends on
    them. Purely local; the file is left behind as a sample trace."""
    import json
    import os

    from torched_impala_tpu.telemetry import (
        FlightRecorder,
        validate_chrome_trace,
    )

    try:
        rec = FlightRecorder(capacity=64)
        with rec.span("doctor/selfcheck", {"lid": "a0u0"}):
            pass
        rec.instant("doctor/event", {"lid": "a0u0"})
        assert len(rec) == 2, len(rec)
        path = os.path.join("traces", "doctor_trace.json")
        n = rec.export(path)
        assert n == 2, n
        with open(path, encoding="utf-8") as f:
            obj = json.load(f)
        problems = validate_chrome_trace(obj)
        if problems:
            return "FAIL", (
                "exported trace violates the Chrome-trace schema: "
                + "; ".join(problems)
            )
        events = [e for e in obj["traceEvents"] if e["ph"] != "M"]
        names = {e["name"] for e in events}
        assert names == {"doctor/selfcheck", "doctor/event"}, names
        assert all(e.get("args", {}).get("lid") == "a0u0"
                   for e in events), events
        return "ok", (
            f"2-event trace round-trips through {path} "
            "(schema valid, lineage args intact)"
        )
    except Exception:
        return "FAIL", f"flight recorder broken:\n{traceback.format_exc()}"


def _check_traj_ring() -> tuple[str, str]:
    """Validate the zero-copy trajectory ring against real preset env
    specs: slot dtypes/shapes must match what the preset's envs emit
    (obs shape/dtype, logits width = action-space size), and the
    acquire -> commit -> pop -> release cycle must round-trip. Purely
    local (tiny slots, no pools or devices); catches a config/ring
    shape drift at doctor time instead of as garbled batches mid-run."""
    import numpy as np

    from torched_impala_tpu import configs
    from torched_impala_tpu.runtime.traj_ring import TrajectoryRing

    try:
        checked = []
        for name in ("cartpole", "pong"):
            cfg = configs.REGISTRY[name]
            obs = configs.example_obs(cfg)
            agent = configs.make_agent(cfg)
            ring = TrajectoryRing(
                num_slots=2,
                unroll_length=3,
                batch_size=2,
                example_obs=obs,
                num_actions=cfg.num_actions,
                agent_state_example=agent.initial_state(1),
            )
            problems = ring.validate_env_spec(obs, cfg.num_actions)
            if problems:
                return "FAIL", (
                    f"{name}: slot/env spec mismatch: " + "; ".join(problems)
                )
            # Roundtrip: one 2-column block fills a whole slot.
            block = ring.acquire(2)
            for arr in (block.obs, block.first, block.actions,
                        block.behaviour_logits, block.rewards, block.cont,
                        block.task):
                arr[...] = np.zeros_like(arr)
            ring.commit(block, param_version=5)
            view = ring.pop_ready(timeout=1.0)
            assert view is not None and view.param_version == 5, view
            assert view.arrays[0].shape == (4, 2) + obs.shape
            ring.release(view.slot)
            checked.append(name)
        return "ok", (
            f"slot dtypes/shapes match env specs ({', '.join(checked)}); "
            "acquire->commit->pop->release roundtrip ok"
        )
    except Exception:
        return "FAIL", f"traj ring broken:\n{traceback.format_exc()}"


def _check_mesh_feed() -> tuple[str, str]:
    """Mesh-native zero-copy feed self-check (ISSUE 15): on a tiny
    data-parallel CPU mesh, the donated ring learner must place every
    batch as per-device shards straight from ring slot memory — zero
    bytes staged host-side, per-shard H2D telemetry populated, every
    slot committed and delivered with none aborted — and replay must
    compose with the mesh instead of being refused at config
    validation. Degrades to a 1-device mesh when the process only sees
    one CPU device (the doctor CLI runs without the host-platform
    device-count flag): the table-driven placement path is identical,
    only the shard count differs, and the detail line says so."""
    import jax
    import numpy as np
    import optax

    from torched_impala_tpu.envs.fake import ScriptedEnv
    from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
    from torched_impala_tpu.parallel import make_mesh
    from torched_impala_tpu.replay import ReplayConfig
    from torched_impala_tpu.runtime import (
        Learner,
        LearnerConfig,
        VectorActor,
    )
    from torched_impala_tpu.telemetry import Registry

    try:
        cpus = jax.devices("cpu")
        num_data = 2 if len(cpus) >= 2 else 1
        mesh = make_mesh(num_data=num_data, devices=cpus[:num_data])
        T, B, E, n = 3, 4, 2, 3

        def run(**cfg_kwargs):
            reg = Registry()
            agent = Agent(
                ImpalaNet(num_actions=2, torso=MLPTorso(hidden_sizes=(16,)))
            )
            learner = Learner(
                agent=agent,
                optimizer=optax.sgd(1e-2),
                config=LearnerConfig(
                    batch_size=B,
                    unroll_length=T,
                    traj_ring=True,
                    **cfg_kwargs,
                ),
                example_obs=np.zeros((4,), np.float32),
                rng=jax.random.key(0),
                telemetry=reg,
                mesh=mesh,
            )
            envs = [ScriptedEnv(episode_len=4) for _ in range(E)]
            actor = VectorActor(
                actor_id=0,
                envs=envs,
                agent=agent,
                param_store=learner.param_store,
                enqueue=learner.enqueue,
                unroll_length=T,
                seed=3,
                traj_ring=learner.traj_ring,
            )
            learner.start()
            try:
                for _ in range(n):
                    for _ in range(B // E):
                        actor.unroll_and_push()
                    logs = learner.step_once(timeout=60)
                    assert np.isfinite(logs["total_loss"]), logs
            finally:
                learner.stop()
            return reg.snapshot()

        snap = run(donate_batch=True)
        staged = snap.get("telemetry/learner/ring_stage_bytes", 0.0)
        if staged != 0:
            return "FAIL", (
                f"donated mesh ring staged {staged:.0f} bytes host-side "
                "(sharded placement must go straight to device memory)"
            )
        donated = int(snap.get("telemetry/learner/donated_batches", 0))
        if donated == 0:
            return "FAIL", "no batch donated on the mesh ring path"
        batches = int(snap.get("telemetry/ring/batches", 0))
        aborted = int(snap.get("telemetry/ring/aborted_slots", 0))
        if batches != n or aborted != 0:
            return "FAIL", (
                f"ring accounting off: {batches} batches (want {n}), "
                f"{aborted} aborted"
            )
        # Lifted carve-out: replay composes with the mesh learner.
        run(replay=ReplayConfig(max_reuse=2, target_update_interval=1))
        degraded = (
            "" if num_data == 2
            else "; DEGRADED to 1 shard (only 1 CPU device visible)"
        )
        return "ok", (
            f"{num_data}-shard mesh: {n} donated batches placed "
            f"shard-wise, 0 bytes staged, replay composes{degraded}"
        )
    except Exception:
        return "FAIL", f"mesh feed broken:\n{traceback.format_exc()}"


def _check_replay() -> tuple[str, str]:
    """Replay self-check (docs/REPLAY.md): run a tiny ring with
    max_reuse=2 through its whole lifecycle — two fresh deliveries, two
    replays, budget exhaustion — and assert the replay telemetry agrees
    exactly (2 replayed batches, every slot retired at reuse_count 2,
    zero evictions). Then pin the target store's staleness refusal: a
    TargetParamStore pushed past max_lag_frames must REFUSE current()
    rather than serve an ancient anchor. Purely local, no devices."""
    import numpy as np

    from torched_impala_tpu.replay import TargetParamStore
    from torched_impala_tpu.runtime.param_store import ParamStore
    from torched_impala_tpu.runtime.traj_ring import TrajectoryRing
    from torched_impala_tpu.telemetry.registry import Registry

    try:
        reg = Registry()
        ring = TrajectoryRing(
            num_slots=3,
            unroll_length=2,
            batch_size=2,
            example_obs=np.zeros((4,), np.float32),
            num_actions=2,
            telemetry=reg,
            max_reuse=2,
        )
        for i in range(2):
            block = ring.acquire(2)
            for arr in (block.obs, block.first, block.actions,
                        block.behaviour_logits, block.rewards, block.cont,
                        block.task):
                arr[...] = np.zeros_like(arr)
            ring.commit(block, param_version=i)
        deliveries = []
        while True:
            view = ring.pop_ready(timeout=0.2)
            if view is None:
                break
            deliveries.append(view.reuse_count)
            ring.release(view.slot)
        assert deliveries == [1, 1, 2, 2], deliveries
        snap = reg.snapshot()
        # _mean, not _p50: the histogram's quantiles interpolate between
        # bucket edges, the mean is exact for a point mass.
        assert snap["telemetry/replay/reuse_delivered"] == 2, snap
        assert snap["telemetry/replay/reuse_count_mean"] == 2.0, snap
        assert snap["telemetry/replay/evict_pressure"] == 0, snap

        store = ParamStore()
        store.publish(0, {"w": np.zeros((2,), np.float32)})
        tps = TargetParamStore(
            store, update_interval=100, max_lag_frames=5, telemetry=reg
        )
        tps.update({"w": np.zeros((2,), np.float32)}, version=0, step=0)
        tps.maybe_update(1, None, 100)  # watermark jumps 100 frames
        try:
            tps.current()
            return "FAIL", (
                "target store served a target 100 frames past "
                "max_lag_frames=5 instead of refusing"
            )
        except RuntimeError:
            pass
        return "ok", (
            "ring max_reuse=2 lifecycle ok (2 fresh + 2 replayed, all "
            "slots retired at reuse 2, no evictions); stale target "
            "refused past max_lag_frames"
        )
    except Exception:
        return "FAIL", f"replay broken:\n{traceback.format_exc()}"


def _check_resilience() -> tuple[str, str]:
    """Resilience self-check (docs/RESILIENCE.md): write a checkpoint
    through the async writer, round-trip the run manifest, corrupt a COPY
    of the state file and verify the loader REJECTS it (clear error, no
    garbage params), and verify a config-hash mismatch refuses to resume.
    Purely local — a temp dir, a tiny state tree, no devices beyond one
    array; proves the crash-recovery path is load-bearing BEFORE a long
    run depends on it."""
    import shutil
    import tempfile

    import numpy as np

    from torched_impala_tpu.resilience import (
        AsyncCheckpointer,
        ResumeConfigMismatch,
        config_fingerprint,
        load_manifest,
        restore_latest,
    )
    from torched_impala_tpu.resilience import chaos as chaos_mod
    from torched_impala_tpu.resilience import recovery
    from torched_impala_tpu.utils.checkpoint import (
        CheckpointCorruptError,
        load_state_file,
    )

    tmp = tempfile.mkdtemp(prefix="doctor_resilience_")
    try:
        state = {
            "params": {"w": np.arange(64.0).reshape(8, 8)},
            "num_frames": np.asarray(480, np.int64),
            "num_steps": np.asarray(3, np.int64),
            "rng": np.asarray([0, 7], np.uint32),
        }
        fp = config_fingerprint({"preset": "doctor", "batch_size": 2})
        ck = AsyncCheckpointer(
            tmp, keep=2, interval_steps=1, config_hash=fp
        )
        try:
            ck.save_now(3, state, param_version=480)
            ck.wait()
        finally:
            ck.close()
        manifest = load_manifest(recovery.manifest_path(tmp, 3))
        assert manifest.step == 3 and manifest.param_version == 480, manifest
        assert manifest.config_hash == fp, manifest
        found = restore_latest(tmp, state, config_hash=fp)
        assert found is not None
        np.testing.assert_array_equal(
            found[1]["params"]["w"], state["params"]["w"]
        )
        # Corrupt a COPY; the loader must reject it with the clear error.
        bad = recovery.checkpoint_path(tmp, 3) + ".copy"
        shutil.copyfile(recovery.checkpoint_path(tmp, 3), bad)
        chaos_mod.corrupt_file(bad)
        try:
            load_state_file(bad, state)
            return "FAIL", "corrupted checkpoint loaded without error"
        except CheckpointCorruptError:
            pass
        # A mismatched config hash must refuse, not restore.
        try:
            restore_latest(tmp, state, config_hash="deadbeef00000000")
            return "FAIL", "config-hash mismatch did not refuse resume"
        except ResumeConfigMismatch:
            pass
        return "ok", (
            "atomic save + manifest round-trip; corrupted copy rejected "
            "(CheckpointCorruptError); config-hash mismatch refused"
        )
    except Exception:
        return "FAIL", f"resilience stack broken:\n{traceback.format_exc()}"
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _check_lint() -> tuple[str, str]:
    """impala-lint self-check (docs/STATIC_ANALYSIS.md): the static-
    analysis suite must (a) catch a seeded violation of each checker —
    a lint that silently stopped firing is worse than no lint — and
    (b) pass over THIS tree with zero non-baselined findings, so a
    dirty tree surfaces at doctor time exactly like a failing
    subsystem. Purely local: AST parsing only, no jax, no threads."""
    import os
    import sys

    repo = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    if repo not in sys.path:
        sys.path.insert(0, repo)
    try:
        from tools.lint import run_all
        from tools.lint.core import SourceFile
        from tools.lint import (
            donation,
            dtypes,
            jitb,
            metrics,
            sharding,
            shm,
            threads,
        )

        seeded = {
            "thread-safety": (
                threads,
                "import threading\n"
                "class C:\n"
                "    def __init__(self):\n"
                "        self._lock = threading.Lock()\n"
                "        self.n = 0\n"
                "    def start(self):\n"
                "        threading.Thread(target=self._loop).start()\n"
                "    def _loop(self):\n"
                "        self.n += 1\n"
                "    def read(self):\n"
                "        return self.n\n",
            ),
            "jit-boundary": (
                jitb,
                "import jax\n"
                "@jax.jit\n"
                "def f(x):\n"
                "    return x.sum().item()\n",
            ),
            "shm-lifecycle": (
                shm,
                "from multiprocessing import shared_memory\n"
                "class C:\n"
                "    def __init__(self):\n"
                "        self._shm = shared_memory.SharedMemory(\n"
                "            create=True, size=8)\n",
            ),
            "telemetry": (
                metrics,
                # The seeded-violation STRING would itself trip the
                # line-based telemetry scan — the annotation is for
                # exactly this.
                'reg.counter("NoSlash")\n',  # lint: allow(telemetry)
            ),
            # v2 interprocedural checkers: a seeded axis-name mismatch
            # (undeclared axis reaching a collective through a call),
            # a donated buffer leaking across a wrapper, and a PopArt
            # stat created in bf16 via a helper.
            "sharding": (
                sharding,
                "import jax\n"
                "def g(q, *, axis_name):\n"
                "    return jax.lax.psum(q, axis_name)\n"
                "def caller(q):\n"
                '    return g(q, axis_name="modle")\n',
            ),
            "donation": (
                donation,
                "import jax\n"
                "class L:\n"
                "    def __init__(self):\n"
                "        self._step = jax.jit(\n"
                "            self._impl, donate_argnums=(0,))\n"
                "    def train(self, params):\n"
                "        return self._step(params)\n"
                "    def run(self, p):\n"
                "        out = self.train(p)\n"
                "        return out, p\n",
            ),
            "dtype": (
                dtypes,
                "import jax.numpy as jnp\n"
                "def halved(x):\n"
                "    return x.astype(jnp.bfloat16)\n"
                "def update(x, mu):\n"
                "    mu = halved(x)\n"
                "    return mu\n",
            ),
        }
        for name, (mod, text) in seeded.items():
            sf = SourceFile(f"<doctor-{name}>", f"doctor_{name}.py", text)
            if not mod.check([sf]):
                return "FAIL", (
                    f"{name} checker missed its seeded violation — the "
                    "lint has gone blind"
                )
        result = run_all(repo)
        if result.findings:
            first = result.findings[0]
            return "FAIL", (
                f"{len(result.findings)} non-baselined finding(s), "
                f"first: {first.format()}"
            )
        return "ok", (
            f"{len(seeded)} checkers catch their seeded violations; "
            f"tree clean ({len(result.suppressed)} baselined, "
            f"{len(result.stale_baseline)} stale)"
        )
    except Exception:
        return "FAIL", f"impala-lint broken:\n{traceback.format_exc()}"


def _check_sharding() -> tuple[str, str]:
    """Sharding-contract self-check (docs/STATIC_ANALYSIS.md): the
    SpecLayout table must parse as pure literals (the static checker
    reads it with ast.literal_eval — a computed entry blinds it), the
    runtime mesh constants must agree with it, the sharding checker
    must catch a seeded axis-name mismatch, and the tree itself must be
    contract-clean."""
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    try:
        from tools.lint import sharding as shard_check
        from tools.lint.core import SourceFile, load_files

        axes, table, placement, errs = shard_check._load_tables([])
        if errs or axes is None:
            return "FAIL", (
                "SpecLayout tables unreadable: "
                + (errs[0].message if errs else "no MESH_AXES")
            )
        from torched_impala_tpu.parallel import mesh, spec_layout

        if tuple(spec_layout.MESH_AXES) != axes:
            return "FAIL", (
                "static/runtime MESH_AXES disagree: "
                f"{axes} vs {spec_layout.MESH_AXES}"
            )
        if (mesh.DATA_AXIS, mesh.MODEL_AXIS, mesh.SEQ_AXIS) != axes:
            return "FAIL", "mesh.py axis constants drifted from table"
        seeded = SourceFile(
            "<doctor-sharding>",
            "doctor_sharding.py",
            "import jax\n"
            "def f(x):\n"
            '    return jax.lax.psum(x, "modle")\n',
        )
        if not any(
            f.rule == "sharding/undeclared-axis"
            for f in shard_check.check([seeded])
        ):
            return "FAIL", (
                "sharding checker missed a seeded axis-name mismatch"
            )
        tree_findings = shard_check.check(load_files(repo))
        if tree_findings:
            return "FAIL", (
                f"{len(tree_findings)} sharding-contract finding(s), "
                f"first: {tree_findings[0].format()}"
            )
        roles = placement.get("__roles__", ())
        return "ok", (
            f"SpecLayout literal tables ok (axes={','.join(axes)}, "
            f"{len(table)} logical tensors, {len(roles)} feed roles); "
            "seeded axis mismatch caught; tree contract-clean"
        )
    except Exception:
        return "FAIL", f"sharding contract broken:\n{traceback.format_exc()}"


def _check_perf() -> tuple[str, str]:
    """Performance-observatory self-check (docs/OBSERVABILITY.md): the
    cost model must report nonzero FLOPs for a tiny jitted matmul —
    from the backend's cost_analysis where available, else the static
    estimator — and export the perf/* gauges; and the overlap analyzer
    must attribute a synthetic two-step trace."""
    try:
        import jax
        import jax.numpy as jnp

        from torched_impala_tpu.perf import CostModel, analyze_records
        from torched_impala_tpu.telemetry import Registry

        reg = Registry()
        cm = CostModel(registry=reg)
        x = jnp.ones((64, 64), jnp.float32)
        compiled = jax.jit(lambda a: (a @ a).sum()).lower(x).compile()
        root = cm.register_root(
            "train_step",
            compiled=compiled,
            # Static fallback for backends whose cost_analysis reports
            # nothing (CPU CI): 6 * params * frames.
            fallback_params={"w": x},
            frames_per_call=64,
        )
        if root.flops <= 0:
            return "FAIL", (
                "cost model reported zero FLOPs for a 64x64 matmul "
                f"(source={root.source})"
            )
        cm.observe_call("train_step", 1e-3)
        snap = reg.snapshot()
        if snap.get("telemetry/perf/flops_per_step", 0.0) <= 0.0:
            return "FAIL", "perf/flops_per_step gauge not exported"
        if "telemetry/perf/mfu" not in snap:
            return "FAIL", "perf/mfu gauge not exported"

        # Overlap analyzer on a synthetic two-step trace: a feed span
        # fills part of the inter-step gap, the second step is marked
        # replayed via its lineage args.
        ms = 1_000_000  # ns
        records = [
            (0 * ms, 10 * ms, "X", "learner/train_step", 1, {}),
            (10 * ms, 4 * ms, "X", "learner/host_stack", 1, None),
            (
                16 * ms,
                10 * ms,
                "X",
                "learner/train_step",
                1,
                # reuse_max 2: a replay RE-delivery (1 = fresh).
                {"reuse_max": 2, "staleness": 5},
            ),
        ]
        learner = analyze_records(records)["learner"]
        if learner["steps"] != 2:
            return "FAIL", f"analyzer saw {learner['steps']} steps, not 2"
        if abs(learner["gaps_s"]["feed"] - 0.004) > 1e-9:
            return "FAIL", (
                f"feed gap {learner['gaps_s']['feed']}s != 0.004s"
            )
        if learner["coverage_frac"] < 0.99:
            return "FAIL", (
                f"compute+gaps cover {learner['coverage_frac']:.0%} of "
                "wall-clock, expected ~100%"
            )
        if learner["replayed"]["steps"] != 1:
            return "FAIL", "replayed step not attributed separately"
        return "ok", (
            f"flops={root.flops:.0f} ({root.source}); analyzer "
            "attributes feed gap + replayed step"
        )
    except Exception:
        return "FAIL", (
            f"performance observatory broken:\n{traceback.format_exc()}"
        )


def _check_control() -> tuple[str, str]:
    """Control-plane self-check (docs/CONTROL.md): a synthetic objective
    drives one hill-climb knob up, a seeded regression forces the
    guardrail revert, and a gated recompile knob is refused — with the
    control/* telemetry counters AND the control/decision flight-recorder
    events accounted exactly (2 sets + 1 revert + 1 refusal), plus the
    post-revert cooldown holding the knob still. Deterministic: explicit
    tick clock, private registry/recorder, no threads."""
    try:
        from torched_impala_tpu.control import (
            ControlLoop,
            FnSignal,
            HillClimbPolicy,
            Knob,
            KnobSpec,
            RecompileGate,
            SloPolicy,
        )
        from torched_impala_tpu.telemetry import Registry
        from torched_impala_tpu.telemetry.tracing import FlightRecorder

        reg = Registry()
        rec = FlightRecorder(capacity=256)
        loop = ControlLoop(interval_s=1.0, telemetry=reg, tracer=rec)
        state = {"k": 4, "obj": 0.5}
        loop.bind(
            Knob(
                KnobSpec(
                    "doctor_knob", lo=0, hi=8, step=1, settle_s=2.0,
                    kind="int",
                    apply=lambda v: state.__setitem__("k", int(v)),
                    read=lambda: state["k"],
                ),
                telemetry=reg,
            ),
            HillClimbPolicy(
                FnSignal(lambda: state["obj"]),
                tolerance=0.05, hysteresis=0.01, cooldown_s=10.0,
            ),
        )
        # Gated B-style knob: a policy-less surface; propose directly.
        gated = loop.add_knob(
            Knob(
                KnobSpec("doctor_batch", lo=1, hi=64, step=1,
                         kind="int", recompile=True),
                gate=RecompileGate(allow=False),
                initial=8,
                telemetry=reg,
            )
        )

        loop.tick(now=0.0)          # climb: 4 -> 5
        if state["k"] != 5:
            return "FAIL", f"synthetic signal did not drive knob up: {state}"
        state["obj"] = 0.6          # the move paid off
        loop.tick(now=3.0)          # settle elapsed: commit
        loop.tick(now=4.0)          # climb again: 5 -> 6
        if state["k"] != 6:
            return "FAIL", f"second climb step missing: {state}"
        state["obj"] = 0.3          # seeded regression (>5% of 0.6)
        loop.tick(now=7.0)          # guardrail: revert 6 -> 5
        if state["k"] != 5:
            return "FAIL", f"guardrail revert did not restore knob: {state}"
        loop.tick(now=8.0)          # inside cooldown: must hold
        if state["k"] != 5:
            return "FAIL", f"knob moved during post-revert cooldown: {state}"
        # Bind the gated knob to a policy that always wants to grow it
        # (violating SLO, grow_on_violation): one more tick must route
        # the proposal into the recompile gate and take the refusal.
        loop.bind(
            gated,
            SloPolicy(
                FnSignal(lambda: -1.0), grow_on_violation=True
            ),
        )
        loop.tick(now=9.0)          # hill-climb in cooldown; B refused
        if state["k"] != 5:
            return "FAIL", f"knob moved during post-revert cooldown: {state}"
        snap = reg.snapshot()
        expected = {
            "telemetry/control/decision_total": 2,
            "telemetry/control/decision_refused": 1,
            "telemetry/control/revert_total": 1,
            "telemetry/control/knob_doctor_knob": 5.0,
            "telemetry/control/knob_doctor_batch": 8.0,
        }
        for key, want in expected.items():
            got = snap.get(key)
            if got != want:
                return "FAIL", f"{key} = {got}, expected {want}"
        decisions = [
            r for r in rec.tail() if r[3] == "control/decision"
        ]
        kinds = [r[5]["kind"] for r in decisions]
        if kinds != ["set", "set", "revert", "refused"]:
            return "FAIL", (
                f"decision audit trail mismatch: {kinds} != "
                "['set', 'set', 'revert', 'refused']"
            )
        if decisions[2][5]["to"] != 5.0:
            return "FAIL", (
                f"revert event restored {decisions[2][5]['to']}, not 5"
            )
        return "ok", (
            "hill-climb drove knob 4->6 on a synthetic objective, seeded "
            "regression reverted to 5 (cooldown holds), recompile gate "
            "refused B; 2 sets + 1 revert + 1 refusal accounted in "
            "telemetry and the flight recorder"
        )
    except Exception:
        return "FAIL", f"control plane broken:\n{traceback.format_exc()}"


def _check_serving(seed: int = 0) -> tuple[str, str]:
    """Serving-tier self-check (docs/SERVING.md): spin up a PolicyServer
    over a fresh ParamStore, connect in-process clients, drive ONE
    batched wave deterministically (service_once), and verify every
    served action equals the direct `agent.step` greedy argmax at the
    same params — plus the bf16 greedy-parity gate the bf16 serving
    path is gated on. Purely local: tiny MLP agent, no threads beyond
    the construction path, no pools."""
    import numpy as np

    try:
        import jax

        from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
        from torched_impala_tpu.runtime.param_store import ParamStore
        from torched_impala_tpu.serving import (
            InProcessClient,
            PolicyServer,
            VersionRegistry,
            greedy_action_parity,
        )

        agent = Agent(
            ImpalaNet(num_actions=4, torso=MLPTorso(hidden_sizes=(32,)))
        )
        example = np.zeros((8,), np.float32)
        params = agent.init_params(jax.random.key(seed), example)
        store = ParamStore()
        store.publish(0, params)
        registry = VersionRegistry.serving_latest(store)
        server = PolicyServer(
            agent=agent,
            registry=registry,
            example_obs=example,
            max_clients=4,
            max_batch=4,
            max_wait_s=0.0,
        )
        try:
            clients = [InProcessClient(server, greedy=True)
                       for _ in range(3)]
            rng = np.random.default_rng(seed)
            obs = rng.normal(size=(3, 8)).astype(np.float32)
            cells = [
                c.act_async(obs[i], True) for i, c in enumerate(clients)
            ]
            served = server.service_once()
            assert served == 3, f"one wave should answer 3 reqs, got {served}"
            results = [cell.result(timeout=10.0) for cell in cells]
            waves = {r.wave for r in results}
            assert len(waves) == 1, f"expected ONE wave, got {waves}"
            out = agent.step(
                params,
                jax.random.key(0),
                obs,
                np.ones((3,), np.bool_),
                agent.initial_state(3),
            )
            direct = np.argmax(np.asarray(out.policy_logits), axis=-1)
            got = np.asarray([r.action for r in results])
            assert np.array_equal(got, direct), (got, direct)
            parity_ok, mismatches = greedy_action_parity(
                agent, params, obs
            )
            if not parity_ok:
                return "FAIL", (
                    f"bf16 greedy parity gate: {mismatches} mismatched "
                    "actions vs f32"
                )
            for c in clients:
                c.close()
        finally:
            server.close()
        return "ok", (
            "one batched wave (3 clients) matches direct agent.step "
            "argmax; bf16 greedy parity gate passes"
        )
    except Exception:
        return "FAIL", f"serving tier broken:\n{traceback.format_exc()}"


def _check_fleet(seed: int = 0) -> tuple[str, str]:
    """Fleet-tier self-check (docs/SERVING.md "Fleet"): a 2-replica
    in-process ServingFleet serves through the least-loaded router under
    live multi-client traffic while one draining rollout re-pins both
    replicas to a new version — zero dropped/errored requests, and every
    (replica, wave) group serves exactly one version. Then the int8
    parity gate (serving/quant.py) must pass on clean quantization and
    CATCH a seeded scale corruption."""
    import threading

    import numpy as np

    try:
        import jax

        from torched_impala_tpu.models import Agent, ImpalaNet, MLPTorso
        from torched_impala_tpu.runtime.param_store import ParamStore
        from torched_impala_tpu.serving import (
            FleetClient,
            ServingFleet,
            corrupt_scales,
            dequantize_params,
            greedy_action_parity,
            quantize_params,
        )

        agent = Agent(
            ImpalaNet(num_actions=4, torso=MLPTorso(hidden_sizes=(32,)))
        )
        example = np.zeros((8,), np.float32)
        params = agent.init_params(jax.random.key(seed), example)
        store = ParamStore()
        store.publish(0, params)
        store.publish(1, params)
        fleet = ServingFleet(
            agent=agent,
            store=store,
            example_obs=example,
            replicas=2,
            version=0,
            max_clients=8,
            max_batch=4,
            max_wait_s=0.0,
            seed=seed,
        ).start()
        results: list = []
        errors: list = []
        lock = threading.Lock()
        rng = np.random.default_rng(seed)
        obs = rng.normal(size=(4, 8)).astype(np.float32)

        def drive(wid: int) -> None:
            client = FleetClient(fleet, client_id=wid)
            try:
                for _ in range(25):
                    res = client.act_full(obs[wid], True)
                    with lock:
                        results.append(res)
            except Exception as e:  # noqa: BLE001 — the check's verdict
                with lock:
                    errors.append(e)
            finally:
                client.close()

        try:
            threads = [
                threading.Thread(target=drive, args=(w,), daemon=True)
                for w in range(4)
            ]
            for t in threads:
                t.start()
            rollout = fleet.rollout(1, timeout_s=20.0)
            for t in threads:
                t.join(timeout=30.0)
            with FleetClient(fleet) as probe:
                final = probe.act_full(obs[0], True)
        finally:
            fleet.close()
        if errors:
            return "FAIL", (
                f"rollout under traffic dropped requests: {errors[:3]}"
            )
        if len(results) != 100:
            return "FAIL", f"expected 100 served requests, got {len(results)}"
        if rollout["replicas"] != ["r0", "r1"]:
            return "FAIL", f"rollout skipped replicas: {rollout}"
        if final.version != 1:
            return "FAIL", f"post-rollout serves v{final.version}, not v1"
        by_wave: dict = {}
        for res in results:
            by_wave.setdefault((res.replica, res.wave), set()).add(
                res.version
            )
        mixed = {k: v for k, v in by_wave.items() if len(v) > 1}
        if mixed:
            return "FAIL", f"mixed versions within a wave: {mixed}"
        replicas_used = {res.replica for res in results}
        if replicas_used != {"r0", "r1"}:
            return "FAIL", (
                f"router used {replicas_used}, expected both replicas"
            )
        parity_ok, mm = greedy_action_parity(
            agent, params, obs, dtype="int8"
        )
        if not parity_ok:
            return "FAIL", f"int8 parity gate: {mm} mismatches vs f32"
        corrupted_ok, corrupted_mm = greedy_action_parity(
            agent,
            params,
            obs,
            cast_fn=lambda p: dequantize_params(
                corrupt_scales(quantize_params(p))
            ),
        )
        if corrupted_ok:
            return "FAIL", (
                "int8 parity gate MISSED a seeded scale corruption"
            )
        return "ok", (
            f"2-replica fleet served {len(results)} requests through "
            "the router with a mid-traffic draining rollout v0->v1 "
            "(zero drops, per-wave version uniformity); int8 parity "
            f"gate passes clean and catches corrupted scales "
            f"({corrupted_mm} mismatches)"
        )
    except Exception:
        return "FAIL", f"serving fleet broken:\n{traceback.format_exc()}"


def _train_probe(config_name: str) -> tuple[str, str]:
    """Two real learner steps through the full runtime on the preset's
    REAL envs (no fakes) — the end-to-end first-contact check."""
    # Runtime imports stay OUTSIDE the missing-vs-failed decision: a
    # broken import in our own code must FAIL the doctor, not report
    # "missing" and exit 0.
    import numpy as np

    from torched_impala_tpu import configs
    from torched_impala_tpu.runtime.loop import train
    from torched_impala_tpu.utils.loggers import NullLogger

    cfg = configs.REGISTRY[config_name]
    gate, gate_detail = _family_gate(cfg.env_family)
    if gate == "absent":
        return "missing", f"{cfg.env_family} needs {gate_detail or '?'}"
    if gate == "broken":
        return "FAIL", f"broken install: {gate_detail}"
    try:
        # Doctor-sized: the smallest batch the runtime accepts, so the
        # probe is dominated by one compile, not data collection.
        import dataclasses

        lcfg = dataclasses.replace(
            configs.make_learner_config(cfg),
            batch_size=2,
        )
        t0 = time.perf_counter()
        result = train(
            agent=configs.make_agent(cfg),
            optimizer=configs.make_optimizer(cfg),
            env_factory=configs.make_env_factory(cfg, fake=False),
            example_obs=configs.example_obs(cfg),
            learner_config=lcfg,
            num_actors=1,
            envs_per_actor=2,
            total_steps=2,
            logger=NullLogger(),
            log_every=1,  # train() overrides log_interval with this
            seed=0,
        )
        loss = float(np.asarray(result.final_logs["total_loss"]))
        assert np.isfinite(loss), loss
        return "ok", (
            f"2 learner steps on real {cfg.env_family!r} envs in "
            f"{time.perf_counter() - t0:.1f}s, total_loss={loss:.3f}"
        )
    except Exception:
        return "FAIL", f"train probe raised:\n{traceback.format_exc()}"


def _check_mixed_precision() -> tuple[str, str]:
    """Mixed-precision policy self-check (docs/OBSERVABILITY.md,
    ISSUE 16): (a) a tiny full-bf16 train forward must pass the
    greedy-action parity gate against f32 (the run.py --train-dtype
    gate); (b) seeded bf16 PopArt statistics must be REFUSED by the
    accumulator assertion Learner.__init__/set_state run (a rogue
    half-precision accumulator is silent return corruption); (c) the
    fused Pallas LSTM cell must match the flax reference on a fixed
    probe within the documented ~1-ulp tolerance."""
    import dataclasses

    import numpy as np

    try:
        import jax
        import jax.numpy as jnp

        from torched_impala_tpu import configs
        from torched_impala_tpu.ops import precision

        cfg = dataclasses.replace(
            configs.REGISTRY["cartpole"], train_dtype="bfloat16"
        )
        ok, mismatches = configs.check_train_dtype_parity(
            cfg, seed=0, batch=8, unroll=4
        )
        if not ok:
            return "FAIL", (
                f"bf16 train step failed the greedy parity gate "
                f"({mismatches} probe actions differ from f32)"
            )

        # (b) the refusal path: bf16 PopArt stats must raise.
        bad_stats = {
            "mu": jnp.zeros((4,), jnp.bfloat16),
            "nu": jnp.ones((4,), jnp.float32),
        }
        try:
            precision.assert_f32_accumulators(
                {"popart_stats": bad_stats}, context="doctor"
            )
            return "FAIL", (
                "seeded bfloat16 PopArt statistics were ACCEPTED by "
                "the f32-accumulator assertion"
            )
        except ValueError:
            pass

        # (c) fused Pallas LSTM vs the flax cell on a fixed probe.
        import flax.linen as nn

        from torched_impala_tpu.models.lstm import PallasLSTMCell

        rng = np.random.default_rng(0)
        B, F, H = 4, 6, 8
        x = jnp.asarray(rng.normal(size=(B, F)), jnp.float32)
        carry = (
            jnp.asarray(rng.normal(size=(B, H)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, H)), jnp.float32),
        )
        ref_cell = nn.OptimizedLSTMCell(H)
        fused_cell = PallasLSTMCell(H)
        params = ref_cell.init(jax.random.key(0), carry, x)
        (c_ref, h_ref), _ = ref_cell.apply(params, carry, x)
        (c_f, h_f), _ = fused_cell.apply(params, carry, x)
        diff = max(
            float(jnp.max(jnp.abs(c_ref - c_f))),
            float(jnp.max(jnp.abs(h_ref - h_f))),
        )
        if diff > 1e-6:
            return "FAIL", (
                f"fused Pallas LSTM diverges from the flax cell by "
                f"{diff:.2e} on the fixed probe (tolerance 1e-6)"
            )
        return "ok", (
            "bf16 parity gate passed, bf16 PopArt stats refused, "
            f"fused LSTM within {diff:.1e} of flax"
        )
    except Exception:
        return "FAIL", (
            f"mixed-precision probe raised:\n{traceback.format_exc()}"
        )


def _obs_fanin_child(descriptor, slot: int, label: str) -> None:
    """Child body for the observability fan-in probe: run the real
    worker-side telemetry path (own registry + recorder, seqlock
    publish through the shared-memory snapshot lane) exactly like an
    env-pool worker does. Module-level so forkserver/spawn can pickle
    it."""
    import time as _time

    from torched_impala_tpu.telemetry import WorkerTelemetry

    wt = WorkerTelemetry(descriptor, slot, label)
    try:
        t0 = _time.monotonic_ns()
        wt.record_step(t0, 1_000_000, "a0u0", 1)
        wt.publish()
    finally:
        wt.close()


def _check_observability() -> tuple[str, str]:
    """Observability-plane self-check (docs/OBSERVABILITY.md, ISSUE 17):
    (a) a 2-process fan-in roundtrip — two real child processes publish
    worker telemetry through the shared-memory snapshot lane and the
    aggregated snapshot must carry both proc<h>w<w>/ re-prefixed
    blocks; (b) a seeded SLO breach must trip the burn-rate engine
    within one slow window and set the alerts/firing_* gauge an
    AlertSignal reads; (c) the merged multi-process trace export must
    validate against the Chrome trace schema with per-process rows."""
    import json
    import tempfile

    try:
        from torched_impala_tpu.control import AlertSignal
        from torched_impala_tpu.runtime.env_pool import _CTX
        from torched_impala_tpu.telemetry import (
            AlertEngine,
            FlightRecorder,
            Registry,
            SloSpec,
            SnapshotLane,
            TelemetryAggregator,
            export_merged_trace,
            proc_label,
        )
        from torched_impala_tpu.telemetry.tracing import (
            validate_chrome_trace,
        )

        # (a) 2-process fan-in roundtrip through the shm lane.
        lane = SnapshotLane(2)
        agg = TelemetryAggregator()
        try:
            labels = [proc_label(0, w) for w in range(2)]
            for w, label in enumerate(labels):
                agg.attach(label, lane, w)
            procs = [
                _CTX.Process(
                    target=_obs_fanin_child,
                    args=(lane.descriptor(), w, labels[w]),
                )
                for w in range(2)
            ]
            for p in procs:
                p.start()
            for p in procs:
                p.join(timeout=60)
                assert p.exitcode == 0, f"fan-in child rc={p.exitcode}"
            local = Registry()
            local.counter("doctor/parent_series").inc()
            snap = agg.aggregated_snapshot(local.snapshot())
            for label in labels:
                key = f"telemetry/{label}/pool/env_steps"
                assert key in snap, (key, sorted(snap)[:20])
            assert "telemetry/doctor/parent_series" in snap
            # Harvest (retire) each worker's last payload so the trace
            # dumps survive the lane teardown, like pool.close() does.
            for w, label in enumerate(labels):
                agg.retire(label, lane.read(w))
                agg.detach(label)
            dumps = agg.trace_dumps()
            assert len(dumps) == 2, len(dumps)
        finally:
            lane.close()

        # (b) seeded SLO breach fires within one slow window.
        reg = Registry()
        spec = SloSpec(
            name="doctor_probe",
            key="doctor/probe_ms",
            objective=10.0,
            budget=0.1,
            fast_window_s=1.0,
            slow_window_s=5.0,
        )
        engine = AlertEngine([spec], registry=reg)
        t = 100.0
        fired_at = None
        while t < 105.0 + 1e-9:  # one slow window of sustained breach
            newly = engine.evaluate(
                {"telemetry/doctor/probe_ms": 50.0}, now=t
            )
            if newly and fired_at is None:
                fired_at = t - 100.0
            t += 0.25
        assert fired_at is not None, "breach never fired"
        sig = AlertSignal("doctor_probe")
        firing = sig.read(reg.snapshot(), t)
        assert firing == 1.0, firing

        # (c) merged trace export schema-validates with process rows.
        rec = FlightRecorder(capacity=64)
        rec.instant("doctor/parent_mark")
        with tempfile.TemporaryDirectory() as td:
            path = f"{td}/doctor_merged.json"
            n = export_merged_trace(path, rec, agg)
            with open(path) as f:
                doc = json.load(f)
            validate_chrome_trace(doc)
            assert n > 0, "merged trace exported no events"
        return "ok", (
            f"2-proc fan-in ok ({len(dumps)} worker dumps), SLO breach "
            f"fired after {fired_at:.2f}s (fast window 1s), merged "
            f"trace schema-valid ({n} events)"
        )
    except Exception:
        return "FAIL", (
            f"observability plane broken:\n{traceback.format_exc()}"
        )


def _check_health() -> tuple[str, str]:
    """Training-health plane self-check (telemetry/health.py, ISSUE 19):
    (a) a tiny jitted loss step with health_diagnostics on emits finite
    health_* series and the pre-clip IS-weight histogram sums to 1;
    (b) a seeded logit collapse (near-one-hot policy) is caught — the
    entropy gauge lands under the SloSpec floor, the burn-rate engine
    fires alerts/firing_entropy_collapse, and a postmortem bundle is
    written; (c) the bundle round-trips through tools/postmortem.py
    with entropy_collapse as the first-breach signal."""
    import math
    import os
    import sys as _sys
    import tempfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in _sys.path:
        _sys.path.insert(0, repo)
    try:
        import jax
        import jax.numpy as jnp

        from tools import postmortem as pm_tool
        from torched_impala_tpu.ops.losses import (
            ImpalaLossConfig,
            impala_loss,
        )
        from torched_impala_tpu.telemetry import (
            FlightRecorder,
            HealthMonitor,
            PostmortemWriter,
            Registry,
        )

        T, B, A = 4, 3, 5
        kt, kb, kv, ka = jax.random.split(jax.random.key(0), 4)
        cfg = ImpalaLossConfig(health_diagnostics=True)

        @jax.jit
        def step(tl, bl, v, bv, a):
            return impala_loss(
                target_logits=tl,
                behaviour_logits=bl,
                values=v,
                bootstrap_value=bv,
                actions=a,
                rewards=jnp.ones((T, B)),
                discounts=jnp.full((T, B), 0.99),
                config=cfg,
            )

        # (a) healthy random step: every health_* series finite, the
        # log-rho histogram bins a full distribution.
        out = step(
            jax.random.normal(kt, (T, B, A)),
            jax.random.normal(kb, (T, B, A)),
            jax.random.normal(kv, (T, B)),
            jnp.zeros((B,)),
            jax.random.randint(ka, (T, B), 0, A),
        )
        health = {
            k: float(v)
            for k, v in out.logs.items()
            if k.startswith("health_")
        }
        assert health, "diagnostics on but no health_* keys emitted"
        bad = {k: v for k, v in health.items() if not math.isfinite(v)}
        assert not bad, f"non-finite health series: {bad}"
        hist = sum(v for k, v in health.items() if "logrho_bin" in k)
        assert abs(hist - 1.0) < 1e-5, f"histogram mass {hist}"

        # (b) seeded logit collapse: near-one-hot logits leave entropy
        # ~0, far under health_slo_specs' 0.05 floor.
        collapsed = step(
            jnp.full((T, B, A), -20.0).at[..., 0].set(20.0),
            jax.random.normal(kb, (T, B, A)),
            jax.random.normal(kv, (T, B)),
            jnp.zeros((B,)),
            jnp.zeros((T, B), jnp.int32),
        )
        ent = float(collapsed.logs["health_entropy_mean"])
        assert ent < 0.05, f"collapse not caught (entropy {ent})"

        with tempfile.TemporaryDirectory() as td:
            reg = Registry()
            rec = FlightRecorder(capacity=32)
            rec.instant("doctor/health_mark")
            mon = HealthMonitor(
                registry=reg,
                recorder=rec,
                postmortem=PostmortemWriter(td, recorder=rec),
            )
            mon.bind_context(
                config={"probe": "doctor"},
                get_counters=lambda: {"num_steps": 1},
            )
            logs = {k: float(v) for k, v in collapsed.logs.items()}
            fired: list = []
            t = 50.0
            for i in range(140):  # sustain past the 30s fast window
                logs["num_steps"] = i
                fired += mon.observe(logs, now=t)
                t += 0.5
            assert "entropy_collapse" in fired, f"never fired: {fired}"
            assert mon.bundles, "alert fired but no bundle written"
            fired_after = None
            for name, info in mon.first_breach.items():
                if name == "entropy_collapse":
                    fired_after = info["t"]

            # (c) round-trip through the CLI renderer. The collapsed
            # batch legitimately trips sibling alerts too (one-hot
            # logits also saturate rho), so compare as sets and render
            # the entropy bundle specifically.
            bundles = pm_tool.list_bundles(td)
            assert set(bundles) == set(mon.bundles), (
                bundles,
                mon.bundles,
            )
            bundle = pm_tool.load_bundle(mon.bundles[0])
            head = pm_tool.first_breach_signal(bundle["manifest"])
            assert head == "entropy_collapse", head
            report = pm_tool.render_report(bundle)
            assert "FIRST BREACH: entropy_collapse" in report
            assert "health/entropy_mean" in report
        return "ok", (
            f"{len(health)} in-step series finite (histogram mass "
            f"{hist:.4f}), seeded logit collapse fired "
            f"entropy_collapse (entropy {ent:.2e}, first breach at "
            f"t={fired_after}), bundle round-tripped through "
            f"tools/postmortem.py"
        )
    except Exception:
        return "FAIL", (
            f"training-health plane broken:\n{traceback.format_exc()}"
        )


def _check_multihost() -> tuple[str, str]:
    """Pod-slice simulation self-check (docs/MULTIHOST.md, ISSUE 18):
    launch a REAL 2-process cluster through the simulated-host harness
    (parallel/simhost.py + runtime/distributed.py — each child is its
    own jax controller with process actors over shm planes) and assert
    (a) the global batch assembles from host-local shards: the two
    local batch halves sum to the spec's global batch and both
    controllers executed the same global program (identical loss
    streams); (b) the param publish fan-out agrees — every host's
    ParamStore reports the same version; (c) shutdown is clean: both
    hosts exit 0 and no shared-memory plane (env-pool lanes, telemetry
    snapshot lanes) outlives the cluster in /dev/shm."""
    try:
        from torched_impala_tpu.runtime import distributed

        shm_dir = "/dev/shm"

        def shm_names() -> set:
            try:
                return set(os.listdir(shm_dir))
            except OSError:
                return set()

        before = shm_names()
        spec = distributed.DistSpec(
            num_hosts=2,
            devices_per_host=1,
            total_steps=2,
            batch_size=4,
            unroll_length=3,
            num_actors=1,
            envs_per_actor=2,
            actor_mode="process",
            seed=7,
        )
        res = distributed.launch_cluster(spec, timeout=240)
        assert res.ok, res.describe()
        payloads = [h.results()[-1] for h in res.hosts]
        assert len(payloads) == 2, len(payloads)
        b_local = [p["local_batch_size"] for p in payloads]
        assert sum(b_local) == spec.batch_size, (b_local, spec.batch_size)
        losses = [tuple(p["losses"]) for p in payloads]
        assert losses[0] and losses[0] == losses[1], losses
        versions = sorted({p["publish_version"] for p in payloads})
        assert len(versions) == 1 and versions[0] >= 1, versions
        leaked = shm_names() - before
        assert not leaked, f"shm planes leaked: {sorted(leaked)}"
        return "ok", (
            f"2-host cluster ok in {res.duration_s:.1f}s: local batches "
            f"{b_local} -> global {spec.batch_size}, publish version "
            f"agreed at {versions[0]}, lockstep losses over "
            f"{len(losses[0])} steps, no leaked shm planes"
        )
    except Exception:
        return "FAIL", (
            f"multi-host harness broken:\n{traceback.format_exc()}"
        )


def run_doctor(config_name: str | None = None) -> int:
    print("== torched_impala_tpu doctor ==")
    print(f"python {sys.version.split()[0]}")
    required_ok = True
    for mod, required in (
        ("jax", True),
        ("flax", True),
        ("optax", True),
        ("gymnasium", True),
        ("cv2", False),  # needed by the atari family only
        ("ale_py", False),
        ("procgen", False),
        ("deepmind_lab", False),
    ):
        status, detail = _probe_module(mod)
        if status == "ok":
            tag = "ok"
        elif status == "broken":
            tag = f"BROKEN: {detail}"
        else:
            tag = "MISSING (required)" if required else "missing"
        required_ok &= status == "ok" or not required
        print(f"  dep {mod:14s} {detail if status == 'ok' else '-':12s} [{tag}]")
    if not required_ok:
        print("doctor: FAIL (required dependency missing)")
        return 1

    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    devices = jax.devices()
    y = jax.jit(lambda x: x @ x)(jnp.ones((128, 128))).block_until_ready()
    del y
    print(
        f"  accelerator: {devices} jit-ok "
        f"({time.perf_counter() - t0:.1f}s)"
    )

    status, detail = _check_telemetry()
    print(f"  telemetry  [{status}] {detail}")
    failed = status == "FAIL"
    status, detail = _check_tracing()
    print(f"  tracing    [{status}] {detail}")
    failed |= status == "FAIL"
    status, detail = _check_traj_ring()
    print(f"  traj ring  [{status}] {detail}")
    failed |= status == "FAIL"
    status, detail = _check_replay()
    print(f"  replay     [{status}] {detail}")
    failed |= status == "FAIL"
    status, detail = _check_mesh_feed()
    print(f"  mesh feed  [{status}] {detail}")
    failed |= status == "FAIL"
    status, detail = _check_resilience()
    print(f"  resilience [{status}] {detail}")
    failed |= status == "FAIL"
    status, detail = _check_serving()
    print(f"  serving    [{status}] {detail}")
    failed |= status == "FAIL"
    status, detail = _check_fleet()
    print(f"  fleet      [{status}] {detail}")
    failed |= status == "FAIL"
    status, detail = _check_lint()
    print(f"  lint       [{status}] {detail}")
    failed |= status == "FAIL"
    status, detail = _check_sharding()
    print(f"  sharding   [{status}] {detail}")
    failed |= status == "FAIL"
    status, detail = _check_perf()
    print(f"  perf       [{status}] {detail}")
    failed |= status == "FAIL"
    status, detail = _check_control()
    print(f"  control    [{status}] {detail}")
    failed |= status == "FAIL"
    status, detail = _check_mixed_precision()
    print(f"  mixed precision [{status}] {detail}")
    failed |= status == "FAIL"
    status, detail = _check_observability()
    print(f"  observability [{status}] {detail}")
    failed |= status == "FAIL"
    status, detail = _check_health()
    print(f"  training health [{status}] {detail}")
    failed |= status == "FAIL"
    status, detail = _check_multihost()
    print(f"  multihost  [{status}] {detail}")
    failed |= status == "FAIL"
    for family in ("cartpole", "atari", "procgen", "dmlab"):
        status, detail = _check_env_contract(family)
        print(f"  env {family:10s} [{status}] {detail}")
        failed |= status == "FAIL"

    if config_name is not None:
        status, detail = _train_probe(config_name)
        print(f"  train {config_name:8s} [{status}] {detail}")
        failed |= status == "FAIL"

    print(f"doctor: {'FAIL' if failed else 'PASS'}")
    return 1 if failed else 0
