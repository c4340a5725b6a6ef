"""Long-run soak: chaos + SIGKILL-and-resume vs an uninterrupted seed.

VERDICT r2 item 7: a >=1 hour wall-clock CartPole run where the WHOLE
process is periodically SIGKILLed and resumed from its latest checkpoint,
with env-crash chaos injected throughout — asserting that

  1. the frame/step budget lands EXACTLY despite every interruption
     (train's total budget semantics + checkpoint resume),
  2. training survives: the soaked policy's greedy eval matches the
     uninterrupted same-seed baseline's (both runs train the same number
     of steps; async actors make the curves stochastic, so the contract
     is eval-quality parity, not bit-identical curves — the bit-exact
     resume contract is pinned separately by
     tests/test_utils.py resume-twice determinism).

CPU-only by design: this parent never imports jax, every child is started
with JAX_PLATFORMS=cpu in its environment (and `--platform cpu`), so no
process here ever takes a chip — a soak SIGKILLs its children, which is
no way to treat a process that holds one. Phases:

  probe     - short uninterrupted run to measure steps/sec on this host
  baseline  - uninterrupted run at the full budget S (sized so the soak
              phase lasts >= --soak-minutes)
  soak      - same seed, same budget S, `--chaos` env crashes, process
              SIGKILLed every --kill-interval seconds, relaunched with
              --resume until it completes the budget on its own
  verify    - greedy eval of both checkpoints + the assertions above;
              writes docs/evidence/SOAK.md

Usage: python tools/soak.py --out /tmp/soak [--soak-minutes 60]
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(msg: str) -> None:
    print(f"[soak {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def run_cmd(steps: int, ckpt: str, logdir: str, args, chaos: int = 0):
    cmd = [
        sys.executable, "-m", "torched_impala_tpu.run",
        "--config", "cartpole", "--platform", "cpu",
        "--seed", str(args.seed),
        "--total-steps", str(steps),
        "--checkpoint-dir", ckpt,
        "--checkpoint-interval", str(args.checkpoint_interval),
        "--resume",
        "--logger", "jsonl", "--logdir", logdir,
        "--log-every", "25",
    ]
    if chaos:
        cmd += ["--chaos", str(chaos), "--max-actor-restarts", "1000000"]
    return cmd


def cpu_env() -> dict:
    """Children are CPU-only (module docstring): forced in their env."""
    return {**os.environ, "JAX_PLATFORMS": "cpu"}


def launch(cmd, logfile):
    return subprocess.Popen(
        cmd, cwd=REPO, stdout=logfile, stderr=subprocess.STDOUT,
        env=cpu_env(),
    )


def wait_or_kill(proc, kill_after: float) -> tuple[bool, int | None]:
    """Wait up to kill_after seconds; SIGKILL if still running.
    Returns (was_killed, returncode_if_finished)."""
    try:
        rc = proc.wait(timeout=kill_after)
        return False, rc
    except subprocess.TimeoutExpired:
        proc.send_signal(signal.SIGKILL)
        proc.wait()
        return True, None


def latest_step(ckpt: str) -> int:
    code = (
        "from torched_impala_tpu.utils.checkpoint import Checkpointer;"
        f"print(Checkpointer({ckpt!r}).latest_step() or 0)"
    )
    # Retry: the probe can land right after a SIGKILL while the newest
    # checkpoint dir is mid-write; a transient failure must not abort an
    # hour-long soak with a context-free parse error.
    last_err = ""
    for _ in range(3):
        try:
            out = subprocess.run(
                [sys.executable, "-c", code], cwd=REPO,
                capture_output=True, text=True, timeout=120,
                env=cpu_env(),
            )
        except subprocess.TimeoutExpired:
            last_err = "probe timed out after 120s"
            continue
        lines = out.stdout.strip().splitlines()
        if out.returncode == 0 and lines:
            try:
                return int(lines[-1])
            except ValueError:
                last_err = f"unparsable stdout: {lines[-1]!r}"
        else:
            last_err = out.stderr.strip()[-300:] or f"rc={out.returncode}"
        time.sleep(5)
    raise RuntimeError(f"checkpoint-step probe failed 3x: {last_err}")


def _meta_path(out: str) -> str:
    return os.path.join(out, "harness_meta.json")


def load_meta(out: str) -> dict:
    """Cross-invocation harness state (cumulative soak wall/kills,
    baseline wall): the --budget resume path must not forget a completed
    phase's counters, or a PASSING soak would re-verify as FAIL."""
    import json

    try:
        with open(_meta_path(out)) as f:
            return json.load(f)
    except Exception:
        return {}


def save_meta(out: str, **kw) -> None:
    import json

    meta = load_meta(out)
    meta.update(kw)
    tmp = _meta_path(out) + ".tmp"
    with open(tmp, "w") as f:
        json.dump(meta, f)
    os.replace(tmp, _meta_path(out))


def eval_ckpt(ckpt: str, args) -> float:
    out = subprocess.run(
        [
            sys.executable, "-m", "torched_impala_tpu.run",
            "--config", "cartpole", "--platform", "cpu",
            "--mode", "eval", "--checkpoint-dir", ckpt,
            "--eval-episodes", str(args.eval_episodes),
            "--eval-max-steps", "500",
        ],
        cwd=REPO, capture_output=True, text=True,
    )
    # Inline nan/inf-safe parse (mirrors sweep.parse_mean_return) — the
    # parent deliberately never imports the package (or jax).
    import re

    m = re.search(r"mean_return=([-+.\w]+)", out.stdout + out.stderr)
    try:
        val = float(m.group(1)) if m else None
    except ValueError:
        val = None
    if out.returncode != 0 or val is None:
        raise RuntimeError(
            f"eval of {ckpt} failed rc={out.returncode}: "
            f"{out.stderr[-400:]}"
        )
    return val


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--out", default="/tmp/soak")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--soak-minutes", type=float, default=60.0)
    p.add_argument("--kill-interval", type=float, default=150.0,
                   help="seconds between SIGKILLs of the training process")
    # Sized so chaos stays ~1 crash/actor/kill-cycle: each crash costs an
    # exponential supervisor backoff (0.5s doubling to 30s per consecutive
    # restart within one process lifetime), so a too-aggressive interval
    # (4000 was ~10 crashes/actor/cycle here) parks the actors in backoff
    # and the run crawls at ~15% speed — measured live on this box.
    p.add_argument("--chaos", type=int, default=25_000,
                   help="each actor env crashes every ~N env steps")
    p.add_argument("--checkpoint-interval", type=int, default=100)
    p.add_argument("--probe-steps", type=int, default=300)
    p.add_argument("--eval-episodes", type=int, default=20)
    p.add_argument("--max-cycles", type=int, default=120,
                   help="hard cap on kill/resume cycles (runaway guard)")
    p.add_argument("--budget", type=int, default=None,
                   help="explicit step budget: skips the probe, and any "
                        "phase whose checkpoint already carries the full "
                        "budget is skipped too — a killed/retuned soak "
                        "HARNESS resumes instead of redoing hours of "
                        "baseline (the training runs were always "
                        "resumable; this makes the harness match)")
    args = p.parse_args()

    os.makedirs(args.out, exist_ok=True)
    t_start = time.time()

    # ---- probe: measure this host's STEADY-state steps/sec ----
    # Two runs against the same checkpoint (the second resumes the first):
    # differencing the walls cancels the constant per-process overhead
    # (jax import + compile), which otherwise understates the steady rate
    # ~5x and undersizes the budget (observed on the mini validation run).
    if args.budget is not None:
        budget = args.budget
        log(f"budget given: {budget} steps (probe skipped)")
        return run_phases(args, budget, t_start)
    probe_dir = os.path.join(args.out, "probe")
    s1, s2 = args.probe_steps, args.probe_steps * 5
    log(f"probe: {s1} then {s2} steps (resumed) to difference out compile")
    walls = []
    with open(os.path.join(args.out, "probe.log"), "w") as f:
        for steps in (s1, s2):
            t0 = time.time()
            proc = launch(
                run_cmd(steps, os.path.join(probe_dir, "ck"),
                        probe_dir, args),
                f,
            )
            rc = proc.wait()
            walls.append(time.time() - t0)
            if rc != 0:
                log(f"probe ({steps} steps) FAILED rc={rc}")
                return 1
    # walls[0] = overhead + s1/rate; walls[1] = overhead + (s2-s1)/rate
    # (the second run resumes at s1 and trains s2-s1 more), so:
    #   rate = (s2 - 2*s1) / (walls[1] - walls[0])
    dw = walls[1] - walls[0]
    rate = (
        (s2 - 2 * s1) / dw if dw > 1e-3 else s2 / walls[1]  # fallback
    )
    budget = max(s2, int(rate * args.soak_minutes * 60))
    budget = (budget // args.checkpoint_interval) * args.checkpoint_interval
    log(
        f"probe: walls={walls[0]:.0f}s/{walls[1]:.0f}s -> steady "
        f"{rate:.1f} steps/s; budget={budget} steps"
    )
    return run_phases(args, budget, t_start)


def _phase_done(ckpt: str, budget: int) -> bool:
    try:
        return latest_step(ckpt) >= budget
    except RuntimeError:
        return False


def run_phases(args, budget: int, t_start: float) -> int:
    # ---- baseline: uninterrupted, same seed, same budget ----
    base_dir = os.path.join(args.out, "baseline")
    base_wall = None
    if _phase_done(os.path.join(base_dir, "ck"), budget):
        base_wall = load_meta(args.out).get("base_wall")
        log("baseline: already complete at this budget; skipping "
            f"(recorded wall: {base_wall})")
    else:
        log(f"baseline: {budget} steps uninterrupted")
        t0 = time.time()
        with open(os.path.join(args.out, "baseline.log"), "a") as f:
            proc = launch(
                run_cmd(
                    budget, os.path.join(base_dir, "ck"), base_dir, args
                ),
                f,
            )
            rc = proc.wait()
        base_wall = time.time() - t0
        if rc != 0:
            log(f"baseline FAILED rc={rc}")
            return 1
        save_meta(args.out, base_wall=base_wall)
    base_step = latest_step(os.path.join(base_dir, "ck"))
    log(f"baseline: complete (final checkpoint step={base_step})")

    # ---- soak: chaos + SIGKILL-and-resume until the budget completes ----
    soak_dir = os.path.join(args.out, "soak")
    ck = os.path.join(soak_dir, "ck")
    # Cumulative across harness invocations (--budget resume): a soak
    # whose phase already completed must keep its kill/duration record.
    meta = load_meta(args.out)
    kills = int(meta.get("soak_kills", 0))
    prior_wall = float(meta.get("soak_wall", 0.0))
    t_soak = time.time()
    rc = 0 if _phase_done(ck, budget) else None
    if rc == 0:
        log(f"soak: already complete at this budget; skipping "
            f"({kills} kills, {prior_wall / 60:.1f} min recorded)")
    last_step = -1
    stagnant = 0
    soak_log = open(os.path.join(args.out, "soak_train.log"), "a")
    for cycle in range(args.max_cycles if rc is None else 0):
        proc = launch(
            run_cmd(budget, ck, soak_dir, args, chaos=args.chaos), soak_log
        )
        killed, rc = wait_or_kill(proc, args.kill_interval)
        elapsed = (time.time() - t_soak) / 60
        if not killed:
            log(f"soak cycle {cycle}: process finished rc={rc} "
                f"({elapsed:.1f} min elapsed)")
            if rc == 0:
                break
            soak_log.close()
            raise SystemExit(f"soak training crashed on its own: rc={rc}")
        kills += 1
        step_now = latest_step(ck)
        save_meta(
            args.out,
            soak_kills=kills,
            soak_wall=prior_wall + (time.time() - t_soak),
        )
        log(f"soak cycle {cycle}: SIGKILLed at step~{step_now}/{budget} "
            f"({elapsed:.1f} min, {kills} kills)")
        # A kill interval shorter than process startup + the first
        # checkpoint save makes NO cycle ever advance (observed on a
        # mini run with an 18s interval) — fail fast with the cause
        # instead of spinning silently to max-cycles.
        if step_now <= last_step:
            stagnant += 1
            if stagnant >= 5:
                soak_log.close()
                raise SystemExit(
                    f"soak made no checkpoint progress for {stagnant} "
                    f"consecutive cycles (stuck at step {step_now}): "
                    f"--kill-interval {args.kill_interval:.0f}s is likely "
                    "shorter than process startup + the first "
                    "--checkpoint-interval save"
                )
        else:
            stagnant = 0
        last_step = step_now
        if step_now >= budget:
            # Killed between final checkpoint and exit; one clean lap to
            # let the run terminate normally.
            continue
    soak_log.close()
    soak_wall = prior_wall + (time.time() - t_soak)
    save_meta(args.out, soak_kills=kills, soak_wall=soak_wall)
    if rc != 0:
        log("soak never completed inside max-cycles")
        return 1

    # ---- verify ----
    soak_step = latest_step(ck)
    log(f"soak: done in {soak_wall / 60:.1f} min, {kills} kills, "
        f"final checkpoint step={soak_step}")
    base_eval = eval_ckpt(os.path.join(base_dir, "ck"), args)
    soak_eval = eval_ckpt(ck, args)
    log(f"eval: baseline={base_eval:.1f} soak={soak_eval:.1f}")

    budget_exact = (soak_step == budget) and (base_step == budget)
    survived = soak_wall >= args.soak_minutes * 60 * 0.9 and kills >= 10
    # CartPole-v1 greedy eval: 500 is solved; the parity bar is the
    # baseline's quality minus slack for the async-actor stochasticity.
    quality = soak_eval >= max(400.0, 0.8 * base_eval)

    verdict = "PASS" if (budget_exact and survived and quality) else "FAIL"
    report = f"""# Chaos + SIGKILL-and-resume soak ({verdict})

VERDICT r2 item 7 evidence. Command: `python tools/soak.py` (CPU-only:
every child runs with JAX_PLATFORMS=cpu).

| | baseline (uninterrupted) | soak (chaos + kills) |
|---|---|---|
| budget (learner steps) | {budget} | {budget} |
| final checkpoint step | {base_step} | {soak_step} |
| wall clock | {f"{base_wall / 60:.1f} min" if base_wall else "n/a (prior invocation, wall not recorded)"} | {soak_wall / 60:.1f} min |
| SIGKILLs of the whole process | 0 | {kills} |
| env chaos | off | every ~{args.chaos} env steps/actor |
| greedy eval ({args.eval_episodes} eps, cap 500) | {base_eval:.1f} | {soak_eval:.1f} |

- Budget exactness: {'OK' if budget_exact else 'VIOLATED'} — both runs'
  final checkpoints landed on exactly the requested step budget; every
  SIGKILL resumed from the latest complete checkpoint and the total
  budget semantics re-ran only the remainder.
- Soak duration/kill bar (>= {args.soak_minutes:.0f} min * 0.9,
  >= 10 kills): {'OK' if survived else 'NOT MET'}.
- Quality parity (soak eval >= max(400, 0.8 * baseline)):
  {'OK' if quality else 'NOT MET'}. Curves are stochastic across runs
  (async actors); bit-exact resume is pinned separately by the
  resume-twice determinism test in tests/test_utils.py.

Seed {args.seed}; kill interval {args.kill_interval:.0f}s; checkpoint
interval {args.checkpoint_interval} steps. Raw logs: probe.log,
baseline.log, soak_train.log, and per-phase jsonl curves under the soak
output dir (committed copy: docs/evidence/soak/).
"""
    ev_dir = os.path.join(REPO, "docs", "evidence")
    os.makedirs(ev_dir, exist_ok=True)
    with open(os.path.join(ev_dir, "SOAK.md"), "w") as f:
        f.write(report)
    # Commit-friendly copies of the training curves (small jsonl files).
    import shutil

    curve_dir = os.path.join(ev_dir, "soak")
    os.makedirs(curve_dir, exist_ok=True)
    for phase, d in (("baseline", base_dir), ("soak", soak_dir)):
        src = os.path.join(d, "cartpole.jsonl")
        if os.path.exists(src):
            shutil.copy(src, os.path.join(curve_dir, f"{phase}.jsonl"))
    log(f"report written: docs/evidence/SOAK.md ({verdict})")
    log(f"total wall: {(time.time() - t_start) / 60:.1f} min")
    return 0 if verdict == "PASS" else 1


if __name__ == "__main__":
    sys.exit(main())
