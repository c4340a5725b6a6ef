"""Atari-57 sweep driver: train/eval one preset across the 57-game suite.

The primary metric is "learner frames/sec/chip on Atari-57; return parity
@200M frames" (BASELINE.md). This driver runs the per-game half: for each
game it invokes the normal CLI (`run.py`) with `--env-id` (which probes
the game's action space and resizes the policy head), a per-game
checkpoint dir, and then greedy eval — collecting one CSV row per game.

Usage (ALE-equipped host):

    python -m torched_impala_tpu.sweep --config pong \
        --out runs/atari57.csv --total-env-frames 200000000 \
        [--games Pong Breakout ...] [--eval-only] [-- <extra run.py flags>]

This parent never imports jax (nor anything that does): a chip belongs to
one process at a time, and each child `run.py` takes it in turn.

Games default to the standard 57-game suite; names are bare (e.g.
"Pong") and expand to `<Game>NoFrameskip-v4`. Each game trains
sequentially (one TPU client at a time); a sweep is resumable at two
levels — games already holding a `mean_return` row in `--out` are
skipped entirely (their rows are preserved), and a partially-trained
game picks its checkpoint back up via run.py `--resume`. Requires
ale-py (gated with a clear error, like envs/factory.py) unless
`--fake-envs` substitutes shape-faithful fakes — which makes the whole
train->checkpoint->eval->CSV pipeline dry-runnable on an emulator-less
host (ADVICE r2 / VERDICT r2 item 5).
"""

from __future__ import annotations

import argparse
import csv
import os
import re
import subprocess
import sys

# The canonical 57-game Atari suite (ALE naming).
ATARI_57 = [
    "Alien", "Amidar", "Assault", "Asterix", "Asteroids", "Atlantis",
    "BankHeist", "BattleZone", "BeamRider", "Berzerk", "Bowling", "Boxing",
    "Breakout", "Centipede", "ChopperCommand", "CrazyClimber", "Defender",
    "DemonAttack", "DoubleDunk", "Enduro", "FishingDerby", "Freeway",
    "Frostbite", "Gopher", "Gravitar", "Hero", "IceHockey", "Jamesbond",
    "Kangaroo", "Krull", "KungFuMaster", "MontezumaRevenge", "MsPacman",
    "NameThisGame", "Phoenix", "Pitfall", "Pong", "PrivateEye", "Qbert",
    "Riverraid", "RoadRunner", "Robotank", "Seaquest", "Skiing",
    "Solaris", "SpaceInvaders", "StarGunner", "Surround", "Tennis",
    "TimePilot", "Tutankham", "UpNDown", "Venture", "VideoPinball",
    "WizardOfWor", "YarsRevenge", "Zaxxon",
]


def game_env_id(game: str) -> str:
    return f"{game}NoFrameskip-v4"


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--config", default="pong",
                   help="preset each game rides (model/optimizer/scale)")
    p.add_argument("--games", nargs="*", default=None,
                   help="subset of games (default: the 57-game suite)")
    p.add_argument("--out", default="atari57.csv")
    p.add_argument("--workdir", default="runs/atari57",
                   help="per-game checkpoints/logs live under here")
    p.add_argument("--total-env-frames", type=int, default=None)
    p.add_argument("--eval-episodes", type=int, default=30)
    p.add_argument("--eval-only", action="store_true",
                   help="skip training; eval existing checkpoints")
    p.add_argument("--fake-envs", action="store_true",
                   help="shape-faithful fake envs instead of ALE (dry-run "
                        "the sweep pipeline on an emulator-less host)")
    p.add_argument("--summarize", action="store_true",
                   help="print a summary of --out instead of running: "
                        "per-game returns, completion/error counts, and "
                        "(with --norm-scores) human-normalized aggregates")
    p.add_argument("--norm-scores", default=None, metavar="JSON",
                   help="path to {game: [random_score, human_score]} for "
                        "human-normalized scoring (the published "
                        "Mnih-2015/IMPALA constants; not baked in so the "
                        "normalization provenance is always explicit)")
    p.add_argument("extra", nargs=argparse.REMAINDER,
                   help="flags after '--' pass through to run.py")
    return p.parse_args(argv)


def require_ale() -> None:
    try:
        import ale_py  # noqa: F401
    except ImportError as e:
        raise SystemExit(
            "the Atari-57 sweep needs ale-py (pip install ale-py "
            "gymnasium[atari]); this host doesn't have it"
        ) from e


def run_game(args, game: str) -> dict:
    """Train (unless --eval-only) then greedy-eval one game; returns the
    CSV row. Failures are captured per game so one crash doesn't kill the
    sweep."""
    env_id = game_env_id(game)
    ckpt = os.path.join(args.workdir, game, "ckpt")
    logdir = os.path.join(args.workdir, game, "logs")
    extra = [a for a in args.extra if a != "--"]
    base = [
        sys.executable, "-m", "torched_impala_tpu.run",
        "--config", args.config, "--env-id", env_id,
        "--checkpoint-dir", ckpt,
    ] + (["--fake-envs"] if args.fake_envs else [])
    row = {"game": game, "env_id": env_id}
    if not args.eval_only:
        cmd = base + [
            "--logger", "jsonl", "--logdir", logdir, "--resume",
        ] + (
            ["--total-env-frames", str(args.total_env_frames)]
            if args.total_env_frames
            else []
        ) + extra
        proc = subprocess.run(cmd, capture_output=True, text=True)
        row["train_rc"] = proc.returncode
        if proc.returncode != 0:
            row["error"] = proc.stderr.strip()[-300:]
            return row
    cmd = base + [
        "--mode", "eval", "--eval-episodes", str(args.eval_episodes),
    ] + extra
    proc = subprocess.run(cmd, capture_output=True, text=True)
    row["eval_rc"] = proc.returncode
    # mean_return is only RECORDED (and the game thereby marked done) on a
    # clean eval of a real checkpoint: run.py exits nonzero when
    # --checkpoint-dir holds no checkpoint, so a missing/corrupt checkpoint
    # can never freeze a random-policy return into the results (ADVICE r2).
    val = parse_mean_return(proc.stderr + proc.stdout)
    if proc.returncode == 0 and val is not None:
        row["mean_return"] = val
    else:
        row["error"] = (
            proc.stderr.strip()[-300:] or "eval output had no mean_return"
        )
    return row


def parse_mean_return(text: str):
    """Extract eval's mean_return, including nan/inf spellings (a plain
    [-\\d.]+ pattern silently skips them and the game re-runs forever —
    ADVICE r2). Returns None when absent/unparsable."""
    m = re.search(r"mean_return=([-+.\w]+)", text)
    if not m:
        return None
    try:
        return float(m.group(1))
    except ValueError:
        return None


def load_prior_rows(path: str) -> tuple[dict, dict]:
    """(done, diagnostics) from a previous sweep: `done` rows carry a
    mean_return — their games are skipped and the rows preserved (a
    resumed sweep must never destroy recorded results). `diagnostics`
    rows (train_rc/error, no return) are preserved for games this
    invocation won't touch; games being re-run get a fresh row instead."""
    done, diag = {}, {}
    if os.path.exists(path):
        with open(path, newline="") as f:
            for row in csv.DictReader(f):
                if row.get("mean_return"):
                    done[row["game"]] = row
                else:
                    diag[row["game"]] = row
    return done, diag


def load_done_rows(path: str) -> dict:
    return load_prior_rows(path)[0]


FIELDS = ["game", "env_id", "train_rc", "eval_rc", "mean_return", "error"]


def rewrite_results(path: str, rows) -> None:
    """Atomically replace the results CSV: the new content lands under a
    temp name and os.replace()s the old file, so no crash window ever
    leaves recorded results truncated (ADVICE r2)."""
    tmp = path + ".tmp"
    with open(tmp, "w", newline="") as f:
        writer = csv.DictWriter(f, fieldnames=FIELDS, extrasaction="ignore")
        writer.writeheader()
        for row in rows:
            writer.writerow(row)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def summarize(args) -> int:
    """Digest a results CSV: per-game table, completion/error counts,
    and — when a {game: [random, human]} table is supplied — the
    human-normalized scores the reference's Atari-57 protocol aggregates
    (HNS = (score - random) / (human - random); median/mean over games
    with both a recorded return and normalization constants)."""
    done, diag = load_prior_rows(args.out)
    games = args.games or ATARI_57
    norms = {}
    if args.norm_scores:
        import json

        with open(args.norm_scores) as f:
            norms = json.load(f)
    import math

    rows = []
    hns = {}
    for game in games:
        if game in done:
            ret = float(done[game]["mean_return"])
            extra = ""
            # Non-finite returns are recorded (so the game isn't re-run
            # forever) but must not poison the HNS aggregate — nan breaks
            # statistics.median's sort silently.
            if not math.isfinite(ret):
                extra = "  (non-finite; excluded from aggregates)"
            elif game in norms:
                rand, human = float(norms[game][0]), float(norms[game][1])
                if human != rand:
                    hns[game] = (ret - rand) / (human - rand)
                    extra = f"  hns={hns[game]:7.3f}"
            rows.append(f"  {game:<20} {ret:12.1f}{extra}")
        elif game in diag:
            err = (diag[game].get("error") or "?")[:50]
            rows.append(f"  {game:<20} {'ERROR':>12}  {err}")
        else:
            rows.append(f"  {game:<20} {'pending':>12}")
    print("\n".join(rows))
    print(
        f"{sum(1 for g in games if g in done)}/{len(games)} done, "
        f"{sum(1 for g in games if g in diag)} error, "
        f"{sum(1 for g in games if g not in done and g not in diag)} "
        "pending"
    )
    if hns:
        import statistics

        print(
            f"human-normalized ({len(hns)} games): "
            f"median {statistics.median(hns.values()):.3f}, "
            f"mean {statistics.mean(hns.values()):.3f}"
        )
    elif args.norm_scores:
        print("human-normalized: no games with both a return and "
              "normalization constants")
    return 0


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.summarize:
        return summarize(args)
    if not args.fake_envs:
        require_ale()
    games = args.games or ATARI_57
    os.makedirs(args.workdir, exist_ok=True)
    os.makedirs(
        os.path.dirname(os.path.abspath(args.out)), exist_ok=True
    )
    done, diag = load_prior_rows(args.out)
    # One full atomic rewrite after every game: the on-disk CSV is always
    # a complete, consistent snapshot (done rows + every game's freshest
    # diagnostic), so neither a crash nor a Ctrl-C can truncate recorded
    # results or lose the failure record of games not yet re-reached.
    rows = dict(done)
    for g, r in diag.items():
        rows.setdefault(g, r)
    if os.path.exists(args.out) or rows:
        rewrite_results(args.out, rows.values())
    for game in games:
        if game in done:
            print(f"{game}: done (kept recorded row)", file=sys.stderr)
            continue
        row = run_game(args, game)
        rows[game] = row
        rewrite_results(args.out, rows.values())
        print(
            f"{game}: return={row.get('mean_return', 'n/a')} "
            f"{'ERROR: ' + row['error'][:80] if 'error' in row else ''}",
            file=sys.stderr,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
