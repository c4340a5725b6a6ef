"""`chip_smoke.py`'s contract where no chip is needed to hold it.

The chip run itself is made through the chip tool; here only what the
script promises on ANY machine: it never reports a CPU run as a pass,
and nothing it started is still running when it ends.
"""

import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, env=env, text=True,
        capture_output=True, timeout=300, **kw,
    )


def test_cpu_run_fails_and_says_so():
    proc = _run(["chip_smoke.py"])
    assert proc.returncode != 0, proc.stdout
    lines = [json.loads(l) for l in proc.stdout.splitlines()]
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    # No phase that needs the chip ran, and no child was started.
    assert [l["phase"] for l in lines[:-1]] == ["device", "children", "total"]
    assert lines[1]["found"] == {} and lines[1]["ok"] is True


def test_stop_children_leaves_no_process(tmp_path):
    """The fork server and the resource tracker that the env pools start
    outlive their pools by design, and a stray child may ignore SIGTERM:
    all of them are gone when `stop_children` returns."""
    script = tmp_path / "drive.py"
    script.write_text(textwrap.dedent(
        f"""
        import json, subprocess, sys, time
        sys.path.insert(0, {REPO!r})
        import chip_smoke
        from torched_impala_tpu.runtime import env_pool

        if __name__ == "__main__":
            env_pool._preload()
            worker = env_pool._CTX.Process(target=print, args=("worker",))
            worker.start()
            worker.join()
            subprocess.Popen(["sleep", "300"])
            subprocess.Popen([sys.executable, "-c",
                "import signal, time; "
                "signal.signal(signal.SIGTERM, signal.SIG_IGN); "
                "print('deaf', flush=True); time.sleep(300)"],
                stdout=subprocess.PIPE).stdout.readline()
            print(json.dumps(chip_smoke.stop_children(grace_s=2.0)))
        """
    ))
    proc = _run([str(script)])
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    assert out["ok"] and out["left_running"] == [], out
    commands = " | ".join(out["found"].values())
    for name in ("forkserver", "resource_tracker", "sleep 300", "SIG_IGN"):
        assert name in commands, out
    # The helpers stop when asked; only the strays are signalled, and the
    # one that ignores SIGTERM is killed.
    signals = [s["signal"] for s in out["signalled"]]
    assert signals.count("SIGTERM") == 2 and signals.count("SIGKILL") == 1
    assert not [p for p in out["found"] if os.path.exists(f"/proc/{p}")]
