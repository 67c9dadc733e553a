"""Lint gate: the suite runs ``tools/lint.sh`` (ruff when present,
stdlib syntax gate otherwise) so style/correctness-floor violations fail
CI the same way a broken test does."""

import os
import subprocess
import sys


def test_lint_gate_passes():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "tools", "lint.sh")
    r = subprocess.run(["bash", script], cwd=repo, capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, "lint gate failed:\n%s\n%s" % (r.stdout,
                                                             r.stderr)


def test_lint_gate_catches_syntax_error(tmp_path):
    """Whichever backend the gate picked, it must actually reject broken
    code — guard against a silently-vacuous gate."""
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    for cmd in (["ruff", "check", str(bad)],
                [sys.executable, "-m", "compileall", "-q", str(bad)]):
        try:
            r = subprocess.run(cmd, capture_output=True, timeout=60)
        except FileNotFoundError:
            continue
        if b"No module named" in r.stderr:
            continue
        assert r.returncode != 0
        return
    raise AssertionError("no lint backend available at all")


_SLOW_FIRST_WRITE = """
import sys, threading, time
sys.argv = ["chaos_fleet.py", "--smoke"]
sys.path.insert(0, %r)
start, first = threading.Thread.start, [True]

def slow_start(thread):
    if first[0] and thread.name == "ckpt-writer":
        first[0] = False
        write = thread._target

        def delayed(*a, **k):
            time.sleep(2.0)
            return write(*a, **k)

        thread._target = delayed
    return start(thread)

threading.Thread.start = slow_start
import chaos_fleet
sys.exit(chaos_fleet.main())
"""


def test_fleet_smoke_holds_when_its_first_publish_lands_late():
    """Section 6 of the gate under a loaded machine (the one failure of
    the whole run at PR 36: six xdist workers, ``swap_ok`` false): the
    trainer's periodic publish writes in the background, and the swap
    drill's clean round used to look for that version before it was on
    disk. The drill now waits for the write, however late it lands."""
    import json

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c",
         _SLOW_FIRST_WRITE % os.path.join(repo, "tools")],
        cwd=repo, capture_output=True, text=True, timeout=240,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo))
    summary = json.loads(r.stdout.strip().splitlines()[-1])
    assert summary["swap"]["clean_round"] and summary["swap_ok"], summary
    assert r.returncode == 0 and summary["verdict"] == "ok"
