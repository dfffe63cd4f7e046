"""chip_smoke.py off the chip: it refuses, and its control flow holds.

The real run needs a TPU and goes through the chip tool (README, "Build /
test"); here only what a CPU can say — the default invocation fails without
a result where JAX finds no accelerator or the repo is not around it, and
(slow lane) the same phases run end to end at a toy size.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _run(args, cwd, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=timeout,
    )


def _results(stdout):
    return [ln for ln in stdout.splitlines() if ln.startswith('{"ok"')]


def test_default_invocation_refuses_a_cpu_backend():
    r = _run([SCRIPT], REPO, 120)
    assert r.returncode == 2, r.stderr[-2000:]
    assert "not 'tpu'" in r.stderr
    assert "platform=cpu" in r.stdout  # it says what it found ...
    assert not _results(r.stdout)  # ... and prints no result
    assert "== " not in r.stdout  # no phase ran


def test_fails_without_the_repo_around_it(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run(["chip_smoke.py"], str(tmp_path), 120)
    assert r.returncode != 0
    assert not _results(r.stdout)


@pytest.mark.slow
def test_tiny_dry_run_on_the_cpu_passes_every_phase():
    code = (
        "import sys, chip_smoke; "
        "sys.exit(chip_smoke.run(chip_smoke.TINY, require_tpu=False))"
    )
    r = _run(["-c", code], REPO, 900)
    assert r.returncode == 0, r.stdout[-4000:]
    (last,) = _results(r.stdout)
    *_, report, final = r.stdout.splitlines()
    assert last == final
    # The result line holds exactly the contract's keys, and is never "ok"
    # off the chip, whatever passed.
    doc = json.loads(last)
    assert set(doc) == {"ok", "device"} and doc["ok"] is False
    assert set(doc["device"]) == {"platform", "kind", "count"}
    assert doc["device"]["platform"] == "cpu"
    assert isinstance(doc["device"]["count"], int)
    # The per-phase report is the line before it.
    assert report.startswith("report: ")
    detail = json.loads(report[len("report: "):])
    assert detail["failed"] == []
    assert {p["status"] for p in detail["phases"].values()} <= {
        "ok", "skipped: 1 device(s)"
    }
