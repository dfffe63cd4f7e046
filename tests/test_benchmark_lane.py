"""The benchmark's accepted cells, guarded in tier-1.

``benchmarks/`` is the repo's one harness, and the driver measures on the
chip every cell ``BENCHMARK.json`` lists under ``workloads``.  A program
change that breaks one of those cells (the train step's protocol, a scope, a
counter, a family's entry point) should fail here, on the CPU, and not first
in the driver's chip check.  So each accepted cell rehearses (the harness's
own CPU path: the file's ``tiny`` block, ``correct`` decided, no time
reported) and resolves every file it names.  Cells that wait outside
``BENCHMARK.json`` and the rest of ``benchmarks/tests`` are run by hand.

CPU: correctness and counts only, never a time.
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run as harness  # noqa: E402  (benchmarks/run.py)
from benchlib import peaks  # noqa: E402

KEYS = ["correct", "attempted", "failed", "metrics", "device"]

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def _run(*args, timeout=600):
    """``benchmarks/run.py`` in a process of its own, on the CPU, with one
    device as on the one-chip machine (tier-1's conftest forces eight)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *args],
        capture_output=True, text=True, env=env, timeout=timeout, cwd=REPO,
    )
    try:
        return p, json.loads(p.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError):
        return p, None  # no result line


@pytest.mark.parametrize("cell", CELLS)
def test_accepted_cell_rehearses(cell):
    p, line = _run(
        "--workload", cell, "--seed", str(2**31 + 5), "--seconds", "2",
        "--trace", "0", "--rehearse",
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert list(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    # A CPU reports no time: the metrics are named, none carries a value.
    assert line["metrics"]
    assert all(m["value"] is None for m in line["metrics"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_every_accepted_cell_resolves_its_files(cell):
    """By name, as ``run.py`` finds them: BENCHMARK.json's cell -> its
    configuration's file and ``workloads/<cell>.json`` -> each metric's
    ``metrics/<name>.json`` -> its reader in ``readers/``."""
    (entry,) = [w for w in BENCHMARK["workloads"] if w["name"] == cell]
    (config,) = [
        c for c in BENCHMARK["configs"] if c["name"] == entry["config"]
    ]
    workload = harness.read_json("workloads", f"{cell}.json")
    assert workload["config"] == entry["config"]
    assert os.path.samefile(  # both exist, and are one file
        os.path.join(REPO, config["file"]),
        os.path.join(harness.HERE, "configs", f"{workload['config']}.json"),
    )
    names = workload["end_to_end"] + workload["per_layer"]
    assert names
    for name in names:
        spec = harness.read_json("metrics", f"{name}.json")
        reader = importlib.import_module(f"readers.{spec['reader']}")
        assert reader.__file__.startswith(harness.HERE), (name, reader)
        assert callable(reader.read)


def test_run_refuses_a_cpu_backend():
    """Without ``--rehearse`` the harness times nothing off the chip."""
    p, line = _run(
        "--workload", CELLS[0], "--seed", "1", "--seconds", "2",
        "--trace", "0", timeout=300,
    )
    assert p.returncode == 2, p.stderr[-2000:]
    assert "refusing to run" in p.stderr
    assert line is None  # no result line


def test_peaks_known_and_unknown_device():
    """A device with no recorded peak is an error, not a missing MFU."""
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v9 imaginary")


def test_docs_gate_passes():
    """Every link and every code reference of README.md and docs/*.md
    points at a file that exists."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "check_docs.py")],
        capture_output=True, text=True, timeout=120,
    )
    assert p.returncode == 0, p.stdout[-4000:]
