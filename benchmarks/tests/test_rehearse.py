"""CPU rehearsal of the harness: every driver end to end at each file's
``tiny`` block, the last line's key set, a compile inside the window, and
additions made as data alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _run(bench_dir, workload, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(bench_dir, "run.py"), "--workload",
         workload, "--seed", str(2**31 + 5), "--seconds", "2", *extra],
        capture_output=True, text=True, env=env, timeout=600,
    )
    try:
        return p, json.loads(p.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError):
        return p, None  # no result line


def _cells():
    """Every cell file, in BENCHMARK.json or waiting for a later PR."""
    return sorted(
        f[: -len(".json")] for f in os.listdir(os.path.join(BENCH, "workloads"))
    )


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", _cells())
def test_cell_rehearses(cell, trace):
    p, line = _run(BENCH, cell, "--trace", trace, "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    assert list(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    with open(os.path.join(BENCH, "workloads", f"{cell}.json")) as f:
        want = json.load(f)["per_layer" if trace == "1" else "end_to_end"]
    # no device on the CPU: the trace's metrics have nothing to read and
    # are left out; nothing carries a value.
    assert set(line["metrics"]) <= set(want) and line["metrics"]
    assert all(m["value"] is None for m in line["metrics"].values())
    assert "platform: cpu" in p.stdout


def test_no_tpu_no_result():
    p, line = _run(BENCH, _cells()[0], "--trace", "0")
    assert p.returncode == 2 and line is None
    assert "refusing to run" in p.stderr


def _overlay(tmp_path):
    dst = tmp_path / "benchmarks"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns("__pycache__"))
    return str(dst)


def _edit(path, fn):
    with open(path) as f:
        obj = json.load(f)
    fn(obj)
    with open(path, "w") as f:
        json.dump(obj, f)


def test_a_compile_inside_the_window_is_incorrect(tmp_path):
    # Data alone: a copy of the chat cell that warms no prefill bucket but
    # one, so the window's first other length compiles a program.
    bench = _overlay(tmp_path)
    src = os.path.join(bench, "workloads", "gpt2-xl.chat.json")
    new = os.path.join(bench, "workloads", "gpt2-xl.coldchat.json")
    shutil.copy(src, new)
    _edit(new, lambda w: w["tiny"].update(warm_prompts=[16]))
    p, line = _run(bench, "gpt2-xl.coldchat", "--trace", "0", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    assert line["correct"] is False
    assert "compiled inside pre-roll + window" in p.stdout


def test_additions_are_data(tmp_path):
    # One configuration file, one cell file and one metric file (an
    # existing reader), no existing file changed: the new cell runs.
    bench = _overlay(tmp_path)
    before = {
        os.path.relpath(os.path.join(d, f), bench): os.path.getmtime(os.path.join(d, f))
        for d, _, fs in os.walk(bench) for f in fs
    }
    cfg = os.path.join(bench, "configs", "mistral-wide.json")
    shutil.copy(os.path.join(bench, "configs", "mistral-7b-v0.3.json"), cfg)
    _edit(cfg, lambda c: c["tiny"].update(num_key_value_heads=4))
    cell = os.path.join(bench, "workloads", "mistral-wide.docs2.json")
    shutil.copy(os.path.join(bench, "workloads", "mistral-7b-v0.3.docs.json"), cell)

    def retarget(w):
        w["config"] = "mistral-wide"
        w["per_layer"] = w["per_layer"] + ["evictions_per_s"]
        w["tiny"]["traffic"]["clients"] = 6

    _edit(cell, retarget)
    with open(os.path.join(bench, "metrics", "evictions_per_s.json"), "w") as f:
        json.dump({"unit": "1/s", "reader": "ratio",
                   "args": {"num": "prefix_evictions", "den": "window_s"}}, f)
    p, line = _run(bench, "mistral-wide.docs2", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    assert line["correct"] is True and "evictions_per_s" in line["metrics"]
    after = {
        k: os.path.getmtime(os.path.join(bench, k)) for k in before
    }
    assert after == before
