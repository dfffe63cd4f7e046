"""Device time by scope on a recorded trace (``fixtures/scoped_step.*``: a
v5e, 2026-09-30, three train steps of a two-layer scanned GPT-2 with remat,
flash attention, AdamW and the guard, two of them whole inside the window),
the readers on hand-made input, and ``run_scopes.py`` on the CPU."""

import json
import os
import subprocess
import sys

import pytest

import run_scopes
from benchlib import scopes, trace
from conftest import BENCH, ROOT
from readers import program_counter, scope_ms

TRACE = os.path.join(BENCH, "fixtures", "scoped_step.xplane.pb")
MAPS = os.path.join(BENCH, "fixtures", "scoped_step.scopes.json")


def _maps():
    with open(MAPS) as f:
        return {
            prog: {name: tuple(paths) for name, paths in m.items()}
            for prog, m in json.load(f).items()
        }


def _total(p):
    return sum(c["fwd"] + c["bwd"] for c in p["scopes"].values())


@pytest.fixture(scope="module")
def step():
    return scopes.reduce(TRACE, _maps())["step_fn"]


def test_paths_to_scopes():
    loop = "jit(step_fn)/loss/transpose(jvp())/while/body/closed_call/checkpoint"
    assert scopes.scope_of(f"{loop}/mlp/dot_general") == "mlp"
    assert scopes.scope_of(
        f"{loop}/rematted_computation/attn/flash_fwd/pallas_call"
    ) == "attn/flash_fwd"
    assert scopes.scope_of("jit(step_fn)/loss/transpose(jvp(head))/add_any") == "head"
    assert scopes.scope_of("jit(step_fn)/loss/jvp(embed)/jit(_take)/gather") == "embed"
    assert scopes.scope_of(f"{loop}/dynamic_update_slice") == "unscoped"
    assert scopes.scope_of("jit(step_fn)/flash_fwd/pallas_call") == "unscoped"
    assert scopes.scope_of("") == "unscoped"
    # a fusion: the scope most of its paths carry; its own path breaks a tie
    select, update = "jit(f)/guard/jit(_where)/select_n", "jit(f)/optimizer/mul"
    assert scopes.classify((select, select, update, update, update)) == (
        "optimizer", "fwd"
    )
    assert scopes.classify((select, update)) == ("guard", "fwd")
    assert scopes.classify((f"{loop}/dynamic_slice", f"{loop}/mlp/mul")) == (
        "mlp", "bwd"
    )
    assert scopes.classify((f"{loop}/dynamic_slice",)) == ("unscoped", "bwd")
    assert scopes.classify(()) == ("unscoped", "fwd")


def test_self_time_leaves_a_container_its_gaps():
    # a loop 0..100 holding two bodies, a kernel 110..150 into which the end
    # of an asynchronous copy falls, and an operation on its own
    events = [(0, 100), (10, 40), (50, 90), (110, 150), (120, 125), (160, 170)]
    own = scopes.self_times(events)
    assert own == [30, 30, 40, 35, 5, 10]
    assert sum(own) == sum(b - a for a, b in trace._union(events))


def test_scopes_sum_to_busy_time_and_containers_count_once(step):
    assert step["count"] == 2
    assert step["device_s"] == pytest.approx(
        trace.reduce(TRACE)["programs"]["step_fn"]["device_s"]
    )
    assert 0.9 * step["device_s"] < step["busy_s"] <= step["device_s"]
    assert _total(step) == pytest.approx(step["busy_s"], rel=0.01)
    # the two loops alone cover more than half the step: counted whole
    # beside their bodies, as breakdown.device_ops ranks them, the sum
    # would pass the step's own device time
    loops = sum(
        s for name, s in trace.reduce(TRACE, top=400)["device_ops"]
        if name.startswith("step_fn/while")
    )
    assert loops > 0.5 * step["device_s"]
    assert set(step["scopes"]) == {
        "embed", "attn", "attn/flash_fwd", "attn/flash_bwd_fused", "mlp",
        "head", "optimizer", "guard", "unscoped",
    }
    assert step["scopes"]["optimizer"]["bwd"] == 0
    assert step["scopes"]["attn/flash_bwd_fused"]["fwd"] == 0
    assert step["scopes"]["mlp"]["bwd"] > step["scopes"]["mlp"]["fwd"] > 0


def test_kernels_are_found_by_name(step):
    # two layers: forward, forward again under remat, one fused backward
    assert step["kernels"]["flash_fwd"]["calls"] == 2 * 4
    assert step["kernels"]["flash_bwd_fused"]["calls"] == 2 * 2
    fwd = step["scopes"]["attn/flash_fwd"]
    assert fwd["fwd"] == pytest.approx(fwd["bwd"], rel=0.1)
    assert fwd["fwd"] + fwd["bwd"] == pytest.approx(
        step["kernels"]["flash_fwd"]["device_s"], rel=0.02
    )


def test_the_optimizers_pass_is_not_the_guards(step):
    """XLA fuses AdamW's update into the guard's select and names the
    fusion after the select; most of its instructions are the update's."""
    maps = _maps()["step_fn"]
    fused = [n for n in maps if n.startswith("broadcast_select_fusion")]
    assert fused
    for name in fused:
        assert scopes.scope_of(maps[name][0]) == "guard"
        assert scopes.classify(maps[name])[0] == "optimizer"
    assert step["scopes"]["optimizer"]["fwd"] > step["scopes"]["guard"]["fwd"] > 0


def test_an_operation_without_a_path_lands_in_unscoped(step):
    maps = _maps()
    dropped = [n for n in maps["step_fn"] if n.startswith("flash_")]
    for name in dropped:
        del maps["step_fn"][name]
    blind = scopes.reduce(TRACE, maps)["step_fn"]
    assert "attn/flash_fwd" not in blind["scopes"] and not blind["kernels"]
    assert _total(blind) == pytest.approx(_total(step))
    moved = sum(
        sum(step["scopes"][k].values())
        for k in ("attn/flash_fwd", "attn/flash_bwd_fused")
    )
    assert sum(blind["scopes"]["unscoped"].values()) == pytest.approx(
        sum(step["scopes"]["unscoped"].values()) + moved
    )
    assert blind["unscoped_ops"][0][0].startswith("flash_")
    assert "unscoped" in scopes.table("step_fn", blind)
    # a program the trace does not hold, and a trace with no device
    assert scopes.reduce(TRACE, {"other": {}})["other"]["count"] == 0
    assert "no whole execution" in scopes.table(
        "other", scopes.reduce(TRACE, {"other": {}})["other"]
    )


def test_scope_reader_on_hand_made_input():
    prog = {
        "count": 4,
        "scopes": {
            "attn": {"fwd": 0.010, "bwd": 0.030},
            "attn/flash_fwd": {"fwd": 0.004, "bwd": 0.004},
            "attn/flash_bwd_fused": {"fwd": 0.0, "bwd": 0.012},
            "unscoped": {"fwd": 0.001, "bwd": 0.001},
        },
    }
    run = {"trace": {"scopes": {"step_fn": prog}}}
    assert scope_ms.read(run, "step_fn", ["attn"]) == pytest.approx(10.0)
    assert scope_ms.read(
        run, "step_fn",
        ["attn/flash_fwd", "attn/flash_bwd_fused", "attn/flash_bwd_dq"],
    ) == pytest.approx(5.0)
    assert scope_ms.read(run, "step_fn", ["guard"]) == 0.0
    # nothing to read: no trace, a trace not reduced by scope (the parent's
    # program records no map), a program that ran no whole execution
    assert scope_ms.read({"trace": None}, "step_fn", ["attn"]) is None
    assert scope_ms.read({"trace": {"programs": {}}}, "step_fn", ["attn"]) is None
    assert scope_ms.read(run, "_decode_chunk", ["attn"]) is None
    idle = {"trace": {"scopes": {"step_fn": dict(prog, count=0)}}}
    assert scope_ms.read(idle, "step_fn", ["attn"]) is None


def test_counter_reader_reads_the_programs_own_counters():
    from torchdistx_tpu import telemetry
    from torchdistx_tpu.telemetry import perf

    label = "tdx_bench_test_prog"
    assert program_counter.read({}, counter=f"compile.count{{program={label}}}") is None
    assert program_counter.read(
        {}, histogram=f"compile.time_s{{program={label}}}"
    ) is None
    perf.record_compile(label, 1.5)
    perf.record_compile(label, 0.25)
    assert program_counter.read({}, counter=f"compile.count{{program={label}}}") == 2
    assert program_counter.read(
        {}, histogram=f"compile.time_s{{program={label}}}"
    ) == pytest.approx(1.75)
    telemetry.reset()


def test_the_nine_metrics_have_files_and_readers():
    assert len(run_scopes.METRICS) == 9
    for name in run_scopes.METRICS:
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            spec = json.load(f)
        assert set(spec) == {"unit", "reader", "args"}
        assert os.path.exists(
            os.path.join(BENCH, "readers", spec["reader"] + ".py")
        )
        if name.startswith("step_"):
            assert spec["unit"] == "ms"
            assert spec["args"]["program"] in run_scopes.PROGRAMS
            for scope in spec["args"]["scopes"]:
                assert scope.split("/")[0] in scopes.SCOPES + (scopes.UNSCOPED,)


def test_run_scopes_rehearses_with_run_pys_last_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run_scopes.py"), "--workload",
         "gpt2-xl.pretrain", "--seed", str(2**31 + 5), "--seconds", "2",
         "--rehearse"],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device"]
    assert line["correct"] is True
    # the CPU's trace has no device plane, so the step_* metrics have
    # nothing to read; the program's counters are read, and no value shown
    assert set(line["metrics"]) == {
        "materialize_s", "warmup_s", "train_mfu_pct", "train_step_compile_s",
        "train_step_compiles",
    }
    assert all(m["value"] is None for m in line["metrics"].values())
    assert "scope map of train_step: recorded in" in p.stdout
