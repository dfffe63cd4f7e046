"""The trace reduction on a small recorded trace (``fixtures/``, a v5e,
2026-09-27): five calls of one compiled program of ~90 us with 20 ms host
sleeps between them, under the benchmark's own host spans."""

import os

import pytest

from benchlib import trace
from conftest import BENCH

FIXTURE = os.path.join(BENCH, "fixtures", "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(FIXTURE)


def test_busy_is_the_union_of_device_intervals(reduced):
    # 16 whole executions of ~90.2 us lie inside the 87 ms window; the
    # copy-start/copy-done operations overlap the fusion and must not be
    # counted twice: busy stays under the programs' own device time.
    prog = reduced["programs"]["_lambda"]
    assert prog["count"] == 16
    assert prog["device_s"] == pytest.approx(16 * 90.2e-6, rel=1e-3)
    assert reduced["window_s"] == pytest.approx(0.086983794)
    assert 0 < reduced["busy_s"] <= prog["device_s"]
    assert reduced["busy_s"] == pytest.approx(prog["device_s"], rel=1e-3)
    assert trace._union([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == [(0, 3), (5, 6)]


def test_gaps_go_to_the_covering_host_span(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) <= {"bench.idle_sleep", "bench.step", "(no span)"}
    # four sleeps of 20 ms lie in the window, and the device idles through
    # each; the rest of the idle time is the host inside bench.step.
    assert gaps["bench.idle_sleep"] == pytest.approx(0.043, abs=0.002)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"]
    )


def test_operation_names_keep_their_program_prefix(reduced):
    names = [n for n, _ in reduced["device_ops"]]
    assert names[0] == "_lambda/fusion bf16[]"
    assert all(n.startswith("_lambda/") for n in names)
    assert trace.program_name("jit__decode_chunk(123)") == "_decode_chunk"
    assert trace.program_name("jit_step_fn(9)") == "step_fn"
    assert trace.op_name(
        "%copy-start.6 = (s32[1,128]{1,0:T(1,128)S(1)}, u32[]{:S(2)}) "
        "copy-start(s32[1,128]{1,0:T(1,128)} %tokens.1)"
    ) == "copy-start.6 s32[1,128]"


def test_a_trace_with_nothing_to_read_gives_nothing(tmp_path):
    # A CPU trace has no TPU plane: the reduction returns None and the
    # harness leaves the trace's metrics out.
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    assert trace.reduce(trace.find_xplane(str(tmp_path))) is None
