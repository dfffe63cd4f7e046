"""Perf plane (ISSUE 12): compile observatory, HBM ledger and OOM
forensics.

The acceptance bar: per-program compile counts are exact (the decode
chunk compiles exactly ONCE through a full serving lifecycle —
admission, chunked prefill, decode, slot recycling, preempt/resume); an
injected shape-churn storm trips the detector (latch gauge +
``reason="recompile_storm"`` flight dump + the engine marked
OVERLOADED); and an induced pool-exhaustion failure's flight dump
carries the HBM ledger snapshot.
"""

import json

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from torchdistx_tpu import telemetry  # noqa: E402
from torchdistx_tpu.models import llama  # noqa: E402
from torchdistx_tpu.models.generate import generate  # noqa: E402
from torchdistx_tpu.serving import Engine  # noqa: E402
from torchdistx_tpu.serving.blocks import BlockAllocator  # noqa: E402
from torchdistx_tpu.telemetry import perf  # noqa: E402


@pytest.fixture(scope="module")
def family():
    cfg = llama.llama_test()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return llama, cfg, params


def counter_value(name):
    return telemetry.counters().get(name, 0)


# ---------------------------------------------------------------------------
# Compile observatory


def test_jit_program_counts_compiles_exactly():
    """Cache-size delta detection: one count per distinct shape, zero
    on reuse, recompiles only past the first."""
    f = jax.jit(lambda x: x + 1)
    jp = perf.JitProgram(lambda: f, "tdx_test_prog_a")
    c = "compile.count{program=tdx_test_prog_a}"
    r = "compile.recompiles{program=tdx_test_prog_a}"
    base_c, base_r = counter_value(c), counter_value(r)
    jp.call(None, None, jax.numpy.ones((2,)))
    assert counter_value(c) - base_c == 1
    assert counter_value(r) - base_r == 0
    jp.call(None, None, jax.numpy.ones((2,)))  # warm: no compile
    assert counter_value(c) - base_c == 1
    jp.call(None, None, jax.numpy.ones((3,)))  # new shape: recompile
    assert counter_value(c) - base_c == 2
    assert counter_value(r) - base_r == 1
    hist = telemetry.histograms().get(
        "compile.time_s{program=tdx_test_prog_a}", {}
    )
    assert hist.get("count", 0) >= 2


def _hist(name):
    return telemetry.histograms().get(name) or {"count": 0, "sum": 0.0}


_PHASE_FAMILIES = ("trace_s", "lower_s", "cache_load_s", "time_s")


def _phases(label):
    return {
        k: _hist(f"compile.{k}{{program={label}}}") for k in _PHASE_FAMILIES
    }


def _new_jit():
    """A new jitted function each call, of one and the same program."""

    def tdx_test_body(x):
        return jax.nn.silu(x) @ x.T + 35.0

    return jax.jit(tdx_test_body)


def test_a_compile_records_its_trace_and_lowering_under_its_label():
    """ISSUE 35: tracing and lowering land beside ``compile.time_s``,
    under the JitProgram's label and not under ``other``; a warm call
    records nothing."""
    import time

    perf.install_monitoring()
    f = _new_jit()
    jp = perf.JitProgram(lambda: f, "tdx_test_phases")
    x = jax.numpy.ones((4, 4))
    other = _phases("other")
    t0 = time.perf_counter()
    jp.call(None, None, x)
    wall = time.perf_counter() - t0
    mine = _phases("tdx_test_phases")
    assert mine["trace_s"]["count"] >= 1 and mine["trace_s"]["sum"] > 0
    assert mine["lower_s"]["count"] == 1 and mine["lower_s"]["sum"] > 0
    assert mine["time_s"]["count"] == 1
    # each second counted once: the phases fit inside the call
    assert sum(h["sum"] for h in mine.values()) <= wall
    assert _phases("other") == other
    jp.call(None, None, x)
    assert _phases("tdx_test_phases") == mine


def test_a_nested_trace_counts_its_seconds_once():
    """JAX reports a jit traced inside another's trace first and then
    again inside its caller's duration: the caller keeps the rest."""
    event = "/jax/core/compile/jaxpr_trace_duration"
    perf._tls.traces = []  # this thread's earlier traces began after -100 s
    with perf.program("tdx_test_nested"):
        perf._on_duration_event(event, 0.25)  # the callee, just ended
        perf._on_duration_event(event, 100.0)  # its caller, ending now
    hist = _hist("compile.trace_s{program=tdx_test_nested}")
    assert hist["count"] == 2
    assert abs(hist["sum"] - 100.0) < 1e-6
    # a step's trace holds thousands of callees, one after another
    with perf.program("tdx_test_siblings"):
        for _ in range(5000):
            perf._on_duration_event(event, 1e-6)
        perf._on_duration_event(event, 50.0)
    hist = _hist("compile.trace_s{program=tdx_test_siblings}")
    assert hist["count"] == 5001
    assert abs(hist["sum"] - 50.0) < 1e-6


def test_a_program_served_by_the_persistent_cache_counts_its_load(tmp_path):
    """What a second process sees: the executable comes out of the
    persistent cache, which counts as a hit with its load time, and as
    that program's first compile, not as a recompile."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    perf.install_monitoring()
    flags = {
        "jax_compilation_cache_dir": str(tmp_path),
        "jax_persistent_cache_min_compile_time_secs": 0.0,
        "jax_persistent_cache_min_entry_size_bytes": -1,
    }
    saved = {k: getattr(jax.config, k) for k in flags}
    hits = "compile.cache_hits{program=tdx_test_cached}"
    recompiles = "compile.recompiles{program=tdx_test_cached}"
    x = jax.numpy.ones((6, 6))
    try:
        for k, v in flags.items():
            jax.config.update(k, v)
        cc.reset_cache()
        first = _new_jit()
        perf.JitProgram(lambda: first, "tdx_test_cached")(x)
        assert counter_value(hits) == 0
        assert _phases("tdx_test_cached")["cache_load_s"]["count"] == 0
        # nothing of the first function is in memory for the second: a
        # new function object is a new jit cache, as in a new process
        second = _new_jit()
        perf.JitProgram(lambda: second, "tdx_test_cached")(x)
        mine = _phases("tdx_test_cached")
        assert counter_value(hits) == 1
        assert mine["cache_load_s"]["count"] == 1
        assert mine["cache_load_s"]["sum"] > 0
        # JAX times the backend compile around the lookup
        assert mine["time_s"]["count"] == 2
        assert mine["time_s"]["max"] >= mine["cache_load_s"]["sum"]
        assert counter_value("compile.count{program=tdx_test_cached}") == 2
        assert counter_value(recompiles) == 0
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_a_broken_event_does_not_fail_the_compile():
    """Whatever arrives at the listeners, the compile they watch runs."""
    from jax import monitoring

    perf.install_monitoring()
    for event in (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/compilation_cache/cache_retrieval_time_sec",
        "/jax/core/compile/backend_compile_duration",
    ):
        with perf.program("tdx_test_broken"):
            monitoring.record_event_duration_secs(event, "not a number")
            monitoring.record_event_duration_secs(event, None)
            perf._on_duration_event(event, object(), fun_name=object())
    monitoring.record_event("/jax/compilation_cache/cache_hits", who=object())
    perf._on_event(None)
    f = _new_jit()
    jp = perf.JitProgram(lambda: f, "tdx_test_broken")
    out = jp.call(None, None, jax.numpy.ones((5, 5)))
    assert out.shape == (5, 5)
    assert counter_value("compile.count{program=tdx_test_broken}") >= 1


def test_monkeypatched_stand_in_passes_through():
    """A plain function swapped in for the jitted one (the chaos tests'
    flaky decode) is not instrumented — and not broken."""
    calls = []

    def stand_in(x):
        calls.append(x)
        return x

    jp = perf.JitProgram(lambda: stand_in, "tdx_test_prog_b")
    base = counter_value("compile.count{program=tdx_test_prog_b}")
    assert jp.call(None, None, 7) == 7
    assert calls == [7]
    assert counter_value("compile.count{program=tdx_test_prog_b}") == base


def test_recompile_storm_latches_dumps_and_marks_owner(tmp_path):
    """An injected shape-churn storm: threshold recompiles in-window ⇒
    the latch gauge, a reason="recompile_storm" flight dump, and the
    owner marked OVERLOADED via its stall hook."""
    flight = str(tmp_path / "flight.jsonl")
    prev_cfg = telemetry.configure(flight=flight)
    prev_storm = perf.storm_config(threshold=3, window_s=60.0)

    class Owner:
        engine_id = "storm-test-eng"
        marked = 0

        def _mark_stalled(self):
            self.marked += 1

    owner = Owner()
    try:
        f = jax.jit(lambda x: x * 2)
        jp = perf.JitProgram(lambda: f, "tdx_test_churny")
        for n in range(1, 6):  # every call a fresh shape
            jp.call(owner, None, jax.numpy.ones((n,)))
        assert owner.marked == 1
        assert (
            telemetry.gauges()[
                "serve.recompile_storm{engine=storm-test-eng}"
            ]
            == 1
        )
        records = [json.loads(line) for line in open(flight)]
        headers = [
            rec for rec in records
            if rec.get("type") == "flight_dump"
            and rec.get("reason") == "recompile_storm"
            and rec.get("attrs", {}).get("program") == "tdx_test_churny"
        ]
        assert headers, "no recompile_storm flight dump for the churny program"
        assert headers[0]["attrs"].get("engine") == "storm-test-eng"
    finally:
        perf.storm_config(*prev_storm)
        telemetry.configure(**prev_cfg)


def test_storm_latch_clears_after_quiet_window(tmp_path):
    prev_cfg = telemetry.configure(flight=str(tmp_path / "f.jsonl"))
    # Latch under a window comfortably wider than CPU compile time...
    prev_storm = perf.storm_config(threshold=2, window_s=30.0)

    class Owner:
        engine_id = "quiet-test-eng"

        def _mark_stalled(self):
            pass

    owner = Owner()
    try:
        f = jax.jit(lambda x: x - 1)
        jp = perf.JitProgram(lambda: f, "tdx_test_quiet")
        for n in range(1, 4):
            jp.call(owner, None, jax.numpy.ones((n,)))
        assert (
            telemetry.gauges()[
                "serve.recompile_storm{engine=quiet-test-eng}"
            ]
            == 1
        )
        import time

        # ...then shrink it so a short quiet period counts as a full
        # recompile-free window.
        perf.storm_config(threshold=2, window_s=0.05)
        time.sleep(0.1)  # the window drains
        jp.call(owner, None, jax.numpy.ones((3,)))  # warm call: no compile
        assert (
            telemetry.gauges()[
                "serve.recompile_storm{engine=quiet-test-eng}"
            ]
            == 0
        )
    finally:
        perf.storm_config(*prev_storm)
        telemetry.configure(**prev_cfg)


def test_decode_chunk_compiles_exactly_once_through_lifecycle(family):
    """The steady-state compile invariant, assertable for the first
    time: ONE decode-chunk compile covers admission → chunked prefill →
    decode → slot recycling → priority preemption → resume.  Unique
    engine geometry (num_slots=3, decode_chunk=5) guarantees a fresh
    program, so the expected count is exactly 1 — anything more is the
    shape leak the storm detector exists for.  Runs with the
    prefix-cache default ON (the flipped default earns its tier-1
    coverage here)."""
    model, cfg, params = family
    eng = Engine(
        params, model=model, cfg=cfg, scheduler="qos", num_slots=3,
        block_size=8, max_model_len=64, decode_chunk=5, prefill_chunk=4,
        min_prefill_bucket=4, preempt_mechanism="replay",
        handle_preemption=False,
    )
    assert eng.prefix is not None  # the new default
    name = "compile.count{program=decode_chunk}"
    base = counter_value(name)

    def solo(prompt, seed, max_new):
        out = generate(
            params, jax.numpy.asarray(prompt)[None],
            jax.random.PRNGKey(seed), model=model, cfg=cfg,
            max_new_tokens=max_new,
        )
        return [int(t) for t in np.asarray(out)[0]]

    # Admission + chunked prefill (12 tokens = 3 chunks of 4) + decode.
    p0 = np.arange(1, 13, dtype=np.int32)
    h0 = eng.submit(p0, max_new_tokens=8, key=0, priority=0)
    # Slot recycling: two more requests through the freed slots.
    h1 = eng.submit(np.arange(2, 8, dtype=np.int32), max_new_tokens=6,
                    key=1, priority=0)
    eng.drain()
    # Preempt/resume: fill every slot with low priority, then a
    # high-priority arrival forces a drop-and-replay preemption.
    victims = [
        eng.submit(np.arange(3, 9, dtype=np.int32), max_new_tokens=20,
                   key=10 + i, priority=0)
        for i in range(3)
    ]
    eng.step()
    urgent = eng.submit(np.arange(4, 10, dtype=np.int32),
                        max_new_tokens=6, key=99, priority=5)
    eng.drain()
    assert eng.stats()["preemptions_replay"] >= 1
    # Token identity held throughout...
    assert h0.result() == solo(p0, 0, 8)
    assert h1.result() == solo(np.arange(2, 8, dtype=np.int32), 1, 6)
    assert urgent.result() == solo(np.arange(4, 10, dtype=np.int32), 99, 6)
    for i, v in enumerate(victims):
        assert v.result() == solo(np.arange(3, 9, dtype=np.int32),
                                  10 + i, 20)
    # ...and the decode chunk compiled exactly once for all of it.
    assert counter_value(name) - base == 1, (
        "decode chunk recompiled during steady-state serving"
    )
    assert (
        "compile.recompiles{program=decode_chunk}"
        not in telemetry.counters()
    )
    # Cache-on idle accounting: the allocator owns exactly the index's
    # pages, each at refcount 1.
    assert eng.allocator.num_in_use == len(eng.prefix)
    assert eng.prefix.check(eng.allocator) is None


# ---------------------------------------------------------------------------
# HBM ledger + OOM forensics


def test_ledger_register_sum_unregister_and_exposition():
    perf.ledger.register("tdx_test_comp", 100, owner="a")
    perf.ledger.register("tdx_test_comp", 50, owner="b")
    g = "mem.hbm_bytes{component=tdx_test_comp}"
    assert telemetry.gauges()[g] == 150
    assert perf.ledger.components()["tdx_test_comp"] == 150
    from torchdistx_tpu.telemetry.ops import render_prometheus

    text = render_prometheus()
    assert 'mem_hbm_bytes{component="tdx_test_comp"} 150' in text
    perf.ledger.unregister("tdx_test_comp", owner="a")
    assert telemetry.gauges()[g] == 50
    perf.ledger.unregister("tdx_test_comp", owner="b")
    assert g not in telemetry.gauges()  # pruned: bounded cardinality


def test_ledger_weights_dedupe_across_engines(family):
    """N engines over ONE params pytree are one copy of HBM: weights
    register under the params identity, not per engine."""
    model, cfg, params = family
    eng_a = Engine(params, model=model, cfg=cfg, num_slots=2,
                   block_size=8, max_model_len=64, decode_chunk=4,
                   handle_preemption=False)
    w1 = telemetry.gauges()["mem.hbm_bytes{component=weights}"]
    eng_b = Engine(params, model=model, cfg=cfg, num_slots=2,
                   block_size=8, max_model_len=64, decode_chunk=4,
                   handle_preemption=False)
    assert telemetry.gauges()["mem.hbm_bytes{component=weights}"] == w1
    # Each engine's pool is its own HBM: kv_pool sums.
    pool_total = telemetry.gauges()["mem.hbm_bytes{component=kv_pool}"]
    eng_a.close()
    assert (
        telemetry.gauges()["mem.hbm_bytes{component=kv_pool}"]
        == pool_total - eng_a._pool_nbytes
    )
    eng_b.close()
    # Retirement: a hot-swapped-out version's weights leave the ledger
    # when the LAST engine over that pytree stops — retired versions
    # must not pile up on the component forever.
    fresh = model.init_params(jax.random.PRNGKey(7), cfg)
    before = telemetry.gauges().get("mem.hbm_bytes{component=weights}", 0)
    eng_c = Engine(fresh, model=model, cfg=cfg, num_slots=2,
                   block_size=8, max_model_len=64, decode_chunk=4,
                   handle_preemption=False)
    eng_d = Engine(fresh, model=model, cfg=cfg, num_slots=2,
                   block_size=8, max_model_len=64, decode_chunk=4,
                   handle_preemption=False)
    during = telemetry.gauges()["mem.hbm_bytes{component=weights}"]
    assert during > before  # counted once for both
    eng_c.close()
    assert telemetry.gauges()["mem.hbm_bytes{component=weights}"] == during
    eng_d.close()  # the last engine over `fresh`: its bytes retire
    assert (
        telemetry.gauges().get("mem.hbm_bytes{component=weights}", 0)
        == before
    )


def test_is_oom_classifier():
    assert perf.is_oom(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 1073741824 bytes"
    ))
    assert perf.is_oom(ValueError("backend ran Out of memory"))
    assert not perf.is_oom(RuntimeError("shape mismatch"))


def test_pool_exhaustion_dump_carries_ledger(tmp_path, family):
    """An induced pool-exhaustion failure's flight dump contains the
    HBM ledger snapshot — the OOM post-mortem names what held the
    memory."""
    model, cfg, params = family
    flight = str(tmp_path / "oom.jsonl")
    prev_cfg = telemetry.configure(flight=flight)
    try:
        eng = Engine(
            params, model=model, cfg=cfg, num_slots=2, block_size=8,
            max_model_len=64, decode_chunk=4, handle_preemption=False,
        )
        # Induce exhaustion: the allocator's map is emptied under the
        # tick (the supervisor-reset race _start_prefill defends
        # against), so the promised reservation cannot be met.
        eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=4,
                   key=0)
        real_alloc = eng.allocator.alloc
        eng.allocator.alloc = lambda n: None
        eng.step()  # admission's _start_prefill fails; request requeues
        eng.allocator.alloc = real_alloc
        records = [json.loads(line) for line in open(flight)]
        headers = [
            rec for rec in records
            if rec.get("type") == "flight_dump"
            and rec.get("reason") == "pool_exhausted"
        ]
        assert headers, "no pool_exhausted flight dump"
        attrs = headers[0]["attrs"]
        assert attrs["engine"] == eng.engine_id
        assert "kv_pool" in attrs["ledger"] and "weights" in attrs["ledger"]
        assert attrs["ledger"]["kv_pool"] >= eng._pool_nbytes
        assert "pool_fragmentation" in attrs
        # The engine survived: the request still completes.
        eng.drain()
        eng.close()
    finally:
        telemetry.configure(**prev_cfg)


def test_device_oom_dump_carries_ledger(tmp_path, family):
    """A RESOURCE_EXHAUSTED device failure routes through the same
    forensic dump under reason="device_oom"."""
    model, cfg, params = family
    flight = str(tmp_path / "oom2.jsonl")
    prev_cfg = telemetry.configure(flight=flight)
    try:
        eng = Engine(
            params, model=model, cfg=cfg, num_slots=2, block_size=8,
            max_model_len=64, decode_chunk=4, handle_preemption=False,
        )
        eng._oom_check(
            RuntimeError("RESOURCE_EXHAUSTED: out of memory"),
            "serve.step",
        )
        records = [json.loads(line) for line in open(flight)]
        headers = [
            rec for rec in records
            if rec.get("type") == "flight_dump"
            and rec.get("reason") == "device_oom"
        ]
        assert headers and "kv_pool" in headers[0]["attrs"]["ledger"]
        eng.close()
    finally:
        telemetry.configure(**prev_cfg)


def test_allocator_fragmentation_estimate():
    a = BlockAllocator(num_blocks=9, block_size=4)
    assert a.fragmentation() == 0.0  # all free: one run
    pages = a.alloc(8)
    assert a.fragmentation() == 0.0  # nothing free
    a.free([pages[1], pages[3], pages[5]])  # single-page holes
    assert a.fragmentation() == pytest.approx(1 - 1 / 3)
    a.free([pages[0], pages[2], pages[4], pages[6], pages[7]])
    assert a.fragmentation() == 0.0  # everything free again
