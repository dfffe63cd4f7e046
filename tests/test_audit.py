"""Audit plane (ISSUE 14): determinism digests, shadow auditing,
divergence latching, incident replay.

The acceptance bars: every request's rolling digest is a pure function
of (prompt, key schedule, model version, committed tokens) however the
stream was chunked, preempted, or failed over; the shadow auditor
catches a silently corrupted stream — and ONLY that stream; resumes
verify their committed buffers against the digest; the fleet's
digest-based failover prefix verification is equivalent to the old
buffered-list walk and additionally rejects version-mixed streams; and
a divergence flight dump replays into a bisected repro."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistx_tpu import telemetry
from torchdistx_tpu.fleet import FailoverDiverged, FleetRouter
from torchdistx_tpu.models import llama
from torchdistx_tpu.models.generate import generate
from torchdistx_tpu.resilience import faults, preemption
from torchdistx_tpu.serving import (
    DeterminismDiverged,
    Engine,
    Health,
)
from torchdistx_tpu.telemetry import audit
from torchdistx_tpu.telemetry import ops as tdx_ops

EOS = 5
ENGINE_KW = dict(
    num_slots=2, block_size=8, max_model_len=64, decode_chunk=4,
    handle_preemption=False,
)


@pytest.fixture(autouse=True)
def _clean():
    prev = telemetry.configure(collect=False, jsonl=None, flight=None)
    telemetry.reset()
    preemption.clear()
    yield
    faults.reset("")
    preemption.clear()
    tdx_ops.enable_tick_attribution(False)
    for plane in list(tdx_ops._PLANES.values()):
        plane.close()
    telemetry.configure(**prev)
    telemetry.reset()


@pytest.fixture(scope="module")
def family():
    cfg = llama.llama_test()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)
    return llama, cfg, params


def prompt_of(n, base=1):
    return np.arange(base, base + n, dtype=np.int32)


def solo(model, cfg, params, prompt, seed, max_new, *, eos=None,
         temperature=0.0, top_k=None):
    out = generate(
        params, jnp.asarray(prompt)[None], jax.random.PRNGKey(seed),
        model=model, cfg=cfg, max_new_tokens=max_new, eos_id=eos,
        temperature=temperature, top_k=top_k,
    )
    toks = [int(t) for t in np.asarray(out)[0]]
    if eos is not None and eos in toks:
        toks = toks[: toks.index(eos) + 1]
    return toks


# ---------------------------------------------------------------------------
# DeterminismDigest


def test_digest_chunk_invariant_and_sensitive():
    """The digest is a pure function of (prompt, key, version, tokens)
    — identical whether tokens fold in per chunk or per token — and
    changes when ANY component changes."""
    key = audit.canonical_key(7)
    a = audit.DeterminismDigest(prompt_of(4), key)
    a.update([10, 11, 12, 13], "v1")
    b = audit.DeterminismDigest(prompt_of(4), key)
    for t in (10, 11, 12, 13):
        b.update([t], "v1")
    assert a.hexdigest() == b.hexdigest() and a.n == b.n == 4
    assert a.matches_stream(prompt_of(4), key, [10, 11, 12, 13], "v1")
    variants = [
        audit.DeterminismDigest.of_stream(
            prompt_of(4), key, [10, 11, 12, 99], "v1"),      # token
        audit.DeterminismDigest.of_stream(
            prompt_of(4), key, [10, 11, 12, 13], "v2"),      # version
        audit.DeterminismDigest.of_stream(
            prompt_of(4), audit.canonical_key(8), [10, 11, 12, 13], "v1"),
        audit.DeterminismDigest.of_stream(
            prompt_of(4, base=2), key, [10, 11, 12, 13], "v1"),
        audit.DeterminismDigest.of_stream(
            prompt_of(4), key, [10, 11, 12], "v1"),          # prefix only
    ]
    assert len({d.hexdigest() for d in variants} | {a.hexdigest()}) == 6
    # Snapshots roll: hexdigest() must not consume the state.
    assert a.hexdigest() == a.hexdigest()


def test_token_chunk_mapping():
    """Token 0 is the prefill's sample (chunk 0); decode chunk j
    commits tokens 1+(j-1)*dc .. j*dc."""
    assert audit.token_chunk(0, 4) == 0
    assert audit.token_chunk(1, 4) == 1
    assert audit.token_chunk(4, 4) == 1
    assert audit.token_chunk(5, 4) == 2
    assert audit.first_divergence([1, 2, 3], [1, 2, 4]) == 2
    assert audit.first_divergence([1, 2], [1, 2, 3]) == 2


def test_engine_stamps_digest_on_lifecycle_events(family):
    """Every request carries the rolling digest; its snapshots land on
    req.first_token (admitted identity) and req.finished (full stream),
    and the final digest equals an of_stream recomputation."""
    model, cfg, params = family
    telemetry.configure(collect=True)
    eng = Engine(params, model=model, cfg=cfg, **ENGINE_KW)
    h = eng.submit(prompt_of(5), max_new_tokens=6, key=11)
    toks = h.result()
    assert h.digest == audit.DeterminismDigest.of_stream(
        prompt_of(5), audit.canonical_key(11), toks, eng.model_version
    ).hexdigest()
    events = {
        r["name"]: r for r in telemetry.snapshot()["spans"]
        if r.get("type") == "event" and r.get("rid") == h._req.trace_id
    }
    assert events["req.finished"]["attrs"]["digest"] == h.digest
    assert "digest" in events["req.first_token"]["attrs"]
    # The replay identity rides req.submitted: a flight dump is a repro.
    sub = events["req.submitted"]["attrs"]
    assert sub["prompt"] == [int(t) for t in prompt_of(5)]
    assert len(sub["key"]) == 2
    eng.close()


# ---------------------------------------------------------------------------
# Satellite: idle ticks publish no attribution


def test_idle_ticks_skip_attribution_and_count(family):
    """A fully idle tick publishes NO per-tick attribution (gauges,
    serve.tick_s) — idle readings would dilute occupancy/goodput — and
    bumps serve.idle_ticks instead.  The FIRST idle tick zeroes the
    rate gauges once, so a quiet engine never advertises its last busy
    tick's goodput."""
    model, cfg, params = family
    tdx_ops.enable_tick_attribution(True)
    eng = Engine(params, model=model, cfg=cfg, **ENGINE_KW)
    h = eng.submit(prompt_of(4), max_new_tokens=6, key=0)
    busy_ticks = 0
    while not h.done:
        eng.step()
        busy_ticks += 1
    eid = eng.engine_id
    hist = telemetry.histograms()[f"serve.tick_s{{engine={eid}}}"]
    assert hist["count"] == busy_ticks  # every busy tick published
    idle_before = telemetry.counter("serve.idle_ticks").value
    for _ in range(5):
        eng.step()
    assert telemetry.counter("serve.idle_ticks").value == idle_before + 5
    assert (
        telemetry.histograms()[f"serve.tick_s{{engine={eid}}}"]["count"]
        == busy_ticks
    ), "idle ticks leaked into serve.tick_s"
    gauges = telemetry.gauges()
    for g in ("serve.occupancy", "serve.prefill_budget", "serve.churn",
              "serve.goodput"):
        assert gauges[f"{g}{{engine={eid}}}"] == 0, g  # zeroed on idle edge
    eng.close()


# ---------------------------------------------------------------------------
# Shadow auditor


def test_auditor_clean_traffic_no_divergence(family):
    """audit_sample=1.0 re-executes every completed request (after the
    user work, through the same programs) and finds nothing: replays
    are token-identical by construction."""
    model, cfg, params = family
    before = telemetry.counter("audit.checked").value
    eng = Engine(
        params, model=model, cfg=cfg, eos_id=EOS, audit_sample=1.0,
        temperature=0.8, top_k=8, **ENGINE_KW,
    )
    handles = [
        eng.submit(prompt_of(4 + i), max_new_tokens=6, key=100 + i)
        for i in range(3)
    ]
    eng.drain()  # drain() waits out the shadow audits too
    for i, h in enumerate(handles):
        assert h.result() == solo(
            model, cfg, params, prompt_of(4 + i), 100 + i, 6, eos=EOS,
            temperature=0.8, top_k=8,
        )
    st = eng.stats()
    assert st["audit_checked"] == 3
    assert st["audit_divergences"] == 0
    assert telemetry.counter("audit.checked").value == before + 3
    assert eng.health() is Health.READY
    assert eng.audit_backlog() == 0
    eng.close()


def test_auditing_compiles_no_second_decode_chunk(family):
    """Audit replays are shadow traffic through the SAME compiled
    programs: once an unaudited engine of this geometry has served,
    the same traffic with the auditor at 100% sampling leaves
    ``compile.count{program=decode_chunk}`` where it was."""
    model, cfg, params = family
    name = "compile.count{program=decode_chunk}"

    def serve(**kw):
        eng = Engine(
            params, model=model, cfg=cfg, eos_id=EOS, **kw, **ENGINE_KW
        )
        for i in range(3):
            eng.submit(prompt_of(4 + i), max_new_tokens=6, key=100 + i)
        eng.drain()
        st = eng.stats()
        eng.close()
        return st

    serve()  # warm-up: every program this traffic needs
    warm = telemetry.counters().get(name, 0)
    st = serve(audit_sample=1.0)
    assert st["audit_checked"] == 3 and st["audit_divergences"] == 0
    assert telemetry.counters().get(name, 0) == warm
    assert (
        "compile.recompiles{program=decode_chunk}"
        not in telemetry.counters()
    )


def test_auditor_off_by_default_and_sample_zero(family):
    model, cfg, params = family
    eng = Engine(params, model=model, cfg=cfg, **ENGINE_KW)
    assert eng._auditor is None and eng.audit_backlog() == 0
    eng.close()
    eng0 = Engine(
        params, model=model, cfg=cfg, audit_sample=0.0, **ENGINE_KW
    )
    assert eng0._auditor is None
    eng0.close()
    with pytest.raises(ValueError):
        Engine(params, model=model, cfg=cfg, audit_sample=1.5, **ENGINE_KW)


def test_bad_audit_sample_does_not_leak_ops_plane(family):
    """audit_sample validation runs BEFORE the ops-plane attach: a
    constructor that raises must not leave a half-built engine watched
    by a plane nothing will ever unwatch."""
    model, cfg, params = family
    with pytest.raises(ValueError):
        Engine(
            params, model=model, cfg=cfg, ops_port=0, audit_sample=2.0,
            ops_config=tdx_ops.OpsConfig(watchdog=False), **ENGINE_KW,
        )
    assert not tdx_ops._PLANES, "failed constructor leaked an ops plane"


def test_env_audit_sample(family, monkeypatch):
    model, cfg, params = family
    monkeypatch.setenv("TDX_AUDIT_SAMPLE", "1.0")
    eng = Engine(params, model=model, cfg=cfg, **ENGINE_KW)
    assert eng._auditor is not None and eng._auditor.sample == 1.0
    eng.close()
    monkeypatch.setenv("TDX_AUDIT_SAMPLE", "nope")
    with pytest.raises(ValueError):
        Engine(params, model=model, cfg=cfg, **ENGINE_KW)
    monkeypatch.delenv("TDX_AUDIT_SAMPLE")


def test_corrupt_fault_flags_exactly_the_corrupted_stream(family):
    """Satellite: TDX_FAULT kind=corrupt at serve.step flips ONE
    committed token silently; the auditor must flag exactly that stream
    — and no others — with the right bisection."""
    model, cfg, params = family
    eng = Engine(
        params, model=model, cfg=cfg, num_slots=4, block_size=8,
        max_model_len=64, decode_chunk=4, max_prefills_per_tick=4,
        handle_preemption=False, audit_sample=1.0,
    )
    # All three streams decoding by chunk 4 (admission takes the first
    # ticks); the victim is the first decoding slot = first admitted.
    faults.reset("serve.step:4:corrupt")
    handles = [
        eng.submit(prompt_of(5), max_new_tokens=16, key=200 + i)
        for i in range(3)
    ]
    eng.drain()
    faults.reset("")
    assert telemetry.counter("serve.corruptions").value == 1
    st = eng.stats()
    assert st["audit_checked"] == 3
    assert st["audit_divergences"] == 1, (
        "auditor must flag exactly the corrupted stream"
    )
    detail = eng._auditor.divergence_detail[0]
    assert detail["rid"] == (
        handles[0]._req.trace_id or f"{eng.engine_id}-r0"
    )
    # The corrupted stream differs from ground truth at exactly one
    # token: the first committed token of the faulted chunk.
    truth = solo(model, cfg, params, prompt_of(5), 200, 16)
    got = handles[0].result()
    diffs = [i for i, (a, b) in enumerate(zip(truth, got)) if a != b]
    assert len(diffs) == 1
    assert detail["first_diverging_token"] == diffs[0]
    assert detail["first_diverging_chunk"] == audit.token_chunk(
        diffs[0], eng.decode_chunk
    )
    # The latch: OVERLOADED until an operator clears it.
    assert eng.health() is Health.OVERLOADED
    assert eng.stats()["diverging"] is True
    eid = eng.engine_id
    assert telemetry.gauges()[f"serve.diverging{{engine={eid}}}"] == 1
    eng.step()
    assert eng.health() is Health.OVERLOADED, "divergence must not self-clear"
    eng.clear_divergence()
    eng.step()
    assert eng.health() is Health.READY
    # The uncorrupted streams replayed clean.
    for i, h in enumerate(handles[1:], start=1):
        assert h.result() == solo(
            model, cfg, params, prompt_of(5), 200 + i, 16
        )
    eng.close()
    assert f"serve.diverging{{engine={eid}}}" not in telemetry.gauges()


def test_diverging_replica_routed_around(family):
    """A latched serve.diverging engine reads OVERLOADED: the router
    avoids it exactly like a stalled or storming replica."""
    model, cfg, params = family
    eng_a = Engine(params, model=model, cfg=cfg, **ENGINE_KW)
    eng_b = Engine(params, model=model, cfg=cfg, **ENGINE_KW)
    router = FleetRouter([eng_a, eng_b], version="v1")
    eng_a._mark_diverging()
    for _ in range(3):
        assert router._pick().engine is eng_b
    h = router.submit(prompt_of(4), max_new_tokens=3, key=0)
    assert h.replica_id == 1
    assert h.result() == solo(model, cfg, params, prompt_of(4), 0, 3)
    router.close()


# ---------------------------------------------------------------------------
# Resume verification (preempt/replay/swap) against the digest


@pytest.mark.parametrize("sampled", [False, True])
def test_preempt_resume_digest_verified_ok(family, sampled):
    """Both preemption mechanisms resume through the digest check and
    stay token-identical — the equivalence half of the satellite: the
    digest-based verification accepts everything the old buffered-list
    behavior accepted, greedy AND sampled."""
    model, cfg, params = family
    sample_kw = dict(temperature=0.8, top_k=20) if sampled else {}
    # Drop-and-replay (slot pressure).
    eng = Engine(
        params, model=model, cfg=cfg, scheduler="qos", num_slots=1,
        block_size=8, max_model_len=64, decode_chunk=4,
        handle_preemption=False, **sample_kw,
    )
    victim = eng.submit(prompt_of(6), max_new_tokens=24, key=700, priority=0)
    eng.step()
    assert not victim.done and len(victim._tokens) > 0
    eng.submit(prompt_of(6, base=3), max_new_tokens=8, key=701, priority=5)
    eng.drain()
    toks = victim.result()
    assert toks == solo(model, cfg, params, prompt_of(6), 700, 24, **sample_kw)
    assert victim.digest == audit.DeterminismDigest.of_stream(
        prompt_of(6), audit.canonical_key(700), toks, eng.model_version
    ).hexdigest()
    assert telemetry.counter("audit.divergences").value == 0
    eng.close()
    # Swap-to-host (page pressure).
    engs = Engine(
        params, model=model, cfg=cfg, scheduler="qos", num_slots=2,
        block_size=8, num_blocks=9, max_model_len=64, decode_chunk=4,
        handle_preemption=False, prefix_cache=False, **sample_kw,
    )
    victim = engs.submit(prompt_of(8), max_new_tokens=26, key=800, priority=0)
    engs.step()
    engs.submit(prompt_of(8, base=2), max_new_tokens=26, key=801, priority=5)
    engs.step()
    assert engs.allocator.num_swapped > 0
    engs.drain()
    assert victim.result() == solo(
        model, cfg, params, prompt_of(8), 800, 26, **sample_kw
    )
    assert telemetry.counter("audit.divergences").value == 0
    engs.close()


def test_replay_resume_rejects_corrupted_buffer(family):
    """Negative half: a committed-token buffer corrupted while the
    stream was parked fails the digest check typed
    (DeterminismDiverged) and latches the engine — never a silent
    poisoned continuation."""
    model, cfg, params = family
    eng = Engine(
        params, model=model, cfg=cfg, scheduler="qos", num_slots=1,
        block_size=8, max_model_len=64, decode_chunk=4,
        handle_preemption=False,
    )
    victim = eng.submit(prompt_of(6), max_new_tokens=24, key=700, priority=0)
    eng.step()
    assert len(victim._tokens) > 0
    urgent = eng.submit(
        prompt_of(6, base=3), max_new_tokens=8, key=701, priority=5
    )
    eng.step()  # victim preempted (drop-and-replay), requeued
    before = telemetry.counter("audit.divergences").value
    victim._tokens[0] ^= 1  # the corruption
    eng.drain()
    assert urgent.error is None
    with pytest.raises(DeterminismDiverged):
        victim.result()
    assert not victim.error.retryable
    assert telemetry.counter("audit.divergences").value == before + 1
    assert eng._diverging and eng.health() is Health.OVERLOADED
    assert eng.allocator.num_in_use == len(eng.prefix)  # pages came back
    eng.close()


def test_swap_resume_rejects_corrupted_buffer(family):
    model, cfg, params = family
    eng = Engine(
        params, model=model, cfg=cfg, scheduler="qos", num_slots=2,
        block_size=8, num_blocks=9, max_model_len=64, decode_chunk=4,
        handle_preemption=False, prefix_cache=False,
    )
    victim = eng.submit(prompt_of(8), max_new_tokens=26, key=800, priority=0)
    eng.step()
    urgent = eng.submit(
        prompt_of(8, base=2), max_new_tokens=26, key=801, priority=5
    )
    eng.step()  # victim swapped out
    assert eng.allocator.num_swapped > 0
    victim._tokens[-1] ^= 1  # corrupt the parked buffer
    eng.drain()
    assert urgent.error is None
    with pytest.raises(DeterminismDiverged):
        victim.result()
    assert eng.allocator.num_swapped == 0  # swap account settled
    assert eng.allocator.num_in_use == 0
    eng.close()


# ---------------------------------------------------------------------------
# Fleet failover: digest-based prefix verification


@pytest.mark.parametrize(
    "temperature,top_k", [(0.0, None), (0.8, 8)]
)
def test_failover_digest_equivalent_to_buffered_list(
    family, temperature, top_k
):
    """Kill + failover, greedy AND sampled: the digest-verified replay
    continues mid-stream token-identically, and the fleet handle's
    digest equals the single-engine digest of the same stream — the
    verification change is invisible wherever the old one accepted."""
    model, cfg, params = family
    kw = dict(
        temperature=temperature, top_k=top_k, eos_id=EOS,
        prefix_cache=False, **ENGINE_KW,
    )
    eng_a = Engine(params, model=model, cfg=cfg, **kw)
    eng_b = Engine(params, model=model, cfg=cfg, **kw)
    router = FleetRouter([eng_a, eng_b], version="v1")
    h = router.submit(prompt_of(6), max_new_tokens=10, key=3)
    g = h.tokens()
    first = [next(g), next(g)]
    eng_a.close()  # dies mid-stream; the iterator keeps going
    rest = list(g)
    toks = first + rest
    assert toks == solo(
        model, cfg, params, prompt_of(6), 3, 10, eos=EOS,
        temperature=temperature, top_k=top_k,
    )
    assert h.hops == 1
    assert h.digest == audit.DeterminismDigest.of_stream(
        prompt_of(6), audit.canonical_key(3), toks, "v0"
    ).hexdigest()
    router.close()


def test_failover_rejects_version_mixed_stream(family):
    """Satellite: a peer under the same ROUTER version tag but a
    different model_version produces byte-identical tokens here (same
    weights) — the old token-by-token walk would splice it silently;
    the digest, with model_version folded per token, rejects it
    typed."""
    model, cfg, params = family
    eng_a = Engine(
        params, model=model, cfg=cfg, model_version="weights-a",
        prefix_cache=False, **ENGINE_KW,
    )
    eng_b = Engine(
        params, model=model, cfg=cfg, model_version="weights-b",
        prefix_cache=False, **ENGINE_KW,
    )
    router = FleetRouter([eng_a, eng_b], version="v1")  # tags lie
    h = router.submit(prompt_of(6), max_new_tokens=8, key=0)
    g = h.tokens()
    consumed = [next(g), next(g)]
    assert consumed == solo(model, cfg, params, prompt_of(6), 0, 8)[:2]
    eng_a.close()
    with pytest.raises(FailoverDiverged) as ei:
        list(g)
    assert "model_version" in str(ei.value)
    assert h.done and h.error is ei.value
    router.close()


# ---------------------------------------------------------------------------
# Incident replay


def test_incident_replay_bisects_corrupt_dump(family, tmp_path):
    """Satellite: the divergence flight dump a corrupt fault produces
    replays into a repro — the clean re-run disagrees with the recorded
    digests, and the bisection lands on the faulted chunk."""
    import sys

    sys.path.insert(
        0,
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts"),
    )
    import incident_replay

    model, cfg, params = family
    flight = str(tmp_path / "flight.jsonl")
    telemetry.configure(flight=flight, flight_capacity=4096)
    faults.reset("serve.step:3:corrupt")
    eng = Engine(
        params, model=model, cfg=cfg, num_slots=2, block_size=8,
        max_model_len=64, decode_chunk=4, max_prefills_per_tick=2,
        handle_preemption=False, audit_sample=1.0,
    )
    handles = [
        eng.submit(prompt_of(5), max_new_tokens=14, key=300 + i)
        for i in range(2)
    ]
    eng.drain()
    faults.reset("")
    st = eng.stats()
    assert st["audit_divergences"] == 1
    detail = eng._auditor.divergence_detail[0]
    eng.close()

    records = incident_replay.load_dump(flight)
    dumps = [r for r in records if r.get("type") == "flight_dump"]
    assert any(d.get("reason") == "divergence" for d in dumps)
    result = incident_replay.analyze(records, with_faults=True)
    assert result["reproduced"], result
    assert result["faulted_rerun_matches_incident"], result
    assert len(result["divergences"]) == 1
    row = result["divergences"][0]
    assert row["rid"] == detail["rid"]
    assert row["first_diverging_token"] == detail["first_diverging_token"]
    assert row["first_diverging_chunk"] == detail["first_diverging_chunk"]
    # Both streams rode the dump: the corrupted original and the
    # auditor's clean replay.
    ddump = next(d for d in dumps if d.get("reason") == "divergence")
    attrs = ddump["attrs"]
    assert attrs["expected_tokens"] != attrs["replayed_tokens"]
    for h in handles:
        assert h.error is None


def test_incident_replay_nothing_replayable(tmp_path):
    import sys

    sys.path.insert(
        0,
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts"),
    )
    import incident_replay

    path = tmp_path / "empty.jsonl"
    path.write_text(json.dumps({"type": "flight_dump", "reason": "stall"})
                    + "\n")
    result = incident_replay.analyze(incident_replay.load_dump(str(path)))
    assert result["n_replayable"] == 0 and "error" in result
