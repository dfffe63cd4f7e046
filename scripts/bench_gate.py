#!/usr/bin/env python
"""Bench regression gate: fresh numbers vs the best of recorded history.

Nothing stops the next change from quietly regressing the headline
serving bench — the ROADMAP's ratchet needs a *gate*, not a log line
someone might read.  This script compares a candidate bench result
against the best value each metric ever achieved across the history
rounds ``--baseline`` names, inside a per-metric tolerance band, and
emits a machine-readable verdict:

    python scripts/bench_gate.py --candidate fresh.json --baseline 'rounds/*.json'
    python scripts/bench_gate.py --candidate fresh.json  # no history: vacuous
    python scripts/bench_gate.py --run-fast          # CI: CPU-sized scenario

The repo holds no recorded rounds for the current installation (the
earlier ones were deleted with the harness that took them), so without
``--baseline`` the history is empty and every row reads ``no_baseline``.

Exit code 0 = every gated metric inside its band; nonzero = regression
(or a metric the history tracks vanished from the candidate — a bench
that silently stops reporting a number is itself a regression).

Gated metrics (ISSUE 12): materialize wall (cold + warm), and the
serving bench's sustained decode tok/s, TTFT p95, TPOT p95, and goodput.
Metrics absent from ALL history rounds gate vacuously (``no_baseline``)
— the serving family enters the gate the first round that records it.

``--run-fast`` runs a CPU-sized serving scenario in-process (tiny
llama, same shape as the chaos soak) and asserts the **compile
observatory invariants** the full bench also enforces: the decode chunk
compiles exactly once (steady-state recompiles == 0 — the engine's
whole perf model rests on it) and the HBM ledger attributes the pool.
Its JSON row is written to ``--output`` so a CI can archive fast-round
history; tolerance gating against that history applies when
``--baseline`` names fast rounds.

File formats accepted: a raw ``bench.py`` line (``{"metric", ...,
"details": {...}}``) or the archived wrapper (``{"parsed": {...}}``).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SERVING = ("details", "serving_llama_350m_continuous")

# (name, path into the bench JSON, higher_is_better, tolerance).
# Tolerance is the fractional band around the historical best a
# candidate may sit on the worse side of: generous to start (no
# run-to-run spread has been measured on the chip yet); tighten per
# metric as rounds accumulate.
METRICS: List[Tuple[str, Tuple[str, ...], bool, float]] = [
    ("materialize_gpt2xl_s",
     ("details", "gpt2xl_1p6b_bf16", "ours_s"), False, 0.35),
    ("materialize_gpt2xl_warm_s",
     ("details", "gpt2xl_1p6b_bf16", "ours_warm_s"), False, 0.50),
    ("serving_sustained_decode_tok_s",
     _SERVING + ("sustained_decode_tokens_per_s",), True, 0.20),
    ("serving_ttft_p95_s", _SERVING + ("ttft_p95_s",), False, 0.35),
    ("serving_tpot_p95_s", _SERVING + ("tpot_p95_s",), False, 0.35),
    ("serving_goodput_tok_s",
     _SERVING + ("goodput_tokens_per_s",), True, 0.20),
    # Audit plane (ISSUE 14): sustained tok/s with the shadow auditor
    # at 100% sampling over sustained tok/s without it, same trace.  A
    # ratio collapse means auditing stopped being shadow traffic
    # (preempting/queueing ahead of user work, or recompiling).  Gates
    # vacuously (no_baseline) until a round records it.
    ("serving_audit_sustained_ratio",
     _SERVING + ("audit", "sustained_ratio"), True, 0.25),
    # Autoscale loop (ISSUE 16): the elastic-fleet probe's burn-edge →
    # recovery-edge wall time, the flash-crowd ramp TTFT p95 under the
    # autoscaler, and the dropped count — zero tolerance: once history
    # records dropped == 0, any drop at all regresses.  All gate
    # vacuously (no_baseline) until a round records them.
    ("autoscale_recover_s",
     ("details", "fleet_autoscale", "time_to_recover_s"), False, 0.60),
    ("autoscale_ramp_ttft_p95_s",
     ("details", "fleet_autoscale", "ramp_ttft_p95_s"), False, 0.50),
    ("autoscale_dropped",
     ("details", "fleet_autoscale", "dropped"), False, 0.0),
    # Stream migration (ISSUE 17): the warm hand-off's wall time, the
    # consumer-visible p95 pull latency of a migrated stream (must stay
    # well under the cold-replay arm's), and the decode tier's p95
    # inter-token gap while a long prompt lands on the prefill peer.
    # All gate vacuously (no_baseline) until a round records them.
    ("migration_handoff_p95_s",
     ("details", "fleet_migration", "migration_handoff_p95_s"),
     False, 0.60),
    ("migration_pull_p95_s",
     ("details", "fleet_migration", "migrated_pull_p95_s"), False, 0.50),
    ("migration_disagg_tpot_p95_ms",
     ("details", "fleet_migration", "disagg_chat_tpot_p95_ms"),
     False, 0.50),
    # Model plane (ISSUE 18): the mixed four-model wave's warm TTFT
    # p95 (includes re-warm stalls under eviction thrash), the
    # materialize stall p95 from the pool's own clock, the decode
    # recompile delta across models (zero tolerance — same-geometry
    # models must share the one compiled chunk), and the n=4 fork page
    # amplification vs 4x solo (must stay far below 1.0: prompt pages
    # are donor-shared, only divergence CoW-copies).  All gate
    # vacuously (no_baseline) until a round records them.
    ("models_warm_ttft_p95_s",
     ("details", "model_plane", "warm_ttft_p95_s"), False, 0.50),
    ("models_materialize_p95_s",
     ("details", "model_plane", "materialize_p95_s"), False, 0.50),
    ("models_decode_recompiles",
     ("details", "model_plane", "decode_recompiles"), False, 0.0),
    ("models_fork_page_amplification",
     ("details", "model_plane", "fork_page_amplification_vs_4x"),
     False, 0.30),
    # Durability plane (ISSUE 20): journal-on sustained tok/s over
    # journal-off, same trace, per fsync policy — the default per-tick
    # group commit carries a HARD 0.9 floor as a run-fast invariant,
    # and these rows ratchet the ratio from history on top — plus the
    # cold-resume wall for the fast wave's in-flight streams.  All
    # gate vacuously (no_baseline) until a round records them.
    ("serving_journal_sustained_ratio",
     _SERVING + ("journal", "sustained_ratio_tick"), True, 0.10),
    ("serving_journal_fsync_always_ratio",
     _SERVING + ("journal", "sustained_ratio_always"), True, 0.25),
    ("serving_journal_recovery_s",
     _SERVING + ("journal", "recovery_s"), False, 0.60),
]


def load_bench(path: str) -> Optional[Dict[str, Any]]:
    """One bench round as its raw result dict, whatever the wrapper."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if isinstance(doc, dict) and "parsed" in doc:
        doc = doc["parsed"]
    if not isinstance(doc, dict) or "details" not in doc:
        return None
    return doc


def extract(doc: Dict[str, Any], path: Tuple[str, ...]) -> Optional[float]:
    node: Any = doc
    for key in path:
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    try:
        return float(node)
    except (TypeError, ValueError):
        return None


def best_of(
    values: List[float], higher_is_better: bool
) -> Optional[float]:
    if not values:
        return None
    return max(values) if higher_is_better else min(values)


def gate(
    candidate: Dict[str, Any],
    history: List[Tuple[str, Dict[str, Any]]],
    tolerance_override: Optional[float] = None,
) -> Dict[str, Any]:
    """The verdict: per metric, candidate vs best-of-history inside the
    tolerance band.  ``pass`` is True iff nothing regressed."""
    verdict: Dict[str, Any] = {
        "baseline_rounds": [name for name, _ in history],
        "metrics": {},
        "pass": True,
    }
    for name, path, higher, tol in METRICS:
        if tolerance_override is not None:
            tol = tolerance_override
        baseline = best_of(
            [
                v for _, doc in history
                if (v := extract(doc, path)) is not None
            ],
            higher,
        )
        cand = extract(candidate, path)
        row: Dict[str, Any] = {
            "baseline_best": baseline,
            "candidate": cand,
            "higher_is_better": higher,
            "tolerance": tol,
        }
        if baseline is None:
            row["status"] = "no_baseline"
        elif cand is None:
            # History tracks this number and the candidate stopped
            # reporting it: the bench itself regressed.
            row["status"] = "missing_from_candidate"
            verdict["pass"] = False
        else:
            limit = (
                baseline * (1.0 - tol) if higher else baseline * (1.0 + tol)
            )
            row["limit"] = round(limit, 6)
            ok = cand >= limit if higher else cand <= limit
            row["status"] = "ok" if ok else "regressed"
            if baseline and cand:
                row["vs_best"] = round(
                    cand / baseline if higher else baseline / cand, 4
                )
            if not ok:
                verdict["pass"] = False
        verdict["metrics"][name] = row
    return verdict


# ---------------------------------------------------------------------------
# --run-fast: the CPU-sized serving scenario + observatory invariants


def run_fast() -> Dict[str, Any]:
    """A minutes-not-hours serving round: tiny llama on whatever backend
    is present (CI: the virtual CPU mesh), reporting the same serving
    metric names the headline bench feeds the gate — plus the compile
    observatory's per-program counts, the steady-state decode-recompile
    invariant (asserted WITH the shadow auditor at 100% sampling: audit
    replays must reuse the same compiled geometries), the audit
    on/off sustained ratio, and the HBM ledger rows."""
    sys.path.insert(0, REPO)
    import jax

    import numpy as np

    from torchdistx_tpu import telemetry
    from torchdistx_tpu.models import llama
    from torchdistx_tpu.serving import Engine

    cfg = llama.llama_test()
    params = llama.init_params(jax.random.PRNGKey(0), cfg)

    def make_engine(journal=None):
        return Engine(
            params, model=llama, cfg=cfg, num_slots=4, block_size=8,
            num_blocks=41, max_model_len=64, decode_chunk=4,
            handle_preemption=False, journal=journal,
        )

    rng = np.random.default_rng(0)
    n_req = 24
    prompts = [
        rng.integers(0, cfg.vocab_size, size=int(p)).astype(np.int32)
        for p in rng.integers(4, 17, size=n_req)
    ]
    outs = rng.integers(8, 25, size=n_req)
    arrival = np.cumsum(rng.poisson(1.0, size=n_req))

    # Warm every program on a throwaway engine; the measured engine
    # reuses the jit cache, so ANY compile it triggers is a recompile
    # the steady-state invariant forbids.
    warm = make_engine()
    for p in (4, 8, 16):
        warm.submit(
            np.arange(1, 1 + p, dtype=np.int32), max_new_tokens=2, key=0
        )
    warm.drain()
    warm.close()

    import time

    def run_trace(eng):
        t0 = time.perf_counter()
        i = tick = 0
        while (
            i < n_req or len(eng.scheduler) or eng.stats()["running"]
            or eng.audit_backlog()
        ):
            while i < n_req and arrival[i] <= tick:
                eng.submit(prompts[i], max_new_tokens=int(outs[i]), key=i)
                i += 1
            eng.step()
            tick += 1
        return time.perf_counter() - t0, eng.stats()

    from torchdistx_tpu.telemetry import ops as tdx_ops

    c0 = telemetry.counters()
    # Time plane on (no HTTP listener): the fast round must produce the
    # host/device split and the tick-phase breakdown the full bench
    # reports — invariants checked in main().
    prev_attr = tdx_ops.enable_tick_attribution(True)
    eng = make_engine()
    wall, st = run_trace(eng)
    from torchdistx_tpu.telemetry import timeplane

    host_frac = telemetry.gauge(
        "serve.host_overhead_frac", engine=eng.engine_id
    ).value
    tick_phases = {
        phase: summ["count"]
        for phase, summ in timeplane.phase_summaries(eng.engine_id).items()
    }
    tdx_ops.enable_tick_attribution(prev_attr)
    # The same trace with the shadow auditor at 100% sampling: the
    # decode-recompile invariant below covers this run too — audit
    # replays must compile NOTHING new — and the sustained ratio is
    # the audit-overhead acceptance number.
    aeng = Engine(
        params, model=llama, cfg=cfg, num_slots=4, block_size=8,
        num_blocks=41, max_model_len=64, decode_chunk=4,
        handle_preemption=False, audit_sample=1.0,
    )
    _a_wall, a_st = run_trace(aeng)
    c1 = telemetry.counters()

    compile_counts = {
        k: v - c0.get(k, 0)
        for k, v in c1.items()
        if k.startswith("compile.count") and v - c0.get(k, 0)
    }
    decode_recompiles = c1.get(
        "compile.count{program=decode_chunk}", 0
    ) - c0.get("compile.count{program=decode_chunk}", 0)
    hbm = {
        k: v
        for k, v in telemetry.gauges().items()
        if k.startswith("mem.hbm_bytes")
    }
    eng.close()
    audit_row = {
        "audit_sample": 1.0,
        "sustained_decode_tokens_per_s": a_st.get("decode_tokens_per_s"),
        "audit_checked": a_st.get("audit_checked"),
        "audit_divergences": a_st.get("audit_divergences"),
    }
    if st.get("decode_tokens_per_s") and a_st.get("decode_tokens_per_s"):
        audit_row["sustained_ratio"] = round(
            a_st["decode_tokens_per_s"] / st["decode_tokens_per_s"], 3
        )
    aeng.close()
    # The same trace again with the request journal on, once per fsync
    # policy — the durability-overhead acceptance numbers.  The default
    # per-tick group commit carries a HARD 0.9 floor (checked in
    # main()); always/async are reported for the record.  Runs after
    # the c0/c1 window on purpose: resume replays prefill
    # prompt+committed, whose lengths can land in buckets the warm-up
    # never saw — legitimate compiles, not steady-state leaks.
    import shutil
    import tempfile

    from torchdistx_tpu.serving import RequestJournal

    jroot = tempfile.mkdtemp(prefix="tdx-bench-journal-")
    journal_row: Dict[str, Any] = {"fsync_policy_default": "tick"}
    try:
        for policy in ("tick", "always", "async"):
            jeng = make_engine(
                journal=RequestJournal(
                    os.path.join(jroot, policy), fsync=policy
                )
            )
            _j_wall, j_st = run_trace(jeng)
            jeng.close()
            tps = j_st.get("decode_tokens_per_s")
            journal_row[f"decode_tokens_per_s_{policy}"] = tps
            if st.get("decode_tokens_per_s") and tps:
                journal_row[f"sustained_ratio_{policy}"] = round(
                    tps / st["decode_tokens_per_s"], 3
                )
        # Cold-resume wall: journal the whole wave, kill the engine
        # mid-decode (in-process kill -9 stand-in: drop the journal
        # unclosed, free the live pid's lock), then time a fresh
        # engine from resume_from_journal through completion of every
        # resumed stream — replay prefills included.
        rdir = os.path.join(jroot, "recover")
        jeng = make_engine(journal=RequestJournal(rdir))
        for i in range(n_req):
            jeng.submit(prompts[i], max_new_tokens=int(outs[i]), key=i)
        for _ in range(4):
            jeng.step()
        jj = jeng._journal
        jeng._journal = None
        jj.release()
        jeng.close()
        reng = make_engine()
        t0 = time.perf_counter()
        handles = reng.resume_from_journal(RequestJournal(rdir))
        reng.drain()
        journal_row["recovery_s"] = round(time.perf_counter() - t0, 4)
        journal_row["recovered_streams"] = sum(
            1 for h in handles.values() if h.error is None
        )
        reng.close()
    finally:
        shutil.rmtree(jroot, ignore_errors=True)
    return {
        "details": {
            "serving_llama_350m_continuous": {
                # The fast scenario reports under the same keys the
                # headline bench uses, so fast rounds gate against fast
                # history with the same METRICS table.
                "sustained_decode_tokens_per_s": st.get(
                    "decode_tokens_per_s"
                ),
                "ttft_p95_s": st.get("ttft_p95_s"),
                "tpot_p95_s": st.get("tpot_p95_s"),
                "wall_s": round(wall, 3),
                "n_requests": n_req,
                "compile_counts": compile_counts,
                "decode_recompiles_steady": decode_recompiles,
                "hbm_bytes": hbm,
                "host_overhead_frac": host_frac,
                "tick_phase_counts": tick_phases,
                "audit": audit_row,
                "journal": journal_row,
            }
        },
        "fast": True,
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--baseline", action="append", default=None,
        help="history file or glob (repeatable; default none — every "
        "row then gates vacuously as no_baseline)",
    )
    ap.add_argument("--candidate", help="bench JSON to gate")
    ap.add_argument(
        "--run-fast", action="store_true",
        help="run the CPU-sized serving scenario as the candidate and "
        "enforce the compile-observatory invariants",
    )
    ap.add_argument(
        "--tolerance", type=float, default=None,
        help="override every metric's tolerance band (fraction)",
    )
    ap.add_argument("--output", help="write the verdict JSON here too")
    args = ap.parse_args(argv)

    # History is only what the caller names (--run-fast produces a
    # serving-only row from a DIFFERENT scenario than the headline bench,
    # so it must name fast-round baselines to gate against any).
    history: List[Tuple[str, Dict[str, Any]]] = []
    for pat in args.baseline or []:
        for path in sorted(glob.glob(pat)):
            doc = load_bench(path)
            if doc is not None:
                history.append((os.path.basename(path), doc))

    invariant_failures: List[str] = []
    if args.run_fast:
        candidate = run_fast()
        fast = candidate["details"]["serving_llama_350m_continuous"]
        if fast["decode_recompiles_steady"] != 0:
            invariant_failures.append(
                "steady-state decode recompiles = "
                f"{fast['decode_recompiles_steady']} (must be 0 — WITH "
                "auditing enabled: the decode chunk compiled again after "
                "warm-up, a shape leak in the serving or audit path)"
            )
        if not fast["hbm_bytes"]:
            invariant_failures.append(
                "HBM ledger empty: mem.hbm_bytes{component=} rows missing"
            )
        hf = fast.get("host_overhead_frac")
        if hf is None or not 0.0 <= hf <= 1.0:
            invariant_failures.append(
                f"host_overhead_frac missing or out of [0,1]: {hf!r} — "
                "the time plane's tick decomposition did not run"
            )
        if not fast.get("tick_phase_counts"):
            invariant_failures.append(
                "serve.tick_phase_s rows missing — no tick-phase "
                "breakdown recorded"
            )
        audit = fast.get("audit") or {}
        if not audit.get("audit_checked"):
            invariant_failures.append(
                "shadow auditor checked nothing in the audited fast round"
            )
        if audit.get("audit_divergences"):
            invariant_failures.append(
                f"audit.divergences = {audit['audit_divergences']} in the "
                "fast round — determinism broke under audit replay"
            )
        journal = fast.get("journal") or {}
        jr = journal.get("sustained_ratio_tick")
        if jr is None:
            invariant_failures.append(
                "journal overhead row missing from the fast round — the "
                "journaled trace did not report a sustained ratio"
            )
        elif jr < 0.9:
            invariant_failures.append(
                f"journal-on sustained tok/s ratio {jr} < 0.9 under the "
                "default per-tick group commit — durability is over "
                "budget (ISSUE 20 acceptance floor)"
            )
        if not journal.get("recovered_streams"):
            invariant_failures.append(
                "cold resume recovered no streams in the fast round — "
                "resume_from_journal re-admitted nothing"
            )
    elif args.candidate:
        candidate = load_bench(args.candidate)
        if candidate is None:
            print(
                f"bench_gate: cannot parse candidate {args.candidate}",
                file=sys.stderr,
            )
            return 2
    else:
        ap.error("one of --candidate or --run-fast is required")
        return 2  # pragma: no cover — argparse exits

    verdict = gate(candidate, history, args.tolerance)
    if args.run_fast:
        verdict["fast_serving"] = candidate["details"][
            "serving_llama_350m_continuous"
        ]
    if invariant_failures:
        verdict["pass"] = False
        verdict["invariant_failures"] = invariant_failures

    out = json.dumps(verdict, indent=2, sort_keys=True)
    print(out)
    if args.output:
        with open(args.output, "w") as f:
            f.write(out + "\n")
    return 0 if verdict["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
