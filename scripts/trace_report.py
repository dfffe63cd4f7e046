#!/usr/bin/env python
"""Reconstruct per-request timelines from a telemetry JSONL trace.

The serving/fleet stack emits a request-scoped lifecycle event stream
(``req.submitted → req.queued → req.admitted → req.prefill_chunk×N →
req.first_token → req.preempted/req.swapped/req.resumed →
req.migrated_out/req.migrated_in/req.migration_fallback →
req.failover_hop → req.finished | req.failed``) where every event — and
every ``serve.*`` span started inside the request's trace scope —
carries the same ``rid`` (trace id), the ``engine`` that emitted it, and
the failover ``hop`` number (see docs/observability.md, "Request
tracing").  This analyzer groups a trace (chaos soak, bench, or
production) by ``rid`` and answers "where did this request's time go":

* a **phase breakdown** per request — queue wait, prefill, decode,
  preemption outage, migration transit, failover — attributed
  interval-by-interval between
  consecutive events, so the phases sum to the request's wall time
  (anything between events this tool does not recognize lands in
  ``unaccounted`` instead of silently inflating a known phase);
* **completeness validation** — every submitted request must reach a
  terminal event (``req.finished`` or ``req.failed``), hop numbers must
  be monotone, the terminal must be the timeline's last event, and no
  span may carry a ``rid`` that never submitted (an orphan span means a
  trace-context leak);
* aggregate percentiles (TTFT from the ``req.first_token`` events,
  per-outcome counts, fleet hop distribution) and optional JSON export.

Usage::

    python scripts/trace_report.py /tmp/chaos.jsonl            # summary
    python scripts/trace_report.py trace.jsonl --per-request   # + rows
    python scripts/trace_report.py trace.jsonl --json out.json
    python scripts/trace_report.py trace.jsonl --strict        # CI gate:
        # exit 1 on any incomplete timeline, orphan span, hop-order
        # violation, or unaccounted time above --tolerance (fraction of
        # the request's wall time, default 0.05)

The ops plane's live ``/requests`` view imports :func:`reconstruct`
directly (``telemetry/ops.py``), so live numbers and post-mortem numbers
come from the same reconstruction path.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, Iterable, List, Optional

__all__ = [
    "RequestTimeline",
    "TraceReport",
    "load_records",
    "reconstruct",
]

# Interval attribution: the time between two consecutive events belongs
# to the phase the EARLIER event put the request in.
_STATE_AFTER = {
    "req.submitted": "queue",
    "req.queued": "queue",
    "req.admitted": "prefill",
    "req.prefill_chunk": "prefill",
    "req.first_token": "decode",
    "req.resumed": "decode",
    "req.preempted": "preempt",
    "req.swapped": "preempt",
    "req.failover_hop": "queue",  # placed on the peer; waiting to admit
    # Stream migration (docs/fleet.md, "Disaggregation & stream
    # migration"): pages in transit between the export and the import;
    # a fallback means the snapshot was dropped and the stream is down
    # until the cold replay re-places it — a failover outage.
    "req.migrated_out": "migrate",
    "req.migrated_in": "decode",
    "req.migration_fallback": "failover",
}
PHASES = (
    "queue", "prefill", "decode", "preempt", "migrate", "failover",
    "unaccounted",
)
_TERMINAL = ("req.finished", "req.failed")


class RequestTimeline:
    """One request's reconstructed life, across engines and hops."""

    def __init__(self, rid: str):
        self.rid = rid
        self.events: List[Dict[str, Any]] = []
        self.spans: List[Dict[str, Any]] = []

    # -- derived views ------------------------------------------------------

    def _sorted(self) -> List[Dict[str, Any]]:
        return sorted(self.events, key=lambda e: e["ts"])

    @property
    def outcome(self) -> str:
        """``"finished"``, ``"failed:<ErrorType>"``, or ``"incomplete"``.

        Only the LAST event decides: a retryable ``req.failed`` with a
        ``req.failover_hop`` after it was not the end of the request."""
        evs = self._sorted()
        if not evs:
            return "incomplete"
        last = evs[-1]
        if last["name"] == "req.finished":
            return "finished"
        if last["name"] == "req.failed":
            return f"failed:{(last.get('attrs') or {}).get('error', '?')}"
        return "incomplete"

    @property
    def complete(self) -> bool:
        evs = self._sorted()
        return bool(evs) and evs[-1]["name"] in _TERMINAL and any(
            e["name"] == "req.submitted" for e in evs
        )

    @property
    def truncated(self) -> bool:
        """Head events evicted by flight-ring wraparound: the timeline
        has events but no ``req.submitted``.  A bounded ring (the
        flight recorder, a capped collector) legitimately drops the
        oldest records, so a long-lived request reconstructed from a
        dump can lose its head — that is ring wraparound, not a
        trace-context leak, and :meth:`TraceReport.problems` excludes
        truncated timelines from ``--strict`` completeness accounting
        (counted separately in the summary) — but only when the trace
        actually contains a flight-dump window; in a full trace a
        headless timeline is still flagged as a leak."""
        return bool(self.events) and not any(
            e["name"] == "req.submitted" for e in self.events
        )

    @property
    def engines(self) -> List[str]:
        """Engines that touched the request, in order of first touch."""
        seen: List[str] = []
        for ev in self._sorted():
            eng = ev.get("engine")
            if eng and eng != "fleet" and eng not in seen:
                seen.append(eng)
        return seen

    @property
    def hops(self) -> List[int]:
        return [
            int(ev.get("hop", 0))
            for ev in self._sorted()
            if ev.get("hop") is not None
        ]

    @property
    def hops_monotone(self) -> bool:
        h = self.hops
        return all(a <= b for a, b in zip(h, h[1:]))

    @property
    def n_tokens(self) -> Optional[int]:
        for ev in reversed(self._sorted()):
            if ev["name"] in _TERMINAL:
                n = (ev.get("attrs") or {}).get("n_tokens")
                return None if n is None else int(n)
        return None

    @property
    def ttft_s(self) -> Optional[float]:
        for ev in self._sorted():
            if ev["name"] == "req.first_token":
                t = (ev.get("attrs") or {}).get("ttft_s")
                return None if t is None else float(t)
        return None

    @property
    def digest(self) -> Optional[str]:
        """The request's determinism digest (docs/observability.md,
        "Audit plane"): the full-stream snapshot from req.finished when
        the request completed, else the admitted-identity snapshot from
        req.first_token."""
        for ev in reversed(self._sorted()):
            if ev["name"] in ("req.finished", "req.first_token"):
                d = (ev.get("attrs") or {}).get("digest")
                if d is not None:
                    return str(d)
        return None

    def phases(self) -> Dict[str, float]:
        """Wall-clock per phase, summing to the request's total.

        Interval attribution between consecutive events; an interval
        following a *retryable* ``req.failed`` is ``failover`` (the
        stream is down until the hop re-places it), and one following
        an event this tool does not know is ``unaccounted``."""
        out = {p: 0.0 for p in PHASES}
        evs = self._sorted()
        if len(evs) < 2:
            out["total"] = 0.0
            return out
        state = "queue"
        for prev, nxt in zip(evs, evs[1:]):
            name = prev["name"]
            if name == "req.failed":
                # Retryable + anything after it = failover outage.
                state = "failover"
            else:
                state = _STATE_AFTER.get(name, "unaccounted")
            out[state] += max(0.0, nxt["ts"] - prev["ts"])
        out["total"] = max(0.0, evs[-1]["ts"] - evs[0]["ts"])
        return out

    def problems(self, tolerance: float = 0.05) -> List[str]:
        """Validation failures for this timeline (empty = clean).
        Truncated timelines (head evicted by ring wraparound) validate
        vacuously — their phase attribution and completeness cannot be
        judged without the missing head."""
        if self.truncated:
            return []
        out: List[str] = []
        evs = self._sorted()
        if not any(e["name"] == "req.submitted" for e in evs):
            out.append("no req.submitted event")
        if not evs or evs[-1]["name"] not in _TERMINAL:
            out.append(
                "incomplete: timeline does not end in req.finished/"
                "req.failed"
            )
        if not self.hops_monotone:
            out.append(f"hop numbers not monotone: {self.hops}")
        ph = self.phases()
        if ph["total"] > 0 and ph["unaccounted"] > tolerance * ph["total"]:
            out.append(
                f"unaccounted wall time {ph['unaccounted']:.4f}s exceeds "
                f"{tolerance:.0%} of total {ph['total']:.4f}s"
            )
        return out

    def summary(self) -> Dict[str, Any]:
        ph = self.phases()
        return {
            "rid": self.rid,
            "outcome": self.outcome,
            "truncated": self.truncated,
            "engines": self.engines,
            "max_hop": max(self.hops, default=0),
            "n_events": len(self.events),
            "n_spans": len(self.spans),
            "n_tokens": self.n_tokens,
            "ttft_s": self.ttft_s,
            "digest": self.digest,
            "phases": {k: round(v, 6) for k, v in ph.items()},
        }


class TraceReport:
    """Whole-trace reconstruction: timelines + trace-level validation."""

    def __init__(self):
        self.requests: Dict[str, RequestTimeline] = {}
        self.orphan_spans: List[Dict[str, Any]] = []
        self.flight_dumps: List[Dict[str, Any]] = []

    def problems(self, tolerance: float = 0.05) -> List[str]:
        out: List[str] = []
        for rid in sorted(self.requests):
            tl = self.requests[rid]
            if tl.truncated and not self.flight_dumps:
                # Ring wraparound is only possible in a dumped ring
                # window — and every dump carries its header marker.  A
                # headless timeline in a trace with NO dump windows is a
                # genuine trace-context leak (a full TDX_TELEMETRY trace
                # never drops a head), so --strict still catches it.
                out.append(
                    f"{rid}: no req.submitted event (and no flight-dump "
                    "window in the trace to explain ring truncation)"
                )
                continue
            for p in tl.problems(tolerance):
                out.append(f"{rid}: {p}")
        if self.orphan_spans:
            names = sorted({s["name"] for s in self.orphan_spans})
            out.append(
                f"{len(self.orphan_spans)} orphan span(s) carrying a rid "
                f"that never submitted: {names}"
            )
        return out

    def summary(self, tolerance: float = 0.05) -> Dict[str, Any]:
        outcomes: Dict[str, int] = {}
        totals = {p: 0.0 for p in PHASES}
        ttfts: List[float] = []
        hops: List[int] = []
        for tl in self.requests.values():
            key = tl.outcome
            outcomes[key] = outcomes.get(key, 0) + 1
            for p, v in tl.phases().items():
                if p in totals:
                    totals[p] += v
            if tl.ttft_s is not None:
                ttfts.append(tl.ttft_s)
            hops.append(max(tl.hops, default=0))
        out: Dict[str, Any] = {
            "n_requests": len(self.requests),
            "outcomes": dict(sorted(outcomes.items())),
            "complete": sum(tl.complete for tl in self.requests.values()),
            "truncated": sum(
                tl.truncated for tl in self.requests.values()
            ),
            "phase_totals_s": {k: round(v, 4) for k, v in totals.items()},
            "failovers": sum(h > 0 for h in hops),
            "max_hop": max(hops, default=0),
            "flight_dumps": len(self.flight_dumps),
            "orphan_spans": len(self.orphan_spans),
            "problems": self.problems(tolerance),
        }
        if ttfts:
            ttfts.sort()

            def pct(p):
                return round(ttfts[min(len(ttfts) - 1,
                                       int(p / 100.0 * len(ttfts)))], 4)

            out["ttft_p50_s"] = pct(50)
            out["ttft_p95_s"] = pct(95)
        return out


def load_records(path: str) -> List[Dict[str, Any]]:
    """Parse a JSONL trace file (malformed lines fail loudly — a trace
    that doesn't parse is a bug, not noise)."""
    records = []
    with open(path, encoding="utf-8") as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError as e:
                raise SystemExit(f"{path}:{i}: unparseable trace line: {e}")
    return records


def reconstruct(records: Iterable[Dict[str, Any]]) -> TraceReport:
    """Group a record stream (from :func:`load_records` or the in-memory
    collector's ``snapshot()["spans"]``) into per-request timelines."""
    report = TraceReport()
    spans_with_rid = []
    for rec in records:
        kind = rec.get("type")
        if kind == "event":
            name = rec.get("name", "")
            rid = rec.get("rid")
            if rid is None or not name.startswith("req."):
                continue
            rid = str(rid)
            tl = report.requests.get(rid)
            if tl is None:
                tl = report.requests[rid] = RequestTimeline(rid)
            tl.events.append(rec)
        elif kind == "span":
            if rec.get("rid") is not None:
                spans_with_rid.append(rec)
        elif kind == "flight_dump":
            report.flight_dumps.append(rec)
    for rec in spans_with_rid:
        tl = report.requests.get(str(rec["rid"]))
        if tl is None:
            report.orphan_spans.append(rec)
        else:
            tl.spans.append(rec)
    return report


def _fmt_row(s: Dict[str, Any]) -> str:
    ph = s["phases"]
    return (
        f"{s['rid']:<18} {s['outcome']:<28} hop={s['max_hop']} "
        f"eng={'+'.join(s['engines']) or '-':<12} "
        f"tok={s['n_tokens'] if s['n_tokens'] is not None else '-':<5} "
        f"total={ph.get('total', 0.0):7.3f}s  "
        f"q={ph['queue']:6.3f} pf={ph['prefill']:6.3f} "
        f"dec={ph['decode']:6.3f} pre={ph['preempt']:6.3f} "
        f"mig={ph['migrate']:6.3f} "
        f"fo={ph['failover']:6.3f} ?={ph['unaccounted']:6.3f}"
    )


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Per-request timeline reconstruction from a "
        "telemetry JSONL trace"
    )
    ap.add_argument("trace", help="JSONL trace file (TDX_TELEMETRY output)")
    ap.add_argument("--json", help="write the full report to this path")
    ap.add_argument(
        "--per-request", action="store_true",
        help="print one row per request",
    )
    ap.add_argument(
        "--strict", action="store_true",
        help="exit 1 on any validation problem (CI gate)",
    )
    ap.add_argument(
        "--tolerance", type=float, default=0.05,
        help="max unaccounted fraction of a request's wall time "
        "(default 0.05)",
    )
    ap.add_argument(
        "--require-flight-dump", action="store_true",
        help="with --strict: also fail unless the trace contains at "
        "least one flight_dump marker",
    )
    ap.add_argument(
        "--format", choices=("report", "perfetto"), default="report",
        help="'perfetto': export the trace as a Chrome/Perfetto "
        "trace-event timeline (scripts/timeline_export.py) to --json "
        "(or <trace>.perfetto.json) instead of the text report",
    )
    args = ap.parse_args(argv)

    if args.format == "perfetto":
        import timeline_export  # noqa: PLC0415 — sibling script

        argv2 = [args.trace]
        if args.json:
            argv2 += ["-o", args.json]
        if args.strict:
            argv2.append("--validate")
        return timeline_export.main(argv2)

    report = reconstruct(load_records(args.trace))
    summary = report.summary(args.tolerance)

    if args.per_request:
        for rid in sorted(report.requests):
            print(_fmt_row(report.requests[rid].summary()))
        print()
    print(f"requests:      {summary['n_requests']}")
    print(f"complete:      {summary['complete']}")
    if summary["truncated"]:
        print(f"truncated:     {summary['truncated']} (ring wraparound)")
    print(f"outcomes:      {summary['outcomes']}")
    print(f"phase totals:  {summary['phase_totals_s']}")
    print(
        f"failovers:     {summary['failovers']} "
        f"(max hop {summary['max_hop']})"
    )
    print(f"flight dumps:  {summary['flight_dumps']}")
    if "ttft_p50_s" in summary:
        print(
            f"ttft:          p50={summary['ttft_p50_s']}s "
            f"p95={summary['ttft_p95_s']}s"
        )
    problems = summary["problems"]
    if args.require_flight_dump and not report.flight_dumps:
        problems = problems + ["no flight_dump marker in the trace"]
    if problems:
        print(f"\nPROBLEMS ({len(problems)}):", file=sys.stderr)
        for p in problems:
            print(f"  - {p}", file=sys.stderr)

    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(
                {
                    "summary": summary,
                    "requests": [
                        report.requests[rid].summary()
                        for rid in sorted(report.requests)
                    ],
                },
                f, indent=2,
            )
        print(f"\nreport written to {args.json}")

    if args.strict and problems:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
