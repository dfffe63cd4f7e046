"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, the
device operations that took most time, the idle gaps by what the host was
doing, and the device time of each compiled program.

What the trace holds (read with ``jax.profiler.ProfileData``, looked at by
hand on a v5e, PR 24): a plane ``/device:TPU:<n>`` per chip with the lines
``XLA Modules`` (one event per execution of a compiled program, named
``jit_<fn>(<hash>)``) and ``XLA Ops`` (one event per operation, named by its
HLO text); and a plane ``/host:CPU`` with a line per thread, on one of which
the benchmark's ``jax.profiler.TraceAnnotation`` spans appear.  Times are
nanoseconds on one clock.
"""

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.trace_window"
_SPAN_PREFIXES = ("bench.", "serve.", "materialize.")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def program_name(module_event: str) -> str:
    """``jit__decode_chunk(123)`` -> ``_decode_chunk``."""
    name = module_event.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


_OP = re.compile(r"^%?(?P<op>[^\s=]+)\s*=\s*(?P<rest>.*)$", re.S)


def op_name(event: str) -> str:
    """``%fusion.2 = bf16[2,8]{1,0:T(8,128)} fusion(...)`` ->
    ``fusion.2 bf16[2,8]``: the operation and the shape of its result,
    without layouts or operands (a tuple result keeps its first shape)."""
    m = _OP.match(event)
    if not m:
        return event[:80]
    shape = re.match(r"\(?\s*([a-z0-9]+\[[^\]]*\])", m.group("rest"))
    return m.group("op") + (" " + shape.group(1) if shape else "")


def reduce(path: str, top: int = 10) -> Optional[dict]:
    """The reduction.  Returns None when the trace holds no window
    annotation or no device plane (nothing to read)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans, window = [], [], None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                evs = [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in ln.events
                ]
                hit = [e for e in evs if e[0] == WINDOW]
                if hit:
                    window = (hit[0][1], hit[0][2])
                    spans = [
                        e for e in evs if e[0].startswith(_SPAN_PREFIXES)
                        and e[0] != WINDOW
                    ]
    if window is None or not devices:
        return None
    w0, w1 = window

    busy_ns, op_ns, prog_ns, prog_n = [], {}, {}, {}
    gaps_by_span: Dict[str, float] = {}
    for lines in devices:
        modules = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, program_name(e.name))
            for e in getattr(lines.get("XLA Modules"), "events", ())
        )
        starts = [m[0] for m in modules]
        for a, b, name in modules:
            if a >= w0 and b <= w1:  # whole executions only
                prog_ns[name] = prog_ns.get(name, 0.0) + (b - a)
                prog_n[name] = prog_n.get(name, 0) + 1
        clipped = []
        for e in lines["XLA Ops"].events:
            a, b = max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            i = bisect.bisect_right(starts, e.start_ns) - 1
            prog = (
                modules[i][2]
                if i >= 0 and e.start_ns < modules[i][1] else "?"
            )
            key = f"{prog}/{op_name(e.name)}"
            op_ns[key] = op_ns.get(key, 0.0) + (b - a)
        merged = _union(clipped)
        busy_ns.append(sum(b - a for a, b in merged))
        # Idle gaps of this chip, each charged to the narrowest host span
        # that covers the gap's start.
        edges = [w0] + [x for ab in merged for x in ab] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            cover = [s for s in spans if s[1] <= a < s[2]]
            name = (
                min(cover, key=lambda s: s[2] - s[1])[0] if cover
                else "(no span)"
            )
            gaps_by_span[name] = gaps_by_span.get(name, 0.0) + (b - a)

    n = len(devices)

    def rank(d):
        return [
            [k, v / 1e9 / n]
            for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]
        ]

    return {
        "busy_s": sum(busy_ns) / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "chips": n,
        "device_ops": rank(op_ns),
        "idle_gaps": rank(gaps_by_span),
        "programs": {
            k: {"count": prog_n[k] / n, "device_s": v / 1e9 / n}
            for k, v in prog_ns.items()
        },
    }

