"""Device time by named scope: a profiler trace joined with the program's
scope map.

The trace (see ``trace.py``) names a device operation by its HLO text,
``%fusion.278 = bf16[2,1024,1600]{...} fusion(...)``; the program's perf
plane (``torchdistx_tpu.telemetry.perf.program_scopes()``) maps every
instruction name of the optimized program to its ``op_name`` path, e.g.
``jit(step_fn)/loss/transpose(jvp())/while/body/closed_call/checkpoint/mlp/dot_general``,
and a fusion also to the paths of the instructions fused into it.  Joined
by instruction name, each operation gets

* its **scope**: the innermost of ``SCOPES`` on its path (a component
  counts by its innermost word, so ``transpose(jvp(head))`` is ``head``),
  extended by a kernel of ``KERNELS`` named further down the path
  (``attn/flash_fwd``).  A FUSED operation takes the scope most of its
  paths carry: XLA fuses AdamW's update into the non-finite guard's select
  and names the fusion after the select, though most of its instructions
  are the optimizer's.  Paths with no known name do not vote,
  and a tie goes to the fusion's own path (the compiler's choice: on the
  TPU the fused matrix multiplication's where there is one).  No known
  name on any path, or no path: ``unscoped``;
* its **phase**: ``bwd`` when a path of that scope holds ``transpose(``,
  else ``fwd``.  The forward recomputed under ``jax.checkpoint`` runs
  inside the backward pass and counts as ``bwd``.

An operation counts by its SELF time: its interval less what the operations
nested in it on the same line cover.  A ``while``, ``conditional`` or
``call`` holds its body's operations and keeps only the loop's own gaps, so
containers are not counted twice; a kernel into whose interval the end of an
asynchronous copy falls gives that stretch to the copy.  So the scopes of a
program sum to the time its operations kept the device busy.  Only WHOLE
executions inside the traced window count, as for ``trace.reduce``'s
``programs``.
"""

import bisect
import re
from typing import Dict, List, Optional

from benchlib import trace

SCOPES = ("embed", "attn", "mlp", "head", "optimizer", "guard")
KERNELS = ("flash_fwd", "flash_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv")
UNSCOPED = "unscoped"

_WORD = re.compile(r"[A-Za-z_][\w.\-]*")
_INSTRUCTION = re.compile(r"^%?([^\s=]+)\s*=")


def scope_of(path: str) -> str:
    """``.../transpose(jvp(attn))/flash_bwd_fused/pallas_call`` ->
    ``attn/flash_bwd_fused``."""
    scope, kernel = UNSCOPED, None
    for component in path.split("/"):
        words = _WORD.findall(component)
        word = words[-1] if words else ""
        if word in SCOPES:
            scope, kernel = word, None
        elif word in KERNELS:
            kernel = word
    return f"{scope}/{kernel}" if kernel and scope != UNSCOPED else scope


def classify(paths) -> tuple:
    """``(scope, phase)`` of an operation from its paths, its own first."""
    voted: Dict[str, list] = {}
    for p in paths:
        scope = scope_of(p)
        if scope != UNSCOPED:
            voted.setdefault(scope, []).append(p)
    # max keeps the first of equals, and the operation's own path is first
    scope = max(voted, key=lambda k: len(voted[k])) if voted else UNSCOPED
    bwd = any("transpose(" in p for p in voted.get(scope, paths))
    return scope, "bwd" if bwd else "fwd"


def instruction(event_name: str) -> str:
    """``%fusion.2 = bf16[2,8]{1,0} fusion(...)`` -> ``fusion.2``."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def self_times(events: List[tuple]) -> List[int]:
    """For ``(start, end, ...)`` tuples of one line, in their order: each
    one's duration less what the events nested directly in it cover."""
    own = [e[1] - e[0] for e in events]
    stack: List[int] = []
    for i in sorted(
        range(len(events)), key=lambda i: (events[i][0], -events[i][1])
    ):
        a, b = events[i][0], events[i][1]
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(b, events[stack[-1]][1]) - a
        stack.append(i)
    return own


def reduce(path: str, scope_maps: Dict[str, Dict[str, tuple]],
           top: int = 10) -> Optional[dict]:
    """Per compiled program named in ``scope_maps`` (by its name in the
    trace, ``step_fn`` for ``jit_step_fn(<hash>)``): executions counted,
    their device seconds, the seconds their operations kept the device busy,
    self seconds per scope and phase, calls and whole seconds per kernel,
    and the ``top`` unscoped operations by self seconds.  None when the
    trace holds no window or device."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, window = [], None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                for e in ln.events:
                    if e.name == trace.WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    if window is None or not devices:
        return None
    w0, w1 = window

    out = {
        prog: {"count": 0, "device_s": 0.0, "busy_s": 0.0, "scopes": {},
               "kernels": {}, "unscoped_ops": {}}
        for prog in scope_maps
    }
    kinds: Dict[tuple, tuple] = {}
    for lines in devices:
        modules = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, trace.program_name(e.name))
            for e in getattr(lines.get("XLA Modules"), "events", ())
        )
        modules = [
            m for m in modules if m[2] in out and m[0] >= w0 and m[1] <= w1
        ]
        starts = [m[0] for m in modules]
        for a, b, prog in modules:
            out[prog]["count"] += 1
            out[prog]["device_s"] += (b - a) / 1e9
        ops = []
        for e in lines["XLA Ops"].events:
            i = bisect.bisect_right(starts, e.start_ns) - 1
            if i >= 0 and e.start_ns < modules[i][1]:
                ops.append(
                    (e.start_ns, e.start_ns + e.duration_ns, e.name, modules[i][2])
                )
        for prog in out:
            mine = [(a, b) for a, b, _, p in ops if p == prog]
            out[prog]["busy_s"] += sum(b - a for a, b in trace._union(mine)) / 1e9
        for (a, b, name, prog), own in zip(ops, self_times(ops)):
            p, s = out[prog], own / 1e9
            if (prog, name) not in kinds:  # one event name per instruction
                kinds[prog, name] = classify(
                    scope_maps[prog].get(instruction(name), ())
                )
            scope, phase = kinds[prog, name]
            cell = p["scopes"].setdefault(scope, {"fwd": 0.0, "bwd": 0.0})
            cell[phase] += s
            if "/" in scope:
                k = p["kernels"].setdefault(
                    scope.split("/", 1)[1], {"calls": 0, "device_s": 0.0}
                )
                k["calls"] += 1
                k["device_s"] += (b - a) / 1e9
            if scope == UNSCOPED:
                key = trace.op_name(name)
                p["unscoped_ops"][key] = p["unscoped_ops"].get(key, 0.0) + s

    n = len(devices)
    for p in out.values():
        p["count"] /= n
        p["device_s"] /= n
        p["busy_s"] /= n
        for cell in p["scopes"].values():
            cell["fwd"] /= n
            cell["bwd"] /= n
        for k in p["kernels"].values():
            k["calls"] /= n
            k["device_s"] /= n
        ranked = sorted(p["unscoped_ops"].items(), key=lambda kv: -kv[1])
        p["unscoped_ops"] = [[k, v / n] for k, v in ranked[:top]]
    return out


def table(program: str, p: dict) -> str:
    """The log's table for one program: scope x phase in device ms per
    execution with each row's share, the kernels' calls per execution,
    and the largest unscoped operations."""
    n = p["count"]
    if not n:
        return f"scopes of {program}: no whole execution in the traced window"
    total = sum(c["fwd"] + c["bwd"] for c in p["scopes"].values())
    rows = [
        f"scopes of {program}: {n:g} executions, device "
        f"{1e3 * p['device_s'] / n:.2f} ms each, operations busy "
        f"{1e3 * p['busy_s'] / n:.2f} ms, scopes sum to {1e3 * total / n:.2f} ms",
        f"  {'scope':<22}{'fwd ms':>10}{'bwd ms':>10}{'ms':>10}{'share':>8}",
    ]
    for scope, c in sorted(
        p["scopes"].items(), key=lambda kv: -(kv[1]["fwd"] + kv[1]["bwd"])
    ):
        both = c["fwd"] + c["bwd"]
        rows.append(
            f"  {scope:<22}{1e3 * c['fwd'] / n:>10.3f}{1e3 * c['bwd'] / n:>10.3f}"
            f"{1e3 * both / n:>10.3f}{100 * both / total:>7.1f}%"
        )
    for kernel, k in sorted(p["kernels"].items()):
        rows.append(
            f"  kernel {kernel}: {k['calls'] / n:g} calls per execution, "
            f"{1e6 * k['device_s'] / k['calls']:.1f} us each"
        )
    for name, s in p["unscoped_ops"]:
        rows.append(f"  unscoped {1e3 * s / n:8.3f} ms  {name}")
    return "\n".join(rows)
