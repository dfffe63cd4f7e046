"""Perf plane: compile observatory + HBM ledger with OOM forensics.

PR 9/10 made the *request* path transparent; this module opens the
*device* side — the two costs that actually sink a serving engine or a
materialization and that nothing upstream could attribute:

**Compile observatory.**  XLA compile time dominates materialization
cost (the very fact ``utils/compilation_cache.py`` exists for), and the
serving engine's whole performance model rests on ONE compiled decode
chunk — a shape leak that recompiles it per tick shows up only as
mysteriously cratered tok/s.  Three labeled families make both visible:

* ``compile.count{program=}`` — compiles per program label,
* ``compile.time_s{program=}`` — compile-duration histogram,
* ``compile.recompiles{program=}`` — compiles beyond a program's first.

Four more say where a program's build time went before and around that
compile, each from the JAX event of the same phase and attributed the
same way (the compiling thread's ambient :func:`program` scope, else
``other``): ``compile.trace_s{program=}`` (tracing to a jaxpr; a jit
traced inside another counts its own time once, not again in its
caller's), ``compile.lower_s{program=}`` (jaxpr to MLIR module),
``compile.cache_load_s{program=}`` (reading an executable out of the
persistent cache; ``compile.time_s`` of the same compile holds it too,
for JAX times the backend compile around the cache lookup) and the
counter ``compile.cache_hits{program=}``.

Attribution is two-layered.  :class:`JitProgram` wraps a jitted callable
under a stable label and detects (re)compiles exactly, via the jit
cache-size delta around each call — donation, tracing, and monkeypatched
stand-ins (chaos tests swap the decode chunk for a flaky double) all
pass through untouched.  Where the running JAX exposes
``jax.monitoring`` duration events (:func:`install_monitoring`, hooked
by ``ensure_compilation_cache``), the listener supplies the precise
backend-compile duration and catches every compile *outside* a wrapped
call too (attributed to the ambient :func:`program` scope, else
``other``); without it, call wall time is the fallback.  Every event
lands exactly once: a scope in which the listener already counted
suppresses the fallback.

**Scope maps.**  A profile names a device operation by its HLO
instruction (``fusion.278``), which says nothing about *whose* time it
is.  A :class:`JitProgram` built with ``scopes=True`` (the train step)
records, at each compile it sees while a span sink is active, a map
from every instruction name of the OPTIMIZED program to its ``op_name``
path (``jit(step_fn)/optimizer/mul``; a fusion also lists the paths
fused into it) — :func:`program_scopes`.
A trace reduction joins its operation names with that map to sum device
time by ``jax.named_scope`` (docs/observability.md, "Scopes inside the
train step").  The map costs no second compile (JAX answers
``lower().compile()`` after the call from its in-memory caches), and
with no sink nothing is asked for at all.

The **recompile-storm detector** rides the recompile counter: the same
program recompiled ``TDX_RECOMPILE_STORM_N`` times (default 3) inside
``TDX_RECOMPILE_STORM_WINDOW_S`` (default 30 s) latches
``serve.recompile_storm{engine=}``, dumps the flight recorder with
``reason="recompile_storm"``, and marks the owning engine OVERLOADED
(the stall-watchdog convention: a fleet router routes around it; the
latch clears once the program goes a full window without recompiling).
A shape leak in the decode chunk is caught live, not as a lower rate
on the benchmark's next ledger line.

**HBM ledger.**  Device memory is spent by four subsystems — weights,
the paged KV pool, swap staging, prefix-cache-held pages — and a
``RESOURCE_EXHAUSTED`` names none of them.  :data:`ledger` attributes
bytes per component as ``mem.hbm_bytes{component=}`` gauges
(``register``/``unregister``; multiple owners of one component sum, and
shared ownership — N engines over one params pytree — dedupes by owner
key).  :func:`oom_dump` snapshots the ledger into the flight record
(``reason="device_oom"`` / ``"pool_exhausted"``) so an OOM post-mortem
reads *what held the memory*, not just that it ran out; :func:`is_oom`
classifies the error strings XLA actually raises.

Like the rest of telemetry: dependency-light (jax imported lazily, only
by the monitoring hookup), never fails the instrumented operation, and
free when nothing records — the non-compile fast path of a wrapped call
is two ints and a perf_counter.
"""

from __future__ import annotations

import itertools
import logging
import os
import re
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Optional, Tuple

from . import _core
from . import timeplane as _timeplane

__all__ = [
    "JitProgram",
    "Ledger",
    "hlo_scopes",
    "install_monitoring",
    "is_oom",
    "ledger",
    "monitoring_installed",
    "oom_dump",
    "program",
    "program_scopes",
    "pytree_nbytes",
    "record_compile",
    "storm_config",
]

_logger = logging.getLogger(__name__)

_T_OOMS = _core.counter("mem.ooms")
_T_STORMS = _core.counter("serve.recompile_storms")

# Substrings of the errors XLA actually raises when device memory runs
# out (XlaRuntimeError carries the grpc-style status name).
_OOM_MARKERS = (
    "RESOURCE_EXHAUSTED",
    "RESOURCE EXHAUSTED",
    "Out of memory",
    "out of memory",
    "OutOfMemory",
)


# ---------------------------------------------------------------------------
# Program attribution scopes + the jax.monitoring hookup

_tls = threading.local()

# The jax.monitoring duration event that means "XLA compiled a program"
# (a persistent-cache load fires it too: it wraps compile_or_get_cached).
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# The other phases of a program's build: duration event -> histogram family.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_PHASES = {
    _TRACE_EVENT: "compile.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile.cache_load_s",
}
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_install_lock = threading.Lock()
_monitoring = False  # listener registered successfully


class _Scope:
    """One thread's ambient program label for compile attribution.

    ``counted`` flips when the monitoring listener lands an event inside
    the scope, so the scope owner's wall-time fallback
    (:meth:`ensure_counted`) never double-counts a compile the listener
    already recorded precisely.  ``track`` marks labels that denote ONE
    program identity (the :class:`JitProgram` scopes): only those feed
    the recompile counter and the storm detector — a broad label like
    ``materialize`` or ``other`` covers many distinct programs, whose
    second compile is not a recompile of anything."""

    __slots__ = ("label", "owner", "counted", "track")

    def __init__(self, label: str, owner: Any = None, track: bool = False):
        self.label = label
        self.owner = owner
        self.counted = 0
        self.track = track

    def ensure_counted(self, fallback_duration_s: float) -> None:
        """Guarantee exactly one compile record for this scope: a no-op
        when the listener already attributed one, else the fallback
        (call wall time — an upper bound that includes the first
        execute, honest enough for the histogram's ~33% buckets)."""
        if not self.counted:
            record_compile(
                self.label, fallback_duration_s, owner=self.owner,
                track=self.track,
            )


class program:
    """Context manager: attribute XLA compiles in this thread to
    ``label`` (``with perf.program("materialize"): ...``).  Nests —
    the innermost scope wins.  Yields the scope object."""

    def __init__(self, label: str, owner: Any = None, track: bool = False):
        self.scope = _Scope(label, owner, track)

    def __enter__(self) -> _Scope:
        stack = getattr(_tls, "scopes", None)
        if stack is None:
            stack = _tls.scopes = []
        stack.append(self.scope)
        return self.scope

    def __exit__(self, *exc) -> bool:
        stack = getattr(_tls, "scopes", None)
        if stack and stack[-1] is self.scope:
            stack.pop()
        elif stack and self.scope in stack:  # tolerate imbalance
            stack.remove(self.scope)
        return False


def _current_scope() -> Optional[_Scope]:
    stack = getattr(_tls, "scopes", None)
    return stack[-1] if stack else None


def _own_trace_s(duration_s: float) -> float:
    """A trace's seconds less those of the traces nested in it.  JAX
    times every jit's tracing, a jit called inside another's too, and
    reports each at its END, so the nested ones are this thread's most
    recent reports that began after this one did.  The sum over a
    program's traces is then the wall time it spent tracing."""
    now = time.perf_counter()
    start = now - duration_s
    done = getattr(_tls, "traces", None)
    if done is None:
        done = _tls.traces = []
    nested = 0.0
    while done and done[-1][0] >= start:
        nested += done.pop()[1]
    done.append((start, duration_s))
    if len(done) > 1 << 15:  # top-level traces nothing will enclose
        del done[: 1 << 14]
    return duration_s - nested


def _on_duration_event(name: str, duration_s: float, **kwargs) -> None:
    """The jax.monitoring listener: every backend compile lands here,
    on the compiling thread, and is attributed to that thread's ambient
    scope (``other`` when none); so does every trace, lowering and
    persistent-cache load (``_PHASES``).  Never raises — telemetry must
    not fail the compile it observes."""
    try:
        scope = _current_scope()
        if name != _COMPILE_EVENT:
            family = _PHASES.get(name)
            if family is not None:
                if name == _TRACE_EVENT:
                    duration_s = _own_trace_s(duration_s)
                _core.histogram(
                    family, program=scope.label if scope else "other"
                ).observe(max(0.0, float(duration_s)))
            return
        if scope is not None:
            scope.counted += 1
            record_compile(
                scope.label, duration_s, owner=scope.owner,
                track=scope.track,
            )
        else:
            record_compile("other", duration_s)
    except Exception:  # noqa: BLE001
        pass


def _on_event(name: str, **kwargs) -> None:
    """The jax.monitoring listener of plain events: a program served by
    the persistent cache counts under the ambient scope.  Never raises."""
    try:
        if name == _CACHE_HIT_EVENT:
            scope = _current_scope()
            _core.counter(
                "compile.cache_hits", program=scope.label if scope else "other"
            ).add()
    except Exception:  # noqa: BLE001
        pass


def install_monitoring() -> bool:
    """Register the compile listeners with ``jax.monitoring``
    (idempotent; False when this JAX has no monitoring API).  Hooked by
    ``ensure_compilation_cache`` and the serving engine, so either
    entry point arms the observatory."""
    global _monitoring
    if _monitoring:
        return True
    with _install_lock:
        if _monitoring:
            return True
        try:
            from jax import monitoring as _jm

            _jm.register_event_duration_secs_listener(_on_duration_event)
            _jm.register_event_listener(_on_event)
            _monitoring = True
        except Exception:  # noqa: BLE001 — no jax / old jax: fallback timing
            return False
    return True


def monitoring_installed() -> bool:
    return _monitoring


# ---------------------------------------------------------------------------
# Compile recording + the recompile-storm detector

_storm_lock = threading.Lock()
# (program, engine_id) -> compiles seen for that exact program identity
# (tracked calls only).  Recompile semantics live HERE, not on the bare
# label: one process may hold N engines of different geometries, each
# legitimately compiling "decode_chunk" once — a recompile is the SAME
# engine's program compiling again.
_per_owner_compiles: Dict[Tuple[str, str], int] = {}
# (program, engine_id) -> deque of recompile timestamps in the window
_recompiles: Dict[Tuple[str, str], deque] = {}
# (program, engine_id) latched storms, cleared when the window drains
_latched: Dict[Tuple[str, str], float] = {}


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


_STORM_N = max(2, int(_env_float("TDX_RECOMPILE_STORM_N", 3)))
_STORM_WINDOW_S = _env_float("TDX_RECOMPILE_STORM_WINDOW_S", 30.0)


def storm_config(
    threshold: Optional[int] = None, window_s: Optional[float] = None
) -> Tuple[int, float]:
    """Read (and optionally set — tests) the storm detector's knobs:
    ``threshold`` recompiles of one program within ``window_s`` seconds
    latch the storm.  Returns the previous ``(threshold, window_s)``."""
    global _STORM_N, _STORM_WINDOW_S
    prev = (_STORM_N, _STORM_WINDOW_S)
    if threshold is not None:
        if threshold < 2:
            raise ValueError("storm threshold must be >= 2")
        _STORM_N = int(threshold)
    if window_s is not None:
        if window_s <= 0:
            raise ValueError("storm window_s must be > 0")
        _STORM_WINDOW_S = float(window_s)
    return prev


def record_compile(
    prog: str, duration_s: float, owner: Any = None, track: bool = False
) -> None:
    """Count one compile of ``prog``: the count/time families always;
    with ``track`` (the label denotes one exact program identity — a
    :class:`JitProgram` call site), also the per-``(program, owner)``
    recompile counter past that identity's first compile, and the storm
    check."""
    c = _core.counter("compile.count", program=prog)
    c.add()
    _core.histogram("compile.time_s", program=prog).observe(
        max(0.0, float(duration_s))
    )
    if track:
        _note_tracked_compile(prog, owner)


def _owner_eid(owner: Any) -> str:
    return str(getattr(owner, "engine_id", "")) if owner is not None else ""


def _identity(prog: str, owner: Any) -> Tuple[str, Any]:
    """Whose program compiled: an engine's by the engine's id, a
    :class:`JitProgram` called as its own owner by its serial (two train
    steps built in one process are two programs), else the bare label."""
    return (prog, _owner_eid(owner) or getattr(owner, "serial", ""))


def _note_tracked_compile(prog: str, owner: Any) -> None:
    eid = _owner_eid(owner)
    key = _identity(prog, owner)
    now = time.monotonic()
    cut = now - _STORM_WINDOW_S
    with _storm_lock:
        n = _per_owner_compiles.get(key, 0) + 1
        _per_owner_compiles[key] = n
        if n <= 1:
            return  # this identity's FIRST compile: not a recompile
        dq = _recompiles.setdefault(key, deque())
        dq.append(now)
        while dq and dq[0] < cut:
            dq.popleft()
        storming = len(dq) >= _STORM_N
        fresh = storming and key not in _latched
        if storming:
            _latched[key] = now
    _core.counter("compile.recompiles", program=prog).add()
    if not fresh:
        return
    # Side effects OUTSIDE the lock (flight_dump is file I/O and the
    # owner hook may take engine-side locks).
    _T_STORMS.add()
    if eid:
        _core.gauge("serve.recompile_storm", engine=eid).set(1)
    _core.event(
        "perf.recompile_storm", engine=eid or None, program=prog,
        n=_STORM_N, window_s=_STORM_WINDOW_S,
    )
    _core.flight_dump(
        "recompile_storm", program=prog, engine=eid or None,
        n_recompiles=_STORM_N, window_s=_STORM_WINDOW_S,
        ledger=ledger.components(),
    )
    # Trigger-fired profiler capture (rate-limited; no-op with no
    # trigger installed): a storm's dump comes with a device profile of
    # the recompiling window — the compile stalls are IN it.
    _timeplane.fire_profile("recompile_storm", engine=eid or None, program=prog)
    if owner is not None:
        try:
            # The stall-watchdog convention: OVERLOADED routes a fleet
            # around the engine; its own healthy ticks restore READY.
            owner._mark_stalled()
        except Exception:  # noqa: BLE001 — observability never fails serving
            pass


def _maybe_unlatch(prog: str, owner: Any) -> None:
    """Clear a latched storm once ``prog`` has gone a full window with
    no recompile (called from the wrapped-call fast path — one dict
    probe when nothing is latched)."""
    if not _latched:
        return
    eid = _owner_eid(owner)
    key = _identity(prog, owner)
    with _storm_lock:
        last = _latched.get(key)
        if last is None or time.monotonic() - last < _STORM_WINDOW_S:
            return
        del _latched[key]
        # The engine gauge covers EVERY program on the engine: it only
        # clears when the last of the engine's latched storms drains —
        # one program going quiet must not mask another still churning.
        still_latched = any(k[1] == eid for k in _latched)
    if eid and not still_latched:
        _core.gauge("serve.recompile_storm", engine=eid).set(0)


# ---------------------------------------------------------------------------
# JitProgram: exact per-program compile detection at the call site


def _cache_size(fn: Any) -> Optional[int]:
    """The jitted callable's executable-cache entry count, or None for
    anything that is not a live jit wrapper (plain functions, chaos
    stand-ins) — those pass through uninstrumented."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:  # noqa: BLE001 — foreign wrapper: pass through
        return None


class JitProgram:
    """One jitted program under a stable observatory label.

    ``resolve`` is a zero-arg callable returning the CURRENT function —
    late-bound so a module-global the engine's tests monkeypatch
    (``engine._decode_chunk``) stays patchable; a stand-in without a
    jit cache is simply not instrumented.  ``call`` passes everything
    through and, when the call grew the jit cache, records the compile
    under ``program`` (per-call override for bucketed variants) against
    ``owner`` (the engine the storm detector should mark).

    A program that belongs to no engine (the train step) is called as
    the function it wraps — ``jp(*args)``, its own owner — and hands
    every other attribute (``.lower``, ...) through to it.  With
    ``scopes=True`` each compile seen while a span sink is active also
    records the program's scope map (:func:`program_scopes`)."""

    __slots__ = ("resolve", "program", "scopes", "serial")

    _serials = itertools.count(1)

    def __init__(
        self, resolve: Callable[[], Any], program: str, scopes: bool = False
    ):
        self.resolve = resolve
        self.program = program
        self.scopes = scopes
        self.serial = next(self._serials)

    def __call__(self, *args, **kwargs) -> Any:
        return self.call(self, None, *args, **kwargs)

    def __getattr__(self, name: str) -> Any:
        if name in self.__slots__ or name.startswith("__"):
            raise AttributeError(name)  # unset slot / copy, pickle probes
        return getattr(self.resolve(), name)

    def call(
        self, owner: Any, prog: Optional[str], *args, **kwargs
    ) -> Any:
        fn = self.resolve()
        n0 = _cache_size(fn)
        if n0 is None:
            return fn(*args, **kwargs)
        label = prog or self.program
        t0 = time.perf_counter()
        with program(label, owner, track=True) as scope:
            out = fn(*args, **kwargs)
        n1 = _cache_size(fn)
        if n1 is not None and n1 > n0 and not scope.counted:
            # The cache grew but no compile event landed on THIS thread:
            # a persistent-cache deserialize (no backend compile), or —
            # these jit fns are module-global — ANOTHER engine's
            # concurrent compile bumping the shared cache.  Count the
            # program load, but feed recompile/storm tracking only when
            # monitoring is absent entirely: with the listener armed, it
            # is the exact per-thread source, and attributing a peer's
            # compile here could storm-latch a healthy engine.
            record_compile(
                label, time.perf_counter() - t0, owner=owner,
                track=not _monitoring,
            )
        elif n1 is not None and n1 <= n0 and not scope.counted:
            _maybe_unlatch(label, owner)
        if self.scopes and n1 is not None and n1 > n0 and _core.enabled():
            _record_scopes(label, fn, args, kwargs)
        return out


# ---------------------------------------------------------------------------
# Scope maps: HLO instruction name -> op_name path of the optimized program

_scope_maps: Dict[str, Dict[str, Tuple[str, ...]]] = {}

_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=(]+)\s*=\s")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$")
_HLO_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_HLO_FUSION = re.compile(r"\sfusion\(")
_HLO_CALLS = re.compile(r"\bcalls=%?([^\s,}]+)")


def hlo_scopes(hlo_text: str) -> Dict[str, Tuple[str, ...]]:
    """``{instruction name: (op_name path, ...)}`` for EVERY instruction
    of an optimized HLO module's text (``compiled.as_text()``): its own
    path first (``""`` where the compiler left none) and, for a fusion,
    the paths of the instructions fused into it — one fused operation
    may span scopes (XLA fuses AdamW's update into the non-finite
    guard's select and gives the fusion the select's path), so the
    reader of the map decides whose time it is.  A fusion fused into
    another brings the paths it holds along: XLA:TPU wraps an expanded
    scatter in two nameless levels, and only the innermost instructions
    still say whose it is."""
    own: Dict[str, str] = {}
    inside: Dict[str, list] = {}  # computation -> its instructions
    calls: Dict[str, str] = {}  # fusion -> the computation it calls
    comp = None
    for line in hlo_text.splitlines():
        if not line[:1].isspace():
            m = _HLO_COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        m = _HLO_INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        name = m.group(1)
        path = _HLO_OP_NAME.search(line, m.end())
        own[name] = path.group(1) if path else ""
        inside.setdefault(comp, []).append(name)
        if _HLO_FUSION.search(line, m.end()):
            calls[name] = _HLO_CALLS.search(line, m.end()).group(1)

    def held(fusion):
        for name in inside.get(calls[fusion], ()):
            if own[name]:
                yield own[name]
            if name in calls:
                yield from held(name)

    return {
        name: (path, *(held(name) if name in calls else ()))
        for name, path in own.items()
    }


def _record_scopes(label: str, fn: Any, args: tuple, kwargs: dict) -> None:
    """Keep the scope map of the program ``fn`` just compiled for these
    arguments.  ``lower().compile()`` right after the call is answered
    from JAX's in-memory caches of the lowering and the executable — no
    second lowering, no second compile (0.07 s for the 48-layer GPT-2-XL
    step on a v5e, all of it text) — and donated arguments still lower:
    only their shapes are read.  Never raises — telemetry must not fail
    the step it observes."""
    try:
        with _core.span("perf.scope_map", program=label):
            text = fn.lower(*args, **kwargs).compile().as_text()
            _scope_maps[label] = hlo_scopes(text)
    except Exception:  # noqa: BLE001
        _logger.warning(
            "perf: no scope map for %s", label, exc_info=True
        )


def program_scopes() -> Dict[str, Dict[str, Tuple[str, ...]]]:
    """``{program label: {HLO instruction name: (op_name path, ...)}}``
    (:func:`hlo_scopes`) of the programs compiled with ``scopes=True``
    while a span sink was active (the latest compile of a label wins;
    empty with no sink)."""
    return {label: dict(m) for label, m in _scope_maps.items()}


# ---------------------------------------------------------------------------
# HBM ledger


def pytree_nbytes(tree: Any) -> int:
    """Total array bytes of a pytree (jax arrays, numpy — anything with
    ``nbytes``)."""
    import jax

    return sum(
        int(getattr(x, "nbytes", 0)) for x in jax.tree.leaves(tree)
    )


class Ledger:
    """Attribute device bytes to named components.

    ``register(component, nbytes, owner=...)`` sets one owner's share of
    a component; the exported ``mem.hbm_bytes{component=}`` gauge is the
    sum over owners, so N engines each registering their ``kv_pool``
    read as one pool total, while N engines sharing ONE params pytree
    register ``weights`` under the same owner key and count once.  An
    owner that goes away (engine close) ``unregister``-s; a component
    whose last owner leaves is pruned from the registry — bounded
    cardinality, same rule as the tenant families."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[Tuple[str, str], int] = {}

    def register(
        self, component: str, nbytes: int, owner: Any = None
    ) -> None:
        key = (str(component), str(owner) if owner is not None else "")
        with self._lock:
            # Gauge update INSIDE the lock: a register racing an
            # unregister (hot swap tearing v1 down while v2 builds)
            # must not apply its total after the other's prune and
            # leave a live component missing from /metrics.  (The
            # registry lock nests under this one and never takes it
            # back — no ordering cycle.)
            self._entries[key] = int(nbytes)
            _core.gauge("mem.hbm_bytes", component=component).set(
                self._component_total(component)
            )

    def unregister(self, component: str, owner: Any = None) -> None:
        key = (str(component), str(owner) if owner is not None else "")
        with self._lock:
            self._entries.pop(key, None)
            total = self._component_total(component)
            if total:
                _core.gauge("mem.hbm_bytes", component=component).set(total)
            else:
                _core.remove("mem.hbm_bytes", component=component)

    def _component_total(self, component: str) -> int:
        return sum(
            v for (c, _), v in self._entries.items() if c == component
        )

    def components(self) -> Dict[str, int]:
        """``{component: total bytes}`` — the snapshot OOM dumps carry."""
        with self._lock:
            out: Dict[str, int] = {}
            for (c, _), v in self._entries.items():
                out[c] = out.get(c, 0) + v
        return out

    def owners(self, component: Optional[str] = None) -> Dict[
        Tuple[str, str], int
    ]:
        """``{(component, owner): bytes}`` — the per-owner attribution.

        This is what the model plane's eviction policy reads
        (docs/serving.md, "Model plane"): real registered numbers for
        who holds what — ``weights`` per model, ``kv_pool`` per engine,
        ``prefix_cache_held`` per engine — not estimates recomputed on
        the side.  ``component`` filters to one component's owners."""
        with self._lock:
            return {
                k: v
                for k, v in self._entries.items()
                if component is None or k[0] == component
            }

    def total(self) -> int:
        with self._lock:
            return sum(self._entries.values())

    def _clear(self) -> None:
        with self._lock:
            comps = {c for c, _ in self._entries}
            self._entries.clear()
        for c in comps:
            _core.remove("mem.hbm_bytes", component=c)


ledger = Ledger()


# ---------------------------------------------------------------------------
# OOM forensics


def is_oom(err: BaseException) -> bool:
    """True when ``err`` is a device out-of-memory (the
    RESOURCE_EXHAUSTED family XLA raises)."""
    msg = f"{type(err).__name__}: {err}"
    return any(marker in msg for marker in _OOM_MARKERS)


def oom_dump(reason: str, *, engine: Optional[str] = None, **attrs) -> int:
    """The OOM post-mortem moment: count it, emit the event, and dump
    the flight ring with the HBM ledger snapshot attached — so the
    record of *what held the memory* survives the failure.  ``reason``
    is ``"device_oom"`` for a RESOURCE_EXHAUSTED device call and
    ``"pool_exhausted"`` for a page-pool reservation that could not be
    met.  Returns the number of flight records dumped."""
    _T_OOMS.add()
    components = ledger.components()
    _core.event(
        "mem.oom", engine=engine, reason=reason,
        hbm_bytes=components, **attrs,
    )
    return _core.flight_dump(
        reason, engine=engine, ledger=components,
        hbm_total_bytes=sum(components.values()), **attrs,
    )


# ---------------------------------------------------------------------------
# Test isolation: telemetry.reset() clears perf state too


def _reset() -> None:
    with _storm_lock:
        _per_owner_compiles.clear()
        _recompiles.clear()
        _latched.clear()
    ledger._clear()
    _scope_maps.clear()


_core.on_reset(_reset)
