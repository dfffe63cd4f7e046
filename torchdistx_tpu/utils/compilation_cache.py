"""Persistent XLA compilation cache for init programs.

Materialization cost is dominated by XLA compile time (the init program
itself executes in milliseconds); the grouped materializer deliberately emits
HLO that is stable across processes — the RNG base key and per-node stream
identities enter as traced inputs rather than baked constants (see _tape.py's
tape-relative numbering) — precisely so JAX's persistent compilation cache
can hit on re-runs.  A training job that restarts (preemption, resharding,
hyperparameter sweeps) re-materializes the same architecture and pays only
trace + cache-lookup time.

Enabled on first materialization unless disabled via
``TDX_NO_COMPILATION_CACHE=1``.  Where the cache lives: with
``JAX_COMPILATION_CACHE_DIR`` set (or ``jax_compilation_cache_dir``
configured by the user) JAX has the directory already and this module sets
none; otherwise :data:`DEFAULT_CACHE_DIR`, one fixed directory inside the
checkout.  The path is part of the cache key, so it never depends on the
home directory, a temp name, a pid or the time — a copied checkout on a
throw-away machine finds what an earlier process of the same command
wrote.  The AOT-executable tier (``materialize._exec_disk_dir``) lives in
a sub-directory of the same place (:func:`cache_dir`).
"""

from __future__ import annotations

import os
import threading

from .. import telemetry as _telemetry

# 1 once the persistent cache is configured, 0 when skipped (CPU backend,
# TDX_NO_COMPILATION_CACHE, setup failure); unset until first
# materialization.  A user-configured jax cache dir also reads 1 — the
# cache is on, just not ours to manage.  The exec-tier hit/miss counters
# live in materialize (materialize.exec_cache_*): JAX does not expose
# per-compile persistent-cache hit events to instrument here.
_T_ENABLED = _telemetry.gauge("compilation_cache.enabled")
# Swallowed cache-management failures (setup, threshold save/restore).
# The cache is a pure optimization — errors must never fail the caller —
# but silent degradation (every compile suddenly cold) must still be
# visible in traces, so every swallowed exception counts here.
_T_ERRORS = _telemetry.counter("compile_cache.errors")

# <checkout>/.jax_cache (git-ignored).
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_lock = threading.Lock()
_done = False
# cache_everything refcount state (guarded by _lock).
_ce_depth = 0
_ce_saved: list = []


def ensure_compilation_cache() -> None:
    global _done
    if _done:
        return
    with _lock:
        if _done:
            return
        _done = True
        # Arm the compile observatory with the cache: both exist because
        # compile time dominates materialization cost, and every entry
        # point that configures one should see the other's metrics
        # (docs/observability.md, "Perf plane").
        from ..telemetry import perf as _perf

        _perf.install_monitoring()
        _T_ENABLED.set(0)
        if os.environ.get("TDX_NO_COMPILATION_CACHE"):
            return
        try:
            import jax

            if jax.config.jax_compilation_cache_dir:
                # JAX_COMPILATION_CACHE_DIR (jax reads it itself) or a
                # programmatic setting: the directory is placed from
                # outside and this module configures none.
                _T_ENABLED.set(1)
                return
            if jax.default_backend() == "cpu":
                # CPU executables are AOT-compiled against the build host's
                # exact machine features; reloading them elsewhere warns (or
                # SIGILLs).  The cache's value is on accelerators, where
                # executables are device-kind-portable.
                return
            os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
            _T_ENABLED.set(1)
        except Exception:
            # Cache is a pure optimization — never fail materialization
            # over it (read-only checkout, ...).
            _T_ERRORS.add()


def cache_dir() -> str:
    """The directory both cache tiers use: whatever JAX is configured
    with — ``JAX_COMPILATION_CACHE_DIR`` when set — else
    :data:`DEFAULT_CACHE_DIR`."""
    import jax

    return jax.config.jax_compilation_cache_dir or DEFAULT_CACHE_DIR


class cache_everything:
    """Scope JAX's persistent-cache admission thresholds to one region.

    Init programs are individually cheap to compile (~100ms per unique
    signature) — below JAX's default min-compile-time admission bar — but
    numerous, so the materializer wants them all cached.  Applying the
    thresholds process-globally would also serialize every tiny throwaway
    jit and every multi-hundred-MB train-step executable the *user*
    compiles; scoping keeps the aggressive admission local to
    materialization.

    The thresholds are process-global jax.config state, so the save/restore
    is refcounted under the module lock: overlapping regions (concurrent
    materializations) share the OUTERMOST save and restore once, instead of
    racing each other into a corrupted restore.  Compiles issued by
    unrelated threads while any region is open are still admitted under the
    aggressive thresholds — inherent to global config, harmless (extra cache
    entries).
    """

    _FLAGS = (
        ("jax_persistent_cache_min_compile_time_secs", 0.0),
        ("jax_persistent_cache_min_entry_size_bytes", -1),
    )

    def __enter__(self):
        global _ce_depth, _ce_saved
        with _lock:
            _ce_depth += 1
            if _ce_depth == 1:
                _ce_saved = []
                try:
                    import jax

                    for name, value in self._FLAGS:
                        _ce_saved.append((name, getattr(jax.config, name)))
                        jax.config.update(name, value)
                except Exception:
                    # Partial failure (e.g. a flag renamed in a newer jax):
                    # roll back what WAS applied rather than leaving the
                    # aggressive thresholds process-global.
                    _T_ERRORS.add()
                    try:
                        import jax

                        for name, value in _ce_saved:
                            jax.config.update(name, value)
                    except Exception:
                        _T_ERRORS.add()
                    _ce_saved = []
        return self

    def __exit__(self, *exc):
        global _ce_depth, _ce_saved
        with _lock:
            _ce_depth -= 1
            if _ce_depth == 0:
                try:
                    import jax

                    for name, value in _ce_saved:
                        jax.config.update(name, value)
                except Exception:
                    _T_ERRORS.add()
                _ce_saved = []
        return False
