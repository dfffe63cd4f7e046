"""ctypes bindings for the native core (libtdx_core.so).

The reference binds its C++ runtime through a pybind11 extension
(/root/reference/src/python/torchdistx/_C/); pybind11 isn't available in this
environment, so the native core exposes a C ABI (src/cc/tdx_core/graph.h)
bound here with ctypes — same layering, different binding tech.

Loading is lazy.  In a checkout the libraries are built from ``src/cc`` at
first use and rebuilt whenever the sources' content hash differs from the
one stored beside the ``.so`` at build time — never by mtime, which a copy
or a fresh checkout resets, so a binary of unknown provenance is not
preferred over the sources.  If the build fails (no ``g++``) the tape falls
back to the pure-Python graph with identical semantics, with a warning.
An installed wheel ships the ``.so`` without sources and loads it as is.
``TDX_DISABLE_NATIVE=1`` forces the fallback (used by tests to compare
both paths).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import List, Optional, Sequence

_PKG_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO_ROOT = os.path.dirname(_PKG_DIR)
_SRC_DIR = os.path.join(_REPO_ROOT, "src", "cc", "tdx_core")
_LIB_PATH = os.path.join(_PKG_DIR, "lib", "libtdx_core.so")
_SRC = os.path.join(_SRC_DIR, "graph.cc")
_HDR = os.path.join(_SRC_DIR, "graph.h")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _src_hash(srcs: Sequence[str]) -> Optional[str]:
    """sha256 of the sources' bytes, concatenated in order (what
    ``cat srcs | sha256sum`` prints — the build scripts write the same
    stamp); None when they are not there (an installed wheel)."""
    h = hashlib.sha256()
    try:
        for path in srcs:
            with open(path, "rb") as f:
                h.update(f.read())
    except OSError:
        return None
    return h.hexdigest()


def _built_from(lib: str, srcs: Sequence[str]) -> bool:
    """Whether ``lib`` exists and was built from ``srcs`` as they are now:
    the hash stamped beside it (``<lib>.srchash``) equals theirs.  With no
    sources to compare against, an existing ``lib`` is all there is."""
    if not os.path.exists(lib):
        return False
    want = _src_hash(srcs)
    if want is None:
        return True
    try:
        with open(lib + ".srchash") as f:
            return f.read().strip() == want
    except OSError:
        return False


def _build(lib: str, srcs: Sequence[str], args: Sequence[str]) -> bool:
    """One-shot on-demand build (g++) so the native path is live in dev
    checkouts without a separate build step.

    Compiles to a process-unique temp file and ``os.replace``s it into
    place: concurrent processes (parallel pytest, pytest + bench) must never
    dlopen a half-written .so or truncate one another process has mapped.
    The source hash is stamped after the library is in place, so a reader
    between the two steps rebuilds rather than trusts.
    """
    want = _src_hash(srcs)
    if want is None:
        return False
    tmp = f"{lib}.{os.getpid()}.tmp"
    try:
        os.makedirs(os.path.dirname(lib), exist_ok=True)
        subprocess.run(
            ["g++", "-std=c++17", "-O2", "-fPIC", "-shared", *args,
             "-o", tmp],
            check=True,
            capture_output=True,
            timeout=120,
        )
        os.replace(tmp, lib)
        with open(tmp, "w") as f:
            f.write(want + "\n")
        os.replace(tmp, lib + ".srchash")
        return True
    except (OSError, subprocess.SubprocessError) as e:
        logging.getLogger(__name__).warning(
            "native core: building %s failed (%s); falling back to the "
            "pure-Python path", os.path.basename(lib), e,
        )
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        if os.environ.get("TDX_DISABLE_NATIVE"):
            _load_failed = True
            return None
        if not _built_from(_LIB_PATH, (_SRC, _HDR)) and not _build(
            _LIB_PATH, (_SRC, _HDR), [_SRC]
        ):
            _load_failed = True
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            _load_failed = True
            return None
        lib.tdx_graph_new.restype = ctypes.c_void_p
        lib.tdx_graph_free.argtypes = [ctypes.c_void_p]
        lib.tdx_graph_add_node.restype = ctypes.c_int
        lib.tdx_graph_add_node.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.tdx_graph_add_dep.restype = ctypes.c_int
        lib.tdx_graph_add_dep.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
        ]
        lib.tdx_graph_note_write.restype = ctypes.c_int
        lib.tdx_graph_note_write.argtypes = [
            ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint64,
        ]
        lib.tdx_graph_num_nodes.restype = ctypes.c_int64
        lib.tdx_graph_num_nodes.argtypes = [ctypes.c_void_p]
        lib.tdx_graph_call_stack.restype = ctypes.c_int64
        lib.tdx_graph_call_stack.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


# ---------------------------------------------------------------------------
# Native stack utilities (_tdx_stack extension module — the stack_utils.cc
# analog; see src/cc/tdx_core/stack.cc)

_STACK_SRC = os.path.join(_SRC_DIR, "stack.cc")
_STACK_LIB = os.path.join(_PKG_DIR, "lib", "_tdx_stack.so")
# The extension links the graph engine (graph.cc) in.
_STACK_SRCS = (_STACK_SRC, _SRC, _HDR)

_stack_lock = threading.Lock()
_stack_mod = None
_stack_failed = False


def _build_stack() -> bool:
    import sysconfig

    include = sysconfig.get_paths()["include"]
    return _build(
        _STACK_LIB, _STACK_SRCS,
        [f"-I{include}", f"-I{_SRC_DIR}", _STACK_SRC, _SRC],
    )


def stack_ops():
    """The native stack-utils module, or None (pytree fallback).

    On first use, registers ``torch.Tensor`` plus the immutable leaf domain
    (the validation analog of deferred_init.cc:227-253) with the extension.
    """
    global _stack_mod, _stack_failed
    if _stack_mod is not None or _stack_failed:
        return _stack_mod
    with _stack_lock:
        if _stack_mod is not None or _stack_failed:
            return _stack_mod
        if os.environ.get("TDX_DISABLE_NATIVE"):
            _stack_failed = True
            return None
        if not _built_from(_STACK_LIB, _STACK_SRCS) and not _build_stack():
            _stack_failed = True
            return None
        try:
            import importlib.util

            spec = importlib.util.spec_from_file_location(
                "_tdx_stack", _STACK_LIB
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
        except Exception:
            _stack_failed = True
            return None
        import torch

        mod.register_types(
            torch.Tensor,
            (
                torch.dtype, torch.device, torch.layout,
                torch.memory_format, torch.Generator,
            ),
        )
        _stack_mod = mod
        return _stack_mod


class NativeGraph:
    """Owning handle over a tdx_graph, plus the op_nr → OpNode registry the
    Python side needs to map native schedules back to payloads.

    The registry holds nodes *weakly*: every node a call-stack traversal can
    return is also strongly reachable from the target through the Python
    graph edges (OutputRef deps / dependents lists), and a strong registry
    would pin the entire tape for as long as any single node survives —
    defeating the incremental freeing the weakref-based Python writers index
    provides.
    """

    def __init__(self):
        import weakref

        lib = _load()
        if lib is None:
            raise RuntimeError("native core unavailable")
        self._lib = lib
        self._ptr = lib.tdx_graph_new()
        self.nodes = weakref.WeakValueDictionary()  # op_nr -> OpNode

    def __del__(self):
        ptr = getattr(self, "_ptr", None)
        if ptr:
            self._lib.tdx_graph_free(ptr)
            self._ptr = None

    def add_node(self, op_nr: int, node) -> None:
        self._lib.tdx_graph_add_node(self._ptr, op_nr)
        self.nodes[op_nr] = node

    def add_dep(self, op_nr: int, producer_op_nr: int) -> None:
        self._lib.tdx_graph_add_dep(self._ptr, op_nr, producer_op_nr)

    def note_write(self, op_nr: int, storage_key: int) -> None:
        self._lib.tdx_graph_note_write(
            self._ptr, op_nr, storage_key & 0xFFFFFFFFFFFFFFFF
        )

    def __len__(self) -> int:
        return int(self._lib.tdx_graph_num_nodes(self._ptr))

    def call_stack(self, target_op_nr: int) -> List[int]:
        # One traversal: the node count bounds the schedule size, so size
        # the buffer up front instead of a sizing call + a fill call.
        cap = int(self._lib.tdx_graph_num_nodes(self._ptr))
        buf = (ctypes.c_int64 * cap)()
        n = self._lib.tdx_graph_call_stack(self._ptr, target_op_nr, buf, cap)
        if n < 0:
            raise KeyError(f"unknown op_nr {target_op_nr}")
        return list(buf[:n])
