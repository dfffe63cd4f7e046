"""Native (C++) tape core vs the pure-Python graph: identical schedules.

The Python implementation is the executable spec; the native core
(src/cc/tdx_core) must produce the same materialization call stacks.
"""

import os
import subprocess
import sys

import pytest
import torch

from torchdistx_tpu import _native, _tape
from torchdistx_tpu.deferred_init import (
    deferred_init,
    materialize_module,
    materialize_tensor,
    _get_record,
)

_FORCED_OFF = bool(os.environ.get("TDX_DISABLE_NATIVE"))


@pytest.mark.skipif(_FORCED_OFF, reason="native explicitly disabled via env")
def test_native_builds_and_loads():
    assert _native.native_available(), (
        "native core should build on demand (g++ is in this image)"
    )


@pytest.mark.skipif(_FORCED_OFF, reason="native explicitly disabled via env")
def test_stack_ops_available():
    assert _native.stack_ops() is not None, (
        "_tdx_stack extension should build on demand"
    )


def test_so_is_trusted_by_source_hash_not_mtime(tmp_path):
    """A copied tree (or a fresh checkout beside an old build) resets
    mtimes, so a ``.so`` counts as built from the sources only when the
    hash stamped beside it equals theirs — otherwise it is rebuilt."""
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no g++")
    src = tmp_path / "f.cc"
    src.write_text('extern "C" int f() { return 1; }\n')
    lib = str(tmp_path / "lib" / "libf.so")
    srcs = (str(src),)

    assert not _native._built_from(lib, srcs)  # nothing there yet
    assert _native._build(lib, srcs, [str(src)])
    assert _native._built_from(lib, srcs)
    assert open(lib + ".srchash").read().strip() == _native._src_hash(srcs)

    # The sources change: the .so no longer matches, however new it is.
    src.write_text('extern "C" int f() { return 2; }\n')
    os.utime(lib)  # newest file in the tree — the old mtime test passes it
    assert not _native._built_from(lib, srcs)
    assert _native._build(lib, srcs, [str(src)])
    assert _native._built_from(lib, srcs)

    # A .so of unknown provenance (no stamp) is not preferred either.
    os.unlink(lib + ".srchash")
    assert not _native._built_from(lib, srcs)

    # No sources at all (an installed wheel): the .so is all there is.
    assert _native._built_from(lib, (str(tmp_path / "absent.cc"),))
    # ... and nothing to build from.
    assert not _native._build(
        lib, (str(tmp_path / "absent.cc"),), [str(src)]
    )


def test_stack_leaves_matches_pytree():
    import torch.utils._pytree as pytree

    s = _native.stack_ops()
    if s is None:
        pytest.skip("native stack unavailable")
    t = torch.ones(2)
    cases = [
        (1, 2, 3),
        (t, [1, t], {"a": t, "b": (None, 2.0)}),
        {"x": [t, {"y": (t,)}]},
        t,
        [],
        ((), [], {}),
    ]
    for obj in cases:
        assert s.leaves(obj) == pytree.tree_leaves(obj), obj


def test_stack_convert_matches_pytree_map():
    import torch.utils._pytree as pytree

    s = _native.stack_ops()
    if s is None:
        pytest.skip("native stack unavailable")
    t = torch.ones(2)
    fn = lambda x: x * 2  # noqa: E731
    obj = (t, [1, t], {"a": t, "b": (None, 2.0)}, "str")
    got = s.convert(obj, fn)
    want = pytree.tree_map(
        lambda a: fn(a) if isinstance(a, torch.Tensor) else a, obj
    )
    assert pytree.tree_structure(got) == pytree.tree_structure(want)
    for g, w in zip(pytree.tree_leaves(got), pytree.tree_leaves(want)):
        if isinstance(g, torch.Tensor):
            assert torch.equal(g, w)
        else:
            assert g == w
    # Copy-on-write: no tensor change -> same object back.
    scalars = (1, [2, 3], {"k": "v"})
    assert s.convert(scalars, fn) is scalars


def test_stack_convert_fallback_signals():
    import collections

    s = _native.stack_ops()
    if s is None:
        pytest.skip("native stack unavailable")
    Point = collections.namedtuple("Point", "x y")
    with pytest.raises(s.Fallback):
        s.convert((Point(1, 2),), lambda x: x)
    # strict mode rejects leaves outside the immutable domain
    with pytest.raises(s.Fallback):
        s.convert((object(),), lambda x: x, True)
    # ...but accepts the torch value types
    ok = (torch.float32, torch.device("cpu"), 1, 2.0, None, "s")
    assert s.convert(ok, lambda x: x, True) is ok


@pytest.mark.skipif(_FORCED_OFF, reason="native explicitly disabled via env")
def test_low_level_graph_roundtrip():
    class Node:  # weak-referenceable registry payload
        def __init__(self, nr):
            self.nr = nr

    g = _native.NativeGraph()
    payloads = [Node(nr) for nr in (10, 11, 12, 13)]
    for p in payloads:
        g.add_node(p.nr, p)
    g.add_dep(11, 10)
    g.add_dep(12, 11)
    g.note_write(10, 0xABC)
    g.note_write(13, 0xABC)  # later in-place write on the same storage
    assert len(g) == 4
    # target 11: deps {10}, horizon from target's dependents only (none for
    # 11; 10's dependent 13 is pulled in via 10 within horizon? no — horizon
    # is computed from the *target*).
    assert g.call_stack(11) == [10, 11]
    # target 10: dependent 13 raises the horizon and joins the stack.
    assert g.call_stack(10) == [10, 13]
    with pytest.raises(KeyError):
        g.call_stack(999)


class Net(torch.nn.Module):
    def __init__(self):
        super().__init__()
        self.fc1 = torch.nn.Linear(8, 16)
        self.fc2 = torch.nn.Linear(16, 4)
        self.register_buffer("scale", torch.ones(4) * 3)

    def forward(self, x):
        return self.fc2(torch.relu(self.fc1(x))) * self.scale


def _schedules(module):
    out = {}
    for name, p in list(module.named_parameters()) + list(
        module.named_buffers()
    ):
        rec = _get_record(p)
        out[name] = [n.op_nr for n in _tape.build_call_stack(rec.node)]
    return out


@pytest.mark.skipif(_FORCED_OFF, reason="native explicitly disabled via env")
def test_schedules_match_python_fallback():
    m_native = deferred_init(Net)
    native_used = any(
        _get_record(p).node.native_graph is not None
        for p in m_native.parameters()
    )
    assert native_used, "native graph should be active for this tape"
    sched_native = _schedules(m_native)

    # Same model recorded with the native core disabled → same schedules
    # relative to each tape's op_nr base.
    code = """
import os
os.environ["TDX_DISABLE_NATIVE"] = "1"
import torch
from torchdistx_tpu import _tape
from torchdistx_tpu.deferred_init import deferred_init, _get_record
import json

class Net(torch.nn.Module):
    def __init__(self):
        super().__init__()
        self.fc1 = torch.nn.Linear(8, 16)
        self.fc2 = torch.nn.Linear(16, 4)
        self.register_buffer("scale", torch.ones(4) * 3)

m = deferred_init(Net)
assert all(
    _get_record(p).node.native_graph is None for p in m.parameters()
)
out = {}
base = None
for name, t in list(m.named_parameters()) + list(m.named_buffers()):
    rec = _get_record(t)
    nrs = [n.op_nr for n in _tape.build_call_stack(rec.node)]
    if base is None:
        base = min(nrs)
    out[name] = nrs
print(json.dumps({"base": base, "sched": out}))
"""
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, check=True
    )
    import json

    py = json.loads(proc.stdout.strip().splitlines()[-1])
    base_native = min(min(v) for v in sched_native.values())
    rel_native = {
        k: [nr - base_native for nr in v] for k, v in sched_native.items()
    }
    rel_py = {
        k: [nr - py["base"] for nr in v] for k, v in py["sched"].items()
    }
    assert rel_native == rel_py


def test_materialize_through_native_path():
    m = deferred_init(Net)
    materialize_module(m)
    assert torch.equal(m.scale, torch.ones(4) * 3)
    x = torch.randn(2, 8)
    y = m(x)
    assert y.shape == (2, 4)


def test_identity_preserved_through_native_path():
    m = deferred_init(Net)
    a = materialize_tensor(m.fc1.weight)
    b = materialize_tensor(m.fc1.weight)
    assert a is b
    assert isinstance(a, torch.nn.Parameter)


def test_inplace_horizon_through_native_path():
    def build():
        t = torch.ones(4)
        u = t[:2]  # view
        u.add_(1.0)  # in-place on the view, later than t's producer
        return t, u

    t, u = deferred_init(build)
    real_t = materialize_tensor(t)
    # The in-place write through the view must be visible in t.
    assert torch.equal(real_t, torch.tensor([2.0, 2.0, 1.0, 1.0]))


def test_inplace_horizon_with_dropped_view():
    """The in-place op's node must stay alive (keep-alive contract) even
    when the view tensor object is dropped before materialization."""
    import gc

    def build():
        t = torch.ones(4)
        u = t[:2]
        u.add_(1.0)
        del u
        return t

    t = deferred_init(build)
    gc.collect()
    assert torch.equal(
        materialize_tensor(t), torch.tensor([2.0, 2.0, 1.0, 1.0])
    )


@pytest.mark.skipif(_FORCED_OFF, reason="native explicitly disabled via env")
def test_native_outputref_type():
    s = _native.stack_ops()
    assert _tape.OutputRef is s.OutputRef

    class N:
        op_nr = 7

    r = s.OutputRef(N(), 2)
    assert r.index == 2 and r.node.op_nr == 7
    assert repr(r) == "OutputRef(op_nr=7, index=2)"


def test_cross_tape_sees_native_inplace_writes():
    """A cross-tape read AFTER an in-place write recorded natively in the
    producer's tape must replay that write (the Python traversal navigates
    the dependents lists the native recorder maintains)."""

    def first():
        t = torch.zeros(4)
        t.add_(5.0)
        return nn.Parameter(t)

    import torch.nn as nn

    p1 = deferred_init(first)
    p2 = deferred_init(lambda: nn.Parameter(p1 * 1.0))
    rec = _get_record(p2)
    assert rec.node.native_graph is None  # cross-tape: downgraded
    assert torch.equal(materialize_tensor(p2), torch.full((4,), 5.0))


def test_concurrent_materialize_across_threads():
    """Tapes are recorded thread-locally but materialization may happen from
    other threads (the reference's graphs cross threads the same way); the
    native call-stack traversal must be safe under concurrent readers.
    The C++-level race coverage is scripts/tsan_native.sh."""
    import concurrent.futures

    import torch.nn as nn

    modules = [deferred_init(Net) for _ in range(4)]

    def materialize_one(m):
        materialize_module(m)
        return float(m.fc1.weight.sum())

    with concurrent.futures.ThreadPoolExecutor(4) as pool:
        sums = list(pool.map(materialize_one, modules))
    assert all(s == s for s in sums)  # finite, no crash
    for m in modules:
        assert isinstance(m.fc1.weight, nn.Parameter)
        assert m.fc1.weight.device.type == "cpu"


def test_post_downgrade_writer_linking():
    """After a tape downgrades (cross-tape dep), later in-place ops in the
    SAME tape must still link against native-era writers — the recorder
    exports its writer index into the Python tape."""
    import torch.nn as nn

    ext = deferred_init(lambda: nn.Parameter(torch.ones(4)))

    def build():
        a = torch.zeros(4)         # recorded natively
        b = a + ext                # cross-tape dep -> tape downgrades
        a.add_(3.0)                # python-path write on a native-era storage
        return nn.Parameter(a), b

    a, b = deferred_init(build)
    assert _get_record(a).node.native_graph is None
    # b first: it read a BEFORE the in-place write, and the per-node replay
    # caches mutate in place (chronological materialization order, same as
    # the reference's cached outputs).
    assert torch.equal(materialize_tensor(b), torch.ones(4))
    assert torch.equal(materialize_tensor(a), torch.full((4,), 3.0))
