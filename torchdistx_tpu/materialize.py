"""TPU-native materialization: replay the deferred-init tape as JAX arrays.

This is the reason this framework exists (SURVEY.md §7, BASELINE.md): take a
module whose parameters are fake + recorded, and instantiate them **directly
as (sharded) ``jax.Array`` leaves on a TPU mesh** — shard-then-materialize
with no full-tensor host round-trip.  The reference stops at replaying onto
real torch devices (deferred_init.cc:505-666); the TPU-native path compiles
the whole init subgraph into a single ``jit`` whose ``out_shardings`` place
every parameter shard on its device over ICI, letting XLA's SPMD partitioner
generate per-shard init (including partitioned RNG) without ever building the
full tensor anywhere.

Mutation/view semantics on an immutable substrate
-------------------------------------------------
The reference replays in-place/view-heavy init code onto *mutable storage*.
Functionally, each recorded meta **storage** becomes a flat value in an
environment; tensors are strided windows onto those values:

* reading a tensor = strided gather from its storage buffer
  (fast path: contiguous whole-storage view = reshape);
* an in-place op = pure compute + strided scatter back through the written
  tensor's layout;
* a view op = no compute at all — its outputs are just layouts, resolved at
  read time (this subsumes the reference's view keep-alive and aliasing
  machinery, deferred_init.cc:416-461).

Replay order is the same chronological call-stack the torch path uses
(_tape.build_call_stack ≈ deferred_init.cc:529-621), so write-after-write and
read-after-write through any alias resolve exactly as recorded.

RNG: every node draws from
``fold_in(fold_in(key(seed), tape_ordinal), tape_relative_op_nr)`` where
``tape_ordinal`` numbers the distinct tapes reachable from the target(s) in
first-appearance order and the relative op_nr is ``op_nr - base_nr`` (first
op of the node's tape).  Properties: deterministic, independent of
materialization order, reproducible across processes *and* across tapes in
one process (absolute op counters never leak in), collision-free when
separately recorded submodules are merged into one module (distinct
ordinals), equal between :func:`materialize_tensor_jax` and
:func:`materialize_module_jax` for the ordinary single-tape module, and
identical across hosts — so multi-host sharded materialization is
consistent by construction (the NCCL-broadcast-init analog: no broadcast
needed at all).

Ops with no JAX lowering fall back to torch replay + ``jax.device_put`` with
the planned sharding (per-tensor, so host RAM stays bounded by the largest
parameter, not the model).
"""

from __future__ import annotations

import functools
import logging
import threading
from typing import Any, Dict, List, Optional, Tuple

import torch
import torch.nn as nn
import torch.utils._pytree as pytree

from . import _tape
from . import telemetry as _telemetry
from .telemetry import perf as _perf
from ._tape import OpNode, OutputRef
from .deferred_init import _get_record, is_deferred
from .fake import FakeTensor
from .ops.aten_jax import LOWERINGS, UnsupportedOpError
from .utils import compilation_cache as _cc
from .utils.compilation_cache import ensure_compilation_cache
from .utils.dtypes import jnp_dtype_of

__all__ = [
    "materialize_tensor_jax",
    "materialize_module_jax",
]


def _is_view_node(node: OpNode) -> bool:
    """Pure view op: outputs alias inputs, nothing is written.

    Ground truth is the op schema (the reference infers the same from output
    storages aliasing argument storages, deferred_init.cc:416-461)."""
    if node.mutated_args:
        return False
    try:
        returns = node.op.func._schema.returns
    except AttributeError:
        return False
    return bool(returns) and all(r.alias_info is not None for r in returns)


class _MetaWindow:
    """Layout of one tensor over its flat storage buffer."""

    __slots__ = (
        "storage_key",
        "shape",
        "strides",
        "offset",
        "dtype",
        "numel",
        "storage_elems",
    )

    def __init__(self, meta: torch.Tensor):
        storage = meta.untyped_storage()
        self.storage_key = storage._cdata
        self.shape = tuple(meta.shape)
        self.strides = tuple(meta.stride())
        self.offset = meta.storage_offset()
        self.dtype = meta.dtype
        self.numel = meta.numel()
        self.storage_elems = storage.size() // max(meta.element_size(), 1)

    def is_whole_contiguous(self, buffer_len: int) -> bool:
        if self.offset != 0 or self.numel != buffer_len:
            return False
        expected = 1
        for size, stride in zip(reversed(self.shape), reversed(self.strides)):
            if size != 1 and stride != expected:
                return False
            expected *= size
        return True

    def flat_indices(self):
        import jax.numpy as jnp

        idx = jnp.asarray(self.offset)
        for size, stride in zip(self.shape, self.strides):
            idx = idx[..., None] + jnp.arange(size) * stride
        return idx


class _FunctionalReplay:
    """Replays tape nodes as pure JAX computation over storage buffers.

    ``key_lookup``/``ext_lookup`` parametrize the replay for template reuse
    (the grouped strategy): per-node PRNG keys and external tensor values come
    in as traced arguments instead of being baked into the trace, so one
    compiled program serves every structurally identical call stack.
    """

    def __init__(
        self,
        base_key,
        *,
        check_guards: bool = True,
        key_lookup=None,
        ext_lookup=None,
    ):
        self.base_key = base_key
        self.check_guards = check_guards
        self.key_lookup = key_lookup
        self.ext_lookup = ext_lookup
        # storage key -> (flat jnp value, element count)
        self.storages: Dict[int, Any] = {}
        self.replayed: set = set()
        # Tape base_nr -> ordinal, assigned in replay (chronological) order;
        # recording order is deterministic for a given program, so ordinals
        # are process-stable.  See key_for.
        self.tape_ordinals: Dict[int, int] = {}

    def key_for(self, node: OpNode):
        import jax

        if self.key_lookup is not None:
            return self.key_lookup(node)
        # Stream identity = (tape ordinal, tape-relative op_nr):
        # reproducible across processes and across tapes in one process —
        # absolute op_nrs depend on how many tapes preceded this one and
        # never enter a key — and collision-free when a call stack spans
        # several tapes (each gets a distinct ordinal).  Matches the module
        # path for single-tape modules (module docstring, RNG note).
        ordinal = self.tape_ordinals.setdefault(
            node.base_nr, len(self.tape_ordinals)
        )
        return jax.random.fold_in(
            jax.random.fold_in(self.base_key, ordinal),
            node.op_nr - node.base_nr,
        )

    # -- engine plumbing ----------------------------------------------------

    def read(self, window: _MetaWindow):
        buf = self.storages[window.storage_key]
        if window.is_whole_contiguous(buf.shape[0]):
            return buf.reshape(window.shape)
        return buf[window.flat_indices()]

    def write(self, window: _MetaWindow, value):
        import jax.numpy as jnp

        value = jnp.broadcast_to(value, window.shape).astype(
            jnp_dtype_of(window.dtype)
        )
        buf = self.storages.get(window.storage_key)
        if buf is None:
            # Fresh storage: a flat buffer covering the whole allocation.
            buf = jnp.zeros(
                (window.storage_elems,), dtype=jnp_dtype_of(window.dtype)
            )
        if window.is_whole_contiguous(buf.shape[0]):
            self.storages[window.storage_key] = value.reshape(-1)
        else:
            self.storages[window.storage_key] = buf.at[
                window.flat_indices()
            ].set(value)

    def value_of_output(self, node: OpNode, index: int):
        meta = node.out_metas[index]
        return self.read(_MetaWindow(meta))

    # -- node replay --------------------------------------------------------

    def run_call_stack(self, target: OpNode) -> None:
        for node in _tape.build_call_stack(target):
            self.run_node(node)

    def run_node(self, node: OpNode) -> None:
        import jax
        import jax.numpy as jnp

        if node.op_nr in self.replayed:
            return
        self.replayed.add(node.op_nr)
        if self.check_guards:
            for guard in node.op.guards:
                guard.check()

        if _is_view_node(node):
            # Views are layouts, not computation; ensure the base storage
            # exists (it must, via dependencies) and move on.
            return

        def resolve(a):
            if isinstance(a, OutputRef):
                meta = a.node.out_metas[a.index]
                return self.read(_MetaWindow(meta))
            if isinstance(a, torch.Tensor):
                if self.ext_lookup is not None:
                    return self.ext_lookup(a)
                return jnp.asarray(a.detach().cpu().numpy())
            return a

        op = node.op
        args, kwargs = pytree.tree_map(resolve, (op.args, op.kwargs))
        name = _packet_name(op.func)
        fn = LOWERINGS.get(name)
        if fn is None:
            raise UnsupportedOpError(
                f"No JAX lowering for '{name}' (recorded as {op.name})."
            )

        ctx = _LowerCtx(self, node)
        out = fn(ctx, *args, **_strip_factory_kwargs(kwargs))
        outs = out if isinstance(out, (list, tuple)) else [out]

        if node.mutated_args:
            # In-place: scatter each mutated arg's OWN result back through
            # that tensor's layout (writes are visible through every alias).
            # The arg→output pairing comes from the schema alias sets; a
            # blanket outs[0] would corrupt the second buffer of a
            # two-mutation op such as aminmax.out.
            out_of = _mutation_output_map(op.func, node.mutated_args, len(outs))
            for pos in node.mutated_args:
                ref = _tape.arg_at_schema_pos(op.func, op.args, op.kwargs, pos)
                if isinstance(ref, OutputRef):
                    meta = ref.node.out_metas[ref.index]
                    self.write(_MetaWindow(meta), outs[out_of[pos]])
        # Fresh outputs define their storages.
        for i, meta in enumerate(node.out_metas):
            if meta is None or i >= len(outs):
                continue
            window = _MetaWindow(meta)
            if window.storage_key not in self.storages:
                self.write(window, outs[i])


class _LowerCtx:
    """Per-node context handed to lowerings: PRNG key + output metadata."""

    __slots__ = ("engine", "node")

    def __init__(self, engine: _FunctionalReplay, node: OpNode):
        self.engine = engine
        self.node = node

    @property
    def key(self):
        return self.engine.key_for(self.node)

    def out_meta(self, index: int) -> torch.Tensor:
        return self.node.out_metas[index]


@functools.lru_cache(maxsize=4096)
def _packet_name(func) -> str:
    # e.g. "aten.uniform_.default" — OpOverload objects are interned
    # singletons, so an identity-keyed cache is safe and saves the str()
    # on every node of every stack analysis.
    return str(func)


def _mutation_output_map(func, mutated_args, n_outs) -> dict:
    """Map each mutated positional arg to the lowering-output index that
    carries its new value.

    Ground truth is the schema's alias-set pairing: an argument annotated
    ``Tensor(a!)`` is returned by the output annotated ``Tensor(a!)``
    (e.g. ``aminmax.out``'s min/max pair).  Ops whose single mutated arg has
    no aliased return (pure in-place like ``uniform_`` lowered to return the
    new buffer) fall back to output 0; multiple mutated args without a
    schema pairing are refused rather than silently corrupted.
    """
    mapping: dict = {}
    schema = getattr(func, "_schema", None)
    if schema is not None:
        for pos in mutated_args:
            if pos >= len(schema.arguments):
                continue
            ainfo = schema.arguments[pos].alias_info
            if ainfo is None:
                continue
            aset = set(ainfo.before_set)
            for j, ret in enumerate(schema.returns):
                rinfo = ret.alias_info
                if rinfo is not None and aset & set(rinfo.before_set):
                    if j < n_outs:
                        mapping[pos] = j
                    break
    missing = [p for p in mutated_args if p not in mapping]
    if missing:
        if len(mutated_args) == 1 and n_outs >= 1:
            mapping[mutated_args[0]] = 0
        else:
            raise UnsupportedOpError(
                f"Cannot pair mutated args {missing} of '{func}' with "
                f"their outputs ({n_outs} returned): the schema has no "
                "aliased return for them and more than one arg is mutated."
            )
    return mapping


def _strip_factory_kwargs(kwargs: dict) -> dict:
    return {
        k: v
        for k, v in kwargs.items()
        if k not in ("device", "layout", "pin_memory", "memory_format",
                     "non_blocking", "generator")
    }


# ---------------------------------------------------------------------------
# Grouped (template) materialization: structural dedup of call stacks.
#
# Deep models repeat their init structure — 48 transformer blocks record 48
# structurally identical call stacks per parameter kind, differing only in
# PRNG stream (op_nr) and captured external tensors.  Compiling the union
# program (the "fused" strategy) makes XLA chew through O(depth) copies of
# the same subgraph; grouping instead compiles ONE small program per unique
# stack *signature* (op sequence + shapes + scalar args) with per-node keys
# and externals passed as traced arguments, then executes it per instance
# (vmap-batched off-mesh).  Compile time becomes O(unique layer kinds), not
# O(depth) — the TPU-idiomatic shape for init, and the reason the deferred
# path beats eager init+transfer (BASELINE.md).


def _analyze_stack(stack: List[OpNode], record) -> Optional[Tuple]:
    """Signature + per-instance data for one call stack.

    Returns ``(sig, ext_values)`` where ``sig`` is a hashable
    structural signature — two stacks with equal signatures trace to
    identical jaxprs when replayed with keys/externals as arguments — or
    ``None`` if the stack is not groupable (unlowerable op present).
    """
    local = {n.op_nr: i for i, n in enumerate(stack)}
    storage_ids: Dict[int, int] = {}

    def sid(key: int) -> int:
        return storage_ids.setdefault(key, len(storage_ids))

    def win_sig(meta: Optional[torch.Tensor]):
        if meta is None:
            return None
        w = _MetaWindow(meta)
        return (
            sid(w.storage_key),
            w.shape,
            w.strides,
            w.offset,
            str(w.dtype),
            w.storage_elems,
        )

    ext_values: List[torch.Tensor] = []
    node_sigs = []
    for n in stack:
        is_view = _is_view_node(n)
        if not is_view and _packet_name(n.op.func) not in LOWERINGS:
            return None

        def norm(a):
            if isinstance(a, OutputRef):
                i = local.get(a.node.op_nr)
                if i is None:
                    # Dependency outside the stack — cannot template.
                    raise _NotGroupable
                return ("ref", i, a.index)
            if isinstance(a, torch.Tensor):
                if is_view:
                    # View nodes are never resolved at replay; their args
                    # must not consume external slots.
                    return ("viewext", tuple(a.shape), str(a.dtype))
                ext_values.append(a)
                return ("ext", len(ext_values) - 1, tuple(a.shape), str(a.dtype))
            if isinstance(
                a,
                (torch.dtype, torch.device, torch.layout, torch.memory_format),
            ):
                return ("t", str(a))
            return ("v", a)

        def rec(a):
            # Structural recursion replacing pytree.tree_flatten +
            # repr(treedef) (which dominated warm-materialize wall time):
            # traversal order over tuple/list/dict matches torch pytree's
            # flatten order (dicts: insertion order), so ``ext_values``
            # pairs up with replay-time ``tree_map`` consumption.  Exotic
            # containers (namedtuple/OrderedDict/registered pytrees) would
            # traverse differently there — send those to the fused path.
            ta = type(a)
            if ta is tuple or ta is list:
                return ("T" if ta is tuple else "L",
                        tuple(rec(x) for x in a))
            if ta is dict:
                return ("D", tuple((k, rec(v)) for k, v in a.items()))
            if isinstance(a, (tuple, list, dict)):
                raise _NotGroupable  # subclass: pytree order unknown
            return norm(a)

        try:
            args_sig = rec((n.op.args, n.op.kwargs))
        except _NotGroupable:
            return None
        except TypeError:
            return None  # unhashable leaf somewhere; fused path handles it
        node_sigs.append(
            (
                _packet_name(n.op.func),
                args_sig,
                tuple(win_sig(m) for m in n.out_metas),
                tuple(n.mutated_args),
                is_view,
            )
        )

    sig = (
        tuple(node_sigs),
        local[record.node.op_nr],
        record.index,
    )
    try:
        hash(sig)
    except TypeError:
        return None
    return sig, ext_values


class _NotGroupable(Exception):
    pass


# ---------------------------------------------------------------------------
# Fill fast path: the overwhelmingly common init stack is
# ``factory → (views) → whole-storage fill`` — every torch.nn default init
# (kaiming/xavier uniform_, normal_, ones/zeros/constant) records this shape.
# Replaying those through per-signature templates makes XLA compile one
# subgraph per unique parameter SHAPE (a resnet50 has 46).  Instead, fills
# are pooled across shapes into padded power-of-two buckets
# (ops.aten_jax.fill_bucket) and drawn as ONE vmapped kernel per
# (fill kind, dtype, bucket) — a handful of subgraphs for any model, with
# per-param slice/reshape being free for XLA.  Values are bitwise identical
# to the per-op lowering (which draws the same padded buckets; threefry
# fold_in keys are vmap-invariant).

_FILL_FINAL_OPS = {
    "aten.uniform_.default": "uniform",
    "aten.normal_.default": "normal",
    "aten.fill_.Scalar": "full",
    "aten.zero_.default": "zero",
}

# Factories whose value is dead once a whole-storage fill follows.
_FILL_FACTORY_OPS = {
    "aten.empty.memory_format",
    "aten.empty.default",
    "aten.empty_strided.default",
    "aten.zeros.default",
    "aten.ones.default",
    "aten.full.default",
}


def _match_fill(stack: List[OpNode], record):
    """Match a ``factory → (views) → whole-storage fill`` stack.

    Returns ``(kind, s0, s1, fill_idx)`` — fill kind, its two scalar
    parameters (raw, dtype-cast at bin build), and the fill node's index in
    ``stack`` — or ``None`` if the stack doesn't qualify.
    """
    non_view = [n for n in stack if not _is_view_node(n)]
    if not non_view:
        return None
    last = non_view[-1]
    kind = _FILL_FINAL_OPS.get(_packet_name(last.op.func))
    if kind is None:
        return None
    # Single storage throughout — so every pre-fill node's effects are
    # confined to this storage — and the final fill overwrites the WHOLE
    # storage, so every preceding compute node is dead regardless of kind
    # (e.g. the kaiming-uniform draw a Linear ctor runs before HF
    # ``_init_weights`` re-fills with ``normal_``).  Skipping dead draws
    # cannot shift RNG: replay keys are per-node (tape ordinal, rel nr),
    # not stream-positional.
    storages = set()
    for n in stack:
        for m in n.out_metas:
            if m is not None:
                storages.add(_MetaWindow(m).storage_key)
    if len(storages) != 1:
        return None
    fw = _MetaWindow(last.out_metas[0])
    if not fw.is_whole_contiguous(fw.storage_elems):
        return None
    rw = _MetaWindow(record.node.out_metas[record.index])
    if not rw.is_whole_contiguous(rw.storage_elems) or rw.dtype != fw.dtype:
        return None

    scalars = _fill_scalars(kind, last)
    if scalars is None:
        return None
    return kind, scalars[0], scalars[1], stack.index(last)


def _fill_scalars(kind: str, fill_node: OpNode):
    """The two scalar parameters of one fill node, or ``None`` when they
    are tensor-valued (not poolable).  Used by :func:`_match_fill` on the
    group representative AND re-derived per member at plan time
    (:func:`_plan_fill_bins` / :func:`_plan_big_fills`): the grouping
    signature does include scalar args, but the fast paths must not
    silently apply the representative's init scale to every member if
    that invariant ever loosens."""
    args = list(fill_node.op.args)
    kw = fill_node.op.kwargs
    if kind == "uniform":
        s0 = args[1] if len(args) > 1 else kw.get("from", 0.0)
        s1 = args[2] if len(args) > 2 else kw.get("to", 1.0)
    elif kind == "normal":
        s0 = args[1] if len(args) > 1 else kw.get("mean", 0.0)
        s1 = args[2] if len(args) > 2 else kw.get("std", 1.0)
    elif kind == "full":
        s0 = args[1] if len(args) > 1 else kw.get("value")
        s1 = 0
        if s0 is None:
            return None
    else:  # zero
        s0 = s1 = 0
    if isinstance(s0, (torch.Tensor, OutputRef)) or isinstance(
        s1, (torch.Tensor, OutputRef)
    ):
        return None
    return s0, s1


def _member_fill_scalars(kind: str, name: str, node: OpNode):
    """Per-member fill scalars for the pooled/big-fill paths.  Signature
    equality should make these equal the representative's; a mismatch in
    kind or a tensor-valued scalar here means the grouping invariant
    broke — refuse loudly rather than draw with the wrong init scale."""
    if _FILL_FINAL_OPS.get(_packet_name(node.op.func)) != kind:
        raise UnsupportedOpError(
            f"fill-fastpath grouping invariant violated for '{name}': "
            f"member fill op {node.op.name!r} does not match the group "
            f"kind {kind!r}"
        )
    scalars = _fill_scalars(kind, node)
    if scalars is None:
        raise UnsupportedOpError(
            f"fill-fastpath grouping invariant violated for '{name}': "
            "member fill scalars are tensor-valued"
        )
    return scalars


def _fill_fastpath_enabled() -> bool:
    import os

    return not os.environ.get("TDX_NO_FILL_FASTPATH")


# Introspection: number of params served by the fill fast path in the most
# recent materialize_module_jax call (tests/bench).
last_fill_fastpath_params = 0

# Phase timings of the most recent materialize_module_jax call:
# {plan_s, compile_s, transfer_s, exec_s, jobs: [(label, s, rss_mb)]}.
# Per-job numbers (blocking execute + RSS read) only under
# TDX_PROFILE_MATERIALIZE=1 — blocking serializes dispatch.
#
# Back-compat view: the numbers are the durations of the telemetry spans
# (materialize.plan/compile/transfer/execute/job — see
# torchdistx_tpu/telemetry and docs/observability.md), assembled into the
# legacy dict shape.  New code should read the telemetry collector.
last_profile: Dict[str, Any] = {}

# Telemetry counters, bound once (see telemetry._core.counter).  The
# whole-call hit counter mirrors the legacy `exec_cache_hits` module
# global; the mem/disk/compile counters resolve *which* tier served each
# program.
_T_CALLS = _telemetry.counter("materialize.calls")
_T_EXEC_HITS = _telemetry.counter("materialize.exec_cache_hits")
_T_EXEC_MEM_HITS = _telemetry.counter("materialize.exec_cache_mem_hits")
_T_EXEC_DISK_HITS = _telemetry.counter("materialize.exec_cache_disk_hits")
_T_COMPILES = _telemetry.counter("materialize.compiles")
_T_FILL_FAST = _telemetry.counter("materialize.fill_fastpath_hits")
_T_TORCH_FALLBACK = _telemetry.counter("materialize.torch_fallback_params")


def _profile_enabled() -> bool:
    import os

    return bool(os.environ.get("TDX_PROFILE_MATERIALIZE"))


def _rss_mb_now() -> float:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1024
    except OSError:
        pass
    return 0.0


# Bound on any one vmapped draw's transient buffer: bins whose padded
# population exceeds this are drawn in row chunks inside the same program
# (a 48-layer model's 16M-element fills would otherwise materialize a
# multi-GB (48, bucket) intermediate).
_FILL_CHUNK_BYTES = 512 * 1024 * 1024

# Fills above this size stay on the template path: large params are few and
# shape-repeated within a model (48 identical qkv projections), so pooling
# them buys no kernel-shape dedup while padding wastes bandwidth/HBM and
# chunking multiplies subgraphs.  Pooling earns its keep on the long tail of
# small unique shapes (a resnet's 40+ conv/bn signatures).  The lowerings
# draw exact (unpadded) lengths above this same bound — ops.aten_jax owns
# the constant so both sides agree.
from .ops.aten_jax import FILL_POOL_MAX as _FILL_POOL_MAX  # noqa: E402


def _plan_fill_bins(group_list, stacks, target_dtypes, tape_ordinals):
    """Split signature groups into pooled fill bins + remaining groups.

    One bin — one compiled program — per ``(draw dtype, bucket)``; all fill
    kinds sharing the bucket ride in it.  Entries carry everything the fast
    draw needs (name, output shape, numel, RNG identity of the fill node,
    scalar params, target dtype).  Ordering is deterministic: bins in
    first-appearance order over ``group_list``, kinds and entries likewise.
    """
    import numpy as np

    from .ops.aten_jax import fill_bucket

    bins: Dict[tuple, dict] = {}
    rest = []
    for g in group_list:
        stack, rec = g["rep"]
        if any(len(e) for e in g["exts"]):
            rest.append(g)
            continue
        m = _match_fill(stack, rec)
        if m is None:
            rest.append(g)
            continue
        kind, _, _, fill_idx = m
        rw = _MetaWindow(rec.node.out_metas[rec.index])
        if rw.numel > _FILL_POOL_MAX:
            rest.append(g)
            continue
        ddt = jnp_dtype_of(rw.dtype)
        bucket = fill_bucket(rw.numel)
        b = bins.setdefault(
            (str(ddt), bucket),
            {"ddt": ddt, "bucket": bucket, "kinds": {}},
        )
        entries = b["kinds"].setdefault(kind, [])
        for name in g["names"]:
            node = stacks[name][fill_idx]
            m_s0, m_s1 = _member_fill_scalars(kind, name, node)
            entries.append(
                {
                    "name": name,
                    "shape": rw.shape,
                    "numel": rw.numel,
                    "ord": tape_ordinals[node.base_nr],
                    "rel": node.op_nr - node.base_nr,
                    "s0": m_s0,
                    "s1": m_s1,
                    "tdt": target_dtypes[name],
                }
            )
    bin_list = list(bins.values())
    for b in bin_list:
        b["kinds"] = list(b["kinds"].items())
    fill_ins = [
        tuple(
            (
                np.asarray([e["ord"] for e in entries], dtype=np.uint32),
                np.asarray([e["rel"] for e in entries], dtype=np.uint32),
                np.asarray([e["s0"] for e in entries], dtype=b["ddt"]),
                np.asarray([e["s1"] for e in entries], dtype=b["ddt"]),
            )
            for _, entries in b["kinds"]
        )
        for b in bin_list
    ]
    return bin_list, fill_ins, rest


def _plan_big_fills(
    group_list, stacks, target_dtypes, tape_ordinals, plan, fakes, mesh
):
    """Extract large-fill groups (numel > _FILL_POOL_MAX) into direct-draw
    subgroups for the big-fill job; returns ``(subgroups, traced_inputs,
    remaining_groups)``.

    Large fills are never pooled (padding buys nothing at few, repeated
    shapes); each subgroup is one (kind, draw dtype, SHAPE, target dtype)
    class.  Draws are emitted directly in the output's N-D shape — under
    counter-based threefry ``normal(k, (n,)).reshape(shape)`` equals
    ``normal(k, shape)`` bitwise, and a direct N-D draw lets the SPMD
    partitioner generate ANY-dim sharding shard-locally (the flat-draw →
    reshape chain only propagates dim-0 shardings; a (2048, 5504)
    down-projection sharded on dim 1 silently replicated).  An
    instance-stacked ``shard_map`` variant was tried and rejected: the
    unstack from instance-sharding to each param's final sharding makes
    the partitioner all-gather the whole group (measured 31 GB peak /
    186 s at 1.35B); direct propagation needs no redistribution at all.
    Measured on the 1.35B HF Llama 8-device materialize, the prior
    template-replay path held peak RSS at 23 GB; this path generates
    every shard on its owner.
    """
    import numpy as np

    subs: Dict[tuple, dict] = {}
    rest = []
    for g in group_list:
        stack, rec = g["rep"]
        m = None
        if not any(len(e) for e in g["exts"]):
            m = _match_fill(stack, rec)
        if m is not None:
            rw = _MetaWindow(rec.node.out_metas[rec.index])
            if rw.numel <= _FILL_POOL_MAX:
                m = None
        if m is None:
            rest.append(g)
            continue
        kind, _, _, fill_idx = m
        rw = _MetaWindow(rec.node.out_metas[rec.index])
        ddt = jnp_dtype_of(rw.dtype)
        tdt = target_dtypes[g["names"][0]]
        for name in g["names"]:
            spec = _resolve_spec(plan, name, fakes[name], mesh)
            sg = subs.setdefault(
                (kind, str(ddt), rw.shape, str(tdt), str(spec)),
                {
                    "kind": kind,
                    "ddt": ddt,
                    "shape": rw.shape,
                    "numel": rw.numel,
                    "tdt": tdt,
                    "spec": spec,
                    "entries": [],
                },
            )
            node = stacks[name][fill_idx]
            m_s0, m_s1 = _member_fill_scalars(kind, name, node)
            sg["entries"].append(
                {
                    "name": name,
                    "shape": rw.shape,
                    "numel": rw.numel,
                    "ord": tape_ordinals[node.base_nr],
                    "rel": node.op_nr - node.base_nr,
                    "s0": m_s0,
                    "s1": m_s1,
                    # target dtype is CLASS-level (sg["tdt"]): the group
                    # key above already folds in target_dtypes[name].
                }
            )
    sub_list = list(subs.values())
    big_ins = [
        (
            np.asarray([e["ord"] for e in sg["entries"]], dtype=np.uint32),
            np.asarray([e["rel"] for e in sg["entries"]], dtype=np.uint32),
            np.asarray([e["s0"] for e in sg["entries"]], dtype=sg["ddt"]),
            np.asarray([e["s1"] for e in sg["entries"]], dtype=sg["ddt"]),
        )
        for sg in sub_list
    ]
    return sub_list, big_ins, rest


def _make_bigfill_class_fn(sg):
    """Single-instance draw program for one big-fill class — bitwise equal
    to the per-op lowering's flat draw + reshape (threefry is counter-
    based; scaling commutes with reshape).  The per-instance RNG key and
    fill scalars are *inputs*, so ONE compiled program serves every
    instance of the class (a 24-layer Llama has ~170 large fills but only
    ~4 classes), and XLA's backward propagation from the output sharding
    generates each shard on its owning device — any sharded dim, zero
    redistribution.  Rejected alternatives, measured at 1.35B HF/8 dev:
    per-entry chains in one program (compiles O(entries): 42 s), stacked
    vmapped draws (in-program unstack makes the partitioner all-gather
    the group: 31 GB peak / 186 s; eager unstack doubles transient RSS).
    """
    kind, ddt, shape, tdt = sg["kind"], sg["ddt"], sg["shape"], sg["tdt"]

    def fn(kk, a, b_):
        import jax
        import jax.numpy as jnp

        if kind == "uniform":
            v = jax.random.uniform(kk, shape, dtype=ddt, minval=a, maxval=b_)
        elif kind == "normal":
            v = jax.random.normal(kk, shape, dtype=ddt) * b_ + a
        elif kind == "full":
            v = jnp.broadcast_to(a, shape).astype(ddt)
        else:  # zero
            v = jnp.zeros(shape, dtype=ddt)
        return v.astype(tdt)

    return fn


def _pack_host_leaves(leaves):
    """Group ``np.ndarray`` leaves by dtype into one flat buffer each.

    Returns ``(by_dt, order, layout, packed)``: slot indices per dtype,
    sorted dtype order, the static layout (shapes per slot — program
    identity for the unpack), and the concatenated host buffers.  Shared
    by the argpack transfer and the mono executable so the offset
    arithmetic exists once.
    """
    import numpy as np

    by_dt: Dict[str, list] = {}
    for i, l in enumerate(leaves):
        if isinstance(l, np.ndarray):
            by_dt.setdefault(str(l.dtype), []).append(i)
    order = sorted(by_dt)
    layout = tuple(
        (dt, tuple(tuple(leaves[i].shape) for i in by_dt[dt]))
        for dt in order
    )
    packed = [
        np.concatenate([leaves[i].ravel() for i in by_dt[dt]])
        for dt in order
    ]
    return by_dt, order, layout, packed


def _unpack_bufs(bufs, by_dt, order, layout):
    """Traced inverse of :func:`_pack_host_leaves`: slot → value dict."""
    import numpy as np

    vals = {}
    for buf, (dt, shapes) in zip(bufs, layout):
        off = 0
        for slot, shp in zip(by_dt[dt], shapes):
            n = int(np.prod(shp, dtype=np.int64))
            vals[slot] = buf[off:off + n].reshape(shp)
            off += n
    return vals


def _bin_entry_key(b):
    """Exec-cache identity of a bin program (scalar params are traced
    inputs, NOT identity — a changed init std reuses the executable)."""
    return tuple(
        (
            kind,
            tuple(
                (e["name"], e["numel"], e["shape"], str(e["tdt"]))
                for e in entries
            ),
        )
        for kind, entries in b["kinds"]
    )


def _bin_names(b):
    return [e["name"] for _, entries in b["kinds"] for e in entries]


def _make_bin_fn(b):
    """Trace function for one fill bin: per kind, a vmapped padded draw in
    row chunks of ≤_FILL_CHUNK_BYTES, then per-entry slice/reshape/cast.
    Bitwise equal to the per-op lowering replay (the lowerings draw the same
    buckets — ops.aten_jax.fill_bucket; threefry fold_in keys are
    vmap-invariant), so module- and tensor-path values agree."""
    import numpy as np

    ddt, bucket = b["ddt"], b["bucket"]
    rows_cap = max(
        1, _FILL_CHUNK_BYTES // (bucket * np.dtype(ddt).itemsize)
    )

    def fn(base_key, kin):
        import jax
        import jax.numpy as jnp

        fold = jax.vmap(
            lambda o, r: jax.random.fold_in(
                jax.random.fold_in(base_key, o), r
            )
        )
        out = {}
        for (kind, entries), (ords, rels, s0, s1) in zip(b["kinds"], kin):
            n = len(entries)
            for lo in range(0, n, rows_cap):
                hi = min(n, lo + rows_cap)
                if kind == "uniform":
                    chunk = jax.vmap(
                        lambda k, a, b_: jax.random.uniform(
                            k, (bucket,), dtype=ddt, minval=a, maxval=b_
                        )
                    )(fold(ords[lo:hi], rels[lo:hi]), s0[lo:hi], s1[lo:hi])
                elif kind == "normal":
                    chunk = jax.vmap(
                        lambda k, mu, sd: jax.random.normal(
                            k, (bucket,), dtype=ddt
                        )
                        * sd
                        + mu
                    )(fold(ords[lo:hi], rels[lo:hi]), s0[lo:hi], s1[lo:hi])
                elif kind == "full":
                    chunk = jnp.broadcast_to(
                        s0[lo:hi, None], (hi - lo, bucket)
                    ).astype(ddt)
                else:  # zero
                    chunk = jnp.zeros((hi - lo, bucket), dtype=ddt)
                for i in range(lo, hi):
                    e = entries[i]
                    out[e["name"]] = (
                        chunk[i - lo, : e["numel"]]
                        .reshape(e["shape"])
                        .astype(e["tdt"])
                    )
        return out

    return fn


def _make_template(stack: List[OpNode], record, target_dtype):
    """Build the replay template for one signature group.

    Closes over the *representative* instance's nodes (shapes/ops identical
    across the group by signature equality); per-node PRNG keys and external
    tensor values come in as arguments, so the jitted template is reused by
    every instance.
    """
    local = {n.op_nr: i for i, n in enumerate(stack)}

    def template(keys, exts):
        ext_iter = iter(exts)
        eng = _FunctionalReplay(
            None,
            check_guards=False,
            key_lookup=lambda node: keys[local[node.op_nr]],
            ext_lookup=lambda t: next(ext_iter),
        )
        for n in stack:
            eng.run_node(n)
        return eng.value_of_output(record.node, record.index).astype(
            target_dtype
        )

    return template


# ---------------------------------------------------------------------------
# Public API


def _named_fakes(module: nn.Module) -> List[Tuple[str, FakeTensor]]:
    out = []
    for name, p in module.named_parameters(remove_duplicate=True):
        if is_deferred(p):
            out.append((name, p))
    for name, b in module.named_buffers(remove_duplicate=True):
        if is_deferred(b):
            out.append((name, b))
    return out


def _resolve_spec(plan, name: str, fake: FakeTensor, mesh=None):
    from jax.sharding import PartitionSpec

    from .parallel.sharding import fit_spec_to_mesh, replicate_indivisible

    if plan is None:
        return PartitionSpec()
    if callable(plan):
        spec = plan(name, tuple(fake.shape))
    else:
        spec = plan.get(name)
    if spec is None:
        return PartitionSpec()
    if mesh is None:
        return spec
    return replicate_indivisible(
        fit_spec_to_mesh(spec, mesh), tuple(fake.shape), mesh
    )


def _base_key(seed: int, rng_impl: str):
    import jax

    return jax.random.key(seed, impl=rng_impl)


def materialize_tensor_jax(
    tensor: torch.Tensor,
    *,
    mesh=None,
    spec=None,
    seed: int = 0,
    dtype: Optional[torch.dtype] = None,
    rng_impl: str = "threefry2x32",
):
    """Materialize one fake tensor as a ``jax.Array`` (optionally sharded).

    ``rng_impl``: ``"threefry2x32"`` (default — bitwise stable across
    topologies/shardings, the multi-host guarantee) or ``"rbg"`` (XLA
    RngBitGenerator — much cheaper to compile, for single-chip or
    throwaway-init use; values may depend on backend/sharding).
    """
    import jax

    ensure_compilation_cache()

    record = _get_record(tensor) if isinstance(tensor, FakeTensor) else None
    if record is None:
        raise ValueError("`tensor` is not a deferred fake tensor.")

    target_dtype = jnp_dtype_of(dtype or tensor.dtype)

    def compute():
        eng = _FunctionalReplay(_base_key(seed, rng_impl), check_guards=False)
        eng.run_call_stack(record.node)
        return eng.value_of_output(record.node, record.index).astype(
            target_dtype
        )

    _check_guards_of(record.node)
    from .utils.compilation_cache import cache_everything

    with _telemetry.span("materialize.tensor"), cache_everything(), \
            _perf.program("materialize"):
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            sharding = NamedSharding(mesh, spec or PartitionSpec())
            return jax.jit(compute, out_shardings=sharding)()
        return jax.jit(compute)()


def _check_guards_of(target: OpNode) -> None:
    # Guard checks touch torch tensors; run them eagerly (outside jit trace).
    for node in _tape.build_call_stack(target):
        for guard in node.op.guards:
            guard.check()


def _plan_groups(
    jax_names: List[str],
    fakes: Dict[str, FakeTensor],
    stacks: Dict[str, List[OpNode]],
    target_dtypes: Dict[str, Any],
) -> Tuple[List[dict], List[str]]:
    """Partition params into signature groups and fused leftovers.

    A param is groupable iff its stack shares no node with any other param's
    stack (per-target replay of a shared storage could otherwise advance it
    past another target's read point) and every arg is hashable/templatable.
    Returns ``(group_list, leftover_names)``; each group carries its
    representative stack, per-instance external tensors, and op_nr rows.
    """
    owner_count: Dict[int, int] = {}
    for name in jax_names:
        for n in stacks[name]:
            owner_count[n.op_nr] = owner_count.get(n.op_nr, 0) + 1

    groups: Dict[tuple, dict] = {}
    fused: List[str] = []
    for name in jax_names:
        stack = stacks[name]
        if any(owner_count[n.op_nr] > 1 for n in stack):
            fused.append(name)
            continue
        rec = _get_record(fakes[name])
        analyzed = _analyze_stack(stack, rec)
        if analyzed is None:
            fused.append(name)
            continue
        sig, ext_values = analyzed
        key = (sig, str(target_dtypes[name]))
        g = groups.setdefault(
            key,
            {"key": key, "names": [], "exts": [], "rep": (stack, rec)},
        )
        g["names"].append(name)
        g["exts"].append(ext_values)
    return list(groups.values()), fused


# ---------------------------------------------------------------------------
# In-process executable cache.
#
# The group signature IS the program identity: two materializations whose
# groups carry equal signatures (and names/shardings/seed/rng) trace to the
# same jaxpr, with all instance data — op_nr rows, external tensors —
# entering as traced inputs.  Re-materializing the same architecture in one
# process (hyperparameter sweeps, re-init after resharding, test suites)
# therefore reuses the compiled executable outright: no retrace, no XLA
# compile, no persistent-cache deserialization.  Cross-process warm starts
# are covered separately by the persistent compilation cache
# (utils/compilation_cache.py).

_EXEC_CACHE: "Dict[tuple, Any]" = {}
_EXEC_CACHE_MAX = 64
_EXEC_CACHE_LOCK = threading.Lock()
# Incremented once per materialize_module_jax call whose programs ALL hit
# the cache (i.e. zero compiles happened) — introspection for tests/bench.
exec_cache_hits = 0


def _exec_cache_enabled() -> bool:
    import os

    return not os.environ.get("TDX_NO_EXEC_CACHE")


# Disk tier: AOT executables serialized per program (key = sha256 of the
# exec key).  A warm PROCESS skips retracing and the XLA-cache machinery
# outright — deserialize_and_load is the only per-program cost.  Follows
# the persistent compilation cache's enable flag AND the exec-cache flag;
# any load failure (jax/runtime version change, different device topology)
# falls back to compiling and counts on ``compile_cache.errors``.
#
# Trust model: jax's deserialize_and_load unpickles the blob, so reading a
# blob executes whatever the writer put there.  The tier therefore only
# reads/writes a PRIVATE directory: created 0700, and refused entirely if
# it is not owned by this uid or is group/other-writable (e.g. inside a
# shared JAX_COMPILATION_CACHE_DIR on a multi-user cluster).

_EXEC_DISK_MAX_ENTRIES = 256


def _exec_disk_dir():
    """The tier's directory (``<compile cache dir>/tdx_exec``), or None
    when the tier is off.  Never raises — the cache is a pure optimization
    — but a directory that cannot be made, or is refused by the trust
    check below, counts on ``compile_cache.errors``: a tier that is off on
    a machine where it was expected on must be visible."""
    import os
    import stat

    import jax

    if os.environ.get("TDX_NO_COMPILATION_CACHE"):
        return None
    if jax.default_backend() == "cpu":
        # Same rule as utils.compilation_cache: CPU executables are tied
        # to the build host's machine features (reloading warns or
        # SIGILLs), and the test suite's cache-hit invariants must not
        # leak across runs.  The tier's value is on accelerators.
        return None
    base = _cc.cache_dir()
    if "://" in base:
        # Remote cache dirs (gs://...) serve JAX's own persistent cache
        # through its filesystem layer; this tier is local-only.
        base = _cc.DEFAULT_CACHE_DIR
    d = os.path.join(base, "tdx_exec")
    try:
        os.makedirs(d, mode=0o700, exist_ok=True)
        st = os.stat(d)
    except OSError:
        _cc._T_ERRORS.add()
        return None
    if st.st_uid != os.getuid() or (
        st.st_mode & (stat.S_IWGRP | stat.S_IWOTH)
    ):
        _cc._T_ERRORS.add()
        return None  # shared/foreign dir: never unpickle from it
    return d


def _exec_disk_path(key):
    import hashlib
    import os

    d = _exec_disk_dir()
    if d is None:
        return None
    # Keys are nested tuples of primitives (strings/ints/bools) by
    # construction (_hashable_or_none guards hashability; all tensor-ish
    # parts are stringified) — repr() is deterministic for those.
    h = hashlib.sha256(repr(key).encode()).hexdigest()
    return os.path.join(d, f"{h}.pkl")


def _exec_disk_has(key) -> bool:
    """Cheap existence probe (no deserialize/load)."""
    import os

    if not _exec_cache_enabled() or key is None:
        return False
    path = _exec_disk_path(key)
    return path is not None and os.path.exists(path)


def _exec_disk_get(key):
    import pickle

    if not _exec_cache_enabled():
        # TDX_NO_EXEC_CACHE opts out of SERVING cached executables, not
        # just storing them.
        return None
    path = _exec_disk_path(key)
    if path is None:
        return None
    try:
        with open(path, "rb") as f:
            blob, in_tree, out_tree = pickle.loads(f.read())
        from jax.experimental.serialize_executable import (
            deserialize_and_load,
        )

        loaded = deserialize_and_load(blob, in_tree, out_tree)
        import os

        os.utime(path)  # recency refresh: the prune evicts oldest-by-mtime
        _T_EXEC_DISK_HITS.add()
        return loaded
    except FileNotFoundError:
        return None  # plain miss
    except Exception:  # noqa: BLE001 — stale/foreign blob: recompile
        _cc._T_ERRORS.add()
        return None


def _exec_disk_put(key, cfn) -> None:
    import os
    import pickle

    path = _exec_disk_path(key)
    if path is None:
        return
    try:
        from jax.experimental.serialize_executable import serialize

        payload = pickle.dumps(serialize(cfn))
        # Unique per process AND thread: puts run from the build pool, and
        # two same-key writers sharing a tmp name would interleave into a
        # corrupt published blob.
        tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
        with open(tmp, "wb") as f:
            f.write(payload)
        os.replace(tmp, path)  # atomic vs concurrent writers
        # Bound the tier: prune least-recently-used (mtime, refreshed on
        # disk hits) past the cap.
        d = os.path.dirname(path)
        entries = [e for e in os.listdir(d) if e.endswith(".pkl")]
        if len(entries) > _EXEC_DISK_MAX_ENTRIES:
            # Per-entry safe mtime: a concurrent process unlinking one
            # file mid-sort must not abort the whole prune (the blanket
            # except below would silently swallow it, letting the
            # directory grow unbounded under concurrent writers).
            def _mtime(e):
                try:
                    return os.path.getmtime(os.path.join(d, e))
                except OSError:
                    return 0.0

            entries.sort(key=_mtime)
            for e in entries[: len(entries) - _EXEC_DISK_MAX_ENTRIES]:
                try:
                    os.unlink(os.path.join(d, e))
                except OSError:
                    pass
    except Exception:  # noqa: BLE001 — cache write is pure optimization
        _cc._T_ERRORS.add()


def _exec_cache_get(key):
    """Memory tier only — the disk tier is consulted explicitly (inside
    the build pool, so deserialize+loads overlap)."""
    if not _exec_cache_enabled():
        return None
    with _EXEC_CACHE_LOCK:
        fn = _EXEC_CACHE.get(key)
        if fn is not None:
            # LRU refresh: eviction pops the front, so a hit must move the
            # key to the back or a hot architecture can be evicted over
            # cold ones.
            del _EXEC_CACHE[key]
            _EXEC_CACHE[key] = fn
    if fn is not None:
        _T_EXEC_MEM_HITS.add()
    return fn


def _exec_cache_put(key, fn, *, disk: bool = True) -> None:
    if not _exec_cache_enabled():
        return
    with _EXEC_CACHE_LOCK:
        if key not in _EXEC_CACHE and len(_EXEC_CACHE) >= _EXEC_CACHE_MAX:
            _EXEC_CACHE.pop(next(iter(_EXEC_CACHE)))
        _EXEC_CACHE[key] = fn
    if disk:
        _exec_disk_put(key, fn)


def materialize_module_jax(
    module: nn.Module,
    *,
    mesh=None,
    plan: Optional[Any] = None,
    seed: int = 0,
    dtype: Optional[torch.dtype] = None,
    rng_impl: str = "threefry2x32",
    strategy: str = "auto",
    _fallback_torch: bool = True,
) -> Dict[str, Any]:
    """Materialize every fake param/buffer of ``module`` as JAX arrays.

    Returns ``{qualified_name: jax.Array}`` with per-leaf shardings from
    ``plan`` — XLA SPMD generates each shard on its own device.

    ``plan``: ``None`` (replicated), a dict ``{name: PartitionSpec}``, or a
    callable ``(name, shape) -> PartitionSpec | None`` (see
    :mod:`torchdistx_tpu.parallel.sharding` for FSDP/TP plan builders).
    ``dtype``: optional cast applied to every leaf (e.g. ``torch.bfloat16``
    for TPU training).  ``rng_impl``: see :func:`materialize_tensor_jax`
    (``"rbg"`` roughly halves XLA compile time for init-heavy tapes).

    ``strategy``:

    * ``"grouped"``/``"auto"`` — dedupe structurally identical per-param call
      stacks and compile one small program per unique signature (compile time
      O(unique layer kinds), not O(depth)); params whose stacks share nodes
      with other params fall back to the fused program, preserving
      write-ordering semantics through aliases.
    * ``"fused"`` — one monolithic jit of the union init subgraph (the
      round-1 behavior).

    XLA compile time dominates a cold materialization; the emitted HLO is
    process-stable by design, and the persistent compilation cache is
    enabled on first use (see utils/compilation_cache.py), so warm runs —
    restarts, sweeps, resharded re-inits of the same architecture — skip
    compilation entirely.
    """
    ensure_compilation_cache()
    global last_profile
    last_profile = {"jobs": []}
    _T_CALLS.add()
    # Phase spans (telemetry): plan → compile → transfer → execute, nested
    # under one materialize.module span.  last_profile is assembled from
    # the spans' durations, so it works with telemetry sinks off.  The
    # spans live in THIS frame so a raising path (guard violation, unknown
    # strategy, UnsupportedOpError) cannot leak them onto the thread-local
    # nesting stack or strand an open jax.profiler annotation — the call
    # span records the error class, the never-completed plan phase drops.
    _sp_call = _telemetry.start_span("materialize.module", strategy=strategy)
    _sp_plan = _telemetry.start_span("materialize.plan")
    try:
        # Compile observatory: every XLA compile this materialization
        # issues on THIS thread (the fused program, the per-job jits of
        # the execute phase) attributes to program="materialize" via the
        # jax.monitoring listener; the grouped compile pool's worker
        # threads scope themselves inside _build.
        with _perf.program("materialize"):
            return _materialize_module_jax(
                module,
                mesh=mesh,
                plan=plan,
                seed=seed,
                dtype=dtype,
                rng_impl=rng_impl,
                strategy=strategy,
                _fallback_torch=_fallback_torch,
                _sp_call=_sp_call,
                _sp_plan=_sp_plan,
            )
    except BaseException as e:
        if _perf.is_oom(e):
            # The OOM post-mortem: which component held the device when
            # materialization could not fit (a serving engine's pool and
            # weights share the chip with this allocation).
            _perf.oom_dump(
                "device_oom", site="materialize",
                error=f"{type(e).__name__}: {e}",
            )
        if _sp_plan.duration is None:
            _sp_plan.cancel()
        if _sp_call.duration is None:
            _sp_call.end(error=type(e).__name__)
        raise


def _replicate_mesh_args(all_args, mesh):
    """Explicitly place host argument leaves for mesh-lowered executables.

    Mesh-job programs are lowered from host numpy leaves, and calling
    them back with those raw leaves leans on ``Compiled.__call__``'s
    input-sharding tolerance — which for committed/host arrays against
    mesh-lowered programs is JAX-version-dependent (advisor r4, VERDICT
    item 8b).  A replicated ``NamedSharding`` placement IS the layout
    the executables were lowered for, on every version.  One batched
    ``device_put`` for all leaves; non-array leaves pass through.
    """
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as _P

    rep = NamedSharding(mesh, _P())
    leaves, treedef = jax.tree.flatten(all_args)
    idx = [
        i for i, x in enumerate(leaves)
        if isinstance(x, (np.ndarray, jax.Array))
    ]
    placed = jax.device_put([leaves[i] for i in idx], rep)
    for i, arr in zip(idx, placed):
        leaves[i] = arr
    return jax.tree.unflatten(treedef, leaves)


def _materialize_module_jax(
    module: nn.Module,
    *,
    mesh,
    plan,
    seed,
    dtype,
    rng_impl,
    strategy,
    _fallback_torch,
    _sp_call,
    _sp_plan,
) -> Dict[str, Any]:
    import jax

    global exec_cache_hits

    named = _named_fakes(module)
    if not named:
        _sp_plan.cancel()
        _sp_call.end(n_params=0)
        return {}

    # Eager guard validation (torch-side, can't run under trace).
    for _, fake in named:
        _check_guards_of(_get_record(fake).node)

    fakes = dict(named)
    stacks: Dict[str, List[OpNode]] = {
        name: _tape.build_call_stack(_get_record(fake).node)
        for name, fake in named
    }

    jax_names: List[str] = []
    unsupported: List[Tuple[str, FakeTensor]] = []
    # Probe lowerability cheaply: every non-view node in each call stack
    # must have a lowering.
    for name, fake in named:
        ok = True
        for n in stacks[name]:
            if _is_view_node(n):
                continue
            if _packet_name(n.op.func) not in LOWERINGS:
                ok = False
                break
        (jax_names.append(name) if ok else unsupported.append((name, fake)))

    target_dtypes = {
        name: jnp_dtype_of(dtype or fakes[name].dtype) for name, _ in named
    }

    results: Dict[str, Any] = {}
    if strategy in ("auto", "grouped"):
        group_list, fused_names = _plan_groups(
            jax_names, fakes, stacks, target_dtypes
        )
    elif strategy == "fused":
        group_list, fused_names = [], list(jax_names)
    else:
        raise ValueError(f"unknown strategy: {strategy!r}")

    # Tape ordinals: distinct tapes reachable from the targets, numbered in
    # first-appearance order over the named params' stacks (deterministic
    # across processes — iteration follows module naming order).
    tape_ordinals: Dict[int, int] = {}
    for name, _ in named:
        for n in stacks[name]:
            tape_ordinals.setdefault(n.base_nr, len(tape_ordinals))

    if jax_names:
        import numpy as np

        # Pool trivial fill stacks across shapes into bucketed vmapped
        # draws; only the remaining groups pay per-signature templates.
        global last_fill_fastpath_params
        if _fill_fastpath_enabled():
            bin_list, fill_ins, tmpl_groups = _plan_fill_bins(
                group_list, stacks, target_dtypes, tape_ordinals
            )
        else:
            bin_list, fill_ins, tmpl_groups = [], [], list(group_list)
        last_fill_fastpath_params = sum(
            len(_bin_names(b)) for b in bin_list
        )
        if last_fill_fastpath_params:
            _T_FILL_FAST.add(last_fill_fastpath_params)

        # Instance-distribution axis for shard_map'd generation: the
        # largest mesh axis (shared by the big-fill job and the template
        # groups below).
        shard_axis = None
        if mesh is not None and mesh.devices.size > 1:
            shard_axis = max(mesh.shape, key=lambda a: mesh.shape[a])
            if mesh.shape[shard_axis] <= 1:
                shard_axis = None

        # Multi-device meshes: large fills leave the template path for the
        # big-fill job (direct draws shard; vmapped replay replicates —
        # see _plan_big_fills).  Single-device runs keep the template path:
        # program structure there is tuned for the number of executable
        # loads and transfers.
        if mesh is not None and mesh.devices.size > 1:
            big_list, big_ins, tmpl_groups = _plan_big_fills(
                tmpl_groups, stacks, target_dtypes, tape_ordinals,
                plan, fakes, mesh,
            )
        else:
            big_list, big_ins = [], []
        if mesh is not None and mesh.devices.size > 1:
            # Anything still generated replicated is visible, not silent.
            lone = [
                (g["names"][0],
                 int(_MetaWindow(
                     g["rep"][1].node.out_metas[g["rep"][1].index]
                 ).numel))
                for g in tmpl_groups
                if len(g["names"]) == 1
            ]
            if lone:
                logging.getLogger(__name__).info(
                    "materialize: %d singleton group(s) generate "
                    "replicated on the mesh: %s",
                    len(lone),
                    ", ".join(f"{n} ({sz} elems)" for n, sz in lone),
                )

        templates = [
            _make_template(*g["rep"], target_dtypes[g["names"][0]])
            for g in tmpl_groups
        ]
        # Per-group traced inputs: per-instance per-node RNG identities —
        # (tape ordinal, tape-relative op_nr) rows of shape (n_inst,
        # n_nodes) — and external tensor slots stacked along the instance
        # axis.  Instance data enters as *arguments*, so the traced program
        # is byte-identical for any same-architecture materialization
        # (exec-cache and persistent-cache hits).
        ords_in = [
            np.asarray(
                [
                    [tape_ordinals[n.base_nr] for n in stacks[name]]
                    for name in g["names"]
                ],
                dtype=np.uint32,
            )
            for g in tmpl_groups
        ]
        rels_in = [
            np.asarray(
                [
                    [n.op_nr - n.base_nr for n in stacks[name]]
                    for name in g["names"]
                ],
                dtype=np.uint32,
            )
            for g in tmpl_groups
        ]
        exts_in = [
            [
                np.stack(
                    [
                        g["exts"][i][j].detach().cpu().numpy()
                        for i in range(len(g["names"]))
                    ]
                )
                for j in range(len(g["exts"][0]))
            ]
            for g in tmpl_groups
        ]

        def compute_rest(base_key, ords_in, rels_in, exts_in):
            fold = jax.vmap(
                jax.vmap(
                    lambda o, r: jax.random.fold_in(
                        jax.random.fold_in(base_key, o), r
                    )
                )
            )
            out = {}
            # Signature groups: one vmapped template each — the compiled
            # program contains one subgraph per unique layer *kind*, not per
            # layer (compile time O(unique kinds), not O(depth)).
            #
            # On a mesh, every multi-instance group runs the vmap INSIDE
            # shard_map over the largest axis (instance rows padded up to a
            # multiple of the axis): each device replays only its own
            # instances.  Without this the SPMD partitioner cannot push the
            # per-param out_shardings back through the unstack/replay
            # machinery and REPLICATES every group's generation on every
            # device — measured 8 × full-model f32 RSS for a 1.35B HF
            # materialize on the 8-device virtual mesh.  Values are
            # unchanged: per-instance keys don't depend on placement.
            # Large-fill groups were already extracted to the big-fill job
            # (direct draws shard natively); remaining singleton groups
            # (e.g. a lone rotary buffer) stay replicated — their transient
            # is one small param, logged at plan time.
            for g, template, ords, rels, exts in zip(
                tmpl_groups, templates, ords_in, rels_in, exts_in
            ):
                import jax.numpy as _jnp

                keys = fold(ords, rels)
                n_inst = len(g["names"])
                ax = shard_axis
                if ax is not None and n_inst >= 2:
                    from jax.sharding import PartitionSpec as _P

                    # Pad the instance axis up to a multiple of the mesh
                    # axis (repeating leading rows — their values are
                    # computed twice and dropped) so every multi-instance
                    # group distributes; only singletons stay replicated.
                    A = mesh.shape[ax]
                    pad = (-n_inst) % A
                    if pad:
                        reps = -(-(n_inst + pad) // n_inst)

                        def _padrow(x):
                            return _jnp.concatenate([x] * reps)[
                                : n_inst + pad
                            ]

                        keys = _padrow(keys)
                        exts = jax.tree.map(_padrow, exts)
                    row = _P(ax)
                    res = jax.shard_map(
                        lambda k, e: jax.vmap(template)(k, e),
                        mesh=mesh,
                        in_specs=(row, jax.tree.map(lambda _: row, exts)),
                        out_specs=row,
                        axis_names=frozenset({ax}),
                        check_vma=False,
                    )(keys, exts)
                else:
                    res = jax.vmap(template)(keys, exts)
                for i, name in enumerate(g["names"]):
                    out[name] = res[i]
            # Fused leftovers: union of the remaining targets' call stacks,
            # replayed once in global chronological order — a per-target
            # replay could advance a shared storage past an earlier target's
            # read point (write-after-read through an alias), making results
            # depend on traversal order.
            if fused_names:
                eng = _FunctionalReplay(
                    base_key,
                    check_guards=False,
                    key_lookup=lambda node: jax.random.fold_in(
                        jax.random.fold_in(
                            base_key,
                            tape_ordinals.setdefault(
                                node.base_nr, len(tape_ordinals)
                            ),
                        ),
                        node.op_nr - node.base_nr,
                    ),
                )
                nodes: Dict[int, OpNode] = {}
                for name in fused_names:
                    for n in stacks[name]:
                        nodes[n.op_nr] = n
                for nr in sorted(nodes):
                    eng.run_node(nodes[nr])
                for name in fused_names:
                    rec = _get_record(fakes[name])
                    out[name] = eng.value_of_output(
                        rec.node, rec.index
                    ).astype(target_dtypes[name])
            return out

        if mesh is not None:
            from jax.sharding import NamedSharding

            shardings = {
                name: NamedSharding(
                    mesh, _resolve_spec(plan, name, fakes[name], mesh)
                )
                for name in jax_names
            }
        else:
            shardings = None

        # Device-id + per-output-sharding component of program identity:
        # str(NamedSharding) omits device identities — two same-shape meshes
        # over different devices must not share executables.
        def _mesh_key(names):
            if mesh is None:
                return None
            return (
                tuple(d.id for d in mesh.devices.flat),
                tuple(
                    (name, str(shardings[name])) for name in sorted(names)
                ),
            )

        def _hashable_or_none(key):
            try:
                hash(key)
            except TypeError:
                return None
            return key

        # The materialization is a set of independent programs — one per
        # fill bin plus one for the template/fused remainder — each
        # separately exec-cached (the AOT executable, not the jit wrapper:
        # the wrapper would pin the tape closure) and, on a miss, compiled
        # CONCURRENTLY: XLA compiles are independent of one another.
        #
        # Program identity excludes the seed — the base key is a traced
        # input, so one executable serves a whole seed sweep.
        #
        # cache_everything covers the WHOLE section, not just the compiles:
        # key construction (`jax.random.key` for rbg dispatches a few tiny
        # eager programs — threefry_seed, convert, concatenate) is a
        # compile per program, and JAX's default admission threshold (min
        # 1s compile time) would silently refuse to persist them — every
        # process would pay them again.
        from .utils.compilation_cache import cache_everything

        with cache_everything():
            base_key = _base_key(seed, rng_impl)
        jobs = []  # (exec_key|None, trace_fn, args, out_shardings|None)
        shadow_jobs = []  # compiled+cached for future runs, never executed
        if bin_list:
            # ALL fill bins ride ONE program on cached runs: each
            # executable costs a deserialize + device load on a
            # cached-cold run, so per-bin programs made exec loads the
            # cached-cold floor.  But a merged program compiles its bins
            # SERIALLY, while separate bins compile CONCURRENTLY — so on a
            # compile run the bins stay per-program (fast first materialize) and
            # the merged fillpack is compiled as a SHADOW job in the same
            # pool (overlapped, results discarded) purely to seed the
            # cache for future cached-cold runs.
            fill_names = [n for b in bin_list for n in _bin_names(b)]
            fkey = _hashable_or_none(
                (
                    "fillpack",
                    rng_impl,
                    tuple(
                        (str(b["ddt"]), b["bucket"], _bin_entry_key(b))
                        for b in bin_list
                    ),
                    _mesh_key(fill_names),
                )
            )
            bin_fns = [_make_bin_fn(b) for b in bin_list]

            def fills_fn(base_key, all_fins):
                out = {}
                for fn, fins in zip(bin_fns, all_fins):
                    out.update(fn(base_key, fins))
                return out

            osh_all = (
                {name: shardings[name] for name in fill_names}
                if shardings is not None
                else None
            )
            fill_args = (base_key, list(fill_ins))
            # Existence probe only — a stale blob (e.g. after a runtime
            # upgrade) routes ONE materialize through a serial merged
            # compile, which stores a fresh blob (self-healing); probing
            # loadability here would pay the full deserialize up
            # front on every cached-cold run instead.
            merged_ready = fkey is not None and (
                _exec_cache_get(fkey) is not None or _exec_disk_has(fkey)
            )
            if merged_ready:
                jobs.append((fkey, fills_fn, fill_args, osh_all))
            else:
                for b, fn, fins in zip(bin_list, bin_fns, fill_ins):
                    names = _bin_names(b)
                    bkey = _hashable_or_none(
                        (
                            "fillbin",
                            str(b["ddt"]),
                            b["bucket"],
                            rng_impl,
                            _bin_entry_key(b),
                            _mesh_key(names),
                        )
                    )
                    osh = (
                        {name: shardings[name] for name in names}
                        if shardings is not None
                        else None
                    )
                    jobs.append((bkey, fn, (base_key, fins), osh))
                if fkey is not None and _exec_cache_enabled():
                    shadow_jobs.append(
                        (fkey, fills_fn, fill_args, osh_all)
                    )

        # Big-fill classes: ONE single-instance program per (kind, dtype,
        # shape, target dtype, sharding) class, executed once per instance
        # with the instance's key/scalars as replicated inputs.  See
        # _make_bigfill_class_fn for why this shape wins.  Class programs
        # join the same build pool (concurrent compiles / disk loads).
        class_jobs = []
        if big_list:
            from jax.sharding import NamedSharding as _NS
            from jax.sharding import PartitionSpec as _P

            repl = _NS(mesh, _P())
            all_ords = np.concatenate([bi[0] for bi in big_ins])
            all_rels = np.concatenate([bi[1] for bi in big_ins])
            with cache_everything():
                keys_rep = jax.device_put(
                    jax.jit(
                        lambda k, o, r: jax.vmap(
                            lambda oo, rr: jax.random.fold_in(
                                jax.random.fold_in(k, oo), rr
                            )
                        )(o, r)
                    )(base_key, all_ords, all_rels),
                    repl,
                )
                s_rep = [
                    (
                        jax.device_put(bi[2], repl),
                        jax.device_put(bi[3], repl),
                    )
                    for bi in big_ins
                ]
            mesh_ids = tuple(d.id for d in mesh.devices.flat)
            for j, sg in enumerate(big_list):
                osh_c = _NS(mesh, sg["spec"])
                ckey = _hashable_or_none(
                    (
                        "bigfillcls",
                        rng_impl,
                        sg["kind"],
                        str(sg["ddt"]),
                        sg["shape"],
                        str(sg["tdt"]),
                        mesh_ids,
                        str(osh_c),
                    )
                )
                class_jobs.append(
                    (
                        ckey,
                        _make_bigfill_class_fn(sg),
                        (keys_rep[0], s_rep[j][0][0], s_rep[j][1][0]),
                        osh_c,
                    )
                )

        if tmpl_groups or fused_names:
            # Cacheable only when nothing takes the fused path — the fused
            # branch bakes instance data into the trace.
            rest_key = None
            if tmpl_groups and not fused_names and not unsupported:
                rest_key = _hashable_or_none(
                    (
                        "rest",
                        tuple(
                            (g["key"], tuple(g["names"]))
                            for g in tmpl_groups
                        ),
                        rng_impl,
                        _mesh_key(
                            [n for g in tmpl_groups for n in g["names"]]
                        ),
                    )
                )
            rest_names = [n for g in tmpl_groups for n in g["names"]]
            rest_names += fused_names
            osh = (
                {name: shardings[name] for name in rest_names}
                if shardings is not None
                else None
            )
            jobs.append(
                (rest_key, compute_rest,
                 (base_key, ords_in, rels_in, exts_in), osh)
            )

        # --- Mono executable: the WHOLE single-chip materialization as ONE
        # program.  The cached-cold floor is the executable loads
        # (deserialize + device load each); the mono path needs exactly
        # one exec load, one packed host→device transfer, and one
        # dispatch.  Composed from the CANONICAL job set — the merged
        # fillpack + the rest program — NOT this run's `jobs` list, whose shape
        # differs between the first run (per-bin jobs) and cached runs
        # (merged fillpack): a key over `jobs` could never hit the blob
        # its own first run seeded.  Identity = canonical keys + packed
        # layout, so any change in architecture/plan/dtype misses cleanly;
        # per-job caches remain the fallback.  Compiled as a shadow job on
        # miss — overlapped with the real compiles.  Single-device only.
        import os as _os

        mono_key = None
        mono_jobs = []
        if (
            jobs
            and mesh is None
            and not unsupported
            and _exec_cache_enabled()
            and not _os.environ.get("TDX_NO_MONO")
        ):
            if bin_list:
                mono_jobs.append((fkey, fills_fn, fill_args))
            if tmpl_groups or fused_names:
                mono_jobs.append(
                    (rest_key, compute_rest,
                     (base_key, ords_in, rels_in, exts_in))
                )
            if mono_jobs and all(k is not None for k, _, _ in mono_jobs):
                all_args_m = [a for _, _, a in mono_jobs]
                leaves_m, treedef_m = jax.tree.flatten(all_args_m)
                # Every non-host leaf must be the base key (true for all
                # current job shapes); anything else falls back silently.
                if all(
                    isinstance(l, np.ndarray) or l is base_key
                    for l in leaves_m
                ):
                    by_dt_m, order_m, layout_m, packed_m = (
                        _pack_host_leaves(leaves_m)
                    )
                    mono_key = _hashable_or_none(
                        (
                            "mono",
                            tuple(k for k, _, _ in mono_jobs),
                            layout_m,
                            rng_impl,
                        )
                    )
        if mono_key is not None:

            def _mono_fn(bk, *bufs):
                vals = _unpack_bufs(bufs, by_dt_m, order_m, layout_m)
                new_leaves = [
                    vals.get(i, bk) for i in range(len(leaves_m))
                ]
                out = {}
                for (_, fn, _), a in zip(
                    mono_jobs, jax.tree.unflatten(treedef_m, new_leaves)
                ):
                    out.update(fn(*a))
                return out

            mfn = _exec_cache_get(mono_key)
            if mfn is None:
                mfn = _exec_disk_get(mono_key)
                if mfn is not None:
                    _exec_cache_put(mono_key, mfn, disk=False)
            if mfn is not None:
                # Phase stamps land here; the downstream stamps are
                # setdefault so the mono timings aren't overwritten.
                last_profile["plan_s"] = _sp_plan.end()
                last_profile["compile_s"] = 0.0
                _sp = _telemetry.start_span(
                    "materialize.transfer", job="mono"
                )
                buf_dev = jax.device_put(packed_m)
                last_profile["transfer_s"] = _sp.end()
                _sp = _telemetry.start_span(
                    "materialize.execute", job="mono"
                )
                results.update(mfn(base_key, *buf_dev))
                if _profile_enabled():
                    jax.block_until_ready(list(results.values()))
                    rss = _rss_mb_now()
                    _sp.end(rss_mb=rss)
                    last_profile["jobs"].append(
                        ("mono", _sp.duration, rss)
                    )
                last_profile["exec_s"] = _sp.end()
                exec_cache_hits += 1
                _T_EXEC_HITS.add()
                # Everything executed; the sections below see empty work.
                jobs, class_jobs, shadow_jobs = [], [], []
            else:
                shadow_jobs.append(
                    (mono_key, _mono_fn, (base_key, *packed_m), None)
                )

        last_profile.setdefault("plan_s", _sp_plan.end())
        compiled: Dict[int, Any] = {}
        misses = []
        n_exec = len(jobs) + len(class_jobs)
        for i, (key, _, _, _) in enumerate(jobs + class_jobs):
            # Memory tier only here; the disk tier (deserialize + device
            # load) runs inside the pool below so loads overlap like
            # compiles do.
            hit = _exec_cache_get(key) if key is not None else None
            compiled[i] = hit
            if hit is None:
                misses.append(i)

        # Shadow jobs (the merged fillpack) ride the same pool — compiled
        # concurrently with the real misses, stored for future cached-cold
        # runs, never executed this run.  They do NOT count toward
        # had_compiles: a run whose every EXECUTED program was cached is
        # still a cache hit even while it seeds the merged blob.
        build_list = jobs + class_jobs + shadow_jobs
        misses += range(n_exec, len(build_list))
        had_compiles = False
        if misses:
            _sp_compile = _telemetry.start_span(
                "materialize.compile", n_programs=len(misses)
            )

            def _build(i):
                nonlocal had_compiles
                key, fn, args, osh = build_list[i]
                if key is not None:
                    cfn = _exec_disk_get(key)
                    if cfn is not None:
                        _exec_cache_put(key, cfn, disk=False)
                        return cfn
                if i < n_exec:
                    had_compiles = True
                jfn = (
                    jax.jit(fn, out_shardings=osh)
                    if osh is not None
                    else jax.jit(fn)
                )
                # Observatory scope per worker thread: the monitoring
                # listener attributes the backend compile precisely;
                # without monitoring, ensure_counted records the
                # lower+compile wall time instead — exactly once either
                # way.  (A persistent-cache hit compiles nothing and
                # deserializes in milliseconds; it still counts as a
                # program load, which is what the count family tracks.)
                import time as _time

                _t0 = _time.perf_counter()
                with _perf.program("materialize") as _sc:
                    cfn = jfn.lower(*args).compile()
                _sc.ensure_counted(_time.perf_counter() - _t0)
                _T_COMPILES.add()
                if key is not None:
                    _exec_cache_put(key, cfn)
                return cfn

            with cache_everything():
                if len(misses) == 1:
                    compiled[misses[0]] = _build(misses[0])
                else:
                    from concurrent.futures import ThreadPoolExecutor

                    with ThreadPoolExecutor(
                        min(len(misses), 16)
                    ) as pool:
                        for i, cfn in zip(
                            misses, pool.map(_build, misses)
                        ):
                            compiled[i] = cfn
            last_profile.setdefault("compile_s", _sp_compile.end())

        last_profile.setdefault("compile_s", 0.0)
        # Ship every job's host argument leaves in ONE transfer per dtype:
        # each host→device put has a fixed cost, and the ~70 tiny
        # index/fill arrays (a few KB total!) pay it ~70 times when
        # transferred one by one.  Pack per dtype on host, put once, and
        # unpack on device with a small exec-cached program (slice +
        # reshape is free for XLA).
        #
        # The argpack applies to single-device runs only.  Mesh jobs instead
        # get their host leaves explicitly placed as mesh-replicated
        # arrays (the elif below): Compiled.__call__ input-sharding
        # tolerance for committed single-device arrays against
        # mesh-lowered programs is version-dependent (advisor r4), so we
        # hand them the placement they were lowered for.
        all_args = [args for _, _, args, _ in jobs]
        if jobs and mesh is None:
            _sp_transfer = _telemetry.start_span("materialize.transfer")
            leaves, treedef = jax.tree.flatten(all_args)
            by_dtype, order, layout, packed = _pack_host_leaves(leaves)
            if packed:
                unpack_key = ("argpack", layout)
                ufn = _exec_cache_get(unpack_key)
                if ufn is None:
                    ufn = _exec_disk_get(unpack_key)
                    if ufn is not None:
                        _exec_cache_put(unpack_key, ufn, disk=False)
                if ufn is None:

                    def unpack(*bufs):
                        vals = _unpack_bufs(bufs, by_dtype, order, layout)
                        # dtype-major slot order — matches the consuming
                        # loop below AND executables cached by earlier
                        # versions of this layout key.
                        return tuple(
                            vals[i] for dt in order for i in by_dtype[dt]
                        )

                    with cache_everything():
                        ufn = jax.jit(unpack).lower(*packed).compile()
                    _exec_cache_put(unpack_key, ufn)
                unpacked = iter(ufn(*jax.device_put(packed)))
                for dt in order:
                    for i in by_dtype[dt]:
                        leaves[i] = next(unpacked)
            all_args = jax.tree.unflatten(treedef, leaves)
            last_profile.setdefault("transfer_s", _sp_transfer.end())
        elif jobs:
            # Mesh jobs: hand the executables explicitly mesh-replicated
            # inputs rather than raw host leaves (VERDICT item 8b — see
            # _replicate_mesh_args).
            _sp_transfer = _telemetry.start_span("materialize.transfer")
            all_args = _replicate_mesh_args(all_args, mesh)
            last_profile.setdefault("transfer_s", _sp_transfer.end())
        last_profile.setdefault("transfer_s", 0.0)
        _sp_exec = (
            _telemetry.start_span(
                "materialize.execute",
                n_jobs=len(jobs),
                n_classes=len(big_list),
            )
            if jobs or big_list
            else None
        )
        _prof = _profile_enabled()
        for i in range(len(jobs)):
            if _prof:
                key = jobs[i][0]
                label = (
                    key[0] if isinstance(key, tuple) and key else "rest"
                )
                _spj = _telemetry.start_span(
                    "materialize.job", label=label
                )
                res_i = compiled[i](*all_args[i])
                jax.block_until_ready(list(res_i.values()))
                rss = _rss_mb_now()
                _spj.end(rss_mb=rss)
                last_profile["jobs"].append((label, _spj.duration, rss))
            else:
                res_i = compiled[i](*all_args[i])
            results.update(res_i)
        # Big-fill classes: one dispatch per instance of the class's
        # compiled program (dispatches are cheap; compiles were O(classes)).
        _spb = (
            _telemetry.start_span("materialize.job", label="bigfillcls")
            if _prof and big_list
            else None
        )
        off = 0
        for j, sg in enumerate(big_list):
            cfn = compiled[len(jobs) + j]
            s0r, s1r = s_rep[j]
            for t, e in enumerate(sg["entries"]):
                results[e["name"]] = cfn(keys_rep[off + t], s0r[t], s1r[t])
            off += len(sg["entries"])
        if _spb is not None:
            jax.block_until_ready(
                [results[e["name"]] for sg in big_list for e in sg["entries"]]
            )
            rss = _rss_mb_now()
            _spb.end(rss_mb=rss)
            last_profile["jobs"].append(
                ("bigfillcls", _spb.duration, rss)
            )
        last_profile.setdefault(
            "exec_s", _sp_exec.end() if _sp_exec is not None else 0.0
        )
        if (jobs or class_jobs) and not had_compiles:
            exec_cache_hits += 1
            _T_EXEC_HITS.add()

    # Torch fallback for ops with no lowering: replay on host, transfer with
    # the planned sharding.  Per-tensor, so peak host RAM ≈ largest param.
    if unsupported:
        if not _fallback_torch:
            raise UnsupportedOpError(
                f"No JAX lowering for params: {[n for n, _ in unsupported]}"
            )
        from .deferred_init import materialize_tensor

        if _sp_plan.duration is None:
            # No jax-path planning closed the phase (every param is
            # unsupported): drop it BEFORE the fallback span starts, so
            # the fallback parents on materialize.module rather than on a
            # plan span the trace will never contain.
            _sp_plan.cancel()
        _T_TORCH_FALLBACK.add(len(unsupported))
        with _telemetry.span(
            "materialize.torch_fallback", n_params=len(unsupported)
        ):
            for name, fake in unsupported:
                real = materialize_tensor(fake, device="cpu")
                arr = jax.numpy.asarray(
                    real.detach().cpu().numpy(), dtype=target_dtypes[name]
                )
                if mesh is not None:
                    from jax.sharding import NamedSharding

                    arr = jax.device_put(
                        arr,
                        NamedSharding(
                            mesh, _resolve_spec(plan, name, fake, mesh)
                        ),
                    )
                results[name] = arr
    if _sp_plan.duration is None:
        # No jax-path planning happened (every param unsupported): the
        # plan phase never closed — drop it rather than record the whole
        # call under the wrong name.
        _sp_plan.cancel()
    _sp_call.end(n_params=len(results))
    _telemetry.emit_counters()
    return results
