"""Multi-host runtime: process-group init + DCN-aware hybrid meshes.

The reference's multi-node story is ``torch.distributed.init_process_group``
(NCCL/Gloo rendezvous) plus ``dist.new_subgroups()`` for the intra-node /
inter-node split (slowmo_comm.py:8-27).  The TPU-native equivalents:

* :func:`initialize` — one call per host process, wrapping
  ``jax.distributed.initialize`` (coordinator rendezvous; on Cloud TPU /
  GKE every argument is auto-detected from the environment, matching the
  reference's env-var init method).  After it returns, ``jax.devices()``
  is the *global* device set and every jit/collective in this framework is
  automatically multi-host SPMD — there is no separate multi-host code
  path anywhere else in the package.
* :func:`make_hybrid_mesh` — meshes spanning several pod slices: each
  axis's extent is split into an ICI factor (within a slice) and a DCN
  factor (across slices), DCN-major, so only the axes you place on DCN
  (SlowMo's ``dp`` averaging axis, classically) ever cross the data-center
  network, and everything else rides ICI.  This is the mesh-construction
  recipe of the scaling playbook: pick the mesh, let XLA route the
  collectives.

Single-host development needs none of this — :func:`make_mesh` over the
local devices is the whole story — and both functions degrade to that case.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

from .mesh import MeshSpec, make_mesh

__all__ = [
    "ProcessInfo",
    "any_flag",
    "any_flags",
    "initialize",
    "make_hybrid_mesh",
]

_initialized = False


@dataclass(frozen=True)
class ProcessInfo:
    process_index: int
    process_count: int
    local_device_count: int
    global_device_count: int


def world_info() -> ProcessInfo:
    import jax

    return ProcessInfo(
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        local_device_count=jax.local_device_count(),
        global_device_count=jax.device_count(),
    )


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    local_device_ids: Optional[Sequence[int]] = None,
) -> ProcessInfo:
    """Join the multi-host process group (init_process_group analog).

    Call once per host process *before* any other JAX API.  With no
    arguments, every parameter is auto-detected on Cloud TPU/GKE (the
    reference's env-var rendezvous, torch.distributed "env://").  Explicit
    arguments serve bare-metal/CPU rendezvous:
    ``initialize("10.0.0.1:8476", num_processes=4, process_id=rank)``.

    Idempotent: a second call (or a call in an already-initialized runtime)
    returns the current :class:`ProcessInfo` instead of raising.
    """
    global _initialized
    import jax

    # Adopt a runtime initialized by an outer launcher/framework.
    if not (_initialized or jax.distributed.is_initialized()):
        kwargs = {}
        if coordinator_address is not None:
            kwargs["coordinator_address"] = coordinator_address
        if num_processes is not None:
            kwargs["num_processes"] = num_processes
        if process_id is not None:
            kwargs["process_id"] = process_id
        if local_device_ids is not None:
            kwargs["local_device_ids"] = list(local_device_ids)
        jax.distributed.initialize(**kwargs)
    _initialized = True
    return world_info()


def any_flag(local: bool) -> bool:
    """Agree on a host-local boolean across all hosts: True anywhere →
    True everywhere.  Single-flag convenience over :func:`any_flags`."""
    return any_flags((local,))[0]


def any_flags(local: "Sequence[bool]") -> tuple:
    """Agree on a vector of host-local booleans across all hosts, in ONE
    collective: position i of the result is True iff any host passed
    True at position i.

    The preemption/exit protocol's collective (see
    :mod:`torchdistx_tpu.resilience.preemption`): the scheduler may
    SIGTERM hosts at different instants, and per-host data streams may
    exhaust at different steps, but a resumable checkpoint needs every
    host to stop at the SAME step — so ``fit()`` folds its exit flags
    (preemption requested, data exhausted) through this small
    all-reduce (an element-wise max over processes) at each step
    boundary before acting on either.

    Degrades to the local flags in a single-process runtime (the common
    dev/test case — no collective, no cost).  Must be called by every
    process at the same point in the program, like any collective.
    """
    import jax

    if jax.process_count() == 1:
        return tuple(bool(x) for x in local)
    import numpy as np
    from jax.experimental import multihost_utils

    gathered = multihost_utils.process_allgather(
        np.asarray(list(local), dtype=np.int32)
    )
    agreed = np.asarray(gathered).reshape(-1, len(list(local))).max(axis=0)
    return tuple(bool(x) for x in agreed)


def _degenerate_cpu_slices(devices) -> bool:
    """True when every device reports the SAME ``slice_index`` on a CPU
    backend — metadata that carries no DCN structure (multi-process CPU
    backends report slice 0 everywhere).  On real accelerators a uniform
    slice_index is genuine single-slice topology and must NOT be treated
    as degenerate, so a caller requesting more DCN granules than the
    topology has fails loudly instead of silently relabeling an ICI
    boundary as DCN.  Shared by :func:`_slice_granules` and
    :func:`make_hybrid_mesh` so the two paths can never disagree."""
    slice_keys = {getattr(d, "slice_index", None) for d in devices}
    return len(slice_keys) == 1 and all(
        getattr(d, "platform", None) == "cpu" for d in devices
    )


def _slice_granules(devices) -> list:
    """Group devices into DCN granules (pod slices / hosts).

    Real TPU devices carry ``slice_index``; grouping falls back to
    ``process_index`` (one granule per host) and finally to a single
    granule.  Granule order is the sorted key order, so every process
    builds the identical mesh.

    Reachability from :func:`make_hybrid_mesh`: only the degenerate-CPU
    and missing-``slice_index`` branches arrive here (real slice metadata
    takes ``create_hybrid_device_mesh`` up there), so the slice-keyed
    branch below serves direct callers and tests.
    """
    # All-or-nothing key domain, with the SAME degeneracy rule as
    # make_hybrid_mesh (:func:`_degenerate_cpu_slices`): mixing
    # slice_index with process_index fallbacks would interleave unrelated
    # id spaces in the sorted granule order; degenerate CPU metadata falls
    # through to process_index (one granule per host).
    slice_keys = [getattr(d, "slice_index", None) for d in devices]
    if all(k is not None for k in slice_keys) and not _degenerate_cpu_slices(
        devices
    ):
        keys = slice_keys
    else:
        keys = [getattr(d, "process_index", 0) for d in devices]
    granules: dict = {}
    for key, d in zip(keys, devices):
        granules.setdefault(key, []).append(d)
    return [granules[k] for k in sorted(granules)]


def make_hybrid_mesh(
    ici: MeshSpec,
    dcn: MeshSpec,
    *,
    devices: Optional[Sequence] = None,
):
    """Build a mesh over multiple slices: ``axis = dcn_factor × ici_factor``.

    ``ici`` shapes each slice's devices; ``dcn`` spans slices.  Every axis
    is DCN-major (the slower network varies the outer index), so a
    ``P("dp")``-sharded collective with ``dcn=MeshSpec(dp=n_slices)``
    crosses DCN exactly ``log`` once while fsdp/tp collectives stay inside
    a slice — the SlowMo intra/inter split on TPU interconnect.

    Falls back to :func:`make_mesh` when ``dcn`` is trivial.  Devices with
    real ``slice_index`` metadata (TPU pods) are placed by
    ``mesh_utils.create_hybrid_device_mesh`` (ICI-topology-aware; genuine
    topology errors propagate); otherwise granules assemble by
    ``process_index`` or a contiguous split (virtual/CPU meshes — the
    test rig).
    """
    import jax
    import numpy as np

    if devices is None:
        devices = jax.devices()
    if dcn.size == 1:
        return make_mesh(ici, devices=devices)

    # Canonical axis order with per-axis (dcn, ici) factors.
    from .mesh import AXIS_ORDER

    names, ici_sizes, dcn_sizes = [], [], []
    for name in AXIS_ORDER:
        i = getattr(ici, name)
        d = getattr(dcn, name)
        if i > 1 or d > 1:
            names.append(name)
            ici_sizes.append(i)
            dcn_sizes.append(d)
    total = int(np.prod(ici_sizes)) * int(np.prod(dcn_sizes))
    if total != len(devices):
        raise ValueError(
            f"Hybrid mesh ici={ici_sizes} × dcn={dcn_sizes} needs {total} "
            f"devices, got {len(devices)}."
        )

    from jax.sharding import Mesh

    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    # Degenerate CPU slice metadata (see _degenerate_cpu_slices) takes the
    # granule fallback below (grouped by process_index).  Real accelerators
    # keep the topology-aware path even with one slice, so a genuine
    # mismatch (dcn extent 2 on a single-slice pod) still raises instead
    # of silently relabeling an ICI boundary as DCN.
    if None not in slice_ids and not _degenerate_cpu_slices(devices):
        # Real slice metadata (TPU pods): use jax's slice- and
        # ICI-topology-aware placement, and let genuine topology errors
        # (unmappable ici factors, wrong dcn extent) propagate instead of
        # degrading to a metadata-blind layout.
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_hybrid_device_mesh(
            tuple(ici_sizes), tuple(dcn_sizes), devices=list(devices)
        )
        return Mesh(dev_array, tuple(names))

    granules = _slice_granules(list(devices))
    n_slices = int(np.prod(dcn_sizes))
    per_slice = int(np.prod(ici_sizes))
    if len(granules) == 1 and n_slices > 1:
        # No granule metadata at all (a flat virtual device list — the CPU
        # test rig): split contiguously.
        flat = granules[0]
        granules = [
            flat[i * per_slice : (i + 1) * per_slice] for i in range(n_slices)
        ]
    elif len(granules) != n_slices:
        # Real metadata that contradicts the requested DCN extent must NOT
        # degrade to a contiguous split — that would silently lay ICI axes
        # across hosts/DCN.
        raise ValueError(
            f"Requested {n_slices} DCN granule(s) but the devices form "
            f"{len(granules)} (by slice_index/process_index); adjust the "
            "dcn spec to match the topology."
        )
    if any(len(g) != per_slice for g in granules):
        raise ValueError(
            f"Each slice must contribute {per_slice} devices; got "
            f"{[len(g) for g in granules]}."
        )

    k = len(names)
    arr = np.array(
        [np.asarray(g, dtype=object).reshape(tuple(ici_sizes)) for g in granules],
        dtype=object,
    ).reshape(tuple(dcn_sizes) + tuple(ici_sizes))
    # (dcn_0..dcn_k, ici_0..ici_k) → per-axis (dcn_i, ici_i) pairs, then
    # merge each pair: DCN-major within every named axis.
    perm = [x for i in range(k) for x in (i, k + i)]
    arr = arr.transpose(perm).reshape(
        tuple(d * i for d, i in zip(dcn_sizes, ici_sizes))
    )
    return Mesh(arr, tuple(names))
