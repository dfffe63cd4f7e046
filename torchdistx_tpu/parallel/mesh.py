"""Device-mesh construction over ICI/DCN.

The reference consumes `torch.distributed` process groups: NCCL/Gloo
transport, `dist.new_subgroups()` for intra-node groups, the default world
group for inter-node collectives (slowmo_comm.py:8-27).  The TPU-native
communication substrate is the `jax.sharding.Mesh`: named axes over the
device topology, with XLA inserting collectives that ride ICI within a pod
slice and DCN across slices.  The subgroup notion maps to mesh sub-axes; no
transport code is needed at all (SURVEY.md §2.3).

Conventions used throughout this framework:

* ``"dp"``   — data parallel (SlowMo's *inter-node* averaging axis; DCN-major)
* ``"fsdp"`` — parameter/optimizer sharding (ZeRO-style; usually the larger
  ICI axis)
* ``"tp"``   — tensor parallel (innermost, fastest ICI axis)
* ``"sp"``   — sequence/context parallel for ring attention (aliases "tp" on
  small meshes)
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence, Tuple


# Canonical DCN-major → ICI-minor axis order, shared by every mesh builder
# (make_mesh, distributed.make_hybrid_mesh).
AXIS_ORDER: Tuple[str, ...] = ("dp", "pp", "fsdp", "tp", "sp", "ep")


@dataclass(frozen=True)
class MeshSpec:
    """Named mesh shape, e.g. ``MeshSpec(dp=2, fsdp=2, tp=2)``."""

    dp: int = 1
    pp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    ep: int = 1

    def axes(self) -> Tuple[Tuple[str, int], ...]:
        return tuple(
            (name, size)
            for name, size in (
                (name, getattr(self, name)) for name in AXIS_ORDER
            )
            if size > 1
        ) or (("dp", 1),)

    @property
    def size(self) -> int:
        n = 1
        for _, s in self.axes():
            n *= s
        return n


def make_mesh(
    spec: Optional[MeshSpec] = None,
    *,
    devices: Optional[Sequence] = None,
    axis_names: Optional[Sequence[str]] = None,
    shape: Optional[Sequence[int]] = None,
):
    """Build a ``jax.sharding.Mesh``.

    With a :class:`MeshSpec`, axes are laid out DCN-major → ICI-minor ("dp"
    outermost, "tp" innermost) so tensor-parallel collectives ride the
    fastest ICI links and only the periodic SlowMo averaging crosses "dp"
    (the reference's intra-node/inter-node split, slowmo_comm.py:24-27,
    mapped onto the TPU interconnect hierarchy).

    Uses ``mesh_utils.create_device_mesh``: ICI-topology-aware ordering on
    TPU devices (an error there is raised, not papered over with an
    arbitrary order), a plain reshape on virtual/CPU devices.
    """
    import jax
    import numpy as np

    if devices is None:
        devices = jax.devices()
    if spec is not None:
        names = [n for n, _ in spec.axes()]
        sizes = [s for _, s in spec.axes()]
    else:
        names = list(axis_names or ("dp",))
        sizes = list(shape or (len(devices),))
    n = int(np.prod(sizes))
    if n != len(devices):
        raise ValueError(
            f"Mesh of shape {dict(zip(names, sizes))} needs {n} devices, "
            f"got {len(devices)}."
        )
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    dev_array = mesh_utils.create_device_mesh(
        tuple(sizes), devices=list(devices)
    )

    return Mesh(dev_array, tuple(names))
