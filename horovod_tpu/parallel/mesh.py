"""Device-mesh construction.

The reference keeps three communicators — GLOBAL, LOCAL (intra-node), CROSS
(one rank per node) — split at ``mpi_context.cc:147-156`` and uses LOCAL for
the fast fabric and CROSS for the slow one (`nccl_operations.cc:194-405`,
the hierarchical allreduce).  On TPU the same idea is expressed as mesh
*axes*: inner axes are laid out over ICI (fast), the outermost axis over DCN
(slow, across pod slices).  XLA then picks hierarchical collective
algorithms automatically — the NCCLHierarchical pattern is what the XLA
runtime already does for multi-slice meshes (SURVEY §5.8).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# Canonical axis names, outermost (slowest fabric) first.
AXIS_DATA = "data"      # data parallelism (the reference's one strategy)
AXIS_PIPE = "pipe"      # pipeline stages
AXIS_EXPERT = "expert"  # expert parallelism (MoE)
AXIS_SEQ = "seq"        # sequence/context parallelism (ring / Ulysses)
AXIS_MODEL = "model"    # tensor (operator) parallelism

# Mesh-axis order: data outermost so DP rides DCN across slices while
# model/seq/pipe axes stay inside a slice on ICI.
_AXIS_ORDER = (AXIS_DATA, AXIS_PIPE, AXIS_EXPERT, AXIS_SEQ, AXIS_MODEL)


@dataclass(frozen=True)
class MeshSpec:
    """Logical parallelism degrees. ``-1`` on ``data`` means "use whatever
    devices remain" (like the reference sizing DP to world size)."""

    data: int = -1
    pipe: int = 1
    expert: int = 1
    seq: int = 1
    model: int = 1
    # Axes that should be laid out contiguously on the fastest fabric first.
    # Default: rightmost axes innermost (model closest on ICI).
    axis_order: Tuple[str, ...] = field(default=_AXIS_ORDER)

    def degrees(self) -> Dict[str, int]:
        return {
            AXIS_DATA: self.data,
            AXIS_PIPE: self.pipe,
            AXIS_EXPERT: self.expert,
            AXIS_SEQ: self.seq,
            AXIS_MODEL: self.model,
        }


def mesh_shape_for(spec: MeshSpec, n_devices: int) -> Tuple[Tuple[str, int], ...]:
    """Resolve a MeshSpec against a device count: fills in ``data=-1`` and
    validates divisibility (the analog of the launcher's slot math,
    reference ``common/util/hosts.py:get_host_assignments``)."""
    degrees = spec.degrees()
    fixed = 1
    for name, d in degrees.items():
        if d != -1:
            if d < 1:
                raise ValueError(f"axis {name!r} must be >=1 or -1, got {d}")
            fixed *= d
    free = [name for name, d in degrees.items() if d == -1]
    if len(free) > 1:
        raise ValueError(f"at most one axis may be -1, got {free}")
    if free:
        if n_devices % fixed != 0:
            raise ValueError(
                f"{n_devices} devices not divisible by fixed axes product {fixed}")
        degrees[free[0]] = n_devices // fixed
    elif fixed != n_devices:
        raise ValueError(
            f"mesh spec wants {fixed} devices but {n_devices} are available")
    return tuple((name, degrees[name]) for name in spec.axis_order)


def build_mesh(spec: Optional[MeshSpec] = None,
               devices: Optional[Sequence] = None,
               contiguous_submeshes: bool = True):
    """Build a :class:`jax.sharding.Mesh` from a spec.

    Device order: ``jax.devices()`` enumerates chips so that nearby indices
    are nearby on ICI (same host first).  Reshaping that flat order into the
    axis grid with the *innermost* axes varying fastest puts model/seq
    collectives on neighboring chips — the LOCAL-communicator role — while
    the outermost (data) axis spans hosts/slices — the CROSS role.
    """
    import jax

    if devices is None:
        devices = jax.devices()
    shape = mesh_shape_for(spec or MeshSpec(), len(devices))
    names = tuple(name for name, _ in shape)
    dims = tuple(d for _, d in shape)
    grid = np.asarray(devices, dtype=object).reshape(dims)
    return jax.sharding.Mesh(grid, names)


def data_parallel_mesh(devices: Optional[Sequence] = None):
    """Pure-DP mesh over all devices — the reference's world communicator."""
    return build_mesh(MeshSpec(data=-1), devices=devices)


def local_mesh_axes(mesh) -> List[str]:
    """Axes of size > 1 (useful for building full psum axis tuples)."""
    return [name for name, size in zip(mesh.axis_names, mesh.devices.shape)
            if size > 1]
