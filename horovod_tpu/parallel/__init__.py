"""SPMD mesh parallelism — the TPU fast path.

The reference (huyutuo/horovod 0.20.3) is a data-parallel allreduce engine
whose data plane is NCCL/MPI (`horovod/common/ops/`, SURVEY §2.3).  On TPU
the XLA runtime plays NCCL's role: collectives are compiled into the program
and ride ICI within a slice / DCN across slices.  This package is therefore
*the* performance path of horovod_tpu:

- :mod:`.mesh` — device-mesh construction mirroring the reference's
  GLOBAL/LOCAL/CROSS communicator split (`mpi_context.cc:147-156`) as mesh
  axes;
- :mod:`.collectives` — jit-path wrappers over ``lax.psum`` /
  ``all_gather`` / ``psum_scatter`` / ``all_to_all`` / ``ppermute``, the
  XLA equivalents of the reference's MPI/NCCL op chain;
- :mod:`.grad_sync` — the SPMD analog of ``DistributedOptimizer``'s
  allreduce-on-gradients;
- :mod:`.ring_attention` — ring (blockwise) attention sequence parallelism;
- :mod:`.ulysses` — all-to-all (DeepSpeed-Ulysses-style) sequence
  parallelism built on the alltoall primitive the reference exposes raw
  (`operations.cc:1081-1142`);
- :mod:`.pipeline` — pipeline parallelism over a ``pipe`` mesh axis;
- :mod:`.moe` — expert parallelism (gating + all_to_all dispatch/combine)
  and the dropless top-k expert layer (sort + grouped matmul).

Beyond-parity scope (TP/PP/SP/EP) is deliberate: on TPU these fall out of
the same mesh machinery that gives data parallelism, and the build target
treats long-context + distributed as first-class.
"""

from .mesh import (  # noqa: F401
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_MODEL,
    AXIS_PIPE,
    AXIS_SEQ,
    MeshSpec,
    build_mesh,
    data_parallel_mesh,
    local_mesh_axes,
    mesh_shape_for,
)
from .collectives import (  # noqa: F401
    allgather,
    allreduce,
    alltoall,
    barrier_value,
    broadcast,
    ppermute_ring,
    reduce_scatter,
)
from .grad_sync import allreduce_gradients, cross_replica_mean  # noqa: F401
from .sharding import (  # noqa: F401
    batch_sharding,
    named_sharding,
    replicate,
    shard_batch,
    shard_map_fn,
)
from .ring_attention import ring_attention  # noqa: F401
from .ulysses import ulysses_attention  # noqa: F401
from .pipeline import pipeline_apply  # noqa: F401
from .moe import moe_dispatch_combine, moe_ffn  # noqa: F401
