"""jax binding — the default framework flavor.

``import horovod_tpu as hvd`` resolves here: lifecycle, eager collectives,
distributed optimizer and parameter/object broadcast utilities, mirroring
the reference's ``horovod.torch``/``horovod.tensorflow`` surfaces
(``torch/__init__.py``, ``tensorflow/__init__.py``).
"""

from .basics import (  # noqa: F401
    ccl_built,
    cross_rank,
    cross_size,
    cuda_built,
    ddl_built,
    gloo_built,
    gloo_enabled,
    init,
    is_homogeneous,
    is_initialized,
    local_rank,
    local_size,
    mpi_built,
    mpi_enabled,
    mpi_threads_supported,
    nccl_built,
    rank,
    rocm_built,
    shutdown,
    size,
    start_timeline,
    stop_timeline,
    xla_built,
    xla_enabled,
)
from .ops import (  # noqa: F401
    Adasum,
    Average,
    Sum,
    allgather,
    allgather_async,
    allreduce,
    allreduce_async,
    alltoall,
    alltoall_async,
    barrier,
    broadcast,
    broadcast_async,
    join,
    poll,
    synchronize,
)
from .compression import Compression  # noqa: F401
from .functions import (  # noqa: F401
    allgather_object,
    broadcast_object,
    broadcast_optimizer_state,
    broadcast_parameters,
)
from .optimizer import (  # noqa: F401
    DistributedAdasumOptimizer,
    DistributedOptimizer,
    distributed_value_and_grad,
)
from .wfbp import (  # noqa: F401
    PROCESS_AXIS,
    OverlappedTrainStep,
    make_overlapped_train_step,
)
from .sync_batch_norm import SyncBatchNorm, SyncBatchNormalization  # noqa: F401
from ... import elastic  # noqa: F401  (hvd.elastic.run / hvd.elastic.JaxState)
