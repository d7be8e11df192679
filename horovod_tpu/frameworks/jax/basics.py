"""Lifecycle + topology queries.

Role of the reference's ``horovod/common/basics.py:25-258`` (``HorovodBasics``:
the ctypes bridge to ``horovod_init/_shutdown/_rank/_size/...``,
``operations.cc:750-938``).  No ctypes needed here — the runtime is
in-process — but the API surface and semantics match.
"""

from __future__ import annotations

from typing import Optional

from ...common.exceptions import HorovodInternalError
from ...common.topology import ProcessTopology
from ...core.state import global_state, reset_global_state
from ...transport.store import Store


def _maybe_init_jax_distributed(topology: Optional[ProcessTopology]) -> None:
    """When the XLA data plane is requested for a multi-process world, bring
    up jax's multi-controller runtime (the ``ncclCommInitRank`` analog)
    BEFORE any jax device is touched.  The launcher distributes the
    coordinator address via ``HOROVOD_JAX_COORDINATOR``."""
    from ...backend import xla as xla_backend
    from ...common import env as env_mod
    from ...common.topology import from_env

    plane = xla_backend.data_plane_requested()
    if plane not in ("xla", "auto"):
        return
    topo = topology or from_env()
    if topo.size <= 1:
        return
    import jax

    if xla_backend.jax_distributed_initialized():
        return
    coord = env_mod.get_str(env_mod.HOROVOD_JAX_COORDINATOR)
    if not coord and env_mod.get_bool(env_mod.HOROVOD_ELASTIC):
        # Elastic jobs negotiate the coordinator through the rendezvous
        # store (epoch-scoped — the launcher cannot pin one for the whole
        # job because the coordinator host itself may be replaced).
        from ...elastic.state import negotiate_jax_coordinator

        coord = negotiate_jax_coordinator(topo)
    if not coord:
        if plane == "xla":
            # An explicit request must fail loudly, not degrade silently.
            raise RuntimeError(
                "HOROVOD_DATA_PLANE=xla but HOROVOD_JAX_COORDINATOR is "
                "unset (launch with `hvdrun --data-plane xla`)")
        return  # auto: quietly stay on the TCP plane
    try:
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=topo.size,
                                   process_id=topo.rank)
    except Exception as e:  # noqa: BLE001
        if plane == "xla":
            raise HorovodInternalError(
                f"jax.distributed init failed for the requested XLA data "
                f"plane: {e}") from e
        from ...common.logging_util import get_logger

        get_logger("horovod_tpu.basics").warning(
            "jax.distributed init failed (%s); eager collectives will use "
            "the TCP data plane", e)


def init(store: Optional[Store] = None,
         topology: Optional[ProcessTopology] = None) -> None:
    """Initialize the runtime: topology from the launcher env (or given
    explicitly), TCP mesh rendezvous when size > 1, background thread up.

    Reference: ``hvd.init()`` → ``horovod_init`` (``operations.cc:752``)."""
    from ...common.compile_cache import configure_compile_cache

    configure_compile_cache()
    _maybe_init_jax_distributed(topology)
    global_state().initialize(store=store, topology=topology)
    from ...common import env as env_mod

    if env_mod.get_bool(env_mod.HOROVOD_ELASTIC):
        # Register the notification endpoint as early as possible so the
        # driver can reach us from the first discovery tick.
        from ...elastic.state import notification_manager

        notification_manager.start()


def shutdown() -> None:
    global_state().shutdown()


def is_initialized() -> bool:
    return global_state().initialized.is_set()


def _topo() -> ProcessTopology:
    state = global_state()
    if not state.initialized.is_set() or state.topo is None:
        raise HorovodInternalError(
            "horovod_tpu has not been initialized; call hvd.init() first.")
    return state.topo


def rank() -> int:
    return _topo().rank


def size() -> int:
    return _topo().size


def local_rank() -> int:
    return _topo().local_rank


def local_size() -> int:
    return _topo().local_size


def cross_rank() -> int:
    return _topo().cross_rank


def cross_size() -> int:
    return _topo().cross_size


def is_homogeneous() -> bool:
    return _topo().is_homogeneous


def start_timeline(file_path: str, mark_cycles: bool = False) -> None:
    """Runtime-togglable timeline (reference ``operations.cc:780-806``).

    Like the ``HOROVOD_TIMELINE`` env path, EVERY rank writes its own
    trace with ``pid = rank`` — rank 0 at ``file_path``, rank r at
    ``file_path.rank<r>`` so ranks sharing a filesystem never clobber one
    file — and ``tools/trace_merge.py`` folds them into one cross-rank
    view.  The coordinator-side negotiation lanes exist only on rank 0
    (the message table lives there, reference ``operations.cc:424-432``)."""
    from ...core.timeline import (
        Timeline,
        estimate_server_clock_offset_ns,
        rank_trace_path,
    )

    state = global_state()
    rank = state.topo.rank if state.topo is not None else 0
    if state.timeline is not None:
        state.timeline.close()
    state.timeline = Timeline(
        rank_trace_path(file_path, rank), mark_cycles=mark_cycles,
        rank=rank, clock_offset_ns=estimate_server_clock_offset_ns())
    if state.controller is not None and rank == 0:
        state.controller.timeline = state.timeline


def stop_timeline() -> None:
    state = global_state()
    if state.timeline is not None:
        state.timeline.close()
        state.timeline = None
    if state.controller is not None:
        state.controller.timeline = None


# ---------------------------------------------------------------------------
# capability predicates (reference basics.py:160-260) — ported scripts use
# these as guards (`if hvd.nccl_built(): ...`).  Truthful answers for a
# TPU-native build: the GPU/MPI-era backends don't exist here, the XLA
# device plane and the self-contained TCP fabric do.
# ---------------------------------------------------------------------------


def mpi_threads_supported() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def mpi_built() -> bool:
    return False


def gloo_enabled() -> bool:
    # The TCP mesh plays the Gloo role and is always compiled in.
    return True


def gloo_built() -> bool:
    return True


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def xla_built() -> bool:
    """TPU-native addition: the XLA device data plane is available."""
    return True


def xla_enabled() -> bool:
    """True when the eager device plane is active in this process."""
    from ...backend import xla as xla_backend

    return xla_backend.context().ready


def _internal_reset() -> None:
    """Full teardown + fresh state (elastic re-init path and tests)."""
    reset_global_state()
