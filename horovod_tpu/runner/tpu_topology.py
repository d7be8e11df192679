"""TPU pod-slice topology discovery and per-chip process visibility.

Role of the reference's per-slot env construction (``runner/gloo_run.py:65-76``
builds ``HOROVOD_RANK``/``CUDA_VISIBLE_DEVICES``-style worker env): on TPU the
launcher must additionally carve the host's chips into one-process-per-chip
visibility windows, because libtpu defaults to a single process owning every
local chip.  Without this, ``hvdrun -np 4`` on a 4-chip TPU VM would have all
four workers contend for chip 0.

Two jobs live here:

1. **Discovery** — on a Cloud TPU VM the runtime env already carries the
   slice shape (``TPU_ACCELERATOR_TYPE`` like ``v5litepod-16``,
   ``TPU_WORKER_HOSTNAMES``, ``TPU_WORKER_ID``).  ``discover()`` turns that
   into an ``hvdrun -H``-style host string so ``hvdrun -np 16`` with no
   ``-H`` flag does the right thing on a pod slice.
2. **Per-slot visibility env** — ``slot_tpu_env()`` produces the
   ``TPU_VISIBLE_*`` / ``TPU_PROCESS_*`` variables that give each worker
   process exactly one chip and tell libtpu how the processes tile the
   physical torus.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Optional, Tuple

# Chips per host for the generations we know; fall back to 4 (the most
# common TPU VM host shape).  TensorCores-per-chip matters only for
# translating accelerator-type suffixes into chip counts.
_GEN_INFO = {
    # generation: (tensorcores_per_chip, chips_per_host)
    "v2": (2, 4),
    "v3": (2, 4),
    "v4": (2, 4),
    "v5litepod": (1, 4),   # v5e: suffix counts chips directly
    "v5p": (2, 4),
    "v6e": (1, 4),
}

# Base port for libtpu's inter-process coordination sockets; any free
# range works as long as every process agrees.
_TPU_PORT_BASE = 8476

# Exactly the keys slot_tpu_env emits — the per-slot set the launcher may
# forward over ssh (ambient TPU_* from the launcher VM must never be).
SLOT_ENV_KEYS = frozenset({
    "TPU_VISIBLE_CHIPS", "TPU_VISIBLE_DEVICES",
    "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS",
    "TPU_PROCESS_ADDRESSES", "TPU_PROCESS_PORT", "CLOUD_TPU_TASK_ID",
})


def parse_accelerator_type(accel: str) -> Optional[Tuple[int, int]]:
    """``"v5litepod-16"`` → (total_chips, chips_per_host); None if unknown."""
    m = re.match(r"^(v\d+[a-z]*)-(\d+)$", accel.strip())
    if not m:
        return None
    gen, count = m.group(1), int(m.group(2))
    cores_per_chip, chips_per_host = _GEN_INFO.get(gen, (1, 4))
    total_chips = max(1, count // cores_per_chip)
    return total_chips, min(chips_per_host, total_chips)


def discover() -> Optional[str]:
    """Return an ``-H``-style host string for the current pod slice, or None
    when not on a TPU VM (or the env doesn't describe one).

    Reads the env the Cloud TPU runtime exports to every worker VM; no
    metadata-server call (works offline, and the env is authoritative for
    the slice the VM belongs to).
    """
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    accel = os.environ.get("TPU_ACCELERATOR_TYPE", "")
    if not hostnames:
        return None
    hosts = [h.strip() for h in hostnames.split(",") if h.strip()]
    parsed = parse_accelerator_type(accel) if accel else None
    if parsed:
        total_chips, chips_per_host = parsed
        # A single-host slice may have fewer chips than a full host.
        if len(hosts) == 1:
            chips_per_host = total_chips
    else:
        chips_per_host = 4
    return ",".join(f"{h}:{chips_per_host}" for h in hosts)


def _process_bounds(n: int) -> str:
    """Factor ``n`` local single-chip processes onto a 2-D grid, most-square
    first (libtpu wants the process tiling of the physical torus; for
    single-host sub-slices a 2-D factorization matches v4/v5e host shapes:
    4 chips → ``2,2,1``, 8 chips → ``2,4,1``)."""
    best = (1, n)
    for x in range(1, int(n ** 0.5) + 1):
        if n % x == 0:
            best = (x, n // x)
    return f"{best[0]},{best[1]},1"


def slot_tpu_env(rank: int, local_rank: int,
                 host_slots: List[Tuple[str, int]]) -> Dict[str, str]:
    """Per-process chip-visibility env for one slot.

    ``TPU_VISIBLE_CHIPS``/``TPU_VISIBLE_DEVICES`` (old and new libtpu
    spellings) pin the process to one chip; ``TPU_CHIPS_PER_PROCESS_BOUNDS``
    declares the 1-chip window; ``TPU_PROCESS_BOUNDS`` the **slice-wide**
    process grid; ``TPU_PROCESS_ADDRESSES``/``TPU_PROCESS_PORT`` the
    coordination sockets libtpu uses to stitch the single-chip processes
    back into one logical slice.

    ``host_slots`` is the in-order (hostname, n_slots) list of the whole
    job, so every rank derives the identical slice-global tiling even when
    ``-np`` doesn't fill the last host.  All values are slice-global:
    ``CLOUD_TPU_TASK_ID`` is the global rank — per-host grids would make
    libtpu stitch each host into an independent slice and cross-host
    collectives could never form.
    """
    addresses = ",".join(
        f"{h}:{_TPU_PORT_BASE + i}"
        for h, n in host_slots for i in range(n))
    total = sum(n for _, n in host_slots)
    return {
        "TPU_VISIBLE_CHIPS": str(local_rank),
        "TPU_VISIBLE_DEVICES": str(local_rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": _process_bounds(total),
        "TPU_PROCESS_ADDRESSES": addresses,
        "TPU_PROCESS_PORT": str(_TPU_PORT_BASE + local_rank),
        "CLOUD_TPU_TASK_ID": str(rank),
    }


def running_on_tpu_vm() -> bool:
    """True when this machine exposes TPU chips: the Cloud TPU runtime env,
    or chip device nodes — ``/dev/accel<N>`` on v2–v4 hosts,
    ``/dev/vfio/<N>`` on v5e and later."""
    if os.environ.get("TPU_ACCELERATOR_TYPE") or \
            os.environ.get("TPU_WORKER_HOSTNAMES"):
        return True
    try:
        if any(name.startswith("accel") for name in os.listdir("/dev")):
            return True
        return any(name.isdigit() for name in os.listdir("/dev/vfio"))
    except OSError:
        return False
