"""Host-level fan-in for worker control-plane pushes.

At np ranks the rendezvous store sees np lease renewals and np metrics
snapshots per push period even though colocated ranks share a host and a
filesystem.  This module makes control traffic scale with HOSTS, not
ranks (ROADMAP item 2's tree-shaped fan-in, built on the batched
``POST /batch`` frame): one rank per host — the **aggregator**, always
``local_rank == 0``, no election protocol — forwards every colocated
rank's ops in a single batched transaction.

Mechanism (filesystem spool, no new sockets):

- every peer rank serializes its period's ops (the same tuples
  ``Store.batch`` takes, encoded with the wire codec from
  ``transport/store.py``) into a per-rank spool file under a directory
  derived from the store endpoint + host identity
  (``transport/select.py``), written atomically via tmp+rename;
- the aggregator, each period, reads the spools, concatenates the ops of
  every file whose **content changed** since its last forward, appends
  its own ops, and sends ONE ``store.batch``; it then touches a
  heartbeat file;
- a spool whose bytes did not change is NOT re-forwarded: a dead rank's
  stale lease must age out at the store, not be renewed on its behalf
  forever (lease values embed a renewal counter, so a live rank's spool
  always differs period-to-period).

Failure behavior (the part the chaos test pins): peers check the
aggregator heartbeat before trusting the spool — if it is older than
``HEARTBEAT_STALE_PERIODS`` push periods (or absent, e.g. before the
aggregator's first period or after its death), ``submit`` returns False
and the caller pushes its ops DIRECTLY.  Aggregator death therefore
degrades to the pre-fan-in per-rank traffic within ~1.5 periods; it
never silences a host, and the only lease that expires is the dead
aggregator's own (docs/control_plane.md "Host-level fan-in").
"""

from __future__ import annotations

import hashlib
import os
import shutil
import tempfile
import time
from typing import Dict, List, Optional

from ..common import env as env_mod
from ..common.logging_util import get_logger
from ..transport.select import host_identity
from ..transport.store import Store, decode_batch_ops, encode_batch_ops

log = get_logger("horovod_tpu.elastic.fanin")

#: Heartbeat older than this many push periods ⇒ aggregator presumed
#: dead ⇒ peers push directly.  1.5 keeps the degrade window well under
#: the default lease timeout (3 push periods) with one period of slack
#: for scheduler jitter.
HEARTBEAT_STALE_PERIODS = 1.5

_HEARTBEAT = "aggregator.hb"


def _spool_root(store: Store, fanin_dir: str) -> str:
    """Spool directory shared by this job's ranks on this host: keyed by
    the store endpoint (job-unique — two jobs on one box must not merge
    spools) and the host identity (boot id and the host's index in the
    job — two "hosts" simulated on one box each get their own spool, as
    they get their own negotiation heartbeat; with one spool between
    them their ``rank-N.ops`` overwrite each other and a live rank's
    lease runs out)."""
    endpoint = getattr(store, "_base", "in-process")
    cross_rank = env_mod.get_int(env_mod.HOROVOD_CROSS_RANK, 0)
    token = hashlib.sha1(
        f"{endpoint}|{host_identity(cross_rank)}".encode()).hexdigest()[:16]
    return os.path.join(fanin_dir, f"hvd-fanin-{token}")


class HostFanin:
    """One per worker process; see module docstring.  ``submit`` is
    called from the metrics-push thread only (single-threaded per
    instance)."""

    def __init__(self, store: Store, local_rank: int, period: float,
                 spool_dir: Optional[str] = None):
        self._store = store
        self._local_rank = local_rank
        self._period = period
        fanin_dir = env_mod.get_str(env_mod.HOROVOD_FANIN_DIR) or "/dev/shm"
        self._dir = spool_dir or _spool_root(store, fanin_dir)
        os.makedirs(self._dir, exist_ok=True)
        self._is_aggregator = local_rank == 0
        # Aggregator: last-forwarded bytes per spool file, the
        # change-detection state that keeps dead ranks' leases honest.
        self._forwarded: Dict[str, bytes] = {}

    # -- peer side -----------------------------------------------------

    def _heartbeat_fresh(self) -> bool:
        try:
            age = time.time() - os.stat(
                os.path.join(self._dir, _HEARTBEAT)).st_mtime
        except OSError:
            return False
        return age < HEARTBEAT_STALE_PERIODS * self._period

    def _write_spool(self, ops: List[tuple]) -> None:
        path = os.path.join(self._dir, f"rank-{self._local_rank}.ops")
        fd, tmp = tempfile.mkstemp(dir=self._dir,
                                   prefix=f".rank-{self._local_rank}.")
        try:
            with os.fdopen(fd, "wb") as fh:
                fh.write(encode_batch_ops(ops))
            os.replace(tmp, path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    # -- aggregator side -----------------------------------------------

    def _collect_peers(self) -> List[tuple]:
        merged: List[tuple] = []
        try:
            names = sorted(os.listdir(self._dir))
        except OSError:
            return merged
        own = f"rank-{self._local_rank}.ops"
        for name in names:
            if not name.startswith("rank-") or not name.endswith(".ops") \
                    or name == own:
                continue
            path = os.path.join(self._dir, name)
            try:
                with open(path, "rb") as fh:
                    blob = fh.read()
            except OSError:
                continue
            if self._forwarded.get(name) == blob:
                continue  # stale spool: let its lease age out
            try:
                ops = decode_batch_ops(blob)
            except (ValueError, KeyError, TypeError):
                continue  # torn/corrupt spool: next period's rewrite wins
            self._forwarded[name] = blob
            merged.extend(ops)
        return merged

    def _touch_heartbeat(self) -> None:
        hb = os.path.join(self._dir, _HEARTBEAT)
        try:
            with open(hb, "a"):
                os.utime(hb, None)
        except OSError as e:
            log.warning("fan-in heartbeat write failed (%s); peers will "
                        "degrade to direct pushes", e)

    # -- entry point ---------------------------------------------------

    def submit(self, ops: List[tuple]) -> bool:
        """Hand this period's ops to the fan-in.  Returns True when the
        ops were delivered (aggregator) or spooled under a live
        aggregator (peer); False means the caller must push directly.
        Aggregator store errors propagate — the caller's outage
        accounting owns them."""
        if self._is_aggregator:
            merged = self._collect_peers() + list(ops)
            self._store.batch(merged)
            # Heartbeat AFTER the successful forward: a wedged store
            # must not keep advertising a live aggregator while spools
            # pile up undelivered.
            self._touch_heartbeat()
            return True
        try:
            self._write_spool(ops)
        except OSError as e:
            log.warning("fan-in spool write failed (%s); pushing "
                        "directly", e)
            return False
        return self._heartbeat_fresh()

    def close(self) -> None:
        """The job's own teardown: the aggregator takes the spool with
        it.  Left behind, it is found by whatever later job's store gets
        the same endpoint, whose aggregator forwards the dead job's
        leases and metrics as its own ranks'.  A peer that pushes after
        this finds no directory to spool in and pushes directly."""
        if self._is_aggregator:
            shutil.rmtree(self._dir, ignore_errors=True)


def maybe_create(store: Store, period: float) -> Optional[HostFanin]:
    """The gate (``HOROVOD_FANIN``): "1" forces fan-in on, "0" off,
    "auto" (default) enables it when the host actually has colocated
    ranks AND batching is on (fan-in forwards via ``/batch``; against an
    old server the per-op fallback would erase the win)."""
    mode = (env_mod.get_str(env_mod.HOROVOD_FANIN) or "auto").lower()
    if mode == "0":
        return None
    if mode == "auto":
        local_size = env_mod.get_int(env_mod.HOROVOD_LOCAL_SIZE, 1)
        batching = env_mod.get_bool(env_mod.HOROVOD_RENDEZVOUS_BATCH, True)
        if local_size <= 1 or not batching:
            return None
    local_rank = env_mod.get_int(env_mod.HOROVOD_LOCAL_RANK, 0)
    try:
        return HostFanin(store, local_rank, period)
    except OSError as e:
        log.warning("fan-in disabled: spool dir unavailable (%s)", e)
        return None
