"""Elastic launch: wires ElasticDriver into the ``hvdrun`` CLI.

Reference: ``runner/gloo_run.py:287-336`` (``launch_gloo_elastic``) — start
the rendezvous server, build discovery from the script (or fixed hosts),
spawn a worker per slot with elastic env, monitor exits, and finish when
the surviving workers complete.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List

from ..common import env as env_mod
from ..common.logging_util import get_logger
from ..runner import config_parser
from ..runner.hosts import SlotInfo, parse_host_files, parse_hosts
from ..runner.launch import (
    _is_local,
    _slot_env,
    _OutputPump,
    spawn_worker,
)
from ..runner.rendezvous import RendezvousServer
from ..transport.shm import sweep_dead_segments
from .discovery import FixedHosts, HostDiscoveryScript, HostManager
from .driver import ElasticDriver
from .registration import FAILURE

log = get_logger("horovod_tpu.elastic.launcher")


def launch_elastic_job(args, command: List[str]) -> int:
    mode = args.host_discovery or (
        "script" if args.host_discovery_script else None)
    hosts_str = args.hosts
    if args.hostfile:
        hosts_str = parse_host_files(args.hostfile)
    if mode == "tpu-metadata":
        from .tpu_metadata import TpuMetadataDiscovery

        if not hosts_str:
            raise SystemExit(
                "hvdrun: --host-discovery tpu-metadata needs the slice "
                "membership via -H/--hostfile (discovery decides which of "
                "those hosts are currently healthy)")
        discovery = TpuMetadataDiscovery(
            parse_hosts(hosts_str),
            url_template=getattr(args, "tpu_metadata_url", None))
    elif mode == "script":
        if not args.host_discovery_script:
            raise SystemExit("hvdrun: --host-discovery script needs "
                             "--host-discovery-script")
        discovery = HostDiscoveryScript(args.host_discovery_script)
    else:
        discovery = FixedHosts(parse_hosts(
            hosts_str or f"localhost:{args.num_proc}"))

    from ..common import secret as secret_mod

    job_secret = secret_mod.ensure_job_secret()
    # Survivable deployment (docs/control_plane.md): with
    # HOROVOD_RENDEZVOUS_EXTERNAL=host:port the launcher attaches to a
    # supervisor-managed, journaled rendezvous server instead of owning
    # one — a SIGKILL'd server restarts and replays, and the driver's
    # partitioned mode rides out the outage without epoch churn.  Both
    # sides must share HOROVOD_SECRET_KEY (ensure_job_secret generated
    # one just now if the operator didn't set it — set it explicitly for
    # external mode or the signatures won't match).
    external = env_mod.get_str(env_mod.HOROVOD_RENDEZVOUS_EXTERNAL)
    if external:
        from ..runner.rendezvous import ExternalRendezvous

        host, _, p = external.rpartition(":")
        if not host or not p.isdigit():
            raise SystemExit(
                "hvdrun: HOROVOD_RENDEZVOUS_EXTERNAL must be host:port, "
                f"got {external!r}")
        server = ExternalRendezvous(host, int(p))
        port = server.port
    else:
        server = RendezvousServer(bind_addr="0.0.0.0",
                                  job_secret=job_secret.encode())
        port = server.start()
    min_np = args.min_np or args.num_proc
    # --start-timeout in elastic mode bounds slot assembly (reference:
    # elastic settings use start_timeout for wait_for_available_slots).
    driver_kwargs = {}
    if getattr(args, "start_timeout", None):
        driver_kwargs["timeout"] = args.start_timeout
    driver = ElasticDriver(
        server, HostManager(discovery), min_np=min_np, max_np=args.max_np,
        reset_limit=args.reset_limit, **driver_kwargs)
    if external:
        # A restarted launcher re-adopts a previous incarnation's epoch
        # and live workers from the journaled store (no-op on a fresh
        # journal).  Use a per-job journal dir: stale state from an OLD
        # job would be re-adopted too.
        driver.recover_from_store()

    from ..transport.tcp import _default_advertise_addr

    rdv_addr = _default_advertise_addr()
    extra = config_parser.env_from_args(args)
    extra[env_mod.HOROVOD_ELASTIC] = "1"
    if args.reset_limit:
        extra[env_mod.HOROVOD_ELASTIC_RESET_LIMIT] = str(args.reset_limit)

    # Driver lifecycle trace (docs/observability.md "Control-plane
    # attribution"): when the operator asked for timelines, the launcher
    # writes <path>.driver with the reserved driver pid — DRV_* tick/
    # spawn spans and CHURN_EVENT windows, hvd-control-path's anchor.
    # Same-host as the in-process server (offset 0); external servers
    # are assumed clock-synced like any worker host without an estimate.
    driver_timeline = None
    timeline_path = env_mod.get_str(env_mod.HOROVOD_TIMELINE)
    if timeline_path:
        from ..core.timeline import DRIVER_TRACE_PID, Timeline

        try:
            driver_timeline = Timeline(
                f"{timeline_path}.driver", rank=DRIVER_TRACE_PID,
                clock_offset_ns=0, process_name="elastic driver")
        except OSError as e:
            log.warning("cannot write driver timeline %s.driver: %s",
                        timeline_path, e)

    procs: Dict[str, subprocess.Popen] = {}
    pumps: List[_OutputPump] = []
    lock = threading.Lock()

    def create_worker(slot: SlotInfo, epoch: int) -> None:
        # No per-chip binding in elastic mode: libtpu reads TPU_PROCESS_*
        # once at process start, but elastic epochs respawn only NEW
        # identities — survivors would keep a stale tiling and the slice
        # could never re-form.  Elastic TPU jobs therefore run one process
        # per host (the host's default libtpu ownership of all its chips),
        # which also matches how preemption works: whole hosts come & go.
        # External mode: every worker dials the external server's address
        # (it need not be on this host); otherwise the launcher's own.
        slot_rdv_addr = server.addr if external else (
            rdv_addr if not _is_local(slot.hostname) else "127.0.0.1")
        env = _slot_env(slot, slot_rdv_addr, port, extra,
                        tpu_chip_binding=False)
        env[env_mod.HOROVOD_EPOCH] = str(epoch)
        identity = f"{slot.hostname}:{slot.local_rank}"
        with lock:
            previous = procs.get(identity)
        if previous is not None and previous.poll() is None:
            # The driver judged this identity dead (its lease ran out)
            # and its process is not: one process an identity, or the two
            # contend for one rank of the new world.
            log.warning("worker %s is respawned while its process %d "
                        "lives; killing that one", identity, previous.pid)
            previous.kill()
        proc = spawn_worker(slot, command, env)
        with lock:
            procs[identity] = proc
        prefix = f"[{slot.rank}]<stdout>: " if args.verbose else ""
        eprefix = f"[{slot.rank}]<stderr>: " if args.verbose else ""
        pumps.append(_OutputPump(proc.stdout, sys.stdout, prefix, None,
                                 name=f"hvd-pump-r{slot.rank}-out"))
        pumps.append(_OutputPump(proc.stderr, sys.stderr, eprefix, None,
                                 name=f"hvd-pump-r{slot.rank}-err"))
        threading.Thread(target=_monitor, args=(identity, slot, proc),
                         name=f"hvd-elastic-mon-{identity}",
                         daemon=True).start()

    def _monitor(identity: str, slot: SlotInfo, proc: subprocess.Popen):
        code = proc.wait()
        with lock:
            current = procs.get(identity) is proc
            if current:
                procs.pop(identity, None)
        log.info("worker %s exited with %d", identity, code)
        if code != 0:
            # A crashed worker never ran ShmMesh.close(); reclaim its
            # /dev/shm ring segments before the next epoch respawns here.
            sweep_dead_segments([proc.pid])
        if current:
            driver.record_worker_exit(slot, code)
        # else: superseded by a respawn of its identity (create_worker
        # killed it); its exit says nothing of the process that holds the
        # identity now.

    try:
        driver.start(create_worker)
        while True:
            time.sleep(0.5)
            with lock:
                alive = len(procs)
            failures = driver._registry.count(FAILURE)
            if driver.job_ended:
                # The driver's judgment (job_end_steps).  A process still
                # up is a respawn in flight with no world left to join:
                # the teardown below cancels it.
                if alive:
                    log.info("cancelling %d respawn(s) in flight", alive)
                return 0
            if alive == 0 and failures and \
                    driver.hosts.total_slots() < min_np:
                log.error("all capacity lost (%d failures)", failures)
                return 1
            if driver.stopped_error:
                log.error("elastic driver stopped: %s", driver.stopped_error)
                return 1
    finally:
        driver.stop()
        with lock:
            for proc in procs.values():
                if proc.poll() is None:
                    proc.send_signal(signal.SIGTERM)
        time.sleep(0.2)
        with lock:
            for proc in procs.values():
                if proc.poll() is None:
                    proc.kill()
            sweep_dead_segments([proc.pid for proc in procs.values()])
        server.stop()
        if driver_timeline is not None:
            driver_timeline.close()
