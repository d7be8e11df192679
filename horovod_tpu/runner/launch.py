"""``hvdrun`` — the launcher CLI (reference ``horovodrun``).

Reference: ``runner/launch.py:1-776`` — parse args, check hosts, start the
rendezvous server, compute slot assignments, export per-slot env, exec the
user command once per slot (ssh for remote hosts), stream output.

TPU-first differences: no mpirun/jsrun backends (the data plane is XLA, the
control plane our own TCP mesh), and single-host multi-chip needs no ssh at
all.  Remote hosts use plain ssh like the reference's gloo path
(``gloo_run.py:133-183``).

Usage::

    python -m horovod_tpu.runner.launch -np 4 python train.py
    hvdrun -np 8 -H host1:4,host2:4 python train.py
"""

from __future__ import annotations

import argparse
import os
import shlex
import signal
import subprocess
import sys
import threading
from typing import Dict, List, Optional

from ..common import env as env_mod
from . import config_parser, tpu_topology
from .hosts import SlotInfo, get_host_assignments, parse_host_files, parse_hosts
from .rendezvous import RendezvousServer


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a horovod_tpu job (reference: horovodrun).")
    p.add_argument("-np", "--num-proc", type=int, required=True,
                   help="number of worker processes")
    p.add_argument("-H", "--hosts", default=None,
                   help='host list like "h1:4,h2:4"; default localhost:np')
    p.add_argument("--hostfile", default=None,
                   help="file with one 'host slots=N' per line")
    p.add_argument("--output-filename", default=None,
                   help="tee each rank's output into <dir>/rank.N/stdout|stderr")
    p.add_argument("--verbose", "-v", action="count", default=0)
    p.add_argument("--start-timeout", type=int, default=None,
                   help="abort unless every worker reaches hvd.init() within "
                        "this many seconds (default: wait forever — "
                        "pre-init work like dataset download may legitimately "
                        "take long)")
    p.add_argument("--config-file", default=None,
                   help="YAML file whose keys mirror the CLI flags")
    # runtime tunables (become HOROVOD_* env; reference launch.py:304-475)
    p.add_argument("--fusion-threshold-mb", type=float, default=None)
    p.add_argument("--cycle-time-ms", type=float, default=None)
    p.add_argument("--cache-capacity", type=int, default=None)
    p.add_argument("--timeline-filename", default=None)
    p.add_argument("--timeline-mark-cycles", action="store_true", default=False)
    p.add_argument("--no-stall-check", action="store_true", default=False)
    p.add_argument("--stall-check-warning-time-seconds", type=int, default=None)
    p.add_argument("--stall-check-shutdown-time-seconds", type=int, default=None)
    p.add_argument("--autotune", action="store_true", default=False)
    p.add_argument("--autotune-log-file", default=None)
    p.add_argument("--autotune-warmup-samples", type=int, default=None)
    p.add_argument("--autotune-steps-per-sample", type=int, default=None)
    p.add_argument("--log-level", default=None,
                   choices=["trace", "debug", "info", "warning", "error"])
    p.add_argument("--mesh-axes", default=None,
                   help='TPU mesh axes, e.g. "dp:4,tp:2"')
    p.add_argument("--no-tpu-chip-binding", action="store_true", default=False,
                   help="don't export per-slot TPU_VISIBLE_CHIPS/"
                        "TPU_PROCESS_* (default: exported on TPU VMs when "
                        "a host runs more than one slot)")
    p.add_argument("--data-plane", default=None, choices=["xla", "tcp", "auto"],
                   help="eager collectives on the device mesh (xla, strict), "
                        "the host ring (tcp), or xla when it comes up "
                        "(auto); default: xla when the launcher binds one "
                        "chip per process, else tcp")
    # elastic (wired by horovod_tpu.elastic)
    p.add_argument("--min-np", type=int, default=None)
    p.add_argument("--max-np", type=int, default=None)
    p.add_argument("--host-discovery-script", default=None)
    p.add_argument("--host-discovery", default=None,
                   choices=["script", "tpu-metadata"],
                   help="elastic discovery source: 'script' (use "
                        "--host-discovery-script) or 'tpu-metadata' (poll "
                        "GCE preemption/maintenance notices for the hosts "
                        "in -H/--hostfile; see "
                        "horovod_tpu.elastic.tpu_metadata)")
    p.add_argument("--tpu-metadata-url", default=None,
                   help="URL template for --host-discovery tpu-metadata "
                        "with a {host} placeholder (default: the per-host "
                        "relay on port 8677)")
    p.add_argument("--reset-limit", type=int, default=None)
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="the training command to run on every slot")
    return p


def _slot_env(slot: SlotInfo, rdv_addr: str, rdv_port: int,
              extra: Dict[str, str],
              tpu_chip_binding: Optional[bool] = None,
              job_host_slots: Optional[List] = None) -> Dict[str, str]:
    env = os.environ.copy()
    env.update(slot.to_env())
    env.update({
        env_mod.HOROVOD_RENDEZVOUS_ADDR: rdv_addr,
        env_mod.HOROVOD_RENDEZVOUS_PORT: str(rdv_port),
    })
    job_host_slots = job_host_slots or [("localhost", slot.local_size)]
    if tpu_chip_binding is None:
        tpu_chip_binding = binds_chips(job_host_slots)
    if tpu_chip_binding:
        # One process per chip (reference role: per-slot CUDA_VISIBLE_DEVICES
        # construction in gloo_run.py:65-76; here libtpu needs the full
        # TPU_PROCESS_* tiling, see tpu_topology.slot_tpu_env).
        env.update(tpu_topology.slot_tpu_env(
            slot.rank, slot.local_rank, job_host_slots))
    env.update(extra)
    # Make horovod_tpu importable in workers regardless of their cwd /
    # script location (the reference relies on pip-installation instead).
    pkg_parent = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    parts = env.get("PYTHONPATH", "").split(os.pathsep)
    if pkg_parent not in parts:
        env["PYTHONPATH"] = os.pathsep.join([pkg_parent] + [p for p in parts if p])
    return env


def binds_chips(job_host_slots: List) -> bool:
    """Whether the launcher gives each slot one chip, decided so every
    launch path (static, programmatic run()) binds consistently; only the
    static CLI exposes an opt-out.  The decision is job-global (ANY host
    multi-slot → every slot binds): a single-slot host must still join the
    slice-wide process tiling the other ranks' TPU_PROCESS_ADDRESSES count.
    """
    return tpu_topology.running_on_tpu_vm() and \
        any(n > 1 for _, n in job_host_slots)


def spawn_worker(slot: SlotInfo, command: List[str],
                 env: Dict[str, str]) -> subprocess.Popen:
    """Spawn one slot's worker: local exec or ssh; remote workers receive
    the job's HMAC key over stdin (never argv — see _ssh_command).

    Fault site ``worker.spawn`` fires per spawn attempt (static AND
    elastic respawns route through here), matched on the SLOT's rank —
    e.g. ``worker.spawn:rank=2:action=raise`` fails exactly rank 2's
    launch."""
    from ..common import faults

    if faults.ACTIVE:
        faults.inject("worker.spawn", rank=slot.rank)
    local = _is_local(slot.hostname)
    cmd = command if local else _ssh_command(slot, command, env)
    proc = subprocess.Popen(
        cmd, env=env, text=True, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, stdin=None if local else subprocess.PIPE)
    if not local:
        proc.stdin.write(env[env_mod.HOROVOD_SECRET_KEY] + "\n")
        proc.stdin.flush()
        proc.stdin.close()
    return proc


def host_slots_of(slots: List[SlotInfo]) -> List:
    """Ordered (hostname, n_slots) pairs of a job's slot list — the
    slice-wide shape every rank must agree on for TPU process tiling."""
    out: List = []
    for s in slots:
        if out and out[-1][0] == s.hostname:
            out[-1] = (s.hostname, out[-1][1] + 1)
        elif any(h == s.hostname for h, _ in out):
            raise ValueError("slot list not host-contiguous")
        else:
            out.append((s.hostname, 1))
    return out


def _is_local(hostname: str) -> bool:
    # All of 127.0.0.0/8 is this machine (loopback aliases let tests and
    # single-node runs present several distinct "hosts" without sshd,
    # mirroring the reference's loopback-ssh CI trick).
    if hostname.startswith("127."):
        return True
    import socket

    return hostname in ("localhost", "127.0.0.1", socket.gethostname())


def _ssh_command(slot: SlotInfo, command: List[str],
                 env: Dict[str, str]) -> List[str]:
    """Remote slot: carry HOROVOD_*/PYTHON* env through ssh explicitly
    (reference ``gloo_run.py:133-183`` builds the same kind of line)."""
    # Forward only keys WE set for this slot: HOROVOD_* plus the per-slot
    # chip-binding keys from slot_tpu_env, and where the operator placed
    # the compile cache.  Never blanket-forward ambient
    # TPU_*/JAX_* from the launcher VM — e.g. its own TPU_WORKER_ID=0
    # would clobber every remote host's identity and break slice init.
    # The job's HMAC key travels over ssh STDIN, not the command line —
    # argv is world-readable via /proc on every host it touches.
    exports = " ".join(
        f"{k}={shlex.quote(v)}" for k, v in env.items()
        if (k.startswith("HOROVOD_") and k != env_mod.HOROVOD_SECRET_KEY)
        or k in ("PYTHONPATH", "PATH", "JAX_COMPILATION_CACHE_DIR")
        or k in tpu_topology.SLOT_ENV_KEYS)
    remote = "IFS= read -r HOROVOD_SECRET_KEY && export HOROVOD_SECRET_KEY" \
        f" && cd {shlex.quote(os.getcwd())} && env {exports} " + \
        " ".join(shlex.quote(c) for c in command)
    return ["ssh", "-o", "StrictHostKeyChecking=no", slot.hostname, remote]


class _OutputPump(threading.Thread):
    """Forward a worker stream line-by-line with a rank prefix, optionally
    teeing into --output-filename/rank.N/ files (reference
    ``gloo_run.py:150-163``)."""

    def __init__(self, stream, sink, prefix: str, tee_path: Optional[str],
                 name: str = "hvd-pump"):
        super().__init__(daemon=True, name=name)
        self._stream = stream
        self._sink = sink
        self._prefix = prefix
        self._tee = open(tee_path, "w") if tee_path else None
        self.start()

    def run(self):
        try:
            for line in self._stream:
                self._sink.write(f"{self._prefix}{line}")
                self._sink.flush()
                if self._tee:
                    self._tee.write(line)
                    self._tee.flush()
        finally:
            if self._tee:
                self._tee.close()


def _pick_coordinator_port(probe: bool) -> int:
    """A port for rank 0's jax.distributed coordinator, below the Linux
    ephemeral range (32768+) to dodge transient clashes; when the
    coordinator host is this machine, bind-probe for availability."""
    import random
    import socket

    for _ in range(32):
        port = random.randint(20000, 32000)
        if not probe:
            return port
        s = socket.socket()
        try:
            s.bind(("0.0.0.0", port))
            return port
        except OSError:
            continue
        finally:
            s.close()
    raise RuntimeError("no free port found for the jax coordinator")


def launch_job(args, command: List[str]) -> int:
    hosts_str = args.hosts
    if args.hostfile:
        hosts_str = parse_host_files(args.hostfile)
    if not hosts_str:
        # On a TPU pod-slice VM the runtime env describes the slice; an
        # explicit -H always wins (reference: the launcher's host list is
        # user-supplied; TPU slices are self-describing).
        hosts_str = tpu_topology.discover() or f"localhost:{args.num_proc}"
        if args.verbose and "," in hosts_str:
            print(f"hvdrun: discovered TPU slice hosts: {hosts_str}",
                  file=sys.stderr)
    slots = get_host_assignments(parse_hosts(hosts_str), args.num_proc)
    job_host_slots = host_slots_of(slots)
    tpu_chip_binding = (not args.no_tpu_chip_binding
                        and binds_chips(job_host_slots))

    # Per-job HMAC key for every service-plane RPC (reference secret.py:36).
    from ..common import secret as secret_mod

    job_secret = secret_mod.ensure_job_secret()
    # Survivable shape (docs/control_plane.md), same contract as the
    # elastic launcher: with HOROVOD_RENDEZVOUS_EXTERNAL=host:port the
    # static launcher attaches to a supervisor-managed journaled server
    # instead of owning one, so a plain -np job also rides out a
    # rendezvous restart (worker store clients reattach per call).
    # Both sides must share HOROVOD_SECRET_KEY.
    ext_host = None
    external = env_mod.get_str(env_mod.HOROVOD_RENDEZVOUS_EXTERNAL)
    if external:
        from .rendezvous import ExternalRendezvous

        ext_host, _, ext_port = external.rpartition(":")
        if not ext_host or not ext_port.isdigit():
            raise SystemExit(
                "hvdrun: HOROVOD_RENDEZVOUS_EXTERNAL must be host:port, "
                f"got {external!r}")
        server = ExternalRendezvous(ext_host, int(ext_port))
        port = server.port
    else:
        server = RendezvousServer(bind_addr="0.0.0.0",
                                  job_secret=job_secret.encode())
        port = server.start()
    server.publish_slots([{
        "hostname": s.hostname, "rank": s.rank, "local_rank": s.local_rank,
        "cross_rank": s.cross_rank, "size": s.size,
        "local_size": s.local_size, "cross_size": s.cross_size,
    } for s in slots])

    from ..transport.tcp import _default_advertise_addr

    any_remote = any(not _is_local(s.hostname) for s in slots)
    rdv_addr = _default_advertise_addr() if any_remote else "127.0.0.1"
    # Workers talk to the external server's host when attached; rdv_addr
    # stays the local advertise address (the jax coordinator below runs
    # in rank 0's process regardless of where the KV store lives).
    rdv_host = ext_host if external else rdv_addr
    extra = config_parser.env_from_args(args)
    data_plane = (args.data_plane or "").lower()
    if not data_plane and tpu_chip_binding:
        # One process per chip means device gradients: reduce them on the
        # chips (strict — a plane that cannot come up is an error), not by
        # copying each one to the host and round the TCP ring.
        data_plane = extra[env_mod.HOROVOD_DATA_PLANE] = "xla"
    if data_plane in ("xla", "auto"):
        # The jax.distributed coordination service runs inside rank 0's
        # process; every worker needs its address before first device use.
        coord_host = slots[0].hostname
        local_coord = _is_local(coord_host)
        if local_coord:
            coord_host = rdv_addr
        extra[env_mod.HOROVOD_JAX_COORDINATOR] = \
            f"{coord_host}:{_pick_coordinator_port(probe=local_coord)}"

    procs: List[subprocess.Popen] = []
    pumps: List[_OutputPump] = []
    try:
        for slot in slots:
            env = _slot_env(slot, rdv_host, port, extra,
                            tpu_chip_binding=tpu_chip_binding,
                            job_host_slots=job_host_slots)
            proc = spawn_worker(slot, command, env)
            procs.append(proc)
            if args.output_filename:
                rank_dir = os.path.join(args.output_filename,
                                        f"rank.{slot.rank}")
                os.makedirs(rank_dir, exist_ok=True)
                out_t = os.path.join(rank_dir, "stdout")
                err_t = os.path.join(rank_dir, "stderr")
            else:
                out_t = err_t = None
            prefix = f"[{slot.rank}]<stdout>: " if args.verbose else ""
            eprefix = f"[{slot.rank}]<stderr>: " if args.verbose else ""
            pumps.append(_OutputPump(proc.stdout, sys.stdout, prefix, out_t,
                                     name=f"hvd-pump-r{slot.rank}-out"))
            pumps.append(_OutputPump(proc.stderr, sys.stderr, eprefix, err_t,
                                     name=f"hvd-pump-r{slot.rank}-err"))

        # Poll ALL workers (not ordered wait): a crash in any rank must
        # tear the job down even while earlier ranks hang in collectives.
        exit_code: Optional[int] = None
        import time as _time

        # --start-timeout (reference launch.py/--start-timeout): every
        # worker marks itself in the rendezvous store when its transport
        # comes up; abort the job if any rank hasn't by the deadline.
        # Single-worker jobs skip the store entirely, so exempt np=1.
        start_deadline = (_time.monotonic() + args.start_timeout
                          if args.start_timeout and len(slots) > 1 else None)
        unstarted = {s.rank for s in slots} if start_deadline else set()

        while True:
            codes = [p.poll() for p in procs]
            failed = [c for c in codes if c not in (None, 0)]
            if failed and exit_code is None:
                exit_code = failed[0]
                # One dead worker hangs the rest (collectives block) —
                # terminate the job like the reference launcher does.
                for p in procs:
                    if p.poll() is None:
                        p.send_signal(signal.SIGTERM)
            if unstarted and exit_code is None:
                unstarted = {r for r in unstarted
                             if server.get("worker_started", str(r)) is None}
                if unstarted and _time.monotonic() > start_deadline:
                    print(f"hvdrun: ranks {sorted(unstarted)} failed to start "
                          f"within --start-timeout={args.start_timeout}s; "
                          "aborting", file=sys.stderr)
                    exit_code = 1
                    for p in procs:
                        if p.poll() is None:
                            p.send_signal(signal.SIGTERM)
            if all(c is not None for c in codes):
                if exit_code is None:
                    exit_code = 0
                break
            _time.sleep(0.1)
        for pump in pumps:
            pump.join(timeout=5)
        return exit_code
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        # Workers that died mid-step (SIGKILL, OOM) can leave their
        # shared-memory ring segments behind in /dev/shm — the creator
        # never reached ShmMesh.close().  Segment names embed the
        # creator's pid, so sweep by the pids we just reaped.
        from ..transport.shm import sweep_dead_segments
        sweep_dead_segments([p.pid for p in procs])
        server.stop()


def run_commandline(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    config_parser.apply_config_file(args, args.config_file)
    command = args.command
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        print("hvdrun: no command given", file=sys.stderr)
        return 2
    if args.host_discovery_script or args.host_discovery \
            or (args.min_np is not None):
        try:
            from ..elastic.launcher import launch_elastic_job
        except ImportError as e:
            print(f"hvdrun: elastic mode unavailable: {e}", file=sys.stderr)
            return 2
        return launch_elastic_job(args, command)
    return launch_job(args, command)


def main() -> None:
    sys.exit(run_commandline())


if __name__ == "__main__":
    main()
