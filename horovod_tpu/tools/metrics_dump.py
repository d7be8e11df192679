"""One-shot metrics snapshot of a LIVE job via the rendezvous server.

The workers push registry snapshots to the rendezvous KV every
``HOROVOD_METRICS_PUSH_SECS`` (core/state.py); the server aggregates them
at ``GET /metrics`` (runner/rendezvous.py).  This tool is the operator's
curl-with-a-brain: fetch the scrape, either raw (Prometheus text, exactly
what a Prometheus scraper would ingest) or pretty-printed per rank.

Usage::

    python -m horovod_tpu.tools.metrics_dump              # addr from env
    python -m horovod_tpu.tools.metrics_dump --addr 10.0.0.2 --port 41999
    python -m horovod_tpu.tools.metrics_dump --raw        # Prometheus text
    hvd-metrics-dump --json                               # raw snapshots
    hvd-metrics-dump --watch 2                            # re-scrape every 2s
    hvd-metrics-dump --watch 2 --rate                     # per-second deltas

Address defaults come from the launcher-propagated
``HOROVOD_GLOO_RENDEZVOUS_ADDR``/``PORT`` env, so running it on any job
host with the job's environment just works.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request
from typing import Optional, Sequence

from ..common import env as env_mod


def fetch(addr: str, port: int, fmt: str = "text",
          timeout: float = 5.0) -> str:
    suffix = "?format=json" if fmt == "json" else ""
    with urllib.request.urlopen(
            f"http://{addr}:{port}/metrics{suffix}", timeout=timeout) as r:
        return r.read().decode()


# Control-plane snapshots (docs/observability.md "Control-plane
# attribution"): the server folds its own registry into the scrape under
# rank="server", and in external mode the driver pushes rank="driver".
# Render them as a distinct section after the worker ranks.
_CONTROL_RANKS = frozenset({"server", "driver"})


def _order(snaps: dict):
    def key_fn(key):
        rank = str(snaps[key].get("rank", key))
        return (1, rank) if rank in _CONTROL_RANKS else (0, str(key))
    return sorted(snaps, key=key_fn)


def _header(snap: dict, key, suffix: str) -> str:
    rank = snap.get("rank", key)
    if str(rank) in _CONTROL_RANKS:
        return f"== control plane: {rank}{suffix} =="
    return f"== rank {rank}{suffix} =="


def _pretty(snaps: dict) -> str:
    out = []
    for key in _order(snaps):
        snap = snaps[key]
        out.append(_header(
            snap, key,
            f" (pushed at unix_ns={snap.get('ts_unix_ns', '?')})"))
        for kind in ("counters", "gauges"):
            for name in sorted(snap.get(kind, {})):
                out.append(f"  {name} = {snap[kind][name]}")
        for name in sorted(snap.get("histograms", {})):
            h = snap["histograms"][name]
            n = max(1, h.get("count", 0))
            out.append(f"  {name}: count={h.get('count', 0)} "
                       f"sum={h.get('sum', 0.0):.6g} "
                       f"mean={h.get('sum', 0.0) / n:.6g}")
    return "\n".join(out)


def _rates(prev: dict, cur: dict, dt: float) -> str:
    """Per-second counter deltas between two snapshot scrapes (gauges are
    levels, not rates — shown as their current value)."""
    out = []
    for key in _order(cur):
        snap = cur[key]
        before = prev.get(key, {})
        out.append(_header(snap, key, f" (Δ over {dt:.1f}s)"))
        prev_c = before.get("counters", {})
        for name in sorted(snap.get("counters", {})):
            d = snap["counters"][name] - prev_c.get(name, 0)
            if d:
                out.append(f"  {name} = +{d / dt:.6g}/s")
        for name in sorted(snap.get("gauges", {})):
            out.append(f"  {name} = {snap['gauges'][name]} (gauge)")
        prev_h = before.get("histograms", {})
        for name in sorted(snap.get("histograms", {})):
            h = snap["histograms"][name]
            p = prev_h.get(name, {})
            dc = h.get("count", 0) - p.get("count", 0)
            if dc:
                ds = h.get("sum", 0.0) - p.get("sum", 0.0)
                out.append(f"  {name}: +{dc / dt:.6g} obs/s "
                           f"mean={ds / dc:.6g}")
    return "\n".join(out)


def _render_once(addr: str, port: int, args,
                 prev: Optional[dict], dt: float) -> Optional[dict]:
    """One scrape + print; returns the parsed snapshots (None in raw
    mode, where rates don't apply)."""
    if args.raw:
        print(fetch(addr, port, "text"), end="")
        return None
    if args.json:
        text = fetch(addr, port, "json")
        print(text)
        return json.loads(text)
    snaps = json.loads(fetch(addr, port, "json"))
    if not snaps:
        print("metrics-dump: no rank has pushed a snapshot yet "
              "(HOROVOD_METRICS_PUSH_SECS=0, or the job just started)")
    elif args.rate and prev is not None:
        print(_rates(prev, snaps, dt))
    else:
        print(_pretty(snaps))
    return snaps


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="metrics-dump",
        description="one-shot cross-rank metrics snapshot of a live "
                    "horovod_tpu job (docs/observability.md)")
    ap.add_argument("--addr", default=None,
                    help="rendezvous server address (default: "
                         "HOROVOD_GLOO_RENDEZVOUS_ADDR)")
    ap.add_argument("--port", type=int, default=None,
                    help="rendezvous server port (default: "
                         "HOROVOD_GLOO_RENDEZVOUS_PORT)")
    ap.add_argument("--raw", action="store_true",
                    help="print the Prometheus text scrape verbatim")
    ap.add_argument("--json", action="store_true",
                    help="print the raw per-rank snapshot JSON")
    ap.add_argument("--watch", type=float, default=None, metavar="N",
                    help="re-scrape every N seconds until interrupted")
    ap.add_argument("--rate", action="store_true",
                    help="with --watch: print per-second counter deltas "
                         "between scrapes instead of absolute values")
    args = ap.parse_args(argv)
    if args.rate and not args.watch:
        ap.error("--rate requires --watch (rates need two scrapes)")

    addr = args.addr or env_mod.get_str(env_mod.HOROVOD_RENDEZVOUS_ADDR)
    port = args.port or env_mod.get_int(env_mod.HOROVOD_RENDEZVOUS_PORT, 0)
    if not addr or not port:
        print("metrics-dump: no rendezvous server (pass --addr/--port or "
              "run inside a job's environment)", file=sys.stderr)
        return 2
    prev: Optional[dict] = None
    t_prev = time.monotonic()
    while True:
        try:
            now = time.monotonic()
            prev = _render_once(addr, port, args, prev,
                                max(now - t_prev, 1e-9))
            t_prev = now
        except OSError as e:
            print(f"metrics-dump: scrape of {addr}:{port} failed: {e}",
                  file=sys.stderr)
            if not args.watch:
                return 1
        if not args.watch:
            return 0
        try:
            time.sleep(args.watch)
        except KeyboardInterrupt:
            return 0
        print(f"---- {time.strftime('%H:%M:%S')} ----")


if __name__ == "__main__":
    sys.exit(main())
