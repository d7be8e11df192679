"""Print the shape of an ``.xplane.pb``: planes, lines, event counts and the
first events of each line.  For looking at one trace by hand before trusting
``trace_reduce.py`` on it.

    python3 chip_bench/tools/dump_trace.py <file.xplane.pb> [events per line]
"""

from __future__ import annotations

import sys


def main():
    from jax.profiler import ProfileData

    data = ProfileData.from_file(sys.argv[1])
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 8
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:n]:
                print(f"    {e.name!r} start_ns={e.start_ns:.0f} "
                      f"dur_ns={e.duration_ns:.0f}")


if __name__ == "__main__":
    main()
