"""Print what a profiler trace holds: its planes, their lines, event
counts, the busiest event names and the statistics events carry.  For
reading a trace by hand before writing a reduction against it.

    python3 bench/tools/trace_dump.py <trace dir or .xplane.pb>
"""
import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(path: str) -> None:
    from jax.profiler import ProfileData

    from bench.lib.trace import latest_xplane

    if os.path.isdir(path):
        path = latest_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            span = (events[-1].start_ns + events[-1].duration_ns
                    - events[0].start_ns) * 1e-9
            total = collections.Counter()
            for ev in events:
                total[ev.name] += ev.duration_ns * 1e-9
            print(f"  LINE {line.name!r}: {len(events)} events over "
                  f"{span:.4f} s, first at {events[0].start_ns}")
            for name, sec in total.most_common(8):
                print(f"    {sec:10.6f} s  {name[:110]}")
            stats = {k: str(v)[:60] for k, v in events[0].stats}
            print(f"    stats of the first event: {stats}")


if __name__ == "__main__":
    main(sys.argv[1])
