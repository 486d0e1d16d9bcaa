"""Print what a profiler trace holds: planes, lines, event counts and the
most frequent event names per line (to find the stable names of
programs and kernels before writing a reader against them).

    python bench/tools/inspect_trace.py <trace.xplane.pb>
"""
import collections
import sys

from jax.profiler import ProfileData


def main(path: str) -> None:
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  LINE {line.name!r} events={len(evs)}")
            for n, c in names.most_common(12):
                print(f"    {c:6d} {n[:120]}")
            for e in evs[:2]:
                print(f"    e.g. start={e.start_ns} dur={e.duration_ns} "
                      f"stats={[(k, str(v)[:60]) for k, v in e.stats][:6]}")


if __name__ == "__main__":
    main(sys.argv[1])
