"""Print the structure of a JAX profiler trace: planes, lines, the busiest
event names per line and a few events with their stats.

  python3 bench/tools/dump_trace.py <trace dir or .xplane.pb> [events per line]
"""

import collections
import glob
import os
import sys


def main(path: str, per_line: int = 25) -> None:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            if not evs:
                continue
            lo = min(e.start_ns for e in evs)
            hi = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {line.name!r} events={len(evs)} "
                  f"span_ns=[{lo:.0f}, {hi:.0f}]")
            tot = collections.Counter()
            cnt = collections.Counter()
            for e in evs:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
            for name, ns in tot.most_common(per_line):
                print(f"    TOP {ns / 1e6:10.3f} ms x{cnt[name]:6d} {name[:160]}")
            seen = set()
            for e in evs:
                if e.name in seen or len(seen) >= 6:
                    continue
                seen.add(e.name)
                stats = [(k, str(v)[:200]) for k, v in e.stats]
                print(f"    EV {e.name[:120]!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f} stats={stats}")


if __name__ == "__main__":
    main(sys.argv[1], *(int(a) for a in sys.argv[2:3]))
