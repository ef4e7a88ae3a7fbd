#!/usr/bin/env python3
"""Print what an ``.xplane.pb`` holds: planes, lines, event counts and
the first events of each line with their stats.  For looking at a trace
by hand before writing a reader against it.

    python kfbench/tools/dump_trace.py <trace dir or .xplane.pb> [n] [excerpt.json seconds]

With the last two it also writes the trace as the reduction reads it
(``lib/trace.py``'s ``Trace``), cut to its first ``seconds``: a recorded
trace small enough to keep beside the tests.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    from jax.profiler import ProfileData

    from kfbench.lib import trace

    path = sys.argv[1]
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print("PLANE", plane.name, len(lines), "lines")
        for line in lines:
            events = list(line.events)
            print("  LINE", repr(line.name), len(events), "events")
            for e in events[:n]:
                print("     ", repr(e.name), e.start_ns, e.duration_ns,
                      {k: str(v)[:80] for k, v in list(e.stats)[:12]})
    if len(sys.argv) > 4:
        import json

        t = trace.load(path)
        lo = trace.traced_window(t)[0]
        hi = lo + float(sys.argv[4])

        def cut(events):
            return [[n, round(s - lo, 9), round(d, 9)] for n, s, d in events
                    if lo <= s and s + d <= hi]

        with open(sys.argv[3], "w") as f:
            json.dump({"devices": {k: {"ops": cut(v["ops"]),
                                       "modules": cut(v["modules"])}
                                   for k, v in t.devices.items()},
                       "annotations": cut(t.annotations)}, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
