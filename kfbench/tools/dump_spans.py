#!/usr/bin/env python3
"""Print what ``lib/spans.py`` reads from an ``.xplane.pb``: the stat
names of each device plane's metadata, some operations with every stat
of their metadata (``tools/dump_trace.py`` cannot show those:
``ProfileData`` gives an event's own stats only), the host's ``kf:``
spans by name, and the device time by scope.  For looking at a trace by
hand before writing a reader against it.

    python kfbench/tools/dump_spans.py <trace dir or .xplane.pb> [n] [excerpt.json seconds]

With the last two it also writes the trace as ``lib/spans.py`` reads it,
cut to the first ``seconds`` after the first ``kf:`` span: a recorded
excerpt small enough to keep beside the tests.
"""

import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def metadata_stats(path, n):
    """Per plane: the stat names, and the first ``n`` event metadata
    that have stats, with each stat's name and value."""
    from kfbench.lib.spans import _fields, metadata_tables

    for name, events, stat_names in metadata_tables(path):
        print("PLANE", name, len(events), "event metadata;",
              "stat names:", sorted(set(stat_names.values())))
        shown = 0
        for meta in events:
            fields = list(_fields(meta))
            stats = [dict(_fields(v)) for mf, v in fields if mf == 5]
            if not stats or shown >= n:
                continue
            shown += 1
            op = next((bytes(v).decode() for mf, v in fields if mf == 2), "")
            print("   ", repr(op[:100]))
            for st in stats:
                value = next((bytes(st[k]).decode(errors="replace")[:160]
                              if hasattr(st[k], "tobytes") else st[k]
                              for k in (5, 2, 3, 4, 6) if k in st),
                             stat_names.get(st.get(7)))
                print("       ", stat_names.get(st.get(1)), "=", value)


def excerpt(sp, lo, hi) -> dict:
    """What of ``sp`` lies wholly inside (lo, hi), times from ``lo``; an
    operation as [scope, start, duration, label], the two names as
    indices into the tables beside them."""

    def keep(start, dur):
        return lo <= start and start + dur <= hi

    ops = [o for o in sp.ops if keep(o[1], o[2])]
    scopes = sorted({o[0] for o in ops}, key=str)
    labels = sorted({o[3] for o in ops})
    si = {x: i for i, x in enumerate(scopes)}
    li = {x: i for i, x in enumerate(labels)}
    return {
        "host": [[s.name, round(s.start - lo, 9), round(s.dur, 9), s.stats,
                  s.thread] for s in sp.host if keep(s.start, s.dur)],
        "scopes": scopes, "labels": labels,
        "ops": [[si[scope], round(s - lo, 9), round(d, 9), li[label]]
                for scope, s, d, label in ops],
        "modules": [[m, round(s - lo, 9), round(d, 9)]
                    for m, s, d in sp.modules if keep(s, d)],
    }


def main() -> int:
    from kfbench.lib import spans, trace

    path = sys.argv[1]
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 4
    if os.path.isdir(path):
        path = trace.find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    metadata_stats(path, n)
    sp = spans.load(path)
    by = collections.defaultdict(list)
    for s in sp.host:
        by[s.name].append(s)
    for name, ss in sorted(by.items()):
        print("SPAN", name, len(ss), "x, mean",
              round(1e3 * sum(s.dur for s in ss) / len(ss), 4), "ms; thread",
              ss[0].thread, "; stats", ss[0].stats)
    print("MODULES", collections.Counter(m[0] for m in sp.modules))
    total = sum(spans.scope_seconds(sp).values()) or 1.0
    for scope, seconds in sorted(spans.scope_seconds(sp).items(),
                                 key=lambda kv: -kv[1]):
        print("SCOPE", scope, round(seconds, 6), "s",
              round(100 * seconds / total, 2), "%")
    unscoped = collections.Counter()  # (an operation nested in another counts in both)
    for scope, _, d, label in sp.ops:
        if scope is None:
            unscoped[label] += d
    for label, seconds in unscoped.most_common(n + 8):
        print("UNSCOPED", label, round(seconds, 6), "s")
    for name, seconds in sorted(
            {**spans.idle_by_span(sp, "serve.step"),
             **spans.idle_by_span(sp, "step.train")}.items(),
            key=lambda kv: -kv[1]):
        print("IDLE", name, round(seconds, 6), "s")
    if len(sys.argv) > 4 and sp.host:
        lo = sp.host[0].start
        hi = lo + float(sys.argv[4])

        with open(sys.argv[3], "w") as f:
            json.dump(excerpt(sp, lo, hi), f, separators=(",", ":"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
