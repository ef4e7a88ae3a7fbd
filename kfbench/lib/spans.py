"""The program's own spans and scopes, read from the profiler's trace.

``lib/trace.py`` keeps the benchmark's ``kfb:`` annotations and the
device's operations by label.  This module reads the same ``.xplane.pb``
for what the program itself writes there:

* the host's ``kf:<kind>.<name>`` annotations (``monitor/timeline.py``'s
  spans) with their stats and the thread they ran on;
* the first device's operations with the scope their HLO instruction
  was traced under (``jax.named_scope`` in the model, the engine and the
  step bodies): the ``op_name`` of the instruction, which the profiler
  keeps as a stat of the event's *metadata* -- ``ProfileData`` shows an
  event's own stats only, so the metadata tables are read from the
  file's wire format (a few thousand small messages; the events, which
  are the bulk of the file, are skipped by their length).

Both are on the clock of ``lib/trace.py``'s ``Trace``, whose interval
arithmetic does the sums.  A trace of a program that writes no such span
or scope (the parent of the PR that added them) gives empty lists, and
every reader then returns None.
"""

from __future__ import annotations

import functools
import re

from kfbench.lib import trace

PREFIX = "kf:"
#: the scope vocabulary of the program (docs/tracing.md)
SCOPES = ("embed", "norm", "attn_proj", "attn_core", "kv_write", "mlp",
          "head", "optimizer", "grad_sync")
#: the metadata stat that carries an operation's scope path:
#: "jit(step3)/transpose(jvp(attn_core))/flash_bwd_dq/pallas_call"
SCOPE_STATS = ("tf_op", "op_name")
#: a reader wants at least this many samples
MIN_SAMPLES = 3
_WRAPPED = re.compile(r"(\w+)\((.*)\)")


class Span:
    __slots__ = ("name", "start", "dur", "stats", "thread")

    def __init__(self, name, start, dur, stats=None, thread=""):
        self.name, self.start, self.dur = name, start, dur
        self.stats, self.thread = stats or {}, thread

    @property
    def end(self):
        return self.start + self.dur


class Spans:
    def __init__(self, host, ops, modules):
        #: [Span] of the host's kf: annotations, by start
        self.host = sorted(host, key=lambda s: (s.start, -s.dur))
        #: [(scope or None, start, dur, label)] of the first device
        self.ops = ops
        #: [(name, start, dur)] of the first device's program runs
        self.modules = modules

    def named(self, name: str):
        return [s for s in self.host if s.name == PREFIX + name]

    def children(self, parent: Span, *names: str):
        """The spans of these names that ran inside ``parent``, on its
        thread."""
        want = {PREFIX + n for n in names}
        return [s for s in self.host
                if s.name in want and s.thread == parent.thread
                and parent.start <= s.start and s.end <= parent.end
                and s is not parent]

    def self_seconds(self, parent: Span, *names: str) -> float:
        """``parent``'s duration less its children of these names."""
        return parent.dur - sum(c.dur for c in self.children(parent, *names))


def scope_of(path) -> str | None:
    """The innermost name of the vocabulary in an operation's scope path
    ("jit(step3)/transpose(jvp(norm))/mul" -> "norm"), or None.  A
    transformation wraps the scope it ran under (``jvp(norm)``); a
    ``jit(head)`` is a function of that name, not the scope."""
    if not path:
        return None
    for part in reversed(path.split("/")[:-1]):  # the last is the primitive
        while (m := _WRAPPED.fullmatch(part)) and m.group(1) not in (
                "jit", "pjit"):
            part = m.group(2)
        if part in SCOPES:
            return part
    return None


# -- the file's wire format: only what the metadata tables need ---------------

def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _map_entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def metadata_tables(path: str):
    """(plane's name, [event metadata message], {stat metadata id: stat
    name}) of every plane of the file (XSpace.planes=1; XPlane.name=2,
    .event_metadata=4, .stat_metadata=5, both maps of id to message;
    XStatMetadata.name=2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:
                events.append(_map_entry(v)[1])
            elif pf == 5:
                key, meta = _map_entry(v)
                stat_names[key] = next(
                    (bytes(x).decode() for mf, x in _fields(meta) if mf == 2),
                    "")
        yield name, events, stat_names


def scope_paths(path: str, plane_prefix: str = "/device:TPU:") -> dict:
    """{operation's name: scope path} from the event metadata of the
    file's device planes (XEventMetadata.name=2, .stats=5;
    XStat.metadata_id=1, .str_value=5, .ref_value=7: a value kept as the
    name of another stat metadata)."""
    out = {}
    for name, events, stat_names in metadata_tables(path):
        if not name.startswith(plane_prefix):
            continue
        wanted = {k for k, n in stat_names.items() if n in SCOPE_STATS}
        for meta in events:
            op, scope = None, None
            for mf, v in _fields(meta):
                if mf == 2:
                    op = bytes(v).decode()
                elif mf == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) in wanted:
                        scope = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7)))
            if op is not None and scope:
                out[op] = scope
    return out


def load(path: str, device_prefix: str = "/device:TPU:") -> Spans:
    from jax.profiler import ProfileData

    scopes = {op: scope_of(p)
              for op, p in scope_paths(path, device_prefix).items()}
    host, ops, modules = [], [], []
    first_device = None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(device_prefix):
            if first_device is None:
                first_device = plane.name
            if plane.name != first_device:
                continue
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops = [(scopes.get(e.name), e.start_ns * 1e-9,
                            e.duration_ns * 1e-9, trace.op_label(e.name))
                           for e in line.events]
                elif line.name == trace.MODULES_LINE:
                    modules = [(re.sub(r"\(\d+\)$", "", e.name),
                                e.start_ns * 1e-9, e.duration_ns * 1e-9)
                               for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [Span(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                              dict(e.stats), line.name)
                         for e in line.events if e.name.startswith(PREFIX)]
    return Spans(host, ops, modules)


def of(facts: dict) -> Spans:
    """The run's ``Spans``, read once and kept on the facts; empty where
    the run was not traced, so that every reader finds nothing there."""
    tr = facts.get("trace")
    if not tr:
        return Spans([], [], [])
    if "spans" not in tr:
        tr["spans"] = load(trace.find_xplane(tr["dir"]))
    return tr["spans"]


# -- the reductions -----------------------------------------------------------

def mean(values, scale: float = 1.0):
    """``scale`` times the mean, or None of fewer than MIN_SAMPLES."""
    values = list(values)
    if len(values) < MIN_SAMPLES:
        return None
    return scale * sum(values) / len(values)


def median(values, scale: float = 1.0):
    from kfbench.lib import stats

    values = list(values)
    if len(values) < MIN_SAMPLES:
        return None
    return scale * stats.median(values)


def innermost(events) -> dict:
    """{name: merged intervals during which an event of that name was
    the innermost one open}, for (name, start, end) events of one thread
    or one device line, which nest (a ``while`` holds its body's
    operations).  Every instant any event covers goes to one name."""
    by, stack, at = {}, [], 0.0

    def note(name, start, end):
        if end > start:
            by.setdefault(name, []).append((start, end))

    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            top, top_end = stack.pop()
            note(top, at, top_end)
            at = max(at, top_end)
        if stack:
            note(stack[-1][0], at, start)
        stack.append((name, end))
        at = start
    while stack:
        top, top_end = stack.pop()
        note(top, at, top_end)
        at = max(at, top_end)
    return {name: trace.union(iv) for name, iv in by.items()}


def both(a, b):
    """The parts of merged intervals ``a`` that merged ``b`` covers."""
    return trace.subtract(a, trace.subtract(a, b))


@functools.lru_cache(maxsize=None)  # (a cell's entries share one sweep)
def idle_by_span(sp: Spans, step: str) -> dict:
    """The first device's idle seconds in the traced stretch (from its
    first operation to its last), split by the innermost ``kf:`` span
    open then on the thread that runs the ``step`` spans; ``None`` keys
    what no span covers.  The values add up to the stretch's idle time."""
    steps = sp.named(step)
    if not sp.ops or not steps:
        return {}
    busy = trace.union((s, s + d) for _, s, d, _ in sp.ops)
    idle = trace.subtract([[busy[0][0], busy[-1][1]]], busy)
    out, left = {}, trace.total(idle)
    for name, held in innermost(
            (h.name, h.start, h.end) for h in sp.host
            if h.thread == steps[0].thread).items():
        out[name] = trace.total(both(idle, held))
        left -= out[name]
    out[None] = left
    return out


@functools.lru_cache(maxsize=None)
def scope_seconds(sp: Spans, within=None) -> dict:
    """{scope or None: seconds} of the first device's operations, each
    instant going to the innermost operation open then, so that the
    values add up to the device's busy time; with ``within`` (a pattern
    of program names) only what lies inside runs of such programs."""
    held = innermost((scope, s, s + d) for scope, s, d, _ in sp.ops)
    if within is not None:
        rx = re.compile(within)
        inside = trace.union((s, s + d) for n, s, d in sp.modules
                             if rx.search(n))
        held = {scope: both(iv, inside) for scope, iv in held.items()}
    return {scope: trace.total(iv) for scope, iv in held.items()}


def runs(sp: Spans, pattern: str) -> int:
    rx = re.compile(pattern)
    return sum(1 for n, _, _ in sp.modules if rx.search(n))
