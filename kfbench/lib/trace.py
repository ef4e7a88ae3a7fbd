"""From the profiler's trace to numbers: the one reduction every PR's
per-layer metrics go through.

``load`` reads an ``.xplane.pb`` (with nothing but JAX) into a plain
``Trace``: per device its operations and its program runs, and the
host's ``kfb:`` annotations, all as ``(name, start_s, duration_s)`` on
one clock.  Everything else here is arithmetic on a ``Trace`` and is
checked against the recorded trace beside the tests.
"""

from __future__ import annotations

import glob
import os
import re

#: lines of a device plane, as libtpu's profiler names them
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANNOTATION_PREFIX = "kfb:"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")


class Trace:
    def __init__(self, devices, annotations):
        #: {device name: {"ops": [(name, start, dur)], "modules": [...]}}
        self.devices = devices
        #: [(name, start, dur)] of the host's kfb: annotations
        self.annotations = annotations

    def to_json(self) -> dict:
        return {"devices": self.devices, "annotations": self.annotations}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: {n: [tuple(e) for e in v] for n, v in dev.items()}
                    for k, dev in d["devices"].items()},
                   [tuple(e) for e in d["annotations"]])


#: a train cell's two whole-step programs, as the device's line names them
TRAIN_STEP = r"^jit_(step3|pulse_outer)"


def of(facts: dict):
    """The run's ``Trace`` as ``reduce`` left it in the facts, or None
    where the run was not traced."""
    return ((facts.get("trace") or {}).get("reduced") or {}).get("trace")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


#: "%copy.12 = bf16[36,16]{1,0:T(8,128)} copy(...)": name, result type
HLO_TEXT = re.compile(r"^%?([\w\-.]+?)(?:\.\d+)? = \(*(\w+\[[\d,]*\])")


def op_label(name: str) -> str:
    """A device operation's name as the reduction keeps it: libtpu names
    an operation by its whole HLO text, of which the instruction's name
    (without its number) and its result's type and shape are kept:
    ``copy bf16[36,16,20,1024,64]``."""
    m = HLO_TEXT.match(name)
    if m:
        return f"{m.group(1)} {m.group(2)}"
    return re.sub(r"\.\d+$", "", name.lstrip("%").split(" ")[0])


def load(path: str, device_prefix: str = "/device:TPU:") -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, annotations = {}, []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [(op_label(e.name), e.start_ns * 1e-9,
                                   e.duration_ns * 1e-9) for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [
                        (re.sub(r"\(\d+\)$", "", e.name), e.start_ns * 1e-9,
                         e.duration_ns * 1e-9) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                annotations += [(e.name, e.start_ns * 1e-9,
                                 e.duration_ns * 1e-9) for e in line.events
                                if e.name.startswith(ANNOTATION_PREFIX)]
    annotations.sort(key=lambda e: e[1])
    return Trace(devices, annotations)


# -- arithmetic on intervals --------------------------------------------------

def union(intervals):
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """The parts of merged intervals ``a`` that no interval of merged
    ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, at = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > at:
                out.append([at, b[k][0]])
            at = max(at, b[k][1])
            k += 1
        if at < e:
            out.append([at, e])
    return out


def spans(events):
    return [(s, s + d) for _, s, d in events]


# -- the reductions -----------------------------------------------------------

def busy_seconds(trace: Trace) -> float:
    """Seconds in which an operation ran on the device: the union of its
    operations' intervals, averaged over the devices."""
    per = [total(union(spans(d["ops"]))) for d in trace.devices.values()]
    return sum(per) / len(per) if per else 0.0


def op_seconds(trace: Trace, pattern: str) -> float:
    """Summed duration of the operations whose name matches, averaged
    over the devices."""
    rx = re.compile(pattern)
    per = [sum(d for n, _, d in dev["ops"] if rx.search(n))
           for dev in trace.devices.values()]
    return sum(per) / len(per) if per else 0.0


def op_count(trace: Trace, pattern: str) -> float:
    rx = re.compile(pattern)
    per = [sum(1 for n, _, _ in dev["ops"] if rx.search(n))
           for dev in trace.devices.values()]
    return sum(per) / len(per) if per else 0.0


def module_runs(trace: Trace, pattern: str):
    """Durations of the runs of the programs whose name matches, on the
    first device."""
    rx = re.compile(pattern)
    for dev in trace.devices.values():
        return [d for n, _, d in dev["modules"] if rx.search(n)]
    return []


def exposed_collective_seconds(trace: Trace) -> float:
    """Collective-operation time during which no other operation runs on
    that device, averaged over the devices."""
    per = []
    for dev in trace.devices.values():
        coll = union(spans([e for e in dev["ops"] if COLLECTIVE.match(e[0])]))
        rest = union(spans([e for e in dev["ops"]
                            if not COLLECTIVE.match(e[0])]))
        per.append(total(subtract(coll, rest)))
    return sum(per) / len(per) if per else 0.0


def top_ops(trace: Trace, n: int = 10):
    """The operations that took most device time, by label, averaged
    over the devices: [[label, seconds], ...]."""
    by = {}
    for dev in trace.devices.values():
        for name, _, d in dev["ops"]:
            by[name] = by.get(name, 0.0) + d
    k = max(len(trace.devices), 1)
    return [[name, s / k] for name, s in
            sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, window, n: int = 10):
    """The device's idle time inside ``window`` (start, end), split by
    what the host was doing: each gap's seconds go to the innermost
    ``kfb:`` annotation open then, or to ``host:unannotated``.  First
    device only.  [[annotation, seconds], ...], longest first."""
    for dev in trace.devices.values():
        idle = subtract([list(window)], union(spans(dev["ops"])))
        break
    else:
        return []
    by = {}
    # innermost first: a later-starting annotation nests inside an earlier
    notes = sorted(trace.annotations, key=lambda e: -e[1])
    for s, e in idle:
        left = [[s, e]]
        for name, a, d in notes:
            if not left:
                break
            cover = [[max(a, x), min(a + d, y)] for x, y in left
                     if min(a + d, y) > max(a, x)]
            if cover:
                by[name] = by.get(name, 0.0) + total(cover)
                left = subtract(left, union(cover))
        if left:
            by["host:unannotated"] = by.get("host:unannotated", 0.0) + total(left)
    return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def traced_window(trace: Trace):
    """From the first to the last thing the devices did."""
    starts = [s for d in trace.devices.values() for _, s, _ in d["ops"]]
    ends = [s + x for d in trace.devices.values() for _, s, x in d["ops"]]
    return (min(starts), max(ends)) if starts else (0.0, 0.0)


def reduce(trace: Trace, n_devices: int) -> dict:
    """``n_devices`` 0 is the CPU rehearsal, whose trace has no device."""
    if n_devices and len(trace.devices) != n_devices:
        raise RuntimeError(f"the trace holds {len(trace.devices)} devices "
                           f"({list(trace.devices)}), the run used {n_devices}")
    window = traced_window(trace)
    return {
        "window_s": window[1] - window[0],
        "busy_s": busy_seconds(trace),
        "device_ops": top_ops(trace),
        "idle_gaps": idle_gaps(trace, window),
        "trace": trace,
    }


def reduce_dir(trace_dir: str, n_devices: int) -> dict:
    return reduce(load(find_xplane(trace_dir)), n_devices)
