"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The run's ``--trace 1`` window is bracketed on the host by a
``bench.window`` annotation.  Inside it:

* busy time is the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:n`` plane), averaged
  over the devices; the idle share is 1 minus busy over the window;
* each operation's time is charged to its own name and to the executable
  (``XLA Modules`` line) that ran it, so a metric can sum the fold's or
  the merge kernel's device time by name;
* each idle gap on the device is charged to what the host was doing: the
  host event that covers most of the gap.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import os
import pathlib
from typing import Dict, List, Optional, Tuple

WINDOW_ANNOTATION = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float                # ns, on the trace's own clock
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    """Device ops and modules per device plane, and host events per line."""
    ops: Dict[str, List[Event]]
    modules: Dict[str, List[Event]]
    host: Dict[str, List[Event]]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` (or a gzipped one, ``.xplane.pb.gz``) with
    JAX's own reader.  Host threads that share a name share a line."""
    from jax.profiler import ProfileData
    data = pathlib.Path(path).read_bytes()
    if path.endswith(".gz"):
        data = gzip.decompress(data)
    pd = ProfileData.from_serialized_xspace(data)
    ops, modules, host = {}, {}, {}
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                evs = [Event(e.name, e.start_ns, e.duration_ns)
                       for e in line.events]
                if line.name == OPS_LINE:
                    ops[plane.name] = evs
                elif line.name == MODULES_LINE:
                    modules[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.setdefault(line.name, []).extend(
                    Event(e.name, e.start_ns, e.duration_ns)
                    for e in line.events)
    return Trace(ops=ops, modules=modules, host=host)


def window_of(trace: Trace) -> Tuple[float, float]:
    for evs in trace.host.values():
        for e in evs:
            if e.name == WINDOW_ANNOTATION:
                return e.start, e.end
    raise ValueError(f"no {WINDOW_ANNOTATION!r} annotation in the trace")


def _clip(evs: List[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for e in evs:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t - s))
    return out


def _union(evs: List[Event]) -> List[Tuple[float, float]]:
    spans = []
    for e in sorted(evs, key=lambda e: e.start):
        if spans and e.start <= spans[-1][1]:
            spans[-1][1] = max(spans[-1][1], e.end)
        else:
            spans.append([e.start, e.end])
    return [(s, t) for s, t in spans]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # mean over devices
    op_s: Dict[str, float]              # op name -> seconds (all devices)
    op_count: Dict[str, int]
    module_s: Dict[str, float]          # module name -> seconds of its ops
    op_in_module_s: Dict[str, float]    # "module/op" -> seconds
    idle_gaps: Dict[str, float]         # host activity -> idle seconds

    def ops_matching(self, part: str) -> Tuple[float, int]:
        """(seconds, count) of the ops whose name contains ``part``."""
        s = sum(v for k, v in self.op_s.items() if part in k)
        n = sum(v for k, v in self.op_count.items() if part in k)
        return s, n

    def modules_matching(self, prefixes) -> float:
        return sum(v for k, v in self.module_s.items()
                   if any(k.startswith(p) for p in prefixes))

    def breakdown(self, top: int = 10) -> dict:
        def best(d):
            ranked = sorted(d.items(), key=lambda kv: (-kv[1], kv[0]))
            return [[k, v] for k, v in ranked[:top]]
        return {"device_ops": best(self.op_in_module_s),
                "idle_gaps": best(self.idle_gaps)}


def module_name(name: str) -> str:
    """``jit_scanned(123)`` -> ``jit_scanned``: the executable's name
    without the id the runtime appends."""
    return name.split("(", 1)[0]


class _HostIndex:
    """Host events per line sorted by start, with the running maximum of
    their ends, so the events overlapping a gap are found without a scan
    of the whole line."""

    def __init__(self, host: Dict[str, List[Event]]):
        self.lines = []
        for line, evs in host.items():
            evs = sorted((e for e in evs if e.name != WINDOW_ANNOTATION),
                         key=lambda e: e.start)
            ends, top = [], float("-inf")
            for e in evs:
                top = max(top, e.end)
                ends.append(top)
            self.lines.append((line, evs, [e.start for e in evs], ends))

    def cover(self, lo: float, hi: float) -> str:
        """The host event covering most of [lo, hi], as "line: event"."""
        best, best_cover = "no host event", 0.0
        for line, evs, starts, ends in self.lines:
            i = bisect.bisect_left(starts, hi) - 1
            while i >= 0 and ends[i] > lo:
                e = evs[i]
                cover = min(e.end, hi) - max(e.start, lo)
                if cover > best_cover:
                    best, best_cover = f"{line}: {e.name}", cover
                i -= 1
        return best


def summarize(trace: Trace, window: Optional[Tuple[float, float]] = None,
              max_gaps: int = 200) -> Summary:
    lo, hi = window or window_of(trace)
    if not trace.ops:
        raise ValueError("the trace holds no device operations")
    op_s = collections.Counter()
    op_count = collections.Counter()
    module_s = collections.Counter()
    op_in_module = collections.Counter()
    gaps = []
    busy = 0.0
    for plane, evs in trace.ops.items():
        evs = _clip(evs, lo, hi)
        mods = sorted(_clip(trace.modules.get(plane, []), lo, hi),
                      key=lambda e: e.start)
        starts = [m.start for m in mods]
        for e in evs:
            op_s[e.name] += e.dur / 1e9
            op_count[e.name] += 1
            j = bisect.bisect_right(starts, e.start) - 1
            mod = (module_name(mods[j].name)
                   if j >= 0 and mods[j].end >= e.start else "?")
            module_s[mod] += e.dur / 1e9
            op_in_module[f"{mod}/{e.name}"] += e.dur / 1e9
        spans = _union(evs)
        busy += sum(t - s for s, t in spans)
        edges = [lo] + [x for s, t in spans for x in (s, t)] + [hi]
        gaps += [(edges[i + 1] - edges[i], edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    idle = collections.Counter()
    index = _HostIndex(trace.host)
    for length, s, t in sorted(gaps, reverse=True)[:max_gaps]:
        idle[index.cover(s, t)] += length / 1e9
    n = len(trace.ops)
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy / n / 1e9,
                   op_s=dict(op_s), op_count=dict(op_count),
                   module_s=dict(module_s), op_in_module_s=dict(op_in_module),
                   idle_gaps=dict(idle))
