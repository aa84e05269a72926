"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Four things come out of a trace, all between two host timestamps (the traced
window):

* busy and idle: the union of the intervals in which an operation ran on each
  device, averaged over the devices;
* per-op and per-program device time: summed durations by name;
* collective-exposed time: the part of the collectives' intervals with no
  other operation running beside them on that device;
* idle gaps: the holes in the busy union, each labelled by the innermost
  benchmark span (``jax.profiler.TraceAnnotation``) the host was in.

``extract`` reads the file into plain lists (also the form the test data is
kept in); ``Trace`` computes on them.
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "send", "recv")


def options():
    """Profiler options: host spans at the user level, no Python tracer."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    return opts


def find(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def extract(path: str, host_prefixes: Sequence[str]) -> dict:
    """Events of the trace as plain lists: per device plane its ops and its
    programs, and the host spans whose names start with ``host_prefixes``.
    Each event is ``[name, start_ns, duration_ns]``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = {"ops": {}, "programs": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "programs"}.get(
                    line.name)
                if key:
                    out[key][plane.name] = [
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns, e.duration_ns] for e in line.events
                    if e.name.startswith(tuple(host_prefixes)))
    return out


def load(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals into a sorted list of disjoint ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def measure(merged: Sequence[Interval], lo: float, hi: float) -> float:
    """Length of the part of disjoint ``merged`` intervals inside [lo, hi]."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Disjoint sorted ``a`` minus disjoint sorted ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def is_collective(name: str) -> bool:
    n = name.lower()
    return any(c in n for c in COLLECTIVES)


class Trace:
    """The extracted events of one trace, cut to the window [lo, hi] (ns)."""

    def __init__(self, ex: dict, lo: float, hi: float):
        self.lo, self.hi = float(lo), float(hi)
        self.ops = {dev: [(n, s, d) for n, s, d in evs
                          if s + d > lo and s < hi]
                    for dev, evs in ex["ops"].items()}
        self.programs = {dev: [(n, s, d) for n, s, d in evs
                               if s + d > lo and s < hi]
                         for dev, evs in ex["programs"].items()}
        self.host = [(n, s, d) for n, s, d in ex["host"]]
        self.busy_union = {dev: union((s, s + d) for _, s, d in evs)
                           for dev, evs in self.ops.items()}

    @property
    def devices(self) -> List[str]:
        return sorted(self.busy_union)

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo

    def busy_ns(self, lo: Optional[float] = None,
                hi: Optional[float] = None) -> float:
        """Device-busy ns inside [lo, hi] (default: the window), averaged
        over the devices that ran anything."""
        lo = self.lo if lo is None else max(lo, self.lo)
        hi = self.hi if hi is None else min(hi, self.hi)
        if not self.devices:
            return 0.0
        return sum(measure(u, lo, hi) for u in self.busy_union.values()) \
            / len(self.devices)

    def op_time(self) -> Dict[str, float]:
        """Summed device ns inside the window, all devices, by
        ``<program>/<op>``: the program that ran the op (its name without
        the fingerprint) and the op's HLO name.  An op that holds others
        (a loop, a call) counts only through them."""
        out: Dict[str, float] = {}
        for dev, evs in self.ops.items():
            progs = sorted(self.programs.get(dev, []), key=lambda e: e[1])
            starts = [s for _, s, _ in progs]
            evs = sorted(evs, key=lambda e: (e[1], -e[2]))
            leaves = [e for e, nxt in zip(evs, evs[1:] + [None])
                      if nxt is None or nxt[1] + nxt[2] > e[1] + e[2]
                      or nxt[1] >= e[1] + e[2]]
            for n, s, d in leaves:
                i = bisect.bisect_right(starts, s) - 1
                prog = progs[i][0].split("(")[0] if i >= 0 \
                    and s < progs[i][1] + progs[i][2] else "?"
                key = f"{prog}/{n.split(' = ')[0].lstrip('%')}"
                out[key] = out.get(key, 0.0) + measure([(s, s + d)], self.lo,
                                                       self.hi)
        return out

    def program_calls(self, match: str) -> List[Tuple[float, float]]:
        """(start, duration) ns of every run of a program whose name contains
        ``match``, inside the window, on all devices."""
        return [(s, d) for evs in self.programs.values()
                for n, s, d in evs
                if match in n and s >= self.lo and s + d <= self.hi]

    def collective_exposed_ns(self) -> float:
        """Collective time with no other op beside it, averaged over devices."""
        if not self.devices:
            return 0.0
        total = 0.0
        for evs in self.ops.values():
            coll = union((s, s + d) for n, s, d in evs if is_collective(n))
            other = union((s, s + d) for n, s, d in evs
                          if not is_collective(n))
            total += measure(subtract(coll, other), self.lo, self.hi)
        return total / len(self.devices)

    def idle_gaps(self) -> List[Tuple[str, float]]:
        """Idle gaps of the busiest device as (host span, ns), longest first;
        a gap is labelled by the shortest benchmark span around its middle."""
        if not self.devices:
            return []
        dev = max(self.devices, key=lambda k: measure(
            self.busy_union[k], self.lo, self.hi))
        gaps = subtract([(self.lo, self.hi)], self.busy_union[dev])
        out = []
        for a, b in gaps:
            mid = 0.5 * (a + b)
            around = [(d, n) for n, s, d in self.host if s <= mid <= s + d]
            out.append((min(around)[1] if around else "none", b - a))
        return sorted(out, key=lambda g: -g[1])
