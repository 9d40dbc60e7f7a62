"""Reduce a profiler trace to what the per-layer metrics read.

``jax.profiler`` writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation
run on the chip and their ``XLA Modules`` line one event per compiled
program run.  The host plane holds the benchmark's own spans
(``TraceAnnotation``, names starting ``bench.``) on the same clock.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field


@dataclass
class Event:
    name: str
    start: int   # ns
    dur: int     # ns

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclass
class Device:
    name: str
    ops: list = field(default_factory=list)
    modules: list = field(default_factory=list)


@dataclass
class Trace:
    devices: list
    spans: list   # host Events named bench.*


def union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def subtract(a, b) -> int:
    """Length of the union of ``a`` not covered by the union of ``b``."""
    ua, ub = union(a), union(b)
    total, j = 0, 0
    for s, e in ua:
        cur = s
        while j < len(ub) and ub[j][1] <= cur:
            j += 1
        k = j
        while k < len(ub) and ub[k][0] < e:
            if ub[k][0] > cur:
                total += ub[k][0] - cur
            cur = max(cur, ub[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return paths[-1] if paths else None


def load(trace_dir: str) -> Trace | None:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    devices, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = Device(plane.name)
            for line in plane.lines:
                evs = [Event(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
                if line.name == "XLA Ops":
                    dev.ops = evs
                elif line.name == "XLA Modules":
                    dev.modules = evs
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append(Event(e.name, int(e.start_ns), int(e.duration_ns)))
    spans.sort(key=lambda e: e.start)
    return Trace(devices=devices, spans=spans)


def from_dict(d: dict) -> Trace:
    """A trace kept as JSON (test fixtures)."""
    mk = lambda xs: [Event(n, int(s), int(du)) for n, s, du in xs]
    return Trace(
        devices=[Device(x["name"], mk(x["ops"]), mk(x["modules"])) for x in d["devices"]],
        spans=mk(d["spans"]),
    )


def busy_s(t: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    if not t.devices:
        return 0.0
    return sum(covered((e.start, e.end) for e in d.ops) for d in t.devices) / len(t.devices) / 1e9


def top_ops(t: Trace, n: int = 10) -> list:
    tot: dict = {}
    for d in t.devices:
        for e in d.ops:
            tot[e.name] = tot.get(e.name, 0) + e.dur
    k = max(len(t.devices), 1)
    return [[name, ns / k / 1e9] for name, ns in sorted(tot.items(), key=lambda x: -x[1])[:n]]


def idle_gaps(t: Trace, n: int = 10) -> list:
    """The longest gaps with no operation on chip 0, each named by the host
    span open at the gap's middle."""
    if not t.devices:
        return []
    busy = union((e.start, e.end) for e in t.devices[0].ops)
    gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]
    gaps.sort(reverse=True)
    out = []
    for g, s, e in gaps[:n]:
        mid = (s + e) // 2
        host = [sp.name for sp in t.spans if sp.start <= mid <= sp.end]
        out.append([host[-1] if host else "no bench span", g / 1e9])
    return out
