"""The program's own spans, with their stats, from the profile of a traced run.

The program writes ``TraceAnnotation`` spans named ``train.*``, ``outer.*``,
``serve.*`` and ``jax.*`` (``src/repro/obs.py``) on the host plane of the
same ``xplane.pb`` as the device operations.  ``bench/run.py`` traces the
window into ``.bench_traces/<cell>-<seed>/`` and removes that directory
only after the per-layer readers have run, so a reader finds the newest
``*.xplane.pb`` under ``.bench_traces/``.  Times keep the ``start_ns`` basis
of ``bench/core/trace.py``: program spans line up with its device events and
with the benchmark's own ``bench.*`` spans.

A program without these spans yields none, and every reader of them then
reports nothing.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

from bench.core import trace as T
from bench.core.harness import ROOT

PREFIXES = ("train.", "outer.", "serve.", "jax.")
TRACES = ROOT / ".bench_traces"


@dataclass
class Span:
    name: str
    start: int   # ns
    dur: int     # ns
    stats: dict = field(default_factory=dict)

    @property
    def end(self) -> int:
        return self.start + self.dur


def newest_xplane() -> str | None:
    paths = glob.glob(os.path.join(str(TRACES), "**", "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def from_profile(pd) -> list[Span]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.append(Span(e.name, int(e.start_ns), int(e.duration_ns), dict(e.stats)))
    out.sort(key=lambda s: (s.start, -s.dur))
    return out


def from_dict(d: dict) -> T.Trace:
    """A trace kept as JSON (test fixtures): ``bench/core/trace.py``'s form
    plus ``program_spans``, a list of ``[name, start, dur, stats]``."""
    tr = T.from_dict(d)
    tr.program_spans = [Span(n, int(s), int(du), dict(st)) for n, s, du, st in d["program_spans"]]
    return tr


def spans(tr: T.Trace) -> list[Span]:
    """The program's spans in the window of ``tr``, in start order: those
    the trace carries, else those of the newest profile, read once per
    trace."""
    got = getattr(tr, "program_spans", None)
    if got is None:
        path = newest_xplane()
        got = []
        if path is not None:
            from jax.profiler import ProfileData

            got = from_profile(ProfileData.from_file(path))
        tr.program_spans = got
    return got


def named(sp: list[Span], *names: str) -> list[Span]:
    return [s for s in sp if s.name in names]


def minus(a, b) -> list[tuple[int, int]]:
    """The parts of the union of ``a`` that the union of ``b`` leaves uncovered."""
    ub = T.union(b)
    out, j = [], 0
    for s, e in T.union(a):
        cur = s
        while j < len(ub) and ub[j][1] <= cur:
            j += 1
        k = j
        while k < len(ub) and ub[k][0] < e:
            if ub[k][0] > cur:
                out.append((cur, ub[k][0]))
            cur = max(cur, ub[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def intersect(a, b) -> list[tuple[int, int]]:
    """The parts of the union of ``a`` inside the union of ``b``."""
    ua, ub = T.union(a), T.union(b)
    out, i, j = [], 0, 0
    while i < len(ua) and j < len(ub):
        s, e = max(ua[i][0], ub[j][0]), min(ua[i][1], ub[j][1])
        if s < e:
            out.append((s, e))
        if ua[i][1] < ub[j][1]:
            i += 1
        else:
            j += 1
    return out
