"""Share of the active intervals (the union of the benchmark's ``bench.tick``
and ``bench.drain`` spans, the basis of ``serve_idle_share``) in which chip
0 is idle while the engine does its own host work: a ``serve.*`` span is
open and the innermost one is not ``serve.fetch`` (a fetch waits on the
chip).  The rest of ``serve_idle_share`` falls while the host is outside the
engine's code."""

from bench.core import program_spans as PS
from bench.core import trace as T


def read(tr, info, peaks):
    sp = PS.spans(tr)
    engine = [(s.start, s.end) for s in sp if s.name.startswith("serve.") and s.name != "serve.fetch"]
    active = [(s.start, s.end) for s in tr.spans if s.name in ("bench.tick", "bench.drain")]
    span = sum(e - s for s, e in T.union(active))
    if not engine or not span or not tr.devices:
        return None
    work = PS.intersect(PS.minus(engine, [(s.start, s.end) for s in PS.named(sp, "serve.fetch")]), active)
    idle = T.subtract(work, [(e.start, e.end) for e in tr.devices[0].ops])
    return 100.0 * idle / span
