"""Device idle share over the intervals in which at least one request is
queued or running: the loop ticks the engine only then, so those intervals
are the union of the benchmark's ``bench.tick`` and ``bench.drain`` spans."""

from bench.core import trace as T


def read(tr, info, peaks):
    active = T.union((s.start, s.end) for s in tr.spans if s.name in ("bench.tick", "bench.drain"))
    span = sum(e - s for s, e in active)
    if not span or not tr.devices:
        return None
    ops = [(e.start, e.end) for e in tr.devices[0].ops]
    idle = T.subtract(active, ops)
    return 100.0 * idle / span
