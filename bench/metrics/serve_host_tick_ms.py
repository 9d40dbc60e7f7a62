"""The engine's own host time per tick: for each ``serve.step`` span, its
duration plus that of the ``serve.drain`` spans before the next tick, less
the ``serve.fetch`` spans inside them (a fetch waits on the chip); the
median over ticks, in ms."""

from bisect import bisect_right
from statistics import median

from bench.core import program_spans as PS


def read(tr, info, peaks):
    sp = PS.spans(tr)
    steps = PS.named(sp, "serve.step")
    if not steps:
        return None
    starts = [s.start for s in steps]
    host = [s.dur for s in steps]
    for s in sp:
        sign = {"serve.drain": 1, "serve.fetch": -1}.get(s.name)
        i = bisect_right(starts, s.start) - 1
        if sign and i >= 0:
            host[i] += sign * s.dur
    return median(host) / 1e6
