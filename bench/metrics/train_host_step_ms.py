"""The program's own host time per inner step: the median duration of its
``train.inner_step`` span (staging the batch on the mesh and dispatching the
step program), in ms."""

from statistics import median

from bench.core import program_spans as PS


def read(tr, info, peaks):
    durs = [s.dur for s in PS.named(PS.spans(tr), "train.inner_step")]
    return median(durs) / 1e6 if durs else None
