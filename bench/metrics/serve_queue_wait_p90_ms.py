"""The 90th percentile of the time a request waits in the engine's queue,
from submission to the claim of its slot and pages: the ``queued_ms`` stat
of every ``serve.admit_request`` span in the traced window."""

from bench.core import harness as H
from bench.core import program_spans as PS


def read(tr, info, peaks):
    waits = [s.stats["queued_ms"] for s in PS.named(PS.spans(tr), "serve.admit_request")]
    return H.percentile(waits, 90) if waits else None
