"""Share of the traced window in which no operation ran on the chip,
averaged over the chips: 1 - busy / window."""

from bench.core import trace as T


def read(tr, info, peaks):
    return 100.0 * (1.0 - T.busy_s(tr) / info["window_s"])
