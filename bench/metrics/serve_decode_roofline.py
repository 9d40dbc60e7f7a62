"""Share of its roofline that the decode step reaches: per step, the larger
of its FLOPs over the bf16 peak and its least bytes (every weight once plus
the live KV cache) over HBM bandwidth; summed over steps, over the summed
device time of the decode programs.  ``info["ticks"]`` holds one entry per
decode program the engine ran (the serving driver records a tick only when
the engine's decode counter moved)."""

from bench.core import readers as R
from bench.flops import dense_lm as F

NEEDLE = "jit__unknown("  # the decode program: jit of a functools.partial


def read(tr, info, peaks):
    mods = [m for d in tr.devices for m in R.modules(d, NEEDLE)]
    if not mods or not info["ticks"]:
        return None
    d = info["dims"]
    least = sum(R.least_time(F.decode_flops(d, n, live), F.decode_bytes(d, live), peaks)
                for n, live in info["ticks"])
    return 100.0 * least / (sum(m.dur for m in mods) / 1e9)
