"""Model FLOPs of the prompt tokens prefilled over the device time of the
chunk-prefill programs times the bf16 peak."""

from bench.core import readers as R
from bench.flops import dense_lm as F

NEEDLE = "chunk_impl"


def read(tr, info, peaks):
    mods = [m for d in tr.devices for m in R.modules(d, NEEDLE)]
    if not mods:
        return None
    flops = sum(F.prefill_flops(info["dims"], n) for n, _ in info["requests"])
    return 100.0 * flops / (sum(m.dur for m in mods) / 1e9 * peaks["bf16_flops"])
