"""Share of its roofline that the paged decode-attention kernel reaches:
per decode step and layer, the larger of its FLOPs over the bf16 peak and
its least bytes (the live K and V pages, q and o) over HBM bandwidth, at the
live cache lengths; over the summed device time of the kernel's calls
inside the decode programs."""

from bench.core import readers as R
from bench.flops import attention_kernels as K

MODULE = "jit__unknown("  # the decode program: jit of a functools.partial
NEEDLE = "paged"


def read(tr, info, peaks):
    d = info["dims"]
    calls = []
    for dev in tr.devices:
        calls += R.ops_within(dev, R.modules(dev, MODULE), NEEDLE)
    if not calls or not info["ticks"]:
        return None
    least = sum(R.least_time(*K.paged_decode(live, n, d["num_heads"], d["num_kv_heads"], d["head_dim"]), peaks)
                for n, live in info["ticks"]) * d["num_layers"]
    return 100.0 * least / (sum(e.dur for e in calls) / 1e9)
