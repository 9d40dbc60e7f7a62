"""Share of its roofline that the flash-attention forward kernel reaches:
its least time per call (the larger of FLOPs over the bf16 peak and bytes
over HBM bandwidth, from the training shapes) times the calls, over the
summed device time of those calls."""

from bench.core import readers as R
from bench.flops import attention_kernels as K

NEEDLE = "flash"


def read(tr, info, peaks):
    d, t = info["dims"], info["traffic"]
    flops, nbytes = K.flash_causal(t["per_replica_batch"], t["seq"], d["num_heads"], d["num_kv_heads"], d["head_dim"])
    least = R.least_time(flops, nbytes, peaks)
    calls = [e for dev in tr.devices for e in dev.ops if NEEDLE in e.name]
    if not calls:
        return None
    return 100.0 * least * len(calls) / (sum(e.dur for e in calls) / 1e9)
