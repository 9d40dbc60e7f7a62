"""Model FLOP/s utilization of the inner step on the device: model FLOPs per
token (6 x matmul parameters with the head, plus causal attention; no
recompute) times the tokens of one replica's step, for every run of the
inner-step program ``jit_step`` on a chip, over the device time of those
runs times the bf16 peak."""

from bench.core import readers as R
from bench.flops import dense_lm as F

PROGRAM = "jit_step"


def read(tr, info, peaks):
    runs = [m for d in tr.devices for m in R.program(d, PROGRAM)]
    if not runs:
        return None
    t = info["traffic"]
    tokens = t["per_replica_batch"] * t["seq"]
    flops = F.train_flops_per_token(info["dims"], t["seq"]) * tokens * len(runs)
    return 100.0 * flops / (sum(m.dur for m in runs) / 1e9 * peaks["bf16_flops"])
