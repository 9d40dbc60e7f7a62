"""Open-loop request schedules.

The traffic file fixes the rate, the length distributions and the order:
prompt lengths, answer lengths and inter-arrival gaps are quantiles of the
stated distributions, shuffled once by the file's ``order_seed``.  So every
run offers the same sizes at the same times, and ``--seed`` makes the token
ids of the prompts (and through them the weights' answers).  With some tens
of requests in a window, the order decides the latency tail: a run-seeded
order made the 90th percentile of time to first token swing 2x between seeds
of the same code on a TPU v5e, so the order is data of the mix and not of
the run.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np


@dataclass
class Req:
    rid: int
    due: float          # seconds after the window opens
    prompt: np.ndarray  # int32 token ids
    max_new: int


def _lognormal_quantiles(n: int, spec: dict) -> np.ndarray:
    from statistics import NormalDist

    nd = NormalDist()
    q = [(i + 0.5) / n for i in range(n)]
    x = np.asarray([spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(p)) for p in q])
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _exp_quantiles(n: int, mean: float) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) * mean


def count(traffic: dict, seconds: float) -> int:
    return max(1, int(round(traffic["rate_per_s"] * seconds)))


def schedule(seed: int, seconds: float, traffic: dict, vocab: int, rid0: int = 0) -> list[Req]:
    n = count(traffic, seconds)
    order = np.random.default_rng(traffic["order_seed"])
    prompts = order.permutation(_lognormal_quantiles(n, traffic["prompt"]))
    outs = order.permutation(_lognormal_quantiles(n, traffic["output"]))
    gaps = order.permutation(_exp_quantiles(n, 1.0 / traffic["rate_per_s"]))
    rng = np.random.default_rng(seed)
    # the gaps are scaled so that the last request is due just inside the window
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    if n > 1 and due[-1] > 0:
        due *= min(1.0, (seconds * (n - 0.5) / n) / due[-1])
    return [
        Req(rid0 + i, float(due[i]), rng.integers(0, vocab, size=int(prompts[i]), dtype=np.int32),
            int(outs[i]))
        for i in range(n)
    ]
