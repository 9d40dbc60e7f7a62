#!/usr/bin/env python3
"""Find the knee of a serving cell: the highest offered rate at which the
engine keeps up (no growing backlog).  One process: the engine is built and
warmed once, then each rate runs the cell's open loop for ``--seconds``.

    python3 bench/tools/sweep.py --workload serve.minitron8b.chat \
        --rates 1,2,4,6,8 --seconds 20 [--serve max_slots=16,num_pages=1024]

Per rate it prints one JSON line: the requests offered and finished within
the window, how many were still queued or running when it closed, the
latency tails, tokens/s, and the gaps between tokens at several
percentiles (where the gaps of ticks that also prefill begin).  A rate is sustained when the requests still
open at the close are few and the time to first token does not grow through
the window (its last-quarter median against its first-quarter median).
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.core import harness as H  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--serve", default="", help="ServeConfig overrides k=v,k=v")
    args = ap.parse_args()
    H.enable_cache()
    cell = H.load("cells", args.workload)
    for kv in filter(None, args.serve.split(",")):
        k, v = kv.split("=")
        cell["serve"][k] = int(v)
    devs = H.require_devices(cell["chips"])
    from bench.drivers import serve as S
    from bench.gen import open_loop as OL

    cfgfile = H.load("configs", cell["config"])
    traffic = H.load("traffic", cell["traffic"])
    engine, dims, _ = S.build(cell, cfgfile, args.seed)
    S.warm(engine, cell, traffic, args.seed, dims["vocab_size"])
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        tr = dict(traffic, rate_per_s=rate)
        reqs = OL.schedule(args.seed + i, args.seconds, tr, dims["vocab_size"], rid0=i * 100_000)
        loop = S.Loop(engine)
        wall = loop.serve(reqs, args.seconds + S.GRACE_S)
        m = S.metrics(loop.recs, args.seconds)
        recs = sorted(loop.recs.values(), key=lambda r: r["due"])
        q = max(1, len(recs) // 4)
        ttft = lambda rs: statistics.median([(r["stamps"][0] - r["due"]) if r["stamps"] else 1e9 for r in rs])
        open_at_close = sum(1 for r in recs if not r["stamps"] or r["stamps"][-1] > args.seconds)
        gaps = [(b - a) * 1e3 for r in recs for a, b in zip(r["stamps"], r["stamps"][1:]) if b <= args.seconds]
        print(json.dumps({
            "rate": rate, "serve": cell["serve"], "offered": len(recs), "open_at_close": open_at_close,
            "ttft_first_quarter_s": ttft(recs[:q]), "ttft_last_quarter_s": ttft(recs[-q:]),
            "drain_s": wall - args.seconds, **{k: v for k, v in m.items()},
            "itl_ms_at": {q: H.percentile(gaps, q) for q in (85, 90, 93, 95, 97, 99)},
            "memory_peak_bytes": H.device_record(devs)["memory_peak_bytes"],
        }), flush=True)


if __name__ == "__main__":
    main()
