#!/usr/bin/env python3
"""Readings that set the limits of a cell's check: the sound program's, the
control's (the reference computed a precision below the configuration's:
float8 e4m3 products for a bfloat16 model) and each fault's, on several seeds
at the cell's own size.  Run on the chip; one JSON line per seed and side.

    python3 bench/tools/control.py --workload train.stablelm2.r1 --seeds 1,2,3 [--program]
    python3 bench/tools/control.py --workload serve.minitron8b.chat --seeds 1,2,3 --seconds 30

Training: the float32 reference against the control and against the
reference with two faults planted (half of each batch left out, the mean
taken over the rest; the exchange between chips left out, four chips only);
with ``--program`` also the program itself, as a run does.  A step that
returns its state unchanged reads 1 on the change and outer numbers by
their definition and needs no run.  Serving: per seed, the program serves
the cell's traffic for ``--seconds``, and its sample is read three ways: the
served tokens, the control's first choice at each position, and the served
tokens with one altered.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.core import harness as H  # noqa: E402


def _num(d: dict) -> dict:
    return {k: v for k, v in d.items() if isinstance(v, (int, float))}


def train(cell, cfgfile, traffic, seeds, devs, program: bool):
    from bench.core import weights as W
    from bench.drivers import train as D

    dims = D.ref_dims(cfgfile)
    R, B = cell["replicas"], traffic["per_replica_batch"]
    for seed in seeds:
        t0 = time.time()
        if program:
            su = D.Setup(cell, cfgfile, traffic, seed, devs)
            prog = D.setup_and_check_steps(su)
            del su
            gc.collect()
        words = W.seed_words(seed)
        batches = W.token_batches(seed, traffic["batches"], R * B, traffic["seq"], dims["vocab_size"])
        base = D.reference(cell, dims, words, batches, R, B, seed, devs)
        rows = {}
        if program:
            rows["program"] = D.compare(prog, base)
        rows["control_fp8"] = D.compare(D.reference(cell, dims, words, batches, R, B, seed, devs, precision="fp8"), base)
        rows["fault_half_batch"] = D.compare(D.reference(cell, dims, words, batches, R, B, seed, devs, half_batch=True), base)
        if R > 1:
            rows["fault_no_exchange"] = D.compare(D.reference(cell, dims, words, batches, R, B, seed, devs, exchange=False), base)
        for side, c in rows.items():
            print(json.dumps({"seed": seed, "side": side, **_num(c),
                              "leaves": {k: c[k] for k in c if k.endswith("_leaf")}}), flush=True)
        print(json.dumps({"seed": seed, "seconds": time.time() - t0}), flush=True)


def serve(cell, cfgfile, traffic, seeds, devs, seconds):
    from bench.drivers import serve as S
    from bench.gen import open_loop as OL
    from bench.ref import dense_lm as ref

    for seed in seeds:
        t0 = time.time()
        engine, dims, words = S.build(cell, cfgfile, seed)
        S.warm(engine, cell, traffic, seed, dims["vocab_size"])
        reqs = OL.schedule(seed, seconds, traffic, dims["vocab_size"])
        loop = S.Loop(engine)
        loop.serve(reqs, seconds + S.GRACE_S)
        recs = loop.recs
        del engine, loop
        gc.collect()
        chk = cell["check"]
        rids = S.sample(recs, seed, chk["min_tokens"], chk["max_requests"])
        seqs, picks, served = S.ref_inputs(recs, rids)
        cap = cell["serve"]["max_new_cap"]
        base = ref.forward_logits(words, dims, seqs, picks, "f32", cap)
        prog = S.widest_gap(words, dims, seqs, picks, served, cap)
        ctrl = S.widest_gap(words, dims, seqs, picks, served, cap, precision="fp8", against=base)
        altered = [s.copy() for s in served]
        altered[0][len(altered[0]) // 2] = (altered[0][len(altered[0]) // 2] + 1) % dims["vocab_size"]
        fault = S.widest_gap(words, dims, seqs, picks, altered, cap)
        n = int(sum(len(s) for s in served))
        for side, v in (("program", prog), ("control_fp8", ctrl), ("fault_token_altered", fault)):
            print(json.dumps({"seed": seed, "side": side, "logit_gap": v, "checked_tokens": n,
                              "checked_requests": len(rids)}), flush=True)
        print(json.dumps({"seed": seed, "seconds": time.time() - t0}), flush=True)
        del base
        gc.collect()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--program", action="store_true")
    args = ap.parse_args()
    H.enable_cache()
    cell = H.load("cells", args.workload)
    devs = H.require_devices(cell["chips"])
    cfgfile = H.load("configs", cell["config"])
    traffic = H.load("traffic", cell["traffic"])
    seeds = [int(s) for s in args.seeds.split(",")]
    if cell["driver"] == "train":
        train(cell, cfgfile, traffic, seeds, devs, args.program)
    else:
        serve(cell, cfgfile, traffic, seeds, devs, args.seconds)


if __name__ == "__main__":
    main()
