"""Serving driver: continuous-batching engine over the paged KV cache.

Generates a synthetic mixed-length request load, optionally promotes a
trained NoLoCo checkpoint (one replica's θ or φ), and serves it through
:class:`repro.serve.ServeEngine` — chunked prefill interleaved with decode,
request-driven admit/evict scheduling, per-request sampling temperatures,
dispatched Pallas/jnp decode kernels.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
        --requests 8 --max-batch 4 --prompt-lens 4,12 --gen-lens 8,24

    # serve a trained checkpoint (replica 1's outer weights):
    ... --ckpt /tmp/run_ck --replica 1 --weights phi

    # ensemble speculative decode: replica 2 drafts for replica 1
    ... --ckpt /tmp/run_ck --replica 1 --spec-decode --draft-replica 2

JSONL telemetry (--log-jsonl): run_start / streamed ``token`` events
(--stream-every; batched host drains, never per-token syncs) / admit-free
`finish` per request (ttft_s, tokens, spec stats) / run_end (tokens_per_s,
p50/p99 latency, acceptance, parity when --verify).  The final stdout line
is the run_end summary JSON.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.configs import registry
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import add_engine_flags, kernel_config_from_args
from repro.models import model as M
from repro.models.common import values_of
from repro.serve import (
    Request,
    ServeConfig,
    ServeEngine,
    SpecServeEngine,
    promote,
    truncate_layers,
)


def synth_requests(
    n: int, vocab: int, prompt_lens: list[int], gen_lens: list[int],
    temps: list[float], seed: int,
) -> list[Request]:
    """Synthetic load: prompts/gen budgets cycled from the given buckets so a
    small ``--requests`` already exercises mixed lengths (the workload where
    continuous batching beats static batching)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        pl = prompt_lens[i % len(prompt_lens)]
        gl = gen_lens[i % len(gen_lens)]
        prompt = rng.integers(0, vocab, size=(pl,)).tolist()
        reqs.append(
            Request(rid=i, prompt=[int(t) for t in prompt], max_new=gl,
                    temperature=temps[i % len(temps)])
        )
    return reqs


def serve_run(
    params, cfg, scfg: ServeConfig, requests: list[Request],
    *, verify: bool = False, log=None, draft=None, spec_k: int = 4,
    stream_every: int = 0,
) -> dict:
    """Run one serving load; returns the run_end summary dict.

    ``draft=(draft_params, draft_cfg)`` switches on speculative decode.
    ``--verify`` always re-decodes solo on a PLAIN engine, so with spec on it
    checks the strongest claim: speculative output == target-only output."""
    if draft is not None:
        engine = SpecServeEngine(params, cfg, scfg, draft[0], draft[1], spec_k=spec_k)
    else:
        engine = ServeEngine(params, cfg, scfg)
    token_cb = None
    if log and stream_every:
        def token_cb(rid, index, token, t):
            log({"event": "token", "rid": rid, "index": index,
                 "token": token, "t": round(t, 6)})
    t0 = time.perf_counter()
    finished = engine.run(
        [dataclasses.replace(r) for r in requests],
        token_cb=token_cb, drain_every=stream_every,
    )
    wall = time.perf_counter() - t0
    gen_tokens = sum(len(f.tokens) for f in finished)
    ttfts = sorted(f.ttft_s for f in finished)
    summary = {
        "event": "run_end",
        "policy": scfg.policy,
        "prefill_chunk": scfg.prefill_chunk,
        "requests": len(finished),
        "gen_tokens": gen_tokens,
        "wall_s": round(wall, 4),
        "tokens_per_s": round(gen_tokens / max(wall, 1e-9), 2),
        "decode_steps": engine.decode_steps,
        "ttft_p50_s": round(float(np.percentile(ttfts, 50)), 4),
        "ttft_p99_s": round(float(np.percentile(ttfts, 99)), 4),
    }
    if draft is not None:
        summary["spec_k"] = spec_k
        summary["spec_rounds"] = engine.spec_rounds
        summary["accept_rate"] = round(engine.accept_rate, 4)
    if engine.decode_step_times:
        st = np.asarray(engine.decode_step_times)
        summary["step_p50_s"] = round(float(np.percentile(st, 50)), 5)
        summary["step_p99_s"] = round(float(np.percentile(st, 99)), 5)
    if log:
        for f in sorted(finished, key=lambda f: f.rid):
            ev = {"event": "finish", "rid": f.rid, "prompt_len": len(f.prompt),
                  "gen_len": len(f.tokens), "ttft_s": round(f.ttft_s, 4),
                  "tokens": f.tokens}
            ev.update(f.stats)
            log(ev)
    if verify:
        batched = {f.rid: f.tokens for f in finished}
        mismatches = 0
        for r in requests:
            solo = ServeEngine(params, cfg, scfg)
            [f] = solo.run([dataclasses.replace(r)])
            if f.tokens != batched[r.rid]:
                mismatches += 1
        summary["verify_requests"] = len(requests)
        summary["verify_mismatches"] = mismatches
        summary["parity"] = mismatches == 0
    return summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the reduced (smoke) variant of the arch")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode slots (concurrent requests)")
    ap.add_argument("--pages", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prompt-lens", default="4,12,24",
                    help="comma-separated prompt-length buckets, cycled")
    ap.add_argument("--gen-lens", default="8,16,32",
                    help="comma-separated generation budgets, cycled")
    ap.add_argument("--temps", default="0.0",
                    help="comma-separated sampling temperatures, cycled (0=greedy)")
    ap.add_argument("--policy", default="continuous", choices=["continuous", "static"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None,
                    help="promote a training checkpoint from this directory")
    ap.add_argument("--step", type=int, default=None,
                    help="checkpoint step (default: latest)")
    ap.add_argument("--replica", type=int, default=0,
                    help="which NoLoCo replica to promote")
    ap.add_argument("--weights", default="theta", choices=["theta", "phi"],
                    help="promote the inner weights (theta) or outer anchor (phi)")
    ap.add_argument("--verify", action="store_true",
                    help="re-decode each request solo and assert exact match")
    ap.add_argument("--sync-each-step", action="store_true",
                    help="block per decode step for per-token latency stats")
    ap.add_argument("--prefill-chunk", type=int, default=32,
                    help="chunked-prefill width; 0 = single-shot baseline")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="max prefill tokens per tick (0 = unlimited)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="ensemble speculative decode (draft replica/slice)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative round width (draft steps per round)")
    ap.add_argument("--draft-replica", type=int, default=None,
                    help="promote this replica as the draft (needs --ckpt)")
    ap.add_argument("--draft-layers", type=int, default=None,
                    help="depth-truncate the target to this many layers as "
                         "the draft (default: half, when no --draft-replica)")
    ap.add_argument("--stream-every", type=int, default=0,
                    help="drain streamed `token` JSONL events every N ticks "
                         "(0 = tokens only surface at request finish)")
    add_engine_flags(ap)
    args = ap.parse_args()
    enable_compile_cache()
    kcfg = kernel_config_from_args(args)

    cfg = registry.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(dtype="float32", remat=False)
    cfg = dataclasses.replace(cfg, kernels=kcfg)

    promo_info = None
    if args.ckpt:
        params, promo_info = promote(
            args.ckpt, step=args.step, replica=args.replica, source=args.weights
        )
        params = jax.tree.map(jax.numpy.asarray, params)
    else:
        params = values_of(M.init_params(jax.random.PRNGKey(args.seed), cfg))

    jsonl = open(args.log_jsonl, "a") if args.log_jsonl else None

    def log(ev: dict) -> None:
        if jsonl:
            jsonl.write(json.dumps(ev) + "\n")
            jsonl.flush()

    prompt_lens = [int(x) for x in args.prompt_lens.split(",")]
    gen_lens = [int(x) for x in args.gen_lens.split(",")]
    temps = [float(x) for x in args.temps.split(",")]
    scfg = ServeConfig(
        max_slots=args.max_batch, num_pages=args.pages, page_size=args.page_size,
        max_new_cap=max(gen_lens), policy=args.policy,
        sync_each_step=args.sync_each_step,
        prefill_chunk=args.prefill_chunk, prefill_budget=args.prefill_budget,
    )
    draft = None
    draft_info = None
    if args.spec_decode:
        if args.draft_replica is not None:
            if not args.ckpt:
                ap.error("--draft-replica needs --ckpt")
            dparams, dinfo = promote(
                args.ckpt, step=args.step, replica=args.draft_replica,
                source=args.weights,
            )
            draft = (jax.tree.map(jax.numpy.asarray, dparams), cfg)
            draft_info = {"kind": "replica", **dinfo}
        else:
            n = args.draft_layers or max(1, cfg.num_layers // 2)
            draft = truncate_layers(params, cfg, n)
            draft_info = {"kind": "truncated", "layers": n}
    requests = synth_requests(
        args.requests, cfg.vocab_size, prompt_lens, gen_lens, temps, args.seed
    )
    log({"event": "run_start", "arch": cfg.name, "policy": args.policy,
         "requests": args.requests, "max_batch": args.max_batch,
         "pages": args.pages, "page_size": args.page_size,
         "prefill_chunk": args.prefill_chunk,
         "spec_decode": bool(args.spec_decode), "draft": draft_info,
         "impl": kcfg.resolved_impl(), "promoted": promo_info})

    summary = serve_run(
        params, cfg, scfg, requests, verify=args.verify, log=log,
        draft=draft, spec_k=args.spec_k, stream_every=args.stream_every,
    )
    if draft_info:
        summary["draft"] = draft_info
    summary["arch"] = cfg.name
    summary["impl"] = kcfg.resolved_impl()
    if promo_info:
        summary["promoted"] = promo_info
    log(summary)
    if jsonl:
        jsonl.close()
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
