"""Routed-pipeline training CLI — the paper's COMPLETE method (§3.1 + §3.2):
dynamic microbatch routing between stage replicas AND the gossip outer
optimizer, driven by the unified engine (:mod:`repro.train`).

    PYTHONPATH=src python -m repro.launch.train_pipeline --arch paper-small-125m \
        --reduced --stages 2 --replicas 4 --method noloco --steps 100 \
        --ckpt-dir /tmp/pipe0 --ckpt-every 25 --resume --log-jsonl /tmp/pipe0.jsonl

``--method none`` is the §5.2 routing-only baseline (no outer step);
``--routing fixed`` is classic pipelining.  Cross-replica weight std is
reported at eval cadence — with ``noloco`` it must stay well below the
``none`` baseline (tested in tests/test_train_engine.py).
"""

from __future__ import annotations

import argparse
import json

from repro.comm import CommConfig
from repro.configs import registry
from repro.core.outer import OuterConfig
from repro.data import LoaderConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.optim import AdamWConfig
from repro.pipeline import PipelineTrainer
from repro.train import LoopConfig, PipelineProgram, make_loop


def main() -> None:
    from repro.launch.train import add_engine_flags, kernel_config_from_args

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-small-125m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--stages", type=int, default=2)
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--method", default="noloco",
                    choices=["noloco", "diloco", "none"])
    ap.add_argument("--routing", default="random", choices=["random", "fixed"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--inner-steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--codec", default="none",
                    choices=["none", "fp16", "bf16", "int8"])
    ap.add_argument("--eval-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    add_engine_flags(ap)
    args = ap.parse_args()
    enable_compile_cache()

    import dataclasses

    kcfg = kernel_config_from_args(args)
    cfg = registry.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(vocab_size=min(cfg.vocab_size, 512), remat=False,
                          dtype="float32")
    cfg = dataclasses.replace(cfg, kernels=kcfg)
    if cfg.num_layers % args.stages:
        raise SystemExit(
            f"num_layers={cfg.num_layers} must divide into --stages={args.stages}"
        )

    outer = None
    if args.method != "none":
        outer = OuterConfig(method=args.method, inner_steps=args.inner_steps,
                            seed=args.seed)
    trainer = PipelineTrainer(
        cfg, num_stages=args.stages, replicas=args.replicas,
        inner=AdamWConfig(lr=args.lr, weight_decay=0.0),
        routing=args.routing, outer=outer,
        comm=CommConfig(codec=args.codec), kernel_cfg=kcfg, seed=args.seed,
    )

    loop = make_loop(
        PipelineProgram(trainer),
        LoaderConfig(
            vocab_size=cfg.vocab_size, seq_len=args.seq,
            per_replica_batch=args.batch, replicas=args.replicas, seed=args.seed,
        ),
        LoopConfig(
            steps=args.steps, eval_every=args.eval_every, seed=args.seed,
            ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
            resume=args.resume, log_jsonl=args.log_jsonl, log=True,
            run_name=f"{cfg.name}-pipe-{args.method}",
        ),
    )
    res = loop.run()
    print(json.dumps({
        "arch": cfg.name, "stages": args.stages, "replicas": args.replicas,
        "method": args.method, "routing": args.routing,
        "final_loss": res["losses"][-1] if res["losses"] else None,
        "final_weight_std": res["final_weight_std"],
        "outer_syncs": res["outer_syncs"],
        "comm_bytes": res["comm_bytes"],
        "tokens_per_s": round(res["tokens_per_s"], 1),
        "wall_s": round(res["wall_s"], 1),
    }))


if __name__ == "__main__":
    main()
