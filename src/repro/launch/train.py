"""Stacked-simulation training CLI — a thin shell over the unified engine.

    PYTHONPATH=src python -m repro.launch.train --arch paper-small-125m --reduced \
        --method noloco --replicas 8 --steps 200 \
        --ckpt-dir /tmp/run0 --ckpt-every 50 --resume --log-jsonl /tmp/run0.jsonl

Simulation mode (CPU-friendly): replicas are a stacked leading axis; the full
NoLoCo machinery (inner AdamW, gossip outer step with random pairings,
weight-std tracking) runs exactly as in the paper.  ``--method`` selects
noloco / diloco / fsdp (grad all-reduce every step) / none (independent runs —
the §5.2 baseline).

``run_training`` is the library entry benchmarks and examples share; the step
loop, eval cadence, telemetry and checkpoint/resume all live in
:mod:`repro.train` (see DESIGN.md §2) — this module only assembles the
program + loader and forwards the knobs.
"""

from __future__ import annotations

import argparse
import json
from typing import Any

import dataclasses

from repro.comm import CommConfig
from repro.configs import registry
from repro.core import OuterConfig, TrainerConfig
from repro.data import LoaderConfig
from repro.kernels import dispatch as kernel_dispatch
from repro.kernels.dispatch import KernelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, warmup_cosine
from repro.train import GossipProgram, LoopConfig, make_loop


def method_config(
    method: str,
    *,
    inner_lr: float,
    total_steps: int,
    warmup: int = 100,
    inner_steps: int | None = None,
    seed: int = 0,
    comm: CommConfig | None = None,
    kernels: KernelConfig | None = None,
    stale: str = "naive",
) -> TrainerConfig:
    """Paper §4 hyper-parameters: β=0.7 both; NoLoCo α=0.5, m=50;
    DiLoCo α=0.3, m=100; inner AdamW + clip 1.0 + warmup-cosine.
    ``comm`` selects the gossip wire codec / payload fusing (repro.comm);
    ``kernels`` the outer-update implementation (repro.kernels.dispatch);
    ``stale`` the asynchronous stale-Δ rule (``"naive"`` applies a delayed Δ
    undiscounted, ``"momentum"`` scales it by 1/(1+τ) — NoLoCo-only, inert
    on synchronous runs)."""
    sched = warmup_cosine(inner_lr, total_steps, warmup_steps=warmup)
    inner = AdamWConfig(lr=sched, weight_decay=0.1, clip_norm=1.0)
    if method == "noloco":
        outer = OuterConfig(method="noloco", alpha=0.5, beta=0.7,
                            inner_steps=inner_steps or 50, seed=seed,
                            stale=stale)
    elif method == "diloco":
        outer = OuterConfig(method="diloco", alpha=0.3, beta=0.7,
                            inner_steps=inner_steps or 100, seed=seed)
    elif method in ("fsdp", "none"):
        outer = OuterConfig(method="none", inner_steps=10**9)
    else:  # pragma: no cover
        raise ValueError(method)
    return TrainerConfig(outer=outer, inner=inner, comm=comm or CommConfig(),
                         kernels=kernels or KernelConfig(),
                         sync_grads=method == "fsdp")


def run_training(
    cfg: ModelConfig,
    *,
    method: str = "noloco",
    replicas: int = 4,
    per_replica_batch: int = 4,
    seq_len: int = 128,
    steps: int = 100,
    total_steps: int | None = None,
    inner_lr: float = 3e-3,
    inner_steps: int | None = None,
    warmup: int | None = None,
    eval_every: int = 0,
    eval_batches: int = 2,
    seed: int = 0,
    ckpt_dir: str | None = None,
    ckpt_every: int = 0,
    resume: bool = False,
    log: bool = False,
    log_jsonl: str | None = None,
    codec: str = "none",
    fuse: bool = True,
    streams: int = 1,
    overlap: bool = False,
    impl: str = "auto",
    interpret: bool | None = None,
) -> dict[str, Any]:
    """Train; returns loss/weight-std trajectories and final eval loss.

    ``codec``/``fuse`` configure the gossip wire (repro.comm.CommConfig): the
    stacked simulation applies lossy codecs to the partner's exchanged values
    exactly as the distributed ppermute path would.  ``streams`` partitions
    the outer payload into that many streams synced on staggered round
    offsets (streaming outer steps, DESIGN.md §2); ``overlap`` adds the §3.2
    φ-prefetch so only each stream's Δ exchange blocks.  ``resume`` restores the
    latest checkpoint under ``ckpt_dir`` (θ/φ/δ/opt/step counters + loader
    fast-forward + PRNG keys) and continues the exact trajectory.

    ``total_steps`` fixes the LR-schedule horizon independently of ``steps``
    (default: equal).  Runs that will be interrupted and resumed must pin it,
    so stopping early does not change the schedule the checkpoint embeds.

    ``impl``/``interpret`` select the kernel implementation for the model
    forward AND the fused outer update (repro.kernels.dispatch), threaded
    explicitly — this library entry never touches the process-wide dispatch
    default (the CLI installs that itself via kernel_config_from_args)."""
    n_eval = eval_batches
    kcfg = KernelConfig(impl=impl, interpret=interpret)
    cfg = dataclasses.replace(cfg, kernels=kcfg)
    tcfg = method_config(
        method, inner_lr=inner_lr, total_steps=total_steps or steps,
        warmup=warmup if warmup is not None else max((total_steps or steps) // 10, 1),
        inner_steps=inner_steps, seed=seed,
        comm=CommConfig(codec=codec, fuse=fuse, streams=streams,
                        overlap=overlap),
        kernels=kcfg,
    )
    program = GossipProgram(cfg, tcfg, replicas=replicas, seed=seed)
    loop = make_loop(
        program,
        LoaderConfig(
            vocab_size=cfg.vocab_size, seq_len=seq_len,
            per_replica_batch=per_replica_batch, replicas=replicas, seed=seed,
        ),
        LoopConfig(
            steps=steps, eval_every=eval_every, seed=seed,
            ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, resume=resume,
            log_jsonl=log_jsonl, log=log, run_name=f"{cfg.name}-{method}",
        ),
        n_eval=n_eval,
    )
    return loop.run()


def add_engine_flags(ap: argparse.ArgumentParser) -> None:
    """The engine flags shared by every runtime's CLI (see DESIGN.md §2)."""
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save every N steps (0: only a final save)")
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint under --ckpt-dir")
    ap.add_argument("--log-jsonl", default=None,
                    help="append one JSON telemetry event per line to this file")
    ap.add_argument("--impl", default="auto", choices=["auto", "pallas", "jnp"],
                    help="kernel implementation (repro.kernels.dispatch): "
                         "auto = Pallas on TPU, jnp elsewhere")
    ap.add_argument("--interpret", action="store_const", const=True, default=None,
                    help="force Pallas interpret mode (default: auto — "
                         "interpret off-TPU, compiled on TPU)")


def kernel_config_from_args(args) -> KernelConfig:
    """KernelConfig from the shared --impl/--interpret flags; also installs
    it as the process-wide dispatch default (codec paths etc.)."""
    kcfg = KernelConfig(impl=args.impl, interpret=args.interpret)
    kernel_dispatch.set_default_config(kcfg)
    return kcfg


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-small-125m")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (smoke) variant of the arch")
    ap.add_argument("--method", default="noloco",
                    choices=["noloco", "diloco", "fsdp", "none"])
    ap.add_argument("--replicas", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--inner-steps", type=int, default=None)
    ap.add_argument("--codec", default="none",
                    choices=["none", "fp16", "bf16", "int8"],
                    help="gossip wire codec (repro.comm)")
    ap.add_argument("--no-fuse", action="store_true",
                    help="per-leaf exchange instead of one fused buffer per dtype")
    ap.add_argument("--stream-count", type=int, default=1,
                    help="streaming outer steps: partition the payload into N "
                         "streams synced on staggered round offsets")
    ap.add_argument("--overlap", action="store_true",
                    help="§3.2 φ-prefetch overlap (auto-enabled by "
                         "--stream-count > 1)")
    ap.add_argument("--eval-every", type=int, default=25)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    add_engine_flags(ap)
    args = ap.parse_args()
    enable_compile_cache()
    kernel_config_from_args(args)  # process-wide default (codec paths etc.)

    cfg = registry.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(vocab_size=min(cfg.vocab_size, 512), remat=False, dtype="float32")
    res = run_training(
        cfg, method=args.method, replicas=args.replicas,
        per_replica_batch=args.batch, seq_len=args.seq, steps=args.steps,
        inner_lr=args.lr, inner_steps=args.inner_steps,
        eval_every=args.eval_every, seed=args.seed,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every, resume=args.resume,
        log=True, log_jsonl=args.log_jsonl,
        codec=args.codec, fuse=not args.no_fuse,
        streams=args.stream_count,
        overlap=args.overlap or args.stream_count > 1,
        impl=args.impl, interpret=args.interpret,
    )
    summary = {
        "arch": cfg.name, "method": args.method, "codec": args.codec,
        "stream_count": res.get("stream_count", 1),
        "blocking_fraction": round(res["blocking_fraction"], 4),
        "final_train_loss": res["losses"][-1] if res["losses"] else None,
        "final_eval": res["evals"][-1][1] if res["evals"] else None,
        "final_weight_std": res["final_weight_std"],
        "tokens_per_s": round(res["tokens_per_s"], 1),
        "wall_s": round(res["wall_s"], 1),
    }
    print(json.dumps(summary))
    if args.out:
        res.pop("state")
        with open(args.out, "w") as f:
            json.dump({k: v for k, v in res.items()}, f)


if __name__ == "__main__":
    main()
