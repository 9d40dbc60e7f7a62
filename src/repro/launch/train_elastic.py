"""Elastic-gossip training CLI: the stacked runtime under a fault plan.

    PYTHONPATH=src python -m repro.launch.train_elastic \\
        --arch paper-small-125m --reduced --replicas 8 --steps 50 \\
        --inner-steps 5 --fault-plan plan.json --eval-every 10

``plan.json`` is a :class:`repro.sim.FaultPlan` (see that module for the
schema): node dropout, rejoin-with-warm-start, stragglers, partitions — all
replayed deterministically against the production gossip outer step, so
"no blocking collective" is exercised as a fault-tolerance property, not
just a latency argument.  Without ``--fault-plan`` this is a healthy run of
the same program (the baseline the scenario compares against).

``run_elastic_training`` is the library entry the tests and the CI smoke
job share; it returns the engine's result dict plus the simulator's
round-participation history.
"""

from __future__ import annotations

import argparse
import json
from typing import Any

from repro.comm import CommConfig
from repro.configs import registry
from repro.data import LoaderConfig
from repro.kernels.dispatch import KernelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import add_engine_flags, kernel_config_from_args, method_config
from repro.models.config import ModelConfig
from repro.sim import FaultPlan, SimCluster
from repro.train import GossipProgram, LoopConfig, make_loop

import dataclasses


def run_elastic_training(
    cfg: ModelConfig,
    plan: FaultPlan,
    *,
    method: str = "noloco",
    replicas: int = 8,
    per_replica_batch: int = 2,
    seq_len: int = 64,
    steps: int = 50,
    total_steps: int | None = None,
    inner_lr: float = 3e-3,
    inner_steps: int = 5,
    eval_every: int = 0,
    eval_batches: int = 2,
    seed: int = 0,
    ckpt_dir: str | None = None,
    ckpt_every: int = 0,
    resume: bool = False,
    log: bool = False,
    log_jsonl: str | None = None,
    codec: str = "none",
    stream_count: int = 1,
    overlap: bool | None = None,
    impl: str = "auto",
    interpret: bool | None = None,
    reassign_data: bool = False,
    stale: str = "naive",
    async_clock: bool | None = None,
) -> dict[str, Any]:
    """Train under ``plan``; returns the engine result dict plus
    ``rounds`` (the simulator's per-round participation history) and the
    final membership.

    ``reassign_data`` redistributes dropped replicas' loader streams over
    survivors (:func:`repro.core.elastic.stream_assignment` — deterministic,
    resume-safe); the default keeps the seed behavior of skipping them.

    ``stream_count`` partitions the outer payload into staggered streams
    (streaming outer steps); ``overlap`` adds the §3.2 φ-prefetch — it
    defaults ON when ``stream_count > 1`` and composes with churn through
    the membership-epoch fallback (a stream whose pre-send pairing went
    stale blocks once; the other streams stay overlapped).

    ``async_clock`` gives every replica its own round clock (per-replica
    step rates from the plan's ``rate`` events; merged sync ticks exchange
    stale Δs instead of blocking on stragglers — DESIGN.md §7).  It defaults
    ON whenever the plan carries rate events; ``stale`` selects the stale-Δ
    rule (``"naive"`` / ``"momentum"``)."""
    if overlap is None:
        overlap = stream_count > 1
    kcfg = KernelConfig(impl=impl, interpret=interpret)
    cfg = dataclasses.replace(cfg, kernels=kcfg)
    tcfg = method_config(
        method, inner_lr=inner_lr, total_steps=total_steps or steps,
        warmup=max((total_steps or steps) // 10, 1), inner_steps=inner_steps,
        seed=seed,
        comm=CommConfig(codec=codec, streams=stream_count, overlap=overlap),
        kernels=kcfg, stale=stale,
    )
    program = GossipProgram(cfg, tcfg, replicas=replicas, seed=seed)
    sim = SimCluster(program, plan, reassign_data=reassign_data,
                     async_clock=async_clock)
    loop = make_loop(
        sim,
        LoaderConfig(
            vocab_size=cfg.vocab_size, seq_len=seq_len,
            per_replica_batch=per_replica_batch, replicas=replicas, seed=seed,
        ),
        LoopConfig(
            steps=steps, eval_every=eval_every, seed=seed,
            ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, resume=resume,
            log_jsonl=log_jsonl, log=log, run_name=f"{cfg.name}-elastic",
        ),
        n_eval=eval_batches,
    )
    res = loop.run()
    res["rounds"] = sim.rounds()
    res["fault_history"] = sim.history
    res["membership"] = {
        "epoch": sim.membership.epoch,
        "active": list(sim.membership.active_ids),
    }
    return res


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-small-125m")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (smoke) variant of the arch")
    ap.add_argument("--method", default="noloco", choices=["noloco", "diloco"])
    ap.add_argument("--fault-plan", default=None,
                    help="JSON FaultPlan (repro.sim.faults); omit for a healthy run")
    ap.add_argument("--replicas", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--total-steps", type=int, default=None,
                    help="LR-schedule horizon (pin it for interrupted runs "
                         "that will resume; default: --steps)")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--inner-steps", type=int, default=5)
    ap.add_argument("--codec", default="none",
                    choices=["none", "fp16", "bf16", "int8"])
    ap.add_argument("--stream-count", type=int, default=1,
                    help="streaming outer steps: partition the payload into N "
                         "streams synced on staggered round offsets "
                         "(implies the §3.2 overlap when > 1)")
    ap.add_argument("--eval-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reassign-data", action="store_true",
                    help="redistribute dropped replicas' loader streams over "
                         "survivors (default: skip them)")
    ap.add_argument("--stale", default="naive", choices=["naive", "momentum"],
                    help="async stale-Δ rule: naive applies a delayed Δ as-is, "
                         "momentum discounts it by 1/(1+τ)")
    ap.add_argument("--async-clock", action="store_true", default=None,
                    help="per-replica round clocks (auto-on when the fault "
                         "plan carries rate events)")
    ap.add_argument("--out", default=None)
    add_engine_flags(ap)
    args = ap.parse_args()
    enable_compile_cache()
    kernel_config_from_args(args)

    cfg = registry.get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(vocab_size=min(cfg.vocab_size, 512), remat=False,
                          dtype="float32")
    plan = FaultPlan.load(args.fault_plan) if args.fault_plan else FaultPlan()
    horizon = plan.max_effect_step(args.inner_steps)
    if horizon > args.steps:
        print(f"warning: fault-plan effects extend to step {horizon}, beyond "
              f"--steps {args.steps}; in-flight straggle debts ride the "
              f"checkpoint and resume exactly", flush=True)
    res = run_elastic_training(
        cfg, plan, method=args.method, replicas=args.replicas,
        per_replica_batch=args.batch, seq_len=args.seq, steps=args.steps,
        total_steps=args.total_steps,
        inner_lr=args.lr, inner_steps=args.inner_steps,
        eval_every=args.eval_every, seed=args.seed,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every, resume=args.resume,
        log=True, log_jsonl=args.log_jsonl, codec=args.codec,
        stream_count=args.stream_count,
        impl=args.impl, interpret=args.interpret,
        reassign_data=args.reassign_data,
        stale=args.stale, async_clock=args.async_clock,
    )
    summary = {
        "arch": cfg.name, "method": args.method,
        "fault_events": len(plan.events),
        "outer_syncs": res["outer_syncs"],
        "stream_count": res.get("stream_count", 1),
        "blocking_fraction": round(res["blocking_fraction"], 4),
        "membership": res["membership"],
        "final_train_loss": res["losses"][-1] if res["losses"] else None,
        "final_eval": res["evals"][-1][1] if res["evals"] else None,
        "final_weight_std": res["final_weight_std"],
        "wall_s": round(res["wall_s"], 1),
    }
    if "max_staleness" in res:
        summary["max_staleness"] = res["max_staleness"]
        summary["blocked_syncs"] = res["blocked_syncs"]
    print(json.dumps(summary))
    if args.out:
        res.pop("state")
        with open(args.out, "w") as f:
            json.dump(res, f)


if __name__ == "__main__":
    main()
