#!/usr/bin/env python3
"""Chip smoke test: NoLoCo training and serving at paper-small-125m width on
a TPU, through the entry points a user calls.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # four chips: shard_map NoLoCo only

One chip runs these phases, in order, in this one process:

  1. device  — require a TPU; print the device, the jax/jaxlib versions and
     the resolved kernel config (compiled Pallas, no interpret mode);
  2. kernels — each main-path Pallas kernel, compiled for the chip, against
     its jnp twin on a small input at paper-small head widths;
  3. train   — NoLoCo on the stacked runtime (``launch/train.py::
     run_training``) at the full paper-small-125m width, seq 1024, with a
     seeded synthetic loader; the first loss is checked against a jnp-kernel
     forward of the same weights and batch, losses must be finite and at
     least two outer syncs must fire; the run ends in a checkpoint;
  4. serve   — one replica promoted from that checkpoint (``serve/promote``)
     serves a few requests through ``ServeEngine`` (chunked prefill, paged
     Pallas kernels); ``--verify`` parity: batched tokens == solo tokens.

``--chips 4`` runs only the multi-chip phase: shard_map NoLoCo
(``DistributedTrainer``, data=4 x model=1) and the stacked runtime at the
same seed, replicas, steps and cut, and compares them: each replica's θ on
its own device, ``collective-permute`` and no ``all-reduce`` in the compiled
outer step, per-step losses within ``LOSS_RTOL``.

Every failure propagates to a non-zero exit.  The last stdout line is
``{"ok": true, "device": {"platform", "kind", "count"}}``; it is printed
only when every phase passed.  Without a TPU, or without the repository's
``src/repro`` beside this file, the script exits non-zero before any work.
The compile cache follows ``JAX_COMPILATION_CACHE_DIR`` when it is set and
``<checkout>/.jax_cache`` otherwise.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

ARCH = "paper-small-125m"
SEQ = 1024
TRAIN = dict(replicas=2, per_replica_batch=2, steps=6, inner_steps=2)
SERVE = dict(max_slots=4, num_pages=64, page_size=16, prefill_chunk=32,
             requests=6, prompt_lens=(7, 40, 100), gen_lens=(8, 16),
             temps=(0.0, 0.8))
# The four-chip comparison runs its stacked reference on one chip.  By
# memory_analysis of that step at seq 1024, four replicas need 17.6 GiB at
# batch 2 even at depth 2, and at batch 1 14.9 / 15.7 / 16.4 GiB at depth
# 1 / 2 / 3.  So BOTH sides cut depth to 2 and batch to 1 per replica;
# widths and seq stay published.
FOUR = dict(replicas=4, per_replica_batch=1, steps=6, inner_steps=2,
            num_layers=2)
# stacked vs shard_map per-step loss (bf16 model): sound runs differ by
# <7e-5; a gradient scaled by 1/replicas on one side differs by >3e-3
LOSS_RTOL = 1e-3
FIRST_LOSS_ATOL = 1e-3  # Pallas-kernel step-1 loss vs the jnp-kernel forward


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def log(*parts) -> None:
    print(*parts, flush=True)


def log_device_memory(tag: str) -> None:
    """Peak and limit of device 0's memory as the runtime reports them
    (TPU only: the CPU client keeps no such statistics)."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"[{tag}] device 0 memory: peak_bytes_in_use="
            f"{stats['peak_bytes_in_use']} bytes_limit={stats.get('bytes_limit')}")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def device_phase(chips: int) -> dict:
    import jax
    import jaxlib

    from repro.kernels.dispatch import KernelConfig

    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    log(f"[device] {json.dumps(info)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__}")
    check(dev.platform == "tpu", f"no TPU: jax reports {dev.platform!r}")
    check(len(devices) >= chips, f"need {chips} chips, jax sees {len(devices)}")
    kcfg = KernelConfig()
    impl, interpret = kcfg.resolved_impl(), kcfg.resolved_interpret()
    log(f"[device] KernelConfig() -> impl={impl!r} interpret={interpret}")
    check(impl == "pallas" and not interpret,
          "kernels must resolve to compiled Pallas on the chip")
    return info


def kernel_phase(impl: str = "pallas", interpret: bool | None = None) -> None:
    """Each main-path kernel against its jnp twin at paper-small head dims."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops
    from repro.kernels.dispatch import KernelConfig

    kp = KernelConfig(impl, interpret)
    kj = KernelConfig("jnp")
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 8)
    bf = jnp.bfloat16
    h, d = 16, 48

    def report(name, got, want, atol):
        got = np.asarray(jnp.asarray(got, jnp.float32))
        want = np.asarray(jnp.asarray(want, jnp.float32))
        err = float(np.max(np.abs(got - want)))
        log(f"[kernels] {name}: shape={got.shape} max|pallas-jnp|={err:.3e} "
            f"(atol {atol})")
        check(np.isfinite(got).all(), f"{name}: non-finite output")
        check(err <= atol, f"{name}: max error {err} > {atol}")

    q = jax.random.normal(ks[0], (1, 256, h, d), bf)
    k = jax.random.normal(ks[1], (1, 256, h, d), bf)
    v = jax.random.normal(ks[2], (1, 256, h, d), bf)
    report("flash_attention",
           ops.flash_attention(q, k, v, config=kp),
           ops.flash_attention(q, k, v, config=kj), 3e-2)

    pages, bs, r, mb = 17, 16, 4, 8
    kpool = jax.random.normal(ks[3], (pages, h, bs, d), bf)
    vpool = jax.random.normal(ks[4], (pages, h, bs, d), bf)
    tables = jax.random.randint(ks[5], (r, mb), 0, pages, jnp.int32)
    pos = jnp.asarray([0, 17, 60, 127], jnp.int32)
    qd = jax.random.normal(ks[6], (r, h, d), bf)
    report("paged_attention",
           ops.paged_attention(qd, kpool, vpool, tables, pos, config=kp),
           ops.paged_attention(qd, kpool, vpool, tables, pos, config=kj), 3e-2)
    qc = jax.random.normal(ks[7], (r, 32, h, d), bf)
    base = jnp.asarray([0, 16, 40, 90], jnp.int32)
    report("paged_chunk_attention",
           ops.paged_chunk_attention(qc, kpool, vpool, tables, base, config=kp),
           ops.paged_chunk_attention(qc, kpool, vpool, tables, base, config=kj),
           3e-2)

    n = 1_000_003  # not a multiple of the 4096 tile: exercises the padding
    leaves = [jax.random.normal(jax.random.fold_in(key, i), (n,)) * 0.02
              for i in range(4)]
    kw = dict(alpha=0.5, beta=0.7, gamma=0.1)
    got = ops.noloco_update_pytree(*leaves, **kw, config=kp)
    want = ops.noloco_update_pytree(*leaves, **kw, config=kj)
    report("noloco_update (phi)", got[0], want[0], 1e-6)
    report("noloco_update (delta)", got[1], want[1], 1e-6)

    x = jax.random.normal(jax.random.fold_in(key, 9), (100, 1024))
    qp, sp, lp = ops.int8_quantize(x, config=kp)
    qj, sj, lj = ops.int8_quantize(x, config=kj)
    report("int8_quantize (q)", qp.astype(jnp.int32), qj.astype(jnp.int32), 1)
    report("int8_quantize (scale)", sp, sj, 1e-6)
    report("int8_quantize (lo)", lp, lj, 0.0)
    report("int8_dequantize",
           ops.int8_dequantize(qj, sj, lj, config=kp),
           ops.int8_dequantize(qj, sj, lj, config=kj), 1e-5)


def reference_first_loss(cfg, loader_cfg) -> float:
    """Mean step-0 loss over replicas with the jnp kernel twins: the
    reference the trained step-1 loss is held to (same init, same batch)."""
    import jax
    import jax.numpy as jnp

    from repro.data import shard_iterator
    from repro.kernels.dispatch import KernelConfig
    from repro.models import model as M
    from repro.models.common import values_of
    from repro.parallel.sharding import ShardCtx

    ref_cfg = dataclasses.replace(cfg, kernels=KernelConfig("jnp"))
    params = values_of(M.init_params(jax.random.PRNGKey(loader_cfg.seed), ref_cfg))
    batch = {k: jnp.asarray(v) for k, v in next(shard_iterator(loader_cfg)).items()}
    ctx = ShardCtx.local()
    # the weights are an argument, not a closure: a captured 183M-parameter
    # tree would be baked into the executable as constants
    losses = jax.jit(jax.vmap(
        lambda p, b: M.loss_fn(p, ref_cfg, b, ctx)[0], in_axes=(None, 0)
    ))(params, batch)
    return float(jnp.mean(losses))


def train_phase(cfg, *, seq: int, ckpt_dir: str, seed: int, replicas: int,
                per_replica_batch: int, steps: int, inner_steps: int,
                impl: str = "auto", interpret: bool | None = None) -> dict:
    import jax
    import numpy as np

    from repro.data import LoaderConfig
    from repro.launch.train import run_training

    ref = reference_first_loss(cfg, LoaderConfig(
        vocab_size=cfg.vocab_size, seq_len=seq,
        per_replica_batch=per_replica_batch, replicas=replicas, seed=seed,
    ))
    log(f"[train] reference step-1 loss (jnp kernels): {ref:.6f}")
    res = run_training(
        cfg, method="noloco", replicas=replicas,
        per_replica_batch=per_replica_batch, seq_len=seq, steps=steps,
        inner_steps=inner_steps, eval_every=0, seed=seed,
        ckpt_dir=ckpt_dir, impl=impl, interpret=interpret,
    )
    losses = res["losses"]
    log(f"[train] losses: {json.dumps(losses)}")
    log(f"[train] outer_syncs={res['outer_syncs']} "
        f"final_weight_std={res['final_weight_std']!r} "
        f"wall_s={res['wall_s']:.1f} (includes compilation)")
    log_device_memory("train")
    check(len(losses) == steps and all(math.isfinite(x) for x in losses),
          "training losses must be finite")
    check(abs(losses[0] - ref) <= FIRST_LOSS_ATOL,
          f"step-1 loss {losses[0]} vs jnp reference {ref}")
    check(res["outer_syncs"] >= 2, f"only {res['outer_syncs']} outer syncs")
    check(math.isfinite(res["final_weight_std"]), "final weight std not finite")
    theta = res["state"].theta
    check(all(bool(np.isfinite(np.asarray(jax.device_get(x), np.float32)).all())
              for x in jax.tree.leaves(theta)), "non-finite trained weights")
    return {k: v for k, v in res.items() if k != "state"}


def serve_phase(cfg, *, ckpt_dir: str, seed: int, max_slots: int,
                num_pages: int, page_size: int, prefill_chunk: int,
                requests: int, prompt_lens, gen_lens, temps) -> dict:
    from repro.launch.serve import serve_run, synth_requests
    from repro.serve import ServeConfig, promote

    params, info = promote(ckpt_dir, replica=0, source="theta")
    log(f"[serve] promoted {json.dumps(info)}")
    scfg = ServeConfig(max_slots=max_slots, num_pages=num_pages,
                       page_size=page_size, max_new_cap=max(gen_lens),
                       prefill_chunk=prefill_chunk)
    reqs = synth_requests(requests, cfg.vocab_size, list(prompt_lens),
                          list(gen_lens), list(temps), seed)
    summary = serve_run(params, cfg, scfg, reqs, verify=True)
    log(f"[serve] impl={cfg.kernels.resolved_impl()} "
        f"interpret={cfg.kernels.resolved_interpret()} "
        f"requests={summary['requests']} gen_tokens={summary['gen_tokens']} "
        f"decode_steps={summary['decode_steps']} "
        f"verify_mismatches={summary['verify_mismatches']} "
        f"parity: {json.dumps(summary['parity'])}")
    check(summary["requests"] == requests, "not every request finished")
    check(summary["gen_tokens"] == sum(r.max_new for r in reqs),
          "a request stopped short of its budget")
    check(summary["parity"], "batched tokens differ from solo tokens")
    return summary


def four_chip_phase(cfg, *, seed: int, replicas: int, per_replica_batch: int,
                    steps: int, inner_steps: int, seq: int,
                    impl: str = "auto", interpret: bool | None = None) -> None:
    """shard_map NoLoCo on a data=4 x model=1 mesh vs the stacked runtime."""
    import jax
    import numpy as np

    from repro.data import LoaderConfig
    from repro.kernels.dispatch import KernelConfig
    from repro.launch import roofline as rf
    from repro.launch.mesh import make_mesh
    from repro.launch.train import method_config, run_training
    from repro.launch.train_distributed import DistributedTrainer
    from repro.parallel import plans as plans_lib
    from repro.train import DistributedProgram, LoopConfig, make_loop

    kcfg = KernelConfig(impl, interpret)
    cfg = dataclasses.replace(cfg, kernels=kcfg)
    mesh = make_mesh((replicas, 1), ("data", "model"))
    plan = plans_lib.make_plan("gossip_dp", mesh, shape_kind="train")
    # the stacked runtime's exact hyper-parameters (run_training below)
    tcfg = method_config(
        "noloco", inner_lr=3e-3, total_steps=steps,
        warmup=max(steps // 10, 1), inner_steps=inner_steps, seed=seed,
        kernels=kcfg,
    )
    trainer = DistributedTrainer(
        cfg=cfg, mesh=mesh, plan=plan, outer_cfg=tcfg.outer,
        inner_cfg=tcfg.inner, kernel_cfg=kcfg, seed=seed,
    )
    loop = make_loop(
        DistributedProgram(trainer),
        LoaderConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                     per_replica_batch=per_replica_batch, replicas=replicas,
                     seed=seed),
        LoopConfig(steps=steps, seed=seed, run_name=f"{cfg.name}-dist"),
    )
    dist = loop.run()
    state = dist.pop("state")
    log(f"[4chip] shard_map losses: {json.dumps(dist['losses'])}")
    log(f"[4chip] shard_map outer_syncs={dist['outer_syncs']} "
        f"final_weight_std={dist['final_weight_std']!r}")

    placement = {}
    for leaf in jax.tree.leaves(state["theta"]):
        for shard in leaf.addressable_shards:
            rows = shard.index[0]
            check(rows.stop - rows.start == 1,
                  f"a θ shard holds replicas {rows.start}..{rows.stop - 1}")
            placement.setdefault(rows.start, set()).add(shard.device.id)
    devices = {r: sorted(ids) for r, ids in sorted(placement.items())}
    log(f"[4chip] replica -> device ids: {json.dumps(devices)}")
    check(len(devices) == replicas and all(len(v) == 1 for v in devices.values()),
          "each replica must live on exactly one device")
    check(len({v[0] for v in devices.values()}) == replicas,
          "replicas must live on distinct devices")

    fn, _ = trainer.pool.program(0)
    with jax.set_mesh(mesh):
        hlo = fn.lower(state["theta"], state["phi"], state["delta"],
                       state["outer_step"]).compile().as_text()
    counts = rf.collective_bytes(hlo, model_size=1).counts
    log(f"[4chip] compiled outer step collectives: "
        f"collective-permute={counts['collective-permute']} "
        f"all-reduce={counts['all-reduce']}")
    check(counts["collective-permute"] > 0, "no collective-permute in outer step")
    check(counts["all-reduce"] == 0, "all-reduce in the NoLoCo outer step")
    del state, fn
    jax.clear_caches()

    ref = run_training(
        cfg, method="noloco", replicas=replicas,
        per_replica_batch=per_replica_batch, seq_len=seq, steps=steps,
        inner_steps=inner_steps, eval_every=0, seed=seed,
        impl=impl, interpret=interpret,
    )
    ref.pop("state")
    log_device_memory("4chip")
    log(f"[4chip] stacked losses:   {json.dumps(ref['losses'])}")
    log(f"[4chip] stacked outer_syncs={ref['outer_syncs']} "
        f"final_weight_std={ref['final_weight_std']!r}")
    a = np.asarray(dist["losses"])
    b = np.asarray(ref["losses"])
    rel = np.abs(a - b) / np.abs(b)
    log(f"[4chip] per-step |shard_map - stacked| / stacked: "
        f"{json.dumps([float(x) for x in rel])} (rtol {LOSS_RTOL})")
    check(np.isfinite(a).all() and np.isfinite(b).all(), "non-finite losses")
    check(dist["outer_syncs"] == ref["outer_syncs"] >= 2, "outer syncs differ")
    check(bool((rel <= LOSS_RTOL).all()), "losses differ beyond tolerance")


# ---------------------------------------------------------------------------
# Entry
# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the shard_map-vs-stacked phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (SRC / "repro" / "__init__.py").is_file():
        fail(f"{SRC / 'repro'} not found: run this from a checkout of the repo")
    sys.path.insert(0, str(SRC))

    from repro.configs import registry
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    device = device_phase(args.chips)
    log(f"[device] compile cache: {cache}")

    cfg = registry.get_config(ARCH)
    n_params = param_count(cfg)
    log(f"[model] {cfg.name}: d_model={cfg.d_model} layers={cfg.num_layers} "
        f"heads={cfg.num_heads}x{cfg.resolved_head_dim} kv_heads={cfg.num_kv_heads} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} dtype={cfg.dtype} "
        f"params={n_params:,} ({n_params / 1e6:.1f}M)")

    if args.chips == 4:
        four = dict(FOUR)
        layers = four.pop("num_layers")
        cut = dataclasses.replace(cfg, num_layers=layers)
        log(f"[4chip] cut on both sides: layers {cfg.num_layers} -> {layers}, "
            f"per-replica batch {TRAIN['per_replica_batch']} -> "
            f"{four['per_replica_batch']}; widths and seq {SEQ} unchanged "
            f"({param_count(cut):,} params)")
        four_chip_phase(cut, seed=args.seed, seq=SEQ, **four)
    else:
        kernel_phase()
        with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
            train_phase(cfg, seq=SEQ, ckpt_dir=ckpt, seed=args.seed, **TRAIN)
            serve_phase(cfg, ckpt_dir=ckpt, seed=args.seed, **SERVE)
    print(json.dumps({"ok": True, "device": device}), flush=True)


def param_count(cfg) -> int:
    import jax
    import numpy as np

    from repro.models import model as M
    from repro.models.common import values_of

    shapes = jax.eval_shape(
        lambda: values_of(M.init_params(jax.random.PRNGKey(0), cfg))
    )
    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(shapes)))


if __name__ == "__main__":
    main()
