import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimbing driver: lower optimization VARIANTS of the three chosen
(arch × shape) pairs and report the roofline-term deltas vs the paper-faithful
baseline.

Variants are selected by name; each encodes one hypothesis from the
EXPERIMENTS.md §Perf log:

  outer_overlap    — NoLoCo outer step with §3.2 φ-prefetch: blocking payload
                     halves (Δ only), φ′ pre-send overlaps inner compute.
  decode_no_zero3  — internvl2 decode: keep weights TP-sharded on `model`
                     only (no per-token ZeRO-3 all-gather); weights fit
                     because decode holds no optimizer state.
  moe_seqshard     — qwen3-moe train: MoE dispatch buffers built on
                     sequence-sharded tokens (already default) vs replicated
                     tokens (ablation: buffers ×tp bigger).
  no_remat         — train_4k: disable full remat (memory for compute trade).
  loss_chunk_512   — smaller CE chunks (memory term of the loss).

    PYTHONPATH=src python -m repro.launch.perf --variant outer_overlap
"""

import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.shapes import SHAPES
from repro.core import pairing
from repro.core.outer import OuterConfig
from repro.launch import dryrun as dr
from repro.launch import roofline as rf
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_api
from repro.models.common import unzip
from repro.parallel import plans as plans_lib
from repro.parallel import steps as steps_lib


def outer_variant(arch: str, overlapped: bool, mesh) -> dict:
    """Lower the NoLoCo outer step, baseline vs φ-overlap, report collective
    bytes on the BLOCKING path."""
    cfg = registry.get_config(arch)
    plan = plans_lib.make_plan(registry.get_plan(arch), mesh)
    params_abs = dr.abstract_params(cfg, plan.replicas)
    theta_abs, _ = unzip(params_abs)
    pspecs = plans_lib.param_pspecs(plan, mesh, params_abs)
    perm = pairing.ppermute_pairs(0, plan.replicas)
    perm_next = pairing.ppermute_pairs(1, plan.replicas)
    ocfg = OuterConfig(method="noloco")
    model_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)

    with jax.set_mesh(mesh):
        rep_sh = jax.ShapeDtypeStruct((plan.replicas,), jnp.int32)
        if not overlapped:
            fn = steps_lib.build_outer_step(plan, mesh, pspecs, ocfg, perm)
            compiled = fn.lower(theta_abs, theta_abs, theta_abs, rep_sh).compile()
        else:
            # the §3.2 overlap is the single-stream streamed program: consume
            # the prefetched φ (block on Δ only) and pre-send φ′ along the
            # next pairing (extra phi_pre input and output)
            from repro.comm import stream_partition

            part = stream_partition(theta_abs, 1)
            fn = steps_lib.build_outer_step(
                plan, mesh, pspecs, ocfg, perm, stream=0, partition=part,
                consume_prefetch=True, perm_presend=perm_next,
            )
            compiled = fn.lower(
                theta_abs, theta_abs, theta_abs, theta_abs, rep_sh
            ).compile()

    stats = rf.collective_bytes(compiled.as_text(), model_size)
    return {
        "variant": "outer_overlap" if overlapped else "outer_baseline",
        "arch": arch,
        "collectives": stats.counts,
        "collective_bytes_total": stats.total_bytes,
        "note": "overlap: the φ′ pre-send permute is overlappable with the next "
                "m inner steps; blocking payload = Δ permute only" if overlapped else
                "blocking payload = Δ AND φ permutes",
    }


def train_variant(arch: str, shape_name: str, mesh, *, remat: bool,
                  seq_parallel: bool, replicate_experts: bool = False) -> dict:
    shape = SHAPES[shape_name]
    cfg = registry.variant_for_shape(registry.get_config(arch), shape)
    cfg = dataclasses.replace(cfg, remat=remat)
    plan = plans_lib.make_plan(
        registry.get_plan(arch), mesh, shape_kind=shape.kind,
        has_global_attention=any(t == "global" for t in cfg.layer_types),
        seq_parallel=seq_parallel, replicate_experts=replicate_experts,
    )
    model_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    c1 = dr._build_lowered(dr._depth_variant(cfg, 1), plan, shape, shape.kind, mesh).compile()
    c2 = dr._build_lowered(dr._depth_variant(cfg, 2), plan, shape, shape.kind, mesh).compile()
    f1, h1, k1 = dr._cost_of(c1, model_size)
    f2, h2, k2 = dr._cost_of(c2, model_size)
    eq = dr._equiv_periods(cfg)
    ext = lambda a, b: a + max(b - a, 0.0) * (eq - 1)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    roof = rf.analyze(
        ext(f1, f2), ext(h1, h2), None, chips=mesh.devices.size,
        model_flops=rf.model_flops_estimate(cfg, tokens, "train" if shape.kind == "train" else "fwd"),
        cross_bytes=ext(k1.cross_replica_bytes, k2.cross_replica_bytes),
        intra_bytes=ext(k1.model_axis_bytes, k2.model_axis_bytes),
    )
    return {"variant": f"remat={remat},seqpar={seq_parallel},repexp={replicate_experts}",
            "arch": arch, "shape": shape_name, "roofline": roof.as_dict()}


def decode_no_zero3(arch: str, shape_name: str, mesh) -> dict:
    """internvl2 decode without per-token ZeRO-3 gathers: weights sharded on
    `model` only (gossip_dp-style specs) for the DECODE step."""
    shape = SHAPES[shape_name]
    cfg = registry.get_config(arch)
    plan = plans_lib.make_plan(
        "gossip_dp", mesh, shape_kind="decode", has_global_attention=True
    )
    model_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    c1 = dr._build_lowered(dr._depth_variant(cfg, 1), plan, shape, "decode", mesh).compile()
    c2 = dr._build_lowered(dr._depth_variant(cfg, 2), plan, shape, "decode", mesh).compile()
    f1, h1, k1 = dr._cost_of(c1, model_size)
    f2, h2, k2 = dr._cost_of(c2, model_size)
    eq = dr._equiv_periods(cfg)
    ext = lambda a, b: a + max(b - a, 0.0) * (eq - 1)
    roof = rf.analyze(
        ext(f1, f2), ext(h1, h2), None, chips=mesh.devices.size,
        model_flops=rf.model_flops_estimate(cfg, shape.global_batch, "fwd"),
        cross_bytes=ext(k1.cross_replica_bytes, k2.cross_replica_bytes),
        intra_bytes=ext(k1.model_axis_bytes, k2.model_axis_bytes),
    )
    return {"variant": "decode_no_zero3", "arch": arch, "shape": shape_name,
            "roofline": roof.as_dict()}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", required=True,
                    choices=["outer_baseline", "outer_overlap",
                             "train_baseline", "train_no_remat", "train_seqpar",
                             "moe_replicate", "decode_no_zero3"])
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--shape", default="train_4k")
    args = ap.parse_args()
    enable_compile_cache()
    mesh = make_production_mesh(multi_pod=False)

    if args.variant in ("outer_baseline", "outer_overlap"):
        rec = outer_variant(args.arch, args.variant == "outer_overlap", mesh)
    elif args.variant == "train_baseline":
        rec = train_variant(args.arch, args.shape, mesh, remat=True, seq_parallel=False)
    elif args.variant == "train_no_remat":
        rec = train_variant(args.arch, args.shape, mesh, remat=False, seq_parallel=False)
    elif args.variant == "train_seqpar":
        rec = train_variant(args.arch, args.shape, mesh, remat=True, seq_parallel=True)
    elif args.variant == "moe_replicate":
        rec = train_variant(args.arch, args.shape, mesh, remat=True,
                            seq_parallel=False, replicate_experts=True)
    else:
        rec = decode_no_zero3(args.arch, args.shape, mesh)
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
