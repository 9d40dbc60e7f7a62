import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) combination with ShapeDtypeStruct stand-ins (no allocation), print
memory/cost analysis, and derive the three roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out dryrun.json
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k

XLA's cost_analysis counts while-loop (scan-over-layers) bodies ONCE, so the
roofline FLOPs/bytes are corrected by DEPTH EXTRAPOLATION: the same step is
lowered at 1× and 2× pattern periods (full dims, tiny depth — fast compiles)
and the per-period cost is extrapolated to the real depth.  The FULL-depth
compile is still what proves the combination lowers and what memory_analysis
reads.

Failures here (sharding mismatch, OOM at compile, unsupported collective) are
bugs in the system, not in the harness.
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.shapes import SHAPES, InputShape, input_specs, shape_skips
from repro.core.outer import OuterConfig
from repro.core import pairing
from repro.launch import roofline as rf
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_api
from repro.models.common import unzip
from repro.optim import AdamWConfig
from repro.parallel import plans as plans_lib
from repro.parallel import steps as steps_lib


def abstract_params(cfg, replicas: int):
    """Param tree of ShapeDtypeStructs (stacked) — no allocation."""
    def build(key):
        p = model_api.init_params(key, cfg)
        return steps_lib.stack_replicas(p, replicas)

    return jax.eval_shape(build, jax.random.PRNGKey(0))


def abstract_caches(cfg, batch: int, length: int):
    return jax.eval_shape(lambda: model_api.init_cache_tree(cfg, batch, length))


def _depth_variant(cfg, periods: int):
    """Full-width model at ``periods`` pattern periods (for cost extrapolation)."""
    reps = {"num_layers": periods * len(cfg.attn_pattern), "unroll_scans": True}
    if cfg.is_encoder_decoder:
        reps["num_encoder_layers"] = periods
    return dataclasses.replace(cfg, **reps)


def _equiv_periods(cfg) -> float:
    return cfg.num_layers / len(cfg.attn_pattern)


def _build_lowered(cfg, plan, shape: InputShape, kind: str, mesh):
    """Build the right step function and .lower() it (no compile)."""
    params_abs = abstract_params(cfg, plan.replicas)
    theta_abs, _ = unzip(params_abs)
    specs = input_specs(cfg, shape)

    with jax.set_mesh(mesh):
        if kind == "train":
            opt_abs = jax.eval_shape(
                lambda v: steps_lib.init_opt_state(v, plan.replicas), theta_abs
            )
            bundle = steps_lib.build_train_step(
                cfg, plan, mesh, params_abs, specs, AdamWConfig(lr=1e-4),
                data_sync=(kind == "train" and getattr(plan, "_data_sync", False)),
            )
            return bundle.step_fn.lower(theta_abs, opt_abs, specs)
        if kind == "prefill":
            caches_abs = abstract_caches(cfg, shape.global_batch, shape.seq_len)
            cvals, _ = unzip(caches_abs)
            fn, _ = steps_lib.build_prefill_step(
                cfg, plan, mesh, params_abs, caches_abs, specs
            )
            return fn.lower(theta_abs, cvals, specs)
        if kind == "decode":
            caches_abs = abstract_caches(cfg, shape.global_batch, shape.seq_len)
            cvals, _ = unzip(caches_abs)
            bspecs = steps_lib.batch_pspecs(plan, specs)
            fn, _ = steps_lib.build_decode_step(
                cfg, plan, mesh, params_abs, caches_abs, bspecs
            )
            return fn.lower(
                theta_abs, cvals, specs["tokens"], jax.ShapeDtypeStruct((), jnp.int32)
            )
        if kind in ("outer_noloco", "outer_diloco"):
            pspecs = plans_lib.param_pspecs(plan, mesh, params_abs)
            method = kind.split("_")[1]
            perm = pairing.ppermute_pairs(0, plan.replicas)
            ocfg = OuterConfig(method=method)
            fn = steps_lib.build_outer_step(plan, mesh, pspecs, ocfg, perm)
            rep_shape = jax.ShapeDtypeStruct((plan.replicas,), jnp.int32)
            return fn.lower(theta_abs, theta_abs, theta_abs, rep_shape)
        raise ValueError(kind)  # pragma: no cover


def _cost_of(compiled, model_size: int):
    try:
        cost_list = compiled.cost_analysis()
        cost = cost_list[0] if isinstance(cost_list, (list, tuple)) else cost_list
    except Exception:
        cost = {}
    flops = float(cost.get("flops", 0.0)) if isinstance(cost, dict) else 0.0
    hbm = float(cost.get("bytes accessed", 0.0)) if isinstance(cost, dict) else 0.0
    coll = rf.collective_bytes(compiled.as_text(), model_size)
    return flops, hbm, coll


def lower_one(
    arch: str,
    shape: InputShape,
    mesh,
    *,
    step_override: str | None = None,
    seq_parallel: bool = False,
    data_sync: bool = False,
    skip_extrapolation: bool = False,
) -> dict[str, Any]:
    """Lower+compile one combination; return a result record."""
    cfg = registry.variant_for_shape(registry.get_config(arch), shape)
    plan_name = registry.get_plan(arch)
    kind = step_override or shape.kind
    has_global = any(t == "global" for t in cfg.layer_types)
    plan = plans_lib.make_plan(
        plan_name, mesh, shape_kind=shape.kind,
        has_global_attention=has_global, seq_parallel=seq_parallel,
    )
    object.__setattr__(plan, "_data_sync", data_sync) if data_sync else None
    chips = mesh.devices.size
    model_size = dict(zip(mesh.axis_names, mesh.devices.shape)).get("model", 1)
    if kind.startswith("outer") and plan.replicas < 2:
        return {"arch": arch, "shape": shape.name, "step": kind, "mesh": "x".join(map(str, mesh.devices.shape)),
                "status": "skip", "reason": "single replica: no outer sync on this mesh"}

    t0 = time.time()
    lowered = _build_lowered(cfg, plan, shape, kind, mesh)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_info = {"error": str(e)}

    full_flops, full_hbm, full_coll = _cost_of(compiled, model_size)
    tokens_total = shape.global_batch * (shape.seq_len if kind not in ("decode",) else 1)
    if kind.startswith("outer"):
        tokens_total = 0
    mf = rf.model_flops_estimate(cfg, tokens_total, "train" if kind == "train" else "fwd")

    # ---- depth extrapolation for trip-count-correct costs -----------------
    if kind.startswith("outer") or skip_extrapolation:
        flops, hbm = full_flops, full_hbm
        cross, intra = full_coll.cross_replica_bytes, full_coll.model_axis_bytes
    else:
        c1 = _build_lowered(_depth_variant(cfg, 1), plan, shape, kind, mesh).compile()
        c2 = _build_lowered(_depth_variant(cfg, 2), plan, shape, kind, mesh).compile()
        f1, h1, k1 = _cost_of(c1, model_size)
        f2, h2, k2 = _cost_of(c2, model_size)
        eq = _equiv_periods(cfg)

        def _extrap(a, b):
            # clamp: DCE/fusion noise between the two tiny compiles can make
            # b < a; per-period cost is never negative
            return a + max(b - a, 0.0) * (eq - 1)

        flops = _extrap(f1, f2)
        hbm = _extrap(h1, h2)
        cross = _extrap(k1.cross_replica_bytes, k2.cross_replica_bytes)
        intra = _extrap(k1.model_axis_bytes, k2.model_axis_bytes)

    roof = rf.analyze(
        flops, hbm, None, chips=chips, model_flops=mf,
        cross_bytes=cross, intra_bytes=intra,
    )

    return {
        "arch": arch,
        "shape": shape.name,
        "step": kind,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "plan": plan_name,
        "replicas": plan.replicas,
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": mem_info,
        "collectives": full_coll.counts,
        "collective_bytes": full_coll.bytes_by_kind,
        "roofline": roof.as_dict(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=[*SHAPES, None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--outer", action="store_true", help="also dry-run outer steps")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--data-sync", action="store_true", help="DDP baseline train step")
    ap.add_argument("--fast", action="store_true", help="skip depth extrapolation")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi", make_production_mesh(multi_pod=True)))

    archs = registry.ASSIGNED if args.all or args.arch is None else [args.arch]
    shapes = list(SHAPES.values()) if args.shape is None else [SHAPES[args.shape]]

    results = []

    def emit(rec):
        print(json.dumps({k: v for k, v in rec.items() if k != "trace"}), flush=True)
        if rec.get("status") == "FAIL":
            print(rec["trace"], flush=True)
        results.append(rec)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)

    for mesh_name, mesh in meshes:
        for arch in archs:
            cfg = registry.get_config(arch)
            for shape in shapes:
                reason = shape_skips(cfg, shape)
                if reason:
                    emit({"arch": arch, "shape": shape.name, "mesh": mesh_name,
                          "status": "skip", "reason": reason})
                    continue
                try:
                    rec = lower_one(
                        arch, shape, mesh,
                        seq_parallel=args.seq_parallel, data_sync=args.data_sync,
                        skip_extrapolation=args.fast,
                    )
                except Exception:
                    rec = {"arch": arch, "shape": shape.name, "mesh": mesh_name,
                           "status": "FAIL", "trace": traceback.format_exc()[-2500:]}
                emit(rec)
            if args.outer:
                for okind in ("outer_noloco", "outer_diloco"):
                    try:
                        rec = lower_one(arch, SHAPES["train_4k"], mesh, step_override=okind)
                    except Exception:
                        rec = {"arch": arch, "step": okind, "mesh": mesh_name,
                               "status": "FAIL", "trace": traceback.format_exc()[-2500:]}
                    emit(rec)

    n_ok = sum(r.get("status") == "ok" for r in results)
    n_fail = sum(r.get("status") == "FAIL" for r in results)
    n_skip = sum(r.get("status") == "skip" for r in results)
    print(f"DRYRUN SUMMARY: ok={n_ok} fail={n_fail} skip={n_skip}")


if __name__ == "__main__":
    main()
