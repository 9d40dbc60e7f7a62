#!/usr/bin/env python3
"""Compile every program of the benchmark's cells for a described v5e:2x2,
with no chip attached, and print ``memory_analysis()`` per chip with the
state that stays live beside each program.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/tools/rehearse.py [cell ...]

Training: the inner step (it donates theta and the AdamW state; phi and
delta stay live beside it) and the outer step (it donates theta, phi and
delta; the AdamW state stays live beside it); with several replicas also
what ``init_state`` holds on device 0 before it places the state.  Serving: the decode step and
the chunk-prefill program at the cell's page pool (both donate the engine
state; the weights are arguments).  One JSON line per program.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench.core import harness as H  # noqa: E402

GIB = 1024 ** 3
LIMIT = 16_909_334_528  # bytes_limit one v5e chip reports


def tree_bytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize for x in jax.tree.leaves(tree))


def mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"argument": m.argument_size_in_bytes, "output": m.output_size_in_bytes,
            "alias": m.alias_size_in_bytes, "temp": m.temp_size_in_bytes,
            "program_bytes": m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes}


def report(cell: str, program: str, m: dict, live: int, **extra) -> dict:
    total = m["program_bytes"] + live
    rec = {"cell": cell, "program": program, **m, "live_beside": live, "total": total,
           "total_gib": round(total / GIB, 3), "fits": total < LIMIT, **extra}
    print(json.dumps(rec), flush=True)
    return rec


def train(topo, name: str, cell: dict, layers: int | None = None, batch: int | None = None):
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.comm import CommConfig
    from repro.core import pairing
    from repro.core.outer import OuterConfig
    from repro.kernels.dispatch import KernelConfig
    from repro.models import model as M
    from repro.models.common import unzip
    from repro.optim import AdamWConfig
    from repro.parallel import plans as PL
    from repro.parallel import steps as ST
    from bench.drivers.train import model_config

    cfgfile = H.load("configs", cell["config"])
    traffic = H.load("traffic", cell["traffic"])
    r, s = cell["replicas"], traffic["seq"]
    b = batch or traffic["per_replica_batch"]
    cfg = dataclasses.replace(model_config(cfgfile), kernels=KernelConfig("pallas", False))
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    pairs = pairing.ppermute_pairs(0, r, seed=0)
    mesh = Mesh(np.array(topo.devices[:r]).reshape(r, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    plan = PL.make_plan("gossip_dp", mesh, shape_kind="train")
    stacked = jax.eval_shape(lambda: ST.stack_replicas(M.init_params(jax.random.PRNGKey(0), cfg), r))
    tok = jax.ShapeDtypeStruct((r * b, s), jnp.int32)
    bt = {"tokens": tok, "labels": tok}

    def placed(tree, shardings):
        return jax.tree.map(lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
                            tree, shardings)

    with jax.set_mesh(mesh):
        bundle = ST.build_train_step(cfg, plan, mesh, stacked, bt, AdamWConfig(**cell["inner_opt"]))
        vals, _ = unzip(stacked)
        theta = placed(vals, bundle.theta_shardings)
        opt = placed(jax.eval_shape(lambda v: ST.init_opt_state(v, r), vals), bundle.opt_shardings)
        bts = placed(bt, PL.shardings(mesh, ST.batch_pspecs(plan, bt)))
        inner = bundle.step_fn.lower(theta, opt, bts).compile()
        o = cell["outer"]
        outer_fn = ST.build_outer_step(
            plan, mesh, bundle.pspecs,
            OuterConfig(method="noloco", inner_steps=o["inner_steps"], alpha=o["alpha"], beta=o["beta"]),
            pairs, comm_cfg=CommConfig(codec=cell["codec"]), kernel_cfg=KernelConfig("pallas", False))
        step = jax.ShapeDtypeStruct((r,), jnp.int32, sharding=NamedSharding(mesh, P("data")))
        outer = outer_fn.lower(theta, theta, theta, step).compile()
    per_rep = lambda t: tree_bytes(t) // r
    th, op = per_rep(vals), per_rep(jax.eval_shape(lambda v: ST.init_opt_state(v, r), vals))
    params = sum(int(np.prod(x.shape[1:])) for x in jax.tree.leaves(vals))
    tag = dict(layers=cfg.num_layers, batch=b, seq=s, params_per_replica=params)
    a = report(name, "inner_step", mem(inner), 2 * th, **tag)
    bcast = th * r if r > 1 else 0
    c = report(name, "outer_step", mem(outer), op, **tag,
               collective_permutes=outer.as_text().count("collective-permute-start")
               or outer.as_text().count("collective-permute("),
               all_reduces=outer.as_text().count("all-reduce("))
    if bcast:
        # DistributedTrainer.init_state runs eagerly on device 0: one
        # replica's params, their broadcast to every replica, theta's own
        # shard once placed, then init_opt_state's vmapped float32 moments
        # for all replicas, made whole on device 0 before they are placed
        moments = tree_bytes(jax.eval_shape(lambda v: ST.init_opt_state(v, r), vals))
        live = th + bcast + th + moments
        print(json.dumps({"cell": name, "program": "init_state_device0", "params": th,
                          "broadcast": bcast, "moments_all_replicas": moments, "total": live,
                          "total_gib": round(live / GIB, 3), "fits": live < LIMIT, **tag}), flush=True)
    return a, c


def serve(topo, name: str, cell: dict, pages: int | None = None):
    from jax.sharding import SingleDeviceSharding

    from repro.kernels.dispatch import KernelConfig
    from repro.models import model as M
    from repro.models.common import values_of
    from repro.serve import engine as E
    from bench.drivers.train import model_config

    cfgfile = H.load("configs", cell["config"])
    sc = dict(cell["serve"])
    if pages:
        sc["num_pages"] = pages
    cfg = dataclasses.replace(model_config(cfgfile), kernels=KernelConfig("pallas", False))
    one = SingleDeviceSharding(topo.devices[0])
    put = lambda t: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), t)
    params = put(jax.eval_shape(lambda: values_of(M.init_params(jax.random.PRNGKey(0), cfg))))
    scfg = E.ServeConfig(**sc)
    r, mb = scfg.max_slots, scfg.num_pages
    st = jax.eval_shape(lambda: E.EngineState(
        caches=M.init_paged_cache_tree(cfg, r, scfg.num_pages, scfg.page_size),
        block_tables=jnp.zeros((r, mb), jnp.int32), tokens=jnp.zeros((r,), jnp.int32),
        positions=jnp.zeros((r,), jnp.int32), active=jnp.zeros((r,), bool),
        temps=jnp.zeros((r,), jnp.float32), rids=jnp.zeros((r,), jnp.int32),
        out_buf=jnp.zeros((r, scfg.max_new_cap), jnp.int32), out_len=jnp.zeros((r,), jnp.int32),
        budgets=jnp.zeros((r,), jnp.int32)))
    st = put(st)
    decode, _ = E._programs(cfg)
    dec = decode.lower(params, st).compile()
    c = scfg.prefill_chunk
    chunk = E._chunk_program(cfg, c)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    f32 = jax.ShapeDtypeStruct((), jnp.float32, sharding=one)
    key = put(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    ch = chunk.lower(params, i32(c), i32(), st.caches, i32(mb), i32(), f32, key).compile()
    kv_bytes = tree_bytes(st.caches)
    tag = dict(pages=scfg.num_pages, page_size=scfg.page_size, slots=r, kv_gib=round(kv_bytes / GIB, 3),
               weights_gib=round(tree_bytes(params) / GIB, 3))
    return report(name, "decode_step", mem(dec), 0, **tag), report(name, "chunk_prefill", mem(ch), 0, **tag)


def main() -> None:
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    args = sys.argv[1:]
    names = [a for a in args if not a.startswith("--")]
    opts = dict(a[2:].split("=") for a in args if a.startswith("--"))
    bm = H.benchmark()
    cells = names or [w["name"] for w in bm["workloads"]]
    for name in cells:
        cell = H.load("cells", name)
        if cell["driver"] == "train":
            train(topo, name, cell, int(opts["layers"]) if "layers" in opts else None,
                  int(opts["batch"]) if "batch" in opts else None)
        else:
            serve(topo, name, cell, int(opts["pages"]) if "pages" in opts else None)


if __name__ == "__main__":
    main()
