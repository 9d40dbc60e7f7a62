"""Compile the main path for a described TPU v5e, with no chip attached.

Every registry kernel compiles with ``interpret=False`` at paper-small-125m
widths, and the full-width programs that ``chip_smoke.py`` runs compile
within the chip's 16 GiB of HBM by ``memory_analysis()``.  Nothing runs: a
pass here says the TPU compiler accepts the program, not that its results
or speed are right (``chip_smoke.py`` on the chip says that).

The topology is described inside a module fixture — never at import — so
that only the worker running this file loads the TPU compiler library.  The
persistent compile cache is off around these compiles (an entry compiled
for a described chip cannot be read back without one).
"""

import dataclasses
import importlib.util
import os
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (
    AxisType, Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)

from repro.configs import registry
from repro.core import pairing
from repro.kernels import (
    decode_update, flash_attention, noloco_update, paged_attention, quantize,
    rglru_scan, ssd_scan,
)
from repro.kernels.dispatch import KernelConfig
from repro.launch import roofline as rf
from repro.launch.train import method_config
from repro.models import model as M
from repro.models.common import unzip
from repro.parallel import plans as PL, steps as ST
from repro.train import GossipProgram

_spec = importlib.util.spec_from_file_location(
    "chip_smoke", Path(__file__).resolve().parents[1] / "chip_smoke.py"
)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)

HBM_BYTES = 16 * 2**30
COMPILED = KernelConfig("pallas", False)
BF = jnp.bfloat16
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler library on this machine
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _memory_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


# name -> (kernel call with interpret=False, [(shape, dtype), ...])
_SERVE = chip_smoke.SERVE
_POOL = (_SERVE["num_pages"] + 1, 16, _SERVE["page_size"], 48)  # (NP, KV, BS, D)
_PAYLOAD_ROWS = -(-183_237_888 // 1024)  # paper-small f32 payload, 1024-chunks
KERNELS = {
    "flash_attention_mha_d48": (
        lambda q, k, v: flash_attention.pallas_flash_attention(q, k, v, interpret=False),
        [((8, 1024, 16, 48), BF)] * 3,
    ),
    "flash_attention_r1_d64": (   # train.stablelm2.r1: batch 2 x 2048, 32 heads of 64
        lambda q, k, v: flash_attention.pallas_flash_attention(q, k, v, interpret=False),
        [((2, 2048, 32, 64), BF)] * 3,
    ),
    "flash_attention_gqa_d128": (
        lambda q, k, v: flash_attention.pallas_flash_attention(q, k, v, interpret=False),
        [((4, 1024, 16, 128), BF), ((4, 1024, 8, 128), BF), ((4, 1024, 8, 128), BF)],
    ),
    "noloco_update_embed_leaf": (
        lambda p, d, md, mp: noloco_update.noloco_update_flat(
            p, d, md, mp, alpha=0.5, beta=0.7, gamma=0.1, interpret=False),
        [((128_000 * 768,), BF)] * 4,
    ),
    "int8_quantize": (
        lambda x: quantize.pallas_int8_quantize(x, interpret=False),
        [((_PAYLOAD_ROWS, 1024), F32)],
    ),
    "int8_dequantize": (
        lambda q, s, lo: quantize.pallas_int8_dequantize(q, s, lo, interpret=False),
        [((_PAYLOAD_ROWS, 1024), jnp.uint8), ((_PAYLOAD_ROWS,), F32),
         ((_PAYLOAD_ROWS,), F32)],
    ),
    "paged_attention": (
        lambda q, k, v, t, pos: paged_attention.pallas_paged_attention(
            q, k, v, t, pos, interpret=False),
        [((_SERVE["max_slots"], 16, 48), BF), (_POOL, BF), (_POOL, BF),
         ((_SERVE["max_slots"], _SERVE["num_pages"]), jnp.int32),
         ((_SERVE["max_slots"],), jnp.int32)],
    ),
    "paged_chunk_attention": (
        lambda q, k, v, t, pos: paged_attention.pallas_paged_chunk_attention(
            q, k, v, t, pos, interpret=False),
        [((1, _SERVE["prefill_chunk"], 16, 48), BF), (_POOL, BF), (_POOL, BF),
         ((1, _SERVE["num_pages"]), jnp.int32), ((1,), jnp.int32)],
    ),
    "rglru_decode": (
        lambda h, a, b: decode_update.pallas_rglru_decode(h, a, b, interpret=False),
        [((4, 2560), F32)] * 3,
    ),
    "rglru_scan": (
        lambda a, b: rglru_scan.pallas_rglru_scan(a, b, interpret=False),
        [((2, 1024, 2560), F32)] * 2,
    ),
    "ssd_decode": (
        lambda s, d, x, b, c: decode_update.pallas_ssd_decode(
            s, d, x, b, c, interpret=False),
        [((4, 2048, 128), F32), ((4, 2048), F32), ((4, 2048), F32),
         ((4, 128), F32), ((4, 128), F32)],
    ),
    "ssd_chunk": (
        lambda x, dt, a, b, c: ssd_scan.ssd_chunk_kernel(x, dt, a, b, c, interpret=False),
        [((2, 8, 128, 32, 64), F32), ((2, 8, 128, 32), F32), ((32,), F32),
         ((2, 8, 128, 128), F32), ((2, 8, 128, 128), F32)],
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    text = _compile(fn, *args).as_text()
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel in the program"


def _stacked_step(one_chip, cfg, *, replicas, per_replica_batch, seq):
    """The stacked runtime's compiled inner step at ``cfg`` for one chip."""
    cfg = dataclasses.replace(cfg, kernels=COMPILED)
    tcfg = method_config("noloco", inner_lr=3e-3, total_steps=6, inner_steps=2,
                         kernels=COMPILED)
    prog = GossipProgram(cfg, tcfg, replicas=replicas)
    tok = jax.ShapeDtypeStruct((replicas, per_replica_batch, seq), jnp.int32)
    batch = {"tokens": tok, "labels": tok}
    on_chip = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree
    )
    state = jax.eval_shape(prog.init_state, batch)
    rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return prog._inner_jit.lower(on_chip(state), on_chip(batch), on_chip(rng)).compile()


def test_smoke_train_step_fits_one_chip(one_chip):
    """chip_smoke.py's train phase: full width and depth, seq 1024."""
    t = chip_smoke.TRAIN
    compiled = _stacked_step(
        one_chip, registry.get_config(chip_smoke.ARCH),
        replicas=t["replicas"], per_replica_batch=t["per_replica_batch"],
        seq=chip_smoke.SEQ,
    )
    assert "tpu_custom_call" in compiled.as_text()
    assert _memory_bytes(compiled) < HBM_BYTES


def test_four_chip_stacked_reference_fits_one_chip(one_chip):
    """chip_smoke.py --chips 4 runs its stacked reference on one chip, at
    the deepest cut that fits: one more layer would not."""
    f = chip_smoke.FOUR
    base = registry.get_config(chip_smoke.ARCH)
    used = {}
    for layers in (f["num_layers"], f["num_layers"] + 1):
        compiled = _stacked_step(
            one_chip, dataclasses.replace(base, num_layers=layers),
            replicas=f["replicas"], per_replica_batch=f["per_replica_batch"],
            seq=chip_smoke.SEQ,
        )
        used[layers] = _memory_bytes(compiled)
    assert used[f["num_layers"]] < HBM_BYTES <= used[f["num_layers"] + 1]


def test_shard_map_noloco_compiles_for_2x2(topo):
    """chip_smoke.py --chips 4's shard_map path on the v5e:2x2 mesh: the
    inner step fits each chip and the outer step moves replicas by
    collective-permute with no all-reduce."""
    f = chip_smoke.FOUR
    r, b, s = f["replicas"], f["per_replica_batch"], chip_smoke.SEQ
    pairs = pairing.ppermute_pairs(0, r, seed=0)  # eager: before the mesh
    mesh = Mesh(np.array(topo.devices[:r]).reshape(r, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = dataclasses.replace(registry.get_config(chip_smoke.ARCH),
                              num_layers=f["num_layers"], kernels=COMPILED)
    tcfg = method_config("noloco", inner_lr=3e-3, total_steps=f["steps"],
                         inner_steps=f["inner_steps"], kernels=COMPILED)
    plan = PL.make_plan("gossip_dp", mesh, shape_kind="train")
    stacked = jax.eval_shape(
        lambda: ST.stack_replicas(M.init_params(jax.random.PRNGKey(0), cfg), r)
    )
    tok = jax.ShapeDtypeStruct((r * b, s), jnp.int32)
    batch = {"tokens": tok, "labels": tok}

    def placed(tree, shardings):
        return jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
            tree, shardings,
        )

    with jax.set_mesh(mesh):
        bundle = ST.build_train_step(cfg, plan, mesh, stacked, batch, tcfg.inner)
        vals, _ = unzip(stacked)
        theta = placed(vals, bundle.theta_shardings)
        opt = placed(jax.eval_shape(lambda v: ST.init_opt_state(v, r), vals),
                     bundle.opt_shardings)
        bt = placed(batch, PL.shardings(mesh, ST.batch_pspecs(plan, batch)))
        inner = bundle.step_fn.lower(theta, opt, bt).compile()
        outer_fn = ST.build_outer_step(plan, mesh, bundle.pspecs, tcfg.outer,
                                       pairs, comm_cfg=tcfg.comm,
                                       kernel_cfg=COMPILED)
        step = jax.ShapeDtypeStruct((r,), jnp.int32,
                                    sharding=NamedSharding(mesh, P("data")))
        outer = outer_fn.lower(theta, theta, theta, step).compile()
    assert _memory_bytes(inner) < HBM_BYTES
    counts = rf.collective_bytes(outer.as_text(), model_size=1).counts
    assert counts["collective-permute"] > 0
    assert counts["all-reduce"] == 0
