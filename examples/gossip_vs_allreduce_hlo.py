"""Inspect the compiled HLO of the NoLoCo vs DiLoCo outer step on an 8-device
host mesh: NoLoCo lowers to collective-permute ONLY; DiLoCo to all-reduce.
This is the paper's central systems claim, visible in the IR.

    python examples/gossip_vs_allreduce_hlo.py   (sets its own XLA_FLAGS)
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.core import pairing
from repro.core.outer import OuterConfig
from repro.launch import roofline as rf
from repro.launch.mesh import make_test_mesh
from repro.models import model as M
from repro.models.common import unzip
from repro.models.config import ModelConfig
from repro.parallel import plans as PL, steps as ST


def main() -> None:
    mesh = make_test_mesh(4, 2)
    cfg = ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                      d_ff=128, vocab_size=256, dtype="float32", remat=False)
    plan = PL.make_plan("gossip_dp", mesh)
    stacked = ST.stack_replicas(M.init_params(jax.random.PRNGKey(0), cfg), plan.replicas)
    vals, _ = unzip(stacked)
    theta_abs = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), vals)
    pspecs = PL.param_pspecs(plan, mesh, stacked)
    perm = pairing.ppermute_pairs(0, plan.replicas)
    rep = jax.ShapeDtypeStruct((plan.replicas,), jnp.int32)

    with jax.set_mesh(mesh):
        for method in ("noloco", "diloco"):
            ocfg = OuterConfig(method=method, alpha=0.5 if method == "noloco" else 0.3)
            fn = ST.build_outer_step(plan, mesh, pspecs, ocfg, perm)
            hlo = fn.lower(theta_abs, theta_abs, theta_abs, rep).compile().as_text()
            stats = rf.collective_bytes(hlo, model_size=2)
            print(f"{method:8s} collectives: {stats.counts}  "
                  f"bytes={stats.total_bytes:,}")


if __name__ == "__main__":
    main()
