"""Distributed-runtime tests on 8 forced host devices.

XLA device count is locked at first jax init, so these run in a SUBPROCESS
with XLA_FLAGS set (conftest must NOT set it globally)."""
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.multidevice

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from repro.models.config import ModelConfig
from repro.models import model as M
from repro.models.common import unzip, values_of
from repro.parallel import plans as PL, steps as ST
from repro.core.outer import OuterConfig
from repro.core import pairing
from repro.optim import AdamWConfig
from repro.launch.mesh import make_test_mesh
mesh = make_test_mesh(4, 2)
cfg = ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                  vocab_size=256, dtype="float32", remat=False)
plan = PL.make_plan("gossip_dp", mesh, shape_kind="train")
params = M.init_params(jax.random.PRNGKey(0), cfg)
stacked = ST.stack_replicas(params, plan.replicas)
vals, _ = unzip(stacked)
"""


def test_sharded_train_matches_stacked_simulation():
    """The shard_map train step must produce the SAME losses as the local
    vmap simulation (same math, different distribution)."""
    out = _run(PRELUDE + """
B, S = 8, 16
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),(B,S),0,256),
         "labels": jax.random.randint(jax.random.PRNGKey(2),(B,S),0,256)}
inner = AdamWConfig(lr=1e-3, weight_decay=0.0)
with jax.set_mesh(mesh):
    bundle = ST.build_train_step(cfg, plan, mesh, stacked, batch, inner)
    theta = jax.device_put(vals, bundle.theta_shardings)
    opt = ST.init_opt_state(theta, plan.replicas)
    opt = jax.device_put(opt, bundle.opt_shardings)
    dist_losses = []
    for i in range(3):
        theta, opt, mets = bundle.step_fn(theta, opt, batch)
        dist_losses.append(np.asarray(mets["loss"]))

# local stacked simulation of the same thing
from repro.parallel.sharding import ShardCtx
from repro.optim import adamw_init, adamw_update
ctx = ShardCtx.local()
R = plan.replicas
bt = {k: v.reshape(R, B//R, S) for k, v in batch.items()}
th = vals
opt2 = jax.vmap(adamw_init)(th)
def one(p, b):
    return M.loss_fn(p, cfg, b, ctx)[0]
for i in range(3):
    losses, grads = jax.vmap(jax.value_and_grad(one))(th, bt)
    th, opt2, _ = jax.vmap(lambda g,o,p: adamw_update(g,o,p, inner))(grads, opt2, th)
    err = np.abs(np.asarray(losses) - dist_losses[i]).max()
    assert err < 2e-4, (i, err, losses, dist_losses[i])
print("MATCH")
""")
    assert "MATCH" in out


def test_gossip_outer_step_pair_exchange_correct():
    """ppermute gossip on the mesh == stacked gather implementation."""
    out = _run(PRELUDE + """
from repro.core import outer as outer_lib
pspecs = PL.param_pspecs(plan, mesh, stacked)
perm_pairs = pairing.ppermute_pairs(0, plan.replicas)
ocfg = OuterConfig(method="noloco")
with jax.set_mesh(mesh):
    fn = ST.build_outer_step(plan, mesh, pspecs, ocfg, perm_pairs)
    sh = PL.shardings(mesh, pspecs)
    key = jax.random.PRNGKey(5)
    theta = jax.tree.map(lambda x: x + jax.random.normal(key, x.shape)*0.1, vals)
    theta_host = jax.device_get(theta)   # donation below deletes the device copy
    theta = jax.device_put(theta, sh)
    phi = jax.device_put(vals, sh)
    delta = jax.tree.map(jnp.zeros_like, phi)
    import jax.sharding as jsh
    stepc = jax.device_put(jnp.zeros((plan.replicas,), jnp.int32),
                           jsh.NamedSharding(mesh, jsh.PartitionSpec("data")))
    th2, phi2, d2, _ = fn(theta, phi, delta, stepc)

# stacked reference
partner = jnp.asarray(pairing.partner_table(0, plan.replicas))
state = outer_lib.init_outer_state(jax.device_get(vals))
new_state, new_theta = outer_lib.outer_step_stacked(
    state, theta_host, ocfg, partner=partner)
for a, b in zip(jax.tree.leaves(jax.device_get(phi2)), jax.tree.leaves(new_state.phi)):
    assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-5), np.abs(a-b).max()
print("GOSSIP MATCH")
""")
    assert "GOSSIP MATCH" in out


def test_outer_hlo_has_permute_not_allreduce():
    """THE paper claim, verified on HLO: NoLoCo outer = collective-permute
    only; DiLoCo outer = all-reduce."""
    out = _run(PRELUDE + """
from repro.launch import roofline as rf
pspecs = PL.param_pspecs(plan, mesh, stacked)
perm_pairs = pairing.ppermute_pairs(0, plan.replicas)
import jax.sharding as jsh
rep_sh = jax.ShapeDtypeStruct((plan.replicas,), jnp.int32)
theta_abs = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), vals)
with jax.set_mesh(mesh):
    for method, want, forbid in (("noloco", "collective-permute", "all-reduce"),
                                 ("diloco", "all-reduce", "collective-permute")):
        ocfg = OuterConfig(method=method, alpha=0.3 if method=="diloco" else 0.5)
        fn = ST.build_outer_step(plan, mesh, pspecs, ocfg, perm_pairs)
        hlo = fn.lower(theta_abs, theta_abs, theta_abs, rep_sh).compile().as_text()
        stats = rf.collective_bytes(hlo, model_size=2)
        assert stats.counts[want] > 0, (method, stats.counts)
        assert stats.counts[forbid] == 0, (method, stats.counts)
        print(method, stats.counts)
print("HLO OK")
""")
    assert "HLO OK" in out


def test_decode_sharded_matches_local():
    """Sequence-sharded flash-decode (kv_shard_seq) == local decode."""
    out = _run(PRELUDE + """
from repro.parallel.sharding import ShardCtx
import jax.sharding as jsh
dcfg = cfg
plan_d = PL.make_plan("gossip_dp", mesh, shape_kind="decode", has_global_attention=True)
assert plan_d.kv_shard_seq
B, CACHE = 8, 32
caches = M.init_cache_tree(dcfg, B, CACHE)
cvals, _ = unzip(jax.eval_shape(lambda: caches))
caches_real = values_of(caches)
toks = jax.random.randint(jax.random.PRNGKey(3), (B, 1), 0, 256)
bspecs = ST.batch_pspecs(plan_d, {"tokens": toks})
with jax.set_mesh(mesh):
    fn, (pspecs, cspecs) = ST.build_decode_step(dcfg, plan_d, mesh, stacked, caches, bspecs)
    theta = jax.device_put(vals, PL.shardings(mesh, pspecs))
    cache_put = jax.device_put(caches_real, PL.shardings(mesh, cspecs))
    tok_sh = jsh.NamedSharding(mesh, bspecs["tokens"])
    idx_sh = jsh.NamedSharding(mesh, jsh.PartitionSpec())
    # place a couple of tokens in the cache first via two decode calls
    lg1, cache_put = fn(theta, cache_put, jax.device_put(toks, tok_sh),
                        jax.device_put(jnp.asarray(0, jnp.int32), idx_sh))
    lg2, cache_put = fn(theta, cache_put, jax.device_put(toks + 1, tok_sh),
                        jax.device_put(jnp.asarray(1, jnp.int32), idx_sh))

# local reference: replica r serves batch rows [r*B/R:(r+1)*B/R]
ctx = ShardCtx.local()
R = plan_d.replicas
errs = []
for r in range(R):
    rows = slice(r*B//R, (r+1)*B//R)
    th_r = jax.tree.map(lambda x: x[r], vals)
    c_r = values_of(M.init_cache_tree(dcfg, B//R, CACHE))
    l1, c_r = M.decode_step(th_r, dcfg, toks[rows], jnp.asarray(0), c_r, ctx)
    l2, c_r = M.decode_step(th_r, dcfg, (toks+1)[rows], jnp.asarray(1), c_r, ctx)
    errs.append(np.abs(np.asarray(l2) - np.asarray(lg2[rows])).max())
assert max(errs) < 2e-3, errs
print("DECODE MATCH", max(errs))
""")
    assert "DECODE MATCH" in out
