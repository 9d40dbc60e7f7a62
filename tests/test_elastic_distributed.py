"""Elastic shard_map runtime: the per-membership-view program pool drives
``train_distributed`` through churn (ISSUE 5 acceptance).

Subprocess tests on 8 XLA-forced host devices (like test_multidevice.py);
the pure pool-key/pairing logic is tested in-process below them."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

pytestmark = pytest.mark.multidevice

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(code: str, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


PRELUDE = """
import json
import jax, jax.numpy as jnp, numpy as np
from repro.comm import CommConfig
from repro.core.elastic import ElasticContext
from repro.core.outer import OuterConfig
from repro.core.pairing import Membership
from repro.core import pairing
from repro.data import LoaderConfig
from repro.launch.mesh import make_test_mesh
from repro.launch.train_distributed import DistributedTrainer
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig
from repro.parallel import plans as PL, steps as ST
from repro.sim import FaultPlan, SimCluster
from repro.train import DistributedProgram, LoopConfig, make_loop

CFG = ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                  d_ff=128, vocab_size=128, dtype="float32", remat=False)

def make_trainer(elastic=None, schedule="random", inner_steps=4, seed=0,
                 stale="naive"):
    mesh = make_test_mesh(8, 1)
    plan = PL.make_plan("gossip_dp", mesh, shape_kind="train")
    return DistributedTrainer(
        cfg=CFG, mesh=mesh, plan=plan,
        outer_cfg=OuterConfig(method="noloco", inner_steps=inner_steps,
                              stale=stale),
        inner_cfg=AdamWConfig(lr=3e-3, weight_decay=0.0),
        schedule=schedule, seed=seed, elastic=elastic,
    )

def make_run(trainer, plan_events, steps, ckpt_dir=None, resume=False,
             eval_every=0, reassign=False, ckpt_every=0, async_clock=None):
    program = DistributedProgram(trainer)
    sim = None
    if plan_events is not None:
        sim = SimCluster(program, FaultPlan.build(plan_events),
                         reassign_data=reassign, async_clock=async_clock)
    loop = make_loop(
        sim or program,
        LoaderConfig(vocab_size=CFG.vocab_size, seq_len=32,
                     per_replica_batch=2, replicas=8, seed=0),
        LoopConfig(steps=steps, eval_every=eval_every, seed=0,
                   ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, resume=resume),
    )
    return loop, sim
"""


def test_full_membership_bit_identical_elastic_vs_static_vs_stacked():
    """At full membership the elastic pool program IS the static program
    (same compiled path), and both match the stacked outer step bit for bit
    where fp allows — the ISSUE 5 equality acceptance."""
    out = _run(PRELUDE + """
from repro.core import outer as outer_lib
from repro.models import model as M
from repro.models.common import unzip

mesh = make_test_mesh(8, 1)
plan = PL.make_plan("gossip_dp", mesh, shape_kind="train")
params = M.init_params(jax.random.PRNGKey(0), CFG)
stacked = ST.stack_replicas(params, plan.replicas)
vals, _ = unzip(stacked)
pspecs = PL.param_pspecs(plan, mesh, stacked)
ocfg = OuterConfig(method="noloco", inner_steps=4)

pool = ST.OuterProgramPool(plan, mesh, pspecs, ocfg, seed=0)
full = Membership.full(8)
# elastic pairs at full membership == static pairs, same pool key
slot, pairs_e = pool.pairs_for(3, full)
slot_s, pairs_s = pool.pairs_for(3, None)
assert slot == slot_s and pairs_e == pairs_s
# the full-membership view key is the STATIC key: same compiled program object
fn_e, info_e = pool.program(3, full)
fn_s, info_s = pool.program(3, None)
assert fn_e is fn_s and info_s["compiled"] is False

key = jax.random.PRNGKey(5)
theta_v = jax.tree.map(lambda x: x + jax.random.normal(key, x.shape) * 0.1, vals)
sh = PL.shardings(mesh, pspecs)
import jax.sharding as jsh
step_sh = jsh.NamedSharding(mesh, jsh.PartitionSpec("data"))
with jax.set_mesh(mesh):
    theta = jax.device_put(theta_v, sh)
    phi = jax.device_put(vals, sh)
    delta = jax.tree.map(jnp.zeros_like, phi)
    stepc = jax.device_put(jnp.full((8,), 3, jnp.int32), step_sh)
    th2, phi2, d2, _ = fn_e(theta, phi, delta, stepc)

# stacked reference with the SAME pairing (pool slot 3)
partner = jnp.asarray(pairing.partner_table(slot, 8))
state = outer_lib.OuterState(phi=jax.device_get(vals),
                             delta=jax.tree.map(np.zeros_like, jax.device_get(vals)),
                             step=jnp.asarray(3, jnp.int32))
new_state, new_theta = outer_lib.outer_step_stacked(state, theta_v, ocfg, partner=partner)
for a, b in zip(jax.tree.leaves(jax.device_get(phi2)), jax.tree.leaves(new_state.phi)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
for a, b in zip(jax.tree.leaves(jax.device_get(th2)), jax.tree.leaves(new_theta)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("BIT IDENTICAL")
""")
    assert "BIT IDENTICAL" in out


def test_elastic_pool_program_freezes_inactive_and_matches_stacked():
    """Under churn the pool compiles a membership-view program whose result
    matches the stacked elastic outer step bit for bit: participants gossip
    over the elastic pairing, dropped replicas' (θ, φ, δ) pass through."""
    out = _run(PRELUDE + """
from repro.core import outer as outer_lib
from repro.models import model as M
from repro.models.common import unzip

mesh = make_test_mesh(8, 1)
plan = PL.make_plan("gossip_dp", mesh, shape_kind="train")
params = M.init_params(jax.random.PRNGKey(0), CFG)
stacked = ST.stack_replicas(params, plan.replicas)
vals, _ = unzip(stacked)
pspecs = PL.param_pspecs(plan, mesh, stacked)
ocfg = OuterConfig(method="noloco", inner_steps=4)
pool = ST.OuterProgramPool(plan, mesh, pspecs, ocfg, seed=0)

mem = Membership.full(8).drop([3, 5])
slot, pairs = pool.pairs_for(2, mem)
fn, info = pool.program(2, mem)
assert info["compiled"] is True

key = jax.random.PRNGKey(7)
theta_v = jax.tree.map(lambda x: x + jax.random.normal(key, x.shape) * 0.1, vals)
delta_v = jax.tree.map(lambda x: jnp.zeros_like(x), vals)
sh = PL.shardings(mesh, pspecs)
import jax.sharding as jsh
step_sh = jsh.NamedSharding(mesh, jsh.PartitionSpec("data"))
with jax.set_mesh(mesh):
    th2, phi2, d2, _ = fn(
        jax.device_put(theta_v, sh), jax.device_put(vals, sh),
        jax.device_put(delta_v, sh),
        jax.device_put(jnp.full((8,), 2, jnp.int32), step_sh),
    )

partner = jnp.asarray(pairing.elastic_partner_table(slot, mem, seed=0))
state = outer_lib.OuterState(phi=jax.device_get(vals),
                             delta=jax.device_get(delta_v),
                             step=jnp.asarray(2, jnp.int32))
new_state, new_theta = outer_lib.outer_step_stacked(
    state, theta_v, ocfg, partner=partner,
    active=jnp.asarray(mem.active_array()))
for a, b in zip(jax.tree.leaves(jax.device_get(phi2)), jax.tree.leaves(new_state.phi)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
for a, b in zip(jax.tree.leaves(jax.device_get(th2)), jax.tree.leaves(new_theta)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
# dropped rows really froze: compare against the program INPUTS
for got, orig in zip(jax.tree.leaves(jax.device_get(th2)), jax.tree.leaves(theta_v)):
    np.testing.assert_array_equal(np.asarray(got)[3], np.asarray(orig)[3])
    np.testing.assert_array_equal(np.asarray(got)[5], np.asarray(orig)[5])
print("ELASTIC MATCH")
""")
    assert "ELASTIC MATCH" in out


def test_acceptance_distributed_drop2_rejoin(tmp_path):
    """ISSUE 5 acceptance: 8-replica ``train_distributed`` under the
    drop-2/rejoin plan completes with ≤ pool-bound recompiles, lands its
    final eval within 5% of the healthy run, and resume-after-churn
    reproduces the uninterrupted trajectory exactly."""
    d = str(tmp_path / "dist_elastic")
    out = _run(PRELUDE + f"""
EVENTS = [
    {{"kind": "drop", "round": 1, "replicas": [3, 5]}},
    {{"kind": "rejoin", "round": 4, "replicas": [3, 5]}},
]
STEPS, M_INNER = 24, 4

# healthy baseline
t0 = make_trainer(elastic=ElasticContext(world=8))
loop0, _ = make_run(t0, [], STEPS, eval_every=STEPS)
healthy = loop0.run()

# faulted run (checkpointing at step 12, mid-churn — rounds 1-2 done,
# the rejoin still pending — so the resume leg below restarts from there)
t1 = make_trainer(elastic=ElasticContext(world=8))
loop1, sim1 = make_run(t1, EVENTS, STEPS, eval_every=STEPS,
                       ckpt_dir={d!r}, ckpt_every=12)
res = loop1.run()
stats = t1.pool.stats()
assert stats["misses"] <= stats["max_programs_per_view"] * 3 + 1, stats
assert np.isfinite(res["losses"]).all()
he, fe = healthy["evals"][-1][1], res["evals"][-1][1]
assert abs(fe - he) / he < 0.05, (fe, he)
rounds = sim1.rounds()
by_round = {{r["round"]: r for r in rounds}}
for k in (1, 2, 3):
    assert by_round[k]["active"] == [0, 1, 2, 4, 6, 7], by_round[k]
    assert by_round[k]["partner"][3] == 3 and by_round[k]["partner"][5] == 5
for k in (0, 4, 5):
    assert by_round[k]["active"] == list(range(8)), by_round[k]
assert sim1.membership.epoch == 2 and sim1.membership.is_full

# resume from the step-12 checkpoint (written with 6 actives): the
# continued run must reproduce the uninterrupted faulted trajectory exactly
import os, shutil
for name in os.listdir({d!r}):
    if name != "step_00000012":
        shutil.rmtree(os.path.join({d!r}, name))
t3 = make_trainer(elastic=ElasticContext(world=8))
loop3, sim3 = make_run(t3, EVENTS, STEPS, ckpt_dir={d!r}, resume=True)
cont = loop3.run()
assert cont["start_step"] == 12
np.testing.assert_array_equal(np.asarray(res["losses"][12:]),
                              np.asarray(cont["losses"]))
for a, b in zip(jax.tree.leaves(jax.device_get(res["state"]["theta"])),
                jax.tree.leaves(jax.device_get(cont["state"]["theta"]))):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
assert sim3.membership.epoch == 2 and sim3.membership.is_full
print("ACCEPTANCE OK", json.dumps(stats))
""")
    assert "ACCEPTANCE OK" in out


def test_hypercube_schedule_bounded_pool_under_churn():
    """The hypercube schedule compiles ≤ log2(world) programs per membership
    view while training through a drop."""
    out = _run(PRELUDE + """
EVENTS = [{"kind": "drop", "round": 1, "replicas": [2]}]
t = make_trainer(elastic=ElasticContext(world=8), schedule="hypercube",
                 inner_steps=2)
loop, sim = make_run(t, EVENTS, 16)
res = loop.run()
stats = t.pool.stats()
assert stats["max_programs_per_view"] == 3
# two views seen (full, minus-2): ≤ 3 programs each
assert stats["pool_size"] <= 6, stats
assert np.isfinite(res["losses"]).all()
# post-drop rounds never touch replica 2
for r in sim.rounds():
    if r["round"] >= 1:
        assert r["partner"][2] == 2
print("HYPERCUBE OK", json.dumps(stats))
""")
    assert "HYPERCUBE OK" in out


def test_distributed_reassign_data_deterministic():
    """Elastic data reassignment on the shard_map runtime: survivors consume
    dropped streams deterministically — two identical runs produce identical
    losses, and differ from the skip-streams default."""
    out = _run(PRELUDE + """
EVENTS = [{"kind": "drop", "round": 1, "replicas": [0, 1]}]
runs = []
for reassign in (True, True, False):
    t = make_trainer(elastic=ElasticContext(world=8), inner_steps=2)
    loop, _ = make_run(t, EVENTS, 8, reassign=reassign)
    runs.append(loop.run()["losses"])
np.testing.assert_array_equal(np.asarray(runs[0]), np.asarray(runs[1]))
assert not np.array_equal(np.asarray(runs[0][3:]), np.asarray(runs[2][3:]))
print("REASSIGN OK")
""")
    assert "REASSIGN OK" in out


def test_async_clock_distributed_tau0_bitwise_and_straggler():
    """Asynchronous round clocks on the shard_map runtime: a rate-1 async
    world reduces to the legacy synchronous program bit for bit (same pool
    fast path), and a 2x straggler syncs late with a stale Δ — zero blocked
    syncs, max τ = 1 — for both stale rules."""
    out = _run(PRELUDE + """
# legacy synchronous reference
t0 = make_trainer(elastic=ElasticContext(world=8), inner_steps=2)
loop0, _ = make_run(t0, [], 12)
ref = loop0.run()

# rate-1 async world: bitwise identical, zero staleness telemetry
t1 = make_trainer(elastic=ElasticContext(world=8), inner_steps=2)
loop1, sim1 = make_run(t1, [], 12, async_clock=True)
res = loop1.run()
np.testing.assert_array_equal(np.asarray(ref["losses"]), np.asarray(res["losses"]))
for a, b in zip(jax.tree.leaves(jax.device_get(ref["state"]["theta"])),
                jax.tree.leaves(jax.device_get(res["state"]["theta"]))):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
assert res["max_staleness"] == 0 and res["blocked_syncs"] == 0, res

# 2x straggler on its own clock, both stale rules
EVENTS = [{"kind": "rate", "round": 0, "replicas": [1], "rate": 0.5}]
for stale in ("naive", "momentum"):
    t2 = make_trainer(elastic=ElasticContext(world=8), inner_steps=2,
                      stale=stale)
    loop2, sim2 = make_run(t2, EVENTS, 16)
    r2 = loop2.run()
    assert np.isfinite(r2["losses"]).all()
    assert r2["blocked_syncs"] == 0, (stale, r2["blocked_syncs"])
    assert r2["max_staleness"] == 1, (stale, r2["max_staleness"])
    ticks = [h for h in sim2.history if h.get("event") == "round"]
    assert any(1 not in h["due"] for h in ticks)   # straggler skipped a tick
    assert any(1 in h["due"] and h["staleness"][1] == 1 for h in ticks)
print("ASYNC DISTRIBUTED OK")
""")
    assert "ASYNC DISTRIBUTED OK" in out


def test_partial_partition_matches_stacked_semantics():
    """A partition that covers only part of the active set: uncovered actives
    must run the self-momentum path (matching the stacked runtime bit for
    bit), NOT freeze — regression test for the participant-mask derivation."""
    out = _run(PRELUDE + """
from repro.core import outer as outer_lib
from repro.models import model as M
from repro.models.common import unzip
import jax.sharding as jsh

mesh = make_test_mesh(8, 1)
plan = PL.make_plan("gossip_dp", mesh, shape_kind="train")
stacked = ST.stack_replicas(M.init_params(jax.random.PRNGKey(0), CFG), 8)
vals, _ = unzip(stacked)
pspecs = PL.param_pspecs(plan, mesh, stacked)
ocfg = OuterConfig(method="noloco", inner_steps=4)
pool = ST.OuterProgramPool(plan, mesh, pspecs, ocfg, seed=0)

mem = Membership.full(8)
groups = ((0, 1, 2),)  # actives 3..7 uncovered: sit out, self-momentum
slot, pairs = pool.pairs_for(1, mem, groups)
fn, info = pool.program(1, mem, groups)
key = jax.random.PRNGKey(9)
theta_v = jax.tree.map(lambda x: x + jax.random.normal(key, x.shape) * 0.1, vals)
sh = PL.shardings(mesh, pspecs)
step_sh = jsh.NamedSharding(mesh, jsh.PartitionSpec("data"))
with jax.set_mesh(mesh):
    th2, phi2, d2, _ = fn(jax.device_put(theta_v, sh), jax.device_put(vals, sh),
                          jax.device_put(jax.tree.map(jnp.zeros_like, vals), sh),
                          jax.device_put(jnp.full((8,), 1, jnp.int32), step_sh))
partner = jnp.asarray(pairing.elastic_partner_table(1, mem, seed=0, groups=groups))
state = outer_lib.OuterState(phi=jax.device_get(vals),
                             delta=jax.tree.map(np.zeros_like, jax.device_get(vals)),
                             step=jnp.asarray(1, jnp.int32))
new_state, new_theta = outer_lib.outer_step_stacked(
    state, theta_v, ocfg, partner=partner, active=jnp.asarray(mem.active_array()))
for a, b in zip(jax.tree.leaves(jax.device_get(phi2)), jax.tree.leaves(new_state.phi)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
for a, b in zip(jax.tree.leaves(jax.device_get(th2)), jax.tree.leaves(new_theta)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
got = np.asarray(jax.tree.leaves(jax.device_get(th2))[0])
orig = np.asarray(jax.tree.leaves(theta_v)[0])
assert not np.array_equal(got[4], orig[4]), "uncovered active must not freeze"
print("PARTIAL PARTITION OK")
""")
    assert "PARTIAL PARTITION OK" in out
