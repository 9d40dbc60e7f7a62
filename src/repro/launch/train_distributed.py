"""Distributed NoLoCo training driver: the shard_map runtime
(parallel/steps.py) — per-replica inner AdamW steps with ZERO cross-replica
collectives, plus a gossip outer step every m steps from the per-membership-
view :class:`~repro.parallel.steps.OuterProgramPool` (ppermute needs static
permutations; the pool bounds recompiles to ``pairing_pool`` — or log2(world)
with ``--schedule hypercube`` — per membership view, recompiling only at
membership-view boundaries).

:class:`DistributedTrainer` owns the compiled programs and mesh state; the
step loop, eval cadence, telemetry and checkpoint/resume are the unified
engine's (:mod:`repro.train`, via :class:`~repro.train.DistributedProgram`).
Elasticity (drop / rejoin / straggle under ``--fault-plan``) is owned by a
:class:`~repro.core.elastic.ElasticContext` exactly as in the stacked
runtime, replayed by the same :class:`~repro.sim.SimCluster`, with rejoin
warm-start performed over the mesh and the membership epoch riding in the
checkpoint — resume-after-churn reproduces the trajectory exactly.

On a CPU it runs on forced host devices for validation:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
        python -m repro.launch.train_distributed --reduced --data 4 --model 2 --steps 40 \
        --ckpt-dir /tmp/dist0 --ckpt-every 20 --resume --log-jsonl /tmp/dist0.jsonl

On TPU the same code drives the production mesh (launch/mesh.py).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import time
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.comm import CommConfig, bytes_model, stream_partition
from repro.configs import registry
from repro.core.elastic import ElasticContext
from repro.core.outer import OuterConfig, StreamSchedule
from repro.kernels.dispatch import KernelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.data import LoaderConfig
from repro.models import model as model_api
from repro.models.common import unzip
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig
from repro.parallel import plans as plans_lib
from repro.parallel import steps as steps_lib

PyTree = Any


@dataclasses.dataclass
class DistributedTrainer:
    """Owns the compiled step functions and the replica-sharded state."""

    cfg: ModelConfig
    mesh: Any
    plan: plans_lib.Plan
    outer_cfg: OuterConfig
    inner_cfg: AdamWConfig
    comm_cfg: CommConfig = dataclasses.field(default_factory=CommConfig)
    kernel_cfg: KernelConfig = dataclasses.field(default_factory=KernelConfig)
    pairing_pool: int = 16        # precompiled random matchings, cycled
    schedule: str = "random"      # "random" pool | "hypercube" (log2 N programs)
    seed: int = 0
    elastic: ElasticContext | None = None  # None: fixed-world (no churn support)

    def __post_init__(self):
        if self.elastic is not None and self.elastic.world != self.plan.replicas:
            raise ValueError(
                f"elastic world {self.elastic.world} != plan replicas "
                f"{self.plan.replicas}"
            )
        self.comm_cfg.validate()
        if self.comm_cfg.streams > 1 and self.outer_cfg.method != "noloco":
            raise ValueError(
                "streams > 1 is a noloco-only feature (gossip pairing)"
            )
        # streaming outer steps (DESIGN.md §2): staggered per-stream syncs,
        # engaged for streams > 1 OR the φ-prefetch overlap (streams=1 +
        # overlap is the legacy §3.2 pre-send expressed as one stream, and —
        # unlike the retired spelling — it composes with elasticity via the
        # membership-epoch fallback)
        self._streaming = self.outer_cfg.method == "noloco" and (
            self.comm_cfg.streams > 1 or self.comm_cfg.overlap
        )
        self._schedule = None
        self._pre_partner = None
        self._pre_epoch = None
        self._stream_cost = None
        if self._streaming:
            s = self.comm_cfg.streams
            self._schedule = StreamSchedule(self.outer_cfg.inner_steps, s)
            self._pre_partner = np.full((s, self.plan.replicas), -1, np.int64)
            self._pre_epoch = np.full((s,), -1, np.int64)
        self.recompile_events: list[dict] = []
        self.stream_events: list[dict] = []

    # -- setup -------------------------------------------------------------

    def init_state(self, batch_example: dict):
        params = model_api.init_params(jax.random.PRNGKey(self.seed), self.cfg)
        stacked = steps_lib.stack_replicas(params, self.plan.replicas)
        vals, _ = unzip(stacked)
        with jax.set_mesh(self.mesh):
            self.bundle = steps_lib.build_train_step(
                self.cfg, self.plan, self.mesh, stacked, batch_example, self.inner_cfg
            )
            theta = jax.device_put(vals, self.bundle.theta_shardings)
            opt = jax.device_put(
                steps_lib.init_opt_state(theta, self.plan.replicas),
                self.bundle.opt_shardings,
            )
            # its own buffers: where the sharding is already the arrays'
            # (one replica on one device) a plain device_put returns them,
            # and the inner step's donation of theta would delete phi
            phi = jax.device_put(vals, self.bundle.theta_shardings, may_alias=False)
            delta = jax.tree.map(jnp.zeros_like, phi)
            step_c = jax.device_put(
                jnp.zeros((self.plan.replicas,), jnp.int32),
                NamedSharding(self.mesh, P(self.plan.replica_entry)),
            )
        self._theta_struct = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), theta
        )
        partition = None
        if self._streaming:
            # the partitioner's midpoint rule is scale-invariant, so the
            # STACKED struct yields the same leaf->stream assignment the
            # squeezed per-replica view inside shard_map sees
            partition = stream_partition(
                self._theta_struct, self.comm_cfg.streams, fuse=self.comm_cfg.fuse
            )
        self.pool = steps_lib.OuterProgramPool(
            self.plan, self.mesh, self.bundle.pspecs, self.outer_cfg,
            comm_cfg=self.comm_cfg, kernel_cfg=self.kernel_cfg,
            schedule=self.schedule, pairing_pool=self.pairing_pool,
            seed=self.seed, partition=partition,
        )
        self._bspecs = steps_lib.batch_pspecs(self.plan, batch_example)
        state = {"theta": theta, "opt": opt, "phi": phi, "delta": delta,
                 "outer_step": step_c, "inner_step": 0}
        if self.comm_cfg.overlap:
            # Bootstrap for the §3.2 φ-prefetch: all replicas start from the
            # SAME φ_0, so "the partner's φ" for the first outer step is just
            # our own copy — no exchange needed before round 0.
            state["phi_pre"] = jax.tree.map(jnp.copy, phi)
        return state

    # -- elastic helpers ----------------------------------------------------

    @functools.cached_property
    def _take_rows(self):
        """jit: gather the given replica rows of a stacked tree."""
        return jax.jit(lambda tree, ids: jax.tree.map(
            lambda x: jnp.take(x, ids, axis=0), tree
        ))

    @functools.cached_property
    def _put_rows(self):
        """jit: scatter saved replica rows back into a stacked tree."""
        return jax.jit(lambda tree, ids, rows: jax.tree.map(
            lambda x, r: x.at[ids].set(r), tree, rows
        ))

    @functools.cached_property
    def _warm_start_fn(self):
        """jit: rejoin surgery over the mesh — the comeback replica adopts a
        live peer's slow weights as BOTH φ and θ (fresh look-ahead), zero
        outer momentum, zero inner-optimizer moments."""
        def surgery(theta, phi, delta, mu, nu, count, replica, source):
            adopt = lambda x: x.at[replica].set(x[source])
            zero = lambda x: x.at[replica].set(jnp.zeros_like(x[replica]))
            theta = jax.tree.map(
                lambda th, p: th.at[replica].set(p[source]), theta, phi
            )
            return (
                theta,
                jax.tree.map(adopt, phi),
                jax.tree.map(zero, delta),
                jax.tree.map(zero, mu),
                jax.tree.map(zero, nu),
                count.at[replica].set(0),
            )
        return jax.jit(surgery)

    def warm_start(self, state: dict, replica: int, source: int) -> dict:
        theta, phi, delta, mu, nu, count = self._warm_start_fn(
            state["theta"], state["phi"], state["delta"],
            state["opt"].mu, state["opt"].nu, state["opt"].count,
            jnp.asarray(replica), jnp.asarray(source),
        )
        from repro.optim import AdamWState

        return dict(state, theta=theta, phi=phi, delta=delta,
                    opt=AdamWState(mu=mu, nu=nu, count=count))

    def _active_mask(self) -> np.ndarray | None:
        if self.elastic is None:
            return None
        return self.elastic.active_array()

    # -- steps ---------------------------------------------------------------

    def stage_batch(self, batch: dict) -> dict:
        """Place global ``(R*B, S)`` rows on the mesh, sharded by replica."""
        with jax.set_mesh(self.mesh):
            return jax.device_put(batch, plans_lib.shardings(self.mesh, self._bspecs))

    def inner_step(self, state, batch):
        """One inner step on a batch placed by :meth:`stage_batch`."""
        mask = self._active_mask()
        snap = None
        if mask is not None:
            # freeze dropped replicas: the step function donates its inputs,
            # so their pre-step rows are snapshotted and written back after
            ids = jnp.asarray(np.nonzero(~mask)[0])
            snap = (
                self._take_rows(state["theta"], ids),
                self._take_rows(state["opt"], ids),
            )
        with jax.set_mesh(self.mesh):
            with obs.span("train.dispatch"):
                theta, opt, metrics = self.bundle.step_fn(state["theta"], state["opt"], batch)
            if snap is not None:
                theta = self._put_rows(theta, ids, snap[0])
                opt = self._put_rows(opt, ids, snap[1])
        state = dict(state, theta=theta, opt=opt, inner_step=state["inner_step"] + 1)
        return state, metrics

    @staticmethod
    def _table_of(pairs) -> np.ndarray:
        """Partner table (dst indexed by src) of an ordered ppermute pair
        list — the canonical form the consume-vs-fallback check compares."""
        return np.asarray([d for _, d in pairs], dtype=np.int64)

    def _drain_compiles(self, info, t0: float, outer_index: int) -> None:
        if info["compiled"]:
            # first invocation of a fresh program: its wall-clock includes the
            # lazy XLA compile — the churn-induced stall telemetry measures
            for ev in self.pool.drain_events():
                self.recompile_events.append(dict(
                    ev, wall_s=round(time.perf_counter() - t0, 4),
                    outer_index=outer_index,
                ))

    def _run_outer(self, state, fn, info, outer_index: int, *,
                   prefetch: bool = False) -> dict:
        """Call one outer program on ``state`` (with the φ-prefetch buffer
        when ``prefetch``), and log a pool miss's recompile event."""
        keys = ("theta", "phi", "delta") + (("phi_pre",) if prefetch else ()) + ("outer_step",)
        t0 = time.perf_counter()
        with obs.span("outer.dispatch"), jax.set_mesh(self.mesh):
            out = fn(*(state[k] for k in keys))
        self._drain_compiles(info, t0, outer_index)
        return dict(state, **dict(zip(keys, out)))

    def maybe_outer_step(self, state):
        if self._streaming:
            return self._maybe_stream_sync(state)
        if state["inner_step"] % self.outer_cfg.inner_steps:
            return state, False
        outer_index = state["inner_step"] // self.outer_cfg.inner_steps - 1
        with obs.span("train.outer_step", outer_index=outer_index, stream=0) as sp:
            with obs.span("outer.plan"):
                if self.elastic is None:
                    fn, info = self.pool.program(outer_index)
                else:
                    partner_fn = None
                    if self.outer_cfg.method == "noloco":
                        # the ppermute pairs ARE the audit table: dst indexed by src
                        def partner_fn(parts):
                            return self._table_of(self.pool.pairs_for(
                                outer_index, parts, self.elastic.partition
                            )[1])

                    plan = self.elastic.plan_round(partner_fn)
                    if plan.all_absent:
                        fn, info = self._all_absent_program(outer_index)
                    else:
                        fn, info = self.pool.program(
                            outer_index, plan.participants, self.elastic.partition
                        )
            sp.set_metadata(compiled=info["compiled"])
            return self._run_outer(state, fn, info, outer_index), True

    def outer_step_async(self, state, *, sync_index: int, due, staleness):
        """One merged sync tick of the asynchronous clock on the compiled
        shard_map path (DESIGN.md §7).

        The ppermute pairing is drawn over ALL round participants at key
        ``sync_index`` (non-due replicas serve as passive sources — their
        in-progress (Δ, φ) shards move, their state stays frozen); only the
        ``due`` set applies the update, and under ``stale="momentum"`` each
        shard's Δ is discounted by its staleness before the exchange.  The
        (update-mask, staleness) pair is baked into the compiled program and
        keyed in the pool alongside the membership view; the
        full-participation / τ=0 tick is the LEGACY pool program — the same
        compiled object, bit for bit."""
        if self.outer_cfg.method != "noloco":
            raise ValueError("asynchronous merged-tick sync is NoLoCo-only")
        if self._streaming:
            raise ValueError(
                "the asynchronous clock does not compose with streaming "
                "outer steps / φ-prefetch yet"
            )
        if self.elastic is None:
            raise ValueError("outer_step_async needs an ElasticContext")

        def partner_fn(parts):
            return self._table_of(self.pool.pairs_for(
                sync_index, parts, self.elastic.partition
            )[1])

        with obs.span("train.outer_step", outer_index=sync_index, stream=0) as sp:
            with obs.span("outer.plan"):
                plan = self.elastic.plan_round(partner_fn)
                if plan.all_absent:
                    fn, info = self._all_absent_program(sync_index)
                else:
                    due = np.asarray(due, dtype=bool)
                    tau = np.asarray(staleness)
                    update = due.copy()
                    if plan.active is not None:
                        update &= np.asarray(plan.active, dtype=bool)
                    if update.all() and not tau.any():
                        # everyone due, nobody late: the legacy synchronous program
                        fn, info = self.pool.program(
                            sync_index, plan.participants, self.elastic.partition
                        )
                    else:
                        stale_host = None
                        if self.outer_cfg.stale == "momentum" and tau.any():
                            stale_host = tau
                        fn, info = self.pool.program(
                            sync_index, plan.participants, self.elastic.partition,
                            update_mask=update, staleness=stale_host,
                        )
            sp.set_metadata(compiled=info["compiled"])
            return self._run_outer(state, fn, info, sync_index), True

    def _maybe_stream_sync(self, state):
        """One stream's staggered sync on the compiled shard_map path.

        Mirrors the stacked runtime's consume-vs-fallback rule exactly: a
        prefetched φ is consumed only when the pairing it was pre-sent along
        still holds (same membership epoch AND the recorded partner table
        equals this round's actual table) — otherwise that stream alone runs
        the blocking program variant (a pool LOOKUP, not a recompile of an
        existing entry); churn never blocks the other streams."""
        t = state["inner_step"]
        k = self._schedule.due(t)
        if k is None:
            return state, False
        i = self._schedule.sync_index(k, t)
        overlap = self.comm_cfg.overlap
        epoch = 0 if self.elastic is None else self.elastic.epoch
        with obs.span("train.outer_step", outer_index=i, stream=k) as sp:
            with obs.span("outer.plan"):
                fn, info, absent, consume, next_table = self._plan_stream_sync(
                    state, k, i, epoch)
            sp.set_metadata(compiled=info["compiled"])
            had_prefetch = bool(self._pre_epoch[k] >= 0) and not absent
            if absent:
                # every live replica timed out: freeze everything, advance the
                # sync counter (the shared whole-payload all-absent program —
                # no per-stream variant needed since nothing moves), and
                # invalidate this stream's prefetch: its pre-send was planned
                # for THIS sync and none was issued for the next one
                new = self._run_outer(state, fn, info, i)
                self._pre_epoch[k] = -1
            else:
                new = self._run_outer(state, fn, info, i, prefetch=overlap)
                if overlap:
                    self._pre_partner[k] = next_table
                    self._pre_epoch[k] = epoch
            self._record_stream_event(k, i, consume=consume,
                                      had_prefetch=had_prefetch)
            return new, True

    def _plan_stream_sync(self, state, k: int, i: int, epoch: int):
        """The program of stream ``k``'s sync ``i``: returns ``(fn, info,
        all_absent, consume, next_table)``, where ``next_table`` is the
        pairing the φ′ pre-send travels on (None without overlap)."""
        overlap = self.comm_cfg.overlap
        groups = None if self.elastic is None else self.elastic.partition
        participants = None
        if self.elastic is None:
            partner_table = self._table_of(self.pool.pairs_for(i)[1])
        else:
            def partner_fn(parts):
                return self._table_of(self.pool.pairs_for(i, parts, groups)[1])

            plan = self.elastic.plan_round(partner_fn)
            if plan.all_absent:
                return (*self._all_absent_program(i), True, False, None)
            participants = plan.participants
            partner_table = np.asarray(plan.partner, dtype=np.int64)

        consume = bool(
            overlap and "phi_pre" in state
            and self._pre_epoch[k] == epoch
            and np.array_equal(self._pre_partner[k], partner_table)
        )
        presend_index = i + self._schedule.stream_count if overlap else None
        presend_membership = None if self.elastic is None else self.elastic.membership
        next_table = None
        if overlap:
            next_table = self._table_of(self.pool.pairs_for(
                presend_index, presend_membership, groups
            )[1])
        fn, info = self.pool.program(
            i, participants, groups, stream=k, consume=consume,
            presend_index=presend_index, presend_membership=presend_membership,
        )
        return fn, info, False, consume, next_table

    def _record_stream_event(self, k: int, i: int, *, consume: bool,
                             had_prefetch: bool) -> None:
        if self._stream_cost is None and self.outer_cfg.method == "noloco":
            one = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype),
                self._theta_struct,
            )
            self._stream_cost = bytes_model.outer_step_cost(
                one, self.comm_cfg, method="noloco", world=self.plan.replicas
            )
        cost = self._stream_cost
        sc = cost.per_stream[k] if cost and cost.per_stream else None
        payload = sc.payload_bytes if sc else 0
        blocking = sc.blocking_bytes if (sc and consume) else payload
        self.stream_events.append({
            "stream": k,
            "offset": self._schedule.offsets[k],
            "sync_index": i,
            "payload_bytes": payload,
            "blocking_bytes": blocking,
            "overlapped_bytes": payload - blocking,
            "blocked": not consume,
            "epoch_fallback": bool(
                self.comm_cfg.overlap and not consume and had_prefetch
            ),
        })

    def _all_absent_program(self, outer_index: int):
        """Every live replica timed out: identity pairing + all-frozen mask,
        cached in the pool (one extra entry total) and telemetered like any
        other program."""
        world = self.plan.replicas
        key = "all-absent"  # identity pairing — the slot is irrelevant
        if key not in self.pool._programs:
            self.pool.misses += 1
            t0 = time.perf_counter()
            with jax.set_mesh(self.mesh):
                self.pool._programs[key] = steps_lib.build_outer_step(
                    self.plan, self.mesh, self.bundle.pspecs, self.outer_cfg,
                    [(i, i) for i in range(world)],
                    comm_cfg=self.comm_cfg, kernel_cfg=self.kernel_cfg,
                    active=np.zeros((world,), dtype=bool),
                )
            self.pool.events.append({
                "slot": key, "view": "all-absent", "epoch": None,
                "build_s": round(time.perf_counter() - t0, 4),
                "pool_size": len(self.pool._programs),
            })
            return self.pool._programs[key], {
                "key": key, "slot": key, "view": "all-absent",
                "compiled": True, "pool_size": len(self.pool._programs),
            }
        self.pool.hits += 1
        return self.pool._programs[key], {
            "key": key, "slot": key, "view": "all-absent",
            "compiled": False, "pool_size": len(self.pool._programs),
        }

    def eval_loss(self, state, batch):
        """Grad-free per-replica losses (R,) via the bundle's eval program,
        on a batch placed by :meth:`stage_batch`."""
        with jax.set_mesh(self.mesh):
            return self.bundle.eval_fn(state["theta"], batch)

    def theta_struct(self):
        """Stacked-theta ShapeDtypeStructs (for static comm costing)."""
        if not hasattr(self, "_theta_struct"):
            raise RuntimeError("init_state must run before theta_struct")
        return self._theta_struct


def main() -> None:
    from repro.launch.train import add_engine_flags, kernel_config_from_args

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-small-125m")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (smoke) variant of the arch")
    ap.add_argument("--data", type=int, default=4)
    ap.add_argument("--model", type=int, default=2)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--inner-steps", type=int, default=10)
    ap.add_argument("--batch-per-replica", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--eval-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--schedule", default="random", choices=["random", "hypercube"])
    ap.add_argument("--pairing-pool", type=int, default=16,
                    help="random-schedule matchings per membership view")
    ap.add_argument("--codec", default="none",
                    choices=["none", "fp16", "bf16", "int8"],
                    help="gossip wire codec (repro.comm)")
    ap.add_argument("--no-fuse", action="store_true",
                    help="one ppermute per leaf instead of one fused buffer per dtype")
    ap.add_argument("--overlap", action="store_true",
                    help="§3.2 φ-prefetch: pre-send φ′ along the next pairing "
                         "(auto-enabled by --stream-count > 1)")
    ap.add_argument("--stream-count", type=int, default=1,
                    help="partition the outer payload into N streams synced "
                         "on staggered round offsets (streaming outer steps)")
    ap.add_argument("--fault-plan", default=None,
                    help="JSON FaultPlan (repro.sim.faults): run the shard_map "
                         "runtime elastically under churn")
    ap.add_argument("--reassign-data", action="store_true",
                    help="redistribute dropped replicas' loader streams over "
                         "survivors (repro.core.elastic.stream_assignment)")
    ap.add_argument("--stale", default="naive", choices=["naive", "momentum"],
                    help="async stale-Δ rule for rate-heterogeneous fault "
                         "plans: naive applies a delayed Δ as-is, momentum "
                         "discounts it by 1/(1+τ)")
    add_engine_flags(ap)
    args = ap.parse_args()
    enable_compile_cache()

    if jax.device_count() < args.data * args.model:
        raise SystemExit(
            f"need {args.data * args.model} devices; set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N"
        )
    # --fault-plan + --overlap now compose: a stream whose pre-send pairing
    # went stale (membership epoch advanced) falls back to blocking for that
    # stream only — no hard error anymore
    overlap = args.overlap or args.stream_count > 1
    mesh = make_mesh((args.data, args.model), ("data", "model"))
    kcfg = kernel_config_from_args(args)
    cfg = dataclasses.replace(registry.get_config(args.arch), kernels=kcfg)
    if args.reduced:
        cfg = cfg.reduced(vocab_size=min(cfg.vocab_size, 512), remat=False, dtype="float32")
    plan = plans_lib.make_plan("gossip_dp", mesh, shape_kind="train")

    elastic = None
    fault_plan = None
    if args.fault_plan:
        from repro.sim import FaultPlan

        fault_plan = FaultPlan.load(args.fault_plan)
        elastic = ElasticContext(world=plan.replicas)
        anchor = fault_plan.max_anchor_step(args.inner_steps)
        if anchor >= args.steps:
            print(f"WARNING: fault plan extends to step {anchor} but the run "
                  f"stops at {args.steps}; later events never fire", flush=True)
        else:
            horizon = fault_plan.max_effect_step(args.inner_steps)
            if horizon > args.steps:
                print(f"warning: fault-plan effects (straggle debts) extend "
                      f"to step {horizon}, beyond --steps {args.steps}; "
                      f"in-flight debts ride the checkpoint and resume "
                      f"exactly", flush=True)

    trainer = DistributedTrainer(
        cfg=cfg, mesh=mesh, plan=plan,
        outer_cfg=OuterConfig(method="noloco", inner_steps=args.inner_steps,
                              stale=args.stale),
        inner_cfg=AdamWConfig(lr=args.lr, weight_decay=0.0),
        comm_cfg=CommConfig(codec=args.codec, fuse=not args.no_fuse,
                            overlap=overlap, streams=args.stream_count),
        kernel_cfg=kcfg,
        schedule=args.schedule, pairing_pool=args.pairing_pool, seed=args.seed,
        elastic=elastic,
    )

    from repro.train import DistributedProgram, LoopConfig, make_loop

    program: Any = DistributedProgram(trainer)
    if fault_plan is not None:
        from repro.sim import SimCluster

        program = SimCluster(program, fault_plan,
                             reassign_data=args.reassign_data)

    loop = make_loop(
        program,
        LoaderConfig(
            vocab_size=cfg.vocab_size, seq_len=args.seq,
            per_replica_batch=args.batch_per_replica, replicas=plan.replicas,
            seed=args.seed,
        ),
        LoopConfig(
            steps=args.steps, eval_every=args.eval_every, seed=args.seed,
            ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
            resume=args.resume, log_jsonl=args.log_jsonl, log=True,
            run_name=f"{cfg.name}-dist",
        ),
    )
    res = loop.run()
    pool_stats = trainer.pool.stats()
    out = {
        "arch": cfg.name, "replicas": plan.replicas, "tp": plan.tp,
        "codec": args.codec, "fuse": not args.no_fuse, "overlap": overlap,
        "stream_count": args.stream_count,
        "blocking_fraction": round(res["blocking_fraction"], 4),
        "final_loss": res["losses"][-1] if res["losses"] else None,
        "final_eval": res["evals"][-1][1] if res["evals"] else None,
        "tokens_per_s": round(res["tokens_per_s"], 1),
        "comm_bytes": res["comm_bytes"],
        "wall_s": round(res["wall_s"], 1),
        "pool": pool_stats,
        "recompiles": pool_stats["misses"],
    }
    if fault_plan is not None:
        out["fault_events"] = len(fault_plan.events)
        out["membership"] = {
            "epoch": elastic.epoch, "active": list(elastic.active_ids()),
        }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
