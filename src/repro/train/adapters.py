"""Adapters wrapping the three runtimes as :class:`TrainProgram`\\ s.

  * :class:`GossipProgram`      — stacked simulation (:class:`repro.core.
    GossipTrainer`): replicas on a leading vmap axis, CPU-friendly.
  * :class:`DistributedProgram` — shard_map runtime (:class:`repro.launch.
    train_distributed.DistributedTrainer`): per-replica shards on a device
    mesh, ppermute gossip from the per-membership-view
    :class:`~repro.parallel.steps.OuterProgramPool`.
  * :class:`PipelineProgram`    — routed pipeline (:class:`repro.pipeline.
    PipelineTrainer`): §3.1 random routing + per-stage §3.2 gossip.

Each adapter owns exactly three concerns: batch-layout conversion, the
checkpoint pytree round trip (``state_pytree`` / ``load_state_pytree``), and
the static :class:`~repro.comm.bytes_model.CommCost` of one outer step.  All
training math stays in the wrapped runtime.

Elasticity is owned by ONE object across all three runtimes: a
:class:`~repro.core.elastic.ElasticContext` (membership epoch + active mask +
partner source, DESIGN.md §7).  The shared :class:`_ElasticSurface` mixin
exposes the context uniformly (``membership`` / ``membership_epoch`` /
``set_membership`` / ``set_partition`` / ``round_absent`` / ``last_partner``)
so :class:`~repro.sim.SimCluster` and the loop's membership telemetry drive
any adapter without knowing which runtime is underneath; membership rides in
every adapter's checkpoint pytree via the context's ``state_dict``.
"""

from __future__ import annotations

from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro import obs
from repro.comm import CommConfig, bytes_model
from repro.comm import payload as payload_lib
from repro.core import metrics as metrics_lib
from repro.core import pairing as pairing_lib
from repro.core.elastic import ElasticContext
from repro.core.noloco import GossipTrainer, TrainState, TrainerConfig
from repro.core.outer import OuterState, StreamSchedule
from repro.core.pairing import Membership
from repro.models import model as model_api
from repro.models.common import values_of
from repro.models.config import ModelConfig
from repro.optim import AdamWState
from repro.parallel.sharding import ShardCtx
from repro.pipeline import PipelineTrainer
from repro.pipeline.runner import init_stage_params

PyTree = Any

__all__ = ["GossipProgram", "DistributedProgram", "PipelineProgram"]


def _one_replica(tree: PyTree) -> PyTree:
    """abstract single-replica view of a stacked tree (for byte costing)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), tree
    )


def _cost(tree_one: PyTree, comm: CommConfig, method: str, world: int):
    if method in ("none", "fsdp"):
        return None
    return bytes_model.outer_step_cost(
        tree_one, comm, method=method, world=world
    )


class _ElasticSurface:
    """The uniform elastic surface over ``self.elastic`` (an
    :class:`~repro.core.elastic.ElasticContext` or None for a fixed world).

    ``membership_epoch`` is None for a fixed-world program — the loop's
    telemetry duck-types on that and stays silent."""

    elastic: ElasticContext | None

    @property
    def membership(self) -> Membership | None:
        return None if self.elastic is None else self.elastic.membership

    @property
    def membership_epoch(self) -> int | None:
        return None if self.elastic is None else self.elastic.epoch

    @property
    def partition(self):
        return None if self.elastic is None else self.elastic.partition

    @property
    def round_absent(self) -> frozenset[int]:
        return frozenset() if self.elastic is None else self.elastic.round_absent

    @round_absent.setter
    def round_absent(self, value) -> None:
        self._require_elastic().round_absent = frozenset(value)

    @property
    def last_partner(self) -> np.ndarray | None:
        return None if self.elastic is None else self.elastic.last_partner

    def set_membership(self, membership: Membership) -> None:
        self._require_elastic().set_membership(membership)

    def set_partition(self, groups) -> None:
        """Restrict pairings to partition components (None heals)."""
        self._require_elastic().set_partition(groups)

    def _require_elastic(self) -> ElasticContext:
        if self.elastic is None:
            raise ValueError(
                f"{type(self).__name__} has no ElasticContext attached; "
                "construct it with one to drive membership changes"
            )
        return self.elastic


# ---------------------------------------------------------------------------
# Stacked simulation
# ---------------------------------------------------------------------------


class GossipProgram(_ElasticSurface):
    """Stacked-simulation runtime: :class:`GossipTrainer` under one jit.

    Elastic membership (DESIGN.md §7): the program's
    :class:`~repro.core.elastic.ElasticContext` carries the epoch-stamped
    :class:`~repro.core.pairing.Membership` over its replica slots plus the
    partition view and per-round straggler set; every round's pairing comes
    from :func:`~repro.core.pairing.elastic_partner_table` via
    ``ElasticContext.plan_round`` — inactive replicas are frozen in both
    inner and outer steps, a replica whose partner misses the round
    self-pairs (pure self-momentum, the odd-world sit-out path), and
    eval/weight-std aggregate over ACTIVE replicas only.  Membership and
    partition ride in the checkpoint pytree, so a resumed run reproduces the
    elastic trajectory.
    """

    def __init__(
        self,
        cfg: ModelConfig,
        tcfg: TrainerConfig,
        *,
        replicas: int,
        seed: int = 0,
        membership: Membership | None = None,
        elastic: ElasticContext | None = None,
    ):
        self.cfg = cfg
        self.tcfg = tcfg
        self.replicas = replicas
        self.seed = seed
        if elastic is None:
            elastic = ElasticContext(membership or Membership.full(replicas))
        elif membership is not None:
            raise ValueError("pass membership OR elastic, not both")
        if elastic.world != replicas:
            raise ValueError(
                f"elastic world {elastic.world} != replicas {replicas}"
            )
        self.elastic = elastic
        ctx = ShardCtx.local()

        def loss_fn(params, batch, rng):
            return model_api.loss_fn(params, cfg, batch, ctx)[0]

        self.trainer = GossipTrainer(tcfg, loss_fn)
        self._inner_jit = jax.jit(self.trainer.inner_step)
        self._eval_jit = jax.jit(self.trainer.eval_loss)

        # streaming outer steps (DESIGN.md §2): staggered per-stream syncs,
        # engaged for streams > 1 OR the φ-prefetch overlap (streams=1 +
        # overlap is the legacy §3.2 pre-send expressed as one stream)
        tcfg.comm.validate()
        self._streaming = tcfg.outer.method == "noloco" and (
            tcfg.comm.streams > 1 or tcfg.comm.overlap
        )
        if tcfg.comm.streams > 1 and tcfg.outer.method != "noloco":
            raise ValueError("streams > 1 is a noloco-only feature (gossip pairing)")
        self._schedule = None
        self._partition = None
        self._stream_events: list[dict] = []
        self._phi_pre = None
        self._pre_partner = None
        self._pre_epoch = None
        self._stream_cost = None
        if self._streaming:
            s = tcfg.comm.streams
            self._schedule = StreamSchedule(tcfg.outer.inner_steps, s)
            one = jax.eval_shape(
                lambda: values_of(
                    model_api.init_params(jax.random.PRNGKey(seed), cfg)
                )
            )
            stacked = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct((replicas,) + x.shape, x.dtype),
                one,
            )
            self._partition = payload_lib.stream_partition(
                stacked, s, fuse=tcfg.comm.fuse
            )
            self._pre_partner = np.full((s, replicas), -1, dtype=np.int64)
            self._pre_epoch = np.full((s,), -1, dtype=np.int64)

    # -- elastic runtime hooks (SimCluster drives these) ---------------------

    def inner_step_index(self, state: TrainState) -> int:
        return int(state.inner_step)

    def outer_round_index(self, state: TrainState) -> int:
        return int(state.outer.step)

    def sync_due(self, state: TrainState) -> bool:
        if self._streaming:
            return self._schedule.due(int(state.inner_step)) is not None
        return self.trainer.should_sync(state)

    def warm_start(self, state: TrainState, replica: int, source: int) -> TrainState:
        """Rejoin surgery: the comeback replica adopts a live peer's slow
        weights as BOTH its φ and θ (fresh look-ahead), zero outer momentum,
        zero inner-optimizer moments — exactly what a node that fetched φ
        from one peer and restarted would hold."""
        import dataclasses

        def adopt(x):
            return x.at[replica].set(x[source])

        def zero_row(x):
            return x.at[replica].set(jnp.zeros_like(x[replica]))

        return TrainState(
            theta=jax.tree.map(
                lambda th, p: th.at[replica].set(p[source]),
                state.theta, state.outer.phi,
            ),
            opt=AdamWState(
                mu=jax.tree.map(zero_row, state.opt.mu),
                nu=jax.tree.map(zero_row, state.opt.nu),
                count=state.opt.count.at[replica].set(0),
            ),
            outer=dataclasses.replace(
                state.outer,
                phi=jax.tree.map(adopt, state.outer.phi),
                delta=jax.tree.map(zero_row, state.outer.delta),
            ),
            inner_step=state.inner_step,
        )

    def _active_arr(self) -> jnp.ndarray | None:
        """(world,) bool mask for the inner step, or None when everyone is in
        (keeps the healthy path's compiled signature untouched)."""
        arr = self.elastic.active_array()
        return None if arr is None else jnp.asarray(arr)

    # -- TrainProgram -------------------------------------------------------

    def init_state(self, example_batch: dict) -> TrainState:
        one = values_of(model_api.init_params(jax.random.PRNGKey(self.seed), self.cfg))
        stacked = jax.tree.map(
            lambda v: jnp.broadcast_to(v[None], (self.replicas,) + v.shape), one
        )
        return self.trainer.init(stacked)

    def inner_step(self, state, batch, rng):
        # the step counter lives on the device here: the span carries none
        with obs.span("train.inner_step"):
            active = self._active_arr()
            args = (state, batch, rng) + (() if active is None else (active,))
            with obs.span("train.dispatch"):
                state, metrics = self._inner_jit(*args)
            if active is None:
                return state, metrics
            # frozen replicas' stale-weight losses are not training signal:
            # the loop's mean (and telemetry) sees active replicas only,
            # consistent with eval_step/weight_std
            ids = jnp.asarray(self.elastic.active_ids())
            metrics = dict(metrics, loss=jnp.take(metrics["loss"], ids))
        return state, metrics

    def maybe_outer_step(self, state):
        if self._streaming:
            return self._maybe_stream_sync(state)
        if not self.trainer.should_sync(state):
            return state, False
        with obs.span("train.outer_step", stream=0):
            with obs.span("outer.plan"):
                partner_fn = None
                if self.tcfg.outer.method == "noloco":
                    step = int(state.outer.step)

                    def partner_fn(parts):
                        return pairing_lib.elastic_partner_table(
                            step, parts, seed=self.tcfg.outer.seed,
                            groups=self.elastic.partition,
                        )

                plan = self.elastic.plan_round(partner_fn)
                partner = None if plan.partner is None else jnp.asarray(plan.partner)
                active = None if plan.active is None else jnp.asarray(plan.active)
            with obs.span("outer.dispatch"):
                return self.trainer.outer_step(state, partner=partner, active=active), True

    def outer_step_async(self, state, *, sync_index: int, due, staleness):
        """One merged sync tick of the asynchronous clock (DESIGN.md §7).

        The pairing is drawn over ALL round participants at key
        ``sync_index`` (the merged-tick counter) — an involution, so non-due
        participants serve as passive sources whose in-progress (Δ, φ) the
        gather reads — but only ``due`` replicas apply the update (the
        active mask freezes everyone else).  Under ``stale="momentum"`` each
        contribution is discounted by its staleness τ before the exchange.
        A rate-1 world takes the full-participation/τ=0 fast path: the exact
        legacy synchronous call, bit for bit."""
        if self.tcfg.outer.method != "noloco":
            raise ValueError("asynchronous merged-tick sync is NoLoCo-only")
        with obs.span("train.outer_step", outer_index=sync_index, stream=0):
            return self._async_sync(state, sync_index, due, staleness)

    def _async_sync(self, state, sync_index: int, due, staleness):
        seed = self.tcfg.outer.seed

        def partner_fn(parts):
            return pairing_lib.elastic_partner_table(
                sync_index, parts, seed=seed, groups=self.elastic.partition,
            )

        plan = self.elastic.plan_round(partner_fn)
        if plan.all_absent:
            # every member is in straggle debt: frozen no-exchange round
            return self.trainer.outer_step(
                state, partner=jnp.asarray(plan.partner),
                active=jnp.asarray(plan.active),
            ), True
        due = np.asarray(due, dtype=bool)
        tau = np.asarray(staleness)
        update = due.copy()
        if plan.active is not None:
            update &= np.asarray(plan.active, dtype=bool)
        partner = jnp.asarray(plan.partner)
        if update.all() and not tau.any():
            # everyone due, nobody late: the legacy synchronous exchange
            return self.trainer.outer_step(state, partner=partner, active=None), True
        stale_arr = None
        if self.tcfg.outer.stale == "momentum" and tau.any():
            stale_arr = jnp.asarray(tau, jnp.float32)
        return self.trainer.outer_step(
            state, partner=partner, active=jnp.asarray(update),
            staleness=stale_arr,
        ), True

    def _maybe_stream_sync(self, state):
        """One stream's staggered sync (DESIGN.md §2, streaming outer steps).

        The global sync index ``i`` — the count of stream syncs so far, which
        ``OuterState.step`` tracks — is the gossip pairing key; stream ``k``'s
        next sync is ``i + streams``, the key its φ′ pre-send travels on.  A
        prefetched φ is consumed only when the pairing it was sent along still
        holds (same membership epoch AND the recorded partner table equals
        this round's actual table); otherwise that stream alone falls back to
        the blocking (Δ, φ) exchange — churn never blocks the other streams.
        """
        t = int(state.inner_step)
        k = self._schedule.due(t)
        if k is None:
            return state, False
        i = self._schedule.sync_index(k, t)
        with obs.span("train.outer_step", outer_index=i, stream=k):
            return self._stream_sync(state, k, i)

    def _stream_sync(self, state, k: int, i: int):
        streams = self._schedule.stream_count
        seed = self.tcfg.outer.seed
        overlap = self.tcfg.comm.overlap

        def partner_fn(parts):
            return pairing_lib.elastic_partner_table(
                i, parts, seed=seed, groups=self.elastic.partition
            )

        with obs.span("outer.plan"):
            plan = self.elastic.plan_round(partner_fn)
        partner = jnp.asarray(plan.partner)
        active = None if plan.active is None else jnp.asarray(plan.active)

        had_prefetch = self._pre_epoch[k] >= 0
        consume = bool(
            overlap
            and self._phi_pre is not None
            and self._pre_epoch[k] == self.elastic.epoch
            and np.array_equal(self._pre_partner[k], np.asarray(plan.partner))
        )
        partner_next = None
        next_table = None
        if overlap:
            next_table = pairing_lib.elastic_partner_table(
                i + streams, self.elastic.membership, seed=seed,
                groups=self.elastic.partition,
            )
            partner_next = jnp.asarray(next_table)

        with obs.span("outer.dispatch"):
            state, phi_pre_out = self.trainer.outer_step_stream(
                state, stream=k, partition=self._partition, partner=partner,
                active=active, phi_pre=self._phi_pre, consume_prefetch=consume,
                partner_next=partner_next,
            )
        if phi_pre_out is not None:
            self._phi_pre = phi_pre_out
            self._pre_partner[k] = np.asarray(next_table)
            self._pre_epoch[k] = self.elastic.epoch

        cost = self._cost_for_streams()
        sc = cost.per_stream[k] if cost else None
        payload = sc.payload_bytes if sc else 0
        blocking = sc.blocking_bytes if (sc and consume) else payload
        self._stream_events.append({
            "stream": k,
            "offset": self._schedule.offsets[k],
            "sync_index": i,
            "payload_bytes": payload,
            "blocking_bytes": blocking,
            "overlapped_bytes": payload - blocking,
            "blocked": not consume,
            "epoch_fallback": bool(overlap and not consume and had_prefetch),
        })
        return state, True

    def _cost_for_streams(self):
        if self._stream_cost is None:
            self._stream_cost = self.comm_cost()
        return self._stream_cost

    def drain_stream_events(self) -> list[dict]:
        events, self._stream_events = self._stream_events, []
        return events

    def eval_step(self, state, batch, rng) -> float:
        losses = self._eval_jit(state.theta, batch, rng)
        return float(jnp.mean(losses[jnp.asarray(self.elastic.active_ids())]))

    def weight_std(self, state) -> float:
        """Cross-replica weight std over ACTIVE replicas (a dropped replica's
        stale weights are not part of the ensemble)."""
        if self.elastic.membership.num_active < 2:
            return 0.0
        ids = jnp.asarray(self.elastic.active_ids())
        theta = jax.tree.map(lambda x: jnp.take(x, ids, axis=0), state.theta)
        return float(metrics_lib.replica_weight_std(theta))

    def state_pytree(self, state: TrainState) -> dict:
        tree = {
            "theta": state.theta,
            "opt": {"mu": state.opt.mu, "nu": state.opt.nu, "count": state.opt.count},
            "outer": {
                "phi": state.outer.phi,
                "delta": state.outer.delta,
                "step": state.outer.step,
            },
            "inner_step": state.inner_step,
            "membership": self.elastic.state_dict(),
        }
        if self._streaming:
            # in-flight stream state: the prefetched φ buffer plus the
            # (pairing, epoch) it was pre-sent along, so a resumed run makes
            # the same consume-vs-fallback decision at every stream sync
            stream = {
                "pre_partner": np.asarray(self._pre_partner),
                "pre_epoch": np.asarray(self._pre_epoch),
            }
            if self._phi_pre is not None:
                stream["phi_pre"] = self._phi_pre
            tree["stream"] = stream
        return tree

    def load_state_pytree(self, state: TrainState, tree: dict) -> TrainState:
        if "membership" in tree:
            self.elastic.load_state_dict(tree["membership"])
        if self._streaming:
            if "stream" in tree:
                st = tree["stream"]
                self._pre_partner = np.asarray(st["pre_partner"]).astype(np.int64)
                self._pre_epoch = np.asarray(st["pre_epoch"]).astype(np.int64)
                self._phi_pre = st.get("phi_pre")
            else:
                # checkpoint written without streaming: nothing was pre-sent,
                # so every stream's first sync after resume is a blocking one
                self._pre_partner = np.full_like(self._pre_partner, -1)
                self._pre_epoch = np.full_like(self._pre_epoch, -1)
                self._phi_pre = None
        return TrainState(
            theta=tree["theta"],
            opt=AdamWState(
                mu=tree["opt"]["mu"], nu=tree["opt"]["nu"],
                count=jnp.asarray(tree["opt"]["count"]),
            ),
            outer=OuterState(
                phi=tree["outer"]["phi"], delta=tree["outer"]["delta"],
                step=jnp.asarray(tree["outer"]["step"]),
            ),
            inner_step=jnp.asarray(tree["inner_step"]),
        )

    def comm_cost(self):
        one = jax.eval_shape(
            lambda: values_of(
                model_api.init_params(jax.random.PRNGKey(0), self.cfg)
            )
        )
        return _cost(one, self.tcfg.comm, self.tcfg.outer.method, self.replicas)


# ---------------------------------------------------------------------------
# shard_map runtime
# ---------------------------------------------------------------------------


class DistributedProgram(_ElasticSurface):
    """Mesh runtime: wraps a configured ``DistributedTrainer``.

    Stacked ``(R, B, S)`` loader batches are flattened to the global
    replica-major ``(R*B, S)`` rows the shard_map step consumes.

    Elasticity: the trainer's :class:`~repro.core.elastic.ElasticContext`
    (when attached) is surfaced here exactly like the stacked program's —
    SimCluster replays fault plans against the REAL compiled path, the outer
    step comes from the per-membership-view program pool, eval/weight-std
    aggregate over active replicas, and the membership epoch rides in the
    checkpoint so resume-after-churn reproduces the trajectory exactly."""

    def __init__(self, trainer):
        self.trainer = trainer
        self.replicas = trainer.plan.replicas
        self.elastic = trainer.elastic

    @staticmethod
    def _to_global(batch: dict) -> dict:
        return {
            k: jnp.asarray(np.asarray(v).reshape(-1, np.asarray(v).shape[-1]))
            for k, v in batch.items()
        }

    # -- elastic runtime hooks ----------------------------------------------

    def inner_step_index(self, state) -> int:
        return int(state["inner_step"])

    def outer_round_index(self, state) -> int:
        if self.trainer._streaming:
            # streaming: the global sync index of the stream due at this
            # step (the pairing key the round will use)
            t = int(state["inner_step"])
            k = self.trainer._schedule.due(t)
            if k is not None:
                return self.trainer._schedule.sync_index(k, t)
        # the stacked runtime reads the outer counter BEFORE the exchange
        # (round labels are 0-indexed); mirror that from the inner counter
        return int(state["inner_step"]) // self.trainer.outer_cfg.inner_steps - 1

    def sync_due(self, state) -> bool:
        if self.trainer._streaming:
            return self.trainer._schedule.due(int(state["inner_step"])) is not None
        m = self.trainer.outer_cfg.inner_steps
        return state["inner_step"] > 0 and state["inner_step"] % m == 0

    def warm_start(self, state, replica: int, source: int):
        """Rejoin over the mesh: the peer's φ row moves across replica shards
        (a gather+scatter on the replica axis — the only cross-replica traffic
        a rejoin costs)."""
        return self.trainer.warm_start(state, replica, source)

    def drain_recompile_events(self) -> list[dict]:
        events, self.trainer.recompile_events = self.trainer.recompile_events, []
        return events

    def drain_stream_events(self) -> list[dict]:
        events, self.trainer.stream_events = self.trainer.stream_events, []
        return events

    def pool_stats(self) -> dict:
        return self.trainer.pool.stats()

    def _active_ids(self) -> jnp.ndarray | None:
        if self.elastic is None or self.elastic.is_full:
            return None
        return jnp.asarray(self.elastic.active_ids())

    # -- TrainProgram -------------------------------------------------------

    def init_state(self, example_batch: dict):
        return self.trainer.init_state(self._to_global(example_batch))

    def _stage(self, batch: dict) -> dict:
        return self.trainer.stage_batch(self._to_global(batch))

    def inner_step(self, state, batch, rng):
        with obs.span("train.inner_step", step=state["inner_step"]):
            with obs.span("train.stage_batch"):
                batch = self._stage(batch)
            state, metrics = self.trainer.inner_step(state, batch)
            ids = self._active_ids()
            if ids is not None:
                metrics = dict(metrics, loss=jnp.take(metrics["loss"], ids))
        return state, metrics

    def maybe_outer_step(self, state):
        return self.trainer.maybe_outer_step(state)

    def outer_step_async(self, state, *, sync_index: int, due, staleness):
        return self.trainer.outer_step_async(
            state, sync_index=sync_index, due=due, staleness=staleness
        )

    def eval_step(self, state, batch, rng) -> float:
        losses = self.trainer.eval_loss(state, self._stage(batch))
        ids = self._active_ids()
        if ids is not None:
            losses = jnp.take(losses, ids)
        return float(jnp.mean(losses))

    def weight_std(self, state) -> float:
        ids = self._active_ids()
        theta = state["theta"]
        if ids is not None:
            if len(ids) < 2:
                return 0.0
            theta = jax.tree.map(lambda x: jnp.take(x, ids, axis=0), theta)
        return float(metrics_lib.replica_weight_std(theta))

    def state_pytree(self, state) -> dict:
        tree = {
            "theta": state["theta"],
            "opt": {
                "mu": state["opt"].mu, "nu": state["opt"].nu,
                "count": state["opt"].count,
            },
            "phi": state["phi"],
            "delta": state["delta"],
            "outer_step": state["outer_step"],
            "inner_step": np.int64(state["inner_step"]),
        }
        if "phi_pre" in state:
            tree["phi_pre"] = state["phi_pre"]
        if self.trainer._streaming:
            # in-flight stream state: the (pairing, epoch) each stream's φ′
            # was pre-sent along, so a resumed run makes the same
            # consume-vs-fallback decision at every stream sync (phi_pre
            # itself rides above as device state)
            tree["stream"] = {
                "pre_partner": np.asarray(self.trainer._pre_partner),
                "pre_epoch": np.asarray(self.trainer._pre_epoch),
            }
        if self.elastic is not None:
            tree["membership"] = self.elastic.state_dict()
        return tree

    def load_state_pytree(self, state, tree) -> dict:
        if "membership" in tree and self.elastic is not None:
            self.elastic.load_state_dict(tree["membership"])
        b = self.trainer.bundle
        put = jax.device_put
        new = dict(
            state,
            theta=put(tree["theta"], b.theta_shardings),
            opt=AdamWState(
                mu=put(tree["opt"]["mu"], b.opt_shardings.mu),
                nu=put(tree["opt"]["nu"], b.opt_shardings.nu),
                count=put(jnp.asarray(tree["opt"]["count"]), b.opt_shardings.count),
            ),
            phi=put(tree["phi"], b.theta_shardings),
            delta=put(tree["delta"], b.theta_shardings),
            outer_step=put(
                jnp.asarray(tree["outer_step"]), state["outer_step"].sharding
            ),
            inner_step=int(tree["inner_step"]),
        )
        if self.trainer._streaming:
            if "stream" in tree:
                st = tree["stream"]
                self.trainer._pre_partner = np.asarray(
                    st["pre_partner"]).astype(np.int64)
                self.trainer._pre_epoch = np.asarray(
                    st["pre_epoch"]).astype(np.int64)
            else:
                # checkpoint written without streaming: nothing was pre-sent,
                # so every stream's first sync after resume blocks once
                self.trainer._pre_partner = np.full_like(
                    self.trainer._pre_partner, -1)
                self.trainer._pre_epoch = np.full_like(
                    self.trainer._pre_epoch, -1)
        if "phi_pre" in tree:
            new["phi_pre"] = put(tree["phi_pre"], b.theta_shardings)
        elif "phi_pre" in state:
            # resuming WITH --overlap from a checkpoint written without it:
            # the partner's φ was never pre-sent, so bootstrap from our own
            # restored φ (self-copy), NOT the random-init φ_0 sitting in the
            # freshly-initialized state — that would drag mean_phi halfway
            # back to init on the first outer step.
            new["phi_pre"] = jax.tree.map(jnp.copy, new["phi"])
        return new

    def comm_cost(self):
        one = _one_replica(self.trainer.theta_struct())
        return _cost(
            one, self.trainer.comm_cfg, self.trainer.outer_cfg.method, self.replicas
        )


# ---------------------------------------------------------------------------
# Routed pipeline
# ---------------------------------------------------------------------------


class PipelineProgram(_ElasticSurface):
    """Routed-pipeline runtime: §3.1 routing + per-stage §3.2 gossip.

    Elasticity: the trainer's :class:`~repro.core.elastic.ElasticContext`
    restricts routing permutations to the active set and draws every stage's
    gossip pairing over the active members only (inactive stage-replicas are
    frozen, carry no routed traffic, and never appear in a pairing)."""

    def __init__(self, trainer: PipelineTrainer):
        self.trainer = trainer
        self.replicas = trainer.replicas
        self.elastic = trainer.elastic

    def init_state(self, example_batch: dict) -> dict:
        return self.trainer.init(jax.random.PRNGKey(self.trainer.seed))

    def inner_step(self, state, batch, rng):
        with obs.span("train.inner_step", step=state["step"]):
            with obs.span("train.dispatch"):
                state, loss = self.trainer.train_step(state, batch)
        return state, {"loss": jnp.asarray(loss)}

    def maybe_outer_step(self, state):
        return self.trainer.maybe_outer_step(state)

    def eval_step(self, state, batch, rng) -> float:
        return float(self.trainer.eval_loss(state["params"], batch))

    def weight_std(self, state) -> float:
        return self.trainer.weight_std(state)

    def state_pytree(self, state) -> dict:
        tree = {
            "params": state["params"],
            "opt": [
                {"mu": o.mu, "nu": o.nu, "count": o.count} for o in state["opt"]
            ],
            "step": np.int64(state["step"]),
        }
        if "outer" in state:
            tree["outer"] = {
                "phi": state["outer"]["phi"],
                "delta": state["outer"]["delta"],
                "step": np.int64(state["outer"]["step"]),
            }
        if self.elastic is not None:
            tree["membership"] = self.elastic.state_dict()
        return tree

    def load_state_pytree(self, state, tree) -> dict:
        if "membership" in tree and self.elastic is not None:
            self.elastic.load_state_dict(tree["membership"])
        new = {
            "params": list(tree["params"]),
            "opt": [
                AdamWState(mu=o["mu"], nu=o["nu"], count=jnp.asarray(o["count"]))
                for o in tree["opt"]
            ],
            "step": int(tree["step"]),
        }
        if "outer" in tree:
            new["outer"] = {
                "phi": list(tree["outer"]["phi"]),
                "delta": list(tree["outer"]["delta"]),
                "step": int(tree["outer"]["step"]),
            }
        elif "outer" in state:
            # warm-starting gossip from a method=none checkpoint: slow
            # weights start AT the restored fast weights (fresh look-ahead),
            # zero momentum, outer counter aligned so the next sync fires at
            # the next m-step boundary
            m = self.trainer.outer.inner_steps
            new["outer"] = {
                "phi": [jax.tree.map(jnp.copy, p) for p in new["params"]],
                "delta": [jax.tree.map(jnp.zeros_like, p) for p in new["params"]],
                "step": new["step"] // m,
            }
        return new

    def comm_cost(self):
        tr = self.trainer
        if not tr.outer_enabled:
            return None
        # one replica's payload = all of its per-stage parameters; the stage
        # trees from init_stage_params are already single-replica
        one = {
            f"stage{s}": jax.eval_shape(
                lambda s=s: values_of(init_stage_params(
                    jax.random.PRNGKey(0), tr.cfg, s, tr.num_stages
                ))
            )
            for s in range(tr.num_stages)
        }
        return _cost(one, tr.comm, tr.outer.method, tr.replicas)
