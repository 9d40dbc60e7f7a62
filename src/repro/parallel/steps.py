"""Distributed step builders: train / prefill / decode / outer (gossip &
all-reduce), all built from the same per-replica model code via shard_map.

Pattern (see DESIGN.md): the per-replica LOSS runs inside ``shard_map`` with
manual collectives (ShardCtx); ``jax.value_and_grad`` is taken OUTSIDE the
shard_map, so JAX's shard_map transposition inserts the correct gradient
collectives (replicated-over-model params automatically get their cotangents
psum'd over the model axis — no hand-written f/g operators to get wrong).
The AdamW update is a vmap over the leading replica dim under plain GSPMD
(elementwise, partitions trivially).

The NoLoCo outer step is a shard_map whose ONLY cross-replica communication
is one ``lax.ppermute`` (collective-permute); the DiLoCo baseline outer step
uses ``lax.pmean`` (all-reduce).  Roofline reads these straight from the HLO.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.comm import CommConfig
from repro.core import outer as outer_lib
from repro.core import pairing as pairing_lib
from repro.core.outer import OuterConfig, OuterState
from repro.core.pairing import Membership
from repro.kernels.dispatch import KernelConfig
from repro.models import model as model_api
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, AdamWState, adamw_init, adamw_update
from repro.parallel import plans as plans_lib
from repro.parallel.plans import Plan

PyTree = Any


# ---------------------------------------------------------------------------
# Parameter stacking (leading replica dim)
# ---------------------------------------------------------------------------


def stack_replicas(params: PyTree, replicas: int) -> PyTree:
    """Add the leading replica dim to every Param leaf (logical "replica").

    For simulation each replica starts from the SAME weights (the paper
    initializes all instances identically: φ_{0,i} ≡ φ_0)."""
    from repro.models.common import Param, param as mk

    def stk(p: Param) -> Param:
        v = jnp.broadcast_to(p.value[None], (replicas,) + p.value.shape)
        return mk(v, "replica", *p.logical)

    return jax.tree.map(stk, params, is_leaf=lambda x: isinstance(x, Param))


# ---------------------------------------------------------------------------
# Batch specs
# ---------------------------------------------------------------------------


def batch_pspecs(plan: Plan, batch: dict) -> dict:
    """tokens/labels (B, S): batch dim over all data axes; embeds likewise.

    A batch that does not divide the data axes (e.g. long_500k's batch of 1)
    is REPLICATED — every replica decodes the same stream (ensemble decode,
    noted in DESIGN.md)."""
    dp = plan.data_axes
    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)
    # product of data-axis sizes: replicas × fsdp covers (pod, data)
    dp_total = plan.replicas * plan.fsdp
    out = {}
    for k, v in batch.items():
        nd = v.ndim if hasattr(v, "ndim") else len(v.shape)
        b = v.shape[0]
        entry = dp_entry if (dp and b % max(dp_total, 1) == 0) else None
        out[k] = P(entry, *([None] * (nd - 1)))
    return out


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TrainStepBundle:
    step_fn: Callable              # (theta, opt, batch) -> (theta, opt, metrics)
    theta_shardings: PyTree
    opt_shardings: PyTree
    pspecs: PyTree                 # theta PartitionSpecs (for checkpoint/outer)
    eval_fn: Callable | None = None  # (theta, batch) -> (R,) losses, grad-free


def _squeeze_replica(tree: PyTree) -> PyTree:
    return jax.tree.map(lambda x: x[0], tree)


def _unsqueeze_replica(tree: PyTree) -> PyTree:
    return jax.tree.map(lambda x: x[None], tree)


def build_loss_shard(
    cfg: ModelConfig, plan: Plan, mesh: Mesh, param_specs: PyTree, batch_specs: dict
):
    """shard_map'd per-replica loss: (stacked theta, batch) -> (R,) losses."""
    ctx = plan.ctx()
    rep_entry = plan.replica_entry

    def body(theta_local, batch_local):
        theta = _squeeze_replica(theta_local)  # drop leading local replica dim
        loss, metrics = model_api.loss_fn(theta, cfg, batch_local, ctx)
        # fsdp plan: tokens are sharded over `data` WITHIN the replica — the
        # per-replica loss is the mean over data shards of the local means
        # (equal token counts per shard).
        if plan.fsdp_axis is not None and plan.fsdp > 1:
            loss = jax.lax.pmean(loss, plan.fsdp_axis)
            metrics = jax.tree.map(lambda m: jax.lax.pmean(m, plan.fsdp_axis), metrics)
        out = jnp.reshape(loss, (1,))
        mets = jax.tree.map(lambda m: jnp.reshape(m, (1,)), metrics)
        return out, mets

    in_specs = (param_specs, batch_specs)
    out_specs = (P(rep_entry), {"lm_loss": P(rep_entry), "aux_loss": P(rep_entry)})
    return jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def build_train_step(
    cfg: ModelConfig,
    plan: Plan,
    mesh: Mesh,
    params: PyTree,          # Param tree WITH leading replica dim (stack_replicas)
    batch_example: dict,     # arrays or ShapeDtypeStructs
    inner: AdamWConfig,
    *,
    data_sync: bool = False,  # DDP/FSDP baseline: all-reduce grads over replicas
) -> TrainStepBundle:
    pspecs = plans_lib.param_pspecs(plan, mesh, params)
    bspecs = batch_pspecs(plan, batch_example)
    loss_shard = build_loss_shard(cfg, plan, mesh, pspecs, bspecs)
    replicas = plan.replicas

    def total_loss(theta, batch):
        # replicas share no parameters, so the gradient of the SUM is each
        # replica's own loss gradient — the scale the per-replica AdamW
        # clip (clip_norm) is defined on, as in the stacked runtime
        losses, metrics = loss_shard(theta, batch)
        return jnp.sum(losses), (losses, metrics)

    def step(theta, opt, batch):
        (_, (losses, metrics)), grads = jax.value_and_grad(total_loss, has_aux=True)(
            theta, batch
        )
        if data_sync and replicas > 1:
            # traditional data-parallel baseline: gradient all-reduce across
            # the replica axes EVERY step (what NoLoCo removes entirely)
            grads = jax.tree.map(
                lambda g: jnp.broadcast_to(
                    jnp.mean(g, axis=0, keepdims=True), g.shape
                ),
                grads,
            )
        new_theta, new_opt, gnorm = jax.vmap(
            lambda g, o, p: adamw_update(g, o, p, inner)
        )(grads, opt, theta)
        metrics = dict(metrics)
        metrics["loss"] = losses
        metrics["grad_norm"] = gnorm
        return new_theta, new_opt, metrics

    theta_sh = plans_lib.shardings(mesh, pspecs)
    # AdamW moments mirror param specs (f32); count is per-replica (R,)
    rep_entry = plan.replica_entry
    opt_pspecs = AdamWState(
        mu=pspecs, nu=jax.tree.map(lambda s: s, pspecs), count=P(rep_entry)
    )
    opt_sh = plans_lib.shardings(mesh, opt_pspecs)
    bsh = plans_lib.shardings(mesh, bspecs)

    jitted = jax.jit(
        step,
        in_shardings=(theta_sh, opt_sh, bsh),
        donate_argnums=(0, 1),
    )
    # grad-free eval: the same shard_map'd loss, no value_and_grad, nothing
    # donated (eval must not consume the training state)
    eval_jit = jax.jit(
        lambda theta, batch: loss_shard(theta, batch)[0],
        in_shardings=(theta_sh, bsh),
    )
    return TrainStepBundle(
        step_fn=jitted, theta_shardings=theta_sh, opt_shardings=opt_sh,
        pspecs=pspecs, eval_fn=eval_jit,
    )


def init_opt_state(params_stacked_values: PyTree, replicas: int) -> AdamWState:
    """Per-replica AdamW state over stacked params (vmapped init)."""
    return jax.vmap(adamw_init)(params_stacked_values)


# ---------------------------------------------------------------------------
# Outer step (gossip / all-reduce)
# ---------------------------------------------------------------------------


def _local_replica_index(plan: Plan, mesh: Mesh) -> jax.Array:
    """This shard's LINEARIZED replica id (pod-major), inside shard_map."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    idx = jnp.zeros((), jnp.int32)
    for a in plan.replica_axes:
        idx = idx * sizes[a] + jax.lax.axis_index(a)
    return idx


def build_outer_step(
    plan: Plan,
    mesh: Mesh,
    param_specs: PyTree,     # stacked-theta PartitionSpecs
    outer_cfg: OuterConfig,
    perm: list[tuple[int, int]] | None,
    *,
    fuse_payload: bool = False,
    comm_cfg: CommConfig | None = None,
    kernel_cfg: KernelConfig | None = None,
    active: Any | None = None,
    staleness: Any | None = None,
    stream: int | None = None,
    partition: Any | None = None,
    consume_prefetch: bool = False,
    perm_presend: list[tuple[int, int]] | None = None,
):
    """One outer step over (theta, phi, delta) -> (theta', phi', delta').

    NoLoCo: ``perm`` is the static partner permutation over the LINEARIZED
    replica axes (pod-major), realized as one collective-permute.  The
    launcher precompiles a rotating set of random matchings (pairings are
    data-independent, so a small cycling pool preserves the paper's random-
    matching statistics without per-step recompilation).

    ``comm_cfg`` selects the wire codec / payload fusing (``fuse_payload`` is
    the legacy switch for ``comm_cfg.fuse``).

    STREAMING (DESIGN.md §2, streaming outer steps): with ``stream`` set, the
    program syncs ONE stream of ``partition`` (a
    :class:`~repro.comm.StreamPartition`) via
    :func:`~repro.core.outer.outer_step_sharded_stream` — only that stream's
    leaves are exchanged over ``perm``; everything else passes through
    bit-untouched.  ``consume_prefetch`` compiles the §3.2 φ-prefetch read
    (block on the Δ permute only) and ``perm_presend`` the φ′ pre-send for
    the stream's NEXT sync; either one switches the program to the
    (theta, phi, delta, phi_pre, step)-in-and-out signature, otherwise the
    legacy (theta, phi, delta, step) signature is kept.  The legacy
    whole-payload overlap spelling (``perm_next``) was removed: a single
    stream with ``consume_prefetch`` + ``perm_presend`` is exactly that
    program, and it now composes with elastic membership (the host falls
    back per stream when the pre-send pairing's epoch is stale).

    ``active`` (optional host-side (world,) bool array) bakes this round's
    PARTICIPANT set into the program (elastic runs; the pairing ``perm``
    already self-loops non-participants): a non-participant's (θ, φ, δ) pass
    through untouched — a dropped replica is frozen, a straggler keeps inner-
    training toward a multi-m Δ — and elastic DiLoCo means over participants
    only.  ``active=None`` (the healthy path) compiles the EXACT program it
    always did, so full membership stays bit-identical to the static
    schedule.  Programs are keyed per (membership view, pairing slot, stream
    variant) by :class:`OuterProgramPool`; this builder never decides who
    participates.

    ``staleness`` (optional host-side (world,) τ vector, ASYNC merged-tick
    rounds only) bakes each shard's staleness into the program the same way
    ``active`` is baked: the per-shard τ scalar feeds
    :func:`~repro.core.outer.outer_step_sharded`'s ``staleness`` hook, which
    applies the ``stale="momentum"`` 1/(1+τ) discount to that replica's OWN
    Δ before the ppermute — the partner receives the discounted
    contribution.  Incompatible with streamed programs (async rounds do not
    compose with streaming)."""
    rep = plan.replica_axes
    rep_entry = plan.replica_entry
    if comm_cfg is None:
        comm_cfg = CommConfig(fuse=fuse_payload)
    streamed = stream is not None
    if streamed and outer_cfg.method != "noloco":
        raise ValueError("streamed outer programs are NoLoCo-only")
    if (consume_prefetch or perm_presend is not None) and not streamed:
        raise ValueError(
            "consume_prefetch/perm_presend require a streamed program: the "
            "legacy whole-payload perm_next overlap was removed — build with "
            "stream=0 and a single-stream partition instead"
        )
    prefetching = streamed and (consume_prefetch or perm_presend is not None)
    if streamed and staleness is not None:
        raise ValueError("staleness (async rounds) does not compose with streaming")
    active_host = None if active is None else np.asarray(active, dtype=bool)
    stale_host = None if staleness is None else np.asarray(staleness, dtype=np.float32)

    def body(theta_l, phi_l, delta_l, *rest):
        theta = _squeeze_replica(theta_l)
        phi = _squeeze_replica(phi_l)
        delta = _squeeze_replica(delta_l)
        flag = None
        if active_host is not None:
            flag = jnp.asarray(active_host)[_local_replica_index(plan, mesh)]
        if streamed:
            if prefetching:
                phi_pre_l, step_l = rest
                phi_pre = _squeeze_replica(phi_pre_l)
            else:
                (step_l,) = rest
                phi_pre = None
            state = OuterState(phi=phi, delta=delta, step=step_l.reshape(()))
            new_state, new_theta, phi_pre_out = outer_lib.outer_step_sharded_stream(
                state, theta, outer_cfg, stream=stream, partition=partition,
                axis_names=rep, perm=perm, phi_pre=phi_pre,
                consume_prefetch=consume_prefetch, perm_next=perm_presend,
                comm_cfg=comm_cfg, kernel_cfg=kernel_cfg, active_flag=flag,
            )
            out = (
                _unsqueeze_replica(new_theta),
                _unsqueeze_replica(new_state.phi),
                _unsqueeze_replica(new_state.delta),
            )
            if prefetching:
                # no pre-send requested but prefetch consumed: the buffer
                # passes through so the program signature stays fixed
                pre = phi_pre_out if phi_pre_out is not None else phi_pre
                out = out + (_unsqueeze_replica(pre),)
            return out + (new_state.step.reshape((1,)),)
        (step_l,) = rest
        stale = None
        if stale_host is not None:
            stale = jnp.asarray(stale_host)[_local_replica_index(plan, mesh)]
        state = OuterState(phi=phi, delta=delta, step=step_l.reshape(()))
        new_state, new_theta = outer_lib.outer_step_sharded(
            state, theta, outer_cfg, axis_names=rep, perm=perm, comm_cfg=comm_cfg,
            kernel_cfg=kernel_cfg, active_flag=flag, staleness=stale,
        )
        if flag is not None:
            # freeze non-participants: keep pre-round (θ, φ, δ); the outer
            # counter still advances so the schedule stays aligned
            _sel = lambda new, old: jax.tree.map(
                lambda a, b: jnp.where(flag, a, b), new, old
            )
            new_theta = _sel(new_theta, theta)
            new_state = OuterState(
                phi=_sel(new_state.phi, phi),
                delta=_sel(new_state.delta, delta),
                step=new_state.step,
            )
        return (
            _unsqueeze_replica(new_theta),
            _unsqueeze_replica(new_state.phi),
            _unsqueeze_replica(new_state.delta),
            new_state.step.reshape((1,)),
        )

    n_params = 4 if prefetching else 3
    in_specs = (param_specs,) * n_params + (P(rep_entry),)
    out_specs = (param_specs,) * n_params + (P(rep_entry),)
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    sh = plans_lib.shardings(mesh, param_specs)
    step_sh = NamedSharding(mesh, P(rep_entry))
    return jax.jit(
        fn,
        in_shardings=(sh,) * n_params + (step_sh,),
        donate_argnums=tuple(range(n_params)),
    )


# ---------------------------------------------------------------------------
# Per-membership-view compiled program pool
# ---------------------------------------------------------------------------


class OuterProgramPool:
    """Compiled outer-step programs keyed by (membership view, pairing slot).

    ``lax.ppermute`` needs a STATIC permutation, so the shard_map runtime
    cannot draw a fresh random matching per round without recompiling.  The
    pool bounds compilation two ways (DESIGN.md §3):

      * ``schedule="random"`` — ``pairing_pool`` cycling matchings: round k
        uses the matching of pairing slot ``k % pairing_pool``, preserving
        the paper's random-matching statistics with at most ``pairing_pool``
        programs per membership view.
      * ``schedule="hypercube"`` — partner = id XOR 2^j with j =
        :func:`~repro.core.pairing.hypercube_dim`: at most log2(world)
        programs per membership view and still optimal mixing.

    Programs are keyed by the PARTICIPANT VIEW (mask + partition), not the
    membership epoch: two epochs with identical masks schedule identically
    (a node that left and came right back recompiles nothing), and the
    healthy view compiles the exact static-schedule programs (``active=None``
    path of :func:`build_outer_step`) — full membership stays bit-identical.
    Recompiles therefore happen ONLY at membership-view boundaries, at most
    ``max_programs_per_view`` per view, and each one is recorded for the
    engine's ``recompile`` telemetry (:mod:`repro.train.loop`).

    STREAMED pools (constructed with a ``partition``) additionally key each
    program by (stream, consume-vs-blocking, pre-send pairing): one stream's
    leaves sync per program call on its staggered round offset, and the
    elastic epoch-fallback from a consuming program to the blocking variant
    of the SAME pairing is a pool lookup, not a recompile of an existing
    entry.
    """

    def __init__(
        self,
        plan: Plan,
        mesh: Mesh,
        param_specs: PyTree,
        outer_cfg: OuterConfig,
        *,
        comm_cfg: CommConfig | None = None,
        kernel_cfg: KernelConfig | None = None,
        schedule: str = "random",
        pairing_pool: int = 16,
        seed: int = 0,
        partition: Any | None = None,  # StreamPartition for streamed programs
    ):
        if schedule not in ("random", "hypercube"):
            raise ValueError(f"unknown pairing schedule: {schedule!r}")
        self.plan = plan
        self.mesh = mesh
        self.param_specs = param_specs
        self.outer_cfg = outer_cfg
        self.comm_cfg = comm_cfg or CommConfig()
        self.kernel_cfg = kernel_cfg
        self.schedule = schedule
        self.pairing_pool = pairing_pool
        self.seed = seed
        self.partition = partition
        self._programs: dict[Any, Any] = {}
        self.hits = 0
        self.misses = 0
        self.events: list[dict] = []  # one record per compile (drained by the loop)

    # -- pure key/pairing derivation (no compilation; property-tested) -------

    @property
    def max_programs_per_view(self) -> int:
        """Upper bound on compiled programs per membership view.

        With the §3.2 overlap each program is keyed by the (slot, pre-send
        slot) PAIR: the random schedule's cycling slots still yield
        ``pairing_pool`` distinct pairs, but the hypercube schedule redraws
        its dimension order every log2(world) rounds, so pairs range over
        dims².  Streamed pools additionally key per stream and per
        consume-vs-blocking variant (a stream's first sync has no prefetch
        to consume), scaling the bound by ``streams`` and — under overlap —
        by 2."""
        world = self.plan.replicas
        noloco = self.outer_cfg.method == "noloco"
        overlap = self.comm_cfg.overlap and noloco
        streams = self.comm_cfg.streams if noloco else 1
        if self.schedule == "hypercube":
            dims = max(int(np.log2(world)), 1)
            base = dims * dims if overlap else dims
        else:
            base = self.pairing_pool
        return base * streams * (2 if overlap else 1)

    def pool_slot(self, outer_index: int) -> int:
        """The pairing slot of outer round ``outer_index`` — the bounded part
        of the program key."""
        if self.schedule == "hypercube":
            return pairing_lib.hypercube_dim(
                outer_index, self.plan.replicas, seed=self.seed
            )
        return outer_index % max(self.pairing_pool, 1)

    def pairs_for(
        self,
        outer_index: int,
        membership: Membership | None = None,
        groups: Any | None = None,
    ) -> tuple[int, list[tuple[int, int]]]:
        """(pool slot, static ppermute pairs) for one outer round.

        A pure function of ``(seed, slot, membership view)``: every node that
        agrees on the membership view derives the same pairs with zero
        control-plane messages — the coordinator-free property, preserved on
        the compiled path."""
        world = self.plan.replicas
        slot = self.pool_slot(outer_index)
        full = membership is None or (membership.is_full and groups is None)
        if self.schedule == "hypercube":
            if full:
                return slot, pairing_lib.hypercube_ppermute_pairs(
                    outer_index, world, seed=self.seed
                )
            return slot, pairing_lib.elastic_hypercube_ppermute_pairs(
                outer_index, membership, seed=self.seed, groups=groups
            )
        if full:
            return slot, pairing_lib.ppermute_pairs(slot, world, seed=self.seed)
        return slot, pairing_lib.elastic_ppermute_pairs(
            slot, membership, seed=self.seed, groups=groups
        )

    def view_key(
        self, membership: Membership | None, groups: Any | None = None
    ) -> Any:
        """Hashable participant-view part of the program key (None = the
        healthy full-membership view, shared by epochs with equal masks)."""
        if membership is None or (membership.is_full and groups is None):
            return None
        gk = None if groups is None else tuple(tuple(int(r) for r in g) for g in groups)
        return (tuple(membership.mask), gk)

    # -- compiled program lookup --------------------------------------------

    def program(
        self,
        outer_index: int,
        membership: Membership | None = None,
        groups: Any | None = None,
        *,
        stream: int | None = None,
        consume: bool = False,
        presend_index: int | None = None,
        presend_membership: Membership | None = None,
        update_mask: Any | None = None,
        staleness: Any | None = None,
    ) -> tuple[Any, dict]:
        """Compiled program for round ``outer_index`` under the given view.

        ``stream`` selects the STREAMED program variant (one stream of the
        pool's :class:`~repro.comm.StreamPartition` synced per call;
        ``outer_index`` is then the global stream-sync index).  ``consume``
        compiles the φ-prefetch read; ``presend_index`` adds the φ′ pre-send
        along the pairing of that FUTURE sync index (drawn against
        ``presend_membership`` — the full current membership, which may
        differ from this round's participant view when stragglers sit out).
        Both signature variants are part of the program key, so the elastic
        epoch-fallback (consume → blocking for one stream) is a pool lookup,
        never a rebuild of an existing entry.

        ASYNC merged-tick rounds (per-replica round clocks, DESIGN.md §7):
        ``update_mask`` is the host-side DUE set — only due replicas apply
        the outer update this tick; everyone else passes through frozen but
        still serves its in-progress (Δ, φ) over the ppermute as a passive
        source.  ``staleness`` is the per-replica τ vector baked into the
        program (``stale="momentum"`` discount; pass None for
        ``stale="naive"``, where τ is telemetry-only).  Both become part of
        the program key alongside the membership view, so the all-due τ=0
        tick takes the ``(view, slot)`` entry — bit-identical to the
        synchronous schedule.

        Returns ``(fn, info)`` with ``info = {key, slot, view, compiled,
        build_s, pool_size}`` — ``compiled`` marks a pool miss (the caller
        times the first invocation for the ``recompile`` telemetry event's
        wall-clock; XLA compiles lazily)."""
        slot, perm = self.pairs_for(outer_index, membership, groups)
        view = self.view_key(membership, groups)
        key: Any = (view, slot)
        perm_presend = None
        presend_key = None
        if stream is None and (consume or presend_index is not None):
            raise ValueError(
                "consume/presend are stream-program options; pass stream="
            )
        if presend_index is not None:
            slot_p, perm_presend = self.pairs_for(
                presend_index, presend_membership, groups
            )
            presend_key = (slot_p, self.view_key(presend_membership, groups))
        if stream is not None:
            if self.partition is None:
                raise ValueError(
                    "streamed programs need the pool constructed with a "
                    "StreamPartition (partition=...)"
                )
            key = (view, slot, "stream", stream, bool(consume), presend_key)
        active = None
        if view is not None:
            # the PARTICIPANT mask is the membership mask alone: an active
            # replica outside every partition component stays a participant
            # (its pairs self-loop, so it runs the self-momentum path) —
            # matching the stacked runtime's semantics exactly
            active = np.asarray(membership.mask, dtype=bool)
        stale_vec = None
        if update_mask is not None or staleness is not None:
            if stream is not None:
                raise ValueError(
                    "async update_mask/staleness do not compose with streamed "
                    "programs (SimCluster forbids the pairing at init)"
                )
            um_key = None
            if update_mask is not None:
                due = np.asarray(update_mask, dtype=bool)
                # the update set is the due replicas; non-due participants
                # freeze (passive sources over the ppermute)
                active = due if active is None else (active & due)
                um_key = tuple(bool(x) for x in due)
            st_key = None
            if staleness is not None:
                stale_vec = np.asarray(staleness, dtype=np.float32)
                st_key = tuple(float(x) for x in stale_vec)
            key = (view, slot, "async", um_key, st_key)
        compiled = key not in self._programs
        build_s = 0.0
        if compiled:
            self.misses += 1
            t0 = time.perf_counter()
            with jax.set_mesh(self.mesh):
                self._programs[key] = build_outer_step(
                    self.plan, self.mesh, self.param_specs, self.outer_cfg, perm,
                    comm_cfg=self.comm_cfg, kernel_cfg=self.kernel_cfg,
                    active=active, staleness=stale_vec, stream=stream,
                    partition=self.partition,
                    consume_prefetch=consume, perm_presend=perm_presend,
                )
            build_s = time.perf_counter() - t0
            self.events.append({
                "slot": str(slot), "view": "full" if view is None else "elastic",
                "epoch": None if membership is None else membership.epoch,
                "stream": stream,
                "async": update_mask is not None or staleness is not None,
                "build_s": round(build_s, 4), "pool_size": len(self._programs),
            })
        else:
            self.hits += 1
        info = {
            "key": key, "slot": slot, "view": view, "compiled": compiled,
            "build_s": build_s, "pool_size": len(self._programs),
        }
        return self._programs[key], info

    def drain_events(self) -> list[dict]:
        events, self.events = self.events, []
        return events

    def stats(self) -> dict:
        return {
            "pool_size": len(self._programs),
            "hits": self.hits,
            "misses": self.misses,
            "schedule": self.schedule,
            "max_programs_per_view": self.max_programs_per_view,
        }


# ---------------------------------------------------------------------------
# Serving steps
# ---------------------------------------------------------------------------


def build_decode_step(
    cfg: ModelConfig,
    plan: Plan,
    mesh: Mesh,
    params: PyTree,      # stacked Param tree
    caches: PyTree,      # Param-annotated cache tree (global shapes)
    batch_specs: dict,
):
    pspecs = plans_lib.param_pspecs(plan, mesh, params)
    pspecs = plans_lib.adjust_attn_specs_for_decode(plan, pspecs, params)
    cspecs = plans_lib.param_pspecs(plan, mesh, caches)
    ctx = plan.ctx()
    rep = plan.replica_axes
    dp = plan.data_axes
    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)

    def body(theta_l, caches_local, tokens, index):
        theta = _squeeze_replica(theta_l)
        logits, new_caches = model_api.decode_step(
            theta, cfg, tokens, index.reshape(()), caches_local, ctx
        )
        return logits, new_caches

    in_specs = (pspecs, cspecs, batch_specs["tokens"], P())
    vocab_entry = (
        plan.model_axis if cfg.vocab_size % plan.tp == 0 and plan.tp > 1 else None
    )
    out_specs = (P(dp_entry, None, vocab_entry), cspecs)
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    logits_sh = NamedSharding(mesh, out_specs[0])
    return jax.jit(
        fn,
        in_shardings=(
            plans_lib.shardings(mesh, pspecs),
            plans_lib.shardings(mesh, cspecs),
            NamedSharding(mesh, batch_specs["tokens"]),
            NamedSharding(mesh, P()),
        ),
        # cache outputs must carry the SAME shardings as the inputs so the
        # serve loop can feed them straight back in (donated)
        out_shardings=(logits_sh, plans_lib.shardings(mesh, cspecs)),
        donate_argnums=(1,),
    ), (pspecs, cspecs)


def build_prefill_step(
    cfg: ModelConfig,
    plan: Plan,
    mesh: Mesh,
    params: PyTree,
    caches: PyTree,
    batch_example: dict,
):
    pspecs = plans_lib.param_pspecs(plan, mesh, params)
    cspecs = plans_lib.param_pspecs(plan, mesh, caches)
    bspecs = batch_pspecs(plan, batch_example)
    ctx = plan.ctx()
    dp = plan.data_axes
    dp_entry = dp if len(dp) > 1 else (dp[0] if dp else None)

    def body(theta_l, caches_local, batch_local):
        theta = _squeeze_replica(theta_l)
        last_hidden, new_caches = model_api.prefill(theta, cfg, batch_local, caches_local, ctx)
        return last_hidden, new_caches

    in_specs = (pspecs, cspecs, bspecs)
    out_specs = (P(dp_entry, None, None), cspecs)
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    return jax.jit(
        fn,
        in_shardings=(
            plans_lib.shardings(mesh, pspecs),
            plans_lib.shardings(mesh, cspecs),
            plans_lib.shardings(mesh, bspecs),
        ),
        out_shardings=(
            NamedSharding(mesh, out_specs[0]),
            plans_lib.shardings(mesh, cspecs),
        ),
        donate_argnums=(1,),
    ), (pspecs, cspecs)
