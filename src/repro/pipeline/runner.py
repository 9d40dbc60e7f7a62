"""Dynamic pipeline routing (paper §3.1) + the §5.2 ablation harness.

The paper splits the model into consecutive stages, replicates each stage
DP-wide, and at every step routes each microbatch from a RANDOM replica of
stage s to a random replica of stage s+1 (backward follows the same path).
This implicitly mixes the weights of different DP instances: §5.2 shows the
cross-replica weight std drops ~10–15% with NO outer synchronization at all.

Simulation realization (exact semantics, one process): stage-s params carry a
leading replica axis; routing between stages is a gather by a per-step random
permutation of the replica axis.  ``jax.grad`` transposes the gather, so
gradients automatically flow back along the forward route — precisely the
paper's backward rule.  On a (stage, replica) device mesh the same
permutation is a ``lax.ppermute`` at each stage boundary; the simulation and
the collective are the same linear operator.

``routing="random"`` vs ``routing="fixed"`` is the §5.2 ablation switch.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro import obs
from repro.comm import CommConfig
from repro.core import metrics as metrics_lib
from repro.core import pairing
from repro.core.elastic import ElasticContext
from repro.core.outer import OuterConfig, OuterState, outer_step_stacked
from repro.kernels.dispatch import KernelConfig
from repro.models import model as model_api
from repro.models import transformer as tfm
from repro.models.common import values_of
from repro.models.config import ModelConfig
from repro.models.layers import apply_norm, cross_entropy_parts, embed_tokens, logits_sharded
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.parallel.sharding import ShardCtx

PyTree = Any


# ---------------------------------------------------------------------------
# Stage splitting of a ModelConfig transformer
# ---------------------------------------------------------------------------


def split_stages(cfg: ModelConfig, num_stages: int) -> list[ModelConfig]:
    """Split layers as evenly as possible into consecutive stage configs."""
    if cfg.num_layers % num_stages:
        raise ValueError("num_layers must divide evenly into stages")
    per = cfg.num_layers // num_stages
    return [dataclasses.replace(cfg, num_layers=per) for _ in range(num_stages)]


def init_stage_params(key, cfg: ModelConfig, stage: int, num_stages: int) -> PyTree:
    """Stage 0 owns the embedding; the last stage owns the final norm (+ the
    tied unembedding reads stage 0's table in the simulation — we give the
    last stage its OWN unembedding to keep stages self-contained)."""
    scfg = split_stages(cfg, num_stages)[stage]
    p: dict = {"stack": tfm.init_stack(key, scfg)}
    if stage == 0:
        from repro.models.layers import init_embedding

        p["embed"] = init_embedding(jax.random.fold_in(key, 1), cfg)
    if stage == num_stages - 1:
        from repro.models.layers import init_embedding, init_norm

        p["final_norm"] = init_norm(cfg, cfg.d_model)
        p["unembed"] = init_embedding(jax.random.fold_in(key, 2), cfg)
    return p


def apply_stage(
    params: PyTree, cfg: ModelConfig, stage: int, num_stages: int, x: jax.Array,
    ctx: ShardCtx,
) -> jax.Array:
    scfg = split_stages(cfg, num_stages)[stage]
    if stage == 0:
        x = embed_tokens(params["embed"], cfg, x, ctx)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)
    x, _, _ = tfm.apply_stack(params["stack"], scfg, x, ctx, positions=positions)
    return x


def stage_loss(
    params: PyTree, cfg: ModelConfig, x: jax.Array, labels: jax.Array, ctx: ShardCtx
) -> jax.Array:
    h = apply_norm(params["final_norm"], x)
    logits = logits_sharded(params["unembed"], cfg, h, ctx)
    nll, cnt = cross_entropy_parts(logits, labels, cfg, ctx)
    return nll / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# Routed pipeline trainer (stacked replicas)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PipelineTrainer:
    """DP×PP trainer with per-step random routing; inner AdamW per replica.

    ``routing``: "random" (paper §3.1) or "fixed" (classic pipelining — the
    §5.2 baseline where DP instances never exchange information when the
    outer optimizer is off).

    ``outer`` enables the paper's COMPLETE method (§3.1 routing + §3.2 gossip
    outer optimizer): every ``outer.inner_steps`` steps each stage runs one
    NoLoCo/DiLoCo outer step over its replica axis, reusing the exact
    :func:`repro.core.outer.outer_step_stacked` machinery (pairings from
    :mod:`repro.core.pairing`, wire codec from ``comm``).  ``outer=None``
    keeps the routing-only trainer (the §5.2 no-outer baseline).

    ``elastic`` attaches the shared :class:`~repro.core.elastic.
    ElasticContext` (DESIGN.md §7): routing permutations restrict to the
    ACTIVE replica set (:func:`~repro.core.pairing.elastic_route_permutation`
    — inactive stage-replicas carry no traffic and their params/opt freeze),
    every stage's gossip pairing is drawn over active members only via
    :func:`~repro.core.pairing.elastic_partner_table` (per-stage seed offset,
    partition-aware), and loss/eval/weight-std aggregate over active
    replicas.  ``elastic=None`` keeps the fixed-world trainer bit-for-bit."""

    cfg: ModelConfig
    num_stages: int
    replicas: int
    inner: AdamWConfig = dataclasses.field(default_factory=lambda: AdamWConfig(lr=1e-3, weight_decay=0.0))
    routing: str = "random"
    outer: OuterConfig | None = None
    comm: CommConfig = dataclasses.field(default_factory=CommConfig)
    kernel_cfg: KernelConfig = dataclasses.field(default_factory=KernelConfig)
    seed: int = 0
    elastic: ElasticContext | None = None

    def __post_init__(self):
        if self.elastic is not None and self.elastic.world != self.replicas:
            raise ValueError(
                f"elastic world {self.elastic.world} != replicas {self.replicas}"
            )

    @property
    def outer_enabled(self) -> bool:
        return self.outer is not None and self.outer.method != "none"

    def init(self, key) -> dict:
        params = []
        for s in range(self.num_stages):
            stage_keys = jax.random.split(jax.random.fold_in(key, s), self.replicas)
            # IMPORTANT: same init across replicas (φ_{0,i} ≡ φ_0, paper §A)
            one = values_of(
                init_stage_params(stage_keys[0], self.cfg, s, self.num_stages)
            )
            params.append(jax.tree.map(
                lambda v: jnp.broadcast_to(v[None], (self.replicas,) + v.shape), one
            ))
        opt = [jax.vmap(adamw_init)(p) for p in params]
        state = {"params": params, "opt": opt, "step": 0}
        if self.outer_enabled:
            state["outer"] = {
                "phi": [jax.tree.map(jnp.copy, p) for p in params],
                "delta": [jax.tree.map(jnp.zeros_like, p) for p in params],
                "step": 0,
            }
        return state

    # -- routing --------------------------------------------------------

    def routes(self, step: int) -> list[jax.Array]:
        """One permutation per stage boundary (num_stages-1 of them).

        With an elastic context and a partial membership the permutations
        restrict to a bijection on the ACTIVE set (inactive replicas route to
        themselves and carry no traffic); at full membership the elastic draw
        is bit-identical to the static one, so the healthy path never
        changes."""
        if self.routing == "fixed":
            return [jnp.arange(self.replicas)] * (self.num_stages - 1)
        elastic_view = (
            self.elastic.membership
            if self.elastic is not None and not self.elastic.is_full
            else None
        )
        out = []
        for b in range(self.num_stages - 1):
            if elastic_view is not None:
                out.append(jnp.asarray(pairing.elastic_route_permutation(
                    step * 97 + b, elastic_view, seed=self.seed
                )))
            else:
                out.append(pairing.pairing_permutation(
                    step * 97 + b, self.replicas, seed=self.seed
                ))
        return out

    def _active_weights(self) -> jax.Array:
        """(R,) f32 participation weights for loss/eval aggregation."""
        if self.elastic is None or self.elastic.is_full:
            return jnp.ones((self.replicas,), jnp.float32)
        return jnp.asarray(self.elastic.membership.active_array()).astype(jnp.float32)

    # -- loss over routed paths ------------------------------------------

    def loss(
        self, params: list, batch: dict, routes: list[jax.Array],
        weights: jax.Array | None = None,
    ) -> jax.Array:
        """Active-weighted mean loss over replicas; x (R, B, S) follows the
        routed path.  ``weights=None`` (or all ones) is the plain mean."""
        ctx = ShardCtx.local()
        x = batch["tokens"]
        for s in range(self.num_stages):
            if s > 0:
                x = jnp.take(x, routes[s - 1], axis=0)
            x = jax.vmap(
                lambda p, xx: apply_stage(p, self.cfg, s, self.num_stages, xx, ctx)
            )(params[s], x)
        # labels must follow the full route of their microbatch
        lab = batch["labels"]
        for r in routes:
            lab = jnp.take(lab, r, axis=0)
        losses = jax.vmap(
            lambda p, xx, ll: stage_loss(p, self.cfg, xx, ll, ctx)
        )(params[-1], x, lab)
        if weights is None:
            return jnp.mean(losses)
        return jnp.sum(losses * weights) / jnp.maximum(jnp.sum(weights), 1.0)

    # -- one SGD step -------------------------------------------------------

    def _jitted_step(self):
        if not hasattr(self, "_step_cache"):
            def step(params, opt, batch, routes, weights):
                loss, grads = jax.value_and_grad(
                    lambda ps: self.loss(ps, batch, routes, weights)
                )(params)
                act = weights > 0

                def _sel(new, old):
                    return jnp.where(
                        act.reshape((-1,) + (1,) * (new.ndim - 1)), new, old
                    )

                new_params, new_opt = [], []
                for p, o, g in zip(params, opt, grads):
                    np_, no_, _ = jax.vmap(
                        lambda gg, oo, pp: adamw_update(gg, oo, pp, self.inner)
                    )(g, o, p)
                    # frozen (inactive) replicas keep params AND moments: the
                    # weighted loss already zeroes their grads, but AdamW's
                    # count/eps math would still drift them
                    np_ = jax.tree.map(_sel, np_, p)
                    no_ = jax.tree.map(_sel, no_, o)
                    new_params.append(np_)
                    new_opt.append(no_)
                return new_params, new_opt, loss

            object.__setattr__(self, "_step_cache", jax.jit(step))
        return self._step_cache

    def train_step(self, state: dict, batch: dict) -> tuple[dict, float]:
        routes = self.routes(state["step"])
        new_params, new_opt, loss = self._jitted_step()(
            state["params"], state["opt"], batch, routes, self._active_weights()
        )
        new_state = dict(
            state, params=new_params, opt=new_opt, step=state["step"] + 1
        )
        return new_state, float(loss)

    # -- outer optimizer (§3.2 gossip, per stage over the replica axis) -----

    def maybe_outer_step(self, state: dict) -> tuple[dict, bool]:
        """Run the NoLoCo/DiLoCo outer step on every stage when due.

        Each stage's replicas form their own gossip group: stage s draws its
        OWN random matching for outer round k (seed offset by stage), so the
        pairings across stages are independent — combined with the random
        routing this is the paper's full §3.1+§3.2 method.  Fast weights are
        reset to the new slow weights (look-ahead semantics); AdamW moments
        persist, matching :class:`~repro.core.GossipTrainer`."""
        if not self.outer_enabled:
            return state, False
        m = self.outer.inner_steps
        k = int(state["outer"]["step"])
        # outer round k fires once step reaches (k+1)*m — idempotent between
        # inner steps (calling twice at the same step is a no-op)
        if state["step"] < (k + 1) * m:
            return state, False
        with obs.span("train.outer_step", outer_index=k, stream=0):
            return self._outer_round(state, k), True

    def _outer_round(self, state: dict, k: int) -> dict:
        round_plan = None
        active = None
        if self.elastic is not None:
            # one participation decision for the round, shared by all stages
            # (consumes the straggler view); each stage draws its OWN pairing
            # over those participants below
            round_plan = self.elastic.plan_round(None)
            active = None if round_plan.active is None else jnp.asarray(round_plan.active)
        new_params, new_phi, new_delta = [], [], []
        for s in range(self.num_stages):
            partner = None
            if self.outer.method == "noloco":
                stage_seed = self.seed + 1_000_003 * (s + 1)
                if round_plan is not None:
                    partner = jnp.asarray(pairing.elastic_partner_table(
                        k, round_plan.participants, seed=stage_seed,
                        groups=self.elastic.partition,
                    ))
                else:
                    partner = jnp.asarray(pairing.partner_table(
                        k, self.replicas, seed=stage_seed
                    ))
            ost = OuterState(
                phi=state["outer"]["phi"][s],
                delta=state["outer"]["delta"][s],
                step=jnp.asarray(k, jnp.int32),
            )
            new_ost, new_theta = outer_step_stacked(
                ost, state["params"][s], self.outer,
                partner=partner, active=active,
                comm_cfg=self.comm, kernel_cfg=self.kernel_cfg,
            )
            new_params.append(new_theta)
            new_phi.append(new_ost.phi)
            new_delta.append(new_ost.delta)
        return dict(
            state,
            params=new_params,
            outer={"phi": new_phi, "delta": new_delta, "step": k + 1},
        )

    # -- grad-free eval --------------------------------------------------------

    def eval_loss(self, params: list, batch: dict) -> jax.Array:
        """Active-mean loss over replicas WITHOUT routing (identity routes):
        each replica is evaluated as a self-contained pipeline, no
        gradients."""
        if not hasattr(self, "_eval_cache"):
            fixed = [jnp.arange(self.replicas)] * (self.num_stages - 1)
            object.__setattr__(
                self, "_eval_cache",
                jax.jit(lambda ps, b, w: self.loss(ps, b, fixed, w)),
            )
        return self._eval_cache(params, batch, self._active_weights())

    # -- §5.2 metric -----------------------------------------------------------

    def weight_std(self, state: dict) -> float:
        """Mean across params of the std across ACTIVE replicas (all stages)
        — shared impl: :func:`repro.core.metrics.replica_weight_std`."""
        params = state["params"]
        if self.elastic is not None and not self.elastic.is_full:
            ids = jnp.asarray(self.elastic.active_ids())
            if len(ids) < 2:
                return 0.0
            params = [
                jax.tree.map(lambda x: jnp.take(x, ids, axis=0), p)
                for p in params
            ]
        return float(metrics_lib.replica_weight_std(params))
