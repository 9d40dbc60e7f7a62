"""Ensemble speculative decoding: a second NoLoCo replica drafts, the
promoted target verifies.

NoLoCo's partial averaging (paper Eq. 2-3) never collapses the ensemble: a
checkpoint holds R slightly-diverse replicas, so a SECOND replica — or a
depth-truncated slice of the first (:func:`repro.serve.promote.
truncate_layers`) — is a free draft model that agrees with the target on
most easy tokens.  The engine here exploits that without changing what is
served:

  * DRAFT — ``spec_k`` scanned decode steps of the draft model propose a
    token run.  The scan body is literally :func:`repro.serve.engine.
    _decode_core` with the draft's params/caches, so proposals (and the
    draft's sampling noise) are bitwise what the draft would decode solo.
  * VERIFY — ONE chunked forward of the target
    (:func:`repro.models.model.paged_prefill_chunk` with ``collect=True``)
    scores all ``spec_k`` fed tokens at once.  The collect path runs
    attention and the recurrent mixers as sequential per-token updates,
    BITWISE identical to the target's own decode steps — which is the whole
    exactness argument: the accepted prefix plus the first corrected token
    are, token for token, what the target would have produced alone (greedy
    or sampled — noise is keyed by (request id, output index), independent
    of who proposed the token).  ``--verify`` in launch/serve.py checks this
    end-to-end against a non-speculative engine.
  * COMMIT / ROLLBACK — per slot, ``commit = accepted + 1`` tokens land in
    the output buffer; positions advance by ``commit``.  KV for rejected
    tokens needs NO explicit rollback: the positional mask (``kv_pos <=
    q_pos``) hides pages past the new position, and the stale entries are
    overwritten in place when decoding reaches them again.  Recurrent states
    DO roll back: the verify pass returns per-token state trajectories and
    the engine selects index ``commit - 1``; the draft restores the matching
    snapshot emitted by its proposal scan.

The draft shares the target's block tables and page allocator (same page
ids index its own, separately-shaped pools), so admission control and leak
accounting stay single-sourced.  Host sync cost: one small device_get of
the per-slot commit vector per ROUND (amortized over up to ``spec_k``
tokens), versus none for plain decode — the acceptance telemetry rides it.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import model as M
from repro.models.attention import PagedAttnCache, PagedView
from repro.models.config import ModelConfig
from repro.parallel.sharding import ShardCtx
from repro.serve.engine import (
    _SAMPLE_KEY,
    EngineState,
    ServeConfig,
    ServeEngine,
    _chunk_program,
    _decode_core,
)

__all__ = ["SpecServeEngine"]


# ---------------------------------------------------------------------------
# Cache-tree walkers.  Engine caches are {"scan": [entry|None], "rem":
# [entry]} with entry = (mixer, cross); "scan" mixers carry a leading layer
# axis (depth-stacked), "rem" mixers do not.  Attention mixers are
# PagedAttnCache (shared pools, no per-token state to roll back); everything
# else is per-slot recurrent state.
# ---------------------------------------------------------------------------


def _rec_snapshot(caches):
    """Recurrent mixers only, every leaf transposed to put the SLOT axis
    first — the draft scan stacks these per step, and a slot-leading layout
    makes the later per-slot trajectory select one take_along_axis."""
    def pick(e, stacked):
        if e is None:
            return None
        mixer, _ = e
        if isinstance(mixer, PagedAttnCache):
            return None
        return jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0) if stacked else x, mixer)

    return {
        "scan": [pick(e, True) for e in caches["scan"]],
        "rem": [pick(e, False) for e in caches["rem"]],
    }


def _where_keep(keep, new, old, stacked):
    k = (
        keep.reshape((1, -1) + (1,) * (new.ndim - 2))
        if stacked
        else keep.reshape((-1,) + (1,) * (new.ndim - 1))
    )
    return jnp.where(k, new, old)


def _restore_draft(old, final, snaps, sel, keep):
    """Draft caches after a round: written page pools from the scan's final
    state, recurrent mixers rolled back to snapshot ``sel[r]`` per slot."""
    def one(o, f, s, stacked):
        if o is None:
            return None
        mixer_o, cross = o
        if isinstance(mixer_o, PagedAttnCache):
            return (f[0], cross)

        def leaf(ol, sl):
            # sl: (k, R, ...) scan-stacked snapshots, slot axis already first
            idx = sel.reshape((1, -1) + (1,) * (sl.ndim - 2))
            picked = jnp.take_along_axis(sl, idx, axis=0)[0]  # (R, ...)
            if stacked:
                picked = jnp.moveaxis(picked, 0, 1)           # (L, R, ...)
            return _where_keep(keep, picked, ol, stacked)

        return (jax.tree.map(leaf, mixer_o, s), cross)

    return {
        "scan": [one(o, f, s, True) for o, f, s in zip(old["scan"], final["scan"], snaps["scan"])],
        "rem": [one(o, f, s, False) for o, f, s in zip(old["rem"], final["rem"], snaps["rem"])],
    }


def _accept_target(old, new, sel, keep):
    """Target caches after a round: written pools from the verify pass,
    recurrent mixers taken from its per-token trajectory at index ``sel[r]``
    (trajectory axis sits right after the slot axis: (L?, R, C, ...))."""
    def one(o, n, stacked):
        if o is None:
            return None
        mixer_o, cross = o
        mixer_n, _ = n
        if isinstance(mixer_o, PagedAttnCache):
            return (mixer_n, cross)
        t_ax = 2 if stacked else 1

        def leaf(ol, nl):
            idx = (
                sel.reshape((1, -1, 1) + (1,) * (nl.ndim - 3))
                if stacked
                else sel.reshape((-1, 1) + (1,) * (nl.ndim - 2))
            )
            picked = jnp.squeeze(jnp.take_along_axis(nl, idx, axis=t_ax), axis=t_ax)
            return _where_keep(keep, picked, ol, stacked)

        return (jax.tree.map(leaf, mixer_o, mixer_n), cross)

    return {
        "scan": [one(o, n, True) for o, n in zip(old["scan"], new["scan"])],
        "rem": [one(o, n, False) for o, n in zip(old["rem"], new["rem"])],
    }


@functools.lru_cache(maxsize=None)
def _spec_program(cfg: ModelConfig, dcfg: ModelConfig, k: int):
    """ONE jitted speculative round per (target, draft, spec_k): draft scan →
    target verify → accept/rollback.  Returns (new target EngineState, new
    draft caches, per-slot commit counts)."""
    ctx = ShardCtx.local()

    def spec_impl(params, draft_params, state, draft_caches):
        # -- draft proposes k tokens (its own decode steps, bitwise) --------
        def dstep(dstate, _):
            ns = _decode_core(dcfg, ctx, draft_params, dstate)
            return ns, (ns.tokens, _rec_snapshot(ns.caches))

        dstate0 = dataclasses.replace(
            state, caches=draft_caches, out_buf=jnp.zeros_like(state.out_buf)
        )
        dfinal, (props, snaps) = jax.lax.scan(dstep, dstate0, None, length=k)
        props_t = props.T                                   # (R, k); col j = p_{j+1}

        # -- target verifies all k feeds in one chunked forward -------------
        # feed = [current token, p_1, ..., p_{k-1}]; o_{j+1} is sampled from
        # the logits after feed j with the SAME (rid, output index) noise a
        # plain decode step would use.
        feed = jnp.concatenate([state.tokens[:, None], props_t[:, : k - 1]], axis=1)
        remaining = jnp.clip(state.budgets - state.out_len, 0, k)
        lengths = jnp.where(state.active, remaining, 0)
        view = PagedView(state.block_tables, state.positions, state.active)
        logits, traj = M.paged_prefill_chunk(
            params, cfg, feed, state.caches, view, ctx,
            lengths=lengths, collect=True,
        )                                                   # (R, k, V)
        idx = state.out_len[:, None] + jnp.arange(k, dtype=jnp.int32)[None]
        keys = jax.vmap(jax.vmap(
            lambda rid, i: jax.random.fold_in(jax.random.fold_in(_SAMPLE_KEY, rid), i)
        ))(jnp.broadcast_to(state.rids[:, None], idx.shape), idx)
        g = jax.vmap(jax.vmap(
            lambda key: jax.random.gumbel(key, logits.shape[-1:], jnp.float32)
        ))(keys)
        o = jnp.argmax(
            logits + state.temps[:, None, None] * g, axis=-1
        ).astype(jnp.int32)                                 # (R, k); col j = o_{j+1}

        # -- accept prefix + first correction -------------------------------
        eq = (props_t[:, : k - 1] == o[:, : k - 1]).astype(jnp.int32)
        accepted = jnp.sum(jnp.cumprod(eq, axis=1), axis=1)             # (R,)
        commit = jnp.minimum(accepted + 1, remaining)
        commit = jnp.where(state.active, commit, 0)
        keep = state.active & (commit > 0)
        sel = jnp.clip(commit - 1, 0, k - 1)

        # committed tokens land at output indices out_len .. out_len+commit-1;
        # rejected columns scatter out of range and are dropped
        cols = jnp.arange(k, dtype=jnp.int32)[None, :]
        cap = state.out_buf.shape[1]
        wi = jnp.where(cols < commit[:, None], idx, cap)
        rows = jnp.broadcast_to(
            jnp.arange(state.out_buf.shape[0], dtype=jnp.int32)[:, None], wi.shape
        )
        out_buf = state.out_buf.at[rows, wi].set(o, mode="drop")

        t_next = jnp.take_along_axis(o, sel[:, None], axis=1)[:, 0]
        new_state = EngineState(
            caches=_accept_target(state.caches, traj, sel, keep),
            block_tables=state.block_tables,
            tokens=jnp.where(keep, t_next, state.tokens),
            positions=state.positions + commit,
            active=state.active,
            temps=state.temps,
            rids=state.rids,
            out_buf=out_buf,
            out_len=state.out_len + commit,
            budgets=state.budgets,
        )
        new_draft = _restore_draft(draft_caches, dfinal.caches, snaps, sel, keep)
        return new_state, new_draft, commit, accepted

    return jax.jit(spec_impl, donate_argnums=(2, 3))


class SpecServeEngine(ServeEngine):
    """ServeEngine whose decode step is a speculative round.

    ``spec_k`` is the round width: the draft runs ``spec_k`` decode steps
    and the target verifies ``spec_k`` fed tokens, committing between 1 and
    ``spec_k`` tokens per round (the classic bonus token is forgone so the
    draft never has to catch up — its snapshots already cover every commit).
    ``spec_k=1`` degenerates to plain decode plus wasted draft work.

    Output is EXACTLY the target engine's, so the draft only affects speed:
    a good draft (second NoLoCo replica, truncated slice) commits close to
    ``spec_k`` tokens per round; a terrible one still serves correct tokens
    at roughly plain-decode speed.
    """

    def __init__(
        self,
        params: Any,
        cfg: ModelConfig,
        scfg: ServeConfig,
        draft_params: Any,
        draft_cfg: ModelConfig | None = None,
        *,
        spec_k: int = 4,
    ):
        if not scfg.prefill_chunk:
            raise ValueError("speculative decode requires chunked prefill "
                             "(prefill_chunk > 0)")
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        super().__init__(params, cfg, scfg)
        self.dcfg = draft_cfg or cfg
        if self.dcfg.vocab_size != cfg.vocab_size:
            raise ValueError("draft and target must share a vocabulary")
        self.draft_params = draft_params
        self.spec_k = spec_k
        self.draft_caches = M.init_paged_cache_tree(
            self.dcfg, scfg.max_slots, scfg.num_pages, scfg.page_size
        )
        self._spec_fn = _spec_program(cfg, self.dcfg, spec_k)
        self._draft_chunk_fn = _chunk_program(self.dcfg, scfg.prefill_chunk)
        self.spec_rounds = 0
        self.spec_commit_total = 0
        self.spec_accept_total = 0
        self.spec_prop_total = 0

    @property
    def accept_rate(self) -> float:
        """Accepted / USABLE draft proposals.  A slot-round with ``rem``
        budget tokens left can accept at most min(spec_k−1, rem−1) proposals
        (commit is capped at rem), so that is what each participation adds to
        the denominator — a perfect draft scores exactly 1.0 even on the
        budget-tail rounds.

        A zero denominator (every round so far had rem == 1 for every slot,
        or no spec round ran at all) is vacuously perfect: not one usable
        proposal was rejected, so the rate is 1.0 — NOT 0.0, which would
        falsely read as "the draft never matched", and NOT NaN."""
        if not self.spec_prop_total:
            return 1.0
        return self.spec_accept_total / self.spec_prop_total

    # -- prefill: the draft walks the same chunks through its own caches ----

    def _prefill_chunk_step(self, slot: int) -> None:
        occ = self._slots[slot]
        req = occ["req"]
        cur = occ["cursor"]
        c = self.scfg.prefill_chunk
        n = min(c, len(req.prompt) - cur)
        toks = req.prompt[cur: cur + n] + [0] * (c - n)
        scratch = self._prefill_caches(self.draft_caches, occ.get("rec_d"))
        key = jax.random.fold_in(jax.random.fold_in(_SAMPLE_KEY, req.rid), 0)
        _tok0, new_d = self._draft_chunk_fn(
            self.draft_params,
            jnp.asarray(toks, jnp.int32),
            jnp.int32(n),
            scratch,
            occ["row"],
            jnp.int32(cur),
            jnp.float32(0.0),
            key,
        )
        if cur + n < len(req.prompt):
            self.draft_caches = self._merge_pools(self.draft_caches, new_d)
            occ["rec_d"] = self._extract_rec(new_d)
        else:
            # the draft's sampled first token is DISCARDED — token 0 comes
            # from the target's chunk step below (exactness)
            self.draft_caches = self._merge_caches(self.draft_caches, new_d, slot)
            occ["rec_d"] = None
        super()._prefill_chunk_step(slot)

    # -- decode: one speculative round per tick -----------------------------

    def _decode(self) -> None:
        """One speculative round over every slot."""
        with obs.span("serve.spec_round") as sp:
            t0 = time.perf_counter()
            new_state, new_draft, commit, accepted = self._spec_fn(
                self.params, self.draft_params, self.state, self.draft_caches
            )
            self.state = new_state
            self.draft_caches = new_draft
            # the round's one host sync: k tokens' worth of scheduling state
            with obs.span("serve.fetch"):
                commits = np.asarray(jax.device_get(commit))
                accepts = np.asarray(jax.device_get(accepted))
            now = time.perf_counter()
            if self.scfg.sync_each_step:
                self.decode_step_times.append(now - t0)
            self.decode_steps += 1
            self.spec_rounds += 1
            prop0, acc0 = self.spec_prop_total, self.spec_accept_total
            for slot, occ in enumerate(self._slots):
                if occ is None or occ["phase"] != "decode":
                    continue
                n = int(commits[slot])
                if n <= 0:
                    continue
                rem = occ["req"].max_new - occ["steps"]
                usable = max(min(self.spec_k - 1, rem - 1), 0)
                acc = min(int(accepts[slot]), usable)
                occ["spec_rounds"] = occ.get("spec_rounds", 0) + 1
                occ["spec_commit"] = occ.get("spec_commit", 0) + n
                occ["spec_accept"] = occ.get("spec_accept", 0) + acc
                occ["spec_prop"] = occ.get("spec_prop", 0) + usable
                self.spec_commit_total += n
                self.spec_accept_total += acc
                self.spec_prop_total += usable
                for _ in range(n):
                    if occ["steps"] < occ["req"].max_new:
                        occ["t_toks"].append(now)
                    occ["steps"] += 1
            sp.set_metadata(proposed=self.spec_prop_total - prop0,
                            accepted=self.spec_accept_total - acc0)

    def _finish_stats(self, occ: dict) -> dict:
        prop = occ.get("spec_prop", 0)
        acc = occ.get("spec_accept", 0)
        return {
            "spec_rounds": occ.get("spec_rounds", 0),
            "spec_tokens": occ.get("spec_commit", 0),
            # 0 usable proposals (e.g. max_new == 1: every round has rem == 1)
            # is vacuously perfect — same convention as ``accept_rate``
            "accept_rate": acc / prop if prop else 1.0,
        }
