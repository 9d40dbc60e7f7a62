"""Continuous-batching inference engine over the paged KV cache.

Scheduler state machine (one host loop around one jitted decode program):

    QUEUED ──admit──► PREFILL ──chunks──► DECODING ──evict──► FINISHED
                 ▲    (interleaved         │
                 │     with decode)        │
                 └──────── pages freed ◄───┘

Each :meth:`ServeEngine.step`:
  1. EVICT — slots whose request hit its token budget are read out (the ONE
     host sync a request ever costs), their pages returned to the allocator.
  2. ADMIT — while a slot and enough pages are free, the next queued request
     claims the slot and RESERVES pages for prompt+max_new up front (lease —
     committed when prefill completes), so a running request can never OOM
     mid-decode.  ``policy="static"`` instead admits only into an all-idle
     engine — classic static batching, kept as the measured baseline.
  3. PREFILL (chunked) — admitted prompts advance ``prefill_chunk`` tokens
     per call through ONE fixed-shape jitted chunk program (ragged last
     chunk masked positionally; RG-LRU/SSD states carried exactly across
     chunk boundaries), at most ``prefill_budget`` tokens per tick so long
     prompts INTERLEAVE with decode instead of stalling the batch.  With
     ``prefill_chunk=0`` the PR-7 single-shot path (batch-1, exact prompt
     length, retraces per distinct length) is kept as the measured baseline.
  4. DECODE — one fused, donated, jitted step advances ALL active slots:
     per-slot positions drive RoPE + the paged-attention mask, per-slot
     temperatures drive gumbel sampling, sampled tokens land in an on-device
     output buffer.  Nothing crosses the host boundary per token; streaming
     consumers get tokens from the eviction-wave device_get plus an optional
     periodic drain (see :meth:`ServeEngine.drain`).

Inactive slots ride along (their writes hit the trash page, their recurrent
states are overwritten at admission) — the decode program never retraces as
requests come and go.

Exactness: with attention/recurrent mixers every slot's row is computed
independently, and sampling noise is keyed by (request id, output index)
rather than engine step, so a request decoded in a churning batch produces
bitwise the tokens of a solo run — greedy or sampled (tested end-to-end).
Chunked prefill preserves this: the chunk decomposition of a prompt depends
only on the prompt length, never on batch occupancy.  MoE blocks break it
(capacity is batch-global); they serve fine but without the guarantee.
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
from repro.serve.paged import BlockAllocator

__all__ = ["Request", "FinishedRequest", "ServeConfig", "EngineState", "ServeEngine"]

# Root of every sampling stream; token i of request rid draws its gumbel
# noise from fold_in(fold_in(_SAMPLE_KEY, rid), i).
_SAMPLE_KEY = jax.random.PRNGKey(17)


def _sample_keys(rids: jax.Array, indices: jax.Array) -> jax.Array:
    """Per-slot sampling keys: token ``indices[r]`` of request ``rids[r]``."""
    return jax.vmap(
        lambda rid, i: jax.random.fold_in(jax.random.fold_in(_SAMPLE_KEY, rid), i)
    )(rids, indices)


def _decode_core(cfg: ModelConfig, ctx: ShardCtx, params, state: "EngineState") -> "EngineState":
    """One batched decode step as a pure function — jitted by
    :func:`_programs`, and scanned by serve/spec.py as the draft proposer
    (which is what keeps draft proposals bitwise-identical to the draft
    engine decoding on its own)."""
    view = PagedView(state.block_tables, state.positions, state.active)
    logits, caches = M.paged_decode_step(
        params, cfg, state.tokens[:, None], state.caches, view, ctx
    )
    logits = logits[:, 0]                                   # (R, V)
    # temperature-t categorical == argmax(logits + t·gumbel); t=0 greedy.
    # Noise is keyed by (request id, output index), NOT engine step — a
    # request draws the same sample stream wherever the scheduler puts it,
    # which is what makes batched sampling match a solo run exactly.
    keys = _sample_keys(state.rids, state.out_len)
    g = jax.vmap(lambda k: jax.random.gumbel(k, logits.shape[-1:], jnp.float32))(keys)
    nxt = jnp.argmax(logits + state.temps[:, None] * g, axis=-1).astype(jnp.int32)
    row = jnp.arange(state.out_buf.shape[0])
    idx = jnp.clip(state.out_len, 0, state.out_buf.shape[1] - 1)
    keep = state.out_buf[row, idx]
    out_buf = state.out_buf.at[row, idx].set(jnp.where(state.active, nxt, keep))
    act = state.active.astype(jnp.int32)
    return EngineState(
        caches=caches,
        block_tables=state.block_tables,
        tokens=jnp.where(state.active, nxt, state.tokens),
        positions=state.positions + act,
        active=state.active,
        temps=state.temps,
        rids=state.rids,
        out_buf=out_buf,
        out_len=state.out_len + act,
        budgets=state.budgets,
    )


@functools.lru_cache(maxsize=None)
def _programs(cfg: ModelConfig):
    """Jitted decode/prefill programs for one model config, shared by every
    engine serving it (ModelConfig is frozen/hashable) — a fresh engine, e.g.
    a solo-verification run or a router replica, reuses the already-compiled
    programs."""
    ctx = ShardCtx.local()
    decode = jax.jit(functools.partial(_decode_core, cfg, ctx), donate_argnums=(1,))

    def prefill_impl(params, tokens, caches, table_row, temp, key):
        view = PagedView(
            table_row[None],
            jnp.zeros((1,), jnp.int32),
            jnp.ones((1,), bool),
        )
        logits, new_caches = M.paged_prefill(params, cfg, tokens[None], caches, view, ctx)
        g = jax.random.gumbel(key, logits[0, 0].shape, jnp.float32)
        tok0 = jnp.argmax(logits[0, 0] + temp * g).astype(jnp.int32)
        return tok0, new_caches

    # one jitted callable; retraces per distinct prompt LENGTH only (exact
    # lengths — lengths are few under bucketed real workloads)
    prefill = jax.jit(prefill_impl, donate_argnums=(2,))
    return decode, prefill


@functools.lru_cache(maxsize=None)
def _chunk_program(cfg: ModelConfig, chunk: int):
    """ONE jitted chunk-prefill program per (model, chunk size) — this is
    what replaces the per-prompt-length compile zoo.  Batch-1: the engine
    walks one slot's prompt through it chunk by chunk, carrying recurrent
    states in the caches and bumping ``base``; the ragged last chunk rides
    the positional mask.  The sampled ``tok0`` is only meaningful on the
    final chunk (logits are taken at the last VALID position)."""
    ctx = ShardCtx.local()

    def chunk_impl(params, tokens, length, caches, table_row, base, temp, key):
        view = PagedView(table_row[None], base[None], jnp.ones((1,), bool))
        logits, new_caches = M.paged_prefill_chunk(
            params, cfg, tokens[None], caches, view, ctx, lengths=length[None]
        )
        g = jax.random.gumbel(key, logits[0, 0].shape, jnp.float32)
        tok0 = jnp.argmax(logits[0, 0] + temp * g).astype(jnp.int32)
        return tok0, new_caches

    return jax.jit(chunk_impl, donate_argnums=(3,))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    temperature: float = 0.0
    submit_t: float = 0.0


@dataclasses.dataclass
class FinishedRequest:
    rid: int
    prompt: list[int]
    tokens: list[int]
    submit_t: float
    admit_t: float       # prefill completed = first token exists
    finish_t: float
    stats: dict = dataclasses.field(default_factory=dict)  # e.g. spec accept rate

    @property
    def ttft_s(self) -> float:
        return self.admit_t - self.submit_t


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_slots: int = 4          # R: concurrent requests in the decode batch
    num_pages: int = 128        # KV page pool size (per layer), excl. trash
    page_size: int = 16         # tokens per page
    max_new_cap: int = 128      # on-device output buffer width
    policy: str = "continuous"  # "continuous" | "static" (baseline)
    sync_each_step: bool = False  # block per decode step (per-token timing)
    prefill_chunk: int = 32     # chunked-prefill width; 0 = single-shot (PR-7)
    prefill_budget: int = 0     # max prefill tokens per tick; 0 = unlimited

    def validate(self) -> None:
        if self.policy not in ("continuous", "static"):
            raise ValueError(f"unknown policy {self.policy!r}")
        if self.max_slots < 1:
            raise ValueError("need at least one slot")
        if self.prefill_chunk < 0 or self.prefill_budget < 0:
            raise ValueError("prefill_chunk/prefill_budget must be >= 0")
        if self.prefill_budget and not self.prefill_chunk:
            raise ValueError("prefill_budget requires chunked prefill")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EngineState:
    """Everything the jitted decode step touches — donated through it."""

    caches: Any               # paged attn pools + per-slot recurrent states
    block_tables: jax.Array   # (R, MB) int32
    tokens: jax.Array         # (R,) int32 — token being fed this step
    positions: jax.Array      # (R,) int32 — its position
    active: jax.Array         # (R,) bool
    temps: jax.Array          # (R,) f32 — 0 = greedy
    rids: jax.Array           # (R,) int32 — request id (seeds its gumbel noise)
    out_buf: jax.Array        # (R, CAP) int32 — generated tokens, on device
    out_len: jax.Array        # (R,) int32
    budgets: jax.Array        # (R,) int32 — max_new per slot (spec clamps on it)


class ServeEngine:
    """Request-driven serving engine for one decoder-only model."""

    def __init__(self, params: Any, cfg: ModelConfig, scfg: ServeConfig):
        scfg.validate()
        self.params = params
        self.cfg = cfg
        self.scfg = scfg
        self.ctx = ShardCtx.local()
        self.alloc = BlockAllocator(scfg.num_pages, scfg.page_size)
        r, mb = scfg.max_slots, scfg.num_pages
        self._mb = mb
        caches = M.init_paged_cache_tree(cfg, r, scfg.num_pages, scfg.page_size)
        self.state = EngineState(
            caches=caches,
            block_tables=jnp.full((r, mb), self.alloc.trash_page, jnp.int32),
            tokens=jnp.zeros((r,), jnp.int32),
            positions=jnp.zeros((r,), jnp.int32),
            active=jnp.zeros((r,), bool),
            temps=jnp.zeros((r,), jnp.float32),
            rids=jnp.zeros((r,), jnp.int32),
            out_buf=jnp.zeros((r, scfg.max_new_cap), jnp.int32),
            out_len=jnp.zeros((r,), jnp.int32),
            budgets=jnp.zeros((r,), jnp.int32),
        )
        self.queue: list[Request] = []
        # host mirror of per-slot occupancy: request, lease/blocks, phase
        # ("prefill" | "decode"), prefill cursor + carried recurrent scratch,
        # admit_t, steps, per-token dispatch times, streamed-token watermark
        self._slots: list[dict | None] = [None] * r
        self._decode_fn, self._prefill_fn = _programs(cfg)
        self._chunk_fn = (
            _chunk_program(cfg, scfg.prefill_chunk) if scfg.prefill_chunk else None
        )
        self._token_cb = None
        self.decode_steps = 0
        self.decode_step_times: list[float] = []

    # -- prefill cache surgery ---------------------------------------------

    def _entry_scratch(self, entry, stacked: bool, prev=None):
        """Prefill view of one layer-group cache entry: shared page pools
        pass through, per-slot recurrent state becomes batch-1 zeros — or the
        batch-1 state CARRIED from the previous chunk of the same prompt."""
        mixer, cross = entry
        if isinstance(mixer, PagedAttnCache):
            return (mixer, cross)
        if prev is not None:
            return prev
        ax = 1 if stacked else 0
        scratch = jax.tree.map(
            lambda x: jnp.zeros(x.shape[:ax] + (1,) + x.shape[ax + 1:], x.dtype),
            mixer,
        )
        return (scratch, cross)

    def _entry_merge(self, old, new, stacked: bool, slot: int):
        mixer_o, _ = old
        mixer_n, cross = new
        if isinstance(mixer_o, PagedAttnCache):
            return (mixer_n, cross)  # pages were written in place
        if stacked:
            merged = jax.tree.map(
                lambda o, n: o.at[:, slot].set(n[:, 0]), mixer_o, mixer_n
            )
        else:
            merged = jax.tree.map(lambda o, n: o.at[slot].set(n[0]), mixer_o, mixer_n)
        return (merged, cross)

    def _prefill_caches(self, caches, rec=None):
        def at(d, kind, i):
            return None if d is None else d[kind][i]

        return {
            "scan": [
                self._entry_scratch(e, True, at(rec, "scan", i))
                if e is not None else None
                for i, e in enumerate(caches["scan"])
            ],
            "rem": [
                self._entry_scratch(e, False, at(rec, "rem", i))
                for i, e in enumerate(caches["rem"])
            ],
        }

    def _extract_rec(self, new):
        """Batch-1 recurrent entries of a chunk's output caches, to be carried
        into the next chunk of the same prompt (page-pool entries drop to
        None — the written pools live in engine state, not per-slot)."""
        def pick(e):
            if e is None:
                return None
            mixer, cross = e
            return None if isinstance(mixer, PagedAttnCache) else (mixer, cross)

        return {
            "scan": [pick(e) for e in new["scan"]],
            "rem": [pick(e) for e in new["rem"]],
        }

    def _merge_pools(self, old, new):
        """Mid-prompt chunk merge: adopt the chunk program's page pools (the
        originals were DONATED into it, so engine state must take the written
        buffers), keep every slot's full-batch recurrent states untouched."""
        def pool(o, n):
            if o is None:
                return None
            mixer_o, cross = o
            mixer_n, _ = n
            return (mixer_n, cross) if isinstance(mixer_o, PagedAttnCache) else o

        return {
            "scan": [pool(o, n) for o, n in zip(old["scan"], new["scan"])],
            "rem": [pool(o, n) for o, n in zip(old["rem"], new["rem"])],
        }

    def _merge_caches(self, old, new, slot: int):
        return {
            "scan": [
                self._entry_merge(o, n, True, slot) if o is not None else None
                for o, n in zip(old["scan"], new["scan"])
            ],
            "rem": [
                self._entry_merge(o, n, False, slot)
                for o, n in zip(old["rem"], new["rem"])
            ],
        }

    # -- scheduler ----------------------------------------------------------

    def submit(self, req: Request) -> None:
        if req.max_new > self.scfg.max_new_cap:
            raise ValueError(
                f"request {req.rid}: max_new {req.max_new} exceeds engine cap "
                f"{self.scfg.max_new_cap}"
            )
        need = self.alloc.blocks_for(len(req.prompt) + req.max_new)
        if need > self.alloc.num_pages or need > self._mb:
            raise ValueError(
                f"request {req.rid} needs {need} pages; pool holds "
                f"{self.alloc.num_pages}"
            )
        if not req.submit_t:
            req.submit_t = time.perf_counter()
        self.queue.append(req)

    def _free_slots(self) -> list[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    def _finish_stats(self, occ: dict) -> dict:
        """Per-request stats attached at eviction; spec engines override."""
        return {}

    def _emit_tokens(self, slot: int, occ: dict, out_buf, upto: int) -> int:
        """Stream tokens [emitted, upto) of a slot to the token callback,
        stamped with their decode DISPATCH times (host times; exact when
        sync_each_step, otherwise early by the device queue depth).  Returns
        how many tokens it streamed."""
        if self._token_cb is None:
            return 0
        req: Request = occ["req"]
        upto = min(upto, req.max_new)
        first = occ["emitted"]
        for i in range(first, upto):
            t = occ["t_toks"][i] if i < len(occ["t_toks"]) else time.perf_counter()
            self._token_cb(req.rid, i, int(out_buf[slot, i]), t)
        occ["emitted"] = max(first, upto)
        return max(upto - first, 0)

    def drain(self) -> None:
        """Flush generated-but-unstreamed tokens to the token callback with
        ONE device_get for the whole batch — the periodic streaming path (the
        free path being the eviction-wave read in :meth:`_evict_finished`).
        Never called per token: decode stays sync-free."""
        with obs.span("serve.drain"):
            if self._token_cb is None:
                return
            pending = [
                (slot, occ) for slot, occ in enumerate(self._slots)
                if occ is not None and occ["phase"] == "decode"
                and occ["emitted"] < min(occ["steps"], occ["req"].max_new)
            ]
            if not pending:
                return
            with obs.span("serve.fetch"):
                out_buf = np.asarray(jax.device_get(self.state.out_buf))
            with obs.span("serve.emit") as sp:
                n = sum(
                    self._emit_tokens(slot, occ, out_buf, min(occ["steps"], occ["req"].max_new))
                    for slot, occ in pending
                )
                sp.set_metadata(tokens=n)

    def _evict_finished(self) -> list[FinishedRequest]:
        done: list[FinishedRequest] = []
        out_buf = None
        with obs.span("serve.evict") as sp:
            for slot, occ in enumerate(self._slots):
                if (
                    occ is None or occ["phase"] != "decode"
                    or occ["steps"] < occ["req"].max_new
                ):
                    continue
                if out_buf is None:  # one device_get serves every eviction this step
                    with obs.span("serve.fetch"):
                        out_buf = np.asarray(jax.device_get(self.state.out_buf))
                req: Request = occ["req"]
                toks = out_buf[slot, : req.max_new].tolist()
                self._emit_tokens(slot, occ, out_buf, req.max_new)
                done.append(
                    FinishedRequest(
                        rid=req.rid, prompt=req.prompt, tokens=toks,
                        submit_t=req.submit_t, admit_t=occ["admit_t"],
                        finish_t=time.perf_counter(),
                        stats=self._finish_stats(occ),
                    )
                )
                self.alloc.free(occ["blocks"])
                self._slots[slot] = None
                st = self.state
                self.state = dataclasses.replace(
                    st,
                    active=st.active.at[slot].set(False),
                    positions=st.positions.at[slot].set(0),
                    tokens=st.tokens.at[slot].set(0),
                    out_len=st.out_len.at[slot].set(0),
                )
            sp.set_metadata(evicted=len(done))
        return done

    def _admit(self) -> None:
        if self.scfg.policy == "static" and any(s is not None for s in self._slots):
            return  # static baseline: wait for the whole batch to drain
        free = self._free_slots()
        while self.queue and free:
            req = self.queue[0]
            need = self.alloc.blocks_for(len(req.prompt) + req.max_new)
            if not self.alloc.can_alloc(need):
                break  # head-of-line blocks until pages free up (no preempt)
            self.queue.pop(0)
            claim_t = time.perf_counter()
            with obs.span(
                "serve.admit_request", rid=req.rid,
                queued_ms=(claim_t - req.submit_t) * 1e3,
                prompt_tokens=len(req.prompt), pages=need,
            ):
                self._claim_slot(free.pop(0), req, need, claim_t)

    def _claim_slot(self, slot: int, req: Request, need: int, claim_t: float) -> None:
        """Give ``req`` the free ``slot`` and ``need`` pages; ``claim_t`` is
        when it left the queue."""
        if self._chunk_fn is not None:
            # chunked path: pages leave the free list under a lease
            # (committed when the last chunk lands), the slot parks in
            # "prefill" phase and _advance_prefills walks it forward
            lease = self.alloc.reserve(need)
            row = np.full((self._mb,), self.alloc.trash_page, np.int32)
            row[: len(lease.blocks)] = lease.blocks
            row_dev = jnp.asarray(row)
            st = self.state
            self.state = dataclasses.replace(
                st, block_tables=st.block_tables.at[slot].set(row_dev)
            )
            self._slots[slot] = {
                "req": req, "lease": lease, "row": row_dev,
                "phase": "prefill", "cursor": 0, "rec": None, "claim_t": claim_t,
                "admit_t": 0.0, "steps": 0, "t_toks": [], "emitted": 0,
            }
            return
        blocks = self.alloc.alloc(need)
        row = np.full((self._mb,), self.alloc.trash_page, np.int32)
        row[: len(blocks)] = blocks
        row_dev = jnp.asarray(row)

        st = self.state
        # scratch shares the page-pool buffers with st.caches; prefill
        # donates them and _merge keeps the returned (written) pools
        scratch = self._prefill_caches(st.caches)
        key = jax.random.fold_in(jax.random.fold_in(_SAMPLE_KEY, req.rid), 0)
        tok0, new_caches = self._prefill_fn(
            self.params,
            jnp.asarray(req.prompt, jnp.int32),
            scratch,
            row_dev,
            jnp.float32(req.temperature),
            key,
        )
        merged = self._merge_caches(st.caches, new_caches, slot)
        self.state = dataclasses.replace(
            st,
            caches=merged,
            block_tables=st.block_tables.at[slot].set(row_dev),
            tokens=st.tokens.at[slot].set(tok0),
            positions=st.positions.at[slot].set(len(req.prompt)),
            active=st.active.at[slot].set(True),
            temps=st.temps.at[slot].set(req.temperature),
            rids=st.rids.at[slot].set(req.rid),
            out_buf=st.out_buf.at[slot, 0].set(tok0),
            out_len=st.out_len.at[slot].set(1),
            budgets=st.budgets.at[slot].set(req.max_new),
        )
        now = time.perf_counter()
        self._slots[slot] = {
            "req": req, "blocks": blocks, "phase": "decode", "claim_t": claim_t,
            "admit_t": now, "steps": 1, "t_toks": [now], "emitted": 0,
        }

    def _prefill_chunk_step(self, slot: int) -> None:
        """Advance one prefill-phase slot by one fixed-width chunk through the
        shared jitted chunk program; on the last chunk, commit the lease and
        flip the slot into the decode batch."""
        occ = self._slots[slot]
        req: Request = occ["req"]
        c = self.scfg.prefill_chunk
        cur = occ["cursor"]
        n = min(c, len(req.prompt) - cur)
        toks = req.prompt[cur: cur + n] + [0] * (c - n)
        st = self.state
        # scratch aliases the engine's page pools (donated by the chunk
        # program) and carries the slot's batch-1 recurrent states
        scratch = self._prefill_caches(st.caches, occ["rec"])
        key = jax.random.fold_in(jax.random.fold_in(_SAMPLE_KEY, req.rid), 0)
        tok0, new_caches = self._chunk_fn(
            self.params,
            jnp.asarray(toks, jnp.int32),
            jnp.int32(n),
            scratch,
            occ["row"],
            jnp.int32(cur),
            jnp.float32(req.temperature),
            key,
        )
        occ["cursor"] = cur + n
        if occ["cursor"] < len(req.prompt):
            self.state = dataclasses.replace(
                st, caches=self._merge_pools(st.caches, new_caches)
            )
            occ["rec"] = self._extract_rec(new_caches)
            return
        blocks = self.alloc.commit(occ.pop("lease"))
        merged = self._merge_caches(st.caches, new_caches, slot)
        now = time.perf_counter()
        self.state = dataclasses.replace(
            st,
            caches=merged,
            tokens=st.tokens.at[slot].set(tok0),
            positions=st.positions.at[slot].set(len(req.prompt)),
            active=st.active.at[slot].set(True),
            temps=st.temps.at[slot].set(req.temperature),
            rids=st.rids.at[slot].set(req.rid),
            out_buf=st.out_buf.at[slot, 0].set(tok0),
            out_len=st.out_len.at[slot].set(1),
            budgets=st.budgets.at[slot].set(req.max_new),
        )
        occ.update(
            {"blocks": blocks, "phase": "decode", "rec": None,
             "admit_t": now, "steps": 1}
        )
        occ["t_toks"].append(now)

    def _advance_prefills(self) -> None:
        """Spend up to ``prefill_budget`` prompt tokens (0 = all pending) on
        chunk steps, round-robin over prefill-phase slots, so long prompts
        interleave with decode instead of stalling the running batch."""
        if self._chunk_fn is None:
            return
        budget = self.scfg.prefill_budget or (1 << 30)
        while budget > 0:
            pending = [
                s for s, occ in enumerate(self._slots)
                if occ is not None and occ["phase"] == "prefill"
            ]
            if not pending:
                return
            for slot in pending:
                if budget <= 0:
                    return
                occ = self._slots[slot]
                base = occ["cursor"]
                n = min(self.scfg.prefill_chunk, len(occ["req"].prompt) - base)
                with obs.span("serve.prefill_chunk", slot=slot, tokens=n, base=base):
                    self._prefill_chunk_step(slot)
                budget -= self.scfg.prefill_chunk

    def step(self) -> list[FinishedRequest]:
        """One scheduler tick: evict → admit → prefill chunks → fused decode."""
        with obs.span("serve.step", **self._tick_stats()):
            done = self._evict_finished()
            with obs.span("serve.admit"):
                self._admit()
            self._advance_prefills()
            if any(
                s is not None and s["phase"] == "decode"
                and s["steps"] < s["req"].max_new
                for s in self._slots
            ):
                self._decode()
        return done

    def _tick_stats(self) -> dict:
        """The ``serve.step`` span's stats: the queue, the free pages and
        the slots in decode at the start of the tick (only while traced)."""
        if not obs.enabled():
            return {}
        return {
            "queue_depth": len(self.queue),
            "free_pages": self.alloc.free_count,
            "decode_slots": sum(
                s is not None and s["phase"] == "decode" for s in self._slots
            ),
        }

    def _decode_stats(self) -> dict:
        """The slots the decode step advances and the cache positions it
        attends over in all (prompt plus tokens so far, per slot)."""
        if not obs.enabled():
            return {}
        live = [
            len(s["req"].prompt) + s["steps"] for s in self._slots
            if s is not None and s["phase"] == "decode"
        ]
        return {"active_slots": len(live), "live_tokens": sum(live)}

    def _decode(self) -> None:
        """One fused, donated decode step over every slot."""
        with obs.span("serve.decode", **self._decode_stats()):
            t0 = time.perf_counter()
            self.state = self._decode_fn(self.params, self.state)
            if self.scfg.sync_each_step:
                jax.block_until_ready(self.state.out_len)
            now = time.perf_counter()
            if self.scfg.sync_each_step:
                self.decode_step_times.append(now - t0)
            self.decode_steps += 1
            for occ in self._slots:
                if occ is not None and occ["phase"] == "decode":
                    if occ["steps"] < occ["req"].max_new:
                        occ["t_toks"].append(now)
                    occ["steps"] += 1

    @property
    def idle(self) -> bool:
        return not self.queue and all(s is None for s in self._slots)

    def run(
        self,
        requests: list[Request],
        token_cb=None,
        drain_every: int = 0,
    ) -> list[FinishedRequest]:
        """Serve a batch of requests to completion (submit-all load).

        ``token_cb(rid, index, token, dispatch_t)`` streams tokens as they
        reach the host: on each eviction wave (free — rides the existing
        device_get) and, if ``drain_every`` > 0, every that-many ticks via
        :meth:`drain`."""
        self._token_cb = token_cb
        for r in requests:
            self.submit(r)
        finished: list[FinishedRequest] = []
        guard = 0
        limit = (
            10_000
            + sum(r.max_new for r in requests) * 4
            + sum(len(r.prompt) for r in requests)
        )
        while not self.idle:
            finished.extend(self.step())
            guard += 1
            if drain_every and guard % drain_every == 0:
                self.drain()
            if guard > limit:  # pragma: no cover
                raise RuntimeError("serve loop failed to converge")
        finished.extend(self._evict_finished())
        return finished
