"""Attention: GQA/MQA/MHA with qk-norm, RoPE, causal / sliding-window / cross
modes, dispatched kernels for training/prefill, and KV caches for decode.

Kernel routing (see DESIGN.md §6): the training / encoder / prefill paths —
canonical ``arange`` positions, no cache reads — go through
``repro.kernels.ops.flash_attention`` (Pallas flash kernel or its jnp
online-softmax twin per ``cfg.kernels``, differentiable via ``custom_vjp``).
The cache-dependent paths (decode over ring buffers / sequence-sharded
caches, flash-decode stats combine) keep the positions-aware
:func:`blockwise_attention` below.

Tensor parallelism: q heads are sharded over the model axis (when divisible —
see ``ShardCtx.heads_tp``); K/V projections are small (num_kv_heads × head_dim)
and are REPLICATED across model shards, which is the standard GQA-under-TP
choice: attention itself then needs no collective, only the output projection
psum (row-parallel).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ops as kernel_ops
from repro.models.common import param, truncated_normal
from repro.models.layers import apply_rope
from repro.parallel.sharding import ShardCtx

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def init_attention(key, cfg, *, cross: bool = False) -> dict:
    d, h, kv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    std = 1.0 / math.sqrt(d)
    p = {
        "w_q": param(truncated_normal(ks[0], (d, h, hd), std, dt), "fsdp", "tp", None),
        "w_k": param(truncated_normal(ks[1], (d, kv, hd), std, dt), "fsdp", None, None),
        "w_v": param(truncated_normal(ks[2], (d, kv, hd), std, dt), "fsdp", None, None),
        "w_o": param(
            truncated_normal(ks[3], (h, hd, d), 1.0 / math.sqrt(h * hd), dt),
            "tp",
            None,
            "fsdp",
        ),
    }
    if cfg.qk_norm:
        p["q_norm"] = param(jnp.ones((hd,), jnp.float32), None)
        p["k_norm"] = param(jnp.ones((hd,), jnp.float32), None)
    return p


def _rms(x, scale, eps=1e-6):
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention in pure jnp — O(S) memory
# ---------------------------------------------------------------------------


def _mask_block(mode, q_pos, kv_pos, window):
    """(Bq, Bk) additive mask block from absolute positions.

    Negative kv positions mark padding / not-yet-written cache slots and are
    NEVER valid (a plain ``kp <= qp`` would let −1e9 sentinels through as
    zero-logit keys and pollute the softmax denominator)."""
    qp = q_pos[:, None]
    kp = kv_pos[None, :]
    alive = kp >= 0
    if mode == "full":
        valid = alive & jnp.ones(qp.shape[:1] + kp.shape[1:], bool)
    elif mode == "causal":
        valid = alive & (kp <= qp)
    elif mode == "local":
        valid = alive & (kp <= qp) & (kp > qp - window)
    else:  # pragma: no cover
        raise ValueError(mode)
    return jnp.where(valid, 0.0, NEG_INF)


@partial(jax.jit, static_argnames=("mode", "window", "block_kv", "return_stats", "unroll"))
def blockwise_attention(
    q: jax.Array,        # (B, Sq, H, D)
    k: jax.Array,        # (B, Sk, H, D)  — kv heads already expanded to H
    v: jax.Array,        # (B, Sk, H, D)
    q_positions: jax.Array,   # (Sq,) absolute positions
    kv_positions: jax.Array,  # (Sk,)
    *,
    mode: str = "causal",
    window: int = 0,
    block_kv: int = 1024,
    return_stats: bool = False,
    unroll: bool = False,
) -> jax.Array | tuple[jax.Array, jax.Array, jax.Array]:
    """Online-softmax attention scanned over KV blocks — the positions-aware
    variant used by the decode/cache paths.  kernels/ref.jnp_flash_attention
    is the grouped canonical-positions twin of this same m/l/acc recurrence;
    a fix to the numerics here (sentinels, l==0 guard, corr rescale) must be
    mirrored there.

    With ``return_stats`` the UNNORMALIZED accumulator and the (m, l) softmax
    stats are returned — used by the sequence-sharded ("flash-decode") cache
    path to combine partial attention across model shards with a psum."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    q32 = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)  # (B,H,Sq,D)

    nblk = max(1, math.ceil(sk / block_kv))
    pad = nblk * block_kv - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad), constant_values=-(10**9))
    kb = k.reshape(b, nblk, block_kv, h, d).transpose(1, 0, 3, 2, 4)  # (n,B,H,Bk,D)
    vb = v.reshape(b, nblk, block_kv, h, d).transpose(1, 0, 3, 2, 4)
    pb = kv_positions.reshape(nblk, block_kv)

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, kpos = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, kblk.astype(jnp.float32))
        s = s + _mask_block(mode, q_positions, kpos, window)[None, None]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vblk.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((b, h, sq), NEG_INF, jnp.float32),
        jnp.zeros((b, h, sq), jnp.float32),
        jnp.zeros((b, h, sq, d), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, (kb, vb, pb), unroll=unroll)
    if return_stats:
        return acc, m, l  # (B,H,Sq,D), (B,H,Sq), (B,H,Sq)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B,Sq,H,D)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class AttnCache:
    """Decode cache. For "global" layers ``k/v`` hold the full context
    (B, S_max, KV, D); for "local" layers they are a ring buffer of size
    (B, window, KV, D) written at ``index % window``."""

    k: jax.Array
    v: jax.Array
    index: jax.Array  # scalar int32: number of tokens already cached

    @staticmethod
    def init(cfg, batch: int, length: int, mode: str) -> "AttnCache":
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        size = min(length, cfg.sliding_window) if mode == "local" else length
        dt = jnp.dtype(cfg.dtype)
        return AttnCache(
            k=jnp.zeros((batch, size, kv, hd), dt),
            v=jnp.zeros((batch, size, kv, hd), dt),
            index=jnp.zeros((), jnp.int32),
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedAttnCache:
    """Serving KV cache: a pool of fixed-size pages shared by all request
    slots, addressed through the per-slot block tables in :class:`PagedView`.

    ``k_pages``/``v_pages`` are (num_pages + 1, KV, page_size, D) — kv-head
    major, so one kv head's page is a contiguous (page_size, D) tile; the LAST
    page is the TRASH page — decode steps of inactive slots redirect their
    masked writes there, so one fully-batched scatter serves every slot
    without conditionals and without corrupting live pages.  Trash contents
    are never read: the positional mask (key pos <= slot pos) rejects any
    page entry past a request's context, and inactive slots' outputs are
    discarded by the engine."""

    k_pages: jax.Array
    v_pages: jax.Array

    @staticmethod
    def init(cfg, num_pages: int, page_size: int) -> "PagedAttnCache":
        kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        dt = jnp.dtype(cfg.dtype)
        return PagedAttnCache(
            k_pages=jnp.zeros((num_pages + 1, kv, page_size, hd), dt),
            v_pages=jnp.zeros((num_pages + 1, kv, page_size, hd), dt),
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedView:
    """Per-step view of the paged cache, shared by every attention layer
    (block tables are layer-independent: all layers of one request use the
    same logical→physical page mapping, each layer owning its own pools).

    ``block_tables`` (R, MB) int32 — physical page id of each slot's logical
    block (rows beyond a request's allocation may hold stale ids; positional
    masking makes them unreachable).  ``positions`` (R,) int32 — index of the
    token being processed this step.  ``active`` (R,) bool — slots currently
    owning a request; inactive slots write to the trash page."""

    block_tables: jax.Array
    positions: jax.Array
    active: jax.Array


def _expand_kv(x: jax.Array, head_map: jax.Array) -> jax.Array:
    """Gather the kv head per (local) q head: (B,S,KV,D) -> (B,S,Hl,D)."""
    return jnp.take(x, head_map, axis=2)


def _dispatched_attention(
    q: jax.Array,   # (B, Sq, H_local, D)
    k: jax.Array,   # (B, Sk, KV, D) — FULL (replicated) kv heads
    v: jax.Array,   # (B, Sk, KV, D)
    cfg,
    ctx: ShardCtx,
    tp_h: int,
    *,
    mode: str,
    window: int,
) -> jax.Array:
    """Training / encoder / prefill attention through the kernel-dispatch
    layer (:func:`repro.kernels.ops.flash_attention` — Pallas or jnp twin per
    ``cfg.kernels``, canonical arange positions).

    When whole GQA groups are shard-local, the kv heads serving this shard's
    query heads are sliced out so K/V stay at kv-head width all the way into
    the kernel; with partial groups per shard (rare) K/V are gathered to
    local-head width first.
    """
    h, kv = cfg.num_heads, cfg.num_kv_heads
    h_local = q.shape[2]
    g = h // kv if kv and h % kv == 0 else 0
    if g and h_local % g == 0:
        if tp_h > 1:
            kv_local = h_local // g
            start = ctx.model_index() * kv_local
            k = jax.lax.dynamic_slice_in_dim(k, start, kv_local, 2)
            v = jax.lax.dynamic_slice_in_dim(v, start, kv_local, 2)
    else:
        shard = ctx.model_index() if tp_h > 1 else jnp.zeros((), jnp.int32)
        global_heads = shard * h_local + jnp.arange(h_local)
        head_map = (global_heads * kv) // h
        k = _expand_kv(k, head_map)
        v = _expand_kv(v, head_map)
    return kernel_ops.flash_attention(
        q, k, v, mode=mode, window=window, unroll=cfg.unroll_scans,
        config=cfg.kernels,
    )


def build_cross_cache(p: dict, cfg, encoder_out: jax.Array, ctx: ShardCtx) -> AttnCache:
    """Precompute encoder K/V once for cross-attention decode (whisper)."""
    w_k = ctx.gather_param(p["w_k"], axis=0)
    w_v = ctx.gather_param(p["w_v"], axis=0)
    k = jnp.einsum("bsd,dhk->bshk", encoder_out, w_k)
    v = jnp.einsum("bsd,dhk->bshk", encoder_out, w_v)
    if cfg.qk_norm:
        k = _rms(k, p["k_norm"])
    return AttnCache(k=k, v=v, index=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# Full attention block (projections + attention + out-proj)
# ---------------------------------------------------------------------------


def apply_attention(
    p: dict,
    cfg,
    x: jax.Array,             # (B, S, d)
    ctx: ShardCtx,
    *,
    mode: str = "causal",     # causal | local | full (cross / encoder self)
    positions: jax.Array | None = None,  # (S,) absolute positions of x
    kv_source: jax.Array | None = None,  # cross-attention encoder states
    cache: AttnCache | None = None,      # prefill (S>1) or decode (S==1)
    paged: PagedView | None = None,      # serving view (with PagedAttnCache)
    decode: bool = False,                # paged phase selector
    chunk_lengths: jax.Array | None = None,  # (R,) valid tokens per chunk row
    chunk_exact: bool = False,           # per-token decode-bitwise attention
) -> tuple[jax.Array, AttnCache | None]:
    """Attention block: projections + (cached) attention + output projection.

    Positions contract: the NO-CACHE and PREFILL paths assume CANONICAL
    positions (``positions[i] == i``) — they route through the dispatched
    kernel, whose causal/sliding masks are derived from row indices, while
    ``positions`` still drives RoPE.  Every current caller satisfies this
    (training, encoder, prefill all pass ``arange``); a future caller with
    offset/packed positions must use :func:`blockwise_attention` (which
    honors arbitrary position vectors) like the cache paths below do.

    Cache semantics:
      * ``cache is None``          — training / encoder forward.
      * ``cache`` and S > 1        — PREFILL: attention over the fresh K/V,
                                     then K/V written into the cache
                                     (sequence-sharded when ctx.kv_shard_seq).
      * ``cache`` and S == 1       — DECODE: append one token, attend over
                                     cache (flash-decode psum combine when the
                                     cache is sequence-sharded).
    """
    b, s, _ = x.shape
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    tp_h = ctx.heads_tp(h)
    h_local = h // tp_h

    w_q = ctx.gather_param(p["w_q"], axis=0)
    w_o = ctx.gather_param(p["w_o"], axis=2)

    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)

    q = jnp.einsum("bsd,dhk->bshk", x, w_q)  # h is LOCAL when sharded
    if cfg.qk_norm:
        q = _rms(q, p["q_norm"])
    if cfg.use_rope and mode != "full":
        q = apply_rope(q, positions, cfg.rope_theta)

    # K/V of the *new* tokens.  For cross-attention with a cache the encoder
    # K/V were precomputed by build_cross_cache — skip the projections.
    reuse_cross = mode == "full" and cache is not None
    if not reuse_cross:
        w_k = ctx.gather_param(p["w_k"], axis=0)
        w_v = ctx.gather_param(p["w_v"], axis=0)
        kv_in = kv_source if kv_source is not None else x
        k = jnp.einsum("bsd,dhk->bshk", kv_in, w_k)  # kv heads replicated
        v = jnp.einsum("bsd,dhk->bshk", kv_in, w_v)
        if cfg.qk_norm:
            k = _rms(k, p["k_norm"])
        if cfg.use_rope and mode != "full":
            k = apply_rope(k, positions, cfg.rope_theta)

    shard = ctx.model_index() if tp_h > 1 else jnp.zeros((), jnp.int32)
    global_heads = shard * h_local + jnp.arange(h_local)
    head_map = (global_heads * kv) // h

    # =====================================================================
    # PAGED serving cache: page-pool scatter + block-table attention
    # =====================================================================
    if isinstance(cache, PagedAttnCache):
        if paged is None:
            raise ValueError("PagedAttnCache requires a PagedView")
        if tp_h > 1:
            raise NotImplementedError(
                "paged serving assumes unsharded attention heads (tp=1)"
            )
        window = cfg.sliding_window or 0
        trash = cache.k_pages.shape[0] - 1
        page_size = cache.k_pages.shape[2]
        mb = paged.block_tables.shape[1]
        if not decode and chunk_lengths is not None:
            # CHUNKED PREFILL / SPEC VERIFY: R slots × C tokens.  Token
            # (r, c) sits at absolute position paged.positions[r] + c and is
            # real iff c < chunk_lengths[r] on an active slot — ragged tails
            # and idle slots scatter to the trash page, and their output rows
            # are garbage the engine discards.
            base = paged.positions
            c_idx = jnp.arange(s, dtype=jnp.int32)[None, :]
            tok_pos = base[:, None] + c_idx                        # (R, C)
            valid = (c_idx < chunk_lengths[:, None]) & paged.active[:, None]
            blk = jnp.clip(tok_pos // page_size, 0, mb - 1)
            pages_idx = jnp.take_along_axis(paged.block_tables, blk, axis=1)
            pages_idx = jnp.where(valid, pages_idx, trash)         # (R, C)
            offs = tok_pos % page_size
            kp = cache.k_pages.at[pages_idx, :, offs].set(k)
            vp = cache.v_pages.at[pages_idx, :, offs].set(v)
            if chunk_exact:
                # Speculative verify: scan single-token paged attention over
                # the chunk so row c is BITWISE the decode step at base + c —
                # this is what makes accepted proposals exactly the tokens
                # non-speculative decode would have produced.
                def step(_, qc_pos):
                    qc, posc = qc_pos
                    out_c = kernel_ops.paged_attention(
                        qc, kp, vp, paged.block_tables, posc,
                        mode=mode, window=window, config=cfg.kernels,
                    )
                    return None, out_c

                _, out = jax.lax.scan(
                    step, None, (q.transpose(1, 0, 2, 3), tok_pos.T)
                )
                out = out.transpose(1, 0, 2, 3)
            else:
                out = kernel_ops.paged_chunk_attention(
                    q, kp, vp, paged.block_tables, base,
                    mode=mode, window=window, config=cfg.kernels,
                )
            return _out_proj(out, w_o, ctx, tp_h), PagedAttnCache(kp, vp)
        if not decode:
            # PREFILL (B == 1, canonical positions): attention over the fresh
            # K/V exactly like the dense prefill, then every prompt token's
            # K/V scattered into the slot's pages.
            out = _dispatched_attention(
                q, k, v, cfg, ctx, tp_h, mode=mode, window=window,
            )
            tok = jnp.arange(s, dtype=jnp.int32)
            pages_idx = paged.block_tables[0, tok // page_size]
            offs = tok % page_size
            kp = cache.k_pages.at[pages_idx, :, offs].set(k[0])
            vp = cache.v_pages.at[pages_idx, :, offs].set(v[0])
            return _out_proj(out, w_o, ctx, tp_h), PagedAttnCache(kp, vp)
        # DECODE: one token per slot — masked page scatter (inactive slots
        # redirect to the trash page) + the dispatched paged-attention kernel.
        pos = paged.positions
        blk = jnp.clip(pos // page_size, 0, mb - 1)
        pages_idx = jnp.take_along_axis(paged.block_tables, blk[:, None], axis=1)[:, 0]
        pages_idx = jnp.where(paged.active, pages_idx, trash)
        offs = pos % page_size
        kp = cache.k_pages.at[pages_idx, :, offs].set(k[:, 0])
        vp = cache.v_pages.at[pages_idx, :, offs].set(v[:, 0])
        out = kernel_ops.paged_attention(
            q[:, 0], kp, vp, paged.block_tables, pos,
            mode=mode, window=window, config=cfg.kernels,
        )[:, None]
        return _out_proj(out, w_o, ctx, tp_h), PagedAttnCache(kp, vp)

    # =====================================================================
    # No cache: plain (training / encoder) attention — dispatched kernels
    # =====================================================================
    if cache is None:
        out = _dispatched_attention(
            q, k, v, cfg, ctx, tp_h, mode=mode, window=cfg.sliding_window or 0,
        )
        return _out_proj(out, w_o, ctx, tp_h), None

    # =====================================================================
    # Cross-attention decode: read-only precomputed encoder K/V
    # =====================================================================
    if reuse_cross:
        ck, cv = cache.k, cache.v
        kv_positions = jnp.arange(ck.shape[1], dtype=jnp.int32)
        out = blockwise_attention(
            q, _expand_kv(ck, head_map), _expand_kv(cv, head_map),
            positions, kv_positions, mode="full", unroll=cfg.unroll_scans,
        )
        return _out_proj(out, w_o, ctx, tp_h), cache

    # =====================================================================
    # PREFILL: attend over fresh K/V (dispatched kernels), then fill the cache
    # =====================================================================
    if s > 1:
        out = _dispatched_attention(
            q, k, v, cfg, ctx, tp_h, mode=mode, window=cfg.sliding_window or 0,
        )
        size_local = cache.k.shape[1]
        if ctx.kv_shard_seq and ctx.tp > 1 and mode == "causal":
            start = ctx.model_index() * size_local
            ck = jax.lax.dynamic_slice(k, (0, start, 0, 0), (b, size_local, kv, hd))
            cv = jax.lax.dynamic_slice(v, (0, start, 0, 0), (b, size_local, kv, hd))
        elif mode == "local" and s >= size_local:
            # keep the LAST `window` tokens in ring order (slot = pos % size)
            take = s - size_local
            ck_lin = jax.lax.dynamic_slice_in_dim(k, take, size_local, 1)
            cv_lin = jax.lax.dynamic_slice_in_dim(v, take, size_local, 1)
            # positions of these tokens are [s-size_local, s); slot = pos % size
            roll = -(take % size_local)
            ck = jnp.roll(ck_lin, roll, axis=1)
            cv = jnp.roll(cv_lin, roll, axis=1)
        else:
            ck = jax.lax.dynamic_update_slice(cache.k, k, (0, 0, 0, 0))
            cv = jax.lax.dynamic_update_slice(cache.v, v, (0, 0, 0, 0))
        new_cache = AttnCache(k=ck, v=cv, index=jnp.full((), s, jnp.int32))
        return _out_proj(out, w_o, ctx, tp_h), new_cache

    # =====================================================================
    # DECODE (S == 1)
    # =====================================================================
    size_local = cache.k.shape[1]

    if ctx.kv_shard_seq and ctx.tp > 1 and mode == "causal":
        # sequence-sharded cache: masked owner write + psum softmax combine
        start = ctx.model_index() * size_local
        local_idx = cache.index - start
        in_range = (local_idx >= 0) & (local_idx < size_local)
        safe = jnp.clip(local_idx, 0, size_local - 1)
        ck = jax.lax.dynamic_update_slice(cache.k, k, (0, safe, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache.v, v, (0, safe, 0, 0))
        ck = jnp.where(in_range, ck, cache.k)
        cv = jnp.where(in_range, cv, cache.v)
        new_cache = AttnCache(k=ck, v=cv, index=cache.index + 1)
        kv_positions = start + jnp.arange(size_local, dtype=jnp.int32)
        kv_positions = jnp.where(kv_positions <= cache.index, kv_positions, -(10**9))
        acc, m, l = blockwise_attention(
            q, _expand_kv(ck, head_map), _expand_kv(cv, head_map),
            positions, kv_positions, mode="causal", return_stats=True,
            unroll=cfg.unroll_scans,
        )
        gm = ctx.pmax_model(m)
        corr = jnp.exp(m - gm)
        l = ctx.psum_model(l * corr)
        acc = ctx.psum_model(acc * corr[..., None])
        out = (acc / jnp.maximum(l[..., None], 1e-30)).transpose(0, 2, 1, 3).astype(q.dtype)
        return jnp.einsum("bshk,hkd->bsd", out, w_o), new_cache  # complete, replicated

    if mode == "local":
        slot = cache.index % size_local
        ck = jax.lax.dynamic_update_slice(cache.k, k, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache.v, v, (0, slot, 0, 0))
        slots = jnp.arange(size_local, dtype=jnp.int32)
        age = (slot - slots) % size_local
        kv_positions = cache.index - age
        valid = kv_positions >= jnp.maximum(cache.index - size_local + 1, 0)
        kv_positions = jnp.where(valid, kv_positions, -(10**9))
    else:  # causal, unsharded cache
        ck = jax.lax.dynamic_update_slice(cache.k, k, (0, cache.index, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache.v, v, (0, cache.index, 0, 0))
        kv_positions = jnp.arange(size_local, dtype=jnp.int32)
        kv_positions = jnp.where(kv_positions <= cache.index, kv_positions, -(10**9))
    new_cache = AttnCache(k=ck, v=cv, index=cache.index + 1)
    out = blockwise_attention(
        q, _expand_kv(ck, head_map), _expand_kv(cv, head_map),
        positions, kv_positions,
        mode=mode, window=cfg.sliding_window or 0,
        unroll=cfg.unroll_scans,
    )
    return _out_proj(out, w_o, ctx, tp_h), new_cache


def _out_proj(out: jax.Array, w_o: jax.Array, ctx: ShardCtx, tp_h: int) -> jax.Array:
    """Row-parallel output projection; psum (or reduce-scatter) when q heads
    are sharded, plain matmul when attention is replicated."""
    y = jnp.einsum("bshk,hkd->bsd", out, w_o)
    if tp_h > 1:
        y = ctx.scatter_seq_sum(y, axis=1)
    return y
