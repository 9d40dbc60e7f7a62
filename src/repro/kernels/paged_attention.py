"""Pallas TPU paged attention: decode (one query token per request slot) and
chunked prefill (a fixed-width chunk of query tokens per slot), with K/V
gathered from fixed-size pages through per-request block tables.

This is the serving twin of kernels/flash_attention.py: same online-softmax
recurrence, but the KV sequence is PHYSICALLY SCATTERED across a page pool
(NP, KV, BS, D) and addressed logically by ``block_tables (R, MB)``.  The
tables (plus per-request positions) ride in as SCALAR-PREFETCH operands
(``pltpu.PrefetchScalarGridSpec``), so each grid step's K/V page index is
known before the body runs and the DMA fetches exactly one page per step —
no dense gather of the whole context ever materializes.

Grid: (R, KV, MB) with the block dim innermost and "arbitrary" (sequential)
so the softmax state lives in VMEM scratch across page iterations.  GQA is
folded like the flash kernel: the G = H/KV query heads sharing a kv head form
the q row dim of a (G, D) tile, so K/V stay at kv-head width.

Masking is positional only: key j is valid iff ``j <= positions[r]`` (and
``j > positions[r] - window`` for sliding-window layers).  Pages past the
context, unallocated table entries (pointing anywhere) and the trash page are
all invalid by position, so garbage page contents never reach the softmax.
Fully-masked pages self-heal exactly as in the flash kernel: their p=1 rows
are wiped by corr=0 once a finite-max page arrives, and for causal decode
page 0 is always valid.

The pool is laid out kv-head-major so that one grid step's K/V tile is a
whole (BS, D) page of one kv head: the block's last two dims equal the
array's, which is what the TPU tiling rules accept for any BS and D.  The
softmax statistics m/l are (G, 1) columns for the same reason.

VMEM per program: q (G, D) + k/v (BS, D) + acc (G, D) f32 + m/l (G, 1)
≈ a few KiB for typical (G ≤ 8, BS ≤ 64, D ≤ 256) — paging keeps the decode
working set independent of context length.  Parity with
ref.jnp_paged_attention is tested in interpret mode; tests/test_chip_compile.py
compiles both kernels for the TPU v5e.

CHUNKED PREFILL (``pallas_paged_chunk_attention``) is the same kernel shape
with C query tokens per slot instead of one: query row c of slot r sits at
absolute position ``positions[r] + c`` and key j is valid iff
``j <= positions[r] + c``.  A RAGGED last chunk needs no extra machinery —
tokens past the slot's valid length were scattered to the trash page by the
caller, so their pages hold nothing, and their query rows compute garbage
that the caller discards; the per-row positional mask is what keeps the
garbage out of every VALID row.  One fixed (C) program therefore serves any
prompt-length mix: this is what retires the per-length prefill compile zoo.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(
    tables_ref, pos_ref,               # scalar-prefetch: (R, MB), (R,)
    q_ref, k_ref, v_ref,               # VMEM tiles
    o_ref,                             # (1, 1, G, D) output tile (revisited)
    acc_ref, m_ref, l_ref,             # scratch: f32 softmax state
    *,
    mode: str,
    window: int,
    page_size: int,
    scale: float,
):
    r = pl.program_id(0)
    bi = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(bi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale        # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)                # (BS, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = q @ k.T                                        # (G, BS)

    pos = pos_ref[r]
    kv_pos = bi * page_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1
    )
    valid = kv_pos <= pos
    if mode == "local":
        valid &= kv_pos > pos - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                # (rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + p @ v
    m_ref[...] = m_new

    @pl.when(bi == nb - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("mode", "window", "interpret")
)
def pallas_paged_attention(
    q: jax.Array,             # (R, H, D) — one decode token per request slot
    k_pages: jax.Array,       # (NP, KV, BS, D)
    v_pages: jax.Array,       # (NP, KV, BS, D)
    block_tables: jax.Array,  # (R, MB) int32
    positions: jax.Array,     # (R,) int32
    *,
    mode: str = "causal",
    window: int = 0,
    interpret: bool = True,
) -> jax.Array:
    """Paged decode attention at model layout — requires H % KV == 0 (the ops
    wrapper routes non-divisible head counts to the jnp twin)."""
    r, h, d = q.shape
    np_, kvh, bs, _ = k_pages.shape
    mb = block_tables.shape[1]
    if h % kvh:
        raise ValueError(
            f"pallas paged attention needs H % KV == 0, got H={h} KV={kvh}"
        )
    g = h // kvh
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(r, kvh, g, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r, kvh, mb),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda ri, hi, bi, tbl, pos: (ri, hi, 0, 0)),
            pl.BlockSpec(
                (1, 1, bs, d), lambda ri, hi, bi, tbl, pos: (tbl[ri, bi], hi, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, bs, d), lambda ri, hi, bi, tbl, pos: (tbl[ri, bi], hi, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, g, d), lambda ri, hi, bi, tbl, pos: (ri, hi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, d), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, mode=mode, window=window, page_size=bs, scale=scale
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), positions.astype(jnp.int32), qg, k_pages, v_pages)
    return out.reshape(r, h, d)


def _chunk_kernel(
    tables_ref, pos_ref,               # scalar-prefetch: (R, MB), (R,)
    q_ref, k_ref, v_ref,               # VMEM tiles
    o_ref,                             # (1, 1, C*G, D) output tile (revisited)
    acc_ref, m_ref, l_ref,             # scratch: f32 softmax state
    *,
    mode: str,
    window: int,
    page_size: int,
    scale: float,
    group: int,
):
    r = pl.program_id(0)
    bi = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(bi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale        # (C*G, D)
    k = k_ref[0, 0].astype(jnp.float32)                # (BS, D)
    v = v_ref[0, 0].astype(jnp.float32)

    s = q @ k.T                                        # (C*G, BS)

    # Row c*G + g of the folded q tile is chunk token c: its absolute query
    # position is the slot base plus the within-chunk offset.
    base = pos_ref[r]
    q_pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
    kv_pos = bi * page_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1
    )
    valid = kv_pos <= q_pos
    if mode == "local":
        valid &= kv_pos > q_pos - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                                # (rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + p @ v
    m_ref[...] = m_new

    @pl.when(bi == nb - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("mode", "window", "interpret")
)
def pallas_paged_chunk_attention(
    q: jax.Array,             # (R, C, H, D) — one prefill chunk per slot
    k_pages: jax.Array,       # (NP, KV, BS, D)
    v_pages: jax.Array,       # (NP, KV, BS, D)
    block_tables: jax.Array,  # (R, MB) int32
    positions: jax.Array,     # (R,) int32 — base position of chunk token 0
    *,
    mode: str = "causal",
    window: int = 0,
    interpret: bool = True,
) -> jax.Array:
    """Chunked paged prefill attention — requires H % KV == 0 (the ops wrapper
    routes non-divisible head counts to the jnp twin).  Chunk token c of slot
    r queries at position ``positions[r] + c``; rows past the slot's ragged
    length produce garbage that the caller discards."""
    r, c, h, d = q.shape
    np_, kvh, bs, _ = k_pages.shape
    mb = block_tables.shape[1]
    if h % kvh:
        raise ValueError(
            f"pallas paged attention needs H % KV == 0, got H={h} KV={kvh}"
        )
    g = h // kvh
    scale = 1.0 / math.sqrt(d)
    # Fold chunk tokens AND grouped query heads into one q row dim so K/V
    # tiles stay at kv-head width: row index = c * g + gi.
    qg = q.reshape(r, c, kvh, g, d).transpose(0, 2, 1, 3, 4).reshape(r, kvh, c * g, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(r, kvh, mb),
        in_specs=[
            pl.BlockSpec(
                (1, 1, c * g, d), lambda ri, hi, bi, tbl, pos: (ri, hi, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, bs, d), lambda ri, hi, bi, tbl, pos: (tbl[ri, bi], hi, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, bs, d), lambda ri, hi, bi, tbl, pos: (tbl[ri, bi], hi, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, c * g, d), lambda ri, hi, bi, tbl, pos: (ri, hi, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((c * g, d), jnp.float32),
            pltpu.VMEM((c * g, 1), jnp.float32),
            pltpu.VMEM((c * g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(
            _chunk_kernel,
            mode=mode,
            window=window,
            page_size=bs,
            scale=scale,
            group=g,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), positions.astype(jnp.int32), qg, k_pages, v_pages)
    return out.reshape(r, kvh, c, g, d).transpose(0, 2, 1, 3, 4).reshape(r, c, h, d)
