"""Pallas TPU flash attention (causal / sliding-window / full), GQA-native.

Grid: (batch·kv_heads, q_blocks, kv_blocks) with the kv dim innermost and
"arbitrary" (sequential) so the online-softmax state lives in VMEM scratch
across kv iterations.  BlockSpecs tile Q/K/V into (block_q|block_kv, head_dim)
VMEM tiles whose sizes :func:`plan` picks from the shapes.

GQA is handled WITHOUT materializing K/V at query-head width: the G = H/KV
query heads sharing one kv head are folded into the q row dimension
(rows enumerate (group, position) pairs, position = row % ``q_stride``), so
K/V buffers stay at kv-head width all the way into the kernel and each K/V
VMEM tile is reused by all G query heads of its grid row.  ``block_q``
divides ``q_stride``, so a q tile never straddles two heads and its
positions are ``(qi·block_q) % q_stride + iota``.

Only the (q tile, kv tile) pairs the mask leaves live do work.  :func:`plan`
gives, per q tile of one head, the first and last live kv tile and the
first and last dense one (every entry unmasked); they ride in as a
scalar-prefetch table.  A dead pair skips its body under ``pl.when``, and
the K/V ``index_map`` clamps its kv index into the live range, so Pallas
fetches no new block for it.  Only live tiles that are not dense build the
mask.

QKᵀ takes the tiles in the dtype they arrive in (bf16 products are exact in
the f32 accumulator); P·V takes P in float32.  The softmax statistics m/l
are lane-dense (block_q, 128) f32 scratch, every lane holding the row's
value.

Parity with kernels/ref.py is tested in interpret mode; the kernel compiles
for the TPU v5e (tests/test_chip_compile.py).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs


NEG_INF = -1e30
LANES = 128
# the scoped VMEM limit Mosaic gives a kernel by default on the TPU v5e
VMEM_LIMIT = 16 * 2**20
Q_TILES = (1024, 512, 256, 128)
KV_TILES = (512, 256, 128)


class FlashPlan(NamedTuple):
    block_q: int
    block_kv: int
    live_tiles: int   # (q tile, kv tile) pairs with an unmasked entry, per grid row
    tiles: int        # all (q tile, kv tile) pairs, per grid row
    # per q tile of one head: first live, last live, first dense, last dense
    # kv tile (an empty range has last < first)
    bounds: tuple[tuple[int, int, int, int], ...]


def vmem_bytes(block_q: int, block_kv: int, d: int) -> int:
    """VMEM working set of one program at float32 operands: double-buffered
    q/o and k/v tiles, the accumulator, m/l, and the score-sized temps."""
    lanes = -(-d // LANES) * LANES
    tiles = 2 * (2 * block_q + 2 * block_kv) * lanes * 4
    scratch = block_q * lanes * 4 + 2 * block_q * LANES * 4
    temps = 3 * block_q * block_kv * 4
    return tiles + scratch + temps


def _tile_bounds(q_lo, q_hi, bk, nk, kv_len, mode, window):
    """(first live, last live, first dense, last dense) kv tile for the q
    positions ``q_lo..q_hi``."""
    last = (kv_len - 1) // bk            # last tile holding a real key
    dense_last = kv_len // bk - 1        # last tile holding only real keys
    if mode == "full":
        return 0, last, 0, dense_last
    hi = min(last, q_hi // bk)
    dhi = min(dense_last, (q_lo + 1) // bk - 1)     # k ≤ q everywhere
    if mode == "causal":
        return 0, hi, 0, dhi
    lo = min(max(q_lo - window + 1, 0) // bk, nk - 1)
    dlo = max(-(-(q_hi - window + 1) // bk), 0)     # k > q - window everywhere
    return lo, hi, dlo, dhi


def plan(
    sq_pad: int,
    sk: int,
    d: int,
    mode: str,
    window: int = 0,
    q_stride: int | None = None,
    *,
    block_q: int | None = None,
    block_kv: int | None = None,
) -> FlashPlan:
    """Tiles and live tile pairs for ``sq_pad`` q rows (``q_stride`` rows a
    head, the whole of ``sq_pad`` unless GQA groups are folded in) against
    ``sk`` keys of head dim ``d``.

    Each tile the caller leaves ``None`` is the largest candidate that
    divides the padded length (``q_stride``, or ``sk`` rounded up to 128)
    and keeps :func:`vmem_bytes` under the scoped VMEM limit.  Under a
    causal or sliding-window mask ``block_q`` is held to ``block_kv`` and
    both to the window rounded up to a power of two (at least 128), since
    a larger tile mostly computes masked entries."""
    if mode not in ("causal", "local", "full"):
        raise ValueError(f"unknown attention mode {mode!r}")
    if mode == "local" and window < 1:
        raise ValueError(f"local attention needs a window of at least 1, got {window}")
    q_stride = sq_pad if q_stride is None else q_stride
    if sq_pad % q_stride:
        raise ValueError(f"q_stride {q_stride} does not divide {sq_pad} rows")
    cap = 1 << 30
    if mode == "local":
        cap = max(LANES, 1 << (window - 1).bit_length())
    if block_kv is None:
        sk_pad = -(-sk // LANES) * LANES
        block_kv = next(
            (t for t in KV_TILES if t <= cap and sk_pad % t == 0), LANES
        )
    if block_q is None:
        q_cap = cap if mode == "full" else min(cap, block_kv)
        fits = [
            t for t in Q_TILES
            if q_stride % t == 0 and vmem_bytes(t, block_kv, d) <= VMEM_LIMIT
        ]
        if not fits:
            raise ValueError(f"no q tile of {Q_TILES} divides q_stride {q_stride}")
        block_q = next((t for t in fits if t <= q_cap), fits[-1])
    if q_stride % block_q:
        raise ValueError(f"block_q {block_q} does not divide q_stride {q_stride}")
    nk = -(-sk // block_kv)
    bounds = tuple(
        _tile_bounds(t * block_q, (t + 1) * block_q - 1, block_kv, nk, sk, mode, window)
        for t in range(q_stride // block_q)
    )
    live = sum(max(hi - lo + 1, 0) for lo, hi, _, _ in bounds) * (sq_pad // q_stride)
    return FlashPlan(block_q, block_kv, live, (sq_pad // block_q) * nk, bounds)


def _lanes(x: jax.Array, n: int) -> jax.Array:
    """A lane-replicated (rows, 128) statistic at ``n`` lanes."""
    if n % LANES == 0:
        return x if n == LANES else pltpu.repeat(x, n // LANES, axis=1)
    if n < LANES:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _kernel(
    bounds_ref,                       # scalar prefetch: (4, q tiles of a head)
    q_ref, k_ref, v_ref,              # VMEM tiles
    o_ref,                            # output tile (revisited over kv grid)
    acc_ref, m_ref, l_ref,            # scratch: f32 accumulators
    *,
    mode: str,
    window: int,
    block_q: int,
    block_kv: int,
    kv_len: int,
    q_tiles: int,
    scale: float,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    t = qi % q_tiles
    lo, hi = bounds_ref[0, t], bounds_ref[1, t]
    dlo, dhi = bounds_ref[2, t], bounds_ref[3, t]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def step(masked: bool):
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                          # (bq, bk)
        if masked:
            shape = (block_q, block_kv)
            q_pos = t * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            valid = k_pos < kv_len
            if mode != "full":
                valid &= k_pos <= q_pos
            if mode == "local":
                valid &= k_pos > q_pos - window
            s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]                                # (bq, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, block_kv))
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot(
            p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
        )
        d = acc_ref.shape[-1]
        acc_ref[...] = acc_ref[...] * _lanes(corr, d) + pv
        m_ref[...] = m_new

    dense = (ki >= dlo) & (ki <= dhi)

    @pl.when(dense)
    def _dense():
        step(masked=False)

    @pl.when((ki >= lo) & (ki <= hi) & jnp.logical_not(dense))
    def _edge():
        step(masked=True)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / _lanes(l, acc_ref.shape[-1])).astype(o_ref.dtype)


def _kv_index(b, qi, ki, bounds_ref, *, q_tiles):
    """The kv block a program reads: its own when live, else the nearest
    live one, which the pipeline already holds, so nothing is fetched."""
    t = qi % q_tiles
    lo = bounds_ref[0, t]
    last = jnp.maximum(bounds_ref[1, t], lo)
    return (b, jnp.minimum(jnp.maximum(ki, lo), last), 0)


@functools.partial(
    jax.jit,
    static_argnames=("mode", "window", "block_q", "block_kv", "q_stride", "interpret"),
)
def flash_attention_bhsd(
    q: jax.Array,   # (BH, Sq, D)  — batch and (kv) heads flattened
    k: jax.Array,   # (BH, Sk, D)
    v: jax.Array,   # (BH, Sk, D)
    *,
    mode: str = "causal",
    window: int = 0,
    block_q: int | None = None,
    block_kv: int | None = None,
    q_stride: int | None = None,   # per-head q length when GQA groups folded
    interpret: bool = True,
) -> jax.Array:
    """``block_q``/``block_kv`` left ``None`` are chosen by :func:`plan`."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(d)

    if q_stride is None:
        pq = (-sq) % (block_q or LANES)
        if pq:
            q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
        q_stride = q.shape[1]
    fp = plan(
        q.shape[1], sk, d, mode, window, q_stride, block_q=block_q, block_kv=block_kv
    )
    obs.mark(
        "jax.flash_plan", block_q=fp.block_q, block_kv=fp.block_kv,
        live_tiles=fp.live_tiles, tiles=fp.tiles,
    )
    pk = (-sk) % fp.block_kv
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    nq = q.shape[1] // fp.block_q
    nk = k.shape[1] // fp.block_kv
    q_tiles = q_stride // fp.block_q
    bounds = jnp.asarray(fp.bounds, jnp.int32).T          # (4, q_tiles)

    kernel = functools.partial(
        _kernel,
        mode=mode,
        window=window,
        block_q=fp.block_q,
        block_kv=fp.block_kv,
        kv_len=sk,
        q_tiles=q_tiles,
        scale=scale,
    )
    kv_index = functools.partial(_kv_index, q_tiles=q_tiles)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, nq, nk),
            in_specs=[
                pl.BlockSpec((1, fp.block_q, d), lambda b, qi, ki, _: (b, qi, 0)),
                pl.BlockSpec((1, fp.block_kv, d), kv_index),
                pl.BlockSpec((1, fp.block_kv, d), kv_index),
            ],
            out_specs=pl.BlockSpec((1, fp.block_q, d), lambda b, qi, ki, _: (b, qi, 0)),
            scratch_shapes=[
                pltpu.VMEM((fp.block_q, d), jnp.float32),
                pltpu.VMEM((fp.block_q, LANES), jnp.float32),
                pltpu.VMEM((fp.block_q, LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(bounds, q, k, v)
    return out[:, :sq]


def pallas_flash_attention(
    q: jax.Array,   # (B, Sq, H, D)
    k: jax.Array,   # (B, Sk, KV, D)
    v: jax.Array,   # (B, Sk, KV, D)
    *,
    mode: str = "causal",
    window: int = 0,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool = True,
) -> jax.Array:
    """GQA flash attention at model layout.

    When H % KV == 0 (all assigned archs) the G = H/KV query heads per kv
    head are FOLDED into the q row dimension: K/V are flattened to
    (B·KV, Sk, D) without any head expansion, and the kernel recovers the
    per-head position as ``row % q_stride``.  The legacy gather-expand path
    remains only for non-divisible head counts.  Tiles left ``None`` are
    chosen by :func:`plan`.
    """
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]

    if h % kvh == 0:
        g = h // kvh
        sq_pad = sq + (-sq) % (block_q or LANES)
        qt = q.transpose(0, 2, 1, 3)                   # (B, H, Sq, D)
        if sq_pad != sq:
            qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sq_pad - sq), (0, 0)))
        qf = qt.reshape(b * kvh, g * sq_pad, d)
        kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, sk, d)
        vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, sk, d)
        out = flash_attention_bhsd(
            qf, kf, vf, mode=mode, window=window,
            block_q=block_q, block_kv=block_kv, q_stride=sq_pad,
            interpret=interpret,
        )
        out = out.reshape(b, kvh, g, sq_pad, d)[:, :, :, :sq]
        return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)

    # non-divisible head counts: gather-expand K/V to query-head width
    head_map = (jnp.arange(h) * kvh) // h
    ke = jnp.take(k, head_map, axis=2)
    ve = jnp.take(v, head_map, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = ke.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
    vf = ve.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
    out = flash_attention_bhsd(
        qf, kf, vf, mode=mode, window=window,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
    )
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
