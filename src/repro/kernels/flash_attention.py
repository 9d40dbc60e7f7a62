"""Pallas TPU flash attention (causal / sliding-window / full), GQA-native.

Grid: (batch·kv_heads, q_blocks, kv_blocks) with the kv dim innermost and
"arbitrary" (sequential) so the online-softmax state lives in VMEM scratch
across kv iterations.  BlockSpecs tile Q/K/V into (block_q|block_kv, head_dim)
VMEM tiles; MXU-aligned defaults block_q = block_kv = 128.

GQA is handled WITHOUT materializing K/V at query-head width: the G = H/KV
query heads sharing one kv head are folded into the q row dimension
(rows enumerate (group, position) pairs, position = row % ``q_stride``), so
K/V buffers stay at kv-head width all the way into the kernel and each K/V
VMEM tile is reused by all G query heads of its grid row.

VMEM working set per program:
    q (bq, d) + k (bk, d) + v (bk, d) + acc (bq, d) f32 + m/l (bq,) f32
    = 128·128·2·3 + 128·128·4 + 1KB ≈ 164 KiB  « 16 MiB VMEM.

Parity with kernels/ref.py is tested in interpret mode; the kernel compiles
for the TPU v5e at paper-small widths (tests/test_chip_compile.py).
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
    q_ref, k_ref, v_ref,             # VMEM tiles
    o_ref,                            # output tile (revisited over kv grid)
    acc_ref, m_ref, l_ref,            # scratch: f32 accumulators
    *,
    mode: str,
    window: int,
    block_q: int,
    block_kv: int,
    kv_len: int,
    q_stride: int,
    scale: float,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)

    s = q @ k.T                                       # (bq, bk)

    # rows enumerate (group, position) pairs when GQA groups are folded in;
    # position within the head is row % q_stride (identity when unfolded)
    row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    q_pos = row % q_stride
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    valid = k_pos < kv_len
    if mode == "causal":
        valid &= k_pos <= q_pos
    elif mode == "local":
        valid &= (k_pos <= q_pos) & (k_pos > q_pos - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + p @ v
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...][:, None], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


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
    block_q: int = 128,
    block_kv: int = 128,
    q_stride: int | None = None,   # per-head q length when GQA groups folded
    interpret: bool = True,
) -> jax.Array:
    bh, sq, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(d)

    pq = (-sq) % block_q
    pk = (-sk) % block_kv
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    nq = q.shape[1] // block_q
    nk = k.shape[1] // block_kv
    if q_stride is None:
        q_stride = q.shape[1]

    kernel = functools.partial(
        _kernel,
        mode=mode,
        window=window,
        block_q=block_q,
        block_kv=block_kv,
        kv_len=sk,
        q_stride=q_stride,
        scale=scale,
    )
    out = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]


def pallas_flash_attention(
    q: jax.Array,   # (B, Sq, H, D)
    k: jax.Array,   # (B, Sk, KV, D)
    v: jax.Array,   # (B, Sk, KV, D)
    *,
    mode: str = "causal",
    window: int = 0,
    block_q: int = 128,
    block_kv: int = 128,
    interpret: bool = True,
) -> jax.Array:
    """GQA flash attention at model layout.

    When H % KV == 0 (all assigned archs) the G = H/KV query heads per kv
    head are FOLDED into the q row dimension: K/V are flattened to
    (B·KV, Sk, D) without any head expansion, and the kernel recovers the
    per-head position as ``row % q_stride``.  The legacy gather-expand path
    remains only for non-divisible head counts.
    """
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]

    if h % kvh == 0:
        g = h // kvh
        sq_pad = sq + (-sq) % block_q
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
