"""Fused int8 per-chunk affine quantize / dequantize Pallas kernels.

The gossip wire codec (:class:`repro.comm.compress.Int8Codec`) maps each
CHUNK-sized group of a packed payload to uint8 with an fp32 (scale, min)
pair.  The jnp expression materializes the padded fp32 buffer, the per-chunk
min/max, AND the normalized intermediate — ≥4 HBM round trips over a buffer
that is the whole model.  The kernels stream (ROWS, CHUNK) tiles through
VMEM and emit the quantized bytes + metadata in one pass (quantize: 1 fp32
read, ~¼ write; dequantize: ¼ read + 1 fp32 write) — LoCo-style low-bit
compression fused on the wire path.

Layout contract (shared with ref.jnp_int8_quantize): input is the
already-padded 2-D (NC, CHUNK) view of the payload; the byte-level wire
packing (values ‖ bitcast metadata) stays in comm/compress.py.

TPU tiling: the per-chunk (scale, min) pair travels through the kernel as
(NC, 1) columns, so every block is 2-D with a full-width last dim, and ROWS
is 32 because a uint8 tile spans 32 sublanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 32  # chunk rows per grid step: (32, 1024) f32 tile = 128 KiB VMEM


def _quant_kernel(x_ref, q_ref, scale_ref, lo_ref):
    x = x_ref[...].astype(jnp.float32)              # (ROWS, CHUNK)
    lo = jnp.min(x, axis=1, keepdims=True)          # (ROWS, 1)
    scale = (jnp.max(x, axis=1, keepdims=True) - lo) / 255.0
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.clip(jnp.round((x - lo) / safe), 0.0, 255.0)
    # Mosaic casts between f32 and uint8 only through int32
    q_ref[...] = q.astype(jnp.int32).astype(jnp.uint8)
    scale_ref[...] = safe
    lo_ref[...] = lo


def _dequant_kernel(q_ref, scale_ref, lo_ref, x_ref):
    q = q_ref[...].astype(jnp.int32).astype(jnp.float32)
    x_ref[...] = q * scale_ref[...] + lo_ref[...]


def _pad_rows(x: jax.Array, rows: int) -> tuple[jax.Array, int]:
    nc = x.shape[0]
    pad = (-nc) % rows
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x, nc


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_int8_quantize(
    x: jax.Array, *, interpret: bool = True
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(NC, CHUNK) f32 → (q uint8 (NC,CHUNK), scale f32 (NC,), lo f32 (NC,))."""
    xp, nc = _pad_rows(x, ROWS)
    chunk = x.shape[1]
    grid = (xp.shape[0] // ROWS,)
    spec2d = pl.BlockSpec((ROWS, chunk), lambda i: (i, 0))
    spec_col = pl.BlockSpec((ROWS, 1), lambda i: (i, 0))
    col = jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32)
    q, scale, lo = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[spec2d],
        out_specs=[spec2d, spec_col, spec_col],
        out_shape=[jax.ShapeDtypeStruct(xp.shape, jnp.uint8), col, col],
        interpret=interpret,
    )(xp)
    return q[:nc], scale[:nc, 0], lo[:nc, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_int8_dequantize(
    q: jax.Array, scale: jax.Array, lo: jax.Array, *, interpret: bool = True
) -> jax.Array:
    """(q uint8 (NC,CHUNK), scale (NC,), lo (NC,)) → f32 (NC, CHUNK)."""
    qp, nc = _pad_rows(q, ROWS)
    sp, _ = _pad_rows(scale[:, None], ROWS)
    lp, _ = _pad_rows(lo[:, None], ROWS)
    chunk = q.shape[1]
    grid = (qp.shape[0] // ROWS,)
    spec2d = pl.BlockSpec((ROWS, chunk), lambda i: (i, 0))
    spec_col = pl.BlockSpec((ROWS, 1), lambda i: (i, 0))
    x = pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[spec2d, spec_col, spec_col],
        out_specs=spec2d,
        out_shape=jax.ShapeDtypeStruct(qp.shape, jnp.float32),
        interpret=interpret,
    )(qp, sp, lp)
    return x[:nc]
