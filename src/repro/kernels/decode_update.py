"""Pallas TPU single-token recurrent-state updates for the serving hot loop.

Decode advances RG-LRU and SSD (Mamba-2) layers one token at a time, so the
training scan kernels (rglru_scan.py's log-step doubling, ssd_chunk.py's
chunked matmuls) degenerate to a single fused elementwise/contraction step.
These kernels keep that step on-chip — state in, state out, no HBM round
trips between the gate math and the output contraction — and exist mostly so
the serving engine exercises the same dispatch machinery (impl=auto|pallas|
jnp, interpret parity tests) as every training op.

Shapes are the serving-engine slot layout (R = request slots):

  rglru:  h, a, b                  (R, W)       → h' = a·h + b       (R, W) f32
  ssd:    state (R, HP, N) f32, decay/dtx (R, HP), b/c (R, N)
          → state' = decay·state + dtx ⊗ b,  y = Σ_n state'·c   ((R,HP,N), (R,HP))

Both compute in f32 (the recurrent state is f32-resident in the engine) and
tile the trailing dims at lane width; the SSD step's per-slot vectors ride as
(R, HP, 1) columns and (R, 1, N) rows so that every block's last two dims
equal the array's, as the TPU tiling rules require.  Parity with the jnp
twins in ref.py is tested in interpret mode; both compile for the TPU v5e.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BLOCK_W = 128   # lane-aligned width tile


def _rglru_kernel(h_ref, a_ref, b_ref, o_ref):
    h = h_ref[...].astype(jnp.float32)
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    o_ref[...] = a * h + b


@functools.partial(jax.jit, static_argnames=("block_w", "interpret"))
def pallas_rglru_decode(
    h: jax.Array,   # (R, W) recurrent state
    a: jax.Array,   # (R, W) per-token decay
    b: jax.Array,   # (R, W) per-token input
    *,
    block_w: int = BLOCK_W,
    interpret: bool = True,
) -> jax.Array:
    """One RG-LRU step h' = a·h + b across all request slots; returns f32."""
    r, w = h.shape
    pw = (-w) % block_w
    if pw:
        h = jnp.pad(h, ((0, 0), (0, pw)))
        a = jnp.pad(a, ((0, 0), (0, pw)))
        b = jnp.pad(b, ((0, 0), (0, pw)))
    wp = w + pw
    grid = (wp // block_w,)
    spec = pl.BlockSpec((r, block_w), lambda wi: (0, wi))
    out = pl.pallas_call(
        _rglru_kernel,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((r, wp), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(h, a, b)
    return out[:, :w]


def _ssd_kernel(state_ref, decay_ref, dtx_ref, b_ref, c_ref, st_ref, y_ref):
    st = state_ref[0].astype(jnp.float32)        # (HP, N)
    decay = decay_ref[0].astype(jnp.float32)     # (HP, 1)
    dtx = dtx_ref[0].astype(jnp.float32)         # (HP, 1)
    b = b_ref[0].astype(jnp.float32)             # (1, N)
    c = c_ref[0].astype(jnp.float32)             # (1, N)
    new = st * decay + dtx * b
    st_ref[0] = new
    y_ref[0] = jnp.sum(new * c, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_ssd_decode(
    state: jax.Array,   # (R, HP, N) f32 recurrent state (HP = heads·headdim)
    decay: jax.Array,   # (R, HP) exp(dt·A) per channel
    dtx: jax.Array,     # (R, HP) dt·x per channel
    b: jax.Array,       # (R, N) input projection
    c: jax.Array,       # (R, N) output projection
    *,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """One SSD step per slot: state' = decay·state + dtx⊗b, y = state'·c."""
    r, hp, n = state.shape
    # per-slot vectors ride as (R, HP, 1) columns / (R, 1, N) rows so every
    # block's last two dims equal the array's (the TPU tiling rule)
    col = pl.BlockSpec((1, hp, 1), lambda ri: (ri, 0, 0))
    row = pl.BlockSpec((1, 1, n), lambda ri: (ri, 0, 0))
    full = pl.BlockSpec((1, hp, n), lambda ri: (ri, 0, 0))
    st, y = pl.pallas_call(
        _ssd_kernel,
        grid=(r,),
        in_specs=[full, col, col, row, row],
        out_specs=[full, col],
        out_shape=[
            jax.ShapeDtypeStruct((r, hp, n), jnp.float32),
            jax.ShapeDtypeStruct((r, hp, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(state, decay[:, :, None], dtx[:, :, None], b[:, None], c[:, None])
    return st, y[:, :, 0]
