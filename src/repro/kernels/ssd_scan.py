"""Mamba-2 SSD intra-chunk kernel (the quadratic hot-spot of the SSD
algorithm) in Pallas.

Per (batch, chunk, head) program:
    inputs  x (Q,P), dt (Q,), B (Q,N), C (Q,N), a (scalar decay rate)
    L[i,j]  = exp(cums_i − cums_j)·[i ≥ j],  cums = cumsum(dt·a)
    y_diag  = (C Bᵀ ∘ L) (dt ∘ x)            — intra-chunk output
    state   = Σ_j exp(cums_Q − cums_j)·dt_j·B_j ⊗ x_j  — chunk end state

The inter-chunk state recurrence is a cheap sequential scan left in jnp
(models/ssd.py); this kernel owns the O(Q²) work.  Q = ssm_chunk (128),
P = head_dim (64), N = d_state (128): VMEM ≈ Q·(P+2N)·4 + Q²·4 ≈ 250 KiB.

TPU layout: heads move ahead of the chunk positions outside the kernel, so
each block's last two dims are a whole (Q, P) / (Q, 1) / (1, Q) tile; dt
rides in both as a column and as a row, which lets the inclusive cumsum
be two masked reductions instead of a scan; the per-head decay rates sit
in SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, dtc_ref, dtr_ref, b_ref, c_ref, y_ref, state_ref):
    a = a_ref[pl.program_id(2)]                      # scalar (SMEM)
    x = x_ref[0, 0, 0].astype(jnp.float32)           # (Q, P)
    dt_col = dtc_ref[0, 0, 0].astype(jnp.float32)    # (Q, 1)
    dt_row = dtr_ref[0, 0, 0].astype(jnp.float32)    # (1, Q)
    b = b_ref[0, 0].astype(jnp.float32)              # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)              # (Q, N)

    q = x.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lower = ii >= jj
    # inclusive cumsum of dt·a, as a column (cums_i) and as a row (cums_j)
    cums_col = jnp.sum(jnp.where(lower, dt_row * a, 0.0), axis=1, keepdims=True)
    cums_row = jnp.sum(jnp.where(ii <= jj, dt_col * a, 0.0), axis=0, keepdims=True)

    l_kern = jnp.where(lower, jnp.exp(cums_col - cums_row), 0.0)   # (Q, Q)
    xdt = x * dt_col                                                # (Q, P)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())))   # C Bᵀ
    y = (scores * l_kern) @ xdt                                     # (Q, P)

    last = jax.lax.slice_in_dim(cums_row, q - 1, q, axis=1)        # (1, 1)
    w = b * (jnp.exp(last - cums_col) * dt_col)                     # (Q, N)
    state = jax.lax.dot_general(w, x, (((0,), (0,)), ((), ())))    # (N, P)

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    state_ref[0, 0, 0] = state.astype(state_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_kernel(
    x: jax.Array,    # (B, NC, Q, H, P)
    dt: jax.Array,   # (B, NC, Q, H)
    a: jax.Array,    # (H,)
    b_mat: jax.Array,  # (B, NC, Q, N)
    c_mat: jax.Array,  # (B, NC, Q, N)
    *,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Returns (y_diag (B,NC,Q,H,P), states (B,NC,H,N,P))."""
    bsz, nc, qlen, h, p = x.shape
    n = b_mat.shape[-1]
    xh = x.transpose(0, 1, 3, 2, 4)                  # (B, NC, H, Q, P)
    dth = dt.transpose(0, 1, 3, 2)                   # (B, NC, H, Q)

    head_tile = lambda *tile: pl.BlockSpec(
        (1, 1, 1) + tile, lambda b, c, hh: (b, c, hh, 0, 0)
    )
    # B/C are shared by every head: the BlockSpec re-reads them per head
    # instead of materializing a broadcast copy
    shared = pl.BlockSpec((1, 1, qlen, n), lambda b, c, hh: (b, c, 0, 0))
    y, states = pl.pallas_call(
        _kernel,
        grid=(bsz, nc, h),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            head_tile(qlen, p),
            head_tile(qlen, 1),
            head_tile(1, qlen),
            shared,
            shared,
        ],
        out_specs=[head_tile(qlen, p), head_tile(n, p)],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, nc, h, qlen, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, nc, h, n, p), jnp.float32),
        ],
        interpret=interpret,
    )(a.astype(jnp.float32), xh, dth[..., None], dth[..., None, :], b_mat, c_mat)
    return y.transpose(0, 1, 3, 2, 4), states
