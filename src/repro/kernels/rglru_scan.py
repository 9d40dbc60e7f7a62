"""RG-LRU linear recurrence  h_t = a_t · h_{t-1} + b_t  as a Pallas kernel.

The recurrence is channelwise (no mixing across the width dim), so the grid
is (batch, width_blocks, seq_chunks) with the sequence dim innermost and
"arbitrary" (sequential): the hidden state at a chunk boundary lives in VMEM
scratch across chunk iterations.  WITHIN a chunk the scan is computed fully
vectorized by log-step doubling on the (a, b) pair representation

    (A_t, B_t) ∘ (A_{t-k}, B_{t-k}) = (A_t·A_{t-k},  A_t·B_{t-k} + B_t)

— ⌈log₂ S_chunk⌉ VPU sweeps over a (S_chunk, block_w) tile instead of an
S-step serial loop, with no dynamic row indexing.  The chunk carry is then
applied as  h_t = B_t + A_t · h_in  (A_t = within-chunk cumprod of a).

VMEM per program ≈ (2 in + 1 out + 2 temps) · S_chunk·block_w·4B
               = 5 · 256·128·4 ≈ 640 KiB  « 16 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


BLOCK_S = 256   # sequence chunk per grid step
BLOCK_W = 128   # lane-aligned width tile


def _kernel(a_ref, b_ref, h_ref, carry_ref, *, block_s: int):
    sc = pl.program_id(2)

    @pl.when(sc == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    a = a_ref[0].astype(jnp.float32)        # (S, W)
    b = b_ref[0].astype(jnp.float32)

    # inclusive scan by doubling: after round k, (A_t, B_t) composes the last
    # min(2^k, t+1) steps ending at t; with zero initial state h_t = B_t.
    big_a, big_b = a, b
    off = 1
    while off < block_s:
        ones = jnp.ones((off,) + big_a.shape[1:], big_a.dtype)
        zeros = jnp.zeros((off,) + big_b.shape[1:], big_b.dtype)
        a_shift = jnp.concatenate([ones, big_a[:-off]], axis=0)
        b_shift = jnp.concatenate([zeros, big_b[:-off]], axis=0)
        big_b = big_a * b_shift + big_b
        big_a = big_a * a_shift
        off *= 2

    h = big_b + big_a * carry_ref[...]      # carry: (1, W) state entering
    h_ref[0] = h.astype(h_ref.dtype)
    carry_ref[...] = jax.lax.slice_in_dim(h, block_s - 1, block_s, axis=0)


@functools.partial(
    jax.jit, static_argnames=("block_s", "block_w", "interpret")
)
def pallas_rglru_scan(
    a: jax.Array,   # (B, S, W) per-step decay in (0, 1]
    b: jax.Array,   # (B, S, W) per-step input
    *,
    block_s: int = BLOCK_S,
    block_w: int = BLOCK_W,
    interpret: bool = True,
) -> jax.Array:
    """Inclusive scan of h_t = a_t·h_{t-1} + b_t over axis 1 (zero h_0)."""
    bsz, s, w = a.shape
    ps = (-s) % block_s
    pw = (-w) % block_w
    if ps or pw:
        # zero padding is inert: a=0, b=0 rows hold h at 0 and are sliced off
        a = jnp.pad(a, ((0, 0), (0, ps), (0, pw)))
        b = jnp.pad(b, ((0, 0), (0, ps), (0, pw)))
    nsc = a.shape[1] // block_s
    nw = a.shape[2] // block_w

    spec = pl.BlockSpec((1, block_s, block_w), lambda bi, wi, sc: (bi, sc, wi))
    out = pl.pallas_call(
        functools.partial(_kernel, block_s=block_s),
        grid=(bsz, nw, nsc),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(a.shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)
    return out[:, :s, :w]
