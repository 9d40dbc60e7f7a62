"""Public, differentiable wrappers around the dispatched kernels.

Each op here is the PRODUCTION entry its consumers call (models, core/outer,
comm): it resolves a :class:`~repro.kernels.dispatch.KernelConfig`, picks the
Pallas kernel or the jnp twin from the dispatch table, and — for the ops that
sit inside the training forward — wraps the choice in ``jax.custom_vjp``
whose backward is the vjp of the jnp twin.  Pallas kernels have no autodiff
rules; the twin computes the SAME function with online-softmax / chunked
recompute, so gradients are exact and memory-bounded regardless of which
implementation ran the forward.

``interpret`` resolution: True off-TPU, False on TPU (overridable via
``KernelConfig.interpret``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.dispatch import KernelConfig, default_config, dispatch

__all__ = [
    "flash_attention",
    "ssd_chunk",
    "rglru_scan",
    "noloco_update_pytree",
    "int8_quantize",
    "int8_dequantize",
    "paged_attention",
    "paged_chunk_attention",
    "paged_impl",
    "rglru_decode",
    "ssd_decode",
]


def _resolve(config: KernelConfig | None) -> tuple[str, bool]:
    cfg = config if config is not None else default_config()
    return cfg.resolved_impl(), cfg.resolved_interpret()


# ---------------------------------------------------------------------------
# Flash attention (differentiable; jnp online-softmax backward)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _attention_op(mode, window, block_q, block_kv, impl, interpret, unroll):
    if impl == "pallas":
        fwd_impl = functools.partial(
            dispatch("flash_attention", KernelConfig("pallas", interpret)),
            mode=mode, window=window, block_q=block_q, block_kv=block_kv,
        )
    else:
        fwd_impl = functools.partial(
            dispatch("flash_attention", KernelConfig("jnp")),
            mode=mode, window=window, unroll=unroll,
        )
    jnp_twin = functools.partial(
        ref.jnp_flash_attention, mode=mode, window=window, unroll=unroll
    )

    @jax.custom_vjp
    def op(q, k, v):
        return fwd_impl(q, k, v)

    def fwd(q, k, v):
        return fwd_impl(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(jnp_twin, q, k, v)
        return vjp(g)

    op.defvjp(fwd, bwd)
    return op


def flash_attention(
    q: jax.Array,   # (B, Sq, H, D)
    k: jax.Array,   # (B, Sk, KV, D)
    v: jax.Array,   # (B, Sk, KV, D)
    *,
    mode: str = "causal",
    window: int = 0,
    block_q: int | None = None,
    block_kv: int | None = None,
    unroll: bool = False,
    config: KernelConfig | None = None,
) -> jax.Array:
    """GQA flash attention over canonical (arange) positions.

    K/V stay at kv-head width end to end: the Pallas path folds the G = H/KV
    query heads per kv head into the q row dimension, the jnp path groups the
    einsums — neither materializes K/V expanded to all query heads.
    The Pallas path's tiles left ``None`` come from
    :func:`repro.kernels.flash_attention.plan`.
    ``unroll`` unrolls the jnp path's KV scan (dry-run cost analysis)."""
    impl, interpret = _resolve(config)
    return _attention_op(mode, window, block_q, block_kv, impl, interpret, unroll)(
        q, k, v
    )


# ---------------------------------------------------------------------------
# SSD (Mamba-2): dispatched intra-chunk quadratic form + jnp inter-chunk scan
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _ssd_intra_op(impl, interpret):
    if impl == "pallas":
        fwd_impl = dispatch("ssd_chunk", KernelConfig("pallas", interpret))
    else:
        fwd_impl = dispatch("ssd_chunk", KernelConfig("jnp"))
    jnp_twin = ref.jnp_ssd_chunk_intra

    @jax.custom_vjp
    def op(xc, dtc, a, bc, cc):
        return fwd_impl(xc, dtc, a, bc, cc)

    def fwd(xc, dtc, a, bc, cc):
        return fwd_impl(xc, dtc, a, bc, cc), (xc, dtc, a, bc, cc)

    def bwd(res, g):
        _, vjp = jax.vjp(jnp_twin, *res)
        return vjp(g)

    op.defvjp(fwd, bwd)
    return op


def ssd_chunk(
    x: jax.Array,      # (B, S, H, P)
    dt: jax.Array,     # (B, S, H)
    a: jax.Array,      # (H,)
    b_mat: jax.Array,  # (B, S, N)
    c_mat: jax.Array,  # (B, S, N)
    *,
    chunk: int,
    initial_state: jax.Array | None = None,  # (B, H, P, N)
    unroll: bool = False,
    config: KernelConfig | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Full chunked SSD: dispatched intra-chunk O(Q²) form + cheap sequential
    inter-chunk state recurrence in jnp.  Matches ref.reference_ssd.
    Returns (y (B,S,H,P) in x.dtype, final_state (B,H,P,N) f32)."""
    impl, interpret = _resolve(config)
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    q = min(chunk, s)
    nc = math.ceil(s / q)
    pad = nc * q - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))

    xc = x.reshape(bsz, nc, q, h, p)
    dtc = dt.reshape(bsz, nc, q, h)
    bc = b_mat.reshape(bsz, nc, q, n)
    cc = c_mat.reshape(bsz, nc, q, n)

    y_diag, states = _ssd_intra_op(impl, interpret)(xc, dtc, a, bc, cc)

    # inter-chunk state recurrence (cheap, sequential, differentiates normally)
    da = dtc.astype(jnp.float32) * a[None, None, None, :]
    chunk_decay = jnp.exp(jnp.sum(da, axis=2))            # (B,nc,H)
    cums = jnp.cumsum(da, axis=2)

    def body(prev, inp):
        st, dec = inp
        new = prev * dec[:, :, None, None] + st
        return new, prev  # emit the state ENTERING this chunk

    s0 = (
        jnp.zeros((bsz, h, n, p), jnp.float32)
        if initial_state is None
        # caches carry (B,H,P,N); the kernel's state layout is (B,H,N,P)
        else initial_state.astype(jnp.float32).transpose(0, 1, 3, 2)
    )
    final, prev_states = jax.lax.scan(
        body,
        s0,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
        unroll=unroll,
    )
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)     # (B,nc,H,N,P)

    y_off = jnp.einsum(
        "bcin,bchnp,bcih->bcihp",
        cc.astype(jnp.float32), prev_states, jnp.exp(cums),
    )
    y = (y_diag.astype(jnp.float32) + y_off).reshape(bsz, nc * q, h, p)[:, :s]
    final = final.transpose(0, 1, 3, 2)                    # (B,H,P,N)
    return y.astype(x.dtype), final


# ---------------------------------------------------------------------------
# RG-LRU linear recurrence (differentiable; associative-scan backward)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _rglru_op(impl, interpret):
    if impl == "pallas":
        fwd_impl = dispatch("rglru_scan", KernelConfig("pallas", interpret))
    else:
        fwd_impl = dispatch("rglru_scan", KernelConfig("jnp"))
    jnp_twin = ref.jnp_rglru_scan

    @jax.custom_vjp
    def op(a, b):
        return fwd_impl(a, b)

    def fwd(a, b):
        return fwd_impl(a, b), (a, b)

    def bwd(res, g):
        _, vjp = jax.vjp(jnp_twin, *res)
        return vjp(g)

    op.defvjp(fwd, bwd)
    return op


def rglru_scan(
    a: jax.Array,   # (B, S, W) f32 per-step decay
    b: jax.Array,   # (B, S, W) f32 per-step input
    *,
    config: KernelConfig | None = None,
) -> jax.Array:
    """Inclusive scan of h_t = a_t·h_{t-1} + b_t over axis 1 (zero h_0)."""
    impl, interpret = _resolve(config)
    return _rglru_op(impl, interpret)(a, b)


# ---------------------------------------------------------------------------
# Fused NoLoCo outer update (Eqs. 2–3 over group statistics)
# ---------------------------------------------------------------------------


def noloco_update_pytree(
    phi,
    delta_mom,
    mean_delta,
    mean_phi,
    *,
    alpha: float,
    beta: float,
    gamma: float,
    config: KernelConfig | None = None,
):
    """Fused Eqs. 2–3 over whole pytrees; returns (phi_next, delta_next).

    The update is elementwise, so leaves are raveled per-leaf into the 1-D
    kernel (leaves are large enough that launch overhead is negligible;
    stacked leaves with a leading replica axis ravel correctly too).  Not
    differentiated — the outer step sits outside jax.grad."""
    impl, interpret = _resolve(config)
    flat_phi, treedef = jax.tree.flatten(phi)
    dms = jax.tree.leaves(delta_mom)
    mds = jax.tree.leaves(mean_delta)
    mps = jax.tree.leaves(mean_phi)
    if impl == "pallas":
        fn = dispatch("noloco_update", KernelConfig("pallas", interpret))
        new_phi, new_delta = [], []
        for p, d, md, mp in zip(flat_phi, dms, mds, mps):
            np_, nd_ = fn(
                p.ravel(), d.ravel(), md.ravel(), mp.ravel(),
                alpha=alpha, beta=beta, gamma=gamma,
            )
            new_phi.append(np_.reshape(p.shape))
            new_delta.append(nd_.reshape(p.shape))
    else:
        fn = dispatch("noloco_update", KernelConfig("jnp"))
        pairs = [
            fn(p, d, md, mp, alpha=alpha, beta=beta, gamma=gamma)
            for p, d, md, mp in zip(flat_phi, dms, mds, mps)
        ]
        new_phi = [a for a, _ in pairs]
        new_delta = [b for _, b in pairs]
    return (
        jax.tree.unflatten(treedef, new_phi),
        jax.tree.unflatten(treedef, new_delta),
    )


# ---------------------------------------------------------------------------
# Serving decode ops (inference-only: no vjp — they sit outside jax.grad)
# ---------------------------------------------------------------------------


def paged_impl(
    q_heads: int, kv_heads: int, config: KernelConfig | None = None
) -> KernelConfig:
    """The implementation the paged ops run for ``q_heads`` query heads over
    a pool of ``kv_heads``: the resolved config, except that the Pallas
    kernels fold whole GQA groups, so H % KV != 0 routes to the jnp twin."""
    impl, interpret = _resolve(config)
    if impl == "pallas" and q_heads % kv_heads == 0:
        return KernelConfig("pallas", interpret)
    return KernelConfig("jnp")


def paged_attention(
    q: jax.Array,             # (R, H, D) one decode token per request slot
    k_pages: jax.Array,       # (NP, KV, BS, D) page pool
    v_pages: jax.Array,       # (NP, KV, BS, D)
    block_tables: jax.Array,  # (R, MB) int32 page ids per slot
    positions: jax.Array,     # (R,) int32 current token position per slot
    *,
    mode: str = "causal",
    window: int = 0,
    config: KernelConfig | None = None,
) -> jax.Array:
    """Paged decode attention: K/V gathered through per-slot block tables.

    Masking is positional (key j valid iff j <= positions[r], plus the
    sliding window for local layers), so trash-page writes and unallocated
    table entries never contribute.  The Pallas kernel requires H % KV == 0
    (GQA folding); ragged head counts route to the jnp twin."""
    impl = paged_impl(q.shape[1], k_pages.shape[1], config)
    return dispatch("paged_attention", impl)(
        q, k_pages, v_pages, block_tables, positions, mode=mode, window=window
    )


def paged_chunk_attention(
    q: jax.Array,             # (R, C, H, D) one prefill chunk per request slot
    k_pages: jax.Array,       # (NP, KV, BS, D) page pool
    v_pages: jax.Array,       # (NP, KV, BS, D)
    block_tables: jax.Array,  # (R, MB) int32 page ids per slot
    positions: jax.Array,     # (R,) int32 base position of chunk token 0
    *,
    mode: str = "causal",
    window: int = 0,
    config: KernelConfig | None = None,
) -> jax.Array:
    """Chunked paged prefill attention: C query tokens per slot against the
    paged KV pool, chunk token c querying at ``positions[r] + c``.

    Ragged last chunks are handled upstream: tokens past a slot's valid
    length scatter to the trash page and their output rows are discarded, so
    ONE fixed-C program covers every prompt-length mix.  The Pallas kernel
    requires H % KV == 0; ragged head counts route to the jnp twin."""
    impl = paged_impl(q.shape[2], k_pages.shape[1], config)
    return dispatch("paged_chunk_attention", impl)(
        q, k_pages, v_pages, block_tables, positions, mode=mode, window=window
    )


def rglru_decode(
    h: jax.Array,   # (R, W) recurrent state
    a: jax.Array,   # (R, W) per-token decay
    b: jax.Array,   # (R, W) per-token input
    *,
    config: KernelConfig | None = None,
) -> jax.Array:
    """Single RG-LRU decode step h' = a·h + b across request slots (f32)."""
    impl, interpret = _resolve(config)
    if impl == "pallas":
        return dispatch("rglru_decode", KernelConfig("pallas", interpret))(h, a, b)
    return dispatch("rglru_decode", KernelConfig("jnp"))(h, a, b)


def ssd_decode(
    state: jax.Array,   # (R, H, P, N) f32 recurrent state
    dt1: jax.Array,     # (R, H) positive step sizes for this token
    a: jax.Array,       # (H,) negative decay rates
    b1: jax.Array,      # (R, N) input projection for this token
    c1: jax.Array,      # (R, N) output projection for this token
    x1: jax.Array,      # (R, H, P) conv+silu'd input for this token
    *,
    config: KernelConfig | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Single SSD decode step at model layout; returns (state', y).

    state' = exp(dt·a)·state + dt·(x ⊗ B),  y = state'·C — the per-head
    decay/input are broadcast to channel granularity (H·P) here so the
    dispatched kernel is a pure fused elementwise + contraction over slots."""
    impl, interpret = _resolve(config)
    r, h, p, n = state.shape
    decay = jnp.repeat(jnp.exp(dt1.astype(jnp.float32) * a[None, :]), p, axis=1)
    dtx = (dt1.astype(jnp.float32)[..., None] * x1.astype(jnp.float32)).reshape(r, h * p)
    flat = state.reshape(r, h * p, n)
    if impl == "pallas":
        st, y = dispatch("ssd_decode", KernelConfig("pallas", interpret))(
            flat, decay, dtx, b1, c1
        )
    else:
        st, y = dispatch("ssd_decode", KernelConfig("jnp"))(flat, decay, dtx, b1, c1)
    return st.reshape(r, h, p, n), y.reshape(r, h, p)


# ---------------------------------------------------------------------------
# int8 wire codec kernels (consumed by comm/compress.py)
# ---------------------------------------------------------------------------


def int8_quantize(x: jax.Array, *, config: KernelConfig | None = None):
    """(NC, CHUNK) f32 → (q uint8, scale f32 (NC,), lo f32 (NC,))."""
    impl, interpret = _resolve(config)
    if impl == "pallas":
        return dispatch("int8_quantize", KernelConfig("pallas", interpret))(x)
    return dispatch("int8_quantize", KernelConfig("jnp"))(x)


def int8_dequantize(
    q: jax.Array, scale: jax.Array, lo: jax.Array,
    *, config: KernelConfig | None = None,
):
    """Inverse of :func:`int8_quantize` → (NC, CHUNK) f32."""
    impl, interpret = _resolve(config)
    if impl == "pallas":
        return dispatch("int8_dequantize", KernelConfig("pallas", interpret))(q, scale, lo)
    return dispatch("int8_dequantize", KernelConfig("jnp"))(q, scale, lo)
