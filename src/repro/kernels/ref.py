"""jnp implementations for every Pallas kernel.

Two kinds of function live here, both pure jnp:

  * ``reference_*`` — naive ORACLES (the allclose ground truth for tests;
    O(S²) memory where that is the simplest correct thing).
  * ``jnp_*``       — PRODUCTION fallbacks registered in
    :mod:`repro.kernels.dispatch` as the ``impl="jnp"`` path of each op and
    used as the ``custom_vjp`` backward of the differentiable ops.  These are
    memory-bounded twins of the Pallas kernels (online softmax, chunked
    forms) and must match the kernels' shapes/dtypes exactly.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def reference_attention(
    q: jax.Array,   # (BH, Sq, D)
    k: jax.Array,   # (BH, Sk, D)
    v: jax.Array,   # (BH, Sk, D)
    *,
    mode: str = "causal",
    window: int = 0,
) -> jax.Array:
    """Naive full-softmax attention (O(S²) memory — oracle only)."""
    d = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / math.sqrt(d)
    sq, sk = q.shape[1], k.shape[1]
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(sk)[None, :]
    if mode == "causal":
        valid = kp <= qp
    elif mode == "local":
        valid = (kp <= qp) & (kp > qp - window)
    else:
        valid = jnp.ones((sq, sk), bool)
    s = jnp.where(valid[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def jnp_flash_attention(
    q: jax.Array,   # (B, Sq, H, D)
    k: jax.Array,   # (B, Sk, KV, D)
    v: jax.Array,   # (B, Sk, KV, D)
    *,
    mode: str = "causal",
    window: int = 0,
    block_kv: int = 512,
    unroll: bool = False,
) -> jax.Array:
    """Online-softmax attention scanned over KV blocks, GQA-grouped.

    The model-layout twin of :func:`repro.kernels.flash_attention.
    pallas_flash_attention`: same (B, Sq, H, D) signature, same grouped K/V
    (never expanded to query-head width when H % KV == 0), O(S) memory.
    Positions are implicit ``arange`` — the training/prefill case; the cache
    paths with explicit positions live in :mod:`repro.models.attention`.
    """
    b, sq, h, d = q.shape
    sk, kvh = k.shape[1], k.shape[2]
    if h % kvh:
        head_map = (jnp.arange(h) * kvh) // h
        k = jnp.take(k, head_map, axis=2)
        v = jnp.take(v, head_map, axis=2)
        kvh = h
    g = h // kvh
    scale = 1.0 / math.sqrt(d)
    qg = (q.astype(jnp.float32) * scale).reshape(b, sq, kvh, g, d)
    qg = qg.transpose(0, 2, 3, 1, 4)                     # (B, KV, G, Sq, D)

    nblk = max(1, math.ceil(sk / block_kv))
    pad = nblk * block_kv - sk
    kv_positions = jnp.arange(sk, dtype=jnp.int32)
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad), constant_values=-(10**9))
    kb = k.reshape(b, nblk, block_kv, kvh, d).transpose(1, 0, 3, 2, 4)  # (n,B,KV,Bk,D)
    vb = v.reshape(b, nblk, block_kv, kvh, d).transpose(1, 0, 3, 2, 4)
    pb = kv_positions.reshape(nblk, block_kv)

    q_pos = jnp.arange(sq, dtype=jnp.int32)[:, None]

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, kpos = blk
        s = jnp.einsum("bkgqd,bktd->bkgqt", qg, kblk.astype(jnp.float32))
        kp = kpos[None, :]
        valid = kp >= 0
        if mode == "causal":
            valid &= kp <= q_pos
        elif mode == "local":
            valid &= (kp <= q_pos) & (kp > q_pos - window)
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgqt,bktd->bkgqd", p, vblk.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32),
        jnp.zeros((b, kvh, g, sq), jnp.float32),
        jnp.zeros((b, kvh, g, sq, d), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, (kb, vb, pb), unroll=unroll)
    out = acc / jnp.maximum(l[..., None], 1e-30)          # (B,KV,G,Sq,D)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)


def _gather_pages(pages: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Dense (R, MB·BS, KV, D) view of each slot's pages from a (NP, KV, BS,
    D) pool through its (R, MB) block table."""
    r, mb = block_tables.shape
    _, kvh, bs, d = pages.shape
    x = jnp.take(pages, block_tables, axis=0)            # (R, MB, KV, BS, D)
    return x.transpose(0, 1, 3, 2, 4).reshape(r, mb * bs, kvh, d)


def jnp_paged_attention(
    q: jax.Array,             # (R, H, D) — one decode token per request slot
    k_pages: jax.Array,       # (NP, KV, BS, D) — fixed-size KV pages (last = trash)
    v_pages: jax.Array,       # (NP, KV, BS, D)
    block_tables: jax.Array,  # (R, MB) int32 page index per logical block
    positions: jax.Array,     # (R,) int32 position of the incoming token
    *,
    mode: str = "causal",
    window: int = 0,
) -> jax.Array:
    """Decode-step paged attention — the jnp twin of
    :func:`repro.kernels.paged_attention.pallas_paged_attention`.

    Gathers each request's K/V pages through its block table into a dense
    (R, MB·BS, KV, D) view and runs one masked softmax per request slot; GQA
    groups the query heads over their kv head like :func:`jnp_flash_attention`
    (non-divisible head counts gather-expand, which the Pallas kernel does not
    support — the ops wrapper falls back here for those).  Valid keys are
    ``kv_pos <= positions[r]`` (and within ``window`` for local layers) — keys
    past the request's context, unallocated table entries and the trash page
    are all masked out by position alone."""
    r, h, d = q.shape
    kvh, bs = k_pages.shape[1], k_pages.shape[2]
    mb = block_tables.shape[1]
    k = _gather_pages(k_pages, block_tables)             # (R, MB·BS, KV, D)
    v = _gather_pages(v_pages, block_tables)
    if h % kvh:
        head_map = (jnp.arange(h) * kvh) // h
        k = jnp.take(k, head_map, axis=2)
        v = jnp.take(v, head_map, axis=2)
        kvh = h
    g = h // kvh
    scale = 1.0 / math.sqrt(d)
    qg = (q.astype(jnp.float32) * scale).reshape(r, kvh, g, d)

    kv_pos = jnp.arange(mb * bs, dtype=jnp.int32)[None, :]   # (1, T)
    pos = positions[:, None]                                  # (R, 1)
    valid = kv_pos <= pos
    if mode == "local":
        valid &= kv_pos > pos - window
    s = jnp.einsum("rkgd,rtkd->rkgt", qg, k.astype(jnp.float32))
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("rkgt,rtkd->rkgd", p, v.astype(jnp.float32))
    return out.reshape(r, h, d).astype(q.dtype)


def jnp_paged_chunk_attention(
    q: jax.Array,             # (R, C, H, D) — one prefill chunk per slot
    k_pages: jax.Array,       # (NP, KV, BS, D)
    v_pages: jax.Array,       # (NP, KV, BS, D)
    block_tables: jax.Array,  # (R, MB) int32
    positions: jax.Array,     # (R,) int32 — base position of chunk token 0
    *,
    mode: str = "causal",
    window: int = 0,
) -> jax.Array:
    """Chunked paged prefill attention — the jnp twin of
    :func:`repro.kernels.paged_attention.pallas_paged_chunk_attention`.

    Same dense block-table gather as :func:`jnp_paged_attention`, but with C
    query tokens per slot: chunk token c of slot r queries at absolute
    position ``positions[r] + c`` and sees keys ``kv_pos <= positions[r] + c``
    (windowed for local layers).  Ragged chunks need no extra masking here —
    rows past the slot's valid length produce garbage that the caller
    discards, and their K/V were scattered to the trash page."""
    r, c, h, d = q.shape
    kvh, bs = k_pages.shape[1], k_pages.shape[2]
    mb = block_tables.shape[1]
    k = _gather_pages(k_pages, block_tables)             # (R, MB·BS, KV, D)
    v = _gather_pages(v_pages, block_tables)
    if h % kvh:
        head_map = (jnp.arange(h) * kvh) // h
        k = jnp.take(k, head_map, axis=2)
        v = jnp.take(v, head_map, axis=2)
        kvh = h
    g = h // kvh
    scale = 1.0 / math.sqrt(d)
    qg = (q.astype(jnp.float32) * scale).reshape(r, c, kvh, g, d)

    kv_pos = jnp.arange(mb * bs, dtype=jnp.int32)[None, None, :]      # (1, 1, T)
    q_pos = positions[:, None, None] + jnp.arange(c, dtype=jnp.int32)[None, :, None]
    valid = kv_pos <= q_pos                                           # (R, C, T)
    if mode == "local":
        valid &= kv_pos > q_pos - window
    s = jnp.einsum("rckgd,rtkd->rckgt", qg, k.astype(jnp.float32))
    s = jnp.where(valid[:, :, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("rckgt,rtkd->rckgd", p, v.astype(jnp.float32))
    return out.reshape(r, c, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# NoLoCo outer update (Eqs. 2–3 over group means)
# ---------------------------------------------------------------------------


def reference_noloco_update(
    phi, delta_mom, mean_delta, mean_phi, *, alpha, beta, gamma
):
    """Eqs. 2–3 given the group statistics, with the appendix-consistent +β
    sign (see core/outer.py).  Shape-agnostic elementwise math — doubles as
    the ``impl="jnp"`` dispatch path of the fused kernel."""
    f = jnp.float32
    new_delta = (
        alpha * delta_mom.astype(f)
        + beta * mean_delta.astype(f)
        - gamma * (phi.astype(f) - mean_phi.astype(f))
    )
    new_phi = phi.astype(f) + new_delta
    return new_phi.astype(phi.dtype), new_delta.astype(delta_mom.dtype)


# ---------------------------------------------------------------------------
# SSD (Mamba-2)
# ---------------------------------------------------------------------------


def jnp_ssd_chunk_intra(
    x: jax.Array,     # (B, NC, Q, H, P)
    dt: jax.Array,    # (B, NC, Q, H)
    a: jax.Array,     # (H,)
    b_mat: jax.Array,  # (B, NC, Q, N)
    c_mat: jax.Array,  # (B, NC, Q, N)
) -> tuple[jax.Array, jax.Array]:
    """Intra-chunk quadratic form + per-chunk end states — the jnp twin of
    :func:`repro.kernels.ssd_scan.ssd_chunk_kernel`.

    Returns ``(y_diag (B,NC,Q,H,P) in x.dtype, states (B,NC,H,N,P) f32)``.
    """
    q = x.shape[2]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    bf = b_mat.astype(jnp.float32)
    cf = c_mat.astype(jnp.float32)

    da = dtf * a[None, None, None, :]                   # (B,NC,Q,H)
    cums = jnp.cumsum(da, axis=2)                       # inclusive
    diff = cums[:, :, :, None, :] - cums[:, :, None, :, :]   # (B,NC,Qi,Qj,H)
    tri = jnp.tril(jnp.ones((q, q), bool))
    l_kern = jnp.where(tri[None, None, :, :, None], jnp.exp(diff), 0.0)

    xdt = xf * dtf[..., None]                           # dt_j · x_j
    scores = jnp.einsum("bcin,bcjn->bcij", cf, bf)      # (B,NC,Q,Q)
    y_diag = jnp.einsum("bcij,bcijh,bcjhp->bcihp", scores, l_kern, xdt)

    decay_states = jnp.exp(cums[:, :, -1:, :] - cums)   # (B,NC,Q,H)
    states = jnp.einsum("bcjn,bcjh,bcjhp->bchnp", bf, decay_states, xdt)
    return y_diag.astype(x.dtype), states


def reference_ssd(
    x: jax.Array,     # (B, S, H, P)
    dt: jax.Array,    # (B, S, H)
    a: jax.Array,     # (H,) negative rates
    b_mat: jax.Array,  # (B, S, N)
    c_mat: jax.Array,  # (B, S, N)
    initial_state: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Token-by-token SSM recurrence (the gold semantics of SSD):
        h_t = exp(dt_t·a)·h_{t-1} + dt_t·(B_t ⊗ x_t);   y_t = C_t · h_t
    Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    f = jnp.float32
    h0 = (
        jnp.zeros((bsz, h, p, n), f)
        if initial_state is None
        else initial_state.astype(f)
    )

    def step(state, inp):
        xt, dtt, bt, ct = inp
        decay = jnp.exp(dtt * a[None, :])                        # (B,H)
        upd = jnp.einsum("bh,bn,bhp->bhpn", dtt, bt, xt)
        state = state * decay[:, :, None, None] + upd
        y = jnp.einsum("bn,bhpn->bhp", ct, state)
        return state, y

    xs = (
        x.astype(f).transpose(1, 0, 2, 3),
        dt.astype(f).transpose(1, 0, 2),
        b_mat.astype(f).transpose(1, 0, 2),
        c_mat.astype(f).transpose(1, 0, 2),
    )
    final, ys = jax.lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), final


# ---------------------------------------------------------------------------
# RG-LRU linear recurrence
# ---------------------------------------------------------------------------


def jnp_rglru_scan(a: jax.Array, b: jax.Array) -> jax.Array:
    """Inclusive scan of h_t = a_t · h_{t-1} + b_t over axis 1 (zero h_0) via
    ``jax.lax.associative_scan`` — the jnp twin of
    :func:`repro.kernels.rglru_scan.pallas_rglru_scan`.  a, b: (B, S, W);
    returns f32 like the kernel (its accumulator dtype) for any input dtype."""

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(
        combine, (a.astype(jnp.float32), b.astype(jnp.float32)), axis=1
    )
    return h


# ---------------------------------------------------------------------------
# Single-token decode state updates (serving hot loop)
# ---------------------------------------------------------------------------


def jnp_rglru_decode(h: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """One RG-LRU decode step  h' = a ⊙ h + b  over (R, W) slot states — the
    jnp twin of :func:`repro.kernels.decode_update.pallas_rglru_decode`.
    Returns f32 like the training scan kernel's accumulator."""
    return a.astype(jnp.float32) * h.astype(jnp.float32) + b.astype(jnp.float32)


def jnp_ssd_decode(
    state: jax.Array,  # (R, H·P, N) f32 slot states, heads folded into rows
    decay: jax.Array,  # (R, H·P) exp(dt·a) broadcast over P
    dtx: jax.Array,    # (R, H·P) dt-scaled inputs (dt_h · x_{h,p})
    b: jax.Array,      # (R, N)
    c: jax.Array,      # (R, N)
) -> tuple[jax.Array, jax.Array]:
    """One SSD decode step over prepared per-slot operands — the jnp twin of
    :func:`repro.kernels.decode_update.pallas_ssd_decode`:

        state' = decay ⊙ state + dtx ⊗ b;   y = state' · c

    Returns ``(state' (R,H·P,N) f32, y (R,H·P) f32)``.  The model-level
    reshapes (head/dim folding, decay broadcast) live in
    :func:`repro.kernels.ops.ssd_decode`."""
    f = jnp.float32
    st = state.astype(f) * decay.astype(f)[..., None] + (
        dtx.astype(f)[..., None] * b.astype(f)[:, None, :]
    )
    y = jnp.einsum("rkn,rn->rk", st, c.astype(f))
    return st, y


# ---------------------------------------------------------------------------
# int8 per-chunk affine codec
# ---------------------------------------------------------------------------


def jnp_int8_quantize(x: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-row affine uint8 quantization of a (NC, CHUNK) f32 buffer.
    Returns ``(q uint8 (NC,CHUNK), scale f32 (NC,), lo f32 (NC,))`` with
    scale already made safe (1.0 for constant rows)."""
    lo = x.min(axis=1)
    scale = (x.max(axis=1) - lo) / 255.0
    safe = jnp.where(scale > 0.0, scale, 1.0)
    q = jnp.clip(jnp.round((x - lo[:, None]) / safe[:, None]), 0.0, 255.0)
    return q.astype(jnp.uint8), safe, lo


def jnp_int8_dequantize(q: jax.Array, scale: jax.Array, lo: jax.Array) -> jax.Array:
    """Inverse of :func:`jnp_int8_quantize`: (NC, CHUNK) f32."""
    return q.astype(jnp.float32) * scale[:, None] + lo[:, None]
