"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle.

Dispatch-level parity (pallas vs jnp twin per registered op, gradients,
end-to-end toy-LM) lives in tests/test_dispatch.py; here each Pallas kernel
is pinned explicitly and checked against the naive oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, ops, ref
from repro.kernels.dispatch import KernelConfig

KEY = jax.random.PRNGKey(0)
PALLAS = KernelConfig(impl="pallas", interpret=True)


def _qkv(b, sq, sk, h, kv, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, sk, kv, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, sk, kv, d), jnp.float32).astype(dtype)
    return q, k, v


def _gold_attention(q, k, v, mode, window):
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    hm = (jnp.arange(h) * kvh) // h
    ke, ve = jnp.take(k, hm, 2), jnp.take(v, hm, 2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = ke.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
    vf = ve.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
    g = ref.reference_attention(qf, kf, vf, mode=mode, window=window)
    return g.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("shape", [
    # (b, sq, sk, h, kv, d, forced (block_q, block_kv) or None for the plan, dtype)
    (1, 128, 128, 4, 4, 64, None, jnp.float32),
    (2, 256, 256, 4, 2, 64, None, jnp.float32),    # GQA (grouped fold, no K/V expansion)
    (1, 256, 256, 2, 1, 128, None, jnp.float32),   # MQA, d=128
    (1, 200, 200, 2, 2, 64, None, jnp.float32),    # non-block-multiple
    (1, 128, 384, 2, 2, 64, None, jnp.float32),    # cross lengths
    # small unequal tiles, so that many tile pairs are skipped
    (1, 1024, 1024, 4, 2, 64, (256, 128), jnp.float32),   # folded GQA
    (1, 1024, 1024, 2, 2, 64, (128, 256), jnp.float32),
    (1, 200, 200, 2, 2, 64, (64, 128), jnp.float32),
    (1, 128, 384, 2, 2, 64, (64, 128), jnp.float32),
    (1, 1024, 1024, 4, 2, 64, (256, 128), jnp.bfloat16),  # bf16 in, f32 reference
])
@pytest.mark.parametrize(
    "mode,window", [("causal", 0), ("local", 64), ("full", 0), ("local", 300)]
)
def test_flash_attention_sweep(shape, mode, window):
    b, sq, sk, h, kv, d, tiles, dtype = shape
    block_q, block_kv = tiles or (None, None)
    q, k, v = _qkv(b, sq, sk, h, kv, d, dtype)
    out = ops.flash_attention(q, k, v, mode=mode, window=window,
                              block_q=block_q, block_kv=block_kv, config=PALLAS)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    gold = _gold_attention(*f32, mode, window)
    atol, rtol = (3e-5, 1e-4) if dtype == jnp.float32 else (1e-2, 1e-2)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(gold), atol=atol, rtol=rtol
    )


def _brute_bounds(sq_pad, sk, q_stride, bq, bk, mode, window):
    """(live, dense) kv-tile index sets of each q tile, from the mask."""
    q_pos = np.arange(sq_pad) % q_stride
    k_pos = np.arange(-(-sk // bk) * bk)
    valid = (k_pos[None, :] < sk) & np.ones((sq_pad, 1), bool)
    if mode != "full":
        valid &= k_pos[None, :] <= q_pos[:, None]
    if mode == "local":
        valid &= k_pos[None, :] > q_pos[:, None] - window
    out = []
    for qi in range(sq_pad // bq):
        rows = valid[qi * bq:(qi + 1) * bq]
        tiles = [rows[:, j * bk:(j + 1) * bk] for j in range(len(k_pos) // bk)]
        out.append(({j for j, t in enumerate(tiles) if t.any()},
                    {j for j, t in enumerate(tiles) if t.all()}))
    return out


@pytest.mark.parametrize("sq_pad,sk,d,mode,window,q_stride,tiles", [
    (2048, 2048, 64, "causal", 0, 2048, None),          # r1's shape, planned tiles
    (2048, 2048, 64, "causal", 0, 2048, (128, 128)),    # r1's shape, the old tiles
    (2048, 2048, 64, "local", 300, 2048, None),
    (2048, 2048, 128, "full", 0, 2048, None),
    (4096, 1024, 64, "causal", 0, 1024, (256, 128)),    # folded GQA, G = 4
    (4096, 1024, 64, "local", 64, 1024, (256, 128)),
    (4096, 1024, 64, "local", 300, 1024, (128, 256)),
    (4096, 1024, 64, "full", 0, 1024, (256, 128)),
    (512, 200, 64, "causal", 0, 256, (64, 128)),        # non-multiple length
    (512, 200, 64, "local", 64, 256, None),
    (128, 384, 64, "causal", 0, 128, (64, 128)),        # cross lengths
    (128, 384, 64, "full", 0, 128, None),
    (8192, 8192, 256, "causal", 0, 8192, None),         # widest head
])
def test_flash_plan_counts_live_tiles(sq_pad, sk, d, mode, window, q_stride, tiles):
    block_q, block_kv = tiles or (None, None)
    p = flash_attention.plan(sq_pad, sk, d, mode, window, q_stride,
                             block_q=block_q, block_kv=block_kv)
    if tiles is None:
        assert q_stride % p.block_q == 0
        assert (-(-sk // 128) * 128) % p.block_kv == 0
        assert p.block_q in flash_attention.Q_TILES
        assert p.block_kv in flash_attention.KV_TILES
        assert flash_attention.vmem_bytes(p.block_q, p.block_kv, d) <= flash_attention.VMEM_LIMIT
    else:
        assert (p.block_q, p.block_kv) == tiles
    brute = _brute_bounds(sq_pad, sk, q_stride, p.block_q, p.block_kv, mode, window)
    nk = -(-sk // p.block_kv)
    assert p.tiles == len(brute) * nk
    assert p.live_tiles == sum(len(live) for live, _ in brute)
    for qi, (live, dense) in enumerate(brute):
        lo, hi, dlo, dhi = p.bounds[qi % len(p.bounds)]
        assert set(range(lo, hi + 1)) == live
        assert set(range(dlo, dhi + 1)) == dense
    if (sq_pad, sk, mode, tiles) == (2048, 2048, "causal", None):
        assert (p.block_q, p.block_kv, p.live_tiles, p.tiles) == (512, 512, 10, 16)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 3e-5), (jnp.bfloat16, 3e-2)])
def test_flash_attention_dtypes(dtype, atol):
    q, k, v = _qkv(1, 128, 128, 4, 2, 64, dtype)
    out = ops.flash_attention(q, k, v, mode="causal", config=PALLAS)
    gold = _gold_attention(q, k, v, "causal", 0)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(gold, np.float32), atol=atol, rtol=1e-2
    )


@pytest.mark.parametrize("n", [100, 4096, 10_000, 50_000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_noloco_update_sweep(n, dtype):
    phi, dmom, mean_d, mean_phi = [
        jax.random.normal(jax.random.fold_in(KEY, i), (n,), jnp.float32).astype(dtype)
        for i in range(4)
    ]
    p1, d1 = ops.noloco_update_pytree(
        {"w": phi}, {"w": dmom}, {"w": mean_d}, {"w": mean_phi},
        alpha=0.5, beta=0.7, gamma=1.0, config=PALLAS,
    )
    p2, d2 = ref.reference_noloco_update(
        phi, dmom, mean_d, mean_phi, alpha=0.5, beta=0.7, gamma=1.0
    )
    atol = 1e-6 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(p1["w"], np.float32), np.asarray(p2, np.float32), atol=atol)
    np.testing.assert_allclose(np.asarray(d1["w"], np.float32), np.asarray(d2, np.float32), atol=atol)


def test_noloco_kernel_matches_outer_module():
    """Kernel must agree with the core outer optimizer (same Eqs. 2-3)."""
    from repro.core import outer as outer_lib

    n = 1000
    args = [jax.random.normal(jax.random.fold_in(KEY, 10 + i), (n,)) for i in range(5)]
    theta, phi, dmom, theta_p, phi_p = args
    mean_d = {"w": 0.5 * ((theta - phi) + (theta_p - phi_p))}
    mean_phi = {"w": 0.5 * (phi + phi_p)}
    p1, d1 = ops.noloco_update_pytree(
        {"w": phi}, {"w": dmom}, mean_d, mean_phi,
        alpha=0.5, beta=0.7, gamma=1.0, config=PALLAS,
    )
    p2, d2 = outer_lib.noloco_momentum_update(
        {"w": phi}, {"w": dmom}, mean_d, mean_phi, alpha=0.5, beta=0.7, gamma=1.0
    )
    np.testing.assert_allclose(np.asarray(p1["w"]), np.asarray(p2["w"]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(d1["w"]), np.asarray(d2["w"]), atol=1e-5)


@pytest.mark.parametrize("shape", [
    (1, 64, 2, 16, 8, 32),
    (2, 96, 2, 16, 8, 32),    # pad (96 = 3 chunks of 32)
    (1, 130, 1, 8, 4, 64),    # non-multiple length
])
def test_ssd_chunk_kernel_sweep(shape):
    b, s, h, p, n, chunk = shape
    x = jax.random.normal(jax.random.fold_in(KEY, 20), (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 21), (b, s, h))) * 0.1
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 22), (h,)) * 0.3)
    bm = jax.random.normal(jax.random.fold_in(KEY, 23), (b, s, n)) * 0.5
    cm = jax.random.normal(jax.random.fold_in(KEY, 24), (b, s, n)) * 0.5
    y1, f1 = ops.ssd_chunk(x, dt, a, bm, cm, chunk=chunk, config=PALLAS)
    y2, f2 = ref.reference_ssd(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("shape", [
    (2, 64, 32),
    (1, 300, 128),   # seq pad (300 -> 2 chunks of 256)
    (2, 257, 130),   # seq + width pad
])
def test_rglru_scan_kernel_sweep(shape):
    b, s, w = shape
    a = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(KEY, 40), (b, s, w))) * 0.5 + 0.45
    bb = jax.random.normal(jax.random.fold_in(KEY, 41), (b, s, w)) * 0.3
    h1 = ops.rglru_scan(a, bb, config=PALLAS)
    h2 = ref.jnp_rglru_scan(a, bb)
    # serial oracle
    def step(h, inp):
        at, bt = inp
        h = at * h + bt
        return h, h
    _, h3 = jax.lax.scan(step, jnp.zeros((b, w)), (a.transpose(1, 0, 2), bb.transpose(1, 0, 2)))
    h3 = h3.transpose(1, 0, 2)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h3), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h3), atol=1e-5, rtol=1e-5)


def test_int8_kernel_roundtrip():
    x = jax.random.normal(jax.random.fold_in(KEY, 50), (37, 256))
    q, scale, lo = ops.int8_quantize(x, config=PALLAS)
    qj, sj, lj = ref.jnp_int8_quantize(x)
    # reduction-order float differences may flip a rounding boundary: q within
    # one level, metadata tight, decode within one quantization step
    assert int(jnp.abs(q.astype(jnp.int32) - qj.astype(jnp.int32)).max()) <= 1
    np.testing.assert_allclose(np.asarray(scale), np.asarray(sj), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(lo), np.asarray(lj), rtol=1e-6, atol=1e-6)
    dec = ops.int8_dequantize(q, scale, lo, config=PALLAS)
    err = jnp.abs(dec - x)
    assert float((err - 1.01 * scale[:, None]).max()) <= 0.0


def test_models_ssd_matches_oracle_too():
    """The model-level wrapper (models/ssd.ssd_chunked) delegates to the
    dispatched op; it must match the token-recurrence oracle as well."""
    from repro.models.ssd import ssd_chunked

    b, s, h, p, n = 2, 64, 2, 16, 8
    x = jax.random.normal(jax.random.fold_in(KEY, 30), (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 31), (b, s, h))) * 0.1
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 32), (h,)) * 0.3)
    bm = jax.random.normal(jax.random.fold_in(KEY, 33), (b, s, n)) * 0.5
    cm = jax.random.normal(jax.random.fold_in(KEY, 34), (b, s, n)) * 0.5
    y1, f1 = ssd_chunked(x, dt, a, bm, cm, 16)
    y2, f2 = ref.reference_ssd(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4, rtol=1e-3)
