"""Kernel bench: the DISPATCHED production path of every registered op (what
models/core/comm actually run — resolved per ``impl="auto"``, so the jnp
twins on this CPU box and the Pallas kernels on TPU), plus the naive oracles
for reference and derived TPU roofline estimates.  Interpret-mode Pallas
timing is meaningless on CPU, so no forced-pallas numbers are recorded.

Writes BENCH_kernels.json (registered in benchmarks/run.py; part of the CI
bench-smoke job) so the production-path perf trajectory is tracked per PR.
"""
import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.comm import CommConfig, get_codec
from repro.kernels import ops, ref
from repro.kernels.dispatch import KernelConfig, default_config
from repro.launch.roofline import HBM_BW, PEAK_FLOPS

OUT = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernels.json")

_RESULTS: dict[str, dict] = {}


def _time(fn, *args, iters=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _record(name: str, us: float, derived: str) -> None:
    emit(name, us, derived)
    _RESULTS[name] = {"us_per_call": round(us, 3), "derived": derived}


def require_route(name: str, routed: KernelConfig, impl: str) -> None:
    """Refuse to record a row under ``impl`` when the op's shapes would send
    it to another implementation."""
    if routed.resolved_impl() != impl:
        raise RuntimeError(
            f"{name}: these shapes route to impl={routed.resolved_impl()}, "
            f"not the impl={impl} this bench records"
        )


def main() -> None:
    key = jax.random.PRNGKey(0)
    impl = default_config().resolved_impl()

    # -- flash attention: b=1 h=8 kv=2 s=1024 d=128 (GQA production path) ---
    b, s, h, kv, d = 1, 1024, 8, 2, 128
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, d), jnp.float32)
    fn = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, mode="causal"))
    us = _time(fn, q, k, v)
    flops = 4 * b * h * s * s * d  # qk + pv
    tpu_us = flops / PEAK_FLOPS * 1e6
    _record("kernel_flash_attn_s1024_gqa", us,
            f"impl={impl};flops={flops:.3g};tpu_roofline_us={tpu_us:.1f}")

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    fn_ref = jax.jit(lambda q: ref.reference_attention(q, q, q, mode="causal"))
    us_ref = _time(fn_ref, qf)
    _record("kernel_flash_attn_s1024_oracle", us_ref, "naive_full_softmax")

    # -- fused noloco update: n = 16M params -------------------------------
    n = 1 << 24
    xs = [jax.random.normal(jax.random.fold_in(key, i), (n,), jnp.bfloat16)
          for i in range(4)]
    fn2 = jax.jit(lambda *a: ops.noloco_update_pytree(
        {"w": a[0]}, {"w": a[1]}, {"w": a[2]}, {"w": a[3]},
        alpha=0.5, beta=0.7, gamma=1.0))
    us2 = _time(fn2, *xs)
    bytes_moved = n * 2 * 6  # 4 reads + 2 writes bf16
    tpu_us2 = bytes_moved / HBM_BW * 1e6
    _record("kernel_noloco_update_16M", us2,
            f"impl={impl};bytes={bytes_moved:.3g};tpu_roofline_us={tpu_us2:.1f}")

    # -- ssd: b=1 s=512 h=4 p=64 n=64, dispatched chunked path --------------
    x = jax.random.normal(key, (1, 512, 4, 64)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 9), (1, 512, 4))) * 0.1
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 8), (4,)) * 0.3)
    bm = jax.random.normal(jax.random.fold_in(key, 7), (1, 512, 64)) * 0.3
    cm = jax.random.normal(jax.random.fold_in(key, 6), (1, 512, 64)) * 0.3
    fn3 = jax.jit(lambda *args: ops.ssd_chunk(*args, chunk=128)[0])
    us3 = _time(fn3, x, dt, a, bm, cm)
    _record("kernel_ssd_s512", us3, f"impl={impl};chunked_production_path")
    fn3r = jax.jit(lambda *args: ref.reference_ssd(*args)[0])
    us3r = _time(fn3r, x, dt, a, bm, cm)
    _record("kernel_ssd_s512_oracle", us3r, "token_recurrence")

    # -- rglru scan: b=1 s=2048 w=512 --------------------------------------
    ar = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 11), (1, 2048, 512))) * 0.5 + 0.45
    br = jax.random.normal(jax.random.fold_in(key, 12), (1, 2048, 512)) * 0.3
    fn4 = jax.jit(lambda a, b: ops.rglru_scan(a, b))
    us4 = _time(fn4, ar, br)
    _record("kernel_rglru_scan_s2048", us4, f"impl={impl};linear_recurrence")

    # -- serving hot-loop ops: R=8 slots, pool 256 pages x 16 tokens --------
    # These are the per-token ops of the ServeEngine decode step and the
    # per-chunk op of chunked prefill — the serving-side counterparts of the
    # training kernels above.
    r, np_, bs, kvh, hq, d = 8, 256, 16, 2, 8, 128
    mbk = 64
    # kv-head-major pools, (NP, KV, BS, D), as models/attention.PagedAttnCache
    kp = jax.random.normal(jax.random.fold_in(key, 20), (np_ + 1, kvh, bs, d)) * 0.3
    vp = jax.random.normal(jax.random.fold_in(key, 21), (np_ + 1, kvh, bs, d)) * 0.3
    require_route("paged_attention", ops.paged_impl(hq, kp.shape[1]), impl)
    tables = jax.random.randint(jax.random.fold_in(key, 22), (r, mbk), 0, np_)
    pos = jnp.full((r,), mbk * bs // 2, jnp.int32)
    qd = jax.random.normal(jax.random.fold_in(key, 23), (r, hq, d))
    fnp = jax.jit(lambda *a: ops.paged_attention(*a, mode="causal"))
    usp = _time(fnp, qd, kp, vp, tables, pos)
    read = r * (mbk * bs // 2) * kvh * d * 4 * 2  # K+V f32 up to position
    _record("kernel_paged_attn_decode_r8", usp,
            f"impl={impl};kv_bytes={read:.3g};tpu_roofline_us={read / HBM_BW * 1e6:.1f}")

    cch = 32
    qc = jax.random.normal(jax.random.fold_in(key, 24), (r, cch, hq, d))
    require_route("paged_chunk_attention", ops.paged_impl(hq, kp.shape[1]), impl)
    fnc = jax.jit(lambda *a: ops.paged_chunk_attention(*a, mode="causal"))
    usc = _time(fnc, qc, kp, vp, tables, pos)
    _record("kernel_paged_attn_chunk_r8_c32", usc,
            f"impl={impl};per_token_us={usc / (r * cch):.2f};"
            f"decode_equiv_us={usp * cch:.1f}")

    w = 2048
    hr = jax.random.normal(jax.random.fold_in(key, 25), (r, w))
    ag = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(key, 26), (r, w)))
    bg = jax.random.normal(jax.random.fold_in(key, 27), (r, w)) * 0.3
    fnr = jax.jit(lambda *a: ops.rglru_decode(*a))
    usr = _time(fnr, hr, ag, bg)
    _record("kernel_rglru_decode_r8_w2048", usr, f"impl={impl};fused_state_update")

    hh, p, nn = 8, 64, 64
    st = jax.random.normal(jax.random.fold_in(key, 28), (r, hh, p, nn)) * 0.1
    dt1 = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 29), (r, hh))) * 0.1
    ad = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 30), (hh,)) * 0.3)
    b1 = jax.random.normal(jax.random.fold_in(key, 31), (r, nn)) * 0.3
    c1 = jax.random.normal(jax.random.fold_in(key, 32), (r, nn)) * 0.3
    x1 = jax.random.normal(jax.random.fold_in(key, 33), (r, hh, p)) * 0.3
    fns = jax.jit(lambda *a: ops.ssd_decode(*a)[1])
    uss = _time(fns, st, dt1, ad, b1, c1, x1)
    sbytes = r * hh * p * nn * 4 * 2  # state read + write dominates
    _record("kernel_ssd_decode_r8", uss,
            f"impl={impl};state_bytes={sbytes:.3g};"
            f"tpu_roofline_us={sbytes / HBM_BW * 1e6:.1f}")

    # -- comm codecs: encode+decode round trip of a 16M-element fp32 gossip
    # buffer through the production codec object (int8 runs the dispatched
    # quantize kernels), plus the exact wire-byte reduction.
    n = 1 << 24
    buf = jax.random.normal(jax.random.fold_in(key, 10), (n,), jnp.float32)
    for name in ("fp16", "int8"):
        cfg = CommConfig(codec=name)
        codec = get_codec(cfg)
        rt = jax.jit(lambda b: codec.decode(codec.encode(b), jnp.float32, n))
        us5 = _time(rt, buf)
        wire = codec.wire_bytes(n, jnp.float32)
        raw = n * 4
        tpu_us5 = (raw + wire) / HBM_BW * 1e6  # read raw + write wire
        _record(f"kernel_comm_codec_{name}_16M", us5,
                f"impl={impl};wire_bytes={wire:.3g};reduction={raw / wire:.2f}x;"
                f"tpu_roofline_us={tpu_us5:.1f}")

    with open(OUT, "w") as f:
        json.dump(
            {"impl": impl, "backend": jax.default_backend(), "kernels": _RESULTS},
            f, indent=2,
        )


if __name__ == "__main__":
    main()
