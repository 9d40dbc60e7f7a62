"""Kernel-dispatch layer: registry completeness, pallas-interpret vs jnp
parity for every registered op, gradient parity through the custom_vjp ops,
and end-to-end toy-LM loss parity with ``impl="pallas"`` interpret mode."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.compress import Int8Codec
from repro.core import outer as outer_lib
from repro.kernels import dispatch as dispatch_mod
from repro.kernels import ops, ref
from repro.kernels.dispatch import KernelConfig
from repro.models import model as model_api
from repro.models.common import values_of
from repro.models.config import ModelConfig
from repro.parallel.sharding import ShardCtx

KEY = jax.random.PRNGKey(7)
PALLAS = KernelConfig(impl="pallas", interpret=True)
JNP = KernelConfig(impl="jnp")

EXPECTED_OPS = {
    "flash_attention",
    "ssd_chunk",
    "rglru_scan",
    "noloco_update",
    "int8_quantize",
    "int8_dequantize",
    "paged_attention",
    "paged_chunk_attention",
    "rglru_decode",
    "ssd_decode",
}


# ---------------------------------------------------------------------------
# Registry / config resolution
# ---------------------------------------------------------------------------


def test_registry_is_complete():
    reg = dispatch_mod.registry()
    assert set(reg) == EXPECTED_OPS
    root = os.path.join(os.path.dirname(__file__), "..", "src", "repro")
    for op in reg.values():
        assert callable(op.pallas) and callable(op.jnp)
        assert op.consumers, f"{op.name} has no documented consumers"
        assert os.path.exists(os.path.join(root, op.pallas_file)), op.pallas_file


def test_config_resolution_rules():
    # this box is CPU: auto -> jnp, interpret -> True unless pinned
    assert jax.default_backend() != "tpu"
    assert KernelConfig().resolved_impl() == "jnp"
    assert KernelConfig("pallas").resolved_interpret() is True
    assert KernelConfig("pallas", interpret=False).resolved_interpret() is False
    with pytest.raises(ValueError):
        KernelConfig(impl="cuda").resolved_impl()
    # dispatch returns distinct callables per impl
    assert dispatch_mod.dispatch("rglru_scan", JNP) is ref.jnp_rglru_scan


# ---------------------------------------------------------------------------
# Per-op forward parity: pallas-interpret vs jnp twin
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,mode,window", [
    ((1, 128, 128, 4, 4, 64), "causal", 0),   # MHA
    ((2, 64, 64, 4, 2, 32), "causal", 0),     # GQA
    ((1, 96, 96, 4, 1, 32), "local", 32),     # MQA sliding window
    ((1, 64, 96, 2, 2, 32), "full", 0),       # cross lengths
])
def test_attention_impl_parity(shape, mode, window):
    b, sq, sk, h, kv, d = shape
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, h, d))
    k = jax.random.normal(ks[1], (b, sk, kv, d))
    v = jax.random.normal(ks[2], (b, sk, kv, d))
    op = ops.flash_attention(q, k, v, mode=mode, window=window, config=PALLAS)
    oj = ops.flash_attention(q, k, v, mode=mode, window=window, config=JNP)
    np.testing.assert_allclose(np.asarray(op), np.asarray(oj), atol=2e-5, rtol=1e-4)


def test_attention_gradient_parity_via_custom_vjp():
    """Gradients through the dispatched op (jnp online-softmax backward) must
    match differentiating the naive oracle — for BOTH forward impls."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))

    def oracle(q, k, v):
        b, sq, h, d = q.shape
        kvh = k.shape[2]
        hm = (jnp.arange(h) * kvh) // h
        ke, ve = jnp.take(k, hm, 2), jnp.take(v, hm, 2)
        qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
        kf = ke.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
        vf = ve.transpose(0, 2, 1, 3).reshape(b * h, -1, d)
        g = ref.reference_attention(qf, kf, vf, mode="causal")
        return g.reshape(b, h, sq, d).transpose(0, 2, 1, 3)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g_ref = jax.grad(loss(oracle), argnums=(0, 1, 2))(q, k, v)
    for cfg in (PALLAS, JNP):
        g = jax.grad(
            loss(lambda q, k, v: ops.flash_attention(q, k, v, config=cfg)),
            argnums=(0, 1, 2),
        )(q, k, v)
        for got, want in zip(g, g_ref):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=3e-5, rtol=1e-4
            )


def test_ssd_impl_parity():
    b, s, h, p, n, chunk = 2, 96, 2, 16, 8, 32
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, h))) * 0.1
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 3), (h,)) * 0.3)
    bm = jax.random.normal(jax.random.fold_in(KEY, 4), (b, s, n)) * 0.5
    cm = jax.random.normal(jax.random.fold_in(KEY, 5), (b, s, n)) * 0.5
    h0 = jax.random.normal(jax.random.fold_in(KEY, 6), (b, h, p, n)) * 0.2
    y1, f1 = ops.ssd_chunk(x, dt, a, bm, cm, chunk=chunk, initial_state=h0, config=PALLAS)
    y2, f2 = ops.ssd_chunk(x, dt, a, bm, cm, chunk=chunk, initial_state=h0, config=JNP)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), atol=2e-5, rtol=1e-4)


def test_rglru_impl_parity_and_gradients():
    b, s, w = 2, 80, 48
    a = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(KEY, 7), (b, s, w))) * 0.5 + 0.45
    bb = jax.random.normal(jax.random.fold_in(KEY, 8), (b, s, w)) * 0.3
    h1 = ops.rglru_scan(a, bb, config=PALLAS)
    h2 = ops.rglru_scan(a, bb, config=JNP)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-5, rtol=1e-5)

    def loss(fn):
        return lambda a, b: jnp.sum(fn(a, b) ** 2)

    g_ref = jax.grad(loss(ref.jnp_rglru_scan), argnums=(0, 1))(a, bb)
    for cfg in (PALLAS, JNP):
        g = jax.grad(
            loss(lambda a, b: ops.rglru_scan(a, b, config=cfg)), argnums=(0, 1)
        )(a, bb)
        for got, want in zip(g, g_ref):
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5
            )


def test_outer_step_stacked_kernel_parity():
    """The stacked gossip outer step must produce identical states whichever
    implementation backs the fused update."""
    world, n = 4, 257
    theta = {"w": jax.random.normal(jax.random.fold_in(KEY, 9), (world, n))}
    state = outer_lib.init_outer_state(
        {"w": jnp.broadcast_to(theta["w"][0], (world, n))}
    )
    cfg = outer_lib.OuterConfig(method="noloco")
    partner = jnp.asarray([1, 0, 3, 2])
    s1, t1 = outer_lib.outer_step_stacked(
        state, theta, cfg, partner=partner, kernel_cfg=PALLAS
    )
    s2, t2 = outer_lib.outer_step_stacked(
        state, theta, cfg, partner=partner, kernel_cfg=JNP
    )
    np.testing.assert_allclose(np.asarray(t1["w"]), np.asarray(t2["w"]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(s1.delta["w"]), np.asarray(s2.delta["w"]), atol=1e-6)


def test_int8_codec_kernel_parity():
    """Int8Codec wired to the Pallas kernels must produce a wire the jnp
    codec decodes (and vice versa) within one quantization step."""
    buf = jax.random.normal(jax.random.fold_in(KEY, 10), (5000,)) * 2.0
    cp = Int8Codec(chunk=256, kernel_cfg=PALLAS)
    cj = Int8Codec(chunk=256, kernel_cfg=JNP)
    wire_p = cp.encode(buf)
    wire_j = cj.encode(buf)
    assert wire_p.shape == wire_j.shape and wire_p.dtype == jnp.uint8
    step = 2.0 * 4.0 / 255.0  # generous bound on the per-chunk scale
    for enc, dec in ((cp, cj), (cj, cp)):
        out = dec.decode(enc.encode(buf), jnp.float32, buf.shape[0])
        assert float(jnp.abs(out - buf).max()) < 2 * step


# ---------------------------------------------------------------------------
# End-to-end toy-LM parity: impl="pallas" (interpret) vs impl="jnp"
# ---------------------------------------------------------------------------


def _toy_cfg(**kw) -> ModelConfig:
    base = dict(
        num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
        vocab_size=128, dtype="float32", remat=False,
    )
    base.update(kw)
    return ModelConfig(**base)


def _toy_batch(cfg, b=2, s=32):
    return {
        "tokens": jax.random.randint(jax.random.fold_in(KEY, 11), (b, s), 0, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.fold_in(KEY, 12), (b, s), 0, cfg.vocab_size),
    }


@pytest.mark.parametrize("arch_kw", [
    dict(),                                                        # dense GQA
    dict(attn_pattern=("rglru", "local"), sliding_window=16, lru_width=64),
    dict(arch_type="ssm", attn_pattern=("ssd",), ssm_state_dim=16,
         ssm_head_dim=16, ssm_chunk=16, num_heads=4, num_kv_heads=4),
])
def test_toy_lm_loss_parity(arch_kw):
    cfg = _toy_cfg(**arch_kw)
    ctx = ShardCtx.local()
    params = values_of(model_api.init_params(jax.random.PRNGKey(3), cfg))
    batch = _toy_batch(cfg)
    cfg_p = dataclasses.replace(cfg, kernels=PALLAS)
    cfg_j = dataclasses.replace(cfg, kernels=JNP)
    lp = model_api.loss_fn(params, cfg_p, batch, ctx)[0]
    lj = model_api.loss_fn(params, cfg_j, batch, ctx)[0]
    np.testing.assert_allclose(float(lp), float(lj), rtol=2e-5, atol=2e-5)


def test_toy_lm_training_parity_pallas_interpret():
    """A short SGD run must follow the same loss trajectory under both
    implementations (forward impl differs, custom_vjp backward shared).
    All steps see one fixed batch, so the loss must fall."""
    cfg = _toy_cfg(attn_pattern=("global", "local"), sliding_window=16)
    ctx = ShardCtx.local()
    batch = {
        "tokens": jax.random.randint(jax.random.fold_in(KEY, 100), (2, 32), 0, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.fold_in(KEY, 200), (2, 32), 0, cfg.vocab_size),
    }

    def run(kcfg):
        c = dataclasses.replace(cfg, kernels=kcfg)
        params = values_of(model_api.init_params(jax.random.PRNGKey(5), c))
        losses = []
        for _ in range(3):
            loss, grads = jax.value_and_grad(
                lambda p: model_api.loss_fn(p, c, batch, ctx)[0]
            )(params)
            params = jax.tree.map(lambda p, g: p - 0.1 * g, params, grads)
            losses.append(float(loss))
        return losses

    lp = run(PALLAS)
    lj = run(JNP)
    np.testing.assert_allclose(lp, lj, rtol=5e-5, atol=5e-5)
    assert lp[-1] < lp[0]  # it actually trains
