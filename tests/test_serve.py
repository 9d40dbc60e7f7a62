"""Serving stack: paged prefill+decode must reproduce the full-forward
logits for every cache family, the Pallas serving kernels must match their
jnp twins, continuous batching must be invisible to each request (batched
tokens == solo-decoded tokens, exactly), and train→serve promotion must
round-trip a checkpoint and refuse frozen replicas."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt as ckpt_lib
from repro.kernels import ops
from repro.kernels.dispatch import KernelConfig
from repro.models import model as M
from repro.models import transformer as tfm
from repro.models.attention import PagedView
from repro.models.common import values_of
from repro.models.config import ModelConfig
from repro.models.layers import apply_norm, logits_sharded
from repro.parallel.sharding import ShardCtx
from repro.serve import (
    BlockAllocator,
    Request,
    ServeConfig,
    ServeEngine,
    promote,
    resolve_replica,
)

CTX = ShardCtx.local()
KEY = jax.random.PRNGKey(11)
PALLAS = KernelConfig(impl="pallas", interpret=True)
JNP = KernelConfig(impl="jnp")

CFGS = {
    "global": ModelConfig(num_layers=3, d_model=64, num_heads=4, num_kv_heads=2,
                          d_ff=128, vocab_size=128, qk_norm=True,
                          dtype="float32", remat=False),
    "local": ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=1,
                         d_ff=128, vocab_size=128, attn_pattern=("local",),
                         sliding_window=6, dtype="float32", remat=False),
    "rglru": ModelConfig(arch_type="hybrid", num_layers=3, d_model=64, num_heads=4,
                         num_kv_heads=1, d_ff=128, vocab_size=128,
                         attn_pattern=("rglru", "rglru", "local"), sliding_window=6,
                         lru_width=64, dtype="float32", remat=False),
    "ssd": ModelConfig(arch_type="ssm", num_layers=2, d_model=64, num_heads=4,
                       num_kv_heads=4, d_ff=0, vocab_size=128, attn_pattern=("ssd",),
                       ssm_state_dim=16, ssm_head_dim=32, ssm_chunk=4,
                       use_rope=False, dtype="float32", remat=False),
}


def _full_logits(vals, cfg, toks):
    x, _ = M.embed_input(vals, cfg, {"tokens": toks}, CTX)
    x, _, _ = tfm.apply_stack(vals["stack"], cfg, x, CTX,
                              positions=jnp.arange(toks.shape[1]))
    x = apply_norm(vals["final_norm"], x)
    return logits_sharded(vals["embed"], cfg, x, CTX)


# ---------------------------------------------------------------------------
# Paged prefill + decode vs full forward (per cache family)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", list(CFGS))
def test_paged_decode_equals_full_forward(kind):
    cfg = CFGS[kind]
    vals = values_of(M.init_params(jax.random.PRNGKey(0), cfg))
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 12), 0, cfg.vocab_size)
    full = _full_logits(vals, cfg, toks)

    num_pages, page_size, mb = 4, 4, 4
    caches = M.init_paged_cache_tree(cfg, 1, num_pages, page_size)
    tables = np.full((1, mb), num_pages, dtype=np.int32)  # trash-filled
    tables[0, :3] = [0, 1, 2]                             # 12 tokens = 3 pages
    tables = jnp.asarray(tables)

    view = PagedView(tables, jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool))
    lg, caches = M.paged_prefill(vals, cfg, toks[:, :6], caches, view, CTX)
    errs = [float(jnp.max(jnp.abs(lg[:, 0] - full[:, 5])))]
    for i in range(6, 12):
        view = PagedView(tables, jnp.asarray([i], jnp.int32), jnp.ones((1,), bool))
        lg, caches = M.paged_decode_step(vals, cfg, toks[:, i:i + 1], caches, view, CTX)
        errs.append(float(jnp.max(jnp.abs(lg[:, 0] - full[:, i]))))
    assert max(errs) < 2e-3, f"{kind}: {errs}"


def test_paged_cache_tree_rejects_encdec():
    cfg = dataclasses.replace(
        CFGS["global"], arch_type="encdec", is_encoder_decoder=True,
        num_encoder_layers=1, encoder_seq=8,
    )
    with pytest.raises(ValueError, match="paged"):
        M.init_paged_cache_tree(cfg, 1, 4, 4)


# ---------------------------------------------------------------------------
# Serving kernels: pallas-interpret vs jnp twin parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("h,kv,mode,window", [
    (4, 4, "causal", 0),   # MHA
    (4, 2, "causal", 0),   # GQA (folded into q tile rows)
    (4, 1, "local", 5),    # MQA sliding window
])
def test_paged_attention_impl_parity(h, kv, mode, window):
    num_pages, page_size, mb, r, d = 6, 4, 4, 3, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (r, h, d))
    kp = jax.random.normal(ks[1], (num_pages, kv, page_size, d))
    vp = jax.random.normal(ks[2], (num_pages, kv, page_size, d))
    tables = jnp.asarray([[0, 1, 2, 3], [4, 5, 0, 1], [2, 3, 4, 5]], jnp.int32)
    positions = jnp.asarray([5, 11, 2], jnp.int32)
    op = ops.paged_attention(q, kp, vp, tables, positions,
                             mode=mode, window=window, config=PALLAS)
    oj = ops.paged_attention(q, kp, vp, tables, positions,
                             mode=mode, window=window, config=JNP)
    np.testing.assert_allclose(np.asarray(op), np.asarray(oj), atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("h,kv,config,want", [
    (8, 2, PALLAS, "pallas"),  # whole GQA groups fold into the kernel
    (8, 16, PALLAS, "jnp"),    # a (NP, BS, KV, D) pool read as kv-head major
    (6, 4, PALLAS, "jnp"),     # ragged groups
    (8, 2, JNP, "jnp"),
])
def test_paged_impl_routes_whole_gqa_groups_only(h, kv, config, want):
    assert ops.paged_impl(h, kv, config).resolved_impl() == want


def test_kernel_bench_refuses_rerouted_rows():
    """The kernel bench records each row under the dispatched impl, so a
    shape that routes the op elsewhere must stop it, not fall through."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks import kernel_bench

    kernel_bench.require_route("paged_attention", ops.paged_impl(8, 2, PALLAS), "pallas")
    with pytest.raises(RuntimeError, match="route to impl=jnp"):
        kernel_bench.require_route("paged_attention", ops.paged_impl(8, 16, PALLAS), "pallas")


def test_paged_attention_masks_unallocated_pages():
    """Entries past positions[r] (stale pages, trash fill) must not leak:
    scrambling them leaves the output bit-unchanged."""
    num_pages, page_size, r, h, d = 4, 4, 2, 2, 8
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (r, h, d))
    kp = jax.random.normal(jax.random.fold_in(KEY, 2), (num_pages, h, page_size, d))
    vp = jax.random.normal(jax.random.fold_in(KEY, 3), (num_pages, h, page_size, d))
    tables = jnp.asarray([[0, 1, 2, 3], [0, 1, 2, 3]], jnp.int32)
    positions = jnp.asarray([3, 6], jnp.int32)  # only the first 1-2 pages live
    for cfg in (PALLAS, JNP):
        base = ops.paged_attention(q, kp, vp, tables, positions, config=cfg)
        # scramble everything strictly after each slot's position
        kp2, vp2 = kp.at[2:].set(99.0), vp.at[2:].set(-99.0)
        kp2 = kp2.at[1, :, 3:].set(99.0)   # slot 1: page 1 holds pos 4..7, 7 > 6
        vp2 = vp2.at[1, :, 3:].set(-99.0)
        got = ops.paged_attention(q, kp2, vp2, tables, positions, config=cfg)
        np.testing.assert_array_equal(np.asarray(base), np.asarray(got))


def test_rglru_decode_impl_parity():
    r, w = 3, 48
    h = jax.random.normal(jax.random.fold_in(KEY, 4), (r, w))
    a = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(KEY, 5), (r, w)))
    b = jax.random.normal(jax.random.fold_in(KEY, 6), (r, w))
    op = ops.rglru_decode(h, a, b, config=PALLAS)
    oj = ops.rglru_decode(h, a, b, config=JNP)
    np.testing.assert_allclose(np.asarray(op), np.asarray(oj), atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(oj), np.asarray(a * h + b),
                               atol=1e-6, rtol=1e-6)


def test_ssd_decode_impl_parity():
    r, h, p, n = 2, 2, 8, 4
    state = jax.random.normal(jax.random.fold_in(KEY, 7), (r, h, p, n)) * 0.3
    dt1 = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 8), (r, h))) * 0.1
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 9), (h,)) * 0.3)
    b1 = jax.random.normal(jax.random.fold_in(KEY, 10), (r, n)) * 0.5
    c1 = jax.random.normal(jax.random.fold_in(KEY, 11), (r, n)) * 0.5
    x1 = jax.random.normal(jax.random.fold_in(KEY, 12), (r, h, p)) * 0.5
    sp, yp = ops.ssd_decode(state, dt1, a, b1, c1, x1, config=PALLAS)
    sj, yj = ops.ssd_decode(state, dt1, a, b1, c1, x1, config=JNP)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sj), atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yj), atol=2e-6, rtol=1e-5)


# ---------------------------------------------------------------------------
# Block allocator
# ---------------------------------------------------------------------------


def test_block_allocator():
    al = BlockAllocator(num_pages=8, page_size=4)
    assert al.trash_page == 8
    assert al.blocks_for(1) == 1 and al.blocks_for(4) == 1 and al.blocks_for(5) == 2
    a = al.alloc(3)
    b = al.alloc(5)
    assert len(set(a) | set(b)) == 8 and al.free_count == 0
    assert not al.can_alloc(1)
    with pytest.raises(MemoryError):
        al.alloc(1)
    al.free(b)
    assert al.free_count == 5
    with pytest.raises(ValueError, match="double free"):
        al.free([b[0]])
    with pytest.raises(ValueError, match="invalid"):
        al.free([al.trash_page])
    al.free(a)
    assert al.free_count == 8


# ---------------------------------------------------------------------------
# Continuous batching is invisible to each request (exact token match)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["global", "rglru"])
def test_continuous_batching_matches_solo_decode(kind):
    cfg = CFGS[kind]
    params = values_of(M.init_params(jax.random.PRNGKey(2), cfg))
    scfg = ServeConfig(max_slots=2, num_pages=24, page_size=4, max_new_cap=8)

    rng = np.random.default_rng(0)
    requests = []
    for rid, (pl, gl, temp) in enumerate(
        [(3, 6, 0.0), (7, 4, 0.0), (5, 8, 0.7), (2, 5, 0.0)]
    ):
        prompt = rng.integers(0, cfg.vocab_size, size=(pl,)).tolist()
        requests.append(Request(rid=rid, prompt=[int(t) for t in prompt],
                                max_new=gl, temperature=temp))

    engine = ServeEngine(params, cfg, scfg)
    finished = {f.rid: f for f in engine.run([dataclasses.replace(r) for r in requests])}
    assert sorted(finished) == [0, 1, 2, 3]

    for r in requests:
        solo = ServeEngine(params, cfg, scfg)
        [f] = solo.run([dataclasses.replace(r)])
        assert len(f.tokens) == r.max_new
        assert f.tokens == finished[r.rid].tokens, (
            f"{kind} rid={r.rid}: batched decode diverged from solo decode"
        )


def test_continuous_policy_beats_static_on_decode_steps():
    """Same mixed load, same slots: continuous refills freed slots mid-flight
    so it needs no more (and here strictly fewer) fused decode steps."""
    cfg = CFGS["global"]
    params = values_of(M.init_params(jax.random.PRNGKey(2), cfg))
    rng = np.random.default_rng(1)
    requests = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=(pl,)).tolist(),
                max_new=gl)
        for i, (pl, gl) in enumerate([(3, 8), (5, 2), (4, 2), (6, 8)])
    ]
    steps = {}
    for policy in ("continuous", "static"):
        scfg = ServeConfig(max_slots=2, num_pages=24, page_size=4,
                           max_new_cap=8, policy=policy)
        eng = ServeEngine(params, cfg, scfg)
        done = eng.run([dataclasses.replace(r) for r in requests])
        assert len(done) == len(requests)
        steps[policy] = eng.decode_steps
    assert steps["continuous"] < steps["static"], steps


# ---------------------------------------------------------------------------
# Train → serve promotion
# ---------------------------------------------------------------------------


def _fake_gossip_ckpt(tmp_path, world=3, n=5, mask=(True, True, True)):
    rng = np.random.default_rng(7)
    theta = {"w": rng.normal(size=(world, n)).astype(np.float32)}
    phi = {"w": rng.normal(size=(world, n)).astype(np.float32)}
    tree = {
        "program": {
            "theta": theta,
            "opt": {"mu": np.zeros((world, n), np.float32)},
            "outer": {"phi": phi, "delta": {"w": np.zeros((world, n), np.float32)},
                      "step": np.int64(4)},
            "inner_step": np.int64(40),
            "membership": {"mask": np.asarray(mask, bool), "epoch": np.int64(1),
                           "partition": np.arange(world, dtype=np.int64)},
        },
        "loop": {"step": np.int64(40)},
    }
    ckpt_lib.save(str(tmp_path), 40, tree)
    return theta, phi


def test_promote_theta_and_phi_roundtrip(tmp_path):
    theta, phi = _fake_gossip_ckpt(tmp_path)
    params, info = promote(str(tmp_path), replica=1, source="theta")
    np.testing.assert_array_equal(np.asarray(params["w"]), theta["w"][1])
    assert info == {"step": 40, "replica": 1, "source": "theta", "world": 3}
    params, info = promote(str(tmp_path), replica=2, source="phi")
    np.testing.assert_array_equal(np.asarray(params["w"]), phi["w"][2])
    assert info["source"] == "phi" and info["replica"] == 2


def test_promote_frozen_replica_falls_back(tmp_path):
    theta, _ = _fake_gossip_ckpt(tmp_path, mask=(False, True, True))
    with pytest.warns(UserWarning, match="frozen"):
        params, info = promote(str(tmp_path), replica=0)
    assert info["replica"] == 1  # first ACTIVE replica
    np.testing.assert_array_equal(np.asarray(params["w"]), theta["w"][1])
    with pytest.warns(UserWarning, match="out of range"):
        _, info = promote(str(tmp_path), replica=9)
    assert info["replica"] == 1


def test_promote_active_replica_does_not_warn(tmp_path):
    _fake_gossip_ckpt(tmp_path, mask=(False, True, True))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        _, info = promote(str(tmp_path), replica=2)
    assert info["replica"] == 2
    assert resolve_replica(None, 1, world=3) == 1


def test_promote_rejects_pipeline_checkpoint(tmp_path):
    tree = {"program": {"params": [{"w": np.zeros((2, 3), np.float32)}],
                        "step": np.int64(1)}}
    ckpt_lib.save(str(tmp_path), 1, tree)
    with pytest.raises(ValueError, match="pipeline"):
        promote(str(tmp_path))
