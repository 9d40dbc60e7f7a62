"""Serving fast path: chunked prefill must equal whole-prompt prefill for
every cache family (ONE fixed-width program, ragged tails masked, recurrent
states carried exactly across chunk boundaries), the block allocator's lease
protocol must never leak or double-own a page, speculative decode must be
invisible in the output (spec tokens == target-only tokens, bitwise, for any
draft), and the multi-replica router must not change what any request
decodes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.dispatch import KernelConfig
from repro.models import model as M
from repro.models.attention import PagedView
from repro.models.common import values_of
from repro.models.config import ModelConfig
from repro.parallel.sharding import ShardCtx
from repro.serve import (
    BlockAllocator,
    ReplicaRouter,
    Request,
    ServeConfig,
    ServeEngine,
    SpecServeEngine,
    truncate_layers,
)

try:  # hypothesis is optional in this image; fall back to seeded draws
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

CTX = ShardCtx.local()
KEY = jax.random.PRNGKey(23)
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


def _params(kind: str, seed: int = 0):
    return values_of(M.init_params(jax.random.PRNGKey(seed), CFGS[kind]))


# ---------------------------------------------------------------------------
# BlockAllocator lease protocol: reserve → commit | rollback, no leaks
# ---------------------------------------------------------------------------


def test_lease_reserve_commit_rollback():
    al = BlockAllocator(num_pages=8, page_size=4)
    lease = al.reserve(3)
    assert al.free_count == 5 and al.reserved_count == 3
    al.check_leaks()  # free + reserved == pool while the lease is pending

    blocks = al.commit(lease)
    assert sorted(blocks) == sorted(lease.blocks) and al.reserved_count == 0
    al.check_leaks(owned=3)
    with pytest.raises(ValueError, match="commit of committed"):
        al.commit(lease)
    with pytest.raises(ValueError, match="rollback of committed"):
        al.rollback(lease)

    other = al.reserve(5)
    assert al.free_count == 0 and not al.can_alloc(1)
    al.rollback(other)
    assert al.free_count == 5 and al.reserved_count == 0
    with pytest.raises(ValueError, match="rollback of rolled_back"):
        al.rollback(other)
    al.check_leaks(owned=3)

    al.free(blocks)
    al.check_leaks()
    assert al.free_count == 8


def test_lease_pages_never_doubly_owned():
    al = BlockAllocator(num_pages=6, page_size=2)
    a = al.reserve(2)
    b = al.reserve(2)
    assert not set(a.blocks) & set(b.blocks)
    with pytest.raises(MemoryError):
        al.reserve(3)  # only 2 left
    kept = al.commit(a)
    al.rollback(b)
    # rolled-back pages went home; committed ones didn't
    with pytest.raises(ValueError, match="double free"):
        al.free([b.blocks[0]])
    al.free(kept)
    al.check_leaks()


def test_check_leaks_detects_missing_pages():
    al = BlockAllocator(num_pages=4, page_size=2)
    al.alloc(1)  # owned by nobody on record
    with pytest.raises(AssertionError, match="leak"):
        al.check_leaks()
    al.check_leaks(owned=1)


# ---------------------------------------------------------------------------
# Chunked paged attention kernel: impl parity + positional masking
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("h,kv,mode,window", [
    (4, 4, "causal", 0),   # MHA
    (4, 2, "causal", 0),   # GQA
    (4, 1, "local", 5),    # MQA sliding window
])
def test_paged_chunk_attention_impl_parity(h, kv, mode, window):
    num_pages, page_size, mb, r, c, d = 6, 4, 4, 3, 5, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (r, c, h, d))
    kp = jax.random.normal(ks[1], (num_pages, kv, page_size, d))
    vp = jax.random.normal(ks[2], (num_pages, kv, page_size, d))
    tables = jnp.asarray([[0, 1, 2, 3], [4, 5, 0, 1], [2, 3, 4, 5]], jnp.int32)
    base = jnp.asarray([0, 4, 9], jnp.int32)  # chunk token 0 positions
    op = ops.paged_chunk_attention(q, kp, vp, tables, base,
                                   mode=mode, window=window, config=PALLAS)
    oj = ops.paged_chunk_attention(q, kp, vp, tables, base,
                                   mode=mode, window=window, config=JNP)
    np.testing.assert_allclose(np.asarray(op), np.asarray(oj), atol=2e-5, rtol=1e-4)


def test_paged_chunk_attention_masks_future_and_trash():
    """Chunk token c at base+c must only see keys j <= base+c: scrambling
    every pool entry past each slot's last chunk position (including whole
    unallocated pages) leaves the output bit-unchanged."""
    num_pages, page_size, r, c, h, d = 4, 4, 2, 3, 2, 8
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (r, c, h, d))
    kp = jax.random.normal(jax.random.fold_in(KEY, 2), (num_pages, h, page_size, d))
    vp = jax.random.normal(jax.random.fold_in(KEY, 3), (num_pages, h, page_size, d))
    # disjoint pages per slot; unallocated table entries just repeat a page
    tables = jnp.asarray([[0, 1, 0, 0], [2, 3, 2, 2]], jnp.int32)
    base = jnp.asarray([2, 4], jnp.int32)  # last chunk tokens at pos 4 and 6
    for cfg in (PALLAS, JNP):
        ref = ops.paged_chunk_attention(q, kp, vp, tables, base, config=cfg)
        kp2 = kp.at[1, :, 1:].set(77.0)     # slot 0: pos 5..7, all > 4
        vp2 = vp.at[1, :, 1:].set(-77.0)
        kp2 = kp2.at[3, :, 3:].set(77.0)    # slot 1: pos 7 > 6
        vp2 = vp2.at[3, :, 3:].set(-77.0)
        got = ops.paged_chunk_attention(q, kp2, vp2, tables, base, config=cfg)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


# ---------------------------------------------------------------------------
# Chunked prefill == whole-prompt prefill, per cache family (property)
# ---------------------------------------------------------------------------


def _chunk_vs_whole(kind: str, plen: int, chunk: int) -> None:
    """Walk a prompt through paged_prefill_chunk in fixed-width chunks (last
    one ragged) and check logits match whole-prompt paged_prefill at the
    final position — then one more decode step from each cache, which fails
    if chunking corrupted ANY carried state (KV pages or recurrences)."""
    cfg = CFGS[kind]
    vals = _params(kind)
    toks = jax.random.randint(jax.random.fold_in(KEY, plen * 31 + chunk),
                              (1, plen), 0, cfg.vocab_size)
    num_pages, page_size, mb = 8, 4, 8
    tables = np.full((1, mb), num_pages, dtype=np.int32)
    n_blk = -(-(plen + 1) // page_size)
    tables[0, :n_blk] = range(n_blk)
    tables = jnp.asarray(tables)

    whole = M.init_paged_cache_tree(cfg, 1, num_pages, page_size)
    view0 = PagedView(tables, jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool))
    lg_whole, whole = M.paged_prefill(vals, cfg, toks, whole, view0, CTX)

    caches = M.init_paged_cache_tree(cfg, 1, num_pages, page_size)
    cur = 0
    while cur < plen:
        n = min(chunk, plen - cur)
        buf = jnp.zeros((1, chunk), toks.dtype).at[0, :n].set(toks[0, cur:cur + n])
        view = PagedView(tables, jnp.asarray([cur], jnp.int32), jnp.ones((1,), bool))
        lg_chunk, caches = M.paged_prefill_chunk(
            vals, cfg, buf, caches, view, CTX, lengths=jnp.asarray([n], jnp.int32)
        )
        cur += n
    np.testing.assert_allclose(np.asarray(lg_chunk), np.asarray(lg_whole),
                               atol=2e-3, rtol=1e-3)

    nxt = jnp.asarray([[7]], toks.dtype)
    view = PagedView(tables, jnp.asarray([plen], jnp.int32), jnp.ones((1,), bool))
    d_whole, _ = M.paged_decode_step(vals, cfg, nxt, whole, view, CTX)
    d_chunk, _ = M.paged_decode_step(vals, cfg, nxt, caches, view, CTX)
    np.testing.assert_allclose(np.asarray(d_chunk), np.asarray(d_whole),
                               atol=2e-3, rtol=1e-3)


if HAVE_HYPOTHESIS:
    @pytest.mark.parametrize("kind", list(CFGS))
    @settings(max_examples=8, deadline=None)
    @given(plen=st.integers(1, 14), chunk=st.integers(2, 6))
    def test_chunked_prefill_matches_whole_prompt(kind, plen, chunk):
        _chunk_vs_whole(kind, plen, chunk)
else:
    @pytest.mark.parametrize("kind", list(CFGS))
    def test_chunked_prefill_matches_whole_prompt(kind):
        rng = np.random.default_rng(5)
        cases = {(int(rng.integers(1, 15)), int(rng.integers(2, 7)))
                 for _ in range(4)}
        cases |= {(13, 4), (3, 6)}  # ragged tail; single under-full chunk
        for plen, chunk in sorted(cases):
            _chunk_vs_whole(kind, plen, chunk)


# ---------------------------------------------------------------------------
# Chunked engine: batched == solo, O(1) compiled programs, no page leaks
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["global", "rglru", "ssd"])
def test_chunked_engine_batched_matches_solo(kind):
    cfg = CFGS[kind]
    params = _params(kind, seed=2)
    # chunk=3 forces multi-chunk admissions with ragged tails on this load
    scfg = ServeConfig(max_slots=2, num_pages=24, page_size=4, max_new_cap=8,
                       prefill_chunk=3)
    rng = np.random.default_rng(0)
    requests = [
        Request(rid=rid, prompt=rng.integers(0, cfg.vocab_size, size=(pl,)).tolist(),
                max_new=gl, temperature=temp)
        for rid, (pl, gl, temp) in enumerate(
            [(3, 6, 0.0), (9, 4, 0.0), (5, 8, 0.7), (2, 5, 0.0)]
        )
    ]
    engine = ServeEngine(params, cfg, scfg)
    finished = {f.rid: f for f in engine.run([dataclasses.replace(r) for r in requests])}
    assert sorted(finished) == [0, 1, 2, 3]
    engine.alloc.check_leaks()
    # the whole mixed-length run compiled exactly ONE chunk program
    assert engine._chunk_fn._cache_size() == 1

    for r in requests:
        solo = ServeEngine(params, cfg, scfg)
        [f] = solo.run([dataclasses.replace(r)])
        assert f.tokens == finished[r.rid].tokens, (
            f"{kind} rid={r.rid}: chunked batched decode diverged from solo"
        )


def test_chunked_engine_matches_single_shot_prefill():
    """Same load through chunk=3 admission and through the single-shot
    (chunk=0) per-length prefill: identical tokens out."""
    cfg = CFGS["global"]
    params = _params("global", seed=2)
    rng = np.random.default_rng(3)
    requests = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=(pl,)).tolist(),
                max_new=5, temperature=t)
        for i, (pl, t) in enumerate([(4, 0.0), (7, 0.7), (11, 0.0)])
    ]
    outs = {}
    for chunk in (3, 0):
        scfg = ServeConfig(max_slots=2, num_pages=24, page_size=4,
                           max_new_cap=8, prefill_chunk=chunk)
        eng = ServeEngine(params, cfg, scfg)
        done = eng.run([dataclasses.replace(r) for r in requests])
        outs[chunk] = {f.rid: f.tokens for f in done}
    assert outs[3] == outs[0]


def test_prefill_budget_throttles_admission():
    """prefill_budget=chunk admits at most one chunk per tick; the run still
    finishes with identical tokens."""
    cfg = CFGS["global"]
    params = _params("global", seed=2)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg.vocab_size, size=(10,)).tolist()
    outs = {}
    for budget in (0, 4):
        scfg = ServeConfig(max_slots=2, num_pages=24, page_size=4,
                           max_new_cap=8, prefill_chunk=4, prefill_budget=budget)
        eng = ServeEngine(params, cfg, scfg)
        done = eng.run([Request(rid=0, prompt=list(prompt), max_new=6)])
        outs[budget] = done[0].tokens
        eng.alloc.check_leaks()
    assert outs[0] == outs[4]


# ---------------------------------------------------------------------------
# Speculative decode: output must be EXACTLY the target's, for any draft
# ---------------------------------------------------------------------------


def _spec_load(cfg):
    rng = np.random.default_rng(9)
    return [
        Request(rid=rid, prompt=rng.integers(0, cfg.vocab_size, size=(pl,)).tolist(),
                max_new=gl, temperature=temp)
        for rid, (pl, gl, temp) in enumerate(
            [(3, 6, 0.0), (8, 5, 0.7), (5, 7, 0.0)]
        )
    ]


def _reference(params, cfg, scfg, requests):
    eng = ServeEngine(params, cfg, scfg)
    return {f.rid: f.tokens for f in eng.run([dataclasses.replace(r) for r in requests])}


@pytest.mark.parametrize("kind", ["global", "rglru", "ssd"])
def test_spec_decode_with_self_draft_is_exact_and_fully_accepted(kind):
    cfg = CFGS[kind]
    params = _params(kind, seed=2)
    scfg = ServeConfig(max_slots=2, num_pages=24, page_size=4, max_new_cap=8,
                       prefill_chunk=4)
    requests = _spec_load(cfg)
    ref = _reference(params, cfg, scfg, requests)

    eng = SpecServeEngine(params, cfg, scfg, params, cfg, spec_k=3)
    got = {f.rid: f for f in eng.run([dataclasses.replace(r) for r in requests])}
    assert {r: f.tokens for r, f in got.items()} == ref
    # the draft IS the target: every proposal must be accepted
    assert eng.accept_rate == 1.0
    assert all(f.stats["accept_rate"] == 1.0 for f in got.values())
    eng.alloc.check_leaks()


@pytest.mark.parametrize("kind", ["global", "rglru"])
def test_spec_decode_with_divergent_draft_is_still_exact(kind):
    """A draft with DIFFERENT weights (another NoLoCo replica in production)
    proposes wrong tokens sometimes — rejections must roll KV + recurrent
    state back so output still equals the target-only run, bitwise."""
    cfg = CFGS[kind]
    params = _params(kind, seed=2)
    draft_params = _params(kind, seed=7)
    scfg = ServeConfig(max_slots=2, num_pages=24, page_size=4, max_new_cap=8,
                       prefill_chunk=4)
    requests = _spec_load(cfg)
    ref = _reference(params, cfg, scfg, requests)

    eng = SpecServeEngine(params, cfg, scfg, draft_params, cfg, spec_k=3)
    got = {f.rid: f.tokens for f in eng.run([dataclasses.replace(r) for r in requests])}
    assert got == ref
    assert 0.0 <= eng.accept_rate <= 1.0 and eng.spec_rounds > 0


def test_spec_accept_rate_well_defined_with_no_usable_proposals():
    """max_new=1 requests: every decode round has rem == 1 for every slot, so
    usable = min(spec_k-1, rem-1) = 0 and the denominator never grows.  The
    accept rate must come back as the vacuously-perfect 1.0 — not NaN, not a
    0/0-as-0.0 that would falsely read as 'draft never matched' — both on the
    engine aggregate and in every request's finish stats."""
    cfg = CFGS["global"]
    params = _params("global", seed=2)
    scfg = ServeConfig(max_slots=2, num_pages=24, page_size=4, max_new_cap=8,
                       prefill_chunk=4)
    rng = np.random.default_rng(11)
    requests = [
        Request(rid=rid, prompt=rng.integers(0, cfg.vocab_size, size=(pl,)).tolist(),
                max_new=1)
        for rid, pl in enumerate([3, 8])
    ]
    ref = _reference(params, cfg, scfg, requests)

    eng = SpecServeEngine(params, cfg, scfg, params, cfg, spec_k=3)
    got = {f.rid: f for f in eng.run([dataclasses.replace(r) for r in requests])}
    assert {r: f.tokens for r, f in got.items()} == ref
    assert eng.spec_prop_total == 0
    assert eng.accept_rate == 1.0
    assert all(f.stats["accept_rate"] == 1.0 for f in got.values())
    eng.alloc.check_leaks()


def test_spec_accept_rate_defined_before_any_round():
    """An engine that has not run a single spec round (empty request list —
    the 'empty final rounds' shape) must still report a finite in-[0,1]
    accept_rate for telemetry summaries."""
    cfg = CFGS["global"]
    params = _params("global", seed=2)
    scfg = ServeConfig(max_slots=2, num_pages=24, page_size=4, max_new_cap=8,
                       prefill_chunk=4)
    eng = SpecServeEngine(params, cfg, scfg, params, cfg, spec_k=3)
    assert eng.run([]) == []
    assert eng.accept_rate == 1.0


def test_spec_decode_with_truncated_draft_is_exact():
    cfg = CFGS["global"]
    params = _params("global", seed=2)
    draft = truncate_layers(params, cfg, 1)
    scfg = ServeConfig(max_slots=2, num_pages=24, page_size=4, max_new_cap=8,
                       prefill_chunk=4)
    requests = _spec_load(cfg)
    ref = _reference(params, cfg, scfg, requests)
    eng = SpecServeEngine(params, cfg, scfg, draft[0], draft[1], spec_k=3)
    got = {f.rid: f.tokens for f in eng.run([dataclasses.replace(r) for r in requests])}
    assert got == ref


def test_spec_engine_requires_chunked_prefill():
    cfg = CFGS["global"]
    params = _params("global")
    scfg = ServeConfig(max_slots=1, num_pages=8, page_size=4, max_new_cap=4,
                       prefill_chunk=0)
    with pytest.raises(ValueError, match="prefill_chunk"):
        SpecServeEngine(params, cfg, scfg, params, cfg, spec_k=2)


# ---------------------------------------------------------------------------
# truncate_layers: structure + runnable draft
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind,keep", [("global", 1), ("global", 2),
                                       ("rglru", 1), ("rglru", 2), ("ssd", 1)])
def test_truncate_layers_structure_and_forward(kind, keep):
    cfg = CFGS[kind]
    params = _params(kind)
    dparams, dcfg = truncate_layers(params, cfg, keep)
    assert dcfg.num_layers == keep
    p = len(cfg.attn_pattern)
    n_full2, rem2 = keep // p, keep % p
    for s in dparams["stack"]["scan"]:
        if s is not None:
            depths = {int(l.shape[0]) for l in jax.tree.leaves(s)}
            assert depths == {n_full2}
    assert len(dparams["stack"]["rem"]) == rem2
    assert dparams["embed"] is params["embed"]  # shared, not copied

    caches = M.init_paged_cache_tree(dcfg, 1, 4, 4)
    tables = jnp.asarray([[0, 1, 2, 4]], jnp.int32)
    view = PagedView(tables, jnp.zeros((1,), jnp.int32), jnp.ones((1,), bool))
    toks = jnp.asarray([[5, 9, 2]], jnp.int32)
    lg, _ = M.paged_prefill(dparams, dcfg, toks, caches, view, CTX)
    assert lg.shape == (1, 1, dcfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(lg)))


def test_truncate_layers_rejects_bad_depth():
    cfg = CFGS["global"]
    params = _params("global")
    with pytest.raises(ValueError, match="num_layers"):
        truncate_layers(params, cfg, 0)
    with pytest.raises(ValueError, match="num_layers"):
        truncate_layers(params, cfg, cfg.num_layers + 1)


# ---------------------------------------------------------------------------
# Router: placement policies; routing never changes what a request decodes
# ---------------------------------------------------------------------------


def test_router_round_robin_and_output_parity():
    cfg = CFGS["global"]
    params = _params("global", seed=2)
    scfg = ServeConfig(max_slots=2, num_pages=24, page_size=4, max_new_cap=8,
                       prefill_chunk=4)
    rng = np.random.default_rng(1)
    requests = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, size=(pl,)).tolist(),
                max_new=5)
        for i, pl in enumerate([3, 7, 4, 9])
    ]
    ref = _reference(params, cfg, scfg, requests)

    router = ReplicaRouter(
        [ServeEngine(params, cfg, scfg) for _ in range(2)], policy="round_robin"
    )
    finished = router.run([dataclasses.replace(r) for r in requests])
    assert router.routed == [2, 2]
    assert {f.rid: f.tokens for _, f in finished} == ref
    replicas = {f.rid: i for i, f in finished}
    assert {replicas[0], replicas[2]} == {0} and {replicas[1], replicas[3]} == {1}


def test_router_least_loaded_prefers_idle_engine():
    cfg = CFGS["global"]
    params = _params("global", seed=2)
    scfg = ServeConfig(max_slots=2, num_pages=24, page_size=4, max_new_cap=8,
                       prefill_chunk=4)
    router = ReplicaRouter(
        [ServeEngine(params, cfg, scfg) for _ in range(2)], policy="least_loaded"
    )
    heavy = Request(rid=0, prompt=[1] * 9, max_new=8)
    light = Request(rid=1, prompt=[2] * 3, max_new=2)
    assert router.submit(heavy) == 0
    assert router.submit(light) == 1  # engine 0 now carries 17 tokens of work
    assert router.submit(Request(rid=2, prompt=[3] * 2, max_new=2)) == 1
    while not router.idle:
        router.step()
    for eng in router.engines:
        eng._evict_finished()
        eng.alloc.check_leaks()


def test_router_validates_inputs():
    with pytest.raises(ValueError, match="at least one"):
        ReplicaRouter([])
    cfg = CFGS["global"]
    params = _params("global")
    scfg = ServeConfig(max_slots=1, num_pages=8, page_size=4, max_new_cap=4)
    with pytest.raises(ValueError, match="policy"):
        ReplicaRouter([ServeEngine(params, cfg, scfg)], policy="random")
