"""repro.comm: payload packing, wire codecs, communicators, byte model.

The HLO test runs in a subprocess with forced host devices (multidevice
marker) like tests/test_multidevice.py; everything else is single-device.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import (
    CommConfig,
    StackedGather,
    bytes_model,
    get_codec,
    make_spec,
    pack,
    unpack,
    wire_roundtrip,
)

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _mixed_tree():
    key = jax.random.PRNGKey(0)
    return {
        "w": jax.random.normal(key, (3, 5), jnp.float32),
        "nested": [
            jax.random.normal(jax.random.fold_in(key, 1), (7,), jnp.bfloat16),
            jnp.arange(4, dtype=jnp.int32),
        ],
        "scalar": jnp.float32(2.5),
        "half": jax.random.normal(jax.random.fold_in(key, 2), (2, 2), jnp.float16),
    }


# ---------------------------------------------------------------------------
# payload
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fuse", [True, False])
def test_pack_unpack_roundtrip_identity(fuse):
    """pack→unpack must be the identity for mixed-dtype pytrees (bit-exact,
    shapes and dtypes preserved) — the invariant the exchange relies on."""
    tree = _mixed_tree()
    buffers, spec = pack(tree, fuse=fuse)
    back = unpack(buffers, spec)
    assert jax.tree.structure(back) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_spec_groups_by_dtype():
    tree = _mixed_tree()
    spec = make_spec(tree, fuse=True)
    dtypes = [b.dtype for b in spec.buffers]
    assert len(dtypes) == len(set(dtypes)) == 4  # f32, bf16, i32, f16
    unfused = make_spec(tree, fuse=False)
    assert len(unfused.buffers) == spec.num_leaves == 5
    assert unfused.nbytes == spec.nbytes


def test_pack_is_jit_and_vmap_safe():
    tree = {"a": jnp.ones((4, 6)), "b": jnp.zeros((4, 3))}

    def rt(sub):
        bufs, spec = pack(sub)
        return unpack(bufs, spec)

    out = jax.jit(jax.vmap(rt))(tree)
    np.testing.assert_array_equal(np.asarray(out["a"]), np.asarray(tree["a"]))


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------


def test_int8_codec_error_bound():
    """Affine uint8 quantization: per-chunk error ≤ half a quantization step
    ((max−min)/255/2) — the exact bound of round-to-nearest."""
    chunk = 512
    x = jax.random.normal(jax.random.PRNGKey(3), (8 * chunk,), jnp.float32) * 3.0
    codec = get_codec(CommConfig(codec="int8", chunk=chunk))
    dec = np.asarray(codec.decode(codec.encode(x), jnp.float32, x.size))
    xr = np.asarray(x).reshape(-1, chunk)
    step = (xr.max(axis=1) - xr.min(axis=1)) / 255.0
    err = np.abs(dec.reshape(-1, chunk) - xr).max(axis=1)
    assert (err <= step * 0.5 + 1e-6).all(), (err, step)
    # and the relative error on the whole vector is small
    rel = np.linalg.norm(dec - np.asarray(x)) / np.linalg.norm(np.asarray(x))
    assert rel < 0.01, rel


def test_int8_codec_non_multiple_and_constant_chunks():
    codec = get_codec(CommConfig(codec="int8", chunk=64))
    x = jnp.concatenate([jnp.full((70,), 3.25), jnp.arange(30, dtype=jnp.float32)])
    dec = np.asarray(codec.decode(codec.encode(x), jnp.float32, x.size))
    assert dec.shape == (100,)
    np.testing.assert_allclose(dec[:64], 3.25, atol=1e-6)  # zero-range chunk exact


def test_int8_tail_chunk_padding_does_not_widen_range():
    """Edge padding: a partial tail chunk of values far from zero must keep
    its own quantization range (zero padding would blow the scale up)."""
    chunk = 1024
    codec = get_codec(CommConfig(codec="int8", chunk=chunk))
    tail = 100.0 + jnp.linspace(0.0, 0.05, 6)
    x = jnp.concatenate([jnp.zeros((chunk,), jnp.float32), tail])
    dec = np.asarray(codec.decode(codec.encode(x), jnp.float32, x.size))
    err = np.abs(dec[chunk:] - np.asarray(tail)).max()
    assert err <= 0.05 / 255.0 * 0.5 + 1e-6, err  # bound from the REAL range


def test_chunk_validation_only_applies_to_int8():
    CommConfig(codec="fp16", chunk=1).validate()  # chunk unused: must not raise
    with pytest.raises(ValueError, match="chunk"):
        CommConfig(codec="int8", chunk=1).validate()


def test_cast_codec_passthrough_for_ints_and_halfs():
    codec = get_codec("fp16")
    ints = jnp.arange(5, dtype=jnp.int32)
    np.testing.assert_array_equal(np.asarray(codec.encode(ints)), np.asarray(ints))
    assert codec.wire_bytes(4, jnp.int32) == 16
    assert codec.wire_bytes(4, jnp.float32) == 8
    assert codec.wire_bytes(4, jnp.float16) == 8  # already half: no-op


def test_error_feedback_residual_shrinks_error():
    """Designed-for EF hook: feeding the residual back recovers what one
    round's quantization dropped (two-round mean error < one-shot error)."""
    codec = get_codec(CommConfig(codec="int8", chunk=256))
    x = jax.random.normal(jax.random.PRNGKey(4), (1024,), jnp.float32)
    res = jnp.zeros_like(x)
    wire, res = codec.encode_with_residual(x, res)
    one_shot = np.asarray(codec.decode(wire, jnp.float32, x.size))
    wire2, _ = codec.encode_with_residual(x, res)
    second = np.asarray(codec.decode(wire2, jnp.float32, x.size))
    two_round = 0.5 * (one_shot + second)
    assert np.abs(two_round - np.asarray(x)).mean() < np.abs(
        one_shot - np.asarray(x)
    ).mean()


def test_error_feedback_config_fails_loudly():
    """error_feedback=True has no trainer path carrying the residual state:
    validate() must refuse it (silently dropping each round's quantization
    residual is the bias the flag claims to remove) until the LoCo-style
    accumulation is actually threaded through the outer step."""
    with pytest.raises(NotImplementedError, match="2407.04480"):
        CommConfig(codec="int8", error_feedback=True).validate()
    with pytest.raises(NotImplementedError, match="residual"):
        CommConfig(codec="fp16", error_feedback=True).validate()
    # "none" keeps its original, more specific rejection
    with pytest.raises(ValueError, match="lossy"):
        CommConfig(codec="none", error_feedback=True).validate()


def test_wire_roundtrip_identity_for_none():
    tree = _mixed_tree()
    out = wire_roundtrip(tree, CommConfig(codec="none"))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# communicators (stacked)
# ---------------------------------------------------------------------------


def test_stacked_gather_codec_matches_manual_cast():
    key = jax.random.PRNGKey(7)
    partner = jnp.asarray([1, 0, 3, 2])
    tree = {"w": jax.random.normal(key, (4, 6, 3)), "v": jax.random.normal(key, (4, 5))}
    comm = StackedGather(partner, CommConfig(codec="fp16"))
    out = comm.exchange(tree)
    ref = jax.tree.map(
        lambda x: jnp.take(x, partner, axis=0).astype(jnp.float16).astype(x.dtype), tree
    )
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stacked_gather_mean_matches_numpy():
    tree = {"w": jax.random.normal(jax.random.PRNGKey(1), (4, 3))}
    mean = StackedGather(None).allreduce_mean(tree)["w"]
    np.testing.assert_allclose(
        np.asarray(mean), np.asarray(tree["w"]).mean(0, keepdims=True).repeat(4, 0),
        rtol=1e-6,
    )


# ---------------------------------------------------------------------------
# bytes model (acceptance: fp16 ≥ 2x, int8 ≥ 3.5x on paper_llama shapes)
# ---------------------------------------------------------------------------


def test_bytes_model_paper_llama_reductions():
    params = bytes_model.abstract_params("paper-small-125m")
    base = bytes_model.outer_step_cost(params, CommConfig(codec="none"))
    fp16 = bytes_model.outer_step_cost(params, CommConfig(codec="fp16"))
    int8 = bytes_model.outer_step_cost(params, CommConfig(codec="int8"))
    assert base.payload_bytes / fp16.payload_bytes >= 2.0
    assert base.payload_bytes / int8.payload_bytes >= 3.5
    # fused: the whole (Δ, φ) payload is ONE message; unfused: one per leaf
    assert base.messages == 1
    unfused = bytes_model.outer_step_cost(params, CommConfig(fuse=False))
    assert unfused.messages > 10
    # overlap halves the blocking bytes (φ pre-sent), total unchanged
    ov = bytes_model.outer_step_cost(params, CommConfig(overlap=True))
    assert ov.blocking_bytes * 2 == ov.payload_bytes == base.payload_bytes


def test_bytes_model_methods():
    tree = {"w": jax.ShapeDtypeStruct((1024,), jnp.float32)}
    none_cost = bytes_model.outer_step_cost(tree, CommConfig(), method="none")
    assert none_cost.payload_bytes == 0 and none_cost.messages == 0
    diloco = bytes_model.outer_step_cost(tree, CommConfig(), method="diloco", world=4)
    # ring all-reduce: 2·(n−1)/n of the Δ payload
    assert diloco.payload_bytes == int(4096 * 2 * 3 / 4)
    # the baseline all-reduce is uncompressed: codecs must not shrink it
    diloco8 = bytes_model.outer_step_cost(
        tree, CommConfig(codec="int8"), method="diloco", world=4
    )
    assert diloco8.payload_bytes == diloco.payload_bytes
    assert diloco8.codec == "none"
    noloco = bytes_model.outer_step_cost(tree, CommConfig(), method="noloco")
    assert noloco.payload_bytes == 2 * 4096  # Δ and φ


# ---------------------------------------------------------------------------
# HLO: the paper claim, at the communicator level
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
def test_sharded_permute_fused_hlo_collective_count():
    """A fused NoLoCo outer step must lower to ≤ 2 collective-permutes (one
    per payload dtype; a single f32 payload gives exactly one) and ZERO
    all-reduces — for the raw wire and the fp16 codec alike."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = SRC
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.comm import CommConfig
    from repro.core import outer as outer_lib
    from repro.core.outer import OuterConfig
    from repro.launch import roofline as rf

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    cfg = OuterConfig(method="noloco")
    tree = {
        "w": jnp.zeros((2, 8, 4), jnp.float32),
        "b": [jnp.zeros((2, 16), jnp.float32), jnp.zeros((2, 3), jnp.float32)],
    }
    specs = jax.tree.map(lambda x: P("data"), tree)

    for codec in ("none", "fp16"):
        comm_cfg = CommConfig(codec=codec, fuse=True)

        def body(theta, phi, delta):
            state = outer_lib.OuterState(phi=phi, delta=delta,
                                         step=jnp.zeros((), jnp.int32))
            new_state, new_theta = outer_lib.outer_step_sharded(
                state, theta, cfg, axis_names=("data",), perm=[(0, 1), (1, 0)],
                comm_cfg=comm_cfg,
            )
            return new_theta, new_state.phi, new_state.delta

        fn = jax.shard_map(body, mesh=mesh, in_specs=(specs, specs, specs),
                           out_specs=(specs, specs, specs), check_vma=False)
        hlo = jax.jit(fn).lower(tree, tree, tree).compile().as_text()
        stats = rf.collective_bytes(hlo, model_size=1)
        assert stats.counts["collective-permute"] <= 2, (codec, stats.counts)
        assert stats.counts["all-reduce"] == 0, (codec, stats.counts)
        print(codec, stats.counts["collective-permute"])
    print("COMM HLO OK")
    """
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "COMM HLO OK" in out.stdout


# ---------------------------------------------------------------------------
# convergence: fp16 gossip matches uncompressed within 2%
# ---------------------------------------------------------------------------


def test_noloco_fp16_codec_convergence_parity():
    """NoLoCo on the toy LM (as in test_gossip_training) with a compressed
    fp16 wire must match the uncompressed final loss within 2%."""
    from repro.launch.train import run_training
    from repro.models.config import ModelConfig

    tiny = ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                       d_ff=128, vocab_size=128, dtype="float32", remat=False)
    kw = dict(method="noloco", replicas=4, per_replica_batch=2, seq_len=32,
              steps=30, inner_lr=3e-3, inner_steps=10, eval_every=0)
    base = run_training(tiny, codec="none", **kw)
    fp16 = run_training(tiny, codec="fp16", **kw)
    l0, l1 = base["losses"][-1], fp16["losses"][-1]
    assert l1 < base["losses"][0] * 0.85  # it actually trains
    assert abs(l1 - l0) / l0 < 0.02, (l0, l1)
