"""The unified training engine (repro/train): resume correctness, telemetry,
the grad-free eval path, and the pipeline runtime's full §3.1+§3.2 method."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.outer import OuterConfig
from repro.data import LoaderConfig, eval_batches, shard_iterator
from repro.launch.train import run_training
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig
from repro.pipeline import PipelineTrainer
from repro.train import LoopConfig, PipelineProgram, TrainLoop

TINY = ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                   d_ff=128, vocab_size=128, dtype="float32", remat=False)

KW = dict(method="noloco", replicas=4, per_replica_batch=2, seq_len=32,
          inner_lr=3e-3, inner_steps=4, eval_every=0, total_steps=12)


def test_resume_matches_uninterrupted(tmp_path):
    """Interrupt at step 6, restore, continue to 12: the loss trajectory must
    be IDENTICAL to an uninterrupted 12-step run (state + loader fast-forward
    + PRNG keys all round-trip)."""
    full = run_training(TINY, steps=12, **KW)
    d = str(tmp_path / "ckpt")
    run_training(TINY, steps=6, ckpt_dir=d, **KW)
    cont = run_training(TINY, steps=12, ckpt_dir=d, resume=True, **KW)
    assert cont["start_step"] == 6
    assert cont["steps_run"] == 6
    np.testing.assert_array_equal(
        np.asarray(full["losses"][6:]), np.asarray(cont["losses"])
    )
    # final states agree too, not just the scalar losses
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(full["state"].theta)[0]),
        np.asarray(jax.tree.leaves(cont["state"].theta)[0]),
    )


def test_resume_without_checkpoint_starts_fresh(tmp_path):
    res = run_training(TINY, steps=4, ckpt_dir=str(tmp_path / "none"),
                       resume=True, **KW)
    assert res["start_step"] == 0 and len(res["losses"]) == 4


def test_periodic_checkpoints_respect_keep(tmp_path):
    d = str(tmp_path / "ckpt")
    run_training(TINY, steps=12, ckpt_dir=d, ckpt_every=3, **KW)
    steps = sorted(int(n.split("_")[1]) for n in os.listdir(d))
    assert len(steps) == 3  # LoopConfig.ckpt_keep default
    assert steps[-1] == 12


def test_jsonl_telemetry_stream(tmp_path):
    path = str(tmp_path / "events.jsonl")
    res = run_training(TINY, steps=8, log_jsonl=path,
                       **{**KW, "eval_every": 4})
    events = [json.loads(l) for l in open(path)]
    kinds = [e["event"] for e in events]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    assert kinds.count("step") == 8
    assert kinds.count("outer") == res["outer_syncs"] == 2
    assert kinds.count("eval") == 2
    steps = [e for e in events if e["event"] == "step"]
    assert [round(e["loss"], 6) for e in steps] == [
        round(l, 6) for l in res["losses"]
    ]
    outer = next(e for e in events if e["event"] == "outer")
    assert outer["payload_bytes"] > 0
    # run_end carries the throughput/comm accounting
    end = events[-1]
    assert end["tokens_per_s"] > 0 and end["comm_bytes"] > 0


def test_eval_is_grad_free_and_matches_training_loss_scale():
    """GossipTrainer.eval_loss (public, no grads) should agree with the loss
    the training step reports on the same batch/params."""
    from repro.core import GossipTrainer
    from repro.launch.train import method_config
    from repro.models import model as model_api
    from repro.models.common import values_of
    from repro.parallel.sharding import ShardCtx

    ctx = ShardCtx.local()
    tcfg = method_config("noloco", inner_lr=1e-3, total_steps=10)
    tr = GossipTrainer(
        tcfg, lambda p, b, r: model_api.loss_fn(p, TINY, b, ctx)[0]
    )
    one = values_of(model_api.init_params(jax.random.PRNGKey(0), TINY))
    stacked = jax.tree.map(
        lambda v: jnp.broadcast_to(v[None], (4,) + v.shape), one
    )
    state = tr.init(stacked)
    it = shard_iterator(LoaderConfig(
        vocab_size=TINY.vocab_size, seq_len=32, per_replica_batch=2, replicas=4
    ))
    batch = {k: jnp.asarray(v) for k, v in next(it).items()}
    rng = jax.random.PRNGKey(1)
    ev = tr.eval_loss(state.theta, batch, rng)
    assert ev.shape == (4,)
    _, metrics = tr.inner_step(state, batch, rng)
    np.testing.assert_allclose(
        np.asarray(ev), np.asarray(metrics["loss"]), rtol=1e-5
    )


def test_shared_weight_std_helper_consistency():
    from repro.core import GossipTrainer
    from repro.core.metrics import replica_weight_std

    tree = {"a": jax.random.normal(jax.random.PRNGKey(0), (4, 8, 3))}
    a = float(GossipTrainer.replica_weight_std(tree))
    b = float(replica_weight_std(tree))
    assert a == b
    # list-of-stages form averages over all leaves of all stages
    c = float(replica_weight_std([tree, tree]))
    np.testing.assert_allclose(c, a, rtol=1e-6)


# ---------------------------------------------------------------------------
# Pipeline runtime: §3.1 routing + §3.2 gossip through the same loop
# ---------------------------------------------------------------------------


def _pipeline_loop(method, steps, tmpdir=None, resume=False, ckpt_every=0):
    outer = None
    if method != "none":
        outer = OuterConfig(method=method, inner_steps=5, seed=0)
    tr = PipelineTrainer(
        TINY, num_stages=2, replicas=4,
        inner=AdamWConfig(lr=3e-3, weight_decay=0.0),
        routing="random", outer=outer, seed=0,
    )
    lcfg = LoaderConfig(vocab_size=TINY.vocab_size, seq_len=32,
                        per_replica_batch=2, replicas=4)
    loop = TrainLoop(
        PipelineProgram(tr),
        lambda start: shard_iterator(lcfg, start_step=start),
        LoopConfig(steps=steps, ckpt_dir=tmpdir, resume=resume,
                   ckpt_every=ckpt_every),
    )
    return loop.run()


def test_pipeline_noloco_reduces_weight_std_vs_none():
    """Acceptance: the pipeline runtime trains with routing AND the gossip
    outer step; cross-replica weight std decreases versus method=none."""
    none = _pipeline_loop("none", 20)
    noloco = _pipeline_loop("noloco", 20)
    assert noloco["outer_syncs"] == 4
    assert noloco["comm_bytes"] > 0
    assert noloco["final_weight_std"] < 0.7 * none["final_weight_std"], (
        noloco["final_weight_std"], none["final_weight_std"]
    )
    assert noloco["losses"][-1] < noloco["losses"][0]


def test_pipeline_resume_matches_uninterrupted(tmp_path):
    full = _pipeline_loop("noloco", 12)
    d = str(tmp_path / "pipe")
    _pipeline_loop("noloco", 6, tmpdir=d)
    cont = _pipeline_loop("noloco", 12, tmpdir=d, resume=True)
    assert cont["start_step"] == 6
    np.testing.assert_array_equal(
        np.asarray(full["losses"][6:]), np.asarray(cont["losses"])
    )


def test_pipeline_outer_state_reset_semantics():
    """After a pipeline outer step every stage's fast weights equal its new
    slow weights (look-ahead), exactly as in the stacked trainer."""
    tr = PipelineTrainer(
        TINY, num_stages=2, replicas=4,
        inner=AdamWConfig(lr=3e-3, weight_decay=0.0),
        outer=OuterConfig(method="noloco", inner_steps=2, seed=0),
    )
    state = tr.init(jax.random.PRNGKey(0))
    it = shard_iterator(LoaderConfig(
        vocab_size=TINY.vocab_size, seq_len=16, per_replica_batch=2, replicas=4
    ))
    for _ in range(2):
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        state, _ = tr.train_step(state, batch)
    state, synced = tr.maybe_outer_step(state)
    assert synced
    for s in range(2):
        for a, b in zip(jax.tree.leaves(state["params"][s]),
                        jax.tree.leaves(state["outer"]["phi"][s])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # counter advanced, next call is a no-op until m more steps
    assert state["outer"]["step"] == 1
    _, synced = tr.maybe_outer_step(state)
    assert not synced


def test_eval_batches_helper():
    lcfg = LoaderConfig(vocab_size=64, seq_len=8, per_replica_batch=2, replicas=2)
    bs = eval_batches(lcfg, 3)
    assert len(bs) == 3
    it = shard_iterator(lcfg)
    np.testing.assert_array_equal(bs[0]["tokens"], next(it)["tokens"])


# ---------------------------------------------------------------------------
# Distributed runtime
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
def test_distributed_entry_resumes():
    """train_distributed drives the engine end-to-end with --resume."""
    import subprocess, sys, tempfile

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    with tempfile.TemporaryDirectory() as d:
        args = [sys.executable, "-m", "repro.launch.train_distributed",
                "--reduced", "--data", "4", "--model", "2", "--steps", "8",
                "--inner-steps", "4", "--ckpt-dir", d, "--ckpt-every", "4"]
        out = subprocess.run(args, capture_output=True, text=True, env=env,
                             timeout=560)
        assert out.returncode == 0, out.stdout + out.stderr
        out2 = subprocess.run(args + ["--resume"], capture_output=True,
                              text=True, env=env, timeout=560)
        assert out2.returncode == 0, out2.stdout + out2.stderr
