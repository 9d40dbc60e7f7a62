"""The training cell's harness path end to end on the CPU, at a tiny size:
set-up, the checked steps, the window and the comparison with the plain
reference; and the faults the comparison must catch."""

import time

import jax
import pytest

from bench.tests.helpers import SEED, TINY_TRAIN
from bench import run as RUN


def _run(patch=None):
    return RUN.execute("train.stablelm2.r1", SEED, 0.5, False, jax.devices()[:1], time.time(),
                       patch=patch or TINY_TRAIN)


def test_train_r1_cell_found_by_name_runs_and_agrees_with_reference():
    result, checks = _run()
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert {c[0] for c in checks} == {"loss_gap", "grad_gap", "change_gap", "outer_gap"}


def test_train_step_returning_its_state_unchanged_is_not_correct(monkeypatch):
    from repro.launch import train_distributed as TD

    real = TD.steps_lib.build_train_step

    def frozen(*a, **k):
        b = real(*a, **k)
        ev = b.eval_fn

        def step(theta, opt, batch):
            losses = ev(theta, batch)
            return theta, opt, {"loss": losses, "grad_norm": losses * 0}
        b.step_fn = step
        return b

    monkeypatch.setattr(TD.steps_lib, "build_train_step", frozen)
    result, checks = _run()
    assert not result["correct"]
    assert dict((n, v) for n, v, _ in checks)["change_gap"] == pytest.approx(1.0)


def test_train_half_batch_is_not_correct(monkeypatch):
    from repro.launch import train_distributed as TD

    real = TD.steps_lib.build_train_step

    def halved(*a, **k):
        b = real(*a, **k)
        inner = b.step_fn

        def step(theta, opt, batch):
            r = theta["embed"]["table"].shape[0]
            half = {key: v.reshape(r, -1, v.shape[-1])[:, : v.shape[0] // r // 2].reshape(-1, v.shape[-1])
                    for key, v in batch.items()}
            return inner(theta, opt, half)
        b.step_fn = step
        return b

    monkeypatch.setattr(TD.steps_lib, "build_train_step", halved)
    result, checks = _run()
    assert not result["correct"], checks


def test_train_control_fp8_reads_above_the_sound_program():
    """The control (the reference with float8 products) at a tiny size reads
    far above what the program reads against the same reference."""
    import numpy as np

    from bench.core import harness as H
    from bench.drivers import train as D

    cell = H.load("cells", "train.stablelm2.r1")
    cfg = H.load("configs", cell["config"])
    cfg["model"].update(TINY_TRAIN["model"])
    tr = dict(H.load("traffic", cell["traffic"]), **TINY_TRAIN["traffic"])
    dims = D.ref_dims(cfg)
    devs = jax.devices()[:1]
    su = D.Setup(cell, cfg, tr, SEED, devs)
    prog = D.setup_and_check_steps(su)
    words, batches = su.words, su.batches
    del su
    base = D.reference(cell, dims, words, batches, 1, 2, SEED, devs)
    ctrl = D.reference(cell, dims, words, batches, 1, 2, SEED, devs, precision="fp8")
    sound, low = D.compare(prog, base), D.compare(ctrl, base)
    for k in ("loss_gap", "grad_gap", "change_gap", "outer_gap"):
        assert sound[k] < 1e-4, (k, sound[k])
    assert max(low[k] for k in ("loss_gap", "grad_gap", "change_gap", "outer_gap")) > 3e-3
    half = D.compare(D.reference(cell, dims, words, batches, 1, 2, SEED, devs, half_batch=True), base)
    assert half["grad_gap"] > 1e-2
    assert np.isfinite(low["loss_gap"])
