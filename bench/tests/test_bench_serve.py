"""The serving cell's harness path end to end on the CPU at a tiny size:
warm-up, the open-loop window with streamed tokens, the metrics, and the
comparison of served tokens with the plain reference's logits; and a token
altered where the decode step produces it."""

import gc
import time

import jax
import jax.numpy as jnp

from bench.tests.helpers import SEED, TINY_SERVE
from bench import run as RUN
from bench.core import harness as H


def _run(seconds=2.0):
    return RUN.execute("serve.minitron8b.chat", SEED, seconds, False, jax.devices()[:1],
                       time.time(), patch=TINY_SERVE)


def test_serve_chat_cell_found_by_name_runs_and_agrees_with_reference():
    from repro.serve import engine as E

    E._programs.cache_clear()
    frozen = gc.get_freeze_count()
    result, checks = _run()
    assert result["correct"], checks
    assert result["attempted"] == 20 and result["failed"] == 0
    assert set(result["metrics"]) == {
        m["name"] for m in H.cell_metrics(H.benchmark(), "serve.minitron8b.chat", "end_to_end")}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert [c[0] for c in checks] == ["logit_gap"]
    assert gc.get_freeze_count() <= frozen  # set-up's objects are unfrozen once the window closes


def test_serve_token_altered_where_produced_is_not_correct(monkeypatch):
    from repro.serve import engine as E

    real = E._decode_core

    def altered(cfg, ctx, params, state):
        new = real(cfg, ctx, params, state)
        # slot 0's third output token is replaced by its neighbour id
        idx = jnp.clip(state.out_len[0], 0, new.out_buf.shape[1] - 1)
        hit = state.active[0] & (state.out_len[0] == 2)
        tok = new.out_buf[0, idx]
        buf = new.out_buf.at[0, idx].set(jnp.where(hit, (tok + 1) % cfg.vocab_size, tok))
        return new.__class__(**{**new.__dict__, "out_buf": buf,
                                "tokens": new.tokens.at[0].set(jnp.where(hit, buf[0, idx], new.tokens[0]))})

    E._programs.cache_clear()
    monkeypatch.setattr(E, "_decode_core", altered)
    try:
        result, checks = _run()
    finally:
        E._programs.cache_clear()
    assert not result["correct"], checks


def test_serve_control_fp8_reads_above_the_sound_program():
    from bench.drivers import serve as S
    from bench.gen import open_loop as OL
    from bench.ref import dense_lm as ref

    cell = H.load("cells", "serve.minitron8b.chat")
    cell.update(TINY_SERVE["cell"])
    cfg = H.load("configs", cell["config"])
    cfg["model"].update(TINY_SERVE["model"])
    tr = dict(H.load("traffic", cell["traffic"]), **TINY_SERVE["traffic"])
    engine, dims, words = S.build(cell, cfg, SEED)
    loop, ticks = S.Loop(engine), []
    loop.serve(OL.schedule(SEED, 1.0, tr, dims["vocab_size"]), 60.0, ticks)
    assert len(ticks) == engine.decode_steps > 0  # one tick kept per decode program
    rids = S.sample(loop.recs, SEED, 40, 6)
    seqs, picks, served = S.ref_inputs(loop.recs, rids)
    cap = cell["serve"]["max_new_cap"]
    base = ref.forward_logits(words, dims, seqs, picks, "f32", cap)
    sound = S.widest_gap(words, dims, seqs, picks, served, cap)
    low = S.widest_gap(words, dims, seqs, picks, served, cap, precision="fp8", against=base)
    assert sound < 1e-3 < low
