"""Serving cells: an open loop of requests through ``ServeEngine``
(continuous batching, chunked prefill, paged KV cache, paged Pallas kernels).

The harness streams as a streaming server does: after every engine tick it
calls ``drain()`` and stamps each token with its own clock when it reaches
the host (the engine's own stamps are dispatch times).  A request's time to
first token runs from the moment the schedule made it due.

Set-up makes the weights on the device from the seed in one program, builds
the engine, and serves a few warm-up requests through the same loop, which
compiles every program the window uses, and freezes what it made out of
the garbage collector until the window has closed.  The window then offers the
schedule's requests as they fall due for ``--seconds``; the loop runs on,
without new arrivals, until every request due in the window has finished or
a minute has passed.  After the device memory peak is read and the engine is
freed, the plain reference recomputes a sample of the finished requests.
"""

from __future__ import annotations

import gc
import math
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from bench.core import harness as H
from bench.core import weights as W
from bench.drivers.train import model_config, ref_dims
from bench.gen import open_loop as OL
from bench.ref import dense_lm as ref

GRACE_S = 60.0
WARM_RID0 = 1 << 30


class GcPauses:
    """Counts the garbage collector's passes while open, and their longest
    pause, so that a run shows whether the collector stalled its window."""

    def __init__(self):
        self.count, self.longest_ms, self._t = 0, 0.0, None

    def _cb(self, phase, _info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.count += 1
            self.longest_ms = max(self.longest_ms, (time.perf_counter() - self._t) * 1e3)

    def __enter__(self):
        gc.callbacks.append(self._cb)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._cb)


class Loop:
    def __init__(self, engine):
        self.engine = engine
        self.recs: dict = {}
        self.t0 = 0.0
        engine._token_cb = self._on_token

    def _on_token(self, rid, index, token, _dispatch_t):
        rec = self.recs.get(rid)
        if rec is not None:
            rec["stamps"].append(time.perf_counter() - self.t0)
            rec["tokens"].append(int(token))

    def serve(self, reqs, deadline_s: float, ticks=None) -> float:
        from jax.profiler import TraceAnnotation

        from repro.serve.engine import Request

        eng = self.engine
        self.t0 = t0 = time.perf_counter()
        for r in reqs:
            self.recs[r.rid] = {"due": r.due, "prompt": r.prompt, "max_new": r.max_new,
                                "stamps": [], "tokens": [], "submit": None}
        i, n = 0, len(reqs)
        while True:
            now = time.perf_counter() - t0
            while i < n and reqs[i].due <= now:
                r = reqs[i]
                self.recs[r.rid]["submit"] = now
                eng.submit(Request(r.rid, r.prompt.tolist(), r.max_new, 0.0, submit_t=t0 + r.due))
                i += 1
            if eng.idle:
                if i >= n:
                    break
                time.sleep(min(max(reqs[i].due - now, 0.0), 0.002))
                continue
            live, decodes = self._live(), eng.decode_steps
            with TraceAnnotation("bench.tick"):
                eng.step()
            with TraceAnnotation("bench.drain"):
                eng.drain()
            if ticks is not None and eng.decode_steps > decodes:
                ticks.append(live)
            if now > deadline_s:
                break
        return time.perf_counter() - t0

    def _live(self) -> tuple[int, int]:
        """(requests in decode, the cache positions the next decode step
        attends over in all: prompt + tokens streamed so far).  Read before
        a tick; ``serve`` keeps it only for ticks that ran a decode step."""
        live = [len(r["prompt"]) + len(r["tokens"]) for r in self.recs.values()
                if r["tokens"] and len(r["tokens"]) < r["max_new"]]
        return len(live), sum(live)


def metrics(recs: dict, window_s: float) -> dict:
    ttft, gaps, toks, failed = [], [], 0, 0
    for rid, r in recs.items():
        s = r["stamps"]
        if len(s) < r["max_new"]:
            failed += 1
        ttft.append((s[0] - r["due"]) * 1e3 if s else math.inf)
        gaps += [(b - a) * 1e3 for a, b in zip(s, s[1:]) if b <= window_s]
        toks += sum(1 for x in s if x <= window_s)
    late = max((r["submit"] - r["due"] for r in recs.values() if r["submit"] is not None), default=0.0)
    return {"serve_ttft_p75_ms": H.percentile(ttft, 75), "serve_ttft_p90_ms": H.percentile(ttft, 90),
            "serve_ttft_p50_ms": H.percentile(ttft, 50),
            "serve_itl_p95_ms": H.percentile(gaps, 95), "serve_itl_p50_ms": H.percentile(gaps, 50),
            "serve_tokens_per_s": toks / window_s, "failed": failed, "attempted": len(recs),
            "generator_late_max_s": late, "n_gaps": len(gaps)}


def sample(recs: dict, seed: int, min_tokens: int, max_requests: int) -> list[int]:
    """Finished requests for the check, drawn from the seed, the longest first."""
    done = [rid for rid, r in recs.items() if len(r["tokens"]) >= r["max_new"]]
    if not done:
        return []
    longest = max(done, key=lambda rid: len(recs[rid]["prompt"]) + recs[rid]["max_new"])
    rest = [rid for rid in done if rid != longest]
    order = list(np.random.default_rng(seed + 1).permutation(len(rest)))
    pick, tokens = [longest], recs[longest]["max_new"]
    for j in order:
        if tokens >= min_tokens or len(pick) >= max_requests:
            break
        pick.append(rest[j])
        tokens += recs[rest[j]]["max_new"]
    return pick


def ref_inputs(recs: dict, rids: list[int]):
    seqs, picks, served = [], [], []
    for rid in rids:
        r = recs[rid]
        toks = r["tokens"][: r["max_new"]]
        n = len(r["prompt"])
        seqs.append(np.concatenate([r["prompt"], np.asarray(toks[:-1], np.int32)]))
        picks.append(np.arange(n - 1, n - 1 + len(toks)))
        served.append(np.asarray(toks, np.int32))
    return seqs, picks, served


def widest_gap(words, dims, seqs, picks, served, cap: int, precision: str = "f32",
               against=None) -> float:
    """The widest gap by which a served token's reference logit lies below
    the reference's best.  With ``against`` (float32 reference logits), the
    served tokens are replaced by the argmax of ``precision``'s logits: the
    control's reading."""
    outs = ref.forward_logits(words, dims, seqs, picks, precision, cap)
    worst = 0.0
    for j, (lg, tok) in enumerate(zip(outs, served)):
        k = len(tok)
        if against is None:
            g = jnp.max(lg[:k], axis=-1) - jnp.take_along_axis(lg[:k], jnp.asarray(tok)[:, None], axis=-1)[:, 0]
        else:
            base = against[j][:k]
            choice = jnp.argmax(lg[:k], axis=-1)
            g = jnp.max(base, axis=-1) - jnp.take_along_axis(base, choice[:, None], axis=-1)[:, 0]
        worst = max(worst, float(jnp.max(g)))
    return worst


def build(cell: dict, cfgfile: dict, seed: int):
    from repro.models import model as M
    from repro.models.common import values_of
    from repro.serve.engine import ServeConfig, ServeEngine

    cfg = model_config(cfgfile)
    dims = ref_dims(cfgfile)
    words = W.seed_words(seed)
    template = jax.eval_shape(lambda: values_of(M.init_params(jax.random.PRNGKey(0), cfg)))
    params = jax.jit(lambda w: W.make_tree(w, template, dims))(words)
    engine = ServeEngine(params, cfg, ServeConfig(**cell["serve"]))
    return engine, dims, words


def warm(engine, cell: dict, traffic: dict, seed: int, vocab: int) -> None:
    """Serve the warm-up requests through the window's own loop: every
    program (chunk prefill, decode, the host-side updates) compiles here."""
    w = dict(traffic, rate_per_s=1000.0)
    reqs = OL.schedule(seed ^ 0x5EED, cell["warmup_requests"] / 1000.0, w, vocab, rid0=WARM_RID0)
    Loop(engine).serve(reqs, GRACE_S)


def run(cell: dict, cfgfile: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, t_start: float, devs, trace_dir: str):
    engine, dims, words = build(cell, cfgfile, seed)
    warm(engine, cell, traffic, seed, dims["vocab_size"])
    reqs = OL.schedule(seed, seconds, traffic, dims["vocab_size"])
    loop = Loop(engine)
    ticks: list = []
    # What set-up made (imports, the engine, compiled programs) lives as long
    # as the server: frozen out of the collector, as a long-running server
    # freezes it after warm-up, a full pass no longer walks it mid-window.
    gc.collect()
    gc.freeze()
    setup_s = time.time() - t_start
    if trace:
        jax.profiler.start_trace(trace_dir)
    with GcPauses() as pauses:
        wall = loop.serve(reqs, seconds + GRACE_S, ticks)
    if trace:
        jax.profiler.stop_trace()
    gc.unfreeze()
    dev = H.device_record(devs)
    m = metrics(loop.recs, seconds)
    print(f"bench: {pauses.count} garbage collections in the window, the longest "
          f"{pauses.longest_ms:.1f} ms", file=sys.stderr, flush=True)
    recs = loop.recs
    scfg = cell["serve"]
    del engine, loop
    gc.collect()
    chk = cell["check"]
    rids = sample(recs, seed, chk["min_tokens"], chk["max_requests"])
    seqs, picks, served = ref_inputs(recs, rids)
    gap = widest_gap(words, dims, seqs, picks, served, scfg["max_new_cap"]) if rids else math.inf
    info = dict(m)
    info.update({
        "setup_s": setup_s, "window_s": wall, "device": dev,
        "compare": {"logit_gap": gap, "checked_requests": len(rids),
                    "checked_tokens": int(sum(len(s) for s in served))},
        "ticks": ticks, "dims": dims, "traffic": traffic,
        "requests": [(len(r["prompt"]), len(r["tokens"])) for r in recs.values()],
    })
    return info
