"""Spans inside the program (``repro.obs``), read back from real profiles
that ``jax.profiler`` writes on the CPU: the serving engine at the serving
benchmark's tiny sizes, a one-replica shard_map training program, the
training loop, and the compile marker."""

import glob
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.outer import OuterConfig
from repro.data import LoaderConfig
from repro.kernels import flash_attention
from repro.launch.mesh import make_mesh
from repro.launch.train_distributed import DistributedTrainer
from repro.models import model as M
from repro.models.common import values_of
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig
from repro.parallel import plans as plans_lib
from repro.serve import Request, ServeConfig, ServeEngine
from repro.train import DistributedProgram, LoopConfig, make_loop

PREFIXES = ("train.", "outer.", "serve.", "jax.")

# the serving benchmark's CPU sizes: six query heads over two KV heads, 16 wide
SERVE_CFG = ModelConfig(num_layers=2, d_model=64, num_heads=6, num_kv_heads=2, head_dim=16,
                        d_ff=128, vocab_size=256, norm_type="layernorm", mlp_variant="relu2",
                        tie_embeddings=False, dtype="float32")
SERVE = ServeConfig(max_slots=4, num_pages=64, page_size=8, max_new_cap=16,
                    prefill_chunk=16, prefill_budget=32)
TRAIN_CFG = ModelConfig(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
                        vocab_size=256, dtype="float32", remat=False)

# where each span may sit: the spans that can enclose it
PARENTS = {
    "serve.evict": ("serve.step",),
    "serve.admit": ("serve.step",),
    "serve.admit_request": ("serve.admit",),
    "serve.prefill_chunk": ("serve.step",),
    "serve.decode": ("serve.step",),
    "serve.fetch": ("serve.evict", "serve.drain"),
    "serve.emit": ("serve.drain",),
    "train.stage_batch": ("train.inner_step",),
    "train.dispatch": ("train.inner_step",),
    "outer.plan": ("train.outer_step",),
    "outer.dispatch": ("train.outer_step",),
}


class Span:
    def __init__(self, e):
        self.name = e.name
        self.start = int(e.start_ns)
        self.end = self.start + int(e.duration_ns)
        self.stats = dict(e.stats)

    def within(self, other) -> bool:
        return other.start <= self.start and self.end <= other.end


def traced(tmp_path, fn):
    """Run ``fn`` under the profiler; returns (its result, the program's
    spans in start order)."""
    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    spans = [Span(e) for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events if e.name.startswith(PREFIXES)]
    return out, sorted(spans, key=lambda s: (s.start, -s.end))


def named(spans, name):
    return [s for s in spans if s.name == name]


def check_nesting(spans):
    for s in spans:
        parents = PARENTS.get(s.name)
        if parents:
            assert any(p.name in parents and s.within(p) for p in spans), s.name


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def params():
    return values_of(M.init_params(jax.random.PRNGKey(0), SERVE_CFG))


def requests(rid0=0, n=6):
    rng = np.random.default_rng(rid0 + 1)
    return [Request(rid0 + i, rng.integers(1, 256, int(rng.integers(4, 40))).tolist(),
                    int(rng.integers(2, 12))) for i in range(n)]


def serve(engine, reqs):
    """The streaming server's loop: a tick, then a drain, until idle.
    Returns the tokens each request streamed."""
    got = {r.rid: [] for r in reqs}
    engine._token_cb = lambda rid, i, tok, t: got[rid].append(tok)
    for r in reqs:
        engine.submit(r)
    while not engine.idle:
        engine.step()
        engine.drain()
    return got


def test_serve_engine_writes_every_span_nested(tmp_path, params):
    eng = ServeEngine(params, SERVE_CFG, SERVE)
    serve(eng, requests(100))  # warm-up
    before = eng.decode_steps
    got, spans = traced(tmp_path, lambda: serve(eng, requests(200)))
    assert all(len(v) > 0 for v in got.values())
    names = {s.name for s in spans}
    assert {"serve.step", "serve.evict", "serve.fetch", "serve.admit", "serve.admit_request",
            "serve.prefill_chunk", "serve.decode", "serve.drain", "serve.emit"} <= names
    check_nesting(spans)
    # one decode span per decode program the engine ran
    assert len(named(spans, "serve.decode")) == eng.decode_steps - before
    # every request admitted once; every streamed token counted by an emit
    assert sorted(s.stats["rid"] for s in named(spans, "serve.admit_request")) == list(range(200, 206))
    assert sum(s.stats["tokens"] for s in named(spans, "serve.emit")) == sum(map(len, got.values()))
    ticks = named(spans, "serve.step")
    assert all({"queue_depth", "free_pages", "decode_slots"} <= set(s.stats) for s in ticks)
    assert ticks[0].stats["queue_depth"] == 6 and ticks[0].stats["free_pages"] == SERVE.num_pages
    assert sum(s.stats["evicted"] for s in named(spans, "serve.evict")) == 6
    for s in named(spans, "serve.prefill_chunk"):
        assert 0 < s.stats["tokens"] <= SERVE.prefill_chunk and s.stats["base"] % SERVE.prefill_chunk == 0


def test_decode_stats_match_the_engine_state(tmp_path, params):
    """``active_slots`` and ``live_tokens`` are the slots the decode step
    advances and the positions it attends over: read from the host mirror,
    they agree with the device state the step is given."""
    eng = ServeEngine(params, SERVE_CFG, SERVE)
    seen = []
    decode = eng._decode

    def recording():
        st = jax.device_get(eng.state)
        act = np.asarray(st.active)
        seen.append((int(act.sum()), int((np.asarray(st.positions)[act] + 1).sum())))
        decode()

    eng._decode = recording
    serve(eng, requests(300))  # warm-up
    seen.clear()
    _, spans = traced(tmp_path, lambda: serve(eng, requests(400)))
    got = [(s.stats["active_slots"], s.stats["live_tokens"]) for s in named(spans, "serve.decode")]
    assert got == seen and len(got) > 0


def test_queue_wait_is_positive_for_a_request_that_waited_for_pages(tmp_path, params):
    # 8 pages of 8: the first request holds 7 of them, the second needs 4
    scfg = ServeConfig(max_slots=4, num_pages=8, page_size=8, max_new_cap=16,
                       prefill_chunk=16, prefill_budget=32)
    eng = ServeEngine(params, SERVE_CFG, scfg)
    serve(eng, requests(500, 2))  # warm-up
    reqs = [Request(1, list(range(1, 41)), 16), Request(2, list(range(1, 21)), 10)]
    _, spans = traced(tmp_path, lambda: serve(eng, reqs))
    adm = {s.stats["rid"]: s for s in named(spans, "serve.admit_request")}
    assert adm[1].stats["queued_ms"] >= 0.0
    assert adm[2].stats["pages"] == 4 and adm[1].stats["pages"] == 7
    # the second request waited through every tick before its admission
    waited = [t for t in named(spans, "serve.step") if t.end <= adm[2].start]
    assert len(waited) > 1
    assert adm[2].stats["queued_ms"] >= sum(t.end - t.start for t in waited) / 1e6 > 0.0
    evicts = [s for s in named(spans, "serve.evict") if s.stats["evicted"] and s.end <= adm[2].start]
    assert evicts  # it was admitted only once the first request's pages came back


def test_tokens_served_under_the_profiler_are_those_served_without_it(tmp_path, params):
    plain = serve(ServeEngine(params, SERVE_CFG, SERVE), requests(600))
    got, spans = traced(tmp_path, lambda: serve(ServeEngine(params, SERVE_CFG, SERVE), requests(600)))
    assert spans and got == plain


def test_no_compile_marker_after_warm_up(tmp_path, params):
    eng = ServeEngine(params, SERVE_CFG, SERVE)
    serve(eng, requests(700, 8))
    _, spans = traced(tmp_path, lambda: serve(eng, requests(800, 8)))
    assert named(spans, "serve.decode") and not named(spans, "jax.compile")
    # a program compiled under the profiler does leave its marker
    _, spans = traced(tmp_path, lambda: jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0)).block_until_ready())
    marks = named(spans, "jax.compile")
    assert marks and all(m.stats["secs"] > 0 for m in marks)


def test_flash_kernel_leaves_its_plan_when_traced(tmp_path):
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 384, 16))

    def call():
        return flash_attention.flash_attention_bhsd(
            q, q, q, mode="causal", block_q=128, block_kv=128).block_until_ready()

    _, spans = traced(tmp_path, call)
    marks = named(spans, "jax.flash_plan")
    assert [m.stats for m in marks] == [{"block_q": 128, "block_kv": 128, "live_tiles": 6, "tiles": 9}]
    # the marker is left when the kernel is traced, not when it runs
    _, spans = traced(tmp_path, call)
    assert not named(spans, "jax.flash_plan")


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def one_replica_program(m=3):
    mesh = make_mesh((1, 1), ("data", "model"))
    trainer = DistributedTrainer(
        cfg=TRAIN_CFG, mesh=mesh, plan=plans_lib.make_plan("gossip_dp", mesh, shape_kind="train"),
        outer_cfg=OuterConfig(method="noloco", inner_steps=m), inner_cfg=AdamWConfig(lr=1e-3),
        pairing_pool=1,
    )
    return DistributedProgram(trainer)


def batch(k):
    t = np.random.default_rng(k).integers(0, 256, (1, 2, 17)).astype(np.int32)
    return {"tokens": t[..., :-1], "labels": t[..., 1:]}


def test_distributed_program_spans_inner_steps_and_outer_only_on_sync(tmp_path):
    prog = one_replica_program(m=3)
    state = prog.init_state(batch(0))
    for k in range(3):  # warm-up: the inner program and the outer step of round 0
        state, _ = prog.inner_step(state, batch(k), None)
        state, _ = prog.maybe_outer_step(state)

    def window():
        nonlocal state
        synced = []
        for k in range(3, 7):
            state, met = prog.inner_step(state, batch(k), None)
            float(jnp.mean(met["loss"]))
            state, s = prog.maybe_outer_step(state)
            synced.append(s)
        return synced

    synced, spans = traced(tmp_path, window)
    check_nesting(spans)
    inner = named(spans, "train.inner_step")
    assert [s.stats["step"] for s in inner] == [3, 4, 5, 6]
    for s in inner:
        kids = [c.name for c in spans if c is not s and c.within(s)]
        assert kids == ["train.stage_batch", "train.dispatch"]
    outer = named(spans, "train.outer_step")
    assert synced == [False, False, True, False] and len(outer) == 1
    assert outer[0].stats == {"outer_index": 1, "stream": 0, "compiled": 0}
    assert [c.name for c in spans if c is not outer[0] and c.within(outer[0])] == [
        "outer.plan", "outer.dispatch"]
    # the outer step runs after the third inner step of the window and before the fourth
    assert inner[2].end <= outer[0].start and outer[0].end <= inner[3].start


def test_train_loop_spans_its_own_host_work(tmp_path):
    prog = one_replica_program(m=2)
    loop = make_loop(
        prog,
        LoaderConfig(vocab_size=256, seq_len=16, per_replica_batch=2, replicas=1, seed=0),
        LoopConfig(steps=4, eval_every=2, seed=0, ckpt_dir=str(tmp_path / "ck"), ckpt_every=4,
                   log_jsonl=str(tmp_path / "run.jsonl")),
    )
    out, spans = traced(tmp_path, loop.run)
    assert out["steps_run"] == 4
    assert len(named(spans, "train.loss_fetch")) == 4
    assert len(named(spans, "train.inner_step")) == 4
    assert len(named(spans, "train.outer_step")) == 2
    assert [s.stats["step"] for s in named(spans, "train.eval")] == [2, 4]
    assert [s.stats["step"] for s in named(spans, "train.checkpoint")] == [4]
    lines = Path(tmp_path / "run.jsonl").read_text().splitlines()
    assert len(named(spans, "train.telemetry")) == len(lines)


def test_spans_take_stats_and_metadata_with_the_profiler_off():
    assert not obs.enabled()
    with obs.span("serve.step", queue_depth=1) as sp:
        sp.set_metadata(evicted=0)


def test_span_names_in_the_program_keep_to_their_prefixes():
    src = Path(__file__).resolve().parents[1] / "src" / "repro"
    names = set()
    for f in src.rglob("*.py"):
        text = f.read_text()
        names |= set(re.findall(r"""(?:obs\.span|obs\.mark|TraceAnnotation)\(\s*["']([^"']+)["']""", text))
        assert "bench." not in "".join(re.findall(r"""span\(\s*["'][^"']*["']""", text)), f
    assert names and all(n.startswith(PREFIXES) for n in names), names
