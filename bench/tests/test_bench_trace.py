"""The reduction from a profiler trace to the per-layer metrics, on two
recorded inner steps of the one-chip training cell."""

import json
from pathlib import Path

import pytest

from bench.tests.helpers import ROOT  # noqa: F401  (paths)
from bench import run as RUN
from bench.core import harness as H
from bench.core import trace as T
from bench.drivers import train as D

FIXTURE = Path(__file__).parent / "data" / "trace_train_r1.json"
PEAKS = H.peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def tr():
    return T.from_dict(json.loads(FIXTURE.read_text()))


def _info(tr):
    cell = H.load("cells", "train.stablelm2.r1")
    cfg = H.load("configs", cell["config"])
    start = min(s.start for s in tr.spans)
    end = max(e.end for e in tr.devices[0].ops)
    traffic = H.load("traffic", cell["traffic"])
    steps = sum(s.name == "bench.inner_step" for s in tr.spans)
    return {"window_s": (end - start) / 1e9, "dims": D.ref_dims(cfg), "traffic": traffic,
            "tokens": steps * traffic["per_replica_batch"] * traffic["seq"], "chips": 1}


def test_interval_arithmetic():
    assert T.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert T.covered([(0, 2), (1, 3), (5, 6)]) == 4
    assert T.subtract([(0, 10)], [(2, 3), (5, 7)]) == 7
    assert T.subtract([(0, 4), (6, 8)], [(3, 7)]) == 4


def test_busy_and_idle_of_two_steps(tr):
    assert len(tr.devices) == 1 and len(tr.spans) == 4
    info = _info(tr)
    busy = T.busy_s(tr)
    assert 0.5 < busy < info["window_s"]
    idle = RUN.metric_reader("train_idle_share")(tr, info, PEAKS)
    assert 0.0 < idle < 5.0


def test_kernel_roofline_and_mfu_from_the_trace(tr):
    info = _info(tr)
    calls = [e for e in tr.devices[0].ops if "flash" in e.name]
    assert len(calls) == 12  # 3 layers, forward and its recompute, 2 steps
    share = RUN.metric_reader("flash_attention_roofline")(tr, info, PEAKS)
    assert 0.1 < share < 100.0
    mfu = RUN.metric_reader("train_mfu")(tr, info, PEAKS)
    assert 5.0 < mfu < 100.0
    # the device time of the two ``jit_step`` runs, not the window, is the basis
    steps = [m for m in tr.devices[0].modules if m.name == "jit_step"]
    per_run = 2.23e9 * info["traffic"]["per_replica_batch"] * info["traffic"]["seq"]
    busy = sum(m.dur for m in steps) / 1e9
    assert mfu == pytest.approx(100.0 * per_run * len(steps) / (busy * PEAKS["bf16_flops"]), rel=0.02)


def test_breakdown_names_ops_and_gaps(tr):
    top = T.top_ops(tr, 10)
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0
    gaps = T.idle_gaps(tr, 3)
    assert gaps and all(isinstance(n, str) and g > 0 for n, g in gaps)


def test_readers_with_nothing_to_read_report_nothing(tr):
    info = _info(tr)
    assert RUN.metric_reader("outer_step_ms")(tr, info, PEAKS) is None


def test_outer_step_readers_on_a_built_trace():
    """A sync span, then the outer program; the inner step and the loss mean
    do not count."""
    ms = 1_000_000
    dev = T.Device("/device:TPU:0",
                   ops=[T.Event("%collective-permute-start.1", 10 * ms, 3 * ms),
                        T.Event("%fusion.2", 12 * ms, 2 * ms),
                        T.Event("%fusion.3", 30 * ms, 5 * ms)],
                   modules=[T.Event("jit__mean(1)", 8 * ms, 1000),
                            T.Event("jit_body(2)", 10 * ms, 5 * ms),
                            T.Event("jit_step(3)", 30 * ms, 5 * ms)])
    tr = T.Trace(devices=[dev], spans=[T.Event("bench.inner_step", 0, 9 * ms),
                                       T.Event("bench.outer_sync", 9 * ms + 500_000, 100_000),
                                       T.Event("bench.inner_step", 10 * ms, 20 * ms)])
    assert RUN.metric_reader("outer_step_ms")(tr, {}, PEAKS) == pytest.approx(5.0)


def test_decode_roofline_reads_one_least_time_per_decode_step():
    """Two decode programs of 20 ms at four requests over ~4k cached
    positions: the least time of each is its weights and live KV at HBM
    bandwidth."""
    from bench.flops import dense_lm as F

    ms = 1_000_000
    dev = T.Device("/device:TPU:0", ops=[],
                   modules=[T.Event("jit__unknown(7)", 0, 20 * ms), T.Event("jit__unknown(7)", 30 * ms, 20 * ms)])
    tr = T.Trace(devices=[dev], spans=[])
    dims = D.ref_dims(H.load("configs", "minitron-8b-d16"))
    info = {"dims": dims, "ticks": [(4, 4000), (4, 4100)]}
    least = (F.decode_bytes(dims, 4000) + F.decode_bytes(dims, 4100)) / PEAKS["hbm_bytes_per_s"]
    got = RUN.metric_reader("serve_decode_roofline")(tr, info, PEAKS)
    assert got == pytest.approx(100.0 * least / 0.040)
    assert 0.0 < got < 100.0


def test_first_token_tail_reader_reports_the_window_tail_and_nothing_unanswered():
    read = RUN.metric_reader("serve_first_token_p90_ms")
    assert read(None, {"serve_ttft_p90_ms": 450.25}, PEAKS) == 450.25
    assert read(None, {"serve_ttft_p90_ms": float("inf")}, PEAKS) is None
    assert read(None, {}, PEAKS) is None
