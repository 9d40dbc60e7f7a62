"""The readers of the program's own spans (``src/repro/obs.py``), on a small
trace kept as JSON: one chip-pair training window of three inner steps and
an outer step, and three serving ticks; every expected value is worked out
by hand from the fixture, in ms."""

import json
from pathlib import Path

import pytest

from bench.tests.helpers import ROOT  # noqa: F401  (paths)
from bench import run as RUN
from bench.core import harness as H
from bench.core import program_spans as PS

FIXTURE = json.loads((Path(__file__).parent / "data" / "trace_program_spans.json").read_text())
PEAKS = H.peaks("TPU v5 lite")
TRAIN = ("train_host_step_ms", "train_program_idle_share")
SERVE = ("serve_host_tick_ms", "serve_engine_idle_share", "serve_queue_wait_p90_ms")
WINDOW = {"window_s": 0.010}


def trace(part, drop=False):
    d = dict(FIXTURE[part])
    if drop:
        d["program_spans"] = []
    return PS.from_dict(d)


def read(name, tr):
    return RUN.metric_reader(name)(tr, WINDOW, PEAKS)


def test_every_reader_is_declared_for_its_cell():
    bm = H.benchmark()
    cells = {m["name"]: m["workloads"] for m in bm["per_layer"]}
    assert all(cells[n] == ["train.stablelm2.r1"] for n in TRAIN)
    assert all(cells[n] == ["serve.minitron8b.chat"] for n in SERVE)


def test_train_host_step_is_the_median_inner_step_span():
    # inner steps of 1.0, 0.5 and 0.8 ms
    assert read("train_host_step_ms", trace("train")) == pytest.approx(0.8)


def test_train_program_idle_share_counts_idle_inside_program_spans_only():
    # chip 0 idle inside program spans: [0.5, 1.2) + [2.0, 2.4) + [4.6, 4.9)
    # + [8.1, 8.5) = 1.8 ms; the loss fetch's idle [1.9, 2.0) and the gaps
    # outside the program do not count; chip 1 is busy throughout; a 10-ms
    # window: (1.8 + 0) / 2 / 10
    assert read("train_program_idle_share", trace("train")) == pytest.approx(9.0)
    assert read("train_program_idle_share", trace("train")) < read("train_idle_share", trace("train"))


def test_serve_host_tick_leaves_out_the_fetches():
    # tick 1: 2.8 + 0.8 - 0.5; tick 2: 1.4 - 0.5 + 0.5 - 0.3; tick 3: 0.9 + 0.3
    assert read("serve_host_tick_ms", trace("serve")) == pytest.approx(1.2)


def test_serve_engine_idle_share_is_part_of_serve_idle_share():
    # engine work less fetches, idle on chip 0: [0.2, 1.0) + [2.0, 2.9)
    # + [3.6, 3.9) + [5.1, 5.2) + [5.7, 6.0) + [7.4, 7.6) + [8.1, 8.6)
    # + [10.2, 10.4) = 3.3 ms of 10 ms active; all idle there is 5.5 ms
    assert read("serve_engine_idle_share", trace("serve")) == pytest.approx(33.0)
    assert read("serve_idle_share", trace("serve")) == pytest.approx(55.0)


def test_serve_queue_wait_p90_interpolates_the_admissions():
    # waits 1, 3, 5 and 20 ms: position 2.7 of 0..3, 5 + 0.7 * 15
    assert read("serve_queue_wait_p90_ms", trace("serve")) == pytest.approx(15.5)


@pytest.mark.parametrize("name", TRAIN + SERVE)
def test_readers_report_nothing_without_program_spans(name):
    part = "train" if name in TRAIN else "serve"
    assert read(name, trace(part, drop=True)) is None


def test_interval_difference_and_intersection():
    assert PS.minus([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert PS.minus([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert PS.intersect([(0, 4), (6, 8)], [(3, 7)]) == [(3, 4), (6, 7)]
    assert PS.intersect([(0, 2)], [(2, 3)]) == []


def test_spans_are_read_from_the_newest_profile(tmp_path, monkeypatch):
    """With no spans carried, a trace's program spans are those of the
    newest ``*.xplane.pb`` under the traces directory, stats included; an
    empty directory gives none."""
    import jax

    from bench.core import trace as T
    from repro import obs

    monkeypatch.setattr(PS, "TRACES", tmp_path)
    empty = T.Trace(devices=[], spans=[])
    assert PS.spans(empty) == []
    jax.profiler.start_trace(str(tmp_path / "cell-1"))
    with obs.span("serve.step", queue_depth=3):
        with obs.span("serve.fetch"):
            pass
    with obs.span("other.name"):
        pass
    jax.profiler.stop_trace()
    tr = T.Trace(devices=[], spans=[])
    got = PS.spans(tr)
    assert [s.name for s in got] == ["serve.step", "serve.fetch"]
    assert got[0].stats == {"queue_depth": 3} and got[1].start >= got[0].start
    assert PS.spans(tr) is got  # read once per trace
