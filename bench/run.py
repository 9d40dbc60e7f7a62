#!/usr/bin/env python3
"""The benchmark's one entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this process, on the chips of the
machine it is started on, and prints one JSON line as the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` with ``--trace 1``), then ``checks``: every
number compared against the plain reference, beside its limit.  With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics.

Everything that belongs to one cell is found by name:
``bench/cells/<cell>.json`` names its driver (``bench/drivers/<driver>.py``),
its configuration (``bench/configs/<config>.json``) and its traffic
(``bench/traffic/<traffic>.json``); a per-layer metric ``<m>`` is read by
``bench/metrics/<m>.py``.  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import importlib
import importlib.util
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench.core import harness as H  # noqa: E402


def metric_reader(name: str):
    path = H.BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def execute(workload: str, seed: int, seconds: float, trace: bool, devs, t_start: float,
            patch: dict | None = None) -> tuple[dict, list]:
    """Run one cell on ``devs``; returns (result line, checks).  ``patch``
    (tests only) updates the cell, its model and its traffic, to run the
    same path at a size a CPU can hold."""
    bm = H.benchmark()
    entry = next(w for w in bm["workloads"] if w["name"] == workload)
    cell = H.load("cells", workload)
    if cell["config"] != entry["config"] or cell["traffic"] != entry["traffic"]:
        raise ValueError(f"{workload}: cell file and BENCHMARK.json disagree")
    cfgfile = H.load("configs", cell["config"])
    traffic = H.load("traffic", cell["traffic"])
    if patch:
        cell.update(patch.get("cell", {}))
        cfgfile["model"].update(patch.get("model", {}))
        traffic.update(patch.get("traffic", {}))
    driver = importlib.import_module(f"bench.drivers.{cell['driver']}")
    trace_dir = str(ROOT / ".bench_traces" / f"{workload}-{seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    info = driver.run(cell, cfgfile, traffic, seed, seconds, trace, t_start, devs, trace_dir)
    checks = [(k, float(info["compare"][k]), float(v)) for k, v in cell["limits"].items()]
    correct = all(v <= lim for _, v, lim in checks) and info["failed"] == 0
    device = dict(info["device"])
    metrics = {}
    result = {"correct": bool(correct), "attempted": int(info["attempted"]),
              "failed": int(info["failed"]), "metrics": metrics, "device": device}
    if not trace:
        for m in H.cell_metrics(bm, workload, "end_to_end"):
            metrics[m["name"]] = {"value": float(info[m["name"]]), "unit": m["unit"]}
        return result, checks
    from bench.core import trace as T

    tr = T.load(trace_dir)
    if tr is None or not tr.devices:
        raise RuntimeError("the profiler wrote no device trace")
    busy = T.busy_s(tr)
    device["busy_s"] = busy
    device["window_s"] = float(info["window_s"])
    peaks = H.peaks(device["kind"])
    for m in H.cell_metrics(bm, workload, "per_layer"):
        try:
            v = metric_reader(m["name"])(tr, info, peaks)
        except Exception as e:  # a reader that fails reports nothing
            print(f"bench: metric {m['name']} failed: {e!r}", file=sys.stderr)
            v = None
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result["breakdown"] = {"device_ops": T.top_ops(tr), "idle_gaps": T.idle_gaps(tr)}
    shutil.rmtree(trace_dir, ignore_errors=True)
    return result, checks


def main(argv=None) -> None:
    t_start = H.process_start()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout", file=sys.stderr)
        sys.exit(2)
    cell = H.load("cells", args.workload)
    H.enable_cache()
    devs = H.require_devices(cell["chips"])
    result, checks = execute(args.workload, args.seed, args.seconds, bool(args.trace), devs, t_start)
    H.emit(result, checks)


if __name__ == "__main__":
    main()
