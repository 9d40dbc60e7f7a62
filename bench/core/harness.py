"""What every run shares: the clock of set-up, the compile cache, the device
check, the cell's files found by name, and the one-line result."""

from __future__ import annotations

import json
import math
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def process_start() -> float:
    """Wall-clock time at which this process started (Linux /proc), so that
    set-up counts interpreter start and imports too."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        start = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19])
        for line in Path("/proc/stat").read_text().splitlines():
            if line.startswith("btime "):
                return int(line.split()[1]) + start / ticks
    except (OSError, ValueError, IndexError):
        pass
    return time.time()


def enable_cache() -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where it
    is set, else the fixed directory ``.jax_cache`` of this checkout (the
    program's own default too)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def load(kind: str, name: str) -> dict:
    """``kind`` is ``cells``, ``configs`` or ``traffic``; the file is
    ``bench/<kind>/<name>.json``."""
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def cell_metrics(bm: dict, cell: str, section: str) -> list[dict]:
    return [m for m in bm[section] if "workloads" not in m or cell in m["workloads"]]


def require_devices(chips: int, expect_platform: str = "tpu"):
    """The devices of the run, or exit non-zero with no result line when
    JAX finds no accelerator or fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform != expect_platform or len(devs) < chips:
        print(f"bench: needs {chips} {expect_platform} device(s); JAX reports "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr, flush=True)
        sys.exit(3)
    return devs[:chips]


def device_record(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile; +inf entries (missing answers) sort
    last, so a tail that reaches them reads +inf."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(xs[hi]):
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "core" / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}: add it to bench/core/peaks.json")
    return table[kind]


def emit(result: dict, checks: list[tuple[str, float, float]]) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, and the result as the last line of standard output."""
    for name, value, limit in checks:
        print(f"check {name}: {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["checks"] = {n: {"value": v, "limit": l} for n, v, l in checks}
    print(json.dumps(result), flush=True)
