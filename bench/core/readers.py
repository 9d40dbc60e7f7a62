"""Pieces the per-layer metric readers share: which device events belong to
which program, and the least time of a piece of work on a chip."""

from __future__ import annotations

from bench.core import trace as T


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_per_s"])


def modules(dev: T.Device, needle: str) -> list:
    return [e for e in dev.modules if needle in e.name]


def program(dev: T.Device, name: str) -> list:
    """Runs of the compiled program ``name`` (module events ``name`` or
    ``name(<id>)``)."""
    return [e for e in dev.modules if e.name == name or e.name.startswith(name + "(")]


def ops_within(dev: T.Device, windows: list, needle: str | None = None) -> list:
    """Ops of ``dev`` that start inside one of ``windows`` (module events)."""
    ws = sorted((w.start, w.end) for w in windows)
    out, j = [], 0
    for e in sorted(dev.ops, key=lambda e: e.start):
        while j < len(ws) and ws[j][1] < e.start:
            j += 1
        if j < len(ws) and ws[j][0] <= e.start <= ws[j][1] and (needle is None or needle in e.name):
            out.append(e)
    return out
