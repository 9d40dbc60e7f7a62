"""Pallas TPU kernels + the dispatch layer that makes them the production path.

  flash_attention  — causal/sliding-window attention, GQA-native fold
  ssd_scan         — Mamba-2 SSD intra-chunk quadratic form
  rglru_scan       — RG-LRU linear recurrence (log-step doubling scan)
  noloco_update    — fused NoLoCo outer step Eqs. 2–3 (memory-bound)
  quantize         — int8 per-chunk affine wire codec kernels

Layering: <name>.py (pl.pallas_call + BlockSpec, array-level), ref.py
(pure-jnp twins + oracles), dispatch.py (KernelConfig + the op registry),
ops.py (public custom_vjp'd wrappers the models/core/comm consumers call).
Parity with the jnp twins is tested on CPU in interpret mode; every kernel
also compiles for the TPU v5e (tests/test_chip_compile.py), and chip_smoke.py
checks the main-path kernels against their twins on the chip.  See DESIGN.md
§6 for the dispatch table.
"""

from repro.kernels import dispatch, ops, ref
from repro.kernels.dispatch import KernelConfig

__all__ = ["dispatch", "ops", "ref", "KernelConfig"]
