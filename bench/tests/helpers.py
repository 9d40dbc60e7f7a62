"""Shared by the benchmark's CPU tests: the real cells, cut to a size a CPU
holds, in float32 so that sound runs agree with the reference to rounding."""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_TRAIN = {
    "model": dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4, d_ff=128,
                  vocab_size=256, dtype="float32"),
    "traffic": dict(seq=64, batches=8),
    # float32 at this size: sound runs read under 1e-5
    "cell": {"limits": {"loss_gap": 1e-3, "grad_gap": 1e-3, "change_gap": 1e-3, "outer_gap": 1e-3}},
}

TINY_SERVE = {
    # six query heads over two KV heads, 96 wide against d_model 64: the
    # grouped, separately sized heads of the served configuration
    "model": dict(num_layers=2, d_model=64, num_heads=6, num_kv_heads=2, head_dim=16, d_ff=128,
                  vocab_size=256, dtype="float32"),
    "traffic": dict(rate_per_s=10.0, prompt={"median": 20, "sigma": 1.0, "min": 4, "max": 64},
                    output={"median": 6, "sigma": 0.8, "min": 2, "max": 16}),
    "cell": {"serve": {"max_slots": 4, "num_pages": 64, "page_size": 8, "max_new_cap": 16,
                       "prefill_chunk": 16, "prefill_budget": 32},
             "limits": {"logit_gap": 1e-3}},
}

SEED = 3_000_000_123  # above 2**31: run seeds need not fit 32 signed bits
