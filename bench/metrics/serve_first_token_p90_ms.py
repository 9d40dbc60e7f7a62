"""The 90th percentile of time to first token over every request due in the
traced window, read by the host's clock as the serving driver stamps it.
Nothing when a request had no answer (its time is infinite)."""

import math


def read(tr, info, peaks):
    v = info.get("serve_ttft_p90_ms")
    if v is None or not math.isfinite(v):
        return None
    return v
