"""Share of the traced window in which a chip is idle while the program does
its own host work, averaged over the chips: a ``train.*`` or ``outer.*``
span is open (staging, dispatch, planning the outer step, the loop's eval,
checkpoint and telemetry).  The loss fetch (``train.loss_fetch``) waits on
the chip and does not count.  The rest of ``train_idle_share`` falls while
the host is outside the program."""

from bench.core import program_spans as PS
from bench.core import trace as T


def read(tr, info, peaks):
    work = [(s.start, s.end) for s in PS.spans(tr)
            if s.name.startswith(("train.", "outer.")) and s.name != "train.loss_fetch"]
    if not work or not tr.devices:
        return None
    idle = sum(T.subtract(work, [(e.start, e.end) for e in d.ops]) for d in tr.devices)
    return 100.0 * idle / len(tr.devices) / 1e9 / info["window_s"]
