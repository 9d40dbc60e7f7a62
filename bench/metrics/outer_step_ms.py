"""Device time of the outer step per sync, on each chip: the programs that
start between the benchmark's ``bench.outer_sync`` span and the next
``bench.inner_step`` span (a millisecond of slack either side for the two
clocks), other than the inner step's own program ``jit_step`` and the loss
mean ``jit__mean``; averaged over syncs and chips."""

SLACK_NS = 1_000_000
INNER = ("jit_step(", "jit__mean(")


def outer_modules(tr, dev):
    spans = sorted(tr.spans, key=lambda s: s.start)
    out = []
    for i, s in enumerate(spans):
        if s.name != "bench.outer_sync":
            continue
        nxt = next((x for x in spans[i + 1:] if x.name == "bench.inner_step"), None)
        end = (nxt.start if nxt else float("inf")) + SLACK_NS
        out.append([m for m in dev.modules
                    if s.start - SLACK_NS <= m.start < end and not m.name.startswith(INNER)])
    return out


def read(tr, info, peaks):
    vals = [sum(m.dur for m in mods) for d in tr.devices for mods in outer_modules(tr, d) if mods]
    return sum(vals) / len(vals) / 1e6 if vals else None
