"""XLA ops: median over the window's requests of the engine's ``device`` phase,
the stream-ordered estimate of the batch's execution time: from its launch,
or from when the batch ahead was seen done, to when it was seen done."""

from metric_util import percentile

PHASES = ("device",)


def read(m):
    wall0 = m["window"].wall0
    sums = [sum(t["phases_ms"].get(p, 0.0) for p in PHASES)
            for t in m["engine_traces"] if t["t0"] >= wall0 and not t["error"]]
    return percentile(sums, 0.50) if sums else None
