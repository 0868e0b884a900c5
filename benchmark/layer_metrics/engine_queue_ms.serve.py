"""Serving: median over the window's requests of the time a request waited
before its batch ran: ``queue_wait`` (in the queue, for room on the
completion ring, and launched behind the batch ahead) + ``coalesce``."""

from metric_util import percentile

PHASES = ("queue_wait", "coalesce")


def read(m):
    wall0 = m["window"].wall0
    sums = [sum(t["phases_ms"].get(p, 0.0) for p in PHASES)
            for t in m["engine_traces"] if t["t0"] >= wall0 and not t["error"]]
    return percentile(sums, 0.50) if sums else None
