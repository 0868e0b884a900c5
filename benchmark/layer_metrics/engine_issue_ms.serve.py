"""Serving: median over the window's requests of the batcher's host work to
put the request's batch on the device: ``cache_lookup`` + ``dispatch``
(assembly and launch) + ``compile``."""

from metric_util import percentile

PHASES = ("cache_lookup", "dispatch", "compile")


def read(m):
    wall0 = m["window"].wall0
    sums = [sum(t["phases_ms"].get(p, 0.0) for p in PHASES)
            for t in m["engine_traces"] if t["t0"] >= wall0 and not t["error"]]
    return percentile(sums, 0.50) if sums else None
