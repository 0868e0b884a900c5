"""Serving: median over the window's requests of the engine's host-side
phases (queue_wait + coalesce + cache_lookup + dispatch + resolve of the
program's seven-phase vector; its ``device`` phase is a host window around a
sync and is not read)."""

from metric_util import percentile

HOST_PHASES = ("queue_wait", "coalesce", "cache_lookup", "dispatch", "resolve")


def read(m):
    wall0 = m["window"].wall0
    sums = [sum(t["phases_ms"].get(p, 0.0) for p in HOST_PHASES)
            for t in m["engine_traces"] if t["t0"] >= wall0 and not t["error"]]
    return percentile(sums, 0.50) if sums else None
