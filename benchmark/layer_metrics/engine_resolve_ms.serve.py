"""Serving: median over the window's requests of ``resolve``: from the batch
seen done on the device to the request's future resolved (lane extraction,
the integrity gate, the wait behind earlier lanes)."""

from metric_util import percentile

PHASES = ("resolve",)


def read(m):
    wall0 = m["window"].wall0
    sums = [sum(t["phases_ms"].get(p, 0.0) for p in PHASES)
            for t in m["engine_traces"] if t["t0"] >= wall0 and not t["error"]]
    return percentile(sums, 0.50) if sums else None
