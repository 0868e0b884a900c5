"""Compile layer: what JAX spent tracing and lowering during set-up, from the
program's ``jax.monitoring`` listeners (``jax_trace_seconds`` +
``jax_lower_seconds``; an interval nested in another is counted once). Read
from the snapshot taken where set-up ends."""


def read(m):
    hists = m["before"]["histograms"]
    found = [hists[k]["sum"] for k in ("jax_trace_seconds",
                                       "jax_lower_seconds") if k in hists]
    return sum(found) if found else None
