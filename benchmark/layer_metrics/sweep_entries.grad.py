"""Gradients: what ONE gradient's program states it applies to a whole
register after the forward replay (``grad_sweep_entries_total``, its four
series summed: a term of the Hamiltonian, a dagger application to ``phi`` and
one to ``lambda``, a bracket a derivative rule), counted once a trace of the
gradient program, over the traces the process made of it
(``engine_trace_total{kind=param_replay}``; the cell traces its one batch
program once). 570 on the 20-qubit ansatz today: 6 + 202 + 202 + 160, the walk
gate by gate; a sweep that fused entries would state fewer. Nothing where the
program does not count them (a tree from before the counter)."""

from metric_util import counter_total


def read(m):
    snap = m["after"]
    entries = counter_total(snap, "grad_sweep_entries_total")
    if not entries:
        return None
    traces = counter_total(snap, "engine_trace_total{kind=param_replay}")
    return entries / max(traces, 1.0)
