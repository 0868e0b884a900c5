"""Planner: frame relabelings of the traced program that lie inside the array
their kernel sees and still ran beside it, as explicit passes over the whole
state, because the kernel's DMA could not fold them
(``fusion_unfolded_swaps_total``, counted once a trace of the program, the
whole process; a planner that holds its frames to what folds emits none). A
series only appears with its first count, so an absent one reads 0 beside
``fusion_inplace_runs_total``, and nothing where the program counts neither
(one from before the counters)."""

from metric_util import counter_total


def read(m):
    snap = m["after"]
    if not counter_total(snap, "fusion_inplace_runs_total"):
        return None
    return counter_total(snap, "fusion_unfolded_swaps_total")
