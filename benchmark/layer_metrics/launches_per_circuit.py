"""Planner: Mosaic kernel launches per application, counted in the trace
(the program's own pass counter counts lowerings, not launches)."""

from metric_util import per_run


def read(m):
    return per_run(m, "kernel_launches")
