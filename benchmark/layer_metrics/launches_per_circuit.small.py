"""Planner, small-register cells: Mosaic kernel launches per application,
counted in the trace."""

from metric_util import per_run


def read(m):
    return per_run(m, "kernel_launches")
