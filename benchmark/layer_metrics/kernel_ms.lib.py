"""Kernels: device time of the Mosaic kernel launches per application."""

from metric_util import per_run_ms


def read(m):
    return per_run_ms(m, "kernel_s")
