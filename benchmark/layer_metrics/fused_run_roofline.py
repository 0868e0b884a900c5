"""Kernels: the least time the chip could take for one application over the
kernel time it took. The bound is HBM: any application reads and writes the
whole state at least once (``bytes_model.application_bytes``), however the
planner cuts it into passes. Reported only where the state is at least four
times the chip's VMEM, so that it has to stream from HBM."""

import bytes_model
from metric_util import per_run


def read(m):
    peaks, shapes = m["peaks"], m["shapes"]
    kernel_s = per_run(m, "kernel_s")
    if not peaks or not kernel_s:
        return None
    if shapes["state_bytes"] < 4 * peaks["vmem_bytes"]:
        return None
    floor_s = (bytes_model.application_bytes(shapes["state_bytes"])
               / peaks["hbm_bytes_per_s"])
    return 100.0 * floor_s / kernel_s
