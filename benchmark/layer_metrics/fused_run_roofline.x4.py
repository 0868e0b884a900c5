"""Kernels, on a sharded register: the least time the chips could take for
one application over the mean kernel time a device took. Every chip streams
its own shard, so the bound is one read and one write of the WHOLE register
(``bytes_model.application_bytes``) over the HBM bandwidth of all the devices
in the trace. ``fused_run_roofline`` sets the whole register against ONE
chip's bandwidth and would read four times too high here."""

import bytes_model
from metric_util import per_run


def read(m):
    peaks, trace = m["peaks"], m["trace"]
    kernel_s = per_run(m, "kernel_s")
    if not peaks or not kernel_s:
        return None
    floor_s = (bytes_model.application_bytes(m["shapes"]["state_bytes"])
               / (trace["devices"] * peaks["hbm_bytes_per_s"]))
    return 100.0 * floor_s / kernel_s
