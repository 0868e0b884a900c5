"""XLA ops: device time of every op that is not a kernel launch (relayout
copies, the ops around the kernels) per application."""

from metric_util import per_run_ms


def read(m):
    return per_run_ms(m, "xla_s")
