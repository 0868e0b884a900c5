"""Serving: mean width of the batches the engine dispatched in the window
(``engine_batch_size``)."""

from metric_util import histogram_delta


def read(m):
    count, total = histogram_delta(m, "engine_batch_size")
    return total / count if count else None
