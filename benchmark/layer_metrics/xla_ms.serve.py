"""XLA ops: device time per served request of the Engine's batch program
(its dense plan of the tape: one pass a block over the whole batch): busy
device time per batch in the traced slice, over the mean width of the batches
the engine dispatched (``engine_batch_size``)."""

from metric_util import histogram_delta, per_run


def read(m):
    busy = per_run(m, "busy_s")
    count, total = histogram_delta(m, "engine_batch_size")
    if busy is None or not total:
        return None
    return busy * 1e3 * count / total
