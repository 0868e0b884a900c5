"""Gradients: device dispatches on the gradient route a batch the engine
launched in the window (``device_dispatch_total{route=grad_request}`` growth
over the growth of ``engine_batch_size``'s count): 1, forward replay, costate,
backward sweep and every derivative in one program. Nothing where no batch
was launched in the window."""

from metric_util import counter_delta, histogram_delta


def read(m):
    batches, _ = histogram_delta(m, "engine_batch_size")
    if not batches:
        return None
    return counter_delta(m, "device_dispatch_total{route=grad_request}") \
        / batches
