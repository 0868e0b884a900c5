"""Distribution: time an application has a collective in flight (all-to-all,
collective-permute, all-gather, all-reduce, reduce-scatter; an async start to
its done is one interval), the union per device plane, the mean over the
planes (``trace_collectives``)."""

import trace_collectives


def read(m):
    c = trace_collectives.of_run(m)
    return None if c is None else c["collective_s"] * 1e3 / c["runs"]
