"""Distribution: of the time a collective is in flight, the share in which
no kernel and no other XLA op runs on the same device: what pipelining the
relabelings against the kernels would have to move."""

import trace_collectives


def read(m):
    c = trace_collectives.of_run(m)
    if c is None or not c["collective_s"]:
        return None
    return 100.0 * c["exposed_s"] / c["collective_s"]
