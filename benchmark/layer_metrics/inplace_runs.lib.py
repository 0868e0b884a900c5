"""Kernels: fused runs of the traced program whose kernel writes over its
operand (``fusion_inplace_runs_total``: a run lowered with its output aliased
to its input, counted once a trace of the program, the whole process). Where
every run is in place this equals ``launches_per_circuit`` and a chain of
runs on a donated register holds no state-sized temporary. The series only
appears with its first count: a program that counts no in-place run (one from
before the counter) gives nothing to read, not 0."""

from metric_util import counter_total


def read(m):
    return counter_total(m["after"], "fusion_inplace_runs_total") or None
