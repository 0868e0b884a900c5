"""Planner: relabelings of the traced program that reach a sharded qubit and
run as a transpose over the mesh (``fusion_collective_swaps_total``, counted
once a trace of the program, the whole process). The series only appear with
their first count: where the program counts neither this nor its per-shard
runs (``fusion_sharded_runs_total``) there is nothing to read -- one device,
or a program from before the counters."""

from metric_util import counter_total


def read(m):
    snap = m["after"]
    if not counter_total(snap, "fusion_sharded_runs_total"):
        return None
    return counter_total(snap, "fusion_collective_swaps_total")
