"""Planner: tape entries the cell's plan left outside every kernel
(``fusion_barriers_total``, counted once a plan, the whole process): an entry
the planner could not lower or no frame localised, which runs as written
between the fused runs -- a channel then as a Kraus sum over three states. 0
on every library cell; a register that fills its chip has no room for one,
and its driver refuses such a plan. A series appears with its plan even where
it counts 0, so nothing is read only where the process made no plan."""

from metric_util import counter_total


def read(m):
    snap = m["after"]
    if not counter_total(snap, "fusion_plans_total"):
        return None
    return counter_total(snap, "fusion_barriers_total")
