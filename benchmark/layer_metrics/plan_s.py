"""Planner: the benchmark's span around building the program (``circ.fused``
for a library cell, the ``Engine`` for a served one)."""


def read(m):
    return m["spans"].get("plan_s")
