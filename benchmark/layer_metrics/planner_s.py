"""Planner: the time inside the program's own planner during set-up, every
arm: ``total_s`` of each ``fusion.plan{mode=...}`` span (``pallas``,
``pallas_sharded``, ``dense``) in the snapshot taken where set-up ends.
``plan_s`` is the benchmark's stopwatch around ``circ.fused`` / ``Engine(...)``
and holds this and whatever else those calls do (the tape's capture, the
plan's stamping and verification, the engine's start). Nothing where the
program's planner ran under no span (a dense plan before PR 39)."""


def read(m):
    found = [s["total_s"] for k, s in m["before"]["spans"].items()
             if k.split("{")[0] == "fusion.plan"]
    return sum(found) if found else None
