"""Arithmetic the metric readers share."""

from __future__ import annotations

import math


def counter_total(snap: dict, name: str) -> float:
    """Every series of counter ``name`` in one snapshot, summed."""
    return sum(v for k, v in snap["counters"].items()
               if k == name or k.startswith(name + "{"))


def counter_delta(m: dict, name: str) -> float:
    """Growth over the window of every series of counter ``name``."""
    return counter_total(m["after"], name) - counter_total(m["before"], name)


#: what the planner counts of a tape's Param gates, once a plan
PARAM_PLAN_COUNTERS = ("fusion_param_fused_total",
                       "fusion_param_barriers_total")


def param_plan_count(m: dict, name: str):
    """Counter ``name`` of ``PARAM_PLAN_COUNTERS`` over the whole process (the
    plan is made in set-up). A series only appears with its first count, so
    an absent one reads 0 beside its sibling, and None where the program's
    planner counts neither."""
    snap = m["after"]
    if not any(counter_total(snap, n) for n in PARAM_PLAN_COUNTERS):
        return None
    return counter_total(snap, name)


def histogram_delta(m: dict, name: str) -> tuple:
    """(count, sum) growth over the window of histogram ``name``."""
    def get(snap):
        h = snap["histograms"].get(name, {"count": 0, "sum": 0.0})
        return h["count"], h["sum"]

    (c0, s0), (c1, s1) = get(m["before"]), get(m["after"])
    return c1 - c0, s1 - s0


def percentile(values, q: float) -> float:
    """The smallest value with at least ``q`` of the sample at or below it."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q * len(ordered)) - 1)]


def latencies_ms(m: dict) -> list:
    """Client-side latency of every request of the window."""
    return [(r[3] - r[2]) * 1e3 for r in m["window"].requests]


def per_run(m: dict, key: str):
    """``trace[key]`` per whole run of the cell's program in the traced
    slice. A library cell's run is one application; a served cell's run is
    one batch."""
    trace = m["trace"]
    if not trace or not trace["runs"]:
        return None
    return trace[key] / trace["runs"]


def per_run_ms(m: dict, key: str):
    """``per_run`` of a time kept in seconds, in milliseconds."""
    s = per_run(m, key)
    return None if s is None else s * 1e3


def window_ms_per_request(m: dict):
    """All the time of the window over all its completed requests."""
    win = m["window"]
    done = len(win.completed)
    return (win.end - win.t0) * 1e3 / done if done else None


def dispatches_per_request(m: dict):
    """``device_dispatch_total``, every route, over the whole window."""
    done = len(m["window"].completed)
    return counter_delta(m, "device_dispatch_total") / done if done else None


def idle_pct(m: dict):
    """Share of the traced window in which no op ran on the device."""
    t = m["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None
