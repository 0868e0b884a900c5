"""Planner: Param gates of the served tape that stayed barriers of the
Engine's plan, each a pass of its own (``fusion_param_barriers_total``; 0
where the plan fused every Param)."""

from metric_util import param_plan_count


def read(m):
    return param_plan_count(m, "fusion_param_barriers_total")
