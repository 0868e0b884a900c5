"""Planner: Param gates of the served tape that joined a dense block of the
Engine's plan (``fusion_param_fused_total``)."""

from metric_util import param_plan_count


def read(m):
    return param_plan_count(m, "fusion_param_fused_total")
