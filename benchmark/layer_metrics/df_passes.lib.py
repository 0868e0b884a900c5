"""Planner: double-float kernels the plan of the traced program states
(``fusion_df_passes_total``, counted once a plan, the whole process: one a
fused run of a one-device plan, which is cut at what a df kernel takes). It
has to equal ``launches_per_circuit`` (what the device ran) and
``inplace_runs.lib`` (what was lowered in place): a run cut again as it
executes is a launch more than this, and a counted ``engine_fallback_total``.
The series only appears with its first count: a program whose planner does not
count them (one from before the counter) gives nothing to read, not 0."""

from metric_util import counter_total


def read(m):
    return counter_total(m["after"], "fusion_df_passes_total") or None
