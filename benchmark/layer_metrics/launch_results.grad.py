"""Serving: arrays the gradient engine's batch program hands back a launch
(``engine_launch_results_total`` over
``device_dispatch_total{route=grad_request}``, both over the window). One
packed array a batch reads 1; a program that returns every lane's value and
every derivative as an output of its own reads the batch width times its
numbers a lane (8 x 321 on this cell before PR 46). Nothing where the program
does not count its results (the parent of PR 46), or launched no batch in the
window."""

from metric_util import counter_delta


def read(m):
    if "engine_launch_results_total" not in m["after"]["counters"]:
        return None
    launches = counter_delta(m, "device_dispatch_total{route=grad_request}")
    if not launches:
        return None
    return counter_delta(m, "engine_launch_results_total") / launches
