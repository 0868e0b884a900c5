"""Serving: arrays handed to the engine's batch program a launch, the state
included (``engine_launch_args_total`` over
``device_dispatch_total{route=engine_vmap}``, both over the window). One packed
array a slot kind reads 2 to 4; a program that takes every slot as an argument
of its own reads its slot count plus one. Nothing where the program does not
count its arguments (the parent of PR 31), or launched no batch in the
window."""

from metric_util import counter_delta


def read(m):
    if "engine_launch_args_total" not in m["after"]["counters"]:
        return None
    launches = counter_delta(m, "device_dispatch_total{route=engine_vmap}")
    if not launches:
        return None
    return counter_delta(m, "engine_launch_args_total") / launches
