"""Planner: terms the channel ops of the cell's kernels apply a pass
(``fusion_channel_terms_total{kind}``, counted once a plan, the whole process,
every kind summed): a Kraus lowering (``kraus1``, ``kraus2``, ``krausn``)
counts its Kraus terms, two matrix sweeps each in the kernel; the closed form
of the depolarising family (``depol1``, ``depol2``) counts 1 an op. A series
only appears with its first count: a program whose planner does not count
them (one from before the counter), or a plan without a channel, gives nothing
to read, not 0."""

from metric_util import counter_total


def read(m):
    return counter_total(m["after"], "fusion_channel_terms_total") or None
