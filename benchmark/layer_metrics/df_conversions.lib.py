"""Kernels: passes between the float64 register and the four float32 planes
the double-float kernels work on, an application
(``fusion_df_conversions_total``, ``dir=split`` and ``dir=join`` together,
counted once a trace of the program, the whole process): each fused run is
``df_join(kernel(df_split(x)))``, so two a run, of which XLA fuses a join
with the split that follows it. They are the XLA ops of this cell
(``xla_ms.lib`` is their time). Nothing where the program does not count them
(one from before the counter, or a float32 program)."""

from metric_util import counter_total


def read(m):
    return counter_total(m["after"], "fusion_df_conversions_total") or None
