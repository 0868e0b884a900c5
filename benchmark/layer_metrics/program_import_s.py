"""Compile layer (set-up): what ``import quest_tpu`` took, timed by the
package itself from its first line to its last (the gauge
``quest_tpu_import_seconds``). The benchmark imports JAX first, so the gauge
carries ``jax_included=0`` and reads the package alone: the part of
``import_s`` that a change to the program can shorten. Nothing where the
program sets no such gauge."""


def read(m):
    found = [v for k, v in m["before"]["gauges"].items()
             if k.split("{")[0] == "quest_tpu_import_seconds"]
    return found[0] if found else None
