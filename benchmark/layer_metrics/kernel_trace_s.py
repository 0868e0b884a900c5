"""Compile layer: what the program's new kernel signatures cost the host
during set-up, summed over every kernel: each fused run's zone fold and the
first dispatch after it, from the program's own record
(``mosaic_compile_seconds{kind}``, made in ``ops/pallas_gates.py`` where the
kernel is first dispatched; the ``pallas.compile`` events list the same
seconds kernel by kernel, by name). Inside the cell's jitted program nothing
compiles at that point: the seconds are the kernel bodies' Python traces,
the part of a warm ``first_call_s`` that is neither XLA's nor the cache's.
Read from the snapshot taken where set-up ends; nothing where the program
dispatched no kernel (the served cells)."""


def read(m):
    found = [h["sum"] for k, h in m["before"]["histograms"].items()
             if k.split("{")[0] == "mosaic_compile_seconds"]
    return sum(found) if found else None
