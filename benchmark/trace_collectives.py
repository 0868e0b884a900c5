"""Collective time of a traced slice, per device plane: what
``trace_reduce.reduce`` cannot give, because it keeps only the ten largest
ops by name. This module loads the slice's ``.xplane.pb`` itself and reads
the window, the whole runs and the op intervals with ``trace_reduce``'s own
functions, so a collective's time is cut as every other device time is.

A collective is an op of the ``XLA Ops`` line whose opcode is one of
``COLLECTIVES``, or that opcode's ``-start`` / ``-done`` pair (also under the
generic ``async-start`` / ``async-done`` opcodes, which are told by the op's
name). A start and its done are ONE interval, from the start's beginning to
the done's end: the transfer is in flight between them. A device's
collective time is the union of those intervals; its exposed part is the
part of that union in which no other op (kernel or XLA) runs on the same
device. Both are means over the device planes.
"""

from __future__ import annotations

import glob
import os

import trace_reduce

COLLECTIVES = ("all-to-all", "collective-permute", "all-gather", "all-reduce",
               "reduce-scatter", "collective-broadcast", "ragged-all-to-all")


def collective_part(hlo: str):
    """``(opcode of the collective, "start" | "done" | "whole")`` of an op's
    HLO text, or None for any other op."""
    name, _, opcode = trace_reduce.short_name(hlo).partition(" ")
    opcode = opcode.strip("()")
    part = "whole"
    for suffix in ("-start", "-done"):
        if opcode.endswith(suffix):
            part = suffix[1:]
            opcode = opcode[:-len(suffix)]
    if opcode == "async":               # async-start / async-done of <name>
        opcode = name
    for kind in COLLECTIVES:
        if opcode == kind or opcode.startswith((kind + "-", kind + ".")):
            return kind, part
    return None


def in_flight(ops) -> list:
    """[(start_ns, end_ns)] of the collectives among one device's ``ops``
    (``trace_reduce.device_ops`` rows, sorted by start): a start joined to
    the next done of its kind, anything else as it stands."""
    out, open_ = [], {}
    for name, a, b, _kernel in ops:
        found = collective_part(name)
        if found is None:
            continue
        kind, part = found
        if part == "start":
            open_.setdefault(kind, []).append(a)
        elif part == "done" and open_.get(kind):
            out.append((open_[kind].pop(0), b))
        else:                   # a whole op, or a done whose start was cut
            out.append((a, b))
    return out


def _length(intervals) -> float:
    return float(sum(b - a for a, b in intervals))


def _minus(intervals, others) -> list:
    """The part of the merged ``intervals`` that no merged ``others`` cover."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(others) and others[j][1] <= a:
            j += 1
        k = j
        while k < len(others) and others[k][0] < b:
            if others[k][0] > a:
                out.append((a, others[k][0]))
            a = max(a, others[k][1])
            k += 1
        if a < b:
            out.append((a, b))
    return out


def reduce(profile) -> dict | None:
    """``{"devices", "runs", "collective_s", "exposed_s", "by_kind"}`` over
    the whole runs of the dominant program in the slice, seconds as means
    over the device planes; None when no whole run is in the trace."""
    ops_by_dev = trace_reduce.device_ops(profile)
    if not ops_by_dev or not any(ops_by_dev.values()):
        return None
    cut = trace_reduce.annotations(profile, (trace_reduce.SLICE,))
    lo, hi = (cut[0][1], cut[0][2]) if cut else (float("-inf"), float("inf"))
    first = sorted(ops_by_dev)[0]
    _, whole = trace_reduce.whole_runs(
        trace_reduce.module_runs(profile).get(first, []), lo, hi)
    if not whole:
        return None
    lo, hi = whole[0][0], whole[-1][1]
    total = exposed = 0.0
    by_kind = {}
    for ops in ops_by_dev.values():
        inside = [(o, collective_part(o[0])) for o in ops
                  if o[2] > lo and o[1] < hi]
        flight = trace_reduce.union(trace_reduce._clip(
            in_flight([o for o, part in inside if part]), lo, hi))
        others = trace_reduce.union(trace_reduce._clip(
            [(o[1], o[2]) for o, part in inside if not part], lo, hi))
        total += _length(flight)
        exposed += _length(_minus(flight, others))
        for _, part in inside:
            if part:
                by_kind[part[0]] = by_kind.get(part[0], 0) + 1
    devices = len(ops_by_dev)
    return {"devices": devices, "runs": len(whole),
            "collective_s": total / devices / 1e9,
            "exposed_s": exposed / devices / 1e9,
            "by_kind": {k: v / devices for k, v in by_kind.items()}}


def of_run(m: dict) -> dict | None:
    """The reduction of the traced slice of the run that gathered ``m`` (made
    once a run and kept in ``m``): the newest trace under the cell's
    directory, as ``run.reduce_slice`` finds it. None where the run was not
    traced or its driver names no cell."""
    if "collectives" not in m:
        cell = m["shapes"].get("cell")
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        paths = sorted(glob.glob(os.path.join(
            root, ".bench_trace", cell or "", "plugins", "profile", "*",
            "*.xplane.pb")))
        m["collectives"] = (reduce(trace_reduce.load(paths[-1]))
                            if m["trace"] and cell and paths else None)
    return m["collectives"]
