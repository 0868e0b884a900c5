"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark reports.

The yardstick, kept with the benchmark: every PR's trace is reduced by this
file, so a device time means the same thing before and after a change.

    python benchmark/trace_reduce.py --dump  <trace.xplane.pb>   # look at one by hand
    python benchmark/trace_reduce.py --reduce <trace.xplane.pb>  # the reduction, as JSON

What is read (seen on a TPU v5e trace, jax 0.9):

- planes ``/device:TPU:<i>``: line ``XLA Ops`` holds one event per executed
  HLO op, named by its whole HLO text (``%name = type opcode(...)``); line
  ``XLA Modules`` one event per program run (``jit_fn(<id>)``). A Pallas
  (Mosaic) kernel is the op whose text holds
  ``custom_call_target="tpu_custom_call"``: kernels are told from XLA's ops by
  kind, whatever they are named (``qt_fused_...`` since PR 26);
- plane ``/host:CPU``, lines ``python3`` (one per thread): the benchmark's own
  ``TraceAnnotation`` spans (``apply``/``sync``/``submit``/``wait``, and
  ``bench.slice`` around the traced slice) and, since PR 26, the program's
  spans and regions under their own names (``circuit.run``, ``engine.launch``,
  ...), on the same clock. A span that began before the capture is not
  recorded.

The traced window runs from the start of the first WHOLE run of the dominant
program (the module with most device time) to the end of its last whole run:
the capture cuts the first and the last run it sees, so those two are left
out, and so is anything outside ``bench.slice``. Device ops are clipped to the
window. Busy time is the UNION of device-op intervals (per device, then
averaged over devices), never a sum; per-run numbers divide by the whole runs.

Idle time is charged, instant by instant, to the innermost host span open at
that instant: a span of the program beats one of the benchmark's (the program
runs inside the benchmark's call, also where that call is another thread's),
and among spans of one kind the one that opened last wins.
"""

from __future__ import annotations

import argparse
import json
import sys

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the benchmark's own spans, around its calls into the program
ANNOTATIONS = ("apply", "sync", "submit", "wait")
#: the program's spans (``quest_tpu.telemetry.span`` / ``region``, PR 26): a
#: library call, and the batcher thread's work on a batch
PROGRAM_SPANS = ("circuit.run", "engine.admit", "engine.assemble",
                 "engine.lookup", "engine.launch", "engine.sync",
                 "engine.resolve", "engine.dispatch", "engine.retire")
SLICE = "bench.slice"
#: what marks a Mosaic kernel launch in an op's HLO text
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def is_kernel(name: str) -> bool:
    """A Mosaic kernel launch, by op kind."""
    return KERNEL_MARK in name


def short_name(hlo: str) -> str:
    """``name (opcode)`` of an op's HLO text ``%name = type opcode(...)``."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:80]
    if rest.startswith("("):        # a tuple type: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return f"{name.lstrip('%')} ({rest.strip().partition('(')[0]})"


def device_ops(profile) -> dict:
    """{device plane name: [(name, start_ns, end_ns, is_kernel), ...]}."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                ops.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                            is_kernel(ev.name)))
        out[plane.name] = sorted(ops, key=lambda o: o[1])
    return out


def module_runs(profile) -> dict:
    """{device plane name: [(module name, start_ns, end_ns), ...]}."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        out[plane.name] = sorted(
            (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for line in plane.lines if line.name == MODULES_LINE
            for ev in line.events)
    return out


def annotations(profile, names=ANNOTATIONS + PROGRAM_SPANS) -> list:
    """[(name, start_ns, end_ns)] of the host spans called ``names``. A
    span's labels (``engine.dispatch#batch=8#``) are not part of its name."""
    out = []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.partition("#")[0]
                if name in names:
                    out.append((name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return sorted(out, key=lambda a: a[1])


def union(intervals) -> list:
    """Merged, sorted [(start, end)]."""
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def timeline(spans) -> list:
    """Sorted, disjoint [(start, end, name)]: at every instant the innermost
    span open then -- the program's before the benchmark's, then the one
    that opened last. Instants under no span are left out."""
    def rank(span):
        return (span[0] in PROGRAM_SPANS, span[1])

    edges = sorted({t for _, a, b in spans for t in (a, b)})
    todo = sorted(spans, key=lambda sp: sp[1])
    out, open_, nxt = [], [], 0
    for lo, hi in zip(edges, edges[1:]):
        while nxt < len(todo) and todo[nxt][1] <= lo:
            open_.append(todo[nxt])
            nxt += 1
        open_ = [sp for sp in open_ if sp[2] > lo]
        if not open_:
            continue
        name = max(open_, key=rank)[0]
        if out and out[-1][2] == name and out[-1][1] == lo:
            out[-1][1] = hi
        else:
            out.append([lo, hi, name])
    return [tuple(seg) for seg in out]


def label_gaps(gaps, spans) -> dict:
    """{span name: idle ns charged to it} over the sorted, disjoint ``gaps``;
    what no span covers is ``unannotated``."""
    line = timeline(spans)
    totals, i = {}, 0
    for lo, hi in gaps:
        while i < len(line) and line[i][1] <= lo:
            i += 1
        covered, j = 0, i
        while j < len(line) and line[j][0] < hi:
            a, b, name = line[j]
            part = min(b, hi) - max(a, lo)
            totals[name] = totals.get(name, 0) + part
            covered += part
            j += 1
        if hi - lo > covered:
            totals["unannotated"] = (totals.get("unannotated", 0)
                                     + hi - lo - covered)
    return totals


def _top(totals: dict, n=10) -> list:
    return [[k, v / 1e9] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def _by_kind(by_name: dict) -> dict:
    """Seconds by op: by the op's own name where a program has few ops, and
    with the numbering dropped (``fusion.327`` -> ``fusion x<count>``) where
    it has so many that no single one says anything."""
    short = {}
    for hlo, v in by_name.items():
        key = short_name(hlo)
        short[key] = short.get(key, 0) + v
    if len(short) <= 32:
        return short
    kinds, counts = {}, {}
    for key, v in short.items():
        name, _, opcode = key.partition(" ")
        kind = f"{name.split('.')[0]} {opcode}"
        kinds[kind] = kinds.get(kind, 0) + v
        counts[kind] = counts.get(kind, 0) + 1
    return {f"{k} x{counts[k]}": v for k, v in kinds.items()}


def whole_runs(runs: list, lo: float, hi: float) -> tuple:
    """``(module, [(start, end), ...])``: the dominant module of ``runs`` and
    its whole runs inside [lo, hi] -- without the first and the last run the
    capture saw, which it may have cut."""
    total = {}
    for name, a, b in runs:
        total[name] = total.get(name, 0) + b - a
    if not total:
        return None, []
    module = max(total, key=total.get)
    mine = [(a, b) for name, a, b in runs if name == module][1:-1]
    return module, [(a, b) for a, b in mine if a >= lo and b <= hi]


def reduce(profile) -> dict | None:
    """The reduction; None when no whole run of a program is in the trace."""
    ops_by_dev = device_ops(profile)
    runs_by_dev = module_runs(profile)
    if not ops_by_dev or not any(ops_by_dev.values()):
        return None
    spans = annotations(profile)
    cut = annotations(profile, (SLICE,))
    lo, hi = (cut[0][1], cut[0][2]) if cut else (float("-inf"), float("inf"))
    first = sorted(ops_by_dev)[0]
    module, whole = whole_runs(runs_by_dev.get(first, []), lo, hi)
    if not whole:
        return None
    lo, hi = whole[0][0], whole[-1][1]
    busy = kernel = xla = 0.0
    launches = xla_count = 0
    by_name, gaps = {}, {}
    for dev, ops in sorted(ops_by_dev.items()):
        inside = [o for o in ops if o[2] > lo and o[1] < hi]
        merged = union(_clip([(o[1], o[2]) for o in inside], lo, hi))
        busy += sum(b - a for a, b in merged)
        for name, a, b, kern in inside:
            dur = min(b, hi) - max(a, lo)
            by_name[name] = by_name.get(name, 0) + dur
            if kern:
                kernel += dur
                launches += 1
            else:
                xla += dur
                xla_count += 1
        if dev == first:    # one host drives every device: label gaps once
            edges = [lo] + [t for ab in merged for t in ab] + [hi]
            gaps = label_gaps([(a, b) for a, b in
                               zip(edges[0::2], edges[1::2]) if b > a], spans)
    devices = len(ops_by_dev)
    return {
        "devices": devices,
        "module": module,
        "runs": len(whole),
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / devices / 1e9,
        "kernel_s": kernel / devices / 1e9,
        "xla_s": xla / devices / 1e9,
        "kernel_launches": launches / devices,
        "xla_ops": xla_count / devices,
        "device_ops": _top({k: v / devices
                            for k, v in _by_kind(by_name).items()}),
        "idle_gaps": _top(gaps),
    }


def dump(profile, events_per_line=12, out=sys.stdout) -> None:
    """Planes, lines and the first events of each, with their stats."""
    for plane in profile.planes:
        print(f"PLANE {plane.name!r}", file=out)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events", file=out)
            for ev in events[:events_per_line]:
                print(f"    {ev.name!r} start={ev.start_ns:.0f} "
                      f"dur={ev.duration_ns:.0f} {_stats(ev)}", file=out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dump", metavar="XPLANE")
    ap.add_argument("--reduce", metavar="XPLANE")
    args = ap.parse_args(argv)
    if args.dump:
        dump(load(args.dump))
    if args.reduce:
        print(json.dumps(reduce(load(args.reduce)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
