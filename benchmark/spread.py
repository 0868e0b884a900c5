"""A set of runs of one cell, read as the driver's check reads it.

    python3 benchmark/spread.py --workload <cell> --seeds 1,2,3,4,5,6 \
        [--seconds 10] [--trace 0] [--cold-first] [--label NAME] [--env K=V]

A bound is set from how widely the runs of ONE tree spread (``PERF.md`` section
2), so this runs them the way a check does: every run a new process of
``run.py``, one seed each, the first of the set on an empty compile cache
where ``--cold-first`` is given and the rest on the cache it leaves. The parent
never imports JAX: each child owns the chip alone. For every metric of the
result lines it prints the median, (Q3-Q1)/median with the quartiles of
``statistics.quantiles(values, n=4)``, and the driver's figure: the range of
the set with the run farthest from the median left out. Each child also
writes its window (``run.py --dump``), from which the spread INSIDE a run is
read: p50 and rate of each second of the window, the requests that took over
1.5 times the median (a stalled round), the batches dispatched and the time
a call of each of the program's regions, and in a traced run the two
populations of ``queue_wait`` with the batcher's phases.

What it keeps goes to ``chiprun_out/<label>.json`` (rows, figures) and
``chiprun_out/<label>/`` (each run's dump and the end of its standard error).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

from metric_util import percentile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: a request this many times the run's median is counted as stalled
STALL = 1.5


def share(part: float, whole: float) -> float | None:
    """``part / whole``; None for a count whose median is 0."""
    return part / whole if whole else None


def iqr_share(values) -> float | None:
    """(Q3-Q1)/median, the contract's spread."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return share(q3 - q1, statistics.median(values))


def trimmed_range(values) -> float | None:
    """The driver's figure: max - min with the run farthest from the median
    left out, in the metric's unit."""
    if len(values) < 3:
        return None
    mid = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - mid))[:-1]
    return max(rest) - min(rest)


def figures(values) -> dict:
    mid = statistics.median(values)
    trimmed = trimmed_range(values)
    return {"n": len(values), "median": mid, "min": min(values),
            "max": max(values), "iqr_share": iqr_share(values),
            "trimmed_range": trimmed,
            "trimmed_share": None if trimmed is None else share(trimmed, mid)}


def inside(dump: dict) -> dict:
    """The spread inside one window: each whole second's p50 and rate (by
    completion), and the stalled requests."""
    done = [(b, (b - a) * 1e3) for _, _, a, b, ok in dump["requests"] if ok]
    lat = [ms for _, ms in done]
    mid = statistics.median(lat)
    p50s, rates = [], []
    for s in range(int(dump["seconds"])):
        mine = [ms for b, ms in done if s <= b < s + 1]
        rates.append(len(mine))
        p50s.append(statistics.median(mine) if mine else None)
    stalled = sorted((ms for ms in lat if ms > STALL * mid), reverse=True)
    out = {"p50_by_second": p50s, "rate_by_second": rates,
           "stalled": len(stalled), "longest_ms": sorted(lat)[-3:][::-1],
           "stalled_end_s": sorted({round(b, 2) for b, ms in done
                                    if ms > STALL * mid}),
           "stalled_ms_total": sum(ms - mid for ms in stalled)}
    # what the program counted over the window: the batches it dispatched
    # and its regions' time a call (the batcher's, on a served cell)
    widths = dump.get("histograms", {}).get("engine_batch_size")
    if widths and widths["count"]:
        out["batches"] = widths["count"]
        out["batch_width"] = widths["sum"] / widths["count"]
    out["region_ms"] = {k: 1e3 * v["total_s"] / v["count"]
                        for k, v in dump.get("program_spans", {}).items()
                        if v["count"]}
    return out


def populations(dump: dict) -> dict | None:
    """A traced run's requests by whether ``queue_wait`` read its low or its
    high value (launched first in a round, or behind the batch ahead)."""
    traces = [t for t in dump.get("engine_traces", []) if not t["error"]]
    if len(traces) < 16:
        return None
    waits = sorted(t["phases_ms"].get("queue_wait", 0.0) for t in traces)
    cut = (waits[len(waits) // 10] + waits[-len(waits) // 10 - 1]) / 2
    out = {"cut_ms": cut}
    for name, group in (
            ("low", [t for t in traces if t["phases_ms"]["queue_wait"] <= cut]),
            ("high", [t for t in traces if t["phases_ms"]["queue_wait"] > cut])):
        if not group:
            continue
        phases = {p: statistics.median(t["phases_ms"].get(p, 0.0)
                                       for t in group)
                  for p in sorted(group[0]["phases_ms"])}
        durs = [t["dur_ms"] for t in group]
        out[name] = {"n": len(group), "dur_p50": statistics.median(durs),
                     "dur_p95": percentile(durs, 0.95), "phases_p50": phases}
    return out


def one_run(args, seed: int, index: int, out_dir: str, env: dict) -> dict:
    dump = os.path.join(out_dir, f"{index}_{seed}.json")
    cmd = [sys.executable, os.path.join(HERE, "run.py"),
           "--workload", args.workload, "--seed", str(seed),
           "--seconds", str(args.seconds), "--trace", str(args.trace),
           "--dump", dump] + (["--rehearse"] if args.rehearse else [])
    t0 = time.time()
    proc = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=args.timeout)
    row = {"seed": seed, "rc": proc.returncode, "wall_s": time.time() - t0,
           "cold": bool(args.cold_first and index == 0)}
    with open(os.path.join(out_dir, f"{index}_{seed}.err"), "w") as f:
        f.write(proc.stderr[-20000:])
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    try:
        row["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        row["result"] = None
        row["stdout_end"] = proc.stdout[-2000:]
        return row
    row["log"] = [ln for ln in proc.stderr.splitlines()
                  if "window:" in ln or "set-up " in ln]
    if os.path.isfile(dump):
        with open(dump) as f:
            d = json.load(f)
        row["inside"] = inside(d)
        pop = populations(d)
        if pop:
            row["populations"] = pop
    return row


def summarise(rows: list) -> dict:
    """Per metric, over the runs that printed a result; ``setup_s`` without
    a cold first run, as the driver judges it."""
    out = {}
    good = [r for r in rows if r.get("result")]
    names = sorted({n for r in good for n in r["result"]["metrics"]})
    for name in names:
        rs = [r for r in good if name in r["result"]["metrics"]
              and not (name == "setup_s" and r["cold"])]
        values = [r["result"]["metrics"][name]["value"] for r in rs]
        if values:
            out[name] = figures(values)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cold-first", action="store_true",
                    help="empty the compile cache before the first run")
    ap.add_argument("--label", default="set")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"),
                    help="directory for <label>.json and <label>/")
    ap.add_argument("--env", action="append", default=[], metavar="K=V",
                    help="set in every child's environment")
    ap.add_argument("--timeout", type=float, default=1500.0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the CPU rehearsal of run.py: counts only")
    args = ap.parse_args(argv)

    out_dir = os.path.join(args.out, args.label)
    os.makedirs(out_dir, exist_ok=True)
    env = dict(os.environ)
    # one fixed path inside the checkout, whatever the machine came with
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    env.update(kv.split("=", 1) for kv in args.env)
    if args.cold_first:
        shutil.rmtree(env["JAX_COMPILATION_CACHE_DIR"], ignore_errors=True)

    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        row = one_run(args, seed, i, out_dir, env)
        rows.append(row)
        res = row.get("result") or {}
        print(json.dumps({
            "label": args.label, "seed": seed, "rc": row["rc"],
            "cold": row["cold"], "correct": res.get("correct"),
            "attempted": res.get("attempted"), "failed": res.get("failed"),
            "metrics": {k: v["value"]
                        for k, v in res.get("metrics", {}).items()},
            "inside": row.get("inside"),
            "populations": row.get("populations"),
            "breakdown": res.get("breakdown"),
            "wall_s": round(row["wall_s"], 1)}), flush=True)
    summary = summarise(rows)
    for name, f in summary.items():
        print(f"{args.label} {name}: n={f['n']} median={f['median']:.6g} "
              f"min={f['min']:.6g} max={f['max']:.6g} "
              f"iqr/median={100 * (f['iqr_share'] or 0):.3f}% "
              f"trimmed range={f['trimmed_range'] or 0:.6g} "
              f"({100 * (f['trimmed_share'] or 0):.3f}%)", flush=True)
    with open(os.path.join(args.out, args.label + ".json"), "w") as f:
        json.dump({"args": vars(args), "rows": rows, "summary": summary}, f,
                  indent=1)
    bad = [r for r in rows if r["rc"] != 0 or not r.get("result")
           or not r["result"]["correct"] or r["result"]["failed"]]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
