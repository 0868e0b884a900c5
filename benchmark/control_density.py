"""The control of ``correct`` for a density register that fills half its chip:
``control.py``'s two readings of every number of the check -- as the program
gives it (``sound``) and with the plain reference computed in bfloat16 put in
the program's place (``control``: ``reference_density_planes`` under
``reference_planes.LOWER``) -- against ONE run of the reference a seed, where
``control.py`` would make it once for each reading (a run of it takes a
minute of the chip at 2^30 elements).

    python3 benchmark/control_density.py --workload density15.noise \
        --seeds 1,2,3,4,5,6 [--control-seeds 2] [--seconds 2] [--rehearse]

One process: the cell set up once, then for each seed a short window at the
cell's own load and the check's numbers; the first ``--control-seeds`` seeds
also get the control, which has to come out as NOT correct. Output as
``control.py``'s: a JSON row a seed, then min and max of each number.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control-seeds", type=int, default=2,
                    help="how many of the seeds also get the control")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    run = harness.Run(args.workload, seeds[0], args.rehearse)
    device = harness.start_jax(run)
    harness.peaks_for(device["kind"], run.rehearse)
    driver = harness.load_module("drivers", run.config["driver"]).Driver(run)
    rows = []
    try:
        driver.setup()
        for seed in seeds:
            run.seed = seed
            driver.load_state()
            win = run.loop.run(driver.request, run.traffic, args.seconds)
            row = {"seed": seed, "requests": len(win.requests),
                   **driver.readings(win, control=len(rows)
                                     < args.control_seeds),
                   "reference_s": run.spans["reference_s"]}
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        driver.close()
    for kind in ("sound", "control"):
        for name in sorted({k for r in rows for k in r.get(kind, {})}):
            vals = [r[kind][name] for r in rows if kind in r]
            print(f"{kind} {name}: min {min(vals)!r} max {max(vals)!r} "
                  f"over {len(vals)} seeds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
