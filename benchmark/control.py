"""The control of ``correct``: the reference in the program's place, computed
in the next precision below the configuration's (bfloat16 for float32). It
has to come out as NOT correct; the benchmark's own runs never run it.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 [--seconds 2]
    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --host-only

The first form sets the cell up once, and for each seed drives a short window
at the cell's own load and prints every number compared twice: as the program
gives it (``sound``) and with the lower-precision reference put in the
program's place (``control``) -- the two readings a limit is set from, in one
process. ``--host-only`` makes no program and no window: the seed's input,
the reference and the control on the host alone, at the cell's full size
(nothing in the comparison touches the device, so it needs no chip).
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as harness


def bfloat16(x):
    """Round to bfloat16 and back (real and imaginary part each)."""
    import ml_dtypes

    x = np.asarray(x)

    def rnd(a):
        return a.astype(ml_dtypes.bfloat16).astype(np.float64)

    return rnd(x.real) + 1j * rnd(x.imag) if np.iscomplexobj(x) else rnd(x)


LOWER = {"float32": bfloat16}


def host_only(run, seeds) -> list:
    import reference
    import states

    cfg, n = run.config, run.circuit_args["num_qubits"]
    lower = LOWER[cfg["precision"]]
    rows = []
    for seed in seeds:
        run.seed = seed
        tape = reference.Tape()
        if cfg["check"]["kind"] == "served_states":
            names = run.builder.param_names(**run.circuit_args)
            params = states.angle_sets(seed, 0, names, 1)[0]
            run.builder.build(tape, angle=params.__getitem__,
                              **run.circuit_args)
            psi0 = np.zeros(1 << n, dtype=np.complex128)
            psi0[0] = 1.0
        else:
            run.builder.build(tape, **run.circuit_args)
            psi0 = states.to_complex(states.statevector_planes(seed, n))
        if cfg["check"]["kind"] == "density_blocks":
            spect = n - len(reference.support(tape.ops))
            pairs = states.sample_pairs(seed, cfg["check"]["blocks"], spect)
            want = reference.run_density_blocks(psi0, n, tape.ops, *pairs)
            low = reference.run_density_blocks(psi0, n, tape.ops, *pairs,
                                               lower=lower)
        else:
            want = reference.run_statevector(psi0, tape.ops)
            low = reference.run_statevector(psi0, tape.ops, lower=lower)
        err_max, err_l2 = reference.errors(low.real, low.imag, want)
        rows.append({"seed": seed, "control": {"err_max": err_max,
                                               "err_l2": err_l2}})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def with_program(run, seeds, seconds, control_seeds) -> list:
    device = harness.start_jax(run)
    harness.peaks_for(device["kind"], run.rehearse)
    driver = harness.load_module("drivers", run.config["driver"]).Driver(run)
    lower = LOWER[run.config["precision"]]
    rows = []
    try:
        run.seed = seeds[0]
        driver.setup()
        for seed in seeds:
            run.seed = seed
            driver.load_state()
            win = run.loop.run(driver.request, run.traffic, seconds)
            row = {"seed": seed, "requests": len(win.requests)}
            for label, fn in (("sound", None), ("control", lower)):
                if fn is not None and len(rows) >= control_seeds:
                    continue
                run.control = fn
                row[label] = {name: value
                              for name, value, _ in driver.check(win)}
            run.control = None
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        driver.close()
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="how many of the seeds also get the control")
    ap.add_argument("--host-only", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    run = harness.Run(args.workload, seeds[0], args.rehearse)
    if args.host_only:
        rows = host_only(run, seeds)
    else:
        rows = with_program(run, seeds, args.seconds, args.control_seeds)
    for kind in ("sound", "control"):
        names = sorted({k for r in rows for k in r.get(kind, {})})
        for name in names:
            vals = [r[kind][name] for r in rows if name in r.get(kind, {})]
            print(f"{kind} {name}: min {min(vals):.4g} max {max(vals):.4g} "
                  f"over {len(vals)} seeds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
