"""The control of ``correct`` for a served gradient: the same request with the
program's arithmetic rounded to bfloat16. ``reference_grad.gradient`` with
every gate matrix, every written amplitude, the costate, each bracket and the
energy rounded to bfloat16 stands in the program's place (the driver puts it
there when ``run.control`` is set) and has to come out as NOT correct: a limit
that a lower precision than float32 would pass is not one.

    python3 benchmark/control_grad.py --workload ansatz20.grad-closed8 --seeds 1,2,3 [--seconds 2]
    python3 benchmark/control_grad.py --workload ansatz20.grad-closed8 --seeds 1,2,3 --host-only

The first form is ``control.py``'s own, unchanged (``control.main``): the cell
set up once, a short window a seed at the cell's own load, every number
compared printed as the program gives it (``sound``) and as the control gives
it (``control``). ``--host-only`` is this file's: no program and no window, a
seed's first request, the reference and the control on the host alone at the
cell's full size (nothing in the comparison touches the device).
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import control
import run as harness
import reference
import reference_grad
import states


def host_only(run, seeds) -> list:
    """Client 0's first request of each seed, (a) in bfloat16 against (a)
    and (b) in complex128."""
    cfg, args = run.config, run.circuit_args
    n = args["num_qubits"]
    codes, coeffs = reference_grad.hamiltonian(cfg, n)
    names = run.builder.param_names(**args)
    lower = control.LOWER[cfg["precision"]]
    rows = []
    for seed in seeds:
        params = states.angle_sets(seed, 0, names, 1)[0]
        tape = reference.Tape()
        run.builder.build(tape, angle=params.__getitem__, **args)
        which = reference_grad.shift_picks(
            np.random.default_rng([seed, 11]), tape.ops,
            cfg["check"]["shift_components"])
        rows.append({"seed": seed, "control": reference_grad.errors(
            reference_grad.gradient(tape.ops, codes, coeffs, lower=lower),
            reference_grad.gradient(tape.ops, codes, coeffs), which,
            reference_grad.shift(tape.ops, codes, coeffs, which))})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if "--host-only" not in argv:
        return control.main(argv)
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--host-only", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = host_only(harness.Run(args.workload, seeds[0], args.rehearse),
                     seeds)
    for name in reference_grad.ERRORS:
        vals = [r["control"][name] for r in rows]
        print(f"control {name}: min {min(vals):.4g} max {max(vals):.4g} "
              f"over {len(vals)} seeds")
    return 0


if __name__ == "__main__":
    sys.exit(main())
