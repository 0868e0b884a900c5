"""What ONE channel costs in a fused-run kernel at a density register's full
size, each alone, by lowering (PR 41):

    chiprun --timeout 2400 -- python tools/channelprobe.py [--qubits 15] [--only NAME]

A one-channel density tape through the public path (``createDensityQureg``,
``Circuit.fused(max_qubits=5, pallas=True)``, ``run``, sync): the planner
gives the channel its frame, so the one kernel of the plan is the channel's
op and a pass over the state. ``depol1`` / ``depol2`` are ``mixDepolarising``
/ ``mixTwoQubitDepolarising`` (the closed-form 'depol' op); ``kraus1`` /
``kraus2`` the SAME channels handed over as Kraus maps (``mixKrausMap`` /
``mixTwoQubitKrausMap`` of the canonical operators), which lower to the
Kraus sum of the superoperator's Choi terms as every channel did before. The
two-qubit pair is (3, 4) by default: at 15 qubits its columns are bits 18 and
19, the pair that straddles the 2^19 tile and takes the narrowed one.

The parent never touches JAX: each case is a child that owns the chip alone,
under its own time limit (Mosaic's compile time is steep in a kernel's op
count, and a ``kraus2`` body is 32 two-target matrix sweeps). One JSON row a
case on standard output: plan, first call (trace, compile, one run), and the
median and the least of ``--reps`` synced applications.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CASES = ("depol1", "kraus1", "depol2", "kraus2")


def child(name: str, n: int, one: int, pair: tuple, reps: int) -> dict:
    sys.path.insert(0, ROOT)
    import jax

    import quest_tpu as qt
    from quest_tpu import channels, fusion, planner
    from quest_tpu.circuits import Circuit
    from quest_tpu.ops import pallas_gates

    circ = Circuit(n, is_density_matrix=True)
    if name == "depol1":
        circ.mixDepolarising(one, 1e-3)
    elif name == "kraus1":
        circ.mixKrausMap(one, channels.depolarising_kraus(1e-3))
    elif name == "depol2":
        circ.mixTwoQubitDepolarising(*pair, 1e-2)
    else:
        circ.mixTwoQubitKrausMap(*pair,
                                 channels.two_qubit_depolarising_kraus(1e-2))
    fused = circ.fused(max_qubits=5, pallas=True)
    (run,) = fusion.plan_from_tape(fused._tape).items
    (op,) = run.ops
    row = {"case": name, "op": op[0], "tile_bits": run.tile_bits,
           "targets": list(pallas_gates.op_dense_targets(op)),
           "own_tile": run.own_tile,
           "frame": [run.load_swap_k, run.load_swap_hi],
           "terms": planner.channel_terms([run])}
    env = qt.createQuESTEnv(jax.devices()[:1])
    q = qt.createDensityQureg(n, env)      # |0><0|: the time is the pass's
    jax.block_until_ready(q.amps)
    t0 = time.perf_counter()
    fused.run(q)
    jax.block_until_ready(q.amps)
    row["first_call_s"] = time.perf_counter() - t0
    took = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fused.run(q)
        jax.block_until_ready(q.amps)
        took.append((time.perf_counter() - t0) * 1e3)
    row.update(ms_median=statistics.median(took), ms_min=min(took),
               trace=float(qt.calcTotalProb(q)),
               platform=jax.devices()[0].platform)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--qubits", type=int, default=15)
    ap.add_argument("--one", type=int, default=10, help="the one-qubit target")
    ap.add_argument("--pair", default="3,4", help="the two-qubit targets")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--timeout", type=float, default=900.0,
                    help="seconds a case may take")
    ap.add_argument("--only", choices=CASES)
    ap.add_argument("--child", choices=CASES, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    pair = tuple(int(q) for q in args.pair.split(","))
    if args.child:
        print(json.dumps(child(args.child, args.qubits, args.one, pair,
                               args.reps)), flush=True)
        return 0
    failed = 0
    for name in ([args.only] if args.only else CASES):
        cmd = [sys.executable, os.path.abspath(__file__), "--child", name,
               "--qubits", str(args.qubits), "--one", str(args.one),
               "--pair", args.pair, "--reps", str(args.reps)]
        t0 = time.perf_counter()
        try:
            out = subprocess.run(cmd, stdout=subprocess.PIPE, text=True,
                                 timeout=args.timeout)
            line = out.stdout.strip().splitlines()[-1:] or [
                json.dumps({"case": name, "rc": out.returncode})]
            failed += out.returncode != 0
        except subprocess.TimeoutExpired:
            line = [json.dumps({"case": name, "timed_out_after_s":
                                args.timeout})]
            failed += 1
        print(line[0], flush=True)
        print(f"# {name}: {time.perf_counter() - t0:.1f} s in all",
              file=sys.stderr, flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
