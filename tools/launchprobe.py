"""What a launch of the gradient batch program costs, by the form its result
takes when it leaves the program (PR 46):

    chiprun --timeout 1800 -- python tools/launchprobe.py [--qubits 20] [--depth 4]

Builds the gradient cell's companion (``Engine(serving_ansatz(n, d),
hamiltonian=..., max_batch=8).grad_engine()``; the configuration's six Pauli
strings, ``RandomState(20)``), fetches its batch program (``Engine._execB``)
and times the CALL alone, then the sync, then the fetch to the host, over
``--reps`` warm launches:

``as_built``  the program as the engine launches it;
``scalars``   the same jitted body with every number of every lane an output
              of its own (the convention before PR 46: 8 x 321 arrays);
``lanes``     one ``(k,)`` array a lane;
``one``       one ``(max_batch, k)`` array.

The form ``as_built`` already has is told from its outputs and not compiled a
second time. Every other form is a program of its own and compiles (some
200 s each at 20 qubits on the chip): ``--forms`` picks. ``--profile`` runs
the launches of ``as_built`` under cProfile and prints where the host was.
One JSON row a form on standard output, all of them in
``chiprun_out/launchprobe.json`` of the working directory. Needs the chip
unless ``--rehearse`` (the CPU at 10 qubits, depth 2: the control flow, no
time worth reading).
"""

from __future__ import annotations

import argparse
import cProfile
import io
import json
import os
import pstats
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FORMS = ("scalars", "lanes", "one")


def _rows(out):
    """The program's result as a list of scalars a lane, whatever its form:
    one array of a row a lane, or lanes as outputs of their own (an array
    each, or a tree of scalars)."""
    import jax

    if hasattr(out, "shape"):
        return [list(row) for row in out]
    return [list(lane) if getattr(lane, "ndim", 0)
            else jax.tree_util.tree_leaves(lane) for lane in out]


def _form_of(outputs: int, width: int) -> str:
    if outputs == 1:
        return "one"
    return "lanes" if outputs == width else "scalars"


def _in_form(form, out):
    """``out`` handed back as ``form``, inside a program."""
    import jax.numpy as jnp

    rows = _rows(out)
    if form == "scalars":
        return tuple(tuple(r) for r in rows)
    if form == "lanes":
        return tuple(jnp.stack(r) for r in rows)
    return jnp.stack([jnp.stack(r) for r in rows])


def _timed(name, fn, args, reps, profile=False) -> dict:
    import jax

    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    first = time.perf_counter() - t0
    launch, sync, fetch = [], [], []
    prof = cProfile.Profile() if profile else None
    for _ in range(reps):
        t0 = time.perf_counter()
        if prof is not None:
            prof.enable()
        out = fn(*args)
        if prof is not None:
            prof.disable()
        t1 = time.perf_counter()
        jax.block_until_ready(out)
        t2 = time.perf_counter()
        jax.device_get(out)
        t3 = time.perf_counter()
        launch.append(1e3 * (t1 - t0))
        sync.append(1e3 * (t2 - t1))
        fetch.append(1e3 * (t3 - t2))
    row = {"form": name,
           "outputs": len(jax.tree_util.tree_leaves(out)),
           "first_call_s": round(first, 2),
           "launch_ms": [round(x, 3) for x in launch],
           "launch_ms_median": round(statistics.median(launch), 3),
           "sync_ms_median": round(statistics.median(sync), 3),
           "fetch_ms_median": round(statistics.median(fetch), 3)}
    if prof is not None:
        s = io.StringIO()
        pstats.Stats(prof, stream=s).sort_stats("cumulative").print_stats(30)
        row["profile"] = s.getvalue()[-6000:]
    return row


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--qubits", type=int, default=20)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--forms", default=",".join(FORMS),
                    help="the forms to compile beside as_built")
    ap.add_argument("--profile", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    if a.rehearse:
        a.qubits, a.depth = min(a.qubits, 10), min(a.depth, 2)
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np

    import bench
    import quest_tpu as qt
    from quest_tpu.engine import Engine
    from quest_tpu.params import _pack_rows, bind

    platform = jax.devices()[0].platform
    if platform != "tpu" and not a.rehearse:
        print(f"launchprobe needs the chip, found {platform!r} "
              "(--rehearse for the CPU)", file=sys.stderr)
        return 2
    rng = np.random.RandomState(20)
    codes = rng.randint(0, 4, size=(6, 20))[:, :a.qubits].astype(np.int32)
    coeffs = rng.normal(size=6)
    engine = Engine(bench.serving_ansatz(a.qubits, a.depth),
                    qt.createQuESTEnv(), hamiltonian=(codes, coeffs),
                    max_batch=a.batch, max_delay_ms=0.5)
    head = {"device": {"platform": platform,
                       "kind": jax.devices()[0].device_kind},
            "qubits": a.qubits, "depth": a.depth, "batch": a.batch}
    rows = []
    try:
        comp = engine.grad_engine()
        angles = np.random.RandomState(46).uniform(
            0, 2 * np.pi, (a.batch, len(comp.param_names)))
        lanes = [_pack_rows(comp._packs, bind(
            comp._lifted, dict(zip(comp.param_names, row)))) for row in angles]
        args = (comp.initial_amps,
                *(np.stack(kind) for kind in zip(*lanes)))
        as_built = comp._execB()
        row = _timed("as_built", as_built, args, a.reps, a.profile)
        row["is"] = built = _form_of(row["outputs"], a.batch)
        rows.append(row)
        body = as_built.__kwdefaults__["_inner"].__wrapped__
        for form in (f for f in a.forms.split(",") if f and f != built):
            rows.append(_timed(form, jax.jit(
                lambda *xs, form=form: _in_form(form, body(*xs))),
                args, a.reps))
    finally:
        engine.close(drain=False)
        out = os.path.join(os.getcwd(), "chiprun_out")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, "launchprobe.json"), "w") as f:
            json.dump({**head, "rows": rows}, f, indent=1)
    print(json.dumps(head))
    for row in rows:
        profile = row.pop("profile", None)
        print(json.dumps(row))
        if profile:
            print(profile)
    return 0


if __name__ == "__main__":
    sys.exit(main())
