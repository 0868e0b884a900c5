"""Per-item profiling of the two-frame plan: times each PallasRun and
FrameSwap of the bench circuit individually (loop-inside-jit), and prints
the op composition of each run -- the breakdown that tells where a block's
milliseconds go.

Each item's timing is also recorded as a telemetry span
(``runprof.item{index,kind}``), and the run ends with the registry's
compile-seconds / pass-count snapshot -- the same series bench.py ships in
BENCH_DETAIL.json, so a runprof session and a bench artifact are directly
comparable."""

from __future__ import annotations

import os
import sys
import time
from collections import Counter

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from quest_tpu.compile_cache import enable_compile_cache

enable_compile_cache()


def sync(a):
    return float(jax.device_get(a.reshape(-1)[0]))


def timeit(fn, amps, reps=10):
    @jax.jit
    def looped(x):
        for _ in range(reps):
            x = fn(x)
        return x

    amps = looped(amps)
    sync(amps)
    t0 = time.perf_counter()
    amps = looped(amps)
    sync(amps)
    return (time.perf_counter() - t0) / reps, amps


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 26
    from __graft_entry__ import _random_layers
    from quest_tpu import fusion, planner, telemetry
    from quest_tpu.circuits import Circuit
    from quest_tpu.ops.pallas_gates import (_fold_zone_ops, local_qubits,
                                            swap_bit_blocks)
    from quest_tpu.registers import Qureg

    circ = Circuit(n)
    _random_layers(circ, n, 8)
    tb = local_qubits(n)
    p = planner.plan(tuple(circ._tape), n, np.dtype("float32"), 5,
                    pallas_tile_bits=tb)

    amps = jnp.zeros((2, 1 << n), jnp.float32).at[0, 0].set(1.0)
    total = 0.0
    for i, item in enumerate(p.items):
        if isinstance(item, planner.PallasRun):
            folded = _fold_zone_ops(item.ops, tb)
            comp = Counter(o[0] for o in folded)
            route = fusion._route(Qureg(n, False, amps, env=None), item)

            # profile what production actually runs: the run's own tape
            # entry, which folds the swaps fusion._route says it can and
            # runs the others as explicit passes
            def run(x, item=item):
                shell = Qureg(n, False, x, env=None)
                fusion._apply_pallas_run(shell, item)
                return shell.amps

            with telemetry.span("runprof.item", index=i, kind="run"):
                dt, amps = timeit(run, amps)
            telemetry.set_gauge("runprof.item_ms", dt * 1e3, index=i,
                                kind="run")
            print(f"[{i:2d}] run  {dt*1e3:7.3f} ms  {len(item.ops):3d} ops "
                  f"ld={item.load_swap_k}{'f' if route.fold_load else ''} "
                  f"st={item.store_swap_k}{'f' if route.fold_store else ''}"
                  f" -> {dict(comp)}")
        elif isinstance(item, planner.FrameSwap):
            with telemetry.span("runprof.item", index=i, kind="swap"):
                dt, amps = timeit(
                    lambda x: swap_bit_blocks(x, n=n,
                                              lo1=item.tile_bits - item.k,
                                              lo2=item.tile_bits, k=item.k),
                    amps)
            telemetry.set_gauge("runprof.item_ms", dt * 1e3, index=i,
                                kind="swap")
            print(f"[{i:2d}] swap {dt*1e3:7.3f} ms")
        else:
            print(f"[{i:2d}] OTHER {type(item).__name__}")
            continue
        total += dt
    print(f"total {total*1e3:.1f} ms per circuit pass")
    import json as _json
    snap = telemetry.snapshot()
    print("# telemetry counters:", _json.dumps(snap["counters"]))
    print("# telemetry compile:", _json.dumps(
        {k: v for k, v in snap["histograms"].items()
         if k.startswith("mosaic_compile_seconds")}))


if __name__ == "__main__":
    main()
