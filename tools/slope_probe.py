"""Two-point-slope microbench of fused-run passes at 2^26 (round 5).

The round-4 probes divided (fixed dispatch+sync cost + work) by the rep
count, so every per-pass figure was inflated by fixed/reps (the
round-5 correction). Here each config is timed at TWO rep counts inside
one jit program and the SLOPE is reported -- the fixed cost cancels.

Usage: python tools/slope_probe.py [n]
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from quest_tpu.compile_cache import enable_compile_cache

enable_compile_cache()


def slope_time(fn, amps, r_small=4, r_big=16, trials=2):
    """Marginal per-application time of ``fn`` via bench.two_point_slope
    (the ONE shared slope protocol; the dispatch+sync fixed cost cancels
    in the two-region difference)."""
    from bench import two_point_slope

    def make(r):
        @jax.jit
        def looped(x):
            for _ in range(r):
                x = fn(x)
            return x, x[0, 0]
        return looped

    dt, amps = two_point_slope(make, amps, r_small, r_big, trials=trials)
    return dt, amps


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 26
    from quest_tpu.ops.pallas_gates import HashableMatrix, fused_local_run

    H = HashableMatrix(np.array([[1, 1], [1, -1]]) / np.sqrt(2))
    T = HashableMatrix(np.diag([1, np.exp(1j * np.pi / 4)]))
    amps = jnp.zeros((2, 1 << n), jnp.float32).at[0, 0].set(1.0)
    print(f"n={n} backend={jax.default_backend()} (two-point slopes)")

    c = np.float32(1.0000001)

    def el(x):
        return jax.lax.optimization_barrier(x) * c

    dt, amps = slope_time(el, amps)
    print(f"{'elementwise floor':24s} {dt * 1e3:8.3f} ms")

    # single-diag pass floor vs chunk size
    for s in (2048, 4096, 8192, 16384):
        def f(x, _s=s):
            return fused_local_run(x, n=n, ops=(("matrix", 0, (), (), T),),
                                   sublanes=_s)
        dt, amps = slope_time(f, amps)
        print(f"{'pass floor S=' + str(s):24s} {dt * 1e3:8.3f} ms")

    # folded-swap pass (the production frame-switch pass shape)
    def fsw(x):
        return fused_local_run(x, n=n, ops=(("matrix", 0, (), (), T),),
                               load_swap_k=7, store_swap_k=7)
    dt, amps = slope_time(fsw, amps)
    print(f"{'ld=7 st=7 S=4096':24s} {dt * 1e3:8.3f} ms")

    # butterfly-heavy pass (the compute the heavy passes carry)
    ops_sub = tuple(("matrix", 7 + (q % 10), (), (), H) for q in range(10))

    def fb(x):
        return fused_local_run(x, n=n, ops=ops_sub)
    dt, amps = slope_time(fb, amps)
    print(f"{'sublane H x10':24s} {dt * 1e3:8.3f} ms")


if __name__ == "__main__":
    main()
