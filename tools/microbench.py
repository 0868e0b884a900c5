"""Micro-benchmarks for the fused-run redesign (round 2).

Times candidate HBM passes at 2^26 amplitudes on the live chip:
  - xla_swap:    bit-block swap [8..16] <-> [17..25] as an XLA transpose
  - pallas_run:  one fused_local_run with ~N per-gate ops (butterflies,
                 grid-bit controls, parity)
  - lane_run:    current lane-folded run (reference point, ~2.4 ms)
  - einsum_win:  dense 5q window at lo>=17 via the engine einsum (~5.6 ms)
  - elementwise: trivial scale pass = HBM roofline floor
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def sync(a):
    return float(jax.device_get(a.reshape(-1)[0]))


def timeit(fn, amps, reps=20, label=""):
    """Time ``fn`` per application with the loop *inside* one jit program:
    a single call's time is mostly the fixed per-dispatch cost (~6.5 ms on
    the shared remote chip of rounds 1-5), not device work."""

    @jax.jit
    def looped(x):
        for _ in range(reps):
            x = fn(x)
        return x

    amps = looped(amps)  # compile + warmup
    sync(amps)
    t0 = time.perf_counter()
    amps = looped(amps)
    amps = looped(amps)
    sync(amps)
    dt = (time.perf_counter() - t0) / (2 * reps)
    print(f"{label:14s} {dt * 1e3:8.3f} ms")
    return amps


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--n", type=int, default=26)
    args = p.parse_args()
    n = args.n
    num = 1 << n

    amps = jnp.zeros((2, num), jnp.float32).at[0, 0].set(1.0)
    print(f"n={n}, state {num * 8 / 2**20:.0f} MiB, backend {jax.default_backend()}")

    # --- elementwise floor ------------------------------------------------
    @jax.jit
    def scale(x):
        return x * np.float32(1.0000001)

    amps = timeit(scale, amps, label="elementwise")

    # --- XLA bit-block swap ----------------------------------------------
    # swap [tb-g .. tb-1] <-> [tb .. n-1] with tb=17
    tb = 17
    g = n - tb
    assert g >= 1

    @jax.jit
    def xla_swap(x):
        v = x.reshape(2, 1 << g, 1 << g, -1)
        return v.transpose(0, 2, 1, 3).reshape(2, -1)

    amps = timeit(xla_swap, amps, label="xla_swap")

    # --- pallas runs ------------------------------------------------------
    from quest_tpu.ops.pallas_gates import HashableMatrix, fused_local_run

    H = HashableMatrix(np.array([[1, 1], [1, -1]]) / np.sqrt(2))
    T = HashableMatrix(np.diag([1, np.exp(1j * np.pi / 4)]))
    X = HashableMatrix(np.array([[0, 1], [1, 0]]))

    def rz(th):
        return HashableMatrix(np.diag([np.exp(-1j * th / 2), np.exp(1j * th / 2)]))

    # a realistic frame-A run: 17 1q gates on 0..16 + 8 CNOTs + parity
    ops = []
    for q in range(17):
        ops.append(("matrix", q, (), (), [H, T, rz(0.3)][q % 3]))
    for q in range(0, 16, 2):
        ops.append(("matrix", q + 1, (q,), (1,), X))
    # grid-bit-controlled phase: diag matrix on in-tile target, grid control
    ops.append(("matrix", 0, (n - 1,), (1,), rz(0.7)))
    ops.append(("parity", tuple(range(0, n, 3)), (), 0.21))
    ops = tuple(ops)

    def prun(x):
        return fused_local_run(x, n=n, ops=ops)

    amps = timeit(prun, amps, label=f"pallas_{len(ops)}ops")

    # lane-only run (all targets < 7): folds to one lane_u
    ops_lane = tuple(("matrix", q % 7, (), (), H) for q in range(17))

    def lrun(x):
        return fused_local_run(x, n=n, ops=ops_lane)

    amps = timeit(lrun, amps, label="lane_run")

    # sublane-butterfly-heavy run: 10 gates on 7..16
    ops_sub = tuple(("matrix", 7 + (q % 10), (), (), H) for q in range(10))

    def srun(x):
        return fused_local_run(x, n=n, ops=ops_sub)

    amps = timeit(srun, amps, label="sublane10")

    # --- dense 5q window at lo >= 17 through the einsum engine -------------
    from quest_tpu.ops import apply as K

    rng = np.random.RandomState(0)
    u, _ = np.linalg.qr(rng.randn(32, 32) + 1j * rng.randn(32, 32))
    m = jnp.stack([jnp.asarray(u.real, jnp.float32), jnp.asarray(u.imag, jnp.float32)])
    targ = tuple(range(n - 5, n))

    def ein(x):
        return K.apply_matrix(x, m, n=n, targets=targ)

    amps = timeit(ein, amps, label="einsum_win5")


if __name__ == "__main__":
    main()
