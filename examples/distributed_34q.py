"""BASELINE.json config 5: a distributed state-vector sharded over a mesh.

The reference scales Hilbert space with MPI amplitude sharding
(QuEST_cpu_distributed.c: exchangeStateVectors pair swaps); here the same
partition is a `jax.sharding.Mesh` over all visible devices, and XLA emits
the collective_permute / all-to-all traffic when a gate touches a sharded
(top) qubit.

At the target scale -- 34 qubits on a v5p-16 pod slice (128 GiB of
amplitudes across 16 chips) -- run this unchanged on the pod:

    python examples/distributed_34q.py --qubits 34

On smaller hardware it auto-scales the register to fit (the sharding logic
is identical; only numAmpsPerChunk changes, exactly as with mpirun -np).
Emulate the 16-way mesh on CPU with:

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=16 \
        python examples/distributed_34q.py --qubits 20
"""

import argparse
import time

import _bootstrap  # noqa: F401  (repo path + QUEST_PLATFORM handling)

import jax
import numpy as np


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--qubits", type=int, default=None,
                   help="default: largest register that fits in ~60%% of HBM")
    p.add_argument("--depth", type=int, default=4)
    args = p.parse_args()

    import quest_tpu as qt
    from quest_tpu.circuits import Circuit

    devices = jax.devices()
    env = qt.createQuESTEnv(devices)
    print(f"mesh: {len(devices)} x {devices[0].device_kind}")

    n = args.qubits
    if n is None:
        stats = devices[0].memory_stats() or {}
        per_dev = stats.get("bytes_limit", 16 << 30) * 0.6
        total = per_dev * len(devices)
        n = int(np.log2(total / 8))  # planar f32: 8 bytes/amp
        print(f"auto-sized to {n} qubits")

    qureg = qt.createQureg(n, env)
    qt.initPlusState(qureg)
    shards = len(qureg.amps.sharding.device_set) if qureg.amps.sharding else 1
    print(f"{n}-qubit register: {qureg.num_amps_total:,} amps over "
          f"{shards} shard(s)")

    # random layers touching both local and sharded (top) qubits: gates on
    # the top log2(ndev) qubits compile to cross-device collectives
    circ = Circuit(n)
    rng = np.random.RandomState(7)
    for layer in range(args.depth):
        for q in range(n):
            (circ.hadamard if rng.rand() < 0.5 else
             lambda q: circ.rotateZ(q, rng.rand()))(q)
        for q in range(layer % 2, n - 1, 2):
            circ.controlledNot(q, q + 1)
        circ.controlledPhaseFlip(0, n - 1)

    # two-frame Pallas planning sized for the shard-local state: fused runs
    # execute per shard under shard_map (sharded-qubit controls/diagonals
    # resolve against the shard index in-kernel); gates no frame localises
    # fall back to the sharding-aware engine automatically
    use_pallas = jax.default_backend() == "tpu"
    fused = circ.fused(max_qubits=5, pallas=use_pallas,
                       shard_devices=shards if use_pallas else None)

    # a chain of segment programs bounds each program's compile size, cut
    # at seams where the frame is at identity. compiled_segments bypasses
    # Circuit.run, so build it under the execution mesh (the segment
    # executables pin the ambient contexts at build time)
    from quest_tpu import environment
    from quest_tpu.circuits import _register_mesh

    with environment.pallas_mesh(_register_mesh(qureg)):
        fn = fused.compiled_segments(max_items=24, donate=True)

    t0 = time.time()
    amps = fn(qureg.amps)
    amps.block_until_ready()
    print(f"compile+first step: {time.time() - t0:.1f}s")

    t0 = time.time()
    amps = fn(amps)
    qureg.put(amps)
    prob = qt.calcTotalProb(qureg)
    dt = time.time() - t0
    print(f"step: {dt:.3f}s  ({len(circ)} gates, {len(circ)/dt:.1f} gates/s)")
    print(f"total probability: {prob:.6f}")
    assert abs(prob - 1.0) < 1e-4


if __name__ == "__main__":
    main()
