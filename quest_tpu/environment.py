"""Execution environment (reference: ``QuESTEnv``, QuEST.h:405-415).

The reference's env carries (rank, numRanks, seeds) and is created once per
process around MPI_Init / GPU probing (QuEST_cpu_distributed.c:131-164,
QuEST_cuQuantum.cu:147-204). The TPU-native env instead carries:

  - a ``jax.sharding.Mesh`` over the visible devices (1-D axis ``"amps"``),
    the analogue of the MPI communicator. The reference requires a power-of-2
    rank count (QuEST_validation.c:354-366); we validate the same so the shard
    axis always aligns with the top qubits.
  - the seed state: a list of user seeds plus a host-side Mersenne-Twister
    generator (numpy's MT19937 -- same algorithm as the reference's
    mt19937ar.c) used for measurement outcomes. Because there is a single
    controller process, cross-rank seed agreement
    (QuEST_cpu_distributed.c:1400-1418) is automatic.

Unlike the reference, distribution and acceleration compose: the same env
drives 1 chip or a pod slice.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import validation

#: name of the mesh axis amplitudes are sharded over
AMP_AXIS = "amps"

_PALLAS_MESH = threading.local()


@contextlib.contextmanager
def pallas_mesh(mesh):
    """Ambient execution mesh for PallasRuns inside jit traces, where the
    amps tracer hides its sharding. Circuit.run derives it from the actual
    register and activates it around the traced replay, so a fused plan is
    never bound to one device set; set it manually only when calling a
    compiled replay directly on a sharded register (see
    examples/distributed_34q.py)."""
    prev = getattr(_PALLAS_MESH, "mesh", None)
    _PALLAS_MESH.mesh = mesh
    try:
        yield
    finally:
        _PALLAS_MESH.mesh = prev


def active_pallas_mesh():
    return getattr(_PALLAS_MESH, "mesh", None)


@dataclass
class QuESTEnv:
    mesh: Optional[Mesh]
    seeds: list[int] = field(default_factory=list)
    rng: np.random.RandomState = None
    #: pod-slice count of the device set (1 = single slice). Devices are
    #: ordered slice-major, so the chip axis forms the LOW shard bits (hot
    #: relocation targets ride ICI) and only the top log2(num_slices)
    #: sharded qubits cross DCN; parallel.mesh.shard_bit_link classifies.
    num_slices: int = 1

    # kept for reference API parity (reportQuESTEnv prints them)
    @property
    def num_ranks(self) -> int:
        return self.mesh.size if self.mesh is not None else 1

    @property
    def rank(self) -> int:
        return 0  # single-controller SPMD: there is one logical process

    @property
    def requires_sharding(self) -> bool:
        """True when registers MUST shard over the mesh: multi-host
        (jax.distributed) execution, where every process owns devices and a
        replicated-on-one-device fallback is impossible. Single-host meshes
        replicate registers too small to split instead of rejecting them
        (more permissive than the reference's >=1-amp-per-node rule,
        QuEST_validation.c:368-377, which applies here only multi-host)."""
        return jax.process_count() > 1

    def sharding(self, num_amps: int) -> Optional[NamedSharding]:
        """Block-partition a planar (2, num_amps) amplitude array over the
        mesh (the top log2(numDevices) qubits), as statevec_createQureg's
        chunking (QuEST_cpu.c:1296-1319). Falls back to None (single device /
        too few amps to split)."""
        if self.mesh is None or self.mesh.size == 1 or num_amps < self.mesh.size:
            return None
        return NamedSharding(self.mesh, PartitionSpec(None, AMP_AXIS))

    def replicated(self) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, PartitionSpec())


def createQuESTEnv(devices: Sequence[jax.Device] | None = None,
                   num_slices: int | None = None) -> QuESTEnv:
    """Create the environment (createQuESTEnv, QuEST.h:2196).

    ``devices`` defaults to all visible devices; a power-of-2 count is
    required (same constraint as the reference's validateNumRanks).
    ``num_slices`` declares a multi-slice (DCN-connected) topology: devices
    are ordered slice-major so intra-slice chips form the minor shard bits
    (hot qubits ride ICI; see parallel.mesh). Auto-detected from the TPU
    runtime's ``slice_index`` attribute when omitted.
    """
    func = "createQuESTEnv"
    if devices is None:
        devices = jax.devices()
        # trim to the largest power of two, like users launching 2^k ranks
        count = 1 << (len(devices).bit_length() - 1)
        devices = devices[:count]
    validation.validate_num_ranks(len(devices), func)
    explicit_slices = num_slices is not None
    if num_slices is None:
        num_slices = len({getattr(d, "slice_index", 0) for d in devices})
    bad = (num_slices < 1 or len(devices) % num_slices
           or num_slices & (num_slices - 1))
    if bad:
        if explicit_slices:
            raise validation.QuESTError(
                f"num_slices={num_slices} does not evenly split "
                f"{len(devices)} devices into power-of-2 slices")
        num_slices = 1  # auto-detect is stats-only; never reject hardware
    if num_slices > 1:
        # slice-major order (chip axis = minor shard bits -> hot qubits
        # ride ICI), stable within a slice to preserve the caller's order
        devices = sorted(devices, key=lambda d: getattr(d, "slice_index", 0))
    mesh = Mesh(np.asarray(devices), (AMP_AXIS,))
    env = QuESTEnv(mesh=mesh, num_slices=num_slices)
    seedQuESTDefault(env)
    return env


def destroyQuESTEnv(env: QuESTEnv) -> None:
    """No-op (no MPI_Finalize needed); kept for API parity."""


def syncQuESTEnv(env: QuESTEnv) -> None:
    """Barrier analogue: block until enqueued device work is done
    (reference: MPI_Barrier, QuEST_cpu_distributed.c:166-168)."""
    (jax.device_put(0) + 0).block_until_ready()


def syncQuESTSuccess(success_code: int) -> int:
    """All-ranks success agreement (MPI_LAND allreduce in the reference,
    QuEST_cpu_distributed.c:170-174). Single controller: identity."""
    return success_code


def reportQuESTEnv(env: QuESTEnv) -> None:
    """Print deployment info (reportQuESTEnv; format follows
    getEnvironmentString, QuEST_cpu_distributed.c:185-208)."""
    plats = {d.platform for d in (env.mesh.devices.flat if env.mesh is not None else [])}
    found = ', '.join(sorted(plats)) or 'none'
    print("EXECUTION ENVIRONMENT:")
    # the platform the devices are actually on, never a claim: a CPU run
    # (a supported test platform) must not report itself as the TPU
    print(f"Backend: {found} (JAX/XLA {jax.__version__})")
    print(f"Number of devices: {env.num_ranks}")
    print(f"Device platform(s): {found}")
    print(f"Precision default: {os.environ.get('QUEST_PRECISION', '1')}")


def getEnvironmentString(env: QuESTEnv) -> str:
    """Fill ``env_str`` with the execution-environment summary (QuEST.h:123)."""
    n = env.num_ranks
    return f"CUDA=0 OpenMP=0 MPI=0 TPU=1 threads=1 ranks={n} devices={n}"


# ---------------------------------------------------------------------------
# seeding (reference: seedQuEST/seedQuESTDefault/getQuESTSeeds,
# QuEST_common.c:195-217 + mt19937ar.c)
# ---------------------------------------------------------------------------

def seedQuEST(env: QuESTEnv, seeds: Sequence[int]) -> None:
    """Seed the measurement RNG from a user key array. numpy's MT19937 seeds
    arrays via init_by_array -- the same routine the reference feeds
    (QuEST_common.c:209-217)."""
    validation.validate_num_seeds(seeds, "seedQuEST")
    env.seeds = [int(s) for s in seeds]
    env.rng = np.random.RandomState(np.asarray(env.seeds, dtype=np.uint32))


def seedQuESTDefault(env: QuESTEnv) -> None:
    """Default seeding from time + pid (QuEST_common.c:195-207)."""
    seedQuEST(env, [int(time.time()) & 0xFFFFFFFF, os.getpid() & 0xFFFFFFFF])


def getQuESTSeeds(env: QuESTEnv) -> list[int]:
    """The seeds the env's RNG was last seeded with (QuEST.h:126)."""
    return list(env.seeds)
