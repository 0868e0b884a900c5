"""Qureg: the qubit register (reference struct at QuEST.h:360-396).

The reference Qureg carries planar host arrays, an MPI receive buffer
(``pairStateVec``), and GPU mirrors + reduction buffers. The TPU-native Qureg
is a thin mutable handle around one device ``jax.Array`` of shape
(2, 2^numQubitsInStateVec) -- planar (real, imag) float amplitudes, the same
SoA layout as the reference's ComplexArray (QuEST.h:94-98), chosen because
the TPU has no native complex dtype. It is sharded over the env's mesh (XLA
owns all scratch/comm buffers, so pairStateVec and the reduction buffers have
no equivalent).

Mutation model: the C API mutates Quregs in place; JAX arrays are immutable.
API functions therefore rebind ``qureg.amps`` to the new functional value --
the handle is stable, the array is fresh (XLA donation keeps this
allocation-neutral inside jit).

Density matrices are state-vectors of 2N qubits (QuEST.c:8-10): element
rho[row, col] lives at flat index col * 2^N + row (row bits low). Gates apply
to row-qubit q and, conjugated, to col-qubit q+N -- the "shadow" op.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from . import precision, validation
from .environment import AMP_AXIS, QuESTEnv, active_pallas_mesh
from .ops import init as ops_init
from .qasm import QASMLogger


@dataclass
class Qureg:
    num_qubits_represented: int
    is_density_matrix: bool
    amps: jax.Array
    env: QuESTEnv
    qasm_log: Optional[QASMLogger] = None
    #: lazily-created host planar mirror for copyState{To,From}GPU
    host_amps: Optional[np.ndarray] = None
    #: ``(amps, planes)``: the (4, N) double-float planes a fused df run
    #: joined ``amps`` from, for the df run after it to take in place of a
    #: split while ``self.amps is amps`` (``fusion._df_local_run``)
    df_planes: Optional[tuple] = None

    @property
    def state_vec(self) -> np.ndarray:
        """Host planar mirror (the reference's ``qureg.stateVec``); sync with
        copyStateFromGPU/copyStateToGPU."""
        return _host_mirror(self)

    @property
    def num_qubits_in_state_vec(self) -> int:
        return (2 if self.is_density_matrix else 1) * self.num_qubits_represented

    @property
    def num_amps_total(self) -> int:
        return 1 << self.num_qubits_in_state_vec

    # parity aliases matching the reference field names
    @property
    def numQubitsRepresented(self) -> int:
        return self.num_qubits_represented

    @property
    def numAmpsTotal(self) -> int:
        return self.num_amps_total

    @property
    def dtype(self):
        """Real dtype of the planar amplitude planes (float32/float64)."""
        return self.amps.dtype

    @property
    def eps(self) -> float:
        return precision.eps_for_dtype(self.amps.dtype)

    def put(self, new_amps) -> None:
        """Rebind the amplitude array, preserving the register's sharding.

        Eagerly the appliers lower their result with the sharding of their
        argument (``ops.layout.amps_jit``). Inside a jitted replay the
        tracer hides it and the partitioner may leave an op's result fully
        replicated (jax 0.9: a dense gate on a sharded qubit), so there the
        new value is constrained to the ambient mesh Circuit.run derived
        from the register -- every step of the replay stays partitioned."""
        if isinstance(new_amps, jax.core.Tracer) and new_amps.ndim == 2:
            mesh = active_pallas_mesh()
            if mesh is not None and mesh.size > 1:
                new_amps = jax.lax.with_sharding_constraint(
                    new_amps, NamedSharding(mesh, PartitionSpec(None, AMP_AXIS)))
        self.amps = new_amps

    def __repr__(self):
        kind = "density-matrix" if self.is_density_matrix else "state-vector"
        return (f"Qureg({kind}, qubits={self.num_qubits_represented}, "
                f"amps=2^{self.num_qubits_in_state_vec}, dtype={self.amps.dtype})")


def _alloc(env: QuESTEnv, num_qubits_sv: int, dtype, index: int = 0,
           func: str = "createQureg") -> jax.Array:
    num_amps = 1 << num_qubits_sv

    def alloc():
        amps = ops_init.init_classical(num_amps, jnp.dtype(dtype), index)
        sharding = env.sharding(num_amps)
        if sharding is not None:
            amps = jax.device_put(amps, sharding)
        return amps

    # allocator failures surface through the validation hook, attributed to
    # the calling API function like validateQuregAllocation (QuEST_cpu.c:1318)
    return validation.validate_qureg_allocation(alloc, func)


def createQureg(num_qubits: int, env: QuESTEnv, precision_code: int | None = None) -> Qureg:
    """State-vector register in |0...0> (createQureg, QuEST.h:579)."""
    func = "createQureg"
    validation._assert(num_qubits > 0, "Invalid number of qubits. Must create >0.", func)
    validation.validate_num_amps_fit_type(num_qubits, False, func)
    if env.requires_sharding:
        validation.validate_qureg_fits_devices(num_qubits, env.mesh.size,
                                               False, func)
    dtype = precision.real_dtype(precision_code)
    q = Qureg(num_qubits, False, _alloc(env, num_qubits, dtype, func=func), env)
    q.qasm_log = QASMLogger(num_qubits, dtype)
    return q


def createDensityQureg(num_qubits: int, env: QuESTEnv, precision_code: int | None = None) -> Qureg:
    """Density-matrix register in |0><0| (createDensityQureg, QuEST.h:673)."""
    func = "createDensityQureg"
    validation._assert(num_qubits > 0, "Invalid number of qubits. Must create >0.", func)
    validation.validate_num_amps_fit_type(num_qubits, True, func)
    if env.requires_sharding:
        validation.validate_qureg_fits_devices(num_qubits, env.mesh.size,
                                               True, func)
    dtype = precision.real_dtype(precision_code)
    q = Qureg(num_qubits, True, _alloc(env, 2 * num_qubits, dtype,
                                       func=func), env)
    q.qasm_log = QASMLogger(num_qubits, dtype)
    return q


def createCloneQureg(qureg: Qureg, env: QuESTEnv) -> Qureg:
    """Deep copy (createCloneQureg, QuEST.h:694)."""
    q = Qureg(qureg.num_qubits_represented, qureg.is_density_matrix,
              qureg.amps + 0, env)
    q.qasm_log = QASMLogger(qureg.num_qubits_represented, qureg.dtype)
    return q


def destroyQureg(qureg: Qureg, env: QuESTEnv | None = None) -> None:
    """Release the device buffer eagerly (destroyQureg, QuEST.h:716)."""
    try:
        qureg.amps.delete()
    except Exception:
        pass
    qureg.amps = None


def get_np(qureg: Qureg) -> np.ndarray:
    """Gather the full amplitude array to host as numpy complex
    (tests / reporting)."""
    from .ops import cplx
    return cplx.to_complex(qureg.amps)


# --------------------------------------------------------------------------
# Host-mirror synchronisation (copyStateToGPU/FromGPU, QuEST.h:2286-2383).
#
# The reference keeps a host planar array (qureg.stateVec) beside the device
# copy and lets users edit it directly, syncing explicitly. Here the device
# jax.Array is the state of record; ``qureg.state_vec`` is a lazily-created
# planar numpy mirror (shape (2, numAmps): real plane, imag plane) that these
# four functions sync in either direction. On CPU backends they still work --
# they are then just host<->host copies, matching the reference's no-op CPU
# definitions (QuEST_cpu_local.c) while keeping the mirror coherent.
# --------------------------------------------------------------------------

def _host_mirror(qureg: Qureg) -> np.ndarray:
    if getattr(qureg, "host_amps", None) is None:
        qureg.host_amps = np.zeros((2, qureg.num_amps_total),
                                   dtype=qureg.amps.dtype)
    return qureg.host_amps


def _validate_live(qureg: Qureg, func: str) -> None:
    validation._assert(
        qureg.amps is not None,
        "Invalid Qureg. The register has been destroyed.", func)


def copyStateFromGPU(qureg: Qureg) -> np.ndarray:
    """Pull the device state into the host mirror (copyStateFromGPU, QuEST.h:2321)."""
    _validate_live(qureg, "copyStateFromGPU")
    mirror = _host_mirror(qureg)
    mirror[...] = np.asarray(qureg.amps)
    return mirror


def copyStateToGPU(qureg: Qureg) -> None:
    """Push the host mirror to the device (copyStateToGPU, QuEST.h:2301)."""
    _validate_live(qureg, "copyStateToGPU")
    mirror = _host_mirror(qureg)
    new = jax.device_put(jnp.asarray(mirror), qureg.amps.sharding)
    qureg.put(new)


def copySubstateFromGPU(qureg: Qureg, start_ind: int, num_amps: int) -> np.ndarray:
    """Pull amplitudes [start, start+num) into the host mirror
    (copySubstateFromGPU, QuEST.h:2383)."""
    func = "copySubstateFromGPU"
    _validate_live(qureg, func)
    validation.validate_num_amps(qureg, start_ind, num_amps, func)
    mirror = _host_mirror(qureg)
    chunk = jax.lax.dynamic_slice_in_dim(qureg.amps, start_ind, num_amps, axis=1)
    mirror[:, start_ind:start_ind + num_amps] = np.asarray(chunk)
    return mirror


def copySubstateToGPU(qureg: Qureg, start_ind: int, num_amps: int) -> None:
    """Push host-mirror amplitudes [start, start+num) to the device
    (copySubstateToGPU, QuEST.h:2352)."""
    func = "copySubstateToGPU"
    _validate_live(qureg, func)
    validation.validate_num_amps(qureg, start_ind, num_amps, func)
    mirror = _host_mirror(qureg)
    patch = jnp.asarray(mirror[:, start_ind:start_ind + num_amps])
    # static-index .at[].set, not dynamic_update_slice: the indices are
    # host ints, and on a sharded operand some jaxlib releases lower the
    # dynamic form with mixed s64/s32 index clamps (hlo verifier error)
    new = qureg.amps.at[:, start_ind:start_ind + num_amps].set(patch)
    new = jax.device_put(new, qureg.amps.sharding)
    qureg.put(new)
