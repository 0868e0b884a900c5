"""Shared test utilities: state injection/extraction and comparison.

Mirrors the reference's toQVector/toQMatrix + areEqual machinery
(tests/utilities.cpp:965-1259) in numpy terms.
"""

from __future__ import annotations

import numpy as np

import quest_tpu as qt

#: default register size, as the reference's NUM_QUBITS (tests/utilities.hpp:37)
NUM_QUBITS = 5

#: comparison tolerance; reference uses REAL_EPS-scaled margins
#: (QuEST_precision.h:48,63 -- 1e-5 single, 1e-13 double; widened for
#: accumulation over deep test circuits)
from quest_tpu.precision import default_precision
TOL = 1e-10 if default_precision() == 2 else 2e-4


def pallas_runs(circuit) -> list:
    """The PallasRuns a fused circuit's tape carries, in order, read through
    the one decoder (``fusion.plan_from_tape``)."""
    from quest_tpu import fusion, planner
    return [i for i in fusion.plan_from_tape(circuit._tape).items
            if isinstance(i, planner.PallasRun)]


def shape_register(n: int, dtype, sharding=None):
    """A state-vector register of shapes only (no amplitudes anywhere):
    all that ``fusion._route`` reads of one."""
    import jax
    return qt.Qureg(n, False, jax.ShapeDtypeStruct(
        (2, 1 << n), np.dtype(dtype), sharding=sharding), env=None)


def get_statevec(qureg) -> np.ndarray:
    return qt.get_np(qureg)


def get_density(qureg) -> np.ndarray:
    """rho as a (2^n, 2^n) matrix; flat layout is [col, row] so transpose."""
    n = qureg.num_qubits_represented
    return qt.get_np(qureg).reshape(1 << n, 1 << n).T


def set_statevec(qureg, vec: np.ndarray) -> None:
    qt.initStateFromAmps(qureg, np.real(vec), np.imag(vec))


def set_density(qureg, rho: np.ndarray) -> None:
    flat = rho.T.reshape(-1)  # [col, row] flattening
    import jax.numpy as jnp
    qureg.put(jnp.asarray(np.stack([flat.real, flat.imag]), dtype=qureg.dtype))


def assert_amps_close(got, ref, tol: float = TOL):
    """Amplitude comparison at the STATE's scale: atol = tol * max|ref|.
    Debug-state amps are unnormalised (up to ~2^n/16), and the f32
    kernels' absolute error scales with the row magnitude (bf16x3 zone
    dots), so per-element rtol on near-zero elements is the wrong
    criterion -- physical states are normalised, where the two coincide.
    """
    got = np.asarray(got)
    ref = np.asarray(ref)
    np.testing.assert_allclose(got, ref, rtol=tol,
                               atol=tol * max(np.abs(ref).max(), 1.0))


def assert_statevec_equal(qureg, ref: np.ndarray, tol: float = TOL):
    got = get_statevec(qureg)
    assert np.allclose(got, ref, atol=tol), (
        f"statevector mismatch: max|diff|={np.abs(got - ref).max():.3e}")


def assert_density_equal(qureg, ref: np.ndarray, tol: float = TOL):
    got = get_density(qureg)
    assert np.allclose(got, ref, atol=tol), (
        f"density mismatch: max|diff|={np.abs(got - ref).max():.3e}")


def debug_state_and_ref(qureg):
    """initDebugState the register and return the matching reference state
    (vector, or [col,row]->matrix for densities). Guards against the
    all-zero-agreement trap like assertQuregAndRefInDebugState
    (tests/utilities.hpp:79-97)."""
    from . import oracle
    qt.initDebugState(qureg)
    amps = oracle.debug_statevec(qureg.num_amps_total)
    assert abs(amps[1] - (0.2 + 0.3j)) < 1e-12
    if qureg.is_density_matrix:
        n = qureg.num_qubits_represented
        return amps.reshape(1 << n, 1 << n).T
    return amps
