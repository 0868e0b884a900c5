"""Calculations: probabilities, inner products, expectation values
(reference QuEST.h:2404-2516, 3544-3799, 4247-4917; kernels in ops.reduce).
"""

from __future__ import annotations

from functools import partial

import numpy as np

from . import validation as V
from .datatypes import PauliHamil, pauliOpType
from .ops import measure as M, reduce as R
from .registers import Qureg, createCloneQureg, get_np

__all__ = [
    "calcTotalProb", "calcProbOfOutcome", "calcProbOfAllOutcomes",
    "calcInnerProduct", "calcDensityInnerProduct", "calcPurity", "calcFidelity",
    "calcHilbertSchmidtDistance", "calcExpecPauliProd", "calcExpecPauliSum",
    "calcExpecPauliHamil", "calcGradExpecPauliSum", "getAmp", "getRealAmp",
    "getImagAmp", "getProbAmp", "getDensityAmp",
]


def calcTotalProb(qureg: Qureg) -> float:
    """sum |amp|^2 (state-vector) or Re tr(rho) (density) (QuEST.h:2516)."""
    if qureg.is_density_matrix:
        return float(R.total_prob_density(qureg.amps, n=qureg.num_qubits_represented))
    return float(R.total_prob_statevec(qureg.amps))


def calcProbOfOutcome(qureg: Qureg, target: int, outcome: int) -> float:
    """Probability of measuring ``outcome`` on ``measureQubit`` (QuEST.h:276)."""
    func = "calcProbOfOutcome"
    V.validate_target(qureg, target, func)
    V.validate_outcome(outcome, func)
    if qureg.is_density_matrix:
        return float(M.density_prob_of_outcome(
            qureg.amps, n=qureg.num_qubits_represented, target=target, outcome=outcome))
    return float(M.prob_of_outcome(
        qureg.amps, n=qureg.num_qubits_in_state_vec, target=target, outcome=outcome))


def calcProbOfAllOutcomes(qureg: Qureg, targets) -> np.ndarray:
    """2^t outcome distribution; targets[0] is the outcome's least-significant
    bit (QuEST.h:3633)."""
    func = "calcProbOfAllOutcomes"
    V.validate_multi_targets(qureg, targets, func)
    if qureg.is_density_matrix:
        p = M.density_prob_of_all_outcomes(
            qureg.amps, n=qureg.num_qubits_represented, targets=tuple(targets))
    else:
        p = M.prob_of_all_outcomes(
            qureg.amps, n=qureg.num_qubits_in_state_vec, targets=tuple(targets))
    return np.asarray(p)


def calcInnerProduct(bra: Qureg, ket: Qureg) -> complex:
    """<bra|ket> (QuEST.h:3746)."""
    func = "calcInnerProduct"
    V.validate_state_vec(bra, func)
    V.validate_state_vec(ket, func)
    V.validate_matching_qureg_dims(bra, ket, func)
    re, im = R.inner_product(bra.amps, ket.amps)
    return complex(float(re), float(im))


def calcDensityInnerProduct(rho1: Qureg, rho2: Qureg) -> float:
    """Re Tr(rho1^dag rho2) (QuEST.h:3799)."""
    func = "calcDensityInnerProduct"
    V.validate_density_matr(rho1, func)
    V.validate_density_matr(rho2, func)
    V.validate_matching_qureg_dims(rho1, rho2, func)
    return float(R.density_inner_product(rho1.amps, rho2.amps))


def calcPurity(qureg: Qureg) -> float:
    """Tr(rho^2) (QuEST.h:4247)."""
    V.validate_density_matr(qureg, "calcPurity")
    return float(R.purity_density(qureg.amps))


def calcFidelity(qureg: Qureg, pure_state: Qureg) -> float:
    """|<psi|phi>|^2 or <psi|rho|psi> (QuEST.h:4283)."""
    func = "calcFidelity"
    V.validate_second_qureg_state_vec(pure_state, func)
    V.validate_matching_qureg_dims(qureg, pure_state, func)
    if qureg.is_density_matrix:
        return float(R.density_fidelity(qureg.amps, pure_state.amps,
                                        n=qureg.num_qubits_represented))
    re, im = R.inner_product(qureg.amps, pure_state.amps)
    return float(re) ** 2 + float(im) ** 2


def calcHilbertSchmidtDistance(a: Qureg, b: Qureg) -> float:
    """sqrt(sum |a-b|^2) (QuEST.h:5663)."""
    func = "calcHilbertSchmidtDistance"
    V.validate_density_matr(a, func)
    V.validate_density_matr(b, func)
    V.validate_matching_qureg_dims(a, b, func)
    return float(R.hilbert_schmidt_distance(a.amps, b.amps))


# ---------------------------------------------------------------------------
# Pauli expectation values (logic: QuEST_common.c:491-555)
# ---------------------------------------------------------------------------

def _apply_pauli_prod(workspace: Qureg, targets, codes) -> None:
    """Apply a product of Paulis gate-wise to the workspace (the clone-based
    scheme of statevec_calcExpecPauliProd, QuEST_common.c:505-518). Note the
    workspace is treated as a plain 2N-amplitude vector even for density
    matrices (no shadow op), matching the reference."""
    from . import matrices
    from .ops import apply as K, cplx, diagonal as D
    nsv = workspace.num_qubits_in_state_vec
    dt = workspace.dtype
    amps = workspace.amps
    for t, c in zip(targets, codes):
        c = int(c)
        if c == 0:
            continue
        if c == 1:
            amps = K.apply_x_class(amps, n=nsv, targets=(int(t),))
        elif c == 2:
            amps = K.apply_matrix(amps, cplx.from_complex(matrices.PAULI_Y_M, dt),
                                  n=nsv, targets=(int(t),))
        else:
            amps = D.apply_diagonal(amps, cplx.from_complex(np.array([1.0, -1.0]), dt),
                                    n=nsv, targets=(int(t),))
    workspace.put(amps)


def calcExpecPauliProd(qureg: Qureg, targets, paulis, workspace: Qureg) -> float:
    """<qureg| P |qureg> (QuEST.h:4777). The workspace is clobbered with
    P|qureg>, matching the reference's contract."""
    func = "calcExpecPauliProd"
    V.validate_multi_targets(qureg, targets, func)
    V.validate_num_pauli_codes(paulis, len(targets), func)
    V.validate_matching_qureg_types(qureg, workspace, func)
    V.validate_matching_qureg_dims(qureg, workspace, func)
    workspace.put(qureg.amps + 0)
    _apply_pauli_prod(workspace, targets, paulis)
    if qureg.is_density_matrix:
        # Tr(P rho): the reference takes densmatr_calcTotalProb of P.rho
        return float(R.total_prob_density(workspace.amps, n=qureg.num_qubits_represented))
    return float(R.inner_product(qureg.amps, workspace.amps)[0])


def _pauli_prod_amps(amps, term, nsv, dt):
    """P|amps> for one static code tuple (inlined under jit)."""
    from . import matrices
    from .ops import apply as K, cplx, diagonal as D
    for t, c in enumerate(term):
        if c == 0:
            continue
        if c == 1:
            amps = K.apply_x_class(amps, n=nsv, targets=(t,))
        elif c == 2:
            amps = K.apply_matrix(amps, cplx.from_complex(matrices.PAULI_Y_M, dt),
                                  n=nsv, targets=(t,))
        else:
            amps = D.apply_diagonal(amps, cplx.from_complex(np.array([1.0, -1.0]), dt),
                                    n=nsv, targets=(t,))
    return amps


#: terms per compiled block in _expec_pauli_sum_fused: each term unrolls an
#: O(n)-op Pauli pipeline into the program, so program size (and compile
#: time) grows linearly with terms -- the same compile-limit failure mode
#: Circuit.compiled_segments bounds. 64 terms x ~n ops stays well under
#: XLA limits.
_EXPEC_TERM_BLOCK = 64


def _expec_pauli_sum_fused(amps, coeffs, *, codes, n, density):
    """sum_t c_t <P_t>, fused into one XLA program per 64-term block.

    The reference pays a full state clone, O(n) kernel launches, and an
    Allreduce per term (QuEST_common.c:505-532); here the term loop unrolls
    at trace time so XLA schedules every term's Pauli pipeline and reduction
    inside a single dispatch (SURVEY.md section 3.5's noted fusion win).
    Hamiltonians beyond _EXPEC_TERM_BLOCK terms chain a few block-sized
    executables instead of growing one unbounded program."""
    total = 0.0
    for i in range(0, len(codes), _EXPEC_TERM_BLOCK):
        block = codes[i:i + _EXPEC_TERM_BLOCK]
        total = total + _expec_pauli_sum_run(
            amps, coeffs[i:i + _EXPEC_TERM_BLOCK], codes=block, n=n,
            density=density)
    return total


def expec_pauli_sum_amps(amps, coeffs, *, codes, n, density):
    """sum_t c_t <P_t> as a TRACEABLE function of the planar amps: the
    body of the fused expectation, exposed (round 19) so the sampling
    request path can lower calcExpecPauliSum into a request executable's
    terminal ``reduce(amps)`` stage -- circuit + shots + expectation as
    one dispatched program. ``codes`` is a static tuple of code tuples;
    term unrolling happens at trace time exactly as under the jitted
    eager entry."""
    nsv = (2 if density else 1) * n
    total = 0.0
    for t, term in enumerate(codes):
        work = _pauli_prod_amps(amps, term, nsv, amps.dtype)
        if density:
            val = R.total_prob_density(work, n=n)
        else:
            val = R.inner_product(amps, work)[0]
        total = total + coeffs[t] * val
    return total


def _make_expec_pauli_sum_run():
    import jax

    @partial(jax.jit, static_argnames=("codes", "n", "density"))
    def run(amps, coeffs, *, codes, n, density):
        return expec_pauli_sum_amps(amps, coeffs, codes=codes, n=n,
                                    density=density)

    return run


_expec_pauli_sum_run = _make_expec_pauli_sum_run()


def calcExpecPauliSum(qureg: Qureg, all_pauli_codes, term_coeffs, workspace: Qureg) -> float:
    """sum_t c_t <P_t> (QuEST.h:4832). Reference semantics (the workspace is
    scratch with unspecified final state), but fused: one compiled program
    for the whole sum instead of the reference's clone + launches + reduce
    per term (QuEST_common.c:520-532)."""
    func = "calcExpecPauliSum"
    codes = np.asarray(all_pauli_codes, dtype=np.int32).reshape(len(term_coeffs), -1)
    V._assert(codes.size == len(term_coeffs) * qureg.num_qubits_represented,
              "Invalid number of Pauli codes. The number of codes must equal numQubits * numSumTerms.",
              func)
    V.validate_pauli_codes(codes.ravel(), func)
    V.validate_matching_qureg_types(qureg, workspace, func)
    V.validate_matching_qureg_dims(qureg, workspace, func)
    import jax.numpy as jnp
    coeffs = jnp.asarray(np.asarray(term_coeffs, dtype=np.float64), dtype=qureg.dtype)
    total = _expec_pauli_sum_fused(
        qureg.amps, coeffs,
        codes=tuple(tuple(int(c) for c in row) for row in codes),
        n=qureg.num_qubits_represented, density=qureg.is_density_matrix)
    return float(total)


def calcExpecPauliHamil(qureg: Qureg, hamil: PauliHamil, workspace: Qureg) -> float:
    """(QuEST.h:4873)."""
    func = "calcExpecPauliHamil"
    V.validate_pauli_hamil(hamil, func)
    V.validate_hamil_matches_qureg(qureg, hamil, func)
    return calcExpecPauliSum(qureg, hamil.pauli_codes, hamil.term_coeffs, workspace)


def calcGradExpecPauliSum(qureg: Qureg, circuit, all_pauli_codes,
                          term_coeffs, params=None):
    """Value and parameter gradients of ``sum_t c_t <P_t>`` after applying
    ``circuit`` to ``qureg``'s current state, by the adjoint-state method
    (quest_tpu/gradients, docs/gradients.md): one forward sweep, one
    Hamiltonian application, one backward sweep -- versus 2P full replays
    for parameter-shift. ``qureg`` is read, never written. Returns
    ``(value, grads)`` with ``grads`` a name -> float dict over the
    circuit's named :class:`~quest_tpu.engine.P` parameters. This is the
    one-shot convenience; the serving route is :meth:`Engine.submit_grad`
    / :meth:`EnginePool.submit_grad` over the same executable."""
    from .gradients import gradient_executable

    func = "calcGradExpecPauliSum"
    V._assert(not qureg.is_density_matrix,
              "calcGradExpecPauliSum needs a state-vector register (the "
              "adjoint sweep differentiates pure states).", func)
    out = gradient_executable(circuit, (all_pauli_codes, term_coeffs),
                              donate=False)(qureg.amps, params)
    return float(out["value"]), {k: float(v) for k, v in
                                 out["grads"].items()}


# ---------------------------------------------------------------------------
# amplitude getters (QuEST.h:2404-2489)
# ---------------------------------------------------------------------------

def getAmp(qureg: Qureg, index: int) -> complex:
    """One statevector amplitude as a complex (QuEST.h:286)."""
    func = "getAmp"
    V.validate_state_vec(qureg, func)
    V.validate_amp_index(qureg, index, func)
    return complex(float(qureg.amps[0, index]), float(qureg.amps[1, index]))


def getRealAmp(qureg: Qureg, index: int) -> float:
    """Real part of one statevector amplitude (QuEST.h:287)."""
    return getAmp(qureg, index).real


def getImagAmp(qureg: Qureg, index: int) -> float:
    """Imaginary part of one statevector amplitude (QuEST.h:288)."""
    return getAmp(qureg, index).imag


def getProbAmp(qureg: Qureg, index: int) -> float:
    """|amp|^2 of one statevector amplitude (QuEST.h:289)."""
    a = getAmp(qureg, index)
    return a.real * a.real + a.imag * a.imag


def getDensityAmp(qureg: Qureg, row: int, col: int) -> complex:
    """rho[row, col] (QuEST.h:2489); flat index col*2^N + row."""
    func = "getDensityAmp"
    V.validate_density_matr(qureg, func)
    dim = 1 << qureg.num_qubits_represented
    V._assert(0 <= row < dim and 0 <= col < dim,
              "Invalid amplitude index. Note amplitudes are zero indexed.", func)
    i = col * dim + row
    return complex(float(qureg.amps[0, i]), float(qureg.amps[1, i]))
