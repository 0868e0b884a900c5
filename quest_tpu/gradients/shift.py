"""Parameter-shift gradients: the independent second oracle.

Not a serving path -- 2P (or 4P) full replays per gradient where the
adjoint sweep does ~3 -- but an *analytically exact* cross-check that
shares nothing with the adjoint code beyond the forward replay: rotation
generators with eigenvalues ±1 and the phase family (unit eigenvalue gap)
obey the two-term rule

    dE/dθ = [E(θ+π/2) - E(θ-π/2)] / 2,

while controlled rotations (generator eigenvalues {-1, 0, +1}, so E mixes
frequencies θ/2 and θ) need the four-term rule

    dE/dθ = c₊[E(θ+π/2) - E(θ-π/2)] - c₋[E(θ+3π/2) - E(θ-3π/2)],
    c± = (√2 ± 1) / (4√2).

Complex (compact-unitary) slots have no shift rule -- ``jax.grad`` covers
those in the test matrix; asking for them here raises.
"""

from __future__ import annotations

import numpy as np

from ..params import _SlotRef, bind as bind_values
from ..validation import QuESTError
from .adjoint import _FIELDS, _entry_view
from .expectation import hamiltonian_terms

__all__ = ["parameter_shift"]

#: four-term rule coefficients for {-1, 0, +1} generator spectra
_C_PLUS = (np.sqrt(2.0) + 1.0) / (4.0 * np.sqrt(2.0))
_C_MINUS = (np.sqrt(2.0) - 1.0) / (4.0 * np.sqrt(2.0))

#: families whose E(θ) is a pure frequency-1 trig polynomial
_TWO_TERM = {
    "rotateX", "rotateY", "rotateZ", "rotateAroundAxis", "multiRotateZ",
    "multiRotatePauli", "phaseShift", "controlledPhaseShift",
    "multiControlledPhaseShift",
}
#: families mixing frequencies θ/2 and θ (controlled ±1 generators)
_FOUR_TERM = {
    "controlledRotateX", "controlledRotateY", "controlledRotateZ",
    "controlledRotateAroundAxis", "multiControlledMultiRotateZ",
    "multiControlledMultiRotatePauli",
}


def _slot_families(lifted):
    """slot index -> owning gate family name."""
    fam = {}
    for fn, args, kwargs in lifted.entries:
        name = getattr(fn, "__name__", str(fn))
        if name not in _FIELDS:
            continue
        for v in _entry_view(name, args, kwargs).values():
            if isinstance(v, _SlotRef):
                fam[v.index] = name
    return fam


def parameter_shift(circuit, hamiltonian, amps, params=None):
    """Full gradient of ⟨H⟩ by parameter shifts -- ``{"value", "grads",
    "slot_grads"}`` matching :func:`adjoint.grad_reduce`'s layout. Every
    shifted evaluation replays the SAME cached expectation executable with
    a perturbed values tuple (no retraces), but there are 2-4 of them per
    slot: use this as an oracle, not a serving route."""
    # lazy: reaches up (sampling/request stands on the Engine's layer) for
    # shot-based shifts
    from ..sampling.request import expectation_reduce

    codes, coeffs = hamiltonian_terms(hamiltonian, circuit.num_qubits)
    red = expectation_reduce(n=circuit.num_qubits, codes=codes,
                             coeffs=coeffs, density=circuit.is_density_matrix)
    ex = circuit.parameterized(donate=False, reduce=red)
    lifted = ex.lifted
    values = list(bind_values(lifted, params))
    fam = _slot_families(lifted)

    def energy(vals):
        return float(ex.with_values(amps, tuple(vals)))

    def shifted(idx, delta):
        vals = list(values)
        vals[idx] = np.asarray(float(vals[idx]) + delta,
                               dtype=np.asarray(vals[idx]).dtype)
        return energy(vals)

    slot_grads = []
    for s in lifted.slots:
        name = fam.get(s.index)
        if s.kind != "real" or name is None:
            raise QuESTError(
                f"parameter_shift: slot {s.index} ({s.kind}, "
                f"{name or 'unknown family'}) has no shift rule -- use "
                "jax.grad or the adjoint engine", "parameter_shift")
        if name in _TWO_TERM:
            g = (shifted(s.index, np.pi / 2)
                 - shifted(s.index, -np.pi / 2)) / 2.0
        elif name in _FOUR_TERM:
            g = (_C_PLUS * (shifted(s.index, np.pi / 2)
                            - shifted(s.index, -np.pi / 2))
                 - _C_MINUS * (shifted(s.index, 3 * np.pi / 2)
                               - shifted(s.index, -3 * np.pi / 2)))
        else:  # pragma: no cover - _FIELDS is partitioned above
            raise QuESTError(
                f"parameter_shift: no rule for family '{name}'",
                "parameter_shift")
        slot_grads.append(g)

    named = {}
    for s, g in zip(lifted.slots, slot_grads):
        if s.name is not None:
            named[s.name] = named.get(s.name, 0.0) + g
    return {"value": energy(values), "grads": named,
            "slot_grads": tuple(slot_grads)}
