"""The plain reference of a served gradient: numpy complex128, gate by gate.

Nothing here imports the program. It stands beside ``reference.py`` and uses
its ``Tape``, its gate matrices and its in-place gate application:

- :func:`energy`: E = sum_k c_k <psi|P_k|psi>, |psi> the tape applied to
  |0...0>, the Pauli strings applied term by term, each by index arithmetic;
- :func:`gradient`, (a): E and dE/dtheta for EVERY rotation of the tape by a
  plain adjoint sweep written from the equations of Jones and Gacon,
  arXiv:2009.02823 (Algorithm 1): three sweeps over the tape, a few seconds
  a request at 2^20 amplitudes;
- :func:`shift`, (b): dE/dtheta of chosen rotations by the two-term
  parameter-shift rule, [E(theta + pi/2) - E(theta - pi/2)] / 2. Every
  parameter of the served ansatz is the angle of a ``rotateZ`` or a
  ``rotateX``, exp(-i theta G / 2) with G of eigenvalues +-1, so the rule is
  exact: it is the DEFINITION of the derivative for these gates and shares no
  algorithm with the program's sweep or with (a), only the gate application.
  (a) covers every component and is tied to (b) on ALL components by tests at
  small sizes (``benchmark/tests/test_grad_cell.py``, ``tests/test_grad_serving.py``).

A parameter is a ``rotateX`` / ``rotateZ`` entry of the tape; derivatives come
in tape order, which is the builder's ``param_names`` order (each name is used
once).

Departures from the paper's Algorithm 1, each where it happens below:
(1) the paper's circuit is all parameterized gates; a tape entry without a
parameter (``controlledNot``, ``controlledPhaseFlip``) is undone on both
registers and gives no bracket. (2) The paper applies H to a copy of |psi>
with its own operator routine; here |lambda> = H|psi> is the weighted sum of
the strings' index-arithmetic applications. (3) The paper forms
|mu> = (dU/dtheta)|phi> through a generator call; here dU/dtheta is the 2x2
derivative matrix of the rotation, applied like any gate. (4) The last dagger
of |lambda> is never read and is left out. (5) (b) replays the tape once to
each shifted entry and keeps that prefix state: both shifted replays start
from it (the gates before the shifted one are the same three times over).

``lower`` is the CONTROL's hook, as in ``reference.py``: with it every gate
matrix and every written amplitude, the costate, each bracket and the energy
are rounded to the next precision below the configuration's. The benchmark's
own runs never pass it.

Conventions are QuEST's: qubit 0 is the least significant bit of a basis
index; Pauli codes are 0 I, 1 X, 2 Y, 3 Z, one a qubit, qubit 0 first.
"""

from __future__ import annotations

import numpy as np

import reference

PARAMETERIZED = ("rotateX", "rotateZ")


def hamiltonian(config: dict, num_qubits: int) -> tuple:
    """``(codes, coeffs)`` of a configuration's ``hamiltonian`` group, drawn
    as its ``draw`` says: ``RandomState(seed)``, the codes first, then the
    coefficients. The draw is over the configuration's OWN ``num_qubits``; a
    rehearsal at fewer qubits keeps each string's first columns."""
    h = config["hamiltonian"]
    rng = np.random.RandomState(h["seed"])
    codes = rng.randint(0, 4, size=(h["terms"], config["num_qubits"]))
    coeffs = rng.normal(size=h["terms"])
    return ([[int(p) for p in row[:num_qubits]] for row in codes],
            [float(c) for c in coeffs])


def zero_state(num_qubits: int) -> np.ndarray:
    psi = np.zeros(1 << num_qubits, dtype=np.complex128)
    psi[0] = 1.0
    return psi


def apply_pauli(psi, row) -> np.ndarray:
    """P|psi> for the string ``row``: with x the mask of its X and Y
    factors, z the mask of its Y and Z factors and y the number of Y's,
    (P psi)[i] = i^y (-1)^popcount(j & z) psi[j], j = i ^ x   (Y = i X Z)."""
    x = sum(1 << q for q, p in enumerate(row) if p in (1, 2))
    z = sum(1 << q for q, p in enumerate(row) if p in (2, 3))
    y = sum(1 for p in row if p == 2)
    index = np.arange(psi.size, dtype=np.int64)
    src = index ^ x
    parity = src & z
    for shift_by in (32, 16, 8, 4, 2, 1):
        parity ^= parity >> shift_by
    sign = 1.0 - 2.0 * (parity & 1)
    return (1j ** y) * sign * psi[src]


def apply_hamiltonian(psi, codes, coeffs, lower=None) -> np.ndarray:
    """H|psi> = sum_k c_k P_k|psi>, term by term."""
    lam = np.zeros_like(psi)
    for row, c in zip(codes, coeffs):
        lam += c * apply_pauli(psi, row)
        if lower is not None:
            lam = lower(lam)
    return lam


def _apply(psi, name, args, lower=None, dagger=False) -> None:
    target, matrix, controls = reference._unitary(name, args)
    if dagger:
        matrix = matrix.conj().T
    reference._apply_1q(psi, target, matrix, controls, None, 1, lower)


def _expectation(psi, codes, coeffs, lower=None) -> float:
    return float(np.vdot(psi, apply_hamiltonian(psi, codes, coeffs,
                                                lower)).real)


def energy(ops, codes, coeffs, lower=None) -> float:
    """<psi|H|psi> of the tape applied to |0...0>."""
    psi = reference.run_statevector(zero_state(len(codes[0])), ops,
                                    lower=lower, threads=1)
    return _expectation(psi, codes, coeffs, lower)


def _derivative(name, theta) -> np.ndarray:
    """dU/dtheta of ``rotateZ`` / ``rotateX`` as a 2x2 matrix."""
    c, s = np.cos(theta / 2), np.sin(theta / 2)
    if name == "rotateZ":
        return np.diag([-0.5j * np.exp(-0.5j * theta),
                        0.5j * np.exp(0.5j * theta)])
    return 0.5 * np.array([[-s, -1j * c], [-1j * c, -s]])


def gradient(ops, codes, coeffs, lower=None) -> tuple:
    """(a): ``(E, dE/dtheta)``, the derivatives as an array over the tape's
    rotations in tape order, by the adjoint sweep:

        |psi> = U_P ... U_1 |0>;  |lambda> = H|psi>;  |phi> = |psi>
        for i = P .. 1:
            |phi> <- U_i^dagger |phi>
            |mu>  <- (dU_i / dtheta_i) |phi>
            dE/dtheta_i = 2 Re <lambda|mu>
            if i > 1: |lambda> <- U_i^dagger |lambda>
    """
    phi = reference.run_statevector(zero_state(len(codes[0])), ops,
                                    lower=lower, threads=1)
    lam = apply_hamiltonian(phi, codes, coeffs, lower)
    value = float(np.vdot(phi, lam).real)
    grads = []
    for i in range(len(ops) - 1, -1, -1):
        name, args = ops[i]
        _apply(phi, name, args, lower, dagger=True)
        if name in PARAMETERIZED:
            mu = phi.copy()
            d = _derivative(name, args[1])
            reference._apply_1q(mu, args[0], d, (), None, 1, lower)
            grads.append(2.0 * float(np.vdot(lam, mu).real))
        # departure (1): an entry without a parameter is only undone;
        # departure (4): the last dagger of lambda is never read
        if i:
            _apply(lam, name, args, lower, dagger=True)
    grads = np.array(grads[::-1])
    if lower is not None:
        value, grads = float(lower(value)), lower(grads)
    return value, grads


def parameter_entries(ops) -> list:
    """Tape positions of the parameters, in tape order."""
    return [i for i, (name, _) in enumerate(ops) if name in PARAMETERIZED]


def shift(ops, codes, coeffs, which) -> np.ndarray:
    """(b): dE/dtheta of the parameters ``which`` (positions in the tape's
    parameter order, any order) by the two-term shift rule. One replay runs
    to each shifted entry in turn (departure (5)); from that prefix state the
    two shifted tapes are finished and measured."""
    entries = parameter_entries(ops)
    order = sorted(range(len(which)), key=lambda j: which[j])
    out = np.zeros(len(which))
    psi = zero_state(len(codes[0]))
    done = 0
    for j in order:
        i = entries[which[j]]
        for name, args in ops[done:i]:
            _apply(psi, name, args)
        done = i
        name, (target, theta) = ops[i]
        moved = []
        for delta in (0.5 * np.pi, -0.5 * np.pi):
            tail = [(name, (target, theta + delta))] + list(ops[i + 1:])
            moved.append(_expectation(
                reference.run_statevector(psi, tail, threads=1),
                codes, coeffs))
        out[j] = 0.5 * (moved[0] - moved[1])
    return out


def shift_picks(rng, ops, count: int) -> list:
    """A seeded pick of ``count`` parameters (positions in parameter order)
    that holds, while ``count`` allows, one ``rotateZ`` and one ``rotateX``
    of every layer: a layer is a maximal run of rotations between two
    entries without a parameter. What ``count`` leaves over is drawn from
    the rest."""
    layers, last = [], None
    k = 0
    for i, (name, _) in enumerate(ops):
        if name not in PARAMETERIZED:
            continue
        if last is None or any(ops[m][0] not in PARAMETERIZED
                               for m in range(last + 1, i)):
            layers.append({"rotateZ": [], "rotateX": []})
        layers[-1][name].append(k)
        last, k = i, k + 1
    picks = []
    for kind in PARAMETERIZED[::-1]:          # rotateZ first, as the tape
        for layer in layers:
            if layer[kind] and len(picks) < count:
                picks.append(int(rng.choice(layer[kind])))
    rest = [j for j in range(k) if j not in picks]
    extra = min(count - len(picks), len(rest))
    if extra > 0:
        picks += [int(j) for j in rng.choice(rest, size=extra, replace=False)]
    return sorted(picks)


ERRORS = ("value_err", "grad_err_max", "grad_err_l2", "shift_err_max")


def errors(got, want, which, shifted) -> dict:
    """What the check compares of one request: ``got`` and ``want`` are
    ``(E, gradient)``, ``want`` from (a); ``shifted`` is (b) on the
    components ``which``. Absolute, in E's own units, but ``grad_err_l2``,
    which is relative to the gradient's norm."""
    (got_e, got_g), (want_e, want_g) = got, want
    return dict(zip(ERRORS, map(float, (
        abs(got_e - want_e),
        np.max(np.abs(got_g - want_g)),
        np.linalg.norm(got_g - want_g) / np.linalg.norm(want_g),
        np.max(np.abs(got_g[which] - shifted))))))
