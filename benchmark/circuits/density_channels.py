"""The 11-op decoherence circuit of BASELINE.json configs[3] (copy of
``bench._density_circuit(n, with_krausn=True)``): Hadamards and CNOTs, then
depolarising, a one-qubit Kraus map, two-qubit dephasing and a three-qubit
Kraus map. It touches qubits 0-4 and ``num_qubits - 1`` only."""

import numpy as np


def build(rec, *, num_qubits: int, angle=None):
    k = 1 / np.sqrt(2)
    x = np.array([[0, 1], [1, 0]])
    for q in range(4):
        rec.hadamard(q)
    rec.controlledNot(0, 1)
    rec.controlledNot(2, 3)
    rec.mixDepolarising(0, 0.05)
    rec.mixDepolarising(num_qubits - 1, 0.05)
    rec.mixKrausMap(1, [np.array([[k, 0], [0, k]]), np.array([[0, k], [k, 0]])])
    rec.mixTwoQubitDephasing(0, 1, 0.1)
    xxx = np.kron(np.kron(x, x), x)
    rec.mixMultiQubitKrausMap([2, 3, 4], [0.8 * xxx, 0.6j * np.eye(8)])
