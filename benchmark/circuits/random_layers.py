"""Random Clifford+T-style layers (copy of ``__graft_entry__._random_layers``,
BASELINE.json's 20q/26q state-vector configs). ``rec`` is anything that
records ``rec.<gate>(...)`` calls: the program's ``Circuit`` or the
benchmark's own ``reference.Tape``."""

import numpy as np


def build(rec, *, num_qubits: int, depth: int, circuit_seed: int, angle=None):
    rng = np.random.RandomState(circuit_seed)
    for layer in range(depth):
        for q in range(num_qubits):
            k = rng.randint(4)
            if k == 0:
                rec.hadamard(q)
            elif k == 1:
                rec.tGate(q)
            elif k == 2:
                rec.rotateZ(q, float(rng.uniform(0, 2 * np.pi)))
            else:
                rec.rotateX(q, float(rng.uniform(0, 2 * np.pi)))
        for q in range(layer % 2, num_qubits - 1, 2):
            rec.controlledNot(q, q + 1)
        # a long-range entangler, so the highest qubit is exercised
        rec.controlledPhaseFlip(0, num_qubits - 1)
