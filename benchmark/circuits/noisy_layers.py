"""``random_layers``' circuit with a depolarising channel after every gate, on
the qubits the gate touched: the placement of noisy-circuit studies, with
QuEST's own channels (``QuEST.h`` ``mixDepolarising``,
``mixTwoQubitDepolarising``).

The gates are ``random_layers.build``'s, in its order and with its draws of
``RandomState(circuit_seed)``: this file records through a proxy that lets
every gate through to ``rec`` and follows it with its channel, so the two
tapes cannot part. After a one-qubit gate on ``q``: ``mixDepolarising(q,
p1)``; after ``controlledNot(q, q + 1)``: ``mixTwoQubitDepolarising(q, q + 1,
p2)``; after the long-range ``controlledPhaseFlip(0, n - 1)``:
``mixTwoQubitDepolarising(0, n - 1, p2)``. ``rec`` is the program's
``Circuit`` (of a density register) or the benchmark's ``reference.Tape``."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "circuits.random_layers",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "random_layers.py"))
random_layers = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(random_layers)

ONE_QUBIT = ("hadamard", "tGate", "rotateZ", "rotateX")
TWO_QUBIT = ("controlledNot", "controlledPhaseFlip")


class _Noisy:
    """``rec`` with every gate followed by its channel."""

    def __init__(self, rec, p1: float, p2: float):
        self.rec, self.p1, self.p2 = rec, p1, p2

    def __getattr__(self, name):
        gate = getattr(self.rec, name)
        if name in ONE_QUBIT:
            def record(q, *args):
                gate(q, *args)
                self.rec.mixDepolarising(q, self.p1)
        elif name in TWO_QUBIT:
            def record(q1, q2):
                gate(q1, q2)
                self.rec.mixTwoQubitDepolarising(q1, q2, self.p2)
        else:
            raise AttributeError(f"noisy_layers: no channel for {name!r}")
        return record


def build(rec, *, num_qubits: int, depth: int, circuit_seed: int, p1: float,
          p2: float, angle=None):
    random_layers.build(_Noisy(rec, p1, p2), num_qubits=num_qubits,
                        depth=depth, circuit_seed=circuit_seed)
