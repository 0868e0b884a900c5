"""The serve_20q VQE-style ansatz (copy of ``bench.serving_ansatz``): every
rotation angle is ``angle(name)`` -- a runtime ``Param`` for the program, the
request's float for the reference."""


def param_names(*, num_qubits: int, depth: int) -> list:
    return [f"{ab}{layer}_{q}" for layer in range(depth)
            for q in range(num_qubits) for ab in "ab"]


def build(rec, *, num_qubits: int, depth: int, angle):
    for layer in range(depth):
        for q in range(num_qubits):
            rec.rotateZ(q, angle(f"a{layer}_{q}"))
            rec.rotateX(q, angle(f"b{layer}_{q}"))
        for q in range(layer % 2, num_qubits - 1, 2):
            rec.controlledNot(q, q + 1)
        rec.controlledPhaseFlip(0, num_qubits - 1)
