"""The block walk of the adjoint's backward half (PR 45), second half of
``tests/test_grad_blocks.py``'s cases (its docstring has the contracts; a
file of their own so that two workers share them): a Param shared inside a
block and between blocks, complex slots, a diagonal block with Param
factors, a state-prep prefix, and 6, 8 and 12 qubits against
``benchmark/reference_grad.py``'s numpy sweep as well as ``jax.grad``.
"""

import os
import sys

import numpy as np
import pytest

from quest_tpu.circuits import Circuit
from quest_tpu.engine import P
from quest_tpu.gradients import adjoint

from .test_grad_blocks import _AL, _BE, ATOL, DTYPES, _check, _items

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference  # noqa: E402
import reference_grad  # noqa: E402


# ---------------------------------------------------------------------------
# shared Params, complex slots, diagonal blocks, a state-prep prefix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", DTYPES)
def test_a_param_shared_inside_a_block_and_between_blocks(dtype):
    c = Circuit(12)
    c.hadamard(0)
    c.rotateX(0, P("a"))
    c.rotateZ(3, P("a"))                     # the same block, [0-6]
    c.rotateX(9, P("a"))                     # another block, [7-11]
    c.controlledNot(3, 4)
    c.rotateY(9, P("b"))
    c.rotateZ(4, P("b"))
    out, _ = _check(c, dtype, params={"a": 0.4, "b": -1.1})
    by_name = {}
    for s, g in zip(c.lifted().slots, out["slot_grads"]):
        by_name[s.name] = by_name.get(s.name, 0.0) + float(np.real(g))
    for name in ("a", "b"):
        np.testing.assert_allclose(float(out["grads"][name]), by_name[name],
                                   atol=10 * ATOL[dtype] ** 1.2, rtol=0)


@pytest.mark.parametrize("dtype", DTYPES)
def test_complex_slots_of_a_compact_unitary_in_a_block(dtype):
    c = Circuit(8)
    for q in range(8):
        c.rotateY(q, 0.3 + 0.17 * q)
    c.compactUnitary(2, P("al"), P("be"))
    c.controlledNot(2, 3)
    c.controlledCompactUnitary(3, 5, _BE.conjugate(), _AL)
    c.rotateX(2, P("t"))
    assert _items(c, dtype)[2] == []
    out, _ = _check(c, dtype, params={"al": _AL, "be": _BE, "t": 0.6})
    # jax.grad's convention for a complex slot: dE/dx - i dE/dy
    assert np.iscomplexobj(np.asarray(out["grads"]["al"]))
    assert abs(complex(out["grads"]["be"]).imag) > 1e-3


@pytest.mark.parametrize("dtype", DTYPES)
def test_a_diagonal_block_with_param_factors(dtype):
    c = Circuit(12)
    c.initPlusState()
    c.multiRotateZ([0, 5, 11], P("a"))       # scattered: a DiagBlock
    c.phaseShift(8, P("b"))
    c.controlledPhaseShift(2, 10, P("a"))
    c.hadamard(11)
    c.rotateX(3, P("c"))
    lifted = adjoint.gatewise(c).lifted()
    items = adjoint._plan_cached(lifted, 12, np.dtype(dtype).str)[2]
    diag = [i for i in items if isinstance(i, adjoint._BlockPlan)
            and i.kind == "diag" and i.spec is not None]
    assert diag and max(len(d.qubits) for d in diag) >= 3
    _check(c, dtype, zero=True)


@pytest.mark.parametrize("dtype", DTYPES)
def test_a_state_prep_prefix_before_the_first_slot(dtype):
    c = Circuit(8)
    c.initPlusState()
    c.controlledNot(0, 7)
    c.tGate(3)
    for q in range(8):
        c.rotateZ(q, P(f"z{q}"))
        c.rotateX(q, P(f"x{q}"))
    c.controlledPhaseFlip(0, 7)
    lifted = c.lifted()
    stop = adjoint.plan_backward(lifted, 8, dtype)[1]
    assert stop == 3                          # the walk never crosses them
    _check(c, dtype, zero=True)


# ---------------------------------------------------------------------------
# windows below, across and above the lane boundary, against the numpy sweep
# ---------------------------------------------------------------------------

def _layers(rec, angle, n, depth, straddle):
    """rotateZ / rotateX on every qubit, bricks of controlledNot, a phase
    flip a layer: the reference's gate set. ``straddle`` starts the tape on
    qubits 5-9, so the first window lies across the lane boundary."""
    rec.hadamard(0)
    if straddle:
        for q in range(5, min(n, 10)):
            rec.rotateX(q, angle(f"s{q}"))
        rec.controlledNot(6, 7)
    for layer in range(depth):
        for q in range(n):
            rec.rotateZ(q, angle(f"a{layer}_{q}"))
            rec.rotateX(q, angle(f"b{layer}_{q}"))
        for q in range(layer % 2, n - 1, 2):
            rec.controlledNot(q, q + 1)
        rec.controlledPhaseFlip(0, n - 1)
    rec.tGate(n - 1)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("n, straddle", [(6, False), (8, False), (8, True),
                                         (12, False), (12, True)])
def test_the_walk_against_the_numpy_sweep_and_jax_grad(n, straddle, dtype):
    circ = Circuit(n)
    _layers(circ, P, n, 2, straddle)
    names = list(circ.lifted().param_names)
    rng = np.random.default_rng([n, int(straddle)])
    params = dict(zip(names, map(float, rng.uniform(0, 2 * np.pi,
                                                    len(names)))))
    blocks, with_params, passed = _items(circ, dtype)
    assert passed == [] and with_params >= (1 if n == 6 else 2)
    if n == 12:
        # windows on both sides of the lane boundary, each with Params
        lifted = adjoint.gatewise(circ).lifted()
        items = adjoint._plan_cached(lifted, n, np.dtype(dtype).str)[2]
        lows = {i.qubits[0] for i in items if i.spec is not None}
        assert min(lows) < 7 <= max(lows)
    # float32 against the numpy sweep alone: jax.grad saw these tapes in
    # float64, and its trace is the dear part of a case
    out, (codes, coeffs) = _check(circ, dtype, params=params, zero=True,
                                  oracle=dtype is np.float64)
    tape = reference.Tape()
    _layers(tape, params.__getitem__, n, 2, straddle)
    want_e, want_g = reference_grad.gradient(
        tape.ops, [[int(p) for p in row] for row in codes],
        [float(x) for x in coeffs])
    assert abs(float(out["value"]) - want_e) <= ATOL[dtype]
    got = np.array([float(out["grads"][name]) for name in names])
    np.testing.assert_allclose(got, want_g, rtol=0, atol=ATOL[dtype])
