"""The adjoint's backward sweep by the dense plan's blocks (PR 45;
quest_tpu/gradients/adjoint.py, docs/gradients.md).

The walk undoes one block of ``fusion.plan(tape, DENSE_WINDOW_QUBITS)`` at
a time on both registers and harvests every derivative a block holds from
ONE window contraction and the VJP of the block's composition. Two oracles
that share nothing with it, and no switch back to the gate walk:

- ``jax.grad`` through the raw parameterized replay of the forward
  program (every case);
- ``benchmark/reference_grad.py``'s numpy complex128 sweep, gate by gate
  (the cases made of its gate set, from |0...0>:
  ``tests/test_grad_blocks_sweep.py``, which holds the second half of
  these cases so that two workers share them).

float64 at 1e-12 and float32 at 1e-5, over: every family of ``_FIELDS``
inside a block; a controlled rotation whose control and target one window
holds, and one that no window holds (it stays a passthrough entry and keeps
the gate walk); a Param shared by two gates of one block and by gates of
two blocks; complex slots of ``compactUnitary`` in a block; a diagonal
block with Param factors; a state-prep prefix before the first slot; and
6, 8 and 12 qubits, so that windows lie below, across and above the lane
boundary (qubit 7).
"""

import numpy as np
import pytest

import jax.numpy as jnp

import quest_tpu as qt
from quest_tpu import telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.engine import P
from quest_tpu.gradients import adjoint

from .test_gradients import _oracle

ATOL = {np.float64: 1e-12, np.float32: 1e-5}
DTYPES = [pytest.param(np.float64, id="f64"), pytest.param(np.float32,
                                                           id="f32")]

_AXIS = qt.Vector(0.3, -1.2, 0.5)
_TH = 0.83
_AL = np.cos(_TH / 2) * np.exp(0.31j)
_BE = np.sin(_TH / 2) * np.exp(-0.74j)


def _ham(n, terms=5, seed=3):
    r = np.random.RandomState(seed)
    return (r.randint(0, 4, size=(terms, n)).astype(np.int32),
            r.normal(size=terms))


def _amps(n, dtype, zero=False, seed=0):
    if zero:
        v = np.zeros(1 << n, dtype=complex)
        v[0] = 1.0
    else:
        r = np.random.RandomState(seed)
        v = r.normal(size=1 << n) + 1j * r.normal(size=1 << n)
        v /= np.linalg.norm(v)
    return jnp.asarray(np.stack([v.real, v.imag]), dtype=dtype)


def _params(circ):
    return {nm: 0.37 + 0.41 * i - 0.05 * i * i
            for i, nm in enumerate(circ.lifted().param_names)}


def _items(circ, dtype):
    """The backward walk's items: ``(blocks, blocks with Params,
    passthrough entry names)``."""
    lifted = adjoint.gatewise(circ).lifted()
    items = adjoint._plan_cached(lifted, circ.num_qubits,
                                 np.dtype(dtype).str)[2]
    blocks = [i for i in items if isinstance(i, adjoint._BlockPlan)]
    return (len(blocks), sum(1 for b in blocks if b.spec is not None),
            [i.name for i in items if not isinstance(i, adjoint._BlockPlan)])


def _check(circ, dtype, params=None, zero=False, oracle=True):
    """The block walk's value and EVERY slot's derivative (anonymous
    constants and complex slots included) against ``jax.grad``
    (``oracle=False``: the run alone, for a caller with another oracle)."""
    codes, coeffs = _ham(circ.num_qubits)
    amps = _amps(circ.num_qubits, dtype, zero=zero)
    params = _params(circ) if params is None else params
    gx = circ.gradient((codes, coeffs), donate=False, dtype=dtype)
    out = gx(amps, params)
    if not oracle:
        return out, (codes, coeffs)
    want_v, want_g = _oracle(circ, codes, coeffs, amps, gx.bind(params),
                             dtype=dtype)
    atol = ATOL[dtype]
    np.testing.assert_allclose(float(out["value"]), float(want_v),
                               atol=atol, rtol=0)
    assert len(out["slot_grads"]) == len(want_g)
    for got, want in zip(out["slot_grads"], want_g):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=atol, rtol=0)
    return out, (codes, coeffs)


# ---------------------------------------------------------------------------
# every family inside a block (8 qubits: one window [0-6], qubit 7 apart)
# ---------------------------------------------------------------------------

_FAMILIES = {
    "phaseShift": lambda c: c.phaseShift(2, P("a")),
    "controlledPhaseShift": lambda c: c.controlledPhaseShift(1, 4, P("a")),
    "multiControlledPhaseShift":
        lambda c: c.multiControlledPhaseShift([0, 3, 5], P("a")),
    "rotateX": lambda c: c.rotateX(3, P("a")),
    "rotateY": lambda c: c.rotateY(6, P("a")),
    "rotateZ": lambda c: c.rotateZ(0, P("a")),
    "rotateAroundAxis": lambda c: c.rotateAroundAxis(4, P("a"), _AXIS),
    "controlledRotateX": lambda c: c.controlledRotateX(5, 1, P("a")),
    "controlledRotateY": lambda c: c.controlledRotateY(0, 6, P("a")),
    "controlledRotateZ": lambda c: c.controlledRotateZ(2, 3, P("a")),
    "controlledRotateAroundAxis":
        lambda c: c.controlledRotateAroundAxis(6, 2, P("a"), _AXIS),
    "multiRotateZ": lambda c: c.multiRotateZ([1, 3, 6], P("a")),
    "multiControlledMultiRotateZ":
        lambda c: c.multiControlledMultiRotateZ([4], [0, 5], P("a")),
    "multiRotatePauli":
        lambda c: c.multiRotatePauli([0, 2, 5], [1, 2, 3], P("a")),
    "multiControlledMultiRotatePauli":
        lambda c: c.multiControlledMultiRotatePauli([3], [1, 6], [2, 1],
                                                    P("a")),
    "compactUnitary": lambda c: c.compactUnitary(5, _AL, _BE),
    "controlledCompactUnitary":
        lambda c: c.controlledCompactUnitary(2, 4, _AL, _BE),
}


def test_the_family_cases_are_the_rule_table_s():
    assert set(_FAMILIES) == set(adjoint._FIELDS)


def _family_circuit(family):
    c = Circuit(8)
    for q in range(8):
        c.rotateY(q, 0.3 + 0.17 * q)        # anonymous slots, differentiated
    c.controlledNot(2, 3)
    _FAMILIES[family](c)
    c.hadamard(1)
    c.controlledNot(6, 7)                   # a block across [6-7]
    c.rotateX(7, P("b"))
    return c


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_a_family_s_derivative_comes_out_of_its_block(family):
    c = _family_circuit(family)
    blocks, with_params, passed = _items(c, np.float64)
    # nothing of this tape stays outside a block: the family's derivative
    # is the contraction's and the composition's VJP, not its rule's
    assert passed == [] and with_params >= 2 and blocks >= with_params
    _check(c, np.float64)


@pytest.mark.parametrize("family", ["rotateX", "controlledRotateY",
                                    "multiRotatePauli", "phaseShift",
                                    "controlledCompactUnitary"])
def test_a_family_s_block_in_float32(family):
    _check(_family_circuit(family), np.float32)


# ---------------------------------------------------------------------------
# where a controlled rotation's qubits lie
# ---------------------------------------------------------------------------

def _controlled(n, control, target):
    c = Circuit(n)
    for q in range(n):
        c.rotateY(q, 0.2 + 0.1 * q)
    c.controlledRotateX(control, target, P("t"))
    c.rotateZ(target, P("u"))
    c.controlledNot(0, 1)
    return c


@pytest.mark.parametrize("dtype", DTYPES)
def test_a_controlled_rotation_one_window_holds(dtype):
    c = _controlled(12, 8, 11)               # both above the lane boundary
    _, with_params, passed = _items(c, dtype)
    assert passed == [] and with_params >= 2
    _check(c, dtype)


@pytest.mark.parametrize("dtype", DTYPES)
def test_a_controlled_rotation_no_window_holds_keeps_the_gate_walk(dtype):
    c = _controlled(12, 0, 11)               # a span of 12 qubits
    _, with_params, passed = _items(c, dtype)
    assert passed == ["controlledRotateX"] and with_params >= 2
    sweeps = {s: telemetry.counter_value("grad_sweep_entries_total", sweep=s)
              for s in ("backward_phi", "bracket")}
    _check(c, dtype)
    # one a rule on the passthrough entry, one a contraction elsewhere
    blocks = _items(c, dtype)[0]
    grown = {s: telemetry.counter_value("grad_sweep_entries_total", sweep=s)
             - v for s, v in sweeps.items()}
    assert grown == {"backward_phi": blocks + 1, "bracket": with_params + 1}


