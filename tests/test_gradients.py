"""Adjoint-mode gradient engine (quest_tpu/gradients/, docs/gradients.md).

Contracts under test:

- the adjoint sweep's value and per-slot gradients match ``jax.grad``
  through the raw parameterized replay (f64 atol 1e-12, f32 atol 1e-5)
  for EVERY rotation / phase / compact-unitary family, controlled
  variants included, and for shared-slot (chain-rule) tapes;
- parameter-shift (quest_tpu/gradients/shift.py) is an independent
  second oracle: two-term and four-term rules agree with the adjoint
  gradients to 1e-8;
- the forward value is BIT-IDENTICAL between the unsharded route and
  the 8-device explicit-scheduler route (fixed chunked reduction
  order), and sharded gradients match to f64 tolerance;
- a warm ``Engine.submit_grad`` loop performs ZERO retraces
  (``engine_trace_total``) across 10 steps and lowers to ONE
  ``route=grad_request`` dispatch per coalesced batch;
- non-differentiable tapes (measurement / trajectory sites, density
  registers, slot-free tapes) raise typed ``QuESTError`` at lift time
  naming the offending site, and tapelint QT006 flags the same sites
  with the sample_request composition hint.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import quest_tpu as qt
from quest_tpu import telemetry
from quest_tpu.calculations import expec_pauli_sum_amps
from quest_tpu.circuits import Circuit
from quest_tpu.engine import Engine, EnginePool, P
from quest_tpu.gradients import (
    check_differentiable, gradient_executable, parameter_shift,
)
from quest_tpu.validation import QuESTError

ENV1 = qt.createQuESTEnv(jax.devices()[:1])
ENV8 = qt.createQuESTEnv(jax.devices()[:8])

needs_mesh = pytest.mark.skipif(
    ENV8.mesh is None or ENV8.mesh.size < 8,
    reason="needs the 8-device host mesh")

#: compact-unitary test point: a generic (alpha, beta) on the unit sphere
_TH = 0.83
_AL = np.cos(_TH / 2) * np.exp(0.31j)
_BE = np.sin(_TH / 2) * np.exp(-0.74j)

_AXIS = qt.Vector(0.3, -1.2, 0.5)


def _ham(n, terms=4, seed=1):
    r = np.random.RandomState(seed)
    return (r.randint(0, 4, size=(terms, n)).astype(np.int32),
            r.normal(size=terms))


def _amps(n, seed=0, dtype=np.float64):
    """A generic normalized random state as stacked (re, im) planes."""
    r = np.random.RandomState(seed)
    v = r.normal(size=(1 << n,)) + 1j * r.normal(size=(1 << n,))
    v /= np.linalg.norm(v)
    return jnp.asarray(np.stack([v.real, v.imag]), dtype=dtype)


def _prefix(c):
    """Generic non-degenerate single-qubit prefix (no vanishing grads)."""
    for q in range(c.num_qubits):
        c.rotateY(q, 0.3 + 0.17 * q)


def _bind_defaults(circ, params):
    params = dict(params or {})
    for i, nm in enumerate(circ.lifted().param_names):
        params.setdefault(nm, 0.37 + 0.41 * i)
    return params


def _oracle(circ, codes, coeffs, amps, values, dtype=np.float64):
    """(value, slot grads) via jax.grad through the raw replay. The
    replay's eager kernels donate their input buffer, so the value
    function is jitted end-to-end and rebuilds amps from a host copy."""
    lifted = circ.lifted()
    replay = circ._replay_fn(lifted)
    cf = jnp.asarray(np.asarray(coeffs), dtype=dtype)
    codes_t = tuple(tuple(int(x) for x in row) for row in codes)
    amps_np = np.asarray(amps)
    n = circ.num_qubits

    @jax.jit
    def value_fn(vals):
        psi = replay(jnp.asarray(amps_np, dtype=dtype), vals)
        return expec_pauli_sum_amps(psi, cf, codes=codes_t, n=n,
                                    density=False)

    jvals = tuple(jnp.asarray(v) for v in values)
    # one program for the value and its gradient: two compiles a case
    # were a third of this file's and tests/test_grad_blocks*.py's time
    return jax.value_and_grad(value_fn)(jvals)


def _check_adjoint(circ, params=None, atol=1e-12, dtype=np.float64,
                   seed=0):
    codes, coeffs = _ham(circ.num_qubits)
    amps = _amps(circ.num_qubits, seed=seed, dtype=dtype)
    params = _bind_defaults(circ, params)
    gx = circ.gradient((codes, coeffs), donate=False, dtype=dtype)
    out = gx(amps, params)
    ref_val, ref_grads = _oracle(circ, codes, coeffs, amps,
                                 gx.bind(params), dtype=dtype)
    np.testing.assert_allclose(float(out["value"]), float(ref_val),
                               atol=atol, rtol=0)
    for g, rg in zip(out["slot_grads"], ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(rg),
                                   atol=atol, rtol=0)
    return out


# ---------------------------------------------------------------------------
# adjoint vs jax.grad: the family matrix (6 qubits, f64)
# ---------------------------------------------------------------------------

_FAMILIES = {
    "rotateX": lambda c: c.rotateX(0, P("a")),
    "rotateY_const": lambda c: c.rotateY(1, 0.37),
    "rotateZ": lambda c: c.rotateZ(2, P("a")),
    "phaseShift": lambda c: c.phaseShift(0, P("a")),
    "controlledPhaseShift": lambda c: c.controlledPhaseShift(0, 1, P("a")),
    "multiControlledPhaseShift":
        lambda c: c.multiControlledPhaseShift([0, 1, 2], P("a")),
    "controlledRotateX": lambda c: c.controlledRotateX(0, 1, P("a")),
    "controlledRotateY": lambda c: c.controlledRotateY(0, 2, P("a")),
    "controlledRotateZ": lambda c: c.controlledRotateZ(0, 1, P("a")),
    "rotateAroundAxis": lambda c: c.rotateAroundAxis(1, P("a"), _AXIS),
    "controlledRotateAroundAxis":
        lambda c: c.controlledRotateAroundAxis(0, 1, P("a"), _AXIS),
    "multiRotateZ": lambda c: c.multiRotateZ([0, 2], P("a")),
    "multiControlledMultiRotateZ":
        lambda c: c.multiControlledMultiRotateZ([0], [1, 2], P("a")),
    "multiRotatePauli": lambda c: c.multiRotatePauli([0, 1], [1, 2], P("a")),
    "multiRotatePauli_identity":
        lambda c: c.multiRotatePauli([0, 1], [0, 0], P("a")),
    "multiControlledMultiRotatePauli":
        lambda c: c.multiControlledMultiRotatePauli([0], [1, 2], [3, 1],
                                                    P("a")),
    "compactUnitary": lambda c: c.compactUnitary(1, _AL, _BE),
    "controlledCompactUnitary":
        lambda c: c.controlledCompactUnitary(0, 1, _AL, _BE),
}


#: one representative per derivative-rule class stays in the fast lane
#: (plain rotation, controlled rotation, phase, parity-word, compact);
#: the rest of the matrix runs under -m slow
_FAST_FAMILIES = {"rotateX", "controlledRotateY", "phaseShift",
                  "multiRotatePauli", "compactUnitary"}


@pytest.mark.parametrize("family", [
    pytest.param(f, marks=() if f in _FAST_FAMILIES
                 else (pytest.mark.slow,))
    for f in sorted(_FAMILIES)])
def test_adjoint_matches_jax_grad_family(family):
    c = Circuit(6)
    _prefix(c)
    _FAMILIES[family](c)
    _check_adjoint(c)


def test_adjoint_shared_slot_chain_rule():
    """One named Param feeding several gates: slot gradients accumulate
    into the name exactly as the chain rule demands, concrete gates
    interleaved and a post-slot tail crossed by the backward sweep."""
    c = Circuit(6)
    c.hadamard(0)
    c.rotateX(0, P("a"))
    c.controlledNot(0, 1)
    c.rotateZ(1, P("a"))
    c.tGate(2)
    c.rotateY(2, P("b"))
    c.swapGate(0, 2)
    c.sGate(1)
    out = _check_adjoint(c, params={"a": 0.4, "b": -1.1})
    lifted = c.lifted()
    by_name = {}
    for s, g in zip(lifted.slots, out["slot_grads"]):
        if s.name is not None:
            by_name[s.name] = by_name.get(s.name, 0.0) + float(np.real(g))
    np.testing.assert_allclose(float(out["grads"]["a"]), by_name["a"],
                               atol=1e-14, rtol=0)


def test_adjoint_deep_mixed_12q():
    """Every family at once on a 12-qubit register (the ISSUE's 6..12q
    band upper edge), f64 atol 1e-12 against jax.grad."""
    c = Circuit(12)
    _prefix(c)
    c.rotateX(0, P("t0"))
    c.controlledRotateY(0, 5, P("t1"))
    c.multiRotateZ([1, 7], P("t2"))
    c.phaseShift(11, P("t3"))
    c.controlledNot(1, 2)
    c.compactUnitary(9, _AL, _BE)
    c.multiControlledMultiRotatePauli([0], [4, 11], [2, 3], P("t4"))
    c.controlledPhaseShift(2, 3, P("t5"))
    c.rotateAroundAxis(6, P("t6"), _AXIS)
    _check_adjoint(
        c, params={f"t{i}": 0.1 * (i + 1) * (-1) ** i for i in range(7)})


def test_adjoint_f32():
    c = Circuit(6)
    _prefix(c)
    c.rotateX(0, P("a"))
    c.controlledRotateZ(0, 3, P("b"))
    c.multiRotatePauli([1, 4], [1, 3], P("c"))
    _check_adjoint(c, atol=1e-5, dtype=np.float32)


def _mixed_6q():
    """The cross-route reference circuit: every family class, concrete
    gates interleaved, shared slots, qubits on both sides of the 8-device
    shard boundary."""
    c = Circuit(6)
    _prefix(c)
    c.rotateX(0, P("a"))
    c.controlledNot(0, 1)
    c.controlledRotateY(1, 2, P("b"))
    c.multiRotateZ([2, 3], P("a"))
    c.compactUnitary(4, np.cos(0.4) * np.exp(0.2j),
                     np.sin(0.4) * np.exp(-0.5j))
    c.controlledPhaseShift(4, 5, P("c"))
    c.swapGate(0, 5)
    c.rotateZ(5, P("b"))
    c.hadamard(3)
    return c


_MIXED_HAM = (np.array([[3, 3, 0, 0, 0, 0], [1, 0, 2, 0, 0, 1],
                        [0, 0, 0, 3, 1, 0], [3, 0, 0, 0, 0, 3]], np.int32),
              [0.7, -0.4, 1.1, 0.25])
_MIXED_PARAMS = {"a": 0.31, "b": -0.9, "c": 1.7}


def _zero_amps(n):
    v = np.zeros((2, 1 << n))
    v[0, 0] = 1.0
    return jnp.asarray(v, dtype=jnp.float64)


def test_adjoint_fused_circuit():
    """Gradients ride the fused route: dense blocks recorded by
    Circuit.fused are daggered via fusion.event_dagger, and the forward
    value is bit-identical to the unfused adjoint program's."""
    out_raw = _mixed_6q().gradient(_MIXED_HAM, donate=False)(
        _zero_amps(6), _MIXED_PARAMS)
    out_fz = _mixed_6q().fused(max_qubits=3).gradient(
        _MIXED_HAM, donate=False)(_zero_amps(6), _MIXED_PARAMS)
    assert float(out_raw["value"]) == float(out_fz["value"])
    for k in out_raw["grads"]:
        np.testing.assert_allclose(float(out_fz["grads"][k]),
                                   float(out_raw["grads"][k]),
                                   atol=1e-12, rtol=0)


# ---------------------------------------------------------------------------
# parameter-shift: the independent second oracle
# ---------------------------------------------------------------------------

def test_parameter_shift_agrees_with_adjoint():
    """Two-term (uncontrolled rotation + phase) and four-term (controlled
    rotation) shift rules against the adjoint sweep, shared slots
    included -- two derivations that share only the forward replay."""
    c = Circuit(6)
    _prefix(c)
    c.rotateX(0, P("a"))
    c.controlledRotateY(0, 1, P("b"))
    c.multiRotateZ([2, 4], P("a"))
    c.phaseShift(5, P("c"))
    c.multiControlledMultiRotateZ([0], [3, 5], P("b"))
    codes, coeffs = _ham(6)
    params = {"a": 0.4, "b": -1.1, "c": 0.9}
    amps = _amps(6)
    out = c.gradient((codes, coeffs), donate=False)(amps, params)
    ps = parameter_shift(c, (codes, coeffs), _amps(6), params)
    np.testing.assert_allclose(float(out["value"]), ps["value"],
                               atol=1e-12, rtol=0)
    for k in out["grads"]:
        np.testing.assert_allclose(float(out["grads"][k]), ps["grads"][k],
                                   atol=1e-8, rtol=0)


def test_parameter_shift_rejects_complex_slots():
    c = Circuit(3)
    c.hadamard(0)
    c.compactUnitary(1, _AL, _BE)
    with pytest.raises(QuESTError, match="no shift rule"):
        parameter_shift(c, _ham(3), _amps(3))


# ---------------------------------------------------------------------------
# sharded route: bit-identical forward value, matching gradients
# ---------------------------------------------------------------------------

@needs_mesh
def test_sharded_forward_value_bit_identical():
    """The gradient program dispatched on the 8-device explicit-scheduler
    route returns the SAME value bits as the unsharded route, and
    gradients to f64 tolerance."""
    out1 = _mixed_6q().gradient(_MIXED_HAM, donate=False)(
        _zero_amps(6), _MIXED_PARAMS)
    with qt.explicit_mesh(ENV8.mesh):
        q8 = qt.createQureg(6, ENV8)
        out8 = _mixed_6q().gradient(_MIXED_HAM, donate=False)(
            q8.amps, _MIXED_PARAMS)
    assert float(out1["value"]) == float(out8["value"])
    for k in out1["grads"]:
        np.testing.assert_allclose(float(out8["grads"][k]),
                                   float(out1["grads"][k]),
                                   atol=1e-12, rtol=0)


@needs_mesh
def test_expectation_reduce_order_is_layout_independent():
    """The fixed chunked-scan reduction gives the exact same bits for
    ANY operand bits, sharded or not -- the contract that makes the
    forward value layout-independent wherever the replay kernels are."""
    from quest_tpu.gradients import expectation_value

    r = np.random.RandomState(3)
    psi = r.normal(size=(2, 64))
    lam = r.normal(size=(2, 64))
    e1 = float(expectation_value(jnp.asarray(psi), jnp.asarray(lam)))
    with qt.explicit_mesh(ENV8.mesh):
        q8 = qt.createQureg(6, ENV8)
        sh = q8.amps.sharding
        e8 = float(expectation_value(jax.device_put(psi, sh),
                                     jax.device_put(lam, sh)))
    assert e1 == e8


# ---------------------------------------------------------------------------
# typed lift-time errors + QT006 lint
# ---------------------------------------------------------------------------

def test_gradient_rejects_trajectory_site():
    c = Circuit(3)
    c.hadamard(0)
    c.rotateX(0, P("a"))
    k0 = np.array([[1, 0], [0, np.sqrt(0.9)]])
    k1 = np.array([[0, np.sqrt(0.1)], [0, 0]])
    c.applyTrajectoryKraus(0, [k0, k1])
    with pytest.raises(QuESTError, match=r"tape\[\d+\]:applyTrajectoryKraus"):
        check_differentiable(c)


def test_gradient_rejects_measurement_site():
    c = Circuit(3)
    c.hadamard(0)
    c.rotateX(0, P("a"))
    c.applyMidMeasurement(0, 5, site=0)
    with pytest.raises(QuESTError, match="sample_request"):
        check_differentiable(c)


def test_gradient_rejects_density_circuit():
    c = Circuit(3, is_density_matrix=True)
    c.rotateX(0, P("a"))
    with pytest.raises(QuESTError, match="density"):
        check_differentiable(c)


def test_calc_grad_rejects_density_register():
    c = Circuit(3)
    c.rotateX(0, P("a"))
    rho = qt.createDensityQureg(3, ENV1)
    with pytest.raises(QuESTError, match="state-vector"):
        qt.calcGradExpecPauliSum(rho, c, *_ham(3), {"a": 0.4})


def test_gradient_rejects_slot_free_tape():
    c = Circuit(3)
    c.hadamard(0)
    c.controlledNot(0, 1)
    with pytest.raises(QuESTError, match="no differentiable parameter"):
        check_differentiable(c)


def test_gradient_measurement_seed_rejected_anywhere():
    """A measurement site carries a stochastic slot seed, so it is
    rejected as an undifferentiable seam wherever it sits -- even in the
    pre-slot prefix the backward walk never inverts."""
    c = Circuit(3)
    c.applyMidMeasurement(0, 5, site=0)
    c.hadamard(0)
    c.rotateX(0, P("a"))
    with pytest.raises(QuESTError, match="sample_request"):
        check_differentiable(c)


def test_qt006_lint_flags_differentiation_hazards():
    from quest_tpu import analysis as A

    c = Circuit(3)
    c.hadamard(0)
    c.rotateX(0, P("a"))
    c.applyMidMeasurement(0, 5, site=0)
    k0 = np.array([[1, 0], [0, np.sqrt(0.9)]])
    k1 = np.array([[0, np.sqrt(0.1)], [0, 0]])
    c.applyTrajectoryKraus(1, [k0, k1])
    findings = A.lint_circuit(c, differentiate=True)
    qt006 = [f for f in findings if f.code == "QT006"]
    assert len(qt006) == 2
    assert all("sample_request" in f.hint for f in qt006)
    # without the differentiate flag the same tape reports no QT006
    assert not [f for f in A.lint_circuit(c) if f.code == "QT006"]


def test_request_executable_rejects_wants_values_reduce():
    from quest_tpu.gradients import grad_reduce
    from quest_tpu.segments import request_executable

    c = Circuit(3)
    c.hadamard(0)
    c.rotateX(0, 0.4)
    with pytest.raises(QuESTError, match="wants_values"):
        request_executable(c, reduce=grad_reduce(c, _ham(3)))


# ---------------------------------------------------------------------------
# serving: Engine.submit_grad, EnginePool.submit_grad, calculations API
# ---------------------------------------------------------------------------

def _vqe_circuit(n=5):
    c = Circuit(n)
    _prefix(c)
    for q in range(n):
        c.rotateX(q, P(f"x{q}"))
    for q in range(n - 1):
        c.controlledNot(q, q + 1)
    c.rotateZ(0, P("z0"))
    return c


def test_engine_submit_grad_warm_loop_zero_retraces():
    c = _vqe_circuit()
    codes, coeffs = _ham(5)
    eng = Engine(c, ENV1, hamiltonian=(codes, coeffs), max_batch=4,
                 max_delay_ms=0.5)
    try:
        base = {f"x{q}": 0.1 * (q + 1) for q in range(5)}
        base["z0"] = -0.7
        eng.warmup_grad(base)
        traces = telemetry.counter_value("engine_trace_total",
                                         kind="param_replay")
        d0 = telemetry.counter_value("device_dispatch_total",
                                     route="grad_request")
        g0 = telemetry.counter_value("grad_requests_total")
        results = []
        for step in range(10):
            p = {k: v + 0.01 * step for k, v in base.items()}
            val, grads = eng.submit_grad(p).result(timeout=60)
            results.append((val, grads))
        # ZERO retraces across the warm loop
        assert telemetry.counter_value("engine_trace_total",
                                       kind="param_replay") == traces
        # every step dispatched exactly one grad_request program
        # (sequential submits never coalesce, so 10 steps = 10 dispatches)
        assert telemetry.counter_value("device_dispatch_total",
                                       route="grad_request") == d0 + 10
        assert telemetry.counter_value("grad_requests_total") == g0 + 10
        # values/grads match the direct executable (the vmapped batch
        # program may differ from the single program by float latitude)
        gx = c.gradient((codes, coeffs), donate=False)
        q = qt.createQureg(5, ENV1)
        ref = gx(q.amps, base)
        np.testing.assert_allclose(results[0][0], float(ref["value"]),
                                   atol=1e-12, rtol=0)
        for k, v in results[0][1].items():
            np.testing.assert_allclose(float(v), float(ref["grads"][k]),
                                       atol=1e-12, rtol=0)
    finally:
        eng.close()


def test_engine_submit_grad_requires_hamiltonian():
    c = _vqe_circuit()
    eng = Engine(c, ENV1, max_batch=2)
    try:
        with pytest.raises(QuESTError, match="hamiltonian"):
            eng.submit_grad({})
    finally:
        eng.close()


def test_pool_submit_grad():
    c = _vqe_circuit()
    codes, coeffs = _ham(5)
    params = [{f"x{q}": 0.1 * (q + 1) for q in range(5)} | {"z0": -0.7},
              {f"x{q}": 0.2 * (q + 1) for q in range(5)} | {"z0": 0.3}]
    pool = EnginePool(replicas=1, max_batch=4, max_delay_ms=0.5)
    try:
        futs = pool.submit_grad_many(c, params, hamiltonian=(codes, coeffs))
        outs = [f.result(timeout=60) for f in futs]
    finally:
        pool.close()
    gx = c.gradient((codes, coeffs), donate=False)
    for p, (val, grads) in zip(params, outs):
        q = qt.createQureg(5, ENV1)
        ref = gx(q.amps, p)
        np.testing.assert_allclose(val, float(ref["value"]), atol=1e-12,
                                   rtol=0)
        for k, v in grads.items():
            np.testing.assert_allclose(float(v), float(ref["grads"][k]),
                                       atol=1e-12, rtol=0)


def test_calc_grad_expec_pauli_sum():
    c = _vqe_circuit()
    codes, coeffs = _ham(5)
    params = {f"x{q}": 0.1 * (q + 1) for q in range(5)} | {"z0": -0.7}
    q = qt.createQureg(5, ENV1)
    qt.initPlusState(q)
    val, grads = qt.calcGradExpecPauliSum(q, c, codes, coeffs, params)
    q2 = qt.createQureg(5, ENV1)
    qt.initPlusState(q2)
    ref = c.gradient((codes, coeffs), donate=False)(q2.amps, params)
    assert val == float(ref["value"])
    assert grads.keys() == ref["grads"].keys()
    for k in grads:
        assert grads[k] == float(ref["grads"][k])
