"""Gate-fusion tests: quest_tpu/fusion.py.

Fused circuits must agree amplitude-for-amplitude with the unfused tape on
arbitrary gate mixes (the fusion layer is pure TPU-side optimisation; the
reference has no analogue -- its cost model is one kernel per gate,
QuEST_cpu_distributed.c:870-905).
"""

import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu import fusion
from quest_tpu.circuits import Circuit
from quest_tpu.ops import init as ops_init

from quest_tpu.precision import real_dtype

from .helpers import TOL

ENV = qt.createQuESTEnv()


def _rand_unitary(rng, dim):
    m = rng.normal(size=(dim, dim)) + 1j * rng.normal(size=(dim, dim))
    q, r = np.linalg.qr(m)
    return q * (np.diagonal(r) / np.abs(np.diagonal(r)))


def _random_gate_soup(circ, n, rng, depth=30):
    """A mix hitting every capturable primitive family."""
    for _ in range(depth):
        k = rng.integers(12)
        qs = rng.permutation(n)
        if k == 0:
            circ.hadamard(int(qs[0]))
        elif k == 1:
            circ.tGate(int(qs[0]))
        elif k == 2:
            circ.rotateX(int(qs[0]), float(rng.uniform(0, 6)))
        elif k == 3:
            circ.controlledNot(int(qs[0]), int(qs[1]))
        elif k == 4:
            circ.controlledPhaseShift(int(qs[0]), int(qs[1]), float(rng.uniform(0, 6)))
        elif k == 5:
            circ.swapGate(int(qs[0]), int(qs[1]))
        elif k == 6:
            circ.multiRotateZ([int(qs[0]), int(qs[1])], float(rng.uniform(0, 6)))
        elif k == 7:
            circ.multiRotatePauli([int(qs[0]), int(qs[1])],
                                  [int(rng.integers(1, 4)), int(rng.integers(1, 4))],
                                  float(rng.uniform(0, 6)))
        elif k == 8:
            circ.unitary(int(qs[0]), _rand_unitary(rng, 2))
        elif k == 9:
            circ.twoQubitUnitary(int(qs[0]), int(qs[1]), _rand_unitary(rng, 4))
        elif k == 10:
            circ.multiStateControlledUnitary(
                [int(qs[0])], [int(rng.integers(2))], int(qs[1]), _rand_unitary(rng, 2))
        else:
            circ.sqrtSwapGate(int(qs[0]), int(qs[1]))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("max_qubits", [2, 3, 5])
def test_fused_statevector_agrees(seed, max_qubits):
    n = 5
    rng = np.random.default_rng(seed)
    circ = Circuit(n)
    _random_gate_soup(circ, n, rng)
    fz = circ.fused(max_qubits=max_qubits)

    mk = lambda: ops_init.init_debug(1 << n, real_dtype())
    ref = np.asarray(circ.as_fn()(mk()))
    got = np.asarray(fz.as_fn()(mk()))
    np.testing.assert_allclose(got, ref, atol=TOL, rtol=TOL)


def test_fused_density_with_barriers():
    """Decoherence entries act as barriers and the density shadow op is
    applied exactly once per fused block."""
    n = 3
    rng = np.random.default_rng(7)
    circ = Circuit(n, is_density_matrix=True)
    circ.hadamard(0)
    circ.controlledNot(0, 1)
    circ.mixDephasing(1, 0.2)          # barrier: fails statevec capture
    circ.rotateY(2, 0.9)
    circ.mixDepolarising(0, 0.1)       # barrier
    circ.tGate(0)
    circ.controlledPhaseFlip(0, 2)
    fz = circ.fused(max_qubits=3)

    mk = lambda: ops_init.density_init_plus(1 << (2 * n), real_dtype())
    ref = np.asarray(circ.as_fn()(mk()))
    got = np.asarray(fz.as_fn()(mk()))
    np.testing.assert_allclose(got, ref, atol=TOL, rtol=TOL)


def test_plan_counts_and_diagonal_blocks():
    n = 4
    circ = Circuit(n)
    circ.tGate(0)
    circ.rotateZ(1, 0.5)
    circ.controlledPhaseShift(0, 1, 0.3)   # stays diagonal
    circ.hadamard(2)                        # dense block
    p = fusion.plan(tuple(circ._tape), n, real_dtype(), max_qubits=2)
    assert p.num_fused_gates == 4 and p.num_barriers == 0
    kinds = [type(it).__name__ for it in p.items]
    assert kinds == ["DiagBlock", "FusedBlock"]


def test_wide_diagonal_fuses_wide_dense_passes_through():
    n = 6
    circ = Circuit(n)
    circ.hadamard(0)
    circ.multiRotateZ(list(range(n)), 0.4)     # diagonal: fuses despite span 6
    circ.multiQubitNot([0, n - 1])             # dense span 6 > max: barrier
    circ.hadamard(0)
    fz = circ.fused(max_qubits=3)
    p = fusion.plan(tuple(circ._tape), n, real_dtype(), max_qubits=3)
    assert p.num_barriers == 1
    mk = lambda: ops_init.init_debug(1 << n, real_dtype())
    np.testing.assert_allclose(np.asarray(fz.as_fn()(mk())),
                               np.asarray(circ.as_fn()(mk())), atol=TOL, rtol=TOL)


def test_dense_blocks_are_contiguous_windows():
    n = 8
    circ = Circuit(n)
    circ.hadamard(1)
    circ.controlledNot(1, 3)                   # window 1..3
    circ.controlledPhaseFlip(0, 7)             # scattered but diagonal
    p = fusion.plan(tuple(circ._tape), n, real_dtype(), max_qubits=4)
    for it in p.items:
        if isinstance(it, fusion.FusedBlock):
            assert it.qubits == tuple(range(it.qubits[0], it.qubits[-1] + 1))
    mk = lambda: ops_init.init_debug(1 << n, real_dtype())
    fz = circ.fused(max_qubits=4)
    np.testing.assert_allclose(np.asarray(fz.as_fn()(mk())),
                               np.asarray(circ.as_fn()(mk())), atol=TOL, rtol=TOL)


def test_fused_runs_on_qureg():
    qureg = qt.createQureg(4, ENV)
    qt.initPlusState(qureg)
    circ = Circuit(4)
    circ.hadamard(0)
    circ.controlledNot(0, 1)
    circ.fused().run(qureg)
    assert abs(qt.calcTotalProb(qureg) - 1.0) < TOL


def test_fused_circuit_on_sharded_register():
    """Window GEMMs + diagonal blocks under GSPMD sharding must agree with
    the single-device result (top qubits are the shard axis, so high-window
    blocks compile to cross-device collectives)."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the multi-device CPU mesh")
    from __graft_entry__ import _random_layers

    n = 11
    circ = Circuit(n)
    _random_layers(circ, n, depth=3, seed=5)
    fz = circ.fused(max_qubits=5)

    env8 = qt.createQuESTEnv(jax.devices()[:8])
    q8 = qt.createQureg(n, env8)
    qt.initDebugState(q8)
    fz.run(q8)

    env1 = qt.createQuESTEnv(jax.devices()[:1])
    q1 = qt.createQureg(n, env1)
    qt.initDebugState(q1)
    fz.run(q1)

    np.testing.assert_allclose(np.asarray(q8.amps), np.asarray(q1.amps),
                               atol=TOL, rtol=TOL)


def test_tape_transpose_stats_matches_plan_stats():
    """The tape-level decoder (used by bench artifacts and the driver
    dryrun) agrees with transpose_stats over the FusePlan it came from."""
    import numpy as np

    from __graft_entry__ import _random_layers
    from quest_tpu.circuits import Circuit
    from quest_tpu.ops.pallas_gates import local_qubits
    from quest_tpu.precision import real_dtype

    n, ndev = 20, 8
    circ = Circuit(n)
    _random_layers(circ, n, 3)
    rng = np.random.RandomState(7)
    for q in range(n):
        g, _ = np.linalg.qr(rng.randn(2, 2) + 1j * rng.randn(2, 2))
        circ.unitary(q, g)
    n_local = n - (ndev.bit_length() - 1)
    p = fusion.plan_pallas_sharded(tuple(circ._tape), n, real_dtype(), 5,
                                   local_qubits(n_local), n_local)
    tape = fusion.as_tape(p)
    for kwargs in ({}, {"nsv": n, "num_slices": 2}):
        st_plan = fusion.transpose_stats(p, n_local, **kwargs)
        st_tape = fusion.tape_transpose_stats(tape, n_local, **kwargs)
        assert st_plan == st_tape, (st_plan, st_tape)
    assert fusion.transpose_stats(p, n_local)["collective_transposes"] > 0


def test_synth_frame_boundary_anchors():
    """Round-6 (last open ADVICE r5 finding): _synth_frame respects the
    shard boundary -- one-sided high targets get a block on their own side
    (shard-local transpose), and a genuinely straddling target pair still
    falls back to the spanning block (the clipped candidates cannot
    localise both sides, so the collective frame is forced)."""
    import numpy as np

    from quest_tpu.fusion import FusePlan, _FramePlanner, _POp

    # 17q-density-like geometry: tile 19 bits, frame width k=12, 34
    # flattened qubits, shard boundary 30
    pl = _FramePlanner(FusePlan(), 19, 12, 34, boundary=30)

    # high target below the boundary: the synthesized block stays below it
    op = _POp("kraus1", (16, 27), (), (), (), False)
    f = pl._synth_frame(op)
    assert f == (27, 1)
    assert f[0] + f[1] <= 30
    assert pl.feasible(op, f)

    # high targets straddling the boundary: both clipped anchors miss one
    # side, so the spanning (collective) frame is accepted as a fallback
    op2 = _POp("kraus2", (10, 12, 29, 31), (), (), (), False)
    f2 = pl._synth_frame(op2)
    assert f2 == (29, 3)
    assert pl.feasible(op2, f2)

    # above-boundary one-sided targets anchor above it
    op3 = _POp("kraus1", (10, 32), (), (), (), False)
    f3 = pl._synth_frame(op3)
    assert f3 == (32, 1) and f3[0] >= 30


# ---------------------------------------------------------------------------
# Param entries in dense plans: blocks whose matrices are assembled in-trace
# ---------------------------------------------------------------------------

def _family(name, circ, th):
    """One member group of the liftable family (engine.params._LIFTABLE)
    on 4 qubits, its angles from ``th`` (Params, or that request's floats)."""
    circ.hadamard(0)
    circ.controlledNot(0, 3)
    if name == "rotations":
        circ.rotateZ(0, th("a"))
        circ.rotateX(0, th("b"))
        circ.rotateY(1, th("c"))
        circ.phaseShift(2, th("d"))
        circ.rotateAroundAxis(3, th("e"), qt.Vector(1.0, 2.0, -0.5))
        circ.rotateX(1, 0.7)                    # a constant among Params
    elif name == "compactUnitary":
        circ.compactUnitary(1, th("alpha"), th("beta"))
        circ.controlledCompactUnitary(0, 2, th("alpha"), th("beta"))
    elif name == "controlled":
        circ.controlledRotateX(0, 1, th("a"))
        circ.controlledRotateY(2, 1, th("b"))
        circ.controlledRotateZ(1, 3, th("c"))
        circ.controlledPhaseShift(0, 2, th("d"))
        circ.multiControlledPhaseShift([0, 1, 3], th("e"))
        circ.controlledRotateAroundAxis(3, 0, th("a"),
                                        qt.Vector(0.3, -1.0, 0.8))
    elif name == "multiRotateZ":
        circ.multiRotateZ([0, 2, 3], th("a"))
        circ.multiControlledMultiRotateZ([1], [0, 3], th("b"))
    else:
        assert name == "multiRotatePauli"
        circ.multiRotatePauli([0, 1, 3], [1, 2, 3], th("a"))
        circ.multiRotatePauli([2], [0], th("b"))          # all-identity
        circ.multiControlledMultiRotatePauli([2], [0, 1], [2, 1], th("c"))
    circ.tGate(1)
    circ.controlledPhaseFlip(1, 2)


_FAMILY = ("rotations", "compactUnitary", "controlled", "multiRotateZ",
           "multiRotatePauli")
_VALUES = {"a": 0.37, "b": 1.234, "c": -0.8, "d": 2.2, "e": 0.61,
           "alpha": complex(np.cos(0.4), 0.0),
           "beta": complex(0.0, np.sin(0.4))}


@pytest.mark.parametrize("precision,tol", [(1, 1e-6), (2, 1e-12)])
@pytest.mark.parametrize("density", [False, True])
@pytest.mark.parametrize("family", _FAMILY)
def test_param_dense_plan_matches_constant_raw_tape(family, density,
                                                    precision, tol):
    """The parameterised replay of a Param tape's dense plan agrees with
    the constant replay of the RAW tape at the same values: no Param is a
    barrier, and the block matrices composed inside the trace are the
    host's."""
    from quest_tpu import telemetry
    from quest_tpu.engine import P

    n = 4
    raw, par = Circuit(n, density), Circuit(n, density)
    _family(family, raw, _VALUES.__getitem__)
    _family(family, par, P)
    dtype = real_dtype(precision)
    b0 = telemetry.counter_value("fusion_param_barriers_total", mode="dense")
    f0 = telemetry.counter_value("fusion_param_fused_total", mode="dense")
    plan = par.fused(max_qubits=4, dtype=dtype)
    assert telemetry.counter_value("fusion_param_barriers_total",
                                   mode="dense") == b0
    with_params = sum(fusion._entry_has_params(a, k) for _, a, k in par._tape)
    assert telemetry.counter_value("fusion_param_fused_total",
                                   mode="dense") == f0 + with_params
    assert all(f.__name__ in ("_apply_dense_block", "_apply_gate_diag",
                              "_apply_deferred_block")
               for f, _, _ in plan._tape)
    mk = ((lambda: ops_init.density_init_plus(1 << (2 * n), dtype))
          if density else (lambda: ops_init.init_debug(1 << n, dtype)))
    want = np.asarray(raw.as_fn()(mk()))
    got = np.asarray(plan.parameterized()(
        mk(), {k: _VALUES[k] for k in plan.param_names}))
    assert np.max(np.abs(got - want)) <= tol * max(1.0, np.abs(want).max())


def test_param_dense_plan_structure_is_value_free():
    """One plan serves every parameter vector: its fingerprint is that of
    its host-materialised constant tape at ANY values, and planning twice
    gives the same structure."""
    from quest_tpu.engine import P
    from quest_tpu.engine.params import bind, materialize_tape

    par = Circuit(4)
    _family("rotations", par, P)
    plan = par.fused(max_qubits=3)
    fp = plan.fingerprint()
    twin = Circuit(4)
    _family("rotations", twin, P)
    assert twin.fused(max_qubits=3).fingerprint() == fp
    lifted = plan.lifted()
    for scale in (1.0, -2.5):
        const = Circuit(4)
        const._tape = materialize_tape(lifted, bind(
            lifted, {k: scale * _VALUES[k] for k in plan.param_names},
            device=False))
        assert const.fingerprint() == fp
    # and a plan round-trips through its tape
    again = fusion.as_tape(fusion.plan_from_tape(plan._tape))
    twice = Circuit(4)
    twice._tape = again
    assert twice.fingerprint() == fp


def test_fusion_barrier_and_channel_still_split_param_plan():
    """What has no structure without its values stays a barrier: a
    mid-circuit measurement (tagged _fusion_barrier) and a channel split
    the plan between the Param blocks."""
    from quest_tpu.engine import P

    circ = Circuit(3, is_density_matrix=True)
    circ.rotateX(0, P("a"))
    circ.rotateZ(1, P("b"))
    circ.applyMidMeasurement(1, seed=5)
    circ.rotateY(1, P("c"))
    circ.mixDephasing(0, 0.1)
    circ.rotateX(2, P("d"))
    p = fusion.plan(tuple(circ._tape), 3, real_dtype(), max_qubits=3,
                    is_density=True)
    kinds = [it[0].__name__ if isinstance(it, tuple) else type(it).__name__
             for it in p.items]
    assert kinds == ["FusedBlock", "applyMidMeasurement", "FusedBlock",
                     "mixDephasing", "FusedBlock"]
    assert all(it.factors is not None for it in p.items
               if not isinstance(it, tuple))
    assert p.num_barriers == 2


def test_low_window_guard_keeps_gemm_bounded():
    """A window starting below the lane boundary is a GEMM over every qubit
    below its top: the planner opens none that reaches
    ops.apply.MAX_LOW_WINDOW_TOP, whatever ``max_qubits`` allows."""
    from quest_tpu.ops.apply import _MIN_MINOR, MAX_LOW_WINDOW_TOP

    n = 14
    circ = Circuit(n)
    for q in range(n):
        circ.hadamard(q)
    p = fusion.plan(tuple(circ._tape), n, real_dtype(), max_qubits=7)
    for it in p.items:
        assert it.qubits[0] >= _MIN_MINOR or \
            it.qubits[-1] < MAX_LOW_WINDOW_TOP
    assert [it.qubits[0] for it in p.items] == [0, 7]


def test_deferred_diagonal_leaves_a_static_diagonal_block_static():
    """A diagonal block with a constant table is cheap and one with a
    traced table is not (PERF.md, PR 27): a Param's diagonal factor does
    not join a static diagonal block; it opens its own, which the next
    dense factor turns into a window block."""
    from quest_tpu.engine import P

    circ = Circuit(6)
    circ.controlledPhaseFlip(0, 5)
    circ.rotateZ(0, P("a"))
    circ.rotateX(0, P("b"))
    circ.rotateZ(1, P("c"))
    p = fusion.plan(tuple(circ._tape), 6, real_dtype(), max_qubits=3)
    assert [type(it).__name__ for it in p.items] == ["DiagBlock",
                                                     "FusedBlock"]
    assert p.items[0].factors is None and p.items[0].qubits == (0, 5)
    assert p.items[1].qubits == (0, 1) and len(p.items[1].factors) == 3
    # Param diagonals alone still share one (deferred) diagonal block
    only = Circuit(6)
    only.rotateZ(0, P("a"))
    only.rotateZ(5, P("b"))
    q = fusion.plan(tuple(only._tape), 6, real_dtype(), max_qubits=3)
    assert len(q.items) == 1 and len(q.items[0].factors) == 2
