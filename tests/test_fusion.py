"""Gate-fusion tests: quest_tpu/fusion.py.

Fused circuits must agree amplitude-for-amplitude with the unfused tape on
arbitrary gate mixes (the fusion layer is pure TPU-side optimisation; the
reference has no analogue -- its cost model is one kernel per gate,
QuEST_cpu_distributed.c:870-905).
"""

import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu import capture, environment, fusion, planner
from quest_tpu.circuits import Circuit
from quest_tpu.ops import init as ops_init
# the df route's switch off the TPU, by its own name: the surface audit
# reads a test FILE that spells the variable out as df coverage of every
# API function the file calls, which the routing tests below are not
from quest_tpu.ops.pallas_df import _DF_ENV

from quest_tpu.precision import real_dtype

from .helpers import TOL, shape_register

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
    p = planner.plan(tuple(circ._tape), n, real_dtype(), max_qubits=2)
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
    p = planner.plan(tuple(circ._tape), n, real_dtype(), max_qubits=3)
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
    p = planner.plan(tuple(circ._tape), n, real_dtype(), max_qubits=4)
    for it in p.items:
        if isinstance(it, planner.FusedBlock):
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
    p = planner.plan_pallas_sharded(tuple(circ._tape), n, real_dtype(), 5,
                                   local_qubits(n_local), n_local)
    tape = fusion.as_tape(p)
    for kwargs in ({}, {"nsv": n, "num_slices": 2}):
        st_plan = planner.transpose_stats(p, n_local, **kwargs)
        st_tape = fusion.tape_transpose_stats(tape, n_local, **kwargs)
        assert st_plan == st_tape, (st_plan, st_tape)
    assert planner.transpose_stats(p, n_local)["collective_transposes"] > 0


def test_synth_frame_boundary_anchors():
    """Round-6 (last open ADVICE r5 finding): _synth_frame respects the
    shard boundary -- one-sided high targets get a block on their own side
    (shard-local transpose), and a genuinely straddling target pair still
    falls back to the spanning block (the clipped candidates cannot
    localise both sides, so the collective frame is forced)."""
    import numpy as np

    from quest_tpu.planner import FusePlan, _FramePlanner, _POp

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
    """One member group of the liftable family (params._LIFTABLE)
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
    with_params = sum(capture._entry_has_params(a, k) for _, a, k in par._tape)
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
    from quest_tpu.params import bind, materialize_tape

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
    p = planner.plan(tuple(circ._tape), 3, real_dtype(), max_qubits=3,
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
    p = planner.plan(tuple(circ._tape), n, real_dtype(), max_qubits=7)
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
    p = planner.plan(tuple(circ._tape), 6, real_dtype(), max_qubits=3)
    assert [type(it).__name__ for it in p.items] == ["DiagBlock",
                                                     "FusedBlock"]
    assert p.items[0].factors is None and p.items[0].qubits == (0, 5)
    assert p.items[1].qubits == (0, 1) and len(p.items[1].factors) == 3
    # Param diagonals alone still share one (deferred) diagonal block
    only = Circuit(6)
    only.rotateZ(0, P("a"))
    only.rotateZ(5, P("b"))
    q = planner.plan(tuple(only._tape), 6, real_dtype(), max_qubits=3)
    assert len(q.items) == 1 and len(q.items[0].factors) == 2


# ---------------------------------------------------------------------------
# a fused run from planner to kernel: the run on the tape, the route, the exit
# ---------------------------------------------------------------------------

def test_fused_run_fingerprints_by_content():
    """A run is its tape entry, and the structure fingerprint hashes it
    field by field: two fused tapes that differ in one gate's angle differ
    in fingerprint, two equal ones do not."""
    def fused(theta):
        c = Circuit(10)
        for q in range(10):
            c.hadamard(q)
        c.rotateZ(3, theta)
        c.controlledNot(0, 9)
        fz = c.fused(max_qubits=4, pallas=True)
        assert all(isinstance(a[0], planner.PallasRun) for _, a, _ in fz._tape)
        return fz

    a, b, other = fused(0.25), fused(0.25), fused(0.26)
    assert a._tape[0][1][0] == b._tape[0][1][0]
    assert hash(a._tape[0][1][0]) == hash(b._tape[0][1][0])
    assert a.fingerprint() == b.fingerprint()
    assert a.fingerprint() != other.fingerprint()
    # no Param hides inside a run (the Pallas planner keeps such entries
    # apart, as barriers), so lifting finds no slot in it
    assert a.param_names == () and not a.lifted().slots


def _mesh(ndev, axis=None):
    import jax
    from jax.sharding import Mesh
    from quest_tpu.environment import AMP_AXIS
    if len(jax.devices()) < ndev:
        pytest.skip(f"needs the {ndev}-device CPU mesh")
    return Mesh(np.array(jax.devices()[:ndev]), (axis or AMP_AXIS,))


def _canonical(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    return NamedSharding(mesh, P(None, mesh.axis_names[0]))


def _x_run(target, tile_bits, **swaps):
    from quest_tpu.ops.pallas_gates import HashableMatrix
    x = HashableMatrix(np.array([[0, 1], [1, 0]], dtype=complex))
    return planner.PallasRun((("matrix", target, (), (), x),), tile_bits,
                            **swaps)


# (case, dtype, df route on, qubits, devices the register is split over,
#  how the replay sees that split, the run as (target, tile_bits, swaps),
#  the route's (kind, fold_load, fold_store, reason))
_F32, _F64 = "float32", "float64"
_LQ22 = 19          # local_qubits(22): 7 lane bits + the 4096-sublane tile
_LQ_DF = 17         # local_qubits(n >= 17, DF_SUBLANES)
_ROUTE_TABLE = [
    # -- one device, plain -------------------------------------------------
    ("local/no-swap", _F32, False, 22, 1, "concrete",
     (0, _LQ22, {}), ("local", False, False, None)),
    ("local/folded", _F32, False, 22, 1, "concrete",
     (0, _LQ22, dict(load_swap_k=2, store_swap_k=2)),
     ("local", True, True, None)),
    ("local/load-only-folded", _F32, False, 22, 1, "concrete",
     (0, _LQ22, dict(load_swap_k=3)), ("local", True, False, None)),
    ("local/geometry-miss", _F32, False, 22, 1, "concrete",
     (0, _LQ22 - 1, dict(load_swap_k=2, store_swap_k=2)),
     ("local", False, False, "swap_not_foldable")),
    ("local/k-too-wide", _F32, False, 29, 1, "concrete",
     (0, _LQ22, dict(load_swap_k=10, store_swap_k=10)),
     ("local", False, False, "swap_not_foldable")),
    ("local/native-f64-interpreter", _F64, False, 22, 1, "concrete",
     (0, _LQ22, dict(load_swap_k=2)), ("local", True, False, None)),
    # -- one device, double-float -------------------------------------------
    ("df_local/folded", _F64, True, 20, 1, "concrete",
     (0, _LQ_DF, dict(load_swap_k=2, store_swap_k=2)),
     ("df_local", True, True, None)),
    ("df_local/geometry-miss", _F64, True, 20, 1, "concrete",
     (0, _LQ_DF - 1, dict(store_swap_k=2)),
     ("df_local", False, False, "swap_not_foldable")),
    ("df_local/tile-mismatch", _F64, True, 20, 1, "concrete",
     (_LQ_DF, _LQ22, {}), ("gatewise", False, False, "df_tile_mismatch")),
    ("df_local/sub-tile", _F64, True, 7, 1, "concrete",
     (0, 7, {}), ("gatewise", False, False, "f64_engine")),
    # -- per shard (GSPMD) ---------------------------------------------------
    ("sharded/shard-local-swap-folded", _F32, False, 24, 4, "concrete",
     (0, _LQ22, dict(load_swap_k=2, store_swap_k=2)),
     ("sharded", True, True, None)),
    ("sharded/traced-ambient-mesh", _F32, False, 24, 4, "traced",
     (0, _LQ22, dict(load_swap_k=2, store_swap_k=2)),
     ("sharded", True, True, None)),
    ("sharded/swap-reaching-sharded-bits-explicit", _F32, False, 24, 4,
     "concrete",
     (0, _LQ22, dict(load_swap_k=2, store_swap_k=2, store_swap_hi=21)),
     ("sharded", True, False, None)),
    ("sharded/shard-local-geometry-miss", _F32, False, 24, 4, "concrete",
     (0, _LQ22 - 1, dict(load_swap_k=2)),
     ("sharded", False, False, "swap_not_foldable")),
    ("sharded/df-folded", _F64, True, 21, 8, "concrete",
     (0, _LQ_DF, dict(load_swap_k=1, store_swap_k=1)),
     ("sharded", True, True, None)),
    ("sharded/df-tile-mismatch", _F64, True, 21, 8, "traced",
     (_LQ_DF, 18, {}), ("gatewise", False, False, "df_tile_mismatch")),
    ("sharded/non-canonical-mesh", _F32, False, 24, 4, "other-axis",
     (0, _LQ22, {}), ("gatewise", False, False, "shard_map_unsupported")),
    ("sharded/non-power-of-two", _F32, False, 24, 3, "traced",
     (0, _LQ22, {}), ("gatewise", False, False, "shard_map_unsupported")),
    ("sharded/dense-target-above-shard-tile", _F32, False, 24, 4, "traced",
     (20, 21, {}), ("gatewise", False, False, "shard_map_unsupported")),
    ("sharded/sub-tile-shard", _F32, False, 10, 8, "concrete",
     (0, 7, {}), ("gatewise", False, False, "shard_map_unsupported")),
    ("sharded/df-sub-tile-shard", _F64, True, 9, 8, "concrete",
     (0, 6, {}), ("gatewise", False, False, "f64_engine")),
    # -- explicit scheduler ---------------------------------------------------
    ("scheduler/f32", _F32, False, 24, 4, "scheduler",
     (0, _LQ22, dict(load_swap_k=2)),
     ("gatewise", False, False, "explicit_scheduler")),
    ("scheduler/df", _F64, True, 21, 8, "scheduler",
     (0, _LQ_DF, dict(load_swap_k=1, store_swap_k=1)),
     ("sched_df", False, False, None)),
    ("scheduler/df-tile-mismatch", _F64, True, 21, 8, "scheduler",
     (_LQ_DF, 18, {}), ("gatewise", False, False, "df_tile_mismatch")),
]


@pytest.mark.parametrize(
    "dtype,df,n,ndev,seen,run,want",
    [c[1:] for c in _ROUTE_TABLE], ids=[c[0] for c in _ROUTE_TABLE])
def test_route_table(monkeypatch, dtype, df, n, ndev, seen, run, want):
    """Every (route, fold, reason) ``fusion._route`` can reach, decided on
    registers of shapes only: no device program runs, no counter moves."""
    import contextlib

    import jax

    from quest_tpu import telemetry
    from quest_tpu.ops import pallas_gates as PG
    from quest_tpu.ops.pallas_df import DF_SUBLANES

    assert PG.local_qubits(22) == _LQ22
    assert PG.local_qubits(20, DF_SUBLANES) == _LQ_DF
    monkeypatch.setenv(_DF_ENV, "1" if df else "0")
    target, tile_bits, swaps = run
    prun = _x_run(target, tile_bits, **swaps)
    mesh = _mesh(ndev, "other" if seen == "other-axis" else None) \
        if ndev > 1 else None
    got = []

    def decide(amps):
        got.append(fusion._route(qt.Qureg(n, False, amps, env=None), prun))
        return amps

    before = telemetry.snapshot()["counters"]
    if seen == "traced":
        # what Circuit.run sets up: the tracer hides the sharding, the
        # ambient mesh carries it
        with environment.pallas_mesh(mesh):
            jax.eval_shape(decide, shape_register(n, dtype).amps)
    else:
        sharding = _canonical(mesh) if mesh is not None else None
        ctx = qt.explicit_mesh(mesh) if seen == "scheduler" \
            else contextlib.nullcontext()
        with ctx:
            decide(shape_register(n, dtype, sharding).amps)
    assert telemetry.snapshot()["counters"] == before, "_route counted"
    route = got[0]
    assert tuple(route[:4]) == want
    if route.kind in ("sharded", "sched_df"):
        assert route.mesh is mesh
        assert route.n_exec == n - (ndev.bit_length() - 1)
    elif route.kind != "gatewise":
        assert route.mesh is None and route.n_exec == n
    assert route.df == (route.kind != "gatewise" and df)


def _fallbacks():
    from quest_tpu import telemetry
    return {k: v for k, v in telemetry.snapshot()["counters"].items()
            if k.startswith("engine_fallback_total") and v}


@pytest.mark.parametrize("reason", [
    "explicit_scheduler", "shard_map_unsupported", "f64_engine",
    "df_tile_mismatch", "fault_degraded"])
def test_every_exit_goes_through_gatewise_once(monkeypatch, reason):
    """Each way out of the kernel routes reaches the device through the one
    ``_gatewise``, with exactly one count of its reason and nothing else
    counted, and the state is the gate's."""
    import contextlib

    import jax

    from quest_tpu import telemetry
    from quest_tpu.ops import pallas_gates as PG
    from quest_tpu.ops.pallas_df import DF_SUBLANES
    from quest_tpu.resilience import fault_plan

    if np.dtype(real_dtype()) != np.dtype("float64"):
        pytest.skip("needs QUEST_PRECISION=2 (the conftest default)")
    one = qt.createQuESTEnv(jax.devices()[:1])
    ctx = contextlib.nullcontext()
    target, code = 0, None
    if reason == "explicit_scheduler":
        mesh = _mesh(8)
        env, n, tile_bits = qt.createQuESTEnv(), 10, 7
        ctx = qt.explicit_mesh(mesh)
    elif reason == "shard_map_unsupported":
        _mesh(8)
        env, n, tile_bits = qt.createQuESTEnv(), 10, 7   # 7-qubit shards
    elif reason == "f64_engine":
        monkeypatch.setenv(_DF_ENV, "1")
        env, n, tile_bits = one, 7, 7                    # below one tile
    elif reason == "df_tile_mismatch":
        monkeypatch.setenv(_DF_ENV, "1")
        env, n, tile_bits = one, 18, PG.local_qubits(18)
        target = PG.local_qubits(18, DF_SUBLANES)        # legal for f32 only
    else:
        env, n, tile_bits, code = one, 10, 10, 1
        ctx = fault_plan("pallas.dispatch:compile:1+")
    q = qt.createQureg(n, env, precision_code=code)
    qt.initClassicalState(q, 0)
    calls = {"gatewise": [], "engine": 0}
    real_gatewise, real_engine = fusion._gatewise, fusion._apply_ops_via_engine

    def spy_gatewise(qureg, run, why, *a, **kw):
        calls["gatewise"].append(why)
        return real_gatewise(qureg, run, why, *a, **kw)

    def spy_engine(qureg, ops):
        calls["engine"] += 1
        return real_engine(qureg, ops)

    monkeypatch.setattr(fusion, "_gatewise", spy_gatewise)
    monkeypatch.setattr(fusion, "_apply_ops_via_engine", spy_engine)
    telemetry.reset()
    with ctx:
        fusion._apply_pallas_run(q, _x_run(target, tile_bits))
    # the guard counts its own degradation; every other exit hands
    # _gatewise the reason to count
    assert calls["gatewise"] == [None if reason == "fault_degraded"
                                 else reason]
    assert calls["engine"] == 1
    assert _fallbacks() == {
        f"engine_fallback_total{{reason={reason}}}": 1.0}
    amps = np.asarray(q.amps)
    assert amps[0, 1 << target] == pytest.approx(1.0)
    assert amps[0, 0] == pytest.approx(0.0)


def test_kernel_routes_count_what_the_plan_did_not_price(monkeypatch):
    """The two labels that are not exits: a relabeling that misses the
    register's tile geometry runs beside the kernel (``swap_not_foldable``,
    once a run), and a df run longer than DF_MAX_OPS splits into chained
    kernels (``df_max_ops_split``, once an extra pass). Neither leaves the
    kernel route."""
    import jax

    from quest_tpu import telemetry
    from quest_tpu.ops.pallas_df import DF_MAX_OPS
    from quest_tpu.ops.pallas_gates import HashableMatrix

    if np.dtype(real_dtype()) != np.dtype("float64"):
        pytest.skip("needs QUEST_PRECISION=2 (the conftest default)")
    monkeypatch.setattr(fusion, "_gatewise", None)   # any exit would raise
    one = qt.createQuESTEnv(jax.devices()[:1])
    n = 12
    q = qt.createQureg(n, one)
    qt.initClassicalState(q, 0)
    telemetry.reset()
    # planned for a 10-bit tile, run on a register whose tile holds 12
    fusion._apply_pallas_run(q, _x_run(0, 10, load_swap_k=2, store_swap_k=2))
    assert _fallbacks() == {
        "engine_fallback_total{reason=swap_not_foldable}": 1.0}
    assert telemetry.counter_value("pallas_pass_total",
                                   kind="frame_swap") == 2
    assert np.asarray(q.amps)[0, 1] == pytest.approx(1.0)

    monkeypatch.setenv(_DF_ENV, "1")
    x = HashableMatrix(np.array([[0, 1], [1, 0]], dtype=complex))
    ops = tuple(("matrix", i % n, (), (), x)
                for i in range(2 * DF_MAX_OPS + 1))
    q = qt.createQureg(n, one)
    qt.initClassicalState(q, 0)
    telemetry.reset()
    fusion._apply_pallas_run(q, planner.PallasRun(ops, n))
    assert _fallbacks() == {
        "engine_fallback_total{reason=df_max_ops_split}": 2.0}
    # 17 X gates over 12 qubits: qubits 0..4 flipped twice, 5..11 once
    want = sum(1 << b for b in range(5, n))
    assert np.asarray(q.amps)[0, want] == pytest.approx(1.0, abs=1e-6)
