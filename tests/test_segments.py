"""Single-dispatch segment programs (quest_tpu.segments, round 13).

What this suite pins down:

- frame-identity boundaries: ``identity_boundaries`` finds the legal
  segment seams of a fused plan (starts at 0, ends at len(tape)),
  reading each run and swap off the tape by attribute
  (``fusion.plan_from_tape``, the one decoder);
- ``segment_cuts`` greedy coarsest capping: cuts are identity
  boundaries, spans respect ``max_items`` unless a single
  boundary-to-boundary gap is longer, ``max_items < 1`` rejects;
- the ``seg`` plan stamp: ``Circuit.fused`` stamps every frame-carrying
  item with its segment index, the stamps survive the tape codec
  roundtrip, plancheck re-derives the segmentation and flags corrupted
  stamps as QT107 (an item no planner stamped carries None and is
  skipped);
- the numeric contract (module docstring of quest_tpu.segments): a
  fixed segmentation is run-to-run DETERMINISTIC (bit-identical) on
  every leg; the whole-tape segment program is bit-identical to
  ``Circuit.compiled()``, the program ``Circuit.run`` dispatches. ACROSS
  program granularities XLA-CPU contracts fma differently per compiled
  program (the documented tests/test_sharded_df.py caveat), so a chain
  of several programs is held to the whole-tape program at ~ulp
  allclose, not array_equal; on TPU the Mosaic kernel is opaque to
  recontraction and the granularities coincide. Both are lowerings of
  one plan, so each leg is also held to the dense oracle
  (tests/oracle.py), which shares no code with either;
- one ``device_dispatch_total{route="segment"}`` per segment program
  launch, the engine's ``engine_vmap``/``engine_param`` sites, and
  run_segmented's per-segment accounting;
- sliced replays journal zero-cost ("segment", lo) markers under the
  explicit scheduler and check_schedule validates them (bad cursor ->
  QT107; mid-layout seam -> QT104).
"""

import contextlib
import dataclasses
import functools

import numpy as np
import pytest

import jax

import quest_tpu as qt
from quest_tpu import analysis as A
from quest_tpu import fusion, planner, segments, telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.engine import Engine, P
from quest_tpu.ops import pallas_gates as PG
from quest_tpu.ops.pallas_df import DF_SUBLANES
from quest_tpu.resilience import segmented

from . import oracle

if np.dtype(qt.precision.real_dtype()) != np.dtype("float64"):
    pytest.skip("segments suite needs QUEST_PRECISION=2 (the conftest "
                "default)", allow_module_level=True)

ENV8 = qt.createQuESTEnv()
ENV1 = qt.createQuESTEnv(jax.devices()[:1])

# 1-2 ulp headroom on ~2^-6-scale amplitudes: the cross-program fma
# recontraction band (see module docstring), NOT an accuracy tolerance
ATOL64 = 5e-15
ATOL32 = 2e-6
# the df route on XLA-CPU: its compiler duplicates producer expressions
# and contracts each copy differently, so the error-free transforms are
# not exact there and a df program holds f32-product accuracy, not the
# chip's 1e-12 (README "QUEST_PRECISION=2"; chip_smoke.py's df phase and
# tools/df_verify.py hold the exact budget on hardware)
ATOL_DF_CPU = 2e-7


def _need_mesh(ndev=8):
    if len(jax.devices()) < ndev:
        pytest.skip(f"needs the {ndev}-device CPU mesh")


def _circuit(n=12):
    c = Circuit(n)
    for q in range(n):
        c.hadamard(q)
    for q in range(n - 1):
        c.controlledNot(q, q + 1)
    for q in range(n):
        c.rotateY(q, 0.1 * (q + 1))
    return c


def _multi_item(n=12, dtype=np.float64, sublanes=4):
    """A single-device fused circuit with a MULTI-item tape: an explicit
    sub-maximal tile geometry defeats the everything-fits-one-run fusion
    at n <= 14, so the plan carries several PallasRuns with folded frame
    swaps -- the interesting case for segmentation."""
    c = _circuit(n)
    p = planner.plan(tuple(c._tape), n, np.dtype(dtype), max_qubits=3,
                    pallas_tile_bits=PG.local_qubits(n, sublanes))
    segments.stamp_plan(p, n)
    out = Circuit(n)
    out._tape = fusion.as_tape(p)
    return out


def _sharded(n=12):
    return _circuit(n).fused(max_qubits=3, pallas=True, shard_devices=8)


def _run_whole(circ, env, precision=2, explicit=False):
    """``Circuit.run``: the whole tape as one program, what a library
    caller dispatches."""
    q = qt.createQureg(circ.num_qubits, env, precision_code=precision)
    ctx = qt.explicit_mesh(env.mesh) if explicit \
        else contextlib.nullcontext()
    with ctx:
        circ.run(q)
    return np.asarray(jax.device_get(q.amps))


@functools.lru_cache(maxsize=None)
def _oracle_planes(n=12):
    """``_circuit(n)`` on |0...0> by the dense oracle's index arithmetic,
    as the register's (2, 2^n) planes."""
    h = np.array([[1, 1], [1, -1]]) / np.sqrt(2)
    x = np.array([[0, 1], [1, 0]])
    psi = np.zeros(1 << n, dtype=np.complex128)
    psi[0] = 1.0
    for q in range(n):
        psi = oracle.apply_to_statevec_indexed(psi, n, (q,), h)
    for q in range(n - 1):
        psi = oracle.apply_to_statevec_indexed(psi, n, (q + 1,), x,
                                               controls=(q,))
    for q in range(n):
        c, s_ = np.cos(0.05 * (q + 1)), np.sin(0.05 * (q + 1))
        psi = oracle.apply_to_statevec_indexed(psi, n, (q,),
                                               [[c, -s_], [s_, c]])
    return np.stack([psi.real, psi.imag])


def _run_chain(circ, env, cap=None, precision=2, explicit=False):
    q = qt.createQureg(circ.num_qubits, env, precision_code=precision)
    ctx = qt.explicit_mesh(env.mesh) if explicit \
        else contextlib.nullcontext()
    with ctx:
        fn = circ.compiled_segments(max_items=cap)
        q.put(fn(q.amps))
    return np.asarray(jax.device_get(q.amps))


# ---------------------------------------------------------------------------
# frame-identity boundaries + greedy cuts
# ---------------------------------------------------------------------------

def test_identity_boundaries_cover_fused_plan():
    c = _multi_item()
    assert len(c._tape) > 1, "fixture must produce a multi-item plan"
    b = segments.identity_boundaries(c._tape, 12)
    assert b[0] == 0
    assert b[-1] == len(c._tape), \
        "every fused plan ends at frame identity (QT102)"
    assert b == sorted(set(b))


def test_identity_boundaries_replay_standalone_swaps():
    """A standalone FrameSwap leaves identity and its twin returns to it,
    whatever else the swap is stamped with; the resilience checkpoint
    planner rides the same replay."""
    for stamps in ({}, {"comm_pipeline": 4}, {"seg": 0,
                                              "comm_pipeline_dcn": 2}):
        swap = (fusion._apply_frame_swap,
                (planner.FrameSwap(9, 2, **stamps),), {})
        assert segments.identity_boundaries([swap, swap], 12) == [0, 2]
        cuts = segmented.segment_plan([swap, swap], 12, 1)
        assert cuts[0] == 0 and cuts[-1] == 2


def test_oracle_indexed_application_matches_full_operator():
    """The matrix-free oracle this suite holds 12-qubit states to is the
    dense oracle's own arithmetic: equal to ``full_operator @ state`` at
    a size where that can be built."""
    rng = np.random.RandomState(7)
    n = 5
    psi = oracle.random_statevec(n, rng)
    for targets, controls, states in (((2,), (), None),
                                      ((0, 3), (1,), None),
                                      ((4, 1), (0, 2), [0, 1])):
        u = oracle.random_unitary(len(targets), rng)
        np.testing.assert_allclose(
            oracle.apply_to_statevec_indexed(psi, n, targets, u, controls,
                                             states),
            oracle.apply_to_statevec(psi, n, targets, u, controls, states),
            rtol=0, atol=1e-15)


def test_segment_cuts_greedy_coarsest_and_capped():
    c = _multi_item()
    tape, n = c._tape, 12
    bounds = set(segments.identity_boundaries(tape, n)) | {len(tape)}
    assert segments.segment_cuts(tape, n, None) == [0, len(tape)], \
        "unbounded cuts collapse to one whole-tape segment"
    for cap in (1, 2, 3):
        cuts = segments.segment_cuts(tape, n, cap)
        assert cuts[0] == 0 and cuts[-1] == len(tape)
        assert cuts == sorted(set(cuts))
        assert set(cuts) <= bounds
        for a, b in zip(cuts, cuts[1:]):
            # each span obeys the cap unless NO boundary splits it
            assert b - a <= cap or not any(
                a < x < b for x in bounds), (a, b, cap)
    with pytest.raises(ValueError, match="max_items"):
        segments.segment_cuts(tape, n, 0)


# ---------------------------------------------------------------------------
# plan stamps: codec roundtrip, plancheck QT107
# ---------------------------------------------------------------------------

def _frame_items(p):
    return [i for i in p.items
            if isinstance(i, (planner.PallasRun, planner.FrameSwap))]


def test_fused_stamps_segments_and_roundtrips():
    _need_mesh()
    fz = _sharded()
    p = fusion.plan_from_tape(tuple(fz._tape))
    items = _frame_items(p)
    assert items and all(isinstance(i.seg, int) for i in items)
    assert [i.seg for i in items] == sorted(i.seg for i in items), \
        "segment indices are monotone in plan order"
    p2 = fusion.plan_from_tape(fusion.as_tape(p))
    assert [i.seg for i in _frame_items(p2)] == [i.seg for i in items]


def _plan_multi():
    c = _multi_item()
    return fusion.plan_from_tape(tuple(c._tape))


def _codes(findings):
    return {f.code for f in findings}


def test_plancheck_accepts_stamped_plan():
    findings = A.check_plan(_plan_multi(), 12)
    assert not A.error_findings(findings), A.render_text(findings)


def test_plancheck_flags_corrupt_segment_stamp():
    plan = _plan_multi()
    items = _frame_items(plan)
    assert items
    mid = plan.items.index(items[len(items) // 2])
    plan.items[mid] = dataclasses.replace(
        plan.items[mid], seg=(plan.items[mid].seg or 0) + 7)
    assert "QT107" in _codes(A.error_findings(A.check_plan(plan, 12)))


def test_plancheck_skips_none_stamps():
    plan = _plan_multi()
    plan.items[:] = [                    # items no planner stamped
        dataclasses.replace(i, seg=None)
        if isinstance(i, (planner.PallasRun, planner.FrameSwap)) else i
        for i in plan.items]
    findings = A.check_plan(plan, 12)
    assert "QT107" not in _codes(findings)


# ---------------------------------------------------------------------------
# numeric contract: f32 / native f64 / df / 8-device mesh
# ---------------------------------------------------------------------------

def test_f32_segment_chain_contract():
    c = _multi_item(dtype=np.float32)
    assert len(c._tape) > 1
    a = _run_whole(c, ENV1, precision=1)
    assert np.array_equal(a, _run_whole(c, ENV1, precision=1)), \
        "the whole-tape program is deterministic"
    np.testing.assert_allclose(a, _oracle_planes(), rtol=0, atol=ATOL32)
    c1 = _run_chain(c, ENV1, cap=1, precision=1)
    assert np.array_equal(c1, _run_chain(c, ENV1, cap=1, precision=1)), \
        "a fixed segmentation is deterministic"
    np.testing.assert_allclose(c1, a, rtol=0, atol=ATOL32)
    np.testing.assert_allclose(c1, _oracle_planes(), rtol=0, atol=ATOL32)
    w = _run_chain(c, ENV1, cap=None, precision=1)
    assert np.array_equal(w, a), \
        "the whole-tape segment program IS Circuit.run's program"


def test_f64_native_segment_chain_contract():
    c = _multi_item(dtype=np.float64)
    a = _run_whole(c, ENV1)
    np.testing.assert_allclose(a, _oracle_planes(), rtol=0, atol=ATOL64)
    c1 = _run_chain(c, ENV1, cap=1)
    assert np.array_equal(c1, _run_chain(c, ENV1, cap=1))
    np.testing.assert_allclose(c1, a, rtol=0, atol=ATOL64)
    np.testing.assert_allclose(c1, _oracle_planes(), rtol=0, atol=ATOL64)
    # whole-tape segment program vs Circuit.compiled(): the SAME program
    # granularity, so bit-identity is exact even on XLA-CPU
    w = _run_chain(c, ENV1, cap=None)
    q = qt.createQureg(12, ENV1, precision_code=2)
    q.put(c.compiled()(q.amps))
    assert np.array_equal(w, np.asarray(jax.device_get(q.amps)))
    assert np.array_equal(w, a)


def test_df_route_segment_chain_contract(monkeypatch):
    """The df/f64 route. Compensated two-sum arithmetic is the MOST
    sensitive case for cross-program fma recontraction, and XLA-CPU does
    not keep it exact at all (ATOL_DF_CPU), so the exactness claims here
    are determinism and same-granularity identity; the chain and the
    whole-tape program agree, and sit from the oracle, within what df
    holds on this backend. The chip's 1e-12 is chip_smoke.py's df
    phase."""
    monkeypatch.setenv("QUEST_PALLAS_DF", "1")
    c = _multi_item(dtype=np.float64, sublanes=DF_SUBLANES)
    a = _run_whole(c, ENV1)
    np.testing.assert_allclose(a, _oracle_planes(), rtol=0,
                               atol=ATOL_DF_CPU)
    w = _run_chain(c, ENV1, cap=None)
    assert np.array_equal(w, _run_chain(c, ENV1, cap=None))
    assert np.array_equal(w, a)
    c1 = _run_chain(c, ENV1, cap=1)
    assert np.array_equal(c1, _run_chain(c, ENV1, cap=1))
    np.testing.assert_allclose(c1, a, rtol=0, atol=ATOL_DF_CPU)
    np.testing.assert_allclose(c1, _oracle_planes(), rtol=0,
                               atol=ATOL_DF_CPU)


@pytest.mark.parametrize("explicit", [False, True],
                         ids=["gspmd", "explicit"])
def test_mesh8_segment_chain_contract(explicit):
    _need_mesh()
    fz = _sharded()
    assert len(fz._tape) > 1
    a = _run_whole(fz, ENV8, explicit=explicit)
    np.testing.assert_allclose(a, _oracle_planes(), rtol=0, atol=ATOL64)
    w = _run_chain(fz, ENV8, cap=None, explicit=explicit)
    assert np.array_equal(
        w, _run_chain(fz, ENV8, cap=None, explicit=explicit))
    np.testing.assert_allclose(w, a, rtol=0, atol=ATOL64)
    c1 = _run_chain(fz, ENV8, cap=1, explicit=explicit)
    np.testing.assert_allclose(c1, a, rtol=0, atol=ATOL64)


# ---------------------------------------------------------------------------
# dispatch accounting: ONE launch per segment program
# ---------------------------------------------------------------------------

def test_run_slice_single_dispatch_per_segment():
    c = _multi_item()
    q = qt.createQureg(12, ENV1, precision_code=2)
    telemetry.reset()
    segments.run_slice(c, q)
    assert telemetry.counter_value(
        "device_dispatch_total", route="segment") == 1.0


def test_chain_counts_num_segments():
    c = _multi_item()
    fn = c.compiled_segments(max_items=2)
    whole = c.compiled_segments()
    assert whole.num_segments == 1
    assert fn.num_segments >= 2
    q = qt.createQureg(12, ENV1, precision_code=2)
    telemetry.reset()
    q.put(fn(q.amps))
    assert telemetry.counter_value(
        "device_dispatch_total", route="segment") == fn.num_segments


def test_circuit_run_counts_circuit_route():
    c = _circuit(6)
    q = qt.createQureg(6, ENV1, precision_code=2)
    telemetry.reset()
    c.run(q)
    assert telemetry.counter_value(
        "device_dispatch_total", route="circuit") == 1.0


def test_run_segmented_counts_segment_dispatches(tmp_path):
    c = _multi_item()
    cuts = segmented.segment_plan(c._tape, 12, 1)
    telemetry.reset()
    out = c.run_segmented(ENV1, checkpoint_dir=str(tmp_path / "seg"),
                          every_n_items=1)
    assert telemetry.counter_value(
        "device_dispatch_total", route="segment") == len(cuts) - 1
    np.testing.assert_allclose(np.asarray(out.amps), _run_whole(c, ENV1),
                               rtol=0, atol=ATOL64)
    np.testing.assert_allclose(np.asarray(out.amps), _oracle_planes(),
                               rtol=0, atol=ATOL64)


def test_engine_dispatch_counters():
    cp = Circuit(4)
    for q in range(4):
        cp.hadamard(q)
    cp.rotateY(0, P("a"))
    cp.rotateY(1, P("b"))
    with Engine(cp, ENV1, max_batch=4, max_delay_ms=0.0) as eng:
        eng.warmup()
        v0 = telemetry.counter_value("device_dispatch_total",
                                     route="engine_vmap")
        futs = eng.submit_many([{"a": 0.1 * i, "b": 0.2 * i}
                                for i in range(1, 5)])
        [f.result() for f in futs]
        assert telemetry.counter_value(
            "device_dispatch_total", route="engine_vmap") > v0
    cv = Circuit(3)
    cv.hadamard(0)
    cv.controlledNot(0, 1)
    with Engine(cv, ENV1, max_batch=4, max_delay_ms=0.0) as eng:
        p0 = telemetry.counter_value("device_dispatch_total",
                                     route="engine_param")
        [f.result() for f in eng.submit_many([None] * 4)]
        assert telemetry.counter_value(
            "device_dispatch_total", route="engine_param") > p0


def test_replay_slice_rejects_lifted_params():
    c = _circuit(4)
    with pytest.raises(ValueError, match="lifted"):
        c._replay_fn(object(), lo=1)


# ---------------------------------------------------------------------------
# scheduler journal: ("segment", lo) markers + check_schedule
# ---------------------------------------------------------------------------

def test_begin_defer_journals_segment_marker():
    from jax.sharding import AbstractMesh
    from quest_tpu.environment import AMP_AXIS
    from quest_tpu.parallel import scheduler as S
    sched = S.DistributedScheduler(mesh=AbstractMesh((8,), (AMP_AXIS,)))
    sched.journal = []
    assert sched.begin_defer(segment=5)
    segs = [rec for rec in sched.journal if rec[0] == "segment"]
    assert segs == [("segment", 5)]
    # nested begin_defer (already deferring) must not duplicate markers
    assert not sched.begin_defer(segment=6)
    assert [rec for rec in sched.journal if rec[0] == "segment"] == segs
    sched.abort_defer()


def test_check_schedule_validates_segment_records():
    import bench
    from jax.sharding import AbstractMesh
    from quest_tpu.environment import AMP_AXIS
    mesh8 = AbstractMesh((8,), (AMP_AXIS,))
    findings, stats, journal = A.check_circuit_comm(
        bench.build_circuit(20, 4), mesh8)
    assert findings == []
    # a valid zero-cost marker at the start of the schedule stays clean
    ok = [journal[0], ("segment", 0)] + list(journal[1:])
    assert not A.error_findings(
        A.check_schedule(ok, stats, 20, mesh8))
    # a malformed cursor is QT107
    bad = [journal[0], ("segment", -3)] + list(journal[1:])
    assert "QT107" in _codes(A.error_findings(
        A.check_schedule(bad, stats, 20, mesh8)))
