"""Single-dispatch segment programs (quest_tpu.segments, round 13).

What this suite pins down:

- frame-identity boundaries: ``identity_boundaries`` finds the legal
  segment seams of a fused plan (starts at 0, ends at len(tape)), and
  tolerates every tape-codec generation (the pre-round-13
  ``resilience.segmented`` replay unpacked FrameSwap args as an exact
  3-tuple and crashed on PR 8's 4-arg comm_pipeline-stamped entries --
  regression-tested here);
- ``segment_cuts`` greedy coarsest capping: cuts are identity
  boundaries, spans respect ``max_items`` unless a single
  boundary-to-boundary gap is longer, ``max_items < 1`` rejects;
- the ``seg`` plan stamp: ``Circuit.fused`` stamps every frame-carrying
  item with its segment index, the stamps survive the tape codec
  roundtrip, pre-round-13 (and pre-round-8) tapes decode ``seg=None``,
  plancheck re-derives the segmentation and flags corrupted stamps as
  QT107 (None stamps are skipped -- compat, not an error);
- the numeric contract of the two execution routes (module docstring of
  quest_tpu.segments): a fixed segmentation is run-to-run DETERMINISTIC
  (bit-identical) on every leg; the whole-tape segment program is
  bit-identical to ``Circuit.compiled()``; on a single device the
  native-dtype per-item chain (``compiled_segments(max_items=1)``)
  reproduces item-by-item interpretation bit-for-bit. ACROSS program
  granularities XLA-CPU contracts fma differently per compiled program
  (the documented tests/test_sharded_df.py caveat -- on the df route
  and the CPU mesh even single items embed differently), so those
  comparisons are asserted at ~ulp allclose, not array_equal; on TPU
  the Mosaic kernel is opaque to recontraction and the routes coincide;
- one ``device_dispatch_total{route="segment"}`` per segment program
  launch, one ``route="item"`` per eagerly interpreted entry, the
  engine's ``engine_vmap``/``engine_param`` sites, and run_segmented's
  per-segment accounting;
- the QUEST_SEGMENT_DISPATCH env knob: warn-once QT306 on malformed
  values, 0 restores the per-item route, ``force_route`` outranks the
  env for A/B harnesses;
- sliced replays journal zero-cost ("segment", lo) markers under the
  explicit scheduler and check_schedule validates them (bad cursor ->
  QT107; mid-layout seam -> QT104).
"""

import contextlib
import warnings

import numpy as np
import pytest

import jax

import quest_tpu as qt
from quest_tpu import analysis as A
from quest_tpu import fusion, segments, telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.engine import Engine, P
from quest_tpu.ops import pallas_gates as PG
from quest_tpu.ops.pallas_df import DF_SUBLANES
from quest_tpu.resilience import segmented

if np.dtype(qt.precision.real_dtype()) != np.dtype("float64"):
    pytest.skip("segments suite needs QUEST_PRECISION=2 (the conftest "
                "default)", allow_module_level=True)

ENV8 = qt.createQuESTEnv()
ENV1 = qt.createQuESTEnv(jax.devices()[:1])

# 1-2 ulp headroom on ~2^-6-scale amplitudes: the cross-program fma
# recontraction band (see module docstring), NOT an accuracy tolerance
ATOL64 = 5e-15
ATOL32 = 2e-6


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
    p = fusion.plan(tuple(c._tape), n, np.dtype(dtype), max_qubits=3,
                    pallas_tile_bits=PG.local_qubits(n, sublanes))
    segments.stamp_plan(p, n)
    out = Circuit(n)
    out._tape = fusion.as_tape(p)
    return out


def _sharded(n=12):
    return _circuit(n).fused(max_qubits=3, pallas=True, shard_devices=8)


def _run_item(circ, env, precision=2, explicit=False):
    q = qt.createQureg(circ.num_qubits, env, precision_code=precision)
    ctx = qt.explicit_mesh(env.mesh) if explicit \
        else contextlib.nullcontext()
    with ctx, segments.force_route("item"):
        segments.run_slice(circ, q)
    return np.asarray(jax.device_get(q.amps))


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


def test_identity_boundaries_tolerate_extended_codec_args():
    """Regression: the pre-round-13 boundary replay in
    resilience.segmented unpacked FrameSwap args as an exact 3-tuple
    (``tb, k, hi = a``) and raised ValueError on the 4-arg
    comm_pipeline-stamped entries PR 8 started emitting. The shared
    ``identity_boundaries`` slice-unpacks, so 3/4/5-arg (and future)
    codec generations all replay."""
    tb = 9
    for extra in ((), (None,), (None, 0)):        # pre-8 / 8-12 / 13+
        tape = [(fusion._apply_frame_swap, (tb, 2, None) + extra, {}),
                (fusion._apply_frame_swap, (tb, 2, None) + extra, {})]
        assert segments.identity_boundaries(tape, 12) == [0, 2]
        # the resilience checkpoint planner rides the same replay
        cuts = segmented.segment_plan(tape, 12, 1)
        assert cuts[0] == 0 and cuts[-1] == 2


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
# plan stamps: codec roundtrip, old tapes, plancheck QT107
# ---------------------------------------------------------------------------

def _frame_items(p):
    return [i for i in p.items
            if isinstance(i, (fusion.PallasRun, fusion.FrameSwap))]


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


def test_old_tapes_decode_seg_none():
    _need_mesh()
    p = fusion.plan_from_tape(tuple(_sharded()._tape))
    # pre-round-13 (8-arg PallasRun / 4-arg FrameSwap) and pre-round-8
    # (7-arg / 3-arg) tapes must decode seg=None -- never a crash, never
    # a fabricated segment index
    for run_n, swap_n in ((8, 4), (7, 3)):
        old = []
        for fn, a, kw in fusion.as_tape(p):
            if getattr(fn, "__name__", "") == "_apply_pallas_run":
                a = a[:run_n]
            elif getattr(fn, "__name__", "") == "_apply_frame_swap":
                a = a[:swap_n]
            old.append((fn, a, kw))
        p2 = fusion.plan_from_tape(old)
        assert all(i.seg is None for i in _frame_items(p2))


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
    items[len(items) // 2].seg = (items[len(items) // 2].seg or 0) + 7
    assert "QT107" in _codes(A.error_findings(A.check_plan(plan, 12)))


def test_plancheck_skips_none_stamps():
    plan = _plan_multi()
    for i in _frame_items(plan):
        i.seg = None                     # a pre-round-13 tape, decoded
    findings = A.check_plan(plan, 12)
    assert "QT107" not in _codes(findings)


# ---------------------------------------------------------------------------
# numeric contract: f32 / native f64 / df / 8-device mesh
# ---------------------------------------------------------------------------

def test_f32_segment_chain_contract():
    c = _multi_item(dtype=np.float32)
    assert len(c._tape) > 1
    a = _run_item(c, ENV1, precision=1)
    assert np.array_equal(a, _run_item(c, ENV1, precision=1)), \
        "the item route is deterministic"
    c1 = _run_chain(c, ENV1, cap=1, precision=1)
    assert np.array_equal(c1, _run_chain(c, ENV1, cap=1, precision=1)), \
        "a fixed segmentation is deterministic"
    np.testing.assert_allclose(c1, a, rtol=0, atol=ATOL32)
    w = _run_chain(c, ENV1, cap=None, precision=1)
    assert np.array_equal(w, _run_chain(c, ENV1, cap=None, precision=1))
    np.testing.assert_allclose(w, a, rtol=0, atol=ATOL32)


def test_f64_native_segment_chain_contract():
    c = _multi_item(dtype=np.float64)
    a = _run_item(c, ENV1)
    c1 = _run_chain(c, ENV1, cap=1)
    assert np.array_equal(c1, _run_chain(c, ENV1, cap=1))
    np.testing.assert_allclose(c1, a, rtol=0, atol=ATOL64)
    # whole-tape segment program vs Circuit.compiled(): the SAME program
    # granularity, so bit-identity is exact even on XLA-CPU
    w = _run_chain(c, ENV1, cap=None)
    q = qt.createQureg(12, ENV1, precision_code=2)
    q.put(c.compiled()(q.amps))
    assert np.array_equal(w, np.asarray(jax.device_get(q.amps)))
    np.testing.assert_allclose(w, a, rtol=0, atol=ATOL64)


def test_df_route_segment_chain_contract(monkeypatch):
    """The df/f64 route. Compensated two-sum arithmetic is the MOST
    sensitive case for cross-program fma recontraction (even a 1-item
    tape embeds differently eager vs in-program on XLA-CPU), so the
    exactness claims here are determinism and same-granularity
    identity; route agreement is ~1 ulp (test_sharded_df caveat)."""
    monkeypatch.setenv("QUEST_PALLAS_DF", "1")
    c = _multi_item(dtype=np.float64, sublanes=DF_SUBLANES)
    a = _run_item(c, ENV1)
    w = _run_chain(c, ENV1, cap=None)
    assert np.array_equal(w, _run_chain(c, ENV1, cap=None))
    np.testing.assert_allclose(w, a, rtol=0, atol=ATOL64)
    c1 = _run_chain(c, ENV1, cap=1)
    assert np.array_equal(c1, _run_chain(c, ENV1, cap=1))
    np.testing.assert_allclose(c1, a, rtol=0, atol=ATOL64)


@pytest.mark.parametrize("explicit", [False, True],
                         ids=["gspmd", "explicit"])
def test_mesh8_segment_chain_contract(explicit):
    _need_mesh()
    fz = _sharded()
    assert len(fz._tape) > 1
    a = _run_item(fz, ENV8, explicit=explicit)
    w = _run_chain(fz, ENV8, cap=None, explicit=explicit)
    assert np.array_equal(
        w, _run_chain(fz, ENV8, cap=None, explicit=explicit))
    np.testing.assert_allclose(w, a, rtol=0, atol=ATOL64)


# ---------------------------------------------------------------------------
# dispatch accounting: ONE launch per segment program
# ---------------------------------------------------------------------------

def test_run_slice_single_dispatch_per_segment():
    c = _multi_item()
    q = qt.createQureg(12, ENV1, precision_code=2)
    telemetry.reset()
    with segments.force_route("segment"):
        segments.run_slice(c, q)
    assert telemetry.counter_value(
        "device_dispatch_total", route="segment") == 1.0
    assert telemetry.counter_value(
        "device_dispatch_total", route="item") == 0.0


def test_item_route_counts_every_entry():
    c = _multi_item()
    q = qt.createQureg(12, ENV1, precision_code=2)
    telemetry.reset()
    with segments.force_route("item"):
        segments.run_slice(c, q)
    assert telemetry.counter_value(
        "device_dispatch_total", route="item") == len(c._tape)
    assert telemetry.counter_value(
        "device_dispatch_total", route="segment") == 0.0


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
    with segments.force_route("segment"):
        out = c.run_segmented(ENV1, checkpoint_dir=str(tmp_path / "seg"),
                              every_n_items=1)
    assert telemetry.counter_value(
        "device_dispatch_total", route="segment") == len(cuts) - 1
    ref = qt.createQureg(12, ENV1, precision_code=2)
    with segments.force_route("item"):
        segments.run_slice(c, ref)
    np.testing.assert_allclose(np.asarray(out.amps), np.asarray(ref.amps),
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


# ---------------------------------------------------------------------------
# QUEST_SEGMENT_DISPATCH env knob + force_route
# ---------------------------------------------------------------------------

@pytest.fixture
def seg_env(monkeypatch):
    monkeypatch.setattr(segments, "_SEG_ENV_WARNED", set())
    return monkeypatch


def test_seg_env_non_integer_warns_once_and_defaults(seg_env):
    seg_env.setenv(segments._SEG_ENV, "turbo")
    telemetry.reset()
    with pytest.warns(RuntimeWarning, match="QT306"):
        assert segments.segment_dispatch_default() == 1
    assert telemetry.counter_value(
        "analysis_findings_total", code="QT306", severity="warning") == 1.0
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # second call must stay silent
        assert segments.segment_dispatch_default() == 1


def test_seg_env_zero_restores_item_route(seg_env):
    seg_env.setenv(segments._SEG_ENV, "0")
    assert segments.segment_dispatch_default() == 0
    assert not segments.segment_dispatch_enabled()
    c = _multi_item()
    q = qt.createQureg(12, ENV1, precision_code=2)
    telemetry.reset()
    segments.run_slice(c, q)
    assert telemetry.counter_value(
        "device_dispatch_total", route="item") == len(c._tape)
    assert telemetry.counter_value(
        "device_dispatch_total", route="segment") == 0.0


def test_force_route_overrides_env(seg_env):
    seg_env.setenv(segments._SEG_ENV, "0")
    with segments.force_route("segment"):
        assert segments.segment_dispatch_enabled()
        with segments.force_route(None):
            assert not segments.segment_dispatch_enabled()
    assert not segments.segment_dispatch_enabled()
    with pytest.raises(ValueError, match="route"):
        with segments.force_route("warp"):
            pass


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
