"""Async dispatch pipeline (round 18, quest_tpu/engine/engine.py
completion ring + quest_tpu/segments.py whole-request chaining +
quest_tpu/engine/pool.py ahead-of-demand precompiler).

Contracts under test:

- the completion-ring route (``async_depth >= 1``) is BIT-IDENTICAL to
  the true-synchronous baseline (``async_depth=0``) -- retirement runs
  the same lane-extraction / sentinel / resolve path a synchronous
  dispatch used;
- ring accounting: retires count ``engine_async_retires_total{outcome}``,
  the ring drains on ``close(drain=True)``, and ``async_depth=0`` never
  touches the ring;
- both serial-issue resolve policies serve identically: deferred
  resolution (spare host core: sync at admission, resolve at post-issue
  settle) and resolve-before-issue (single-core), plus the
  stream-ordered (non-serial) mode;
- ``QUEST_ASYNC_DEPTH`` parses through the shared env-int path: warn
  ONCE per malformed value as QT310, fall back to the default of 2,
  clamp negatives to 0;
- an injected retire-stage hang fails exactly the retired batch typed
  (QuESTHangError) while its ring neighbour still serves bit-identically
  (fault ATTRIBUTION across the issue/retire split);
- ``Circuit.compiled_request`` launches exactly ONE device program
  (``device_dispatch_total{route="request"}``) per call --
  ``dispatches_per_circuit == 1`` -- run-to-run bit-identical and ~1 ulp
  from the item route (the documented segments.py caveat);
- ``EnginePool.precompile`` warms cold replicas off the request path and
  counts every (fingerprint, replica) outcome
  (``engine_precompile_total{outcome=warmed|cached|error}``);
- ``tracecheck.phase_coverage`` counts overlapped phase windows ONCE
  (the async dispatch/device overlap rule) and ``check_phase_tiling``
  flags only genuinely gappy or double-counted traces (QT704).
"""

import os
import warnings

import numpy as np
import pytest

import jax

import quest_tpu as qt
from quest_tpu import telemetry
from quest_tpu.analysis import tracecheck
from quest_tpu.circuits import Circuit
from quest_tpu.engine import Engine, P
from quest_tpu.engine import engine as engmod
from quest_tpu.engine.pool import EnginePool
from quest_tpu.resilience import fault_plan, watchdog_deadline
from quest_tpu.resilience.errors import QuESTCancelledError, QuESTHangError

ENV1 = qt.createQuESTEnv(jax.devices()[:1])


def _param_circuit(n=3):
    c = Circuit(n)
    c.hadamard(0)
    c.controlledNot(0, 1)
    c.rotateX(n - 1, P("t"))
    c.rotateZ(0, P("u"))
    return c


def _sweep(k):
    return [{"t": 0.1 * i, "u": -0.05 * i} for i in range(k)]


def _serve(eng, params_list, timeout=120):
    return [np.asarray(f.result(timeout))
            for f in eng.submit_many(params_list)]


# ---------------------------------------------------------------------------
# ring bit-identity + accounting
# ---------------------------------------------------------------------------

def test_async_vs_sync_bit_identity():
    circ, plist = _param_circuit(), _sweep(12)
    outs = {}
    for depth in (2, 0):
        eng = Engine(circ, ENV1, max_batch=4, max_delay_ms=0.0,
                     async_depth=depth)
        eng.run(plist[0])  # warm: the compared streams are pure replay
        outs[depth] = _serve(eng, plist)
        eng.close()
    assert all(np.array_equal(a, b)
               for a, b in zip(outs[2], outs[0]))


def test_ring_retires_counted_and_drained():
    telemetry.reset()
    eng = Engine(_param_circuit(), ENV1, max_batch=4, max_delay_ms=0.0,
                 async_depth=2)
    eng.run(_sweep(1)[0])
    _serve(eng, _sweep(8))  # two pipelined batches of 4
    eng.close(drain=True)
    assert not eng._ring
    assert telemetry.counter_value(
        "engine_async_retires_total", outcome="ok") >= 2


def test_depth_zero_never_rings():
    telemetry.reset()
    eng = Engine(_param_circuit(), ENV1, max_batch=4, max_delay_ms=0.0,
                 async_depth=0)
    eng.run(_sweep(1)[0])
    _serve(eng, _sweep(8))
    eng.close()
    assert telemetry.counter_value("engine_async_retires_total",
                                   outcome="ok") == 0


def test_close_nodrain_cancels_or_serves_typed():
    eng = Engine(_param_circuit(), ENV1, max_batch=4, max_delay_ms=0.0,
                 async_depth=2)
    eng.run(_sweep(1)[0])
    futs = eng.submit_many(_sweep(8))
    eng.close(drain=False)
    for f in futs:
        try:
            np.asarray(f.result(120))
        except QuESTCancelledError:
            pass  # queued-then-dropped is a legal typed outcome
    assert not eng._ring


# ---------------------------------------------------------------------------
# the serial-issue / spare-core scheduling policies
# ---------------------------------------------------------------------------

def test_issue_serial_on_cpu_and_spare_core_probe():
    eng = Engine(_param_circuit(), ENV1, max_batch=4, async_depth=2)
    try:
        assert eng._issue_serial() is True  # XLA:CPU timeshares cores
        assert eng._spare_core() == ((os.cpu_count() or 1) > 1)
        eng._cores = 1
        assert eng._spare_core() is False
        eng._cores = 8
        assert eng._spare_core() is True
    finally:
        eng.close()


@pytest.mark.parametrize("policy", ["defer", "resolve_early", "streamed"])
def test_resolve_policies_bit_identical(policy, monkeypatch):
    """All three scheduling modes run the same retirement path: deferred
    resolution (sync at admission, resolve at the post-issue settle),
    resolve-before-issue (single-core), and stream-ordered issue (no
    admission sync at all -- the TPU/GPU shape, emulated here)."""
    circ, plist = _param_circuit(), _sweep(12)
    ref = Engine(circ, ENV1, max_batch=4, max_delay_ms=0.0, async_depth=0)
    ref.run(plist[0])
    want = _serve(ref, plist)
    ref.close()

    eng = Engine(circ, ENV1, max_batch=4, max_delay_ms=0.0, async_depth=2)
    if policy == "defer":
        monkeypatch.setattr(eng, "_spare_core", lambda: True)
    elif policy == "resolve_early":
        monkeypatch.setattr(eng, "_spare_core", lambda: False)
    else:
        eng._serial = False  # stream-ordered backend: depth alone bounds
    eng.run(plist[0])
    got = _serve(eng, plist)
    eng.close(drain=True)
    assert not eng._ring
    assert all(np.array_equal(a, b) for a, b in zip(want, got))


# ---------------------------------------------------------------------------
# QT310: the QUEST_ASYNC_DEPTH knob
# ---------------------------------------------------------------------------

def test_qt310_warns_once_and_defaults(monkeypatch):
    monkeypatch.setattr(engmod, "_ASYNC_ENV_WARNED", set())
    monkeypatch.setenv("QUEST_ASYNC_DEPTH", "lots")
    telemetry.reset()
    with pytest.warns(RuntimeWarning, match="QT310"):
        assert engmod.async_depth_default() == 2
    assert telemetry.counter_value(
        "analysis_findings_total", code="QT310", severity="warning") == 1.0
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # second read must stay silent
        assert engmod.async_depth_default() == 2


def test_qt310_negative_clamps_to_synchronous(monkeypatch):
    monkeypatch.setattr(engmod, "_ASYNC_ENV_WARNED", set())
    monkeypatch.setenv("QUEST_ASYNC_DEPTH", "-3")
    with pytest.warns(RuntimeWarning, match="QT310"):
        assert engmod.async_depth_default() == 0


def test_env_depth_wellformed_applies(monkeypatch):
    monkeypatch.setenv("QUEST_ASYNC_DEPTH", "3")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert engmod.async_depth_default() == 3
    eng = Engine(_param_circuit(), ENV1, max_batch=2)
    try:
        assert eng.async_depth == 3
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# fault attribution across the issue/retire split
# ---------------------------------------------------------------------------

def test_retire_hang_fails_only_the_retired_batch():
    circ, plist = _param_circuit(), _sweep(8)
    oracle = Engine(circ, ENV1, max_batch=4, max_delay_ms=0.0,
                    async_depth=0)
    oracle.run(plist[0])
    want = _serve(oracle, plist)
    oracle.close()

    eng = Engine(circ, ENV1, max_batch=4, max_delay_ms=0.0, async_depth=2)
    eng.run(plist[0])
    with watchdog_deadline(200), fault_plan("engine.retire:hang:1"):
        futs = eng.submit_many(plist)
        served, hung = {}, []
        for i, f in enumerate(futs):
            try:
                served[i] = np.asarray(f.result(120))
            except QuESTHangError:
                hung.append(i)
    eng.close()
    assert len(hung) == 4, f"exactly one batch of 4 must hang, got {hung}"
    assert len(served) == 4
    for i, g in served.items():
        assert np.array_equal(want[i], g), \
            f"lane {i} diverged next to the hung retire"


# ---------------------------------------------------------------------------
# whole-request chaining: the dispatches_per_circuit == 1 floor
# ---------------------------------------------------------------------------

def test_compiled_request_single_dispatch_bit_identical():
    from quest_tpu.ops import init as ops_init

    from . import oracle

    n = 3
    conc = Circuit(n)
    conc.hadamard(0)
    conc.rotateZ(1, 0.37)
    conc.controlledNot(0, 2)
    conc.rotateX(2, -0.8)
    fnR = conc.compiled_request(donate=False)
    amps0 = ops_init.init_classical(1 << n, np.dtype(np.complex64), 0)
    fnR(amps0 + 0).block_until_ready()  # compile outside the counted call
    d0 = telemetry.counter_value("device_dispatch_total", route="request")
    out = fnR(amps0 + 0)
    out.block_until_ready()
    assert telemetry.counter_value(
        "device_dispatch_total", route="request") - d0 == 1
    assert fnR.num_segments >= 1
    # run-to-run bit-identity of the one chained program
    assert np.array_equal(np.asarray(out), np.asarray(fnR(amps0 + 0)))
    # ~1 ulp agreement across program granularities (segments.py caveat):
    # against the whole-tape program Circuit.run dispatches ...
    qreg = qt.createQureg(n, ENV1)
    conc.run(qreg)
    assert np.allclose(np.asarray(out), np.asarray(qreg.amps),
                       rtol=1e-5, atol=1e-6)
    # ... and, both being lowerings of one tape, against the dense oracle
    psi = np.zeros(1 << n, dtype=np.complex128)
    psi[0] = 1.0
    half = lambda t: (np.cos(t / 2), np.sin(t / 2))  # noqa: E731
    cz, sz = half(0.37)
    cx, sx = half(-0.8)
    for targets, m, controls in (
            ((0,), np.array([[1, 1], [1, -1]]) / np.sqrt(2), ()),
            ((1,), np.diag([cz - 1j * sz, cz + 1j * sz]), ()),
            ((2,), np.array([[0, 1], [1, 0]]), (0,)),
            ((2,), np.array([[cx, -1j * sx], [-1j * sx, cx]]), ())):
        psi = oracle.apply_to_statevec(psi, n, targets, m, controls)
    assert np.allclose(np.asarray(out), np.stack([psi.real, psi.imag]),
                       rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# ahead-of-demand compilation
# ---------------------------------------------------------------------------

def test_precompile_outcomes(monkeypatch):
    circ = _param_circuit()
    pool = EnginePool(replicas=2, spawn_replacements=False, hedge_ms=0,
                      max_batch=2, max_delay_ms=0.0)
    try:
        np.asarray(pool.submit(circ, _sweep(1)[0]).result(120))
        telemetry.reset()
        # the serving replica holds a live executable -> cached; the
        # cold peer compiles ahead of demand -> warmed
        done = pool.precompile()
        assert done == [circ.fingerprint()]
        assert telemetry.counter_value(
            "engine_precompile_total", outcome="cached") == 1
        assert telemetry.counter_value(
            "engine_precompile_total", outcome="warmed") == 1
        # both replicas warm now: a second pass is all-cached
        telemetry.reset()
        pool.precompile()
        assert telemetry.counter_value(
            "engine_precompile_total", outcome="cached") == 2
        # a failing warm attempt counts error and spares the request path
        telemetry.reset()
        monkeypatch.setattr(Engine, "warmup",
                            lambda self: 1 / 0)
        monkeypatch.setattr(engmod.Engine, "_mode", lambda self: "vmap")
        from quest_tpu import cache as _ec
        monkeypatch.setattr(_ec.executables(), "peek",
                            lambda key: None)
        assert pool.precompile() == []
        assert telemetry.counter_value(
            "engine_precompile_total", outcome="error") == 2
    finally:
        pool.close(drain=False)


# ---------------------------------------------------------------------------
# QT704: overlap-aware phase tiling
# ---------------------------------------------------------------------------

def _trace(dur, spans=None, phases=None):
    tr = {"trace_id": "t1", "dur_ms": dur}
    if spans is not None:
        tr["spans"] = [{"cat": "phase", "name": n, "t0_ms": a,
                        "dur_ms": b - a} for n, a, b in spans]
    if phases is not None:
        tr["phases_ms"] = dict(phases)
    return tr


def test_phase_coverage_counts_overlap_once():
    # dispatch [0,60] overlaps device [40,100]: union covers all 100ms
    tr = _trace(100.0, spans=[("dispatch", 0.0, 60.0),
                              ("device", 40.0, 100.0)])
    assert tracecheck.phase_coverage(tr) == pytest.approx(1.0)
    # the span-less fallback is the plain (overlap-blind) ratio
    tr2 = _trace(100.0, phases={"dispatch": 60.0, "device": 60.0})
    assert tracecheck.phase_coverage(tr2) == pytest.approx(1.2)


def test_qt704_flags_gaps_not_overlap():
    full = {p: 1.0 for p in tracecheck.PHASES}
    overlapped = _trace(100.0, spans=[("dispatch", 0.0, 60.0),
                                      ("device", 40.0, 100.0)],
                        phases=full)
    gappy = _trace(100.0, spans=[("dispatch", 0.0, 20.0),
                                 ("device", 30.0, 50.0)],
                   phases=full)
    partial = _trace(100.0, spans=[("dispatch", 0.0, 10.0)],
                     phases={"dispatch": 10.0})  # not a full vector
    finds = tracecheck.check_phase_tiling([overlapped, gappy, partial])
    assert len(finds) == 1
    assert finds[0].code == "QT704"
    assert "40.0%" in finds[0].message
