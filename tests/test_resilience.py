"""Resilience layer (ISSUE 7 + 8): fault injection, retry/backoff,
poisoned-request isolation, preemption-safe segmented execution, and the
integrity-sentinel / self-healing machinery.

Contracts under test, mirroring the failure-mode table in
docs/resilience.md:

- with ``QUEST_FAULTS`` unset every injection site is a no-op: zero new
  ``engine_fallback_total`` entries, zero retry series;
- a transient Pallas/collective fault retries and the recovered run is
  BIT-IDENTICAL to the clean run; a compile fault degrades along the
  existing fallback lattice (``engine_fallback_total{reason=
  fault_degraded}``) and matches the eager oracle;
- a poisoned request in a batch is isolated by bisection: its future
  fails typed, its neighbors complete bit-identically to solo replays;
- request deadlines and the bounded queue fail closed with
  QuESTTimeoutError / QuESTBackpressureError;
- a segmented run checkpoints at frame-identity boundaries, and an
  injected mid-plan preemption + resume is bit-identical to the
  uninterrupted run (8-device mesh, f32 and double-float routes);
- resume rejects corrupt generations (QT305) and falls back to the
  previous verified one (a CRC-divergent shard counts
  ``outcome=skipped_corrupt`` with both CRC32s in the finding);
- an injected single-bit flip is detected within one sentinel cadence
  (norm AND per-shard checksum, QT402 naming the shard), rolled back and
  replayed BIT-IDENTICAL on the 8-device mesh, f32 and df routes; a
  breach the lattice cannot clear fails closed (QuESTIntegrityError);
- an injected hang raises a typed QuESTHangError within the
  ``QUEST_WATCHDOG_MS`` deadline (QT405) and quarantines the engine; a
  quarantined engine sheds load via backpressure until ``revive()``;
- with no sentinel policy armed every probe point is a no-op: zero
  sentinel/rollback/watchdog series.
"""

import os
import threading
import time

import numpy as np
import pytest

import jax

import quest_tpu as qt
from quest_tpu import telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.resilience import (
    FaultPlan, QuESTBackpressureError, QuESTHangError, QuESTIntegrityError,
    QuESTPreemptionError, QuESTRetryError, QuESTTimeoutError, RetryPolicy,
    SentinelPolicy, call_with_retry, fault_plan, faultinject,
    resume_segmented, segment_plan, sentinel, sentinel_policy, watchdog,
    watchdog_deadline,
)
from quest_tpu.resilience.errors import (
    KernelCompileFault, PoisonedRequestFault, TransientFault,
)
from quest_tpu.validation import QuESTError

ENV = qt.createQuESTEnv(jax.devices()[:1])
ENV8 = qt.createQuESTEnv(jax.devices()[:8])


def _ghz_plus(n):
    c = Circuit(n)
    for q in range(n):
        c.hadamard(q)
    for q in range(n - 1):
        c.controlledNot(q, q + 1)
    for q in range(n):
        c.tGate(q)
        c.rotateZ(q, 0.1 + 0.05 * q)
    return c


# -- fault-plan parsing and the disabled path -------------------------------

def test_fault_plan_parse_nth_and_from_on():
    p = FaultPlan.parse("pallas.dispatch:transient:2,"
                        "exchange.collective:transient:1+")
    assert len(p.specs) == 2
    s0, s1 = p.specs
    assert (s0.site, s0.kind, s0.nth, s0.from_nth_on) == \
        ("pallas.dispatch", "transient", 2, False)
    assert s1.from_nth_on and s1.nth == 1
    assert not s0.matches(1) and s0.matches(2) and not s0.matches(3)
    assert s1.matches(1) and s1.matches(7)


def test_fault_plan_malformed_entries_skipped_with_qt302():
    telemetry.reset()
    p = FaultPlan.parse("nosite:transient:1,pallas.dispatch:nokind:1,"
                        "pallas.dispatch:transient:0,short,"
                        "engine.request:poison:3")
    assert len(p.specs) == 1  # only the last entry is valid
    assert telemetry.counter_value("analysis_findings_total",
                                   code="QT302", severity="warning") == 4
    with pytest.raises(QuESTError, match="QT302"):
        FaultPlan.parse("nosite:transient:1", strict=True)


def test_fault_plan_visit_counting_is_deterministic():
    with fault_plan("engine.request:poison:2") as plan:
        assert faultinject.fire("engine.request") is None
        assert faultinject.fire("engine.request") == "poison"
        assert faultinject.fire("engine.request") is None
        assert plan.visits("engine.request") == 3
    # context exit restores the disabled state
    assert faultinject.fire("engine.request") is None


def test_env_var_plan_loads_once(monkeypatch):
    monkeypatch.setattr(faultinject, "_active", None)
    monkeypatch.setattr(faultinject, "_env_read", False)
    monkeypatch.setenv("QUEST_FAULTS", "segment.boundary:preempt:1")
    assert faultinject.enabled()
    plan = faultinject.active_plan()
    assert plan.specs[0].site == "segment.boundary"
    faultinject.clear()
    assert not faultinject.enabled()


def test_disabled_sites_are_noops_and_add_zero_fallbacks():
    faultinject.clear()
    telemetry.reset()
    c = _ghz_plus(8).fused(max_qubits=4, pallas=True)
    q = qt.createQureg(8, ENV)
    c.run(q)
    with qt.explicit_mesh(ENV8.mesh):
        qe = qt.createQureg(5, ENV8)
        qt.hadamard(qe, 4)
    assert telemetry.counters("retry_attempts_total") == {}
    assert telemetry.counters("fault_injected_total") == {}
    assert telemetry.counter_value("engine_fallback_total",
                                   reason="fault_degraded") == 0


# -- retry policy -----------------------------------------------------------

def test_retry_schedule_is_deterministic_and_capped():
    pol = RetryPolicy(max_attempts=5, base_delay_s=0.004, multiplier=2.0,
                      max_delay_s=0.01, seed=7)
    a, b = list(pol.delays()), list(pol.delays())
    assert a == b and len(a) == 4
    assert all(0.002 <= d <= 0.01 for d in a)
    assert list(RetryPolicy(max_attempts=5, seed=8).delays()) != \
        list(RetryPolicy(max_attempts=5, seed=7).delays())


def test_call_with_retry_outcomes_and_exhaustion():
    telemetry.reset()
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TransientFault("x", "transient")
        return 42

    pol = RetryPolicy(max_attempts=3, base_delay_s=0.0)
    assert call_with_retry(flaky, site="x", policy=pol,
                           sleep=lambda _d: None) == 42
    assert telemetry.counter_value("retry_attempts_total", site="x",
                                   outcome="retried") == 2
    assert telemetry.counter_value("retry_attempts_total", site="x",
                                   outcome="ok") == 1

    def always():
        raise TransientFault("y", "transient")

    with pytest.raises(TransientFault):
        call_with_retry(always, site="y", policy=pol, sleep=lambda _d: None)
    assert telemetry.counter_value("retry_attempts_total", site="y",
                                   outcome="exhausted") == 1


def test_call_with_retry_deadline_stops_early():
    telemetry.reset()
    t = {"now": 0.0}

    def always():
        t["now"] += 1.0  # each attempt burns fake time past the deadline
        raise TransientFault("z", "transient")

    pol = RetryPolicy(max_attempts=10, base_delay_s=0.0, deadline_s=0.5)
    real = time.monotonic
    time.monotonic = lambda: t["now"]
    try:
        with pytest.raises(TransientFault):
            call_with_retry(always, site="z", policy=pol,
                            sleep=lambda _d: None)
    finally:
        time.monotonic = real
    assert telemetry.counter_value("retry_attempts_total", site="z",
                                   outcome="exhausted") == 1
    assert telemetry.counter_value("retry_attempts_total", site="z",
                                   outcome="retried") == 0


def test_default_policy_env_knobs(monkeypatch):
    from quest_tpu.resilience.retry import default_policy
    monkeypatch.setenv("QUEST_RETRY_MAX", "5")
    monkeypatch.setenv("QUEST_RETRY_BASE_MS", "1")
    monkeypatch.setenv("QUEST_RETRY_DEADLINE_MS", "250")
    pol = default_policy()
    assert pol.max_attempts == 5
    assert pol.base_delay_s == pytest.approx(0.001)
    assert pol.deadline_s == pytest.approx(0.25)
    telemetry.reset()
    monkeypatch.setenv("QUEST_RETRY_MAX", "banana")
    assert default_policy().max_attempts == 3
    assert telemetry.counter_value("analysis_findings_total",
                                   code="QT303", severity="warning") == 1


# -- pallas.dispatch faults -------------------------------------------------

def test_pallas_transient_retries_bit_identical():
    fz = _ghz_plus(8).fused(max_qubits=4, pallas=True)
    q0 = qt.createQureg(8, ENV)
    fz.run(q0)
    want = np.asarray(q0.amps)

    telemetry.reset()
    with fault_plan("pallas.dispatch:transient:1"):
        fz1 = _ghz_plus(8).fused(max_qubits=4, pallas=True)  # fresh trace
        q1 = qt.createQureg(8, ENV)
        fz1.run(q1)
    assert np.array_equal(want, np.asarray(q1.amps))
    assert telemetry.counter_value("fault_injected_total",
                                   site="pallas.dispatch",
                                   kind="transient") == 1
    assert telemetry.counter_value("retry_attempts_total",
                                   site="pallas.dispatch",
                                   outcome="retried") == 1
    assert telemetry.counter_value("engine_fallback_total",
                                   reason="fault_degraded") == 0


def test_pallas_compile_fault_degrades_matching_oracle():
    oracle = qt.createQureg(8, ENV)
    _ghz_plus(8).run(oracle)
    telemetry.reset()
    with fault_plan("pallas.dispatch:compile:1+"):
        fz = _ghz_plus(8).fused(max_qubits=4, pallas=True)
        q = qt.createQureg(8, ENV)
        fz.run(q)
    np.testing.assert_allclose(np.asarray(q.amps), np.asarray(oracle.amps),
                               atol=1e-12)
    assert telemetry.counter_value("engine_fallback_total",
                                   reason="fault_degraded") >= 1
    assert telemetry.counter_value("fault_injected_total",
                                   site="pallas.dispatch", kind="compile") >= 1


def test_pallas_sharded_transient_retries_bit_identical():
    fz = _ghz_plus(10).fused(max_qubits=5, pallas=True, shard_devices=8)
    q0 = qt.createQureg(10, ENV8)
    fz.run(q0)
    want = np.asarray(q0.amps)
    with fault_plan("pallas.dispatch:transient:1"):
        fz1 = _ghz_plus(10).fused(max_qubits=5, pallas=True, shard_devices=8)
        q1 = qt.createQureg(10, ENV8)
        fz1.run(q1)
    assert np.array_equal(want, np.asarray(q1.amps))


# -- exchange.collective faults ---------------------------------------------

def test_collective_transient_retries_bit_identical():
    with qt.explicit_mesh(ENV8.mesh):
        q0 = qt.createQureg(5, ENV8)
        qt.hadamard(q0, 4)
    want = np.asarray(q0.amps)
    telemetry.reset()
    with fault_plan("exchange.collective:transient:1"):
        with qt.explicit_mesh(ENV8.mesh):
            q1 = qt.createQureg(5, ENV8)
            qt.hadamard(q1, 4)
    assert np.array_equal(want, np.asarray(q1.amps))
    assert telemetry.counter_value("retry_attempts_total",
                                   site="exchange.collective",
                                   outcome="ok") == 1


def test_collective_exhaustion_fails_closed():
    telemetry.reset()
    with fault_plan("exchange.collective:transient:1+"):
        with pytest.raises(QuESTRetryError):
            with qt.explicit_mesh(ENV8.mesh):
                q = qt.createQureg(5, ENV8)
                qt.hadamard(q, 4)
    assert telemetry.counter_value("retry_attempts_total",
                                   site="exchange.collective",
                                   outcome="exhausted") == 1


# -- engine hardening -------------------------------------------------------

def _param_circuit(n=3):
    c = Circuit(n)
    c.hadamard(0)
    c.controlledNot(0, 1)
    c.rotateX(n - 1, qt.P("t"))
    return c


def test_engine_poisoned_request_isolated_by_bisection():
    c = _param_circuit()
    telemetry.reset()
    with fault_plan("engine.request:poison:2"):
        eng = qt.Engine(c, ENV, max_batch=4)
        futs = [eng.submit({"t": 0.1 * i}) for i in range(4)]
        results = []
        for f in futs:
            try:
                results.append(np.asarray(f.result(timeout=120)))
            except PoisonedRequestFault as e:
                results.append(e)
        eng.close()
    assert isinstance(results[1], PoisonedRequestFault)
    exe = c.parameterized(donate=False)
    for i in (0, 2, 3):
        q = qt.createQureg(3, ENV)
        want = np.asarray(exe(q.amps, {"t": 0.1 * i}))
        assert np.array_equal(want, results[i]), f"lane {i} diverged"
    assert telemetry.counter_value("engine_bisections_total") >= 1
    assert telemetry.counter_value("engine_poisoned_requests_total") == 1


def test_engine_request_timeout_queued_past_deadline():
    c = _param_circuit()
    eng = qt.Engine(c, ENV, max_batch=1)
    gate = threading.Event()
    orig = eng._dispatch
    eng._dispatch = lambda b: (gate.wait(5), orig(b))
    try:
        f1 = eng.submit({"t": 0.1})           # occupies the dispatch loop
        time.sleep(0.05)
        f2 = eng.submit({"t": 0.2}, timeout=0.01)   # expires while queued
        gate.set()
        with pytest.raises(QuESTTimeoutError):
            f2.result(timeout=60)
        assert f1.result(timeout=60) is not None
    finally:
        gate.set()
        eng.close()
    assert telemetry.counter_value("engine_request_timeouts_total") >= 1
    with pytest.raises(ValueError):
        qt.Engine(_param_circuit(), ENV).submit({"t": 1.0}, timeout=-1)


def test_engine_backpressure_bounded_queue():
    c = _param_circuit()
    eng = qt.Engine(c, ENV, max_batch=1, queue_max=1)
    assert eng.queue_max == 1
    gate = threading.Event()
    orig = eng._dispatch
    eng._dispatch = lambda b: (gate.wait(5), orig(b))
    try:
        eng.submit({"t": 0.1})
        time.sleep(0.05)  # let the loop pop the first request
        with pytest.raises(QuESTBackpressureError):
            eng.submit({"t": 0.2})
            eng.submit({"t": 0.3})
    finally:
        gate.set()
        eng.close()
    assert telemetry.counter_value("engine_backpressure_total") >= 1


def test_engine_queue_max_env_knob(monkeypatch):
    monkeypatch.setenv("QUEST_ENGINE_QUEUE_MAX", "7")
    eng = qt.Engine(_param_circuit(), ENV)
    assert eng.queue_max == 7
    eng.close()
    telemetry.reset()
    monkeypatch.setenv("QUEST_ENGINE_QUEUE_MAX", "lots")
    eng = qt.Engine(_param_circuit(), ENV)
    assert eng.queue_max == 0  # malformed -> unbounded, flight-recorded
    eng.close()
    assert telemetry.counter_value("analysis_findings_total",
                                   code="QT303", severity="warning") == 1


# -- segmented execution ----------------------------------------------------

def test_segment_plan_identity_boundaries():
    fz = _ghz_plus(8).fused(max_qubits=4, pallas=True)
    cuts = segment_plan(fz._tape, 8, every_n_items=1)
    assert cuts[0] == 0 and cuts[-1] == len(fz._tape)
    assert cuts == sorted(set(cuts))
    sparse = segment_plan(fz._tape, 8, every_n_items=3)
    assert sparse[0] == 0 and sparse[-1] == len(fz._tape)
    assert all(b - a >= 3 for a, b in zip(sparse, sparse[1:-1]))
    assert set(sparse) <= set(cuts)
    with pytest.raises(QuESTError, match="QT304"):
        segment_plan(fz._tape, 8, every_n_items=0)


def test_run_segmented_matches_plain_run(tmp_path):
    c = _ghz_plus(6)
    ref = qt.createQureg(6, ENV)
    c.run(ref)
    out = c.run_segmented(ENV, checkpoint_dir=str(tmp_path / "seg"),
                          every_n_items=4)
    # the chain of segment programs against the whole-tape program: two
    # program granularities, which XLA-CPU contracts differently (~1 ulp,
    # the segments.py numeric contract); resume-from-snapshot, the SAME
    # programs both times, is what stays bit-identical (the tests below)
    np.testing.assert_allclose(np.asarray(out.amps), np.asarray(ref.amps),
                               rtol=0, atol=4 * np.finfo(out.amps.dtype).eps)
    with pytest.raises(QuESTError, match="QT304"):
        c.run_segmented(ENV, checkpoint_dir=str(tmp_path / "k0"), keep=0)


@pytest.mark.parametrize("route", ["f32", "df"])
def test_preempt_resume_bit_identical_sharded(tmp_path, route, monkeypatch):
    """The acceptance proof: a mid-plan preemption on the 8-device mesh
    resumes from the last verified generation and finishes bit-identical
    to the uninterrupted run, on both the f32 and double-float routes."""
    if route == "df":
        monkeypatch.setenv("QUEST_PALLAS_DF", "1")
        code = 2
    else:
        code = 1
    c = _ghz_plus(10).fused(max_qubits=5, pallas=True, shard_devices=8)

    q_ref = qt.createQureg(10, ENV8, precision_code=code)
    c.run(q_ref)
    want = np.asarray(q_ref.amps)

    d = str(tmp_path / route)
    q0 = qt.createQureg(10, ENV8, precision_code=code)
    telemetry.reset()
    with fault_plan("segment.boundary:preempt:1"):
        with pytest.raises(QuESTPreemptionError) as ei:
            c.run_segmented(q0, checkpoint_dir=d, every_n_items=1)
    assert ei.value.cursor is not None and ei.value.checkpoint_dir == d

    env2 = qt.createQuESTEnv(jax.devices()[:8])
    out = resume_segmented(c, d, env2)
    assert np.asarray(out.amps).dtype == want.dtype
    assert np.array_equal(want, np.asarray(out.amps))
    assert telemetry.counter_value("segmented_resume_total",
                                   outcome="verified") == 1
    assert telemetry.counter_value("segmented_checkpoints_total") >= 1


def test_resume_skips_corrupt_generation_qt305(tmp_path):
    c = _ghz_plus(6)
    ref = qt.createQureg(6, ENV)
    c.run(ref)
    want = np.asarray(ref.amps)

    d = str(tmp_path / "seg")
    with fault_plan("segment.boundary:preempt:2"):
        with pytest.raises(QuESTPreemptionError):
            c.run_segmented(ENV, checkpoint_dir=d, every_n_items=1, keep=3)
    gens = sorted(g for g in os.listdir(d) if g.startswith("gen_"))
    assert len(gens) >= 2
    # bit-flip the newest generation's shard payload: resume must reject it
    # (QT305), fall back to the previous generation, and still finish
    newest = os.path.join(d, gens[-1])
    shard = [f for f in os.listdir(newest) if f.startswith("amps.shard_")][0]
    from quest_tpu.resilience.guard import _flip_payload
    _flip_payload(os.path.join(newest, shard))
    # the CRC teeth are typed: direct verification of the flipped
    # generation raises the checksum error resume classifies on
    from quest_tpu.checkpoint import verify_snapshot
    with pytest.raises(qt.QuESTChecksumError):
        verify_snapshot(newest)

    telemetry.reset()
    out = resume_segmented(c, d, qt.createQuESTEnv(jax.devices()[:1]))
    assert np.array_equal(want, np.asarray(out.amps))
    assert telemetry.counter_value("segmented_resume_total",
                                   outcome="skipped_corrupt") == 1
    assert telemetry.counter_value("analysis_findings_total",
                                   code="QT305", severity="warning") == 1


def test_resume_all_generations_corrupt_fails_closed(tmp_path):
    c = _ghz_plus(5)
    d = str(tmp_path / "seg")
    with fault_plan("segment.boundary:preempt:1"):
        with pytest.raises(QuESTPreemptionError):
            c.run_segmented(ENV, checkpoint_dir=d, every_n_items=1, keep=1)
    for gen in os.listdir(d):
        for f in os.listdir(os.path.join(d, gen)):
            if f.startswith("amps.shard_"):
                with open(os.path.join(d, gen, f), "wb") as fh:
                    fh.write(b"PK\x03\x04 torn")
    telemetry.reset()
    with pytest.raises(QuESTError, match="passed verification"):
        resume_segmented(c, d, ENV)
    assert telemetry.counter_value("segmented_resume_total",
                                   outcome="no_verified_gen") == 1


def test_resume_fingerprint_mismatch_raises(tmp_path):
    c = _ghz_plus(5)
    d = str(tmp_path / "seg")
    c.run_segmented(ENV, checkpoint_dir=d, every_n_items=2)
    other = _ghz_plus(5)
    other.hadamard(0)
    with pytest.raises(QuESTError, match="fingerprint"):
        resume_segmented(other, d, ENV)
    with pytest.raises(QuESTError, match="no checkpoint generations"):
        resume_segmented(c, str(tmp_path / "empty"), ENV)


def test_segmented_retention_keeps_last_k(tmp_path):
    c = _ghz_plus(6)
    d = str(tmp_path / "seg")
    c.run_segmented(ENV, checkpoint_dir=d, every_n_items=1, keep=2)
    gens = sorted(g for g in os.listdir(d) if g.startswith("gen_"))
    assert len(gens) == 2
    assert int(gens[-1][len("gen_"):]) == len(c._tape)


def test_resume_of_completed_run_is_loadable(tmp_path):
    c = _ghz_plus(5)
    ref = qt.createQureg(5, ENV)
    c.run(ref)
    d = str(tmp_path / "seg")
    c.run_segmented(ENV, checkpoint_dir=d, every_n_items=2)
    out = resume_segmented(c, d, qt.createQuESTEnv(jax.devices()[:1]))
    assert np.array_equal(np.asarray(ref.amps), np.asarray(out.amps))


# -- integrity sentinels (ISSUE 8) ------------------------------------------

def test_sentinel_policy_parse_cadences_and_qt403():
    pol = SentinelPolicy.parse("norm:every_2,checksum:segment,trace:3")
    assert [(s.kind, s.cadence) for s in pol.specs] == \
        [("norm", 2), ("checksum", 1), ("trace", 3)]
    assert pol.due_kinds(1) == ("checksum",)
    assert pol.due_kinds(2) == ("norm", "checksum")
    assert pol.due_kinds(6) == ("norm", "checksum", "trace")
    assert pol.due_kinds(0) == ("norm", "checksum", "trace")  # heal recheck
    assert SentinelPolicy.parse("off").specs == ()
    assert [(s.kind, s.cadence) for s in
            SentinelPolicy.parse("default").specs] == \
        [("norm", 1), ("checksum", 1)]

    telemetry.reset()
    pol = SentinelPolicy.parse("bogus:1,norm:zero,norm:segment")
    assert [(s.kind, s.cadence) for s in pol.specs] == [("norm", 1)]
    assert telemetry.counter_value("analysis_findings_total",
                                   code="QT403", severity="warning") == 2
    with pytest.raises(QuESTError, match="QT403"):
        SentinelPolicy.parse("bogus:1", strict=True)


def test_sentinel_env_policy_loads_once(monkeypatch):
    monkeypatch.setattr(sentinel, "_active", None)
    monkeypatch.setattr(sentinel, "_env_read", False)
    monkeypatch.setenv("QUEST_SENTINEL", "norm:every_2")
    assert sentinel.enabled()
    pol = sentinel.active_policy()
    assert pol.specs == (sentinel.SentinelSpec("norm", 2),)
    sentinel.clear()
    assert not sentinel.enabled()


def test_sentinel_clean_and_bitflip_detection_sharded():
    """One check opportunity is enough: a single flipped exponent bit on
    shard 3 breaches BOTH the norm band and the per-shard checksum, and
    the QT402 finding names the divergent shard."""
    # the eager collective path keeps the amps-sharded layout, so the
    # checksum fold sees the real 8-shard mesh (a fused run's output is
    # replicated and degenerates to one shard)
    with qt.explicit_mesh(ENV8.mesh):
        q = qt.createQureg(10, ENV8)
        for i in range(10):
            qt.hadamard(q, i)
    telemetry.reset()
    with sentinel_policy("norm:segment,checksum:segment") as pol:
        assert sentinel.check_qureg(q, policy=pol, where="clean") == []
        assert telemetry.counter_value("sentinel_checks_total",
                                       kind="norm", outcome="ok") == 1
        assert telemetry.counter_value("sentinel_checks_total",
                                       kind="checksum", outcome="ok") == 1
        from quest_tpu.resilience import guard
        with fault_plan("state.corrupt:bitflip3:1"):
            q.put(guard.corrupt_amps(q.amps))
        findings = sentinel.check_qureg(q, policy=pol, where="flipped")
    assert [f.code for f in findings] == ["QT401", "QT402"]
    assert "shard 3" in findings[1].message
    assert telemetry.counter_value("sentinel_checks_total",
                                   kind="checksum", outcome="breach") == 1
    assert telemetry.counter_value("analysis_findings_total",
                                   code="QT402", severity="error") == 1


def test_sentinel_density_trace_qt404_and_statevec_skip():
    q = qt.createDensityQureg(3, ENV)
    telemetry.reset()
    with sentinel_policy("trace:segment") as pol:
        assert sentinel.check_qureg(q, policy=pol) == []
        host = np.array(q.amps)
        host[0].reshape(8, 8)[0, 1] += 0.25  # hermiticity broken, trace ok
        q.put(jax.device_put(host))
        findings = sentinel.check_qureg(q, policy=pol)
        assert [f.code for f in findings] == ["QT404"]
        assert "hermiticity" in findings[0].message
        # trace over a statevector is not applicable: counted, not breached
        sv = qt.createQureg(3, ENV)
        assert sentinel.check_qureg(sv, policy=pol) == []
    assert telemetry.counter_value("sentinel_checks_total",
                                   kind="trace", outcome="skipped") == 1
    assert telemetry.counter_value("sentinel_checks_total",
                                   kind="trace", outcome="breach") == 1


# -- self-healing rollback-and-replay (ISSUE 8) -----------------------------

@pytest.mark.parametrize("route", ["f32", "df"])
def test_sdc_rollback_replay_bit_identical_sharded(tmp_path, route,
                                                   monkeypatch):
    """The ISSUE 8 acceptance proof: an injected single-bit flip on the
    8-device mesh is detected at the next segment boundary, rolled back
    (to the in-memory baseline on the df leg -- the flip lands in the
    FIRST segment -- and to a CRC-verified disk generation on the f32
    leg) and replayed on the same route, finishing bit-identical to the
    uncorrupted run. The nth-scoped fault is visit-counted, so the flip
    provably does not re-fire during the healing replay."""
    if route == "df":
        monkeypatch.setenv("QUEST_PALLAS_DF", "1")
        code, nth = 2, 1
    else:
        code, nth = 1, 2
    c = _ghz_plus(10).fused(max_qubits=5, pallas=True, shard_devices=8)

    q_ref = qt.createQureg(10, ENV8, precision_code=code)
    c.run(q_ref)
    want = np.asarray(q_ref.amps)

    telemetry.reset()
    q = qt.createQureg(10, ENV8, precision_code=code)
    with sentinel_policy("norm:segment,checksum:segment"):
        with fault_plan(f"state.corrupt:bitflip2:{nth}"):
            out = c.run_segmented(q, checkpoint_dir=str(tmp_path / route),
                                  every_n_items=1)
    assert np.array_equal(want, np.asarray(out.amps))
    assert telemetry.counter_value("segmented_rollbacks_total",
                                   outcome="replayed") == 1
    assert telemetry.counter_value("sentinel_checks_total",
                                   kind="norm", outcome="breach") == 1
    assert telemetry.counter_value("sentinel_checks_total",
                                   kind="checksum", outcome="breach") == 1
    assert telemetry.counter_value("analysis_findings_total",
                                   code="QT402", severity="error") == 1
    assert telemetry.counter_value("engine_fallback_total",
                                   reason="sentinel_degraded") == 0


def test_sentinel_fail_closed_when_rollback_target_is_corrupt(tmp_path):
    """A breach the lattice cannot clear -- here the INITIAL state is
    corrupt, so rollback restores the same bad norm -- must escalate
    retry -> degrade -> fail closed, never serve the corrupt state."""
    c = _ghz_plus(6)
    q = qt.createQureg(6, ENV)
    host = np.array(q.amps)
    host[0, 0] = 7.0
    q.put(jax.device_put(host))
    telemetry.reset()
    with sentinel_policy("norm:segment"):
        with pytest.raises(QuESTIntegrityError) as ei:
            c.run_segmented(q, checkpoint_dir=str(tmp_path / "seg"),
                            every_n_items=len(c._tape))
    assert any(f.code == "QT401" for f in ei.value.findings)
    assert telemetry.counter_value("segmented_rollbacks_total",
                                   outcome="failed") == 1
    assert telemetry.counter_value("engine_fallback_total",
                                   reason="sentinel_degraded") == 1


def test_sentinel_sparse_cadence_fails_closed_past_window(tmp_path):
    """The cadence trade-off (docs/resilience.md): with norm:every_2 a
    flip in segment 1 passes the unchecked tick-1 boundary and is
    CHECKPOINTED; the tick-2 breach then rolls back to the corrupt
    generation, and the lattice fails closed rather than heal."""
    c = _ghz_plus(6)
    telemetry.reset()
    with sentinel_policy("norm:every_2"):
        with fault_plan("state.corrupt:bitflip0:1"):
            with pytest.raises(QuESTIntegrityError):
                c.run_segmented(ENV, checkpoint_dir=str(tmp_path / "seg"),
                                every_n_items=1)
    assert telemetry.counter_value("segmented_rollbacks_total",
                                   outcome="failed") == 1


def test_sentinels_off_probe_points_are_noops(tmp_path):
    sentinel.clear()
    faultinject.clear()
    telemetry.reset()
    c = _ghz_plus(6)
    c.run_segmented(ENV, checkpoint_dir=str(tmp_path / "seg"),
                    every_n_items=2)
    with qt.Engine(_param_circuit(), ENV, max_batch=2) as eng:
        eng.run({"t": 0.1})
    assert telemetry.counters("sentinel_checks_total") == {}
    assert telemetry.counters("segmented_rollbacks_total") == {}
    assert telemetry.counters("watchdog_timeouts_total") == {}
    assert telemetry.counter_value("engine_fallback_total",
                                   reason="sentinel_degraded") == 0


# -- hung-collective watchdog (ISSUE 8) -------------------------------------

def test_watchdog_collective_hang_raises_typed_qt405():
    with qt.explicit_mesh(ENV8.mesh):  # warm the kernels off the deadline
        qw = qt.createQureg(5, ENV8)
        qt.hadamard(qw, 4)
    telemetry.reset()
    with watchdog_deadline(100), fault_plan("exchange.collective:hang:1"):
        with pytest.raises(QuESTHangError) as ei:
            with qt.explicit_mesh(ENV8.mesh):
                q = qt.createQureg(5, ENV8)
                qt.hadamard(q, 4)
    assert ei.value.site == "exchange.collective"
    assert ei.value.deadline_ms == pytest.approx(100.0)
    assert telemetry.counter_value("watchdog_timeouts_total",
                                   site="exchange.collective") == 1
    assert telemetry.counter_value("analysis_findings_total",
                                   code="QT405", severity="error") == 1


def test_injected_hang_without_watchdog_is_bounded_stall():
    """With no deadline armed an injected 'eternal' hang degenerates to
    the bounded HANG_SLEEP_S stall and the result is still correct."""
    with qt.explicit_mesh(ENV8.mesh):
        q0 = qt.createQureg(5, ENV8)
        qt.hadamard(q0, 4)
    want = np.asarray(q0.amps)
    watchdog.reset()
    assert watchdog.deadline_s() is None
    t0 = time.monotonic()
    with fault_plan("exchange.collective:hang:1"):
        with qt.explicit_mesh(ENV8.mesh):
            q = qt.createQureg(5, ENV8)
            qt.hadamard(q, 4)
    # the stall itself is HANG_SLEEP_S (0.1s); the budget absorbs the
    # qureg build + dispatch around it, which on a loaded 1-core CI box
    # alone can take several seconds -- the assertion only has to
    # separate "bounded stall" from "eternal hang"
    assert time.monotonic() - t0 < 30.0
    assert np.array_equal(want, np.asarray(q.amps))


def test_watchdog_env_knob_and_qt303(monkeypatch):
    try:
        watchdog.reset()
        monkeypatch.setenv(watchdog.ENV_MS, "250")
        assert watchdog.deadline_s() == pytest.approx(0.25)
        watchdog.reset()
        telemetry.reset()
        monkeypatch.setenv(watchdog.ENV_MS, "forever")
        assert watchdog.deadline_s() is None
        assert telemetry.counter_value("analysis_findings_total",
                                       code="QT303",
                                       severity="warning") == 1
    finally:
        watchdog.reset()  # drop the cached env read for later tests


# -- engine health states (ISSUE 8) -----------------------------------------

def test_engine_hang_quarantines_then_revive_heals():
    eng = qt.Engine(_param_circuit(), ENV, max_batch=1)
    try:
        eng.warmup()  # compile BEFORE arming the deadline
        assert eng.health() == "healthy"
        telemetry.reset()
        with watchdog_deadline(150), fault_plan("engine.dispatch:hang:1"):
            with pytest.raises(QuESTHangError):
                eng.submit({"t": 0.3}).result(timeout=60)
        assert eng.health() == "quarantined"
        with pytest.raises(QuESTBackpressureError, match="quarantined"):
            eng.submit({"t": 0.4})
        assert telemetry.counter_value("engine_backpressure_total",
                                       reason="quarantined") == 1
        assert eng.revive() == "degraded"
        for i in range(3):  # _HEAL_STREAK clean dispatches
            assert eng.run({"t": 0.1 * i}) is not None
        assert eng.health() == "healthy"
        trans = telemetry.counter_value
        assert trans("engine_health_transitions_total",
                     **{"from": "healthy", "to": "quarantined"}) == 1
        assert trans("engine_health_transitions_total",
                     **{"from": "quarantined", "to": "degraded"}) == 1
        assert trans("engine_health_transitions_total",
                     **{"from": "degraded", "to": "healthy"}) == 1
        assert telemetry.counter_value("watchdog_timeouts_total",
                                       site="engine.dispatch") == 1
    finally:
        eng.close()


def test_engine_sentinel_breach_degrades_and_heals():
    eng = qt.Engine(_param_circuit(), ENV, max_batch=1)
    try:
        eng.warmup()
        telemetry.reset()
        with sentinel_policy("norm:segment"):
            with fault_plan("state.corrupt:bitflip0:1"):
                fut = eng.submit({"t": 0.2})
                with pytest.raises(QuESTIntegrityError) as ei:
                    fut.result(timeout=60)
        # the corrupt result never reached the future; the engine is
        # degraded and heals after a clean streak
        assert any(f.code == "QT401" for f in ei.value.findings)
        assert eng.health() == "degraded"
        assert telemetry.counter_value("sentinel_checks_total",
                                       kind="norm", outcome="breach") == 1
        for i in range(3):
            eng.run({"t": 0.1 * i})
        assert eng.health() == "healthy"
    finally:
        eng.close()
