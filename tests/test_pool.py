"""Replica-pool serving (quest_tpu/engine/pool.py + admission.py).

Contracts under test:

- pool-served results are BIT-IDENTICAL to a lone Engine over the same
  structure (same fingerprint -> same executable -> the PR 4 vmap/replay
  identity carries through the router);
- routing: health rank first (quarantined never routes), structure
  affinity second, load third -- the health-transition routing matrix;
- quarantine failover drains queued work to peers with ZERO dropped
  futures and bit-identical recovered results (8-device sharded mesh
  included), and the warmed replacement serves its first request with
  zero retraces (``engine_trace_total{kind=param_replay}`` flat);
- admission: token-bucket quota exhaustion rejects typed
  (``reason="quota"``) while the reserve band keeps high-priority
  requests admissible by construction;
- hedged dispatch re-issues past the deadline and first-completion-wins
  deterministically (both paths compute the same bits);
- the QUEST_POOL_REPLICAS / QUEST_HEDGE_MS / QUEST_TENANT_QPS knobs warn
  once (QT307) on malformed values, like QT205/QT206;
- ``Engine.close(drain=True)`` on a quarantined engine resolves queued
  futures promptly with QuESTCancelledError (regression, ISSUE 13).
"""

import threading
import time
import warnings

import numpy as np
import pytest

import jax

import quest_tpu as qt
from quest_tpu import telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.engine import (AdmissionController, Engine, EnginePool, P,
                              TokenBucket)
from quest_tpu.engine import admission as _admission
from quest_tpu.engine import pool as _pool
from quest_tpu.resilience import faultinject
from quest_tpu.resilience.errors import (QuESTBackpressureError,
                                         QuESTCancelledError)

ENV1 = qt.createQuESTEnv(jax.devices()[:1])
ENV8 = qt.createQuESTEnv(jax.devices()[:8])

_TRACE = dict(kind="param_replay")


def _ansatz(n=3):
    c = Circuit(n)
    for q in range(n):
        c.rotateY(q, P(f"t{q}"))
    for q in range(n - 1):
        c.controlledNot(q, q + 1)
    for q in range(n):
        c.rotateZ(q, P(f"p{q}"))
    return c


def _other(n=3):
    """A structurally DIFFERENT circuit (distinct fingerprint)."""
    c = Circuit(n)
    c.hadamard(0)
    for q in range(n):
        c.rotateX(q, P(f"x{q}"))
    return c


def _params(c, seed):
    rng = np.random.default_rng(seed)
    return {name: float(v) for name, v
            in zip(c.lifted().param_names, rng.uniform(-2, 2, 64))}


def _block(eng):
    """Stall ``eng``'s dispatches behind an Event; returns the gate."""
    gate = threading.Event()
    orig = eng._dispatch_one

    def blocked(batch, mode):
        gate.wait(30)
        return orig(batch, mode)

    eng._dispatch_one = blocked
    return gate


# ---------------------------------------------------------------------------
# serving bit-identity + affinity
# ---------------------------------------------------------------------------

def test_pool_results_bit_identical_to_lone_engine():
    c = _ansatz()
    plist = [_params(c, s) for s in range(6)]
    with Engine(c, ENV1, max_batch=4, max_delay_ms=0.0) as eng:
        oracle = [np.asarray(f.result(60))
                  for f in [eng.submit(p) for p in plist]]
    with EnginePool(ENV1, replicas=2, max_batch=4, max_delay_ms=0.0) as pool:
        futs = pool.submit_many(c, plist)
        got = [np.asarray(f.result(60)) for f in futs]
    for o, g in zip(oracle, got):
        assert np.array_equal(o, g)


def test_structure_affinity_and_spread():
    a, b = _ansatz(), _other()
    with EnginePool(ENV1, replicas=2, max_batch=2, max_delay_ms=0.0) as pool:
        for s in range(3):
            pool.submit(a, _params(a, s)).result(60)
        # repeated same-structure traffic stays on ONE replica (affinity)
        owners_a = [r.id for r in pool._replicas
                    if a.fingerprint() in r.engines]
        assert len(owners_a) == 1
        # a different structure spreads to the OTHER replica
        pool.submit(b, _params(b, 0)).result(60)
        owners_b = [r.id for r in pool._replicas
                    if b.fingerprint() in r.engines]
        assert len(owners_b) == 1 and owners_b != owners_a


def test_health_transition_routing_matrix():
    with EnginePool(ENV1, replicas=3, spawn_replacements=False) as pool:
        r0, r1, r2 = pool._replicas
        fp = "fp-under-test"
        with pool._cv:
            pick = pool._select_locked(fp)
        assert pick is r0  # all healthy, all cold: lowest id
        r0.state = "degraded"
        with pool._cv:
            assert pool._select_locked(fp) is r1  # healthy before degraded
            assert pool._select_locked(fp, allow_degraded=False) is r1
        r1.state = "quarantined"
        with pool._cv:
            assert pool._select_locked(fp) is r2  # quarantined never routes
        r2.state = "degraded"
        with pool._cv:
            # only degraded members left: still routable...
            assert pool._select_locked(fp) in (r0, r2)
            # ...unless the caller (hedging) insists on healthy peers
            assert pool._select_locked(fp, allow_degraded=False) is None
        r1.state = "healthy"
        stub = type("EngStub", (), {"health": lambda self: "healthy"})()
        r1.engines[fp] = stub  # affinity marker
        with pool._cv:
            assert pool._select_locked(fp) is r1  # healthy + affine wins
        del r1.engines[fp]
        assert set(pool.health()) == {0, 1, 2}


# ---------------------------------------------------------------------------
# quarantine failover: zero lost futures, bit-identical, sharded mesh too
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("env", [ENV1, ENV8], ids=["vmap", "sharded8"])
def test_failover_drain_zero_lost_bit_identical(env):
    c = _ansatz()
    plist = [_params(c, s) for s in range(5)]
    with Engine(c, env, max_batch=4, max_delay_ms=0.0) as eng:
        oracle = [np.asarray(f.result(60))
                  for f in [eng.submit(p) for p in plist]]
    telemetry.reset()
    with EnginePool(env, replicas=2, max_batch=4, max_delay_ms=0.0,
                    spawn_replacements=False) as pool:
        with faultinject.fault_plan("pool.replica:kill:2"):
            futs = pool.submit_many(c, plist)
            got = [np.asarray(f.result(60)) for f in futs]  # ZERO lost
        assert telemetry.counter_value("pool_failovers_total",
                                       reason="kill") >= 1.0
        assert "quarantined" in pool.health().values()
    for o, g in zip(oracle, got):
        assert np.array_equal(o, g)


def test_replacement_spawn_and_warm_zero_retrace():
    c = _ansatz()
    telemetry.reset()
    with EnginePool(ENV1, replicas=2, max_batch=2, max_delay_ms=0.0) as pool:
        pool.submit(c, _params(c, 0)).result(60)
        with faultinject.fault_plan("pool.replica:kill:1"):
            r = pool.submit(c, _params(c, 1)).result(60)
            assert r is not None
        pool.await_rotation(2, timeout=120)  # replacement warmed + rotated
        assert telemetry.counter_value("pool_replacements_total",
                                       reason="kill") == 1.0
        new_rep = max(pool._replicas, key=lambda r: r.id)
        assert new_rep.in_rotation and c.fingerprint() in new_rep.engines
        tr0 = telemetry.counter_value("engine_trace_total", **_TRACE)
        fut = new_rep.engines[c.fingerprint()].submit(_params(c, 2))
        fut.result(60)
        # first real request on the replacement: zero retraces
        assert telemetry.counter_value("engine_trace_total",
                                       **_TRACE) == tr0


def test_warm_from_manifest_explicit_replica_zero_retrace():
    c = _ansatz()
    with EnginePool(ENV1, replicas=2, max_batch=2, max_delay_ms=0.0) as pool:
        pool.submit(c, _params(c, 0)).result(60)
        cold = next(r for r in pool._replicas
                    if c.fingerprint() not in r.engines)
        warmed = pool.warm_from_manifest(replica=cold.id)
        assert warmed == [c.fingerprint()]
        tr0 = telemetry.counter_value("engine_trace_total", **_TRACE)
        res = cold.engines[c.fingerprint()].submit(_params(c, 3)).result(60)
        assert telemetry.counter_value("engine_trace_total",
                                       **_TRACE) == tr0
        # and the warmed replica computes the same bits as the original
        hot = next(r for r in pool._replicas if r is not cold)
        res2 = hot.engines[c.fingerprint()].submit(_params(c, 3)).result(60)
        assert np.array_equal(np.asarray(res), np.asarray(res2))


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------

def test_token_bucket_reserve_non_starvation():
    t = [0.0]
    b = TokenBucket(4, clock=lambda: t[0])  # burst 4, reserve 1
    assert [b.take(priority="normal") for _ in range(4)] == \
        [True, True, True, False]  # normals cannot drain the reserve
    assert b.take(priority="high")          # the reserve admits high
    assert not b.take(priority="high")      # empty rejects everyone
    t[0] += 0.5                             # 2 tokens back
    assert b.take(priority="normal")
    with pytest.raises(ValueError):
        b.take(priority="urgent")


def test_pool_quota_exhaustion_typed_and_counted():
    c = _ansatz()
    adm = AdmissionController(4, clock=lambda: 0.0)  # frozen: no refill
    telemetry.reset()
    with EnginePool(ENV1, replicas=1, max_batch=2, max_delay_ms=0.0,
                    admission=adm) as pool:
        futs = [pool.submit(c, _params(c, s), tenant="acme")
                for s in range(3)]
        with pytest.raises(QuESTBackpressureError) as ei:
            pool.submit(c, _params(c, 9), tenant="acme")
        assert ei.value.reason == "quota"
        # the reserve band still admits a high-priority request
        futs.append(pool.submit(c, _params(c, 4), tenant="acme",
                                priority="high"))
        [f.result(60) for f in futs]
        # an unrelated tenant has its own bucket
        pool.submit(c, _params(c, 5), tenant="other").result(60)
    assert telemetry.counter_value("admission_admitted_total",
                                   tenant="acme", priority="normal") == 3.0
    assert telemetry.counter_value("admission_admitted_total",
                                   tenant="acme", priority="high") == 1.0
    assert telemetry.counter_value("admission_rejected_total",
                                   tenant="acme", priority="normal") == 1.0
    assert telemetry.counter_value("engine_backpressure_total",
                                   reason="quota") == 1.0


def test_parked_requests_drain_in_priority_order_and_close_cancels():
    c = _ansatz()
    telemetry.reset()
    with EnginePool(ENV1, replicas=1, max_batch=2, max_delay_ms=0.0,
                    spawn_replacements=False) as pool:
        pool.submit(c, _params(c, 0)).result(60)
        pool._quarantine(pool._replicas[0], reason="test")
        # no routable replica: admitted requests PARK instead of rejecting
        fn = pool.submit(c, _params(c, 1))
        fh = pool.submit(c, _params(c, 2), priority="high")
        assert not fn.done() and not fh.done()
        assert telemetry.counter_value("admission_queued_total",
                                       tenant="default",
                                       priority="high") == 1.0
        with pool._cv:
            assert len(pool._pending["high"]) == 1
        pool.close()
    for f in (fn, fh):
        with pytest.raises(QuESTCancelledError):
            f.result(10)


def test_parked_requests_serve_after_revive():
    c = _ansatz()
    with EnginePool(ENV1, replicas=1, max_batch=2, max_delay_ms=0.0,
                    spawn_replacements=False) as pool:
        pool.submit(c, _params(c, 0)).result(60)
        pool._quarantine(pool._replicas[0], reason="test")
        fut = pool.submit(c, _params(c, 1))
        assert pool.revive(0) == "healthy"
        assert np.asarray(fut.result(60)).shape[0] == 2


# ---------------------------------------------------------------------------
# hedged dispatch
# ---------------------------------------------------------------------------

def test_hedged_dispatch_winner_determinism():
    c = _ansatz()
    p = _params(c, 7)
    with Engine(c, ENV1, max_batch=2, max_delay_ms=0.0) as eng:
        oracle = np.asarray(eng.submit(p).result(60))
    telemetry.reset()
    with EnginePool(ENV1, replicas=2, max_batch=2, max_delay_ms=0.0,
                    hedge_ms=40) as pool:
        pool.submit(c, _params(c, 0)).result(60)   # builds the affine engine
        rep = next(r for r in pool._replicas if r.engines)
        eng0 = rep.engines[c.fingerprint()]
        gate = _block(eng0)                        # primary stalls...
        try:
            fut = pool.submit(c, p)
            eng0._note_breach(hang=False)          # ...and is degraded
            got = np.asarray(fut.result(60))       # hedge completes it
        finally:
            gate.set()
        assert np.array_equal(oracle, got)         # winner-independent bits
        assert telemetry.counter_value("pool_hedges_total",
                                       outcome="issued") >= 1.0
        assert telemetry.counter_value("pool_hedges_total",
                                       outcome="won_hedge") >= 1.0


# ---------------------------------------------------------------------------
# QT307 env knobs (idiom of the QT205/QT206 tests)
# ---------------------------------------------------------------------------

@pytest.fixture
def knob_env(monkeypatch):
    monkeypatch.setattr(_pool, "_REPLICAS_WARNED", set())
    monkeypatch.setattr(_pool, "_HEDGE_WARNED", set())
    monkeypatch.setattr(_admission, "_QPS_WARNED", set())
    return monkeypatch


@pytest.mark.parametrize("env_var,reader,default", [
    ("QUEST_POOL_REPLICAS", _pool._env_replicas, 2),
    ("QUEST_HEDGE_MS", _pool._env_hedge_ms, 0),
    ("QUEST_TENANT_QPS", _admission._env_tenant_qps, 0),
])
def test_qt307_warns_once_and_defaults(knob_env, env_var, reader, default):
    knob_env.setenv(env_var, "lots")
    telemetry.reset()
    with pytest.warns(RuntimeWarning, match="QT307"):
        assert reader() == default
    assert telemetry.counter_value(
        "analysis_findings_total", code="QT307", severity="warning") == 1.0
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # second call must stay silent
        assert reader() == default


def test_qt307_below_minimum_clamps(knob_env):
    knob_env.setenv("QUEST_POOL_REPLICAS", "0")
    with pytest.warns(RuntimeWarning, match="QT307"):
        assert _pool._env_replicas() == 1
    knob_env.setenv("QUEST_HEDGE_MS", "-5")
    with pytest.warns(RuntimeWarning, match="QT307"):
        assert _pool._env_hedge_ms() == 0


def test_env_knobs_wellformed_values_apply(knob_env):
    knob_env.setenv("QUEST_POOL_REPLICAS", "3")
    knob_env.setenv("QUEST_HEDGE_MS", "25")
    knob_env.setenv("QUEST_TENANT_QPS", "7")
    with EnginePool(ENV1) as pool:
        assert len(pool._replicas) == 3
        assert pool.hedge_s == pytest.approx(0.025)
        assert pool.admission.default_qps == 7


# ---------------------------------------------------------------------------
# Engine.close(drain=True) on a quarantined engine (regression, ISSUE 13)
# ---------------------------------------------------------------------------

def test_quarantined_engine_drain_close_cancels_queued_promptly():
    c = _ansatz()
    eng = Engine(c, ENV1, max_batch=1, max_delay_ms=0.0)
    eng.run(_params(c, 0))
    gate = _block(eng)
    try:
        f1 = eng.submit(_params(c, 1))            # picked up, then blocked
        deadline = time.monotonic() + 10
        while eng._q and time.monotonic() < deadline:
            time.sleep(0.005)                     # wait for batcher pickup
        f2 = eng.submit(_params(c, 2))            # still queued
        eng._note_breach(hang=True)
        assert eng.health() == "quarantined"
        closed = threading.Event()
        closer = threading.Thread(
            target=lambda: (eng.close(drain=True), closed.set()))
        closer.start()
        # the queued future resolves typed BEFORE the blocked batcher is
        # released -- the old behavior waited on a wedged drain forever
        with pytest.raises(QuESTCancelledError):
            f2.result(timeout=10)
        assert not closed.is_set()
    finally:
        gate.set()
    closer.join(30)
    assert closed.is_set()
    assert f1.done()          # in-flight work still completed


def test_backpressure_error_reason_attribute():
    e = QuESTBackpressureError("m", "f", reason="quota")
    assert e.reason == "quota"
    assert QuESTBackpressureError("m", "f").reason is None


def test_pool_mixed_structures_in_parallel_match_raw_replay():
    """Six structures over three replicas, submitted from six threads at
    once: each replica's Engine plans and traces while the others do, and
    every reply agrees with the raw tape's parameterized replay."""
    circuits = [_ansatz(3), _other(3), _ansatz(4), _other(4), _ansatz(5),
                _other(5)]
    start = threading.Barrier(len(circuits))
    errs, failures = {}, []

    with EnginePool(ENV1, replicas=3, max_batch=2, max_delay_ms=0.0) as pool:
        def serve(i):
            try:
                c = circuits[i]
                p = _params(c, i)
                start.wait(60)
                got = np.asarray(pool.submit(c, p).result(120))
                amps0 = np.zeros((2, 1 << c.num_qubits))
                amps0[0, 0] = 1.0
                want = np.asarray(c.parameterized(donate=False)(
                    jax.numpy.asarray(amps0), p))
                errs[i] = float(np.max(np.abs(got - want)))
            except Exception as e:  # surfaced on the test's thread
                failures.append((i, repr(e)))

        threads = [threading.Thread(target=serve, args=(i,))
                   for i in range(len(circuits))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
    assert not failures, failures
    assert sorted(errs) == list(range(len(circuits)))
    assert max(errs.values()) <= 1e-12, errs
