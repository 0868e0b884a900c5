"""Serving engine: parameterized replay, plan/executable cache, and
micro-batched ensemble execution (quest_tpu/engine/).

Contracts under test:

- a parameterized replay is BIT-IDENTICAL to the freshly traced constant
  tape of the same structure (f32 and f64/df registers, unsharded and
  CPU-mesh sharded);
- a vmap-batched ensemble execution matches a Python loop of single
  replays bit-identically;
- the bounded LRU's hit/miss/evict counters match a scripted access
  pattern exactly, and structure fingerprints collide iff structures
  match (values never contribute);
- a warm ``Engine.submit`` performs zero retraces
  (``engine_trace_total{kind=param_replay}``) and serves from the
  executable cache (``plan_cache_hit_total``).
"""

import time

import numpy as np
import pytest

import jax

import quest_tpu as qt
from quest_tpu import telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.engine import Engine, LRUCache, P, Param
from quest_tpu import cache as ecache
from quest_tpu.params import (_pack_rows, bind, lift_tape,
                                     materialize_tape)
from quest_tpu.validation import QuESTError

ENV1 = qt.createQuESTEnv(jax.devices()[:1])
ENV8 = qt.createQuESTEnv(jax.devices()[:8])

VALS = (0.37, 1.234, -0.8, 2.2, 0.61, 1.9, -1.1)
NAMES = tuple(f"t{i}" for i in range(len(VALS)))
PARAMS = dict(zip(NAMES, VALS))


def _ansatz(circ, th):
    """Every liftable gate family at least once, entangled."""
    circ.hadamard(0)
    circ.rotateZ(1, th[0])
    circ.rotateX(2, th[1])
    circ.controlledNot(0, 2)
    circ.phaseShift(3, th[2])
    circ.controlledRotateY(1, 3, th[3])
    circ.multiRotateZ([0, 2, 4], th[4])
    circ.rotateAroundAxis(4, th[5], qt.Vector(1.0, 2.0, -0.5))
    circ.compactUnitary(2, complex(np.cos(0.3), 0.0),
                        complex(0.0, np.sin(0.3)))
    circ.multiRotatePauli([0, 1], [1, 2], th[6])
    circ.controlledPhaseShift(0, 4, th[2])
    circ.tGate(4)


def _pair(n=5):
    """(constant circuit, param circuit) over the same structure."""
    cc, cp = Circuit(n), Circuit(n)
    _ansatz(cc, VALS)
    _ansatz(cp, [P(name) for name in NAMES])
    return cc, cp


# ---------------------------------------------------------------------------
# parameterized replay bit-identity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("precision", [1, 2])
def test_param_replay_bit_identical_unsharded(precision):
    cc, cp = _pair()
    q1 = qt.createQureg(5, ENV1, precision_code=precision)
    qt.initPlusState(q1)
    cc.run(q1)
    q2 = qt.createQureg(5, ENV1, precision_code=precision)
    qt.initPlusState(q2)
    out = cp.parameterized()(q2.amps, PARAMS)
    assert np.array_equal(np.asarray(q1.amps), np.asarray(out))


def test_param_replay_bit_identical_sharded():
    n = 8  # 2^8 amps over the 8-device CPU mesh
    cc, cp = Circuit(n), Circuit(n)
    _ansatz(cc, VALS)
    _ansatz(cp, [P(name) for name in NAMES])
    cc.rotateZ(n - 1, 0.5)           # touch a sharded qubit
    cp.rotateZ(n - 1, 0.5)
    q1 = qt.createQureg(n, ENV8)
    qt.initPlusState(q1)
    cc.run(q1)
    q2 = qt.createQureg(n, ENV8)
    qt.initPlusState(q2)
    out = cp.parameterized()(q2.amps, PARAMS)
    assert len(q1.amps.sharding.device_set) == 8
    assert np.array_equal(np.asarray(q1.amps), np.asarray(out))


def test_param_replay_new_values_zero_retraces():
    _, cp = _pair()
    exe = cp.parameterized()
    q = qt.createQureg(5, ENV1)
    qt.initPlusState(q)
    exe(q.amps, PARAMS)
    traces = telemetry.counter_value("engine_trace_total",
                                     kind="param_replay")
    for shift in (0.1, 0.2, 0.3):
        q2 = qt.createQureg(5, ENV1)
        qt.initPlusState(q2)
        exe(q2.amps, {k: v + shift for k, v in PARAMS.items()})
    assert telemetry.counter_value("engine_trace_total",
                                   kind="param_replay") == traces


def test_constant_tape_parameterized_defaults():
    """Constant angles lift to anonymous slots replaying their recorded
    values -- parameterized() with no params matches run() bitwise."""
    cc, _ = _pair()
    q1 = qt.createQureg(5, ENV1)
    qt.initPlusState(q1)
    cc.run(q1)
    q2 = qt.createQureg(5, ENV1)
    qt.initPlusState(q2)
    out = cc.parameterized()(q2.amps)
    assert np.array_equal(np.asarray(q1.amps), np.asarray(out))


def test_param_fused_pallas_replay_bit_identical():
    """Params ride a fused Pallas plan as apply-time-assembled barriers:
    the plan structure is value-independent and the replay matches the
    host-materialized constant variant of the SAME plan bitwise."""
    n = 8
    cp = Circuit(n)
    for q in range(n):
        cp.hadamard(q)
    cp.rotateZ(1, P("a"))
    cp.controlledNot(0, 2)
    cp.rotateX(3, P("b"))
    cp.multiRotateZ([0, n - 1], P("a"))
    cp.controlledNot(6, 7)
    fzp = cp.fused(max_qubits=5, pallas=True)
    assert any(f.__name__ == "_apply_pallas_run" for f, _, _ in fzp._tape)
    params = {"a": 0.7, "b": -1.3}
    lifted = fzp.lifted()
    base = Circuit(n)
    base._tape = materialize_tape(lifted, bind(lifted, params, device=False))
    q1 = qt.createQureg(n, ENV1)
    qt.initPlusState(q1)
    base.run(q1)
    q2 = qt.createQureg(n, ENV1)
    qt.initPlusState(q2)
    out = fzp.parameterized()(q2.amps, params)
    assert np.array_equal(np.asarray(q1.amps), np.asarray(out))
    # and the whole route stays numerically faithful to the raw tape
    q3 = qt.createQureg(n, ENV1)
    qt.initPlusState(q3)
    base2 = Circuit(n)
    base2._tape = materialize_tape(cp.lifted(),
                                   bind(cp.lifted(), params, device=False))
    base2.run(q3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(q3.amps),
                               atol=1e-12)


def test_param_fused_df_sharded_bit_identical(monkeypatch):
    """PRECISION=2 on the per-shard double-float Pallas route with runtime
    params: bit-identical to the same plan with host constants, zero
    f64-engine fallbacks."""
    monkeypatch.setenv("QUEST_PALLAS_DF", "1")
    env4 = qt.createQuESTEnv(jax.devices()[:4])
    n = 8
    cp = Circuit(n)
    for q in range(n):
        cp.hadamard(q)
    cp.rotateZ(1, P("a"))
    cp.controlledNot(0, 2)
    cp.rotateX(3, P("b"))
    cp.controlledNot(6, 7)
    fzs = cp.fused(max_qubits=5, pallas=True, shard_devices=4,
                   dtype=np.float64)
    params = {"a": 0.7, "b": -1.3}
    lifted = fzs.lifted()
    base = Circuit(n)
    base._tape = materialize_tape(lifted, bind(lifted, params, device=False))
    f0 = telemetry.counter_value("engine_fallback_total", reason="f64_engine")
    q1 = qt.createQureg(n, env4, precision_code=2)
    qt.initPlusState(q1)
    base.run(q1)
    q2 = qt.createQureg(n, env4, precision_code=2)
    qt.initPlusState(q2)
    out = fzs.parameterized()(q2.amps, params)
    assert np.array_equal(np.asarray(q1.amps), np.asarray(out))
    assert telemetry.counter_value("engine_fallback_total",
                                   reason="f64_engine") == f0


def test_param_plan_structure_is_static():
    """Fusing a param tape counts param barriers and the fused fingerprint
    does not depend on the other (constant) angles."""
    def build(th0):
        c = Circuit(8)  # above the 2^LANE_BITS Pallas planning floor
        for q in range(8):
            c.hadamard(q)
        c.rotateZ(1, P("a"))
        c.rotateX(2, th0)
        c.controlledNot(0, 2)
        return c.fused(max_qubits=4, pallas=True)

    b0 = telemetry.counter_value("fusion_param_barriers_total", mode="pallas")
    f1, f2 = build(0.3), build(0.3)
    assert telemetry.counter_value("fusion_param_barriers_total",
                                   mode="pallas") > b0
    # planning is deterministic: two fuses of the same tape share structure
    assert f1.fingerprint() == f2.fingerprint()
    # a constant fused INTO a kernel op is baked structure (by design --
    # the kernel data is value-dependent); only Param barriers stay free
    assert f1.fingerprint() != build(0.9).fingerprint()
    # whereas on the RAW tape the same constants are lifted values
    def raw(th0):
        c = Circuit(8)
        c.rotateZ(1, P("a"))
        c.rotateX(2, th0)
        return c
    assert raw(0.3).fingerprint() == raw(0.9).fingerprint()


# ---------------------------------------------------------------------------
# lifting and binding
# ---------------------------------------------------------------------------

def test_param_names_ordered_unique():
    c = Circuit(3)
    c.rotateZ(0, P("beta"))
    c.rotateX(1, P("alpha"))
    c.rotateZ(2, P("beta"))
    assert c.param_names == ("beta", "alpha")


def test_param_complex_slots():
    a, b = complex(np.cos(0.4), 0.0), complex(0.0, np.sin(0.4))
    cc, cp = Circuit(3), Circuit(3)
    cc.hadamard(0)
    cc.compactUnitary(1, a, b)
    cp.hadamard(0)
    cp.compactUnitary(1, P("alpha"), P("beta"))
    assert cc.fingerprint() == cp.fingerprint()
    q1 = qt.createQureg(3, ENV1)
    qt.initPlusState(q1)
    cc.run(q1)
    q2 = qt.createQureg(3, ENV1)
    qt.initPlusState(q2)
    out = cp.parameterized()(q2.amps, {"alpha": a, "beta": b})
    assert np.array_equal(np.asarray(q1.amps), np.asarray(out))


def test_param_rejected_outside_liftable_positions():
    # a constant channel probability is fine (baked structure) ...
    cd = Circuit(3, is_density_matrix=True)
    cd.mixDepolarising(0, 0.05)
    assert cd.lifted().slots == ()
    # ... a Param there has no traced assembly route and must raise
    cd2 = Circuit(3, is_density_matrix=True)
    cd2.mixDepolarising(0, P("p"))
    with pytest.raises(QuESTError, match="not supported"):
        cd2.lifted()


def test_missing_param_binding_raises():
    c = Circuit(2)
    c.rotateZ(0, P("theta"))
    with pytest.raises(QuESTError, match="missing values.*theta"):
        bind(c.lifted(), {})


def test_param_repr_eq_hash():
    assert P("x") == Param("x") and P("x") != P("y")
    assert hash(P("x")) == hash(Param("x"))
    assert repr(P("x")) == "P('x')"


# ---------------------------------------------------------------------------
# fingerprints
# ---------------------------------------------------------------------------

def test_fingerprint_value_collision_structure_miss():
    """Same structure / different values -> SAME fingerprint (cache hit by
    design); different structure -> different fingerprint."""
    def make(angle, target, extra=False):
        c = Circuit(4)
        c.hadamard(0)
        c.rotateZ(target, angle)
        c.controlledNot(0, 2)
        if extra:
            c.tGate(3)
        return c

    assert make(0.1, 1).fingerprint() == make(2.9, 1).fingerprint()
    assert make(0.1, 1).fingerprint() != make(0.1, 2).fingerprint()
    assert make(0.1, 1).fingerprint() != make(0.1, 1, extra=True).fingerprint()
    # baked operands (matrices) hash by value
    u1, u2 = np.eye(2, dtype=complex), np.diag([1.0, 1.0j])
    ca, cb = Circuit(2), Circuit(2)
    ca.unitary(0, u1)
    cb.unitary(0, u2)
    assert ca.fingerprint() != cb.fingerprint()


def test_fingerprint_structure_share_hits_cache():
    """A second circuit with the same structure but different constants
    reuses the compiled parameterized executable (plan_cache_hit_total)
    and stays bit-faithful to its OWN values."""
    def make(vals):
        c = Circuit(4)
        c.hadamard(0)
        c.rotateZ(1, vals[0])
        c.rotateX(2, vals[1])
        c.controlledNot(1, 3)
        return c

    c1, c2 = make((0.3, 1.1)), make((2.7, -0.4))
    exe1 = c1.parameterized()
    q = qt.createQureg(4, ENV1)
    qt.initPlusState(q)
    exe1(q.amps)  # trace + compile once
    hits = telemetry.counter_value("plan_cache_hit_total", cache="executable")
    traces = telemetry.counter_value("engine_trace_total",
                                     kind="param_replay")
    exe2 = c2.parameterized()
    assert telemetry.counter_value("plan_cache_hit_total",
                                   cache="executable") == hits + 1
    q2 = qt.createQureg(4, ENV1)
    qt.initPlusState(q2)
    out = exe2(q2.amps)
    assert telemetry.counter_value("engine_trace_total",
                                   kind="param_replay") == traces
    ref = qt.createQureg(4, ENV1)
    qt.initPlusState(ref)
    make((2.7, -0.4)).run(ref)
    assert np.array_equal(np.asarray(ref.amps), np.asarray(out))


# ---------------------------------------------------------------------------
# the LRU itself
# ---------------------------------------------------------------------------

def test_lru_scripted_hit_miss_evict_counters():
    cache = LRUCache(capacity=2, name="testlru")

    def c(name):
        return telemetry.counter_value(f"plan_cache_{name}_total",
                                       cache="testlru")

    h0, m0, e0 = c("hit"), c("miss"), c("evict")
    assert cache.get("a") is None                      # miss
    cache.put("a", 1)
    assert cache.get("a") == 1                         # hit
    assert cache.get_or_create("b", lambda: 2) == 2    # miss (create)
    assert cache.get_or_create("b", lambda: 99) == 2   # hit
    cache.put("c", 3)                                  # evicts "a" (LRU)
    assert cache.get("a") is None                      # miss
    assert cache.get("b") == 2 and cache.get("c") == 3  # 2 hits
    assert (c("hit") - h0, c("miss") - m0, c("evict") - e0) == (4, 3, 1)
    assert set(cache.keys()) == {"b", "c"}
    cache.clear()
    assert len(cache) == 0


def test_circuit_compiled_routes_through_global_lru(monkeypatch):
    """The per-circuit executable dicts are gone: compiled() hits the
    bounded global LRU, a tape append invalidates, and capacity
    pressure evicts with counters."""
    small = LRUCache(capacity=2, name="executable")
    monkeypatch.setattr(ecache, "_EXECUTABLES", small)
    c = Circuit(3)
    c.hadamard(0)
    c.controlledNot(0, 1)
    m0 = telemetry.counter_value("plan_cache_miss_total", cache="executable")
    f1 = c.compiled()
    assert c.compiled() is f1        # same mode -> hit, same object
    h = telemetry.counter_value("plan_cache_hit_total", cache="executable")
    c.tGate(2)                       # append invalidates the token
    f2 = c.compiled()
    assert f2 is not f1
    assert telemetry.counter_value(
        "plan_cache_hit_total", cache="executable") == h
    # fill past capacity -> uniform eviction telemetry
    e0 = telemetry.counter_value("plan_cache_evict_total", cache="executable")
    for _ in range(3):
        c.tGate(2)
        c.compiled()
    assert telemetry.counter_value(
        "plan_cache_evict_total", cache="executable") > e0
    assert len(small) <= 2
    assert telemetry.counter_value(
        "plan_cache_miss_total", cache="executable") >= m0 + 2


# ---------------------------------------------------------------------------
# the Engine
# ---------------------------------------------------------------------------

def _sweep(n_req, rng):
    return [{name: float(v) for name, v in zip(NAMES,
                                               rng.uniform(0, 6, len(NAMES)))}
            for _ in range(n_req)]


def test_engine_vmap_batch_matches_loop_bit_identical():
    _, cp = _pair()
    with Engine(cp, ENV1, max_batch=8, max_delay_ms=0.0,
                initial="plus") as eng:
        eng.warmup()
        sweep = _sweep(8, np.random.RandomState(11))
        traces = telemetry.counter_value("engine_trace_total",
                                         kind="param_replay")
        futs = eng.submit_many(sweep)
        batched = [np.asarray(f.result()) for f in futs]
        looped = [np.asarray(eng.run(p)) for p in sweep]
        assert all(np.array_equal(a, b) for a, b in zip(batched, looped))
        assert telemetry.counter_value(
            "engine_trace_total", kind="param_replay") == traces


def test_engine_warm_submit_zero_retraces_cache_hits():
    _, cp = _pair()
    with Engine(cp, ENV1, max_batch=4, max_delay_ms=0.0) as eng:
        eng.warmup()
        traces = telemetry.counter_value("engine_trace_total",
                                         kind="param_replay")
        hits = telemetry.counter_value("plan_cache_hit_total",
                                       cache="executable")
        for p in _sweep(3, np.random.RandomState(5)):
            eng.run(p)
        assert telemetry.counter_value(
            "engine_trace_total", kind="param_replay") == traces
        assert telemetry.counter_value(
            "plan_cache_hit_total", cache="executable") >= hits + 3


def test_engine_sharded_sequential_one_dispatch():
    n = 8
    cp = Circuit(n)
    _ansatz(cp, [P(name) for name in NAMES])
    cp.rotateZ(n - 1, 0.25)
    with Engine(cp, ENV8, max_batch=8, max_delay_ms=0.0) as eng:
        assert eng.sharded
        eng.warmup()
        sweep = _sweep(8, np.random.RandomState(3))
        b0 = telemetry.counter_value("engine_batches_total",
                                     mode="sequential")
        traces = telemetry.counter_value("engine_trace_total",
                                         kind="param_replay")
        futs = eng.submit_many(sweep)
        outs = [f.result() for f in futs]
        assert telemetry.counter_value(
            "engine_batches_total", mode="sequential") == b0 + 1
        assert telemetry.counter_value(
            "engine_trace_total", kind="param_replay") == traces
        assert all(len(o.sharding.device_set) == 8 for o in outs)
        # per-request results match the direct parameterized replay
        exe = cp.parameterized(donate=False)
        for p, o in zip(sweep, outs):
            ref = exe(eng.initial_amps + 0, p)
            assert np.array_equal(np.asarray(ref), np.asarray(o))


def test_engine_close_drains_and_rejects():
    _, cp = _pair()
    eng = Engine(cp, ENV1, max_batch=4, max_delay_ms=50.0)
    futs = eng.submit_many(_sweep(6, np.random.RandomState(1)))
    eng.close()
    assert all(f.done() for f in futs)
    shapes = {np.asarray(f.result()).shape for f in futs}
    assert shapes == {(2, 32)}
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(PARAMS)


def test_engine_close_nodrain_resolves_blocked_waiters():
    """Regression: close(drain=False) used to drop queued requests with
    their futures forever pending, deadlocking any thread blocked in
    result(). Every undispatched future must resolve with the typed
    cancellation error instead."""
    import threading

    from quest_tpu.resilience import QuESTCancelledError

    import time

    _, cp = _pair()
    eng = Engine(cp, ENV1, max_batch=1, max_delay_ms=0.0)
    gate = threading.Event()
    orig = eng._dispatch
    eng._dispatch = lambda b: (gate.wait(10), orig(b))
    futs = eng.submit_many(_sweep(4, np.random.RandomState(3)))
    waited = {}

    def waiter():
        try:
            waited["out"] = futs[-1].result(timeout=30)
        except BaseException as e:  # noqa: BLE001 - recorded for assert
            waited["out"] = e

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.1)  # the loop is now blocked dispatching request 0
    # release the in-flight dispatch only after close() has started, so
    # requests 1..3 are provably still queued when the close decision lands
    threading.Timer(0.2, gate.set).start()
    eng.close(drain=False)
    t.join(timeout=30)
    assert not t.is_alive(), "waiter deadlocked on an unresolved future"
    assert all(f.done() for f in futs)
    assert isinstance(waited["out"], QuESTCancelledError)
    assert futs[0].exception() is None  # in-flight work still completed
    for f in futs[1:]:
        assert isinstance(f.exception(), QuESTCancelledError)


def test_engine_value_free_circuit():
    c = Circuit(3)
    c.hadamard(0)
    c.controlledNot(0, 1)
    c.pauliX(2)
    with Engine(c, ENV1, max_batch=4, max_delay_ms=0.0) as eng:
        futs = eng.submit_many([None] * 4)
        outs = [np.asarray(f.result()) for f in futs]
        ref = qt.createQureg(3, ENV1)
        c.run(ref)
        # the engine replays the tape's dense plan (one block here), the
        # reference the tape gate by gate: equal to f64 rounding, and the
        # four replies of the one program equal to the bit
        assert all(np.max(np.abs(o - np.asarray(ref.amps))) <= 1e-13
                   for o in outs)
        assert all(np.array_equal(o, outs[0]) for o in outs)


def test_engine_bad_params_raise_at_submit():
    _, cp = _pair()
    with Engine(cp, ENV1, max_batch=2, max_delay_ms=0.0) as eng:
        with pytest.raises(QuESTError, match="missing values"):
            eng.submit({"nope": 1.0})


def test_engine_telemetry_series():
    _, cp = _pair()
    r0 = telemetry.counter_value("engine_requests_total")
    with Engine(cp, ENV1, max_batch=4, max_delay_ms=0.0) as eng:
        eng.warmup()
        [f.result() for f in eng.submit_many(_sweep(4,
                                                    np.random.RandomState(9)))]
    assert telemetry.counter_value("engine_requests_total") >= r0 + 4
    snap = telemetry.snapshot()
    assert any(k.startswith("engine_batch_size") for k in snap["histograms"])
    assert any(k.startswith("engine_request_latency_seconds")
               for k in snap["histograms"])
    assert snap["gauges"].get("engine_queue_depth") == 0


# ---------------------------------------------------------------------------
# the Engine replays a raw tape's dense plan
# ---------------------------------------------------------------------------

def _benchmark_ansatz():
    """The served cell's builder (benchmark/circuits/serving_ansatz.py)."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "benchmark", "circuits", "serving_ansatz.py")
    spec = importlib.util.spec_from_file_location("serving_ansatz", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_engine_serves_raw_tape_through_its_dense_plan():
    """The benchmark's ansatz at rehearsal size (10q, depth 2): the Engine
    fuses the raw tape (40 Param entries in blocks, none a barrier) and
    serves 16 requests from ONE program: no retrace, no fallback, every
    coalesced lane bit-equal to ``eng.run`` of the same angles, and the
    unfused parameterized replay within f32 rounding."""
    sa = _benchmark_ansatz()
    n, depth = 10, 2
    circ = Circuit(n)
    sa.build(circ, num_qubits=n, depth=depth, angle=P)
    names = sa.param_names(num_qubits=n, depth=depth)
    rng = np.random.RandomState(27)
    sweep = [dict(zip(names, map(float, rng.uniform(0, 2 * np.pi,
                                                    len(names)))))
             for _ in range(16)]
    fused0 = telemetry.counter_value("fusion_param_fused_total", mode="dense")
    barriers0 = telemetry.counter_value("fusion_param_barriers_total",
                                        mode="dense")
    fallback0 = telemetry.counter_value("engine_fallback_total")
    with Engine(circ, ENV1, max_batch=8, max_delay_ms=0.0,
                precision_code=1) as eng:
        assert telemetry.counter_value(
            "fusion_param_fused_total", mode="dense") == fused0 + len(names)
        assert telemetry.counter_value(
            "fusion_param_barriers_total", mode="dense") == barriers0
        start = [e for e in telemetry.events()
                 if e.get("name") == "engine.start"][-1]
        assert start["plan_blocks"] == len(eng._program._tape) == 10
        assert start["plan_barriers"] == 0
        assert set(eng.param_names) == set(names)
        eng.warmup()
        traces = telemetry.counter_value("engine_trace_total",
                                         kind="param_replay")
        lanes = [np.asarray(f.result()) for f in eng.submit_many(sweep)]
        lone = [np.asarray(eng.run(p)) for p in sweep]
        assert telemetry.counter_value(
            "engine_trace_total", kind="param_replay") == traces
        assert all(np.array_equal(a, b) for a, b in zip(lanes, lone))
    assert telemetry.counter_value("engine_fallback_total") == fallback0
    exe = circ.parameterized(donate=False)
    amps0 = np.zeros((2, 1 << n), np.float32)
    amps0[0, 0] = 1.0
    for p, lane in zip(sweep[:4], lanes):
        want = np.asarray(exe(jax.numpy.asarray(amps0), p))
        assert np.max(np.abs(lane - want)) <= 1e-6


def test_engine_keeps_given_plan_sharded_and_gradient_routes():
    """What the code can see decides what an Engine replays: a circuit the
    caller fused, a sharded register and a values-aware finalize keep the
    circuit as given."""
    _, cp = _pair()
    fused = cp.fused(max_qubits=3)
    with Engine(fused, ENV1, max_batch=2) as eng:
        assert eng._program is fused
    with Engine(cp, ENV8, max_batch=2) as eng:
        assert eng.sharded and eng._program is cp
    codes = [[3, 0, 0, 0, 0]]
    with Engine(cp, ENV1, max_batch=2, hamiltonian=(codes, [1.0])) as eng:
        assert eng._program is not cp
        assert eng.grad_engine()._program is cp


def test_engines_plan_and_trace_side_by_side():
    """Planning and tracing touch no process-wide state: eight Engines
    over distinct structures, built and served from eight threads at
    once, each reply with the raw tape's parameterized replay -- every
    Param entry in a block, none fallen back to a barrier."""
    import threading

    sa = _benchmark_ansatz()
    barriers0 = telemetry.counter_value("fusion_param_barriers_total",
                                        mode="dense")
    start = threading.Barrier(8)
    errs, failures = {}, []

    def serve(i):
        try:
            n, depth = 6 + i % 4, 1 + i // 4
            circ = Circuit(n)
            sa.build(circ, num_qubits=n, depth=depth, angle=P)
            names = sa.param_names(num_qubits=n, depth=depth)
            rng = np.random.RandomState(i)
            params = dict(zip(names, map(float, rng.uniform(
                0, 2 * np.pi, len(names)))))
            start.wait(60)
            with Engine(circ, ENV1, max_batch=4, max_delay_ms=0.0) as eng:
                got = np.asarray(eng.submit(params).result(120))
            amps0 = np.zeros((2, 1 << n))
            amps0[0, 0] = 1.0
            want = np.asarray(circ.parameterized(donate=False)(
                jax.numpy.asarray(amps0), params))
            errs[i] = float(np.max(np.abs(got - want)))
        except Exception as e:  # surfaced below, on the test's thread
            failures.append((i, repr(e)))

    threads = [threading.Thread(target=serve, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    assert not failures, failures
    assert sorted(errs) == list(range(8))
    assert max(errs.values()) <= 1e-12, errs
    assert telemetry.counter_value("fusion_param_barriers_total",
                                   mode="dense") == barriers0


# ---------------------------------------------------------------------------
# the batch program's calling convention: one packed array a slot kind in,
# the lanes as outputs of their own (PR 31)
# ---------------------------------------------------------------------------

def _kraus():
    return tuple(qt.channels.kraus_ops("depolarising", 0.3))


def _served(n, depth):
    """The served cell's ansatz at a test's size: ``2 * n * depth`` named
    real slots."""
    sa = _benchmark_ansatz()
    circ = Circuit(n)
    sa.build(circ, num_qubits=n, depth=depth, angle=P)
    return circ, sa.param_names(num_qubits=n, depth=depth)


def _three_real():
    circ, names = Circuit(3), ["u", "v", "w"]
    for q, name in enumerate(names):
        circ.rotateY(q, P(name))
    return circ, names


def _angle_sets(names, rng, count):
    return [dict(zip(names, map(float, rng.uniform(-3, 3, len(names)))))
            for _ in range(count)]


def _three_kinds():
    """Real, complex and seed slots interleaved on one tape, named and
    anonymous: slot order real, complex, complex, seed, real, seed,
    complex, complex, real."""
    c = Circuit(3)
    c.hadamard(0)
    c.rotateY(0, P("a0"))
    c.compactUnitary(1, P("al"), P("be"))
    c.applyTrajectoryKraus((2,), _kraus(), P("s0"), site=0)
    c.rotateZ(2, P("a1"))
    c.applyTrajectoryKraus((0,), _kraus(), P("s1"), site=1)
    c.compactUnitary(0, complex(np.cos(0.3), 0.0), complex(0.0, np.sin(0.3)))
    c.rotateX(1, 0.25)
    return c


def _three_kinds_params(rng):
    th = float(rng.uniform(0, np.pi))
    return {"a0": float(rng.uniform(-3, 3)), "a1": float(rng.uniform(-3, 3)),
            "al": complex(np.cos(th), 0.0), "be": complex(0.0, np.sin(th)),
            "s0": int(rng.randint(1, 2**31)), "s1": int(rng.randint(1, 2**31))}


def _launch_counts():
    return (telemetry.counter_value("engine_launch_args_total"),
            telemetry.counter_value("device_dispatch_total",
                                    route="engine_vmap"))


@pytest.mark.parametrize("shape,slots,kinds", [
    ("served", 160, 1), ("three real", 3, 1), ("three kinds", 9, 3)])
def test_batch_program_takes_one_array_per_slot_kind(shape, slots, kinds):
    """The state and one packed array a slot kind the tape has, however
    many slots: ``engine_launch_args_total`` over the launches."""
    rng = np.random.RandomState(31)
    if shape == "three kinds":
        circ = _three_kinds()
        sweep = [_three_kinds_params(rng) for _ in range(4)]
    else:
        circ, names = _served(4, 20) if shape == "served" else _three_real()
        sweep = _angle_sets(names, rng, 4)
    with Engine(circ, ENV1, max_batch=4, max_delay_ms=0.0) as eng:
        assert len(eng._lifted.slots) == slots
        assert len(eng._packs) == kinds
        assert sorted(i for _, cols in eng._packs for i in cols) == list(
            range(len(eng._lifted.slots)))
        eng.warmup()
        args0, launches0 = _launch_counts()
        for f in eng.submit_many(sweep):
            f.result()
        for p in sweep[:2]:
            eng.run(p)
        args1, launches1 = _launch_counts()
    assert launches1 - launches0 == 3
    assert (args1 - args0) / (launches1 - launches0) == 1 + kinds


def test_interleaved_slot_kinds_bind_each_slot_to_its_own_column():
    """Every lane of a batch over a tape with the three kinds interleaved
    is the single-request executable's state on the same values tuple: no
    slot reads another slot's column, no lane another's row."""
    circ = _three_kinds()
    rng = np.random.RandomState(7)
    sweep = [_three_kinds_params(rng) for _ in range(8)]
    with Engine(circ, ENV1, max_batch=8, max_delay_ms=0.0) as eng:
        assert [s.kind for s in eng._lifted.slots] == [
            "real", "complex", "complex", "seed", "real", "seed",
            "complex", "complex", "real"]
        assert eng._packs == (("real", (0, 4, 8)),
                              ("complex", (1, 2, 6, 7)), ("seed", (3, 5)))
        eng.warmup()
        lanes = [np.asarray(f.result()) for f in eng.submit_many(sweep)]
        one = eng._exec1()
        lone = [np.asarray(eng.run(p)) for p in sweep]
        for p, lane, alone in zip(sweep, lanes, lone):
            values = bind(eng._lifted, p)
            rows = _pack_rows(eng._packs, values)
            assert [r.dtype for r in rows] == [
                values[0].dtype, values[1].dtype, np.uint32]
            assert [r.tolist() for r in rows] == [
                [values[i].item() for i in cols] for _, cols in eng._packs]
            # the same program, alone in its batch: the same bits
            assert np.array_equal(lane, alone)
            # the single-request executable is another XLA program (an
            # unbatched contraction may round its last bit otherwise): a
            # slot bound to a neighbour's column would miss by O(1)
            want = np.asarray(one.with_values(eng.initial_amps + 0, values))
            assert np.max(np.abs(lane - want)) <= 1e-14
    assert len({lane.tobytes() for lane in lanes}) == 8


def test_short_batch_padded_to_width_resolves_its_own_lanes():
    """Five requests through the program of eight: five futures, five
    distinct states, each the state of its own angles."""
    circ, names = _served(4, 2)
    rng = np.random.RandomState(5)
    sweep = _angle_sets(names, rng, 5)
    with Engine(circ, ENV1, max_batch=8, max_delay_ms=0.0) as eng:
        eng.warmup()
        _, launches0 = _launch_counts()
        futs = eng.submit_many(sweep)
        lanes = [np.asarray(f.result()) for f in futs]
        assert _launch_counts()[1] == launches0 + 1
        lone = [np.asarray(eng.run(p)) for p in sweep]
    assert len(lanes) == 5 and len({lane.tobytes() for lane in lanes}) == 5
    assert all(np.array_equal(a, b) for a, b in zip(lanes, lone))
    exe = circ.parameterized(donate=False)
    for p, lane in zip(sweep, lanes):
        q = qt.createQureg(4, ENV1)
        assert np.max(np.abs(lane - np.asarray(exe(q.amps, p)))) <= 1e-12


def test_finalize_returning_a_dict_resolves_per_lane_dicts():
    circ, names = _served(3, 1)
    rng = np.random.RandomState(9)
    sweep = _angle_sets(names, rng, 3)

    def finalize(amps):
        return {"p0": amps[0, 0] ** 2 + amps[1, 0] ** 2,
                "head": amps[:, :2], "nested": (amps[0, 1],)}

    with Engine(circ, ENV1, max_batch=4, max_delay_ms=0.0) as plain:
        states = [np.asarray(f.result()) for f in plain.submit_many(sweep)]
    with Engine(circ, ENV1, max_batch=4, max_delay_ms=0.0,
                finalize=finalize) as eng:
        outs = [f.result() for f in eng.submit_many(sweep)]
    for out, state in zip(outs, states):
        assert set(out) == {"p0", "head", "nested"}
        # no ``unpack`` on this finalize: a lane stays device arrays
        assert all(isinstance(leaf, jax.Array)
                   for leaf in jax.tree_util.tree_leaves(out))
        assert np.asarray(out["head"]).shape == (2, 2)
        assert np.array_equal(np.asarray(out["head"]), state[:, :2])
        assert float(out["p0"]) == pytest.approx(
            state[0, 0] ** 2 + state[1, 0] ** 2, abs=1e-15)
        assert float(out["nested"][0]) == state[0, 1]


def test_grad_engine_lanes_match_the_single_request_gradient():
    circ, names = _served(3, 2)
    codes, coeffs = [[3, 0, 0], [1, 1, 0]], [0.7, -0.4]
    rng = np.random.RandomState(13)
    sweep = _angle_sets(names, rng, 4)
    with Engine(circ, ENV1, max_batch=4, max_delay_ms=0.0,
                hamiltonian=(codes, coeffs)) as eng:
        eng.warmup_grad()
        grad = eng.grad_engine()
        assert grad._packs == (("real", tuple(range(len(names)))),)
        d0 = telemetry.counter_value("device_dispatch_total",
                                     route="grad_request")
        batch = [f.result() for f in grad.submit_many(sweep)]
        assert telemetry.counter_value(
            "device_dispatch_total", route="grad_request") == d0 + 1
        gx = circ.gradient((codes, coeffs), donate=False)
        for p, out in zip(sweep, batch):
            value, grads = eng.submit_grad(p).result()
            assert float(out["value"]) == float(value)
            assert {k: float(v) for k, v in out["grads"].items()} == {
                k: float(v) for k, v in grads.items()}
            ref = gx(qt.createQureg(3, ENV1).amps, p)
            np.testing.assert_allclose(float(value), float(ref["value"]),
                                       atol=1e-12, rtol=0)
            for k in names:
                np.testing.assert_allclose(float(grads[k]),
                                           float(ref["grads"][k]),
                                           atol=1e-12, rtol=0)


def test_poisoned_request_bisects_to_its_neighbours_exact_results():
    from quest_tpu.resilience import fault_plan
    from quest_tpu.resilience.errors import PoisonedRequestFault

    circ, names = _served(3, 2)
    rng = np.random.RandomState(17)
    sweep = _angle_sets(names, rng, 6)
    b0 = telemetry.counter_value("engine_bisections_total")
    with Engine(circ, ENV1, max_batch=8, max_delay_ms=0.0) as eng:
        eng.warmup()
        with fault_plan("engine.request:poison:4"):
            futs = eng.submit_many(sweep)
            got = []
            for f in futs:
                try:
                    got.append(np.asarray(f.result(timeout=120)))
                except PoisonedRequestFault as e:
                    got.append(e)
        assert isinstance(got[3], PoisonedRequestFault)
        for i in (0, 1, 2, 4, 5):
            assert np.array_equal(got[i], np.asarray(eng.run(sweep[i])))
    assert telemetry.counter_value("engine_bisections_total") > b0


def test_warm_batch_is_one_executable_and_one_transfer(tmp_path):
    """After warm-up a batch is exactly one device program and one transfer
    a slot kind: the profiler's host line counts every executable the
    process runs and every array it puts, whoever dispatched it, so an
    eager slice, repeat or stack in assemble, launch or resolve would show."""
    import glob

    from jax.profiler import ProfileData

    circ, names = _served(4, 20)
    rng = np.random.RandomState(23)
    sweeps = [_angle_sets(names, rng, 8) for _ in range(20)]
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    with Engine(circ, ENV1, max_batch=8, max_delay_ms=0.0) as eng:
        eng.warmup()
        for f in eng.submit_many(sweeps[0]):
            jax.block_until_ready(f.result())
        traces = telemetry.counter_value("engine_trace_total",
                                         kind="param_replay")
        _, launches0 = _launch_counts()
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for sweep in sweeps:
                for f in eng.submit_many(sweep):
                    jax.block_until_ready(f.result())
        finally:
            jax.profiler.stop_trace()
        assert _launch_counts()[1] == launches0 + 20
        assert telemetry.counter_value(
            "engine_trace_total", kind="param_replay") == traces
    [path] = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                           / "*.xplane.pb"))
    seen = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                seen[ev.name] = seen.get(ev.name, 0) + 1
    executed = {k: v for k, v in seen.items()
                if k.startswith("PjRt") and k.endswith("Executable::Execute")}
    assert sum(executed.values()) == 20, executed
    assert seen.get("DevicePut", 0) == 20
    assert not [k for k in seen if k.startswith("PjitFunction(")
                and "engine_vmap" not in k], seen


@pytest.mark.parametrize("closes", ["full", "device done", "quiet"])
def test_window_stays_open_while_the_batch_ahead_executes(closes):
    """With a batch in flight a request that finds the window open is not
    issued alone behind it as a whole padded program of its own: every
    arrival restarts the timer, and the window closes when it is full,
    at once when the device finishes the batch ahead, or when
    ``max_delay_ms`` passes with no arrival."""
    import threading

    circ, names = _served(3, 1)
    sweep = _angle_sets(names, np.random.RandomState(29), 8)
    with Engine(circ, ENV1, max_batch=4, max_delay_ms=0.0) as oracle:
        want = [np.asarray(oracle.run(p)) for p in sweep]
    # the executable is the oracle's (one structure, one cache entry)
    with Engine(circ, ENV1, max_batch=4, max_delay_ms=400.0,
                async_depth=2) as eng:
        done_ahead = threading.Event()
        probe = eng._ring_head_ready
        eng._ring_head_ready = lambda: done_ahead.is_set() and probe()
        n0, sum0 = (telemetry.snapshot("engine_batch_size")["histograms"]
                    ["engine_batch_size"][k] for k in ("count", "sum"))

        def batches():
            h = telemetry.snapshot("engine_batch_size")["histograms"][
                "engine_batch_size"]
            return h["count"] - n0, h["sum"] - sum0

        futs = eng.submit_many(sweep[:5])      # a full batch and one more
        deadline = time.perf_counter() + 30
        while batches()[0] < 1 and time.perf_counter() < deadline:
            time.sleep(0.005)
        time.sleep(0.1)                        # 200 polls of the window
        assert batches() == (1, 4) and not futs[4].done()
        if closes == "full":
            # one at a time, each inside the timer the one before restarted:
            # 3 x 0.25 s is past the 0.4 s the first request's timer had
            for p in sweep[5:]:
                time.sleep(0.25)
                assert batches() == (1, 4)
                futs.append(eng.submit(p))
            want_batches = (2, 8)
        elif closes == "device done":
            done_ahead.set()
            want_batches = (2, 5)
        else:
            want_batches = (2, 5)              # the timer alone closes it
        lanes = [np.asarray(f.result(timeout=60)) for f in futs]
        assert batches() == want_batches
        done_ahead.set()
        assert all(np.array_equal(lane, w) for lane, w in zip(lanes, want))


def _held_window(eng, sweep):
    """A full batch issued and one more request held in the open window
    behind it: the readiness probe is made to say the batch ahead is
    still executing. Returns the five futures."""
    eng._ring_head_ready = lambda: False
    n0 = telemetry.snapshot("engine_batch_size")["histograms"][
        "engine_batch_size"]["count"]
    futs = eng.submit_many(sweep[:4]) + [eng.submit(sweep[4])]
    deadline = time.perf_counter() + 30
    while (telemetry.snapshot("engine_batch_size")["histograms"][
            "engine_batch_size"]["count"] == n0
           and time.perf_counter() < deadline):
        time.sleep(0.005)
    return futs


def test_partial_window_behind_a_hung_head_is_bounded_by_the_watchdog():
    """A wedged device must stay a DETECTABLE hang with a partial window
    behind it: the window closes by its timer, the head's bounded retire
    names the hang, and the request that was held is served behind it."""
    from quest_tpu.resilience import fault_plan, watchdog_deadline
    from quest_tpu.resilience.errors import QuESTHangError

    circ, names = _served(3, 1)
    sweep = _angle_sets(names, np.random.RandomState(31), 5)
    with Engine(circ, ENV1, max_batch=4, max_delay_ms=20.0,
                async_depth=2) as eng:
        eng.warmup()
        want = np.asarray(eng.run(sweep[4]))
        with watchdog_deadline(200), fault_plan("engine.retire:hang:1"):
            t0 = time.perf_counter()
            futs = _held_window(eng, sweep)
            for f in futs[:4]:
                with pytest.raises(QuESTHangError):
                    f.result(timeout=30)
            held = np.asarray(futs[4].result(timeout=30))
            took = time.perf_counter() - t0
        assert np.array_equal(held, want)
        # the window's timer, one deadline of bounded sync, and slack
        assert 0.2 <= took < 10, took


def test_request_deadline_expires_inside_a_held_window():
    """``timeout=`` is honoured behind a batch the device does not let go
    of: the window closes by its timer and the request, its deadline
    passed, expires instead of running."""
    from quest_tpu.resilience.errors import QuESTTimeoutError

    circ, names = _served(3, 1)
    sweep = _angle_sets(names, np.random.RandomState(37), 5)
    with Engine(circ, ENV1, max_batch=4, max_delay_ms=100.0,
                async_depth=2) as eng:
        eng.warmup()
        eng._ring_head_ready = lambda: False
        futs = eng.submit_many(sweep[:4])
        late = eng.submit(sweep[4], timeout=0.02)
        t0 = time.perf_counter()
        with pytest.raises(QuESTTimeoutError):
            late.result(timeout=30)
        assert time.perf_counter() - t0 < 10
        lanes = [np.asarray(f.result(timeout=30)) for f in futs]
        assert all(np.array_equal(lane, np.asarray(eng.run(p)))
                   for lane, p in zip(lanes, sweep))


def test_finished_batch_ahead_closes_the_window_without_the_timer():
    """Once the batch ahead is done the window closes at once: neither
    its futures nor the held request wait out ``max_delay_ms``."""
    circ, names = _served(3, 1)
    sweep = _angle_sets(names, np.random.RandomState(41), 5)
    with Engine(circ, ENV1, max_batch=4, max_delay_ms=0.0) as oracle:
        want = [np.asarray(oracle.run(p)) for p in sweep]
    # the executable is the oracle's (one structure, one cache entry), so
    # nothing compiles inside the timed part
    with Engine(circ, ENV1, max_batch=4, max_delay_ms=5_000.0,
                async_depth=2) as eng:
        t0 = time.perf_counter()
        futs = eng.submit_many(sweep)          # a full batch and one more
        lanes = [np.asarray(f.result(timeout=15)) for f in futs]
        assert time.perf_counter() - t0 < 2.5
        assert all(np.array_equal(lane, w) for lane, w in zip(lanes, want))
