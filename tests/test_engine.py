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

import numpy as np
import pytest

import jax

import quest_tpu as qt
from quest_tpu import telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.engine import Engine, LRUCache, P, Param
from quest_tpu.engine import cache as ecache
from quest_tpu.engine.params import bind, lift_tape, materialize_tape
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
    """The per-circuit executable dicts are gone: compiled()/compiled_blocks
    hit the bounded global LRU, a tape append invalidates, and capacity
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
