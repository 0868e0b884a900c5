"""The program's tracing joined to the device trace (PR 26).

Contracts under test, each deterministic (no wall-clock ratio with a
tolerance that a loaded host could miss):

- a ``telemetry.span`` and a hot-path ``telemetry.region`` open a profiler
  ``TraceAnnotation`` of their own name for their lifetime, and the
  annotation really lands on the profiler's host line;
- a region aggregates and annotates only: no ring event, and the ring's rare
  events (``pallas.compile``) survive 10^5 ``circuit.run`` windows;
- the ``pallas_call`` names and the jitted programs' names are pure functions
  of static arguments: the same from two fresh processes, different for a
  different op count or swap;
- the ``jax.monitoring`` listeners count one trace, one lowering and one
  compile for a first call and nothing for a second, and charge an interval
  nested in another once, however many nest (PR 39), in a stack as deep as
  the nest;
- the seven phases tile ``dur_ms`` within 1% on every engine route, with
  delays injected between the batcher's regions;
- the ``device`` phase is the stream-ordered estimate (a synthetic
  three-entry ring).
"""

import glob
import subprocess
import sys
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import quest_tpu as qt
from quest_tpu import telemetry
from quest_tpu.circuits import Circuit, named_program
from quest_tpu.engine import Engine, EnginePool, P
from quest_tpu.engine import engine as engmod
from quest_tpu.ops import pallas_gates as PG

ENV1 = qt.createQuESTEnv(jax.devices()[:1])


def _ansatz(n=3):
    c = Circuit(n)
    for q in range(n):
        c.rotateY(q, P(f"t{q}"))
    for q in range(n - 1):
        c.controlledNot(q, q + 1)
    return c


def _params(c, seed):
    rng = np.random.default_rng(seed)
    return {name: float(v) for name, v
            in zip(c.lifted().param_names, rng.uniform(-2, 2, 64))}


# ---------------------------------------------------------------------------
# spans and regions on the profiler's clock
# ---------------------------------------------------------------------------

class _FakeAnnotation:
    log: list = []

    def __init__(self, name, **labels):
        self.name, self.labels = name, labels

    def __enter__(self):
        self.log.append(("enter", self.name, self.labels,
                         threading.get_ident()))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, self.labels,
                         threading.get_ident()))
        return False


@pytest.mark.parametrize("kind", ["span", "region"])
def test_span_and_region_open_an_annotation_of_their_name(kind, monkeypatch):
    monkeypatch.setattr(_FakeAnnotation, "log", [])
    monkeypatch.setattr(telemetry, "_ANNOTATION", _FakeAnnotation)
    telemetry.reset()
    if kind == "span":
        with telemetry.span("probe.outer", qubits=5):
            assert _FakeAnnotation.log[-1][:3] == (
                "enter", "probe.outer", {"qubits": 5})
        want_labels = {"qubits": 5}
    else:
        with telemetry.region("probe.outer") as rg:
            assert _FakeAnnotation.log[-1][:3] == ("enter", "probe.outer", {})
        assert rg.t1 >= rg.t0 > 0.0
        want_labels = {}
    me = threading.get_ident()
    assert _FakeAnnotation.log == [
        ("enter", "probe.outer", want_labels, me),
        ("exit", "probe.outer", want_labels, me)]
    # nothing is annotated while telemetry is disabled
    with telemetry.disabled():
        with getattr(telemetry, kind)("probe.off"):
            pass
    assert len(_FakeAnnotation.log) == 2


def test_annotations_land_on_the_profiler_s_host_line(tmp_path):
    from jax.profiler import ProfileData

    telemetry.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("probe.span", k=3):
            with telemetry.region("probe.region"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                           / "*.xplane.pb"))
    seen = {ev.name: dict(ev.stats)
            for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith("probe.")}
    assert seen == {"probe.span": {"k": 3}, "probe.region": {}}


def test_region_writes_no_ring_event_and_rare_events_survive():
    telemetry.reset()
    telemetry.event("pallas.compile", kind="float32", interpret=False)
    for _ in range(100_000):
        with telemetry.region("circuit.run"):
            pass
    evs = telemetry.events()
    assert [e["name"] for e in evs] == ["pallas.compile"]
    assert telemetry.counter_value("telemetry_events_dropped_total") == 0
    agg = telemetry.snapshot("circuit.run")["spans"]["circuit.run"]
    assert agg["count"] == 100_000 and agg["total_s"] >= agg["max_s"] > 0
    telemetry.reset()


def test_circuit_run_is_a_region_not_a_ring_event():
    c = Circuit(3)
    c.hadamard(0)
    c.controlledNot(0, 1)
    q = qt.createQureg(3, ENV1)
    c.run(q)                      # compiles; the ring may hold what that did
    telemetry.reset()
    for _ in range(5):
        c.run(q)
    assert telemetry.events() == []
    assert telemetry.snapshot("circuit.run")["spans"]["circuit.run"][
        "count"] == 5
    assert telemetry.counter_value("device_dispatch_total",
                                   route="circuit") == 5


def test_event_ring_is_a_bounded_deque(monkeypatch):
    monkeypatch.setenv("QUEST_TELEMETRY_EVENTS_MAX", "4")
    monkeypatch.setattr(telemetry.REGISTRY, "_events_max", None)
    telemetry.reset()
    for i in range(6):
        telemetry.event("ring.probe", i=i)
    ring = telemetry.REGISTRY._events
    assert isinstance(ring, deque) and ring.maxlen == 4
    assert [e["i"] for e in telemetry.events()] == [2, 3, 4, 5]
    assert telemetry.counter_value("telemetry_events_dropped_total") == 2
    monkeypatch.setattr(telemetry.REGISTRY, "_events_max", None)
    monkeypatch.delenv("QUEST_TELEMETRY_EVENTS_MAX")
    telemetry.reset()


# ---------------------------------------------------------------------------
# names: pure functions of static arguments
# ---------------------------------------------------------------------------

_NAMES_SCRIPT = """
import numpy as np
from quest_tpu.circuits import Circuit, named_program
from quest_tpu.ops import pallas_gates as PG
print(PG.kernel_name("dma", 2, np.float32, 57, 0, 7))
print(PG.kernel_name("grid", 2, np.float64, 20, 7, 0))
print(PG.kernel_name("df1", 4, np.float32, 4))
c = Circuit(5)
c.hadamard(0); c.controlledNot(0, 1); c.rotateY(2, 0.3)
print(named_program(lambda a: a, c, "circuit").__name__)
print(named_program(lambda a: a, c, "engine_vmap", "b8").__name__)
d = Circuit(4, is_density_matrix=True)
print(named_program(lambda a: a, d, "segment", "i0_12").__name__)
"""


def test_names_are_the_same_from_two_fresh_processes():
    outs = [subprocess.run([sys.executable, "-c", _NAMES_SCRIPT],
                           capture_output=True, text=True, timeout=300)
            for _ in range(2)]
    assert all(o.returncode == 0 for o in outs), outs[0].stderr[-2000:]
    assert outs[0].stdout == outs[1].stdout
    assert outs[0].stdout.split() == [
        "qt_fused_dma_f32_ops57_ls0_ss7", "qt_fused_grid_f64_ops20_ls7_ss0",
        "qt_fused_df1_df_ops4_ls0_ss0", "qt_circuit_sv_n5_g3",
        "qt_engine_vmap_sv_n5_g3_b8", "qt_segment_dm_n4_g0_i0_12"]


def test_kernel_name_tells_op_count_and_swaps_apart():
    base = PG.kernel_name("dma", 2, np.float32, 57, 0, 7)
    others = {PG.kernel_name("dma", 2, np.float32, 20, 0, 7),
              PG.kernel_name("dma", 2, np.float32, 57, 7, 0),
              PG.kernel_name("dma", 2, np.float32, 57, 0, 0),
              PG.kernel_name("grid", 2, np.float32, 57, 0, 7),
              PG.kernel_name("dma", 4, np.float32, 57, 0, 7)}
    assert base not in others and len(others) == 5
    # trace_reduce splits an op's name at its first '.': none inside ours
    assert all(ch.isalnum() or ch == "_" for name in others | {base}
               for ch in name)


def test_jitted_programs_carry_their_circuit_s_name():
    c = Circuit(4)
    c.hadamard(0)
    c.controlledNot(0, 1)
    amps = jnp.zeros((2, 16), c_dtype := qt.precision.real_dtype())
    assert c_dtype is not None
    text = jax.jit(named_program(c.as_fn(), c, "circuit")).lower(amps).as_text()
    assert "module @jit_qt_circuit_sv_n4_g2" in text
    # each tape entry's gate kind is in the op metadata of what it lowers to
    debug = jax.jit(c.as_fn()).lower(amps).as_text(debug_info=True)
    assert "hadamard" in debug and "controlledNot" in debug


# ---------------------------------------------------------------------------
# compile events from inside JAX
# ---------------------------------------------------------------------------

def _hist_counts():
    h = telemetry.snapshot("jax_")["histograms"]
    return {k: h.get(k, {"count": 0})["count"]
            for k in ("jax_trace_seconds", "jax_lower_seconds",
                      "jax_backend_compile_seconds")}


def test_listeners_count_a_first_call_and_nothing_on_a_second():
    assert telemetry.watch_jax_compiles() is True   # registered at import
    x = jnp.arange(8.0)
    # lax primitives only: a jnp operator is itself jitted and would
    # report a nested trace of its own
    fn = jax.jit(lambda v: jax.lax.add(jax.lax.mul(v, v), v))
    telemetry.reset()
    fn(x).block_until_ready()
    assert _hist_counts() == {"jax_trace_seconds": 1, "jax_lower_seconds": 1,
                              "jax_backend_compile_seconds": 1}
    sums = telemetry.snapshot("jax_")["histograms"]
    assert all(h["sum"] >= 0.0 for h in sums.values())
    telemetry.reset()
    fn(x).block_until_ready()
    assert _hist_counts() == {"jax_trace_seconds": 0, "jax_lower_seconds": 0,
                              "jax_backend_compile_seconds": 0}


def test_listeners_are_registered_once():
    from jax._src import monitoring

    before = len(monitoring.get_event_duration_listeners())
    assert telemetry._jax_duration in monitoring.get_event_duration_listeners()
    assert telemetry.watch_jax_compiles() is True
    assert len(monitoring.get_event_duration_listeners()) == before


def test_nested_intervals_are_charged_once():
    seen = []
    own = telemetry._own_time
    # the caller's trace [0.5, 6] begins, then an inner jit's trace [1, 2]
    # and a second [3, 3.5]
    seen.append(None)
    seen.append(None)
    assert own(seen, 1.0, True) == pytest.approx(1.0)
    seen.append(None)
    assert own(seen, 0.5, True) == pytest.approx(0.5)
    assert seen == [None, 1.5]              # siblings: one entry
    assert own(seen, 5.5, True) == pytest.approx(5.5 - 1.5)
    assert seen == [5.5]
    # a later, disjoint interval is charged whole; all of it sums to the
    # wall time covered, never more
    seen.append(None)
    assert own(seen, 1.0, True) == pytest.approx(1.0)
    assert seen == [6.5]
    assert 1.0 + 0.5 + 4.0 + 1.0 == pytest.approx((6.0 - 0.5) + 1.0)
    # a retrieval, which JAX does not announce, in the compile call that
    # made it, whatever the series
    seen.clear()
    seen.append(None)
    assert own(seen, 0.3, False) == pytest.approx(0.3)
    assert own(seen, 1.0, True) == pytest.approx(0.7)
    assert seen == [1.0]


def test_5000_nested_siblings_charge_their_parent_s_wall_time_once():
    """What the deque of 1024 got wrong (PR 39): siblings past the cap had
    been evicted when their parent ended, were already charged, and were
    charged again with it -- 1.9 times the wall time on ``df26.block``."""
    seen, own, charged = [None], telemetry._own_time, 0.0   # the parent began
    for _ in range(5000):
        seen.append(None)                   # 1 ms of the parent's own Python,
        charged += own(seen, 0.004, True)   # then a child of 4 ms
        assert len(seen) <= 2               # memory: the nesting depth
    wall = 5000 * 0.005 + 0.001
    parent = own(seen, wall, True)
    assert parent == pytest.approx(5001 * 0.001)
    assert charged + parent == pytest.approx(wall)
    assert seen == [pytest.approx(wall)]


def _random_tree(rng, t, depth):
    """(intervals in the order they end, end time) of a random nest."""
    out, start = [], t
    t += rng.uniform(0.001, 0.01)
    for _ in range(rng.integers(0, 4) if depth < 4 else 0):
        inner, t = _random_tree(rng, t, depth + 1)
        out += inner
        t += rng.uniform(0.001, 0.01)
    return out + [(start, t, depth)], t


@pytest.mark.parametrize("seed", range(8))
def test_any_nest_is_charged_the_wall_time_it_covers(seed):
    """Fed the starts as JAX announces them, the charge is the wall time
    covered, to rounding, and the stack is as deep as the nest; leaves
    that are not announced (retrievals) change neither."""
    rng = np.random.default_rng(seed)
    order, t, wall = [], 0.0, 0.0
    for _ in range(6):                      # six top-level programs
        tree, end = _random_tree(rng, t, 0)
        wall += end - t
        order += tree
        t = end + rng.uniform(0.001, 0.01)
    leaf = [all(not (a < c and d < b) for c, d, _ in order)
            and rng.integers(2) == 0 for a, b, _ in order]
    # replay starts and ends in time order
    moments = sorted([(a, 0, i) for i, (a, _, _) in enumerate(order)]
                     + [(b, 1, i) for i, (_, b, _) in enumerate(order)],
                     key=lambda m: (m[0], m[1] == 0, m[2] if m[1] else -m[2]))
    seen, charged, own = [], 0.0, telemetry._own_time
    for when, ends, i in moments:
        if ends:
            charged += own(seen, when - order[i][0], not leaf[i])
        elif not leaf[i]:
            seen.append(None)
        assert len(seen) <= 2 * 5 + 1
    assert charged == pytest.approx(wall)
    assert seen == [pytest.approx(wall)]


def test_a_real_nest_is_announced_and_leaves_one_entry():
    """JAX 0.9 announces a trace where it begins (``record_scalar``), so
    the listeners read real nests exactly: 300 inner jits under one outer
    trace leave the thread's stack one entry deep and are charged, with
    the outer program, no more than the call took."""
    inner = [jax.jit(lambda v, k=k: jax.lax.add(v, float(k)))
             for k in range(300)]

    def outer(v):
        for f in inner:
            v = f(v)
        return v

    x = jnp.arange(4.0)
    state = telemetry._COMPILING
    del state.seen[:]
    telemetry.reset()
    mark = telemetry.compile_mark()
    t0 = time.perf_counter()
    jax.jit(outer)(x).block_until_ready()
    wall = time.perf_counter() - t0
    grown = [b - a for a, b in zip(mark, telemetry.compile_mark())]
    assert grown[1] == 301                          # traces
    assert _hist_counts()["jax_trace_seconds"] == 301
    assert len(state.seen) == 1 and 0.0 < state.seen[0] <= wall
    assert 0.0 < sum(grown[2:6]) <= wall
    hists = telemetry.snapshot("jax_")["histograms"]
    assert sum(h["sum"] for h in hists.values()) == pytest.approx(
        sum(grown[2:6]), abs=1e-5)
    telemetry.reset()


def test_jax_events_feed_the_registry():
    telemetry.reset()
    # this thread's real intervals of a moment ago would nest in the
    # quarter second reported below
    del telemetry._COMPILING.seen[:]
    mark = telemetry.compile_mark()
    telemetry._jax_event("/jax/compilation_cache/cache_hits")
    telemetry._jax_event("/jax/compilation_cache/cache_misses")
    telemetry._jax_event("/jax/compilation_cache/cache_misses")
    telemetry._jax_event("/jax/some/other/event")
    telemetry._jax_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                            0.25)
    telemetry._jax_duration("/jax/not/ours", 9.0)
    assert telemetry.counter_value("jax_cache_hits_total") == 1
    assert telemetry.counter_value("jax_cache_misses_total") == 2
    hists = telemetry.snapshot("jax_")["histograms"]
    assert list(hists) == ["jax_cache_retrieval_seconds"]
    assert hists["jax_cache_retrieval_seconds"]["sum"] == pytest.approx(0.25)
    # the thread's own totals moved with them: a new tuple, by identity
    now = telemetry.compile_mark()
    assert now is not mark
    grown = dict(zip(telemetry._MARK_FIELDS,
                     (b - a for a, b in zip(mark, now))))
    assert grown == {"events": 1, "traces": 0, "trace_s": 0.0,
                     "lower_s": 0.0, "compile_s": 0.0,
                     "cache_load_s": pytest.approx(0.25), "cache_hits": 1,
                     "cache_misses": 2, "kernels": 0, "kernel_trace_s": 0.0}
    assert telemetry.compile_mark() is now      # and stays, with no event
    telemetry.reset()


# ---------------------------------------------------------------------------
# the phase vector tiles, whatever the host does between two regions
# ---------------------------------------------------------------------------

def _delayed(fn, before=0.02, after=0.02):
    def wrapped(*args, **kwargs):
        time.sleep(before)
        try:
            return fn(*args, **kwargs)
        finally:
            time.sleep(after)
    return wrapped


@pytest.mark.parametrize("route", ["ring", "sync", "sequential",
                                   "value_free"])
def test_phases_tile_with_delays_between_regions(route):
    if route == "value_free":
        c = Circuit(3)
        c.hadamard(0)
        c.controlledNot(0, 1)
        c.pauliX(2)
    else:
        c = _ansatz()
    kwargs = {"ring": dict(max_batch=2, async_depth=2),
              "sync": dict(max_batch=2, async_depth=0),
              "sequential": dict(max_batch=1),
              "value_free": dict(max_batch=2)}[route]
    telemetry.reset()
    with Engine(c, ENV1, max_delay_ms=0.0, **kwargs) as eng:
        # sleeps where no region is open: around the whole dispatch (after
        # the admission region, before the assembly; after the launch),
        # around ring admission, inside the resolve loop
        eng._dispatch_one = _delayed(eng._dispatch_one)
        eng._ring_admit = _delayed(eng._ring_admit)
        eng._sentinel_gate = _delayed(eng._sentinel_gate, 0.01, 0.0)
        with telemetry.trace_policy("all"):
            futs = [eng.submit(_params(c, s) if route != "value_free"
                               else None) for s in range(5)]
            for f in futs:
                jax.block_until_ready(f.result(120))
    trs = telemetry.traces()
    assert len(trs) == 5
    for t in trs:
        assert t["error"] is None and t["dur_ms"] >= 20.0
        assert sorted(t["phases_ms"]) == sorted(telemetry.PHASES)
        total = sum(t["phases_ms"].values())
        assert abs(total / t["dur_ms"] - 1.0) <= 0.01, (
            route, total, t["dur_ms"], t["phases_ms"])
        # windows share their stamps: each phase span starts where the one
        # before ended, from the root's start to its end
        spans = sorted((sp["t0_ms"], sp["t0_ms"] + sp["dur_ms"])
                       for sp in t["spans"] if sp.get("cat") == "phase")
        assert spans[0][0] == pytest.approx(0.0, abs=1e-3)
        for (_, end), (start, _) in zip(spans, spans[1:]):
            assert start == pytest.approx(end, abs=1e-3)
        assert spans[-1][1] == pytest.approx(t["dur_ms"], abs=1e-3)
    telemetry.reset()


def test_an_errored_request_s_phases_tile_too():
    c = _ansatz()
    telemetry.reset()
    with Engine(c, ENV1, max_batch=2, max_delay_ms=0.0) as eng:
        gate = threading.Event()
        orig = eng._dispatch_one
        eng._dispatch_one = lambda b, m, **k: (gate.wait(30), orig(b, m, **k))[1]
        with telemetry.trace_policy("all"):
            first = eng.submit(_params(c, 0))
            time.sleep(0.05)
            late = eng.submit(_params(c, 1), timeout=0.01)
            time.sleep(0.05)
            gate.set()
            first.result(60)
            with pytest.raises(qt.QuESTTimeoutError):
                late.result(60)
    errored = [t for t in telemetry.traces() if t["error"]]
    assert len(errored) == 1
    t = errored[0]
    assert abs(sum(t["phases_ms"].values()) / t["dur_ms"] - 1.0) <= 0.01
    assert t["phases_ms"]["queue_wait"] == pytest.approx(t["dur_ms"],
                                                         rel=0.01)
    telemetry.reset()


def test_a_pool_request_s_phases_tile_across_the_pool_and_the_engine():
    """Pool and engine charge the trace's one mark: the hop between them
    is neither dropped nor counted twice."""
    c = _ansatz()
    telemetry.reset()
    pool = EnginePool(replicas=2, spawn_replacements=False, hedge_ms=0,
                      max_batch=2, max_delay_ms=0.0)
    try:
        with telemetry.trace_policy("all"):
            futs = [pool.submit(c, _params(c, s)) for s in range(4)]
            for f in futs:
                jax.block_until_ready(f.result(120))
    finally:
        pool.close(drain=False)
    trs = [t for t in telemetry.traces() if t["labels"].get("kind") == "pool"]
    assert len(trs) == 4
    for t in trs:
        assert t["error"] is None
        total = sum(t["phases_ms"].values())
        assert abs(total / t["dur_ms"] - 1.0) <= 0.01, (total, t["dur_ms"],
                                                        t["phases_ms"])
        assert t["phases_ms"]["device"] > 0.0      # the engine's share
    telemetry.reset()


# ---------------------------------------------------------------------------
# the device phase: the stream-ordered estimate
# ---------------------------------------------------------------------------

def test_device_phase_on_a_synthetic_three_entry_ring():
    """Three batches launched at 1.0, 1.1 and 1.2 s, seen done at 2.0, 3.0
    and 4.5 s: the chip ran them one after the other, so each one's device
    time starts where the one ahead was seen done, and what it spent
    launched behind it is queue_wait -- not the launch -> ready window,
    which would charge the second batch 1.9 s and the third 3.3 s."""
    c = _ansatz()
    with Engine(c, ENV1, max_batch=2, max_delay_ms=0.0) as eng:
        with telemetry.trace_policy("all"):
            telemetry.reset()
            batches = []
            for t_launch in (1.0, 1.1, 1.2):
                ctx = telemetry.start_trace("request", t0=0.5, kind="engine")
                req = engmod._Request((), Future(), 0.5, None, None, ctx)
                eng._charge((req,), "dispatch", t_launch)
                batches.append([req])
            eng._last_ready = 0.0
            for batch, t_ready in zip(batches, (2.0, 3.0, 4.5)):
                eng._charge_device(batch, t_ready)
                telemetry.finish_trace(batch[0].trace, now=t_ready)
    got = [(round(t["phases_ms"]["queue_wait"], 3),
            round(t["phases_ms"]["device"], 3), round(t["dur_ms"], 3))
           for t in telemetry.traces()]
    assert got == [(0.0, 1000.0, 1500.0),      # nothing ahead: launch -> ready
                   (900.0, 1000.0, 2500.0),    # 1.1 -> 2.0 behind the first
                   (1800.0, 1500.0, 4000.0)]   # 1.2 -> 3.0 behind the second
    for t in telemetry.traces():
        assert sum(t["phases_ms"].values()) == pytest.approx(t["dur_ms"])
    telemetry.reset()
