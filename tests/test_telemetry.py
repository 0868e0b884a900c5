"""Engine flight-recorder tests: quest_tpu/telemetry.py and its
instrumentation hooks.

Covers the registry/span primitives (CPU mesh), the cross-check that the
scheduler's comm chunk-unit counters agree EXACTLY with the plan_circuit
comm-volume model on a sharded 20q fused run, the QUEST_TELEMETRY=0
bit-identity guarantee, the df tile-mismatch engine fallback (counted, not
raised), and the bench headline-line contract (<= 1 KB, json.loads-able,
BENCH_DETAIL.json written).
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu import telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.parallel.scheduler import comm_chunks, plan_circuit

ENV = qt.createQuESTEnv()


# ---------------------------------------------------------------------------
# registry / span units
# ---------------------------------------------------------------------------

def test_counters_labels_and_totals():
    telemetry.reset()
    telemetry.inc("widgets_total")
    telemetry.inc("widgets_total", 2.0, kind="a")
    telemetry.inc("widgets_total", 3.0, kind="b", link="x")
    assert telemetry.counter_value("widgets_total") == 1.0
    assert telemetry.counter_value("widgets_total", kind="a") == 2.0
    assert telemetry.counter_value("widgets_total", kind="b", link="x") == 3.0
    assert telemetry.counter_total("widgets_total") == 6.0
    series = telemetry.counters("widgets_total")
    assert series[""] == 1.0 and series["{kind=a}"] == 2.0
    # label order in the call must not create distinct series
    telemetry.inc("widgets_total", 1.0, link="x", kind="b")
    assert telemetry.counter_value("widgets_total", kind="b", link="x") == 4.0


def test_gauges_and_histograms():
    telemetry.reset()
    telemetry.set_gauge("temp", 3.5, zone="a")
    telemetry.set_gauge("temp", 4.5, zone="a")  # gauges overwrite
    for v in (1.0, 5.0, 3.0):
        telemetry.observe("lat_seconds", v, op="x")
    snap = telemetry.snapshot()
    assert snap["gauges"]["temp{zone=a}"] == 4.5
    h = snap["histograms"]["lat_seconds{op=x}"]
    assert h == {"count": 3, "sum": 9.0, "min": 1.0, "max": 5.0}


def test_span_nesting_aggregation_and_events():
    telemetry.reset()
    with telemetry.span("outer", phase="p"):
        with telemetry.span("inner"):
            pass
        with telemetry.span("inner"):
            pass
    snap = telemetry.snapshot()
    assert snap["spans"]["outer{phase=p}"]["count"] == 1
    assert snap["spans"]["inner"]["count"] == 2
    assert snap["spans"]["inner"]["total_s"] >= 0
    paths = [e["path"] for e in telemetry.events() if e["kind"] == "span"]
    assert paths.count("outer/inner") == 2 and "outer" in paths


def test_reset_and_export_jsonl(tmp_path):
    telemetry.reset()
    telemetry.event("boot", detail=1)
    with telemetry.span("s"):
        pass
    path = tmp_path / "flight.jsonl"
    n = telemetry.export_jsonl(str(path))
    lines = path.read_text().strip().splitlines()
    assert n == len(lines) == 2
    assert all(isinstance(json.loads(l), dict) for l in lines)
    telemetry.reset()
    assert telemetry.events() == []
    assert telemetry.snapshot() == {"counters": {}, "gauges": {},
                                    "histograms": {}, "spans": {}}


def test_disabled_context_records_nothing():
    telemetry.reset()
    with telemetry.disabled():
        assert not telemetry.enabled()
        telemetry.inc("ghost_total")
        telemetry.set_gauge("ghost", 1.0)
        telemetry.observe("ghost_h", 1.0)
        with telemetry.span("ghost_span"):
            pass
        telemetry.event("ghost_ev")
    assert telemetry.enabled()
    assert telemetry.snapshot() == {"counters": {}, "gauges": {},
                                    "histograms": {}, "spans": {}}


def test_env_zero_swaps_in_noop_stubs():
    """QUEST_TELEMETRY=0 at process start rebinds the whole surface to
    no-op stubs (the zero-overhead guarantee)."""
    code = (
        "import quest_tpu.telemetry as t\n"
        "t.inc('x'); t.observe('h', 1.0); t.event('e')\n"
        "assert t.counter_total('x') == 0.0\n"
        "assert t.span('s') is t._NULL_SPAN\n"
        # PR 39: no listener on JAX's compile path, no first-call record,
        # no kernel record, no import gauge
        "import quest_tpu\n"
        "from jax._src import monitoring as m\n"
        "assert t._jax_duration not in m.get_event_duration_listeners()\n"
        "assert t.compile_mark() is None\n"
        "assert t.first_call(None, None, 'p', 'circuit') is False\n"
        "assert t.kernel_traced('k', None) == 0\n"
        "assert t.snapshot() == {'counters': {}, 'gauges': {},"
        " 'histograms': {}, 'spans': {}}\n"
        "print('STUBS-OK')\n")
    env = dict(os.environ, QUEST_TELEMETRY="0", JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-500:]
    assert "STUBS-OK" in out.stdout


# ---------------------------------------------------------------------------
# comm chunk-unit counters vs the plan_circuit model (sharded 20q)
# ---------------------------------------------------------------------------

def _sharded_circuit(n):
    """Layers with local gates, sharded-qubit targets (pair exchanges /
    relocations), virtual-swap candidates and a cross-shard phase."""
    rng = np.random.RandomState(11)
    circ = Circuit(n)
    for layer in range(2):
        for q in range(n):
            k = rng.randint(3)
            if k == 0:
                circ.hadamard(q)
            elif k == 1:
                circ.tGate(q)
            else:
                circ.rotateX(q, float(rng.uniform(0, 6)))
        for q in range(layer % 2, n - 1, 2):
            circ.controlledNot(q, q + 1)
        circ.controlledPhaseFlip(0, n - 1)
    circ.swapGate(1, n - 1)
    circ.hadamard(n - 1)
    return circ


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs the 8-dev mesh")
def test_comm_chunk_counters_match_plan_circuit_model():
    """Acceptance: a sharded fused run on the 8-virtual-device CPU mesh
    reports comm chunk-unit counters that match the plan_circuit
    comm-volume model exactly."""
    n = 20
    mesh = ENV.mesh
    fz = _sharded_circuit(n).fused(max_qubits=4)

    telemetry.reset()
    stats = plan_circuit(fz, mesh)
    model = comm_chunks(stats)
    assert model > 0
    planned = sum(telemetry.counters("comm_chunk_units_total").values())
    assert planned == pytest.approx(model, abs=1e-9)

    # now execute the same fused tape for real on the sharded register:
    # the trace-time counters of the actual run must agree with the model
    qureg = qt.createQureg(n, ENV)
    qt.initPlusState(qureg)
    telemetry.reset()
    with qt.explicit_mesh(mesh):
        fz.run(qureg)
    ran = telemetry.counters("comm_chunk_units_total")
    assert sum(ran.values()) == pytest.approx(model, abs=1e-9)
    # per-kind breakdown is labeled (dist_swap / pair_exchange /
    # grouped_permute / reconciliation), all attributed to a link
    assert all("kind=" in k and "link=" in k for k in ran)
    # the executed state is sane (the run really happened)
    assert abs(qt.calcTotalProb(qureg) - 1.0) < 1e-10


# ---------------------------------------------------------------------------
# QUEST_TELEMETRY off: bit-identical results and plans
# ---------------------------------------------------------------------------

def _fused_run(n):
    circ = Circuit(n)
    rng = np.random.RandomState(7)
    for q in range(n):
        circ.hadamard(q)
    for q in range(n - 1):
        circ.controlledNot(q, q + 1)
    for q in range(n):
        circ.rotateZ(q, float(rng.uniform(0, 6)))
    circ.controlledPhaseFlip(0, n - 1)
    fz = circ.fused(max_qubits=4, pallas=True)
    qureg = qt.createQureg(n, ENV)
    qt.initPlusState(qureg)
    fz.run(qureg)
    names = tuple(f.__name__ for f, _, _ in fz._tape)
    return np.asarray(qureg.amps), names


def test_disabled_telemetry_is_bit_identical():
    n = 10
    base_amps, base_plan = _fused_run(n)
    telemetry.reset()
    with telemetry.disabled():
        off_amps, off_plan = _fused_run(n)
    assert base_plan == off_plan          # same fused plan structure
    assert base_amps.dtype == off_amps.dtype
    assert np.array_equal(base_amps, off_amps)  # bit-identical amplitudes
    # the second plan is a new circuit, so its program traced anew: with
    # recording off there is no first-call record, no compile series and
    # no planner span of it (PR 39)
    assert telemetry.events() == []
    assert telemetry.snapshot() == {"counters": {}, "gauges": {},
                                    "histograms": {}, "spans": {}}


# ---------------------------------------------------------------------------
# engine fallback counters
# ---------------------------------------------------------------------------

def test_df_tile_mismatch_increments_fallback_not_raises(monkeypatch):
    """Acceptance: engine_fallback_total{reason=df_tile_mismatch} is
    incremented (and the ops replay through the engine) instead of
    fused_local_run raising ValueError, when a plan built with non-DF tile
    geometry replays on an f64 register taking the double-float path."""
    from quest_tpu import fusion, planner
    from quest_tpu.ops import pallas_gates as PG
    from quest_tpu.ops.pallas_df import DF_SUBLANES

    if np.dtype(qt.precision.real_dtype()) != np.dtype("float64"):
        pytest.skip("df path needs an f64 register (QUEST_PRECISION=2)")
    n = 18
    lq_df = PG.local_qubits(n, DF_SUBLANES)
    lq_f32 = PG.local_qubits(n)
    assert lq_df < lq_f32  # the mismatch window this test exercises
    target = lq_df  # dense target legal for the f32 plan, not for df
    # simulate the TPU dispatch decision (CPU _mosaic_supports is
    # unconditionally True): f64 has no Mosaic lowering
    monkeypatch.setattr(qt.precision, "_mosaic_supports",
                        lambda dtype: np.dtype(dtype) != np.dtype("float64"))
    env1 = qt.createQuESTEnv(jax.devices()[:1])
    qureg = qt.createQureg(n, env1)
    qt.initClassicalState(qureg, 0)
    X = np.array([[0, 1], [1, 0]], dtype=complex)
    ops = (("matrix", target, (), (), PG.HashableMatrix(X)),)
    telemetry.reset()
    fusion._apply_pallas_run(
        qureg, planner.PallasRun(ops, lq_f32))  # must not raise
    assert telemetry.counter_value("engine_fallback_total",
                                   reason="df_tile_mismatch") == 1
    amps = np.asarray(qureg.amps)
    assert amps[0, 1 << target] == pytest.approx(1.0)  # X really applied
    assert amps[0, 0] == pytest.approx(0.0)


def test_pallas_pass_and_compile_telemetry():
    """A fused Pallas run records its pass count (one per lowering) and a
    compile-seconds observation for its first kernel signature."""
    from quest_tpu.ops import pallas_gates as PG

    n = 9
    dt = qt.precision.real_dtype()
    amps = np.zeros((2, 1 << n), dtype=dt)
    amps[0, 0] = 1.0
    H = np.array([[1, 1], [1, -1]], dtype=complex) / np.sqrt(2)
    ops = (("matrix", 0, (), (), PG.HashableMatrix(H)),)
    telemetry.reset()
    out = PG.fused_local_run(jax.numpy.asarray(amps), n=n, ops=ops)
    assert out.shape == (2, 1 << n)
    assert telemetry.counter_total("pallas_pass_total") == 1
    snap = telemetry.snapshot("mosaic_compile_seconds")
    assert len(snap["histograms"]) == 1


# ---------------------------------------------------------------------------
# bench artifact chain
# ---------------------------------------------------------------------------

def test_bench_headline_is_compact_and_detail_complete(tmp_path,
                                                       monkeypatch, capsys):
    """The printed headline must be <= 1 KB and json.loads-able, with every
    per-config field (and a telemetry snapshot) in BENCH_DETAIL.json."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    monkeypatch.setattr(bench, "DETAIL_FILE",
                        str(tmp_path / "BENCH_DETAIL.json"))
    configs = [
        {"config": f"{n}q",
         "metric": f"gate-ops/sec, {n}-qubit state-vector random Clifford+T",
         "value": 1234.5, "unit": "gates/sec", "vs_baseline": 12.3,
         "detail": {"stream_floor_ms": 1.44, "per_pass_ms": 8.1,
                    "passes": 9, "per_pass_vs_floor": 5.67,
                    "eff_bandwidth_gbs": 746.0,
                    "blob": "x" * 4096}}  # detail may be arbitrarily large
        for n in (20, 24, 26)]
    telemetry.reset()
    telemetry.inc("engine_fallback_total", reason="df_tile_mismatch")
    bench._emit(configs[-1], configs, "headline")
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert len(line.encode()) <= 1024
    head = json.loads(line)
    assert head["metric"].startswith("gate-ops/sec, 26-qubit")
    assert head["detail_file"] == "BENCH_DETAIL.json"
    assert "roofline" in head and "floor 1.44ms/pass" in head["roofline"]
    detail = json.loads((tmp_path / "BENCH_DETAIL.json").read_text())
    assert detail["configs"] == configs  # every per-config field survives
    assert detail["telemetry"]["counters"][
        "engine_fallback_total{reason=df_tile_mismatch}"] == 1


@pytest.mark.slow
def test_bench_smoke_subprocess_headline(tmp_path):
    """End-to-end: `bench.py --smoke` prints a parseable final line and
    writes BENCH_DETAIL.json (the CI bench-smoke contract)."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(root, "bench.py"),
                          "--smoke"], capture_output=True, text=True,
                         env=env, timeout=600, cwd=root)
    assert out.returncode == 0, out.stderr[-800:]
    last = out.stdout.strip().splitlines()[-1]
    assert len(last.encode()) <= 1024
    head = json.loads(last)
    assert head["detail_file"] == "BENCH_DETAIL.json"
    detail = json.load(open(os.path.join(root, "BENCH_DETAIL.json")))
    assert "telemetry" in detail and detail["configs"]
