"""Set-up read from inside the program (PR 39).

- a kernel's compile record (``pallas.compile``) says which kernel -- the
  ``pallas_call``'s own name -- and all of its cost: the zone fold, the
  trace, the JAX traces nested in it; ``mosaic_compile_seconds`` holds fold
  and trace together;
- a first ``Circuit.run`` leaves exactly one ``program.first_call`` that
  names its program and tiles the region into trace, lowering, compile,
  cache load and the rest, with the kernels first traced in it, in program
  order; a second call leaves none;
- the engine's launch that retraces leaves the same record on its route;
- the dense planner runs under the ``fusion.plan{mode=dense}`` span its
  Pallas arms have, and the package's import is a gauge;
- the body of a jitted program is traced on a frame that gets an
  interpreter data-stack chunk of its own.
"""

import inspect
import sys

import jax
import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu import planner, telemetry
from quest_tpu.circuits import Circuit, named_program
from quest_tpu.engine import Engine, P
from quest_tpu.ops import pallas_gates as PG

ENV1 = qt.createQuESTEnv(jax.devices()[:1])
PHASES = ("trace_s", "lower_s", "compile_s", "cache_load_s", "rest_s")


def _two_run_plan(seed, n=9):
    """A plan of two fused runs (156 gates pass the 96-op cap once) whose
    angles no other test uses: their kernels' signatures are new."""
    circ = Circuit(n)
    rng = np.random.RandomState(seed)
    for _ in range(6):
        for q in range(n):
            circ.rotateX(q, float(rng.uniform(0, 6)))
        for q in range(n - 1):
            circ.controlledNot(q, q + 1)
        for q in range(n):
            circ.rotateZ(q, float(rng.uniform(0, 6)))
    fz = circ.fused(max_qubits=4, pallas=True)
    assert [f.__name__ for f, _, _ in fz._tape] == ["_apply_pallas_run"] * 2
    return fz


def _pallas_call_names(fn, *args):
    """The ``name=`` of every ``pallas_call`` in ``fn``'s jaxpr, in order."""
    def walk(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                out.append(eqn.params["name"])
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    walk(inner, out)
        return out

    return walk(jax.make_jaxpr(fn)(*args).jaxpr, [])


def _events(name):
    return [e for e in telemetry.events() if e.get("name") == name]


def test_first_run_is_one_record_that_names_its_program_and_tiles():
    fz = _two_run_plan(39001)
    qureg = qt.createQureg(fz.num_qubits, ENV1)
    qt.initPlusState(qureg)
    telemetry.reset()
    fz.run(qureg)
    [first] = _events("program.first_call")
    assert first["program"] == "qt_circuit_sv_n9_g2"
    assert first["route"] == "circuit"
    # the five phases add up to the region's own time, by construction
    assert all(first[p] >= 0.0 for p in PHASES[:4])
    assert sum(first[p] for p in PHASES) == pytest.approx(first["dur_s"],
                                                          abs=1e-6)
    assert first["trace_s"] > 0 and first["lower_s"] > 0 \
        and first["compile_s"] > 0
    region = telemetry.snapshot("circuit.run")["spans"]["circuit.run"]
    assert region["count"] == 1
    assert first["dur_s"] == pytest.approx(region["total_s"], abs=2e-6)
    # its kernels, in program order, under the names the device trace shows
    compiles = _events("pallas.compile")
    names = _pallas_call_names(fz.as_fn(), qureg.amps)
    assert len(names) == 2
    assert first["kernels"] == names == [e["kernel"] for e in compiles]
    assert 0.0 < first["kernel_trace_s"] <= first["trace_s"]
    assert first["nested_traces"] >= sum(e["nested_traces"]
                                         for e in compiles) > 0
    # a second call compiles nothing: no record
    fz.run(qureg)
    assert len(_events("program.first_call")) == 1
    assert telemetry.snapshot("circuit.run")["spans"]["circuit.run"][
        "count"] == 2
    telemetry.reset()


def test_compile_record_holds_the_fold_and_names_the_pallas_call():
    n = 9
    fz = _two_run_plan(39002, n)
    run = fz._tape[0][1][0]
    assert isinstance(run, planner.PallasRun)
    amps = jax.numpy.zeros((2, 1 << n), qt.precision.real_dtype())
    telemetry.reset()
    PG.fused_local_run(amps + 0, n=n, ops=run.ops, sublanes=1 << (
        run.tile_bits - PG.LANE_BITS))
    [ev] = _events("pallas.compile")
    folded = PG._fold_zone_ops(run.ops, run.tile_bits)
    assert ev["ops"] == len(folded) < len(run.ops)
    assert ev["kernel"] == PG.kernel_name("grid", 2, amps.dtype,
                                          len(folded))
    assert ev["fold_s"] > 0 and ev["trace_s"] == ev["seconds"] > 0
    assert ev["nested_traces"] > 0 and ev["interpret"] is True
    # the histogram takes the fold too; the event rounds to 0.1 ms
    [hist] = telemetry.snapshot("mosaic_compile_seconds")[
        "histograms"].values()
    assert hist["sum"] == pytest.approx(ev["fold_s"] + ev["trace_s"],
                                        abs=2e-4)
    # the signature is known now: no second record
    PG.fused_local_run(amps, n=n, ops=run.ops, sublanes=1 << (
        run.tile_bits - PG.LANE_BITS))
    assert len(_events("pallas.compile")) == 1
    telemetry.reset()


def test_engine_s_retraced_launch_is_a_first_call_on_its_route():
    c = Circuit(3)
    for q in range(3):
        c.rotateY(q, P(f"a{q}"))
    c.controlledNot(0, 1)
    c.rotateZ(2, 0.39004)         # a constant no other test's plan holds
    telemetry.reset()
    with Engine(c, ENV1, max_batch=2, max_delay_ms=0.0) as eng:
        params = [{f"a{q}": 0.1 * (s + q) for q in range(3)}
                  for s in range(6)]
        with telemetry.trace_policy("all"):
            for f in [eng.submit(p) for p in params]:
                jax.block_until_ready(f.result(120))
        name = eng._execB().__name__
    [first] = _events("program.first_call")
    assert first["route"] == "engine_vmap"
    assert first["program"] == name
    assert name.startswith("qt_engine_vmap_sv_n3_g") and name.endswith("_b2")
    assert first["kernels"] == [] and first["kernel_trace_s"] == 0.0
    assert sum(first[p] for p in PHASES) == pytest.approx(first["dur_s"],
                                                          abs=1e-6)
    # the launch that retraced is the one whose requests were charged
    # ``compile``; every later launch ``dispatch``
    compiled = [t for t in telemetry.traces()
                if t["phases_ms"]["compile"] > 0.0]
    assert 1 <= len(compiled) <= 2 < len(telemetry.traces())
    for t in compiled:
        assert t["phases_ms"]["compile"] == pytest.approx(
            first["dur_s"] * 1e3, rel=0.05)
    telemetry.reset()


def test_a_warm_window_reads_one_mark_at_each_end_and_records_nothing():
    mark = telemetry.compile_mark()
    with telemetry.region("probe.warm") as rg:
        pass
    assert telemetry.compile_mark() is mark
    telemetry.reset()
    assert telemetry.first_call(mark, rg, "never", "circuit") is False
    assert telemetry.events() == []
    assert telemetry.snapshot()["histograms"] == {}


@pytest.mark.parametrize("mode", ["dense", "pallas"])
def test_every_planner_arm_runs_under_the_fusion_plan_span(mode):
    circ = Circuit(9)
    for q in range(9):
        circ.hadamard(q)
        circ.rotateZ(q, 0.1 + q)
    telemetry.reset()
    circ.fused(max_qubits=4, pallas=mode == "pallas")
    spans = telemetry.snapshot("fusion.plan")["spans"]
    assert list(spans) == [f"fusion.plan{{mode={mode}}}"]
    assert spans[f"fusion.plan{{mode={mode}}}"]["count"] == 1
    assert telemetry.snapshot("fusion.plan_seconds")["histograms"] == {}
    [ev] = _events("fusion.plan")[-1:]
    assert ev["mode"] == mode
    telemetry.reset()


def test_the_package_times_its_own_import():
    import subprocess
    import sys

    code = ("{first}\n"
            "import quest_tpu\n"
            "g = quest_tpu.telemetry.snapshot('quest_tpu_import')['gauges']\n"
            "[(k, v)] = g.items()\n"
            "assert v > 0.0\n"
            "print(k)\n")
    for first, label in (("import jax", 0), ("", 1)):
        out = subprocess.run([sys.executable, "-c", code.format(first=first)],
                             capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-500:]
        assert out.stdout.strip() == \
            f"quest_tpu_import_seconds{{jax_included={label}}}"


def test_a_program_s_body_is_traced_on_a_frame_with_a_chunk_of_its_own():
    """CPython's data stack is made of 16 KiB chunks, and a call site at a
    chunk's end maps and unmaps one every call: most of a warm first call
    on the chip's host (PR 39). ``named_program`` puts the body behind a
    frame wide enough to be given one large chunk, and keeps the name, the
    signature and the result."""
    c = Circuit(3)
    c.hadamard(0)
    seen = []

    def body(amps, values=None):
        frame = sys._getframe(1)
        seen.append((frame.f_code.co_stacksize, frame.f_code.co_name))
        return amps * 2.0

    wide = named_program(body, c, "circuit")
    assert wide.__name__ == wide.__qualname__ == "qt_circuit_sv_n3_g1"
    assert str(inspect.signature(wide)) == "(amps, values=None)"
    # 2^20 slots and a little: over 8 MiB, so the chunk is 16 MiB and
    # half of it is free for every frame below
    assert wide.__code__.co_stacksize > 1 << 20
    x = np.arange(3.0, dtype=np.float32)
    jitted = jax.jit(wide)
    assert np.array_equal(np.asarray(jitted(x)), 2.0 * x)
    assert np.array_equal(np.asarray(jitted(x + 1)), 2.0 * (x + 1))
    # the body ran once, when JAX traced it, right under the wide frame;
    # the warm calls never came to Python
    assert seen == [(wide.__code__.co_stacksize, "body")]
    assert "jit_qt_circuit_sv_n3_g1" in jitted.lower(x).as_text()[:200]
