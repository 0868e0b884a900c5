"""What the reference's default build (PRECISION=2: on the chip the
double-float kernels of ``ops/pallas_df.py``) asks of the planner (benchmark
cell ``df26.block``): a one-device plan for the df route is cut where a df
kernel ends, ``DF_MAX_OPS`` ops, so that every df kernel is one ``PallasRun``
-- one pass the plan states, one in-place launch, its frame on its own DMA --
and the executor's chunk loop, with its ``df_max_ops_split`` count, is left
for plans replayed where they were not built and for sharded df plans. The
cut is keyed on the route: float32 plans are what they were, item for item.
Plans only at the cell's size; execution at 14 qubits."""

import hashlib
import importlib.util
import os

import numpy as np
import pytest

import jax

import quest_tpu as qt
from quest_tpu import fusion, telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.ops import pallas_gates as PG
from quest_tpu.ops.pallas_df import _DF_ENV, DF_MAX_OPS, DF_SUBLANES
from quest_tpu.precision import real_dtype

from .helpers import pallas_runs, shape_register

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _bench_module(*parts):
    spec = importlib.util.spec_from_file_location(
        parts[-1], os.path.join(BENCH, *parts) + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def layers():
    return _bench_module("circuits", "random_layers")


@pytest.fixture
def df_route(monkeypatch):
    """The chip's routing of an f64 register, here: ``df_wanted`` is True on
    the TPU backend on its own."""
    if np.dtype(real_dtype()) != np.dtype("float64"):
        pytest.skip("needs QUEST_PRECISION=2 (the conftest default)")
    monkeypatch.setenv(_DF_ENV, "1")


def _circuit(layers, n, depth=2):
    circ = Circuit(n)
    layers.build(circ, num_qubits=n, depth=depth, circuit_seed=2026)
    return circ


def _flat(x):
    """A plan item, op or matrix as nested tuples of plain values."""
    if isinstance(x, PG.HashableMatrix):
        return ("matrix", x.arr.shape, x.arr.tobytes().hex())
    if isinstance(x, np.ndarray):
        return ("array", x.shape, str(x.dtype), x.tobytes().hex())
    if isinstance(x, fusion.PallasRun):
        return ("run", x.tile_bits, x.load_swap_k, x.load_swap_hi,
                x.store_swap_k, x.store_swap_hi, _flat(x.ops))
    if isinstance(x, fusion.FrameSwap):
        return ("swap", x.tile_bits, x.k, x.hi)
    if isinstance(x, (tuple, list)):
        return tuple(_flat(v) for v in x)
    if isinstance(x, (np.floating, np.integer, np.complexfloating)):
        return x.item()
    return x


def _digest(plan) -> str:
    return hashlib.sha256(repr(_flat(plan.items)).encode()).hexdigest()[:16]


# -- the planner --------------------------------------------------------------

@pytest.mark.parametrize("n,pieces", [
    (14, [8, 8, 8, 8, 8, 3]),
    (20, [8, 8, 8, 8, 8, 8, 3, 8, 1, 1]),
    (26, [8, 8, 8, 8, 8, 8, 5, 8, 8, 1, 7, 2])])    # df26.block
def test_a_df_plan_is_cut_where_a_df_kernel_ends(layers, df_route,
                                                 monkeypatch, n, pieces):
    """``random_layers`` at the df tile: no run over ``DF_MAX_OPS`` ops,
    every run matched (so in place), every route ``df_local`` with both
    relabelings folded and nothing counted, and the ops of the uncut plan in
    its order, each piece under its run's frame."""
    circ = _circuit(layers, n)
    cut = pallas_runs(circ.fused(max_qubits=5, pallas=True))
    monkeypatch.setattr(fusion, "_run_op_cap",
                        lambda dtype, sharded: fusion._RUN_OP_CAP)
    whole = pallas_runs(circ.fused(max_qubits=5, pallas=True))
    assert [len(r.ops) for r in cut] == pieces
    assert sum(-(-len(r.ops) // DF_MAX_OPS) for r in whole) == len(cut)
    register = shape_register(n, np.float64)
    for run in cut:
        assert len(run.ops) <= DF_MAX_OPS and run.matched, run
        assert run.tile_bits == PG.local_qubits(n, DF_SUBLANES)
        route = fusion._route(register, run)
        assert route.kind == "df_local" and route.df, route
        assert route.reason is None and route.unfolded == 0
        assert route.fold_load == route.fold_store == bool(run.load_swap_k)
    framed = [(op, (r.load_swap_k, r.load_swap_hi, r.store_swap_k,
                    r.store_swap_hi)) for r in cut for op in r.ops]
    assert framed == [(op, (r.load_swap_k, r.load_swap_hi, r.store_swap_k,
                            r.store_swap_hi)) for r in whole for op in r.ops]


def test_the_26q_df_plan_is_the_cell_s(layers, df_route):
    """``df26.block``: runs of 53, 17 (k=7 @17), 7 (k=2 @24) and 2 ops at the
    df tile (17 bits), both frames within what folds there, cut into 12."""
    runs = pallas_runs(_circuit(layers, 26).fused(max_qubits=5, pallas=True))
    frames = [(r.load_swap_k, r.load_swap_hi) for r in runs]
    assert frames == [(0, None)] * 7 + [(7, 17)] * 3 + [(2, 24), (0, None)]
    assert fusion._fold_width(17) == 7
    assert sum(len(r.ops) for r in runs) == 79


def test_the_cap_is_keyed_on_the_route(df_route, monkeypatch):
    """One function says a plan's cap: ``DF_MAX_OPS`` for a one-device plan
    on the df route, ``_RUN_OP_CAP`` for every float32 plan, for an f64 plan
    off the df route, and for a SHARDED df plan (a piece that carried a
    collective frame in and out would pay it twice)."""
    cap = fusion._run_op_cap
    assert cap(np.float64, False) == DF_MAX_OPS
    assert cap(np.float64, True) == fusion._RUN_OP_CAP
    assert cap(np.float32, False) == cap(np.float32, True) \
        == fusion._RUN_OP_CAP
    monkeypatch.delenv(_DF_ENV)
    assert cap(np.float64, False) == fusion._RUN_OP_CAP


def test_a_sharded_df_plan_keeps_its_runs_whole(layers, df_route):
    """``plan_pallas_sharded`` (the ``sched_df`` route's plans) is what it
    was: runs longer than a df kernel, cut where they execute."""
    circ = _circuit(layers, 20)
    runs = pallas_runs(circ.fused(max_qubits=5, pallas=True,
                                  shard_devices=4))
    assert max(len(r.ops) for r in runs) > DF_MAX_OPS
    telemetry.reset()
    circ.fused(max_qubits=5, pallas=True, shard_devices=4)
    event = [e for e in telemetry.events() if e.get("name") == "fusion.plan"
             and e.get("mode") == "pallas_sharded"][-1]
    assert event["df"] and event["run_op_cap"] == fusion._RUN_OP_CAP
    assert event["df_passes"] == sum(-(-len(r.ops) // DF_MAX_OPS)
                                     for r in runs) > event["pallas_runs"]


#: sha256 (16 hex digits) of every item of the float32 plan -- tile, frames,
#: every op with its matrix bytes -- as the parent of PR 37 (e549e58) plans it
_F32_PLANS = {(20, 8): "7f1dfe2c887d5aa8", (26, 2): "9c8ff9f0fc73e72d",
              (30, 2): "1c0fb71ed899ffed"}


@pytest.mark.parametrize("df_env", ["", "1"])
@pytest.mark.parametrize("n,depth", sorted(_F32_PLANS))
def test_float32_plans_are_the_parent_s_item_for_item(layers, monkeypatch,
                                                      n, depth, df_env):
    """``sv20`` / ``sv26`` / ``sv30`` shapes: the same items as before the df
    cut, whether or not the df route is switched on for f64 registers."""
    monkeypatch.setenv(_DF_ENV, df_env)
    fused = _circuit(layers, n, depth).fused(max_qubits=5, pallas=True,
                                             dtype=np.float32)
    assert _digest(fusion.plan_from_tape(fused._tape)) == _F32_PLANS[n, depth]


# -- the route, executed ------------------------------------------------------

def test_a_df_circuit_runs_as_its_plan_states(layers, df_route):
    """14 qubits through ``createQureg`` -> ``fused`` -> ``run``: no
    ``engine_fallback_total`` of any reason, as many df kernels, in-place
    runs and f64 <-> planes conversions as the plan event states, and the
    benchmark's numpy complex128 replay of the tape on a seeded float64
    state. The tolerance is ``test_pallas.py::
    test_df_kernel_matches_native_f64_interpreter``'s, for its reason:
    XLA:CPU duplicates and contracts the error-free transforms, so the
    interpreted df chain keeps about float32 accuracy here; the chip's check
    (``df26.block``) holds the real limit."""
    n = 14
    reference = _bench_module("reference")
    circ = _circuit(layers, n)
    telemetry.reset()
    fused = circ.fused(max_qubits=5, pallas=True)
    event = [e for e in telemetry.events() if e.get("name") == "fusion.plan"
             and e.get("mode") == "pallas"][-1]
    assert event["df"] and event["run_op_cap"] == DF_MAX_OPS
    assert event["df_passes"] == event["pallas_runs"] \
        == event["inplace_runs"] == 6
    q = qt.createQureg(n, qt.createQuESTEnv(jax.devices()[:1]))
    assert q.amps.dtype == np.float64
    rng = np.random.default_rng(2 ** 31 + 37)
    g = rng.standard_normal((2, 1 << n))
    g /= np.sqrt(np.sum(g * g))
    q.put(jax.numpy.asarray(g))
    fused.run(q)
    counters = telemetry.snapshot()["counters"]
    assert not any(k.startswith("engine_fallback_total") and v
                   for k, v in counters.items()), counters
    assert counters["pallas_pass_total{dtype=df,kind=fused_run}"] \
        == counters["fusion_inplace_runs_total"] \
        == counters["fusion_df_passes_total{mode=pallas}"] \
        == event["df_passes"]
    assert counters["fusion_df_conversions_total{dir=split}"] \
        == counters["fusion_df_conversions_total{dir=join}"] \
        == event["pallas_runs"]
    tape = reference.Tape()
    layers.build(tape, num_qubits=n, depth=2, circuit_seed=2026)
    want = reference.run_statevector(g[0] + 1j * g[1], tape.ops)
    got = np.asarray(q.amps)
    assert got.dtype == np.float64
    np.testing.assert_allclose(got[0] + 1j * got[1], want, atol=5e-8)
