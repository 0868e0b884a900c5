"""What the reference's default build (PRECISION=2: on the chip the
double-float kernels of ``ops/pallas_df.py``) asks of the planner (benchmark
cell ``df26.block``): a one-device plan for the df route is cut where a df
kernel ends, ``DF_MAX_OPS`` ops, so that every df kernel is one ``PallasRun``
-- one pass the plan states, one in-place launch, its frame on its own DMA --
and the executor's chunk loop, with its ``df_max_ops_split`` count, is left
for plans replayed where they were not built and for sharded df plans. The
cut is keyed on the route: float32 plans are what they were, item for item.
Inside one tape program such runs carry their planes from one to the next
(PR 38: one split, one join, and the f64 array wherever something reads it).
Plans only at the cell's size; execution at 14 qubits."""

import contextlib
import hashlib
import importlib.util
import os
from typing import NamedTuple

import numpy as np
import pytest

import jax

import quest_tpu as qt
from quest_tpu import fusion, planner, telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.ops import pallas_gates as PG
from quest_tpu.ops.pallas_df import _DF_ENV, DF_MAX_OPS, DF_SUBLANES
from quest_tpu.precision import real_dtype
from quest_tpu.resilience.faultinject import fault_plan

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
    if isinstance(x, planner.PallasRun):
        return ("run", x.tile_bits, x.load_swap_k, x.load_swap_hi,
                x.store_swap_k, x.store_swap_hi, _flat(x.ops))
    if isinstance(x, planner.FrameSwap):
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
    monkeypatch.setattr(planner, "_run_op_cap",
                        lambda dtype, sharded: planner._RUN_OP_CAP)
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
    assert planner._fold_width(17) == 7
    assert sum(len(r.ops) for r in runs) == 79


def test_the_cap_is_keyed_on_the_route(df_route, monkeypatch):
    """One function says a plan's cap: ``DF_MAX_OPS`` for a one-device plan
    on the df route, ``_RUN_OP_CAP`` for every float32 plan, for an f64 plan
    off the df route, and for a SHARDED df plan (a piece that carried a
    collective frame in and out would pay it twice)."""
    cap = planner._run_op_cap
    assert cap(np.float64, False) == DF_MAX_OPS
    assert cap(np.float64, True) == planner._RUN_OP_CAP
    assert cap(np.float32, False) == cap(np.float32, True) \
        == planner._RUN_OP_CAP
    monkeypatch.delenv(_DF_ENV)
    assert cap(np.float64, False) == planner._RUN_OP_CAP


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
    assert event["df"] and event["run_op_cap"] == planner._RUN_OP_CAP
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
    ``engine_fallback_total`` of any reason, as many df kernels and
    in-place runs as the plan event states, ONE split and ONE join for the
    whole chain (every run but the first takes the planes of the run before
    it, as the event's ``df_carried`` states), and the
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
        == counters["fusion_df_conversions_total{dir=join}"] == 1
    assert counters["fusion_df_carried_total"] \
        == event["pallas_runs"] - 1 == event["df_carried"]
    tape = reference.Tape()
    layers.build(tape, num_qubits=n, depth=2, circuit_seed=2026)
    want = reference.run_statevector(g[0] + 1j * g[1], tape.ops)
    got = np.asarray(q.amps)
    assert got.dtype == np.float64
    np.testing.assert_allclose(got[0] + 1j * got[1], want, atol=5e-8)


# -- where a chain of df runs ends --------------------------------------------

def _x_on(psi, n, q):
    """Pauli X on qubit ``q`` of a 2^n vector."""
    return np.flip(psi.reshape((2,) * n), axis=n - 1 - q).reshape(-1)


def _swap_blocks(psi, n, lo1, lo2, k):
    """Qubits [lo1, lo1 + k) exchanged with [lo2, lo2 + k)."""
    v = psi.reshape((2,) * n)
    for j in range(k):
        v = np.swapaxes(v, n - 1 - (lo1 + j), n - 1 - (lo2 + j))
    return v.reshape(-1)


#: X on physical bit 9 under a ``k=2`` frame at tile 10, which the df tile of
#: a 14-qubit register (14 bits) does not fold: X on qubit 11, between two
#: explicit relabelings
_X_IN_FRAME = planner.PallasRun(
    (("matrix", 9, (), (), PG.HashableMatrix(
        np.array([[0, 1], [1, 0]], dtype=complex))),),
    10, load_swap_k=2, store_swap_k=2)

#: sha256 (16 hex digits) of the two 14-qubit depth-1 float32 plans below,
#: as the parent of PR 38 (3d5e59a) plans them
_F32_HALVES = ("0b916d40accdbc65", "6559d2936c3333e4")


class _Break(NamedTuple):
    """What stands between the two halves: as tape entries and in numpy;
    the fault plan (its visit filled in); whether the replay runs eagerly;
    df kernels besides the halves' own; and the chains of carried planes
    the program then holds (a split and a join each)."""
    mid: tuple = ()
    numpy: object = lambda psi, n: psi
    faults: str | None = None
    eager: bool = False
    extra: int = 0
    chains: int = 1


_BREAKS = {
    "unbroken": _Break(),
    "frame_swap": _Break(
        ((fusion._apply_frame_swap, (planner.FrameSwap(10, 2),)),),
        lambda psi, n: _swap_blocks(psi, n, 8, 10, 2), chains=2),
    "explicit_swap": _Break(
        ((fusion._apply_pallas_run, (_X_IN_FRAME,)),),
        lambda psi, n: _x_on(psi, n, 11), extra=1, chains=3),
    "gatewise": _Break(faults="pallas.dispatch:compile:{}", extra=-1,
                       chains=2),
    "tape_entry": _Break(((qt.pauliX, (11,)),),
                         lambda psi, n: _x_on(psi, n, 11), chains=2),
    "eager": _Break(eager=True),
    "float32": _Break(),
}


@pytest.mark.parametrize("case", sorted(_BREAKS))
def test_a_df_chain_joins_where_the_register_is_read(layers, df_route, case):
    """Two separately planned halves of 14-qubit random layers on one tape,
    and between them what ends a chain of carried planes: a ``FrameSwap``
    entry, a run whose relabeling does not fold (its explicit passes read
    and write the f64 array), a run the guard degrades to ``_gatewise``
    (the first of the second half), a tape entry that is no fused run. Each
    reads the JOINED register and the run after it splits again: the
    counters say so, and the result is the numpy complex128 replay's.
    ``eager``: ``as_fn()`` outside ``jit`` carries too, so it computes what
    the jitted program computes; every join has then executed and counts,
    and the carry saves the splits. ``float32``: no df series at all, the
    parent's plans."""
    n = 14
    mid, mid_numpy, faults, eager, extra, chains = _BREAKS[case]
    f32 = case == "float32"
    dtype = np.float32 if f32 else np.float64
    reference = _bench_module("reference")
    halves, tapes = [], []
    for seed in (2026, 2027):
        circ, tape = Circuit(n), reference.Tape()
        for rec in (circ, tape):
            layers.build(rec, num_qubits=n, depth=1, circuit_seed=seed)
        halves.append(circ)
        tapes.append(tape)
    telemetry.reset()
    fused = [c.fused(max_qubits=5, pallas=True, dtype=dtype) for c in halves]
    events = [e for e in telemetry.events()
              if e.get("name") == "fusion.plan" and "pallas_runs" in e]
    runs = [len(pallas_runs(f)) for f in fused]
    assert [e["df_carried"] for e in events] \
        == [0 if f32 else r - 1 for r in runs]
    if f32:
        assert tuple(_digest(fusion.plan_from_tape(f._tape))
                     for f in fused) == _F32_HALVES
    whole = Circuit(n)
    for fn, args in (*((f, a) for f, a, _ in fused[0]._tape), *mid,
                     *((f, a) for f, a, _ in fused[1]._tape)):
        whole.append(fn, *args)
    rng = np.random.default_rng(2 ** 31 + 38)
    g = rng.standard_normal((2, 1 << n))
    g /= np.sqrt(np.sum(g * g))
    q = qt.createQureg(n, qt.createQuESTEnv(jax.devices()[:1]),
                       1 if f32 else 2)
    q.put(jax.numpy.asarray(g, dtype=dtype))
    telemetry.reset()
    with contextlib.ExitStack() as stack:
        if faults:
            stack.enter_context(fault_plan(faults.format(runs[0] + 1)))
        if eager:
            q.put(whole.as_fn()(q.amps))
        else:
            whole.run(q)
    counters = telemetry.snapshot()["counters"]
    assert q.amps.dtype == dtype and q.amps.shape == (2, 1 << n)
    kernels = sum(runs) + extra
    if f32:
        assert not any(k.startswith("fusion_df_") for k in counters), counters
        assert counters["pallas_pass_total{dtype=float32,kind=fused_run}"] \
            == kernels
    else:
        assert counters["pallas_pass_total{dtype=df,kind=fused_run}"] \
            == kernels
        assert counters["fusion_df_conversions_total{dir=split}"] == chains
        assert counters["fusion_df_conversions_total{dir=join}"] \
            == (kernels if eager else chains)
        assert counters["fusion_df_carried_total"] == kernels - chains
    fallbacks = {k: v for k, v in counters.items()
                 if k.startswith("engine_fallback_total") and v}
    assert fallbacks == {
        "explicit_swap": {"engine_fallback_total{reason=swap_not_foldable}":
                          1},
        "gatewise": {"engine_fallback_total{reason=fault_degraded}": 1},
    }.get(case, {})
    want = reference.run_statevector(g[0] + 1j * g[1], tapes[0].ops)
    want = reference.run_statevector(mid_numpy(want, n), tapes[1].ops)
    got = np.asarray(q.amps)
    np.testing.assert_allclose(got[0] + 1j * got[1], want,
                               atol=2e-6 if f32 else 5e-8)
