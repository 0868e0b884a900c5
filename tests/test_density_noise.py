"""What per-gate noise on a density register asks of the planner and the
kernels (benchmark cell ``density15.noise``, PR 41: 46 gates and a
depolarising channel after each on 15 qubits, 2^30 elements, 8 GiB):

- every channel of a density tape finds a frame: the pair whose columns
  straddle the tile's edge takes a run at a NARROWED tile
  (``PallasRun.own_tile``), where it was a barrier in the plan;
- the depolarising family rides the fused-run kernel in closed form (the
  'depol' op), the same channel as the Kraus sum, in float32, native float64
  and double-float;
- the plans of the cells the benchmark already had stay what they were, item
  for item;
- a pending run's frame grows to hold the next column op (PR 42): the cell's
  tape plans to six passes where it planned to twelve, every op still in a
  frame that holds it and in its tape order, never more passes than with
  frames fixed at birth.

Plans only at the real sizes; execution at rehearsal sizes whose tile is cut
(``PG._DEF_SUBLANES``) so that a pair straddles it. (What the chip's compiler
says of the real size is in ``tests/test_chip_compile.py``.)"""

import importlib.util
import itertools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import quest_tpu as qt
from quest_tpu import channels, fusion, telemetry
from quest_tpu import planner as planner_mod
from quest_tpu.circuits import Circuit
from quest_tpu.ops import density as DN
from quest_tpu.ops import pallas_df as DF
from quest_tpu.ops import pallas_gates as PG

from . import oracle
from .helpers import get_density, pallas_runs, set_density, shape_register
from .plan_digest import plan_digest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
P1, P2 = 1e-2, 5e-2          # larger than the cell's: errors show


def _builder(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "circuits", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _noisy(n, depth=2, rec=None):
    rec = Circuit(n, is_density_matrix=True) if rec is None else rec
    _builder("noisy_layers").build(rec, num_qubits=n, depth=depth,
                                   circuit_seed=2026, p1=P1, p2=P2)
    return rec


def _plan_at(circ, tile_bits, dtype=np.float32, **kw):
    """(plan, the fused circuit) of a density tape at a forced tile."""
    n = circ.num_qubits
    plan = planner_mod.plan(tuple(circ._tape), n, np.dtype(dtype), max_qubits=5,
                       pallas_tile_bits=tile_bits, is_density=True, **kw)
    fz = Circuit(n, is_density_matrix=True)
    fz._tape = fusion.as_tape(plan)
    return plan, fz


def _only_runs(plan):
    assert all(isinstance(i, planner_mod.PallasRun) for i in plan.items), [
        i for i in plan.items if not isinstance(i, planner_mod.PallasRun)]
    assert plan.num_barriers == 0
    return plan.items


# -- (a) the planner ---------------------------------------------------------

@pytest.mark.parametrize("n", range(10, 18))
def test_the_noisy_tape_plans_as_fused_runs_alone(n):
    """10 to 17 qubits on one device at the register's own tile (2^19): no
    raw tape entry, no dense block; every run matched, no frame wider than
    what folds, and the route folds both relabelings of each and counts no
    fallback. Exactly one run narrows its tile: the one that holds the pair
    whose columns are bits 18 and 19 (before: a barrier, and the channel a
    Kraus sum over three states)."""
    fz = _noisy(n).fused(max_qubits=5, pallas=True, dtype=np.float32)
    runs = _only_runs(fusion.plan_from_tape(fz._tape))
    register = shape_register(2 * n, np.float32)
    for run in runs:
        assert run.matched, run
        assert run.load_swap_k <= planner_mod._fold_width(run.tile_bits)
        route = fusion._route(register, run)
        assert (route.kind, route.reason, route.unfolded) == ("local", None, 0)
        assert route.fold_load == route.fold_store == bool(run.load_swap_k)
    narrowed = [r for r in runs if r.own_tile]
    assert [(r.tile_bits, r.load_swap_k, r.load_swap_hi)
            for r in narrowed] == [(18, 2, 18)]
    straddling = [op for op in narrowed[0].ops if op[0] == "depol"
                  and len(op[1]) == 2]
    assert ("depol", (18 - n, 19 - n), (16, 17), P2 * 16 / 15) in straddling


@pytest.mark.parametrize("n,tile_bits", [(6, 10), (6, 11), (7, 11), (7, 12),
                                         (7, 13)])
def test_a_pair_that_straddles_a_forced_tile_finds_a_frame(n, tile_bits):
    """6 and 7 qubits at tiles cut so small that a neighbouring pair's
    columns straddle the edge (at 5 qubits no tile under the register's 10
    bits has the two sublane bits a pair's columns need): fused runs alone,
    every one matched, one of them at a narrower tile."""
    plan, _ = _plan_at(_noisy(n), tile_bits)
    runs = _only_runs(plan)
    assert all(r.matched for r in runs)
    assert any(r.own_tile and r.tile_bits < tile_bits for r in runs)
    for run in runs:
        assert all(q < run.tile_bits for op in run.ops
                   for q in PG.op_dense_targets(op))


def test_a_kraus_pair_that_straddles_the_tile_finds_a_frame_too():
    """Not the closed form alone: a two-qubit Kraus map on the straddling
    pair (a ``kraus2`` on bits 3, 4, 18, 19 at 15 qubits) and a three-qubit
    one across the edge (``krausn``) plan as fused runs at narrowed tiles."""
    circ = Circuit(15, is_density_matrix=True)
    cx = np.eye(4)[[0, 1, 3, 2]]
    circ.mixTwoQubitKrausMap(3, 4, [0.8 * np.eye(4), 0.6 * cx])
    x3 = np.eye(8)[::-1]
    circ.mixMultiQubitKrausMap([2, 3, 4], [0.8 * x3, 0.6j * np.eye(8)])
    fz = circ.fused(max_qubits=5, pallas=True, dtype=np.float32)
    runs = _only_runs(fusion.plan_from_tape(fz._tape))
    assert [(r.ops[0][0], r.tile_bits, r.load_swap_k, r.load_swap_hi)
            for r in runs] == [("kraus2", 18, 2, 18), ("krausn", 17, 3, 17)]


def _plan_events():
    """The ``fusion.plan`` events of the pallas plans made since the last
    ``telemetry.reset()`` (the span of the same name carries no ``items``)."""
    return [e for e in telemetry.events() if e.get("name") == "fusion.plan"
            and e.get("mode") in ("pallas", "pallas_sharded")
            and "items" in e]


def _cell_plan(case, monkeypatch):
    def layers(n, depth, **kw):
        circ = Circuit(n)
        _builder("random_layers").build(circ, num_qubits=n, depth=depth,
                                        circuit_seed=2026)
        return circ.fused(max_qubits=5, pallas=True, **kw)

    if case == "density14.block":
        circ = Circuit(14, is_density_matrix=True)
        _builder("density_channels").build(circ, num_qubits=14)
        return circ.fused(max_qubits=5, pallas=True, dtype=np.float32)
    if case == "sv20.block":
        return layers(20, 8, dtype=np.float32)
    if case == "sv26.block":
        return layers(26, 2, dtype=np.float32)
    if case == "sv30.block":
        return layers(30, 2, dtype=np.float32)
    if case == "sv31x4.block":
        return layers(31, 2, dtype=np.float32, shard_devices=4)
    monkeypatch.setenv("QUEST_PALLAS_DF", "1")
    return layers(26, 2, dtype=np.float64)


#: ``plan_digest`` of each library cell's plan on the parent commit (a54b86d)
_PARENT_PLANS = {
    "density14.block": "2:af858bbf9d6f4833",
    "sv20.block": "9:31df6a66a3e340d6",
    "sv26.block": "3:33950854705f3455",
    "sv30.block": "4:9af239f1db24002a",
    "sv31x4.block": "3:36cb81ff1be4d58d",
    "df26.block": "12:c319be7e77247e06",
}


@pytest.mark.parametrize("case", sorted(_PARENT_PLANS))
def test_the_cells_plans_are_the_parent_s_item_for_item(case, monkeypatch):
    """The six library cells' plans (the two served cells run the dense
    plan, which this change does not touch): every run's tile, frame and ops
    in order as on the parent commit, by digest (``tests/plan_digest.py``: a
    channel op by its qubits, so that ``density14.block``'s two
    ``mixDepolarising`` may change their lowering and nothing around them
    may move). No run narrows its tile: none had an op no frame held."""
    telemetry.reset()
    fz = _cell_plan(case, monkeypatch)
    plan = fusion.plan_from_tape(fz._tape)
    assert plan_digest(plan) == _PARENT_PLANS[case]
    assert not any(r.own_tile for r in pallas_runs(fz))
    # ... and no frame of theirs grew (PR 42): the counter has no series
    assert not [k for k in telemetry.snapshot()["counters"]
                if k.startswith("fusion_frames_grown_total")]
    assert [e["frames_grown"] for e in _plan_events()] == [0]
    if case == "density14.block":
        kinds = [op[0] for r in pallas_runs(fz) for op in r.ops
                 if op[0] in planner_mod._CHANNEL_OPS]
        assert sorted(kinds) == ["depol", "depol", "kraus1", "krausn"]


def test_the_served_cells_dense_plan_is_untouched():
    """``ansatz20``'s tape through the planner the Engine uses (no Pallas
    plan): blocks only, and the counters of a dense plan."""
    from quest_tpu.params import Param
    from quest_tpu.ops.apply import DENSE_WINDOW_QUBITS

    circ = Circuit(20)
    _builder("serving_ansatz").build(circ, num_qubits=20, depth=4,
                                     angle=Param)
    plan = planner_mod.plan(tuple(circ._tape), 20, np.dtype("float32"),
                       max_qubits=DENSE_WINDOW_QUBITS)
    assert plan.num_barriers == 0 and len(plan.items) == 30
    assert not any(isinstance(i, planner_mod.PallasRun) for i in plan.items)


def test_the_plan_event_counts_channel_terms():
    """``fusion_channel_terms_total{kind}`` once a plan, and the
    ``fusion.plan`` event's ``channel_ops``, ``channel_terms``, ``barriers``
    and ``run_tile_bits``: the closed form counts 1 an op, a Kraus lowering
    its terms."""
    circ = _noisy(6)
    circ.mixDamping(0, 0.1)
    circ.mixTwoQubitKrausMap(1, 2, channels.two_qubit_depolarising_kraus(.1))
    telemetry.reset()
    plan, _ = _plan_at(circ, 10)
    counters = telemetry.snapshot()["counters"]
    terms = {k.split("kind=")[1].rstrip("}"): v for k, v in counters.items()
             if k.startswith("fusion_channel_terms_total")}
    assert terms == {"depol1": 12, "depol2": 7, "kraus1": 2, "kraus2": 16}
    assert counters["fusion_barriers_total{mode=pallas}"] == 0
    (event,) = _plan_events()
    assert event["channel_ops"] == 21 and event["channel_terms"] == 37
    assert event["barriers"] == 0
    assert event["run_tile_bits"] == [r.tile_bits for r in plan.items]
    assert event["kernel_op_kinds"]["depol"] == 19


# -- (b) the closed-form op --------------------------------------------------

N_OP = 5
_TARGETS = [(t,) for t in range(N_OP)] + list(
    itertools.permutations(range(N_OP), 2))
_PROBS = {1: (0.0, 1e-3, 0.3, 0.75), 2: (0.0, 1e-3, 0.3, 15 / 16)}


def _depol_op(targets, p):
    d2 = 4 ** len(targets)
    return ("depol", tuple(targets), tuple(t + N_OP for t in targets),
            p * d2 / (d2 - 1))


def _kraus_of(targets, p):
    return (channels.depolarising_kraus(p) if len(targets) == 1
            else channels.two_qubit_depolarising_kraus(p))


def _random_rho(seed):
    return oracle.random_density(N_OP, np.random.RandomState(seed))


def _planes(rho, dtype):
    flat = rho.T.reshape(-1)
    return jnp.asarray(np.stack([flat.real, flat.imag]), dtype=dtype)


def _matrix(planes):
    host = np.asarray(planes, dtype=np.float64)
    return (host[0] + 1j * host[1]).reshape(1 << N_OP, 1 << N_OP).T


@pytest.mark.parametrize("precision", ["float32", "float64", "df"])
@pytest.mark.parametrize("targets", _TARGETS, ids=lambda t: "q" + "_".join(
    map(str, t)))
def test_the_closed_form_is_the_kraus_sum(targets, precision):
    """Every target and every ordered pair of a 5-qubit density register,
    ``p`` from 0 to the channel's maximum: the 'depol' kernel op against
    ``tests/oracle.py``'s Kraus sum (complex128) and against
    ``ops.density.apply_channel``'s superoperator, in float32, native
    float64 and the double-float planes (on the CPU those keep about
    float32's accuracy: XLA:CPU does not hold the error-free transforms).
    Trace and hermiticity are kept."""
    rho = _random_rho(7 + len(targets))
    dtype = np.float32 if precision == "float32" else np.float64
    tol = 1e-12 if precision == "float64" else 2e-6
    for p in _PROBS[len(targets)]:
        amps = _planes(rho, dtype)
        ops = (_depol_op(targets, p),)
        if precision == "df":
            out = DF.df_join(PG.fused_local_run(
                DF.df_split(amps), n=2 * N_OP, ops=ops, interpret=True))
        else:
            out = PG.fused_local_run(amps, n=2 * N_OP, ops=ops,
                                     interpret=True)
        got = _matrix(out)
        want = oracle.apply_kraus_to_density(rho, N_OP, list(targets),
                                             _kraus_of(targets, p))
        assert np.max(np.abs(got - want)) < tol, p
        superop = DN.kraus_superoperator(_kraus_of(targets, p))
        via = _matrix(DN.apply_channel(_planes(rho, np.float64), superop,
                                       n=N_OP, targets=tuple(targets)))
        assert np.max(np.abs(got - via)) < tol, p
        assert abs(np.trace(got) - np.trace(rho)) < tol
        assert np.max(np.abs(got - got.conj().T)) < tol


def test_the_fully_depolarising_channel_leaves_the_maximally_mixed_target():
    """At the channel's maximum (``l`` = 1) the targets hold I/d whatever
    came in: the op is the channel, not a truncation of its Kraus sum."""
    rho = _random_rho(3)
    out = PG.fused_local_run(_planes(rho, np.float64), n=2 * N_OP,
                             ops=(_depol_op((1, 3), 15 / 16),),
                             interpret=True)
    got = _matrix(out).reshape((2,) * (2 * N_OP))
    # bits of a row index, most significant first: qubit q is axis 4 - q
    rest = np.einsum("abcdeAbCdE->aceACE", got.reshape((2,) * 10))
    want = np.einsum("abcdeAbCdE->aceACE",
                     rho.reshape((2,) * 10)) / 1.0
    assert np.allclose(rest, want, atol=1e-12)
    block = np.einsum("abcdeaBcDe->bdBD", got.reshape((2,) * 10))
    assert np.allclose(block.reshape(4, 4), np.eye(4) * np.trace(rho) / 4,
                       atol=1e-12)


@pytest.mark.parametrize("lowering", ["depol", "kraus"])
@pytest.mark.parametrize("n,sublanes,pair", [
    (6, 8, (3, 4)), (6, 8, (4, 3)), (6, 16, (4, 5)), (7, 16, (3, 4)),
    (7, 32, (5, 4)), (7, 64, (5, 6))])
def test_a_straddling_pair_runs_in_its_narrowed_kernel(n, sublanes, pair,
                                                       lowering, monkeypatch):
    """The pair whose columns straddle a forced tile, both orders, as the
    closed form and as a Kraus map: the plan's one run at a narrowed tile,
    executed (interpreted) through ``Circuit.run``, against the oracle's
    Kraus sum."""
    monkeypatch.setattr(PG, "_DEF_SUBLANES", sublanes)
    tile_bits = PG.local_qubits(2 * n, sublanes)
    assert {q + n for q in pair} == {tile_bits - 1, tile_bits}
    kraus = channels.two_qubit_depolarising_kraus(0.3)
    circ = Circuit(n, is_density_matrix=True)
    if lowering == "depol":
        circ.mixTwoQubitDepolarising(*pair, 0.3)
    else:
        circ.mixTwoQubitKrausMap(*pair, kraus)
    plan, fz = _plan_at(circ, tile_bits, dtype=np.float64)
    (run,) = _only_runs(plan)
    assert run.own_tile and run.tile_bits < tile_bits
    assert run.ops[0][0] == ("depol" if lowering == "depol" else "kraus2")
    env = qt.createQuESTEnv(jax.devices()[:1])
    q = qt.createDensityQureg(n, env)
    rho = oracle.random_density(n, np.random.RandomState(11))
    set_density(q, rho)
    telemetry.reset()
    fz.run(q)
    want = oracle.apply_kraus_to_density(rho, n, list(pair), kraus)
    assert np.max(np.abs(get_density(q) - want)) < 1e-12
    names = [e["kernel"] for e in telemetry.events()
             if e.get("name") == "pallas.compile"]
    assert names and all(k.endswith(f"_tb{run.tile_bits}") for k in names)


def test_the_gatewise_exit_replays_the_closed_form():
    """A 'depol' op that leaves the kernel route (here: replayed by hand)
    runs as the canonical Kraus sum of the same channel."""
    env = qt.createQuESTEnv(jax.devices()[:1])
    q = qt.createDensityQureg(N_OP, env)
    rho = _random_rho(5)
    set_density(q, rho)
    fusion._apply_ops_via_engine(q, (_depol_op((0, 4), 0.2),
                                     _depol_op((2,), 0.1)))
    want = oracle.apply_kraus_to_density(rho, N_OP, [0, 4],
                                         _kraus_of((0, 4), 0.2))
    want = oracle.apply_kraus_to_density(want, N_OP, [2],
                                         _kraus_of((2,), 0.1))
    assert np.max(np.abs(get_density(q) - want)) < 1e-12


@pytest.mark.parametrize("n", [6, 7])
def test_the_sharded_plan_runs_the_noisy_tape_per_shard(n):
    """The same tape planned for 4 devices and run on a register split over
    them: fused runs alone, each per shard (``_shard_route`` reads a 'depol'
    op's dense targets as it reads a Kraus op's), against the unfused
    replay on one device."""
    env4 = qt.createQuESTEnv(jax.devices()[:4])
    circ = _noisy(n)
    fz = circ.fused(max_qubits=5, pallas=True, shard_devices=4)
    runs = _only_runs(fusion.plan_from_tape(fz._tape))
    assert any(op[0] == "depol" for r in runs for op in r.ops)
    rho = oracle.random_density(n, np.random.RandomState(2))
    q = qt.createDensityQureg(n, env4)
    set_density(q, rho)
    q.put(jax.device_put(q.amps, env4.sharding(1 << 2 * n)))
    telemetry.reset()
    fz.run(q)
    counters = telemetry.snapshot()["counters"]
    assert not any(k.startswith("engine_fallback_total") for k in counters)
    one = qt.createQuESTEnv(jax.devices()[:1])
    ref = qt.createDensityQureg(n, one)
    set_density(ref, rho)
    circ.run(ref)
    assert np.max(np.abs(get_density(q) - get_density(ref))) < 1e-11
    assert abs(qt.calcTotalProb(q) - 1.0) < 1e-11


# -- (c) the whole tape -------------------------------------------------------

def _oracle_replay(rho, n):
    import sys

    sys.path.insert(0, BENCH)
    try:
        import reference
    finally:
        sys.path.remove(BENCH)
    tape = _noisy(n, rec=reference.Tape())
    for name, args in tape.ops:
        u = reference._unitary(name, args)
        if u is not None:
            rho = oracle.apply_to_density(rho, n, [u[0]], u[1], list(u[2]))
        elif name == "mixDepolarising":
            rho = oracle.apply_kraus_to_density(
                rho, n, [args[0]], channels.depolarising_kraus(args[1]))
        else:
            rho = oracle.apply_kraus_to_density(
                rho, n, list(args[:2]),
                channels.two_qubit_depolarising_kraus(args[2]))
    return rho, len(tape.ops)


@pytest.mark.parametrize("sublanes", [8, 16, PG._DEF_SUBLANES])
def test_the_whole_noisy_tape_fused_unfused_and_oracle(sublanes, monkeypatch):
    """Depth 2 on 6 qubits (38 tape entries): fused at a straddled tile
    (2^10, 2^11) and at the register's own, against the unfused replay and
    against the oracle's gate-by-gate, channel-by-channel replay."""
    n = 6
    monkeypatch.setattr(PG, "_DEF_SUBLANES", sublanes)
    circ = _noisy(n)
    plan, fz = _plan_at(circ, PG.local_qubits(2 * n, sublanes),
                        dtype=np.float64)
    runs = _only_runs(plan)
    assert any(r.own_tile for r in runs) == (sublanes < 32)
    env = qt.createQuESTEnv(jax.devices()[:1])
    rho = oracle.random_density(n, np.random.RandomState(6))
    fused, plain = (qt.createDensityQureg(n, env) for _ in (0, 1))
    set_density(fused, rho)
    set_density(plain, rho)
    fz.run(fused)
    circ.run(plain)
    want, entries = _oracle_replay(rho, n)
    assert entries == len(circ._tape) == 38
    assert np.max(np.abs(get_density(fused) - want)) < 1e-12
    assert np.max(np.abs(get_density(plain) - want)) < 1e-12
    assert abs(qt.calcTotalProb(fused) - 1.0) < 1e-12


# -- (d) a frame that grows to hold the next column op (PR 42) ----------------

def _frames(runs):
    return [(len(r.ops), r.load_swap_k, r.load_swap_hi, r.tile_bits)
            for r in runs]


def test_the_cells_tape_plans_in_six_passes_where_it_took_twelve():
    """``density15.noise``'s tape at the register's own tile: at most 7 fused
    runs (6: the parent planned 12, seven of them of one to five ops under
    frames ``k=1 @25``, ``@26``, ``@27``, ``k=2 @24`` to ``@28``), every one
    matched, exactly one at a tile of its own; the column ops of qubits 10
    to 13 ride ONE run whose block grew to ``k=5 @25``. The counter and the
    event say how often a block grew."""
    telemetry.reset()
    fz = _noisy(15).fused(max_qubits=5, pallas=True, dtype=np.float32)
    runs = _only_runs(fusion.plan_from_tape(fz._tape))
    assert len(runs) <= 7 and all(r.matched for r in runs)
    assert [r.tile_bits for r in runs if r.own_tile] == [18]
    assert _frames(runs) == [(43, 0, None, 19), (48, 9, 19, 19),
                             (29, 5, 25, 19), (16, 2, 28, 19),
                             (1, 2, 18, 18), (1, 2, 24, 19)]
    assert sum(len(r.ops) for r in runs) == 138
    (event,) = _plan_events()
    assert event["frames_grown"] == 4
    assert event["frame_widths"] == [0, 9, 5, 2, 2, 2]
    assert telemetry.snapshot()["counters"][
        "fusion_frames_grown_total{mode=pallas}"] == 4


def test_the_schedule_with_frames_fixed_is_the_parent_s(monkeypatch):
    """With growth refused the same tape plans as on the parent commit:
    twelve runs. The fixed schedule is what :func:`planner._plan_pallas`
    keeps among its candidates."""
    monkeypatch.setattr(planner_mod._FramePlanner, "_grown", lambda *a: None)
    fz = _noisy(15).fused(max_qubits=5, pallas=True, dtype=np.float32)
    runs = _only_runs(fusion.plan_from_tape(fz._tape))
    assert [f[:3] for f in _frames(runs)] == [
        (43, 0, None), (48, 9, 19), (1, 1, 25), (3, 1, 26), (1, 1, 27),
        (15, 2, 28), (9, 2, 25), (10, 2, 27), (1, 2, 18), (1, 2, 24),
        (1, 2, 26), (5, 2, 28)]


def _random_tape(n, density, seed, gates=48):
    """A seeded tape of one- and two-qubit gates on any pair (dense,
    diagonal, controlled, swaps), on a density register most of them
    followed by a channel on the qubits they touched."""
    rng = np.random.RandomState(seed)
    circ = Circuit(n, is_density_matrix=density)
    for _ in range(gates):
        q = int(rng.randint(n))
        r = int((q + 1 + rng.randint(n - 1)) % n)
        kind = rng.randint(6)
        if kind == 0:
            circ.hadamard(q)
        elif kind == 1:
            circ.rotateX(q, float(rng.uniform(0, 2 * np.pi)))
        elif kind == 2:
            circ.tGate(q)
        elif kind == 3:
            circ.controlledNot(q, r)
        elif kind == 4:
            circ.swapGate(q, r)
        else:
            circ.controlledPhaseShift(q, r, 0.7)
        if density and rng.rand() < 0.7:
            if kind >= 3:
                circ.mixTwoQubitDepolarising(q, r, P2)
            elif rng.rand() < 0.7:
                circ.mixDepolarising(q, P1)
            else:
                circ.mixDamping(q, 0.1)
    return circ


def _plan_random(circ, tile_bits):
    return planner_mod.plan(tuple(circ._tape), circ.num_qubits, np.dtype("float32"),
                       max_qubits=5, pallas_tile_bits=tile_bits,
                       is_density=circ.is_density_matrix)


def _watch_list_scheduler(monkeypatch):
    """Every list scheduler with growth on, as :func:`planner._plan_pallas`
    drives it: the ops in the order they arrive and the pending runs as
    they stand at each flush (the two-slot scheduler has its own ``add`` and
    ``flush`` and is not seen)."""
    seen = {}
    add, flush = planner_mod._FramePlanner.add, planner_mod._FramePlanner.flush

    def record(planner):
        return seen.setdefault(id(planner), dict(
            planner=planner, arrived=[], flushed=[]))

    def spy_add(self, op):
        record(self)["arrived"].append(op)
        add(self, op)

    def spy_flush(self):
        record(self)["flushed"].append([(f, list(ops)) for f, ops in self.runs])
        flush(self)

    monkeypatch.setattr(planner_mod._FramePlanner, "add", spy_add)
    monkeypatch.setattr(planner_mod._FramePlanner, "flush", spy_flush)
    return seen


_RANDOM_CASES = [(True, n, tb) for n in (6, 7, 8) for tb in (10, 11, 12, 13)
                 if tb < 2 * n] + \
                [(False, n, tb) for n in (11, 12, 13, 14)
                 for tb in (10, 11, 12, 13) if tb < n]


@pytest.mark.parametrize("density,n,tile_bits", _RANDOM_CASES, ids=[
    f"{'dm' if d else 'sv'}{n}-tile{tb}" for d, n, tb in _RANDOM_CASES])
def test_a_grown_schedule_keeps_every_op_in_a_frame_and_in_order(
        density, n, tile_bits, monkeypatch):
    """Seeded random tapes (noisy on 6 to 8 qubit density registers, plain on
    11 to 14 qubit state-vectors) at tiles of 2^10 to 2^13. Of the list
    scheduler with growth on: every op is ``feasible`` in the FINAL frame of
    the run that holds it, a run's ops stand in the order they arrived, and
    two ops that do not commute keep their tape order across runs. Of the
    plan: every run matched, every dense target inside its run's tile, no
    frame wider than the planner's ``width``, and no more items than the
    schedule with frames fixed at birth gives."""
    for seed in range(4):
        circ = _random_tape(n, density, 100 * n + seed)
        with monkeypatch.context() as patch:
            seen = _watch_list_scheduler(patch)
            plan = _plan_random(circ, tile_bits)
        (rec,) = [rec for rec in seen.values() if rec["planner"].grow]
        planner = rec["planner"]
        when = {id(op): i for i, op in enumerate(rec["arrived"])}
        placed = 0
        for runs in rec["flushed"]:
            where = {}
            for i, (frame, ops) in enumerate(runs):
                assert [when[id(op)] for op in ops] \
                    == sorted(when[id(op)] for op in ops)
                for op in ops:
                    assert planner.feasible(op, frame), (seed, op, frame)
                    where[id(op)] = i
                if frame is not None and len(frame) == 2:
                    assert frame[1] <= planner.width(sum(frame)), frame
            held = [op for _, ops in runs for op in ops]
            placed += len(held)
            for a in held:
                for b in held:
                    if when[id(a)] < when[id(b)] \
                            and not planner._commutes(a, b):
                        assert where[id(a)] <= where[id(b)], (seed, a, b)
        assert placed == len(rec["arrived"])
        for run in plan.items:
            if isinstance(run, planner_mod.PallasRun):
                assert run.matched
                assert all(q < run.tile_bits for op in run.ops
                           for q in PG.op_dense_targets(op))
        with monkeypatch.context() as patch:
            patch.setattr(planner_mod._FramePlanner, "_grown", lambda *a: None)
            fixed = _plan_random(circ, tile_bits)
        assert fixed.frames_grown == 0
        assert len(plan.items) <= len(fixed.items), seed
        assert (plan.num_barriers, plan.num_fused_gates) \
            == (fixed.num_barriers, fixed.num_fused_gates)


@pytest.mark.parametrize("density,n,tile_bits,seed", [
    (True, 7, 10, 700), (True, 7, 10, 704), (True, 8, 13, 803),
    (True, 8, 13, 807), (False, 14, 10, 1402), (False, 14, 10, 1405)])
def test_growth_engages_on_random_tapes(density, n, tile_bits, seed,
                                        monkeypatch):
    """Tapes of the property test above on which a block did grow: fewer
    items than with frames fixed at birth, and the plan says so."""
    circ = _random_tape(n, density, seed)
    plan = _plan_random(circ, tile_bits)
    monkeypatch.setattr(planner_mod._FramePlanner, "_grown", lambda *a: None)
    fixed = _plan_random(circ, tile_bits)
    assert plan.frames_grown > 0 and len(plan.items) < len(fixed.items)


def test_a_block_never_grows_across_the_shard_boundary():
    """The planner of a register split over 4 devices (17 qubits of density,
    34 bits, 32 a shard): a block below the boundary grows up to it and not
    across, one above it stays above, and the widths are those of
    ``width``: what folds below the boundary, the planner's ``k`` for a
    collective."""
    planner = planner_mod._FramePlanner(planner_mod.FusePlan(), 19, 12, 34,
                                   boundary=32, n_exec=32)

    def depol(q):
        return planner_mod._POp("depol", (q, q + 17), (), (), 0.1, False)

    def column(bit):     # a gate's shadow: one dense target, a column bit
        return planner_mod._POp("matrix", (bit,), (), (), np.eye(2)[::-1], False)

    assert planner._grown((27, 1), [depol(10)], depol(13)) == (27, 4)
    assert planner._grown((27, 4), [depol(10)], column(31)) == (27, 5)
    assert planner._grown((27, 5), [depol(10)], column(32)) is None
    assert planner._grown((32, 1), [column(32)], column(33)) == (32, 2)
    assert planner._grown((32, 1), [column(32)], column(31)) is None
    # the same block on one device, where nothing is sharded, may take it
    one = planner_mod._FramePlanner(planner_mod.FusePlan(), 19, 12, 34)
    assert one._grown((27, 5), [depol(10)], column(32)) == (27, 6)
    # an identity run and a narrowed-tile frame do not grow; nor a block
    # whose wider form would displace a target the run holds (row 14) or
    # would be wider than what folds (9 at this tile)
    assert one._grown(None, [], depol(10)) is None
    assert one._grown((18, 2, 18), [], depol(10)) is None
    assert one._grown((27, 4), [depol(13)], depol(14)) is None
    assert one._grown((19, 9), [], column(28)) is None


@pytest.mark.parametrize("precision,tol", [(1, 2e-6), (2, 1e-12)])
def test_a_grown_frame_plan_runs_to_the_oracle_s_result(precision, tol,
                                                        monkeypatch):
    """The noisy tape on 9 qubits at a tile of 2^13 (64 sublanes: frames of
    up to 3 bits fold): a plan in which a block grew (``k=3 @15`` holds 30
    ops), run through ``Circuit.run`` in float32 and in float64, against
    the oracle's gate-by-gate, channel-by-channel replay."""
    n, sublanes = 9, 64
    monkeypatch.setattr(PG, "_DEF_SUBLANES", sublanes)
    circ = _noisy(n)
    dtype = np.float32 if precision == 1 else np.float64
    plan, fz = _plan_at(circ, PG.local_qubits(2 * n, sublanes), dtype=dtype)
    runs = _only_runs(plan)
    assert plan.frames_grown == 1
    assert (30, 3, 15, 13) in _frames(runs)
    register = shape_register(2 * n, dtype)
    assert all(fusion._route(register, r).unfolded == 0 for r in runs)
    env = qt.createQuESTEnv(jax.devices()[:1])
    q = qt.createDensityQureg(n, env, precision_code=precision)
    assert q.dtype == dtype
    rho = oracle.random_density(n, np.random.RandomState(6))
    set_density(q, rho)
    fz.run(q)
    want, _ = _oracle_replay(rho, n)
    assert np.max(np.abs(get_density(q) - want)) < tol
    assert abs(qt.calcTotalProb(q) - 1.0) < 10 * tol
