"""What a register that fills its chip asks of the planner and the kernels
(benchmark cell ``sv30.block``: 2^30 amplitudes, 8 GiB of a v5e's 16 GB):
every frame relabeling rides its kernel's DMA, every fused run leaves the
frame it entered and so may write over its operand, and the plans of the
cells the benchmark already had stay what they were. Plans only at the real
sizes; execution at a rehearsal size whose tile is cut so that both of the
30-qubit plan's frame kinds occur. (What the chip's compiler says of the
real sizes is in ``tests/test_chip_compile.py``.)"""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from quest_tpu import fusion, planner, telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.ops import pallas_gates as PG

from . import oracle
from .helpers import pallas_runs, shape_register

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


@pytest.fixture(scope="module")
def bench():
    """The benchmark's builder and plain references, imported as its own
    files import each other (``benchmark/`` on the path for this module)."""
    sys.path.insert(0, BENCH)
    try:
        import reference
        import reference_planes

        mods = {"reference": reference, "planes": reference_planes}
        for name in ("random_layers", "density_channels", "serving_ansatz"):
            spec = importlib.util.spec_from_file_location(
                name, os.path.join(BENCH, "circuits", name + ".py"))
            mods[name] = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mods[name])
        yield mods
    finally:
        sys.path.remove(BENCH)


def _layers(bench, n, depth=2, **fused_kw):
    circ = Circuit(n)
    bench["random_layers"].build(circ, num_qubits=n, depth=depth,
                                 circuit_seed=2026)
    return circ, circ.fused(max_qubits=5, pallas=True, dtype=np.float32,
                            **fused_kw)


def _shape(run):
    """(ops, load k @ hi, store k @ hi) of a run, as the cells' ``why``
    lines and the kernels' names tell them."""
    return (len(run.ops), (run.load_swap_k, run.load_swap_hi),
            (run.store_swap_k, run.store_swap_hi))


# -- the planner ------------------------------------------------------------

@pytest.mark.parametrize("n", [27, 28, 29, 30, 31])
def test_every_relabeling_of_a_one_device_plan_folds(bench, n):
    """27 to 31 qubits on one device: every run leaves on its store the
    frame it entered on its load, no frame is wider than what the kernel's
    DMA folds, and the route folds both and counts no fallback (before: from
    29 qubits up the one k = n - 19 frame ran as two explicit passes)."""
    _, fz = _layers(bench, n)
    register = shape_register(n, np.float32)
    runs = pallas_runs(fz)
    assert len(runs) >= 3
    for run in runs:
        assert run.matched, run
        assert run.load_swap_k <= planner._fold_width(run.tile_bits)
        route = fusion._route(register, run)
        assert route.kind == "local" and route.reason is None, route
        assert route.unfolded == 0
        assert route.fold_load == route.fold_store == bool(run.load_swap_k)


def test_the_30q_plan_is_the_cell_s(bench):
    """``sv30.block``: 60 ops, 23 under the k=9 frame at the tile, 6 under
    a k=2 frame at bit 28, 2; depth 1 (the depth rule's other arm): 34, 10, 2."""
    shapes = [_shape(r) for r in pallas_runs(_layers(bench, 30)[1])]
    assert shapes == [(60, (0, None), (0, None)), (23, (9, 19), (9, 19)),
                      (6, (2, 28), (2, 28)), (2, (0, None), (0, None))]
    shapes = [_shape(r) for r in pallas_runs(_layers(bench, 30, depth=1)[1])]
    assert [s[0] for s in shapes] == [34, 10, 2]
    assert [s[1] for s in shapes] == [(0, None), (9, 19), (2, 28)]


def test_the_accepted_cells_plans_did_not_move(bench):
    """What ``BENCHMARK.json``'s ``why`` lines say of the five cells that
    were there: runs, op counts, frame widths."""
    sv26 = [_shape(r) for r in pallas_runs(_layers(bench, 26)[1])]
    assert sv26 == [(57, (0, None), (0, None)), (20, (7, 19), (7, 19)),
                    (2, (0, None), (0, None))]
    sv20 = pallas_runs(_layers(bench, 20, depth=8)[1])
    assert len(sv20) == 9 and sum(len(r.ops) for r in sv20) == 244
    assert {r.load_swap_k for r in sv20} == {0, 1} and all(
        r.matched for r in sv20)
    x4 = [_shape(r) for r in pallas_runs(
        _layers(bench, 31, shard_devices=4)[1])]
    assert x4 == [(62, (0, None), (0, None)), (31, (12, 19), (12, 19)),
                  (1, (0, None), (0, None))]
    dm = Circuit(14, is_density_matrix=True)
    bench["density_channels"].build(dm, num_qubits=14)
    density = pallas_runs(dm.fused(max_qubits=5, pallas=True,
                                   dtype=np.float32))
    assert [(r.load_swap_k, r.store_swap_k) for r in density] \
        == [(0, 0), (1, 1)]
    # the served plan is dense: no fused run to frame
    served = Circuit(20)
    names = bench["serving_ansatz"].param_names(num_qubits=20, depth=4)
    from quest_tpu.params import Param
    bench["serving_ansatz"].build(served, num_qubits=20, depth=4,
                                  angle=lambda name: Param(name))
    assert len(names) == 160
    plan = planner.plan(tuple(served._tape), 20, np.dtype("float32"),
                       max_qubits=7)
    assert not any(isinstance(i, planner.PallasRun) for i in plan.items)


def test_a_run_split_by_the_op_cap_leaves_its_frame_in_every_piece():
    """More ops in one frame than ``_RUN_OP_CAP``: each piece carries the
    frame on its load and on its store (before: the first its load, the last
    its store, and an in-place pass under either alone is wrong)."""
    n, tile_bits = 24, 19
    circ = Circuit(n)
    rng = np.random.RandomState(3)
    for _ in range(planner._RUN_OP_CAP + 8):
        g, _ = np.linalg.qr(rng.randn(2, 2) + 1j * rng.randn(2, 2))
        circ.unitary(n - 1, g)      # a grid-bit target: frame (19, 5) only
        circ.unitary(int(rng.randint(0, 7)), g)
    plan = planner.plan(tuple(circ._tape), n, np.dtype("float32"),
                       max_qubits=1, pallas_tile_bits=tile_bits)
    framed = [r for r in plan.items if isinstance(r, planner.PallasRun)
              and r.load_swap_k]
    assert len(framed) >= 2
    assert all(r.matched and r.store_swap_k == r.load_swap_k for r in framed)


def test_plan_event_names_the_frames_and_the_runs_in_place(bench):
    telemetry.reset()
    _layers(bench, 30)
    event = [e for e in telemetry.events() if e.get("name") == "fusion.plan"
             and e.get("mode") == "pallas"][-1]
    assert event["frame_widths"] == [0, 9, 2, 0]
    assert event["inplace_runs"] == event["pallas_runs"] == 4


# -- the in-place contract, structurally --------------------------------------

def _chunk_rows(rows, s, planes, k, hi, monkeypatch):
    """Row ids (of the row-interleaved register) that chunk ``c`` of a dma
    kernel's load or store touches under the frame ``(k, hi)``, for every
    chunk: (chunks, rows a chunk). ``_swap_view``'s own reshape over an
    array of row ids one lane wide, indexed as ``_make_dma_kernel``'s
    ``chunk_coords`` indexes it."""
    monkeypatch.setattr(PG, "_LANES", 1)
    ids = np.arange(rows * planes, dtype=np.int32).reshape(-1, 1)
    chunks = rows // s
    if not k:
        return ids.reshape(chunks, planes * s)
    s_bits = s.bit_length() - 1
    view = PG._swap_view(ids, rows, s, hi - PG.LANE_BITS, k)
    dk, gm_sz = 1 << k, 1 << (hi - PG.LANE_BITS - s_bits)
    c = np.arange(chunks)
    gm, rest = c % gm_sz, c // gm_sz
    picked = view[(rest // dk)[:, None], np.arange(dk)[None, :],
                  gm[:, None], (rest % dk)[:, None]]
    return picked.reshape(chunks, -1)


@pytest.mark.parametrize("n,k,hi", [(30, 9, 19), (30, 2, 28), (26, 7, 19),
                                    (26, 0, None)])
def test_a_matched_run_s_chunks_own_their_addresses(n, k, hi, monkeypatch):
    """The frames of the 30q plan and of ``sv26``'s, and no frame: chunk
    ``c`` loads and stores ONE set of rows (the same geometry on both
    sides), the sets of different chunks are disjoint and together the
    whole register -- so a store lands only where its own chunk has been
    read, and a load running ahead reads only chunks not yet written. The
    Pallas interpreter copies an aliased operand, so no parity test can show
    a race; this can."""
    s, planes = PG._DEF_SUBLANES, 2
    rows = (1 << n) >> PG.LANE_BITS
    assert PG.writes_in_place(19, k, hi, k, hi)
    touched = _chunk_rows(rows, s, planes, k, hi, monkeypatch)
    assert touched.shape == (rows // s, planes * s)
    flat = np.sort(touched.reshape(-1))
    assert np.array_equal(flat, np.arange(rows * planes, dtype=np.int32))


def test_an_unmatched_run_s_store_lands_in_other_chunks(monkeypatch):
    """Load under a frame, store under none (what a piece of a split run
    was): chunk 0's store covers rows that later chunks still have to load,
    and ``writes_in_place`` refuses the alias."""
    n, k, hi, s = 26, 7, 19, PG._DEF_SUBLANES
    rows = (1 << n) >> PG.LANE_BITS
    assert not PG.writes_in_place(19, k, hi, 0, None)
    assert not PG.writes_in_place(19, k, hi, k, hi + 1)
    loads = _chunk_rows(rows, s, 2, k, hi, monkeypatch)
    stores = _chunk_rows(rows, s, 2, 0, None, monkeypatch)
    later = np.isin(stores[0], loads[1:].reshape(-1))
    assert later.any()


# -- parity at a rehearsal size ----------------------------------------------

N_SMALL, SUBLANES_SMALL = 15, 32


@pytest.fixture
def small_tile(monkeypatch):
    """A 2^12 tile, so that a 15-qubit register has what the 30-qubit one
    has at 2^19: a frame of the fold rule's own width at the tile and a
    narrower one above it. Routing reads the sublane count when it routes."""
    monkeypatch.setattr(PG, "_DEF_SUBLANES", SUBLANES_SMALL)
    return PG.local_qubits(N_SMALL, SUBLANES_SMALL)


def _small_plan(bench, tile_bits):
    circ = Circuit(N_SMALL)
    bench["random_layers"].build(circ, num_qubits=N_SMALL, depth=2,
                                 circuit_seed=2026)
    plan = planner.plan(tuple(circ._tape), N_SMALL, np.dtype("float32"),
                       max_qubits=5, pallas_tile_bits=tile_bits)
    fz = Circuit(N_SMALL)
    fz._tape = fusion.as_tape(plan)
    return circ, fz


def _gaussian_planes(seed):
    rng = np.random.default_rng(seed)
    g = rng.standard_normal((2, 1 << N_SMALL))
    return (g / np.sqrt(np.sum(g * g))).astype(np.float32)


@pytest.mark.parametrize("donate", [True, False])
def test_both_frame_kinds_in_place_agree_with_the_plain_references(
        bench, small_tile, donate):
    """The ``sv30`` tape at 15 qubits on a 2^12 tile: a k=2 frame at the
    tile (the widest that folds there) and a k=1 frame above it, folded and
    in place, against ``reference_planes`` (float32 planes, gate by gate)
    and against ``tests/oracle.py``; donated, and undonated, where the
    caller's argument must come back intact."""
    circ, fz = _small_plan(bench, small_tile)
    runs = pallas_runs(fz)
    widths = [(r.load_swap_k, r.load_swap_hi) for r in runs if r.load_swap_k]
    assert (planner._fold_width(small_tile), small_tile) in widths
    assert any(k < planner._fold_width(small_tile) and hi > small_tile
               for k, hi in widths), widths
    register = shape_register(N_SMALL, np.float32)
    for run in runs:
        route = fusion._route(register, run)
        assert route.reason is None and route.fold_load == bool(
            run.load_swap_k), route

    telemetry.reset()
    start = _gaussian_planes(2 ** 31 + 35)
    amps = jnp.asarray(start)
    out = fz.compiled(donate=donate)(amps)
    got = np.asarray(out)
    if donate:
        assert amps.is_deleted()
    else:
        assert np.array_equal(np.asarray(amps), start)
    counters = telemetry.snapshot()["counters"]
    assert counters.get("fusion_inplace_runs_total") == len(runs)
    assert "fusion_unfolded_swaps_total" not in counters
    assert not any(k.startswith("engine_fallback_total") for k in counters)

    tape = bench["reference"].Tape()
    bench["random_layers"].build(tape, num_qubits=N_SMALL, depth=2,
                                 circuit_seed=2026)
    want = bench["planes"].run_statevector(jnp.asarray(start), N_SMALL,
                                           tape.ops)
    want = np.stack([np.asarray(p).reshape(-1) for p in want])
    assert np.max(np.abs(got - want)) < 1e-4 * np.max(np.abs(want))
    ref = start[0].astype(np.complex128) + 1j * start[1]
    for name, args in tape.ops:
        t, m, ctl = bench["reference"]._unitary(name, args)
        ref = oracle.apply_to_statevec_indexed(ref, N_SMALL, [t], m,
                                               list(ctl))
    err = np.max(np.abs((got[0] + 1j * got[1]) - ref)) / np.max(np.abs(ref))
    assert err < 1e-4
    del circ


def test_an_unfolded_relabeling_is_counted_by_name(bench):
    """A plan made for another tile than the register's runs its frames as
    explicit passes beside the kernel: ``fusion_unfolded_swaps_total``
    counts each (two a framed run), beside the fallback label."""
    circ, fz = _small_plan(bench, PG.local_qubits(N_SMALL, SUBLANES_SMALL))
    framed = sum(bool(r.load_swap_k) for r in pallas_runs(fz))
    assert framed
    telemetry.reset()
    start = _gaussian_planes(7)
    got = np.asarray(fz.compiled(donate=False)(jnp.asarray(start)))
    counters = telemetry.snapshot()["counters"]
    assert counters["fusion_unfolded_swaps_total"] == 2 * framed
    fallbacks = {k: v for k, v in counters.items()
                 if k.startswith("engine_fallback_total")}
    assert list(fallbacks.values()) == [framed]
    assert "swap_not_foldable" in next(iter(fallbacks))
    want = np.asarray(circ.as_fn()(jnp.asarray(start.astype(np.float64))))
    assert np.max(np.abs(got - want)) < 1e-5
