"""What ``sv30.block`` and ``ansatz20.serve-closed12`` added to the benchmark:
the large-register driver's check, whose pieces (the state to the host in
slices, the reference from the host's copy of the seed's state, the errors
piece by piece) are tied at 14 qubits to the whole-vector forms they stand in
for; its refusal of a plan that would not fit; the two program-counter
readers; and both cells' controls and counts under ``--rehearse``."""

import json
import os
import types

import numpy as np
import pytest

import jax

import reference
import reference_planes
import run as harness
import states
import states_sharded
from conftest import ROOT, run_child

N, SEED = 14, 2 ** 31 + 35


@pytest.fixture(scope="module")
def large():
    return harness.load_module("drivers", "library_large")


@pytest.fixture(scope="module")
def mesh():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("amps",))


def _tape(n=N):
    tape = reference.Tape()
    harness.load_module("circuits", "random_layers").build(
        tape, num_qubits=n, depth=2, circuit_seed=2026)
    return tape


def test_a_state_goes_to_the_host_in_pieces_as_it_is(large, mesh):
    state = states_sharded.statevector_planes(SEED, N, mesh, "amps")
    np.testing.assert_array_equal(large.to_host(state, 1), np.asarray(state))
    re, im = reference_planes.split(state)
    np.testing.assert_array_equal(large.to_host(im, 0), np.asarray(im))


def test_reference_from_the_host_copy_is_run_statevector_s(large, mesh):
    """The driver's gate loop (planes cut on the host) gives what
    ``reference_planes.run_statevector`` gives from the same state, bit for
    bit, in float32 and in the control's bfloat16; and that is the numpy
    complex128 replay to float32 rounding."""
    tape = _tape()
    state = states_sharded.statevector_planes(SEED, N, mesh, "amps")
    seed = np.asarray(state)
    rows = reference_planes._rows(state.sharding)
    driver = types.SimpleNamespace(n=N)
    for lower in (None, reference_planes.bfloat16):
        got = large.Driver.reference(driver, seed, rows, tape.ops, lower)
        want = reference_planes.run_statevector(
            states_sharded.statevector_planes(SEED, N, mesh, "amps"), N,
            tape.ops, lower=lower)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    host = reference.run_statevector(states.to_complex(seed), tape.ops)
    err_max, err_l2 = reference.errors(np.asarray(want[0]).reshape(-1),
                                       np.asarray(want[1]).reshape(-1), host)
    assert err_max > 1e-3 and err_l2 > 1e-3     # want is the bfloat16 one
    err_max, err_l2 = large.errors_by_piece(
        np.stack([host.real, host.imag]).astype(np.float32),
        large.Driver.reference(driver, seed, rows, tape.ops))
    assert err_max < 1e-5 and err_l2 < 1e-5


def test_errors_piece_by_piece_are_the_whole_vector_s(large, mesh):
    tape = _tape()
    want = reference_planes.run_statevector(
        states_sharded.statevector_planes(SEED, N, mesh, "amps"), N, tape.ops)
    rng = np.random.default_rng(SEED)
    got = np.stack([np.asarray(p).reshape(-1) for p in want])
    got = got + rng.standard_normal(got.shape).astype(np.float32) * 1e-6
    got[1, 12345] += 3e-4           # one amplitude far off, in one piece
    by_piece = large.errors_by_piece(got, want)
    whole = reference_planes.errors(
        tuple(jax.numpy.asarray(g.reshape(-1, 128)) for g in got), want)
    host = reference.errors(got[0], got[1], states.to_complex(
        np.stack([np.asarray(p).reshape(-1) for p in want])))
    np.testing.assert_allclose(by_piece, whole, rtol=1e-5)
    np.testing.assert_allclose(by_piece, host, rtol=1e-4)
    assert by_piece[0] > 1e-3


def test_a_plan_that_would_not_fold_is_refused_before_any_compile(large):
    """What the parent of PR 35 plans at 30 qubits: a frame wider than the
    kernel's DMA folds. The driver exits with its own code, at once."""
    from quest_tpu import fusion
    from quest_tpu.circuits import Circuit
    from quest_tpu.registers import Qureg

    n = 30
    wide = fusion.PallasRun((), 19, load_swap_k=11, load_swap_hi=19,
                            store_swap_k=11, store_swap_hi=19)
    fused = Circuit(n)
    fused._tape = fusion.as_tape(types.SimpleNamespace(
        items=[fusion.PallasRun((), 19), wide]))
    driver = types.SimpleNamespace(
        fused=fused, shapes=lambda: {"state_bytes": 8 << n},
        q=Qureg(n, False, jax.ShapeDtypeStruct((2, 1 << n), np.float32),
                None))
    with pytest.raises(SystemExit) as exit_:
        large.Driver.refuse_unfolded_plan(driver)
    assert exit_.value.code == large.EXIT_PLAN_REFUSED
    fused._tape = fusion.as_tape(types.SimpleNamespace(
        items=[fusion.PallasRun((), 19, load_swap_k=9, load_swap_hi=19,
                                store_swap_k=9, store_swap_hi=19)]))
    large.Driver.refuse_unfolded_plan(driver)       # folds: no exit


@pytest.mark.parametrize("counters,inplace,unfolded", [
    ({}, None, None),                               # a program without them
    ({"fusion_inplace_runs_total": 4}, 4, 0),
    ({"fusion_inplace_runs_total": 3, "fusion_unfolded_swaps_total": 2,
      'engine_fallback_total{reason="swap_not_foldable"}': 1}, 3, 2)])
def test_the_two_counter_readers(counters, inplace, unfolded):
    m = {"after": {"counters": counters}, "before": {"counters": {}}}
    assert harness.load_module("layer_metrics",
                               "inplace_runs.lib").read(m) == inplace
    assert harness.load_module("layer_metrics",
                               "unfolded_swaps.lib").read(m) == unfolded


def test_sv30_rehearsal_counts_its_runs_in_place_and_its_control_fails(bench):
    rc, last, out = run_child(["benchmark/run.py", "--workload", "sv30.block",
                               "--seed", str(SEED), "--seconds", "1",
                               "--trace", "1", "--rehearse"])
    assert rc == 0 and last["correct"] is True, out[-3000:]
    # 14 qubits are one tile: one fused run, in place, nothing unfolded
    assert last["metrics"]["inplace_runs.lib"]["value"] == 1
    assert last["metrics"]["unfolded_swaps.lib"]["value"] == 0
    assert set(last["checks"]) >= {"err_max", "err_l2",
                                   "drift_per_application"}
    rc, _, out = run_child(["benchmark/control.py", "--workload", "sv30.block",
                            "--seeds", "3,4", "--seconds", "0.5",
                            "--rehearse"])
    assert rc == 0, out[-3000:]
    rows = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sv30-f32-random.json")) as f:
        limits = json.load(f)["check"]["limits"]
    assert len(rows) == 2
    for row in rows:
        assert all(row["sound"][k] <= limits[k] for k in limits), row
        assert any(row["control"][k] > limits[k] for k in limits
                   if k in row["control"]), row


def test_twelve_closed_loops_do_not_fill_every_batch(bench):
    """``ansatz20.serve-closed12`` is data only: the accepted driver and
    loop on 12 clients. 12 is not a multiple of the engine's batch of 8, so
    the mean batch lies between 4 and 8."""
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "serve-closed12.json")) as f:
        assert json.load(f)["clients"] == 12
    rc, last, out = run_child(["benchmark/run.py", "--workload",
                               "ansatz20.serve-closed12", "--seed", str(SEED),
                               "--seconds", "2", "--trace", "1",
                               "--rehearse"])
    assert rc == 0 and last["correct"] is True, out[-3000:]
    assert 4.0 <= last["metrics"]["batch_width.serve"]["value"] <= 8.0
    # the planner's two counts stay the first served cell's alone: an
    # accepted test (test_program_readers.py) pins their lists of cells
    assert "param_barriers.serve" not in last["metrics"]
