"""What ``density15.noise`` added to the benchmark (PR 41): the plain reference
for a density register touched on every qubit (``reference_density_planes``),
tied to ``reference.run_density_blocks`` and to a dense numpy replay; the
seed's projector written block by block (``states_density``), tied to
``states.projector_planes``; the driver's refusal of a plan that holds
anything but fused runs; the two planner readers; and the cell's rehearsal at
a tile cut so that a pair's columns straddle it, with its control."""

import json
import os
import types

import numpy as np
import pytest

import jax

import reference
import reference_density_planes as rdp
import run as harness
import states
import states_density
from conftest import ROOT, run_child

SEED = 2 ** 31 + 41


def _rows(planes):
    """(2, N) planes as ``reference_density_planes`` takes them."""
    import jax.numpy as jnp

    return tuple(jnp.asarray(np.asarray(planes[p]).reshape(-1, rdp.planes.LANES))
                 for p in (0, 1))


def _matrix(re, im, n):
    """rho[row, col] (complex128) of the planes of a flat ``col * 2^n + row``."""
    flat = (np.asarray(re, dtype=np.float64).reshape(-1)
            + 1j * np.asarray(im, dtype=np.float64).reshape(-1))
    return flat.reshape(1 << n, 1 << n).T


def _dense_replay(rho, n, ops):
    """The tape on a dense complex128 rho, every operator a full 2^n matrix:
    a gate ``U rho U^dagger``, a channel its Kraus sum over the Paulis."""
    paulis = [np.eye(2), reference._X, reference._Y, reference._Z]

    def full(ms):
        """kron over qubits, qubit 0 least significant."""
        out = np.eye(1)
        for q in range(n):
            out = np.kron(ms.get(q, np.eye(2)), out)
        return out

    for name, args in ops:
        u = reference._unitary(name, args)
        if u is not None:
            t, m, ctl = u
            op = full({t: m})
            if ctl:
                p1 = full({ctl[0]: np.diag([0.0, 1.0])})
                op = (np.eye(1 << n) - p1) + p1 @ op
            rho = op @ rho @ op.conj().T
            continue
        targets, p = rdp.channel_of(name, args)
        new = (1 - p) * rho
        words = [w for w in np.ndindex(*(4,) * len(targets)) if any(w)]
        for w in words:
            op = full({t: paulis[k] for t, k in zip(targets, w)})
            new = new + p / len(words) * (op @ rho @ op.conj().T)
        rho = new
    return rho


def test_the_projector_block_by_block_is_states_projector_planes():
    n = 7
    psi, rho = states.projector_planes(SEED, n)
    psi2, rho2 = states_density.projector_planes(SEED, n)
    np.testing.assert_array_equal(np.asarray(psi), np.asarray(psi2))
    np.testing.assert_allclose(np.asarray(rho2), np.asarray(rho), rtol=0,
                               atol=1e-9)
    re, im = states_density.projector_rows(SEED, n)
    np.testing.assert_array_equal(np.asarray(re).reshape(-1),
                                  np.asarray(rho2[0]))
    np.testing.assert_array_equal(np.asarray(im).reshape(-1),
                                  np.asarray(rho2[1]))


def test_the_reference_is_run_density_blocks_on_density14_s_tape():
    """Gates on a density register (``U`` on bit q, ``conj(U)`` on bit
    q + n, controls likewise) against ``reference.run_density_blocks`` at 7
    qubits: ``density14.block``'s gates and its ``mixDepolarising`` (its
    other channels are not this replay's)."""
    n = 7
    tape = reference.Tape()
    harness.load_module("circuits", "density_channels").build(tape,
                                                              num_qubits=n)
    ops = [op for op in tape.ops if reference._unitary(*op) is not None
           or op[0] == "mixDepolarising"]
    assert len(ops) == 8
    psi, rho = states.projector_planes(SEED, n)
    got = _matrix(*rdp.run_density(*_rows(rho), n, ops), n)
    active = reference.support(ops)
    rows, cols = states.sample_pairs(SEED, 8, n - len(active))
    want = reference.run_density_blocks(states.to_complex(psi), n, ops, rows,
                                        cols)
    idx = reference.block_indices(n, active, rows, cols)
    flat = got.T.reshape(-1)            # flat index col * 2^n + row
    assert np.max(np.abs(flat[idx] - want)) < 1e-6 * np.max(np.abs(want))


def test_the_reference_is_the_dense_kraus_replay_of_the_noisy_tape():
    """The whole noisy tape at 6 qubits (38 entries, channels on every
    qubit and on pairs inside a tile, across it and above it) against a
    dense complex128 replay with full 2^n operators, to 1e-6; its trace."""
    n = 6
    tape = reference.Tape()
    harness.load_module("circuits", "noisy_layers").build(
        tape, num_qubits=n, depth=2, circuit_seed=2026, p1=0.01, p2=0.05)
    psi, rho = states.projector_planes(SEED, n)
    re, im = rdp.run_density(*_rows(rho), n, tape.ops)
    want = _dense_replay(_matrix(rho[0], rho[1], n), n, tape.ops)
    assert np.max(np.abs(_matrix(re, im, n) - want)) \
        < 1e-6 * np.max(np.abs(want))
    assert abs(rdp.trace(re, n) - np.trace(want).real) < 1e-6
    # the control's arithmetic is another result, by far
    low = rdp.run_density(*_rows(rho), n, tape.ops, rdp.planes.bfloat16)
    assert np.max(np.abs(_matrix(*low, n) - want)) \
        > 1e-3 * np.max(np.abs(want))


def test_a_plan_with_a_barrier_or_a_block_is_refused_before_any_register():
    from quest_tpu import fusion
    from quest_tpu.circuits import Circuit
    from quest_tpu.decoherence import mixTwoQubitDepolarising

    large = harness.load_module("drivers", "library_density_large")
    run = fusion.PallasRun((), 19)
    for items in ([run, (mixTwoQubitDepolarising, (3, 4, 0.01), {})],
                  [fusion.FusedBlock((0, 1), np.eye(4)), run]):
        fused = Circuit(15, is_density_matrix=True)
        fused._tape = fusion.as_tape(types.SimpleNamespace(items=items))
        driver = types.SimpleNamespace(
            fused=fused, shapes=lambda: {"state_bytes": 8 << 30})
        with pytest.raises(SystemExit) as exit_:
            large.Driver.refuse_plan(driver)
        assert exit_.value.code == large.EXIT_PLAN_REFUSED
    fused._tape = fusion.as_tape(types.SimpleNamespace(items=[run, run]))
    large.Driver.refuse_plan(driver)                # runs alone: no exit


@pytest.mark.parametrize("counters,barriers,terms", [
    ({}, None, None),                               # a process without a plan
    ({"fusion_plans_total{mode=pallas}": 1,
      "fusion_barriers_total{mode=pallas}": 0}, 0, None),   # the parent's
    ({"fusion_plans_total{mode=pallas}": 1,
      "fusion_barriers_total{mode=pallas}": 1,
      "fusion_channel_terms_total{kind=kraus1}": 8,
      "fusion_channel_terms_total{kind=depol2}": 16}, 1, 24)])
def test_the_two_planner_readers(counters, barriers, terms):
    m = {"after": {"counters": counters}, "before": {"counters": {}}}
    assert harness.load_module("layer_metrics",
                               "plan_barriers.lib").read(m) == barriers
    assert harness.load_module("layer_metrics",
                               "channel_terms.lib").read(m) == terms


def test_the_new_entries_and_only_appended_names(bench):
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == ("density15.noise", "density15-noise", "noise", 1)
    config = bench["configs"][-1]
    assert config["reduced"] == ["num_qubits", "devices", "depth"]
    assert len(config["source"]) <= 200
    new = [m["name"] for m in bench["per_layer"][-2:]]
    assert new == ["plan_barriers.lib", "channel_terms.lib"]
    for m in bench["per_layer"]:
        if "density14.block" in m.get("workloads", []):
            assert "density15.noise" in m["workloads"], m["name"]


def test_the_rehearsal_straddles_its_tile_and_its_control_fails(bench):
    """7 qubits at a 2^13 tile: the pair (5, 6) has its columns on bits 12
    and 13, so the plan holds a run at a narrowed tile; fused runs alone,
    every relabeling folded, every amplitude compared."""
    rc, last, out = run_child(["benchmark/run.py", "--workload",
                               "density15.noise", "--seed", str(SEED),
                               "--seconds", "1", "--trace", "1",
                               "--rehearse"])
    assert rc == 0 and last["correct"] is True, out[-3000:]
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    assert metrics["plan_barriers.lib"] == 0
    assert metrics["channel_terms.lib"] == 22      # channels of its tape
    assert metrics["unfolded_swaps.lib"] == 0
    assert metrics["inplace_runs.lib"] >= 3
    assert set(last["checks"]) >= {"err_max", "err_l2", "trace_err",
                                   "drift_per_application"}
    assert "16384 amplitudes compared" in out
    rc, _, out = run_child(["benchmark/control_density.py", "--workload",
                            "density15.noise", "--seeds", "3,4",
                            "--seconds", "0.5", "--control-seeds", "1",
                            "--rehearse"])
    assert rc == 0, out[-3000:]
    rows = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "density15-noise.json")) as f:
        limits = json.load(f)["check"]["limits"]
    assert len(rows) == 2 and "control" in rows[0] and "control" not in rows[1]
    for row in rows:
        assert all(row["sound"][k] <= limits[k] for k in limits), row
    assert all(rows[0]["control"][k] > 10 * limits[k]
               for k in ("err_max", "err_l2")), rows[0]
