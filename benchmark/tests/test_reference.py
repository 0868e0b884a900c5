"""The benchmark's numpy reference against ``tests/oracle.py`` (dense 2^n
operators, written for the repo's own tests) on each configuration's circuit
builder at 7-12 qubits, and the generators' determinism."""

import os
import sys

import numpy as np
import pytest

from conftest import ROOT

import reference
import run as harness
import states

sys.path.insert(0, os.path.join(ROOT, "tests"))
import oracle  # noqa: E402


def _tape(builder, **args):
    tape = reference.Tape()
    harness.load_module("circuits", builder).build(tape, **args)
    return tape


def _dense_replay(ops, n, psi):
    for name, args in ops:
        t, m, ctl = reference._unitary(name, args)
        psi = oracle.apply_to_statevec(psi, n, [t], m, list(ctl))
    return psi


@pytest.mark.parametrize("n,depth", [(7, 3), (10, 2), (12, 1)])
def test_random_layers_reference_matches_oracle(n, depth):
    tape = _tape("random_layers", num_qubits=n, depth=depth, circuit_seed=2026)
    psi0 = states.to_complex(states.statevector_planes(17, n))
    got = reference.run_statevector(psi0, tape.ops, threads=3)
    assert np.max(np.abs(got - _dense_replay(tape.ops, n, psi0))) < 1e-12


def test_serving_ansatz_reference_matches_oracle():
    n, depth = 8, 2
    names = harness.load_module("circuits", "serving_ansatz").param_names(
        num_qubits=n, depth=depth)
    params = states.angle_sets(5, 0, names, 1)[0]
    tape = _tape("serving_ansatz", num_qubits=n, depth=depth,
                 angle=params.__getitem__)
    assert len(tape.ops) == depth * (2 * n + 1) + 4 + 3
    psi0 = np.zeros(1 << n, dtype=complex)
    psi0[0] = 1
    got = reference.run_statevector(psi0, tape.ops)
    assert np.max(np.abs(got - _dense_replay(tape.ops, n, psi0))) < 1e-12


def test_density_blocks_reference_matches_oracle():
    n = 7
    tape = _tape("density_channels", num_qubits=n)
    assert len(tape.ops) == 11
    active = reference.support(tape.ops)
    assert active == [0, 1, 2, 3, 4, 6]
    psi0 = states.to_complex(states.statevector_planes(23, n))
    rho = np.outer(psi0, psi0.conj())
    for name, args in tape.ops:
        u = reference._unitary(name, args)
        if u is not None:
            rho = oracle.apply_to_density(rho, n, [u[0]], u[1],
                                          controls=list(u[2]))
        else:
            targets, ks = reference._kraus(name, args)
            rho = oracle.apply_kraus_to_density(rho, n, list(targets), ks)
    # one spectator qubit: the four (row, column) patterns cover all of rho
    rows, cols = np.array([0, 0, 1, 1]), np.array([0, 1, 0, 1])
    blocks = reference.run_density_blocks(psi0, n, tape.ops, rows, cols)
    idx = reference.block_indices(n, active, rows, cols)
    want = rho.T.reshape(-1)[idx]      # flat index = col * 2^n + row
    assert np.max(np.abs(blocks - want)) < 1e-13
    assert abs(np.trace(rho) - 1) < 1e-6     # psi0 is normalised in float32


def test_same_seed_same_inputs_other_seed_other_inputs():
    big = 2**31 + 12345            # more than 32 signed bits hold
    a = np.asarray(states.statevector_planes(big, 9))
    assert np.array_equal(a, np.asarray(states.statevector_planes(big, 9)))
    assert not np.array_equal(a, np.asarray(states.statevector_planes(big + 1, 9)))
    assert abs(float((a * a).sum()) - 1) < 1e-5
    assert states.angle_sets(big, 3, ["a", "b"], 4) == \
        states.angle_sets(big, 3, ["a", "b"], 4)
    assert states.angle_sets(big, 3, ["a", "b"], 4) != \
        states.angle_sets(big, 4, ["a", "b"], 4)
    psi, rho = states.projector_planes(big, 5)
    v = states.to_complex(psi)
    r = states.to_complex(rho).reshape(32, 32).T
    assert np.max(np.abs(r - np.outer(v, v.conj()))) < 1e-7


def test_the_seed_never_reaches_a_circuit_builder(bench):
    """Every configuration fixes its circuit: the tape is the same whatever
    the run's seed, so one compiled program serves every seed."""
    for cfg in bench["configs"]:
        conf = harness.load_json(ROOT, cfg["file"])
        args = dict(conf["rehearse"]["circuit_args"])
        if conf["circuit"]["builder"] == "serving_ansatz":
            args["angle"] = lambda name: name
        one, two = (_tape(conf["circuit"]["builder"], **args).ops
                    for _ in range(2))
        assert repr(one) == repr(two) and one
