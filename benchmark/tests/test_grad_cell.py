"""What ``ansatz20.grad-closed8`` added to the benchmark (PR 44): the cell and
its configuration as ISSUE 44 names them; the plain reference of a served
gradient (``reference_grad``): a Pauli string against its Kronecker product,
the adjoint sweep (a) against the parameter-shift rule (b) on ALL components
at 6 qubits; ``bytes_model_grad`` against a hand count at 4 qubits; the three
new readers on recorded data; the driver's rounds (the source's loop on the
closed loop's callers); the cell's rehearsal to its last line, and its
bfloat16 control, which has to fail."""

import json
import os
import threading
import time
import types

import numpy as np
import pytest

import bytes_model_grad
import reference
import reference_grad
import run as harness
from conftest import ROOT, run_child

CELL = "ansatz20.grad-closed8"
SERVED_E2E = ("request_p50_ms", "request_p95_ms", "request_rate")


@pytest.fixture(scope="module")
def config():
    return harness.load_json(ROOT, "benchmark", "configs",
                             "ansatz20-vqe-grad.json")


def ansatz(num_qubits, depth, seed):
    builder = harness.load_module("circuits", "serving_ansatz")
    names = builder.param_names(num_qubits=num_qubits, depth=depth)
    rng = np.random.default_rng(seed)
    params = dict(zip(names, rng.uniform(0, 2 * np.pi, len(names))))
    tape = reference.Tape()
    builder.build(tape, angle=params.__getitem__, num_qubits=num_qubits,
                  depth=depth)
    return tape.ops


# -- the declaration ----------------------------------------------------------

def test_the_cell_is_declared_as_the_issue_says(bench, config):
    cell = {c["name"]: c for c in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("ansatz20-vqe-grad", "grad-closed8", 1)
    assert len(cell["why"]) <= 200
    entry = {c["name"]: c for c in bench["configs"]}["ansatz20-vqe-grad"]
    assert entry["file"] == "benchmark/configs/ansatz20-vqe-grad.json"
    assert entry["reduced"] == [] and config["reduced"] == []
    assert (config["num_qubits"], config["depth"], config["state_bytes"]) == \
        (20, 4, 8 << 20)
    assert config["driver"] == "engine_grad"
    assert config["engine"] == {"max_batch": 8, "max_delay_ms": 0.5}
    assert config["circuit"] == {"builder": "serving_ansatz",
                                 "args": {"num_qubits": 20, "depth": 4}}
    assert config["rehearse"]["circuit_args"] == {"num_qubits": 10, "depth": 2}
    traffic = harness.load_json(ROOT, "benchmark", "traffic",
                                "grad-closed8.json")
    assert {k: traffic[k] for k in ("loop", "clients", "inputs_per_client",
                                    "trace_slice_s")} == \
        {"loop": "closed", "clients": 8, "inputs_per_client": 32,
         "trace_slice_s": 4.0}
    assert (config["check"]["requests"],
            config["check"]["shift_components"]) == (4, 8)


def test_the_cell_reports_what_it_is_listed_for(bench):
    e2e = {m["name"] for m in bench["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert "setup_s" in e2e and e2e & set(SERVED_E2E)
    assert e2e <= set(SERVED_E2E) | {"setup_s"}
    layer = {m["name"]: m for m in bench["per_layer"]
             if CELL in m.get("workloads", [CELL])}
    # every per-layer metric of the cell moves an end-to-end metric it reports
    assert all(m["moves"] in e2e for m in layer.values())
    for name in ("sweep_entries.grad", "dispatches_per_batch.grad",
                 "grad_sweep_roofline.grad"):
        assert layer[name]["workloads"] == [CELL]
        assert layer[name]["layer"] == "gradients"
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "layer_metrics",
                                           name + ".py"))
    assert layer["grad_sweep_roofline.grad"]["unit"] == "%"


def test_the_hamiltonian_is_the_source_s_draw(config):
    # bench.py:1921-1923: codes first, then the coefficients
    rng = np.random.RandomState(20)
    codes = rng.randint(0, 4, size=(6, 20))
    coeffs = rng.normal(size=6)
    got_codes, got_coeffs = reference_grad.hamiltonian(config, 20)
    assert got_codes == codes.tolist() and got_coeffs == coeffs.tolist()
    # a rehearsal keeps each string's first columns
    cut, same = reference_grad.hamiltonian(config, 10)
    assert cut == codes[:, :10].tolist() and same == got_coeffs
    with open(os.path.join(ROOT, "bench.py")) as f:
        source = f.read()
    assert "rng = np.random.RandomState(20)" in source
    assert "codes = rng.randint(0, 4, size=(6, n))" in source


# -- the reference --------------------------------------------------------------

PAULIS = [np.eye(2), reference._X, reference._Y, reference._Z]


@pytest.mark.parametrize("row", [(1, 0, 0, 0), (2, 0, 0, 0), (0, 3, 0, 0),
                                 (2, 1, 3, 2), (3, 2, 2, 1), (0, 0, 0, 0)])
def test_a_pauli_string_is_its_kronecker_product(row):
    rng = np.random.default_rng(5)
    psi = rng.normal(size=16) + 1j * rng.normal(size=16)
    full = np.eye(1)
    for p in row:                       # qubit 0 least significant
        full = np.kron(PAULIS[p], full)
    np.testing.assert_allclose(reference_grad.apply_pauli(psi, row),
                               full @ psi, rtol=0, atol=1e-15)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_the_sweep_is_the_shift_rule_on_every_component_at_6_qubits(depth):
    n = 6
    ops = ansatz(n, depth, seed=depth)
    rng = np.random.RandomState(20)
    codes = rng.randint(0, 4, size=(6, n)).tolist()
    coeffs = rng.normal(size=6).tolist()
    value, grads = reference_grad.gradient(ops, codes, coeffs)
    assert len(grads) == 2 * n * depth
    assert abs(value - reference_grad.energy(ops, codes, coeffs)) < 1e-14
    # both exact, complex128 on both sides: what parts them is rounding
    every = list(range(len(grads)))
    np.testing.assert_allclose(
        grads, reference_grad.shift(ops, codes, coeffs, every),
        rtol=0, atol=1e-13)
    # ... and the order the components are asked for does not matter
    some = [7, 0, 2 * n * depth - 1, 3]
    np.testing.assert_allclose(
        reference_grad.shift(ops, codes, coeffs, some), grads[some],
        rtol=0, atol=1e-13)
    # ... and the rule is the derivative: central differences, h = 1e-5
    entries = reference_grad.parameter_entries(ops)
    for k in (0, 5, len(grads) - 1):
        name, (q, theta) = ops[entries[k]]
        e = []
        for h in (1e-5, -1e-5):
            moved = list(ops)
            moved[entries[k]] = (name, (q, theta + h))
            e.append(reference_grad.energy(moved, codes, coeffs))
        assert abs((e[0] - e[1]) / 2e-5 - grads[k]) < 1e-8


def test_the_shift_picks_hold_a_z_and_an_x_of_every_layer():
    ops = ansatz(20, 4, seed=1)
    entries = reference_grad.parameter_entries(ops)
    for seed in range(20):
        picks = reference_grad.shift_picks(np.random.default_rng([seed, 11]),
                                           ops, 8)
        assert len(set(picks)) == 8 and picks == sorted(picks)
        kinds = {(k // 40, ops[entries[k]][0]) for k in picks}
        assert kinds == {(layer, kind) for layer in range(4)
                         for kind in ("rotateZ", "rotateX")}
    # fewer than a pair a layer, and more than the tape has
    assert len(reference_grad.shift_picks(np.random.default_rng(1), ops, 3)) == 3
    small = ansatz(3, 1, seed=1)
    assert reference_grad.shift_picks(np.random.default_rng(1), small, 8) == \
        list(range(6))


def test_the_control_s_rounding_reaches_every_number(config):
    import control

    ops = ansatz(6, 2, seed=3)
    codes, coeffs = reference_grad.hamiltonian(config, 6)
    e, g = reference_grad.gradient(ops, codes, coeffs)
    le, lg = reference_grad.gradient(ops, codes, coeffs,
                                     lower=control.bfloat16)
    # bfloat16 keeps 8 bits: what it returns is representable in it
    assert le == control.bfloat16(le) and np.all(lg == control.bfloat16(lg))
    assert abs(le - e) > 1e-4 and np.max(np.abs(lg - g)) > 1e-4


# -- the count of applications --------------------------------------------------

def test_bytes_model_against_a_hand_count_at_4_qubits():
    # serving_ansatz(4, 2): layer 0: 8 rotations, CNOT(0,1), CNOT(2,3), CZ(0,3);
    # layer 1: 8 rotations, CNOT(1,2), CZ(0,3): 21 entries, 16 parameters, the
    # first entry a parameter
    ops = ansatz(4, 2, seed=0)
    assert len(ops) == 21
    assert bytes_model_grad.applications(ops, terms=6) == {
        "forward": 21, "hamiltonian": 6, "backward": 42, "bracket": 16}
    assert bytes_model_grad.gradient_bytes(ops, 6, state_bytes=128) == \
        85 * 2 * 128
    # entries before the first parameter are the initial state: not undone
    led = [("hadamard", (0,)), ("hadamard", (1,))] + ops
    assert bytes_model_grad.applications(led, terms=1) == {
        "forward": 23, "hamiltonian": 1, "backward": 42, "bracket": 16}
    # the cell's own tape
    assert bytes_model_grad.applications(ansatz(20, 4, seed=0), 6) == {
        "forward": 202, "hamiltonian": 6, "backward": 404, "bracket": 160}


# -- the readers, on recorded data -----------------------------------------------

DISPATCH = "device_dispatch_total{route=grad_request}"
TRACES = "engine_trace_total{kind=param_replay}"


def snap(counters=None, batches=(0, 0)):
    return {"counters": dict(counters or {}), "gauges": {}, "spans": {},
            "histograms": {"engine_batch_size": {"count": batches[0],
                                                 "sum": batches[1]}}}


def reader(name):
    return harness.load_module("layer_metrics", name).read


def test_sweep_entries_is_the_four_series_a_trace():
    series = {"grad_sweep_entries_total{sweep=hamiltonian}": 6,
              "grad_sweep_entries_total{sweep=backward_phi}": 202,
              "grad_sweep_entries_total{sweep=backward_lambda}": 202,
              "grad_sweep_entries_total{sweep=bracket}": 160}
    read = reader("sweep_entries.grad")
    assert read({"after": snap({**series, TRACES: 1})}) == 570
    # a process that traced the program twice counted twice
    twice = {k: 2 * v for k, v in series.items()}
    assert read({"after": snap({**twice, TRACES: 2})}) == 570
    # a tree without the counter: nothing, not 0
    assert read({"after": snap({TRACES: 1, DISPATCH: 9})}) is None


def test_dispatches_per_batch_is_growth_over_growth():
    read = reader("dispatches_per_batch.grad")
    before = snap({DISPATCH: 4}, batches=(4, 18))
    assert read({"before": before,
                 "after": snap({DISPATCH: 19}, batches=(19, 78))}) == 1.0
    assert read({"before": before,
                 "after": snap({DISPATCH: 34}, batches=(19, 78))}) == 2.0
    assert read({"before": before, "after": before}) is None


def trace(t0, device_ms, route=None, error=None):
    return {"t0": t0, "error": error, "dur_ms": device_ms + 5.0,
            "labels": {"kind": "engine", **({"route": route} if route else {})},
            "phases_ms": {"device": device_ms, "queue_wait": 5.0}}


def test_the_roofline_share_is_floor_over_the_device_phase():
    read = reader("grad_sweep_roofline.grad")
    ops = ansatz(20, 4, seed=0)
    grad_bytes = bytes_model_grad.gradient_bytes(ops, 6, 8 << 20)
    assert grad_bytes == 772 * 2 * (8 << 20)
    m = {"peaks": {"hbm_bytes_per_s": 819e9},
         "shapes": {"state_bytes": 8 << 20, "grad_bytes": grad_bytes},
         "window": types.SimpleNamespace(wall0=100.0),
         "before": snap(batches=(3, 24)), "after": snap(batches=(11, 88)),
         "engine_traces": [trace(99.0, 1.0, "grad_request"),       # set-up's
                           trace(101.0, 1366.0, "grad_request"),
                           trace(102.0, 1366.0, "grad_request"),
                           trace(103.0, 1400.0, "grad_request"),
                           trace(104.0, 15.0),                     # a replay
                           trace(105.0, 9000.0, "grad_request", "Boom")]}
    floor_s = grad_bytes * 8 / 819e9
    assert read(m) == pytest.approx(100.0 * floor_s / 1.366)
    assert 9.0 < read(m) < 9.5
    # half-empty batches carry half the lanes through the same program
    m["after"] = snap(batches=(11, 56))
    assert read(m) == pytest.approx(50.0 * floor_s / 1.366)
    # a tree that labels no trace (the parent): nothing, as sweep_entries.grad
    assert read({**m, "engine_traces": [trace(101.0, 1366.0),
                                        trace(102.0, 1366.0)]}) is None
    # an untraced run, a rehearsal without peaks, another driver's shapes
    assert read({**m, "engine_traces": []}) is None
    assert read({**m, "peaks": None}) is None
    assert read({**m, "shapes": {"state_bytes": 8 << 20}}) is None


# -- the driver's rounds ------------------------------------------------------

def lanes_through(rounds, calls):
    """Each ``(lane, angles)`` of ``calls`` from a thread of its own; what
    each got back, or what it raised, by lane."""
    got = {}

    def lane(c, angles):
        try:
            got[c] = rounds.submit(c, angles)
        except Exception as exc:
            got[c] = exc

    threads = [threading.Thread(target=lane, args=call) for call in calls]
    for t in threads:
        t.start()
        time.sleep(0.01)       # the lanes are ready one after another
    for t in threads:
        t.join(10)
    return got


def test_a_round_is_sent_whole_in_lane_order_from_one_thread():
    engine_grad = harness.load_module("drivers", "engine_grad")
    sent = []

    def send(angles):
        sent.append((angles, threading.get_ident()))
        return f"future of {angles}"

    rounds = engine_grad.Rounds(4, send)
    for step in range(3):
        del sent[:]
        got = lanes_through(rounds, [(c, (step, c)) for c in (2, 0, 3, 1)])
        assert [a for a, _ in sent] == [(step, c) for c in range(4)]
        assert len({thread for _, thread in sent}) == 1
        assert got == {c: f"future of {(step, c)}" for c in range(4)}
    assert not rounds.ready and not rounds.sent


def test_a_send_that_fails_fails_its_own_lane_alone():
    engine_grad = harness.load_module("drivers", "engine_grad")

    def send(angles):
        if angles == 1:
            raise RuntimeError("queue full")
        return angles

    got = lanes_through(engine_grad.Rounds(3, send), [(0, 0), (1, 1), (2, 2)])
    assert got[0] == 0 and got[2] == 2
    assert isinstance(got[1], RuntimeError)


def test_a_round_whose_lanes_have_stopped_is_sent_as_it_stands(monkeypatch):
    engine_grad = harness.load_module("drivers", "engine_grad")
    monkeypatch.setattr(engine_grad, "ROUND_PATIENCE_S", 0.2)
    sent = []
    rounds = engine_grad.Rounds(8, lambda a: sent.append(a) or a)
    t0 = time.perf_counter()
    got = lanes_through(rounds, [(5, "e"), (1, "a")])
    assert 0.2 <= time.perf_counter() - t0 < 5.0
    assert sent == ["a", "e"] and got == {1: "a", 5: "e"}
    # longer than a batch of the cell takes, so a lane out of step waits
    # for the others' next round
    monkeypatch.undo()
    assert engine_grad.ROUND_PATIENCE_S > 1.5


# -- the cell, rehearsed -----------------------------------------------------------

def test_the_rehearsal_runs_to_its_last_line_and_its_control_fails(config):
    rc, last, out = run_child(["benchmark/run.py", "--workload", CELL,
                               "--seed", str(2 ** 31 + 44), "--seconds", "1.0",
                               "--trace", "1", "--rehearse"])
    assert rc == 0, out[-3000:]
    assert last["correct"] is True and last["rehearsed"] is True
    assert last["failed"] == 0 and last["attempted"] >= 8
    checks = last["checks"]
    for name, limit in config["check"]["limits"].items():
        assert checks[name]["limit"] == limit
        assert 0.0 < checks[name]["value"] <= limit
    for name in ("requests_left_unchecked", "retraces_in_window",
                 "dispatches_not_one_a_launch", "engine_fallback_total"):
        assert checks[name] == {"value": 0.0, "limit": 0.0}
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    # 10 qubits, depth 2: 6 terms, 2 x 51 entries undone, 40 brackets
    assert metrics["sweep_entries.grad"] == 6 + 2 * 51 + 40
    assert metrics["dispatches_per_batch.grad"] == 1.0
    assert 1.0 <= metrics["batch_width.serve"] <= 8.0
    # no time is written by a rehearsal
    assert "grad_sweep_roofline.grad" not in metrics

    rc, _, out = run_child(["benchmark/control_grad.py", "--workload", CELL,
                            "--seeds", "3,4", "--seconds", "0.5",
                            "--rehearse"])
    assert rc == 0, out[-3000:]
    rows = [json.loads(ln) for ln in out.splitlines()
            if ln.startswith('{"seed"')]
    assert len(rows) == 2
    limits = config["check"]["limits"]
    for row in rows:
        assert all(row["sound"][k] <= v for k, v in limits.items())
        assert any(row["control"][k] >= 10 * v for k, v in limits.items())
