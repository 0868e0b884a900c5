"""Gradients as served traffic (PR 44): ``Engine.submit_grad`` against the
benchmark's plain reference (``benchmark/reference_grad.py``, numpy complex128,
nothing of the program), and the records the gradient program leaves.

Contracts under test, each at 6 and 8 qubits (``serving_ansatz`` depth 2, six
random Pauli strings, seeded angles), in float32 and, under x64, float64:

- eight ``submit_grad`` requests coalesced into ONE batch agree with the
  reference's adjoint sweep (a) on every component and with its exact
  parameter-shift rule (b) on every component;
- a request served alone equals, bit for bit, the same request served
  coalesced (both ride a lane of the one padded batch program);
- ``grad_sweep_entries_total{sweep}`` reads what a hand count of the tape's
  dense plan and the Hamiltonian gives (an item undone on each register, a
  contraction a block with Params; PR 45: the gate walk read the tape's
  entries and its angles), counted once a trace: ten warm steps add nothing,
  retrace nothing, and dispatch once a batch;
- the ``grad.plan`` event's fields and the ``grad.plan_backward`` span;
- the ``route`` label on a gradient request's trace and on the companion's
  ``program.first_call`` record, and nothing of it on a replay request's.
"""

import os
import sys

import numpy as np
import pytest

import jax

import quest_tpu as qt
from quest_tpu import telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.engine import Engine, P

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import reference  # noqa: E402
import reference_grad  # noqa: E402
from circuits import serving_ansatz  # noqa: E402

ENV1 = qt.createQuESTEnv(jax.devices()[:1])
DEPTH, TERMS, LANES = 2, 6, 8

#: float32: a gradient is some 5 n gates of rounding at 6e-8 on numbers of
#: order 1, read here at 1-3e-7, so 1e-5 is thirty times that and a hundred
#: times under what bfloat16 gives (3e-3); float64: the same walk at 1e-16
#: reads 1e-14 to 1e-13, and 1e-10 is a million times under float32's error
ATOL = {1: 1e-5, 2: 1e-10}

#: serving_ansatz(n, 2) by hand: 4 n rotations, controlledNot on the even
#: pairs then on the odd pairs, one controlledPhaseFlip a layer
CONCRETE = {6: 3 + 2 + 2, 8: 4 + 3 + 2}

#: the dense plan of that tape at ``DENSE_WINDOW_QUBITS`` 7 by hand, (blocks,
#: blocks with Params). 6 qubits: one window holds the whole tape. 8 qubits:
#: a layer's rotations fall into [0-6] (14 angles) and [7] (2); the bricks
#: of layer 0 into [0-5] and [6-7], then its phase flip, a diagonal on
#: (0, 7); layer 1's [7] takes the odd bricks with it as [1-7], and its
#: phase flip closes: 3 + 2 static blocks, 4 with Params
BLOCKS = {6: (1, 1), 8: (8, 4)}

CASES = [pytest.param(n, code, id=f"{n}q-f{32 * code}")
         for n in (6, 8) for code in (1, 2)]

SWEEPS = ("hamiltonian", "backward_phi", "backward_lambda", "bracket")


def sweep_counts() -> dict:
    return {s: telemetry.counter_value("grad_sweep_entries_total", sweep=s)
            for s in SWEEPS}


def counts() -> dict:
    return {"dispatches": telemetry.counter_value("device_dispatch_total",
                                                  route="grad_request"),
            "batches": telemetry.counter_total("engine_batches_total"),
            "retraces": telemetry.counter_value("engine_trace_total",
                                                kind="param_replay")}


def grown(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


class Served:
    """One Engine over the ansatz and its observable, warmed, with what its
    build and warm-up counted."""

    def __init__(self, n, code):
        self.n, self.code = n, code
        self.names = serving_ansatz.param_names(num_qubits=n, depth=DEPTH)
        rng = np.random.RandomState(20)
        self.codes = rng.randint(0, 4, size=(TERMS, n)).tolist()
        self.coeffs = rng.normal(size=TERMS).tolist()
        circ = Circuit(n)
        serving_ansatz.build(circ, angle=P, num_qubits=n, depth=DEPTH)
        sweeps, events = sweep_counts(), len(telemetry.events())
        spans = telemetry.snapshot()["spans"].get("grad.plan_backward",
                                                  {"count": 0})["count"]
        # a window long enough that eight submits in a row make one batch
        self.engine = Engine(circ, ENV1, precision_code=code,
                             hamiltonian=(np.asarray(self.codes, np.int32),
                                          np.asarray(self.coeffs)),
                             max_batch=LANES, max_delay_ms=500.0)
        self.engine.warmup_grad()
        self.sweeps = grown(sweeps, sweep_counts())
        self.events = telemetry.events()[events:]
        self.plan_spans = telemetry.snapshot()["spans"][
            "grad.plan_backward"]["count"] - spans

    def angles(self, seed, count=LANES) -> list:
        rng = np.random.default_rng([seed, self.n, self.code])
        return [dict(zip(self.names, map(float, row)))
                for row in rng.uniform(0, 2 * np.pi,
                                       size=(count, len(self.names)))]

    def tape(self, params) -> list:
        tape = reference.Tape()
        serving_ansatz.build(tape, angle=params.__getitem__,
                             num_qubits=self.n, depth=DEPTH)
        return tape.ops

    def coalesced(self, sets) -> list:
        """``sets`` through ``submit_grad`` as ONE batch: ``[(E, g)]``, ``g``
        in ``param_names`` order."""
        before = counts()
        futs = [self.engine.submit_grad(p) for p in sets]
        replies = [f.result(timeout=300) for f in futs]
        assert grown(before, counts()) == {"dispatches": 1, "batches": 1,
                                           "retraces": 0}
        return [(np.asarray(v), np.array([np.asarray(g[name])
                                          for name in self.names]))
                for v, g in replies]


@pytest.fixture(scope="module")
def served():
    made = {}

    def get(n, code):
        if (n, code) not in made:
            made[(n, code)] = Served(n, code)
        return made[(n, code)]

    yield get
    for s in made.values():
        s.engine.close()


@pytest.mark.parametrize("n, code", CASES)
def test_a_coalesced_batch_agrees_with_the_reference_on_every_component(
        served, n, code):
    s = served(n, code)
    sets = s.angles(seed=1)
    every = list(range(len(s.names)))
    for params, (value, grads) in zip(sets, s.coalesced(sets)):
        ops = s.tape(params)
        want_e, want_g = reference_grad.gradient(ops, s.codes, s.coeffs)
        shifted = reference_grad.shift(ops, s.codes, s.coeffs, every)
        # the reference's two ways agree to rounding in complex128
        np.testing.assert_allclose(want_g, shifted, rtol=0, atol=1e-13)
        assert abs(float(value) - want_e) <= ATOL[code]
        np.testing.assert_allclose(grads, want_g, rtol=0, atol=ATOL[code])
        np.testing.assert_allclose(grads, shifted, rtol=0, atol=ATOL[code])


@pytest.mark.parametrize("n, code", CASES)
def test_a_request_served_alone_is_the_same_request_served_coalesced(
        served, n, code):
    s = served(n, code)
    sets = s.angles(seed=2)
    together = s.coalesced(sets)
    for lane in (0, 5):
        [(value, grads)] = s.coalesced([sets[lane]])
        np.testing.assert_array_equal(value, together[lane][0])
        np.testing.assert_array_equal(grads, together[lane][1])


@pytest.mark.parametrize("n, code", CASES)
def test_the_sweep_counter_reads_the_hand_count_once_a_trace(served, n, code):
    s = served(n, code)
    blocks, with_params = BLOCKS[n]
    # one trace of the one batch program: build and warm-up counted once
    assert s.sweeps == {"hamiltonian": TERMS, "backward_phi": blocks,
                        "backward_lambda": blocks, "bracket": with_params}
    before, sweeps = counts(), sweep_counts()
    base = s.angles(seed=3, count=1)[0]
    for step in range(10):
        p = {k: v + 0.01 * step for k, v in base.items()}
        s.engine.submit_grad(p).result(timeout=300)
    # ten warm steps: nothing traced, so nothing counted; one dispatch each
    assert sweep_counts() == sweeps
    assert grown(before, counts()) == {"dispatches": 10, "batches": 10,
                                       "retraces": 0}


@pytest.mark.parametrize("n, code", CASES)
def test_the_grad_plan_event_and_the_plan_s_span(served, n, code):
    s = served(n, code)
    [plan] = [e for e in s.events if e["name"] == "grad.plan"]
    fields = {k: plan[k] for k in ("num_qubits", "entries", "slots", "terms",
                                   "param_entries", "concrete_events",
                                   "first_slot", "blocks", "param_blocks")}
    assert fields == {"num_qubits": n, "entries": 4 * n + CONCRETE[n],
                      "slots": 4 * n, "terms": TERMS, "param_entries": 4 * n,
                      "concrete_events": CONCRETE[n], "first_slot": 0,
                      "blocks": BLOCKS[n][0], "param_blocks": BLOCKS[n][1]}
    # the backward plan was built once, under its span
    assert s.plan_spans == 1
    # the companion replays the raw tape: no block in its plan
    starts = [e for e in s.events if e["name"] == "engine.start"]
    assert [e["plan_blocks"] for e in starts][-1] == 0


@pytest.mark.parametrize("n, code", CASES)
def test_a_gradient_request_s_trace_carries_its_route(served, n, code):
    s = served(n, code)
    params = s.angles(seed=4, count=1)[0]
    with telemetry.trace_policy("all"):
        seen, events = len(telemetry.traces()), len(telemetry.events())
        s.engine.submit_grad(params).result(timeout=300)
        state = s.engine.submit(params).result(timeout=300)
        jax.block_until_ready(state)
        grad_trace, replay_trace = telemetry.traces()[seen:][-2:]
    assert grad_trace["labels"] == {"kind": "engine", "route": "grad_request",
                                    "engine": grad_trace["labels"]["engine"]}
    # a replay request's trace is what it was: no route, the same phases
    assert set(replay_trace["labels"]) == {"kind", "engine"}
    assert set(replay_trace["phases_ms"]) == set(grad_trace["phases_ms"])
    assert grad_trace["error"] is None and replay_trace["error"] is None

    def first_calls(events):
        return [(e["program"].split("_sv_")[0], e["route"]) for e in events
                if e["name"] == "program.first_call"]

    # the launch that compiled the gradient program named its route; the
    # replay engine's launch (its first is here) keeps the name it had
    assert first_calls(s.events) == [("qt_engine_vmap", "grad_request")]
    assert set(first_calls(telemetry.events()[events:])) <= \
        {("qt_engine_vmap", "engine_vmap")}
