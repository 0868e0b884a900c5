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
  ``program.first_call`` record, and nothing of it on a replay request's;
- a gradient batch leaves its program as ONE array (PR 46): the built
  program's outputs by ``jax.eval_shape``, ``engine_launch_results_total``
  a launch, and the replies, which are host scalars and equal bit for bit
  the reduce's own ``{"value", "grads", "slot_grads"}`` tree handed back the
  old way (every number an output of its own) from a program built outside
  the engine: coalesced, alone, and with every Param shared by two gates;
  a finalize that names no ``unpack`` keeps a device array a lane;
  ``Circuit.gradient`` returns that tree's numbers, complex slots included.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import quest_tpu as qt
from quest_tpu import telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.engine import Engine, P
from quest_tpu.params import _pack_rows, _unpack_columns, bind

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

    def __init__(self, n, code, name=str):
        self.n, self.code = n, code
        self.names = list(dict.fromkeys(map(name, serving_ansatz.param_names(
            num_qubits=n, depth=DEPTH))))
        rng = np.random.RandomState(20)
        self.codes = rng.randint(0, 4, size=(TERMS, n)).tolist()
        self.coeffs = rng.normal(size=TERMS).tolist()
        circ = Circuit(n)
        serving_ansatz.build(circ, angle=lambda a: P(name(a)), num_qubits=n,
                             depth=DEPTH)
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
        # a reply is host scalars: nothing is left on the device to fetch
        assert not any(isinstance(leaf, jax.Array)
                       for leaf in jax.tree_util.tree_leaves(replies))
        return [(np.asarray(v), np.array([np.asarray(g[name])
                                          for name in self.names]))
                for v, g in replies]

    def old_parcel(self, sets) -> list:
        """``sets`` through a batch program built HERE, outside the engine,
        the way ``Engine._execB`` built it before PR 46: the same replay,
        the reduce's own tree (``reduce.tree``), the lanes as a scan (the
        CPU's form), every number of every lane an output of its own.
        ``[(E, g)]`` as :meth:`coalesced` gives them."""
        comp = self.engine.grad_engine()
        reduce, width = comp._finalize, comp.max_batch
        inner = comp._program._replay_fn(comp._lifted)

        def program(amps, *packed):
            amps_b = jnp.broadcast_to(amps[None], (width,) + amps.shape)
            out = jax.lax.map(
                lambda av: reduce.tree(inner(av[0], av[1]), av[1]),
                (amps_b, _unpack_columns(comp._packs, packed)))
            return tuple(jax.tree_util.tree_map(lambda a: a[i], out)
                         for i in range(width))

        rows = [_pack_rows(comp._packs, bind(comp._lifted, p)) for p in sets]
        rows += [rows[-1]] * (width - len(rows))
        out = jax.jit(program)(comp.initial_amps,
                               *(np.stack(kind) for kind in zip(*rows)))
        assert len(jax.tree_util.tree_leaves(out)) == width * (
            1 + reduce.num_slots + len(self.names))
        return [(np.asarray(lane["value"]),
                 np.array([np.asarray(lane["grads"][name])
                           for name in self.names]))
                for lane in out[:len(sets)]]


@pytest.fixture(scope="module")
def served():
    made = {}

    def get(n, code, name=str):
        if (n, code, name) not in made:
            made[(n, code, name)] = Served(n, code, name)
        return made[(n, code, name)]

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


# ---------------------------------------------------------------------------
# PR 46: a gradient batch hands back one array
# ---------------------------------------------------------------------------

def launch_results() -> float:
    return telemetry.counter_total("engine_launch_results_total")


def first_layer(name: str) -> str:
    """Layer 1's angle under layer 0's name: every Param rides two gates."""
    return name.replace("1_", "0_")


@pytest.mark.parametrize("n, code", CASES)
def test_the_batch_program_hands_back_one_array_and_counts_it(served, n, code):
    s = served(n, code)
    comp = s.engine.grad_engine()
    dtype = np.dtype(comp.dtype)
    args = [jax.ShapeDtypeStruct(comp.initial_amps.shape, dtype)]
    args += [jax.ShapeDtypeStruct((LANES, len(cols)),
                                  jnp.result_type(float))
             for _, cols in comp._packs]
    out = jax.eval_shape(comp._execB(), *args)
    leaves = jax.tree_util.tree_leaves(out)
    assert len(leaves) <= LANES
    # a value, a derivative a slot, a derivative a name; a row a lane
    assert [leaf.shape for leaf in leaves] == [(LANES, 1 + 2 * len(s.names))]
    before = launch_results()
    s.coalesced(s.angles(seed=5))
    s.coalesced(s.angles(seed=5, count=3))
    assert launch_results() - before == 2 * len(leaves)


@pytest.mark.parametrize("n, code", CASES)
def test_a_coalesced_batch_is_the_reduce_s_own_tree_bit_for_bit(
        served, n, code):
    s = served(n, code)
    sets = s.angles(seed=6)
    want = s.old_parcel(sets)
    for (value, grads), (want_e, want_g) in zip(s.coalesced(sets), want):
        np.testing.assert_array_equal(value, want_e)
        np.testing.assert_array_equal(grads, want_g)
    # a request served alone rides the same row of the same array
    [(value, grads)] = s.coalesced([sets[3]])
    np.testing.assert_array_equal(value, want[3][0])
    np.testing.assert_array_equal(grads, want[3][1])


@pytest.mark.parametrize("n, code", CASES)
def test_a_shared_param_s_sum_is_made_in_the_program_bit_for_bit(
        served, n, code):
    s = served(n, code, first_layer)
    assert len(s.names) == 2 * n
    assert s.engine.grad_engine()._finalize.num_slots == 4 * n
    sets = s.angles(seed=7)
    got, want = s.coalesced(sets), s.old_parcel(sets)
    for (value, grads), (want_e, want_g) in zip(got, want):
        np.testing.assert_array_equal(value, want_e)
        np.testing.assert_array_equal(grads, want_g)
    # and the sums are the reference's: both gates' derivatives added
    params = sets[0]
    both = {f"{ab}{layer}_{q}": params[f"{ab}0_{q}"] for ab in "ab"
            for layer in range(DEPTH) for q in range(n)}
    _, slots = reference_grad.gradient(s.tape(both), s.codes, s.coeffs)
    summed = np.asarray(slots).reshape(DEPTH, -1).sum(axis=0)
    np.testing.assert_allclose(got[0][1], summed, rtol=0, atol=ATOL[code])


@pytest.mark.parametrize("n, code", CASES)
def test_a_finalize_that_names_no_unpack_keeps_an_array_a_lane(n, code):
    names = serving_ansatz.param_names(num_qubits=n, depth=DEPTH)
    circ = Circuit(n)
    serving_ansatz.build(circ, angle=P, num_qubits=n, depth=DEPTH)

    def finalize(amps):
        return {"p0": amps[0, 0] ** 2 + amps[1, 0] ** 2, "head": amps[:, :2]}

    rng = np.random.default_rng([8, n, code])
    sets = [dict(zip(names, map(float, row)))
            for row in rng.uniform(0, 2 * np.pi, size=(3, len(names)))]
    with Engine(circ, ENV1, precision_code=code, max_batch=4,
                max_delay_ms=500.0, finalize=finalize) as eng:
        before = launch_results()
        outs = [f.result(timeout=300) for f in eng.submit_many(sets)]
        # four lanes of two arrays each, as outputs of their own
        assert launch_results() - before == 4 * 2
    for out in outs:
        assert set(out) == {"p0", "head"}
        assert all(isinstance(leaf, jax.Array) for leaf in out.values())
        assert out["head"].shape == (2, 2)


@pytest.mark.parametrize("code", [1, 2])
def test_circuit_gradient_returns_the_tree_s_numbers(code):
    """``Circuit.gradient`` (and ``calcGradExpecPauliSum`` through it)
    fetches the vector and names it: the numbers of the reduce's own tree
    from one jitted program, complex slots and a shared Param among them."""
    from quest_tpu.gradients import grad_reduce

    n = 6
    dtype = np.dtype(np.float32 if code == 1 else np.float64)
    circ = Circuit(n)
    circ.hadamard(0)
    circ.rotateX(1, P("a"))
    circ.compactUnitary(2, P("alpha"), P("beta"))
    circ.controlledNot(1, 2)
    circ.rotateZ(3, P("a"))
    circ.rotateY(4, 0.3)
    circ.controlledCompactUnitary(4, 5, np.cos(0.4) * np.exp(0.2j),
                                  np.sin(0.4) * np.exp(-0.5j))
    rng = np.random.RandomState(20)
    ham = (rng.randint(0, 4, size=(TERMS, n)).tolist(),
           rng.normal(size=TERMS).tolist())
    params = {"a": 0.7, "alpha": np.cos(0.3) * np.exp(0.1j),
              "beta": np.sin(0.3) * np.exp(-0.4j)}
    amps = np.zeros((2, 1 << n), dtype)
    amps[0, 0] = 1.0
    gx = circ.gradient(ham, donate=False, dtype=dtype)
    got = gx(jnp.asarray(amps), params)
    reduce = grad_reduce(circ, ham, dtype=dtype)
    replay = circ._replay_fn(circ.lifted())
    want = jax.jit(lambda a, v: reduce.tree(replay(a, v), v))(
        jnp.asarray(amps), gx.bind(params))
    assert not any(isinstance(leaf, jax.Array)
                   for leaf in jax.tree_util.tree_leaves(got))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    assert list(got["grads"]) == ["a", "alpha", "beta"]
    assert np.iscomplexobj(got["grads"]["alpha"])
    assert not np.iscomplexobj(got["grads"]["a"])
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
