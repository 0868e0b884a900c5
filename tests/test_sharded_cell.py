"""The four-chip cell's path at CPU sizes (benchmark cell ``sv31x4.block``):
the ``random_layers`` tape planned for shards and run on a mesh of the root
conftest's virtual devices against the benchmark's numpy complex128 replay,
and the counters that tell a collective relabeling from a shard-local one.
(The benchmark's own files -- its sharded seed state, its device-side
reference -- are tested in ``benchmark/tests``.)"""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax

import quest_tpu as qt
from quest_tpu import environment, fusion, planner, telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.environment import AMP_AXIS

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules, imported as its own files import each other
    (``benchmark/`` on the path for the module's tests only)."""
    sys.path.insert(0, BENCH)
    try:
        import reference
        import states
        import states_sharded

        spec = importlib.util.spec_from_file_location(
            "random_layers", os.path.join(BENCH, "circuits",
                                          "random_layers.py"))
        layers = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(layers)
        yield {"reference": reference, "states": states,
               "sharded": states_sharded, "layers": layers}
    finally:
        sys.path.remove(BENCH)


def _env(devices: int):
    if len(jax.devices()) < devices:
        pytest.skip(f"needs {devices} devices")
    return qt.createQuESTEnv(jax.devices()[:devices])


def _planned(bench, n, depth, devices):
    """(circuit's args, the fused circuit, its plan, local qubits a shard)."""
    args = dict(num_qubits=n, depth=depth, circuit_seed=2026)
    circ = Circuit(n)
    bench["layers"].build(circ, **args)
    kw = {"shard_devices": devices} if devices > 1 else {}
    fused = circ.fused(max_qubits=5, pallas=True, **kw)
    return (args, fused, fusion.plan_from_tape(fused._tape),
            n - (devices.bit_length() - 1))


def _seeded_register(bench, n, env):
    """A register of ``env`` holding the benchmark's seeded Gaussian state
    (float32 values in the register's own precision), and that state."""
    q = qt.createQureg(n, env)
    planes = bench["sharded"].statevector_planes(SEED, n, env.mesh, AMP_AXIS)
    q.put(jax.device_put(planes.astype(q.amps.dtype), q.amps.sharding))
    return q, bench["states"].to_complex(planes)


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("n", [12, 13, 14])
def test_sharded_plan_on_four_devices_matches_the_numpy_replay(bench, n,
                                                               depth):
    env = _env(4)
    args, fused, _, _ = _planned(bench, n, depth, 4)
    q, psi0 = _seeded_register(bench, n, env)
    telemetry.reset()
    fused.run(q)
    tape = bench["reference"].Tape()
    bench["layers"].build(tape, **args)
    want = bench["reference"].run_statevector(psi0, tape.ops)
    got = np.asarray(q.amps)
    np.testing.assert_allclose(got[0] + 1j * got[1], want, atol=1e-12)
    assert telemetry.counter_total("engine_fallback_total") == 0
    assert ({s.data.shape for s in q.amps.addressable_shards}
            == {(2, (1 << n) // 4)})


@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("devices", [1, 2, 4])
@pytest.mark.parametrize("n", [12, 14])
def test_counters_count_what_the_plan_says(bench, n, devices, depth):
    """More than zero collective swaps when a relabeling reaches a sharded
    qubit, none on one device; every run per shard; no fallback; and the
    plan's event carries the same numbers."""
    env = _env(devices)
    telemetry.reset()
    _, fused, plan, local = _planned(bench, n, depth, devices)
    runs = sum(isinstance(i, planner.PallasRun) for i in plan.items)
    q, _ = _seeded_register(bench, n, env)
    fused.run(q)
    swaps = telemetry.counter_total("fusion_collective_swaps_total")
    sharded = telemetry.counter_total("fusion_sharded_runs_total")
    assert telemetry.counter_total("engine_fallback_total") == 0
    if devices == 1:
        assert swaps == 0 and sharded == 0
        return
    stats = planner.transpose_stats(plan, local)
    assert swaps == stats["collective_transposes"] > 0
    assert sharded == runs > 0
    event = [e for e in telemetry.events() if e.get("name") == "fusion.plan"
             and e.get("mode") == "pallas_sharded"][-1]
    assert event["collective_swaps"] == swaps
    assert event["sharded_runs"] == runs


@pytest.mark.parametrize("devices,hi,collective", [
    (4, 8, 1), (4, 6, 0), (4, 7, 0), (2, 8, 0), (2, 9, 1), (1, 9, 0)])
def test_a_relabeling_is_collective_only_where_it_reaches_a_sharded_qubit(
        devices, hi, collective):
    """12 qubits: over 4 devices qubits 10 and 11 are sharded, over 2 qubit
    11, on one none. The block [hi, hi + 3) swapped with [3, 6) reaches
    them from hi = 8, from hi = 9, never."""
    env = _env(devices)
    q = qt.createQureg(12, env)
    qt.initDebugState(q)
    before = np.asarray(q.amps)
    telemetry.reset()
    fusion._apply_frame_swap(q, planner.FrameSwap(6, 3, hi))
    assert telemetry.counter_value("pallas_pass_total",
                                   kind="frame_swap") == 1
    assert (telemetry.counter_total("fusion_collective_swaps_total")
            == collective)
    fusion._apply_frame_swap(q, planner.FrameSwap(6, 3, hi))   # its own inverse
    np.testing.assert_array_equal(np.asarray(q.amps), before)


# -- the collective relabeling, per shard on the rows view (PR 40) ----------

#: state qubits of the two register shapes: a 13-qubit state-vector and a
#: 7-qubit density matrix (flattened, 14). The tile leaves a grid block of
#: three bits, all of them sharded over 8 devices, two over 4, one over 2
_SHAPES = {"sv": 13, "dm": 14}
_GRID = 3
#: (k, the block's offset above the tile): every block of the grid
_BLOCKS = [(1, 0), (1, 1), (1, 2), (2, 0), (2, 1), (3, 0)]


def _local(nsv: int, devices: int) -> int:
    return nsv - (devices.bit_length() - 1)


def _swap_case(kind: str, k: int, off: int):
    """(state qubits, tile bits, lo2) of a block of ``_BLOCKS``."""
    nsv = _SHAPES[kind]
    return nsv, nsv - _GRID, nsv - _GRID + off


@pytest.mark.parametrize("kind", ["sv", "dm"])
@pytest.mark.parametrize("k,off", _BLOCKS)
@pytest.mark.parametrize("devices", [2, 4, 8])
def test_a_frame_swap_of_a_sharded_register_is_swap_bit_blocks_to_the_bit(
        devices, k, off, kind):
    """Every block of the grid, on a state-vector and on a density
    register over 2, 4 and 8 devices: blocks that reach one, some and all
    of the sharded qubits run per shard around one stated all-to-all,
    shard-local ones as the whole-array transpose, and either way the
    register holds what ``swap_bit_blocks`` makes of the gathered array --
    a relabeling moves amplitudes and rounds nothing."""
    from quest_tpu.ops.pallas_gates import swap_bit_blocks

    env = _env(devices)
    nsv, tile_bits, lo2 = _swap_case(kind, k, off)
    make = qt.createQureg if kind == "sv" else qt.createDensityQureg
    q = make(nsv if kind == "sv" else nsv // 2, env)
    qt.initDebugState(q)
    before = np.asarray(q.amps)
    want = np.asarray(swap_bit_blocks(jax.numpy.asarray(before), n=nsv,
                                      lo1=tile_bits - k, lo2=lo2, k=k))
    collective = lo2 + k > _local(nsv, devices)
    mesh = fusion._swap_mesh(q, tile_bits - k, lo2, k)
    assert (mesh is q.amps.sharding.mesh) if collective else (mesh is None)
    telemetry.reset()
    fusion._apply_frame_swap(q, planner.FrameSwap(tile_bits, k, lo2))
    np.testing.assert_array_equal(np.asarray(q.amps), want)
    assert q.amps.sharding.is_equivalent_to(env.sharding(1 << nsv), 2)
    assert ({s.data.shape for s in q.amps.addressable_shards}
            == {(2, (1 << nsv) // devices)})
    for series in ("fusion_collective_swaps_total",
                   "fusion_per_shard_swaps_total"):
        assert telemetry.counter_total(series) == collective
    assert telemetry.counter_value("pallas_pass_total",
                                   kind="frame_swap") == 1


_COLLECTIVE = [(d, kind, k, off) for d in (2, 4, 8) for kind in _SHAPES
               for k, off in _BLOCKS
               if _swap_case(kind, k, off)[2] + k
               > _local(_SHAPES[kind], d)]


@pytest.mark.parametrize("planes", [2, 4])
@pytest.mark.parametrize("devices,kind,k,off", _COLLECTIVE)
def test_the_per_shard_form_carries_any_plane_count(devices, kind, k, off,
                                                    planes):
    """The per-shard form itself on a (P, 2^n) array, P = 2 and the
    double-float layout's 4, inside a jitted program as a replay holds it.
    The low block stands as high as the shard lets it (next under the
    moved block, or at the shard's top), so that what moves whole below it
    is a row group of more than one row wherever the block starts above
    the tile."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from quest_tpu.ops.pallas_gates import swap_bit_blocks

    mesh = _env(devices).mesh
    nsv, _, lo2 = _swap_case(kind, k, off)
    lo1 = min(lo2, _local(nsv, devices)) - k
    x = np.arange(planes << nsv, dtype=np.float32).reshape(planes, -1)
    want = np.asarray(swap_bit_blocks(jax.numpy.asarray(x), n=nsv, lo1=lo1,
                                      lo2=lo2, k=k))
    sharding = NamedSharding(mesh, P(None, AMP_AXIS))
    swap = fusion._swap_per_shard(mesh, nsv, lo1, lo2, k)
    got = jax.jit(lambda a: swap(a + 0.0))(jax.device_put(x, sharding))
    np.testing.assert_array_equal(np.asarray(got), want)
    assert got.sharding.is_equivalent_to(sharding, 2)


@pytest.mark.parametrize("case,devices,lo1,lo2,k,per_shard", [
    ("collective", 4, 7, 10, 2, True),
    ("collective-traced", 4, 7, 10, 2, True),
    ("shard-local", 4, 7, 9, 1, False),
    ("one-device", 1, 7, 10, 2, False),
    ("explicit-scheduler", 4, 7, 10, 2, False),
    ("other-sharding", 4, 7, 10, 2, False),
    ("other-axis-traced", 4, 7, 10, 2, False),
    ("below-the-lanes", 4, 3, 10, 2, False),
    ("traced-no-mesh", 4, 7, 10, 2, False)])
def test_how_a_relabeling_is_stated_follows_where_the_register_lies(
        case, devices, lo1, lo2, k, per_shard):
    """``fusion._swap_mesh``, decided on registers of shapes only, as
    ``tests/test_fusion.py`` decides ``_route``: per shard where the block
    reaches a sharded qubit of a register on the canonical amps mesh;
    today's whole-array ``swap_bit_blocks`` for a shard-local block, one
    device, the explicit scheduler, any other sharding, and a block below
    the lane rows. Nothing runs and nothing is counted."""
    import contextlib

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from .helpers import shape_register

    n = 12
    mesh = _env(devices).mesh
    spec = P(None, AMP_AXIS)
    if case.startswith("other"):
        mesh = Mesh(np.array(jax.devices()[:devices]), ("other",))
        spec = P(None, "other")
    got = []

    def decide(amps):
        got.append(fusion._swap_mesh(qt.Qureg(n, False, amps, env=None),
                                     lo1, lo2, k))
        return amps

    before = telemetry.snapshot()["counters"]
    if case.endswith("traced"):
        with environment.pallas_mesh(mesh):
            jax.eval_shape(decide, shape_register(n, np.float32).amps)
    elif case == "traced-no-mesh":
        jax.eval_shape(decide, shape_register(n, np.float32).amps)
    else:
        ctx = (qt.explicit_mesh(mesh) if case == "explicit-scheduler"
               else contextlib.nullcontext())
        sharding = None if mesh is None else NamedSharding(mesh, spec)
        with ctx:
            decide(shape_register(n, np.float32, sharding).amps)
    assert telemetry.snapshot()["counters"] == before, "_swap_mesh counted"
    assert (got[0] is mesh) if per_shard else (got[0] is None)


@pytest.mark.parametrize("devices", [2, 4])
def test_a_sharded_plan_runs_its_collective_relabelings_per_shard(bench,
                                                                  devices):
    """The cell's plan at a CPU size: every relabeling that reaches a
    sharded qubit ran per shard on the rows view (``sv31x4.block``: 2 of
    2), and under the explicit scheduler none did."""
    env = _env(devices)
    telemetry.reset()
    _, fused, plan, local = _planned(bench, 14, 2, devices)
    q, _ = _seeded_register(bench, 14, env)
    fused.run(q)
    swaps = telemetry.counter_total("fusion_collective_swaps_total")
    assert swaps == planner.transpose_stats(plan, local)[
        "collective_transposes"] > 0
    assert telemetry.counter_total("fusion_per_shard_swaps_total") == swaps
    telemetry.reset()
    with qt.explicit_mesh(env.mesh):
        fused.run(q)
    assert telemetry.counter_total("fusion_collective_swaps_total") > 0
    assert telemetry.counter_total("fusion_per_shard_swaps_total") == 0
