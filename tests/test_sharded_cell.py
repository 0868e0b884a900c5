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
from quest_tpu import fusion, telemetry
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
    runs = sum(isinstance(i, fusion.PallasRun) for i in plan.items)
    q, _ = _seeded_register(bench, n, env)
    fused.run(q)
    swaps = telemetry.counter_total("fusion_collective_swaps_total")
    sharded = telemetry.counter_total("fusion_sharded_runs_total")
    assert telemetry.counter_total("engine_fallback_total") == 0
    if devices == 1:
        assert swaps == 0 and sharded == 0
        return
    stats = fusion.transpose_stats(plan, local)
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
    fusion._apply_frame_swap(q, fusion.FrameSwap(6, 3, hi))
    assert telemetry.counter_value("pallas_pass_total",
                                   kind="frame_swap") == 1
    assert (telemetry.counter_total("fusion_collective_swaps_total")
            == collective)
    fusion._apply_frame_swap(q, fusion.FrameSwap(6, 3, hi))   # its own inverse
    np.testing.assert_array_equal(np.asarray(q.amps), before)
