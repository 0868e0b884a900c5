"""What ``sv31x4.block`` added to the benchmark: the seed's state made shard
by shard, the plain reference on the device, the collective readers on a
synthetic two-device trace whose numbers are known exactly, the per-chip
roofline, and the cell's rehearsal and control on four virtual devices."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import reference
import reference_planes
import run as harness
import states
import states_sharded
import trace_collectives as tc
from conftest import ROOT, child_env

CELL = "sv31x4.block"
SEED = 2 ** 31 + 9


def mesh_of(devices):
    if len(jax.devices()) < devices:
        pytest.skip(f"needs {devices} devices")
    return jax.sharding.Mesh(np.asarray(jax.devices()[:devices]), ("amps",))


def test_seed_state_is_the_same_for_1_2_and_4_devices_and_normalised():
    made = [np.asarray(states_sharded.statevector_planes(
        SEED, 13, mesh_of(d), "amps")) for d in (1, 2, 4)]
    assert made[0].shape == (2, 1 << 13) and made[0].dtype == np.float32
    np.testing.assert_array_equal(made[0], made[1])
    np.testing.assert_array_equal(made[0], made[2])
    assert abs(float(np.sum(made[0].astype(np.float64) ** 2)) - 1) < 1e-6
    with pytest.raises(ValueError):
        states_sharded.statevector_planes(SEED, 3, mesh_of(1), "amps")


@pytest.fixture(params=[11, 24])
def block_bits(request, monkeypatch):
    """The reference's sweeps in blocks of 2^11 amplitudes (at 14 qubits on
    one device 8 blocks of a plane, placed along one axis of a gate's view
    or along two) and of 2^24 (the cell's: one block at these sizes)."""
    monkeypatch.setattr(reference_planes, "BLOCK_BITS", request.param)
    reference_planes._program.cache_clear()
    yield request.param
    reference_planes._program.cache_clear()


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("n", [12, 14])
def test_device_reference_against_the_numpy_complex128_replay(n, devices,
                                                              block_bits):
    """Every kind of gate the view has: targets inside a tile (matrix), above
    it (flip), on a sharded qubit (exchange), diagonal ones, and controls on
    each side of each; swept in one block and in many."""
    mesh = mesh_of(devices)
    tape = reference.Tape()
    harness.load_module("circuits", "random_layers").build(
        tape, num_qubits=n, depth=2, circuit_seed=2026)
    for target, control in ((0, n - 1), (n - 1, 0), (n - 1, n - 2), (3, 9),
                            (11, 4), (10, 11)):
        tape.controlledNot(control, target)
        tape.rotateX(target, 0.3 + target)
        tape.controlledPhaseFlip(control, target)

    def state():
        return states_sharded.statevector_planes(SEED, n, mesh, "amps")

    want = reference.run_statevector(states.to_complex(state()), tape.ops)
    given = state()
    got = reference_planes.run_statevector(given, n, tape.ops)
    assert given.is_deleted()           # in place: the input is given up
    assert all(len(p.sharding.device_set) == devices for p in got)
    assert max(reference.errors(np.asarray(got[0]), np.asarray(got[1]),
                                want)) < 1e-6
    assert reference_planes.total_probability(got) == pytest.approx(1, abs=1e-5)
    low = reference_planes.run_statevector(state(), n, tape.ops,
                                           lower=reference_planes.bfloat16)
    assert min(reference_planes.errors(low, got)) > 2e-3
    with pytest.raises(ValueError):
        reference_planes.gates_of([("mixDepolarising", (0, 0.1))])


@pytest.mark.parametrize("devices", [1, 4])
def test_errors_are_reduced_where_the_planes_live(devices):
    """``errors`` of planes on the devices reads what ``reference.errors``
    reads of the whole vectors on the host."""
    mesh, n = mesh_of(devices), 13
    want = states_sharded.statevector_planes(SEED, n, mesh, "amps")
    other = states_sharded.statevector_planes(SEED + 1, n, mesh, "amps")
    got = want + 1e-3 * other
    on_host = reference.errors(*np.asarray(got), states.to_complex(want))
    norm = float(np.sum(np.asarray(got).astype(np.float64) ** 2))
    got, want = reference_planes.split(got), reference_planes.split(want)
    assert all(p.shape == ((1 << n) // 128, 128) for p in got)
    assert all(len(p.sharding.device_set) == devices for p in got)
    on_device = reference_planes.errors(got, want)
    assert on_device == pytest.approx(on_host, rel=1e-5)
    assert on_device[1] == pytest.approx(1e-3, rel=1e-2)
    assert reference_planes.total_probability(got) == pytest.approx(norm,
                                                                    rel=1e-6)
    assert reference_planes.errors(want, want) == (0.0, 0.0)


# -- the collective readers ---------------------------------------------------

def _ev(mid, start_ns, dur_ns):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _synthetic():
    """Six runs, 1000 ns apart and 800 long, on two devices. Device 0: a
    kernel, then an async all-to-all in flight for 300 ns (start 300-310,
    done 590-600) with a 100 ns copy under it, then a kernel. Device 1: a
    synchronous all-to-all of 200 ns whose last 50 a kernel overlaps."""
    from jax.profiler import ProfileData

    runs = " ".join(_ev(1, 1000 * i, 800) for i in range(6))
    dev0 = " ".join(
        " ".join([_ev(2, 1000 * i, 300), _ev(3, 1000 * i + 300, 10),
                  _ev(5, 1000 * i + 400, 100), _ev(4, 1000 * i + 590, 10),
                  _ev(2, 1000 * i + 600, 200)]) for i in range(6))
    dev1 = " ".join(
        " ".join([_ev(6, 1000 * i + 300, 200), _ev(2, 1000 * i + 450, 100)])
        for i in range(6))
    meta = '''
  event_metadata { key: 1 value { id: 1 name: "jit_fn(1)" } }
  event_metadata { key: 2 value { id: 2 name: "%%_k.1 = f32[8]{0} custom-call(f32[8]{0} %%p), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "%%all-to-all-start.1 = (f32[8]{0}, f32[8]{0}) all-to-all-start(f32[8]{0} %%p)" } }
  event_metadata { key: 4 value { id: 4 name: "%%all-to-all-done.1 = f32[8]{0} all-to-all-done((f32[8]{0}, f32[8]{0}) %%all-to-all-start.1)" } }
  event_metadata { key: 5 value { id: 5 name: "%%copy.2 = f32[8]{0} copy(f32[8]{0} %%p)" } }
  event_metadata { key: 6 value { id: 6 name: "%%all-to-all.7 = f32[8]{0} all-to-all(f32[8]{0} %%p), channel_id=1" } }
'''
    return ProfileData.from_text_proto('''
planes { name: "/device:TPU:0"
  lines { name: "XLA Modules" %s }
  lines { name: "XLA Ops" %s } %s }
planes { name: "/device:TPU:1"
  lines { name: "XLA Modules" %s }
  lines { name: "XLA Ops" %s } %s }
planes { name: "/host:CPU"
  lines { name: "python3" %s }
  event_metadata { key: 1 value { id: 1 name: "bench.slice" } }
}''' % (runs, dev0, meta, runs, dev1, meta, _ev(1, 500, 5000)))


@pytest.mark.parametrize("hlo,part", [
    ("%all-to-all.3 = f32[2,4]{1,0} all-to-all(f32[2,4]{1,0} %c), "
     "channel_id=1", ("all-to-all", "whole")),
    ("%collective-permute-start.2 = (f32[8]{0}, f32[8]{0}) "
     "collective-permute-start(f32[8]{0} %p)", ("collective-permute",
                                                 "start")),
    ("%collective-permute-done.2 = f32[8]{0} collective-permute-done("
     "(f32[8]{0}, f32[8]{0}) %collective-permute-start.2)",
     ("collective-permute", "done")),
    ("%all-reduce.1 = f32[] all-reduce(f32[] %x), to_apply=%add",
     ("all-reduce", "whole")),
    ("%all-gather-start = (f32[2]{0}, f32[8]{0}) async-start(f32[2]{0} %p), "
     "calls=%wrapped", ("all-gather", "start")),
    ("%copy.2 = f32[8]{0} copy(f32[8]{0} %p)", None),
    ("%_k.1 = f32[8]{0} custom-call(f32[8]{0} %p), "
     "custom_call_target=\"tpu_custom_call\"", None),
])
def test_a_collective_is_told_by_its_opcode(hlo, part):
    assert tc.collective_part(hlo) == part


def test_collectives_of_a_synthetic_trace_reduce_to_known_numbers():
    c = tc.reduce(_synthetic())
    # runs 1..4 are whole: 1000 -> 4800 ns
    assert c["devices"] == 2 and c["runs"] == 4
    # an async pair is ONE interval of 300 ns, not two ops of 10; the mean
    # over the planes of 4 x 300 and 4 x 200
    assert c["collective_s"] == pytest.approx(4 * 250e-9)
    # the copy under the flight (100) and the kernel's overlap (50) are not
    # exposed: 4 x 200 and 4 x 150
    assert c["exposed_s"] == pytest.approx(4 * 175e-9)
    assert c["by_kind"] == {"all-to-all": 4 * 1.5}


def test_the_x4_readers_read_the_reduction_and_nothing_where_there_is_none():
    def read(name, m):
        return harness.load_module("layer_metrics", name).read(m)

    m = {"collectives": tc.reduce(_synthetic())}
    assert read("collective_ms.x4", m) == pytest.approx(250e-6)
    assert read("collective_exposed_pct.x4", m) == pytest.approx(70.0)
    # not traced, or a driver that names no cell: nothing to read
    for bare in ({"trace": None, "shapes": {"cell": CELL}},
                 {"trace": {"runs": 1}, "shapes": {}}):
        assert read("collective_ms.x4", dict(bare)) is None
        assert read("collective_exposed_pct.x4", dict(bare)) is None


def test_collective_swaps_reads_the_process_total_or_nothing():
    read = harness.load_module("layer_metrics", "collective_swaps.x4").read

    def after(**counters):
        return {"after": {"counters": counters}}

    assert read(after()) is None                # the parent, or one device
    assert read(after(**{"pallas_pass_total{kind=frame_swap}": 2})) is None
    assert read(after(fusion_sharded_runs_total=3,
                      fusion_collective_swaps_total=2)) == 2
    assert read(after(fusion_sharded_runs_total=3)) == 0


def test_per_chip_roofline_is_the_whole_register_over_every_chip_s_bandwidth():
    read = harness.load_module("layer_metrics", "fused_run_roofline.x4").read
    peaks = harness.peaks_for("TPU v5 lite", False)
    m = {"peaks": peaks, "shapes": {"state_bytes": 8 << 31},
         "trace": {"devices": 4, "runs": 5, "kernel_s": 5 * 0.1}}
    floor_ms = 2 * (8 << 31) / (4 * 819e9) * 1e3            # 10.49 ms
    assert read(m) == pytest.approx(floor_ms / 100 * 100)
    one = harness.load_module("layer_metrics", "fused_run_roofline").read(m)
    assert one == pytest.approx(4 * read(m))     # why the cell does not join it
    m["trace"]["kernel_s"] = 5 * 0.005           # faster than the chips can
    assert read(m) > 100
    with pytest.raises(SystemExit):
        harness.refuse_over_roofline("fused_run_roofline.x4", read(m))
    assert read(dict(m, trace=None)) is None


# -- the cell itself, on four virtual devices ---------------------------------

def four_devices(args, timeout=900):
    env = child_env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    proc = subprocess.run([sys.executable] + args, cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=timeout)
    return proc.returncode, proc.stdout, proc.stdout + proc.stderr


def test_cell_rehearses_on_four_devices_to_its_last_line():
    rc, stdout, out = four_devices(
        ["benchmark/run.py", "--workload", CELL, "--seed", str(SEED),
         "--seconds", "1.5", "--trace", "1", "--rehearse"])
    assert rc == 0, out[-3000:]
    last = json.loads(stdout.splitlines()[-1])
    assert last["correct"] is True and last["rehearsed"] is True, out[-3000:]
    assert last["device"]["count"] == 4 and last["failed"] == 0
    # the sharded route was taken: both relabelings of the plan are collective
    assert last["metrics"]["collective_swaps.x4"]["value"] == 2
    assert last["metrics"]["dispatches_per_circuit"]["value"] == 1
    assert last["checks"]["engine_fallback_total"]["value"] == 0


def test_control_in_bfloat16_fails_both_limits_ten_times_over(bench):
    rc, stdout, out = four_devices(
        ["benchmark/control.py", "--workload", CELL, "--seeds", "3,4,5",
         "--seconds", "0.5", "--rehearse"])
    assert rc == 0, out[-3000:]
    rows = [json.loads(ln) for ln in stdout.splitlines()
            if ln.startswith("{")]
    assert len(rows) == 3
    files = {c["name"]: c["file"] for c in bench["configs"]}
    with open(os.path.join(ROOT, files["sv31x4-f32-random"])) as f:
        limits = json.load(f)["check"]["limits"]
    for row in rows:
        assert all(row["sound"][k] <= limits[k] for k in limits), row
        assert all(row["control"][k] >= 10 * limits[k]
                   for k in ("err_max", "err_l2")), row
