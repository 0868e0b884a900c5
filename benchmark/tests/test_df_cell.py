"""What ``df26.block`` added to the benchmark: the double-precision driver's
pieces (a float64 seed state that is normalised and fills the low planes, 16
bytes an amplitude, the refusal of a plan whose df runs were not cut), the two
program-counter readers, the cell's rehearsal to its last line, and the
float32 control, which has to read NOT correct against the cell's limits."""

import json
import os
import types

import numpy as np
import pytest

import run as harness
from conftest import ROOT, run_child

N, SEED = 14, 2 ** 31 + 37


@pytest.fixture(scope="module")
def df():
    return harness.load_module("drivers", "library_df")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "df26-f64-random.json")) as f:
        return json.load(f)


def test_the_seed_state_is_float64_normalised_with_low_planes(df):
    """The float32 draw of ``states.statevector_planes`` for the seed,
    normalised in float64: norm 1 to float64 rounding, and nearly every
    amplitude has bits below its float32 rounding (a float32 state widened
    as it is would have none, and a program that dropped the low planes
    would then be exact)."""
    import jax

    import states

    if not jax.config.jax_enable_x64:
        pytest.skip("needs x64 (the repo's conftest turns it on)")
    planes = np.asarray(df.statevector_planes64(SEED, N))
    assert planes.dtype == np.float64 and planes.shape == (2, 1 << N)
    assert abs(float(np.sum(planes * planes)) - 1.0) < 1e-14
    low = planes - planes.astype(np.float32).astype(np.float64)
    assert np.count_nonzero(low) > 0.99 * low.size
    f32 = np.asarray(states.statevector_planes(SEED, N), dtype=np.float64)
    np.testing.assert_allclose(planes, f32, rtol=1e-6)   # the same draw
    other = np.asarray(df.statevector_planes64(SEED + 1, N))
    assert np.max(np.abs(other - planes)) > 1e-3


def test_shapes_count_sixteen_bytes_an_amplitude(df, config):
    driver = types.SimpleNamespace(n=26)
    assert df.Driver.shapes(driver) == {"state_bytes": 16 << 26} \
        == {"state_bytes": config["state_bytes"]}


def _driver_with_runs(op_counts, dtype=np.float64, **swap):
    """A driver whose fused circuit carries runs of ``op_counts`` X gates at
    the 26-qubit df tile, on a register of shapes only."""
    import jax
    from quest_tpu import fusion
    from quest_tpu.circuits import Circuit
    from quest_tpu.ops.pallas_gates import HashableMatrix
    from quest_tpu.registers import Qureg

    n = 26
    x = HashableMatrix(np.array([[0, 1], [1, 0]], dtype=complex))
    fused = Circuit(n)
    fused._tape = fusion.as_tape(types.SimpleNamespace(items=[
        fusion.PallasRun(tuple(("matrix", i % 17, (), (), x)
                               for i in range(count)), 17, **swap)
        for count in op_counts]))
    return types.SimpleNamespace(
        fused=fused, q=Qureg(n, False, jax.ShapeDtypeStruct(
            (2, 1 << n), np.dtype(dtype)), None))


def test_an_uncut_df_plan_is_refused_before_any_compile(df, monkeypatch):
    """What the parent of PR 37 plans for this cell: df runs longer than a df
    kernel takes, cut as they execute and counted ``df_max_ops_split``. The
    driver exits with ``library_large``'s code, at once; so it does for a
    relabeling that would not fold and for a register off the df route."""
    monkeypatch.setenv("QUEST_PALLAS_DF", "1")
    df.Driver.refuse_uncut_plan(_driver_with_runs([8, 8, 5, 1]))   # as cut
    folds = dict(load_swap_k=7, load_swap_hi=17, store_swap_k=7,
                 store_swap_hi=17)
    df.Driver.refuse_uncut_plan(_driver_with_runs([8], **folds))
    wide = dict(load_swap_k=9, load_swap_hi=17, store_swap_k=9,
                store_swap_hi=17)
    for refused in (_driver_with_runs([8, 53]),
                    _driver_with_runs([8], **wide),
                    _driver_with_runs([8], dtype=np.float32)):
        with pytest.raises(SystemExit) as exit_:
            df.Driver.refuse_uncut_plan(refused)
        assert exit_.value.code == df.EXIT_PLAN_REFUSED == 5


@pytest.mark.parametrize("counters,passes,conversions", [
    ({}, None, None),                               # a program without them
    ({"pallas_pass_total{dtype=f32,kind=fused_run}": 3,
      "fusion_inplace_runs_total": 3}, None, None),         # a float32 cell
    ({"fusion_df_passes_total{mode=pallas}": 12,
      "fusion_df_conversions_total{dir=split}": 12,
      "fusion_df_conversions_total{dir=join}": 12,
      "pallas_pass_total{dtype=df,kind=fused_run}": 12}, 12, 24)])
def test_the_two_counter_readers(counters, passes, conversions):
    m = {"after": {"counters": counters}, "before": {"counters": {}}}
    assert harness.load_module("layer_metrics",
                               "df_passes.lib").read(m) == passes
    assert harness.load_module("layer_metrics",
                               "df_conversions.lib").read(m) == conversions


def test_the_cell_is_listed_where_its_metrics_read(bench):
    cell = {c["name"]: c for c in bench["workloads"]}["df26.block"]
    assert cell == dict(cell, config="df26-f64-random", traffic="block",
                        chips=1)
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if "df26.block" in m.get("workloads", [])}
    assert listed == {
        "circuit_ms", "dispatches_per_circuit", "launches_per_circuit",
        "kernel_ms.lib", "fused_run_roofline", "xla_ms.lib",
        "device_idle_pct.lib", "host_launch_ms.lib", "inplace_runs.lib",
        "unfolded_swaps.lib", "df_passes.lib", "df_conversions.lib"}
    new = {m["name"]: m for m in bench["per_layer"]
           if m["name"] in ("df_passes.lib", "df_conversions.lib")}
    assert all(m["workloads"] == ["df26.block"] and m["moves"] == "circuit_ms"
               and m["source"] == "program_counter" for m in new.values())


def test_df26_rehearsal_counts_what_its_plan_states(config):
    """14 qubits on the CPU, the df route switched on: six df kernels of at
    most 8 ops, each in place between a split and a join, nothing left for
    the engine; ``correct`` at the rehearsal's limits (XLA:CPU does not keep
    the error-free transforms exact: only the chip holds the cell's)."""
    rc, last, out = run_child(["benchmark/run.py", "--workload", "df26.block",
                               "--seed", str(SEED), "--seconds", "1",
                               "--trace", "1", "--rehearse"])
    assert rc == 0 and last["correct"] is True, out[-3000:]
    assert last["rehearsed"] is True
    metrics = {k: v["value"] for k, v in last["metrics"].items()}
    assert metrics["df_passes.lib"] == metrics["inplace_runs.lib"] == 6
    assert metrics["df_conversions.lib"] == 12
    assert metrics["unfolded_swaps.lib"] == 0
    assert metrics["dispatches_per_circuit"] == 1.0
    checks = last["checks"]
    assert checks["engine_fallback_total"] == {"value": 0.0, "limit": 0.0}
    rehearsal = config["rehearse"]["limits"]
    assert {k: checks[k]["limit"] for k in rehearsal} == rehearsal
    # what the cell's own limits would say of this CPU run: not correct
    assert checks["err_l2"]["value"] > config["check"]["limits"]["err_l2"]


def test_the_float32_control_is_not_correct(config):
    """The reference replayed in float32, in the program's place, fails each
    of the cell's limits on the amplitudes by orders of magnitude."""
    rc, _, out = run_child(["benchmark/control_df.py", "--workload",
                            "df26.block", "--seeds", "3,4", "--seconds",
                            "0.5", "--rehearse"])
    assert rc == 0, out[-3000:]
    rows = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    assert len(rows) == 2
    limits = config["check"]["limits"]
    for row in rows:
        for name in ("err_max", "err_l2"):
            assert row["control"][name] > 1e4 * limits[name], row


def test_host_only_control_needs_no_program(config):
    """``control_df.py --host-only``: the seed's input, numpy complex128 and
    its float32 replay on the host alone, here at the rehearsal's size."""
    import control
    import control_df

    assert control.LOWER["float64"] is control_df.float32
    z = np.array([1 / 3 + 2j / 3])
    low = control_df.float32(z)
    assert low.dtype == np.complex128 and 0 < abs(low - z) < 1e-7
    rows = control.host_only(harness.Run("df26.block", 3, rehearse=True),
                             [3, 4])
    limits = config["check"]["limits"]
    assert len(rows) == 2
    for row in rows:
        for name in ("err_max", "err_l2"):
            assert row["control"][name] > 1e4 * limits[name], row
