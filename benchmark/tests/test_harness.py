"""The command itself: every cell to its last line under ``--rehearse``, the
refusals, the control (not correct) and a broken timed path (not correct)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT, child_env, run_child

CELLS = [c["name"] for c in
         json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["workloads"]]
#: counts a rehearsal has to read, by (cell, trace): the served plan at the
#: rehearsal's 10 qubits and depth 2 fuses its 40 Params and leaves none
COUNTS = {("ansatz20.serve-closed16", 1): {"param_fused.serve": 40,
                                           "param_barriers.serve": 0}}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses_to_its_last_line(cell, trace, bench):
    rc, last, out = run_child(["benchmark/run.py", "--workload", cell,
                               "--seed", str(2**31 + 7), "--seconds", "1.5",
                               "--trace", str(trace), "--rehearse"])
    assert rc == 0, out[-3000:]
    assert set(last) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert last["correct"] is True and last["rehearsed"] is True, out[-3000:]
    assert last["attempted"] > 0 and last["failed"] == 0
    # a CPU run writes counts only, never a time under a device metric's name
    sources = {m["name"]: m["source"]
               for m in bench["end_to_end"] + bench["per_layer"]}
    assert all(sources[name] == "program_counter" for name in last["metrics"])
    assert "busy_s" not in last["device"]
    for name, count in COUNTS.get((cell, trace), {}).items():
        assert last["metrics"][name]["value"] == count


def test_no_chip_no_result():
    rc, last, out = run_child(["benchmark/run.py", "--workload", CELLS[0],
                               "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0 and last is None, out[-2000:]


def test_nothing_but_the_benchmark_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths``: no program, so another exit code than 0 and no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=child_env(), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


@pytest.mark.parametrize("cell", ["density14.block", "sv20.block",
                                  "ansatz20.serve-closed16"])
def test_control_in_lower_precision_is_not_correct(cell, bench):
    """bfloat16 in the program's place fails the limits the program holds,
    at a size a test run can hold."""
    rc, _, out = run_child(["benchmark/control.py", "--workload", cell,
                            "--seeds", "3,4,5", "--seconds", "0.5",
                            "--rehearse"])
    assert rc == 0, out[-3000:]
    rows = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    assert len(rows) == 3
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = {c["name"]: c["config"] for c in bench["workloads"]}[cell]
    with open(os.path.join(ROOT, files[config])) as f:
        limits = json.load(f)["check"]["limits"]
    for row in rows:
        assert all(row["sound"][k] <= limits[k] for k in limits
                   if k in row["sound"]), row
        assert any(row["control"][k] > limits[k] for k in limits
                   if k in row["control"]), row


BROKEN = {
    # a step that returns its state unchanged
    "sv20.block": """
orig = run.load_module
def patched(kind, name):
    mod = orig(kind, name)
    if kind == "drivers":
        real = mod.Driver.apply
        calls = []
        def apply(self):
            calls.append(1)
            return real(self) if len(calls) <= 2 else self.sync()
        mod.Driver.apply = apply
    return mod
run.load_module = patched
""",
    # an answer altered where it is produced: every lane gets lane 0's state
    "ansatz20.serve-closed16": """
from quest_tpu.engine import engine as E
real = E.Engine._lane
E.Engine._lane = lambda self, out, i: real(self, out, 0)
""",
}


@pytest.mark.parametrize("cell", sorted(BROKEN))
def test_broken_timed_path_is_not_correct(cell):
    script = ("import sys; sys.path[:0] = ['benchmark', '.']\n"
              "import run\n" + BROKEN[cell] +
              f"sys.exit(run.main(['--workload', {cell!r}, '--seed', '9', "
              "'--seconds', '1', '--trace', '0', '--rehearse']))\n")
    rc, last, out = run_child(["-c", script])
    assert rc == 0, out[-3000:]
    assert last["correct"] is False, out[-3000:]
    assert "FAILED" in out


def test_unknown_device_and_roofline_over_100_are_refused():
    import run as harness

    assert harness.peaks_for("TPU v5 lite", False)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v9 imaginary", False)
    assert harness.peaks_for("cpu", True) is None      # rehearsal only
    harness.refuse_over_roofline("fused_run_roofline", 99.9)
    with pytest.raises(SystemExit):
        harness.refuse_over_roofline("fused_run_roofline", 100.5)


def test_traffic_generator_is_found_by_the_mix_s_loop_key():
    import run as harness

    for mix in ("block", "serve-closed16"):
        traffic = harness.load_json(harness.HERE, "traffic", mix + ".json")
        loop = harness.load_module("loops", traffic["loop"])
        win = loop.run(lambda c, k: True, dict(traffic, clients=2), 0.05)
        assert {r[0] for r in win.requests} == {0, 1} and not win.errors
    with pytest.raises(SystemExit):
        harness.load_module("loops", "no-such-loop")


def test_a_cold_cache_is_filled_by_a_child_before_this_process_touches_jax(
        tmp_path, monkeypatch):
    """No marker of the cell in the cache: the set-up runs once in a child
    (same cell, seed and trace, no result line), whose exit code is handed
    on; with the marker there, and in a rehearsal, nothing is started."""
    import run as harness

    calls = []

    def fake(cmd, **kw):
        calls.append((cmd, kw))
        return subprocess.CompletedProcess(cmd, 3 if len(calls) == 1 else 0)

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(harness.subprocess, "run", fake)
    run = harness.Run("sv20.block", 2**31 + 5)
    assert harness.set_up_apart(run, 1) == 3            # no chip: handed on
    cmd, kw = calls[0]
    marker = str(tmp_path / "sv20.block.set-up")
    assert cmd[1:] == [os.path.join(ROOT, "benchmark", "run.py"),
                       "--workload", "sv20.block", "--seed", str(2**31 + 5),
                       "--trace", "1", "--set-up-only", marker]
    assert kw["stdout"] == subprocess.DEVNULL and "apart_s" in run.spans
    assert harness.set_up_apart(run, 1) == 0 and len(calls) == 2
    open(marker, "w").close()
    assert harness.set_up_apart(run, 1) == 0 and len(calls) == 2
    os.remove(marker)
    assert harness.set_up_apart(harness.Run("sv20.block", 1, True), 0) == 0
    assert len(calls) == 2
