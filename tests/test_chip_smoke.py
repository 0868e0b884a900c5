"""chip_smoke.py's contract off the chip, and the compile-cache placement.

The smoke itself only means something on the TPU (the builder runs it
there); what tier-1 can hold is that it REFUSES everything else: no
``"ok": true`` from a CPU, a parent that never imports JAX, and one rule
for where the persistent compilation cache lives.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, env=None, cwd=ROOT, timeout=300):
    full = dict(os.environ, JAX_PLATFORMS="cpu")
    full.pop("XLA_FLAGS", None)  # one CPU device, like the driver's sandbox
    full.update(env or {})
    return subprocess.run([sys.executable, SMOKE] + args, env=full, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


def test_smoke_refuses_cpu():
    """No accelerator: non-zero exit, no result line."""
    out = _run([])
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_smoke_four_chip_option_refuses_cpu():
    out = _run(["--chips", "4"])
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_smoke_alone_in_a_directory_fails(tmp_path):
    """chip_smoke.py without the program beside it must fail too."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(SMOKE).read())
    out = subprocess.run([sys.executable, str(lone)], cwd=tmp_path,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_smoke_parent_module_never_imports_jax():
    code = ("import sys; sys.argv=['chip_smoke.py']; import chip_smoke; "
            "assert 'jax' not in sys.modules and "
            "'quest_tpu' not in sys.modules, sorted(sys.modules); "
            "print('clean')")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-800:]
    assert out.stdout.strip() == "clean"


def test_bench_parent_refuses_cpu_without_smoke_flag():
    """bench.py without --smoke and without a TPU exits non-zero, from
    main() -- the bench_* functions stay importable on the CPU."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py"), "--config", "20q"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert not [ln for ln in out.stdout.splitlines() if ln.startswith("{")]


def test_smoke_rehearsal_never_claims_ok():
    """--rehearse drives one phase end to end on the CPU at tiny size and
    says so: exit 0, last line not an ok result."""
    out = _run(["--rehearse", "--only", "density"])
    assert out.returncode == 0, out.stderr[-1500:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["rehearsed"] is True
    assert last["device"]["platform"] == "cpu"
    row = json.loads(lines[-2])
    assert row["phase"] == "density" and row["passed"]
    assert not any(row["engine_fallback_total"].values())


@pytest.mark.parametrize("case", ["env_set", "env_unset"])
def test_compile_cache_helper_placement(case, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: nothing sets another directory (the
    helper, QUEST_COMPILE_CACHE and an explicit path all yield to it).
    Unset: the one fixed path <checkout>/.jax_cache."""
    code = (
        "import os, jax\n"
        "from quest_tpu.compile_cache import (compile_cache_dir, "
        "enable_compile_cache)\n"
        "from quest_tpu.engine import enable_persistent_cache\n"
        "a = enable_compile_cache()\n"
        "b = enable_persistent_cache(os.environ['OTHER'])\n"
        "print(repr((compile_cache_dir(), a, b, "
        "jax.config.jax_compilation_cache_dir)))\n")
    other = str(tmp_path / "other")
    env = dict(os.environ, JAX_PLATFORMS="cpu", OTHER=other,
               QUEST_COMPILE_CACHE=other)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    placed = str(tmp_path / "placed")
    if case == "env_set":
        env["JAX_COMPILATION_CACHE_DIR"] = placed
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-800:]
    where, a, b, cfg = eval(out.stdout.strip().splitlines()[-1])
    if case == "env_set":
        assert where == a == b == cfg == placed
    else:
        assert where == a == os.path.join(ROOT, ".jax_cache")
        assert b == cfg == other  # QUEST_COMPILE_CACHE still works alone


def test_bench_all_parent_stays_off_the_device_and_fails_with_a_child():
    """The no---config parent runs every config as a child, initialises no
    JAX backend itself, and a failed child makes its exit code non-zero."""
    code = (
        "import sys, bench\n"
        "calls = []\n"
        "def fake(extra_args, budget_s, metric, env=None, unit='ops/sec',"
        " slug=None):\n"
        "    calls.append(extra_args[1])\n"
        "    return {'config': slug, 'metric': metric, 'value': None,"
        " 'unit': unit, 'vs_baseline': None, 'failed': True}\n"
        "bench._subprocess_config = fake\n"
        "bench.DETAIL_FILE = sys.argv[1]\n"
        "sys.argv = ['bench.py']\n"
        "try:\n"
        "    bench.main()\n"
        "    rc = 0\n"
        "except SystemExit as e:\n"
        "    rc = e.code\n"
        "import jax._src.xla_bridge as xb\n"
        "print(repr((rc, len(calls), sorted(xb._backends))))\n")
    out = subprocess.run([sys.executable, "-c", code, os.devnull],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-800:]
    rc, n_children, backends = eval(out.stdout.strip().splitlines()[-1])
    assert rc == 1 and n_children == 21 and backends == []


def test_smoke_subset_only_in_rehearsal():
    """A subset of the phases never ends in an ok result: --only is refused
    outside --rehearse."""
    out = _run(["--only", "density"])
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "--only needs --rehearse" in out.stderr
