"""Helpers of the benchmark's own tests: they import the benchmark's modules
by file, and run the command in a child process whose environment is not the
repo's test environment (the root ``conftest.py`` forces f64 and 8 virtual
devices; the benchmark serves float32 on one device)."""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def child_env() -> dict:
    env = {k: v for k, v in os.environ.items()
           if k not in ("QUEST_PRECISION", "XLA_FLAGS", "QUEST_TRACE",
                        "JAX_COMPILATION_CACHE_DIR")}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def run_child(args, timeout=600):
    """(exit code, last stdout line parsed as JSON or None, stdout)."""
    proc = subprocess.run([sys.executable] + args, cwd=ROOT, env=child_env(),
                          capture_output=True, text=True, timeout=timeout)
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    try:
        last = json.loads(lines[-1]) if lines else None
    except ValueError:
        last = None
    return proc.returncode, last, proc.stdout + proc.stderr


@pytest.fixture(scope="session")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
