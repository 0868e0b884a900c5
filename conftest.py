"""Root pytest config: force the CPU backend with 8 virtual devices and f64.

Must run before jax initialises its backends, hence env vars here rather than
in a fixture. This is the TPU analogue of the reference's "just run mpirun"
testing strategy (examples/README.md section Testing): the same engine runs
on an emulated 8-device mesh so every sharded code path executes in CI.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("QUEST_PRECISION", "2")

import jax  # noqa: E402

# JAX_PLATFORMS is only a default above; the config update is what pins the
# tests to the 8-device host mesh whatever the caller exported.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

# Reuse compiled binaries across test runs (the same persistent cache
# bench.py, chip_smoke.py and the serving engine use): the suite is
# dominated by >1s XLA compiles of 8-device sharded programs that are
# bit-identical run over run, so a warm cache cuts wall time without
# touching what any test asserts. JAX_COMPILATION_CACHE_DIR, where set,
# places it; otherwise <checkout>/.jax_cache (quest_tpu.compile_cache).
from quest_tpu.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()
