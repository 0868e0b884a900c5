"""Precision system for quest_tpu.

The reference selects float/double/long-double at compile time
(``QuEST/include/QuEST_precision.h:40-96``) and derives ``REAL_EPS`` from it.
Here precision is a *runtime* choice carried per-Qureg (the dtype of its
amplitude array), with a process-wide default selectable via the
``QUEST_PRECISION`` environment variable (1 = single, 2 = double), mirroring
the reference's ``-DPRECISION`` CMake cache variable.

Quad precision (PRECISION=4) is impossible on TPU and is not supported; the
validation layer rejects it explicitly.

TPU notes (the QUEST_PRECISION=2 policy, probed round 3 on a v5e chip):

- Requesting double precision auto-enables jax's x64 mode (:func:`_ensure_x64`)
  -- without it jnp silently truncates f64 arrays to f32, violating the
  reference's PRECISION=2 contract (QuEST_precision.h:52-64).
- f64 **is supported on the TPU backend**: XLA emulates it in software. The
  Pallas/Mosaic kernels have no f64 lowering (MXU dots are bf16/f32 hardware),
  so f64 registers on TPU transparently take the XLA engine paths
  (:func:`_mosaic_supports`); measured ~866 gates/s at 20 qubits vs ~30-50k
  in f32 -- "supported but slow", still ~2x the reference CPU anchor, with
  true double accuracy (22q fused-circuit norm error ~3e-14).
- f32 (QUEST_PRECISION=1, the default) is the performance dtype; REAL_EPS
  tolerances scale accordingly (1e-5 vs 1e-13, mirroring the reference).

bfloat16 state storage is an extension beyond reference parity (not a default).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

#: map of QuEST PRECISION codes -> (real dtype, complex dtype, REAL_EPS)
#: eps values mirror QuEST_precision.h:48,63 (1e-5 single, 1e-13 double).
_PRECISIONS = {
    1: ("float32", "complex64", 1e-5),
    2: ("float64", "complex128", 1e-13),
}


def default_precision() -> int:
    """Process-wide default precision code (1 or 2), from $QUEST_PRECISION."""
    code = int(os.environ.get("QUEST_PRECISION", "1"))
    if code not in _PRECISIONS:
        raise ValueError(f"QUEST_PRECISION must be 1 or 2, got {code}")
    return code


def _ensure_x64(code: int, explicit: bool) -> None:
    """Double precision requires jax's x64 mode; without it jnp silently
    truncates requested f64 arrays to f32 -- a register created under
    QUEST_PRECISION=2 would quietly lose half its mantissa (the reference's
    PRECISION=2 is a hard contract, QuEST_precision.h:52-64).

    Policy: when the PROCESS default is double (QUEST_PRECISION=2) the
    flag auto-enables on first use -- the whole session is f64 and the
    global flip is the declared intent. An EXPLICIT per-register
    ``precision_code=2`` in an otherwise-f32 process raises instead:
    flipping jax_enable_x64 mid-run would silently change dtype promotion
    (and TPU kernel selection) for every concurrent f32 register."""
    if code != 2:
        return
    if jax.config.jax_enable_x64:
        return
    if explicit and default_precision() != 2:
        from .validation import QuESTError

        raise QuESTError(
            "precision_code=2 requires jax x64 mode. Set QUEST_PRECISION=2 "
            "(process-wide double precision) or enable jax_enable_x64 before "
            "creating f64 registers; enabling it implicitly here would "
            "change dtype semantics for every existing f32 register.")
    jax.config.update("jax_enable_x64", True)


def real_dtype(precision: int | None = None):
    explicit = precision is not None
    code = default_precision() if precision is None else precision
    _ensure_x64(code, explicit)
    return jnp.dtype(_PRECISIONS[code][0])


def complex_dtype(precision: int | None = None):
    explicit = precision is not None
    code = default_precision() if precision is None else precision
    _ensure_x64(code, explicit)
    return jnp.dtype(_PRECISIONS[code][1])


def real_eps(precision: int | None = None) -> float:
    """Validation tolerance, as REAL_EPS in QuEST_precision.h:48,63."""
    code = default_precision() if precision is None else precision
    return _PRECISIONS[code][2]


def eps_for_dtype(dtype) -> float:
    """REAL_EPS for a given amplitude dtype."""
    d = jnp.dtype(dtype)
    if d in (jnp.dtype("complex64"), jnp.dtype("float32")):
        return 1e-5
    return 1e-13


def precision_for_dtype(dtype) -> int:
    d = jnp.dtype(dtype)
    if d in (jnp.dtype("complex64"), jnp.dtype("float32")):
        return 1
    return 2


def _mosaic_supports(dtype) -> bool:
    """Mosaic (TPU Pallas) has no f64 lowering for the kernel's MXU dots;
    f64 registers on TPU take the XLA engine paths instead (XLA emulates
    f64 on TPU -- slow but correct, the documented QUEST_PRECISION=2
    policy; see the module docstring). Read through this module
    (``precision._mosaic_supports``) by the router above and the density
    kernels below alike."""
    if jax.default_backend() != "tpu":
        return True  # CPU interpreter handles f64
    return np.dtype(dtype) != np.dtype("float64")
