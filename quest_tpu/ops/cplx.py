"""Planar complex arithmetic.

The TPU has no native complex dtype (this backend rejects complex64 outright),
so the state is stored planar: one float array of shape (2, ...) holding
(real, imag) -- the same SoA layout as the reference's ComplexArray
(QuEST.h:94-98). Complex algebra is spelled out over the two planes; XLA fuses
the elementwise forms and maps the matmul forms onto real MXU ops (which beats
emulated complex even where complex is available).

Host <-> device conversion happens only at the API boundary (gate matrices in,
amplitudes out).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def from_complex(arr, dtype) -> jnp.ndarray:
    """Complex array -> planar (2, *shape) device array. Host numpy input
    converts at trace time (the constant-matrix path); a jax array/tracer
    input -- a gate matrix assembled from runtime parameters inside the
    trace (quest_tpu.params) -- splits into planes symbolically."""
    import jax

    if isinstance(arr, jax.Array):
        a = jnp.asarray(arr)
        if jnp.iscomplexobj(a):
            return jnp.stack([jnp.real(a), jnp.imag(a)]).astype(dtype)
        return jnp.stack([a, jnp.zeros_like(a)]).astype(dtype)
    a = np.asarray(arr)
    return jnp.asarray(np.stack([a.real, a.imag]), dtype=dtype)


def to_complex(x) -> np.ndarray:
    """planar device array -> numpy complex host array."""
    h = np.asarray(x)
    return h[0] + 1j * h[1]


def cmul(ar, ai, br, bi):
    """(ar+i ai)(br+i bi) -> (re, im)."""
    return ar * br - ai * bi, ar * bi + ai * br


def cmatmul(mr, mi, vr, vi):
    """Complex matmul via 4 real matmuls: (mr+i mi)(vr+i vi)."""
    return mr @ vr - mi @ vi, mr @ vi + mi @ vr


def abs2(x):
    """|x|^2 plane-wise: x is (2, ...)."""
    return x[0] * x[0] + x[1] * x[1]
