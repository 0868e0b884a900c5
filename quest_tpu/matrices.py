"""Standard gate matrices and rotation decompositions.

Mirrors the reference's hardware-agnostic algebra (QuEST_common.c:120-139,
310-324): axis rotations reduce to a "compact unitary" (alpha, beta) pair,
i.e. the 2x2 matrix [[alpha, -conj(beta)], [beta, conj(alpha)]].

Host-side numpy by default; cast to the register dtype at apply time. The
parameterized-replay path (quest_tpu.params) instead feeds TRACED
scalars, and every angle-taking builder carries a traced branch assembling
the same matrix with jax.numpy *inside* the jit trace -- entrywise from
real cos/sin components (never a complex transcendental), which keeps the
assembly TPU-portable (no complex dtypes on device) and bit-identical to
the numpy path after the planar cast: libm's ``cexp(iy)`` is exactly
``(cos y, sin y)``, and XLA:CPU lowers ``cos``/``sin`` to the same libm.
"""

from __future__ import annotations

import math

import numpy as np


def is_traced(*xs) -> bool:
    """True when any argument is a jax array/tracer -- matrix assembly must
    then happen inside the trace (runtime gate parameters)."""
    import jax

    return any(isinstance(x, jax.Array) for x in xs)

SQRT2_INV = 1.0 / math.sqrt(2.0)

HADAMARD = np.array([[SQRT2_INV, SQRT2_INV], [SQRT2_INV, -SQRT2_INV]], dtype=np.complex128)
PAULI_X_M = np.array([[0, 1], [1, 0]], dtype=np.complex128)
PAULI_Y_M = np.array([[0, -1j], [1j, 0]], dtype=np.complex128)
PAULI_Z_M = np.array([[1, 0], [0, -1]], dtype=np.complex128)
S_GATE = np.array([[1, 0], [0, 1j]], dtype=np.complex128)
T_GATE = np.array([[1, 0], [0, np.exp(1j * math.pi / 4)]], dtype=np.complex128)

SQRT_SWAP = np.array(
    [[1, 0, 0, 0],
     [0, 0.5 + 0.5j, 0.5 - 0.5j, 0],
     [0, 0.5 - 0.5j, 0.5 + 0.5j, 0],
     [0, 0, 0, 1]], dtype=np.complex128)


def compact_unitary_matrix(alpha: complex, beta: complex) -> np.ndarray:
    """[[alpha, -conj(beta)], [beta, conj(alpha)]] (compactUnitary, QuEST.h:2562)."""
    if is_traced(alpha, beta):
        import jax
        import jax.numpy as jnp

        a, b = jnp.asarray(alpha), jnp.asarray(beta)
        ar, ai = jnp.real(a), jnp.imag(a)
        br, bi = jnp.real(b), jnp.imag(b)
        re = jnp.stack([jnp.stack([ar, -br]), jnp.stack([br, ar])])
        im = jnp.stack([jnp.stack([ai, bi]), jnp.stack([bi, -ai])])
        return jax.lax.complex(re, im)
    return np.array([[alpha, -np.conj(beta)], [beta, np.conj(alpha)]], dtype=np.complex128)


def rotation_around_axis_pair(angle: float, axis) -> tuple[complex, complex]:
    """(alpha, beta) for exp(-i angle/2 (n . sigma)) about unit axis n
    (getComplexPairFromRotation, QuEST_common.c:120-127)."""
    x, y, z = axis[0], axis[1], axis[2]
    mag = math.sqrt(x * x + y * y + z * z)
    x, y, z = x / mag, y / mag, z / mag
    if is_traced(angle):
        import jax
        import jax.numpy as jnp

        c, s = jnp.cos(angle / 2), jnp.sin(angle / 2)
        alpha = jax.lax.complex(c, -s * z)
        beta = jax.lax.complex(s * y, -s * x)
        return alpha, beta
    c, s = math.cos(angle / 2), math.sin(angle / 2)
    alpha = complex(c, -s * z)
    beta = complex(s * y, -s * x)
    return alpha, beta


def rotation_matrix(angle: float, axis) -> np.ndarray:
    a, b = rotation_around_axis_pair(angle, axis)
    return compact_unitary_matrix(a, b)


def rx_matrix(theta: float) -> np.ndarray:
    return rotation_matrix(theta, (1.0, 0.0, 0.0))


def ry_matrix(theta: float) -> np.ndarray:
    return rotation_matrix(theta, (0.0, 1.0, 0.0))


def rz_diag(theta: float) -> np.ndarray:
    """Diagonal of Rz(theta) = exp(-i theta/2 Z)."""
    if is_traced(theta):
        import jax
        import jax.numpy as jnp

        c, s = jnp.cos(theta / 2), jnp.sin(theta / 2)
        return jax.lax.complex(jnp.stack([c, c]), jnp.stack([-s, s]))
    return np.array([np.exp(-0.5j * theta), np.exp(0.5j * theta)], dtype=np.complex128)


def phase_shift_diag(theta: float) -> np.ndarray:
    """diag(1, e^{i theta}) (phaseShift, QuEST.h:1916)."""
    if is_traced(theta):
        import jax
        import jax.numpy as jnp

        c, s = jnp.cos(theta), jnp.sin(theta)
        one, zero = jnp.ones_like(c), jnp.zeros_like(c)
        return jax.lax.complex(jnp.stack([one, c]), jnp.stack([zero, s]))
    return np.array([1.0, np.exp(1j * theta)], dtype=np.complex128)


#: basis-change matrices sending Pauli P to Z: P = U^dagger Z U
#: X = H Z H; Y = (H S^dagger)^dagger Z (H S^dagger)
BASIS_TO_Z = {
    1: HADAMARD,
    2: HADAMARD @ np.conj(S_GATE).T,
}
