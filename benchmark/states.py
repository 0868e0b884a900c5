"""Seeded inputs, made by the benchmark on the device in one jitted call.

``--seed`` changes the data and never the program: the initial register is a
normalised Gaussian vector (planar float32, the layout the program stores),
a density register the projector of such a vector, and a served request a
seeded set of angles. A seed may exceed 32 bits, so it is split into the two
words of a threefry key.
"""

from __future__ import annotations

import numpy as np


def _key(seed: int, stream: int = 0):
    import jax
    import jax.numpy as jnp

    words = [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF]
    key = jax.random.wrap_key_data(jnp.asarray(words, dtype=jnp.uint32),
                                   impl="threefry2x32")
    return jax.random.fold_in(key, stream)


def _vector_planes(key, num_qubits):
    import jax
    import jax.numpy as jnp

    g = jax.random.normal(key, (2, 1 << num_qubits), dtype=jnp.float32)
    return g * jax.lax.rsqrt(jnp.sum(g * g))


def statevector_planes(seed: int, num_qubits: int):
    """(2, 2^n) float32 planes (re, im) of a normalised Gaussian vector."""
    import jax

    return jax.jit(_vector_planes, static_argnums=1)(_key(seed), num_qubits)


def projector_planes(seed: int, num_qubits: int):
    """``(psi, rho)``: the (2, 2^n) vector and the (2, 4^n) planes of its
    projector, element rho[row, col] = psi[row] conj(psi[col]) at flat index
    ``col * 2^n + row``."""
    import jax
    import jax.numpy as jnp

    def make(key):
        psi = _vector_planes(key, num_qubits)
        re, im = psi[0], psi[1]
        rho_re = re[None, :] * re[:, None] + im[None, :] * im[:, None]
        rho_im = im[None, :] * re[:, None] - re[None, :] * im[:, None]
        return psi, jnp.stack([rho_re.reshape(-1), rho_im.reshape(-1)])

    return jax.jit(make)(_key(seed))


def to_complex(planes) -> np.ndarray:
    """Host complex128 copy of (2, N) planes."""
    host = np.asarray(planes)
    return host[0].astype(np.float64) + 1j * host[1].astype(np.float64)


def angle_sets(seed: int, stream: int, names: list, count: int) -> list:
    """``count`` parameter sets (name -> angle in [0, 2 pi)) of one client."""
    rng = np.random.default_rng([seed, stream])
    draws = rng.uniform(0.0, 2 * np.pi, size=(count, len(names)))
    return [dict(zip(names, map(float, row))) for row in draws]


def sample_pairs(seed: int, count: int, spectator_bits: int) -> tuple:
    """Spectator (row, column) bit patterns of ``count`` density blocks: half
    on the diagonal (they carry the trace), half off it."""
    rng = np.random.default_rng([seed, 7])
    size = 1 << spectator_bits
    rows = rng.integers(0, size, size=count)
    cols = np.where(np.arange(count) % 2 == 0, rows,
                    rng.integers(0, size, size=count))
    return rows, cols
