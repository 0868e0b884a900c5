"""The seed's projector made ON the chip block by block, for a density
register that leaves no room for a second state.

``states.projector_planes`` holds the outer product, both planes and their
stack at once: three states and more, where a 15-qubit register (8 GiB) is
half the chip. Here the same state -- the projector of the seeded normalised
Gaussian vector ``states._vector_planes`` makes for this seed, element
rho[row, col] = psi[row] conj(psi[col]) at flat index ``col * 2^n + row`` --
is written where it will live, ``BLOCKS`` blocks of columns one after the
other, by the same products and sums: the peak is the register and one block.
"""

from __future__ import annotations

import functools

import states

#: blocks of columns the projector is written in (at 15 qubits 512 MiB each)
BLOCKS = 16

#: a row of the planes' (rows, 128) form (``reference_planes.LANES``)
LANES = 128


def _block(psi, at, cols: int):
    """Columns [at, at + cols) of the projector of ``psi`` (2, 2^n): its
    planes (re, im), each (cols, 2^n)."""
    import jax

    re, im = psi[0], psi[1]
    cr = jax.lax.dynamic_slice(re, (at,), (cols,))[:, None]
    ci = jax.lax.dynamic_slice(im, (at,), (cols,))[:, None]
    return (re[None, :] * cr + im[None, :] * ci,
            im[None, :] * cr - re[None, :] * ci)


@functools.lru_cache(maxsize=None)
def _maker(num_qubits: int, rows_form: bool):
    import jax
    import jax.numpy as jnp

    dim = 1 << num_qubits
    blocks = min(BLOCKS, dim)
    cols = dim // blocks
    size = cols * dim

    def make(key):
        psi = states._vector_planes(key, num_qubits)

        def body(b, out):
            new = _block(psi, b * cols, cols)
            if rows_form:
                return tuple(jax.lax.dynamic_update_slice(
                    o, v.reshape(size // LANES, LANES),
                    (b * (size // LANES), 0)) for o, v in zip(out, new))
            return jax.lax.dynamic_update_slice(
                out, jnp.stack([v.reshape(-1) for v in new]), (0, b * size))

        if rows_form:
            start = tuple(jnp.zeros((dim * dim // LANES, LANES), jnp.float32)
                          for _ in (0, 1))
        else:
            start = jnp.zeros((2, dim * dim), jnp.float32)
        return psi, jax.lax.fori_loop(0, blocks, body, start)

    return jax.jit(make)


def projector_planes(seed: int, num_qubits: int):
    """``(psi, rho)`` as ``states.projector_planes`` gives them: the (2, 2^n)
    vector and the (2, 4^n) float32 planes of its projector."""
    return _maker(num_qubits, False)(states._key(seed))


def projector_rows(seed: int, num_qubits: int) -> tuple:
    """The same projector as two planes ``(re, im)`` of their own, each rows
    of 128 lanes: the form ``reference_density_planes`` works on."""
    return _maker(num_qubits, True)(states._key(seed))[1]
