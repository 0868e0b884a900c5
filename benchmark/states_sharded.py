"""The seed's state made ON the devices of a mesh, shard by shard, already in
the register's sharding: ``states.statevector_planes`` makes the whole vector
on one device, which a register that only fits sharded cannot afford.

The vector is cut into ``BLOCKS`` equal blocks whatever the mesh; block ``b``
draws its Gaussians from ``fold_in(key(seed), b)``, each device makes the
blocks of its own shard one after the other, and the squared norms of the
blocks are gathered in block order and summed in that order on every device.
So the same seed gives the same normalised state on 1, 2 or 4 devices, and
in whatever order the shards are made.
"""

from __future__ import annotations

import functools

import states

#: at least the largest mesh, and small enough that one block's random bits
#: are a fraction of a shard (2^31 amplitudes: 1 GiB of planes a block)
BLOCKS = 16


def statevector_planes(seed: int, num_qubits: int, mesh, axis: str):
    """(2, 2^n) float32 planes (re, im) of a normalised Gaussian vector,
    sharded ``P(None, axis)`` over the one-axis ``mesh``."""
    return _maker(num_qubits, mesh, axis)(states._key(seed))


@functools.lru_cache(maxsize=None)
def _maker(num_qubits: int, mesh, axis: str):
    """The jitted maker of one size on one mesh (traced once a process)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    devices = mesh.shape[axis]
    if BLOCKS % devices or (1 << num_qubits) % BLOCKS:
        raise ValueError(f"{devices} devices or 2^{num_qubits} amplitudes "
                         f"do not divide into {BLOCKS} blocks")
    each = BLOCKS // devices
    size = (1 << num_qubits) // BLOCKS

    def shard(key):
        first = jax.lax.axis_index(axis) * each

        def block(j, carry):
            out, norms = carry
            g = jax.random.normal(jax.random.fold_in(key, first + j),
                                  (2, size), dtype=jnp.float32)
            return (jax.lax.dynamic_update_slice(out, g, (0, j * size)),
                    norms.at[j].set(jnp.sum(g * g)))

        out, norms = jax.lax.fori_loop(
            0, each, block, (jnp.zeros((2, each * size), jnp.float32),
                             jnp.zeros((each,), jnp.float32)))
        norms = jax.lax.all_gather(norms, axis, tiled=True)
        return out * jax.lax.rsqrt(jnp.sum(norms))

    return jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=P(),
                                 out_specs=P(None, axis), check_vma=False))
