"""Amplitude-index layout algebra.

Convention (identical to the reference): qubit q is bit q of the flat
amplitude index -- qubit 0 is the least-significant bit
(``QuEST_cpu_internal.h:26-53`` extractBit/flipBit do exactly this).

A state over n qubits is a flat array of 2^n amplitudes. Reshaping it to
``(2,)*n`` would make qubit q axis ``n-1-q``, but rank-n tensors are hostile
to the TPU compiler for large n. Instead we *group*: for an operation touching
qubits Q = {q1 > q2 > ... > qk}, reshape to rank <= 2k+1 where each touched
qubit is its own 2-sized axis and the untouched index segments between them
stay fused:

    shape = (2^(n-1-q1), 2, 2^(q1-1-q2), 2, ..., 2, 2^qk)

This is the moral equivalent of the reference's block/stride loops
(e.g. statevec_compactUnitaryLocal's sizeBlock/sizeHalfBlock arithmetic,
``QuEST_cpu.c:1682-1739``) but leaves the actual tiling to XLA.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
from jax.sharding import NamedSharding


def grouped_shape(n: int, qubits_desc: Sequence[int]) -> tuple[int, ...]:
    """Shape with one 2-sized axis per qubit in ``qubits_desc`` (strictly
    descending) and fused segments elsewhere. Product is always 2^n."""
    dims = []
    prev = n
    for q in qubits_desc:
        dims.append(1 << (prev - 1 - q))
        dims.append(2)
        prev = q
    dims.append(1 << prev)
    return tuple(dims)


def grouped_axes(n: int, qubits: Sequence[int]) -> tuple[tuple[int, ...], dict[int, int]]:
    """(shape, {qubit: axis}) for the grouped view over ``qubits`` (any order)."""
    qs = sorted(set(qubits), reverse=True)
    shape = grouped_shape(n, qs)
    axis_of = {q: 2 * i + 1 for i, q in enumerate(qs)}
    return shape, axis_of


def inverse_permutation(perm: Sequence[int]) -> tuple[int, ...]:
    inv = [0] * len(perm)
    for i, p in enumerate(perm):
        inv[p] = i
    return tuple(inv)


def amps_jit(**jit_kwargs):
    """``jax.jit`` for a dense applier (amps in as argument 0, amps out)
    whose result is LOWERED with the sharding of its argument.

    Left alone the partitioner chooses an output layout, and under jax 0.9
    the window GEMM over a sharded qubit comes back fully replicated: every
    device then holds, and from then on updates, the whole state (a register
    sized to fill the mesh no longer fits), while ``len(sharding.device_set)``
    still counts every device. ``out_shardings`` is fixed when a function is
    jitted, so there is one jitted form per sharding met (a process sees one
    or two meshes). A tracer hides its sharding: inside a jitted replay the
    register boundary constrains the result instead (``Qureg.put``)."""
    def deco(fn):
        plain = jax.jit(fn, **jit_kwargs)

        @functools.lru_cache(maxsize=None)
        def pinned(sharding):
            return jax.jit(fn, out_shardings=sharding, **jit_kwargs)

        @functools.wraps(fn)
        def call(amps, *args, **kwargs):
            sharding = (None if isinstance(amps, jax.core.Tracer)
                        else getattr(amps, "sharding", None))
            if isinstance(sharding, NamedSharding) and sharding.mesh.size > 1:
                return pinned(sharding)(amps, *args, **kwargs)
            return plain(amps, *args, **kwargs)

        return call

    return deco
