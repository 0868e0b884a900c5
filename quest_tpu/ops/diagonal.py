"""Diagonal / phase-only kernels: no data movement, one fused pass.

The reference implements these as mask-parity loops (phaseShiftByTerm
``QuEST_cpu.c:3113``, multiRotateZ ``QuEST_cpu.c:3235-3285``). On TPU a phase
gate never reshapes or moves the state: the per-amplitude factor is computed
from flat-index bits (iota + shifts) and either gathered from the 2^t-entry
diagonal table or, for parity phases, derived from an XOR chain -- XLA fuses
the whole thing into one VPU pass over HBM, and it works unchanged on sharded
arrays (the iota is global under GSPMD).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .spy import records


def _flat_bits(num_flat: int, qubit: int):
    """Elementwise bit-q of the flat amplitude index, shape (1, num_flat).

    Built from a >=2-D iota (TPU requires it); stays fused into the consuming
    multiply -- no reshape of the state, no materialised index array."""
    i = jax.lax.broadcasted_iota(jnp.int32, (1, num_flat), 1)
    return (i >> qubit) & 1


def _ctrl_ok(num_flat: int, controls):
    sel = None
    for c in controls:
        b = _flat_bits(num_flat, c)
        sel = b if sel is None else sel & b
    return sel


def _apply_diagonal_flat(amps, diag, targets, controls, conj):
    """Layout-clean diagonal: phase factors computed elementwise over the
    *flat* (2, 2^n) state from index bits.

    The grouped-broadcast formulation reshapes the state to rank 2t+2 with
    2-sized trailing axes; on TPU such views materialise with (8, 128) tile
    padding -- observed 64x inflation (512 MB state -> 34 GB allocation) for
    a 5-target diagonal at 26 qubits. Here the state is never reshaped: the
    2^t-entry table is gathered by an index assembled from flat-index bits
    (the same formulation as the explicit distributed backend,
    parallel/exchange.py dist_apply_diag_phase), one pass at any width,
    sharding-transparent (iota is global)."""
    num = amps.shape[-1]
    rdtype = amps.dtype
    d = diag.astype(rdtype)
    dr, di = d[0], d[1]
    if conj:
        di = -di

    sel = jnp.zeros((1, num), jnp.int32)
    for k, q in enumerate(targets):
        sel = sel | (_flat_bits(num, q) << k)
    fr = jnp.take(dr, sel[0])
    fi = jnp.take(di, sel[0])

    if controls:
        ok = _ctrl_ok(num, controls)[0].astype(rdtype)
        fr = 1 + ok * (fr - 1)
        fi = ok * fi

    re = amps[0] * fr - amps[1] * fi
    im = amps[0] * fi + amps[1] * fr
    return jnp.stack([re, im])


@records
@partial(jax.jit, static_argnames=("n", "targets", "controls", "conj"), donate_argnums=(0,))
def apply_diagonal(amps, diag, *, n: int, targets: tuple[int, ...],
                   controls: tuple[int, ...] = (), conj: bool = False):
    """Multiply by a planar (2, 2^t) diagonal on ``targets`` (controls gate it
    to the all-1 subspace). Index convention matches apply_matrix: targets[0]
    is the least-significant bit of the diagonal's index.

    Covers phaseShift/sGate/tGate/rotateZ/controlledPhaseFlip/diagonalUnitary/
    applySubDiagonalOp (reference kernels ``QuEST_cpu.c:1339-1386,3113-3233``).
    """
    del n
    return _apply_diagonal_flat(amps, diag, targets, controls, conj)


@partial(jax.jit, static_argnames=("n", "qubits", "controls", "conj"), donate_argnums=(0,))
def apply_parity_phase(amps, theta, *, n: int, qubits: tuple[int, ...],
                       controls: tuple[int, ...] = (), conj: bool = False):
    """exp(-i theta/2 * Z x Z x ... x Z) on ``qubits`` -- multiRotateZ and its
    controlled variant (reference mask-parity kernel ``QuEST_cpu.c:3235-3285``).

    Computed elementwise over the flat state (no reshape, see
    :func:`_apply_diagonal_flat` for why): the factor is
    cos(theta/2) - i sin(theta/2) * (-1)^{parity of the target bits},
    with the parity an XOR chain over index bits gathering from a 2-entry
    phase table (the same formulation as :func:`_apply_diagonal_flat`) --
    one fused VPU pass, sharding-transparent. The table gather, rather
    than a multiply by the +-1 sign, keeps the kernel BIT-STABLE between
    a constant-folded theta and a runtime-parameter theta (the serving
    engine's parameterized replay): the sign-multiply form left the
    trailing complex multiply eligible for FMA contraction in one
    compilation but not the other, a 1-ulp divergence per parity gate.
    ``conj`` negates theta (density shadow op).
    """
    num = amps.shape[-1]
    rdtype = amps.dtype

    par = None
    for q in qubits:
        b = _flat_bits(num, q)
        par = b if par is None else par ^ b

    theta = jnp.asarray(theta, dtype=rdtype)
    if conj:
        theta = -theta
    c, s = jnp.cos(theta / 2), jnp.sin(theta / 2)
    fr = jnp.take(jnp.stack([c, c]), par[0])
    fi = jnp.take(jnp.stack([-s, s]), par[0])

    if controls:
        ok = _ctrl_ok(num, controls)[0].astype(rdtype)
        fr = 1 + ok * (fr - 1)
        fi = ok * fi

    re = amps[0] * fr - amps[1] * fi
    im = amps[0] * fi + amps[1] * fr
    return jnp.stack([re, im])


@partial(jax.jit, static_argnames=("conj",), donate_argnums=(0,))
def apply_full_diagonal(amps, elems, *, conj: bool = False):
    """Elementwise multiply by a full planar 2^n diagonal operator
    (applyDiagonalOp; reference kernel ``QuEST_cpu.c:3975-4030``). ``elems``
    (2, 2^n) is sharded like ``amps`` so the multiply is purely local."""
    er, ei = elems[0].astype(amps.dtype), elems[1].astype(amps.dtype)
    if conj:
        ei = -ei
    re = amps[0] * er - amps[1] * ei
    im = amps[0] * ei + amps[1] * er
    return jnp.stack([re, im])


@partial(jax.jit, static_argnames=("n",), donate_argnums=(0,))
def apply_full_diagonal_to_density(amps, elems, *, n: int):
    """applyDiagonalOp on a density matrix: rho -> D rho (left-multiply only,
    per the reference's densmatr_applyDiagonalOp). Row bits are the low n bits
    of the 2n-qubit flattening, so broadcast D along the column axis."""
    dim = 1 << n
    t = amps.reshape(2, dim, dim)  # [plane, col, row]
    er, ei = elems[0].astype(amps.dtype)[None, :], elems[1].astype(amps.dtype)[None, :]
    re = t[0] * er - t[1] * ei
    im = t[0] * ei + t[1] * er
    return jnp.stack([re, im]).reshape(2, -1)
