"""The plain reference for a density register too large for the host replay
and touched on every qubit: the tape entry by entry in straightforward
``jax.numpy`` on float32 planes, on the chip, one sweep in place an entry.

``reference.run_density_blocks`` follows sampled blocks of fixed spectator
bits, which are closed only under a tape of few qubits (at most 8); a tape
that touches all 15 leaves no spectator. This replay works on the whole
register where it lives, as ``reference_planes`` does for a state-vector, and
by its means: the planes ``(re, im)`` as rows of 128 lanes, ``_sweep`` block by
block in place, one program an entry (``_program``). Nothing here imports the
program.

A density matrix of ``n`` qubits is a vector of ``2n``: element rho[row, col]
at flat index ``col * 2^n + row`` (``reference.py``'s convention), so the row
qubit ``q`` is bit ``q`` and its column twin bit ``q + n``.

- A gate ``U`` on ``q`` under controls ``c`` is ``rho -> U rho U^dagger``:
  ``U`` on bit ``q`` under bits ``c``, then ``conj(U)`` on bit ``q + n`` under
  bits ``c + n`` -- two of ``reference_planes``' own sweeps.
- A channel is written from its definition in ``QuEST.h``, term by term, and
  not from any lowering of the program's: ``mixDepolarising(q, p)`` is
  ``(1 - p) rho + p/3 (X rho X + Y rho Y + Z rho Z)``,
  ``mixTwoQubitDepolarising(q1, q2, p)`` is ``(1 - p) rho + p/15 sum (A x B)
  rho (A x B)`` over the 15 pairs of Paulis that are not both the identity. A
  Pauli acts on an index bit as what it is: ``X`` and ``Y`` take the element
  whose bit is flipped, ``Y`` and ``Z`` give a sign by the bit (``Y``'s two
  factors of ``i`` on the row and ``-i`` on the column make one sign), so
  ``P rho P`` is the partner element under both flips times the product of
  the row's and the column's sign. In a block the column bit, and a row bit
  above a tile, are whole axes, so that a channel's whole group of 4 or 16
  elements lies inside it and a partner is the block flipped along an axis; a
  row bit inside a tile is flipped by the tile's (1024, 1024) permutation
  (``reference_planes.tile_matrix`` of ``X``: at ``precision=HIGHEST`` a
  product with 0 and 1, exact).

``lower`` is the hook of the CONTROL, as in ``reference_planes``: with it
every gate matrix, every channel weight and every written amplitude is
rounded to the next precision below (bfloat16 for a float32 configuration).
"""

from __future__ import annotations

import functools
import itertools

import numpy as np

import reference
import reference_planes as planes

TILE_BITS = planes.TILE_BITS

#: (flips its bit, signs by its bit) of the Paulis I, X, Y, Z
PAULIS = ((False, False), (True, False), (True, True), (False, True))


def channel_of(name, args):
    """(row targets, probability) of a depolarising tape entry."""
    if name == "mixDepolarising":
        return (int(args[0]),), float(args[1])
    if name == "mixTwoQubitDepolarising":
        return (int(args[0]), int(args[1])), float(args[2])
    raise ValueError(f"reference: {name!r} is no channel of this replay")


def inside_tile(targets, n: int) -> list:
    """The row and column bits of the row qubits ``targets`` that lie inside
    a tile (at 15 qubits row bits only: the columns start at bit 15)."""
    return [b for t in targets for b in (t, t + n) if b < TILE_BITS]


def _axes_in_block(cut, whole) -> dict:
    """Where each axis of ``cut`` lies in the blocks ``planes._sweep`` hands a
    gate (index into ``cut`` -> axis of the block), by its own rule: the
    ``whole`` axes whole, of the others the innermost first up to
    2^``BLOCK_BITS`` amplitudes, and an axis of which a block holds one index
    is not an axis of the block."""
    axes = [(i, bits) for i, (_, bits) in enumerate(cut) if bits]
    room = planes.BLOCK_BITS - sum(bits for i, bits in axes if i in whole)
    present = []
    for i, bits in reversed(axes):
        taken = bits if i in whole else max(0, min(bits, room))
        room -= 0 if i in whole else taken
        if taken:
            present.insert(0, i)
    return {i: a for a, i in enumerate(present)}


def apply_channel(re, im, flips, *, num_qubits: int, targets: tuple,
                  prob: float, lower=None, shard_axis=None):
    """One depolarising channel on the row qubits ``targets`` (one or two) of
    the planes ``re``, ``im`` of a density register of ``num_qubits`` / 2
    qubits. ``flips`` is (k, 1024, 1024): the tile's permutation that flips
    a bit, for each bit of ``inside_tile(targets, n)`` in that order (one
    unused plane where there is none)."""
    import jax
    import jax.numpy as jnp

    n = num_qubits // 2
    pairs = [(t, t + n) for t in targets]
    # every column bit and every row bit above the tile an axis of its own
    high = sorted({b for pair in pairs for b in pair if b >= TILE_BITS},
                  reverse=True)
    cut, top = [], num_qubits
    for b in high:
        cut += [(b + 1, top - b - 1), (b, 1)]
        top = b
    cut += [(planes.LANE_BITS, top - planes.LANE_BITS),
            (0, planes.LANE_BITS)]
    at = {b: 2 * j + 1 for j, b in enumerate(high)}
    whole = tuple(at.values()) + (len(cut) - 1,)
    axis = _axes_in_block(cut, whole)
    inside = inside_tile(targets, n)
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    weights = [1.0 - prob, prob / (4 ** len(targets) - 1)]
    if lower is not None:
        weights = [lower(jnp.float32(w)) for w in weights]

    def gate(xr, xi, by_bit):
        def flipped(x, pair):
            """``x`` with the row and the column bit of ``pair`` flipped."""
            for bit in pair:
                if bit >= TILE_BITS:
                    x = jnp.flip(x, axis[at[bit]])
                else:
                    x = dot(x.reshape(-1, 1 << TILE_BITS),
                            flips[inside.index(bit)]).reshape(x.shape)
            return x

        def sign(pair):
            """+1 where the row and the column bit agree, -1 elsewhere."""
            return by_bit(pair[0], 1.0, -1.0) * by_bit(pair[1], 1.0, -1.0)

        signs = [sign(pair) for pair in pairs]
        # the partners under every set of flipped pairs, each in a buffer
        # of its own before the sums that read it (``planes.apply_gate``)
        partner = {(False,) * len(pairs): (xr, xi)}
        for which in itertools.product((False, True), repeat=len(pairs)):
            if which in partner:
                continue
            j = which.index(True)
            less = which[:j] + (False,) + which[j + 1:]
            partner[which] = tuple(flipped(x, pairs[j])
                                   for x in partner[less])
        partner = jax.lax.optimization_barrier(partner)
        sums = [jnp.zeros_like(xr), jnp.zeros_like(xi)]
        for word in itertools.product(PAULIS, repeat=len(pairs)):
            if not any(f or s for f, s in word):
                continue        # the identity on every target: no term
            yr, yi = partner[tuple(f for f, _ in word)]
            for (_, signed), sgn in zip(word, signs):
                if signed:
                    yr, yi = sgn * yr, sgn * yi
            sums = [sums[0] + yr, sums[1] + yi]
        nr = weights[0] * xr + weights[1] * sums[0]
        ni = weights[0] * xi + weights[1] * sums[1]
        if lower is not None:
            nr, ni = lower(nr), lower(ni)
        return nr, ni

    return planes._sweep(re, im, gate, cut=cut, whole=whole,
                         num_qubits=num_qubits, shard_axis=shard_axis)


def _gate_program(rows, nsv: int, target: int, m, controls, lower):
    """(program, its matrix argument) of one of ``reference_planes``' sweeps:
    ``m`` on bit ``target`` under the bits ``controls``."""
    import jax.numpy as jnp

    if target >= TILE_BITS:
        program = planes._program(planes.apply_gate, rows, nsv,
                                  target=target, controls=controls,
                                  lower=lower)
        arg = np.stack([m.real, m.imag]).astype(np.float32)
    else:
        above = tuple(c for c in controls if c >= TILE_BITS)
        program = planes._program(planes.apply_tile_matrix, rows, nsv,
                                  lower=lower, controls=above)
        arg = planes.tile_matrix(m, target,
                                 [c for c in controls if c not in above])
    return program, (arg if lower is None else lower(jnp.asarray(arg)))


def run_density(re, im, num_qubits: int, ops, lower=None) -> tuple:
    """The tape applied to the planes ``(re, im)`` (rows of 128 lanes, given
    up and written in place) of a density register of ``num_qubits`` qubits:
    the planes of the result."""
    import jax

    n, nsv = num_qubits, 2 * num_qubits
    rows = re.sharding
    if lower is not None:
        rounded = jax.jit(lower, donate_argnums=(0,))
        re, im = rounded(re), rounded(im)
    x = np.array([[0, 1], [1, 0]], dtype=np.complex128)
    for name, args in ops:
        u = reference._unitary(name, args)
        if u is not None:
            t, m, ctl = int(u[0]), np.asarray(u[1], np.complex128), u[2]
            for shift, mat in ((0, m), (n, np.conj(m))):
                program, arg = _gate_program(
                    rows, nsv, t + shift, mat,
                    tuple(int(c) + shift for c in ctl), lower)
                re, im = program(re, im, arg)
            continue
        targets, prob = channel_of(name, args)
        inside = inside_tile(targets, n)
        flips = (np.stack([planes.tile_matrix(x, b, [])[0] for b in inside])
                 if inside else np.zeros((1, 1, 1), np.float32))
        program = planes._program(apply_channel, rows, nsv, targets=targets,
                                  prob=prob, lower=lower)
        re, im = program(re, im, flips)
    return re, im


def trace(re, num_qubits: int) -> float:
    """Re tr(rho) of the planes' real one: its elements at a stride of
    2^n + 1, gathered where they lie and summed in float64 on the host."""
    at = np.arange(1 << num_qubits, dtype=np.int64) * ((1 << num_qubits) + 1)
    diag = re[(at // planes.LANES).astype(np.int32),
              (at % planes.LANES).astype(np.int32)]
    return float(np.sum(np.asarray(diag), dtype=np.float64))
