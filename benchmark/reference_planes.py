"""The plain reference for a register too large for the host replay: the tape
gate by gate in straightforward ``jax.numpy`` on float32 planes, no kernels,
no fusion of gates, sharded like the register it is given so that it fits.

``reference.run_statevector`` would need 2^31 complex128 amplitudes (32 GiB)
and minutes of host time a run; this replay runs where the register lives.
Nothing here imports the program: the gate table is ``reference._unitary``
and tests tie the replay to ``reference.run_statevector`` (complex128) on the
same tape at a small size.

The state is a pair of planes ``(re, im)``, each 2^n / 128 rows of 128 lanes.
One gate is one program and one sweep over the planes IN PLACE, block by
block (``_sweep``: 2^``BLOCK_BITS`` amplitudes a block, a ``fori_loop`` of
``dynamic_slice`` and ``dynamic_update_slice``), so that a gate's peak is the
state and a few blocks, and the program's output can stay on the chips
beside it (at 31 qubits over four chips 8 GiB a chip, where a whole-shard
pass with its partner and its result held 12 and sent the output to the
host). In a block the target is an axis of its own, an amplitude's partner is
the block flipped along that axis, and ``new = diag * x + off * partner`` in
complex arithmetic written out plane by plane (``apply_gate``). A state split
over a mesh axis is worked on shard by shard under ``shard_map``: a qubit
above the shard is a bit of the device's index, and a target there fetches
the partner device's block by a collective permute, the reference's
``exchangeStateVectors``.

What is not as plain as it could be is what the chip made so (PERF.md section
6, PR 30). No view cuts the 128 lanes (an axis shorter than that is padded to
it, 128 times the memory) or flips beside an axis of length 1 (the compiler
did not come back from one), so a target among the low ten qubits is one
(1024, 1024) matrix on whole tiles (``apply_tile_matrix``; the reference's
one matmul, at ``precision=HIGHEST``, float32 on the chip). The two planes
are arrays of their own and every block and every partner is in a buffer of
its own before the sum that uses it (``optimization_barrier``): with both
planes in one donated array, the chip wrote a gate's result over its input
while still reading the other plane from it, and a ``tGate`` lost 18% of the
norm. A qubit's bit is read from an iota of the whole block: as a 128-long
vector of factors broadcast along the lanes it was not what the chip
multiplied by.

``lower`` is the hook of the CONTROL, as in ``reference.py``: with it every
gate matrix and every written amplitude is rounded (``LOWER``: bfloat16 for a
float32 configuration).
"""

from __future__ import annotations

import functools
import math

import numpy as np

import reference


def bfloat16(x):
    """Round to bfloat16 and back, on the device."""
    import jax.numpy as jnp

    return x.astype(jnp.bfloat16).astype(jnp.float32)


#: the next precision below the configuration's (``control.LOWER`` on the host)
LOWER = {"float32": bfloat16}

#: a lane of the chip's (8, 128) tiles, and a whole tile
LANE_BITS, TILE_BITS = 7, 10
LANES = 1 << LANE_BITS

#: amplitudes of a block: 64 MiB a plane, a few of them a gate's temporaries
#: (more than a tile's; read when a gate's program is traced)
BLOCK_BITS = 24


def _sweep(re, im, gate, *, cut, whole, num_qubits: int, shard_axis=None):
    """``gate`` over the planes ``re``, ``im`` in place, block by block.

    The planes are viewed with their amplitudes cut into the axes ``cut``
    ((lowest bit, number of bits), outermost first; none of length 1 is
    made). A block takes the axes in ``whole`` (indices into ``cut``) whole
    and of the others the innermost first, up to 2^``BLOCK_BITS`` amplitudes.
    ``gate(xr, xi, by_bit) -> (nr, ni)`` sees a block of each plane without
    its axes of length 1; ``by_bit(bit, zero, one)`` is ``one`` where qubit
    ``bit`` of an amplitude's index is set, else ``zero``, by an iota of the
    whole block and the block's place. Under ``shard_map`` a qubit at or above
    ``num_qubits`` is a bit of the device's index along ``shard_axis``."""
    import jax
    import jax.numpy as jnp

    axes = [(i, low, bits) for i, (low, bits) in enumerate(cut) if bits]
    room = BLOCK_BITS - sum(bits for i, _, bits in axes if i in whole)
    taken = {}
    for i, _, bits in reversed(axes):
        taken[i] = bits if i in whole else max(0, min(bits, room))
        room -= 0 if i in whole else taken[i]
    shape = tuple(1 << bits for _, _, bits in axes)
    block = tuple(1 << taken[i] for i, _, _ in axes)
    inner = tuple(b for b in block if b > 1)
    grid = tuple(s // b for s, b in zip(shape, block))

    def body(k, planes):
        start = []
        for g, b in zip(reversed(grid), reversed(block)):
            start.insert(0, (k % g) * b)
            k = k // g

        def by_bit(bit, zero, one):
            if bit >= num_qubits:
                at, low = jax.lax.axis_index(shard_axis), num_qubits
            else:
                a, low = next((a, lo) for a, (_, lo, bits) in enumerate(axes)
                              if lo <= bit < lo + bits)
                at = start[a]
                if block[a] > 1:
                    at = at + jax.lax.broadcasted_iota(
                        jnp.int32, inner, sum(b > 1 for b in block[:a]))
            return jnp.where((at >> (bit - low)) & 1 == 1, one, zero)

        # a block of each plane in a buffer of its own before anything of it
        # is written back
        xs = jax.lax.optimization_barrier(tuple(
            jax.lax.dynamic_slice(p, start, block).reshape(inner)
            for p in planes))
        new = gate(*xs, by_bit)
        return tuple(jax.lax.dynamic_update_slice(p, v.reshape(block), start)
                     for p, v in zip(planes, new))

    xr, xi = jax.lax.fori_loop(0, math.prod(grid), body,
                               (re.reshape(shape), im.reshape(shape)))
    return xr.reshape(re.shape), xi.reshape(im.shape)


def apply_gate(re, im, m, *, num_qubits: int, target: int, controls: tuple,
               lower=None, shard_axis=None):
    """One gate whose target lies above a tile, on the planes ``re``, ``im``
    of 2^n amplitudes. ``m`` is (2, 2, 2): the real and the imaginary plane
    of its 2x2 matrix.

    The target is an axis of its own, whole in every block, along which the
    block flipped is every amplitude's partner. Under ``shard_map``
    (``shard_axis`` names the mesh axis) the planes are one device's shard of
    ``num_qubits`` LOCAL qubits, and a target at or above them has its
    partner on the partner device, fetched block by block by a collective
    permute."""
    import jax
    import jax.numpy as jnp

    n = num_qubits
    if target >= n:
        cut, whole = [(LANE_BITS, n - LANE_BITS), (0, LANE_BITS)], (1,)
    else:
        cut = [(target + 1, n - target - 1), (target, 1),
               (LANE_BITS, target - LANE_BITS), (0, LANE_BITS)]
        whole = (1, 3)

    def gate(xr, xi, by_bit):
        if target >= n:
            step = 1 << (target - n)
            pairs = [(d, d ^ step)
                     for d in range(jax.lax.axis_size(shard_axis))]
            yr, yi = (jax.lax.ppermute(v, shard_axis, pairs)
                      for v in (xr, xi))
        else:
            # the target's axis follows the block's part of the axis above it
            along = xr.ndim - 3
            yr, yi = jnp.flip(xr, along), jnp.flip(xi, along)
        # the partners in buffers of their own too: the sums below then read
        # every operand where they write
        xr, xi, yr, yi = jax.lax.optimization_barrier((xr, xi, yr, yi))
        dr, di = (by_bit(target, m[p, 0, 0], m[p, 1, 1]) for p in (0, 1))
        cr, ci = (by_bit(target, m[p, 0, 1], m[p, 1, 0]) for p in (0, 1))
        nr = dr * xr - di * xi + cr * yr - ci * yi
        ni = dr * xi + di * xr + cr * yi + ci * yr
        if lower is not None:
            nr, ni = lower(nr), lower(ni)
        for c in controls:
            nr, ni = by_bit(c, xr, nr), by_bit(c, xi, ni)
        return nr, ni

    return _sweep(re, im, gate, cut=cut, whole=whole, num_qubits=n,
                  shard_axis=shard_axis)


def tile_matrix(m, target: int, controls) -> np.ndarray:
    """(2, 1024, 1024) float32 planes of the gate ``m`` on qubit ``target``
    of a tile under the tile's qubits ``controls``, transposed for
    ``x @ K``: K[l, l'] is what amplitude l of a tile gives to amplitude l'."""
    at = np.arange(1 << TILE_BITS)
    on = np.ones(at.shape, dtype=bool)
    for c in controls:
        on &= (at >> c) & 1 == 1
    bit = (at >> target) & 1
    k = np.zeros((at.size, at.size), dtype=np.complex128)
    k[at, at] = np.where(on, m[bit, bit], 1.0)
    k[at, at ^ (1 << target)] = np.where(on, m[1 - bit, bit], 0.0)
    return np.stack([k.real, k.imag]).astype(np.float32)


def apply_tile_matrix(re, im, k, *, num_qubits: int, controls: tuple,
                      lower=None, shard_axis=None):
    """A gate inside a tile: whole tiles of 1024 amplitudes times its
    (1024, 1024) matrix ``k`` (``tile_matrix``, which holds the controls
    inside the tile; ``controls`` are those above it). The one matmul of the
    reference, at ``precision=HIGHEST``: float32 on the chip."""
    import jax
    import jax.numpy as jnp

    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)

    def gate(xr, xi, by_bit):
        # the block's rows as whole tiles (viewing the PLANE as tiles would
        # cost the chip a second copy of it: another tiling of its rows)
        tr, ti = (x.reshape(-1, 1 << TILE_BITS) for x in (xr, xi))
        nr = (dot(tr, k[0]) - dot(ti, k[1])).reshape(xr.shape)
        ni = (dot(tr, k[1]) + dot(ti, k[0])).reshape(xi.shape)
        if lower is not None:
            nr, ni = lower(nr), lower(ni)
        for c in controls:
            nr, ni = by_bit(c, xr, nr), by_bit(c, xi, ni)
        return nr, ni

    return _sweep(re, im, gate, whole=(1,), num_qubits=num_qubits,
                  cut=[(LANE_BITS, num_qubits - LANE_BITS), (0, LANE_BITS)],
                  shard_axis=shard_axis)


def _shard_axis(sharding):
    """The mesh axis an array's amplitudes are split over, or None."""
    return next((a for a in getattr(sharding, "spec", ()) if a is not None),
                None)


def _rows(sharding):
    """The sharding of a plane of rows, split as ``sharding`` splits the
    amplitudes of a (2, 2^n) array."""
    import jax

    axis = _shard_axis(sharding)
    if axis is None:
        return sharding
    return jax.sharding.NamedSharding(sharding.mesh,
                                      jax.sharding.PartitionSpec(axis, None))


@functools.lru_cache(maxsize=None)
def _program(apply, rows, num_qubits: int, **static):
    """``apply`` as a program of its own on planes sharded ``rows`` (both
    given up and written in place, the results sharded alike): a gate's peak
    is the state and its blocks, whatever the tape's length. A state split
    over a mesh axis is worked on shard by shard (``shard_map``), so that
    nothing is left to the partitioner."""
    import jax

    axis = _shard_axis(rows)
    local = num_qubits
    if axis is not None:
        local -= rows.mesh.shape[axis].bit_length() - 1
    if local < TILE_BITS:
        raise ValueError(f"reference: {local} qubits a device are fewer than "
                         f"the {TILE_BITS} of a tile")
    fn = functools.partial(apply, num_qubits=local, shard_axis=axis, **static)
    if axis is not None:
        spec, whole = rows.spec, jax.sharding.PartitionSpec()
        fn = jax.shard_map(fn, mesh=rows.mesh, in_specs=(spec, spec, whole),
                           out_specs=(spec, spec), check_vma=False)
    return jax.jit(fn, donate_argnums=(0, 1), out_shardings=(rows, rows))


def gates_of(ops) -> list:
    """``[(target, 2x2 complex128 matrix, controls), ...]`` of a tape."""
    out = []
    for name, args in ops:
        u = reference._unitary(name, args)
        if u is None:
            raise ValueError(f"reference: {name!r} is not a state-vector gate")
        out.append((int(u[0]), np.asarray(u[1], dtype=np.complex128),
                    tuple(int(c) for c in u[2])))
    return out


def split(planes) -> tuple:
    """The planes ``(re, im)`` of a (2, 2^n) array, each rows of 128 lanes,
    split over the devices as its amplitudes are."""
    import jax

    rows = _rows(planes.sharding)

    def cut(x):
        # a plane first, then its rows: the other way round the chip's
        # compiler holds a second whole copy while it cuts
        return x[0].reshape(-1, LANES), x[1].reshape(-1, LANES)

    return jax.jit(cut, out_shardings=(rows, rows))(planes)


def run_statevector(planes, num_qubits: int, ops, lower=None) -> tuple:
    """The tape applied to the (2, 2^n) float32 ``planes``, gate by gate:
    the planes ``(re, im)`` of the result (``split``'s form), sharded as
    ``planes`` is. ``planes`` is given up (deleted once split): beside the
    result there is no room for it."""
    import jax.numpy as jnp

    re, im = split(planes)
    planes.delete()
    rows = re.sharding
    if lower is not None:
        re, im = lower(re), lower(im)
    for target, m, controls in gates_of(ops):
        if target >= TILE_BITS:
            program = _program(apply_gate, rows, num_qubits, target=target,
                               controls=controls, lower=lower)
            arg = np.stack([m.real, m.imag]).astype(np.float32)
        else:
            program = _program(
                apply_tile_matrix, rows, num_qubits, lower=lower,
                controls=tuple(c for c in controls if c >= TILE_BITS))
            arg = tile_matrix(m, target,
                              [c for c in controls if c < TILE_BITS])
        re, im = program(re, im, arg if lower is None
                         else lower(jnp.asarray(arg)))
    return re, im


def _max_and_sum(re, im) -> tuple:
    """(max, sum) of |amplitude|^2 over the planes, in one pass."""
    import jax.numpy as jnp

    a2 = re * re + im * im
    return jnp.max(a2), jnp.sum(a2)


def errors(got, want) -> tuple:
    """``reference.errors`` of two results of ``run_statevector`` (or
    ``split``), every amplitude, reduced where they live: (max |got - want|
    / max |want|, ||got - want|| / ||want||). Two passes, of the difference
    and of ``want``: as one program the chip's compiler keeps a whole plane
    of temporaries."""
    import jax

    d_max, d_sum = jax.jit(lambda gr, gi, wr, wi: _max_and_sum(
        gr - wr, gi - wi))(*got, *want)
    w_max, w_sum = jax.jit(_max_and_sum)(*want)
    return (math.sqrt(float(d_max) / float(w_max)),
            math.sqrt(float(d_sum) / float(w_sum)))


def total_probability(planes) -> float:
    """sum |amplitude|^2 of the planes ``(re, im)``, reduced where they live."""
    import jax

    return float(jax.jit(_max_and_sum)(*planes)[1])
