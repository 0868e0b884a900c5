"""Mesh/shard bookkeeping.

The state (2, 2^n) is block-sharded over the 1-D ``amps`` mesh axis into
D = 2^d chunks, exactly the reference's rank partition
(``numAmpsPerChunk = 2^n / numRanks``, QuEST_cpu.c:1296-1319): device r holds
flat indices [r*C, (r+1)*C), C = 2^(n-d). Hence qubit q is **local** iff
q < n - d (its amplitude pairs lie within one chunk -- the reference's
``halfMatrixBlockFitsInChunk`` predicate, QuEST_cpu_distributed.c:372-377),
and a **sharded** qubit q >= n - d is bit (q - (n-d)) of the device index.
"""

from __future__ import annotations

from jax.sharding import Mesh

from ..environment import AMP_AXIS

def local_qubit_count(n: int, mesh: Mesh | None) -> int:
    """Number of low qubits entirely local to each shard."""
    if mesh is None or mesh.size == 1:
        return n
    d = (mesh.size - 1).bit_length()
    return n - d


def device_groups(size: int, mask: int) -> list[list[int]]:
    """The ``size`` device indices in groups that differ only in the bits
    of ``mask``, each group ascending (so a member's place is what its
    masked bits spell, low bit first) and the groups by their first member:
    the ``axis_index_groups`` of a collective over those shard bits."""
    by_rest: dict[int, list[int]] = {}
    for r in range(size):
        by_rest.setdefault(r & ~mask, []).append(r)
    return list(by_rest.values())


def shard_info(n: int, mesh: Mesh | None):
    """(num_local_qubits, num_shard_qubits, axis_name)."""
    nl = local_qubit_count(n, mesh)
    return nl, n - nl, AMP_AXIS


def slice_chip_bits(mesh: Mesh | None, num_slices: int) -> int:
    """Number of intra-slice (ICI) shard bits of a slice-major pod
    topology: the device index's low bits address chips within a slice,
    the top log2(num_slices) bits cross slices (DCN). Rejects a slice
    count that does not evenly power-of-two-partition the mesh -- the
    slice-major device order is only meaningful when every slice holds
    the same power-of-two chip count."""
    ns = max(int(num_slices), 1)
    if ns & (ns - 1):
        raise ValueError(
            f"num_slices must be a power of two (got {ns}): slice-major "
            f"device order splits the shard bits at a bit boundary")
    size = 1 if mesh is None else mesh.size
    if ns > size or size % ns:
        raise ValueError(
            f"num_slices={ns} does not partition the {size}-device mesh "
            f"into equal power-of-two slices")
    return ((size // ns) - 1).bit_length()


def shard_bit_link(n: int, mesh: Mesh | None, num_slices: int,
                   qubit: int) -> str | None:
    """Which interconnect a comm op on sharded ``qubit`` rides: 'ici'
    (intra-slice chip axis, the low shard bits) or 'dcn' (inter-slice,
    the top log2(num_slices) shard bits); None for local qubits."""
    nl = local_qubit_count(n, mesh)
    if qubit < nl:
        return None
    return "ici" if (qubit - nl) < slice_chip_bits(mesh, num_slices) \
        else "dcn"
