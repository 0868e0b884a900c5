"""Density-matrix decoherence kernels.

Design (mirrors the reference's Choi trick, generalised): a density matrix on
n qubits is stored as a 2n-qubit state-vector with row bits low and column
bits high (QuEST.c:8-10). Any Kraus channel on targets T becomes *one* dense
matrix -- the superoperator sum_k conj(K_k) (x) K_k -- applied to qubits
(T, T+n) with the ordinary gate engine (:func:`..ops.apply.apply_matrix`).
The reference does the same (Kraus -> superoperator -> 2t-qubit "unitary",
QuEST_common.c:581-638) but then needs bespoke MPI half-chunk exchanges for
the non-local cases (QuEST_cpu_distributed.c:569-868); here XLA's partitioner
handles that automatically.

Purely-diagonal channels (dephasing) skip the matmul entirely and use the
broadcasted-factor path, like the reference's dedicated dephase kernels
(QuEST_cpu.c:60-135).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import precision
from ..environment import active_pallas_mesh
from . import apply, cplx, diagonal
from .layout import amps_jit
from .spy import records


def kraus_superoperator(kraus_ops) -> np.ndarray:
    """sum_k conj(K_k) (x) K_k, ordered for application on targets
    (T..., T+n...): row bits (K's action) are the low half of the matrix index,
    column bits (conj(K)'s action) the high half.

    Matches the reference's populateKrausSuperOperator (QuEST_common.c:581-638).
    """
    ops = [np.asarray(k, dtype=np.complex128) for k in kraus_ops]
    dim = ops[0].shape[0]
    s = np.zeros((dim * dim, dim * dim), dtype=np.complex128)
    for k in ops:
        s += np.kron(np.conj(k), k)
    return s


#: up to this many flattened qubits the one-pass superoperator apply is used;
#: beyond it, the scattered (q, q+n) target pair would take the grouped-
#: transpose path whose tile padding explodes at scale (see ops.apply), so
#: the channel is applied as a sum of per-Kraus-term window passes instead.
_SUPEROP_MAX_QUBITS = 22


def choi_kraus(superop) -> list[tuple[float, np.ndarray]]:
    """Decompose a superoperator (ordered as :func:`kraus_superoperator`,
    sum_k conj(K) (x) K) into weighted Kraus terms [(sign, K_i), ...] via
    the eigendecomposition of its Choi matrix. Signs carry non-CP maps
    (mixNonTP* family); CP maps yield all +1."""
    d2 = superop.shape[0]
    d = int(np.sqrt(d2))
    s = np.asarray(superop, dtype=np.complex128).reshape(d, d, d, d)
    # S[(c',r'),(c,r)] -> M[(r',r),(c',c)] = sum_k vec(K_k) vec(K_k)^dagger
    m = s.transpose(1, 3, 0, 2).reshape(d2, d2)
    vals, vecs = np.linalg.eigh((m + m.conj().T) / 2)
    out = []
    for lam, v in zip(vals, vecs.T):
        if abs(lam) < 1e-12:
            continue
        out.append((float(np.sign(lam)), np.sqrt(abs(lam)) * v.reshape(d, d)))
    return out


@records
def apply_channel(amps, superop, *, n: int, targets: tuple[int, ...],
                  depol: float | None = None):
    """Apply a (numpy complex) superoperator to density targets: qubits
    (T..., T+n...) of the flattened 2n-qubit state.

    ``depol`` is what ``mixDepolarising`` / ``mixTwoQubitDepolarising`` say
    of their call beside its superoperator: the probability. Nothing here
    reads it; a planner that captures the call does (``events.GateEvent``),
    and lowers the channel to its closed form (``planner._lower_channel``).

    Large registers use the Kraus-sum formulation: rho' = sum_i s_i K_i rho
    K_i^dagger, each term two layout-clean single-group passes (row bits,
    then conjugated column bits) -- the TPU equivalent of the reference's
    pair-exchange channel protocol (QuEST_cpu_distributed.c:724-868).

    Under an explicit_mesh context, every dense application routes through
    the distributed scheduler, so channels on sharded qubits take the same
    relocation-planner path as gates (the analogue of the reference's
    half-chunk depolarising/damping exchanges,
    QuEST_cpu_distributed.c:535-868) and show up in the plan stats."""
    from ..parallel import scheduler as _dist  # lazy: parallel stands on ops

    sched = _dist.active()
    if sched is not None:
        sched.stats["channel_superops"] += 1
    if 2 * n <= _SUPEROP_MAX_QUBITS:
        ext_targets = tuple(targets) + tuple(q + n for q in targets)
        so = cplx.from_complex(superop, amps.dtype)
        if sched is not None:
            return sched.apply_matrix(amps, so, n=2 * n, targets=ext_targets)
        return apply.apply_matrix(amps, so, n=2 * n, targets=ext_targets)

    terms = choi_kraus(superop)
    if sched is not None:
        shifted = tuple(q + n for q in targets)
        out = None
        for sign, k in terms:
            km = jnp.asarray(np.stack([k.real, k.imag]), dtype=amps.dtype)
            t = sched.apply_matrix(amps + 0, km, n=2 * n, targets=tuple(targets))
            t = sched.apply_matrix(t, km, n=2 * n, targets=shifted, conj=True)
            out = _acc_kraus_term(out, sign, t)
        return out
    if len(targets) == 1 and jax.default_backend() == "tpu":
        # non-TPU backends stay on the XLA engine path: fused_local_run
        # would fall into the Pallas interpreter there, which is orders of
        # magnitude slower than _apply_kraus_sum at these sizes
        new = _kraus_sum_pallas(amps, terms, n, targets[0])
        if new is not None:
            return new
    signs = tuple(s for s, _ in terms)
    ks = np.stack([np.stack([k.real, k.imag]) for _, k in terms])
    return _apply_kraus_sum(amps, jnp.asarray(ks, dtype=amps.dtype),
                            n=n, targets=tuple(targets), signs=signs)


def _kraus_sum_pallas(amps, terms, n, t, lq=None):
    """Single-target Kraus sum as ONE fused Pallas pass: the whole channel
    (every term's K on the row qubit + conj(K) on the column qubit, with
    the signed accumulation) runs in-register per tile via the 'kraus1'
    kernel op -- one HBM read+write total. Returns None when the path
    doesn't apply (multi-device, row qubit above the tile, sub-tile state).

    The column qubit t+n usually sits above the tile (the density state
    has 2n qubits); its relocation to the top in-tile slot is then FOLDED
    into the pass's load/store DMA (fused_local_run's load_swap_hi) --
    the free generalisation of the reference's half-chunk density
    exchanges (QuEST_cpu_distributed.c:535-868), which pay dedicated
    pack/exchange/unpack passes. Round 2 paid ~2 passes per Kraus term
    plus 2 relocation transposes; this is one pass, always. ``lq``
    overrides the tile limit for tests."""
    from . import pallas_gates as PG  # lazy: Pallas

    nsv = 2 * n
    if amps.shape[-1] < 2 * PG._LANES:
        return None
    if not precision._mosaic_supports(amps.dtype):
        return None  # f64 on TPU: no Mosaic lowering (engine path)
    sharding = getattr(amps, "sharding", None)
    if sharding is not None and len(sharding.device_set) > 1:
        return None  # pallas_call would gather the shards
    if (isinstance(amps, jax.core.Tracer)
            and active_pallas_mesh() is not None):
        return None  # traced replay of a register known to be sharded
    if lq is None:
        lq = PG.local_qubits(nsv)
    c = t + n
    hi = None
    if c >= lq:
        # fold the 1-bit relocation [lq-1, lq) <-> [c, c+1) into the DMA;
        # it would displace a row qubit sitting at lq-1 (impossible for
        # single-chip sizes, but guard anyway)
        if t >= lq - 1:
            return None
        hi = c
        c = lq - 1
    if t >= lq:
        return None  # row qubit itself above the tile: engine path
    terms_h = tuple((float(s), PG.HashableMatrix(k)) for s, k in terms)
    return _kraus_sum_pallas_run(amps + 0, n=n, t=t, c=c, hi=hi,
                                 terms=terms_h,
                                 sublanes=1 << (lq - PG.LANE_BITS))


def _acc_kraus_term(out, sign, term):
    """out + sign * term (None-seeded), the shared Kraus accumulator."""
    term = sign * term if sign != 1.0 else term
    return term if out is None else out + term


@partial(jax.jit, static_argnames=("n", "t", "c", "hi", "terms", "sublanes"),
         donate_argnums=(0,))
def _kraus_sum_pallas_run(amps, *, n, t, c, hi, terms, sublanes):
    """The whole fused-Kraus channel as one kernel pass (see
    _kraus_sum_pallas); ``hi`` is the grid-bit column position relocated
    into the top tile slot by the folded load/store swaps. ``sublanes``
    pins the tile geometry to the ``lq`` the caller planned against."""
    from . import pallas_gates as PG  # lazy: Pallas

    k = 0 if hi is None else 1
    return PG.fused_local_run(
        amps, n=2 * n, ops=(("kraus1", t, c, terms),), sublanes=sublanes,
        load_swap_k=k, load_swap_hi=hi,
        store_swap_k=k, store_swap_hi=hi)


@amps_jit(static_argnames=("n", "targets", "signs"), donate_argnums=(0,))
def _apply_kraus_sum(amps, ks, *, n: int, targets: tuple[int, ...],
                     signs: tuple[float, ...]):
    shifted = tuple(q + n for q in targets)
    out = None
    for i, sign in enumerate(signs):
        t = apply.apply_matrix(amps + 0, ks[i], n=2 * n, targets=targets)
        t = apply.apply_matrix(t, ks[i], n=2 * n, targets=shifted, conj=True)
        out = _acc_kraus_term(out, sign, t)
    return out


def dephase_factors_1q(prob: float) -> np.ndarray:
    """Diagonal of the 1-qubit dephasing superoperator on (q, q+n):
    off-diagonal (row bit != col bit) scaled by 1-2p
    (densmatr_mixDephasing via densmatr_oneQubitDegradeOffDiagonal,
    QuEST_cpu.c:60-105)."""
    f = 1 - 2 * prob
    return np.array([1, f, f, 1], dtype=np.complex128)


def dephase_factors_2q(prob: float) -> np.ndarray:
    """Diagonal on (q1, q2, q1+n, q2+n): rho -> (1-p)rho + p/3 (Z1 r Z1 +
    Z2 r Z2 + Z1Z2 r Z1Z2); element factor (1-p) + p/3 (s1 + s2 + s1 s2) with
    s_i = sign agreement of row/col bit i (densmatr_mixTwoQubitDephasing,
    QuEST_cpu.c:84-135). Index bits: (b_{q2+n} b_{q1+n} b_{q2} b_{q1})."""
    d = np.empty(16, dtype=np.complex128)
    p = prob
    for idx in range(16):
        r1, r2, c1, c2 = (idx >> 0) & 1, (idx >> 1) & 1, (idx >> 2) & 1, (idx >> 3) & 1
        s1 = 1 if r1 == c1 else -1
        s2 = 1 if r2 == c2 else -1
        d[idx] = (1 - p) + p / 3 * (s1 + s2 + s1 * s2)
    return d


@records
def _diag_dispatch(amps, d, *, n, targets):
    """Dephasing diagonals via the explicit scheduler when one is active
    (comm-free by construction, counted in its plan stats)."""
    from ..parallel import scheduler as _dist  # lazy: parallel stands on ops

    sched = _dist.active()
    if sched is not None:
        return sched.apply_diagonal(amps, d, n=n, targets=targets)
    return diagonal.apply_diagonal(amps, d, n=n, targets=targets)


def apply_dephasing(amps, prob, *, n: int, target: int):
    d = cplx.from_complex(dephase_factors_1q(prob), amps.dtype)
    return _diag_dispatch(amps, d, n=2 * n, targets=(target, target + n))


def apply_two_qubit_dephasing(amps, prob, *, n: int, q1: int, q2: int):
    d = cplx.from_complex(dephase_factors_2q(prob), amps.dtype)
    return _diag_dispatch(amps, d, n=2 * n, targets=(q1, q2, q1 + n, q2 + n))


def depolarising_kraus(prob: float):
    """(1-p) rho + p/3 (X r X + Y r Y + Z r Z) (mixDepolarising, QuEST.h:4051).
    Operators come from the canonical channel table (quest_tpu.channels),
    shared with the trajectory sampler."""
    from ..channels import depolarising_kraus as _k
    return _k(prob)


def two_qubit_depolarising_superop(prob: float) -> np.ndarray:
    """rho -> (1-p) rho + p/15 sum_{(A,B) != (I,I)} (A x B) rho (A x B)
    (mixTwoQubitDepolarising, QuEST.h:4156). Built from the canonical
    16-operator Kraus list (quest_tpu.channels.two_qubit_depolarising_kraus)."""
    from ..channels import two_qubit_depolarising_kraus as _k
    return kraus_superoperator(_k(prob))


def damping_kraus(prob: float):
    """Amplitude damping (mixDamping, QuEST.h:4089); canonical operators
    from quest_tpu.channels."""
    from ..channels import damping_kraus as _k
    return _k(prob)


def pauli_kraus(px: float, py: float, pz: float):
    """mixPauli as a 4-operator Kraus map (QuEST_common.c:740-760); canonical
    operators from quest_tpu.channels."""
    from ..channels import pauli_kraus as _k
    return _k(px, py, pz)
