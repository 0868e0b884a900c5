"""Scalar reductions: inner products, norms, purity, fidelity, distances.

Reference kernels: statevec_calcInnerProductLocal + MPI_Allreduce
(``QuEST_cpu_distributed.c:35-51``), calcTotalProb with Kahan summation
(``:90-119``), densmatr purity/fidelity/HS-distance/inner-product loops
(``QuEST_cpu.c:878-1130``). Each is a fused elementwise + ``jnp.sum`` here;
on sharded inputs XLA emits local reduce + psum (the Allreduce analogue).

States are planar (2, 2^n) float arrays; results are real scalars or (re, im)
pairs.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _acc(x):
    return x.astype(jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)


def _pairwise_sum(flat):
    """Pairwise (cascade) summation: rounding error grows O(log N) instead
    of naive accumulation's O(N). ADJACENT pairing (2i, 2i+1) keeps every
    add shard-local on block-sharded inputs -- a front/back-half split would
    cross shard boundaries and turn each reduction into O(N) collective
    traffic. Total cost is ~2x one bandwidth pass, like a plain sum."""
    m = flat.shape[0]
    while m > 1 and m % 2 == 0:
        flat = flat.reshape(-1, 2).sum(axis=-1)
        m //= 2
    return jnp.sum(flat)


def _pairwise_sum_rows(x):
    """Rowwise pairwise (cascade) summation over the LAST axis of a 2-D
    array: the marginal-group analogue of :func:`_pairwise_sum`, same
    O(log N) error growth and same adjacent (2i, 2i+1) pairing so every
    add stays shard-local on block-sharded rows."""
    m = x.shape[-1]
    while m > 1 and m % 2 == 0:
        x = x.reshape(x.shape[0], -1, 2).sum(axis=-1)
        m //= 2
    return jnp.sum(x, axis=-1)


def _csum(x):
    """Compensated reduction of ``x`` (any shape).

    The reference protects its f32/f64 norm and trace accumulations with
    Kahan summation precisely because low precision drifts over 2^N terms
    (statevec_calcTotalProb, QuEST_cpu_distributed.c:62-119). Here: with
    x64 enabled, accumulate in f64 (error ~1e-16, strictly better than f32
    Kahan); with x64 off (the on-TPU f32 configuration), pairwise-sum --
    measured 2^24-amp calcTotalProb error ~1e-7 vs ~1e-5 for the naive
    jnp.sum this replaces."""
    if jax.config.jax_enable_x64:
        return jnp.sum(x.astype(jnp.float64))
    return _pairwise_sum(x.reshape(-1))


def csum_rows(x):
    """Compensated ROWWISE reduction of a 2-D array over its last axis --
    the marginal-group accumulation of ``ops.measure._group_outcome_probs``
    (round 19: the bare ``.sum(axis=1)`` it replaces drifted ~1e-5 at 20q+
    f32 marginals while the total-probability path already cascaded).
    Same policy as :func:`_csum`: f64 accumulate when x64 is on, adjacent-
    pair cascade otherwise."""
    if jax.config.jax_enable_x64:
        return jnp.sum(x.astype(jnp.float64), axis=-1)
    return _pairwise_sum_rows(x)


@jax.jit
def inner_product(bra, ket):
    """<bra|ket> with bra conjugated (statevec_calcInnerProduct); returns
    a (re, im) pair."""
    re = _csum(bra[0] * ket[0] + bra[1] * ket[1])
    im = _csum(bra[0] * ket[1] - bra[1] * ket[0])
    return re, im


@jax.jit
def total_prob_statevec(amps):
    """sum |amp|^2 (statevec_calcTotalProb, Kahan in the reference)."""
    return _csum(amps[0] * amps[0] + amps[1] * amps[1])


@partial(jax.jit, static_argnames=("n",))
def total_prob_density(amps, *, n: int):
    """Re(trace(rho)) (densmatr_calcTotalProb): the real plane read at a
    stride of 2^n + 1, the diagonal of the (2^n, 2^n) matrix where it lies.
    Seen as that matrix first (``reshape(2, dim, dim)``, then
    ``jnp.diagonal``), the v5e's compiler relayouts the plane and holds two
    temporaries of its size: 8 GiB beside the 8 GiB of a 15-qubit register
    (``tests/test_chip_compile.py``)."""
    return _csum(amps[0, ::(1 << n) + 1])


@jax.jit
def purity_density(amps):
    """Tr(rho^2) = sum |rho_ij|^2 for Hermitian rho (densmatr_calcPurityLocal,
    QuEST_cpu.c:878)."""
    return _csum(amps[0] * amps[0] + amps[1] * amps[1])


@jax.jit
def density_inner_product(a, b):
    """Re(Tr(a^dagger b)) = sum Re(conj(a_i) b_i)
    (densmatr_calcInnerProductLocal, QuEST_cpu.c:975-1003)."""
    return _csum(a[0] * b[0] + a[1] * b[1])


@jax.jit
def hilbert_schmidt_distance(a, b):
    """sqrt(sum |a_ij - b_ij|^2) (densmatr_calcHilbertSchmidtDistance)."""
    d = a - b
    return jnp.sqrt(_csum(d[0] * d[0] + d[1] * d[1]))


@partial(jax.jit, static_argnames=("n",))
def density_fidelity(rho_amps, pure_amps, *, n: int):
    """<psi| rho |psi> real part (densmatr_calcFidelityLocal, QuEST_cpu.c:1007).

    rho flat layout is [col, row] so as a matrix mat[c, r] = rho(r, c);
    <psi|rho|psi> = sum_r conj(psi_r) (mat^T psi)_r.
    """
    dim = 1 << n
    m = rho_amps.reshape(2, dim, dim)
    mr, mi = m[0].T, m[1].T
    pr, pi = pure_amps[0], pure_amps[1]
    mm = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    vr = mm(mr, pr) - mm(mi, pi)
    vi = mm(mr, pi) + mm(mi, pr)
    return _csum(pr * vr + pi * vi)


@jax.jit
def expec_diag_op_statevec(amps, elems):
    """sum |amp_i|^2 d_i, complex (re, im) (statevec_calcExpecDiagonalOp,
    QuEST_cpu_distributed.c:1612-1647)."""
    p = _acc(amps[0] * amps[0] + amps[1] * amps[1])
    return _csum(p * _acc(elems[0])), _csum(p * _acc(elems[1]))


@partial(jax.jit, static_argnames=("n",))
def expec_diag_op_density(amps, elems, *, n: int):
    """Tr(rho D) = sum_r rho[r,r] d_r, complex (densmatr_calcExpecDiagonalOp)."""
    dim = 1 << n
    t = amps.reshape(2, dim, dim)
    dr, di = _acc(jnp.diagonal(t[0])), _acc(jnp.diagonal(t[1]))
    er, ei = _acc(elems[0]), _acc(elems[1])
    return _csum(dr * er - di * ei), _csum(dr * ei + di * er)
