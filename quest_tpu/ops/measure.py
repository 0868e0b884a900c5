"""Probability and collapse kernels.

Reference: statevec_findProbabilityOfZeroLocal (``QuEST_cpu.c:3385``),
calcProbOfAllOutcomesLocal (``:3477``), collapse/renormalise (``:3695-3848``),
with MPI_Allreduce completing each reduction
(``QuEST_cpu_distributed.c:1324-1368``). Here every reduction is one
``jnp.sum`` -- on a sharded array XLA lowers it to a local reduce + psum over
the ICI mesh, exactly the Allreduce the reference hand-codes.

States are planar (2, 2^n) float arrays. Accumulation is float64 when x64 is
enabled (tests/CPU) else float32; the reference's Kahan summation
(QuEST_cpu_distributed.c:62-119) addresses the same drift.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .layout import grouped_axes
from .reduce import csum_rows
from .spy import records


def _acc_dtype():
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def _density_diag(amps, n: int):
    """Planar diagonal (2, 2^n) of a flattened density matrix."""
    dim = 1 << n
    t = amps.reshape(2, dim, dim)
    return jnp.stack([jnp.diagonal(t[0]), jnp.diagonal(t[1])])


@partial(jax.jit, static_argnames=("n", "target", "outcome"))
def prob_of_outcome(amps, *, n: int, target: int, outcome: int):
    """P(measuring ``outcome`` on ``target``) of a state-vector."""
    shape, axis_of = grouped_axes(n, (target,))
    tensor = amps.reshape((2,) + shape)
    sub = jax.lax.index_in_dim(tensor, outcome, axis=axis_of[target] + 1, keepdims=False)
    p = (sub[0] * sub[0] + sub[1] * sub[1]).astype(_acc_dtype())
    return jnp.sum(p)


def _group_outcome_probs(p, n, targets):
    """Reorder a real 2^n tensor so target bits (targets[0]=LSB) lead, then
    sum the rest; returns (2^t,). The per-group accumulation is the
    compensated rowwise cascade (ops.reduce.csum_rows): a bare
    ``.sum(axis=1)`` drifts ~1e-5 against the f64 oracle at 20q f32
    marginals, well past the sampler's CDF resolution."""
    t = len(targets)
    shape, axis_of = grouped_axes(n, targets)
    p = p.reshape(shape)
    targ_axes = [axis_of[q] for q in reversed(targets)]  # MSB first
    rest = [ax for ax in range(len(shape)) if ax not in targ_axes]
    p = p.transpose(tuple(targ_axes + rest))
    return csum_rows(p.reshape((1 << t, -1)))


@partial(jax.jit, static_argnames=("n", "targets"))
def prob_of_all_outcomes(amps, *, n: int, targets: tuple[int, ...]):
    """2^t vector of outcome probabilities; outcome index o has targets[0] as
    its least-significant bit (calcProbOfAllOutcomes, QuEST.h:3633)."""
    p = (amps[0] * amps[0] + amps[1] * amps[1]).astype(_acc_dtype())
    return _group_outcome_probs(p, n, targets)


def _project_mask(n, target, outcome, dtype):
    shape, axis_of = grouped_axes(n, (target,))
    keep = [0.0, 0.0]
    keep[outcome] = 1.0
    m = [1] * len(shape)
    m[axis_of[target]] = 2
    return jnp.asarray(keep, dtype=dtype).reshape(m), shape


@partial(jax.jit, static_argnames=("n", "target", "outcome"), donate_argnums=(0,))
def collapse_statevec(amps, prob, *, n: int, target: int, outcome: int):
    """Project ``target`` to ``outcome`` and renormalise by 1/sqrt(prob)
    (statevec_collapseToKnownProbOutcome, QuEST_cpu.c:3695-3775)."""
    mask, shape = _project_mask(n, target, outcome, amps.dtype)
    scale = (1.0 / jnp.sqrt(prob)).astype(amps.dtype)
    return (amps.reshape((2,) + shape) * mask[None] * scale).reshape(2, -1)


@records
@partial(jax.jit, static_argnames=("n", "target", "outcome"), donate_argnums=(0,))
def project_statevec(amps, *, n: int, target: int, outcome: int):
    """Unnormalised projection (applyProjector, QuEST.h:7421)."""
    mask, shape = _project_mask(n, target, outcome, amps.dtype)
    return (amps.reshape((2,) + shape) * mask[None]).reshape(2, -1)


# ---------------------------------------------------------------------------
# density-matrix variants (row bits = low n, col bits = high n of the 2n-qubit
# flattening; see registers.Qureg)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n", "target", "outcome"))
def density_prob_of_outcome(amps, *, n: int, target: int, outcome: int):
    """Tr(rho P_outcome): sum diagonal elements whose bit ``target`` equals
    ``outcome`` (densmatr_calcProbOfOutcome)."""
    diag_re = _density_diag(amps, n)[0].astype(_acc_dtype())
    shape, axis_of = grouped_axes(n, (target,))
    d = diag_re.reshape(shape)
    sub = jax.lax.index_in_dim(d, outcome, axis=axis_of[target], keepdims=False)
    return jnp.sum(sub)


@partial(jax.jit, static_argnames=("n", "targets"))
def density_prob_of_all_outcomes(amps, *, n: int, targets: tuple[int, ...]):
    diag_re = _density_diag(amps, n)[0].astype(_acc_dtype())
    return _group_outcome_probs(diag_re, n, targets)


@partial(jax.jit, static_argnames=("n", "target", "outcome", "renorm"), donate_argnums=(0,))
def density_collapse(amps, prob, *, n: int, target: int, outcome: int, renorm: bool = True):
    """Zero every element where row-bit or col-bit of ``target`` differs from
    ``outcome``; scale by 1/prob (densmatr_collapseToKnownProbOutcome,
    QuEST_cpu.c:3777-3848)."""
    shape, axis_of = grouped_axes(2 * n, (target, target + n))
    rank = len(shape)
    keep = [0.0, 0.0]
    keep[outcome] = 1.0
    mask = None
    for q in (target, target + n):
        s = [1] * rank
        s[axis_of[q]] = 2
        v = jnp.asarray(keep, dtype=amps.dtype).reshape(s)
        mask = v if mask is None else mask * v

    out = amps.reshape((2,) + shape) * mask[None]
    if renorm:
        out = out * (1.0 / prob).astype(amps.dtype)
    return out.reshape(2, -1)
