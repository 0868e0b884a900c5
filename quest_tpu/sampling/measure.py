"""Tapeable mid-circuit measurement and collapse (round 19).

``measure``/``collapseToOutcome`` are excluded from tapes because they
host-sync a probability and branch on it (gates.py pays one
``float(p)`` round-trip per shot -- counted as
``measure_host_syncs_total``). These two entries are their RECORDABLE
forms: the outcome is drawn (or forced) and applied entirely on device
with the branch-free one-hot collapse + rsqrt renormalisation of
``trajectories.sample``, so plan structure is value-independent and the
site rides the fused/segment/request-chain routes like any gate.

Contract, mirroring ``trajectories.noise.applyTrajectoryKraus``:

- both functions are unconditional fusion barriers (``capture.capture``
  returns None for them -- the collapse mask only exists at apply time);
- the module is NOT in ``circuits._DEFER_SAFE_MODULES``, so under the
  explicit scheduler a measurement site is a reconciliation point: the
  deferred qubit layout returns to identity before the marginal is
  reduced (tapelint QT005 flags any site that is not at one);
- ``segments.segment_cuts`` forces a segment seam at each site, so
  checkpoint/resume boundaries align with the points where a recorded
  outcome becomes definite;
- the ``seed`` argument of ``applyMidMeasurement`` is a runtime value
  slot of kind ``'seed'`` (params._LIFTABLE): a plain int or a
  ``P("name")`` placeholder both lift, S seeds replay one executable.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from .. import validation as V
from ..ops import reduce as R
from ..ops.layout import grouped_axes
from .sampler import shot_key

if TYPE_CHECKING:
    from ..registers import Qureg

__all__ = ["applyMidMeasurement", "applyMidCollapse"]

#: probability floor of the folded renormalisation (the trajectories
#: clamp): a branch this small is numerical cancellation, not physics.
_P_FLOOR = 1e-30


def _statevec_outcome_mask(n, target, outcome, dtype):
    """(mask, shape): the one-hot keep-mask over the target axis for a
    TRACED outcome (0 or 1), broadcastable against the grouped state."""
    shape, axis_of = grouped_axes(n, (target,))
    m = [1] * len(shape)
    m[axis_of[target]] = 2
    keep = (jnp.arange(2) == outcome).astype(dtype)
    return keep.reshape(m), shape


def _collapse_statevec_traced(amps, *, n, target, outcome, p_sel):
    """Branch-free collapse+renormalise with a traced outcome: one-hot
    mask times rsqrt(max(p_sel, floor)) -- the trajectories.sample
    contraction, structure independent of the drawn value."""
    mask, shape = _statevec_outcome_mask(n, target, outcome, amps.dtype)
    scale = jax.lax.rsqrt(jnp.maximum(p_sel, jnp.asarray(_P_FLOOR,
                                                         amps.dtype)))
    return (amps.reshape((2,) + shape) * mask[None]
            * scale.astype(amps.dtype)).reshape(2, -1)


def _collapse_density_traced(amps, *, n, target, outcome, p_sel):
    """Density variant: zero every element whose row- or col-bit of
    ``target`` differs from the traced outcome, scale by 1/p."""
    shape, axis_of = grouped_axes(2 * n, (target, target + n))
    rank = len(shape)
    keep = (jnp.arange(2) == outcome).astype(amps.dtype)
    mask = None
    for q in (target, target + n):
        s = [1] * rank
        s[axis_of[q]] = 2
        v = keep.reshape(s)
        mask = v if mask is None else mask * v
    scale = 1.0 / jnp.maximum(p_sel, jnp.asarray(_P_FLOOR, amps.dtype))
    return (amps.reshape((2,) + shape) * mask[None]
            * scale.astype(amps.dtype)).reshape(2, -1)


def _zero_prob(amps, n, target, density):
    """P(outcome 0 on ``target``) and the state's total probability, both
    traceable compensated reductions (no host sync)."""
    if density:
        dim = 1 << n
        diag = jnp.diagonal(amps.reshape(2, dim, dim)[0])
        shape, axis_of = grouped_axes(n, (target,))
        d = diag.astype(jnp.float64 if jax.config.jax_enable_x64
                        else jnp.float32).reshape(shape)
        sub = jax.lax.index_in_dim(d, 0, axis=axis_of[target],
                                   keepdims=False)
        p0 = jnp.sum(sub)
        total = R.total_prob_density(amps, n=n)
    else:
        shape, axis_of = grouped_axes(n, (target,))
        tensor = amps.reshape((2,) + shape)
        sub = jax.lax.index_in_dim(tensor, 0, axis=axis_of[target] + 1,
                                   keepdims=False)
        p0 = R._csum(sub[0] * sub[0] + sub[1] * sub[1])
        total = R.total_prob_statevec(amps)
    return p0, total


def applyMidMeasurement(qureg: Qureg, target: int, seed: object,
                        site: int = 0) -> None:
    """Measure ``target`` mid-circuit, entirely on device: draw the
    outcome from the qubit's marginal with the counter-based stream
    ``fold_in(PRNGKey(seed), site)`` and collapse+renormalise branch-free.
    Recordable on a Circuit tape; the drawn outcome never reaches the
    host (read it out with a final shot table over the same seed, or use
    eager ``measure`` when host control flow needs the bit).

    ``seed``: per-request uint32 -- recordable as ``P("name")`` so the
    engine batches S requests into one vmap dispatch. ``site``: static
    per-site counter; distinct measurement sites of one tape must carry
    distinct sites (trajectory channel sites share the same convention).
    """
    func = "applyMidMeasurement"
    V.validate_target(qureg, target, func)
    target = int(target)
    density = qureg.is_density_matrix
    n = qureg.num_qubits_represented
    amps = qureg.amps
    p0, total = _zero_prob(amps, n, target, density)
    # f32 draw regardless of route (the trajectories discipline):
    # f32/f64/df replays of one seed take the same branch
    u = jax.random.uniform(shot_key(seed, site), dtype=jnp.float32)
    outcome = (u.astype(p0.dtype) * total >= p0).astype(jnp.int32)
    p_sel = jnp.where(outcome == 0, p0, total - p0).astype(amps.dtype)
    if density:
        out = _collapse_density_traced(amps, n=n, target=target,
                                       outcome=outcome, p_sel=p_sel)
    else:
        out = _collapse_statevec_traced(amps, n=n, target=target,
                                        outcome=outcome, p_sel=p_sel)
    qureg.put(out)
    if qureg.qasm_log is not None:
        qureg.qasm_log.record_comment(
            f"midMeasurement site {int(site)} on qubit {target}")


def applyMidCollapse(qureg: Qureg, target: int, outcome: int) -> None:
    """Force ``target`` to ``outcome`` mid-circuit, on device: the
    recordable form of ``collapseToOutcome``, minus the host-returned
    probability (and minus its zero-probability validation -- the
    branch-free renormalisation clamps instead; a zero-probability
    branch collapses to a zero state exactly like a trajectory hitting
    the probability floor). Deterministic: no seed, no RNG."""
    func = "applyMidCollapse"
    V.validate_target(qureg, target, func)
    V.validate_outcome(outcome, func)
    target, outcome = int(target), int(outcome)
    density = qureg.is_density_matrix
    n = qureg.num_qubits_represented
    amps = qureg.amps
    p0, total = _zero_prob(amps, n, target, density)
    p_sel = (p0 if outcome == 0 else total - p0).astype(amps.dtype)
    if density:
        out = _collapse_density_traced(amps, n=n, target=target,
                                       outcome=outcome, p_sel=p_sel)
    else:
        out = _collapse_statevec_traced(amps, n=n, target=target,
                                        outcome=outcome, p_sel=p_sel)
    qureg.put(out)
    if qureg.qasm_log is not None:
        qureg.qasm_log.record_comment(
            f"midCollapse of qubit {target} to outcome {outcome}")


# the collapse mask is assembled at apply time from the runtime draw --
# never a spy-capturable static event (the applyTrajectoryKraus contract)
applyMidMeasurement._fusion_barrier = True
applyMidCollapse._fusion_barrier = True
# segment seams and the QT005 reconciliation lint key off this tag
applyMidMeasurement._measurement_site = True
applyMidCollapse._measurement_site = True
