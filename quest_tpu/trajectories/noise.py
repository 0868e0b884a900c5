"""Tapeable trajectory-noise entry: the channel site of an unraveled tape.

``applyTrajectoryKraus`` is the single recordable primitive every unraveled
channel lowers to (trajectories.unravel maps the built-in mix* table onto
it). Its Kraus stack, targets and site index are baked tape *structure*;
the ``seed`` argument is a runtime value slot of kind ``'seed'``
(params._LIFTABLE) -- a plain int or a :class:`~quest_tpu.engine.P`
placeholder both lift, so plan structure and the executable-cache
fingerprint never depend on the seed.

On the fused path these entries are unconditional barriers
(capture.capture returns None for them -- the drawn operator only exists at
apply time), exactly like PR 4's param barriers; on the deferred scheduler
they reconcile first (the module is not in circuits._DEFER_SAFE_MODULES).
"""

from __future__ import annotations

import numpy as np

from typing import TYPE_CHECKING, Iterable, Sequence

from .. import validation as V
from ..validation import QuESTError
from .sample import apply_traj_kraus

if TYPE_CHECKING:
    from ..registers import Qureg

__all__ = ["applyTrajectoryKraus"]


def applyTrajectoryKraus(qureg: Qureg, targets: Iterable[int],
                         ops: Sequence[np.ndarray], seed: object,
                         site: int = 0) -> None:
    """Sample one Kraus operator of ``ops`` on ``targets`` with the
    trajectory's PRNG stream and apply it renormalised to the state-vector
    ``qureg`` (density registers take the exact channel via mix* instead).

    ``ops``: the channel's CPTP Kraus set (host matrices, baked structure).
    ``seed``: the per-trajectory uint32 seed -- recordable as ``P("seed")``
    so the engine batches T trajectories into one vmap dispatch.
    ``site``: static per-site counter (``fold_in`` stream split); distinct
    channel sites of one tape must carry distinct sites.
    """
    func = "applyTrajectoryKraus"
    if qureg.is_density_matrix:
        raise QuESTError(
            f"{func} unravels noise over pure states; density registers "
            "apply the exact channel via the mix* family instead")
    targets = tuple(int(t) for t in targets)
    V.validate_multi_targets(qureg, targets, func)
    ops = [np.asarray(op) for op in ops]
    V.validate_kraus_ops(ops, len(targets), qureg.eps, func, check_cptp=True)
    amps = apply_traj_kraus(qureg.amps, ops,
                            n=qureg.num_qubits_in_state_vec,
                            targets=targets, seed=seed, site=int(site))
    qureg.put(amps)
    if qureg.qasm_log is not None:
        qureg.qasm_log.record_comment(
            f"trajectoryKraus site {int(site)} on qubits {list(targets)} "
            f"({len(ops)} ops)")


# the drawn operator is assembled at apply time from the runtime seed --
# there is never a spy-capturable static event, even for a constant seed
applyTrajectoryKraus._fusion_barrier = True
