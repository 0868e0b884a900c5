"""Quantum-trajectory noise engine: noisy circuits at state-vector cost.

Unravels the decoherence channels of a density-matrix tape into stochastic
pure-state trajectories (the qsim Monte-Carlo-wavefunction technique,
arXiv:2111.02396) and runs the ensemble as ONE fixed-shape batched program
through the serving engine's vmap-over-params batcher: channel sites carry
a runtime uint32 seed slot (params kind ``'seed'``), so T
trajectories compile once and replay with T independent counter-based PRNG
streams -- branch-free selection keeps plan structure value-independent,
the same invariant PR 4 proved for param barriers.

Surface:

- :func:`unravel` -- density tape -> trajectory tape (shared seed Param)
- :func:`noise.applyTrajectoryKraus` -- the recordable channel site
- :func:`run_ensemble` -- T seeds through one Engine, ``TrajectoryResult``
- :func:`ensemble_density` -- small-n oracle-comparison helper
- the canonical channel table both noise routes share lives in
  :mod:`quest_tpu.channels`

docs/trajectories.md carries the math, the seeding contract and the
when-to-prefer table; the QT501/QT502 diagnostics band covers the env knob
and non-CPTP hazards.
"""

from .ensemble import (DEFAULT_TRAJECTORIES, SEED_PARAM, TrajectoryResult,
                       ensemble_density, run_ensemble,
                       trajectory_count_default, unravel)
from .noise import applyTrajectoryKraus
from .sample import apply_traj_kraus

__all__ = [
    "unravel", "run_ensemble", "ensemble_density", "TrajectoryResult",
    "trajectory_count_default", "applyTrajectoryKraus", "apply_traj_kraus",
    "DEFAULT_TRAJECTORIES", "SEED_PARAM",
]
