"""Capture: a tape entry replayed once against a spy register.

Handed a spy, the gate-application primitives record (kind, operands,
qubits) as :class:`~.events.GateEvent` instead of touching any device
array (the spy carries its recorders, ops.spy: nothing process-wide is
patched). Entries that don't route through the capturable primitives
(phase functions, state inits, measurements, ...) fail capture and act as
fusion barriers. An entry that carries Params is captured by structure
alone (:func:`_capture_deferred`). Read by the planner (:mod:`.planner`),
by a deferred block's assembly at apply time (``fusion._resolve_factors``),
by the deferred scheduler's lookahead (``circuits._tape_accesses``), the
adjoint sweep and the tape linter.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import numpy as np

from . import precision
from .events import GateEvent
from .matrices import is_traced
from .ops.spy import Spy
from .parallel import scheduler as _dist
from .params import bind, has_params, lift_tape, materialize_entry
from .validation import QuESTError


class _SpyAmps(Spy):
    """Stands in for ``qureg.amps`` during capture: carries a dtype for
    validation tolerances, raises on any real use."""

    def __init__(self, dtype, recorders):
        self.dtype = dtype
        self.recorders = recorders


class _SpyQureg(Spy):
    """Minimal stand-in satisfying validation + the capturable primitives
    (ops.spy.records): they hand their arguments to ``recorders``."""

    def __init__(self, num_qubits: int, is_density: bool, dtype, recorders):
        self.num_qubits_represented = int(num_qubits)
        self.is_density_matrix = bool(is_density)
        self.recorders = recorders
        self.amps = _SpyAmps(dtype, recorders)
        self.qasm_log = None
        self.env = None

    @property
    def num_qubits_in_state_vec(self):
        return (2 if self.is_density_matrix else 1) * self.num_qubits_represented

    @property
    def dtype(self):
        return self.amps.dtype

    @property
    def eps(self):
        return precision.eps_for_dtype(self.amps.dtype)

    def put(self, amps):  # swapGate's inline path calls this with the token
        self.amps = amps


def _channel_recorders(events: list) -> dict:
    """Recorders of the density-channel appliers in :mod:`.ops.density`:
    Kraus channels (via apply_channel) and dephasing diagonals (via
    _diag_dispatch) -- both in flattened 2n coordinates."""

    def cap_channel(amps, superop, *, n, targets, depol=None):
        events.append(GateEvent(
            "channel", tuple(targets),
            superop=np.asarray(superop, dtype=complex), depol=depol,
            extended=True))
        return amps

    def cap_dens_diag(amps, d, *, n, targets):
        dc = np.asarray(d[0]) + 1j * np.asarray(d[1])
        events.append(GateEvent("diag", tuple(targets), diag=dc,
                                extended=True))
        return amps

    return {"apply_channel": cap_channel, "_diag_dispatch": cap_dens_diag}


def _aux_recorders(events: list) -> dict:
    """Recorders of the operator-level kernel appliers (phase functions,
    direct diagonals, projections, raw matrix applications): ACCESS-ONLY
    events (kind 'aux': support coordinates, no operator data). Only the
    deferred scheduler's lookahead (circuits._tape_accesses) uses these --
    the fuser never captures with them, so operator entries keep acting as
    fusion barriers while still exposing their qubit sets to Belady
    eviction."""

    def cap_phase(amps, *a, **kw):
        events.append(GateEvent("aux", tuple(kw["qubits"])))
        return amps

    def cap_diag(amps, d, *, targets, **kw):
        events.append(GateEvent("aux", tuple(targets)))
        return amps

    def cap_project(amps, *, target, **kw):
        events.append(GateEvent("aux", (target,)))
        return amps

    def cap_matrix(amps, m, *, targets, controls=(), **kw):
        events.append(GateEvent("aux", tuple(targets), tuple(controls)))
        return amps

    return {"apply_poly_phase": cap_phase, "apply_named_phase": cap_phase,
            "apply_diagonal": cap_diag, "project_statevec": cap_project,
            "apply_matrix": cap_matrix}


def _gate_recorders(events: list) -> dict:
    """Recorders of the gate primitives in :mod:`.gates` (and the swap
    kernel swapGate calls inline)."""
    # operands assembled from runtime values (matrices.py's traced
    # branches) are kept as they come: a deferred block composes them
    # inside the trace (_compose_dense / _compose_diag)
    def cap_matrix(qureg, matrix, targets, controls=(), states=()):
        events.append(GateEvent(
            "matrix", tuple(targets), tuple(controls), tuple(states),
            matrix=matrix if is_traced(matrix)
            else np.asarray(matrix, dtype=complex)))

    def cap_diag(qureg, diag, targets, controls=()):
        events.append(GateEvent(
            "diag", tuple(targets), tuple(controls),
            diag=diag.reshape(-1) if is_traced(diag)
            else np.asarray(diag, dtype=complex).reshape(-1)))

    def cap_x(qureg, targets, controls=(), states=()):
        events.append(GateEvent("x", tuple(targets), tuple(controls), tuple(states)))

    def cap_parity(qureg, theta, qubits, controls=()):
        events.append(GateEvent(
            "parity", tuple(qubits), tuple(controls),
            theta=theta if is_traced(theta) else float(theta)))

    def cap_swap(amps, *, n, qb1, qb2, controls=()):
        events.append(GateEvent("swap", (qb1, qb2), tuple(controls)))
        return amps

    return {"_apply_gate_matrix": cap_matrix, "_apply_gate_diag": cap_diag,
            "_apply_gate_x": cap_x, "_apply_gate_parity_phase": cap_parity,
            "apply_swap": cap_swap}


def _entry_has_params(args, kwargs) -> bool:
    """True when a tape entry carries params.Param placeholders:
    there is no concrete matrix to fuse at plan time. The dense planner
    captures such an entry's STRUCTURE (:func:`_capture_deferred`) and
    lets it join a block whose matrix is assembled inside the program; the
    Pallas planner passes it through as a barrier assembled at apply time.
    Either way the plan's structure stays value-independent and one
    compiled replay serves every parameter vector."""
    return has_params(args, kwargs)


def _event_traced(ev: GateEvent) -> bool:
    return is_traced(ev.matrix, ev.diag, ev.theta)


def _deferrable(ev: GateEvent) -> bool:
    """The deferred factors the in-trace composition takes: what the
    liftable family (params._LIFTABLE) captures to -- one-target
    matrices and diagonals under any controls, and parity phases."""
    if ev.kind == "parity":
        return True
    return ev.kind in ("matrix", "diag") and len(ev.targets) == 1


def _capture_deferred(entry, num_qubits: int, dtype) -> Optional[list]:
    """Structure-only capture of a tape entry that carries Params: the
    entry is replayed against the spy under ``jax.eval_shape`` with its
    value slots abstract, so every gate builder takes its traced branch
    and whatever needs a value to decide its structure raises a
    concretization error (the entry then stays a barrier; any other error
    is a defect and propagates). Every event names its ``source``; those
    whose operands came out traced are returned DEFERRED (no data),
    operands that never saw a value (multiRotatePauli's basis changes)
    stay. None when the entry cannot be captured, holds a deferred event
    the composition does not take, or defers nothing (its Params would
    vanish from the plan)."""
    if getattr(entry[0], "_fusion_barrier", False):
        return None
    try:
        lifted = lift_tape((entry,))
    except QuESTError:
        # a Param where the lifter has no slot: the replay names it
        return None
    got = []

    def run(values):
        events = _spy_replay(*materialize_entry(lifted.entries[0], values),
                             num_qubits, dtype)
        for i, ev in enumerate(events):
            source = (entry, i, len(events))
            got.append(
                GateEvent(ev.kind, ev.targets, ev.controls, ev.states,
                          theta=None, source=source) if _event_traced(ev)
                else dataclasses.replace(ev, source=source))

    try:
        jax.eval_shape(run, bind(lifted, dict.fromkeys(
            lifted.param_names, 0.0)))
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError,
            jax.errors.TracerIntegerConversionError):
        return None
    deferred = [ev for ev in got if ev.deferred]
    if not deferred or not all(_deferrable(ev) for ev in deferred):
        return None
    return got


def _spy_replay(fn, args, kwargs, num_qubits: int, dtype,
                density_spy: bool = False, aux: bool = False) -> list:
    """The GateEvents ``fn`` records on a spy register (:func:`capture`
    says which); raises whatever ``fn`` raises on one."""
    events: list = []
    recorders = _gate_recorders(events)
    if density_spy:
        recorders.update(_channel_recorders(events))
    if aux:
        recorders.update(_aux_recorders(events))
    shell = _SpyQureg(num_qubits, density_spy, dtype, recorders)
    # suspend any active distributed scheduler: the spy replay must not
    # route through (or mutate) it -- swapGate's inline dispatch would
    # otherwise record phantom virtual swaps in its layout/stats
    with _dist.explicit_mesh(None):
        fn(shell, *args, **kwargs)
    return events


def capture(fn, args, kwargs, num_qubits: int, dtype,
            is_density: bool = False, aux: bool = False) -> Optional[list]:
    """Replay one tape entry against a spy register; return its GateEvents,
    or None if the entry doesn't route through the capturable primitives
    (it then acts as a fusion barrier and runs on the device path
    unchanged).

    The first attempt always uses a STATE-VECTOR spy: gate functions with
    inline density branches (swapGate) would otherwise record their shadow
    op too, and shadows are derived at planning/emission instead. Entries
    that fail that attempt on a density tape (decoherence channels, whose
    validation demands a density register) get a second attempt against a
    density spy that also records the channel appliers -- their events
    carry flattened-state coordinates and ``extended=True``.

    The spy carries its recorders (ops.spy): no process-wide state is
    touched, so captures run beside real traces in any number of threads.

    ``aux=True`` additionally records the operator-level appliers
    (_aux_recorders) so phase-function/projector/matrixN entries yield
    access-only 'aux' events -- used by the deferred scheduler's lookahead,
    never by the fuser (aux events carry no operator data)."""
    # trajectory-noise sites (and anything else tagged _fusion_barrier)
    # assemble their operator at apply time from runtime PRNG draws: there
    # is no static event to capture, even with a constant seed. The
    # mid-circuit measurement/collapse entries of sampling.measure carry
    # the same tag: their one-hot collapse mask is a function of the
    # runtime draw (or of the state's own marginal), so a measurement
    # site is always a fusion barrier -- gate runs fuse up to it and
    # resume after it, mirroring the segment seam it also forces.
    if getattr(fn, "_fusion_barrier", False):
        return None

    try:
        return _spy_replay(fn, args, kwargs, num_qubits, dtype,
                           aux=aux) or None
    except Exception:
        pass
    if not is_density:
        return None
    try:
        return _spy_replay(fn, args, kwargs, num_qubits, dtype,
                           density_spy=True, aux=aux) or None
    except Exception:
        return None

