"""Tape linter: circuit-level advice and apply-time traps (QT0xx, QT502).

Walks a recorded ``Circuit`` tape through the fuser's own spy-capture
(:func:`..fusion.capture`), so what is linted is exactly what the planner
sees -- GateEvents in primitive form, with API sugar and density shadows
resolved. Four lints:

- **QT001** adjacent self-inverse pairs: two events with the same
  support composing to the identity (up to global phase), separated only
  by support-disjoint events -- both gates are dead weight.
- **QT002** mergeable same-axis rotations: two tape entries of the same
  rotation/phase-family function with identical structure (targets,
  controls, axes) separated only by support-disjoint entries -- one
  rotation of the summed angle does the same work in half the passes.
- **QT003** constant angles at liftable positions: every anonymous slot
  :func:`..params.lift_tape` would create is a parameter the
  circuit could have recorded as ``engine.P(...)``; as plain constants
  they bake into the structure fingerprint, so structure-equal circuits
  compile separate executables instead of sharing one
  (docs/serving.md). Cross-checked against ``lift_tape`` itself: the
  reported count IS the lifted tape's anonymous-slot count.
- **QT004** control/target overlap in a captured event: the runtime
  validators only see this at apply time; the linter sees it at record
  time. Also exposed standalone as :func:`lint_events` for synthetic /
  kernel-level event streams.

Two more checks ride the same walk: **QT502** flags trajectory channel
sites (``applyTrajectoryKraus`` entries, quest_tpu/trajectories) whose
Kraus set is not CPTP -- a biased unraveling, caught at record time --
and **QT005** flags mid-circuit measurement/collapse sites
(``quest_tpu.sampling.measure`` entries, tagged ``_measurement_site``)
that sit inside a deferred-relocation window: their marginal reduction
reads raw amplitude order, so the frame must be at identity there
(:func:`..segments.identity_boundaries`).

With ``differentiate=True`` (a tape headed for ``Circuit.gradient`` /
the adjoint engine, quest_tpu/gradients) one more check runs: **QT006**
flags every mid-circuit measurement/collapse and trajectory-Kraus site
-- stochastic seams the adjoint backward sweep cannot invert.
``Circuit.gradient`` raises a typed error at the first such site; the
lint reports them ALL at record time, with the fix hint pointing at
``sample_request`` composition (run the gradient on the unitary tape,
sample the measurement separately).

Entries the spy cannot capture (operator entries, Param-carrying
entries, inits) act as lint barriers, exactly as they act as fusion
barriers -- nothing is matched across them.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from .diagnostics import Finding, make_finding

__all__ = ["lint_events", "lint_tape", "lint_circuit"]

_TOL = 1e-9


def lint_events(events, location: str = "events") -> list[Finding]:
    """QT004 over a GateEvent stream: control/target aliasing and
    duplicate targets, per event."""
    findings: list[Finding] = []
    for i, ev in enumerate(events):
        where = f"{location}[{i}]:{ev.kind}"
        if ev.kind in ("aux",):
            continue
        ts = tuple(ev.targets)
        if len(set(ts)) != len(ts):
            findings.append(make_finding(
                "QT004", f"{ev.kind} event repeats a target in {ts}",
                where))
        overlap = sorted(set(ts) & set(ev.controls))
        if overlap:
            findings.append(make_finding(
                "QT004",
                f"{ev.kind} event uses qubit(s) {overlap} as both "
                f"target and control", where))
    return findings


def _events_cancel(a, b) -> bool:
    """True when events ``a`` then ``b`` compose to the identity (up to
    global phase). Conservative: False on anything uncertain."""
    if (a.kind != b.kind or tuple(a.targets) != tuple(b.targets)
            or tuple(a.controls) != tuple(b.controls)
            or tuple(a.states) != tuple(b.states)):
        if a.kind == b.kind == "swap" and not a.controls and not b.controls:
            return set(a.targets) == set(b.targets)
        return False
    if a.kind == "x":
        return True
    if a.kind == "swap":
        return True
    if a.kind == "parity":
        return abs(a.theta + b.theta) < _TOL
    if a.kind == "matrix" and a.matrix is not None and b.matrix is not None:
        if a.matrix.shape != b.matrix.shape:
            return False
        prod = np.asarray(b.matrix) @ np.asarray(a.matrix)
        c = prod[0, 0]
        return (abs(abs(c) - 1.0) < 1e-7
                and np.allclose(prod, c * np.eye(prod.shape[0]),
                                atol=1e-7))
    if a.kind == "diag" and a.diag is not None and b.diag is not None:
        if a.diag.shape != b.diag.shape:
            return False
        return np.allclose(np.asarray(a.diag) * np.asarray(b.diag), 1.0,
                           atol=1e-7)
    return False


def _structure_key(name: str, args, kwargs) -> tuple:
    """A tape entry with its liftable value positions masked out -- two
    entries with the same key differ only in angles."""
    from ..params import _LIFTABLE, is_value

    spec = _LIFTABLE.get(name, {})
    masked_args = tuple(
        "<value>" if spec.get(i) is not None and is_value(v) else _freeze(v)
        for i, v in enumerate(args))
    masked_kwargs = tuple(sorted(
        (k, "<value>" if spec.get(k) is not None and is_value(v)
         else _freeze(v))
        for k, v in kwargs.items()))
    return (name, masked_args, masked_kwargs)


def _freeze(v):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, np.ndarray):
        return ("<array>", v.shape)
    return v


#: completeness tolerance of the QT502 check, scaled by the operator
#: dimension (mirrors validation.validate_kraus_ops at f64 working eps)
_CPTP_ATOL = 1e-6


def _lint_traj_kraus(args, kwargs, where: str) -> list[Finding]:
    """QT502: a trajectory channel site whose Kraus set is not CPTP.
    The sampler draws k with p_k = <psi|K_k^dagger K_k|psi>; unless
    sum_k K_k^dagger K_k = I those probabilities are biased and the
    ensemble mean converges to the WRONG channel -- flagged at record
    time, before any trajectory runs."""
    ops = kwargs.get("ops", args[1] if len(args) > 1 else None)
    if ops is None:
        return []
    try:
        k = [np.asarray(op, dtype=np.complex128) for op in ops]
        dim = k[0].shape[0]
        acc = np.zeros((dim, dim), dtype=np.complex128)
        for op in k:
            acc += op.conj().T @ op
        dev = float(np.max(np.abs(acc - np.eye(dim))))
    except Exception:
        return []
    if dev > _CPTP_ATOL * dim:
        return [make_finding(
            "QT502",
            f"sum_k K_k^dagger K_k deviates from identity by {dev:.3g} "
            f"({len(k)} ops, dim {dim}): trajectory selection "
            f"probabilities are biased", where)]
    return []


def lint_tape(tape, num_qubits: int, *, is_density: bool = False,
              dtype=None, location: str = "tape",
              differentiate: bool = False) -> list[Finding]:
    """Lint a recorded tape (list of ``(fn, args, kwargs)`` entries); see
    the module docstring for the lint classes. ``differentiate=True``
    additionally runs QT006 (non-differentiable sites) for tapes headed
    to :meth:`..circuits.Circuit.gradient`."""
    from ..capture import capture
    from ..params import _LIFTABLE, lift_slot_census
    from ..precision import real_dtype
    from ..validation import QuESTError

    dt = np.dtype(dtype) if dtype is not None else real_dtype(None)
    findings: list[Finding] = []

    # event-level window since the last barrier, for QT001/QT004
    live_events: list[tuple] = []   # (entry_idx, GateEvent)
    # entry-level window for QT002
    live_entries: list[tuple] = []  # (entry_idx, structure_key, support)
    # identity-boundary set for QT005, computed lazily on the first
    # measurement site (the walk is O(tape) either way)
    id_bounds: set | None = None

    for idx, (fn, args, kwargs) in enumerate(tape):
        name = getattr(fn, "__name__", "")
        where = f"{location}[{idx}]:{name}"
        if name == "applyTrajectoryKraus":
            findings.extend(_lint_traj_kraus(args, kwargs, where))
        # QT006: a stochastic seam in a tape submitted for differentiation
        # -- the adjoint backward sweep (quest_tpu/gradients) cannot invert
        # a measurement or a sampled Kraus selection
        if differentiate and (getattr(fn, "_measurement_site", False)
                              or name == "applyTrajectoryKraus"):
            what = ("trajectory-Kraus" if name == "applyTrajectoryKraus"
                    else "mid-circuit measurement/collapse")
            findings.append(make_finding(
                "QT006",
                f"{what} site '{name}' at entry [{idx}] in a tape "
                f"submitted for differentiation: the adjoint sweep has "
                f"no inverse for it", where))
        # QT005: a mid-circuit measurement/collapse site reduces the
        # target's marginal in RAW amplitude order -- inside a deferred-
        # relocation window (frame not at identity) that marginal is over
        # the WRONG qubit
        if getattr(fn, "_measurement_site", False):
            if id_bounds is None:
                from ..segments import identity_boundaries
                nsv = (2 if is_density else 1) * num_qubits
                id_bounds = set(identity_boundaries(tape, nsv))
            if idx not in id_bounds:
                findings.append(make_finding(
                    "QT005",
                    f"measurement site '{name}' at entry [{idx}] is not "
                    f"at a frame-identity boundary: its marginal would "
                    f"be reduced under a deferred qubit layout", where))
        events = capture(fn, args, kwargs, num_qubits, dt,
                         is_density=is_density)
        if events is None:
            live_events.clear()
            live_entries.clear()
            continue
        findings.extend(lint_events(events, location=where))
        support = frozenset().union(*(ev.support for ev in events)) \
            if events else frozenset()

        # QT001: scan back over support-disjoint events for an inverse
        for ev in events:
            matched = None
            for j in range(len(live_events) - 1, -1, -1):
                pidx, pev = live_events[j]
                if not (pev.support & ev.support):
                    continue
                if _events_cancel(pev, ev):
                    matched = (j, pidx)
                break  # first support-overlapping event decides
            if matched is not None:
                j, pidx = matched
                findings.append(make_finding(
                    "QT001",
                    f"cancels the {live_events[j][1].kind} gate of "
                    f"entry [{pidx}] on qubits "
                    f"{sorted(ev.support)}", where))
                del live_events[j]
            else:
                live_events.append((idx, ev))

        # QT002: same-structure rotation-family entries
        if name in _LIFTABLE and len(events) >= 1:
            key = _structure_key(name, args, kwargs)
            for j in range(len(live_entries) - 1, -1, -1):
                pidx, pkey, psupport = live_entries[j]
                if not (psupport & support):
                    continue
                if pkey == key:
                    findings.append(make_finding(
                        "QT002",
                        f"same-axis {name} as entry [{pidx}] on qubits "
                        f"{sorted(support)}; the two angles sum", where))
                break
            live_entries.append((idx, key, support))
        elif support:
            # a non-rotation entry on these qubits blocks merging across
            live_entries.append((idx, None, support))

    # QT003: aggregate param-lift candidacy -- the count comes from
    # lift_tape itself (params.lift_slot_census), so the lint and
    # the serving engine agree by construction
    try:
        anon, named = lift_slot_census(tape)
    except QuESTError:
        anon = 0
    if anon:
        findings.append(make_finding(
            "QT003",
            f"{anon} constant angle(s)/scalar(s) at liftable "
            f"positions ({named} already Params): structure-equal "
            f"variants of this circuit will not share a compiled "
            f"executable", f"{location}.params"))
    return findings


def lint_circuit(circuit, *, location: Optional[str] = None,
                 differentiate: bool = False) -> list[Finding]:
    """:func:`lint_tape` over a :class:`..circuits.Circuit`."""
    loc = location if location is not None else \
        f"circuit({circuit.num_qubits}q)"
    return lint_tape(list(circuit._tape), circuit.num_qubits,
                     is_density=circuit.is_density_matrix,
                     location=loc, differentiate=differentiate)
