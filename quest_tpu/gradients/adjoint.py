"""Adjoint-state reverse-mode differentiation of parameterized tapes.

The method (Jones & Gacon, arXiv:2009.02823): for E(θ) = ⟨ψ(θ)|H|ψ(θ)⟩
with |ψ⟩ = U_P···U_1|ψ₀⟩, run ONE forward sweep to |ψ⟩, build the costate
λ = H|ψ⟩, then walk backward k = P..1 keeping two registers in lockstep --
φ ← U_k†φ and λ ← U_k†λ -- harvesting each parameter's derivative from the
bracket dE/dθ_k = 2·Re⟨λ_k|∂U_k|φ_{k-1}⟩ along the way. Total cost is
~3 sweeps and O(1) extra state, vs parameter-shift's 2P full replays.

The whole thing is a *reduce* over the forward replay: ``grad_reduce``
returns a finalize callable (``wants_values=True``) that
``Circuit.parameterized`` / the engine batcher compose as
``reduce(body(amps, values), values)``, so forward + backward + all P
accumulations lower into ONE jitted program -- one device dispatch per
gradient (``route=grad_request``), vmappable over T parameter sets.

Derivative rules per lifted family (``params._LIFTABLE``):

- rotations (rotate{X,Y,Z}, rotateAroundAxis, multiRotateZ/Pauli and their
  controlled forms), generator G with U = exp(-iθG/2) on the controlled
  block: ∂U = -(i/2)(Π₁⊗G)·U, so dE/dθ = Im⟨λ|(Π₁⊗G)|φ_k⟩ evaluated on
  the POST-gate state (the (Π₁⊗G)(Π₀⊗I) cross term vanishes);
- phase shifts: U = diag(1,…,e^{iθ}) gives ∂U = iΠ·U and
  dE/dθ = -2·Im⟨λ|Π|φ_k⟩ with Π the all-ones projector over every
  involved qubit;
- compactUnitary(α, β) (non-holomorphic, two complex slots): per real
  component on the PRE-gate state φ' -- ∂U/∂xα = I, ∂U/∂yα = iZ,
  ∂U/∂xβ = -iY, ∂U/∂yβ = iX -- packed to complex cotangents in
  ``jax.grad``'s convention (∂E/∂x + i·∂E/∂y for C→R).

Chain rule through the slot graph: contributions accumulate per *slot*
(so a constant-folded anonymous slot gets its own derivative) and named
slots sharing one Param sum into that Param's gradient.

The backward half walks the tape's DENSE PLAN, not its gates (PR 45): the
tape from the first slot on is planned with the call the serving Engine
makes (``planner.dense_plan``: windows of ``ops.apply.DENSE_WINDOW_QUBITS``), and the
recurrence steps block by block -- φ ← B†φ, one window contraction
T[a, b] = Σ_rest conj(λ[a, rest])·φ[b, rest], λ ← B†λ -- with every
derivative a block holds read off that ONE contraction:
dE/dθ_k = 2·Re Σ_ab T[a, b]·∂B[a, b]/∂θ_k, the VJP of the block's
in-program composition (``fusion._resolve_factors`` + ``_compose_dense`` /
``_compose_diag``) at the cotangent (2 Re T, -2 Im T); blocks that are
one function of their values (a layer's window in every layer) are
composed and differentiated together (:class:`_Composed`). B† is the product
of the daggers below, nothing approximate; the rules above stay the gate
walk's, which an entry the planner passes through (no window holds it)
still takes, and which EVERY entry takes under an active explicit
scheduler, where each gate routes through the scheduler one by one
(docs/gradients.md).

Inverses ride the ordinary routes: parameterized families dagger through
their own public gate functions (negated angle / (α,β) → (α*, -β), traced
branches included), concrete entries dagger through the fusion planner's
spy capture (matrix → M†, diag → conj, parity → -θ, x/swap self-inverse),
so a sharded backward sweep re-uses the explicit scheduler's relocation
machinery gate by gate -- the reversed forward plan. Anything
non-invertible (measurement, trajectory Kraus, channels, pallas-run plan
entries) raises a typed QuESTError at lift time naming the site.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import fusion
from .. import gates as G
from .. import matrices as M
from .. import planner
from .. import telemetry
from ..cache import _canon
from ..capture import capture
from ..circuits import Circuit
from ..events import (GateEvent, _event_diag, _event_is_diag, event_dagger,
                      event_matrix)
from ..ops import reduce as R
from ..ops.apply import _MIN_MINOR, _mxu_precision
from ..params import _CPLX, Param, _SlotRef, materialize_entry
from ..parallel import scheduler as _dist
from ..registers import Qureg
from ..validation import QuESTError
from .expectation import apply_hamiltonian, expectation_value, hamiltonian_terms

__all__ = ["grad_reduce", "gradient_executable", "plan_backward",
           "check_differentiable", "GradExecutable"]


#: positional field names (qureg excluded) per differentiable family --
#: the merge key turning a tape entry's (args, kwargs) into one view
_FIELDS = {
    "phaseShift": ("target", "angle"),
    "controlledPhaseShift": ("q1", "q2", "angle"),
    "multiControlledPhaseShift": ("qubits", "angle"),
    "rotateX": ("target", "angle"),
    "rotateY": ("target", "angle"),
    "rotateZ": ("target", "angle"),
    "rotateAroundAxis": ("target", "angle", "axis"),
    "controlledRotateX": ("control", "target", "angle"),
    "controlledRotateY": ("control", "target", "angle"),
    "controlledRotateZ": ("control", "target", "angle"),
    "controlledRotateAroundAxis": ("control", "target", "angle", "axis"),
    "multiRotateZ": ("qubits", "angle"),
    "multiControlledMultiRotateZ": ("controls", "targets", "angle"),
    "multiRotatePauli": ("targets", "paulis", "angle"),
    "multiControlledMultiRotatePauli": ("controls", "targets", "paulis",
                                        "angle"),
    "compactUnitary": ("target", "alpha", "beta"),
    "controlledCompactUnitary": ("control", "target", "alpha", "beta"),
}

#: jax.grad packs a C→R cotangent as ∂E/∂x - i·∂E/∂y (2·∂E/∂z in
#: Wirtinger terms); complex slot gradients follow the same convention so
#: the oracle comparison is sign-exact
_CPLX_IM = -1.0


def _entry_view(name, args, kwargs) -> dict:
    """Field -> value (``_SlotRef`` template marker or structure constant)."""
    fields = _FIELDS[name]
    view = dict(zip(fields, args))
    for k, v in (kwargs or {}).items():
        view[k] = v
    missing = [f for f in fields if f not in view]
    if missing:
        raise QuESTError(
            f"tape entry '{name}' is missing arguments {missing}", "gradient")
    return view


def _slot_refs(args, kwargs):
    return [a for a in list(args) + list((kwargs or {}).values())
            if isinstance(a, _SlotRef)]


# ---------------------------------------------------------------------------
# derivative rules: static "bracket step" programs per family
# ---------------------------------------------------------------------------

def _proj(qubits):
    """|1⟩⟨1| per qubit -- the controlled-block projector Π₁."""
    return tuple(("diag", (0.0, 1.0), (int(q),)) for q in qubits)


def _zs(qubits):
    return tuple(("diag", (1.0, -1.0), (int(q),)) for q in qubits)


def _pauli_steps(targets, paulis):
    steps = []
    for t, p in zip(targets, paulis):
        p = int(p)
        if p == 1:
            steps.append(("x", None, (int(t),)))
        elif p == 2:
            steps.append(("matrix", M.PAULI_Y_M, (int(t),)))
        elif p == 3:
            steps.append(("diag", (1.0, -1.0), (int(t),)))
    return tuple(steps)


def _axis_generator(axis) -> np.ndarray:
    """Normalised (x·X + y·Y + z·Z) -- rotateAroundAxis's generator."""
    x, y, z = float(axis.x), float(axis.y), float(axis.z)
    norm = np.sqrt(x * x + y * y + z * z)
    if norm == 0.0:
        raise QuESTError("rotateAroundAxis axis has zero norm", "gradient")
    return np.array([[z, x - 1j * y], [x + 1j * y, -z]],
                    dtype=np.complex128) / norm


def _rules(name, view):
    """``(post, pre)`` contribution lists for one entry.

    Each contribution is ``(field, coef, part, steps, comp)``: the slot at
    ``view[field]`` accumulates ``coef * part⟨λ|Op|φ⟩`` where ``Op`` is the
    ``steps`` program, ``part`` picks Re/Im of the bracket, and ``comp``
    says which component of a complex slot it feeds (None for real slots).
    ``post`` brackets evaluate on the post-gate φ_k, ``pre`` on φ_{k-1}.
    """
    post, pre = [], []
    if name in ("rotateX", "rotateY", "rotateZ", "controlledRotateX",
                "controlledRotateY", "controlledRotateZ"):
        axis = name[-1]
        t = int(view["target"])
        ctrl = _proj((view["control"],)) if name.startswith("controlled") \
            else ()
        op = {"X": ("x", None, (t,)),
              "Y": ("matrix", M.PAULI_Y_M, (t,)),
              "Z": ("diag", (1.0, -1.0), (t,))}[axis]
        post.append(("angle", 1.0, "im", ctrl + (op,), None))
    elif name in ("rotateAroundAxis", "controlledRotateAroundAxis"):
        t = int(view["target"])
        ctrl = _proj((view["control"],)) if name.startswith("controlled") \
            else ()
        gen = _axis_generator(view["axis"])
        post.append(("angle", 1.0, "im",
                     ctrl + (("matrix", gen, (t,)),), None))
    elif name == "multiRotateZ":
        post.append(("angle", 1.0, "im", _zs(view["qubits"]), None))
    elif name == "multiControlledMultiRotateZ":
        post.append(("angle", 1.0, "im",
                     _proj(view["controls"]) + _zs(view["targets"]), None))
    elif name == "multiRotatePauli":
        post.append(("angle", 1.0, "im",
                     _pauli_steps(view["targets"], view["paulis"]), None))
    elif name == "multiControlledMultiRotatePauli":
        post.append(("angle", 1.0, "im",
                     _proj(view["controls"])
                     + _pauli_steps(view["targets"], view["paulis"]), None))
    elif name == "phaseShift":
        post.append(("angle", -2.0, "im", _proj((view["target"],)), None))
    elif name == "controlledPhaseShift":
        post.append(("angle", -2.0, "im",
                     _proj((view["q1"], view["q2"])), None))
    elif name == "multiControlledPhaseShift":
        post.append(("angle", -2.0, "im", _proj(view["qubits"]), None))
    elif name in ("compactUnitary", "controlledCompactUnitary"):
        t = int(view["target"])
        ctrl = _proj((view["control"],)) if name.startswith("controlled") \
            else ()
        pre.extend([
            ("alpha", 2.0, "re", ctrl, "re"),
            ("alpha", -2.0, "im", ctrl + (("diag", (1.0, -1.0), (t,)),),
             "im"),
            ("beta", 2.0, "im", ctrl + (("matrix", M.PAULI_Y_M, (t,)),),
             "re"),
            ("beta", -2.0, "im", ctrl + (("x", None, (t,)),), "im"),
        ])
    else:  # pragma: no cover - guarded by plan_backward
        raise QuESTError(f"no derivative rule for '{name}'", "gradient")
    return tuple(post), tuple(pre)


def _apply_steps(shell: Qureg, steps) -> None:
    for kind, payload, qs in steps:
        if kind == "x":
            G._apply_gate_x(shell, qs)
        elif kind == "diag":
            G._apply_gate_diag(shell, list(payload), qs)
        else:
            G._apply_gate_matrix(shell, payload, qs)


def _bracket(lam_amps, phi_amps, steps, num_qubits, part):
    """Re or Im of ⟨λ|Op|φ⟩ with Op the steps program (identity if empty)."""
    if steps:
        shell = Qureg(num_qubits, False, phi_amps, env=None)
        _apply_steps(shell, steps)
        phi_amps = shell.amps
    re, im = R.inner_product(lam_amps, phi_amps)
    return re if part == "re" else im


# ---------------------------------------------------------------------------
# exact daggers
# ---------------------------------------------------------------------------

def _dagger_param(shell: Qureg, name: str, vals: dict) -> None:
    """Apply the entry's exact inverse through its own public gate function
    (traced-angle branches included): angle → -angle for the rotation and
    phase families, (α, β) → (α*, -β) for the compact-unitary family."""
    if name == "compactUnitary":
        G.compactUnitary(shell, vals["target"],
                         jnp.conj(vals["alpha"]), -vals["beta"])
        return
    if name == "controlledCompactUnitary":
        G.controlledCompactUnitary(shell, vals["control"], vals["target"],
                                   jnp.conj(vals["alpha"]), -vals["beta"])
        return
    fields = _FIELDS[name]
    args = [vals[f] for f in fields]
    args[fields.index("angle")] = -vals["angle"]
    getattr(G, name)(shell, *args)


def _apply_event_dagger(shell: Qureg, ev) -> None:
    """Invert one captured GateEvent through the scheduler-aware helpers:
    :func:`..events.event_dagger` builds the inverse event, applied here
    by kind."""
    try:
        inv = event_dagger(ev)
    except ValueError as e:  # pragma: no cover - guarded by plan_backward
        raise QuESTError(str(e), "gradient") from None
    if inv.kind == "matrix":
        G._apply_gate_matrix(shell, inv.matrix, inv.targets,
                             inv.controls, inv.states)
    elif inv.kind == "diag":
        G._apply_gate_diag(shell, inv.diag, inv.targets, inv.controls)
    elif inv.kind == "x":
        G._apply_gate_x(shell, inv.targets, inv.controls, inv.states)
    elif inv.kind == "parity":
        G._apply_gate_parity_phase(shell, inv.theta, inv.targets,
                                   inv.controls)
    elif inv.kind == "swap":
        G.swapGate(shell, inv.targets[0], inv.targets[1])
    else:  # pragma: no cover - event_dagger returns unitary kinds only
        raise QuESTError(f"cannot apply '{inv.kind}' event", "gradient")


# ---------------------------------------------------------------------------
# backward plan
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class _EntryPlan:
    name: str
    param: bool
    view: Optional[tuple] = None      # ((field, template-value), ...)
    post: tuple = ()
    pre: tuple = ()
    events: tuple = ()                # captured GateEvents (concrete entry)


def _site(idx, name):
    return f"tape[{idx}]:{name}"


def _capture_events(fn, args, kwargs, idx, name, num_qubits, dtype):
    """Concrete entry -> invertible GateEvents, or a typed lift-time error
    naming the site."""
    if name == "_apply_dense_block":
        u, qubits = args
        return (GateEvent("matrix", tuple(qubits), matrix=np.asarray(u)),)
    if name == "_apply_gate_diag":
        diag, qubits = args[0], args[1]
        return (GateEvent("diag", tuple(qubits), diag=np.asarray(diag)),)
    if name in ("_apply_pallas_run", "_apply_frame_swap"):
        raise QuESTError(
            f"Circuit.gradient: {_site(idx, name)} is a pallas-fused plan "
            "entry with no gate-by-gate inverse; differentiate the raw "
            "(unfused) circuit -- the gradient program is one jitted "
            "dispatch either way", "gradient")
    events = capture(fn, args, kwargs, num_qubits, dtype)
    if events is None or any(ev.kind in ("channel", "aux") or ev.extended
                             for ev in events):
        hint = (" -- compose measurement statistics via sample_request "
                "instead of differentiating through them"
                if ("easure" in name or "collapse" in name.lower())
                else "")
        raise QuESTError(
            f"Circuit.gradient: {_site(idx, name)} is not invertible, so "
            f"the adjoint backward sweep cannot cross it{hint}", "gradient")
    return tuple(events)


#: plan/reduce caches key on the LiftedTape's identity (entry kwargs make
#: it unhashable); the cached value keeps the tape alive so ids are stable.
#: Circuits memoize their lifted tape per revision, so this deduplicates
#: exactly like an lru would.
_PLAN_CACHE: dict = {}
_REDUCE_CACHE: dict = {}


def _plan_cached(lifted, num_qubits, dtype_str):
    key = (id(lifted), num_qubits, dtype_str)
    hit = _PLAN_CACHE.get(key)
    if hit is not None:
        return hit[1:]
    # the build captures every concrete entry through the planner's spy:
    # set-up time of a gradient program, read as a span of its own
    with telemetry.span("grad.plan_backward"):
        plans, stop = _plan_build(lifted, num_qubits, dtype_str)
        items = _plan_blocks(lifted, plans, stop, num_qubits, dtype_str)
    _PLAN_CACHE[key] = (lifted, plans, stop, items)
    return plans, stop, items


def _plan_build(lifted, num_qubits, dtype_str):
    entries = lifted.entries
    plans = [None] * len(entries)
    first_slot = None
    for idx, (fn, args, kwargs) in enumerate(entries):
        name = getattr(fn, "__name__", str(fn))
        refs = _slot_refs(args, kwargs)
        if name in _FIELDS:
            view = _entry_view(name, args, kwargs)
            post, pre = _rules(name, view)
            plans[idx] = _EntryPlan(name, True, tuple(view.items()),
                                    post, pre)
            if first_slot is None:
                first_slot = idx
        elif refs:
            # a slot outside the differentiable families is a stochastic
            # seed (trajectory Kraus / mid-circuit measurement)
            hint = ("mid-circuit measurement"
                    if name == "applyMidMeasurement"
                    else "trajectory noise")
            raise QuESTError(
                f"Circuit.gradient: {_site(idx, name)} is a {hint} site -- "
                "an undifferentiable stochastic seam; compose it via "
                "sample_request instead of differentiating through it",
                "gradient")
        else:
            plans[idx] = (fn, args, kwargs, name)  # resolved below
    if first_slot is None:
        raise QuESTError(
            "Circuit.gradient: tape has no differentiable parameter slots "
            "(no rotation/phase/compact-unitary entries)", "gradient")
    # entries before the first slot are the effective initial state (state
    # preps included) -- the backward walk never crosses them, so they need
    # no inverse; everything after must be invertible
    dtype = np.dtype(dtype_str)
    for idx in range(first_slot + 1, len(entries)):
        if isinstance(plans[idx], _EntryPlan):
            continue
        fn, args, kwargs, name = plans[idx]
        events = _capture_events(fn, args, kwargs, idx, name,
                                 num_qubits, dtype)
        plans[idx] = _EntryPlan(name, False, events=events)
    return tuple(plans[first_slot:]), first_slot


def gatewise(circuit):
    """``circuit`` with every deferred block spelled out again: a block's
    Param entries come back as recorded, its constant factors as the
    static block entries they would be alone. The same operator, the same
    slots in the same order; memoized per tape revision. ``circuit``
    itself when it holds no deferred block. What the gradient reduce lifts
    (:func:`grad_reduce`, :func:`gradient_executable`) and what
    ``Engine.grad_engine`` hands its companion: the reduce plans the raw
    tape's blocks itself (:func:`_plan_blocks`), so a plan the caller had
    fused must first read as the tape it came from."""
    deferred = fusion._apply_deferred_block
    if not any(f is deferred for f, _, _ in circuit._tape):
        return circuit
    memo = circuit.__dict__.get("_gatewise")
    if memo is not None and memo[0] is circuit._cache_token:
        return memo[1]
    tape = []
    for entry in circuit._tape:
        if entry[0] is not deferred:
            tape.append(entry)
            continue
        spec, values = entry[1][0], entry[1][1:]
        k = 0
        while k < len(spec.factors):
            ev = spec.factors[k]
            if ev.source is not None:
                i, j, count = ev.source
                run = spec.factors[k:k + count]
                if j == 0 and [e.source for e in run] == [
                        (i, m, count) for m in range(count)]:
                    tape.append(materialize_entry(spec.entries[i], values))
                    k += count
                    continue
            if ev.deferred:
                name = getattr(spec.entries[ev.source[0]][0], "__name__", "")
                raise QuESTError(
                    f"'{name}' was split between two fused blocks and "
                    "cannot be spelled out again; use the unfused circuit")
            if _event_is_diag(ev):
                qs = tuple(sorted(ev.support))
                tape.append((G._apply_gate_diag, (_event_diag(ev, qs), qs),
                             {}))
            else:
                win = planner._window(ev.support)
                tape.append((fusion._apply_dense_block,
                             (event_matrix(ev, win), win), {}))
            k += 1
    out = Circuit(circuit.num_qubits, circuit.is_density_matrix)
    out._tape = tape
    circuit.__dict__["_gatewise"] = (circuit._cache_token, out)
    return out


@dataclass(frozen=True)
class _BlockPlan:
    """One block of the tape's dense plan, as the backward walk undoes it:
    a static block holds its DAGGERED operator, a block with Param factors
    its :class:`..planner.DeferredBlock`, for each of the spec's value
    slots the tape slot it reads, and ``like``, the spec's content key:
    blocks with one key are ONE function of their values (a layer's window
    in every layer of an ansatz), composed and differentiated together."""
    kind: str                         # 'dense' | 'diag'
    qubits: tuple
    dagger: Optional[np.ndarray] = None
    spec: Optional[object] = None
    slots: tuple = ()
    like: Optional[tuple] = None


def _plan_blocks(lifted, plans, stop, num_qubits, dtype_str):
    """The backward walk's items: the tape from the first slot on, planned
    with the call ``Engine._plan_program`` makes (``planner.dense_plan``). A block becomes a
    :class:`_BlockPlan`; an entry the planner passes through (no block
    holds it) keeps its :class:`_EntryPlan`. EVERY slot enters the planner
    as a Param named by its index, so a constant angle's gate is a factor
    composed in the program like a named one and keeps its derivative."""
    marks = tuple(Param(str(s.index)) for s in lifted.slots)
    tape = tuple(materialize_entry(e, marks) for e in lifted.entries[stop:])
    plan = planner.dense_plan(tape, num_qubits, np.dtype(dtype_str))
    items, at = [], 0
    for item in plan.items:
        if isinstance(item, tuple):
            while tape[at][1] is not item[1]:
                at += 1
            items.append(plans[at])
            at += 1
            continue
        diag = isinstance(item, planner.DiagBlock)
        kind = "diag" if diag else "dense"
        if item.factors is None:
            op = np.conj(item.diag) if diag else np.conj(item.matrix).T
            items.append(_BlockPlan(kind, tuple(item.qubits), dagger=op))
            continue
        _, (spec, *values), _ = fusion._deferred_entry(item)
        items.append(_BlockPlan(kind, tuple(item.qubits), spec=spec,
                                slots=tuple(int(v.name) for v in values),
                                like=_canon(spec)))
    return tuple(items)


def plan_backward(lifted, num_qubits: int, dtype=None):
    """``(plans, stop)``: per-entry backward plans for entries ``stop..P-1``
    (``stop`` = first slot-bearing entry; the prefix is the effective
    initial state). Raises a typed :class:`QuESTError` naming the first
    non-invertible site."""
    dt = np.dtype(dtype if dtype is not None else jnp.result_type(float))
    return _plan_cached(lifted, num_qubits, dt.str)[:2]


def check_differentiable(circuit, dtype=None) -> int:
    """Satellite audit entry point: validate every tape item is adjoint-
    differentiable, returning the slot count. Typed QuESTError (offending
    site named) otherwise."""
    if circuit.is_density_matrix:
        raise QuESTError(
            "Circuit.gradient: density-matrix tapes are not supported by "
            "the adjoint sweep (⟨λ|∂G|φ⟩ needs pure states); use a "
            "statevector register", "gradient")
    lifted = gatewise(circuit).lifted()
    plan_backward(lifted, circuit.num_qubits, dtype)
    return len(lifted.slots)


# ---------------------------------------------------------------------------
# a block undone: one contraction gives every derivative it holds
# ---------------------------------------------------------------------------

def _window_contraction(lam, phi, n, lo, hi):
    """``(re, im)`` of T[a, b] = sum_rest conj(lam[a, rest]) phi[b, rest]
    over the contiguous window [lo, hi] (qubit lo is bit 0 of a and b): one
    pass over the two registers, contracted over every qubit outside the
    window in the views :func:`..ops.apply._apply_matrix_window` applies a
    block in, so no register is transposed. A window that starts below the
    lane boundary is contracted over the low ``w`` qubits whole and the
    qubits of ``w`` outside the window traced out of the small result
    (4 * 4^w numbers a lane, w at most MAX_LOW_WINDOW_TOP: what the
    expanded matrix of that block's own application holds)."""
    mm = partial(jnp.einsum, precision=_mxu_precision(phi.dtype))
    dim = 1 << (hi - lo + 1)
    if lo >= _MIN_MINOR:
        view = (2, -1, dim, 1 << lo)
        m = mm("pgak,qgbk->pqab", lam.reshape(view), phi.reshape(view))
    else:
        w = min(max(hi + 1, _MIN_MINOR), n)
        view = (2, -1, 1 << w)
        m = mm("pri,qrj->pqij", lam.reshape(view), phi.reshape(view))
        above, below = 1 << (w - 1 - hi), 1 << lo
        if above * below > 1:
            m = jnp.einsum("pqhalhbl->pqab", m.reshape(
                (2, 2) + 2 * (above, dim, below)))
    return m[0, 0] + m[1, 1], m[0, 1] - m[1, 0]


def _diag_contraction(lam, phi, n, qubits):
    """``(re, im)`` of T[s] = sum_rest conj(lam[s, rest]) phi[s, rest] over
    the (possibly scattered) ``qubits`` (qubits[j] is bit j of s): the
    diagonal of the window contraction, which is all a diagonal block's
    derivative reads. One elementwise pass, then the rest summed away in
    views whose minor dimension stays a lane row: the qubits from the lane
    boundary up one at a time from the top (each sum leaves at most what
    it read), the ones below it by ONE small 0/1 matrix on the lane axis
    (the grouped view of every qubit at once pads its 2-sized minor axes
    to whole tiles on the chip: 268 MB for an 8 MiB register)."""
    lanes = min(n, _MIN_MINOR)
    high = [q for q in qubits if q >= lanes]
    low = [q for q in qubits if q < lanes]
    pick = np.zeros((1 << lanes, 1 << len(low)), dtype=phi.dtype)
    rows = np.arange(1 << lanes)
    pick[rows, sum(((rows >> q) & 1) << j for j, q in enumerate(low))] = 1

    def down(c):
        c, top = c.reshape(1, -1), n
        for q in reversed(high):
            c = c.reshape(c.shape[0], 1 << (top - 1 - q), 2, 1 << q).sum(1)
            c, top = c.reshape(-1, 1 << q), q
        c = c.reshape(c.shape[0], -1, 1 << lanes).sum(1)
        return jnp.matmul(c, pick, precision=_mxu_precision(c.dtype)
                          ).reshape(-1)

    return (down(lam[0] * phi[0] + lam[1] * phi[1]),
            down(lam[0] * phi[1] - lam[1] * phi[0]))


class _Composed:
    """The operators of the plan's blocks with Params and, after the walk,
    their slots' derivatives. Blocks that are one function of their values
    (``_BlockPlan.like``) are composed by ONE ``vmap`` of
    ``fusion._resolve_factors`` + ``_compose_dense`` / ``_compose_diag``
    over their stacked values and differentiated by ONE VJP at their
    stacked cotangents: the trace and the program hold a composition a
    structure, not one a block (three for the twelve blocks of a four-layer
    ansatz on 20 qubits)."""

    def __init__(self, items, values, n, dtype):
        alike = {}
        for item in items:
            if isinstance(item, _BlockPlan) and item.spec is not None:
                alike.setdefault(item.like, []).append(item)
        self._row, self._groups = {}, []
        for members in alike.values():
            head = members[0]

            def compose(local, head=head):
                events = fusion._resolve_factors(head.spec, local, n, dtype)
                return (fusion._compose_diag if head.kind == "diag"
                        else fusion._compose_dense)(events, head.qubits,
                                                    dtype)

            stacked = tuple(jnp.stack([values[m.slots[k]] for m in members])
                            for k in range(len(head.slots)))
            (re, im), vjp = jax.vjp(jax.vmap(compose), stacked)
            for row, m in enumerate(members):
                self._row[id(m)] = (len(self._groups), row)
            self._groups.append((members, re, im, vjp, [None] * len(members)))

    def operator(self, item):
        """``(re, im)`` planes of the block's operator B."""
        group, row = self._row[id(item)]
        _, re, im, _, _ = self._groups[group]
        return re[row], im[row]

    def contracted(self, item, t_re, t_im):
        """The block's T: dE/dtheta = 2 Re sum T dB/dtheta, so the
        cotangent of B's planes is (2 Re T, -2 Im T)."""
        group, row = self._row[id(item)]
        self._groups[group][4][row] = (2.0 * t_re, -2.0 * t_im)

    def harvest(self, grads):
        """Every slot's derivative, added into ``grads`` by tape slot.
        Complex slots come back in ``jax.grad``'s convention
        (``_CPLX_IM``), which is the VJP's own."""
        for members, _, _, vjp, cts in self._groups:
            (local,) = vjp((jnp.stack([c[0] for c in cts]),
                            jnp.stack([c[1] for c in cts])))
            for row, m in enumerate(members):
                for i, g in zip(m.slots, local):
                    grads[i] = g[row] if grads[i] is None \
                        else grads[i] + g[row]


def _undo_block(item, phi: Qureg, lamq: Qureg, composed) -> int:
    """Undo one block of the plan on both registers; returns the
    contractions made (one for a block with Param factors, none for a
    static one).

    With B the block's operator, phi_pre = B^dagger phi_post and
    dE/dtheta = 2 Re <lam_post| dB/dtheta |phi_pre>
              = 2 Re sum_ab T[a, b] dB[a, b]/dtheta,
    T contracted from the two registers once a BLOCK; every slot's
    derivative is then the composition's VJP at T's cotangent
    (:class:`_Composed`): O(4^|W|) a factor, no pass over a register."""
    n = phi.num_qubits_represented
    diag = item.kind == "diag"
    apply = G._apply_gate_diag if diag else G._apply_gate_matrix
    if item.spec is None:
        apply(phi, item.dagger, item.qubits)
        apply(lamq, item.dagger, item.qubits)
        return 0
    re, im = composed.operator(item)
    dagger = jax.lax.complex(re, -im) if diag else jax.lax.complex(re.T, -im.T)
    apply(phi, dagger, item.qubits)
    if diag:
        t = _diag_contraction(lamq.amps, phi.amps, n, item.qubits)
    else:
        t = _window_contraction(lamq.amps, phi.amps, n,
                                item.qubits[0], item.qubits[-1])
    apply(lamq, dagger, item.qubits)
    composed.contracted(item, *t)
    return 1


# ---------------------------------------------------------------------------
# the reduce: forward value + backward sweep, one traceable program
# ---------------------------------------------------------------------------

class _Parcel:
    """How ONE gradient's result leaves the program: one real vector,
    ``[value, a derivative a slot, a derivative a Param name]`` in slot
    order then first-appearance order of the names, a complex slot's (or
    name's) derivative as two entries, real then imaginary. A program that
    hands its numbers back as outputs of their own pays for each at the
    host-device boundary (a buffer, a ``jax.Array``: 2,568 a batch of
    eight on a 160-angle ansatz); the vector crosses in one transfer and
    :meth:`unpack` names its entries on the host."""

    def __init__(self, slots):
        kinds = {}
        for s in slots:
            if s.name is not None:
                kinds[s.name] = kinds.get(s.name, False) or s.kind == _CPLX
        self.num_slots, self.names = len(slots), tuple(kinds)
        #: per entry, whether it takes two columns of the vector
        self.complex = (False, *(s.kind == _CPLX for s in slots),
                        *kinds.values())
        self._all_real = not any(self.complex)

    def pack(self, tree):
        """The vector of a ``{"value", "grads", "slot_grads"}`` tree,
        inside the program."""
        parts = []
        for e, cplx in zip((tree["value"], *tree["slot_grads"],
                            *tree["grads"].values()), self.complex):
            parts += [jnp.real(e), jnp.imag(e)] if cplx else [e]
        return jnp.stack(parts)

    def unpack(self, vector):
        """The tree of one lane's vector, on the host: numpy scalars at the
        program's width, no device array among them."""
        row = np.asarray(vector)
        got = list(row)
        if not self._all_real:
            ctype = np.result_type(row.dtype, np.complex64).type
            cols = iter(got)
            got = [ctype(complex(next(cols), next(cols))) if cplx
                   else next(cols) for cplx in self.complex]
        cut = 1 + self.num_slots
        return {"value": got[0], "grads": dict(zip(self.names, got[cut:])),
                "slot_grads": tuple(got[1:cut])}


def _accumulate(grads, ref, g, comp):
    idx = ref.index
    if comp == "im":
        g = (_CPLX_IM * 1j) * g
    cur = grads[idx]
    grads[idx] = g if cur is None else cur + g


def _cached_reduce(lifted, num_qubits, codes, coeffs, dtype_str):
    key = (id(lifted), num_qubits, codes, coeffs, dtype_str)
    hit = _REDUCE_CACHE.get(key)
    if hit is not None:
        return hit[1]
    plans, stop, items = _plan_cached(lifted, num_qubits, dtype_str)
    slots = lifted.slots
    slot_count = len(slots)
    blocks = [i for i in items if isinstance(i, _BlockPlan)]
    telemetry.event(
        "grad.plan", num_qubits=num_qubits, entries=len(plans),
        slots=slot_count, terms=len(codes),
        param_entries=sum(1 for p in plans if p.param),
        concrete_events=sum(len(p.events) for p in plans),
        first_slot=stop, blocks=len(blocks),
        param_blocks=sum(1 for b in blocks if b.spec is not None))

    def applied(sweep, count=1):
        # what ONE gradient's program applies to a whole register, counted
        # as this body walks: once a trace, like fusion_df_passes_total
        if count:
            telemetry.inc("grad_sweep_entries_total", count, sweep=sweep)

    def sweep(amps, values):
        lam = apply_hamiltonian(amps, codes=codes, coeffs=coeffs,
                                num_qubits=num_qubits)
        applied("hamiltonian", len(codes))
        value = expectation_value(amps, lam)
        grads = [None] * slot_count
        phi = Qureg(num_qubits, False, amps, env=None)
        lamq = Qureg(num_qubits, False, lam, env=None)
        # the items of the dense plan; gate by gate where an explicit
        # scheduler routes every gate itself (docs/gradients.md)
        walk = plans if _dist.active() is not None else items
        composed = _Composed(walk, values, num_qubits, phi.dtype)
        for plan in reversed(walk):
            if isinstance(plan, _BlockPlan):
                applied("bracket", _undo_block(plan, phi, lamq, composed))
                applied("backward_phi")
                applied("backward_lambda")
            elif plan.param:
                view = dict(plan.view)
                vals = {f: (values[v.index] if isinstance(v, _SlotRef)
                            else v) for f, v in view.items()}
                for field, coef, part, steps, comp in plan.post:
                    g = coef * _bracket(lamq.amps, phi.amps, steps,
                                        num_qubits, part)
                    _accumulate(grads, view[field], g, comp)
                _dagger_param(phi, plan.name, vals)
                for field, coef, part, steps, comp in plan.pre:
                    g = coef * _bracket(lamq.amps, phi.amps, steps,
                                        num_qubits, part)
                    _accumulate(grads, view[field], g, comp)
                _dagger_param(lamq, plan.name, vals)
                applied("bracket", len(plan.post) + len(plan.pre))
                applied("backward_phi")
                applied("backward_lambda")
            else:
                for ev in reversed(plan.events):
                    _apply_event_dagger(phi, ev)
                for ev in reversed(plan.events):
                    _apply_event_dagger(lamq, ev)
                applied("backward_phi", len(plan.events))
                applied("backward_lambda", len(plan.events))
        composed.harvest(grads)
        slot_grads = tuple(
            g if g is not None else jnp.real(values[i]) * 0.0
            for i, g in enumerate(grads))
        named = {}
        for s, g in zip(slots, slot_grads):
            if s.name is not None:
                named[s.name] = named[s.name] + g if s.name in named else g
        return {"value": value, "grads": named, "slot_grads": slot_grads}

    parcel = _Parcel(slots)

    def grad_fn(amps, values):
        return parcel.pack(sweep(amps, values))

    grad_fn.wants_values = True
    grad_fn.dispatch_route = "grad_request"
    grad_fn.num_slots = slot_count
    # the result is stated once, as a vector: whoever composes this reduce
    # fetches it and names its entries on the host with ``unpack``
    # (``tree`` is the sweep's own dict, which the vector is tested against)
    grad_fn.unpack = parcel.unpack
    grad_fn.tree = sweep
    grad_fn.hamiltonian = (codes, coeffs)
    _REDUCE_CACHE[key] = (lifted, grad_fn)
    return grad_fn


def grad_reduce(circuit, hamiltonian, *, dtype=None):
    """The values-aware finalize lowering a circuit's adjoint gradient into
    its parameterized replay: ``reduce(ψ, values)`` is ONE real vector
    (:class:`_Parcel`) and ``reduce.unpack(vector)``, on the host, the
    ``{"value", "grads", "slot_grads"}`` it stands for. Cached per (tape
    structure, Hamiltonian, dtype) so warm optimizer loops share one
    compiled program (zero retraces)."""
    codes, coeffs = hamiltonian_terms(hamiltonian, circuit.num_qubits)
    check_differentiable(circuit, dtype)
    dt = np.dtype(dtype if dtype is not None else jnp.result_type(float))
    return _cached_reduce(gatewise(circuit).lifted(), circuit.num_qubits,
                          codes, coeffs, dt.str)


# ---------------------------------------------------------------------------
# host-facing executable
# ---------------------------------------------------------------------------

class GradExecutable:
    """A compiled gradient program bound to one circuit's slot layout.

    ``__call__(amps, params)`` runs forward + backward + accumulation as
    ONE device dispatch (``device_dispatch_total{route="grad_request"}``),
    fetches the program's one result vector and returns ``{"value",
    "grads", "slot_grads"}`` of host scalars.
    """

    def __init__(self, ex, reduce_fn):
        self._ex = ex
        self._reduce = reduce_fn
        self.lifted = ex.lifted
        self.fingerprint = ex.fingerprint

    @property
    def param_names(self):
        return self._ex.param_names

    @property
    def num_slots(self):
        return self._reduce.num_slots

    def bind(self, params=None):
        return self._ex.bind(params)

    def with_values(self, amps, values):
        telemetry.inc("grad_requests_total")
        telemetry.inc("grad_slots_total", self._reduce.num_slots)
        telemetry.inc("device_dispatch_total", route="grad_request")
        return self._reduce.unpack(self._ex.with_values(amps, values))

    def __call__(self, amps, params=None):
        return self.with_values(amps, self.bind(params))


def gradient_executable(circuit, hamiltonian, *, donate=True, dtype=None):
    """Compile ``circuit``'s adjoint gradient against a Pauli-sum
    Hamiltonian -- the implementation behind :meth:`Circuit.gradient`."""
    # the sweep harvests a derivative per Param gate: a dense plan's
    # deferred blocks are spelled out again (the slots keep their order)
    circuit = gatewise(circuit)
    reduce_fn = grad_reduce(circuit, hamiltonian, dtype=dtype)
    ex = circuit.parameterized(donate=donate, reduce=reduce_fn)
    return GradExecutable(ex, reduce_fn)
