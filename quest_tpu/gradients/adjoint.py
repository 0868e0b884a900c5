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

Derivative rules per lifted family (``engine/params._LIFTABLE``):

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
from typing import Optional

import jax.numpy as jnp
import numpy as np

from .. import gates as G
from .. import matrices as M
from .. import telemetry
from ..engine.params import _SlotRef
from ..fusion import gatewise
from ..ops import reduce as R
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
    :func:`..fusion.event_dagger` builds the inverse event, applied here
    by kind."""
    from ..fusion import event_dagger

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
    from .. import fusion

    if name == "_apply_dense_block":
        u, qubits = args
        return (fusion.GateEvent("matrix", tuple(qubits),
                                 matrix=np.asarray(u)),)
    if name == "_apply_gate_diag":
        diag, qubits = args[0], args[1]
        return (fusion.GateEvent("diag", tuple(qubits),
                                 diag=np.asarray(diag)),)
    if name in ("_apply_pallas_run", "_apply_frame_swap"):
        raise QuESTError(
            f"Circuit.gradient: {_site(idx, name)} is a pallas-fused plan "
            "entry with no gate-by-gate inverse; differentiate the raw "
            "(unfused) circuit -- the gradient program is one jitted "
            "dispatch either way", "gradient")
    events = fusion.capture(fn, args, kwargs, num_qubits, dtype)
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
        return hit[1], hit[2]
    # the build captures every concrete entry through the planner's spy:
    # set-up time of a gradient program, read as a span of its own
    with telemetry.span("grad.plan_backward"):
        plans, stop = _plan_build(lifted, num_qubits, dtype_str)
    _PLAN_CACHE[key] = (lifted, plans, stop)
    return plans, stop


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


def plan_backward(lifted, num_qubits: int, dtype=None):
    """``(plans, stop)``: per-entry backward plans for entries ``stop..P-1``
    (``stop`` = first slot-bearing entry; the prefix is the effective
    initial state). Raises a typed :class:`QuESTError` naming the first
    non-invertible site."""
    dt = np.dtype(dtype if dtype is not None else jnp.result_type(float))
    return _plan_cached(lifted, num_qubits, dt.str)


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
# the reduce: forward value + backward sweep, one traceable program
# ---------------------------------------------------------------------------

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
    plans, stop = _plan_cached(lifted, num_qubits, dtype_str)
    slots = lifted.slots
    slot_count = len(slots)
    telemetry.event(
        "grad.plan", num_qubits=num_qubits, entries=len(plans),
        slots=slot_count, terms=len(codes),
        param_entries=sum(1 for p in plans if p.param),
        concrete_events=sum(len(p.events) for p in plans),
        first_slot=stop)

    def applied(sweep, count=1):
        # what ONE gradient's program applies to a whole register, counted
        # as this body walks: once a trace, like fusion_df_passes_total
        if count:
            telemetry.inc("grad_sweep_entries_total", count, sweep=sweep)

    def grad_fn(amps, values):
        lam = apply_hamiltonian(amps, codes=codes, coeffs=coeffs,
                                num_qubits=num_qubits)
        applied("hamiltonian", len(codes))
        value = expectation_value(amps, lam)
        grads = [None] * slot_count
        phi = Qureg(num_qubits, False, amps, env=None)
        lamq = Qureg(num_qubits, False, lam, env=None)
        for plan in reversed(plans):
            if plan.param:
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
        slot_grads = tuple(
            g if g is not None else jnp.real(values[i]) * 0.0
            for i, g in enumerate(grads))
        named = {}
        for s, g in zip(slots, slot_grads):
            if s.name is not None:
                named[s.name] = named[s.name] + g if s.name in named else g
        return {"value": value, "grads": named, "slot_grads": slot_grads}

    grad_fn.wants_values = True
    grad_fn.dispatch_route = "grad_request"
    grad_fn.num_slots = slot_count
    grad_fn.hamiltonian = (codes, coeffs)
    _REDUCE_CACHE[key] = (lifted, grad_fn)
    return grad_fn


def grad_reduce(circuit, hamiltonian, *, dtype=None):
    """The values-aware finalize lowering a circuit's adjoint gradient into
    its parameterized replay: ``reduce(ψ, values) -> {"value", "grads",
    "slot_grads"}``. Cached per (tape structure, Hamiltonian, dtype) so
    warm optimizer loops share one compiled program (zero retraces)."""
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
    ONE device dispatch (``device_dispatch_total{route="grad_request"}``)
    and returns ``{"value", "grads", "slot_grads"}``.
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
        return self._ex.with_values(amps, values)

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
