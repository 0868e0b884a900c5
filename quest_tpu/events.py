"""Captured gate events and their host algebra.

A :class:`GateEvent` is one primitive application captured from a tape
entry (:mod:`.capture`): kind, qubits and a numpy operand. What follows
from an event alone lives here with it -- its inverse, its dense operator
or diagonal over a block's qubits -- so that the planner above
(:mod:`.planner`), the kernels' host-side zone folding below
(``ops.pallas_gates._fold_zone_ops``) and the adjoint sweep
(:mod:`.gradients.adjoint`) read one algebra. Host only: numpy, no device
array, nothing of the package imported.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np


# ---------------------------------------------------------------------------
# captured gate events
# ---------------------------------------------------------------------------

@dataclass
class GateEvent:
    """One primitive application captured from a tape entry.

    kind: 'matrix' | 'diag' | 'x' | 'parity' | 'swap' | 'channel'

    Events captured from an entry recorded with params.Param carry
    ``source = (entry, index, count)``: the ``(fn, args, kwargs)`` tape
    entry, the event's position among the ``count`` events that entry
    captures. Those whose coefficients are runtime values are DEFERRED
    (``theta`` None and no ``matrix`` / ``diag``): only the structure --
    kind, targets, controls, control states -- is known at plan time, and
    the operand is produced at trace time by running the same capture on
    the materialised entry (:func:`_resolve_factors`), where it may be a
    traced array.

    ``extended=True`` marks events that take no conj-shadow twin during
    density planning. For 'diag' events captured from the dephasing
    appliers the targets are already FLATTENED-state coordinates (column
    qubits at q + n explicit); 'channel' events instead carry ROW targets
    only -- their lowering (_lower_channel) and access sets
    (circuits._tape_accesses) add the + n column coordinates themselves.
    """
    kind: str
    targets: tuple
    controls: tuple = ()
    states: tuple = ()
    matrix: Optional[np.ndarray] = None   # 'matrix': (2^t, 2^t) complex
    diag: Optional[np.ndarray] = None     # 'diag':   (2^t,) complex
    theta: Optional[float] = 0.0          # 'parity'; None: deferred
    superop: Optional[np.ndarray] = None  # 'channel': (4^t, 4^t) complex
    #: 'channel' recorded by mixDepolarising / mixTwoQubitDepolarising: the
    #: call's probability (the channel's name is its target count), which
    #: selects the closed-form kernel op (:func:`_lower_channel`)
    depol: Optional[float] = None
    extended: bool = False                # targets already in 2n coords
    source: Optional[tuple] = None        # (entry, event index, count)

    @property
    def support(self) -> frozenset:
        return frozenset(self.targets) | frozenset(self.controls)

    @property
    def deferred(self) -> bool:
        return self.theta is None

    @property
    def structure(self) -> tuple:
        return (self.kind, tuple(self.targets), tuple(self.controls),
                tuple(self.states))


def event_dagger(ev: GateEvent) -> GateEvent:
    """The exact inverse of a captured unitary event, as a new event.

    Unitary kinds only: 'matrix' conjugate-transposes its block, 'diag'
    conjugates its diagonal, 'parity' negates its angle, 'x' and 'swap'
    are self-inverse. 'channel'/'aux' events (and ``extended`` density
    shadows) are not unitary -- no inverse exists; raising here is what
    lets the adjoint gradient planner (quest_tpu/gradients/adjoint.py)
    turn "cannot invert" into a typed lift-time error naming the site.
    """
    if ev.kind == "matrix" and ev.matrix is not None and not ev.extended:
        return GateEvent("matrix", ev.targets, ev.controls, ev.states,
                         matrix=np.conj(np.asarray(ev.matrix)).T)
    if ev.kind == "diag" and ev.diag is not None and not ev.extended:
        return GateEvent("diag", ev.targets, ev.controls, ev.states,
                         diag=np.conj(np.asarray(ev.diag)))
    if ev.kind == "parity":
        return GateEvent("parity", ev.targets, ev.controls, ev.states,
                         theta=-ev.theta)
    if ev.kind in ("x", "swap"):
        return ev
    raise ValueError(f"'{ev.kind}' event has no unitary inverse")



# ---------------------------------------------------------------------------
# dense embedding of one event into a block's qubit space
# ---------------------------------------------------------------------------

def event_matrix(ev: GateEvent, block_qubits: Sequence[int]) -> np.ndarray:
    """The event's full operator on ``block_qubits`` (ascending order; qubit
    block_qubits[j] is bit j of the matrix index). Controls are folded in
    (identity on control-unsatisfied states). Matrix index convention matches
    apply_matrix: for the event's own matrix, targets[k] is bit k
    (reference multiQubitUnitary doc, QuEST.h:5193)."""
    pos = {q: j for j, q in enumerate(block_qubits)}
    k = len(block_qubits)
    N = 1 << k
    out = np.zeros((N, N), dtype=complex)

    cbits = [pos[c] for c in ev.controls]
    states = ev.states if ev.states else (1,) * len(ev.controls)
    tbits = [pos[q] for q in ev.targets]
    t = len(ev.targets)

    if ev.kind == "matrix":
        M = ev.matrix
    elif ev.kind == "diag":
        M = np.diag(ev.diag)
    elif ev.kind == "x":
        M = None  # pure bit-flip, handled per column below
    elif ev.kind == "swap":
        M = np.array([[1, 0, 0, 0], [0, 0, 1, 0],
                      [0, 1, 0, 0], [0, 0, 0, 1]], dtype=complex)
    elif ev.kind == "parity":
        # exp(-i theta/2 Z x...x Z): diagonal, phase sign by parity of bits
        d = np.empty(1 << t, dtype=complex)
        for s in range(1 << t):
            par = bin(s).count("1") & 1
            d[s] = np.exp(-1j * ev.theta / 2 * (1 - 2 * par))
        M = np.diag(d)
    else:  # pragma: no cover
        raise ValueError(f"unknown event kind {ev.kind!r}")

    for s in range(N):
        if any(((s >> c) & 1) != st for c, st in zip(cbits, states)):
            out[s, s] = 1.0
            continue
        if ev.kind == "x":
            s2 = s
            for b in tbits:
                s2 ^= 1 << b
            out[s2, s] = 1.0
            continue
        col = 0
        for j, b in enumerate(tbits):
            col |= ((s >> b) & 1) << j
        base = s
        for b in tbits:
            base &= ~(1 << b)
        for row in range(1 << t):
            s2 = base
            for j, b in enumerate(tbits):
                s2 |= ((row >> j) & 1) << b
            out[s2, s] = M[row, col]
    return out


def _embed_block(U: np.ndarray, old_qubits: Sequence[int],
                 new_qubits: Sequence[int]) -> np.ndarray:
    """Re-embed a block unitary when its qubit set grows (kron with identity
    on the added qubits, bits interleaved by qubit order)."""
    if tuple(old_qubits) == tuple(new_qubits):
        return U
    ev = GateEvent("matrix", tuple(old_qubits), matrix=U)
    return event_matrix(ev, new_qubits)



_DIAG_KINDS = ("diag", "parity")


def _event_is_diag(ev: GateEvent) -> bool:
    return ev.kind in _DIAG_KINDS


def _event_diag(ev: GateEvent, qubits: Sequence[int]) -> np.ndarray:
    """The event's diagonal over ``qubits`` (ascending; qubits[j] is bit j).
    Only valid for diagonal-kind events; controls folded in."""
    pos = {q: j for j, q in enumerate(qubits)}
    k = len(qubits)
    cbits = [pos[c] for c in ev.controls]
    states = ev.states if ev.states else (1,) * len(ev.controls)
    tbits = [pos[q] for q in ev.targets]
    out = np.ones(1 << k, dtype=complex)
    for s in range(1 << k):
        if any(((s >> c) & 1) != st for c, st in zip(cbits, states)):
            continue
        if ev.kind == "parity":
            par = bin(sum(((s >> b) & 1) << j for j, b in enumerate(tbits))).count("1") & 1
            out[s] = np.exp(-1j * ev.theta / 2 * (1 - 2 * par))
        else:
            idx = sum(((s >> b) & 1) << j for j, b in enumerate(tbits))
            out[s] = ev.diag[idx]
    return out

