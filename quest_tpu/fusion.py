"""Tape-level gate fusion: contract runs of gates into k-qubit unitaries.

The reference executes one kernel (and one MPI exchange, when distributed)
per gate -- its cost model is per-gate (QuEST_cpu_distributed.c:870-905).
On TPU the optimal execution unit is much coarser: a block of consecutive
gates whose combined support fits in k qubits multiplies into a single
2^k x 2^k unitary **on the host** (numpy, trace-time), and the whole block
hits the state as one dense matmul that XLA tiles onto the MXU. A deep
circuit collapses from hundreds of elementwise passes into a handful of
GEMMs: fewer HBM round-trips, drastically smaller XLA programs (compile
time scales with op count), and MXU utilisation instead of VPU.

This is the standard dense-fusion technique of state-vector simulators
(qsim's gate fusion, cuQuantum's custatevecApplyMatrix batching); the
reference itself has no analogue -- it is pure TPU-side gain.

Mechanics: each recorded tape entry is *replayed once against a spy
register*: handed a spy, the gate-application primitives record
(kind, operands, qubits) instead of touching any device array (the spy
carries its recorders, ops.spy: nothing process-wide is patched). Entries
that don't route through the four gate primitives (decoherence, phase
functions, state inits, ...) simply fail capture and act as fusion
barriers, passing through to the device path unchanged -- so ``fused()``
is semantics-preserving for arbitrary tapes.

Blocks that remain diagonal are emitted through the broadcast-multiply
diagonal kernel (no matmul, one VPU pass) instead of a dense GEMM.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from functools import lru_cache, partial
from typing import NamedTuple, Optional, Sequence

import numpy as np

from . import precision
from . import telemetry
# the ambient replay mesh lives with the mesh (environment.py) so the
# register layer can read it; fusion.pallas_mesh stays the documented name
from .environment import active_pallas_mesh, pallas_mesh  # noqa: F401
from .ops.spy import Spy


# ---------------------------------------------------------------------------
# captured gate events
# ---------------------------------------------------------------------------

@dataclass
class GateEvent:
    """One primitive application captured from a tape entry.

    kind: 'matrix' | 'diag' | 'x' | 'parity' | 'swap' | 'channel'

    Events captured from an entry recorded with engine.params.Param carry
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
    from .matrices import is_traced

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
    """True when a tape entry carries engine.params.Param placeholders:
    there is no concrete matrix to fuse at plan time. The dense planner
    captures such an entry's STRUCTURE (:func:`_capture_deferred`) and
    lets it join a block whose matrix is assembled inside the program; the
    Pallas planner passes it through as a barrier assembled at apply time.
    Either way the plan's structure stays value-independent and one
    compiled replay serves every parameter vector."""
    from .engine.params import has_params

    return has_params(args, kwargs)


def _event_traced(ev: GateEvent) -> bool:
    from .matrices import is_traced

    return is_traced(ev.matrix, ev.diag, ev.theta)


def _deferrable(ev: GateEvent) -> bool:
    """The deferred factors the in-trace composition takes: what the
    liftable family (engine.params._LIFTABLE) captures to -- one-target
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
    import jax

    from .engine.params import bind, lift_tape, materialize_entry
    from .validation import QuESTError

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
    from .parallel import scheduler as _dist

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


# ---------------------------------------------------------------------------
# the fuser
# ---------------------------------------------------------------------------

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


@dataclass
class FusedBlock:
    """A dense unitary over a *contiguous* qubit window [qubits[0], qubits[-1]].

    Contiguity is load-bearing: a contiguous window applies with zero
    transposes as one MXU GEMM (ops.apply._apply_matrix_window), whereas
    scattered targets take the grouped-transpose path whose high-rank
    intermediates tile-pad catastrophically at large n.

    A block any of whose factors is deferred (GateEvent.deferred) has no
    product at plan time: ``matrix`` is None and ``factors`` holds the
    ordered GateEvents (first applied first), the block's static prefix as
    one of them; :func:`_compose_dense` multiplies them out at apply time
    and the product goes through the gate primitive."""
    qubits: tuple            # ascending contiguous run; qubits[j] is bit j
    matrix: Optional[np.ndarray]      # (2^k, 2^k) complex; None if deferred
    factors: Optional[tuple] = None   # deferred: the ordered GateEvents

    def factored(self) -> "FusedBlock":
        """The block as its factor list (a static block: its product as
        the one factor), the form that is applied through the gate
        primitive -- the XLA window GEMM, which batches under ``vmap`` --
        and never through :func:`_apply_dense_block`'s lane kernel over
        ONE (2, 2^n) state."""
        return FusedBlock(self.qubits, None, _block_factors(self))


@dataclass
class DiagBlock:
    """An accumulated diagonal over (possibly scattered) support qubits --
    diagonals broadcast against the grouped view without any transpose, so
    they need no window constraint. Deferred like :class:`FusedBlock`:
    ``diag`` None, ``factors`` the ordered diagonal-kind GateEvents."""
    qubits: tuple            # ascending; qubits[j] is bit j of the diag index
    diag: Optional[np.ndarray]        # (2^k,) complex; None if deferred
    factors: Optional[tuple] = None


def _block_factors(block) -> tuple:
    """A block as an ordered factor list: its own when deferred, else its
    static product as the one factor."""
    if block.factors is not None:
        return block.factors
    if isinstance(block, DiagBlock):
        return (GateEvent("diag", block.qubits, diag=block.diag),)
    return (GateEvent("matrix", block.qubits, matrix=block.matrix),)


@dataclass
class DeferredBlock:
    """What a deferred block's tape entry carries
    (``(_apply_deferred_block, (DeferredBlock, *values), {})``): the
    block's factors with each ``source`` rewritten to ``(index into
    entries, event index, count)``, and ``entries``, the source tape entries
    as lifted templates (engine.params.lift_tape) whose slots are the
    entry's trailing ``values``, kinds in ``slot_kinds``. Everything here
    is structure: the values ride beside it, where ``lift_tape`` finds
    them."""
    kind: str                # 'dense' | 'diag'
    qubits: tuple
    factors: tuple
    entries: tuple
    slot_kinds: tuple


@dataclass
class FusePlan:
    #: sequence of FusedBlock | DiagBlock | (fn, args, kwargs) passthroughs
    items: list = field(default_factory=list)
    num_fused_gates: int = 0
    num_barriers: int = 0
    #: times the list scheduler widened a pending run's frame to take an op
    #: that fitted no pending run (``_FramePlanner._grown``)
    frames_grown: int = 0


@dataclass(frozen=True)
class PallasRun:
    """A run of tile-local 1-qubit matrices / parity phases executed in ONE
    Pallas HBM pass (ops.pallas_gates.fused_local_run). Gate targets must be
    below ``tile_bits``; controls and parity members may be any qubit.
    Ops are in PHYSICAL coordinates (after any active frame swap).

    The run IS its tape entry (``(_apply_pallas_run, (run,), {})``):
    frozen and hashable, stamped on the plan with ``dataclasses.replace``
    before :func:`as_tape`, never changed once it is on a tape.

    ``load_swap_k`` / ``store_swap_k`` fold the frame-switch transpose into
    this run's input gather / output scatter (zero extra HBM passes; see
    ops.pallas_gates._swap_spec): nonzero k means the amps arrive in (or
    must be left in) another frame and the kernel's block specs perform
    the relabeling during DMA. ``load_swap_hi``/``store_swap_hi`` give the
    grid-bit offset of the swapped block (None = tile_bits, the classic
    two-frame case; round 4 generalises to ANY grid block so registers
    wider than 2*tile_bits - LANE_BITS qubits -- e.g. a sharded 34q state
    -- are fully covered by multiple frames). When the executing register
    cannot take the folded path (sharded, mismatched tile geometry), the
    swap runs as an explicit pass instead (:func:`_explicit_swap`) -- same
    semantics; where its block reaches a sharded qubit that is ONE
    collective (all-to-all) transpose, the analogue of the reference's
    swap-to-local exchanges (QuEST_cpu_distributed.c:1526-1568)."""
    ops: tuple
    tile_bits: int
    load_swap_k: int = 0
    store_swap_k: int = 0
    load_swap_hi: int | None = None
    store_swap_hi: int | None = None
    #: manual-DMA ring depth override for this run (None = the process
    #: default: QUEST_PALLAS_RING env, else pallas_gates._DEF_RING_DEPTH)
    ring_depth: int | None = None
    #: comm-pipeline depth for the collective frame relabelings this run
    #: triggers under the explicit scheduler (None = the scheduler's /
    #: QUEST_COMM_PIPELINE default; bit-identical at every depth --
    #: exchange.dist_permute_bits)
    comm_pipeline: int | None = None
    #: frame-identity segment index this run belongs to
    #: (quest_tpu.segments.stamp_plan; plancheck QT107 re-derives and
    #: checks it). Plan-time annotation only -- ignored at apply time;
    #: None on an item no planner stamped.
    seg: int | None = None
    #: per-link-class pipeline depth: sub-collectives of this run's frame
    #: relabelings that cross a DCN shard bit pipeline at this depth
    #: instead of ``comm_pipeline`` (None = inherit --
    #: QUEST_COMM_PIPELINE_DCN env, else the base depth)
    comm_pipeline_dcn: int | None = None
    #: the kernel is cut at THIS run's ``tile_bits``, a tile narrower than
    #: the register's own: the planner narrowed it so that a frame there
    #: holds an op whose targets straddle the register's tile edge
    #: (``_FramePlanner._synth_frame``). :func:`_route` then runs the kernel
    #: at that many sublanes and its frame folds as any other. False, a run
    #: whose ``tile_bits`` differs from the register's is a plan made for
    #: another register: the kernel runs at the register's tile and the
    #: relabelings beside it (``swap_not_foldable``).
    own_tile: bool = False

    @property
    def matched(self) -> bool:
        """The load and the store relabeling are the same one (or there is
        none): chunk ``c`` of the kernel then reads and writes the same
        addresses, so the pass is sound in place
        (``pallas_gates._fused_local_run_impl`` aliases its output to its
        operand). Every run the planner emits is matched
        (``_FramePlanner._emit_run``)."""
        from .ops.pallas_gates import writes_in_place

        return writes_in_place(self.tile_bits, self.load_swap_k,
                               self.load_swap_hi, self.store_swap_k,
                               self.store_swap_hi)


@dataclass(frozen=True)
class FrameSwap:
    """Exchange the k-bit grid block [hi, hi+k) (hi = None means
    tile_bits) with the sublane block [tile_bits-k, tile_bits): one
    bandwidth-cost transpose (ops.pallas_gates.swap_bit_blocks) that
    relabels high qubits tile-local so the next PallasRun can target them.
    Self-inverse; the planner always returns the register to the identity
    frame before any non-Pallas item. On sharded registers the transpose
    is a collective when [hi, hi+k) includes sharded qubits, and
    shard-local otherwise. Its own tape entry, like :class:`PallasRun`
    (``(_apply_frame_swap, (swap,), {})``)."""
    tile_bits: int
    k: int
    hi: int | None = None
    #: comm-pipeline depth when the transpose rides the scheduler's
    #: grouped permute collective (None = default; see PallasRun)
    comm_pipeline: int | None = None
    #: frame-identity segment index (see PallasRun.seg)
    seg: int | None = None
    #: DCN-crossing pipeline depth (see PallasRun)
    comm_pipeline_dcn: int | None = None


def _window(qubits) -> tuple:
    return tuple(range(min(qubits), max(qubits) + 1))


# ---------------------------------------------------------------------------
# two-frame Pallas planning
#
# The fused Pallas kernel can target any qubit below tile_bits (in-tile) and
# can use any qubit diagonally (controls, parity members, diagonal targets
# -- grid bits enter as per-program scalars). The only thing it cannot do is
# a dense target on a grid bit. The planner therefore runs the circuit in
# two alternating qubit labelings ("frames"):
#
#   frame A: identity; in-tile logical qubits = [0, tile_bits)
#   frame B: grid block [tile_bits, tile_bits+k) swapped with sublane block
#            [tile_bits-k, tile_bits); in-tile = [0, tile_bits-k) and
#            [tile_bits, tile_bits+k)
#
# with k = min(num grid bits, num sublane bits). Switching frames is ONE
# bandwidth-cost transpose (swap_bit_blocks, ~ the elementwise floor), so a
# deep circuit executes as [run_A][swap][run_B][swap][run_A]... -- every
# gate rides a fused single-HBM-pass kernel and the whole layer costs ~2
# kernel passes + ~2 transposes instead of one einsum block per high-qubit
# window (the round-1 scheme: 60 blocks for a 26q depth-8 circuit; this
# scheme: ~32 passes). This generalises the reference's swap-to-local trick
# (QuEST_cpu_distributed.c:1526-1568) from one qubit per exchange to the
# whole high block per transpose.
# ---------------------------------------------------------------------------

@dataclass
class _POp:
    """A primitive op in LOGICAL coordinates plus its diagonality roles."""
    kind: str            # 'matrix' | 'swap' | 'diagw' | 'parity' |
    #                      'kraus1' | 'kraus2' | 'krausn' | 'depol'
    targets: tuple
    controls: tuple
    states: tuple
    data: object         # matrix ndarray | diag ndarray | theta
    diag_targets: bool   # True if the op acts diagonally on its targets

    @property
    def support(self):
        return frozenset(self.targets) | frozenset(self.controls)

    def diag_on(self, q: int) -> bool:
        return q in self.controls or self.diag_targets


def _lower_event(ev: GateEvent):
    """GateEvent -> list of _POp, or None if not expressible as kernel ops
    (dense multi-qubit matrices, wide diagonals)."""
    states = tuple(ev.states) if ev.states else (1,) * len(ev.controls)
    ctrls = tuple(ev.controls)
    if ev.kind == "parity":
        return [_POp("parity", tuple(ev.targets), ctrls, (), float(ev.theta), True)]
    if ev.kind == "swap":
        return [_POp("swap", tuple(ev.targets), ctrls, states, None, False)]
    if ev.kind == "x":
        # C[X (x) X ...] = product of single-target CXs (identical controls)
        X = np.array([[0, 1], [1, 0]], dtype=complex)
        return [_POp("matrix", (t,), ctrls, states, X, False)
                for t in ev.targets]
    if ev.kind == "diag":
        if len(ev.targets) == 1:
            return [_POp("matrix", tuple(ev.targets), ctrls, states,
                         np.diag(ev.diag), True)]
        if len(ev.targets) <= 5:
            if any(s == 0 for s in states):
                # the kernel diagw op has no control-state slot; an
                # anti-controlled wide diagonal must not silently drop its
                # states -- run the entry through the ordinary engine
                return None
            return [_POp("diagw", tuple(ev.targets), ctrls, (),
                         np.asarray(ev.diag).reshape(-1), True)]
        return None
    if ev.kind == "matrix":
        if len(ev.targets) != 1:
            return None
        m = np.asarray(ev.matrix)
        is_diag = m[0, 1] == 0 and m[1, 0] == 0
        return [_POp("matrix", tuple(ev.targets), ctrls, states, m, is_diag)]
    return None  # pragma: no cover


#: max kernel primitive ops per emitted PallasRun (pre-fold); splitting a
#: longer run costs one extra HBM pass (the bench circuit's 8-pass
#: structural floor is worth more than compile time: capping at 48 split
#: it to 10 passes and cost ~4% of throughput), but the cap must exist:
#: Mosaic compile time is strongly superlinear in op count (round-4
#: matrix at 2^26: 24 ops 16 s, 48 ops 112 s, 96 ops 737 s) and a 20q
#: mono-kernel at 316 ops ran past 20 minutes. 96 covers the bench's
#: largest natural run; the persistent compilation cache amortises the
#: one-time cost.
_RUN_OP_CAP = 96


def _run_op_cap(dtype, sharded: bool) -> int:
    """The most ops one emitted PallasRun holds: the one statement of a
    plan's cap. A one-device plan for the double-float route
    (:func:`_df_route`) cuts at ``pallas_df.DF_MAX_OPS``, the longest run a
    df kernel takes, so that every df kernel is one PallasRun: one pass the
    plan states, one in-place launch, its frame on its own DMA, and the
    executor's chunk loop (:func:`_kernel_fn`) never sees more than one
    chunk of a plan built for its register. A SHARDED df plan keeps
    ``_RUN_OP_CAP``: a frame that reaches a sharded qubit is a collective,
    and a piece that carried it in and out would pay it twice; its runs
    are cut where they execute, and counted (``df_max_ops_split``)."""
    if not sharded and _df_route(dtype):
        from .ops.pallas_df import DF_MAX_OPS

        return DF_MAX_OPS
    return _RUN_OP_CAP


class _FramePlanner:
    """Greedy multi-frame scheduler over an ordered list of pending runs
    (see the Scheduling paragraph below; the eager two-slot variant lives
    in _FramePlannerTwoSlot).

    A *frame* is a qubit relabeling: ``None`` is the identity; ``(hi, kf)``
    means the grid-bit block [hi, hi+kf) is swapped with the sublane block
    [tb-kf, tb); ``(hi, kf, tb')`` is the same at a NARROWED tile of
    ``tb' < tb`` bits (only ever synthesized, for an op no frame of the
    register's tile holds: :meth:`_synth_frame`), and its runs carry
    ``tb'`` as their own tile (``PallasRun.own_tile``). The candidate
    frames tile the grid bits in k-sized blocks
    from tb upward, so EVERY qubit of an arbitrarily wide (e.g. sharded)
    register is in-tile in some frame -- the round-4 generalisation that
    lets a sharded 34q register execute fused PallasRuns per shard with
    each frame switch one (collective) transpose (VERDICT r3 missing #1).

    Scheduling (round-4b): an ordered list of PENDING runs, each in a
    frame. A new op joins the EARLIEST run whose frame localises it
    and whose every LATER pending op commutes past it (runs execute in
    list order; an op placed in run i runs before everything in runs
    j > i, so it must commute with what is already there -- and later
    arrivals into runs j < i check against it symmetrically). Holding
    every run open until flush lets late ops join early runs, which cuts
    frame alternations well below the two-slot (open + one lookahead)
    round-4a scheme on >=3-frame plans (34q sharded, density tapes).

    A run's frame is pinned at FLUSH, not at birth: an op that no pending
    run localises is offered, before it opens a run of its own, to each
    pending run in order with the run's block GROWN to reach the op's high
    targets (:meth:`_grown`: still within what folds, still holding every
    op the run has, never across the shard boundary), under the same
    commutation test. ``_emit_run`` derives the relabeling and the
    physical ops from whatever frame the run has by then. So the column
    ops of a density layer, each of which synthesizes the minimal block
    for its own targets (``k=1 @25``, ``@26``, ``k=2 @27`` ...: a pass
    over the state apiece), collect in ONE run whose block widens as they
    arrive (``k=5 @25``). Identity runs have no block to grow and a
    narrowed-tile frame exists for one straddling op: neither grows.
    ``grow=False`` is the schedule with frames fixed at birth, which
    :func:`_plan_pallas` keeps among its candidates."""

    def __init__(self, out: FusePlan, tile_bits: int, k: int, nsv: int,
                 boundary: int | None = None, n_exec: int | None = None,
                 run_op_cap: int = _RUN_OP_CAP, grow: bool = True):
        self.out = out
        #: may a pending run's block widen for an op no run holds
        self.grow = grow
        self.tb = tile_bits
        self.k = k
        self.nsv = nsv
        #: ops an emitted run holds at most (:func:`_run_op_cap`)
        self.run_op_cap = run_op_cap
        self.boundary = boundary  # shard-local qubit count (or None)
        #: qubits of the array a kernel sees: the register, or one shard
        self.n_exec = nsv if n_exec is None else n_exec
        #: candidate frames: identity + one per k-wide grid block. Block
        #: edges align to ``boundary`` (the shard-local qubit count) so
        #: frames stay entirely below it where possible -- their
        #: transposes are then shard-LOCAL (no collective); only frames
        #: reaching into the sharded bits pay an all-to-all
        self.frames = [None]
        edges = [tile_bits, nsv]
        if boundary is not None and tile_bits < boundary < nsv:
            edges.insert(1, boundary)
        for lo, hi_edge in zip(edges, edges[1:]):
            hi, w = lo, self.width(hi_edge)
            while w > 0 and hi < hi_edge:
                self.frames.append((hi, min(w, hi_edge - hi)))
                hi += w
        self.runs = []               # ordered pending [frame, [_POp]]

    # -- frame geometry -----------------------------------------------------

    def width(self, end: int, tb: int | None = None) -> int:
        """The widest frame whose grid block ends at qubit ``end`` (at the
        tile of ``tb`` bits; None: the planner's). A block
        inside the array the kernel sees rides the kernel's DMA, and is
        never wider than what folds there (:func:`_fold_width`): a wider
        one would run as two explicit passes over the whole state beside
        its kernel. One that reaches a sharded qubit is a collective
        transpose whatever its width, and keeps the planner's ``k``; so
        does every frame of a tile too small for any to fold (under 16
        sublanes: an explicit ``sublanes=`` only), each an explicit pass
        whatever its width."""
        from .ops.pallas_gates import LANE_BITS

        tb = self.tb if tb is None else tb
        k = self.k if tb == self.tb else min(max(self.nsv - tb, 0),
                                             tb - LANE_BITS)
        fold = _fold_width(tb)
        if end <= self.n_exec and fold > 0:
            return min(k, fold)
        return k

    def tile(self, frame) -> int:
        """The tile bits of ``frame``'s runs: the planner's, or a narrowed
        frame's own."""
        return self.tb if frame is None or len(frame) == 2 else frame[2]

    def phys(self, q: int, frame) -> int:
        if frame is None:
            return q
        hi, kf = frame[:2]
        tb = self.tile(frame)
        if tb - kf <= q < tb:
            return q - (tb - kf) + hi
        if hi <= q < hi + kf:
            return q - hi + (tb - kf)
        return q

    def feasible(self, op: _POp, frame) -> bool:
        if op.kind in ("parity", "diagw") or (op.kind == "matrix" and op.diag_targets):
            return True
        tb = self.tile(frame)
        return all(self.phys(t, frame) < tb for t in op.targets)

    def _frame_for(self, op: _POp, exclude):
        for f in self.frames:
            if f != exclude and self.feasible(op, f):
                return f
        f = self._synth_frame(op)
        if f is not None and f != exclude:
            self.frames.append(f)
            return f
        return Ellipsis

    def _synth_frame(self, op: _POp):
        """Invent a frame when the static k-block tiling localises none
        (round 5): the fixed tiling displaces the sublane block
        [tb-k, tb), so an op pairing a HIGH qubit with a row target
        inside that block -- e.g. a 17q density channel's (row 16,
        column 33) kraus pair over a 19-bit shard tile -- fits no
        candidate. A bespoke block [hi0, hi0+kf) anchored at the op's
        high targets, with kf kept small enough that the displaced
        sublane region avoids the op's low targets, restores coverage.
        The synthesized frame joins ``self.frames`` so later ops (and
        the run scheduler) reuse it. It is the MINIMAL block for this op:
        what the run it opens is emitted under is decided at flush, after
        the list scheduler has widened it for the ops that followed
        (:meth:`_grown`).

        When a shard boundary is set and the minimal span block straddles
        it, boundary-CLIPPED anchors are tried first (round 6, closing the
        last round-5 ADVICE finding): a clipped block keeps its transposes
        shard-local (or confines the collective to the genuinely sharded
        bits), so a straddling frame -- whose reuse by later ops would pay
        collective transposes they don't need -- is accepted only when no
        clipped anchor localises the op."""
        f = self._synth_at(op, self.tb)
        if f is not None:
            return f
        # No frame of this tile holds the op: its targets straddle the
        # tile's edge, one in the sublane block [tb-k, tb) that every
        # frame bringing the other in displaces -- on every density
        # register of 10 qubits or more the kraus2 / depol of the pair
        # whose columns are bits tb-1 and tb. A NARROWER tile puts both
        # above its edge, where one block [tb', ...) brings them in
        # together: the widest such tile, the run carrying it
        # (``PallasRun.own_tile``), its frame folded like any other --
        # no state-sized pass, where the entry was a barrier before.
        from .ops.pallas_gates import LANE_BITS

        for tb in range(self.tb - 1, LANE_BITS, -1):
            f = self._synth_at(op, tb)
            if f is not None:
                return f
        return None

    def _synth_at(self, op: _POp, tb: int):
        """:meth:`_synth_frame` at a tile of ``tb`` bits."""
        targs = tuple(op.targets)
        high = sorted(t for t in targs if t >= tb)
        if not high or self.k <= 0:
            return None
        lo_t = [t for t in targs if t < tb]
        max_lo = max(lo_t, default=-1)
        hi0 = high[0]
        kf = high[-1] + 1 - hi0
        b = self.boundary
        cands = []
        if b is not None and hi0 < b < hi0 + kf:
            # span block straddles the boundary: clipped anchors first
            cands.append((hi0, b - hi0))
            cands.append((b, high[-1] + 1 - b))
        cands.append((hi0, kf))
        for a0, w in cands:
            # the displaced region [tb-w, tb) must stay above every low
            # target, and the block must fit the frame width and register
            if w <= 0 or w > self.width(a0 + w, tb) or w >= tb - max_lo \
                    or a0 + w > self.nsv:
                continue
            f = (a0, w) if tb == self.tb else (a0, w, tb)
            if self.feasible(op, f):
                return f
        return None

    def feasible_somewhere(self, op: _POp) -> bool:
        return (any(self.feasible(op, f) for f in self.frames)
                or self._synth_frame(op) is not None)

    # -- emission -----------------------------------------------------------

    def _emit_run(self, frame, ops: list):
        """One PallasRun a ``run_op_cap`` ops of the pending run, each
        entering ``frame`` on its load DMA and leaving it on its store DMA
        (zero extra HBM passes): between two items the register is always
        in the identity frame, and a run's load and store relabelings are
        the same one (``PallasRun.matched``) -- what lets its kernel write
        over its operand."""
        hi, k = (None, 0) if frame is None else frame[:2]
        tb = self.tile(frame)
        # cap ops per kernel: Mosaic compile time explodes past a few
        # hundred ops in one program (20q mono-kernel probe: >20 min at
        # 316 ops; a df kernel past DF_MAX_OPS), so over-long runs split
        # into consecutive passes
        phys = [self._phys_op(op, frame) for op in ops]
        for i in range(0, len(phys), self.run_op_cap):
            run = PallasRun(tuple(phys[i:i + self.run_op_cap]), tb,
                            load_swap_k=k, load_swap_hi=hi,
                            store_swap_k=k, store_swap_hi=hi,
                            own_tile=tb != self.tb)
            assert run.matched, run
            self.out.items.append(run)

    def _phys_op(self, op: _POp, frame):
        from .ops.pallas_gates import HashableMatrix

        t = tuple(self.phys(q, frame) for q in op.targets)
        c = tuple(self.phys(q, frame) for q in op.controls)
        if op.kind == "matrix":
            return ("matrix", t[0], c, op.states, HashableMatrix(op.data))
        if op.kind == "swap":
            return ("swap", t[0], t[1], c, op.states)
        if op.kind == "kraus1":
            return ("kraus1", t[0], t[1], op.data)
        if op.kind == "kraus2":
            return ("kraus2", t[0], t[1], t[2], t[3], op.data)
        if op.kind in ("krausn", "depol"):
            h = len(t) // 2
            return (op.kind, t[:h], t[h:], op.data)
        if op.kind == "diagw":
            return ("diagw", t, c, HashableMatrix(op.data))
        return ("parity", t, c, op.data)

    def flush(self):
        """Emit every pending run in order."""
        for frame, ops in self.runs:
            self._emit_run(frame, ops)
        self.runs = []

    # -- scheduling ---------------------------------------------------------

    def _grown(self, frame, ops: list, op: _POp):
        """``frame`` with its block widened to cover ``op``'s high targets,
        ``[min(hi, min(high)), max(hi + kf, max(high) + 1))``, or None
        where that is no frame this run can take: wider than the kernel's
        DMA folds there (:meth:`width`); a target of the run's own ``ops``
        or of ``op`` inside the sublane block the wider frame displaces;
        across the shard boundary (a shard-local block stays below it, a
        collective one above it, a block that straddles it stays as it
        is). Identity and narrowed-tile frames do not grow."""
        if frame is None or len(frame) != 2:
            return None
        hi, kf = frame
        high = [t for t in op.targets if t >= self.tb]
        if not high:
            return None
        lo, end = min(hi, min(high)), max(hi + kf, max(high) + 1)
        w = end - lo
        if w == kf or w > self.width(end):
            return None
        if any(e is not None and lo < e < end
               for e in (self.boundary, self.n_exec)):
            return None
        wide = (lo, w)
        if all(self.feasible(o, wide) for o in (*ops, op)):
            return wide
        return None

    def add(self, op: _POp):
        # earliest run that localises the op AND whose every later op
        # commutes past it (see class docstring for the ordering argument)
        def commutes_past(i):
            return all(self._commutes(op, other)
                       for _, later in self.runs[i + 1:] for other in later)

        for i, (frame, ops) in enumerate(self.runs):
            if self.feasible(op, frame) and commutes_past(i):
                ops.append(op)
                return
        # ... else the earliest run whose block can grow to localise it
        for i, run in enumerate(self.runs if self.grow else ()):
            wide = self._grown(*run, op)
            if wide is not None and commutes_past(i):
                run[0] = wide
                run[1].append(op)
                self.out.frames_grown += 1
                return
        f = self._frame_for(op, exclude=Ellipsis)
        if f is Ellipsis:  # pragma: no cover - callers pre-check
            raise AssertionError("op feasible in no frame reached the scheduler")
        self.runs.append([f, [op]])

    @staticmethod
    def _commutes(a: _POp, b: _POp) -> bool:
        return all(a.diag_on(q) and b.diag_on(q)
                   for q in a.support & b.support)


class _FramePlannerTwoSlot(_FramePlanner):
    """The round-4a two-slot variant: one OPEN run plus one lookahead run,
    rotated eagerly when an op fits neither. Kept alongside the ordered-
    list scheduler because neither dominates: eager rotation balances
    two-frame tapes better (26q bench: 8 raw runs vs the list's 9, whose
    first run absorbs 153 ops and then pays an op-cap split), while the
    list wins on >=3-frame plans (34q sharded: 14 passes vs 42).
    _plan_pallas schedules with both and keeps the cheaper plan."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.open = [None, []]       # [frame, [_POp]]
        self.next = [Ellipsis, []]   # Ellipsis = frame not yet chosen

    def rotate(self):
        frame, ops = self.open
        self._emit_run(frame, ops)
        self.open = self.next
        if self.open[0] is Ellipsis:
            self.open[0] = None
        self.next = [Ellipsis, []]

    def flush(self):
        self._emit_run(*self.open)
        if self.next[0] is not Ellipsis:
            self._emit_run(*self.next)
        self.open = [None, []]
        self.next = [Ellipsis, []]

    def add(self, op: _POp):
        for _ in range(3):
            of, oops = self.open
            nf, nops = self.next
            if self.feasible(op, of) and all(
                    self._commutes(op, other) for other in nops):
                oops.append(op)
                return
            if nf is Ellipsis:
                nf = self._frame_for(op, exclude=of)
                if nf is not Ellipsis:
                    self.next[0] = nf
                    nops.append(op)
                    return
            elif self.feasible(op, nf):
                nops.append(op)
                return
            self.rotate()
        raise AssertionError(  # pragma: no cover
            "op feasible in no frame reached the scheduler")


def _record_plan_telemetry(p: FusePlan, mode: str, nsv: int,
                           tile_bits: int | None,
                           shard_qubits: int | None = None,
                           df: bool = False,
                           run_op_cap: int | None = None) -> None:
    """Flight-record a finished plan's shape: item mix, frame-transpose
    counts, tile geometry. One counter per plan plus a structured event
    (the per-plan detail bench.py ships in BENCH_DETAIL.json)."""
    if not telemetry.enabled():
        return
    runs = [i for i in p.items if isinstance(i, PallasRun)]
    folded = sum((1 if r.load_swap_k else 0) + (1 if r.store_swap_k else 0)
                 for r in runs)
    explicit = sum(isinstance(i, FrameSwap) for i in p.items)
    telemetry.inc("fusion_plans_total", mode=mode)
    telemetry.inc("fusion_fused_gates_total", p.num_fused_gates, mode=mode)
    telemetry.inc("fusion_barriers_total", p.num_barriers, mode=mode)
    telemetry.inc("fusion_pallas_runs_total", len(runs), mode=mode)
    telemetry.inc("fusion_frame_transposes_total", folded + explicit,
                  mode=mode)
    if p.frames_grown:
        telemetry.inc("fusion_frames_grown_total", p.frames_grown, mode=mode)
    df_passes = 0
    if df:
        # the df kernels the plan states: its runs, but where a run is
        # still cut as it executes (_kernel_fn)
        df_passes = sum(len(_df_chunks(r.ops)) for r in runs)
        telemetry.inc("fusion_df_passes_total", df_passes, mode=mode)
    sharded = {}
    if shard_qubits is not None:
        # what the plan prices, under the names of the counters that say
        # what the replay then decided (fusion_collective_swaps_total,
        # fusion_sharded_runs_total)
        sharded = transpose_stats(p, shard_qubits)
        sharded.update(collective_swaps=sharded["collective_transposes"],
                       sharded_runs=len(runs))
    kernel = {}
    if mode != "dense":
        # the channels the plan's kernels hold, by lowering: the terms an
        # op applies a pass (a Kraus sum its terms, the closed form one)
        channels = channel_terms(runs)
        for kind, terms in channels.items():
            telemetry.inc("fusion_channel_terms_total", terms, kind=kind)
        # what the kernels will hold, by kind: each run's zones folded as
        # fused_local_run folds them at the same tile, but for a
        # double-float plan, whose kernels take the ops as they are
        from .ops import pallas_gates as PG
        kernel = dict(kernel_op_kinds=PG.kernel_op_kinds(
            op for r in runs for op in (
                r.ops if df else PG._fold_zone_ops(r.ops, r.tile_bits))),
            # the cap the runs were cut at, and for a double-float plan
            # the kernels it states (pallas_pass_total{dtype=df} then
            # counts as many a trace) and, on one device, the runs that
            # stand right behind a run and so take its planes
            # (fusion_df_carried_total: _df_local_run)
            df=df, run_op_cap=run_op_cap, df_passes=df_passes,
            channel_ops=sum(op[0] in _CHANNEL_OPS
                            for r in runs for op in r.ops),
            channel_terms=sum(channels.values()),
            # a run at a tile of its own says so (PallasRun.own_tile)
            run_tile_bits=[r.tile_bits for r in runs],
            df_carried=sum(
                isinstance(a, PallasRun) and isinstance(b, PallasRun)
                for a, b in zip(p.items, p.items[1:]))
            if df and shard_qubits is None else 0)
    telemetry.event(
        "fusion.plan", mode=mode, nsv=nsv, tile_bits=tile_bits,
        items=len(p.items), pallas_runs=len(runs),
        dense_blocks=sum(isinstance(i, FusedBlock) for i in p.items),
        diag_blocks=sum(isinstance(i, DiagBlock) for i in p.items),
        frame_transposes=folded + explicit,
        ops_per_run=[len(r.ops) for r in runs],
        inplace_runs=sum(r.matched for r in runs),
        frame_widths=[r.load_swap_k for r in runs],
        frames_grown=p.frames_grown,
        fused_gates=p.num_fused_gates, barriers=p.num_barriers,
        **sharded, **kernel)


#: the kernel ops that are channels (non-unitary: on a density register)
_CHANNEL_OPS = ("kraus1", "kraus2", "krausn", "depol")


def channel_terms(runs) -> dict:
    """Terms the channel ops of ``runs`` apply a pass, by how each was
    lowered (``fusion_channel_terms_total{kind}``): a ``kraus1`` /
    ``kraus2`` / ``krausn`` op its Kraus terms, two matrix sweeps each; a
    closed-form depolarising op 1, under ``depol1`` / ``depol2`` by its
    targets. Kinds the plan does not hold are left out."""
    out = {}
    for run in runs:
        for op in run.ops:
            if op[0] == "depol":
                kind, terms = f"depol{len(op[1])}", 1
            elif op[0] in _CHANNEL_OPS:
                kind, terms = op[0], len(op[-1])
            else:
                continue
            out[kind] = out.get(kind, 0) + terms
    return out


def plan(tape, num_qubits: int, dtype, max_qubits: int = 5,
         max_diag_qubits: int = 12, pallas_tile_bits: int | None = None,
         is_density: bool = False,
         shard_boundary: int | None = None) -> FusePlan:
    """Greedy left-to-right fusion of a Circuit tape.

    Without ``pallas_tile_bits``: dense events merge while the combined
    contiguous window spans at most ``max_qubits``; diagonal events (phase
    gates, Z-rotations, parity phases) merge by support up to
    ``max_diag_qubits`` regardless of span. A tape entry that fails capture,
    or containing an event too wide for either rule, flushes the current
    block and passes through unchanged.

    With ``pallas_tile_bits``: two-frame Pallas planning (see the
    _FramePlanner block comment) -- every expressible gate joins a fused
    single-HBM-pass kernel run, with frame swaps localising high qubits;
    only dense multi-qubit matrices fall out as window blocks.
    ``is_density`` extends this to density tapes: the captured row ops gain
    explicit conj-shadow twins on (targets + n) and the planner schedules
    both over the flattened 2n-qubit state -- the column qubits are just
    more high qubits for the frame machinery to relabel (the round-2 build
    excluded density tapes entirely; VERDICT r2 missing #1).
    """
    nsv = (2 if is_density else 1) * num_qubits
    if pallas_tile_bits is not None:
        with telemetry.span("fusion.plan", mode="pallas"):
            p = _plan_pallas(tape, num_qubits, dtype, max_qubits,
                             pallas_tile_bits, is_density=is_density,
                             shard_boundary=shard_boundary)
        _record_plan_telemetry(
            p, "pallas", nsv, pallas_tile_bits, df=_df_route(dtype),
            run_op_cap=_run_op_cap(dtype, shard_boundary is not None))
        return p
    with telemetry.span("fusion.plan", mode="dense"):
        out = _plan_dense(tape, num_qubits, dtype, max_qubits,
                          max_diag_qubits)
    _record_plan_telemetry(out, "dense", nsv, None)
    return out


def _plan_dense(tape, num_qubits: int, dtype, max_qubits: int,
                max_diag_qubits: int) -> FusePlan:
    """The dense arm of :func:`plan`: window and diagonal blocks."""
    from .ops.apply import _MIN_MINOR, MAX_LOW_WINDOW_TOP

    out = FusePlan()
    cur = None  # None | FusedBlock | DiagBlock (mutable accumulators)

    def flush():
        nonlocal cur
        if cur is not None:
            out.items.append(cur)
        cur = None

    def window_ok(joint):
        # a window that starts below the lane boundary is lowered as a
        # GEMM over EVERY qubit below its top (ops.apply.
        # _apply_matrix_window kron-expands it down to qubit 0), so its
        # price is 2^(hi+1), not 2^len: past MAX_LOW_WINDOW_TOP that is a
        # multi-GiB operand. No window of <= 5 qubits reaches it
        return len(joint) <= max_qubits and (
            joint[0] >= _MIN_MINOR or joint[-1] < MAX_LOW_WINDOW_TOP)

    def add_dense(ev):
        nonlocal cur
        win = _window(ev.support)
        if isinstance(cur, DiagBlock):
            joint = _window(set(cur.qubits) | ev.support)
            if not window_ok(joint):
                flush()
            elif cur.factors is not None:
                # diagonal-kind events are dense factors as they stand
                cur = FusedBlock(joint, None, cur.factors)
            else:
                cur = FusedBlock(joint, np.diag(
                    _event_diag(GateEvent("diag", cur.qubits, diag=cur.diag),
                                joint)))
        if isinstance(cur, FusedBlock):
            joint = _window(set(cur.qubits) | ev.support)
            if window_ok(joint):
                if not ev.deferred and cur.factors is None:
                    U = _embed_block(cur.matrix, cur.qubits, joint)
                    cur = FusedBlock(joint, event_matrix(ev, joint) @ U)
                else:
                    cur = FusedBlock(joint, None, _block_factors(cur) + (ev,))
                return
            flush()
        cur = (FusedBlock(win, event_matrix(ev, win)) if not ev.deferred
               else FusedBlock(win, None, (ev,)))

    def add_diag(ev):
        nonlocal cur
        static = not ev.deferred and (cur is None or cur.factors is None)
        if isinstance(cur, FusedBlock):
            joint = _window(set(cur.qubits) | ev.support)
            if window_ok(joint):
                if static:
                    cur = FusedBlock(
                        joint, np.diag(_event_diag(ev, joint)) @
                        _embed_block(cur.matrix, cur.qubits, joint))
                else:
                    cur = FusedBlock(joint, None, _block_factors(cur) + (ev,))
                return
            flush()
        if isinstance(cur, DiagBlock) and ev.deferred \
                and cur.factors is None:
            # a deferred factor would turn the block's constant table into
            # a traced one: on the chip a diagonal pass with a traced table
            # over (0, 19) took 11.7 ms of a batch of 20q lanes where the
            # constant one takes 0.2 (PR 27, PERF.md). It opens a block of
            # its own, which the next dense factor turns into a window
            flush()
        if isinstance(cur, DiagBlock):
            joint = tuple(sorted(set(cur.qubits) | ev.support))
            if len(joint) <= max_diag_qubits:
                if static:
                    d = _event_diag(
                        GateEvent("diag", cur.qubits, diag=cur.diag), joint)
                    cur = DiagBlock(joint, d * _event_diag(ev, joint))
                else:
                    cur = DiagBlock(joint, None, _block_factors(cur) + (ev,))
                return
            flush()
        qs = tuple(sorted(ev.support))
        cur = (DiagBlock(qs, _event_diag(ev, qs)) if not ev.deferred
               else DiagBlock(qs, None, (ev,)))

    for entry in tape:
        fn, args, kwargs = entry
        # an entry with Params joins blocks by its structure alone; its
        # coefficients are assembled in the program (_capture_deferred)
        has_params = _entry_has_params(args, kwargs)
        events = (_capture_deferred(entry, num_qubits, dtype) if has_params
                  else capture(fn, args, kwargs, num_qubits, dtype))
        fusible = events is not None and all(
            (len(ev.support) <= max_diag_qubits) if _event_is_diag(ev)
            else window_ok(_window(ev.support))
            for ev in events)
        if has_params:
            telemetry.inc("fusion_param_fused_total" if fusible
                          else "fusion_param_barriers_total", mode="dense")
        if not fusible:
            flush()
            out.items.append((fn, args, kwargs))
            out.num_barriers += 1
            continue
        for ev in events:
            if _event_is_diag(ev):
                add_diag(ev)
            else:
                add_dense(ev)
            out.num_fused_gates += 1
    flush()
    return out


#: widest channel the krausn kernel op takes: each extra target doubles the
#: matn delta count (4^t coefficient selects per term), so t=3 (a 512-delta
#: pair of matn sweeps per Kraus term) is the practical in-register ceiling
_KRAUSN_MAX_TARGETS = 3


def _lower_channel(ev: GateEvent, n: int):
    """'channel' event -> [_POp('depol'|'kraus1'|'kraus2'|'krausn', extended
    targets, ...)] for <= _KRAUSN_MAX_TARGETS-target channels, or None
    (wider channels stay barriers and run the engine path).

    An event recorded by ``mixDepolarising`` / ``mixTwoQubitDepolarising``
    (``ev.depol``: the call's probability, not guessed from the
    superoperator's numbers) lowers to the family's CLOSED FORM, the
    'depol' kernel op: ``rho -> (1 - l) rho + l (I/d (x) Tr_T rho)`` with
    ``l = 4p/3`` on one target and ``16p/15`` on two -- the same channel,
    exactly, as one masked sum over the group's diagonal where the Kraus
    sum is 4 or 16 terms of two matrix sweeps each (the reference's
    dedicated depolarising kernels, QuEST_gpu.cu:2423-2600). Its data is
    ``l``. Every other channel's data is the hashable Kraus-term tuple
    ((sign, K), ...) from the superoperator's Choi decomposition -- ALL
    arities ride the one-pass kernel, mirroring the reference's single
    superoperator mechanism for every channel width
    (QuEST_common.c:581-638)."""
    from .ops.density import choi_kraus
    from .ops.pallas_gates import HashableMatrix

    if not 1 <= len(ev.targets) <= _KRAUSN_MAX_TARGETS:
        return None
    rows = tuple(ev.targets)
    ext = rows + tuple(q + n for q in rows)
    if ev.depol is not None:
        d2 = 4 ** len(rows)
        return [_POp("depol", ext, (), (), float(ev.depol) * d2 / (d2 - 1),
                     False)]
    terms = tuple((float(s), HashableMatrix(k))
                  for s, k in choi_kraus(ev.superop))
    kind = {1: "kraus1", 2: "kraus2"}.get(len(rows), "krausn")
    return [_POp(kind, ext, (), (), terms, False)]


def _shadow_pop(op: _POp, n: int) -> _POp:
    """The density conj-shadow twin of a lowered row op: same op on the
    column qubits (q + n) with conjugated data (QuEST.c:184-193). Parity
    phases conjugate by negating theta; swaps are real."""
    targets = tuple(q + n for q in op.targets)
    controls = tuple(q + n for q in op.controls)
    if op.kind == "parity":
        data = -float(op.data)
    elif op.kind == "swap":
        data = op.data
    else:  # 'matrix' | 'diagw'
        data = np.conj(np.asarray(op.data))
    return _POp(op.kind, targets, controls, op.states, data, op.diag_targets)


def transpose_stats(p: FusePlan, shard_qubits: int | None,
                    nsv: int | None = None, num_slices: int = 1) -> dict:
    """(collective, local) frame-transpose counts of a pallas plan: a
    relabeling is a cross-device collective exactly when its grid block
    reaches a sharded qubit (>= ``shard_qubits``); None counts all as
    local (single device).

    With ``nsv`` and ``num_slices`` > 1, collective transposes further
    split by the interconnect they ride on a slice-major pod topology
    (parallel.mesh.shard_bit_link): a transpose whose grid block reaches
    one of the top log2(num_slices) shard bits crosses slices (DCN);
    the rest stay on the intra-slice ICI axis."""
    coll = loc = dcn = 0
    slice_bits = (num_slices - 1).bit_length() if num_slices > 1 else 0
    for i in p.items:
        swaps = []
        if isinstance(i, PallasRun):
            for k, hi in ((i.load_swap_k, i.load_swap_hi),
                          (i.store_swap_k, i.store_swap_hi)):
                if k:
                    swaps.append((k, i.tile_bits if hi is None else hi))
        elif isinstance(i, FrameSwap):
            swaps.append((i.k, i.tile_bits if i.hi is None else i.hi))
        for k, hi in swaps:
            if shard_qubits is not None and hi + k > shard_qubits:
                coll += 1
                if nsv is not None and slice_bits and \
                        hi + k > nsv - slice_bits:
                    dcn += 1
            else:
                loc += 1
    out = {"collective_transposes": coll, "local_transposes": loc}
    if nsv is not None and slice_bits:
        out["dcn_transposes"] = dcn
        out["ici_transposes"] = coll - dcn
    return out


def plan_from_tape(tape) -> FusePlan:
    """Decode an ``as_tape`` tape back into a :class:`FusePlan` -- the
    ONE decoder of the tape-entry layouts (:func:`as_tape` is the
    encoder): a `_apply_pallas_run` / `_apply_frame_swap` entry carries
    its PallasRun / FrameSwap whole, `_apply_dense_block` /
    `_apply_gate_diag` / `_apply_deferred_block` their operands. Entries
    that aren't plan items pass through verbatim as ``(fn, args, kwargs)``
    tuples, so ``plan_from_tape(as_tape(p))`` round-trips. Every reader of
    an executed circuit's runs (segments, plancheck, the bench artifacts,
    the tools, the tests) reads them here, by attribute."""
    p = FusePlan()
    for entry in tape:
        f, a, _kw = entry
        name = getattr(f, "__name__", "")
        if name in ("_apply_pallas_run", "_apply_frame_swap"):
            p.items.append(a[0])
        elif name == "_apply_dense_block":
            p.items.append(FusedBlock(tuple(a[1]), a[0]))
        elif name == "_apply_gate_diag":
            p.items.append(DiagBlock(tuple(a[1]), a[0]))
        elif name == "_apply_deferred_block":
            from .engine.params import materialize_entry

            spec, values = a[0], a[1:]
            entries = [materialize_entry(e, values) for e in spec.entries]
            factors = tuple(
                ev if ev.source is None else dataclasses.replace(
                    ev, source=(entries[ev.source[0]],) + ev.source[1:])
                for ev in spec.factors)
            block = DiagBlock if spec.kind == "diag" else FusedBlock
            p.items.append(block(tuple(spec.qubits), None, factors))
        else:
            p.items.append(entry)
    return p


def tape_transpose_stats(tape, shard_qubits: int | None,
                         nsv: int | None = None,
                         num_slices: int = 1) -> dict:
    """:func:`transpose_stats` over an ``as_tape`` tape instead of a
    FusePlan (used by the bench artifacts and the driver dryrun, which
    see executed circuits, not plans)."""
    return transpose_stats(plan_from_tape(tape), shard_qubits, nsv=nsv,
                           num_slices=num_slices)


def plan_pallas_sharded(tape, num_qubits: int, dtype, max_qubits: int,
                        tile_bits: int, n_local: int,
                        is_density: bool = False) -> FusePlan:
    """Plan a sharded register's pallas schedule twice -- frame blocks
    tiled plainly from tile_bits, and aligned to the shard boundary (so
    sub-boundary frames relabel shard-locally) -- and keep whichever plan
    pays fewer collective transposes (ties: fewer total passes). Which
    wins depends on the tape: boundary alignment removes collectives for
    tapes concentrated below the boundary but splits frames (more passes)
    for tapes with dense layers across every qubit."""
    nsv = (2 if is_density else 1) * num_qubits
    boundaries = [None]
    if tile_bits < n_local < nsv:
        # otherwise the aligned tiling is identical and the second full
        # spy-replay of the tape (the dominant trace-time cost) is waste
        boundaries.append(n_local)
    with telemetry.span("fusion.plan", mode="pallas_sharded"):
        cands = [
            _plan_pallas(tape, num_qubits, dtype, max_qubits, tile_bits,
                         is_density=is_density, shard_boundary=b,
                         score_shard_qubits=n_local)
            for b in boundaries
        ]
        best = min(cands, key=lambda p: (
            transpose_stats(p, n_local)["collective_transposes"],
            len(p.items)))
    _record_plan_telemetry(best, "pallas_sharded", nsv, tile_bits,
                           shard_qubits=n_local, df=_df_route(dtype),
                           run_op_cap=_run_op_cap(dtype, True))
    return best


def _plan_pallas(tape, num_qubits: int, dtype, max_qubits: int,
                 tile_bits: int, is_density: bool = False,
                 shard_boundary: int | None = None,
                 score_shard_qubits: int | None = None) -> FusePlan:
    """Multi-frame Pallas plan: lower every event to kernel primitive ops
    (ONE spy-capture pass over the tape -- the dominant trace-time cost),
    then schedule the lowered stream with BOTH frame schedulers (the
    ordered-list _FramePlanner and the two-slot variant) and keep the
    cheaper plan: fewer passes single-chip, fewer collective transposes
    first when ``score_shard_qubits`` is set. Where the list scheduler
    widened a frame, its schedule with frames fixed at birth is a
    candidate too, and the grown one the last: a tape on which an early
    growth shuts a later op out of a block cannot come out with more
    passes than before, and on a tie the plan is the one it was. Density
    tapes
    (``is_density``) plan over the flattened 2n-qubit state: every
    lowered row op is paired with its conj-shadow twin and both are
    scheduled; the emitted PallasRuns then carry EXPLICIT shadow ops, and
    every execution path applies them raw (no shadow re-derivation)."""
    from .ops.pallas_gates import LANE_BITS

    nsv = (2 if is_density else 1) * num_qubits
    k = min(max(nsv - tile_bits, 0), tile_bits - LANE_BITS)

    cap = _run_op_cap(dtype, sharded=(shard_boundary is not None
                                      or score_shard_qubits is not None))

    def make_planner(cls, **kw):
        return cls(FusePlan(), tile_bits, k, nsv, boundary=shard_boundary,
                   n_exec=score_shard_qubits, run_op_cap=cap, **kw)

    probe = make_planner(_FramePlanner)  # frame geometry only

    # -- pass 1: resolve every tape entry (capture + lower + routability) --
    resolved = []  # ('barrier', entry) | ('events', [(ev, pops|None)])
    for fn, args, kwargs in tape:
        if _entry_has_params(args, kwargs):
            # runtime-parameter entry: apply-time-assembled barrier between
            # the static kernel runs (see _entry_has_params)
            telemetry.inc("fusion_param_barriers_total", mode="pallas")
            resolved.append(("barrier", (fn, args, kwargs)))
            continue
        events = capture(fn, args, kwargs, num_qubits, dtype,
                         is_density=is_density)
        lowered = None
        if events is not None:
            lowered = []
            for ev in events:
                if ev.kind == "channel":
                    pops = _lower_channel(ev, num_qubits)
                else:
                    pops = _lower_event(ev)
                    if pops is not None and is_density and not ev.extended:
                        pops = [q for p in pops
                                for q in (p, _shadow_pop(p, num_qubits))]
                if pops is not None and not all(
                        probe.feasible_somewhere(p) for p in pops):
                    pops = None  # a target no frame localises
                lowered.append(pops)

            def routable(ev, pops):
                if pops is not None:
                    return True
                # dense window fallback -- unitary events only (a channel
                # has no dense 2^w x 2^w unitary to fall back to)
                return (ev.kind != "channel"
                        and len(_window(ev.support)) <= max_qubits)

            if not all(routable(ev, pops)
                       for ev, pops in zip(events, lowered)):
                events = None  # no route for some event: run the entry as-is
        if events is None:
            resolved.append(("barrier", (fn, args, kwargs)))
        else:
            resolved.append(("events", list(zip(events, lowered))))

    # -- pass 2: schedule with each planner, keep the cheaper plan --------
    def schedule(cls, **kw):
        sched = make_planner(cls, **kw)
        out = sched.out
        for kind, payload in resolved:
            if kind == "barrier":
                sched.flush()
                out.items.append(payload)
                out.num_barriers += 1
                continue
            for ev, pops in payload:
                if pops is not None:
                    for p in pops:
                        sched.add(p)
                else:
                    # dense multi-qubit matrix (or a target no frame
                    # localises): standalone window block through the
                    # engine, identity frame (FusedBlock stays in ROW
                    # coordinates; _apply_dense_block re-derives the
                    # density shadow itself)
                    sched.flush()
                    win = _window(ev.support)
                    out.items.append(FusedBlock(win, event_matrix(ev, win)))
                out.num_fused_gates += 1
        sched.flush()
        return out

    def score(p):
        st = transpose_stats(p, score_shard_qubits)
        if score_shard_qubits is not None:
            return (st["collective_transposes"], len(p.items))
        return (len(p.items), st["local_transposes"])

    grown = schedule(_FramePlanner)
    fixed = schedule(_FramePlanner, grow=False) if grown.frames_grown \
        else grown
    return min((fixed, schedule(_FramePlannerTwoSlot), grown), key=score)


def _df_route(dtype) -> bool:
    """True when an f64 register's PallasRuns take the double-float
    (4-plane f32) kernel route: always on the TPU backend (Mosaic has no
    f64 lowering, so df IS the f64 fast path there), opt-in elsewhere via
    ``QUEST_PALLAS_DF=1`` (pallas_df.df_wanted) -- the switch the CPU-mesh
    parity suite and the driver dryrun flip so CI executes the same route
    as the chip. Off: non-TPU f64 keeps the native-f64 interpreter/engine
    policy unchanged."""
    import numpy as np

    from .ops.pallas_df import df_wanted

    return np.dtype(dtype) == np.dtype("float64") and df_wanted()


def _amp_shards(qureg) -> int:
    """Devices the register's amplitudes are split over, as a replay sees
    them: the explicit scheduler's mesh, inside a trace the ambient mesh
    (the tracer hides its sharding; Circuit.run derived the mesh from the
    register), else the concrete array's own sharding."""
    import jax

    from .parallel import scheduler as _dist

    sched = _dist.active()
    if sched is not None and sched.mesh is not None:
        return sched.mesh.size
    if isinstance(qureg.amps, jax.core.Tracer):
        mesh = active_pallas_mesh()
        return 1 if mesh is None else mesh.size
    sharding = getattr(qureg.amps, "sharding", None)
    return 1 if sharding is None else len(sharding.device_set)


def _count_frame_swap(qureg, lo2: int, k: int) -> None:
    """Count one explicit relabeling pass (a LOWERING, like every counter
    inside a replay). Where the moved block [lo2, lo2 + k) reaches a
    sharded qubit the pass is a transpose over the mesh -- the stated
    all-to-all of :func:`_swap_per_shard`, the one GSPMD finds for a
    ``swap_bit_blocks`` of the whole array, or the scheduler's grouped
    permute -- and is counted as ``fusion_collective_swaps_total`` too; a
    shard-local one is not."""
    telemetry.inc("pallas_pass_total", kind="frame_swap")
    shard_bits = _amp_shards(qureg).bit_length() - 1
    if lo2 + k > qureg.num_qubits_in_state_vec - shard_bits:
        telemetry.inc("fusion_collective_swaps_total")


class Route(NamedTuple):
    """How one PallasRun executes on one register, as :func:`_route`
    decides it. ``kind``: ``"local"`` (the fused kernel over the whole
    register), ``"df_local"`` (the same on the double-float planes),
    ``"sharded"`` (the kernel per shard under shard_map), ``"sched_df"``
    (per shard on the planes, the relabelings the explicit scheduler's
    counted permutes) or ``"gatewise"`` (the one exit: the ops replayed
    through the gate-by-gate appliers). ``fold_load`` / ``fold_store``:
    the run's frame relabeling rides the kernel's DMA; one that does not
    runs as an explicit pass. ``reason``: the ``engine_fallback_total``
    label the decision counts, or None; ``unfolded``: how many of the
    run's relabelings lie inside the array and still did not fold
    (``fusion_unfolded_swaps_total``). The rest is what the executor runs
    on: the mesh of a per-shard route, the qubits and tile sublanes of the
    array the kernel sees, and whether that kernel is the df one."""
    kind: str
    fold_load: bool = False
    fold_store: bool = False
    reason: str | None = None
    mesh: object = None
    n_exec: int = 0
    sublanes: int = 0
    df: bool = False
    unfolded: int = 0


def _fold_width(tile_bits: int) -> int:
    """The widest relabeling a kernel's DMA folds at this tile: the low
    part of the split sublane axis keeps one sublane tile of 8 rows
    (``tile_bits - LANE_BITS - k >= 3``), so that the gathered
    (P * s_low, 128) pieces stay layout-free (``pallas_gates._load_planes``).
    The one statement of the number: :func:`_folded` routes by it and
    ``_FramePlanner.width`` holds its frames to it."""
    from .ops.pallas_gates import LANE_BITS

    return tile_bits - LANE_BITS - 3


def _folded(route: Route, run: PallasRun) -> Route:
    """``route`` with the run's relabelings folded where they can be --
    the ONE copy of the foldability rule. A relabeling folds into the
    kernel's DMA when its block lies inside the array the kernel sees
    (``hi + k <= n_exec``: on a shard, a block reaching sharded bits is
    the collective transpose, explicit by design and no fallback), the
    plan's tile is that array's (``tile_bits == local_qubits(n_exec,
    sublanes)``) and the block is no wider than :func:`_fold_width`. A
    block inside the array that misses the geometry is the counted
    ``swap_not_foldable``: the kernel still runs, the relabeling beside
    it (``fusion_unfolded_swaps_total``). The planner emits none on the
    tile it planned for (``_FramePlanner.width``)."""
    from .ops import pallas_gates as PG

    fits = run.tile_bits == PG.local_qubits(route.n_exec, route.sublanes)
    folds, missed = [], 0
    for k, hi in ((run.load_swap_k, run.load_swap_hi),
                  (run.store_swap_k, run.store_swap_hi)):
        hi = run.tile_bits if hi is None else hi
        inside = k > 0 and hi + k <= route.n_exec
        folds.append(inside and fits and k <= _fold_width(run.tile_bits))
        missed += inside and not folds[-1]
    return route._replace(fold_load=folds[0], fold_store=folds[1],
                          reason="swap_not_foldable" if missed else None,
                          unfolded=missed)


def _route(qureg, run: PallasRun) -> Route:
    """Decide how ``run`` executes on ``qureg`` -- pure: no device work,
    no telemetry, so the whole routing table is testable without a device
    program behind it (tests/test_fusion.py).

    Multi-device registers run the kernel PER SHARD under shard_map when
    every op is shard-executable (:func:`_shard_route`). PRECISION=2
    registers on the df route (:func:`_df_route`) run the double-float
    4-plane kernels; under the explicit distributed scheduler the
    per-shard df runs are joined by the scheduler's COUNTED grouped
    permute collectives (``sched_df``). Otherwise (f32 under the explicit
    scheduler, non-canonical sharding, a target the shard can't pair, an
    f64 register no df kernel takes) the run goes gate by gate, with the
    reason."""
    import jax

    from .ops import pallas_gates as PG
    from .parallel import scheduler as _dist

    sched = _dist.active()
    df = _df_route(qureg.dtype)
    if (sched is not None and sched.mesh is not None
            and sched.mesh.size > 1 and df):
        return _shard_route(qureg, run, sched.mesh, "sched_df")
    mesh = active_pallas_mesh()
    if (sched is None and mesh is not None and mesh.size > 1
            and isinstance(qureg.amps, jax.core.Tracer)):
        # inside a jit trace the tracer hides its sharding; use the ambient
        # mesh, which Circuit.run derived from the register actually being
        # replayed (so it always matches the traced input's sharding)
        return _shard_route(qureg, run, mesh, "sharded")
    sharding = getattr(qureg.amps, "sharding", None)
    if sharding is not None and len(sharding.device_set) > 1:
        if sched is not None:
            return Route("gatewise", reason="explicit_scheduler")
        own = _canonical_amps_mesh(qureg)
        if own is None:
            return Route("gatewise", reason="shard_map_unsupported")
        return _shard_route(qureg, run, own, "sharded")
    nsv = qureg.num_qubits_in_state_vec
    if not df and _mosaic_supports(qureg.dtype):
        return _folded(Route("local", n_exec=nsv,
                             sublanes=_run_sublanes(run, PG._DEF_SUBLANES)),
                       run)
    if ((mesh is None or mesh.size == 1)
            and np.dtype(qureg.dtype) == np.dtype("float64")
            and (1 << nsv) >= 2 * PG._LANES):
        # f64 on the TPU backend, single device: the double-float fast
        # path. The f64 state splits exactly into paired-f32 (hi, lo)
        # planes and the run executes as error-free-transform VPU
        # arithmetic inside the SAME fused single-pass kernel -- the
        # PRECISION=2 analogue of the f32 path's bf16x3 zone dots
        # (ops/pallas_df).
        from .ops.pallas_df import DF_SUBLANES

        sublanes = _run_sublanes(run, DF_SUBLANES)
        lq_df = PG.local_qubits(nsv, sublanes)
        if any(q >= lq_df for op in run.ops
               for q in PG.op_dense_targets(op)):
            # a plan built with non-DF tile geometry (e.g.
            # Circuit.fused(dtype=np.float32) replayed on an f64
            # register) can carry dense targets in [lq_df, plan
            # tile_bits); the gate-by-gate exit -- not a runtime
            # ValueError from fused_local_run -- is the contract for
            # f64 registers (ADVICE round 5)
            return Route("gatewise", reason="df_tile_mismatch")
        return _folded(Route("df_local", n_exec=nsv, sublanes=sublanes,
                             df=True), run)
    # the genuinely unsupported f64 residue: sub-tile registers
    return Route("gatewise", reason="f64_engine")


def _run_sublanes(run: PallasRun, sublanes: int) -> int:
    """The sublanes of the tile ``run``'s kernel is cut at: the route's
    own (``sublanes``), but for a run the planner narrowed
    (``PallasRun.own_tile``), whose ``tile_bits`` say it."""
    from .ops.pallas_gates import LANE_BITS

    if run.own_tile:
        return min(sublanes, 1 << (run.tile_bits - LANE_BITS))
    return sublanes


def _canonical_amps_mesh(qureg):
    """The 1-D amps mesh of the register's concrete canonical sharding
    (NamedSharding over P(None, AMP_AXIS)), or None."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .environment import AMP_AXIS

    sharding = getattr(qureg.amps, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        return None
    if sharding.spec != P(None, AMP_AXIS):
        return None
    return sharding.mesh


def _shard_route(qureg, run: PallasRun, mesh, kind: str) -> Route:
    """The sharded arm of :func:`_route`: ``kind`` (``"sharded"``, or
    ``"sched_df"`` under the explicit scheduler) when every op of the run
    is executable against the shard-local tile, else the gate-by-gate exit
    with its reason.

    Legality: amplitude sharding splits off the TOP qubits, so each shard
    is a contiguous (2, 2^n_local) sub-state on which in-tile targets pair
    locally, while sharded-qubit controls/diagonals/parity members depend
    only on the shard index (jax.lax.axis_index -> the kernel's SMEM
    scalar). One HBM pass per device, zero communication -- the fusion
    analogue of the reference running its local kernel per rank between
    exchanges (QuEST_cpu_distributed.c:870-905). PRECISION=2 registers on
    the df route check against the DF tile geometry (DF_SUBLANES), and a
    plan built with non-DF geometry is the SHARDED df_tile_mismatch case
    -- never a runtime ValueError (the round-7 generalisation of the
    single-device guard). Relabelings fold per shard when the block is
    SHARD-LOCAL (:func:`_folded`); under the scheduler none folds: they
    are its counted permutes on the planes."""
    from .environment import AMP_AXIS
    from .ops import pallas_gates as PG

    df = _df_route(qureg.dtype)
    unsupported = Route("gatewise", reason=("f64_engine" if df
                                            else "shard_map_unsupported"))
    if tuple(mesh.shape.keys()) != (AMP_AXIS,):
        return unsupported
    ndev = mesh.shape[AMP_AXIS]
    if ndev & (ndev - 1):
        return unsupported
    n_local = qureg.num_qubits_in_state_vec - (ndev.bit_length() - 1)
    if df:
        # one lane tile per shard suffices for the gridless df kernel
        if (1 << n_local) < PG._LANES:
            return unsupported
        from .ops.pallas_df import DF_SUBLANES
        sublanes = DF_SUBLANES
    else:
        if not _mosaic_supports(qureg.dtype):
            return Route("gatewise", reason="f64_engine")
        if (1 << n_local) < 2 * PG._LANES:
            return unsupported
        sublanes = PG._DEF_SUBLANES
    sublanes = _run_sublanes(run, sublanes)
    lq = PG.local_qubits(n_local, sublanes)
    if any(q >= lq for op in run.ops for q in PG.op_dense_targets(op)):
        return Route("gatewise", reason=("df_tile_mismatch" if df
                                         else "shard_map_unsupported"))
    route = Route(kind, mesh=mesh, n_exec=n_local, sublanes=sublanes, df=df)
    return route if kind == "sched_df" else _folded(route, run)


def _apply_pallas_run(qureg, run: PallasRun) -> None:
    """Tape-entry wrapper for a PallasRun: ask :func:`_route`, then run
    what it decided. Ops are RAW kernel ops over the full flattened state:
    density plans carry explicit conj-shadow twins (fusion._shadow_pop),
    so no path here re-derives shadows.

    A relabeling the route did not fold runs as an explicit
    swap_bit_blocks pass before / after the kernel -- identical
    semantics. The kernel attempt goes through the resilience guard:
    injected transients retry it (every attempt re-reads ``qureg.amps``
    and is idempotent until its result is put), and a compile fault or an
    exhausted budget degrades to the gate-by-gate exit
    (``fault_degraded``, counted by the guard), the swaps that would have
    ridden the kernel explicit beside it."""
    from .resilience import guard as _guard

    route = _route(qureg, run)
    if route.kind == "gatewise":
        _gatewise(qureg, run, route.reason)
        return
    if route.reason is not None:
        telemetry.inc("engine_fallback_total", reason=route.reason)
    if route.unfolded:
        telemetry.inc("fusion_unfolded_swaps_total", route.unfolded)
    sched_df = route.kind == "sched_df"
    # under the scheduler both relabelings ride inside the attempt, on the
    # 4-plane state; elsewhere those the kernel's DMA folds
    inside = (True, True) if sched_df else (route.fold_load, route.fold_store)
    attempt = partial(_sched_df_run if sched_df else _kernel_run,
                      qureg, run, route)
    _explicit_swaps(qureg, run, load=not inside[0])
    out = _guard.pallas_dispatch(
        attempt, lambda: _gatewise(qureg, run, None, *inside))
    if out is not _guard.DEGRADED:
        qureg.put(out)
    _explicit_swaps(qureg, run, store=not inside[1])


def _explicit_swaps(qureg, run: PallasRun, load: bool = False,
                    store: bool = False) -> None:
    """The run's load and/or store relabeling as an explicit, counted
    pass (:func:`_explicit_swap`; nothing where the run has none): what a
    route that did not fold it runs in its place."""
    for wanted, k, hi in ((load, run.load_swap_k, run.load_swap_hi),
                          (store, run.store_swap_k, run.store_swap_hi)):
        if wanted and k:
            _explicit_swap(qureg, run.tile_bits, k, hi)


def _explicit_swap(qureg, tile_bits: int, k: int, hi: int | None) -> None:
    """One counted relabeling pass outside any kernel: the blocks
    [tile_bits - k, tile_bits) and [lo2, lo2 + k) of the index change
    places (``lo2`` = ``hi``, or ``tile_bits``). ONE permutation, stated
    to the compiler one of two ways by where the register lies
    (:func:`_swap_mesh`): per shard with its all-to-all written out where
    the block reaches a sharded qubit of a register on the canonical amps
    mesh (:func:`_swap_per_shard`), as ``swap_bit_blocks`` of the whole
    array everywhere else."""
    from .ops.pallas_gates import swap_bit_blocks

    lo1, lo2 = tile_bits - k, tile_bits if hi is None else hi
    nsv = qureg.num_qubits_in_state_vec
    _count_frame_swap(qureg, lo2, k)
    mesh = _swap_mesh(qureg, lo1, lo2, k)
    if mesh is None:
        qureg.put(swap_bit_blocks(qureg.amps, n=nsv, lo1=lo1, lo2=lo2, k=k))
        return
    telemetry.inc("fusion_per_shard_swaps_total")
    qureg.put(_swap_per_shard(mesh, nsv, lo1, lo2, k)(qureg.amps))


def _swap_mesh(qureg, lo1: int, lo2: int, k: int):
    """The mesh over which the relabeling [lo1, lo1 + k) <-> [lo2, lo2 + k)
    is stated per shard, or None where ``swap_bit_blocks`` of the whole
    array states it -- pure, like :func:`_route`. Per shard: the block
    reaches a sharded qubit (what :func:`_count_frame_swap` counts a
    collective by), the register lies on the canonical power-of-two amps
    mesh (inside a trace the ambient one, as :func:`_route` reads it), and
    what it changes places with is whole lane rows of one shard
    (``LANE_BITS <= lo1``, ``lo1 + k <= n_local``: every planned frame).
    Otherwise -- one device, a shard-local block that did not
    fold, a non-canonical sharding, a block below the lanes, and under
    the explicit scheduler, whose relabelings are its own counted
    permutes -- the whole-array form, as before."""
    import jax

    from .environment import AMP_AXIS
    from .ops.pallas_gates import LANE_BITS
    from .parallel import scheduler as _dist

    if _dist.active() is not None or lo1 < LANE_BITS:
        return None
    mesh = (active_pallas_mesh() if isinstance(qureg.amps, jax.core.Tracer)
            else _canonical_amps_mesh(qureg))
    if mesh is None or tuple(mesh.shape.keys()) != (AMP_AXIS,):
        return None
    ndev = mesh.size
    n_local = qureg.num_qubits_in_state_vec - (ndev.bit_length() - 1)
    if ndev & (ndev - 1) or lo2 + k <= n_local or lo1 + k > n_local:
        return None
    return mesh


@lru_cache(maxsize=None)
def _swap_per_shard(mesh, n: int, lo1: int, lo2: int, k: int):
    """``swap_bit_blocks(n, lo1, lo2, k)`` of a (P, 2^n) register sharded
    over ``mesh``, for a block [lo2, lo2 + k) that reaches a sharded
    qubit, as ``amps -> amps`` (jitted, the operand donated): the same
    permutation of the index, bit for bit, written per shard on the view
    the kernels use and with the collective stated.

    Of the block's k bits the top ``c`` are device bits (``c <= log2
    devices``: bits [d0, d0 + c) of the shard index) and the low ``kl`` lie
    on the shard, so a shard's rows (``pallas_gates._rows_view``, a
    bitcast) are ``in[B2, M, X, B1, U]``: B2 the block's local bits, M the
    bits between the blocks, X and B1 the top c and low kl bits of
    [lo1, lo1 + k), U the (2^(lo1 - 7) * P, 128) row group below them,
    which moves whole. Device x of the 2^c that differ in those device
    bits must end with ``out[B1', M, Y, B2', U] = in_Y[B2', M, x, B1', U]``:

    1. ``T[X, B1, M, B2, U]``: the local part, one transposition that
       leaves the bits that cross as the major axis;
    2. one ``all_to_all`` over those 2^c devices, split and concatenated
       on that axis: a piece is a contiguous 2^-c of the shard;
    3. the received device index put where the new frame wants it,
       ``(Y, B1, M, ..) -> (B1, M, Y, ..)``, and ``_planes_view`` back
       (again a bitcast), so the next per-shard kernel reads the result
       where it lies.

    The v5e's compiler makes ``copy``, ``all-to-all``, ``copy`` of it: two
    passes over the shard beside the collective, where it finds four
    around the collective of the whole-array form (plane-major there, so
    two of them only undo and redo the kernels' view;
    ``tests/test_chip_compile.py`` pins the count)."""
    import jax

    from .environment import AMP_AXIS
    from .ops import pallas_gates as PG
    from .parallel.mesh import device_groups

    ndev = mesh.shape[AMP_AXIS]
    n_local = n - (ndev.bit_length() - 1)
    cut = max(lo2, n_local)
    c, d0 = lo2 + k - cut, cut - n_local
    kl = k - c
    # the devices that differ in the block's device bits only
    groups = (device_groups(ndev, ((1 << c) - 1) << d0) if 1 << c < ndev
              else None)

    def swap(shard):
        planes = shard.shape[0]
        x = PG._rows_view(shard).reshape(
            1 << kl, 1 << (min(lo2, n_local) - lo1 - k), 1 << c, 1 << kl,
            planes << (lo1 - PG.LANE_BITS), PG._LANES)
        x = x.transpose(2, 3, 1, 0, 4, 5)
        x = jax.lax.all_to_all(x, AMP_AXIS, 0, 0, axis_index_groups=groups,
                               tiled=True)
        x = x.transpose(1, 2, 0, 3, 4, 5)
        return PG._planes_view(x.reshape(-1, PG._LANES), planes)

    return jax.jit(_per_shard(swap, mesh), donate_argnums=(0,))


def _gatewise(qureg, run: PallasRun, reason: str | None,
              load: bool = True, store: bool = True) -> None:
    """The ONE exit from the kernel routes: count ``reason``
    (``engine_fallback_total``; None where the guard already counted its
    ``fault_degraded``) and replay the run's ops through the
    sharding-aware gate-by-gate appliers, its relabelings explicit passes
    around them (``load`` / ``store`` False: that one has already run, or
    will, outside)."""
    if reason is not None:
        telemetry.inc("engine_fallback_total", reason=reason)
    _explicit_swaps(qureg, run, load=load)
    _apply_ops_via_engine(qureg, run.ops)
    _explicit_swaps(qureg, run, store=store)


def _kernel_fn(run: PallasRun, route: Route, on_planes: bool = False):
    """The fused kernel as ``x -> x`` over the array ``route`` executes on
    (the register, or inside shard_map one shard of it, where op roles on
    sharded qubits resolve against the shard index), folded relabelings
    riding its DMA. A df route splits to the 4-plane layout, runs the df
    kernels and joins back (split / join are exact and shard-local: the
    ``sharded`` route's way, a run at a time); ``on_planes`` leaves both
    to the caller (:func:`_df_local_run`, :func:`_sched_df_run`)."""
    import jax

    from .environment import AMP_AXIS
    from .ops import pallas_gates as PG

    def shard_index():
        return None if route.mesh is None else jax.lax.axis_index(AMP_AXIS)

    lk, lh = (run.load_swap_k, run.load_swap_hi) if route.fold_load \
        else (0, None)
    sk, sh = (run.store_swap_k, run.store_swap_hi) if route.fold_store \
        else (0, None)
    if not route.df:
        return lambda x: PG.fused_local_run(
            x, n=route.n_exec, ops=run.ops, sublanes=route.sublanes,
            shard_index=shard_index(), load_swap_k=lk, load_swap_hi=lh,
            store_swap_k=sk, store_swap_hi=sh, ring_depth=run.ring_depth)

    # a df kernel takes at most DF_MAX_OPS ops (Mosaic compile time is
    # superlinear in op count and a df op carries ~15x the arithmetic: a
    # 27-op df kernel exceeded 9 minutes, 8-op kernels compile in
    # seconds). A one-device plan built for this register is already cut
    # there (:func:`_run_op_cap`): one chunk, nothing counted. What still
    # arrives longer is cut here into kernels chained on the (4, N)
    # planes, folded swaps riding the first / last chunk's DMA: a run of a
    # SHARDED df plan (its frame may be a collective, which a piece of its
    # own would pay twice), and a plan replayed on a register it was not
    # built for
    chunks = _df_chunks(run.ops)
    if len(chunks) > 1:
        # each extra chunk is one extra HBM pass the plan did not price
        # in -- visible, not silent (ISSUE 1 tentpole)
        telemetry.inc("engine_fallback_total", len(chunks) - 1,
                      reason="df_max_ops_split")
    last = len(chunks) - 1

    def planes_fn(planes):
        idx = shard_index()
        for ci, chunk in enumerate(chunks):
            planes = PG.fused_local_run(
                planes, n=route.n_exec, ops=chunk, sublanes=route.sublanes,
                shard_index=idx,
                load_swap_k=lk if ci == 0 else 0,
                load_swap_hi=lh if ci == 0 else None,
                store_swap_k=sk if ci == last else 0,
                store_swap_hi=sh if ci == last else None,
                ring_depth=run.ring_depth)
        return planes

    if on_planes:
        return planes_fn
    return lambda x: _df_join(planes_fn(_df_split(x)))


def _df_local_run(qureg, run: PallasRun, route: Route):
    """Executor of the ``df_local`` route: the df kernel on the (4, N)
    planes, returned as the f64 array they join to. The planes come from
    the df run before this one where that run's result is still what the
    register holds (``Qureg.df_planes``, told by identity; counted
    ``fusion_df_carried_total``) and from a split of the register
    otherwise, so a chain of df runs splits once and joins once: whatever
    else reads ``qureg.amps`` between two runs reads the joined array, and
    the next run then splits what it left.

    Inside a trace the join of a run whose planes were taken is dead code
    that XLA drops, and the taker's join stands in its place: it is not
    counted a second time, so ``fusion_df_conversions_total`` counts what
    the compiled program holds. Run eagerly every join has executed: the
    carry saves the split alone, and each join counts."""
    import jax

    kept = qureg.df_planes
    carried = kept is not None and kept[0] is qureg.amps
    if carried:
        telemetry.inc("fusion_df_carried_total")
    planes = _kernel_fn(run, route, on_planes=True)(
        kept[1] if carried else _df_split(qureg.amps))
    amps = _df_join(planes, count=not (
        carried and isinstance(planes, jax.core.Tracer)))
    qureg.df_planes = (amps, planes)
    return amps


def _df_chunks(ops: tuple) -> list:
    """``ops`` in pieces of at most ``DF_MAX_OPS``, one df kernel each (an
    empty run is one empty piece)."""
    from .ops.pallas_df import DF_MAX_OPS

    return ([ops[i:i + DF_MAX_OPS]
             for i in range(0, len(ops), DF_MAX_OPS)] or [ops])


def _df_split(amps64):
    """``pallas_df.df_split`` around a fused run, counted once a trace
    (``fusion_df_conversions_total{dir=split}``): a pass over the f64
    state and its planes that the plan's kernels do not state."""
    from .ops.pallas_df import df_split

    telemetry.inc("fusion_df_conversions_total", dir="split")
    return df_split(amps64)


def _df_join(planes, count: bool = True):
    """``pallas_df.df_join`` around a fused run, counted as
    :func:`_df_split` (``dir=join``) unless it takes the place of a join
    already counted (:func:`_df_local_run`)."""
    from .ops.pallas_df import df_join

    if count:
        telemetry.inc("fusion_df_conversions_total", dir="join")
    return df_join(planes)


def _per_shard(fn, mesh):
    """``fn`` over each device's shard of a (planes, amplitudes) array."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from .environment import AMP_AXIS

    # check_vma=False: pallas_call's out_shape carries no varying-mesh-axes
    # annotation, which the checker (on by default) rejects
    return shard_map(fn, mesh=mesh, in_specs=P(None, AMP_AXIS),
                     out_specs=P(None, AMP_AXIS), check_vma=False)


def _kernel_run(qureg, run: PallasRun, route: Route):
    """Executor of the ``local``, ``df_local`` and ``sharded`` routes: the
    register's new amplitudes."""
    if route.kind == "df_local":
        return _df_local_run(qureg, run, route)
    fn = _kernel_fn(run, route)
    if route.mesh is None:
        return fn(qureg.amps)
    telemetry.inc("fusion_sharded_runs_total")
    return _per_shard(fn, route.mesh)(qureg.amps)


def _sched_df_run(qureg, run: PallasRun, route: Route):
    """Executor of the ``sched_df`` route, a PallasRun on a sharded
    PRECISION=2 register under the explicit scheduler (the ISSUE 3
    tentpole): df-split ONCE, run the fused df kernels per shard over the
    scheduler's mesh, and execute the run's frame relabelings through the
    scheduler's COUNTED grouped permute collective ON the 4-plane state
    (exchange.dist_permute_bits carries all four planes natively;
    chunk-units price at the df 2x scale --
    scheduler.DistributedScheduler.apply_frame_permute)."""
    from .parallel import scheduler as _dist

    sched = _dist.active()

    def permute(planes, k, hi):
        if not k:
            return planes
        lo2 = run.tile_bits if hi is None else hi
        _count_frame_swap(qureg, lo2, k)
        return sched.apply_frame_permute(
            planes, n=qureg.num_qubits_in_state_vec, lo1=run.tile_bits - k,
            lo2=lo2, k=k, pipeline=run.comm_pipeline,
            pipeline_dcn=run.comm_pipeline_dcn)

    planes = permute(_df_split(qureg.amps), run.load_swap_k,
                     run.load_swap_hi)
    planes = _per_shard(_kernel_fn(run, route, on_planes=True),
                        route.mesh)(planes)
    return _df_join(permute(planes, run.store_swap_k, run.store_swap_hi))


def _apply_ops_via_engine(qureg, ops: tuple) -> None:
    """Replay pallas-format ops through the standard kernels (sharding-aware
    via GSPMD or the explicit scheduler). Ops are in physical coordinates
    over the FULL flattened state and already include any density shadow
    twins, so they apply raw -- routing through the gates.py wrappers would
    re-derive shadows and double-apply them on density registers."""
    from .ops import apply as K
    from .ops import cplx
    from .ops import diagonal as D
    from .parallel import scheduler as _dist

    nsv = qureg.num_qubits_in_state_vec
    telemetry.inc("engine_replayed_ops_total", len(ops))
    sched = _dist.active()
    apply_m = sched.apply_matrix if sched else K.apply_matrix
    apply_d = sched.apply_diagonal if sched else D.apply_diagonal
    apply_p = sched.apply_parity_phase if sched else D.apply_parity_phase
    for op in ops:
        if op[0] == "matrix":
            _, q, controls, states, m = op
            mm = cplx.from_complex(np.asarray(m.arr), qureg.dtype)
            qureg.put(apply_m(qureg.amps, mm, n=nsv, targets=(q,),
                              controls=controls, control_states=states))
        elif op[0] == "parity":
            _, qubits, controls, theta = op
            qureg.put(apply_p(qureg.amps, theta, n=nsv, qubits=qubits,
                              controls=controls))
        elif op[0] == "diagw":
            _, targets, controls, d = op
            dd = cplx.from_complex(np.asarray(d.arr), qureg.dtype)
            qureg.put(apply_d(qureg.amps, dd, n=nsv, targets=targets,
                              controls=controls))
        elif op[0] == "swap":
            _, q1, q2, controls, states = op
            if states and any(s == 0 for s in states):  # pragma: no cover
                raise ValueError("swap with 0-controls has no engine route")
            qureg.put(K.apply_swap(qureg.amps, n=nsv, qb1=q1, qb2=q2,
                                   controls=controls))
        elif op[0] in _CHANNEL_OPS:
            from .ops.density import _acc_kraus_term

            if op[0] == "depol":
                _, rows, cols, lam = op
                terms = _depol_kraus_terms(len(rows), lam)
            elif op[0] == "kraus1":
                _, t, c, terms = op
                rows, cols = (t,), (c,)
            elif op[0] == "kraus2":
                _, t1, t2, c1, c2, terms = op
                rows, cols = (t1, t2), (c1, c2)
            elif op[0] == "krausn":
                _, rows, cols, terms = op
            amps0 = qureg.amps
            out = None
            for sign, kk in terms:
                km = cplx.from_complex(np.asarray(kk.arr), qureg.dtype)
                y = apply_m(amps0 + 0, km, n=nsv, targets=rows)
                y = apply_m(y, km, n=nsv, targets=cols, conj=True)
                out = _acc_kraus_term(out, sign, y)
            qureg.put(out)
        else:  # pragma: no cover
            raise ValueError(f"unknown pallas op {op[0]!r}")


def _depol_kraus_terms(num_targets: int, lam: float) -> list:
    """The Kraus terms ``[(1.0, K), ...]`` of the closed-form 'depol' op
    on ``num_targets`` targets with mixing weight ``lam``, in a Kraus op's
    own form -- the canonical table's operators (:mod:`.channels`) at
    ``p = lam (d^2 - 1) / d^2``: what the gate-by-gate exit replays in the
    op's place."""
    from . import channels
    from .ops.pallas_gates import HashableMatrix

    d2 = 4 ** num_targets
    ks = (channels.depolarising_kraus if num_targets == 1
          else channels.two_qubit_depolarising_kraus)(lam * (d2 - 1) / d2)
    return [(1.0, HashableMatrix(np.asarray(k, dtype=complex))) for k in ks]


def _mosaic_supports(dtype) -> bool:
    """Mosaic (TPU Pallas) has no f64 lowering for the kernel's MXU dots;
    f64 registers on TPU take the XLA engine paths instead (XLA emulates
    f64 on TPU -- slow but correct, the documented QUEST_PRECISION=2
    policy; see precision.py)."""
    import jax
    import numpy as np

    if jax.default_backend() != "tpu":
        return True  # CPU interpreter handles f64
    return np.dtype(dtype) != np.dtype("float64")


def _pallas_usable(qureg) -> bool:
    import jax

    sharding = getattr(qureg.amps, "sharding", None)
    if sharding is not None and len(sharding.device_set) > 1:
        return False
    return jax.default_backend() == "tpu" and _mosaic_supports(qureg.dtype)


def _apply_dense_block(qureg, U: np.ndarray, qubits: tuple) -> None:
    """Dense window block dispatch: Pallas MXU dot paths when the register
    is single-device on TPU (window_dot for lo >= 7, a folded lane_u pass
    for hi < 7), the ordinary engine otherwise (CPU, sharded, windows the
    dot kernels can't take).

    Measured per-block at 2^26 amps f32, loop-inside-jit (tools/microbench):
    elementwise floor 3.0 ms, lane_u pallas 4.0 ms, window_dot (5q, hi
    qubits) 4.5 ms, XLA einsum same window 32 ms standalone -- yet routing
    the hi-window blocks through window_dot made the *full* bench slightly
    slower (694 vs 739 gates/s): inside one program XLA fuses the einsum
    with neighbouring diagonal/elementwise work, while a pallas_call is an
    opaque barrier. The einsum engine therefore keeps the hi windows; the
    real win is eliminating standalone blocks entirely (two-frame Pallas
    scheduling, see plan())."""
    from . import gates as G
    from .ops import pallas_gates as PG

    lo, hi = qubits[0], qubits[-1]
    nsv = qureg.num_qubits_in_state_vec
    if (_pallas_usable(qureg) and hi < PG.LANE_BITS
            and (1 << nsv) >= 2 * PG._LANES
            and not qureg.is_density_matrix):
        ev = GateEvent("matrix", tuple(qubits), matrix=U)
        lane_U = event_matrix(ev, tuple(range(PG.LANE_BITS)))
        ur, ui = lane_U.real, lane_U.imag
        # Karatsuba operand stack, matching the kernel's lane_u format
        W = np.stack([ur.T, ui.T, ur.T + ui.T])
        amps = PG.fused_local_run(
            qureg.amps, n=nsv, ops=(("lane_u", PG.HashableMatrix(W)),))
        qureg.put(amps)
        return
    G._apply_gate_matrix(qureg, U, qubits)


# ---------------------------------------------------------------------------
# deferred blocks: factors composed inside the program
# ---------------------------------------------------------------------------

def _deferred_entry(block) -> tuple:
    """The tape entry of a block with deferred factors (the encoder of
    :class:`DeferredBlock`; :func:`plan_from_tape` decodes)."""
    from .engine.params import Param, lift_tape

    sources, index = [], {}     # the distinct source entries, by identity
    for ev in block.factors:
        if ev.source is not None and id(ev.source[0]) not in index:
            index[id(ev.source[0])] = len(sources)
            sources.append(ev.source[0])
    lifted = lift_tape(tuple(sources))
    factors = tuple(
        ev if ev.source is None else dataclasses.replace(
            ev, source=(index[id(ev.source[0])],) + ev.source[1:])
        for ev in block.factors)
    spec = DeferredBlock("diag" if isinstance(block, DiagBlock) else "dense",
                         tuple(block.qubits), factors, lifted.entries,
                         tuple(s.kind for s in lifted.slots))
    values = tuple(Param(s.name) if s.name is not None else s.default
                   for s in lifted.slots)
    return (_apply_deferred_block, (spec,) + values, {})


def _resolve_factors(spec: DeferredBlock, values, num_qubits: int,
                     dtype) -> list:
    """The block's factors with every deferred one given its operand: each
    source entry is materialised with ``values`` (traced inside a
    parameterized replay, host scalars in a constant one) and captured
    again by the capture that planned it."""
    from .engine.params import materialize_entry

    captured = {}
    out = []
    for ev in spec.factors:
        if not ev.deferred:
            out.append(ev)
            continue
        i, j = ev.source[:2]
        if i not in captured:
            captured[i] = capture(*materialize_entry(spec.entries[i], values),
                                  num_qubits, dtype)
        events = captured[i]
        if events is None or len(events) <= j \
                or events[j].structure != ev.structure:
            name = getattr(spec.entries[i][0], "__name__", "entry")
            raise ValueError(
                f"'{name}' no longer captures the structure it was planned "
                f"with: {ev.structure}")
        out.append(events[j])
    return out


def _controls_ok(ev: GateEvent, qubits: Sequence[int]) -> np.ndarray:
    """Static mask over the 2^k indices of ``qubits``: True where every
    control of ``ev`` reads its required state."""
    rows = np.arange(1 << len(qubits))
    ok = np.ones(rows.shape, dtype=bool)
    states = ev.states if ev.states else (1,) * len(ev.controls)
    for c, st in zip(ev.controls, states):
        ok &= ((rows >> list(qubits).index(c)) & 1) == st
    return ok


def _event_phases(ev: GateEvent, qubits: Sequence[int], dtype) -> tuple:
    """(re, im) planes, each (2^k,), of a diagonal-kind event whose operand
    is traced, over ``qubits`` (qubits[j] is bit j), controls folded in:
    the in-trace twin of :func:`_event_diag`. Index algebra is static
    numpy; only selects and the two transcendentals of a parity phase are
    traced. Planes travel apart through the whole composition: a stack is
    a concatenate, which the TPU compiler keeps as an op of its own."""
    import jax.numpy as jnp

    from .ops import cplx

    rows = np.arange(1 << len(qubits))
    bits = [(rows >> list(qubits).index(q)) & 1 for q in ev.targets]
    if ev.kind == "parity":
        sign = np.prod([1 - 2 * b for b in bits], axis=0).astype(dtype)
        half = jnp.asarray(ev.theta) / 2
        re = jnp.cos(half).astype(dtype)
        im = -jnp.sin(half).astype(dtype) * sign
    else:
        d = cplx.from_complex(ev.diag, dtype)
        if len(bits) == 1:
            re = jnp.where(bits[0] == 1, d[0, 1], d[0, 0])
            im = jnp.where(bits[0] == 1, d[1, 1], d[1, 0])
        else:
            idx = sum(b << j for j, b in enumerate(bits))
            re, im = jnp.take(d[0], idx), jnp.take(d[1], idx)
    ok = _controls_ok(ev, qubits)
    return jnp.where(ok, re, 1.0), jnp.where(ok, im, 0.0)


def _cmul(ar, ai, br, bi):
    return ar * br - ai * bi, ar * bi + ai * br


def _rows_matrix(re, im, ev: GateEvent, qubits: Sequence[int], dtype):
    """A traced one-target matrix event applied to the ROW index of the
    accumulator (planes ``re``, ``im``, each (N, N)), as to an N-amplitude
    state: every row takes its own entry times itself plus the other
    entry times its partner row (the target bit flipped -- a reverse of
    the size-2 axis the target splits off). Elementwise f32 arithmetic:
    exact products, no MXU pass, so no precision choice to make."""
    import jax.numpy as jnp

    from .ops import cplx

    n_rows = re.shape[0]
    b = list(qubits).index(ev.targets[0])
    m = cplx.from_complex(ev.matrix, dtype)
    split = (n_rows >> (b + 1), 2, 1 << b, re.shape[1])
    one = np.arange(2).reshape(1, 2, 1, 1) == 1
    own = [jnp.where(one, m[p, 1, 1], m[p, 0, 0]) for p in (0, 1)]
    other = [jnp.where(one, m[p, 1, 0], m[p, 0, 1]) for p in (0, 1)]
    xr, xi = re.reshape(split), im.reshape(split)
    ar, ai = _cmul(own[0], own[1], xr, xi)
    br, bi = _cmul(other[0], other[1], xr[:, ::-1], xi[:, ::-1])
    new = (ar + br).reshape(re.shape), (ai + bi).reshape(re.shape)
    if ev.controls:
        ok = _controls_ok(ev, qubits)[:, None]
        new = jnp.where(ok, new[0], re), jnp.where(ok, new[1], im)
    return new


def _compose_dense(events: Sequence[GateEvent], qubits: Sequence[int],
                   dtype):
    """Product of ``events`` (first applied first) over the window
    ``qubits``. Every operand on the host: the complex128 numpy product,
    as the planner composes a static block. Otherwise the (re, im) planes,
    each (N, N) traced in ``dtype``: each traced factor is applied to the
    accumulator's row index as to an N-amplitude state (O(N^2) a factor,
    elementwise), and each run of host factors is multiplied out on the
    host first and enters as ONE small HIGHEST matmul."""
    import jax
    import jax.numpy as jnp

    acc = None       # None (identity) | numpy complex | (re, im) traced
    run = None       # host product of the static factors since ``acc``

    def settle():
        nonlocal acc, run
        if run is None:
            return
        if acc is None:
            acc = run
        elif isinstance(acc, np.ndarray):
            acc = run @ acc
        else:
            mm = partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
            r, i = jnp.asarray(run.real, dtype), jnp.asarray(run.imag, dtype)
            acc = (mm(r, acc[0]) - mm(i, acc[1]),
                   mm(r, acc[1]) + mm(i, acc[0]))
        run = None

    for ev in events:
        if not _event_traced(ev):
            m = event_matrix(ev, qubits)
            run = m if run is None else m @ run
            continue
        settle()
        if acc is None:
            acc = np.eye(1 << len(qubits), dtype=complex)
        if isinstance(acc, np.ndarray):
            acc = (jnp.asarray(acc.real, dtype), jnp.asarray(acc.imag, dtype))
        if ev.kind == "matrix":
            acc = _rows_matrix(*acc, ev, qubits, dtype)
        else:
            pr, pi = _event_phases(ev, qubits, dtype)
            acc = _cmul(pr[:, None], pi[:, None], *acc)
    settle()
    return acc


def _compose_diag(events: Sequence[GateEvent], qubits: Sequence[int], dtype):
    """Product of diagonal-kind ``events`` over ``qubits``: the complex128
    numpy diagonal when every operand is on the host, else (re, im) planes
    of 2^k, traced in ``dtype`` (the host factors multiplied out first)."""
    import jax.numpy as jnp

    host = np.ones(1 << len(qubits), dtype=complex)
    acc = None
    for ev in events:
        if not _event_traced(ev):
            host = host * _event_diag(ev, qubits)
            continue
        ph = _event_phases(ev, qubits, dtype)
        acc = ph if acc is None else _cmul(*acc, *ph)
    if acc is None:
        return host
    return _cmul(*acc, jnp.asarray(host.real, dtype),
                 jnp.asarray(host.imag, dtype))


def _apply_deferred_block(qureg, spec: DeferredBlock, *values) -> None:
    """Tape-entry wrapper for a block with deferred factors: assemble its
    operator from ``values`` and apply it through the gate primitives (the
    contiguous-window GEMM of ops.apply, the flat diagonal pass, the
    density shadow, the explicit scheduler's routing). Traced values give a
    traced operator, assembled inside the program; host values (a constant
    replay of the same plan) give the numpy product a static block has."""
    import jax

    from . import gates as G

    events = _resolve_factors(spec, values, qureg.num_qubits_represented,
                              qureg.dtype)
    if spec.kind == "diag":
        op = _compose_diag(events, spec.qubits, qureg.dtype)
        apply = G._apply_gate_diag
    else:
        op = _compose_dense(events, spec.qubits, qureg.dtype)
        apply = G._apply_gate_matrix
    if not isinstance(op, np.ndarray):
        op = jax.lax.complex(op[0], op[1])
    apply(qureg, op, spec.qubits)


def gatewise(circuit):
    """``circuit`` with every deferred block spelled out again, for a
    consumer that needs each Param gate on its own (the adjoint sweep of
    quest_tpu.gradients harvests a derivative per gate): a block's Param
    entries come back as recorded, its constant factors as the static
    block entries they would be alone. The same operator, the same slots
    in the same order; memoized per tape revision. ``circuit`` itself when
    it holds no deferred block."""
    from . import gates as G
    from .circuits import Circuit
    from .engine.params import materialize_entry
    from .validation import QuESTError

    if not any(f is _apply_deferred_block for f, _, _ in circuit._tape):
        return circuit
    memo = circuit.__dict__.get("_gatewise")
    if memo is not None and memo[0] is circuit._cache_token:
        return memo[1]
    tape = []
    for entry in circuit._tape:
        if entry[0] is not _apply_deferred_block:
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
                win = _window(ev.support)
                tape.append((_apply_dense_block, (event_matrix(ev, win), win),
                             {}))
            k += 1
    out = Circuit(circuit.num_qubits, circuit.is_density_matrix)
    out._tape = tape
    circuit.__dict__["_gatewise"] = (circuit._cache_token, out)
    return out


def _lift_positions(args) -> dict:
    """engine.params.lift_tape's view of a deferred block entry: the values
    trail the DeferredBlock, kinds as it records them."""
    return {1 + i: kind for i, kind in enumerate(args[0].slot_kinds)}


_apply_deferred_block._lift_positions = _lift_positions


def _apply_frame_swap(qureg, swap: FrameSwap) -> None:
    """Tape-entry wrapper for FrameSwap: one relabeling transpose
    (:func:`_explicit_swap`). Works on every backend (plain XLA); where
    [hi, hi+k) reaches a sharded qubit it is a transpose over the mesh
    (per shard around one stated all-to-all on the canonical amps mesh),
    shard-local otherwise. Under an active explicit scheduler the
    transpose rides the scheduler's COUNTED grouped permute instead
    (apply_frame_permute), so the plan_circuit comm model and the
    frame_transpose telemetry series stay exact."""
    from .parallel import scheduler as _dist

    tile_bits, k = swap.tile_bits, swap.k
    sched = _dist.active()
    if sched is not None and sched.mesh is not None and sched.mesh.size > 1:
        lo2 = tile_bits if swap.hi is None else swap.hi
        _count_frame_swap(qureg, lo2, k)
        qureg.put(sched.apply_frame_permute(
            qureg.amps, n=qureg.num_qubits_in_state_vec, lo1=tile_bits - k,
            lo2=lo2, k=k, pipeline=swap.comm_pipeline,
            pipeline_dcn=swap.comm_pipeline_dcn))
        return
    _explicit_swap(qureg, tile_bits, k, swap.hi)


def as_tape(p: FusePlan) -> list:
    """Lower a FusePlan back to Circuit tape entries (fn, args, kwargs).
    A PallasRun / FrameSwap is its entry's one argument."""
    from . import gates as G

    entries = []
    for item in p.items:
        if getattr(item, "factors", None) is not None:
            entries.append(_deferred_entry(item))
        elif isinstance(item, DiagBlock):
            entries.append((G._apply_gate_diag, (item.diag, item.qubits), {}))
        elif isinstance(item, FusedBlock):
            entries.append((_apply_dense_block, (item.matrix, item.qubits), {}))
        elif isinstance(item, PallasRun):
            entries.append((_apply_pallas_run, (item,), {}))
        elif isinstance(item, FrameSwap):
            entries.append((_apply_frame_swap, (item,), {}))
        else:
            entries.append(item)
    return entries
