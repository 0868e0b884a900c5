"""The planner: a tape's captured events scheduled into a plan.

Host only. A tape entry is captured once against a spy register
(:mod:`.capture`) and its events (:mod:`.events`) are scheduled into a
:class:`FusePlan`, one of two ways (:func:`plan`):

* the DENSE plan: runs of gates whose combined support fits a contiguous
  window of ``max_qubits`` qubits multiply into one 2^k x 2^k unitary on
  the host (numpy, plan time) and hit the state as one GEMM that XLA tiles
  onto the MXU; diagonal events merge by support into one broadcast
  multiply. The dense-fusion technique of state-vector simulators (qsim's
  gate fusion, cuQuantum's custatevecApplyMatrix batching); the reference,
  whose cost model is per gate (QuEST_cpu_distributed.c:870-905), has no
  analogue. An entry that carries Params joins a block by its structure
  alone (a deferred factor), its operand assembled inside the program.
* the PALLAS plan: every expressible gate lowered to a kernel op and
  scheduled into fused single-HBM-pass kernel runs (:class:`PallasRun`)
  over alternating qubit frames (:class:`_FramePlanner`).

An entry that fails capture is a barrier and passes through unchanged, so
a plan is semantics-preserving for arbitrary tapes. What RUNS a plan --
the routing of a run on a register, the appliers a plan's tape entries
name -- is :mod:`.fusion`, which stands on this module; nothing here
imports it, ``circuits`` or ``engine``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from . import telemetry
from .capture import _capture_deferred, _entry_has_params, capture
from .events import (GateEvent, _embed_block, _event_diag, _event_is_diag,
                     event_matrix)
from .ops.apply import (_MIN_MINOR, DENSE_WINDOW_QUBITS,
                        MAX_LOW_WINDOW_TOP)
from .ops.density import choi_kraus
from .ops.pallas_df import DF_MAX_OPS, df_wanted

#: why every ``ops.pallas_gates`` import below stays inside its function:
#: that module's import is ``jax.experimental.pallas``'s, about a second
#: that a process which plans no kernel (a served ansatz) does not pay.
#: The host-side helpers it holds (``LANE_BITS``, ``HashableMatrix``,
#: ``writes_in_place``, the zone fold) are a named debt (ROADMAP C1).


# ---------------------------------------------------------------------------
# the fuser
# ---------------------------------------------------------------------------

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
    as lifted templates (params.lift_tape) whose slots are the
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
        from .ops.pallas_gates import writes_in_place  # lazy: Pallas

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
        from .ops.pallas_gates import LANE_BITS  # lazy: Pallas

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
        from .ops.pallas_gates import LANE_BITS  # lazy: Pallas

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
        from .ops.pallas_gates import HashableMatrix  # lazy: Pallas

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
        from .ops import pallas_gates as PG  # lazy: Pallas
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


def dense_plan(tape, num_qubits: int, dtype,
               is_density: bool = False) -> FusePlan:
    """The dense plan a served program replays: :func:`plan` at windows
    of ``ops.apply.DENSE_WINDOW_QUBITS``. The ONE statement of that call:
    ``Engine._plan_program`` builds its forward program from it and the
    adjoint sweep its backward walk (``gradients.adjoint._plan_blocks``),
    which undoes block by block what the forward half applied."""
    return plan(tuple(tape), num_qubits, dtype,
                max_qubits=DENSE_WINDOW_QUBITS, is_density=is_density)


def _plan_dense(tape, num_qubits: int, dtype, max_qubits: int,
                max_diag_qubits: int) -> FusePlan:
    """The dense arm of :func:`plan`: window and diagonal blocks."""
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
    from .ops.pallas_gates import HashableMatrix  # lazy: Pallas

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
    from .ops.pallas_gates import LANE_BITS  # lazy: Pallas

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
    return np.dtype(dtype) == np.dtype("float64") and df_wanted()



def _fold_width(tile_bits: int) -> int:
    """The widest relabeling a kernel's DMA folds at this tile: the low
    part of the split sublane axis keeps one sublane tile of 8 rows
    (``tile_bits - LANE_BITS - k >= 3``), so that the gathered
    (P * s_low, 128) pieces stay layout-free (``pallas_gates._load_planes``).
    The one statement of the number: :func:`_folded` routes by it and
    ``_FramePlanner.width`` holds its frames to it."""
    from .ops.pallas_gates import LANE_BITS  # lazy: Pallas

    return tile_bits - LANE_BITS - 3



def _df_chunks(ops: tuple) -> list:
    """``ops`` in pieces of at most ``DF_MAX_OPS``, one df kernel each (an
    empty run is one empty piece)."""
    return ([ops[i:i + DF_MAX_OPS]
             for i in range(0, len(ops), DF_MAX_OPS)] or [ops])


