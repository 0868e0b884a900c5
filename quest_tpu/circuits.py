"""Circuit: a recorded gate tape compiled into ONE fused XLA program.

The reference applies gates eagerly, one kernel launch (and, when distributed,
one MPI exchange) per gate -- its whole cost model is per-gate
(QuEST_cpu_distributed.c:870-905). On TPU the dominant cost of that scheme is
neither FLOPs nor bandwidth but per-dispatch overhead and lost fusion: XLA
fuses runs of elementwise/diagonal gates into single HBM passes and overlaps
collective traffic with compute *within* one compiled program, never across
programs.

``Circuit`` is therefore the TPU-native execution unit: record the same L5
API calls (same names, same argument order as ``QuEST.h``) against a tape,
then replay the tape symbolically through one ``jax.jit``. Validation and
matrix construction happen once at trace time on the host; the device sees a
single fused program. Eager per-gate application (the reference's model)
remains available by simply calling the API functions directly.

Measurement and host-returning calculations are excluded from tapes (they
need host control flow / RNG); use the eager API for those, or
``lax.cond``-based collapse via ``collapseToOutcome`` eagerly between
circuits.
"""

from __future__ import annotations

import dataclasses
import importlib
import inspect

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from . import cache as _ec
from . import fusion
from . import params as _prm
from . import planner
from . import telemetry
from .capture import capture
from .environment import AMP_AXIS, active_pallas_mesh, pallas_mesh
from .ops.pallas_df import DF_SUBLANES, df_wanted
from .parallel import scheduler as _dist
from .precision import real_dtype
from .registers import Qureg

#: API names that can be recorded on a tape: mutate qureg.amps, need no host
#: round-trip at run time. (measure/collapse and calc* are excluded --
#: their RECORDABLE forms live in sampling.measure, which draws/forces
#: outcomes branch-free on device instead of host-syncing a probability.)
_TAPEABLE_MODULES = ("gates", "operators", "decoherence", "state_init",
                     "trajectories.noise", "sampling.measure")
_EXCLUDED = {
    "measure", "measureWithStats", "collapseToOutcome",
    # these need host data or aren't pure amps->amps
    "createDiagonalOp", "destroyDiagonalOp", "syncDiagonalOp",
    "initDiagonalOp", "setDiagonalOpElems", "initDiagonalOpFromPauliHamil",
    "createDiagonalOpFromPauliHamilFile", "calcExpecDiagonalOp",
    "initStateFromAmps", "setAmps", "setDensityAmps",
}


def _tape_compatible(fn) -> bool:
    """True iff ``fn``'s signature fits the tape contract: the target Qureg
    is the sole Qureg argument and comes first. Functions taking a second
    register (initPureState, cloneQureg, setWeightedQureg, applyPauliSum,
    mixDensityMatrix, ...) would either leak jit tracers into the other
    register or bake its amplitudes into the executable as a stale constant.
    """
    try:
        params = list(inspect.signature(fn).parameters.values())
    except (TypeError, ValueError):
        return False
    if not params:
        return False

    def is_qureg(p):
        return "Qureg" in str(p.annotation) or "qureg" in p.name.lower()

    return is_qureg(params[0]) and not any(is_qureg(p) for p in params[1:])


def _resolve(name):
    for mod_name in _TAPEABLE_MODULES:
        mod = importlib.import_module(f".{mod_name}", __package__)
        fn = getattr(mod, name, None)
        if fn is not None and callable(fn):
            if not _tape_compatible(fn):
                raise AttributeError(
                    f"'{name}' takes a second Qureg (or none first); it must "
                    f"run eagerly, not on a Circuit tape")
            return fn
    raise AttributeError(
        f"'{name}' is not a tapeable quest_tpu API function "
        f"(measurement and calc* functions must run eagerly)")


#: modules whose tape entries route EVERY amps access through the explicit
#: scheduler's coordinate remapping -- safe to run under a deferred layout
_DEFER_SAFE_MODULES = ("quest_tpu.gates", "quest_tpu.decoherence",
                       "quest_tpu.operators")

#: operators-module entries that DO read/write raw full-state amplitude
#: order (a full 2^N diagonal indexed by flat position; a wholesale state
#: overwrite) -- these still force reconciliation
_DEFER_BARRIER_NAMES = {"applyDiagonalOp", "setQuregToPauliHamil"}


def _defer_safe(f) -> bool:
    """True if tape entry ``f`` may run while the scheduler's deferred
    qubit layout is non-identity. Gate, channel and operator entries remap
    their coordinates through the scheduler (phase functions, projectors
    and sub-diagonal ops are pure index algebra -- remapping is
    scheduler.map_diagonal_qubits; matrixN routes through apply_matrix);
    fused dense/diag blocks route through the same gate primitives.
    Everything else (inits, full-state diagonals, Pallas runs and frame
    swaps) assumes the identity layout and forces reconciliation."""

    if getattr(f, "__module__", None) in _DEFER_SAFE_MODULES:
        return getattr(f, "__name__", "") not in _DEFER_BARRIER_NAMES
    return f in (fusion._apply_dense_block, fusion._apply_deferred_block)


def _tape_accesses(tape, num_qubits, is_density, dtype):
    """Per-entry logical-qubit access sets for the deferred scheduler's
    Belady eviction (None = barrier), PLUS the aligned per-entry DENSE
    subsets (qubits used in a relocation-forcing role) the round-6
    relocation batcher prefetches from; returns ``(accesses, dense)``.
    Dense membership mirrors the scheduler's own dispatch: non-diagonal
    matrix targets and X-class targets relocate (apply_matrix / apply_x in
    deferred mode) and channel rows AND columns relocate, while controls,
    parity members, diagonal targets and uncontrolled SWAPs (virtual)
    never do. Dense/diag fused blocks expose their qubits directly; raw
    gate entries are spy-captured; density row events gain their
    conj-shadow column coordinates."""

    def event_dense(ev):
        """The event's relocation-forcing qubits (row coordinates)."""
        if ev.kind == "x":
            return set(ev.targets)
        if ev.kind == "swap":
            # uncontrolled SWAP is a pure layout update (virtual swap)
            return set(ev.targets) if ev.controls else set()
        if ev.kind == "channel":
            return set(ev.targets)
        if ev.kind == "matrix":
            m = np.asarray(ev.matrix)
            if np.any(m - np.diag(np.diag(m)) != 0):
                return set(ev.targets)
            return set()
        return set()  # diag / parity / aux: comm-free under any layout

    out = []
    dense_out = []
    for f, args, kwargs in tape:
        if not _defer_safe(f):
            out.append(None)
            dense_out.append(None)
            continue
        # fused blocks expose their qubits directly: (qubits, dense?)
        block = None
        if f is fusion._apply_dense_block:
            block = (args[1], True)       # FusedBlock: (matrix, qubits)
        elif getattr(f, "__name__", "") == "_apply_gate_diag":
            block = (args[1], False)      # DiagBlock: (diag, qubits)
        elif f is fusion._apply_deferred_block:
            block = (args[0].qubits, args[0].kind == "dense")
        if block is not None:
            qs = set(block[0])
            if is_density:
                qs |= {q + num_qubits for q in qs}
            out.append(frozenset(qs))
            dense_out.append(frozenset(qs if block[1] else ()))
            continue
        events = capture(f, args, kwargs, num_qubits, dtype,
                                is_density=is_density, aux=True)
        if events is None:
            out.append(None)
            dense_out.append(None)
            continue
        qs = set()
        ds = set()
        for ev in events:
            s = set(ev.support)
            d = event_dense(ev)
            if is_density and (not ev.extended or ev.kind == "channel"):
                # channel events carry ROW targets (extended only means "no
                # shadow twin"); their column qubits are accessed too
                s |= {q + num_qubits for q in s}
                d |= {q + num_qubits for q in d}
            qs |= s
            ds |= d
        out.append(frozenset(qs))
        dense_out.append(frozenset(ds))
    return out, dense_out


def _amps_mesh(amps):
    """The 1-D amps mesh a (concrete) amplitude array is sharded over, or
    None for single-device / traced arrays."""
    sharding = getattr(amps, "sharding", None)
    if (isinstance(sharding, NamedSharding)
            and sharding.spec == PartitionSpec(None, AMP_AXIS)
            and sharding.mesh.size > 1):
        return sharding.mesh
    return None


def _register_mesh(qureg):
    """The 1-D amps mesh the register is actually sharded over, or None."""
    return _amps_mesh(qureg.amps)


def named_program(fn, circuit, route: str, *extra) -> object:
    """``fn`` under the stable name its jitted program carries into the
    device trace: ``qt_<route>_<sv|dm>_n<qubits>_g<tape length>`` and
    whatever ``extra`` adds (a batch width ``b8``, a slice ``i0_12``).
    ``jax.jit`` names the module ``jit_<name>``, so the profiler's
    ``XLA Modules`` line says which program of which circuit ran in place
    of ``jit_fn``. Built from the circuit's static shape only -- the name
    is part of the compile-cache key and must be the same in every
    process."""
    kind = "dm" if circuit.is_density_matrix else "sv"
    name = "_".join([f"qt_{route}_{kind}_n{circuit.num_qubits}"
                     f"_g{len(circuit._tape)}", *map(str, extra)])
    fn = _on_a_wide_frame(fn)
    fn.__name__ = fn.__qualname__ = name
    return fn


#: interpreter stack slots the body of a traced program declares (8 MiB
#: and a little: the chunk it forces is the next power of two, 16 MiB)
_TRACE_STACK_SLOTS = (1 << 20) + 64


def _on_a_wide_frame(fn):
    """``fn`` behind a frame that gets a data-stack chunk of its own.

    CPython keeps interpreter frames on a per-thread stack of 16 KiB
    chunks: a call whose frame does not fit the current chunk maps a new
    one, and its return unmaps it at once, so a call site that sits at
    such a depth pays two system calls every time it is reached. A JAX
    trace runs a hundred frames deep across several chunk ends, and on
    the chip's host, where a system call is dear, that is most of a warm
    first call (``df26.block``: 68 s of trace for 3; ``PERF.md`` section
    6, PR 39). A code object that declares a stack of
    :data:`_TRACE_STACK_SLOTS` slots must be given one chunk large enough
    for itself, and the half of it that stays free holds every frame
    below -- pages nobody touches are never faulted. The body of a jitted
    program runs only when JAX traces it: a warm call never comes here."""
    def body(*args, **kwargs):
        return fn(*args, **kwargs)

    try:
        wide = type(body)(
            body.__code__.replace(co_stacksize=_TRACE_STACK_SLOTS),
            body.__globals__, body.__name__, None, body.__closure__)
        wide.__signature__ = inspect.signature(fn)   # JAX names the arguments
    except (AttributeError, TypeError, ValueError):  # no such code objects
        return fn
    return wide


class Circuit:
    """Deferred-execution circuit over ``num_qubits`` qubits.

    Usage::

        c = Circuit(3)
        c.hadamard(0)
        c.controlledNot(0, 1)
        c.run(qureg)           # compiles once, then reuses the executable

    Any L5 gate/operator/decoherence/init function is available as a method
    (without the leading ``qureg`` argument).
    """

    def __init__(self, num_qubits: int, is_density_matrix: bool = False):
        self.num_qubits = int(num_qubits)
        self.is_density_matrix = bool(is_density_matrix)
        self._tape: list = []
        # identity of this tape revision: executable-cache keys carry it, so
        # mutating the tape invalidates them without any per-circuit dict
        # (compiled replays live in the BOUNDED process-global LRU,
        # cache.executables(), with uniform hit/miss/evict telemetry)
        self._cache_token = object()
        self._lifted_cache = None
        self._fp_cache = None

    # -- recording ----------------------------------------------------------

    def __getattr__(self, name):
        if name.startswith("_") or name in _EXCLUDED:
            raise AttributeError(name)
        fn = _resolve(name)

        def record(*args, **kwargs):
            self.append(fn, *args, **kwargs)

        record.__name__ = name
        return record

    def append(self, fn, *args, **kwargs) -> "Circuit":
        """Record ``fn(qureg, *args, **kwargs)`` on the tape."""
        self._tape.append((fn, args, kwargs))
        self._cache_token = object()
        self._lifted_cache = None
        self._fp_cache = None
        return self

    def __len__(self) -> int:
        return len(self._tape)

    # -- execution ----------------------------------------------------------

    def as_fn(self):
        """Pure amps->amps function replaying the tape (jit-compatible).

        Under an active explicit-mesh scheduler the replay runs in DEFERRED
        permutation mode (parallel.scheduler.DistributedScheduler): gate
        relocation swap-backs are elided and the qubit layout reconciles to
        identity only at barrier entries and at replay end. Entries that
        bypass the scheduler's coordinate remapping (state inits, phase
        functions, Pallas runs) are barriers; gate/channel/dense-block
        entries defer."""
        return self._replay_fn(None)

    def _replay_fn(self, lifted, lo: int = 0, hi: int | None = None):
        """The replay body behind :meth:`as_fn` (``lifted=None``) and the
        parameterized executables (``lifted`` a params.LiftedTape):
        with a lifted tape the returned ``fn(amps, values)`` substitutes the
        bound -- typically traced -- scalars into the slotted entries before
        each application, so gate matrices assemble from runtime values
        inside the one compiled program. Each trace of the parameterized
        form counts ``engine_trace_total{kind=param_replay}`` (the retrace
        detector the serving tests assert on).

        ``lo``/``hi`` restrict the replay to ``tape[lo:hi]`` -- the
        segment programs of :mod:`quest_tpu.segments` (round 13). Slices
        are whole replays in miniature: lookahead, deferred-permutation
        scope, and reconciliation all cover exactly the slice, which is
        sound because segment boundaries are frame-identity points.
        Slicing composes with plain replay only (``lifted`` entries are
        indexed against the whole tape)."""

        if lifted is not None and (lo != 0 or hi is not None):
            raise ValueError("sliced replay requires lifted=None")
        tape = tuple(self._tape[lo:hi])
        entries = tuple(lifted.entries) if lifted is not None else None
        num_qubits, is_density = self.num_qubits, self.is_density_matrix
        nsv = (2 if is_density else 1) * num_qubits

        lookahead_cell = []  # memoized across retraces

        def fn(amps, values=()):
            if entries is None:
                steps = tape
            else:
                telemetry.inc("engine_trace_total", kind="param_replay")
                steps = [_prm.materialize_entry(e, values) for e in entries]
            shell = Qureg(num_qubits, is_density, amps, env=None)
            sched = _dist.active()
            # sliced replays label their defer span with the slice origin
            # so a journaled segmented plan re-prices per segment
            # (plancheck.check_schedule "segment" records)
            seg_label = lo if (lo != 0 or hi is not None) else None
            started = sched.begin_defer(segment=seg_label) \
                if sched is not None else False
            try:
                if started:
                    if not lookahead_cell:
                        # access sets come from the ORIGINAL tape: entries
                        # carrying value slots fail capture and barrier,
                        # identically for every values binding
                        lookahead_cell.append(_tape_accesses(
                            tape, num_qubits, is_density, shell.dtype))
                    sched.set_lookahead(*lookahead_cell[0])
                for i, (f, args, kwargs) in enumerate(steps):
                    if sched is not None and sched.deferring:
                        sched.advance(i)
                        if not _defer_safe(f):
                            shell.put(sched.reconcile(shell.amps, nsv))
                    # the gate kind, into the op metadata of whatever
                    # the entry lowers to (trace time only)
                    with jax.named_scope(getattr(f, "__name__", "entry")):
                        f(shell, *args, **kwargs)
                if started:
                    shell.put(sched.end_defer(shell.amps, nsv))
                    sched.set_lookahead(None)
                return shell.amps
            except BaseException:
                if started:
                    # the amps are being discarded; a stale non-identity
                    # layout must not leak into the next replay
                    sched.abort_defer()
                raise

        return fn

    def compiled(self, donate: bool = True):
        """The tape as one jitted executable, cached per execution mode in
        the process-global bounded LRU (cache.executables(): uniform
        eviction + ``plan_cache_{hit,miss,evict}_total`` telemetry -- the
        per-circuit dict of earlier rounds grew without limit per
        (mode, mesh) key).

        Gate routing (default GSPMD vs the explicit_mesh scheduler) is
        trace-time state, so the cache is keyed on the active scheduler's
        mesh -- entering/leaving ``explicit_mesh`` retraces rather than
        silently replaying the other mode's executable.
        """
        sched = _dist.active()
        mesh = sched.mesh if sched else None
        pmesh = active_pallas_mesh()
        key = ("circuit", self._cache_token, donate, mesh, pmesh)

        def build():
            inner = jax.jit(named_program(self.as_fn(), self, "circuit"),
                            donate_argnums=(0,) if donate else ())

            def fn(amps, _inner=inner, _mesh=mesh, _pmesh=pmesh):
                # jit traces on first *call*, which may happen under a
                # different scheduler/pallas-mesh context than the one this
                # executable is keyed on -- pin the modes captured here.
                # With no ambient pallas mesh, derive it from the concrete
                # amps so calling compiled() directly on a sharded register
                # behaves like run() (Pallas/Kraus paths would otherwise
                # trace meshless and GSPMD-gather the shards onto one device)
                pm = _pmesh if _pmesh is not None else _amps_mesh(amps)
                with _dist.explicit_mesh(_mesh), pallas_mesh(pm):
                    return _inner(amps)

            fn.__name__ = inner.__name__     # what a first call's record says
            return fn

        return _ec.executables().get_or_create(key, build)

    # -- parameterized execution (the serving engine's entry points) --------

    def lifted(self):
        """This tape's :class:`~quest_tpu.params.LiftedTape` (value
        slots factored out of Params AND constant angles/Complex scalars),
        memoized per tape revision."""
        tok = self._cache_token
        if self._lifted_cache is None or self._lifted_cache[0] is not tok:
            self._lifted_cache = (tok, _prm.lift_tape(tuple(self._tape)))
        return self._lifted_cache[1]

    @property
    def param_names(self) -> tuple:
        """Ordered unique :class:`~quest_tpu.params.Param` names
        recorded on the tape."""
        return self.lifted().param_names

    def fingerprint(self) -> str:
        """Structure fingerprint of the tape (gate names, targets/controls,
        value-slot kinds -- never the lifted values): the executable-cache
        key under which structure-equal circuits share compiled replays.
        See cache.structure_fingerprint."""
        tok = self._cache_token
        if self._fp_cache is None or self._fp_cache[0] is not tok:
            self._fp_cache = (tok, _ec.structure_fingerprint(
                self._tape, self.num_qubits, self.is_density_matrix))
        return self._fp_cache[1]

    def parameterized(self, donate: bool = True, reduce=None):
        """The tape as ONE jitted executable whose lifted values (Params and
        constant angles/Complex scalars) are runtime arguments: a
        :class:`~quest_tpu.params.ParamExecutable` called as
        ``exe(amps, {"theta": 0.3})``. Changing values never retraces --
        gate matrices assemble from the traced scalars inside the program
        (matrices.py traced branches), including between the static kernel
        runs of a fused Pallas plan.

        ``reduce`` (round 19): an optional traceable terminal stage
        composed INSIDE the jitted program -- the executable returns
        ``reduce(final_amps)`` (e.g. a shot table, an expectation)
        instead of the amplitudes, so the 2^N state never crosses to the
        host. Must be a stable (cached) callable: it is part of the
        executable-cache key.

        Cached in the global LRU keyed by (structure fingerprint, mode
        meshes): two structure-equal circuits -- same ansatz, different
        recorded angles -- share one compiled executable
        (``plan_cache_hit_total``)."""
        sched = _dist.active()
        mesh = sched.mesh if sched else None
        pmesh = active_pallas_mesh()
        lifted = self.lifted()
        fp = self.fingerprint()
        key = ("param", fp, donate, mesh, pmesh, reduce)

        def build():
            body = self._replay_fn(lifted)
            if reduce is not None and getattr(reduce, "wants_values", False):
                # values-aware reduce (the adjoint gradient sweep): the
                # terminal stage sees the bound slot values too, so the
                # backward walk re-assembles daggered gates from the same
                # traced scalars the forward replay used
                whole = lambda amps, values: reduce(body(amps, values),  # noqa: E731
                                                    values)
            elif reduce is not None:
                whole = lambda amps, values: reduce(body(amps, values))  # noqa: E731
            else:
                whole = body
            inner = jax.jit(
                named_program(whole, self, "param",
                              *(() if reduce is None else ("reduce",))),
                donate_argnums=(0,) if donate else ())

            def fn(amps, values, _inner=inner, _mesh=mesh, _pmesh=pmesh):
                pm = _pmesh if _pmesh is not None else _amps_mesh(amps)
                with _dist.explicit_mesh(_mesh), pallas_mesh(pm):
                    return _inner(amps, values)

            fn.__name__ = inner.__name__
            return fn

        return _prm.ParamExecutable(
            _ec.executables().get_or_create(key, build), lifted, fp)

    def gradient(self, hamiltonian, *, donate: bool = True, dtype=None):
        """Compile the tape's adjoint-state gradient against a Pauli-sum
        Hamiltonian (:mod:`quest_tpu.gradients`): one forward sweep, one
        backward walk daggering every gate while harvesting ⟨λ|∂G/∂θ|φ⟩
        per slot -- all lowered into ONE jitted program dispatched as
        ``route=grad_request``. Returns a
        :class:`~quest_tpu.gradients.GradExecutable` called as
        ``grad(amps, {"theta": 0.3}) -> {"value", "grads", "slot_grads"}``.

        Non-invertible tape items (measurement, trajectory noise,
        channels) raise a typed :class:`QuESTError` here, at lift time,
        naming the offending site."""
        # lazy: a facade method that reaches up (gradients stands on
        # circuits)
        from .gradients import gradient_executable
        return gradient_executable(self, hamiltonian, donate=donate,
                                   dtype=dtype)

    def fused(self, max_qubits: int = 5, dtype=None,
              pallas: bool = False, shard_devices: int | None = None,
              ring_depth: int | None = None,
              comm_pipeline: int | None = None,
              comm_pipeline_dcn: int | None = None) -> "Circuit":
        """A new Circuit with runs of gates contracted into ``max_qubits``-
        qubit unitaries at trace time (see :mod:`quest_tpu.fusion`).

        Semantics-preserving for arbitrary tapes: entries that cannot be
        captured as gate primitives (decoherence, phase functions, inits)
        pass through unchanged and act as fusion barriers.

        ``pallas=True`` additionally routes gate runs through the fused
        Pallas kernel (ops.pallas_gates) with two-frame scheduling: one HBM
        pass per run instead of one GEMM pass per dense block. Density
        tapes plan over the flattened 2n-qubit state with explicit
        conj-shadow ops (planner._shadow_pop). ``shard_devices`` plans for execution on a register
        sharded over that many devices: the tile limit shrinks to the
        shard-local size so every emitted run is per-shard executable under
        shard_map (fusion._shard_route); Circuit.run keeps that
        per-shard path active inside the jitted replay by deriving the
        execution mesh from the register it is given (environment.pallas_mesh).

        ``ring_depth`` is the PLAN-level knob for the manual-DMA ring
        (ops.pallas_gates._make_dma_kernel): stamped onto every emitted
        PallasRun, it outranks the QUEST_PALLAS_RING env default when the
        runs execute. None leaves the process default in charge.

        ``comm_pipeline`` is the comm-side twin: the collective pipeline
        depth (parallel.exchange) stamped onto every emitted PallasRun and
        FrameSwap, outranking the QUEST_COMM_PIPELINE env default when the
        plan's frame relabelings ride the explicit scheduler's grouped
        collectives. Bit-identical at every depth; 1 = the monolithic
        launch. None leaves the process default in charge.

        ``comm_pipeline_dcn`` (round 15) is the per-link-class refinement:
        sub-collectives that cross a DCN shard bit (num_slices > 1 under
        the explicit scheduler) pipeline at this depth while ICI ones keep
        ``comm_pipeline``. None defers to QUEST_COMM_PIPELINE_DCN, then to
        the base depth (parallel.exchange.resolve_pipeline_dcn).
        """
        tile_bits = None
        shard_boundary = None
        if pallas:
            from .ops.pallas_gates import (  # lazy: Pallas
                LANE_BITS, local_qubits)
            # density tapes plan over the flattened 2n-qubit state: the
            # conj-shadow column qubits are explicit ops in the plan
            # (planner._shadow_pop), so the tile geometry is the state's
            n_eff = (2 if self.is_density_matrix else 1) * self.num_qubits
            if shard_devices and shard_devices > 1:
                d = int(shard_devices)
                if d & (d - 1):
                    raise ValueError(
                        f"shard_devices must be a power of 2 (got {d}); "
                        "amplitude sharding splits whole top qubits")
                n_eff -= d.bit_length() - 1
                # align frame blocks to the shard boundary: frames below
                # it relabel with shard-LOCAL transposes (no collective)
                shard_boundary = n_eff
            # below 2^LANE_BITS amplitudes there is no lane tile to build;
            # the ordinary fusion path handles such registers
            if n_eff > LANE_BITS:
                dt_plan = np.dtype(dtype) if dtype else real_dtype()
                if dt_plan == np.dtype("float64") and df_wanted():
                    # f64 on the df route (TPU always; elsewhere opt-in
                    # via QUEST_PALLAS_DF=1) runs the double-float
                    # kernel, whose tuned tile is smaller
                    # (ops/pallas_df.DF_SUBLANES) -- sharded plans built
                    # here use the SAME geometry per shard, so the
                    # local/dense split matches the df executor; the
                    # native-f64 interpreter geometry applies otherwise
                    tile_bits = local_qubits(n_eff, DF_SUBLANES)
                else:
                    tile_bits = local_qubits(n_eff)
        dt = np.dtype(dtype) if dtype else real_dtype()
        if tile_bits is not None and shard_boundary is not None:
            # sharded: try plain and boundary-aligned frame tilings, keep
            # the one with fewer collective transposes
            p = planner.plan_pallas_sharded(
                tuple(self._tape), self.num_qubits, dt, max_qubits,
                tile_bits, shard_boundary,
                is_density=self.is_density_matrix)
        else:
            p = planner.plan(tuple(self._tape), self.num_qubits, dt,
                            max_qubits=max_qubits,
                            pallas_tile_bits=tile_bits,
                            is_density=self.is_density_matrix)
        # all stamping happens here, on the plan: its runs and swaps are
        # frozen, and nothing changes one that is on a tape
        comm = {name: int(depth) for name, depth in (
            ("comm_pipeline", comm_pipeline),
            ("comm_pipeline_dcn", comm_pipeline_dcn)) if depth is not None}
        for i, item in enumerate(p.items):
            if ring_depth is not None and isinstance(item, planner.PallasRun):
                item = dataclasses.replace(item, ring_depth=int(ring_depth))
            if comm and isinstance(item, (planner.PallasRun,
                                          planner.FrameSwap)):
                item = dataclasses.replace(item, **comm)
            p.items[i] = item
        # round 13: stamp each frame-carrying item with its frame-identity
        # segment index (the single-dispatch segment programs' seams;
        # plancheck QT107 re-derives and cross-checks the stamps)
        # lazy: reaches up (segments stands on circuits); what it calls
        # there reads the plan alone
        from . import segments as _segments
        _segments.stamp_plan(
            p, (2 if self.is_density_matrix else 1) * self.num_qubits)
        from . import analysis  # lazy: the checkers read every layer
        if analysis.verify_enabled():
            # QUEST_VERIFY=1: statically verify the plan's frame/ring
            # invariants at compile time; raises AnalysisError on
            # error-severity findings (docs/analysis.md). Sharded plans
            # are verified over the FULL state-vector space: frame grid
            # blocks may reach sharded qubits (collective transposes).
            plan_space = \
                (2 if self.is_density_matrix else 1) * self.num_qubits
            analysis.verify_plan(
                p, nsv=plan_space, dtype=dt, shard_qubits=shard_boundary,
                location=f"fused({self.num_qubits}q)")
        out = Circuit(self.num_qubits, self.is_density_matrix)
        out._tape = fusion.as_tape(p)
        return out

    def compiled_segments(self, max_items: int | None = None,
                          donate: bool = True):
        """The tape as a chain of frame-identity-aligned segment programs
        (round 13, :mod:`quest_tpu.segments`): each segment is ONE jitted
        dispatch covering up to ``max_items`` tape entries, cut only at
        frame-identity seams. For deep tapes: one arbitrarily deep circuit
        as a single XLA program eventually exhausts the compiler, and a
        chain bounds the per-program compile size; the seams are legal
        checkpoint/resume points and the dispatch tax is the SEGMENT
        count (``max_items=None`` = the whole tape as one program). The chain exposes its link count as
        ``.num_segments``; every link launch counts
        ``device_dispatch_total{route="segment"}``."""
        from . import segments  # lazy: a facade method that reaches up
        return segments.chain_executable(self, max_items=max_items,
                                         donate=donate)

    def compiled_request(self, donate: bool = True, reduce=None):
        """The WHOLE request -- every frame-identity segment plus an
        optional final traceable ``reduce(amps)`` -- composed into ONE
        dispatched program with the state buffer donated end-to-end
        (round 18, :func:`quest_tpu.segments.request_executable`).
        ``dispatches_per_circuit`` hits its floor of 1: calling the
        returned executable counts exactly one
        ``device_dispatch_total{route="request"}`` however many segments
        (``.num_segments``) were composed."""
        from . import segments  # lazy: a facade method that reaches up
        return segments.request_executable(self, donate=donate,
                                           reduce=reduce)

    def run(self, qureg: Qureg) -> Qureg:
        """Apply the circuit to ``qureg`` (mutates its amps, like the C API).

        The whole tape is one jitted program -- already the degenerate
        single-dispatch segment -- counted as
        ``device_dispatch_total{route="circuit"}`` (host-side: counters
        inside the program would count traces, not launches)."""
        if qureg.num_qubits_represented != self.num_qubits or \
           qureg.is_density_matrix != self.is_density_matrix:
            raise ValueError(
                f"Circuit({self.num_qubits}q, density={self.is_density_matrix}) "
                f"cannot run on {qureg!r}")
        # the library path's host cost per application, measured where it
        # is spent: cache lookup, mesh context, the jitted call, the put.
        # It ends before any sync, and is on every application's path: a
        # region (aggregate + profiler annotation), never a ring event
        mark = telemetry.compile_mark()
        with telemetry.region("circuit.run") as rg, \
                pallas_mesh(_register_mesh(qureg)):
            telemetry.inc("device_dispatch_total", route="circuit")
            program = self.compiled()
            qureg.put(program(qureg.amps))
        if telemetry.compile_mark() is not mark:
            # a call that traced or compiled is a first call: one record
            # that names the program and tiles the region (a warm call
            # pays the two thread-local reads)
            telemetry.first_call(mark, rg, program.__name__, "circuit")
        return qureg

    def run_segmented(self, target, *, checkpoint_dir: str,
                      every_n_items: int = 1, keep: int = 2) -> Qureg:
        """Run the tape in segments, checkpointing at frame-identity
        boundaries so a preempted run resumes bit-identically from the
        last *verified* snapshot (:func:`quest_tpu.resilience.segmented.
        resume_segmented`). ``target`` is a Qureg or a QuESTEnv (a fresh
        zero-state register is created). ``every_n_items`` spaces the
        checkpoint cadence in tape items; ``keep`` bounds snapshot
        generations retained on disk. See docs/resilience.md."""
        # lazy: a facade method that reaches up (segmented runs circuits)
        from .resilience import segmented as _seg
        return _seg.run_segmented(self, target, checkpoint_dir=checkpoint_dir,
                                  every_n_items=every_n_items, keep=keep)
