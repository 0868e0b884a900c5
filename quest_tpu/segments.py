"""Whole-segment single-dispatch execution (ISSUE 12, round 13).

Historically every PallasRun / FrameSwap / collective on a tape was its
own device dispatch with the host interpreting the tape between them --
BASELINE.md's round-5 methodology measured that fixed host dispatch+sync
cost at ~25-100 ms per round (``dispatch_fixed_ms``), dominating serve
latency at small sizes. This module lowers a whole FusePlan *segment* --
a maximal tape slice whose two-frame permutation starts AND ends at
identity (the same boundaries ``run_segmented`` checkpoints at, proved
by plancheck QT102) -- into ONE jitted program dispatched once: the
command-buffer/graph-launch idea from the cuQuantum lineage (PAPERS.md)
re-targeted at XLA's one-traced-program-per-structure executable model.

Two execution surfaces ride it:

- :func:`run_slice` -- execute ``tape[lo:hi]`` on a register as one
  segment program; ``resilience.segmented`` uses it between checkpoints,
  with a stable cache key so resumed/healed segments never retrace.
- :func:`chain_executable` (behind ``Circuit.compiled_segments``) -- the
  tape as a chain of frame-identity-aligned segment programs, each at
  most ``max_items`` tape entries: a bounded compile size per program,
  checkpointable seams, and a dispatch count equal to the SEGMENT count,
  not the gate count.

Numeric contract (tests/test_segments.py pins all of it): a fixed
segmentation is run-to-run deterministic (bit-identical) on every leg,
and the whole-tape segment program is bit-identical to ``compiled()``.
ACROSS program granularities XLA-CPU duplicates producer expressions and
contracts fma differently per compiled program (the documented
tests/test_sharded_df.py caveat), so a chain of several programs and the
whole-tape program -- and anything on the df route or a CPU mesh, where
even single items embed differently -- agree to ~1 ulp, not bit-exactly.
On TPU the Mosaic kernel is opaque to XLA, so recontraction cannot reach
inside it and the granularities coincide.

Every device program launch counts ``device_dispatch_total{route}``
host-side (telemetry counters inside jit would count traces, not
executions): ``route="segment"`` per segment program,
``route="circuit"`` per whole-tape ``Circuit.run`` dispatch,
``route="request"`` per whole-request program
(:func:`request_executable` -- round 18: every segment plus the final
reduction composed into ONE dispatched program, the
``dispatches_per_circuit == 1`` floor), ``route="engine_vmap"`` /
``"engine_param"`` at the serving engine's two dispatch sites.
docs/observability.md has the full table.
"""

from __future__ import annotations

import dataclasses
import time

import jax

from . import cache as _ec
from . import fusion
from . import telemetry
from .circuits import _amps_mesh, _register_mesh, named_program
from .environment import active_pallas_mesh, pallas_mesh
from .parallel import scheduler as _dist
from .planner import FrameSwap, PallasRun
from .validation import QuESTError

__all__ = [
    "identity_boundaries", "segment_cuts", "stamp_plan",
    "slice_executable", "run_slice", "chain_executable",
    "request_executable",
]


# -- frame-identity boundaries -----------------------------------------------

def _swap_blocks(perm: list, tile_bits: int, k: int, hi) -> None:
    """Apply one frame relabeling to the symbolic qubit permutation:
    blocks ``[tile_bits-k, tile_bits)`` and ``[hi, hi+k)`` (``hi`` =
    tile_bits when None) exchange, exactly mirroring what
    ``swap_bit_blocks`` / the scheduler's frame transpose do to the
    physical layout."""
    lo = tile_bits - k
    hi = tile_bits if hi is None else hi
    for i in range(k):
        perm[lo + i], perm[hi + i] = perm[hi + i], perm[lo + i]


def _replay_frame(perm: list, item) -> None:
    """Apply a plan item's frame relabelings (a PallasRun's load / store
    swaps, a standalone FrameSwap) to ``perm``; every other item leaves
    the frame untouched."""
    if isinstance(item, PallasRun):
        if item.load_swap_k:
            _swap_blocks(perm, item.tile_bits, item.load_swap_k,
                         item.load_swap_hi)
        if item.store_swap_k:
            _swap_blocks(perm, item.tile_bits, item.store_swap_k,
                         item.store_swap_hi)
    elif isinstance(item, FrameSwap):
        _swap_blocks(perm, item.tile_bits, item.k, item.hi)


def identity_boundaries(tape, nsv: int) -> list:
    """Indices ``i`` where the two-frame permutation is identity after
    ``tape[:i]`` -- the legal segment seams. Always includes 0; includes
    ``len(tape)`` iff the tape ends at identity (every fused plan does,
    by the QT102 contract). Replays the frame symbolically over the
    tape's decoded items (``fusion.plan_from_tape``, one item an entry).

    This is the ONE boundary computation -- ``resilience.segmented``
    delegates here."""
    perm = list(range(nsv))
    ident = list(range(nsv))
    bounds = [0]
    for i, item in enumerate(fusion.plan_from_tape(tape).items):
        _replay_frame(perm, item)
        if perm == ident:
            bounds.append(i + 1)
    return bounds


def measurement_seams(tape) -> set:
    """Tape indices that MUST be segment cuts because a measurement site
    (round 19, ``quest_tpu.sampling.measure`` -- entries tagged
    ``_measurement_site``) sits between them: the seam before and after
    each site. Measurement sites are where recorded outcomes become
    definite, so checkpoint/resume boundaries align with them exactly
    like they align with frame identity."""
    seams: set = set()
    for i, (f, _a, _kw) in enumerate(tape):
        if getattr(f, "_measurement_site", False):
            seams.add(i)
            seams.add(i + 1)
    return seams


def segment_cuts(tape, nsv: int, max_items: int | None = None) -> list:
    """Greedy coarsest identity-aligned cut list ``[0, ..., len(tape)]``:
    each segment is the LARGEST boundary-to-boundary span of at most
    ``max_items`` tape entries (None = unbounded, typically the whole
    tape as one program -- in the two-frame scheme most items restore
    identity individually, so boundaries are plentiful and the cap, not
    the boundary supply, sets the segment size). A single
    boundary-to-boundary gap longer than ``max_items`` becomes its own
    segment (frames cannot be cut mid-flight). A tape that does not end
    at identity gets a final non-checkpointable segment to ``len(tape)``
    -- execution stays correct; only fused plans guarantee the QT102
    tail.

    Measurement sites (:func:`measurement_seams`) force additional cuts:
    a segment never spans across a mid-circuit measurement, so every
    site starts (and ends) its own segment -- the seam where a recorded
    outcome becomes definite. A seam that is not at frame identity is
    skipped (the frame cannot be cut mid-flight; tapelint QT005 flags
    that tape)."""
    if max_items is not None and max_items < 1:
        raise ValueError("max_items must be >= 1")
    bounds = identity_boundaries(tape, nsv)
    if bounds[-1] != len(tape):
        bounds.append(len(tape))
    # forced measurement seams, restricted to legal (identity) boundaries
    forced = sorted(measurement_seams(tape) & set(bounds))
    cuts = [0]
    while cuts[-1] < len(tape):
        start = cuts[-1]
        fence = next((b for b in forced if b > start), None)
        nxt = [b for b in bounds if b > start
               and (fence is None or b <= fence)]
        if max_items is not None:
            capped = [b for b in nxt if b - start <= max_items]
            cuts.append(capped[-1] if capped else nxt[0])
        else:
            cuts.append(nxt[-1])
    return cuts


def stamp_plan(plan, nsv: int) -> int:
    """Stamp every frame-carrying plan item (PallasRun / FrameSwap) with
    the index of the frame-identity segment it belongs to (``item.seg``;
    the items are frozen, so each is replaced on the plan) and return the
    segment count. Segment indices advance exactly at identity returns,
    so plancheck's QT107 check can re-derive them independently and prove
    each emitted segment starts and ends at frame identity in FusePlan
    order."""

    perm = list(range(nsv))
    ident = list(range(nsv))
    seg = 0
    for i, item in enumerate(plan.items):
        if isinstance(item, (PallasRun, FrameSwap)):
            plan.items[i] = dataclasses.replace(item, seg=seg)
            _replay_frame(perm, item)
        if perm == ident:
            seg += 1
    return seg


# -- segment programs --------------------------------------------------------

def slice_executable(circuit, lo: int, hi: int, donate: bool = True):
    """``tape[lo:hi]`` as ONE jitted executable -- the segment program.

    Cached in the process-global bounded LRU (cache.executables)
    keyed on the circuit's stable ``_cache_token`` plus the slice and
    execution-mode meshes, so repeated segment executions -- checkpoint
    cadences, rollback-and-replay healing, bench chains -- dispatch
    warm without retracing (the pre-round-13 ``run_segmented`` built a
    fresh Circuit per segment and paid a full recompile every run).
    Mesh pinning mirrors ``Circuit.compiled``: jit traces on first
    call, which may happen under a different scheduler/pallas-mesh
    context than the one this executable is keyed on."""

    sched = _dist.active()
    mesh = sched.mesh if sched else None
    pmesh = active_pallas_mesh()
    key = ("segment", circuit._cache_token, lo, hi, donate, mesh, pmesh)

    def build():
        stop = len(circuit._tape) if hi is None else hi
        inner = jax.jit(
            named_program(circuit._replay_fn(None, lo=lo, hi=hi), circuit,
                          "segment", f"i{lo}_{stop}"),
            donate_argnums=(0,) if donate else ())

        def fn(amps, _inner=inner, _mesh=mesh, _pmesh=pmesh):
            pm = _pmesh if _pmesh is not None else _amps_mesh(amps)
            with _dist.explicit_mesh(_mesh), pallas_mesh(pm):
                return _inner(amps)

        return fn

    return _ec.executables().get_or_create(key, build)


def run_slice(circuit, qureg, lo: int = 0, hi: int | None = None, *,
              donate: bool = True):
    """Execute ``tape[lo:hi]`` on ``qureg`` (mutates its amps) as ONE
    segment program: ``device_dispatch_total{route="segment"}`` counts
    exactly one launch. The numeric contract is the module docstring's:
    deterministic run to run, bit-identical where the compiled programs
    match, ~1 ulp across program granularities on XLA-CPU
    (granularity-invariant on TPU, where Mosaic kernels are opaque to fma
    recontraction)."""
    hi = len(circuit._tape) if hi is None else hi
    if hi <= lo:
        return qureg
    ctx = telemetry.current_trace() if telemetry.trace_on() else None
    with pallas_mesh(_register_mesh(qureg)):
        fn = slice_executable(circuit, lo, hi, donate=donate)
        telemetry.inc("device_dispatch_total", route="segment")
        if ctx is not None:
            # the segment launch splits into its dispatch/device
            # phases: an explicit sync separates the host-side
            # launch from the device drain (armed path only -- the
            # untraced path never blocks)

            out = fn(qureg.amps)
            ctx.charge("dispatch", time.perf_counter())
            jax.block_until_ready(out)
            ctx.charge("device", time.perf_counter())
            qureg.put(out)
        else:
            qureg.put(fn(qureg.amps))
    return qureg


def chain_executable(circuit, max_items: int | None = None,
                     donate: bool = True):
    """The whole tape as a chain of segment programs (one per
    :func:`segment_cuts` span), behind ``Circuit.compiled_segments``.
    Each link is a cached :func:`slice_executable`; the chain itself is
    cached too. Calling the chain counts one
    ``device_dispatch_total{route="segment"}`` per link -- the dispatch
    tax is the segment count, not the gate count."""
    sched = _dist.active()
    key = ("segment_chain", circuit._cache_token, max_items, donate,
           sched.mesh if sched else None, active_pallas_mesh())

    def build():
        nsv = (2 if circuit.is_density_matrix else 1) * circuit.num_qubits
        cuts = segment_cuts(circuit._tape, nsv, max_items)
        fns = tuple(slice_executable(circuit, a, b, donate=donate)
                    for a, b in zip(cuts, cuts[1:]))

        def chained(amps, _fns=fns):
            for f in _fns:
                telemetry.inc("device_dispatch_total", route="segment")
                amps = f(amps)
            return amps

        chained.num_segments = len(fns)
        return chained

    return _ec.executables().get_or_create(key, build)


def request_executable(circuit, donate: bool = True, reduce=None):
    """The WHOLE request as ONE dispatched program (round 18): every
    frame-identity segment of the tape, plus an optional final traceable
    ``reduce(amps)`` (a probability readout, an expectation contraction),
    composed inside a single ``jax.jit`` with the state buffer donated
    end-to-end -- intermediate segment states live and die inside the
    one XLA program, never round-tripping through the host. ``reduce``
    may declare extra RUNTIME positional arguments after ``amps`` (the
    round-19 shot sampler's PRNG seed); the returned executable passes
    them through -- ``fn(amps, *extra)`` -- so value changes never touch
    the cache key or the compiled structure. A request
    then touches the host exactly twice (submit, result) and
    ``device_dispatch_total{route="request"}`` counts exactly ONE launch
    per call: ``dispatches_per_circuit`` hits its floor of 1, where
    :func:`chain_executable` pays one launch per segment.

    The segment seams (every :func:`identity_boundaries` return to frame
    identity) are preserved as replay-slice boundaries, so the program
    is the composition of the SAME per-segment replays the chained and
    checkpointed routes run -- slice replays compose into the identical
    primitive sequence as the whole-tape replay, making the request
    program bit-identical to ``compiled()`` run-to-run (the
    cross-granularity caveat in the module docstring still applies on
    XLA-CPU). Cached in the process-global LRU under
    ``("request_chain", ...)``; ``fn.num_segments`` reports how many
    segments were composed, ``fn.num_dispatches = 1`` the launch
    count."""

    if getattr(reduce, "wants_values", False):
        raise QuESTError(
            "request_executable replays a concrete tape and has no "
            "parameter-values vector to hand a wants_values reduce (the "
            "gradient engine's grad_reduce); use Circuit.gradient / "
            "Engine.submit_grad for the one-dispatch grad_request route",
            "request_executable")
    sched = _dist.active()
    mesh = sched.mesh if sched else None
    pmesh = active_pallas_mesh()
    key = ("request_chain", circuit._cache_token, donate, reduce, mesh,
           pmesh)

    def build():
        nsv = (2 if circuit.is_density_matrix else 1) * circuit.num_qubits
        bounds = identity_boundaries(circuit._tape, nsv)
        if bounds[-1] != len(circuit._tape):
            bounds.append(len(circuit._tape))
        replays = tuple(circuit._replay_fn(None, lo=a, hi=b)
                        for a, b in zip(bounds, bounds[1:]))

        def whole(amps, *extra, _replays=replays, _reduce=reduce):
            for f in _replays:
                amps = f(amps)
            return amps if _reduce is None else _reduce(amps, *extra)

        inner = jax.jit(
            named_program(whole, circuit, "request", f"s{len(replays)}"),
            donate_argnums=(0,) if donate else ())

        def fn(amps, *extra, _inner=inner, _mesh=mesh, _pmesh=pmesh):
            pm = _pmesh if _pmesh is not None else _amps_mesh(amps)
            # ONE launch for the whole request -- the counter delta the
            # bench's dispatches_per_circuit row and native.yml gate read
            telemetry.inc("device_dispatch_total", route="request")
            with _dist.explicit_mesh(_mesh), pallas_mesh(pm):
                return _inner(amps, *extra)

        fn.num_segments = len(replays)
        fn.num_dispatches = 1
        return fn

    return _ec.executables().get_or_create(key, build)
