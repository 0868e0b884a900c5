"""Running a plan: routes, appliers, and the plan as a tape.

The reference executes one kernel (and one MPI exchange, when distributed)
per gate -- its cost model is per-gate (QuEST_cpu_distributed.c:870-905).
On TPU the execution unit is much coarser: the planner below
(:mod:`.planner`, on the event algebra of :mod:`.events` and the spy
capture of :mod:`.capture`) contracts a tape into dense window blocks,
diagonal blocks and fused single-HBM-pass kernel runs. This module is what
a plan stands on at run time:

* :func:`as_tape` lowers a plan to Circuit tape entries and
  :func:`plan_from_tape` decodes them again; the appliers those entries
  name (:func:`_apply_pallas_run`, :func:`_apply_frame_swap`,
  :func:`_apply_dense_block`, :func:`_apply_deferred_block`) live here;
* :func:`_route` decides how one :class:`~.planner.PallasRun` executes on
  one register (the fused kernel whole, on double-float planes, per shard
  under ``shard_map``, or the one gate-by-gate exit, with its reason), and
  the executors run what it decided, relabelings folded into the kernel's
  DMA or stated per shard around one all-to-all;
* a block with deferred factors (an entry recorded with Params) has its
  operator composed inside the program (:func:`_compose_dense`).

Blocks that remain diagonal are emitted through the broadcast-multiply
diagonal kernel (no matmul, one VPU pass) instead of a dense GEMM.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from . import channels
from . import gates as G
from . import precision
from . import telemetry
from .capture import _event_traced, capture
from .environment import AMP_AXIS, active_pallas_mesh
from .events import GateEvent, _event_diag, event_matrix
from .ops import apply as K
from .ops import cplx
from .ops import diagonal as D
from .ops.density import _acc_kraus_term
from .ops.pallas_df import DF_SUBLANES, df_join, df_split
from .parallel import scheduler as _dist
from .parallel.mesh import device_groups
from .params import Param, lift_tape, materialize_entry
# ``plan`` is the planner's; it is named here for the yardstick alone
# (benchmark/drivers/library_density_large.py calls ``fusion.plan``), until
# a ``benchmark`` PR re-points that driver (ROADMAP C1). Everything else
# reads ``planner.plan``.
from .planner import plan  # noqa: F401
from .planner import (_CHANNEL_OPS, DeferredBlock, DiagBlock, FrameSwap,
                      FusedBlock, FusePlan, PallasRun, _df_chunks, _df_route,
                      _fold_width, transpose_stats)
from .resilience import guard as _guard

#: why every ``ops.pallas_gates`` import below stays inside its function:
#: that module's import is ``jax.experimental.pallas``'s, about a second
#: that a process which runs no kernel (a served ansatz) does not pay.


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



def _amp_shards(qureg) -> int:
    """Devices the register's amplitudes are split over, as a replay sees
    them: the explicit scheduler's mesh, inside a trace the ambient mesh
    (the tracer hides its sharding; Circuit.run derived the mesh from the
    register), else the concrete array's own sharding."""
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
    from .ops import pallas_gates as PG  # lazy: Pallas

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

    from .ops import pallas_gates as PG  # lazy: Pallas

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
    if not df and precision._mosaic_supports(qureg.dtype):
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
    from .ops.pallas_gates import LANE_BITS  # lazy: Pallas

    if run.own_tile:
        return min(sublanes, 1 << (run.tile_bits - LANE_BITS))
    return sublanes


def _canonical_amps_mesh(qureg):
    """The 1-D amps mesh of the register's concrete canonical sharding
    (NamedSharding over P(None, AMP_AXIS)), or None."""
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
    from .ops import pallas_gates as PG  # lazy: Pallas

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
        sublanes = DF_SUBLANES
    else:
        if not precision._mosaic_supports(qureg.dtype):
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
    density plans carry explicit conj-shadow twins (planner._shadow_pop),
    so no path here re-derives shadows.

    A relabeling the route did not fold runs as an explicit
    swap_bit_blocks pass before / after the kernel -- identical
    semantics. The kernel attempt goes through the resilience guard:
    injected transients retry it (every attempt re-reads ``qureg.amps``
    and is idempotent until its result is put), and a compile fault or an
    exhausted budget degrades to the gate-by-gate exit
    (``fault_degraded``, counted by the guard), the swaps that would have
    ridden the kernel explicit beside it."""
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
    from .ops.pallas_gates import swap_bit_blocks  # lazy: Pallas

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

    from .ops.pallas_gates import LANE_BITS  # lazy: Pallas

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

    from .ops import pallas_gates as PG  # lazy: Pallas

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

    from .ops import pallas_gates as PG  # lazy: Pallas

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



def _df_split(amps64):
    """``pallas_df.df_split`` around a fused run, counted once a trace
    (``fusion_df_conversions_total{dir=split}``): a pass over the f64
    state and its planes that the plan's kernels do not state."""
    telemetry.inc("fusion_df_conversions_total", dir="split")
    return df_split(amps64)


def _df_join(planes, count: bool = True):
    """``pallas_df.df_join`` around a fused run, counted as
    :func:`_df_split` (``dir=join``) unless it takes the place of a join
    already counted (:func:`_df_local_run`)."""
    if count:
        telemetry.inc("fusion_df_conversions_total", dir="join")
    return df_join(planes)


def _per_shard(fn, mesh):
    """``fn`` over each device's shard of a (planes, amplitudes) array."""

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
    from .ops.pallas_gates import HashableMatrix  # lazy: Pallas

    d2 = 4 ** num_targets
    ks = (channels.depolarising_kraus if num_targets == 1
          else channels.two_qubit_depolarising_kraus)(lam * (d2 - 1) / d2)
    return [(1.0, HashableMatrix(np.asarray(k, dtype=complex))) for k in ks]


def _pallas_usable(qureg) -> bool:

    sharding = getattr(qureg.amps, "sharding", None)
    if sharding is not None and len(sharding.device_set) > 1:
        return False
    return (jax.default_backend() == "tpu"
            and precision._mosaic_supports(qureg.dtype))


def _apply_dense_block(qureg, U: np.ndarray, qubits: tuple) -> None:
    """Dense window block dispatch: a folded lane_u pass (the Pallas MXU
    dot on the lane axis) for a window under the lane boundary (hi < 7)
    when the register is single-device on TPU, the ordinary engine
    otherwise (CPU, sharded, every window that reaches a sublane qubit).

    Measured per-block at 2^26 amps f32, loop-inside-jit (tools/microbench):
    elementwise floor 3.0 ms, lane_u pallas 4.0 ms, XLA einsum of a 5q
    window on the hi qubits 32 ms standalone. The einsum engine keeps the
    hi windows all the same: inside one program XLA fuses the einsum with
    neighbouring diagonal/elementwise work, while a pallas_call is an
    opaque barrier; the real win is eliminating standalone blocks entirely
    (two-frame Pallas scheduling, see planner.plan())."""
    from .ops import pallas_gates as PG  # lazy: Pallas

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



def _lift_positions(args) -> dict:
    """params.lift_tape's view of a deferred block entry: the values
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
