"""Plan verifier: prove FusePlan frame / scheduler-journal invariants.

Two symbolic replays, both zero-device:

**Frames** (:func:`check_plan`): a ``FusePlan`` interleaves PallasRuns
(ops pre-relabeled into PHYSICAL coordinates), folded load/store frame
swaps, standalone ``FrameSwap`` transposes, and non-Pallas items that
require the identity frame (the planner's contract -- see the FrameSwap
docstring in :mod:`..planner`). The checker composes every bit-block swap
over an explicit position permutation and proves

- every dense kernel-op target lands below ``tile_bits`` in its run's
  frame (QT101) with no control/target aliasing (QT105),
- every folded swap's geometry fits the kernel's sublane/grid blocks
  (QT106, the static twin of ``_fused_local_run``'s runtime ValueError),
- the composed permutation returns to identity before any non-Pallas
  item and at plan end (QT102),
- every segment-program stamp (``item.seg``, round 13:
  :func:`quest_tpu.segments.stamp_plan`) equals the independently
  re-derived frame-identity segment index, in FusePlan order (QT107) --
  so each emitted single-dispatch segment provably starts and ends at
  frame identity; items no planner stamped skip the check,
- each run's DMA-ring operating point is hazard-free and in budget
  (delegated to :mod:`.ringcheck`).

**Comm schedule** (:func:`check_schedule`): the explicit scheduler
journals every communication decision (``DistributedScheduler.journal``:
pair exchanges, dist swaps, rank/grouped permutes, virtual swaps,
reconcile chains and collectives). The checker re-prices each record
from first principles (:func:`.._swap_price`,
:func:`..parallel.exchange.permute_collective_stats`,
``plane_unit_scale`` -- the df 2x rule) and replays the layout shadow,
proving the deferred relocations and ``dist_permute_bits`` batches
compose back to the tracked permutation at every ``reconcile`` (QT104)
and that the recomputed chunk-unit totals equal the ``plan_circuit``
stats per kind (QT103) -- a model-vs-plan gate.
"""

from __future__ import annotations

from typing import Optional

from .diagnostics import Finding, make_finding
from .ringcheck import check_ring

__all__ = ["swap_position", "check_plan", "check_tape",
           "check_schedule", "check_circuit_comm"]

#: float tolerance for chunk-unit total comparisons
_TOL = 1e-6


def swap_position(p: int, tile_bits: int, k: int, hi: Optional[int]) -> int:
    """Where physical position ``p`` lands under the k-bit block swap of
    sublane block [tile_bits-k, tile_bits) with grid block [hi, hi+k)
    (hi = None means tile_bits) -- the single position map every frame
    event in a plan composes through."""
    h = tile_bits if hi is None else hi
    lo = tile_bits - k
    if lo <= p < tile_bits:
        return p - lo + h
    if h <= p < h + k:
        return p - h + lo
    return p


def _op_overlap_findings(op: tuple, where: str) -> list[Finding]:
    """QT105: control/target aliasing inside one lowered kernel op."""
    findings: list[Finding] = []

    def bad(msg: str) -> None:
        findings.append(make_finding("QT105", msg, where))

    kind = op[0]
    if kind == "matrix":
        t, controls = op[1], op[2]
        if t in controls:
            bad(f"matrix target {t} is also a control")
    elif kind == "swap":
        q1, q2, controls = op[1], op[2], op[3]
        if q1 == q2:
            bad(f"swap targets alias (both {q1})")
        for q in (q1, q2):
            if q in controls:
                bad(f"swap target {q} is also a control")
    elif kind in ("parity", "diagw"):
        targets, controls = tuple(op[1]), tuple(op[2])
        if len(set(targets)) != len(targets):
            bad(f"{kind} repeats a target in {targets}")
        overlap = set(targets) & set(controls)
        if overlap:
            bad(f"{kind} targets {sorted(overlap)} are also controls")
    # kraus1/kraus2/krausn/depol/lane_u/window: target disjointness is
    # structural in their tuple layouts (validated at lowering)
    return findings


def check_plan(plan, nsv: int, *, dtype=None,
               shard_qubits: Optional[int] = None,
               check_rings: bool = True,
               location: str = "plan") -> list[Finding]:
    """Symbolically replay ``plan`` over ``nsv`` state-vector qubits; see
    the module docstring for the proven invariant set. ``dtype`` selects
    the ring geometry (planar f32/f64 or, when the double-float route is
    enabled, the 4-plane f32 layout). ``shard_qubits`` (shard-LOCAL
    qubit count of a sharded plan) bounds each run's DMA-ring grid to
    what one shard's kernel actually sweeps; frames are always verified
    over the full ``nsv`` space (grid blocks may reach sharded
    qubits)."""
    import numpy as np

    from ..planner import DiagBlock, FrameSwap, FusedBlock, PallasRun
    from ..ops.pallas_gates import (LANE_BITS, _LANES, op_dense_targets,
                                    ring_depth_default)

    findings: list[Finding] = []
    perm = list(range(nsv))  # physical position -> original position
    identity = list(range(nsv))

    dt = np.dtype(dtype) if dtype is not None else None
    df = False
    if dt is not None and dt == np.float64:
        from ..ops.pallas_df import df_wanted
        df = df_wanted()

    def apply_swap_event(tile_bits: int, k: int, hi: Optional[int],
                         where: str) -> None:
        nonlocal perm
        h = tile_bits if hi is None else hi
        if (k > tile_bits - LANE_BITS or h < tile_bits
                or h + k > nsv or k < 0):
            findings.append(make_finding(
                "QT106",
                f"block swap k={k}, hi={h} illegal for tile_bits="
                f"{tile_bits}, n={nsv} (sublane block has "
                f"{tile_bits - LANE_BITS} bits)", where))
            return
        if k == 0:
            return
        perm = [swap_position(perm[p], tile_bits, k, hi)
                for p in range(nsv)]

    # QT107: re-derive the frame-identity segment index independently of
    # the stamps (segments.stamp_plan's rule: the index advances at every
    # return to identity) and cross-check each stamped item
    seg_expect = 0

    def check_seg(item, where: str) -> None:
        if item.seg is None:
            return  # an item no planner stamped
        if item.seg != seg_expect:
            findings.append(make_finding(
                "QT107",
                f"item stamped seg={item.seg} but the frame-identity "
                f"replay puts it in segment {seg_expect}: the emitted "
                f"segment program would not start/end at identity or "
                f"the plan order was shuffled", where))

    for i, item in enumerate(plan.items):
        where = f"{location}.items[{i}]"
        if isinstance(item, PallasRun):
            check_seg(item, where)
            apply_swap_event(item.tile_bits, item.load_swap_k,
                             item.load_swap_hi, where + ".load_swap")
            for j, op in enumerate(item.ops):
                opw = f"{where}.ops[{j}]:{op[0]}"
                for t in op_dense_targets(op):
                    if not (0 <= t < item.tile_bits):
                        findings.append(make_finding(
                            "QT101",
                            f"dense target {t} outside the physical tile "
                            f"[0, {item.tile_bits}) in this run's frame",
                            opw))
                findings.extend(_op_overlap_findings(op, opw))
            apply_swap_event(item.tile_bits, item.store_swap_k,
                             item.store_swap_hi, where + ".store_swap")
            if check_rings:
                kernel_n = nsv if shard_qubits is None else shard_qubits
                grid = 1 << max(kernel_n - item.tile_bits, 0)
                if grid > 1:
                    planes = 4 if df else 2
                    itemsize = 4 if df or dt is None else dt.itemsize
                    s = 1 << (item.tile_bits - LANE_BITS)
                    depth = (item.ring_depth if item.ring_depth is not None
                             else ring_depth_default())
                    findings.extend(check_ring(
                        grid, depth, planes * s * _LANES * itemsize,
                        location=where + ".ring"))
        elif isinstance(item, FrameSwap):
            check_seg(item, where)
            apply_swap_event(item.tile_bits, item.k, item.hi, where)
        elif isinstance(item, (FusedBlock, DiagBlock)) or \
                isinstance(item, tuple):
            if perm != identity:
                moved = [p for p in range(nsv) if perm[p] != p]
                findings.append(make_finding(
                    "QT102",
                    f"non-Pallas item reached with a live frame "
                    f"(positions {moved[:8]} displaced)", where))
                perm = list(identity)  # report once, keep checking
        if perm == identity:
            seg_expect += 1
    if perm != identity:
        moved = [p for p in range(nsv) if perm[p] != p]
        findings.append(make_finding(
            "QT102",
            f"plan ends with a live frame (positions {moved[:8]} "
            f"displaced); the planner must restore identity",
            f"{location}.end"))
    return findings


def check_tape(tape, nsv: int, **kwargs) -> list[Finding]:
    """:func:`check_plan` over a ``Circuit`` tape (the executed form):
    decode it back to a FusePlan via :func:`..fusion.plan_from_tape`."""
    from ..fusion import plan_from_tape

    return check_plan(plan_from_tape(tape), nsv, **kwargs)


def check_schedule(journal: list, stats: dict, n: int, mesh, *,
                   num_slices: int = 1,
                   location: str = "schedule") -> list[Finding]:
    """Re-price and layout-replay a scheduler journal against its
    ``plan_circuit`` stats (see the module docstring). ``journal`` is the
    record list a :class:`..parallel.scheduler.DistributedScheduler`
    collects when its ``journal`` attribute is set.

    Round 15 (two-tier model): ``num_slices`` reproduces the scheduler's
    ICI/DCN shard-bit split, and the replay additionally re-derives the
    per-``(kind, link)`` chunk-unit cells from the records alone (the
    same even-split attribution the scheduler's accounting uses),
    proving ``stats["chunks_by_kind_link"]`` against the journal, and
    counts how often each DCN shard bit moves inside one reconciliation
    chain -- more than once means the chain decomposition crossed the
    slow link redundantly where the path decomposition would not
    (QT108)."""
    from ..parallel import exchange as X
    from ..parallel.mesh import local_qubit_count, shard_bit_link
    from ..parallel.scheduler import _swap_price

    findings: list[Finding] = []
    nl = local_qubit_count(n, mesh)
    pos = list(range(n))   # logical -> physical shadow
    occ = list(range(n))   # physical -> logical shadow

    def shadow_swap(a: int, b: int) -> None:
        la, lb = occ[a], occ[b]
        occ[a], occ[b] = lb, la
        pos[la], pos[lb] = b, a

    totals = {"pair_exchanges": 0, "rank_permutes": 0,
              "relocation_swaps": 0, "virtual_swaps": 0,
              "reconcile_chunks": 0.0, "relocation_batch_chunks": 0.0,
              "frame_transpose_chunks": 0.0}
    cells: dict[str, float] = {}  # re-derived chunks_by_kind_link

    def count_cell(kind: str, qubit: int, chunks: float) -> None:
        link = shard_bit_link(n, mesh, num_slices, qubit)
        cell = f"{kind}/{link or 'local'}"
        cells[cell] = cells.get(cell, 0.0) + chunks

    def count_permute_cells(rn, source, scale, kind) -> None:
        # mirror the scheduler's even-split attribution: the grouped
        # all-to-all's volume over the crossing bits, the relabel
        # ppermute's 2 units over the relabeled bits
        cross = [q for q in range(nl, rn) if source[q] < nl]
        if cross:
            share = 2.0 * (1.0 - 0.5 ** len(cross)) * scale / len(cross)
            for q in cross:
                count_cell(kind, q, share)
        moved = [q for q in range(nl, rn)
                 if source[q] >= nl and source[q] != q]
        if moved:
            for q in moved:
                count_cell(kind, q, 2.0 * scale / len(moved))

    # QT108: DCN shard-bit touch count inside the CURRENT reconciliation
    # chain (reconcile_swap records up to the next reconcile_done)
    recon_dcn_touch: dict[int, int] = {}

    for idx, rec in enumerate(journal):
        where = f"{location}[{idx}]:{rec[0]}"
        kind = rec[0]
        if kind == "comm_pipeline":
            # the pipeline-depth stamp: a valid depth prices at ZERO
            # chunk-units -- the depth-invariance proof the re-priced
            # totals below then complete (any depth, same model) -- and
            # its transfer/compute interleaving must simulate hazard-free
            # (commcheck QT207/QT208). Round 15: a two-slice schedule
            # stamps (base, dcn) -- both depths must verify; pre-round-15
            # journals carry the 2-tuple form
            for depth in rec[1:]:
                if not isinstance(depth, int) or depth < 1:
                    findings.append(make_finding(
                        "QT103", f"comm_pipeline stamp {depth!r} is not "
                                 f"a depth >= 1", where))
                else:
                    from .commcheck import check_comm_pipeline
                    findings.extend(check_comm_pipeline(
                        depth, 1 << nl, location=where))
        elif kind == "pair_exchange":
            _, rn, q = rec
            count_cell("pair_exchange", q, 2.0)
            totals["pair_exchanges"] += 1
        elif kind == "rank_permute":
            _, rn, q = rec
            if q < nl:
                findings.append(make_finding(
                    "QT103", f"rank permute on local position {q} "
                             f"(< {nl}) would be free, not 2 units",
                    where))
            count_cell("grouped_permute", q, 2.0)
            totals["rank_permutes"] += 1
        elif kind == "dist_swap":
            _, rn, a, b, tracked = rec
            price = _swap_price(a, b, nl)
            if abs(price - 1.0) > _TOL:
                findings.append(make_finding(
                    "QT103",
                    f"dist_swap({a},{b}) priced {price} chunk-units; "
                    f"the relocation path budgets exactly 1.0 "
                    f"(one local, one sharded position)", where))
            count_cell("dist_swap", max(a, b), 1.0)
            totals["relocation_swaps"] += 1
            if tracked:
                shadow_swap(a, b)
        elif kind == "virtual_swap":
            _, p1, p2 = rec
            totals["virtual_swaps"] += 1
            shadow_swap(p1, p2)
        elif kind == "staged_relay":
            # zero-cost marker: the next three dist_swap/reconcile_swap
            # records are one ICI-relayed cross-slice exchange; the swaps
            # themselves carry the pricing
            _, rn, a, b, r = rec
            if not (shard_bit_link(n, mesh, num_slices, max(a, b)) ==
                    "dcn" and r < nl):
                findings.append(make_finding(
                    "QT103",
                    f"staged_relay({a},{b} via {r}) does not stage a "
                    f"DCN-crossing swap through a local relay slot",
                    where))
        elif kind == "reconcile_swap":
            _, rn, a, b = rec
            price = _swap_price(a, b, nl)
            if price:
                count_cell("reconciliation", max(a, b), price)
            totals["reconcile_chunks"] += price
            for q in (a, b):
                if shard_bit_link(n, mesh, num_slices, q) == "dcn":
                    recon_dcn_touch[q] = recon_dcn_touch.get(q, 0) + 1
            shadow_swap(a, b)
        elif kind == "permute":
            _, rn, source, scale, pkind = rec
            cstats = X.permute_collective_stats(rn, tuple(source), mesh)
            units = cstats["chunk_units"] * float(scale)
            if pkind == "reconciliation":
                totals["reconcile_chunks"] += units
                count_permute_cells(rn, source, float(scale), pkind)
                if tuple(pos) != tuple(source):
                    findings.append(make_finding(
                        "QT104",
                        f"reconcile collective permutes by {source} but "
                        f"the tracked layout is {tuple(pos)}: the "
                        f"deferred schedule diverged", where))
                pos = list(range(rn))
                occ = list(range(rn))
            elif pkind == "relocation_batch":
                totals["relocation_batch_chunks"] += units
                # even split over the batch's sharded positions (every
                # pair swaps one sharded with one local slot)
                touched = [q for q in range(nl, rn) if source[q] != q]
                for q in touched:
                    count_cell(pkind, q, units / len(touched))
                for a in range(rn):
                    b = source[a]
                    if a < b:
                        shadow_swap(a, b)
            elif pkind == "frame_transpose":
                # frame transposes permute amplitudes without touching
                # the scheduler's logical layout (the pallas plan itself
                # carries the frame); only the pricing is checked
                totals["frame_transpose_chunks"] += units
                count_permute_cells(rn, source, float(scale), pkind)
            else:
                findings.append(make_finding(
                    "QT103", f"unknown permute kind {pkind!r}", where))
        elif kind == "segment":
            # round 13: zero-cost marker -- a sliced segment-program
            # replay opened a defer span at tape cursor rec[1]. Segments
            # cut at frame-identity points, so the tracked layout must be
            # identity when a new span opens (QT104 otherwise: a prior
            # span leaked an unreconciled layout across the segment seam)
            _, cursor = rec
            if not isinstance(cursor, int) or cursor < 0:
                findings.append(make_finding(
                    "QT107", f"segment marker cursor {cursor!r} is not a "
                             f"tape index >= 0", where))
            if pos != list(range(n)):
                moved = [q for q in range(n) if pos[q] != q]
                findings.append(make_finding(
                    "QT104",
                    f"segment span opens at cursor {cursor} with logical "
                    f"qubits {moved[:8]} displaced: the previous span "
                    f"did not reconcile", where))
        elif kind == "reconcile_done":
            for q, cnt in sorted(recon_dcn_touch.items()):
                if cnt > 1:
                    findings.append(make_finding(
                        "QT108",
                        f"DCN shard bit {q} moved {cnt} times inside one "
                        f"reconciliation chain: the cycle decomposition "
                        f"crossed the inter-slice link redundantly "
                        f"(hierarchical=True path-decomposes each cycle "
                        f"to touch the DCN bit once)", where))
            recon_dcn_touch = {}
            if pos != list(range(n)):
                moved = [q for q in range(n) if pos[q] != q]
                findings.append(make_finding(
                    "QT104",
                    f"reconcile completed but the replayed layout is "
                    f"not identity (logical qubits {moved[:8]} "
                    f"displaced): a relocation/virtual swap was dropped "
                    f"or double-counted", where))
                pos = list(range(n))
                occ = list(range(n))
        else:
            findings.append(make_finding(
                "QT103", f"unknown journal record kind {kind!r}", where))

    # a journal that ends mid-reconciliation (truncated or malformed)
    # must not silently discard the accumulated DCN touch counts: flag
    # the unterminated chain and run the same QT108 emission over the
    # leftovers that reconcile_done would have
    if recon_dcn_touch:
        findings.append(make_finding(
            "QT103",
            f"journal ends inside a reconciliation chain (DCN shard "
            f"bits {sorted(recon_dcn_touch)} touched with no "
            f"terminating reconcile_done record)", f"{location}.end"))
        for q, cnt in sorted(recon_dcn_touch.items()):
            if cnt > 1:
                findings.append(make_finding(
                    "QT108",
                    f"DCN shard bit {q} moved {cnt} times inside one "
                    f"reconciliation chain: the cycle decomposition "
                    f"crossed the inter-slice link redundantly "
                    f"(hierarchical=True path-decomposes each cycle "
                    f"to touch the DCN bit once)", f"{location}.end"))

    for key in ("pair_exchanges", "rank_permutes", "relocation_swaps",
                "virtual_swaps"):
        if totals[key] != stats.get(key, 0):
            findings.append(make_finding(
                "QT103",
                f"journal replays {totals[key]} {key} but the plan "
                f"stats claim {stats.get(key, 0)}",
                f"{location}.totals"))
    for key in ("reconcile_chunks", "relocation_batch_chunks",
                "frame_transpose_chunks"):
        if abs(totals[key] - float(stats.get(key, 0.0))) > _TOL:
            findings.append(make_finding(
                "QT103",
                f"recomputed {key} = {totals[key]:.6g} chunk-units but "
                f"the plan stats claim {float(stats.get(key, 0.0)):.6g}",
                f"{location}.totals"))
    claimed = stats.get("chunks_by_kind_link")
    if claimed is not None:
        for cell in sorted(set(cells) | set(claimed)):
            got, want = cells.get(cell, 0.0), float(claimed.get(cell, 0.0))
            if abs(got - want) > _TOL:
                findings.append(make_finding(
                    "QT103",
                    f"re-derived chunk-unit cell {cell} = {got:.6g} but "
                    f"the plan stats claim {want:.6g}: the two-tier "
                    f"(kind, link) attribution diverged from the "
                    f"journal", f"{location}.totals"))
    if pos != list(range(n)):
        moved = [q for q in range(n) if pos[q] != q]
        findings.append(make_finding(
            "QT104",
            f"schedule ends with logical qubits {moved[:8]} displaced "
            f"and no reconcile", f"{location}.end"))
    return findings


def check_circuit_comm(circuit, mesh, *, num_slices: int = 1,
                       dtype=None, defer: bool = True,
                       collective_reconcile: bool = True,
                       batch_relocations: bool = True,
                       comm_pipeline: int | None = None,
                       hierarchical: bool = False,
                       comm_pipeline_dcn: int | None = None,
                       location: str = "plan_circuit"):
    """Plan ``circuit`` abstractly (zero devices) with journaling on and
    verify the journal against the returned stats (``comm_pipeline``
    stamps the depth into the journal; the re-priced totals prove the
    model is depth-invariant). ``hierarchical``/``comm_pipeline_dcn``/
    ``num_slices`` select the two-tier route (round 15); the journal is
    then additionally checked under the per-(kind, link) attribution and
    the QT108 once-per-reconcile DCN rule. Returns
    ``(findings, stats, journal)``."""
    from ..parallel.scheduler import plan_circuit

    journal: list = []
    stats = plan_circuit(circuit, mesh, num_slices=num_slices,
                         defer=defer,
                         collective_reconcile=collective_reconcile,
                         batch_relocations=batch_relocations,
                         dtype=dtype, journal=journal,
                         comm_pipeline=comm_pipeline,
                         hierarchical=hierarchical,
                         comm_pipeline_dcn=comm_pipeline_dcn)
    n = (2 if circuit.is_density_matrix else 1) * circuit.num_qubits
    findings = check_schedule(journal, stats, n, mesh,
                              num_slices=num_slices, location=location)
    return findings, stats, journal
