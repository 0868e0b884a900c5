"""Pallas TPU kernel: a fused run of gates in ONE pass over HBM.

The hot loop of a state-vector simulator is "stream 2^n amplitudes through
an update rule". XLA's GEMM formulation (ops.apply) pays one full HBM
round-trip per fused block; this kernel applies an arbitrarily long run of
single-qubit matrices, controlled gates, and parity phases in a single
read+write of the state: each grid program pulls a tile of S rows of 128
lanes from each plane (re, im) into VMEM, applies every gate of the run
in-register, and writes the tile back. The reference's analogous hot loops
are one kernel launch per gate (statevec_compactUnitaryLocal,
QuEST_cpu.c:1682-1739; CUDA variant QuEST_gpu.cu:492-554) -- fusing the run
is pure TPU-side gain, the same bandwidth argument as the dense-fusion layer
(quest_tpu/fusion.py) taken to its limit for the 1-qubit-dominated parts of
a circuit.

Geometry: the flat amplitude index is split (grid, sublane, lane) =
(i >> (7+log2 S), (i >> 7) & (S-1), i & 127). The PLANE index (re / im;
four planes in the double-float layout) is the LOWEST row bit of what the
kernels address: they take the (P, 2^n) register as the row-interleaved
(rows * P, 128) array, row r * P + p = row r of plane p (_rows_view), and
read plane p of a block with a sublane-strided slice (_plane_rows). That is
where the register already lies: the TPU compiler tiles a (P, N) f32 array
T(P,128), which is byte for byte the interleaved array under T(8,128), so
XLA compiles the view to a bitcast -- the plane-major (P, rows, 128) view
cost a relayout copy of the whole state into a program's first kernel and
another out of its last (PR 34). The ops below never see it: they receive
and return (S, 128) planes. A gate on qubit q pairs amplitude i with
i ^ 2^q:

- q < 7 (lane bits): partner = two pltpu.rolls along the lane axis,
  selected per element by bit q of the lane index -- a VPU permute.
- 7 <= q < 7+log2 S (sublane bits): same along the sublane axis.
- q >= 7+log2 S (grid bits): only *diagonal* roles are supported (control
  qubits, parity-phase members): their bit is a per-program scalar from
  pl.program_id. Gate TARGETS on grid bits need cross-tile data and are
  the caller's job to route elsewhere (ops.apply window GEMMs).

Ops format (all matrix data static at trace time, baked into the kernel):

    ("matrix", q, controls, states, M)   M: 2x2 complex ndarray; q local,
                                         OR any qubit if M is diagonal
                                         (grid-bit diagonals need only a
                                         per-program scalar select)
    ("parity", qubits, controls, theta)  exp(-i theta/2 Z...Z), any qubits
    ("swap", q1, q2, controls, states)   SWAP(q1, q2); both targets local
    ("diagw", targets, controls, D)      D: (2^t,) complex diagonal over
                                         ``targets`` (any qubits; grid
                                         members enter the table index as
                                         per-program scalars)
    ("lane_u", W)                        W: (3, 128, 128) real stack
                                         (Ur^T, Ui^T, Ur^T+Ui^T) -- a
                                         folded run of lane-qubit gates as
                                         THREE Karatsuba MXU dots
    ("window", lo, span, W)              W: (2*2^span)^2 real block matrix
                                         [[Ur,-Ui],[Ui,Ur]] -- a folded run
                                         of gates confined to the sublane
                                         window [lo, lo+span), applied as
                                         per-slab W @ y MXU dots

Before the kernel is built, _fold_zone_ops contracts gates into dense
per-zone unitaries: the tile's qubits split into the lane zone [0, 7) and
successive 5-qubit sublane zones, and each zone accumulates the (not
necessarily consecutive) gates fully contained in it -- open zones commute
because they touch disjoint qubits -- until a cross-zone op forces a
flush. Folded zones run on the MXU instead of per-gate butterfly rolls
(VPU): the same dense-fusion economics as quest_tpu/fusion.py, one level
down.
"""

from __future__ import annotations

import functools
import math
import os
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry

LANE_BITS = 7          # minor dim fixed at 128 lanes
_LANES = 1 << LANE_BITS
#: sublanes of one f32 vreg, the (8, 128) tile: a sublane bit below
#: LANE_BITS + log2(_VREG_ROWS) pairs rows INSIDE a vreg (_partner)
_VREG_ROWS = 8
#: (2, 4096, 128) f32 tile = 4 MiB. Round-4 re-sweep of the manual-DMA
#: kernel's chunk size at 2^26 amps (tools/kernelprobe, min-of-3): the
#: per-PASS floor is per-chunk-overhead-bound at the old S=2048 default
#: (256 chunks, 11.2 ms) and drops to ~7.7 ms at S=4096; S=8192 is flat
#: within noise (7.5) but its 32 MiB of double-buffers plus op
#: temporaries overflow the 100 MiB Mosaic VMEM stack on op-heavy runs
#: (measured OOM at 24 mixed ops). S=4096 also raises local_qubits by
#: one over round 3 -- more in-tile targets per fused run.
_DEF_SUBLANES = 1 << 12

#: default in-flight DMA ring depth for the manual chunk pipeline
#: (_make_dma_kernel). 2 = the classic double buffer; 3 adds one spare
#: slot so a chunk whose bf16x3 zone dots finish before its store drains
#: does not stall the sweep on the store-wait (the round-5 verdict's
#: per-pass-stall finding). 3 is the widest depth whose ring buffers
#: (2 * ring * 4 MiB at the S=4096 f32 tile) stay within _RING_VMEM_BUDGET
#: alongside the op temporaries of the bench's longest fused runs -- the
#: operating point committed from the tools/kernelprobe --ring sweep
#: (re-sweep it on-chip when S or the op mix changes).
_DEF_RING_DEPTH = 3

#: env override for the ring depth: sweepable without code edits
#: (acceptance: ISSUE 2 tentpole). The fused_local_run ``ring_depth``
#: argument -- the plan-level knob -- outranks it.
_RING_ENV = "QUEST_PALLAS_RING"

#: VMEM the ring's in+out tile buffers may claim. The Mosaic scoped-VMEM
#: limit is raised to 100 MiB for these kernels; holding the ring to
#: slightly under half keeps room for the per-op temporaries that made
#: S=8192 double-buffers OOM at 24 mixed ops (round-4 probe). Depths that
#: exceed it derate one slot at a time rather than failing to compile.
_RING_VMEM_BUDGET = 48 * 1024 * 1024


#: raw QUEST_PALLAS_RING values already diagnosed (QT205 warns once per
#: distinct value, not once per kernel launch)
_RING_ENV_WARNED: set = set()


def ring_depth_default() -> int:
    """The process-wide DMA ring depth: QUEST_PALLAS_RING if set (min 2),
    else _DEF_RING_DEPTH. Malformed or sub-minimum values are coerced as
    before, but leave a QT205 diagnostic (warn-once telemetry record
    stating the clamped value) instead of being swallowed silently --
    the shared env-int parser (analysis.diagnostics.parse_env_int, also
    behind QUEST_COMM_PIPELINE's QT206)."""
    # deliberate late import: diagnostics depends only on telemetry, so
    # this cannot cycle back into the ops layer
    from ..analysis.diagnostics import parse_env_int

    return parse_env_int(_RING_ENV, _DEF_RING_DEPTH, minimum=2,
                         code="QT205", noun="ring depth",
                         below="is below the 2-slot ring minimum",
                         warned=_RING_ENV_WARNED)


def effective_ring_depth(ring_depth: int, nchunks: int, slot_bytes: int,
                         budget: int = _RING_VMEM_BUDGET) -> int:
    """The ring depth a grid kernel actually runs: the requested depth
    clamped to [2, nchunks], then derated one slot at a time while the
    in+out ring buffers (2 * ring * slot_bytes) overflow ``budget``.
    The ONE clamp shared by the kernel caller (_fused_local_run) and the
    static ring checker (analysis.ringcheck), so the checker verifies
    the operating point the kernel really uses."""
    ring = max(2, min(int(ring_depth), int(nchunks)))
    while ring > 2 and 2 * ring * slot_bytes > budget:
        ring -= 1
    return ring


#: matmul precision for the in-kernel zone dots (lane_u / window). Mosaic
#: lowers only DEFAULT and HIGHEST (Precision.HIGH raises
#: NotImplementedError, probed round 3); HIGHEST keeps the 26q depth-8
#: norm drift at ~1.4e-5 after 7 circuits vs DEFAULT's ~8e-5 per circuit
#: (round-3/4 chip measurements). f32 tiles take the manual bf16x3 route
#: below instead; this setting remains for the f64-interpreter path.
_DOT_PRECISION = jax.lax.Precision.HIGHEST


def _split_bf16(w: np.ndarray):
    """Host-side hi/lo bf16 decomposition of an f32 operand matrix:
    w ~= hi + lo with hi = bf16(w) and lo = bf16(w - hi). Stacked on a new
    leading axis so the pair ships as ONE kernel operand."""
    import ml_dtypes

    hi = w.astype(ml_dtypes.bfloat16)
    lo = (w - hi.astype(np.float32)).astype(ml_dtypes.bfloat16)
    return np.stack([hi, lo])


def _dot_bf16x3(x, w_pair, dtype):
    """x @ W at ~f32 accuracy from THREE DEFAULT-precision bf16 MXU passes.

    Mosaic's HIGHEST lowers an f32 dot to SIX bf16 passes (full 3x3 hi/lo
    cross terms); the manual split keeps the three leading terms
    (hi*hi + hi*lo + lo*hi), whose dropped lo*lo term is O(2^-16) relative
    -- measured norm drift ~1e-6/circuit on the 26q depth-8 bench vs
    HIGHEST's 1.4e-5/7-circuits budget (round-4 chip measurement).
    Halves the MXU time of every zone dot: the lane dots are the
    serialized compute that bounds the 26q bench (round-3 floor
    analysis). ``w_pair`` = (2, ...) stacked bf16 hi/lo from _split_bf16."""
    xh = x.astype(jnp.bfloat16)
    xl = (x - xh.astype(dtype)).astype(jnp.bfloat16)
    wh, wl = w_pair[0], w_pair[1]
    acc = jnp.dot(xh, wh, preferred_element_type=dtype)
    acc += jnp.dot(xh, wl, preferred_element_type=dtype)
    acc += jnp.dot(xl, wh, preferred_element_type=dtype)
    return acc


def _dot_bf16x3_rev(w_pair, y, dtype):
    """W @ y variant of _dot_bf16x3 (static matrix on the LEFT)."""
    yh = y.astype(jnp.bfloat16)
    yl = (y - yh.astype(dtype)).astype(jnp.bfloat16)
    wh, wl = w_pair[0], w_pair[1]
    acc = jnp.dot(wh, yh, preferred_element_type=dtype)
    acc += jnp.dot(wl, yh, preferred_element_type=dtype)
    acc += jnp.dot(wh, yl, preferred_element_type=dtype)
    return acc


def local_qubits(n: int, sublanes: int = _DEF_SUBLANES) -> int:
    """Number of low qubits a tile holds entirely (targets must be below)."""
    rows = 1 << max(n - LANE_BITS, 0)
    s = min(sublanes, rows)
    return min(n, LANE_BITS + int(math.log2(s)) if s > 1 else LANE_BITS)


def _bit_mask(q: int, shape):
    """Bit q of the in-tile flat index as a (S, 128) {0,1} i32 array."""
    if q < LANE_BITS:
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return (lane >> q) & 1
    sub = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (sub >> (q - LANE_BITS)) & 1


def _grid_bit(q: int, tile_bits: int):
    """Bit q of the flat index when q is a grid bit: per-program scalar."""
    return (pl.program_id(0) >> (q - tile_bits)) & 1


def _partner(arr, q: int):
    """arr[i ^ 2^q] within the tile, one of three exchanges by where bit q
    lies (ms a plain complex gate at 2^26 amplitudes, S = 4096: PR 36's
    chip call 1, PERF.md section 6):

    - a lane bit (q < 7): two circular lane rotates and a select on the
      bit -- 0.50.
    - a sublane bit INSIDE a vreg (7 <= q < 10: the partner row is 1, 2 or
      4 sublanes away in the same (8, 128) tile): the same recipe one level
      up, on the plane seen as whole vregs -- 0.90, 0.90, 0.78 (q9's two
      rotates are one, by 4 of 8, and the select then costs nothing:
      0.774 with it, 0.778 without). Until PR 36 these took the slice
      exchange below, whose reshape cuts every vreg when m < 8: 3.48,
      1.71, 1.27 (Mosaic relayouts the plane in and out), and 20 / 192 s
      of compile for one / three of them on q7 where the rotates take
      2.3 s. A tile of fewer than 8 sublanes (tests only) holds no whole
      vreg and keeps the slices.
    - a sublane bit across vregs (q >= 10): split the sublane axis at the
      bit and swap the halves, whole vregs changing places -- 0.785 on
      every bit from 10 to 18. (Round 3 read ~8 ms for the same butterfly
      as pltpu.rolls along the WHOLE sublane axis; PR 36 read 1.02 for
      that on q7: still the dearest of the candidates.)"""
    if q < LANE_BITS:
        # np.int32 shifts: under jax x64 a python int would trace as i64,
        # which Mosaic's tpu.dynamic_rotate rejects (round-5 df path find)
        m = np.int32(1 << q)
        size = np.int32(arr.shape[1])
        up = pltpu.roll(arr, size - m, 1)  # up[i] = arr[i + m] (shift >= 0)
        dn = pltpu.roll(arr, m, 1)         # dn[i] = arr[i - m]
        bit = _bit_mask(q, arr.shape)
        return jnp.where(bit == 0, up, dn)
    m = 1 << (q - LANE_BITS)
    s, lanes = arr.shape
    if m < _VREG_ROWS <= s:
        # the partner lies INSIDE the (8, 128) vreg: the lane recipe one
        # level up, on the plane seen as whole vregs (a layout-free view)
        v = arr.reshape(s // _VREG_ROWS, _VREG_ROWS, lanes)
        dn = pltpu.roll(v, np.int32(m), 1)               # dn[i] = v[i - m]
        up = pltpu.roll(v, np.int32(_VREG_ROWS - m), 1)  # up[i] = v[i + m]
        bit = _bit_mask(q, arr.shape).reshape(v.shape)
        return jnp.where(bit == 0, up, dn).reshape(s, lanes)
    v = arr.reshape(s // (2 * m), 2, m, lanes)
    return jnp.stack([v[:, 1], v[:, 0]], axis=1).reshape(s, lanes)


def _ctrl_scalar_and_mask(controls, states, tile_bits, shape, gbit):
    """(per-program scalar {0,1} or None, elementwise {0,1} mask or None)
    for a control set; ``gbit(q)`` resolves bits above the tile (grid bits
    from pl.program_id, shard bits from the SMEM shard-index scalar)."""
    states = states if states else (1,) * len(controls)
    mask = None
    scalar = None
    # np.int32 literals: under jax x64 (PRECISION=2 df kernels) python
    # ints would make these i64 vectors, which Mosaic cannot lower
    one, zero = np.int32(1), np.int32(0)
    for c, st in zip(controls, states):
        if c >= tile_bits:
            b = gbit(c)
            ok = jnp.where(b == st, one, zero)
            scalar = ok if scalar is None else scalar * ok
        else:
            b = _bit_mask(c, shape)
            ok = jnp.where(b == st, one, zero)
            mask = ok if mask is None else mask * ok
    return scalar, mask


#: width (in qubits) of each sublane fold zone; D = 2^5 gives 64x64 real
#: block matrices -- small enough to replicate per program, big enough that
#: a zone absorbs most of a layer's sublane gates
_ZONE_SPAN = 5


def _op_event(op):
    """Kernel op tuple -> GateEvent (for host-side dense folding)."""
    from ..events import GateEvent

    if op[0] == "matrix":
        return GateEvent("matrix", (op[1],), tuple(op[2]), tuple(op[3]),
                         matrix=np.asarray(op[4].arr if hasattr(op[4], "arr")
                                           else op[4]))
    if op[0] == "swap":
        return GateEvent("swap", (op[1], op[2]), tuple(op[3]), tuple(op[4]))
    if op[0] == "diagw":
        return GateEvent("diag", tuple(op[1]), tuple(op[2]),
                         diag=np.asarray(op[3].arr if hasattr(op[3], "arr")
                                         else op[3]).reshape(-1))
    return GateEvent("parity", tuple(op[1]), tuple(op[2]), theta=float(op[3]))


def op_dense_targets(op) -> tuple:
    """Qubits on which ``op`` needs a DENSE (partner-exchanging) action --
    the ones that must sit below the tile/shard limit. Diagonal roles
    (controls, parity members, diagw/grid-diagonal targets) are excluded:
    they resolve per-program/per-shard. The ONE authoritative extraction
    for the legality checks in fused_local_run and
    fusion._kernel_run."""
    if op[0] == "matrix":
        m = op[4].arr if hasattr(op[4], "arr") else op[4]
        if complex(m[0][1]) == 0 and complex(m[1][0]) == 0:
            return ()
        return (op[1],)
    if op[0] in ("swap", "kraus1"):
        return (op[1], op[2])
    if op[0] == "kraus2":
        return tuple(op[1:5])
    if op[0] in ("krausn", "depol"):
        return (*op[1], *op[2])
    return ()  # parity / diagw / lane_u / window: no dense roles above tile


def _op_support(op):
    if op[0] == "matrix":
        return {op[1], *op[2]}
    if op[0] in ("swap", "kraus1"):
        return {op[1], op[2], *(op[3] if op[0] == "swap" else ())}
    if op[0] == "kraus2":
        return {op[1], op[2], op[3], op[4]}
    if op[0] in ("diagw", "parity", "krausn", "depol"):
        return {*op[1], *op[2]}
    return set(range(LANE_BITS))  # lane_u acts on the lane zone


def _op_is_diag(op):
    if op[0] in ("diagw", "parity"):
        return True
    if op[0] == "matrix":
        m = op[4].arr if hasattr(op[4], "arr") else op[4]
        return complex(m[0][1]) == 0 and complex(m[1][0]) == 0
    return False


#: estimated per-op kernel cost in ms at 2^26 amps f32 (round-4
#: kernelprobe slopes at the S=8192 default, min-of-3 methodology). Only
#: the RATIOS matter: the fold decision compares accumulated butterfly
#: cost against the zone's dense-dot cost on the same scale. The round-3
#: model had these backwards (lane butterflies cheap, dots expensive);
#: with bf16x3 dots and the 8192-row tile, a lane butterfly (two
#: cross-lane rolls + selects over the whole tile) costs MORE than the
#: whole folded lane dot, so the lane zone folds from the first dense
#: gate, while sublane butterflies stay cheaper than the per-slab
#: window dots until a zone accumulates several of them.
#: PR 36 read every entry on the v5e at S = 4096 (PERF.md section 6, chip
#: calls 1 and 3) and the readings disagree with most of them. None is put
#: in here: a re-priced model moves ops from exact f32 butterflies into
#: bf16x3 dots, a change of arithmetic that wants a before and after of
#: its own (ROADMAP A1).
_FOLD_LANE_DOT_MS = 0.47    # lane_u: 3 Karatsuba bf16x3 dot triples
_FOLD_WINDOW_DOT_MS = 0.87  # sublane window: per-slab (2D,2D) dots
#: one partner exchange and its arithmetic, by how _partner exchanges
#: (_exchange_kind). ``invreg`` is the cheapest entry and should be
#: ``rows``': the slice exchange it was set for read 1.3-3.5 ms alone
#: (call 1), the in-vreg rotates that replaced it what a whole-vreg
#: exchange reads (0.42 against 0.41 among the ops of sv26.block's first
#: kernel, call 3). At 0.25 zone [7, 12) folds from its fourth butterfly
#: and four cells ride one or two more window dots an application
#: (tests/test_kernel_op_kinds.py pins today's counts): held with the rest.
_BUTTERFLY_MS = {"lane": 0.76, "invreg": 0.07, "rows": 0.25}

#: the kinds a folded run's ops are counted under (the ``kernel_op_kinds``
#: field of a pallas plan's ``fusion.plan`` event)
KERNEL_OP_KINDS = ("lane_u", "window", "diag", "butterfly_lane",
                   "butterfly_invreg", "butterfly_rows", "kraus", "depol")


def _exchange_kind(q: int) -> str:
    """How _partner exchanges across in-tile bit q: ``lane`` (two lane
    rotates and a select), ``invreg`` (the same on the sublanes inside a
    vreg), ``rows`` (whole vregs change places)."""
    if q < LANE_BITS:
        return "lane"
    return "invreg" if (1 << (q - LANE_BITS)) < _VREG_ROWS else "rows"


def kernel_op_kind(op) -> str:
    """The one of KERNEL_OP_KINDS a kernel op (after zone folding) is
    counted as. A butterfly on two dense targets (``swap``) counts as
    ``invreg`` if either exchanges inside a vreg, else as ``lane`` if
    either is a lane bit, else as ``rows``: by the exchanges' shapes in
    that fixed order, whatever the model's prices."""
    if op[0] in ("lane_u", "window"):
        return op[0]
    if op[0] in ("kraus1", "kraus2", "krausn"):
        return "kraus"
    if op[0] == "depol":
        return "depol"
    if _op_is_diag(op):
        return "diag"
    kinds = set(map(_exchange_kind, op_dense_targets(op)))
    return "butterfly_" + next(
        k for k in ("invreg", "lane", "rows") if k in kinds)


def kernel_op_kinds(ops_folded) -> dict:
    """Count of every kind of KERNEL_OP_KINDS over a folded run's ops."""
    counts = dict.fromkeys(KERNEL_OP_KINDS, 0)
    for op in ops_folded:
        counts[kernel_op_kind(op)] += 1
    return counts


def _op_cost_ms(op) -> float:
    """Estimated in-kernel cost of one un-folded op (see table above):
    diagonals are ~free; a butterfly pays one exchange a dense target."""
    if _op_is_diag(op):
        return 0.01
    if op[0] in ("matrix", "swap"):
        return sum(_BUTTERFLY_MS[_exchange_kind(q)]
                   for q in op_dense_targets(op))
    # channel ops never reach this model: zone_of() bars them from
    # accumulators
    return 0.02


def _fold_zone_ops(ops, tile_bits: int) -> tuple:
    """Contract runs of zone-local ops into dense per-zone matrices.

    The tile's qubits split into the lane zone [0, 7) and successive
    _ZONE_SPAN-wide sublane zones [7, 12), [12, 17)... Ops fully contained
    in one zone accumulate into that zone's dense unitary; because distinct
    zones touch disjoint qubits, the open accumulators commute with each
    other, so each can keep absorbing gates until an op that OVERLAPS its
    zone (a cross-zone butterfly, parity, or grid-bit-controlled gate)
    forces a flush. Emission:

      lane zone   -> ("lane_u", W3)  three Karatsuba dots on the lane axis
      sublane zone-> ("window", lo, span, W_2Dx2D)  per-A W @ y dots (MXU)

    This is the dense-fusion economics of quest_tpu/fusion.py applied
    inside the kernel, with a COST MODEL deciding each flush: a zone folds
    only when the estimated cost of its accumulated butterflies
    (_op_cost_ms) exceeds the zone's dense-dot cost. Under the round-4
    measurements (bf16x3 dots, S=8192 tiles) lane butterflies cost more
    than the whole folded lane dot -- the lane zone folds from the first
    dense gate -- while sublane butterflies stay cheaper than the
    window dots until a zone accumulates several of them."""
    from ..events import event_matrix

    zones = [(0, LANE_BITS)]
    lo = LANE_BITS
    while lo < tile_bits:
        zones.append((lo, min(lo + _ZONE_SPAN, tile_bits)))
        lo += _ZONE_SPAN

    out = []
    accum = {z: [] for z in zones}   # zone -> [op]

    def zone_of(op):
        if op[0] in ("kraus1", "kraus2", "krausn", "depol"):
            return None  # non-unitary: must never enter a zone's dense fold
        s = _op_support(op)
        for z in zones:
            if all(z[0] <= q < z[1] for q in s):
                return z
        return None

    def flush(z):
        run = accum[z]
        if not run:
            return
        dot_ms = _FOLD_LANE_DOT_MS if z[0] == 0 else _FOLD_WINDOW_DOT_MS
        if sum(_op_cost_ms(o) for o in run) <= dot_ms:
            out.extend(run)
            run.clear()
            return
        qubits = tuple(range(z[0], z[1]))
        U = np.eye(1 << len(qubits), dtype=complex)
        for op in run:
            U = event_matrix(_op_event(op), qubits) @ U
        ur, ui = U.real, U.imag
        if z[0] == 0:
            # Karatsuba 3-multiplication complex product: ship
            # (Ur^T, Ui^T, Ur^T + Ui^T) and compute out_r = P1 - P2,
            # out_i = P3 - P1 - P2 from three 128x128 dots -- 25% fewer
            # MXU passes than the single 256x256 block dot (the lane dots
            # are the serialized compute that bounds the 26q bench)
            W = np.stack([ur.T, ui.T, ur.T + ui.T])
            out.append(("lane_u", HashableMatrix(W)))
        else:
            W = np.block([[ur, -ui], [ui, ur]])
            out.append(("window", z[0], z[1] - z[0], HashableMatrix(W)))
        run.clear()

    for op in ops:
        z = zone_of(op)
        if z is not None:
            accum[z].append(op)
            continue
        s = _op_support(op)
        for z2 in zones:
            if any(z2[0] <= q < z2[1] for q in s):
                flush(z2)
        out.append(op)
    for z in zones:
        flush(z)
    return tuple(out)


def _keep_factor(controls, states, tile_bits, shape, dtype, gbit):
    """{0,1} dtype factor that is 1 exactly where the control pattern is
    satisfied (combining grid-bit scalars and in-tile masks), or None."""
    scalar, mask = _ctrl_scalar_and_mask(controls, states, tile_bits, shape, gbit)
    if scalar is not None and mask is not None:
        return (scalar * mask).astype(dtype)
    if scalar is not None:
        return (scalar * jnp.ones(shape, jnp.int32)).astype(dtype)
    if mask is not None:
        return mask.astype(dtype)
    return None


def _ops_body(ops, xr, xi, *, tile_bits, dtype, gbit, get_w):
    """Apply a fused op run to one in-register tile (xr, xi): the shared
    compute core of both kernel styles (the BlockSpec-pipelined grid
    kernel and the manual-DMA chunk loop). ``gbit(q)`` resolves index
    bits above the tile; ``get_w(i)`` fetches the i-th dense block
    matrix from VMEM."""
    one = np.array(1, dtype)

    def mat2(xr, xi, q, M):
        """Uncontrolled 2x2 on in-tile qubit q (the core of the 'matrix'
        op, reused per-term by the kraus ops); returns new (xr, xi)."""
        shape = xr.shape
        m00, m01, m10, m11 = (complex(M[0, 0]), complex(M[0, 1]),
                              complex(M[1, 0]), complex(M[1, 1]))
        bit = _bit_mask(q, shape)
        if m01 == 0 and m10 == 0:
            dr = jnp.where(bit == 0, dtype.type(m00.real), dtype.type(m11.real))
            di = jnp.where(bit == 0, dtype.type(m00.imag), dtype.type(m11.imag))
            return (dr * xr - di * xi, dr * xi + di * xr)
        pr = _partner(xr, q)
        pi = _partner(xi, q)
        csr = jnp.where(bit == 0, dtype.type(m00.real), dtype.type(m11.real))
        cpr = jnp.where(bit == 0, dtype.type(m01.real), dtype.type(m10.real))
        if (m00.imag == 0 and m01.imag == 0 and
                m10.imag == 0 and m11.imag == 0):
            return (csr * xr + cpr * pr, csr * xi + cpr * pi)
        csi = jnp.where(bit == 0, dtype.type(m00.imag), dtype.type(m11.imag))
        cpi = jnp.where(bit == 0, dtype.type(m01.imag), dtype.type(m10.imag))
        return (csr * xr - csi * xi + cpr * pr - cpi * pi,
                csr * xi + csi * xr + cpr * pi + cpi * pr)

    def matn(xr, xi, qs, M):
        """Uncontrolled 2^t x 2^t on in-tile qubits ``qs`` (qs[j] is bit j
        of the matrix index). Row r = the element's own target bits;
        out[i] = sum_delta M[r, r^delta] * amp[i ^ delta] -- one partner
        set per delta (built incrementally, one butterfly per new bit),
        coefficients selected per element by r. Generalises the reference's
        multiQubitUnitary local kernel (QuEST_cpu.c:1846-1912) to any
        in-tile target set; used per-term by the kraus channel ops."""
        t = len(qs)
        shape = xr.shape
        r = None
        for j, q in enumerate(qs):
            term = _bit_mask(q, shape) << j
            r = term if r is None else r + term
        ps = {0: (xr, xi)}
        for delta in range(1, 1 << t):
            low = delta & -delta
            j = low.bit_length() - 1
            pr, pi = ps[delta ^ low]
            ps[delta] = (_partner(pr, qs[j]), _partner(pi, qs[j]))
        acc_r = acc_i = None
        for delta in range(1 << t):
            cvals = [complex(M[row, row ^ delta]) for row in range(1 << t)]
            if all(v == 0 for v in cvals):
                continue
            cr = jnp.full(shape, dtype.type(cvals[0].real))
            ci = jnp.full(shape, dtype.type(cvals[0].imag))
            for row in range(1, 1 << t):
                hit = r == row
                cr = jnp.where(hit, dtype.type(cvals[row].real), cr)
                ci = jnp.where(hit, dtype.type(cvals[row].imag), ci)
            sr, si = ps[delta]
            tr = cr * sr - ci * si
            ti = cr * si + ci * sr
            acc_r = tr if acc_r is None else acc_r + tr
            acc_i = ti if acc_i is None else acc_i + ti
        zero = jnp.zeros(shape, dtype)
        return (zero if acc_r is None else acc_r,
                zero if acc_i is None else acc_i)

    def mat4(xr, xi, q1, q2, M):
        return matn(xr, xi, (q1, q2), M)

    shape = xr.shape
    for op in ops:
        if op[0] == "lane_u":
            W3 = get_w(op[1])              # (3, 128, 128): Ur^T, Ui^T, sum
            if W3.dtype == jnp.bfloat16:   # (2, 3, 128, 128) hi/lo pair
                p1 = _dot_bf16x3(xr, W3[:, 0], dtype)
                p2 = _dot_bf16x3(xi, W3[:, 1], dtype)
                p3 = _dot_bf16x3(xr + xi, W3[:, 2], dtype)
            else:
                p1 = jnp.dot(xr, W3[0], preferred_element_type=xr.dtype,
                             precision=_DOT_PRECISION)
                p2 = jnp.dot(xi, W3[1], preferred_element_type=xi.dtype,
                             precision=_DOT_PRECISION)
                p3 = jnp.dot(xr + xi, W3[2], preferred_element_type=xr.dtype,
                             precision=_DOT_PRECISION)
            xr = p1 - p2
            xi = p3 - p1 - p2

        elif op[0] == "window":
            # dense folded unitary on sublane window [lo, lo+span):
            # view the tile as (A, D, B*128) and hit each A-slab with
            # one (2D, 2D) @ (2D, B*128) MXU dot (W = [[Ur,-Ui],[Ui,Ur]])
            _, wi, lo, span = op
            W = get_w(wi)
            d = 1 << span
            blk = (1 << (lo - LANE_BITS)) * _LANES
            a_cnt = (shape[0] * shape[1]) // (d * blk)
            xr4 = xr.reshape(a_cnt, d, blk)
            xi4 = xi.reshape(a_cnt, d, blk)
            outs_r, outs_i = [], []
            for a in range(a_cnt):
                y = jnp.concatenate([xr4[a], xi4[a]], axis=0)
                if W.dtype == jnp.bfloat16:  # (2, 2D, 2D) hi/lo pair
                    o = _dot_bf16x3_rev(W, y, dtype)
                else:
                    o = jnp.dot(W, y, preferred_element_type=y.dtype,
                                precision=_DOT_PRECISION)
                outs_r.append(o[:d])
                outs_i.append(o[d:])
            xr = jnp.concatenate(outs_r, axis=0).reshape(shape)
            xi = jnp.concatenate(outs_i, axis=0).reshape(shape)

        elif op[0] == "matrix":
            _, q, controls, states, M = op
            m00, m01, m10, m11 = (complex(M[0, 0]), complex(M[0, 1]),
                                  complex(M[1, 0]), complex(M[1, 1]))

            if m01 == 0 and m10 == 0:
                # diagonal 2x2: no partner exchange at all; the target
                # may even be a grid bit (per-program scalar select)
                bit = gbit(q) if q >= tile_bits else _bit_mask(q, shape)
                dr = jnp.where(bit == 0, dtype.type(m00.real), dtype.type(m11.real))
                di = jnp.where(bit == 0, dtype.type(m00.imag), dtype.type(m11.imag))
                keep = _keep_factor(controls, states, tile_bits, shape, dtype, gbit)
                if keep is not None:
                    dr = one + keep * (dr - one)
                    di = keep * di
                xr, xi = (dr * xr - di * xi, dr * xi + di * xr)
                continue
            bit = _bit_mask(q, shape)

            pr = _partner(xr, q)
            pi = _partner(xi, q)

            if (m00.imag == 0 and m01.imag == 0 and
                    m10.imag == 0 and m11.imag == 0):
                # real matrix (H, X, Ry...): half the arithmetic
                csr = jnp.where(bit == 0, dtype.type(m00.real), dtype.type(m11.real))
                cpr = jnp.where(bit == 0, dtype.type(m01.real), dtype.type(m10.real))
                keep = _keep_factor(controls, states, tile_bits, shape, dtype, gbit)
                if keep is not None:
                    csr = one + keep * (csr - one)
                    cpr = keep * cpr
                xr, xi = (csr * xr + cpr * pr, csr * xi + cpr * pi)
                continue
            # coefficient planes: self = m00/m11, pair = m01/m10 by bit q
            csr = jnp.where(bit == 0, dtype.type(m00.real), dtype.type(m11.real))
            csi = jnp.where(bit == 0, dtype.type(m00.imag), dtype.type(m11.imag))
            cpr = jnp.where(bit == 0, dtype.type(m01.real), dtype.type(m10.real))
            cpi = jnp.where(bit == 0, dtype.type(m01.imag), dtype.type(m10.imag))
            # fold controls into the coefficients (identity where the
            # control pattern misses) -- cheaper than output blending
            keep = _keep_factor(controls, states, tile_bits, shape, dtype, gbit)
            if keep is not None:
                csr = one + keep * (csr - one)
                csi = keep * csi
                cpr = keep * cpr
                cpi = keep * cpi
            xr, xi = (csr * xr - csi * xi + cpr * pr - cpi * pi,
                      csr * xi + csi * xr + cpr * pi + cpi * pr)

        elif op[0] == "parity":
            _, qubits, controls, theta = op
            sign_scalar = jnp.array(1, jnp.int32)
            par = None
            for q in qubits:
                if q >= tile_bits:
                    gb = gbit(q)
                    sign_scalar = sign_scalar * (1 - 2 * gb)
                else:
                    b = _bit_mask(q, shape)
                    par = b if par is None else par ^ b
            sign = sign_scalar.astype(dtype)
            if par is not None:
                sign = sign * (1 - 2 * par).astype(dtype)
            c = dtype.type(math.cos(theta / 2))
            s = dtype.type(math.sin(theta / 2))
            fr = c * jnp.ones_like(sign)
            fi = -s * sign
            keep = _keep_factor(controls, (), tile_bits, shape, dtype, gbit)
            if keep is not None:
                fr = one + keep * (fr - one)
                fi = keep * fi
            xr, xi = (xr * fr - xi * fi, xr * fi + xi * fr)

        elif op[0] == "swap":
            _, q1, q2, controls, states = op
            # amps where bits q1,q2 differ exchange with partner(^q1^q2)
            p2r = _partner(_partner(xr, q1), q2)
            p2i = _partner(_partner(xi, q1), q2)
            differ = (_bit_mask(q1, shape) ^ _bit_mask(q2, shape)).astype(dtype)
            keep = _keep_factor(controls, states, tile_bits, shape, dtype, gbit)
            sel = differ if keep is None else differ * keep
            xr = xr + sel * (p2r - xr)
            xi = xi + sel * (p2i - xi)

        elif op[0] in ("kraus1", "kraus2", "krausn"):
            # a whole 1-, 2- or t-target channel in ONE pass: for each
            # Kraus term apply K on the row qubit(s) and conj(K) on the
            # column qubit(s) to a COPY of the registers, accumulate
            # sign-weighted -- rho' = sum_k s_k K_k rho K_k^dagger with
            # zero extra HBM traffic. The reference pays a dedicated
            # kernel launch per channel (QuEST_gpu.cu:2423-2600) and,
            # distributed, the 3-exchange two-qubit depolarising
            # protocol (QuEST_cpu_distributed.c:778-868); round 2 paid
            # ~2 passes per term. The >=3-target form routes every
            # backend through one mechanism, like the reference's
            # superoperator treatment (QuEST_common.c:581-638).
            if op[0] == "kraus1":
                _, t, c, terms = op
                apply_k = lambda r, i, K: mat2(*mat2(r, i, t, K),
                                               c, np.conj(K))
            elif op[0] == "kraus2":
                _, t1, t2, c1, c2, terms = op
                apply_k = lambda r, i, K: mat4(*mat4(r, i, t1, t2, K),
                                               c1, c2, np.conj(K))
            else:
                _, rows_q, cols_q, terms = op
                apply_k = lambda r, i, K: matn(*matn(r, i, rows_q, K),
                                               cols_q, np.conj(K))
            acc_r = acc_i = None
            for sign, K in terms:
                K = np.asarray(K.arr if hasattr(K, "arr") else K)
                yr, yi = apply_k(xr, xi, K)
                if sign != 1.0:
                    yr = dtype.type(sign) * yr
                    yi = dtype.type(sign) * yi
                acc_r = yr if acc_r is None else acc_r + yr
                acc_i = yi if acc_i is None else acc_i + yi
            xr, xi = acc_r, acc_i

        elif op[0] == "depol":
            # the depolarising family in closed form, on one target or
            # two: rho -> (1 - l) rho + l (I/d (x) Tr_T rho). Of a group
            # (the 4^t elements that differ in the row targets ``rows_q``
            # and their column twins ``cols_q``) only the 2^t on its
            # diagonal -- row bit == column bit on every target -- take
            # anything in, and what they take is the mean of those 2^t:
            # one paired exchange of (t, t + n) a target sums them, where
            # the Kraus sum is 4^t terms of two matrix sweeps each. The
            # reference's dedicated kernels (QuEST_gpu.cu:2423-2600,
            # densmatr_mixDepolarising / mixTwoQubitDepolarising).
            _, rows_q, cols_q, lam = op
            sr, si, same = xr, xi, None
            for t, c in zip(rows_q, cols_q):
                sr = sr + _partner(_partner(sr, t), c)
                si = si + _partner(_partner(si, t), c)
                eq = _bit_mask(t, shape) == _bit_mask(c, shape)
                same = eq if same is None else same & eq
            keep = dtype.type(1.0 - lam)
            mean = jnp.where(same, dtype.type(lam / (1 << len(rows_q))),
                             dtype.type(0.0))
            xr, xi = keep * xr + mean * sr, keep * xi + mean * si

        elif op[0] == "diagw":
            _, targets, controls, D = op
            d = np.asarray(D.arr if hasattr(D, "arr") else D).reshape(-1)
            # table index: in-tile target bits come from iota masks,
            # grid-bit targets from per-program scalars (broadcasts)
            idx = None
            for j, q in enumerate(targets):
                b = gbit(q) if q >= tile_bits else _bit_mask(q, shape)
                term = b << j
                idx = term if idx is None else idx + term
            fr = jnp.full(shape, dtype.type(d[0].real))
            fi = jnp.full(shape, dtype.type(d[0].imag))
            for k in range(1, d.size):
                hit = idx == k
                fr = jnp.where(hit, dtype.type(d[k].real), fr)
                fi = jnp.where(hit, dtype.type(d[k].imag), fi)
            keep = _keep_factor(controls, (), tile_bits, shape, dtype, gbit)
            if keep is not None:
                fr = one + keep * (fr - one)
                fi = keep * fi
            xr, xi = (xr * fr - xi * fi, xr * fi + xi * fr)

        else:  # pragma: no cover
            raise ValueError(f"unknown pallas op {op[0]!r}")

    return xr, xi


def _make_kernel(ops, s_bits, tile_bits, dtype, local_n=None,
                 load_swap=None, store_swap=None, df=False, df_acc=False):
    """BlockSpec-pipelined grid kernel over (x_ref, hi_ref, *w_refs,
    o_ref); ops of kind 'lane_u'/'window' carry an index into w_refs
    (their block matrices arrive as operands -- Pallas kernels may not
    capture array constants).

    ``hi_ref`` is an SMEM scalar holding the shard index when the kernel
    runs per-device inside shard_map (``local_n`` = the shard's qubit
    count): qubit roles at q >= local_n resolve against it, so controls,
    parity members and diagonal targets on SHARDED qubits work in-kernel
    with zero communication -- the Pallas analogue of the scheduler's
    rank-bit controls (parallel/exchange.py).

    A block is (P * s, 128) rows of the row-interleaved register
    (_rows_view): plane i is its rows i, i + P, ... (_plane_rows).

    ``load_swap``/``store_swap`` = (dk, s_low) fold a frame-swap transpose
    (swap_bit_blocks of the top-k sublane block with a k-bit grid block)
    into this pass: the input block arrives frame-permuted (gathered by the
    BlockSpec from dk strided row-chunks), and/or the output block scatters
    back the same way. The relabeling then costs zero extra HBM passes --
    the pass count of a two-frame circuit drops by ~2x (round-3 attack on
    the reference hot loop QuEST_cpu.c:1682-1739; see fusion._FramePlanner).
    """

    P = 4 if df else 2

    def kernel(x_ref, hi_ref, *refs):
        w_refs = refs[:-1]
        o_ref = refs[-1]
        # a swap block is (1, dk, 1, 1, P*s_low, 128): see _swap_spec
        planes = _load_planes(
            x_ref if load_swap is None else x_ref.at[0, :, 0, 0], P,
            load_swap)

        def gbit(q):
            if local_n is not None and q >= local_n:
                return (hi_ref[0] >> (q - local_n)) & 1
            return _grid_bit(q, tile_bits)

        if df:
            from .pallas_df import _ops_body_df
            (rh, rl), (ih, il) = _ops_body_df(
                ops, (planes[0], planes[2]), (planes[1], planes[3]),
                tile_bits=tile_bits, gbit=gbit, accurate_add=df_acc)
            planes = [rh, ih, rl, il]
        else:
            xr, xi = _ops_body(ops, planes[0], planes[1],
                               tile_bits=tile_bits, dtype=dtype, gbit=gbit,
                               get_w=lambda i: w_refs[i][:])
            planes = [xr, xi]

        _store_planes(o_ref if store_swap is None else o_ref.at[0, :, 0, 0],
                      planes, store_swap)

    return kernel


def _make_dma_kernel(ops, s: int, tile_bits: int, dtype,
                     nchunks: int, load_swap, store_swap, df=False,
                     ring: int = 2, local_n=None, df_acc=False):
    """Manual ring-buffered-DMA kernel: ONE pallas program owns the whole
    pass, looping over the 2^grid chunks with explicit async copies through
    an N-slot in-flight ring (``ring`` load buffers + ``ring`` store
    buffers) -- up to ring-1 chunk loads stay in flight ahead of the chunk
    being computed, and a store only blocks when its slot comes around
    again ``ring`` chunks later. Measured vs the BlockSpec grid pipeline at
    2^26 amps: full-state copy 3.9 vs 6.3 ms (the BlockSpec pipeline
    leaves ~40% of HBM bandwidth on the table; round-3 probe), which is
    most of the 26q bench's per-pass floor. Depth > 2 exists to hide the
    round-5 finding that the two-slot ring serialises on its own
    store-wait whenever a chunk's compute (the bf16x3 zone dots) runs
    shorter than its store drains: with N slots the dots of chunks
    c..c+N-2 overlap the still-draining stores of chunks c-N..c-1 instead
    of stalling the sweep. Depth is a tunable (``ring_depth`` on
    fused_local_run / QUEST_PALLAS_RING); VMEM cost is linear in depth
    (2 * ring tile buffers), so the caller derates depth on op-heavy runs.

    The operand is the row-interleaved register (_rows_view) cut into
    chunks: a chunk is ONE contiguous (P * s, 128) piece of HBM holding its
    P planes row by row, so a ring slot is that piece and plane i its rows
    i, i + P, ... (_plane_rows: sublane-strided loads, strided stores on the
    way out).

    ``load_swap``/``store_swap`` = (dk, s_low, gm_sz) fold the frame-swap
    relabeling into the chunk DMAs: the operand arrives as the 6-D
    bit-block-swap view (_swap_view) and each chunk load/store is one
    strided descriptor gathering/scattering the dk sub-blocks, each a
    contiguous (P * s_low, 128) piece.

    ``hi_ref`` is the SMEM shard-index scalar (as _make_kernel's): when
    ``local_n`` is set the kernel runs per-device inside shard_map and
    qubit roles at q >= local_n resolve against it -- the df per-shard
    route takes THIS kernel because Mosaic fails to legalize the 4-plane
    block under a BlockSpec grid (round-5 find; the round-7 extension of
    that single-tile workaround to the sharded grid: the chunk loop is one
    gridless program whatever the chunk count)."""

    P = 4 if df else 2
    ring = max(2, min(int(ring), nchunks))

    def kernel(x_hbm, hi_ref, *refs):
        w_refs = refs[:-1]
        o_hbm = refs[-1]

        def body(ins, outs, rsem, wsem):
            def chunk_coords(geo, c):
                # decompose the chunk index against THIS DMA's swap
                # geometry (load and store may use different k / hi);
                # static (python int) chunk indices compute on the host,
                # traced ones via lax with np.int32 divisors (Mosaic's
                # memref_slice rejects i64 operands)
                dk, _, gm_sz = geo
                if isinstance(c, (int, np.integer)):
                    gm = np.int32(c % gm_sz)
                    rest = c // gm_sz
                    return (np.int32(rest // dk), gm, np.int32(rest % dk))
                # np.int32 divisors: bare python ints materialise as i64
                # constants under jax x64 and Mosaic's convert-lowering
                # recurses narrowing them; the counter itself is always
                # i32 (the while_loop carry below)
                dk, gm_sz = np.int32(dk), np.int32(gm_sz)
                gm = c % gm_sz
                rest = c // gm_sz
                return (rest // dk, gm, rest % dk)

            def _i32(v):
                # static python indices canonicalise to i64 under jax
                # x64, which Mosaic's memref_slice rejects
                return np.int32(v) if isinstance(v, (int, np.integer)) \
                    else v

            def load_dma(slot, c):
                slot, c = _i32(slot), _i32(c)
                if load_swap is None:
                    return pltpu.make_async_copy(
                        x_hbm.at[c], ins.at[slot], rsem.at[slot])
                hi2, gm, dnew = chunk_coords(load_swap, c)
                return pltpu.make_async_copy(
                    x_hbm.at[hi2, :, gm, dnew], ins.at[slot],
                    rsem.at[slot])

            def store_dma(slot, c):
                slot, c = _i32(slot), _i32(c)
                if store_swap is None:
                    return pltpu.make_async_copy(
                        outs.at[slot], o_hbm.at[c], wsem.at[slot])
                hi2, gm, dnew = chunk_coords(store_swap, c)
                return pltpu.make_async_copy(
                    outs.at[slot], o_hbm.at[hi2, :, gm, dnew],
                    wsem.at[slot])

            # prologue: fill all but one ring slot, so the steady-state
            # loop always has ring-1 loads in flight ahead of the compute
            for j in range(min(ring - 1, nchunks)):
                load_dma(j, j).start()

            def gbit_for(c):
                def gbit(q):
                    if local_n is not None and q >= local_n:
                        return (hi_ref[0] >> (q - local_n)) & 1
                    return (c >> (q - tile_bits)) & 1
                return gbit

            def compute(planes, gbit):
                if df:
                    from .pallas_df import _ops_body_df
                    (rh, rl), (ih, il) = _ops_body_df(
                        ops, (planes[0], planes[2]),
                        (planes[1], planes[3]),
                        tile_bits=tile_bits, gbit=gbit, accurate_add=df_acc)
                    return [rh, ih, rl, il]
                xr, xi = _ops_body(ops, planes[0], planes[1],
                                   tile_bits=tile_bits,
                                   dtype=dtype, gbit=gbit,
                                   get_w=lambda i: w_refs[i][:])
                return [xr, xi]

            def loop(c, carry):
                # np.int32 literals: a bare python int materialises as an
                # i64 constant under jax x64, and Mosaic's convert-lowering
                # recurses infinitely narrowing it (round-5 find)
                ring_i = np.int32(ring)
                slot = c % ring_i
                ahead = c + np.int32(ring - 1)
                nxt = ahead % ring_i

                @pl.when(ahead < nchunks)
                def _():
                    # slot (c-1) % ring was freed when chunk c-1's compute
                    # consumed it last iteration; refill it ring-1 ahead
                    load_dma(nxt, ahead).start()

                load_dma(slot, c).wait()
                planes = compute(_load_planes(ins.at[slot], P, load_swap),
                                 gbit_for(c))

                @pl.when(c >= ring_i)
                def _():
                    # the store that used this slot ring chunks ago must
                    # drain before the slot's output buffer is overwritten
                    store_dma(slot, c - ring_i).wait()

                _store_planes(outs.at[slot], planes, store_swap)
                store_dma(slot, c).start()
                return carry

            # while_loop with an EXPLICIT i32 carry, not fori_loop: under
            # jax x64 (the df kernels) fori's counter canonicalises to
            # i64, and Mosaic's convert-lowering recurses infinitely
            # trying to narrow it (round-5 find); a strongly-typed i32
            # carry never needs converting
            def w_cond(c):
                return c < np.int32(nchunks)

            def w_body(c):
                loop(c, 0)
                return c + np.int32(1)

            jax.lax.while_loop(w_cond, w_body, jnp.asarray(0, jnp.int32))
            for c in range(max(0, nchunks - ring), nchunks):
                store_dma(c % ring, c).wait()

        def slot_shape(swap):
            if swap is None:
                return (P * s, _LANES)
            dk, s_low, _ = swap
            return (dk, P * s_low, _LANES)

        in_shape, out_shape = slot_shape(load_swap), slot_shape(store_swap)
        pl.run_scoped(
            body,
            ins=pltpu.VMEM((ring,) + in_shape, dtype),
            outs=pltpu.VMEM((ring,) + out_shape, dtype),
            rsem=pltpu.SemaphoreType.DMA((ring,)),
            wsem=pltpu.SemaphoreType.DMA((ring,)),
        )

    return kernel


def fused_local_run(amps, *, n: int, ops: tuple, sublanes: int = _DEF_SUBLANES,
                    interpret: bool | None = None, shard_index=None,
                    load_swap_k: int = 0, store_swap_k: int = 0,
                    load_swap_hi: int | None = None,
                    store_swap_hi: int | None = None,
                    ring_depth: int | None = None):
    """Apply ``ops`` (see module doc) to the planar (2, 2^n) state in one
    fused Pallas pass. Every matrix target must satisfy
    ``q < local_qubits(n, sublanes)``; parity members and controls may be
    any qubit. ``ops`` is hashable (tuples + HashableMatrix wrappers).
    On non-TPU backends the kernel runs in the Pallas interpreter (CI).

    ``shard_index`` (traced i32 scalar, e.g. ``jax.lax.axis_index`` inside
    shard_map) enables per-shard execution: ``amps`` is then one device's
    shard with ``n`` LOCAL qubits, and op roles on qubits >= n (sharded
    qubits of the global register) resolve against the shard index.

    ``load_swap_k`` = k > 0 folds ``swap_bit_blocks(lo1=tb-k, lo2, k)``
    (tb = the tile-bit count of this call's geometry; lo2 =
    ``load_swap_hi`` or tb) into the input DMA: the state arrives in the
    OTHER frame and is relabeled during load, so ``ops`` must already be
    in this run's frame. ``store_swap_k``/``store_swap_hi`` fold the same
    relabeling into the output DMA (the result lands in the other frame).
    Either costs zero extra HBM passes. A non-default ``*_hi`` relocates
    an ARBITRARY grid-bit block into the top sublane slots -- the free
    generalisation of the reference's swap-to-local relocation
    (QuEST_cpu_distributed.c:1526-1568). Composes with ``shard_index``
    when the swapped block is SHARD-LOCAL (``hi + k <= n`` in the shard's
    coordinates; swaps reaching sharded bits are collectives and stay the
    caller's job -- fusion runs them as explicit transposes).

    ``ring_depth`` sets the manual DMA pipeline's in-flight slot count
    (None = the QUEST_PALLAS_RING env override, else _DEF_RING_DEPTH;
    min 2); the chosen depth is clamped to the chunk count and derated to
    fit _RING_VMEM_BUDGET, and the per-shard/BlockSpec grid paths ignore
    it (the BlockSpec pipeline owns its own buffering)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if amps.shape[-1] < _LANES:
        raise ValueError(
            f"state has {amps.shape[-1]} amplitudes < one {_LANES}-lane tile; "
            f"registers below {LANE_BITS + 1} qubits take the ordinary path")
    # Folded frame swaps compose with shard_index when the swapped grid
    # block is SHARD-LOCAL (hi + k <= n in the shard's own coordinates) --
    # _fused_local_run's geometry check rejects anything reaching past the
    # shard (round 7; the round-4..6 builds raised unconditionally here).
    # double-float layout (4 planes = re/im x hi/lo, ops/pallas_df): pure
    # VPU arithmetic, so zone folding (MXU dots) is skipped. It runs
    # per-shard too (round 7, ISSUE 3): grid bits resolve from the chunk
    # counter, sharded bits from the SMEM shard-index scalar.
    df = amps.shape[0] == 4

    lq = local_qubits(n, sublanes)
    for o in ops:
        bad = [q for q in op_dense_targets(o) if q >= lq]
        if bad:
            raise ValueError(
                f"{o[0]} dense target(s) {bad} >= local_qubits({n}, "
                f"{sublanes}) = {lq}; route wide targets via ops.apply")
    if shard_index is None:
        shard_index = jnp.zeros((1,), jnp.int32)
        local_n = None
    else:
        shard_index = jnp.asarray(shard_index, jnp.int32).reshape(1)
        local_n = n
    # the compile record's clock starts before the zone fold, which is
    # part of what a new kernel costs the host (_compile_record)
    t0 = time.perf_counter()
    ops_l = tuple(ops) if df else _fold_zone_ops(ops, lq)
    t_fold = time.perf_counter()
    ring = (max(2, int(ring_depth)) if ring_depth is not None
            else ring_depth_default())
    from .pallas_df import accurate_add_enabled
    df_acc = bool(df and accurate_add_enabled())

    # one jitted function a kernel name, so that the device trace tells
    # the kernels of a program apart (_named_jit)
    name = kernel_name(
        _kernel_kind(_tile_geometry(amps.shape[-1], sublanes)[2], local_n,
                     df),
        amps.shape[0], amps.dtype, len(ops_l), int(load_swap_k),
        int(store_swap_k), _narrowed_tile_bits(amps.shape, sublanes))
    run = _named_jit(_fused_local_run_impl, name, _FUSED_STATIC)

    def call():
        return run(
            amps, shard_index, n=n, ops=ops_l, sublanes=sublanes,
            interpret=bool(interpret), local_n=local_n,
            load_swap_k=int(load_swap_k), store_swap_k=int(store_swap_k),
            load_swap_hi=load_swap_hi, store_swap_hi=store_swap_hi,
            ring_depth=ring, df_acc=df_acc)

    if not telemetry.enabled():
        return call()
    kind = "df" if df else str(np.dtype(amps.dtype))
    # counts LOWERINGS: under an outer jax.jit this wrapper runs once per
    # trace of the program, not once per launch (the launches are counted
    # in the device trace: launches_per_circuit)
    telemetry.inc("pallas_pass_total", kind="fused_run", dtype=kind)
    if writes_in_place(lq, load_swap_k, load_swap_hi, store_swap_k,
                       store_swap_hi):
        telemetry.inc("fusion_inplace_runs_total")
    # the requested operating point (pre clamp/derate -- the knob value)
    telemetry.set_gauge("pallas_ring_depth", ring)
    sig = (n, ops_l, sublanes, int(load_swap_k), int(store_swap_k),
           load_swap_hi, store_swap_hi, local_n, str(amps.dtype),
           amps.shape, bool(interpret), ring, df_acc)
    if sig in _SEEN_KERNEL_SIGS:
        return call()
    _SEEN_KERNEL_SIGS.add(sig)
    mark = telemetry.compile_mark()
    out = call()
    _compile_record(kind, name, t0, t_fold, mark, n=n, ops=len(ops_l),
                    sublanes=min(sublanes, max(amps.shape[-1] >> LANE_BITS,
                                               1)),
                    load_swap_k=int(load_swap_k),
                    store_swap_k=int(store_swap_k), ring=ring,
                    interpret=bool(interpret))
    return out


#: kernel signatures already dispatched once (compile timing recorded)
_SEEN_KERNEL_SIGS: set = set()


def _compile_record(kind: str, name: str, t0: float, t_fold: float, mark,
                    **fields) -> None:
    """The record of a new kernel signature's first dispatch, made as it
    returns: ``mosaic_compile_seconds{kind}`` and one ``pallas.compile``
    event that says which ``kernel`` (the ``pallas_call``'s ``name=``,
    which the device trace shows; the event's own ``name`` is taken: the
    benchmark finds the record by it). ``fold_s`` is the zone fold (``t0`` to
    ``t_fold``), ``trace_s`` -- and ``seconds``, its older name -- the
    dispatch after it: Mosaic trace and compile on an eager call; inside
    an outer ``jit``, where nothing compiles yet, the kernel body's Python
    trace. The histogram takes both. ``nested_traces`` counts the JAX
    trace events that fired inside since ``mark``
    (``telemetry.compile_mark``, read before the dispatch). Either way it
    is the host-side cost a new signature charges."""
    t1 = time.perf_counter()
    nested = telemetry.kernel_traced(name, mark)
    telemetry.observe("mosaic_compile_seconds", t1 - t0, kind=kind)
    trace_s = round(t1 - t_fold, 4)
    telemetry.event("pallas.compile", kind=kind, kernel=name, **fields,
                    seconds=trace_s, fold_s=round(t_fold - t0, 4),
                    trace_s=trace_s, nested_traces=nested)


def kernel_name(kind: str, planes: int, dtype, nops: int,
                load_swap_k: int = 0, store_swap_k: int = 0,
                narrowed_tile_bits: int | None = None) -> str:
    """The ``name=`` of a fused-run ``pallas_call``, which the device
    trace shows in place of ``_fused_local_run.<n>``: kernel kind (``dma``
    the manual-DMA chunk loop, ``grid`` the BlockSpec grid, ``df1`` the
    gridless one-tile double-float call), ``df`` for the 4-plane layout
    or the dtype, the op count after zone folding, and the folded load
    and store swap ``k``: ``qt_fused_dma_f32_ops57_ls0_ss7``; a kernel cut
    at a narrower tile than its layout's own (``_narrowed_tile_bits``) says
    so, ``..._ss2_tb18``, or two kernels of one program that differ in
    nothing else would share a name and the device trace merge them. A pure
    function of the call's static arguments, in letters, digits and ``_``:
    the name enters the kernel's lowering and so the compile-cache key,
    and must be the same in every process (no counter, id or hash)."""
    dt = "df" if planes == 4 else f"f{8 * np.dtype(dtype).itemsize}"
    tile = "" if narrowed_tile_bits is None else f"_tb{narrowed_tile_bits}"
    return (f"qt_fused_{kind}_{dt}_ops{nops}"
            f"_ls{load_swap_k}_ss{store_swap_k}{tile}")


def _narrowed_tile_bits(shape: tuple, sublanes: int) -> int | None:
    """The tile bits of a call whose ``sublanes`` cut a narrower tile than
    its layout's own (``_DEF_SUBLANES``, or the double-float layout's
    ``DF_SUBLANES``) would on the same array -- a run the planner narrowed
    (``fusion.PallasRun.own_tile``) -- or None."""
    from .pallas_df import DF_SUBLANES

    own = DF_SUBLANES if shape[0] == 4 else _DEF_SUBLANES
    s = _tile_geometry(shape[-1], sublanes)[1]
    if s >= _tile_geometry(shape[-1], own)[1]:
        return None
    return LANE_BITS + s.bit_length() - 1


def writes_in_place(tile_bits: int, load_swap_k: int, load_swap_hi,
                    store_swap_k: int, store_swap_hi) -> bool:
    """Whether a fused run's kernel writes over its operand
    (``input_output_aliases``): where it leaves on its store the frame it
    entered on its load, or has neither relabeling. Chunk ``c`` then reads
    and writes the SAME addresses, the chunks' address sets are disjoint,
    and a load running ahead of the compute touches only chunks not yet
    written -- so a chain of such runs on a donated register holds no
    state-sized temporary. Where the two relabelings differ a chunk's
    store lands in other chunks' unread input, and the pass keeps an
    output of its own. Where the operand is still live (an undonated
    call) XLA keeps a copy by itself."""
    def geo(k, hi):
        return (k, tile_bits if hi is None else hi) if k else None

    return geo(load_swap_k, load_swap_hi) == geo(store_swap_k, store_swap_hi)


def _tile_geometry(num: int, sublanes: int):
    """(rows, sublanes used, grid) of a ``num``-amplitude plane cut into
    (sublanes, 128) tiles."""
    rows = max(num >> LANE_BITS, 1)
    s = min(sublanes, rows)
    return rows, s, rows // s


def _kernel_kind(grid: int, local_n, df: bool) -> str:
    """Which of the three fused-run kernels a call takes: ``dma`` (the
    manual-DMA chunk loop), ``df1`` (the gridless one-tile double-float
    call) or ``grid`` (the BlockSpec grid)."""
    if grid > 1 and (local_n is None or df):
        return "dma"
    return "df1" if df and grid == 1 else "grid"


@functools.lru_cache(maxsize=None)
def _named_jit(impl, name: str, static_argnames: tuple):
    """``impl`` jitted (state donated) under ``name``, one jitted function
    a kernel name. XLA names a Mosaic custom call after the innermost
    jitted function it was traced in -- ``_fused_local_run.<n>`` for every
    kernel of a program, if they all share one. The ``name=`` of the
    ``pallas_call`` itself only becomes a name scope, which the lowering
    drops when ``jax_include_full_tracebacks_in_locations`` is off, and
    the benchmark turns that off to keep its compile-cache keys stable."""
    def kernel_program(*args, **kwargs):
        return impl(*args, **kwargs)

    kernel_program.__name__ = kernel_program.__qualname__ = name
    return jax.jit(kernel_program, static_argnames=static_argnames,
                   donate_argnums=(0,))


def _rows_view(amps):
    """The (P, 2^n) register as the kernels read it: (rows * P, 128), row
    ``r * P + i`` = row ``r`` of plane ``i``. Not a relayout: the TPU
    compiler tiles a (P, N) f32 array T(P,128), whose bytes ARE this array
    under T(8,128), and compiles the view (and ``_planes_view``, its
    inverse) to a bitcast -- where the plane-major (P, rows, 128) view cost
    a copy of the whole state on the way into a program's first kernel and
    another out of its last."""
    P = amps.shape[0]
    rows = amps.shape[-1] >> LANE_BITS
    return (amps.reshape(P, rows, _LANES).transpose(1, 0, 2)
            .reshape(rows * P, _LANES))


def _planes_view(x, P: int):
    """Inverse of ``_rows_view``: any row-interleaved view back to (P, N)."""
    return x.reshape(-1, P, _LANES).transpose(1, 0, 2).reshape(P, -1)


def _plane_rows(i: int, rows: int, P: int):
    """The ``rows`` rows of plane ``i`` in a row-interleaved block: a
    sublane-strided slice of a VMEM ref."""
    return pl.ds(i, rows, stride=P)


def _load_planes(ref, P: int, swap=None):
    """The P (S, 128) planes of a row-interleaved VMEM block: ``ref`` is
    (P * S, 128), or under a folded swap (``swap`` = (dk, s_low, ...)) the
    dk gathered row-chunks (dk, P * s_low, 128). There axis 0 is the (old)
    grid-bit block, already sitting where the new frame's high sublane
    bits belong -- collapsing (dk, s_low) into the sublane axis IS the
    bit-block swap, and is layout-free when s_low fills >= 1 sublane tile
    (the callers guarantee s_low >= 8)."""
    if swap is None:
        return [ref[_plane_rows(i, ref.shape[0] // P, P), :]
                for i in range(P)]
    dk, s_low = swap[:2]
    return [ref[:, _plane_rows(i, s_low, P), :].reshape(dk * s_low, _LANES)
            for i in range(P)]


def _store_planes(ref, planes, swap=None):
    """``_load_planes``' inverse: strided stores of the planes into ``ref``."""
    P = len(planes)
    for i, plane in enumerate(planes):
        if swap is None:
            ref[_plane_rows(i, ref.shape[0] // P, P), :] = plane
        else:
            dk, s_low = swap[:2]
            ref[:, _plane_rows(i, s_low, P), :] = \
                plane.reshape(dk, s_low, _LANES)


def _swap_view(x, rows: int, s: int, lo2_rel: int, k: int):
    """(rows * P, 128) -> the 6-D bit-block-swap view
    (high, dg, gmid, ds, P * s_low, 128): ``dg`` is the k-bit grid block at
    row bits [lo2_rel, lo2_rel+k), ``ds`` the top-k sublane block at
    [s_bits-k, s_bits), ``gmid`` the grid bits between them. Exchanging dg
    and ds relabels amplitudes exactly like swap_bit_blocks(tb-k, lo2, k)
    -- lo2 may be ANY grid-bit offset, not just the tile boundary. The plane
    index (P = 2 planar planes re, im, or 4 in the double-float layout) is
    the LOWEST row bit (_rows_view), so it rides inside the s_low axis and
    every (P * s_low, 128) piece is contiguous in HBM."""
    s_bits = s.bit_length() - 1
    dk = 1 << k
    gmid = 1 << (lo2_rel - s_bits)
    high = rows // (dk * gmid * (s >> k) * dk)
    P = x.shape[0] // rows
    return x.reshape(high, dk, gmid, dk, P * (s >> k), _LANES)


def _swap_spec(s: int, lo2_rel: int, k: int, planes: int = 2):
    """BlockSpec gathering/scattering one swap-permuted tile per program:
    for new grid index i, all dk positions of the old grid block, at the
    old-sublane-block position encoded in i's [lo2_rel - s_bits) bits --
    dk strided (planes * s_low, 128) row-chunks, each holding its planes
    row-interleaved, whose per-plane concatenation IS the tile in the new
    frame."""
    s_bits = s.bit_length() - 1
    dk = 1 << k
    gm_sz = 1 << (lo2_rel - s_bits)

    def imap(i):
        # np.int32 throughout: under jax x64 a bare python int is an i64
        # constant, and Mosaic cannot legalize an index map that returns
        # mixed (i64, i32) block indices (first v5e AOT compile, PR 24)
        z = np.int32(0)
        gm = i % np.int32(gm_sz)
        rest = i // np.int32(gm_sz)
        return (rest // np.int32(dk), z, gm, rest % np.int32(dk), z, z)

    return pl.BlockSpec((1, dk, 1, 1, planes * (s >> k), _LANES), imap,
                        memory_space=pltpu.VMEM)


_FUSED_STATIC = ("n", "ops", "sublanes", "interpret", "local_n",
                 "load_swap_k", "store_swap_k", "load_swap_hi",
                 "store_swap_hi", "ring_depth", "df_acc")


def _fused_local_run_impl(amps, shard_index, *, n: int, ops: tuple,
                          sublanes: int, interpret: bool,
                          local_n: int | None,
                          load_swap_k: int = 0, store_swap_k: int = 0,
                          load_swap_hi: int | None = None,
                          store_swap_hi: int | None = None,
                          ring_depth: int = _DEF_RING_DEPTH,
                          df_acc: bool = False):
    P = amps.shape[0]          # 2 planar planes, or 4 in df layout
    df = P == 4
    rows, s, grid = _tile_geometry(amps.shape[-1], sublanes)
    s_bits = int(math.log2(s)) if s > 1 else 0
    tile_bits = LANE_BITS + s_bits
    kind = _kernel_kind(grid, local_n, df)
    name = kernel_name(kind, P, amps.dtype, len(ops), load_swap_k,
                       store_swap_k, _narrowed_tile_bits(amps.shape, sublanes))
    for k, hi in ((load_swap_k, load_swap_hi), (store_swap_k, store_swap_hi)):
        if k:
            hi = tile_bits if hi is None else hi
            if k > s_bits or hi < tile_bits or hi + k > n:
                raise ValueError(
                    f"bit-block swap (k={k}, hi={hi}) exceeds the call "
                    f"geometry (tile_bits={tile_bits}, n={n})")

    # lane_u block matrices become pallas operands (replicated per program);
    # their op entries carry the operand index instead of the matrix
    ws = []
    ops_r = []
    # f32 tiles ship the zone matrices as bf16 hi/lo pairs (the bf16x3
    # three-DEFAULT-pass dot, half of HIGHEST's six); f64 keeps full-width
    # operands for the interpreter/engine path
    bf16x3 = np.dtype(amps.dtype) == np.dtype("float32")

    def ship(w):
        w = np.asarray(w, dtype=np.float32 if bf16x3 else amps.dtype)
        return jnp.asarray(_split_bf16(w) if bf16x3 else w)

    for o in ops:
        if o[0] == "lane_u":
            ops_r.append(("lane_u", len(ws)))
            ws.append(ship(o[1].arr.real))
        elif o[0] == "window":
            ops_r.append(("window", len(ws), o[1], o[2]))
            ws.append(ship(o[3].arr.real))
        elif o[0] == "matrix":
            ops_r.append((o[0], o[1], o[2], o[3],
                          np.asarray(o[4].arr if hasattr(o[4], "arr") else o[4])))
        elif o[0] == "diagw":
            ops_r.append((o[0], o[1], o[2],
                          np.asarray(o[3].arr if hasattr(o[3], "arr") else o[3])))
        else:
            ops_r.append(o)
    x = _rows_view(amps)
    lo2_load = (load_swap_hi if load_swap_hi is not None else tile_bits)
    lo2_store = (store_swap_hi if store_swap_hi is not None else tile_bits)
    aliases = {0: 0} if writes_in_place(
        tile_bits, load_swap_k, load_swap_hi, store_swap_k,
        store_swap_hi) else {}

    if kind == "dma":
        # manual double-buffered-DMA kernel (see _make_dma_kernel): one
        # program, explicit chunk pipeline -- ~40% more HBM bandwidth than
        # the BlockSpec grid pipeline on this geometry. Runs under the
        # interpreter too, so CI covers the production path; the per-shard
        # (shard_map) f32 path keeps the grid kernel, while per-shard DF
        # runs take this kernel too: Mosaic cannot legalize the 4-plane
        # block under a BlockSpec grid (round-5 find), and the one-program
        # chunk loop sidesteps the grid entirely (round 7).
        def swap_geo(k, lo2):
            if not k:
                return None
            return (1 << k, s >> k, 1 << (lo2 - LANE_BITS - s_bits))

        lsw = swap_geo(load_swap_k, lo2_load)
        ssw = swap_geo(store_swap_k, lo2_store)
        x_in = (_swap_view(x, rows, s, lo2_load - LANE_BITS, load_swap_k)
                if load_swap_k else x.reshape(grid, P * s, _LANES))
        if store_swap_k:
            oshape = _swap_view(x, rows, s, lo2_store - LANE_BITS,
                                store_swap_k).shape
        else:
            oshape = (grid, P * s, _LANES)
        # ring depth: clamp to the chunk count, then derate until the ring
        # buffers (in + out) fit the VMEM budget -- depth must never turn a
        # compiling kernel into a Mosaic OOM
        slot_bytes = P * s * _LANES * np.dtype(amps.dtype).itemsize
        ring = effective_ring_depth(ring_depth, grid, slot_bytes)
        kernel = _make_dma_kernel(tuple(ops_r), s, tile_bits,
                                  np.dtype(amps.dtype), grid, lsw, ssw,
                                  df=df, ring=ring, local_n=local_n,
                                  df_acc=df_acc)
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(oshape, x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pltpu.SMEM)] +
                     [pl.BlockSpec(memory_space=pltpu.VMEM) for _ in ws],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
            input_output_aliases=aliases,
            interpret=interpret,
            name=name,
        )(x_in, shard_index, *ws)
        return _planes_view(out, P)

    kernel = _make_kernel(
        tuple(ops_r), s_bits, tile_bits, np.dtype(amps.dtype),
        local_n=local_n, df=df, df_acc=df_acc,
        load_swap=(1 << load_swap_k, s >> load_swap_k) if load_swap_k else None,
        store_swap=(1 << store_swap_k, s >> store_swap_k) if store_swap_k else None)

    if kind == "df1":
        # single-tile df call: Mosaic fails to legalize the 4-plane block
        # under a grid (func.return legalization, round-5 find); gridless
        # whole-array VMEM refs compile fine (frame swaps never reach
        # here: a one-tile register has no grid bits to exchange)
        assert not (load_swap_k or store_swap_k)
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pltpu.SMEM)] +
                     [pl.BlockSpec(memory_space=pltpu.VMEM) for _ in ws],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
            input_output_aliases=aliases,
            interpret=interpret,
            name=name,
        )(x, shard_index, *ws)
        return _planes_view(out, P)

    # np.int32 zeros: see _swap_spec (x64 turns a bare 0 into an i64)
    plain = pl.BlockSpec((P * s, _LANES),
                         lambda i: (i, np.int32(0)),
                         memory_space=pltpu.VMEM)
    if load_swap_k:
        x_in = _swap_view(x, rows, s, lo2_load - LANE_BITS, load_swap_k)
        in_spec0 = _swap_spec(s, lo2_load - LANE_BITS, load_swap_k, planes=P)
    else:
        x_in = x
        in_spec0 = plain
    if store_swap_k:
        out_shape = jax.ShapeDtypeStruct(
            _swap_view(x, rows, s, lo2_store - LANE_BITS,
                       store_swap_k).shape, x.dtype)
        out_spec = _swap_spec(s, lo2_store - LANE_BITS, store_swap_k,
                              planes=P)
    else:
        out_shape = jax.ShapeDtypeStruct(x.shape, x.dtype)
        out_spec = plain
    out = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(grid,),
        in_specs=[in_spec0,
                  pl.BlockSpec((1,), lambda i: (np.int32(0),),
                               memory_space=pltpu.SMEM)] +
                 [pl.BlockSpec(w.shape,
                               lambda i, _nd=w.ndim: (np.int32(0),) * _nd,
                               memory_space=pltpu.VMEM) for w in ws],
        out_specs=out_spec,
        # long fused runs accumulate per-gate temporaries past the default
        # 16 MiB scoped-VMEM budget; the physical VMEM is far larger
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024),
        input_output_aliases=aliases,
        interpret=interpret,
        name=name,
    )(x_in, shard_index, *ws)
    return _planes_view(out, P)


#: the implementation jitted under its own name: what anything that has no
#: kernel name to give calls (the AOT compile tests)
_fused_local_run = jax.jit(_fused_local_run_impl,
                           static_argnames=_FUSED_STATIC, donate_argnums=(0,))


@partial(jax.jit, static_argnames=("n", "lo1", "lo2", "k"), donate_argnums=(0,))
def swap_bit_blocks(amps, *, n: int, lo1: int, lo2: int, k: int):
    """Exchange the k-bit index blocks [lo1, lo1+k) and [lo2, lo2+k)
    (lo1 + k <= lo2) of the planar (2, 2^n) state: a pure qubit relabeling
    executed as one XLA transpose. Measured at the elementwise floor
    (2.8 ms at 2^26 f32, tools/microbench) -- switching the two-frame
    execution scheme's frame costs one bandwidth pass.

    This is the single-chip analogue of the reference's swap-to-local
    relocation (QuEST_cpu_distributed.c:1526-1568): instead of moving one
    distributed qubit at a time through pair exchanges, the whole grid-bit
    block swaps with an equal sublane block so gates on high qubits become
    tile-local for the fused Pallas kernel.

    Plane-agnostic: the leading axis may be the planar pair (2, 2^n) or
    the 4-plane double-float layout (4, 2^n) -- the relabeling is pure
    index algebra on the amplitude axis."""
    assert lo1 + k <= lo2 and lo2 + k <= n
    P = amps.shape[0]
    d = 1 << k
    low = 1 << lo1
    mid = 1 << (lo2 - lo1 - k)
    x = amps.reshape(P, -1, d, mid, d, low)
    return x.transpose(0, 1, 4, 3, 2, 5).reshape(P, -1)


class HashableMatrix:
    """Immutable ndarray wrapper usable inside the static ``ops`` tuple."""

    def __init__(self, arr):
        self.arr = np.asarray(arr, dtype=complex)
        self.arr.setflags(write=False)
        self._key = self.arr.tobytes()

    def __getitem__(self, idx):
        return self.arr[idx]

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, HashableMatrix) and self._key == other._key
