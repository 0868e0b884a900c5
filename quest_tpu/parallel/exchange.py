"""shard_map kernels spelling out the reference's distributed protocol in
XLA collectives.

Reference protocol (QuEST_cpu_distributed.c):
  - non-local 1q dense gate: pairwise full-chunk swap over MPI_Isend/Irecv
    (``exchangeStateVectors``, :495-533) then a rank-conditional half-update
    (``getRotAngle``, :260-308; ``statevec_compactUnitaryDistributed``).
  - non-local X class: pure chunk exchange (:1109-1152).
  - diagonal/phase ops: never communicate (phase depends only on index bits).
  - qubit relocation: odd-parity half-chunk exchange
    (``statevec_swapQubitAmps``, :1424-1459).
  - scalar reductions: MPI_Allreduce -> here ``jnp.sum`` on the sharded
    array (XLA emits the psum) or an explicit ``lax.psum`` inside shard_map.

Here each becomes a ``shard_map`` over the 1-D ``amps`` mesh axis with
``lax.ppermute`` as the exchange primitive, riding ICI instead of MPI.
All kernels are pure (amps -> amps), composable under an outer ``jax.jit``,
and handle controls split into *local* controls (index-mask inside the
chunk) and *sharded* controls (device-index predicate -- zero communication,
an improvement over shipping them into the exchange).

Layout (see .mesh): device r of D=2^d holds flat indices [r*C, (r+1)*C);
qubit q local iff q < nl = n-d; sharded qubit q is bit (q-nl) of r.

Plane contract (round 7, the sharded double-float path): the DATA-MOVEMENT
collectives (``dist_permute_bits``, ``dist_swap``'s sharded regimes, the
``dist_apply_x`` chunk permute) are plane-agnostic -- they carry the planar
(2, 2^n) pair or the PRECISION=2 double-float (4, 2^n) f32 layout natively,
which is how per-shard df kernel runs are joined by the same grouped
collectives as f32 plans. The ARITHMETIC kernels (pair exchange's blended
update, diag/parity phases) stay planar: a df state REJOINS to (2, 2^n)
f64 via the exact ``pallas_df.df_join`` before any of them runs -- the
documented hi/lo plane-pair relabeling (both conversions are exact, so the
round trip costs bandwidth, never precision).

Pipelined collectives (round 8): every launch site here accepts a
``pipeline`` depth. At depth ``P > 1`` the per-device chunk is split into
``P`` contiguous power-of-two sub-chunks and the collective is issued as
``P`` independent sub-collectives interleaved with the per-sub-chunk
blend/mask/scatter compute -- the prologue issues slice 0's transfer, the
steady state issues slice k+1 while consuming slice k, and the epilogue
drains (``_pipeline_schedule``). XLA's latency-hiding scheduler can then
run slice k's compute while slice k+1's ``ppermute``/``all_to_all`` is in
flight -- the comm-side twin of the Pallas N-slot DMA ring. Slicing is
always along the amplitude axis with purely elementwise / slice-local
compute per sub-chunk, so the pipelined result is BIT-IDENTICAL to the
monolithic ``P=1`` launch by construction, and the chunk-unit cost model
(:func:`permute_collective_stats`, scheduler journal pricing) is
deliberately blind to the depth: pipelining re-times the same traffic, it
never adds any. Depth resolution: explicit ``pipeline=`` argument, else
the ``QUEST_COMM_PIPELINE`` env default (:func:`comm_pipeline_default`),
then one clamp to the site's slice limit (:func:`effective_comm_pipeline`,
shared with analysis.commcheck exactly like effective_ring_depth is
shared with ringcheck).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .. import telemetry
from jax import shard_map

from ..environment import AMP_AXIS
from ..ops import apply as K
from ..ops.layout import grouped_axes
from .mesh import device_groups, local_qubit_count

__all__ = ["dist_apply_matrix1", "dist_apply_x", "dist_apply_diag_phase",
           "dist_apply_parity_phase", "dist_apply_local_matrix", "dist_swap",
           "dist_permute_bits", "permute_collective_stats",
           "comm_pipeline_default", "comm_pipeline_dcn_default",
           "resolve_pipeline", "resolve_pipeline_dcn",
           "effective_comm_pipeline"]


def _specs(mesh):
    return dict(mesh=mesh, in_specs=P(None, AMP_AXIS), out_specs=P(None, AMP_AXIS))


#: env knob for the default comm-pipeline depth (1 = monolithic launch);
#: overridden per-plan by Circuit.fused(comm_pipeline=) / per-context by
#: explicit_mesh(comm_pipeline=). Deliberately distinct from the
#: scheduler's num_slices ICI/DCN split: num_slices partitions the MESH,
#: the pipeline depth partitions each device's CHUNK.
_PIPE_ENV = "QUEST_COMM_PIPELINE"

#: monolithic until the on-chip kernelprobe sweep picks a better default
#: (tools/kernelprobe.py comm_sweep is the recipe); the emulated-CPU tier-1 mesh
#: cannot measure overlap, so the committed default keeps the exchange
#: lowering byte-identical to round 7.
_DEF_COMM_PIPELINE = 1

_PIPE_ENV_WARNED: set = set()


def comm_pipeline_default() -> int:
    """The env-resolved comm-pipeline depth (warn-once QT206 on a
    malformed ``QUEST_COMM_PIPELINE``, mirroring the ring's QT205)."""
    from ..analysis.diagnostics import parse_env_int
    return parse_env_int(_PIPE_ENV, _DEF_COMM_PIPELINE, minimum=1,
                         code="QT206", noun="pipeline depth",
                         below="is below the monolithic minimum",
                         warned=_PIPE_ENV_WARNED)


def resolve_pipeline(pipeline) -> int:
    """Explicit ``pipeline=`` argument if given, else the env default."""
    return int(pipeline) if pipeline is not None else comm_pipeline_default()


#: per-link-class override (round 15): collectives whose shard bits ride
#: the slow cross-slice DCN link pipeline at this depth instead of the
#: base QUEST_COMM_PIPELINE -- the latency gap between DCN and ICI means
#: the overlap window a DCN sub-collective must fill is deeper. Unset
#: inherits the base depth (the flat, single-tier behaviour).
_PIPE_DCN_ENV = "QUEST_COMM_PIPELINE_DCN"

_PIPE_DCN_ENV_WARNED: set = set()


def comm_pipeline_dcn_default():
    """The env-resolved DCN comm-pipeline depth, or None when
    ``QUEST_COMM_PIPELINE_DCN`` is unset (inherit the base depth).
    Malformed values warn once via QT210, mirroring the base knob's
    QT206."""
    import os
    if not os.environ.get(_PIPE_DCN_ENV, "").strip():
        return None
    from ..analysis.diagnostics import parse_env_int
    return parse_env_int(_PIPE_DCN_ENV, 1, minimum=1,
                         code="QT210", noun="DCN pipeline depth",
                         below="is below the monolithic minimum",
                         warned=_PIPE_DCN_ENV_WARNED)


def resolve_pipeline_dcn(pipeline_dcn, pipeline=None) -> int:
    """Depth for a DCN-riding collective: the explicit ``pipeline_dcn``
    argument, else the ``QUEST_COMM_PIPELINE_DCN`` env, else fall all the
    way back to the base (ICI) resolution of ``pipeline``."""
    if pipeline_dcn is not None:
        return int(pipeline_dcn)
    env = comm_pipeline_dcn_default()
    if env is not None:
        return env
    return resolve_pipeline(pipeline)


def effective_comm_pipeline(depth: int, limit: int, *,
                            site: str = "exchange") -> int:
    """The ONE clamp from a requested depth to what a launch site can
    slice: the largest power of two that is neither above the request nor
    above ``limit`` (the site's slice count ceiling -- per-device columns
    for the elementwise kernels, the grouped-view minor axis for
    all_to_all / odd-parity sends). Pure -- no diagnostics are emitted
    here; analysis.commcheck re-runs this clamp and reports QT209 when it
    bites, exactly as ringcheck shares pallas_gates.effective_ring_depth.
    ``site`` only labels commcheck findings."""
    depth = max(1, int(depth))
    depth = 1 << (depth.bit_length() - 1)      # round down to power of two
    limit = max(1, int(limit))
    limit = 1 << (limit.bit_length() - 1)
    return min(depth, limit)


def _pipeline_schedule(nslices, transfer, compute, src=None):
    """Emit the software-pipelined transfer/compute interleaving for
    ``nslices`` sub-chunks and return the per-slice outputs in order.

    ``transfer(j)`` issues sub-chunk j's collective; ``compute(k, landed)``
    consumes the landed transfer that output slice k needs, which is
    transfer ``src(k)`` (identity when the collective does not permute the
    slice index; dist_apply_x's local hi-bit flips make it an XOR). The
    emission order is the classic three phases -- prologue issues slice 0's
    transfer; the steady state issues transfer k+1 BEFORE computing slice k
    so XLA's latency-hiding scheduler always has the next collective in
    flight behind the current blend; the epilogue drains the last transfer
    into the last compute. Every transfer is issued exactly once and
    consumed exactly once (analysis.commcheck proves the QT207/QT208
    hazard-freedom of this exact schedule)."""
    if src is None:
        src = lambda k: k
    inflight = {}

    def ensure(j):
        if j not in inflight:
            inflight[j] = transfer(j)

    ensure(src(0))                       # prologue: slice 0's transfer
    outs = []
    for k in range(nslices):             # steady state + epilogue
        if k + 1 < nslices:
            ensure(src(k + 1))           # next transfer in flight ...
        outs.append(compute(k, inflight.pop(src(k))))  # ... behind compute k
    assert not inflight                  # epilogue drained
    return outs


def _launch(kernel, mesh, amps, *, kind="collective", pipeline=1):
    """The one launch point for every collective kernel here, threaded
    through the resilience guard (site ``exchange.collective``): a direct
    call when no fault plan is installed; injected transient comm faults
    retry under the backoff policy and exhaustion fails closed with a
    typed QuESTRetryError (quest_tpu.resilience.guard.collective). With
    ``QUEST_WATCHDOG_MS`` armed the launch is deadline-bounded -- a hung
    collective raises a typed QuESTHangError instead of blocking forever
    -- EXCEPT under jit tracing: jax trace state is thread-local, so a
    traced launch must stay on the tracing thread (the compiled
    execution is covered by the engine-dispatch watchdog instead).

    Retry-vs-pipeline contract (round 8): the guard wraps the WHOLE
    shard_map closure, so at pipeline depth > 1 a transient fault replays
    the ENTIRE multi-slice launch from the untouched input -- never a
    resume mid-slice. The kernels are pure (amps -> amps, no donation at
    this boundary), which is what makes the whole-launch replay
    bit-identical.

    ``kind``/``pipeline`` label telemetry: the effective depth lands in
    the ``comm_pipeline_depth`` gauge, and eager (non-traced) launches are
    wall-timed into the ``comm_collective_ms{kind,pipeline}`` histogram
    (traced launches fuse into an enclosing jit, so there is no
    per-collective wall time to observe)."""
    import time

    import jax

    from ..resilience import guard
    telemetry.set_gauge("comm_pipeline_depth", int(pipeline))
    run = lambda: shard_map(kernel, **_specs(mesh))(amps)
    traced = isinstance(amps, jax.core.Tracer)
    if traced or not telemetry.enabled():
        return guard.collective(run, watched=not traced)
    t0 = time.perf_counter()
    out = guard.collective(run, watched=True)
    jax.block_until_ready(out)
    telemetry.observe("comm_collective_ms",
                      (time.perf_counter() - t0) * 1e3,
                      kind=kind, pipeline=int(pipeline))
    return out


def _rank_bit(r, q, nl):
    return (r >> (q - nl)) & 1


def _ctrl_pred(r, shard_controls, shard_states, nl):
    """Device-index predicate for sharded controls (comm-free)."""
    pred = jnp.bool_(True)
    for c, s in zip(shard_controls, shard_states):
        pred = jnp.logical_and(pred, _rank_bit(r, c, nl) == s)
    return pred


def _apply_local_ctrl_mask(own, new, nl, local_controls, local_states,
                           offset=0):
    """new where all local controls match, else own (flat-iota bit mask).

    ``offset`` is the in-chunk column index of ``own[:, 0]`` -- 0 for a
    whole-chunk call, ``k * slice_width`` when a pipelined launch masks
    sub-chunk k (the control bits are tested on the GLOBAL in-chunk index,
    so a sliced mask composes bit-identically with the monolithic one).

    This was a grouped-view ``told.at[idx].set(new[idx])`` until round 6:
    that scatter form MISCOMPILES when two shard_map kernels compose under
    one jit on this container's jax (batched-relocation layouts surfaced
    it: eager and single-kernel jit agree with the numpy oracle, two
    chained kernels under jit corrupt exactly the control-masked half).
    The elementwise select lowers to a fused where with identical traffic
    and is immune to the scatter fusion."""
    if not local_controls:
        return new
    j = lax.iota(jnp.int32, own.shape[1]) + offset
    ok = jnp.ones(own.shape[1], bool)
    for c, s in zip(local_controls, local_states):
        ok = jnp.logical_and(ok, ((j >> c) & 1) == s)
    return jnp.where(ok[None, :], new, own)


def _split_controls(controls, states, nl):
    states = tuple(states) if states else (1,) * len(controls)
    lc = [(c, s) for c, s in zip(controls, states) if c < nl]
    sc = [(c, s) for c, s in zip(controls, states) if c >= nl]
    return ([c for c, _ in lc], [s for _, s in lc],
            [c for c, _ in sc], [s for _, s in sc])


# ---------------------------------------------------------------------------
# 1-qubit dense gate (compactUnitary / unitary class)
# ---------------------------------------------------------------------------

def dist_apply_matrix1(amps, matrix, *, n: int, target: int,
                       controls: tuple[int, ...] = (),
                       control_states: tuple[int, ...] = (),
                       conj: bool = False, mesh: Mesh, pipeline=None):
    """U (planar (2,2,2)) on ``target``; the explicit-exchange analogue of
    ops.apply.apply_matrix for one target qubit.

    Sharded target: ``ppermute`` pair exchange + blended update --
    identical traffic to the reference's exchangeStateVectors scheme. At
    ``pipeline`` depth P > 1 the chunk is split into P column slices and
    each slice's exchange is issued ahead of the previous slice's blend
    (the blend, control mask and rank predicate are all elementwise, so
    the sliced launch is bit-identical to the monolithic one). Local
    target with (possibly) sharded controls: no communication.
    """
    nl = local_qubit_count(n, mesh)
    eff, kind = 1, "local_matrix"
    if target >= nl:
        telemetry.inc("exchange_calls_total", kind="pair_exchange")
        eff = effective_comm_pipeline(resolve_pipeline(pipeline), 1 << nl,
                                      site="pair_exchange")
        kind = "pair_exchange"
    lc, ls, sc, ss = _split_controls(controls, control_states, nl)
    mr, mi = matrix[0], matrix[1]
    if conj:
        mi = -mi

    def kernel(chunk):
        own = chunk
        r = lax.axis_index(AMP_AXIS)
        if target < nl:
            new = K.apply_matrix(own, matrix, n=nl, targets=(target,),
                                 controls=tuple(lc), control_states=tuple(ls),
                                 conj=conj)
        else:
            bitpos = target - nl
            size = mesh.shape[AMP_AXIS]
            perm = [(i, i ^ (1 << bitpos)) for i in range(size)]
            b = _rank_bit(r, target, nl)
            # new_amp(bit=b) = m[b,b] * own + m[b,1-b] * pair
            m_bb_r, m_bb_i = mr[b, b], mi[b, b]
            m_bo_r, m_bo_i = mr[b, 1 - b], mi[b, 1 - b]

            def blend(own_s, pair_s, off):
                re = (m_bb_r * own_s[0] - m_bb_i * own_s[1]
                      + m_bo_r * pair_s[0] - m_bo_i * pair_s[1])
                im = (m_bb_r * own_s[1] + m_bb_i * own_s[0]
                      + m_bo_r * pair_s[1] + m_bo_i * pair_s[0])
                return _apply_local_ctrl_mask(own_s, jnp.stack([re, im]),
                                              nl, lc, ls, offset=off)

            if eff == 1:
                pair = lax.ppermute(own, AMP_AXIS, perm)
                new = blend(own, pair, 0)
            else:
                s = own.shape[1] // eff

                def sl(k):
                    return lax.slice_in_dim(own, k * s, (k + 1) * s, axis=1)

                new = jnp.concatenate(_pipeline_schedule(
                    eff,
                    lambda j: lax.ppermute(sl(j), AMP_AXIS, perm),
                    lambda k, pair_s: blend(sl(k), pair_s, k * s)), axis=1)
        if sc:
            new = jnp.where(_ctrl_pred(r, sc, ss, nl), new, own)
        return new

    return _launch(kernel, mesh, amps, kind=kind, pipeline=eff)


def dist_apply_local_matrix(amps, matrix, *, n: int, targets: tuple[int, ...],
                            controls: tuple[int, ...] = (),
                            control_states: tuple[int, ...] = (),
                            conj: bool = False, mesh: Mesh, pipeline=None):
    """Dense gate whose targets are ALL local: embarrassingly parallel
    shard_map around the single-chunk kernel (the reference's *Local fast
    path, QuEST_cpu_distributed.c:372-377) -- sharded controls become a
    comm-free device-index predicate instead of participating in the kernel.

    ``pipeline`` is accepted for launch-site uniformity but the kernel is
    comm-free and its GEMM gathers across the whole chunk, so the launch
    is always monolithic (there is no transfer to overlap).
    """
    nl = local_qubit_count(n, mesh)
    assert all(t < nl for t in targets)
    lc, ls, sc, ss = _split_controls(controls, control_states, nl)

    def kernel(chunk):
        own = chunk
        new = K.apply_matrix(own, matrix, n=nl, targets=tuple(targets),
                             controls=tuple(lc), control_states=tuple(ls),
                             conj=conj)
        if sc:
            r = lax.axis_index(AMP_AXIS)
            new = jnp.where(_ctrl_pred(r, sc, ss, nl), new, own)
        return new

    return _launch(kernel, mesh, amps, kind="local_matrix", pipeline=1)


# ---------------------------------------------------------------------------
# X class (amplitude permutation)
# ---------------------------------------------------------------------------

def dist_apply_x(amps, *, n: int, targets: tuple[int, ...],
                 controls: tuple[int, ...] = (),
                 control_states: tuple[int, ...] = (),
                 mesh: Mesh, pipeline=None):
    """Multi-controlled multi-target NOT: sharded target bits become one
    ``ppermute`` (rank-index XOR), local target bits an in-chunk flip
    (reference: ctrl-skip exchange, QuEST_cpu_distributed.c:1109-1152).

    Pipelined form (depth P > 1, sharded targets present): the chunk is
    split into P column slices and each slice is exchanged independently.
    The local target bits split at the slice width -- bits at or above
    log2(slice) select WHICH transferred slice feeds output slice k (an
    XOR of the slice index, the ``src`` hook of ``_pipeline_schedule``)
    while bits below it flip within the slice -- so the permutation the
    monolithic kernel applies in one piece is reproduced slice-exactly.
    """
    nl = local_qubit_count(n, mesh)
    lc, ls, sc, ss = _split_controls(controls, control_states, nl)
    local_t = tuple(t for t in targets if t < nl)
    shard_t = tuple(t for t in targets if t >= nl)
    eff = 1
    if shard_t:
        telemetry.inc("exchange_calls_total", kind="x_permute")
        eff = effective_comm_pipeline(resolve_pipeline(pipeline), 1 << nl,
                                      site="x_permute")

    def kernel(chunk):
        own = chunk
        r = lax.axis_index(AMP_AXIS)
        if eff == 1 or not shard_t:
            new = own
            if shard_t:
                mask = 0
                for t in shard_t:
                    mask |= 1 << (t - nl)
                size = mesh.shape[AMP_AXIS]
                perm = [(i, i ^ mask) for i in range(size)]
                new = lax.ppermute(new, AMP_AXIS, perm)
            if local_t:
                new = K.apply_x_class(new, n=nl, targets=local_t)
            new = _apply_local_ctrl_mask(own, new, nl, lc, ls)
        else:
            mask = 0
            for t in shard_t:
                mask |= 1 << (t - nl)
            size = mesh.shape[AMP_AXIS]
            perm = [(i, i ^ mask) for i in range(size)]
            s = own.shape[1] // eff
            s_bits = s.bit_length() - 1
            lo_t = tuple(t for t in local_t if t < s_bits)
            hi_mask = 0
            for t in local_t:
                if t >= s_bits:
                    hi_mask |= 1 << (t - s_bits)

            def transfer(j):
                return lax.ppermute(
                    lax.slice_in_dim(own, j * s, (j + 1) * s, axis=1),
                    AMP_AXIS, perm)

            def compute(k, recv):
                new_s = (K.apply_x_class(recv, n=s_bits, targets=lo_t)
                         if lo_t else recv)
                own_s = lax.slice_in_dim(own, k * s, (k + 1) * s, axis=1)
                return _apply_local_ctrl_mask(own_s, new_s, nl, lc, ls,
                                              offset=k * s)

            new = jnp.concatenate(
                _pipeline_schedule(eff, transfer, compute,
                                   src=lambda k: k ^ hi_mask), axis=1)
        if sc:
            new = jnp.where(_ctrl_pred(r, sc, ss, nl), new, own)
        return new

    return _launch(kernel, mesh, amps,
                   kind="x_permute" if shard_t else "local_x", pipeline=eff)


# ---------------------------------------------------------------------------
# whole-layout bit permutation (one-collective reconciliation)
# ---------------------------------------------------------------------------

def _permute_decompose(n: int, source, nl: int):
    """Split the bit permutation ``new_bit[q] = old_bit[source[q]]`` into
    the three machine moves: a device-index relabel (shard->shard bits), a
    grouped all-to-all (shard<->local crossings), and a free local
    transpose. Returns (rho_src, Q_c, L_in, L_out, dest) where ``rho_src``
    maps shard position -> old shard position it takes its bit from (None
    when no relabel is needed), ``Q_c`` lists the shard positions fed from
    local bits, ``L_in[k]``/``L_out[k]`` the outgoing/incoming local bit of
    crossing ``k``, and ``dest`` the inverse permutation."""
    source = tuple(source)
    assert sorted(source) == list(range(n)), source
    dest = [0] * n
    for q, p in enumerate(source):
        dest[p] = q
    shard = range(nl, n)
    Q_c = [q for q in shard if source[q] < nl]
    P_out = [p for p in shard if dest[p] < nl]
    rho_src = None
    holds = {q: q for q in shard}  # device position -> original bit it holds
    if any(source[q] >= nl and source[q] != q for q in shard):
        # shard->shard bits displaced: one ppermute relabel puts each at its
        # home device-bit position; the outgoing (P_out) bits park at the
        # Q_c positions so the residual crossing is position-aligned
        rho_src = {q: source[q] for q in shard if source[q] >= nl}
        for q, p in zip(sorted(Q_c), sorted(P_out)):
            rho_src[q] = p
        holds = dict(rho_src)
    L_in = [source[q] for q in sorted(Q_c)]
    L_out = [dest[holds[q]] for q in sorted(Q_c)]
    return rho_src, sorted(Q_c), L_in, L_out, dest


def permute_collective_stats(n: int, source, mesh: Mesh,
                             unit_scale: float = 1.0) -> dict:
    """Trace-free cost model of :func:`dist_permute_bits`: number of
    collectives and chunk-units ((send+recv)/half-chunk pairs) it will pay.
    A relabel ppermute re-routes the full chunk (2 units, like a rank
    permute); the grouped all-to-all over m crossing bits moves
    (2^m - 1)/2^m of the chunk each way (2*(1 - 2^-m) units: m=1 is exactly
    the odd-parity half-exchange's 1 unit).

    ``unit_scale`` restates the units for wider state layouts: 1 is the
    planar f32 pair; the double-precision layouts -- planar f64, or the
    double-float 4-plane f32 state the sharded PRECISION=2 fast path
    permutes between per-shard kernel runs -- move twice the bytes per
    chunk and price at ``unit_scale=2`` (the df 2x chunk-unit accounting,
    scheduler.DistributedScheduler.apply_frame_permute)."""
    nl = local_qubit_count(n, mesh)
    rho_src, Q_c, _, _, _ = _permute_decompose(n, source, nl)
    m = len(Q_c)
    units = (2.0 if rho_src is not None else 0.0)
    units += 2.0 * (1.0 - 0.5 ** m) if m else 0.0
    return {"relabel_ppermute": rho_src is not None, "crossing_bits": m,
            "chunk_units": units * unit_scale,
            "collectives": int(rho_src is not None) + int(m > 0)}


def dist_permute_bits(amps, *, n: int, source, mesh: Mesh, pipeline=None):
    """Apply an arbitrary bit permutation of the physical index in at most
    two collectives: ``new_bit[q] = old_bit[source[q]]``.

    This is the deferred scheduler's reconciliation primitive (round 5):
    instead of restoring the identity layout one odd-parity pair swap per
    displaced qubit (the reference's swapQubitAmps unit,
    QuEST_cpu_distributed.c:1443-1459), the whole permutation runs as

    - one ``ppermute`` device relabel IF any shard bit moves to another
      shard position (pure re-route, no local data motion), then
    - one grouped ``lax.all_to_all`` carrying ALL shard<->local crossings
      at once (each device sends (2^m-1)/2^m of its chunk for m crossing
      bits -- vs m full half-exchanges for m sequential swaps), then
    - one free in-chunk transpose for the local->local remainder.

    Plane-agnostic (round 7): ``amps`` may carry any leading plane count --
    the planar (2, 2^n) pair or the double-float (4, 2^n) layout the
    sharded PRECISION=2 fast path permutes between per-shard kernel runs.
    The permutation is pure data movement on the amplitude axis, so all
    P planes ride the same relabel/all-to-all/transpose natively.

    Pipelined form (depth > 1, crossing bits present): the grouped view's
    residual minor axis (the 2^(nl-m) columns every crossing piece keeps
    in place) is split into ``pipeline`` slices and each slice ships as
    its own grouped ``all_to_all`` -- the all-to-all routing depends only
    on the major (piece) axis, so per-slice collectives concatenate back
    bit-exactly, and the df 4-plane layout rides the sliced collective as
    natively as the monolithic one (the planes axis is untouched). The
    device-relabel ppermute (a pure re-route) stays monolithic.
    """
    nl = local_qubit_count(n, mesh)
    source = tuple(source)
    if all(source[q] == q for q in range(n)):
        return amps
    telemetry.inc("exchange_calls_total", kind="grouped_permute")
    rho_src, Q_c, L_in, L_out, dest = _permute_decompose(n, source, nl)
    m = len(Q_c)
    P = amps.shape[0]
    size = mesh.shape[AMP_AXIS] if mesh is not None and mesh.size > 1 else 1
    eff = (effective_comm_pipeline(resolve_pipeline(pipeline),
                                   1 << (nl - m), site="grouped_permute")
           if m else 1)

    if rho_src is not None:
        def relabel(r: int) -> int:
            out = 0
            for q, p in rho_src.items():
                out |= ((r >> (p - nl)) & 1) << (q - nl)
            return out

        perm = [(r, relabel(r)) for r in range(size)]

        def relabel_kernel(chunk):
            return lax.ppermute(chunk, AMP_AXIS, perm)

        amps = shard_map(relabel_kernel, **_specs(mesh))(amps)

    groups = None
    if m:
        groups = device_groups(size, sum(1 << (q - nl) for q in Q_c))

    def kernel(chunk):
        # grouped view: axis 0 = the P planes (re/im, or the df 4-plane
        # stack), then bits nl-1 .. 0 (bit b at axis 1 + (nl-1-b))
        t = chunk.reshape((P,) + (2,) * nl)

        def ax(b):
            return 1 + (nl - 1 - b)

        if m:
            front = [ax(b) for b in reversed(L_in)]
            fset = set(front)
            rest = [a for a in range(1, nl + 1) if a not in fset]
            t = t.transpose(front + [0] + rest)
            t = t.reshape((1 << m, P) + (2,) * len(rest))
            # piece j (chunk bits at L_in spell j) -> group member whose
            # device bits at Q_c spell j; received concat index j' = the
            # sender's Q_c device bits = the incoming values for L_out
            if eff == 1:
                t = lax.all_to_all(t, AMP_AXIS, 0, 0,
                                   axis_index_groups=groups)
            else:
                # routing depends only on the piece (major) axis: slicing
                # the residual minor axis into eff independent grouped
                # all_to_alls ships the same bytes to the same peers,
                # just in overlap-schedulable sub-collectives
                R = 1 << (nl - m)
                sR = R // eff
                t2 = t.reshape((1 << m, P, R))
                t2 = jnp.concatenate(_pipeline_schedule(
                    eff,
                    lambda j: lax.all_to_all(
                        lax.slice_in_dim(t2, j * sR, (j + 1) * sR, axis=2),
                        AMP_AXIS, 0, 0, axis_index_groups=groups),
                    lambda k, got: got), axis=2)
                t = t2.reshape((1 << m, P) + (2,) * len(rest))
            t = t.reshape((2,) * m + (P,) + (2,) * len(rest))
            src_axis = {}
            for k in range(m):
                src_axis[L_out[k]] = m - 1 - k
            rest_bits = [nl - 1 - (a - 1) for a in rest]
            for i, b in enumerate(rest_bits):
                src_axis[dest[b]] = m + 1 + i
            perm_axes = [m] + [src_axis[u] for u in range(nl - 1, -1, -1)]
            t = t.transpose(perm_axes)
        else:
            # no crossings: only the local->local remainder moves
            src_axis = {dest[b]: ax(b) for b in range(nl)}
            t = t.transpose([0] + [src_axis[u] for u in range(nl - 1, -1, -1)])
        return t.reshape(P, -1)

    if mesh is None or mesh.size == 1:
        assert m == 0 and rho_src is None
        return kernel(amps)
    return _launch(kernel, mesh, amps, kind="grouped_permute", pipeline=eff)

def dist_apply_diag_phase(amps, diag, *, n: int, targets: tuple[int, ...],
                          controls: tuple[int, ...] = (),
                          control_states: tuple[int, ...] = (),
                          conj: bool = False, mesh: Mesh, pipeline=None):
    """diag (planar (2, 2^t)) applied to ``targets``; entry index bit k is
    targets[k]'s bit. Phases depend only on index bits, so sharded qubits
    contribute a per-device scalar offset into the diagonal -- no traffic at
    all (the reference's phase kernels are likewise exchange-free,
    QuEST_cpu.c:3235-3285). At ``pipeline`` depth P > 1 the (comm-free,
    purely elementwise) phase is emitted in P column slices so XLA can
    interleave it with any in-flight neighbouring collective."""
    nl = local_qubit_count(n, mesh)
    lc, ls, sc, ss = _split_controls(controls, control_states, nl)
    eff = effective_comm_pipeline(resolve_pipeline(pipeline), 1 << nl,
                                  site="diag_phase")
    dr, di = diag[0], diag[1]
    if conj:
        di = -di

    def kernel(chunk):
        own = chunk
        r = lax.axis_index(AMP_AXIS)

        def phase(own_s, off):
            j = lax.iota(jnp.int32, own_s.shape[1]) + off
            idx = jnp.zeros((), jnp.int32)
            for k, t in enumerate(targets):
                if t < nl:
                    bit = (j >> t) & 1
                else:
                    bit = _rank_bit(r, t, nl).astype(jnp.int32)
                idx = idx + (bit << k)
            fr, fi = dr[idx], di[idx]
            re = fr * own_s[0] - fi * own_s[1]
            im = fr * own_s[1] + fi * own_s[0]
            return _apply_local_ctrl_mask(own_s, jnp.stack([re, im]),
                                          nl, lc, ls, offset=off)

        if eff == 1:
            new = phase(own, 0)
        else:
            s = own.shape[1] // eff
            new = jnp.concatenate(
                [phase(lax.slice_in_dim(own, k * s, (k + 1) * s, axis=1),
                       k * s) for k in range(eff)], axis=1)
        if sc:
            new = jnp.where(_ctrl_pred(r, sc, ss, nl), new, own)
        return new

    return _launch(kernel, mesh, amps, kind="diag_phase", pipeline=eff)


def dist_apply_parity_phase(amps, theta, *, n: int, qubits: tuple[int, ...],
                            controls: tuple[int, ...] = (),
                            control_states: tuple[int, ...] = (),
                            conj: bool = False, mesh: Mesh, pipeline=None):
    """exp(-i theta/2 Z x...x Z): comm-free; sharded qubits fold their bit
    into the device-index parity (reference mask-parity kernel
    QuEST_cpu.c:3235-3285 -- likewise exchange-free). At ``pipeline``
    depth P > 1 the elementwise sign flip is emitted in P column slices,
    as :func:`dist_apply_diag_phase`."""
    nl = local_qubit_count(n, mesh)
    lc, ls, sc, ss = _split_controls(controls, control_states, nl)
    eff = effective_comm_pipeline(resolve_pipeline(pipeline), 1 << nl,
                                  site="parity_phase")
    local_q = [q for q in qubits if q < nl]
    shard_q = [q for q in qubits if q >= nl]

    def kernel(chunk):
        own = chunk
        r = lax.axis_index(AMP_AXIS)

        def phase(own_s, off):
            j = lax.iota(jnp.int32, own_s.shape[1]) + off
            par = jnp.zeros((), jnp.int32)
            for q in local_q:
                par = par ^ ((j >> q) & 1)
            for q in shard_q:
                par = par ^ _rank_bit(r, q, nl).astype(jnp.int32)
            sign = (1 - 2 * par).astype(own_s.dtype)
            th = jnp.asarray(-theta if conj else theta, dtype=own_s.dtype)
            fr, fi = jnp.cos(th / 2), -jnp.sin(th / 2) * sign
            re = fr * own_s[0] - fi * own_s[1]
            im = fr * own_s[1] + fi * own_s[0]
            return _apply_local_ctrl_mask(own_s, jnp.stack([re, im]),
                                          nl, lc, ls, offset=off)

        if eff == 1:
            new = phase(own, 0)
        else:
            s = own.shape[1] // eff
            new = jnp.concatenate(
                [phase(lax.slice_in_dim(own, k * s, (k + 1) * s, axis=1),
                       k * s) for k in range(eff)], axis=1)
        if sc:
            new = jnp.where(_ctrl_pred(r, sc, ss, nl), new, own)
        return new

    return _launch(kernel, mesh, amps, kind="parity_phase", pipeline=eff)


# ---------------------------------------------------------------------------
# qubit-amplitude swap (the relocation primitive)
# ---------------------------------------------------------------------------

def dist_swap(amps, *, n: int, qb1: int, qb2: int, mesh: Mesh,
              pipeline=None):
    """SWAP(qb1, qb2). Three regimes, as the reference (:1424-1459):

    - both local: in-chunk axis transposition;
    - both sharded: pure device-index bit swap (one ppermute);
    - mixed: odd-parity half-chunk exchange -- each device sends the half of
      its chunk whose local bit differs from its device bit, halving traffic
      vs a full exchange.

    The sharded regimes are pure data movement and carry any leading plane
    count (planar pair or the df 4-plane layout); the both-local regime
    routes through the planar apply_swap kernel and takes (2, N) only.

    Pipelined form (depth P > 1): the both-sharded ppermute slices the
    chunk columns; the odd-parity exchange slices the grouped view's
    MAJOR axis (the 2^(nl-1-lo) blocks above the swapped local bit), so
    each slice's send/recv/reassemble is independent and the per-slice
    stacks concatenate back bit-exactly.
    """
    nl = local_qubit_count(n, mesh)
    lo, hi = min(qb1, qb2), max(qb1, qb2)
    eff, kind = 1, "swap_local"
    if hi >= nl:
        kind = "swap_rank_permute" if lo >= nl else "swap_odd_parity"
        telemetry.inc("exchange_calls_total", kind=kind)
        limit = (1 << nl) if lo >= nl else (1 << (nl - 1 - lo))
        eff = effective_comm_pipeline(resolve_pipeline(pipeline), limit,
                                      site=kind)

    def kernel(chunk):
        own = chunk
        r = lax.axis_index(AMP_AXIS)
        size = mesh.shape[AMP_AXIS]
        if hi < nl:  # both local
            return K.apply_swap(own, n=nl, qb1=lo, qb2=hi)
        if lo >= nl:  # both sharded: permute device indices
            b1, b2 = lo - nl, hi - nl

            def swap_bits(i):
                x, y = (i >> b1) & 1, (i >> b2) & 1
                return i ^ (((x ^ y) << b1) | ((x ^ y) << b2))

            perm = [(i, swap_bits(i)) for i in range(size)]
            if eff == 1:
                return lax.ppermute(own, AMP_AXIS, perm)
            s = own.shape[1] // eff
            return jnp.concatenate(_pipeline_schedule(
                eff,
                lambda j: lax.ppermute(
                    lax.slice_in_dim(own, j * s, (j + 1) * s, axis=1),
                    AMP_AXIS, perm),
                lambda k, recv: recv), axis=1)

        # mixed: lo local, hi sharded
        bitpos = hi - nl
        perm = [(i, i ^ (1 << bitpos)) for i in range(size)]
        b = _rank_bit(r, hi, nl)  # device's bit of qb2
        # grouped view over the local qubit: (P, A, 2, B), axis 2 = lo's bit
        shape, axis_of = grouped_axes(nl, (lo,))
        gshape = (own.shape[0],) + shape
        ax = axis_of[lo] + 1
        t = own.reshape(gshape)
        sub0 = lax.index_in_dim(t, 0, axis=ax, keepdims=False)
        sub1 = lax.index_in_dim(t, 1, axis=ax, keepdims=False)
        send = jnp.where(b == 0, sub1, sub0)       # local bit != device bit
        keep = jnp.where(b == 0, sub0, sub1)

        def reassemble(send_s, keep_s):
            recv = lax.ppermute(send_s, AMP_AXIS, perm)  # partner's half
            # slot (local bit == b) keeps own, other slot gets recv
            new0 = jnp.where(b == 0, keep_s, recv)
            new1 = jnp.where(b == 0, recv, keep_s)
            return jnp.stack([new0, new1], axis=ax)

        if eff == 1:
            new = reassemble(send, keep)
        else:
            # slice the A (major-block) axis of the (P, A, B) halves; each
            # sub-block's exchange + reassembly is independent
            sA = send.shape[1] // eff

            def sl(x, k):
                return lax.slice_in_dim(x, k * sA, (k + 1) * sA, axis=1)

            new = jnp.concatenate(_pipeline_schedule(
                eff,
                lambda j: lax.ppermute(sl(send, j), AMP_AXIS, perm),
                lambda k, recv: jnp.stack(
                    [jnp.where(b == 0, sl(keep, k), recv),
                     jnp.where(b == 0, recv, sl(keep, k))], axis=ax)),
                axis=1)
        return new.reshape(own.shape)

    return _launch(kernel, mesh, amps, kind=kind, pipeline=eff)
