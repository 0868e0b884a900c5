"""Benchmark: gate-ops/sec on an N-qubit state-vector (BASELINE.json metric).

Runs the same pseudo-random Clifford+T layer circuit as __graft_entry__
(H/T/Rz/Rx layers + CNOT ladders + long-range CZ) with trace-time gate
fusion (quest_tpu/fusion.py), on the default JAX backend (the real TPU chip
when run by the driver).

Artifact chain (round 6; VERDICT r5 ask #1 -- BENCH_r05.json arrived with
``parsed: null`` because the giant single line truncated in the driver's
tail window):

- stdout's FINAL line is a COMPACT (<= 1 KB) headline JSON:
  {"metric", "value", "unit", "vs_baseline", "roofline": <one-line
  summary>, "detail_file": "BENCH_DETAIL.json"} -- always parseable, never
  truncatable.
- the full per-config detail (every field previously embedded in the giant
  line) plus a :mod:`quest_tpu.telemetry` snapshot (pass counts, comm
  chunk-units by kind, engine-fallback counters, Mosaic compile seconds)
  is written to ``BENCH_DETAIL.json`` next to this file and committed.
- sub-configs running in budgeted subprocesses print their FULL config
  JSON (``--emit full``) for the parent to collect; only the top-level
  invocation emits the headline + detail file.

vs_baseline compares against the reference QuEST (/root/reference) compiled
-O3 -DMULTITHREADED=1 and timed on this host's CPU with the identical circuit
shape (tools/ref_bench.c); measured 2026-07-29 on the 1-core build host:

    qubits->gates/sec: {20: 422.99, 24: 23.42, 26: 5.86}

(The reference cannot run its CUDA backend here and cannot combine
CUDA with MPI at all -- QuEST/CMakeLists.txt:64-68 -- so host CPU is the
available anchor.)

Timing methodology: on the shared remote chip of rounds 1-5,
``block_until_ready`` returned before the device work had drained (observed
"42 TB/s" for an elementwise pass), so the timed region ends with a
1-element host readback, which cannot complete until the whole
donated-buffer chain has executed. Rep count amortises the readback
round-trip. Whether the sealed chip machine still needs this is for the
benchmark PR to re-measure.

No silent CPU: without ``--smoke`` a run that finds no TPU exits non-zero
(``main``); the ``bench_*`` functions themselves stay callable on the CPU
for tier-1. One process per chip: the no-``--config`` run and the configs
whose precision is fixed at import run as child processes of a parent that
never initialises a JAX backend, and a failed child makes the exit code
non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

#: reference QuEST gates/sec on this host (see module docstring; 28q
#: measured 2026-07-31, 1 rep of the depth-8 circuit = ~10.5 min)
REF_GATES_PER_SEC = {20: 422.99, 24: 23.42, 26: 5.86, 28: 0.54}

#: reference QuEST 14q density channel-ops/sec on this host
#: (tools/ref_bench.c --density 14 5; 1-core -O3 -DMULTITHREADED=1 build
#: -- kernels timed: densmatr_mixDepolarisingLocal QuEST_cpu.c:137-185
#: and the all-arity Kraus superoperator path QuEST_common.c:581-638).
#: TWO anchors, one per bench circuit (VERDICT r4 weak #4 / ask #6: the
#: round-4 circuit added a 3-target mixMultiQubitKrausMap whose 6-qubit
#: superoperator sweep dominates the reference's step, moving the anchor
#: 0.93 -> 0.20; both circuits are timed so multiples stay comparable
#: across rounds):
#:   "r3" = the 10-op round-3 circuit (anchor 0.93, measured 2026-07-30)
#:   "r4" = the 11-op circuit incl. krausn (anchor 0.20, measured 2026-07-31)
REF_DENSITY_CHANNEL_OPS_PER_SEC = {(14, "r3"): 0.93, (14, "r4"): 0.20}


def _ring_depth() -> int:
    from quest_tpu.ops.pallas_gates import ring_depth_default
    return ring_depth_default()


def build_circuit(n: int, depth: int):
    from quest_tpu.circuits import Circuit
    from __graft_entry__ import _random_layers

    circ = Circuit(n)
    _random_layers(circ, n, depth)
    return circ


def serving_ansatz(n: int, depth: int, values: dict | None = None):
    """The serve_20q VQE-style ansatz -- shared by bench_serving and the
    static-analysis smoke specs. By default every rotation is a runtime
    Param; passing ``values`` (angle-name -> float) bakes the angles in
    instead, producing the CONCRETE structure-identical twin the round-18
    whole-request chaining smoke lowers through ``compiled_request``
    (tape slicing replays concrete entries; value slots need the
    parameterized route)."""
    from quest_tpu.circuits import Circuit
    from quest_tpu.engine import P

    def angle(name):
        return P(name) if values is None else float(values[name])

    circ = Circuit(n)
    for layer in range(depth):
        for q in range(n):
            circ.rotateZ(q, angle(f"a{layer}_{q}"))
            circ.rotateX(q, angle(f"b{layer}_{q}"))
        for q in range(layer % 2, n - 1, 2):
            circ.controlledNot(q, q + 1)
        circ.controlledPhaseFlip(0, n - 1)
    return circ


def trace_phase_stats(trs: list) -> dict:
    """Per-phase p50/p99 and attribution coverage over finished trace
    dicts (``telemetry.traces()``) -- the serving rows' traced sections
    reduce to this. ``phase_sum_ok`` asserts the canonical phase vector
    tiles each request's own end-to-end latency within 10% using the
    round-18 UNION coverage (``tracecheck.phase_coverage``): under async
    dispatch the dispatch/device phases legitimately overlap the launch
    window, so the shared interval counts once -- a plain sum would
    over-count exactly the pipelined requests (the QT704 rule CI
    re-checks)."""
    from quest_tpu.analysis.tracecheck import phase_coverage
    from quest_tpu.telemetry import PHASES

    p50: dict = {}
    p99: dict = {}
    for ph in PHASES:
        vals = [t.get("phases_ms", {}).get(ph, 0.0) for t in trs]
        p50[ph] = round(float(np.percentile(vals, 50)), 3) if vals else 0.0
        p99[ph] = round(float(np.percentile(vals, 99)), 3) if vals else 0.0
    fracs = [f for f in (phase_coverage(t) for t in trs) if f is not None]
    return {
        "traced_requests": len(trs),
        "phase_p50_ms": p50,
        "phase_p99_ms": p99,
        "phase_sum_frac": round(float(np.median(fracs)), 3) if fracs else 0.0,
        "phase_sum_ok": bool(fracs) and all(0.9 <= f <= 1.1 for f in fracs),
    }


def smoke_plan_specs() -> list:
    """The ``--smoke`` plan configs in statically-checkable form -- the
    ONE source shared by ``tools/lint.py --bench-plans`` and the tier-1
    analysis gate (tests/test_analysis_smoke_plans.py). Each spec names a
    config and how to verify it: ``build`` returns its circuit,
    ``mesh_shape`` (or None) selects the comm-schedule check on an
    abstract mesh, ``fused`` gives the Circuit.fused kwargs for the
    frame/ring plan check (None = not a pallas-plan config), ``dtype``
    the plan dtype. plan_20q_f64 needs a QUEST_PRECISION=2 process with
    the df route enabled (QUEST_PALLAS_DF=1 off-TPU), as in main()."""
    import numpy as np

    return [
        {"name": "plan_20q_relocation",
         "build": lambda: build_circuit(20, 4),
         "mesh_shape": (8,), "dtype": None, "fused": None},
        {"name": "plan_20q_f64",
         "build": lambda: build_circuit(20, 2),
         "mesh_shape": (8,), "dtype": np.float64,
         "fused": {"max_qubits": 5, "pallas": True, "shard_devices": 8,
                   "dtype": np.float64}},
        {"name": "serve_20q",
         "build": lambda: serving_ansatz(20, 2),
         "mesh_shape": None, "dtype": None,
         "fused": {"max_qubits": 5, "pallas": True}},
        # the comm_20q circuit planned WITH the pipeline knob stamped:
        # the schedule check re-prices the depth-4 journal and proves the
        # chunk-unit model is pipeline-invariant (ISSUE 10)
        {"name": "comm_20q",
         "build": lambda: build_circuit(20, 2),
         "mesh_shape": (8,), "dtype": None, "fused": None,
         "comm_pipeline": 4},
        # the two-slice hierarchical route (ISSUE 14): the schedule check
        # re-prices the journal under the two-tier (kind, link) model and
        # proves the once-per-reconcile DCN rule (QT108)
        {"name": "plan_20q_2slice",
         "build": lambda: build_circuit(20, 4),
         "mesh_shape": (8,), "dtype": None, "fused": None,
         "num_slices": 2, "hierarchical": True, "comm_pipeline_dcn": 2},
    ]


#: the fast-window per-pass stream floor at 2^26 amps f32: the anchor that
#: drift-normalises cross-session headline figures (scales linearly with
#: state size). Measured with the SAME two-point-slope methodology as
#: _stream_floor_ms (2026-07-31, barrier-separated multiplies; the
#: round-4 "2.6 ms" figure was a fixed-cost lottery and is NOT comparable
#: -- the round-5 correction).
_FLOOR_ANCHOR_26Q_MS = 1.44


def _stream_floor_ms(nsv: int) -> float:
    """Same-process HBM roofline: one bare XLA elementwise pass over a
    (2, 2^nsv) state at the configured precision. Emitted with every
    config so the artifact distinguishes chip-bandwidth drift from kernel
    overhead (VERDICT r4 weak #1: headline figures were 'a draw from the
    window lottery' without a same-process floor).

    Methodology (round 5): TWO-POINT SLOPE. A dispatch+sync round on the
    shared remote chip of rounds 1-5 carried a large, size-independent
    fixed cost (measured
    ~25-100 ms -- the round-4 'per-pass floors' at small states were this
    artifact divided by the rep count), so the floor is the marginal cost
    between a short and a long loop-inside-jit program, not any
    single-call time. The drain scalar is computed INSIDE the program
    (no eager reshape of the big array outside the program)."""
    import time

    import jax

    from quest_tpu.ops import init as ops_init
    from quest_tpu.precision import real_dtype

    c = np.asarray(1.0000001, real_dtype())
    r_small, r_big = (50, 550) if nsv <= 22 else (10, 110)

    def make(r):
        @jax.jit
        def looped(x):
            for _ in range(r):
                # the barrier keeps each multiply a separate HBM pass --
                # XLA would otherwise fuse the whole chain into ONE pass
                # (which is what the round-4 floor probes unknowingly
                # measured)
                x = jax.lax.optimization_barrier(x) * c
            return x, x[0, 0] + x[1, 1]
        return looped

    amps = ops_init.init_classical(1 << nsv, real_dtype(), 0)
    floor_s, amps = two_point_slope(make, amps, r_small, r_big)
    del amps
    return max(floor_s * 1e3, 1e-4)


def two_point_slope(make, x0, r_small: int, r_big: int,
                    trials: int = 2) -> tuple:
    """The round-5 slope protocol, shared by every probe (bench and
    tools/slope_probe): ``make(r)`` returns a jitted fn looping r
    applications and returning (state, drain_scalar); returns the
    marginal per-application SECONDS (slope between the two rep counts,
    min over ``trials``, two calls per timed region -- the fixed
    dispatch+sync cost cancels) and the final state (the looped fn
    may donate its input)."""
    import time

    import jax

    f_s, f_b = make(r_small), make(r_big)
    x = x0
    for f in (f_s, f_b):  # compile + warmup
        x, s = f(x)
        float(jax.device_get(s))

    def timed(f, x):
        best = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            x, s = f(x)
            x, s = f(x)
            float(jax.device_get(s))
            best = min(best, (time.perf_counter() - t0) / 2)
        return best, x

    tb, x = timed(f_b, x)
    ts, x = timed(f_s, x)
    return max((tb - ts) / (r_big - r_small), 0.0), x


def _roofline(nsv: int, circuit_ms: float, passes: int) -> dict:
    """Per-config roofline block: the same-window stream floor, the
    per-pass cost, their ratio, the implied effective bandwidth, and the
    drift-normalisation factor (measured_floor / floor_anchor -- multiply
    the headline by it to restate it at the fast-window anchor
    bandwidth)."""
    from quest_tpu.precision import real_dtype

    from quest_tpu import telemetry

    floor_ms = _stream_floor_ms(nsv)
    bytes_per_pass = 2 * (1 << nsv) * 2 * np.dtype(real_dtype()).itemsize
    per_pass = circuit_ms / max(passes, 1)
    anchor = _FLOOR_ANCHOR_26Q_MS * (1 << nsv) / (1 << 26) * \
        np.dtype(real_dtype()).itemsize / 4
    # queryable, not bench-printout-only (ISSUE 1): the roofline trio as
    # gauges, labeled by flattened state size
    telemetry.set_gauge("bench.stream_floor_ms", floor_ms, nsv=nsv)
    telemetry.set_gauge("bench.per_pass_ms", per_pass, nsv=nsv)
    telemetry.set_gauge("bench.per_pass_vs_floor", per_pass / floor_ms,
                        nsv=nsv)
    # per-signature pass histogram keyed by the active DMA ring depth, so
    # a ring sweep (QUEST_PALLAS_RING=2..4 bench runs) accumulates a
    # per-depth table in the artifact (ISSUE 2 tentpole)
    telemetry.observe("pallas_per_pass_ms", per_pass, nsv=nsv,
                      ring=_ring_depth())
    return {
        "stream_floor_ms": round(floor_ms, 3),
        "per_pass_ms": round(per_pass, 3),
        "passes": passes,
        "per_pass_vs_floor": round(per_pass / floor_ms, 2),
        "eff_bandwidth_gbs": round(bytes_per_pass / floor_ms / 1e6, 1),
        "drift_norm_factor": round(floor_ms / anchor, 4),
        "_floor_over_anchor": floor_ms / anchor,  # unrounded, for callers
    }


def _density_circuit(n: int, with_krausn: bool):
    """The bench channel circuit. ``with_krausn=False`` is the 10-op
    round-3 circuit (anchor 0.93); True adds the 3-target Kraus map
    (round-4, rides the one-pass 'krausn' kernel op; reference anchor
    0.20 because its 6-qubit superoperator sweep dominates,
    QuEST_common.c:581-638)."""
    import numpy as np

    from quest_tpu.circuits import Circuit

    k = 1 / np.sqrt(2)
    kraus = [np.array([[k, 0], [0, k]]), np.array([[0, k], [k, 0]])]
    circ = Circuit(n, is_density_matrix=True)
    for q in range(4):
        circ.hadamard(q)
    circ.controlledNot(0, 1)
    circ.controlledNot(2, 3)
    circ.mixDepolarising(0, 0.05)
    circ.mixDepolarising(n - 1, 0.05)
    circ.mixKrausMap(1, kraus)
    circ.mixTwoQubitDephasing(0, 1, 0.1)
    if with_krausn:
        xxx = np.kron(np.kron([[0, 1], [1, 0]], [[0, 1], [1, 0]]),
                      [[0, 1], [1, 0]])
        kraus3 = [0.8 * xxx, 0.6j * np.eye(8)]  # CPTP: 0.64 I + 0.36 I
        circ.mixMultiQubitKrausMap([2, 3, 4], kraus3)
    return circ


def bench_density(n: int, reps: int, sync) -> dict:
    """BASELINE.json config 4: n-qubit density matrix driven through
    mixDepolarising + mixKrausMap interleaved with unitaries.

    BOTH bench circuits are timed (VERDICT r4 ask #6): the 11-op round-4
    circuit is the headline; the 10-op round-3 circuit keeps the
    round-over-round anchor stable."""
    import time

    import quest_tpu as qt

    env = qt.createQuESTEnv()

    def run_one(tag: str, with_krausn: bool):
        rho = qt.createDensityQureg(n, env)
        qt.initPlusState(rho)
        circ = _density_circuit(n, with_krausn)
        num_ops = len(circ)
        # pallas=True: the unitary prefix rides fused kernel runs with
        # explicit conj-shadow ops; channels stay barriers on their own
        # fused-Kraus passes
        fn = circ.fused(max_qubits=4, pallas=True).compiled_segments(
            max_items=4, donate=True)
        amps = rho.amps
        amps = fn(amps)
        sync(amps)
        t0 = time.perf_counter()
        for _ in range(reps):
            amps = fn(amps)
        sync(amps)
        dt1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(2 * reps):
            amps = fn(amps)
        sync(amps)
        dt2 = time.perf_counter() - t0
        del amps
        val = num_ops * 3 * reps / (dt1 + dt2)
        ref = REF_DENSITY_CHANNEL_OPS_PER_SEC.get((n, tag))
        return val, ref, dt1, dt2

    val_r3, ref_r3, _, _ = run_one("r3", with_krausn=False)
    val_r4, ref_r4, dt1, dt2 = run_one("r4", with_krausn=True)
    # same slope_ok guard as bench_statevec (ADVICE round 5): fixed-cost
    # jitter can make dt2 - dt1 non-positive, and a negative circuit_ms
    # must never reach the roofline fields
    slope_ok = dt2 - dt1 > 0.2 * dt1
    circuit_ms = ((dt2 - dt1) if slope_ok else (dt1 + dt2) / 3) / reps * 1e3
    roof = _roofline(2 * n, circuit_ms, 1)
    roof.pop("_floor_over_anchor")
    roof.pop("per_pass_ms"), roof.pop("passes"), roof.pop("per_pass_vs_floor")
    return {
        "config": f"density{n}",
        "metric": f"channel-ops/sec, {n}-qubit density matrix "
                  f"(mixDepolarising+mixKrausMap)",
        "value": round(val_r4, 2),
        "unit": "ops/sec",
        "vs_baseline": round(val_r4 / ref_r4, 3) if ref_r4 else None,
        "detail": {
            "r4_circuit_11op": {"value": round(val_r4, 2),
                                "anchor": ref_r4,
                                "vs_baseline": round(val_r4 / ref_r4, 3)
                                if ref_r4 else None},
            "r3_circuit_10op": {"value": round(val_r3, 2),
                                "anchor": ref_r3,
                                "vs_baseline": round(val_r3 / ref_r3, 3)
                                if ref_r3 else None},
            **roof,
        },
    }


def bench_statevec(n: int, depth: int, reps: int, sync) -> dict:
    """One statevec config: random Clifford+T layers, two-frame fused."""
    import time

    from quest_tpu.ops import init as ops_init

    circ = build_circuit(n, depth)
    num_gates = len(circ)
    from quest_tpu.precision import real_dtype as _rd
    f64 = np.dtype(_rd()) == np.dtype("float64")
    import jax as _jax
    on_tpu = _jax.default_backend() == "tpu"
    # 4x the reps below 22q -- sub-ms circuits are dispatch-bound, so short
    # runs measure dispatch jitter
    if n < 22 and not f64 and on_tpu:
        reps *= 4
    # chain circuit applications per program: one ~6.5 ms dispatch per
    # circuit (rounds 1-5) is a ~35% tax at 20q even with 4 chained; 16
    # at <22q / 4 at 22-25q / 2 at 26q+ amortise it below ~5% everywhere
    # (VERDICT r4 asks #4/#5). f64 circuits run ~100x longer (double-float
    # kernels), so 2 chained suffice and keep the program small.
    inner = 2 if f64 else (16 if n < 22 else (4 if n < 26 else 2))
    if not on_tpu:
        # CPU (the Pallas interpreter; main() admits it only under
        # --smoke, tier-1 calls this directly): every pass is emulated --
        # keep the program count minimal
        reps = min(reps, 2)
        inner = 1
    # two-frame pallas from 20q up: with frame swaps folded into the run
    # DMA (round 3) the fused kernel wins well below the HBM-resident
    # sizes (20q measured 96k gates/s pallas vs 31k XLA same-session);
    # tiny smoke configs stay on the XLA path (one inlined program)
    fused = circ.fused(max_qubits=5, pallas=n >= 20)
    print(f"# {n}q: fused {num_gates} gates -> {len(fused)} blocks",
          file=sys.stderr)
    if len(fused) > 48:
        # round 13: frame-identity segment programs instead of raw
        # 24-entry blocks -- same compile-boundedness, but every seam is
        # checkpointable and the dispatch count is the SEGMENT count
        fn = fused.compiled_segments(max_items=24, donate=True)
        inner = 1
        dispatches_per_circuit = float(fn.num_segments)
    elif inner > 1:
        # chain INNER applications inside one program (the loop-inside-jit
        # methodology of tools/microbench.py) so the timed region measures
        # device work, not the dispatch
        import jax

        base = fused.as_fn()

        def chained(amps):
            for _ in range(inner):
                amps = base(amps)
            return amps

        fn = jax.jit(chained, donate_argnums=(0,))
        num_gates *= inner
        dispatches_per_circuit = 1.0 / inner
    else:
        fn = fused.compiled(donate=True)
        dispatches_per_circuit = 1.0

    t0 = time.perf_counter()
    # the configured precision, NOT hardcoded f32: under QUEST_PRECISION=2
    # the fused plan is built for f64, and mixing f32 amps into it trips an
    # XLA-internal Mosaic i64 lowering on TPU (round-4 find)
    from quest_tpu.precision import real_dtype
    amps = ops_init.init_classical(1 << n, real_dtype(), 0)
    amps = fn(amps)  # compile + warmup
    sync(amps)
    print(f"# {n}q compile+warmup {time.perf_counter() - t0:.1f}s",
          file=sys.stderr)

    # two timed regions (reps and 2*reps programs): a region carried a
    # large fixed dispatch+sync cost on the shared remote chip (measured
    # ~25-100 ms, round 5), so the SLOPE between them is the device rate; the
    # headline uses the all-programs total (same methodology as earlier
    # rounds, more reps), with the fixed cost reported alongside
    t0 = time.perf_counter()
    for _ in range(reps):
        amps = fn(amps)
    sync(amps)
    dt1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(2 * reps):
        amps = fn(amps)
    sync(amps)
    dt2 = time.perf_counter() - t0
    del amps

    gates_per_sec = num_gates * 3 * reps / (dt1 + dt2)
    # guard: fixed-cost jitter between the two regions can make the slope
    # non-positive on sub-100ms workloads; fall back to the total-based
    # figure rather than emitting a nonsense marginal rate
    slope_ok = dt2 - dt1 > 0.2 * dt1
    device_rate = (num_gates * reps / (dt2 - dt1) if slope_ok
                   else gates_per_sec)
    fixed_ms = max(2 * dt1 - dt2, 0.0) * 1e3
    ref = REF_GATES_PER_SEC.get(n)
    roof = _roofline(n, ((dt2 - dt1) if slope_ok else
                         (dt1 + dt2) / 3) / reps * 1e3,
                     len(fused) * inner)
    norm = gates_per_sec * roof.pop("_floor_over_anchor")
    return {
        "config": f"{n}q",
        "metric": f"gate-ops/sec, {n}-qubit state-vector random Clifford+T",
        "value": round(gates_per_sec, 2),
        "unit": "gates/sec",
        "vs_baseline": round(gates_per_sec / ref, 3) if ref else None,
        "detail": {
            "chained_circuits": inner, "blocks_per_circuit": len(fused),
            # device dispatches ONE circuit application costs on this
            # operating point (round 13: <1 when several applications
            # chain inside one program, num_segments on the segment-
            # chain path for deep tapes)
            "dispatches_per_circuit": round(dispatches_per_circuit, 4),
            # the DMA ring operating point this run executed with
            # (sweepable via QUEST_PALLAS_RING / Circuit.fused(ring_depth))
            "ring_depth": _ring_depth(),
            # marginal (fixed-dispatch-free) device throughput + the
            # measured per-region fixed cost it excludes
            "device_gates_per_sec": round(device_rate, 1),
            "dispatch_fixed_ms": round(fixed_ms, 1),
            **roof,
            # the headline scaled to the fast-window bandwidth anchor:
            # cross-session-comparable (the chip's effective bandwidth
            # swings ~5x between windows, the round-5 drift warning)
            "drift_normalized_gates_per_sec": round(norm, 1),
        },
    }


def plan_34q_distributed() -> dict:
    """Config 5 (34q sharded state-vector) cannot run on one 16 GiB chip;
    report the trace-time execution plan for the v5p-16 target instead
    (the driver's virtual-mesh dryrun separately validates the sharded
    path executes).

    Round-4: the plan is the MULTI-FRAME PALLAS plan (planner._FramePlanner
    over the 30-qubit shard tile) -- every gate rides a per-shard fused
    kernel run, with frame relabelings lowered to bit-block transposes
    (collective all-to-alls when the swapped block includes sharded
    qubits, shard-local otherwise). Round 3 planned 122 window GEMMs and
    zero PallasRuns here (VERDICT r3 missing #1)."""
    from quest_tpu import planner
    from quest_tpu.ops.pallas_gates import local_qubits
    from quest_tpu.precision import real_dtype

    n, depth, ndev = 34, 8, 16
    n_local = n - (ndev.bit_length() - 1)
    circ = build_circuit(n, depth)
    p = planner.plan_pallas_sharded(tuple(circ._tape), n, real_dtype(), 5,
                                   local_qubits(n_local), n_local)
    runs = [i for i in p.items if isinstance(i, planner.PallasRun)]
    dense = sum(isinstance(i, planner.FusedBlock) for i in p.items)
    detail = {"gates": len(circ), "pallas_runs": len(runs),
              "dense_blocks": dense,
              **planner.transpose_stats(p, n_local),
              "examples": "examples/distributed_34q.py"}
    try:
        detail["comm_plan_16dev"] = _dist_comm_plan(circ)
    except Exception as e:  # the plan stats must not sink the artifact
        detail["comm_plan_16dev"] = f"unavailable: {e}"
    return {
        "config": "plan_34q",
        "metric": "34q distributed plan: per-shard Pallas runs for "
                  "v5p-16 execution",
        "value": len(p.items),
        "unit": "blocks",
        "vs_baseline": None,
        "detail": detail,
    }


def plan_20q_f64_smoke() -> dict:
    """CI-gate config (round 7, ISSUE 3): the sharded 20q PRECISION=2 plan
    on the double-float fast path, modeled on an abstract 8-device mesh --
    the fused df tape's PallasRuns execute per shard under the explicit
    scheduler and its frame transposes ride the COUNTED grouped permute on
    the 4-plane state at the df 2x chunk-unit scale. The bench-smoke gate
    asserts the config's presence, model == telemetry, the exact 2x df
    accounting, and zero f64-engine fallbacks
    (.github/workflows/native.yml). Pure jax.eval_shape; requires a
    QUEST_PRECISION=2 + QUEST_PALLAS_DF=1 process (main() re-execs into
    one)."""
    import numpy as np

    from quest_tpu import telemetry
    from jax.sharding import AbstractMesh
    from quest_tpu.environment import AMP_AXIS
    from quest_tpu.parallel.scheduler import comm_chunks, plan_circuit

    mesh = AbstractMesh((8,), (AMP_AXIS,))
    circ = build_circuit(20, 2)
    fz = circ.fused(max_qubits=5, pallas=True, shard_devices=8,
                    dtype=np.float64)

    def counter_sum():
        return sum(telemetry.counters("comm_chunk_units_total").values())

    def fb():
        return telemetry.counter_value("engine_fallback_total",
                                       reason="f64_engine")

    t0, f0 = counter_sum(), fb()
    stats = plan_circuit(fz, mesh, dtype=np.float64)
    t1, f1 = counter_sum(), fb()
    model = comm_chunks(stats)
    ft = stats["frame_transpose_chunks"]
    ftp = stats["frame_transpose_planar_chunks"]
    return {
        "config": "plan_20q_f64",
        "metric": "20q PRECISION=2 sharded df plan comm chunk-units "
                  "(8-device model, frame transposes at the df 2x scale)",
        "value": round(model, 4),
        "unit": "chunk-units",
        "vs_baseline": None,
        "detail": {
            "frame_transposes": stats["frame_transpose_collectives"],
            "frame_transpose_chunks": ft,
            "frame_transpose_planar_chunks": ftp,
            "df_plane_scale": (ft / ftp) if ftp else None,
            "relocation_batches": stats["relocation_batches"],
            "relocation_batch_chunks": stats["relocation_batch_chunks"],
            "telemetry_chunk_units": round(t1 - t0, 6),
            "model_matches_telemetry": bool(abs((t1 - t0) - model) < 1e-6),
            "engine_fallback_f64": f1 - f0,
        },
    }


def plan_34q_f64() -> dict:
    """The 34q flagship at PRECISION=2 (round 7, ISSUE 3): the
    deferred-scheduler comm plan with the SAME relocation-batch A/B fields
    as the f32 row (the exchange protocol is precision-agnostic in chunk
    counts; bytes double via comm_volume(bytes_per_amp=16)), plus the
    sharded DOUBLE-FLOAT pallas plan's shape -- the df tile
    (ops/pallas_df.DF_SUBLANES -> 17-qubit tiles over the 30-qubit v5p-16
    shards) re-planned for per-shard df execution, the path the round-6
    policy routed to the ~170x-slower emulated-f64 engine. Requires a
    QUEST_PRECISION=2 process (main() re-execs)."""
    import numpy as np

    from quest_tpu import planner
    from quest_tpu.ops.pallas_df import DF_SUBLANES
    from quest_tpu.ops.pallas_gates import local_qubits

    n, depth, ndev = 34, 8, 16
    n_local = n - (ndev.bit_length() - 1)
    circ = build_circuit(n, depth)
    tile = local_qubits(n_local, DF_SUBLANES)
    p = planner.plan_pallas_sharded(tuple(circ._tape), n,
                                   np.dtype(np.float64), 5, tile, n_local)
    runs = [i for i in p.items if isinstance(i, planner.PallasRun)]
    detail = {
        "gates": len(circ),
        "df_tile_bits": tile,
        "pallas_runs": len(runs),
        "dense_blocks": sum(isinstance(i, planner.FusedBlock)
                            for i in p.items),
        **planner.transpose_stats(p, n_local),
    }
    try:
        detail["comm_plan_16dev"] = _dist_comm_plan(circ, dtype=np.float64)
    except Exception as e:  # the plan stats must not sink the artifact
        detail["comm_plan_16dev"] = f"unavailable: {e}"
    return {
        "config": "plan_34q_f64",
        "metric": "34q PRECISION=2 distributed plan: per-shard double-"
                  "float PallasRuns for v5p-16 execution",
        "value": len(p.items),
        "unit": "blocks",
        "vs_baseline": None,
        "detail": detail,
    }


def _dist_comm_plan(circ, dtype=None) -> dict:
    """Deferred-permutation scheduler comm stats for the 34q circuit on an
    emulated 16-device mesh, vs the reference's immediate-swap-back policy
    (QuEST_cpu_distributed.c:1526-1568). Chunk units: 2 per pair exchange /
    rank permute, 1 per relocation or reconciliation swap, measured
    grouped-permute units per relocation batch. The batched-vs-per-swap
    relocation A/B (ISSUE 2 acceptance) ships in the stats: ``deferred``
    is the production batched policy, ``deferred_per_swap_chunks`` the
    same plan with batch_relocations=False."""
    from jax.sharding import AbstractMesh
    from quest_tpu.environment import AMP_AXIS
    from quest_tpu.parallel.scheduler import comm_chunks, plan_circuit

    # plan stats are trace-time only (jax.eval_shape): an abstract
    # 16-device mesh needs no hardware
    mesh = AbstractMesh((16,), (AMP_AXIS,))
    deferred = plan_circuit(circ, mesh, dtype=dtype)
    per_swap = plan_circuit(circ, mesh, batch_relocations=False, dtype=dtype)
    immediate = plan_circuit(circ, mesh, defer=False, dtype=dtype)
    return {
        "deferred_chunks": comm_chunks(deferred),
        "deferred_per_swap_chunks": comm_chunks(per_swap),
        "relocation_batch_ab": {
            "batched_chunks": deferred["relocation_batch_chunks"],
            "swap_equiv_chunks":
                deferred["relocation_batch_swap_equiv_chunks"],
            "batches": deferred["relocation_batches"],
            "batched_qubits": deferred["relocation_batch_qubits"],
            "prefetched": deferred["relocation_prefetched"],
        },
        "reference_policy_chunks": comm_chunks(immediate),
        "reduction_pct": round(100 * (1 - comm_chunks(deferred) /
                                      max(comm_chunks(immediate), 1)), 1),
        "deferred": {k: v for k, v in deferred.items() if k != "comm_volume"},
    }


def plan_17q_density_distributed() -> dict:
    """The SECOND BASELINE.json north-star target (VERDICT r4 missing #1):
    a 17-qubit density-matrix depolarising-channel workload sharded over a
    v5p-16. 34 flattened qubits cannot fit one chip; report the trace-time
    sharded Pallas plan -- per-shard kernel runs with the channels riding
    kraus ops, collective vs shard-local frame transposes, and the
    deferred-scheduler comm stats -- mirroring the 34q state-vector
    artifact. Reference counterpart: the distributed density-channel
    protocol, QuEST_cpu_distributed.c:724-749 (single-qubit) and :778-868
    (two-qubit depolarising, 3-exchange); the dryrun executes a scaled
    replica (>=8q density on the 8-device CPU mesh)."""
    from quest_tpu import fusion, planner

    n, ndev = 17, 16
    circ = _density_circuit(n, with_krausn=True)
    # make the sharded-column regime explicit: a channel whose column
    # coordinate (q + n) lives above the 30-qubit shard boundary
    circ.mixDepolarising(n - 2, 0.03)
    fz = circ.fused(max_qubits=4, pallas=True, shard_devices=ndev)
    runs = [i for i in fusion.plan_from_tape(fz._tape).items
            if isinstance(i, planner.PallasRun)]
    kraus_ops = [op for r in runs for op in r.ops
                 if op[0].startswith("kraus")]
    tstats = fusion.tape_transpose_stats(
        fz._tape, 2 * n - (ndev.bit_length() - 1))
    n_coll = tstats["collective_transposes"] + tstats["local_transposes"]
    detail = {
        "channel_ops": sum(1 for f, _, _ in circ._tape
                           if f.__name__.startswith("mix")),
        "pallas_runs": len(runs),
        "kraus_kernel_ops": len(kraus_ops),
        "kraus_arities": sorted({op[0] for op in kraus_ops}),
        "frame_transposes": n_coll,
        "collective_transposes": tstats["collective_transposes"],
        "flattened_qubits": 2 * n,
        "examples": "__graft_entry__.dryrun_multichip density leg",
    }
    try:
        from jax.sharding import AbstractMesh
        from quest_tpu.environment import AMP_AXIS
        from quest_tpu.parallel.scheduler import comm_chunks, plan_circuit

        mesh = AbstractMesh((ndev,), (AMP_AXIS,))
        deferred = plan_circuit(circ, mesh)
        per_swap = plan_circuit(circ, mesh, batch_relocations=False)
        immediate = plan_circuit(circ, mesh, defer=False)
        detail["comm_plan_16dev"] = {
            "deferred_chunks": comm_chunks(deferred),
            "deferred_per_swap_chunks": comm_chunks(per_swap),
            "relocation_batches": deferred["relocation_batches"],
            "reference_policy_chunks": comm_chunks(immediate),
            "reduction_pct": round(100 * (1 - comm_chunks(deferred) /
                                          max(comm_chunks(immediate), 1)),
                                   1),
        }
    except Exception as e:  # plan stats must not sink the artifact
        detail["comm_plan_16dev"] = f"unavailable: {e}"
    return {
        "config": "plan_17q_density",
        "metric": "17q density-matrix channel plan: per-shard Pallas runs "
                  "with kraus ops for v5p-16 execution",
        "value": len(kraus_ops),
        "unit": "kraus kernel ops",
        "vs_baseline": None,
        "detail": detail,
    }


def plan_20q_relocation_smoke() -> dict:
    """CI-gate config (round 6): the sharded 20q plan's batched-relocation
    stats on an abstract 8-device mesh, with the trace-time telemetry
    chunk-units cross-checked against the plan_circuit comm model in the
    artifact itself -- the bench-smoke workflow asserts
    ``model_matches_telemetry`` and the A/B fields are present
    (.github/workflows/native.yml). Pure jax.eval_shape: no devices, no
    state allocation, runs in seconds on the CI box."""
    from quest_tpu import telemetry
    from jax.sharding import AbstractMesh
    from quest_tpu.environment import AMP_AXIS
    from quest_tpu.parallel.scheduler import comm_chunks, plan_circuit

    mesh = AbstractMesh((8,), (AMP_AXIS,))
    circ = build_circuit(20, 4)
    t0 = sum(telemetry.counters("comm_chunk_units_total").values())
    batched = plan_circuit(circ, mesh)
    t1 = sum(telemetry.counters("comm_chunk_units_total").values())
    per_swap = plan_circuit(circ, mesh, batch_relocations=False)
    model = comm_chunks(batched)
    return {
        "config": "plan_20q_relocation",
        "metric": "20q sharded plan comm chunk-units, batched relocations "
                  "(8-device model)",
        "value": round(model, 4),
        "unit": "chunk-units",
        "vs_baseline": None,
        "detail": {
            "relocation_batches": batched["relocation_batches"],
            "relocation_batch_qubits": batched["relocation_batch_qubits"],
            "relocation_prefetched": batched["relocation_prefetched"],
            "relocation_batch_chunks": batched["relocation_batch_chunks"],
            "relocation_batch_swap_equiv_chunks":
                batched["relocation_batch_swap_equiv_chunks"],
            "per_swap_chunks": round(comm_chunks(per_swap), 4),
            "telemetry_chunk_units": round(t1 - t0, 6),
            "model_matches_telemetry": bool(abs((t1 - t0) - model) < 1e-6),
        },
    }


def plan_34q_2slice() -> dict:
    """CI-gate config (round 15): the 34q deferred plan on a modeled
    2x8 TWO-SLICE mesh (16 devices, slice-major order: shard bits 30-32
    ride ICI, bit 33 crosses DCN), flat vs hierarchical A/B split by
    link class. The hierarchical planner defers every DCN relocation to
    its forced dense use, fattens the all-to-all it rides, and parks the
    globally most-idle qubit on the DCN bit -- the bench-smoke gate
    asserts ``dcn_chunks_hierarchical < dcn_chunks_flat`` and the
    per-(kind, link) telemetry == model cross-check
    (.github/workflows/native.yml). Pure jax.eval_shape: no devices."""
    from quest_tpu import telemetry
    from jax.sharding import AbstractMesh
    from quest_tpu.environment import AMP_AXIS
    from quest_tpu.parallel.scheduler import comm_chunks, plan_circuit

    mesh = AbstractMesh((16,), (AMP_AXIS,))
    circ = build_circuit(34, 8)
    flat = plan_circuit(circ, mesh, num_slices=2)
    t0 = dict(telemetry.counters("comm_chunk_units_total"))
    hier = plan_circuit(circ, mesh, num_slices=2, hierarchical=True)
    t1 = telemetry.counters("comm_chunk_units_total")
    # the hierarchical run's per-(kind, link) telemetry deltas must sum
    # to the plan model cell-for-cell (the round-15 split of the older
    # scalar model==telemetry gate)
    seen = {}
    for key, v in t1.items():
        dv = v - t0.get(key, 0.0)
        if abs(dv) < 1e-12:
            continue
        kind = key.split("kind=", 1)[1].split(",", 1)[0].rstrip("}")
        link = key.split("link=", 1)[1].split(",", 1)[0].rstrip("}")
        seen[f"{kind}/{link}"] = dv
    cells = hier["chunks_by_kind_link"]
    cells_match = set(seen) == set(cells) and all(
        abs(seen[c] - cells[c]) < 1e-6 for c in cells)
    return {
        "config": "plan_34q_2slice",
        "metric": "34q deferred plan DCN chunk-units, hierarchical "
                  "two-tier planner (modeled 2x8 two-slice mesh)",
        "value": round(hier["dcn_chunks"], 4),
        "unit": "chunk-units",
        "vs_baseline": None,
        "detail": {
            "dcn_chunks_flat": round(flat["dcn_chunks"], 4),
            "dcn_chunks_hierarchical": round(hier["dcn_chunks"], 4),
            "ici_chunks_flat": round(flat["ici_chunks"], 4),
            "ici_chunks_hierarchical": round(hier["ici_chunks"], 4),
            "total_chunks_flat": round(comm_chunks(flat), 4),
            "total_chunks_hierarchical": round(comm_chunks(hier), 4),
            "dcn_reduction_pct": round(
                100 * (1 - hier["dcn_chunks"] /
                       max(flat["dcn_chunks"], 1e-12)), 1),
            "relocation_batches_flat": flat["relocation_batches"],
            "relocation_batches_hierarchical": hier["relocation_batches"],
            "staged_relays": hier["staged_relays"],
            "chunks_by_kind_link_hierarchical":
                {k: round(v, 4) for k, v in cells.items()},
            "model_matches_telemetry": bool(cells_match),
        },
    }


def bench_serving(n: int, depth: int, reps: int) -> dict:
    """CI-gate config ``serve_20q``: the serving engine's parameter-sweep
    economics on an n-qubit VQE-style ansatz (every rotation a runtime
    Param). Measures cold compile vs cached replay (the whole point of the
    parameterized executable: the gate asserts cached replay < 10% of
    cold), one coalesced batch-of-8 dispatch vs the same 8 requests
    uncoalesced (bit-identical BY CONSTRUCTION -- both run the one padded
    vmap program, asserted here and by the workflow), warm-path retraces
    (must be zero) and the executable-cache hit counters, including the
    structure-share hit when a second engine serves a fresh circuit of the
    same structure."""
    import time

    import jax

    import quest_tpu as qt
    from quest_tpu import telemetry
    from quest_tpu.engine import Engine

    circ = serving_ansatz(n, depth)
    names = circ.param_names
    rng = np.random.RandomState(6)

    def draw():
        return {nm: float(v)
                for nm, v in zip(names, rng.uniform(0, 2 * np.pi,
                                                    len(names)))}

    env = qt.createQuESTEnv(jax.devices()[:1])
    eng = Engine(circ, env, max_batch=8, max_delay_ms=0.0)
    h0 = telemetry.counter_value("plan_cache_hit_total", cache="executable")
    m0 = telemetry.counter_value("plan_cache_miss_total", cache="executable")
    t0 = time.perf_counter()
    eng.run(draw()).block_until_ready()
    cold_s = time.perf_counter() - t0
    tr0 = telemetry.counter_value("engine_trace_total", kind="param_replay")
    # warm batch-of-8: ONE coalesced vmap dispatch; the per-request warm
    # latency (batch/8) is the serving-path "cached replay" the gate
    # compares against the cold compile
    sweep = [draw() for _ in range(8)]
    best_batch = float("inf")
    for _ in range(max(min(reps, 3), 1)):
        tb = time.perf_counter()
        outs = [f.result() for f in eng.submit_many(sweep)]
        outs[-1].block_until_ready()
        best_batch = min(best_batch, time.perf_counter() - tb)
    batch_s = best_batch
    # loop-of-8: the SAME 8 requests uncoalesced (each still runs the one
    # padded program -- hence bit-identical lanes), timed per request
    singles = []
    louts = []
    tl = time.perf_counter()
    for p in sweep:
        t1 = time.perf_counter()
        r = eng.run(p)
        r.block_until_ready()
        singles.append(time.perf_counter() - t1)
        louts.append(r)
    loop_s = time.perf_counter() - tl
    bitident = all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(outs, louts))
    warm_retraces = telemetry.counter_value(
        "engine_trace_total", kind="param_replay") - tr0
    # structure share: a second engine over a FRESH circuit of the same
    # structure serves from the executable cache -- no trace, no compile
    # (the trace counter stays flat across its first request)
    eng2 = Engine(serving_ansatz(n, depth), env, max_batch=8,
                  max_delay_ms=0.0)
    tr1 = telemetry.counter_value("engine_trace_total", kind="param_replay")
    t2 = time.perf_counter()
    eng2.run(draw()).block_until_ready()
    share_s = time.perf_counter() - t2
    share_retraces = telemetry.counter_value(
        "engine_trace_total", kind="param_replay") - tr1
    eng2.close()
    # -- async dispatch A/B (round 18): stream the same 16-request load
    # through the default completion-ring engine and a true-synchronous
    # twin (async_depth=0: the batcher drains each batch before issuing
    # the next). Latency is submit -> future resolution, stamped by done
    # callbacks so the waiting order cannot skew it; both legs share the
    # warm executable (same structure fingerprint), so the A/B measures
    # the pipeline, not compilation.
    ab_sweep = [draw() for _ in range(16)]

    def _stream(async_depth):
        e = Engine(serving_ansatz(n, depth), env, max_batch=4,
                   max_delay_ms=0.0, async_depth=async_depth)
        e.run(ab_sweep[0])
        done_at: dict = {}
        futs, subs = [], []
        t_s0 = time.perf_counter()
        for i in range(0, len(ab_sweep), 4):
            fs = e.submit_many(ab_sweep[i:i + 4])
            t_sub = time.perf_counter()
            for f in fs:
                k = len(futs)
                futs.append(f)
                subs.append(t_sub)
                f.add_done_callback(
                    lambda _f, _k=k: done_at.setdefault(
                        _k, time.perf_counter()))
        outs = [np.asarray(f.result(600)) for f in futs]
        wall = time.perf_counter() - t_s0
        e.close()
        lats = [(done_at[k] - subs[k]) * 1e3 for k in range(len(futs))]
        return outs, lats, wall

    # best-of-reps per route: a single 16-request stream on a shared
    # host jitters by several percent run to run, which would drown the
    # pipeline delta; the min-p50 stream is the standard noise damper
    # (same convention as the batch timings above)
    ab_reps = max(min(reps, 2), 1)
    async_outs, async_lats, async_wall = _stream(None)  # default ring
    sync_outs, sync_lats, sync_wall = _stream(0)
    for _ in range(ab_reps - 1):
        ao, al, aw = _stream(None)
        if np.percentile(al, 50) < np.percentile(async_lats, 50):
            async_lats, async_wall = al, aw
        so, sl, sw = _stream(0)
        if np.percentile(sl, 50) < np.percentile(sync_lats, 50):
            sync_lats, sync_wall = sl, sw
    async_bitident = all(np.array_equal(a, b)
                         for a, b in zip(async_outs, sync_outs))
    # -- whole-request chaining (round 18): the concrete (bound-angle)
    # structure twin lowers -- every frame-identity segment composed --
    # into ONE dispatched program: dispatches_per_circuit floors at 1
    from quest_tpu.ops import init as ops_init
    conc = serving_ansatz(n, depth, values=ab_sweep[0])
    fnR = conc.compiled_request(donate=False)
    amps0 = ops_init.init_classical(1 << n, eng.dtype, 0)
    fnR(amps0 + 0).block_until_ready()  # compile outside the counted call
    d0 = telemetry.counter_value("device_dispatch_total", route="request")
    t_r = time.perf_counter()
    out_req = fnR(amps0 + 0)
    out_req.block_until_ready()
    chained_ms = (time.perf_counter() - t_r) * 1e3
    dpc = telemetry.counter_value("device_dispatch_total",
                                  route="request") - d0
    chained_bitident = bool(np.array_equal(
        np.asarray(out_req), np.asarray(fnR(amps0 + 0))))
    # traced section (round 17): a handful of extra warm requests under
    # trace_policy("all"), OUTSIDE every timed window above -- per-phase
    # attribution for the row without perturbing the gated numbers
    seen = len(telemetry.traces())
    with telemetry.trace_policy("all"):
        for f in eng.submit_many([draw() for _ in range(8)]):
            f.result(600)
    traced = [t for t in telemetry.traces()[seen:]
              if t["labels"].get("kind") == "engine"]
    phase_stats = trace_phase_stats(traced)
    eng.close()
    hits = telemetry.counter_value("plan_cache_hit_total",
                                   cache="executable") - h0
    misses = telemetry.counter_value("plan_cache_miss_total",
                                     cache="executable") - m0
    return {
        "config": "serve_20q",
        "metric": f"serving engine, {n}q depth-{depth} param ansatz: warm "
                  "batched requests/sec (one vmap-over-params dispatch)",
        "value": round(8 / batch_s, 2),
        "unit": "req/sec",
        "vs_baseline": None,
        "detail": {
            "qubits": n,
            "depth": depth,
            "num_params": len(names),
            "cold_compile_ms": round(cold_s * 1e3, 1),
            "cached_replay_ms": round(batch_s / 8 * 1e3, 2),
            "replay_over_cold": round(batch_s / 8 / cold_s, 4),
            "uncoalesced_replay_ms": round(min(singles) * 1e3, 2),
            "batch8_ms": round(batch_s * 1e3, 2),
            "loop8_ms": round(loop_s * 1e3, 2),
            "batch_speedup": round(loop_s / batch_s, 2),
            "batch_bitident": bool(bitident),
            "warm_retraces": int(warm_retraces),
            "plan_cache_hits": int(hits),
            "plan_cache_misses": int(misses),
            "structure_share_ms": round(share_s * 1e3, 2),
            "structure_share_retraces": int(share_retraces),
            # async dispatch pipeline A/B (round 18): per-request latency
            # (submit -> future resolution) under the completion ring vs
            # the true-synchronous twin, over the identical 16-req stream
            "latency_p50_ms": round(float(np.percentile(async_lats, 50)), 2),
            "latency_p99_ms": round(float(np.percentile(async_lats, 99)), 2),
            "async_p50_ms": round(float(np.percentile(async_lats, 50)), 2),
            "sync_p50_ms": round(float(np.percentile(sync_lats, 50)), 2),
            "async_p99_ms": round(float(np.percentile(async_lats, 99)), 2),
            "sync_p99_ms": round(float(np.percentile(sync_lats, 99)), 2),
            "async_wall_ms": round(async_wall * 1e3, 2),
            "sync_wall_ms": round(sync_wall * 1e3, 2),
            "async_bitident": bool(async_bitident),
            # overlap needs a core the XLA execution thread isn't using:
            # on a 1-core host the pipeline degrades to a reordering of
            # identical work (engine resolves-before-issue there), so the
            # CI gate holds async to strict improvement only when > 1
            "host_cores": int(os.cpu_count() or 1),
            # whole-request chaining: the concrete twin runs end-to-end as
            # ONE dispatched program (the round-18 floor)
            "dispatches_per_circuit": int(dpc),
            "request_num_segments": int(fnR.num_segments),
            "chained_request_ms": round(chained_ms, 2),
            "chained_bitident": bool(chained_bitident),
            **phase_stats,
        },
    }


def bench_pool(n: int, depth: int, reps: int) -> dict:
    """CI-gate config ``pool_20q``: replica-pool serving (ISSUE 13) --
    mixed-structure open-loop load over 3 replicas with ONE injected
    replica kill mid-run. Measures sustained req/sec and p50/p99 request
    latency under the failover, and asserts the robustness contract the
    round-14 gate checks: ``lost_requests == 0`` (every future resolves),
    ``failover_bitident`` (every served result -- failed-over ones
    included -- is bit-identical to a lone-engine oracle; same
    fingerprint -> same executable) and ``replacement_zero_retrace`` (the
    replacement replica is warmed from the fingerprint manifest before
    rotation, so its first real request performs zero retraces)."""
    import time

    import jax

    import quest_tpu as qt
    from quest_tpu import telemetry
    from quest_tpu.engine import Engine, EnginePool
    from quest_tpu.resilience import fault_plan

    structures = [serving_ansatz(n, depth), serving_ansatz(n, depth + 1)]
    rng = np.random.RandomState(13)

    def draw(circ):
        return {nm: float(v)
                for nm, v in zip(circ.param_names,
                                 rng.uniform(0, 2 * np.pi,
                                             len(circ.param_names)))}

    requests = 8 * max(min(reps, 4), 2)
    work = [(c, draw(c))
            for c in (structures[i % len(structures)]
                      for i in range(requests))]

    env = qt.createQuESTEnv(jax.devices()[:1])
    # per-request oracle from lone engines (identical executable keys)
    oracle = []
    engs = {}
    for c, p in work:
        fp = c.fingerprint()
        if fp not in engs:
            engs[fp] = Engine(c, env, max_batch=8, max_delay_ms=0.0)
        oracle.append(np.asarray(engs[fp].submit(p).result(600)))
    for e in engs.values():
        e.close()

    f0 = telemetry.counter_value("pool_failovers_total", reason="kill")
    r0 = telemetry.counter_value("pool_replacements_total", reason="kill")
    pool = EnginePool(env, replicas=3, max_batch=8, max_delay_ms=1.0)
    # absorb the per-structure cold compile outside the timed window (the
    # executable LRU then shares it across every replica and the oracle)
    for c in structures:
        pool.submit(c, draw(c)).result(600)
    lat: dict = {}
    kill_at = requests // 2
    with fault_plan(f"pool.replica:kill:{kill_at}"):
        t0 = time.perf_counter()
        futs = []
        for i, (c, p) in enumerate(work):
            ts = time.perf_counter()
            f = pool.submit(c, p, tenant=f"tenant{i % 2}")
            f.add_done_callback(
                lambda fut, ts=ts, i=i:
                lat.__setitem__(i, time.perf_counter() - ts))
            futs.append(f)
        results = [np.asarray(f.result(600)) for f in futs]
        wall = time.perf_counter() - t0
    lost = sum(1 for f in futs if not f.done())
    bitident = all(np.array_equal(w, g) for w, g in zip(oracle, results))
    failovers = telemetry.counter_value("pool_failovers_total",
                                        reason="kill") - f0
    # the replacement replica must re-enter rotation warm: first real
    # request on it performs zero retraces (manifest warm + shared LRU)
    pool.await_rotation(3, timeout=600)
    replacements = telemetry.counter_value("pool_replacements_total",
                                           reason="kill") - r0
    new_rep = max(pool._replicas, key=lambda r: r.id)
    tr0 = telemetry.counter_value("engine_trace_total", kind="param_replay")
    c0, _ = work[0]
    first = np.asarray(
        new_rep.engines[c0.fingerprint()].submit(draw(c0)).result(600))
    zero_retrace = telemetry.counter_value(
        "engine_trace_total", kind="param_replay") == tr0
    # traced section (round 17): extra warm requests over the healed
    # pool under trace_policy("all"), outside every timed window --
    # per-phase attribution for the row (kind=pool roots only: engine
    # warmup mints its own kind=engine traces)
    seen = len(telemetry.traces())
    with telemetry.trace_policy("all"):
        tfs = [pool.submit(c, p, tenant=f"tenant{i % 2}")
               for i, (c, p) in enumerate(work[:8])]
        for f in tfs:
            f.result(600)
    phase_stats = trace_phase_stats(
        [t for t in telemetry.traces()[seen:]
         if t["labels"].get("kind") == "pool"])
    pool.close()
    lats_ms = np.asarray(sorted(lat.values())) * 1e3
    return {
        "config": "pool_20q",
        "metric": f"replica-pool serving, {requests} mixed-structure "
                  f"{n}q requests over 3 replicas with one injected "
                  "replica kill mid-run: sustained req/sec",
        "value": round(requests / wall, 2),
        "unit": "req/sec",
        "vs_baseline": None,
        "detail": {
            "qubits": n,
            "depth": depth,
            "replicas": 3,
            "structures": len(structures),
            "requests": requests,
            "req_per_sec": round(requests / wall, 2),
            "p50_ms": round(float(np.percentile(lats_ms, 50)), 2),
            "p99_ms": round(float(np.percentile(lats_ms, 99)), 2),
            "wall_s": round(wall, 3),
            "failovers": int(failovers),
            "replacements": int(replacements),
            "lost_requests": int(lost),
            "failover_bitident": bool(bitident),
            "replacement_zero_retrace": bool(zero_retrace),
            "replacement_first_abs_sum": round(float(np.abs(first).sum()), 6),
            **phase_stats,
        },
    }


def trajectory_circuit(n: int):
    """The trajectories_20q noisy circuit: an entangled n-qubit base with
    one channel site from each built-in family (depolarising, damping,
    two-qubit dephasing, Pauli) -- recorded as a density tape; the bench
    unravels it into the stochastic pure-state form."""
    from quest_tpu.circuits import Circuit

    circ = Circuit(n, is_density_matrix=True)
    for q in range(n):
        circ.hadamard(q)
    for q in range(0, n - 1, 2):
        circ.controlledNot(q, q + 1)
    circ.mixDepolarising(1, 0.05)
    circ.rotateY(n // 2, 0.9)
    circ.mixDamping(0, 0.1)
    circ.mixTwoQubitDephasing(2, 5, 0.2)
    circ.rotateX(1, -0.4)
    circ.mixPauli(3, 0.02, 0.03, 0.05)
    return circ


def bench_trajectories(n: int, t: int, reps: int) -> dict:
    """CI-gate config ``trajectories_20q``: quantum-trajectory unraveling
    throughput -- T stochastic pure-state trajectories of a noisy n-qubit
    circuit run as ONE compiled executable replayed over T seed streams
    (the engine's vmap-over-params batcher, seeds as uint32 slots). The
    density route for the same circuit at n qubits would cost 2n qubits
    of state; the anchor row compares channel-site throughput against the
    density14 reference instead. The workflow gate asserts the two
    correctness invariants alongside the rate: ``ensemble_mean_ok`` (the
    6q ensemble mean matches the density-matrix oracle within the
    4/sqrt(T) band) and ``seed_replay_bitident`` (the same seed list
    replays the n-qubit ensemble bit-identically)."""
    import time

    import jax

    import quest_tpu as qt
    from quest_tpu import telemetry
    from quest_tpu import trajectories as traj

    env = qt.createQuESTEnv(jax.devices()[:1])

    # correctness leg 1: 6q ensemble mean vs the exact density oracle
    t_small = max(t, 128)
    small = trajectory_circuit(6)
    dm = qt.createDensityQureg(6, env)
    small.run(dm)
    rho = qt.get_np(dm).reshape(64, 64).T  # flat layout is [col, row]
    res = traj.run_ensemble(small, t_small, env=env, base_seed=11)
    mean_err = float(np.max(np.abs(res.density() - rho)))
    mean_tol = 4.0 / np.sqrt(t_small)
    mean_ok = bool(mean_err < mean_tol)

    # the timed leg: T n-qubit trajectories through one executable
    circ = traj.unravel(trajectory_circuit(n))
    sites = sum(1 for fn, _, _ in circ._tape
                if getattr(fn, "__name__", "") == "applyTrajectoryKraus")
    seeds = list(range(100, 100 + t))
    t0 = time.perf_counter()
    first = traj.run_ensemble(circ, env=env, seeds=seeds, max_batch=t)
    cold_s = time.perf_counter() - t0
    tr0 = telemetry.counter_value("engine_trace_total", kind="param_replay")
    best = float("inf")
    last = first
    for _ in range(max(reps, 1)):
        t1 = time.perf_counter()
        last = traj.run_ensemble(circ, env=env, seeds=seeds, max_batch=t)
        best = min(best, time.perf_counter() - t1)
    # correctness leg 2: the fixed seed list replayed bit-identically at
    # the bench size (warm engines serve the SAME cached executable, so
    # this also pins the cache path); warm runs must never retrace
    bitident = bool(np.array_equal(first.states, last.states))
    warm_retraces = int(telemetry.counter_value(
        "engine_trace_total", kind="param_replay") - tr0)
    # correctness leg 3: the same fixed seeds replay the n-qubit run
    # bit-identically on the full (8-virtual-device) mesh -- the sharded
    # engine replays lanes sequentially with donated buffers, so this
    # pins the acceptance contract beyond density-matrix reach
    mesh_devices = jax.device_count()
    mesh_bitident = None
    if mesh_devices >= 2:
        env_mesh = qt.createQuESTEnv(jax.devices())
        ma = traj.run_ensemble(circ, env=env_mesh, seeds=seeds[:2],
                               max_batch=2)
        mb = traj.run_ensemble(circ, env=env_mesh, seeds=seeds[:2],
                               max_batch=2)
        mesh_bitident = bool(np.array_equal(ma.states, mb.states))
    traj_per_sec = t / best
    site_rate = sites * traj_per_sec
    ref = REF_DENSITY_CHANNEL_OPS_PER_SEC.get((14, "r4"))
    return {
        "config": "trajectories_20q",
        "metric": f"trajectories/sec, {n}q noisy circuit ({sites} channel "
                  f"sites) as one batch-{t} vmap ensemble at state-vector "
                  "cost",
        "value": round(traj_per_sec, 2),
        "unit": "traj/sec",
        "vs_baseline": round(site_rate / ref, 2) if ref else None,
        "detail": {
            "qubits": n,
            "num_trajectories": t,
            "channel_sites": sites,
            "ensemble_mean_ok": mean_ok,
            "ensemble_mean_err": round(mean_err, 4),
            "ensemble_mean_tol": round(mean_tol, 4),
            "ensemble_mean_trajectories": t_small,
            "seed_replay_bitident": bitident,
            "mesh_devices": mesh_devices,
            "mesh_replay_bitident": mesh_bitident,
            "warm_retraces": warm_retraces,
            "cold_ensemble_ms": round(cold_s * 1e3, 1),
            "warm_ensemble_ms": round(best * 1e3, 2),
            "channel_sites_per_sec": round(site_rate, 2),
            "density14_anchor_ops_per_sec": ref,
            "vs_baseline_note": "channel-sites/sec over the density14 r4 "
                                "anchor: trajectory sites at 20q (2^20 "
                                "amps/lane) vs density channel ops at 14q "
                                "(2^28 amps)",
        },
    }


def bench_resilience(n: int, depth: int, reps: int) -> dict:
    """CI-gate config ``resilience_20q``: what arming the resilience layer
    (ISSUE 7) costs on the serving path. Injection sites live at TRACE
    time, so the honest steady-state metric is the warm compiled replay
    with a fault plan armed (and already fired + retried during trace) vs
    the clean warm replay -- the workflow gates that overhead < 10%. The
    trace-time retry cost and the segmented-run (checkpoint-per-boundary)
    cost are recorded as informational fields, and the row re-proves the
    preempt -> resume bit-identity contract end to end."""
    import tempfile
    import time

    import jax

    import quest_tpu as qt
    from quest_tpu import telemetry
    from quest_tpu.resilience import (QuESTPreemptionError, fault_plan,
                                      resume_segmented)

    env = qt.createQuESTEnv(jax.devices()[:1])
    k = max(reps, 7)

    def trace(circ):
        """(register, first-run seconds) -- trace + first execution."""
        q = qt.createQureg(n, env)
        t0 = time.perf_counter()
        circ.run(q)
        q.amps.block_until_ready()
        return q, time.perf_counter() - t0

    clean = build_circuit(n, depth).fused(max_qubits=5, pallas=True)
    clean_q, _ = trace(clean)

    r0 = telemetry.counter_value("retry_attempts_total",
                                 site="pallas.dispatch", outcome="retried")
    with fault_plan("pallas.dispatch:transient:1"):
        armed = build_circuit(n, depth).fused(max_qubits=5, pallas=True)
        armed_q, retry_trace_s = trace(armed)
    retries = telemetry.counter_value(
        "retry_attempts_total", site="pallas.dispatch",
        outcome="retried") - r0

    # warm steady state, INTERLEAVED best-of-k so host drift hits both
    # variants equally (back-to-back blocks made the gate noise-bound);
    # the armed replays run with the plan re-armed, as production would
    clean_s = armed_s = float("inf")
    for _ in range(k):
        t0 = time.perf_counter()
        clean.run(clean_q)
        clean_q.amps.block_until_ready()
        clean_s = min(clean_s, time.perf_counter() - t0)
        with fault_plan("pallas.dispatch:transient:1"):
            t0 = time.perf_counter()
            armed.run(armed_q)
            armed_q.amps.block_until_ready()
            armed_s = min(armed_s, time.perf_counter() - t0)

    # segmented execution + the preempt -> resume bit-identity proof
    ref = qt.createQureg(n, env)
    clean.run(ref)
    want = np.asarray(ref.amps)
    with tempfile.TemporaryDirectory() as d:
        t0 = time.perf_counter()
        clean.run_segmented(qt.createQureg(n, env), checkpoint_dir=d,
                            every_n_items=1)
        seg_s = time.perf_counter() - t0
    with tempfile.TemporaryDirectory() as d:
        resume_s = 0.0
        with fault_plan("segment.boundary:preempt:1"):
            try:
                clean.run_segmented(qt.createQureg(n, env),
                                    checkpoint_dir=d, every_n_items=1)
                resumed = None  # single-segment plan: nothing to preempt
            except QuESTPreemptionError:
                t0 = time.perf_counter()
                resumed = resume_segmented(clean, d, env)
                resume_s = time.perf_counter() - t0
        gens = sum(1 for g in os.listdir(d) if g.startswith("gen_"))
        bitident = (resumed is not None
                    and np.array_equal(want, np.asarray(resumed.amps)))

    return {
        "config": "resilience_20q",
        "metric": f"{n}q fused-pallas steady-state runs/sec with a fault "
                  "plan armed (trace-time injection + retry already paid)",
        "value": round(1.0 / armed_s, 2),
        "unit": "runs/sec",
        "vs_baseline": None,
        "detail": {
            "qubits": n,
            "depth": depth,
            "clean_run_ms": round(clean_s * 1e3, 2),
            "armed_run_ms": round(armed_s * 1e3, 2),
            "overhead_frac": round(armed_s / clean_s - 1.0, 4),
            "retry_trace_ms": round(retry_trace_s * 1e3, 1),
            "retries_observed": int(retries),
            "segmented_run_ms": round(seg_s * 1e3, 1),
            "segmented_over_clean": round(seg_s / clean_s, 2),
            "resume_ms": round(resume_s * 1e3, 1),
            "checkpoint_generations": int(gens),
            "resume_bitident": bool(bitident),
        },
    }


def bench_sentinel(n: int, depth: int, reps: int) -> dict:
    """CI-gate config ``sentinel_20q``: what arming the integrity
    sentinels (ISSUE 8) costs when nothing is wrong, and proof that
    recovery works when something is. The gated ``overhead_frac`` is the
    DIRECTLY timed per-boundary probe work (baseline capture + the
    norm+checksum checks -- the only work the armed path adds) over the
    clean warm run; the run-level A/B is recorded alongside as
    ``ab_overhead_frac`` but not gated, because checkpoint-I/O noise on a
    ~2s segmented run is an order of magnitude larger than the ~10ms the
    probes actually cost. The workflow gates overhead_frac < 5%. The row
    then injects a single-bit flip mid-run and re-proves the
    rollback-and-replay contract: the healed run must be BIT-IDENTICAL
    to the uncorrupted one (``recovery_bitident``)."""
    import tempfile
    import time

    import jax

    import quest_tpu as qt
    from quest_tpu import telemetry
    from quest_tpu.resilience import (fault_plan, segment_plan, sentinel,
                                      sentinel_policy)

    env = qt.createQuESTEnv(jax.devices()[:1])
    k = max(reps, 7)
    spec = "norm:segment,checksum:segment"

    circ = build_circuit(n, depth).fused(max_qubits=5, pallas=True)
    ref = qt.createQureg(n, env)
    circ.run(ref)  # warms the fused plan; segmented runs are bit-equal
    want = np.asarray(ref.amps)

    with tempfile.TemporaryDirectory() as dc, \
            tempfile.TemporaryDirectory() as da:
        # warm both variants (segment executables compile once)
        circ.run_segmented(env, checkpoint_dir=dc, every_n_items=8)
        with sentinel_policy(spec):
            circ.run_segmented(env, checkpoint_dir=da, every_n_items=8)
        telemetry.reset()
        # warm steady state, INTERLEAVED best-of-k (the bench_resilience
        # discipline) with the in-rep ORDER alternating: checkpoint I/O
        # noise on these runs is tens of ms, so a fixed clean-then-armed
        # order would bias whichever leg consistently runs second
        def _one(armed: bool) -> float:
            if armed:
                with sentinel_policy(spec):
                    t0 = time.perf_counter()
                    out = circ.run_segmented(env, checkpoint_dir=da,
                                             every_n_items=8)
                    out.amps.block_until_ready()
                    return time.perf_counter() - t0
            t0 = time.perf_counter()
            out = circ.run_segmented(env, checkpoint_dir=dc,
                                     every_n_items=8)
            out.amps.block_until_ready()
            return time.perf_counter() - t0

        clean_s = armed_s = float("inf")
        for i in range(k):
            for armed in ((False, True) if i % 2 == 0 else (True, False)):
                dt = _one(armed)
                if armed:
                    armed_s = min(armed_s, dt)
                else:
                    clean_s = min(clean_s, dt)
        checks = (telemetry.counter_value("sentinel_checks_total",
                                          kind="norm", outcome="ok")
                  + telemetry.counter_value("sentinel_checks_total",
                                            kind="checksum", outcome="ok"))
        breaches = (telemetry.counter_value("sentinel_checks_total",
                                            kind="norm", outcome="breach")
                    + telemetry.counter_value("sentinel_checks_total",
                                              kind="checksum",
                                              outcome="breach"))

    # the gated overhead: time the probe work itself (best-of-k) and
    # scale by boundaries-per-run -- deterministic where the run-level
    # A/B above is noise-bound (see docstring)
    pol = sentinel.SentinelPolicy.parse(spec)
    boundaries = len(segment_plan(circ._tape, n, 8)) - 1
    sentinel.check_qureg(ref, policy=pol, tick=1)  # compile the checks
    probe_s = float("inf")
    for _ in range(k):
        t0 = time.perf_counter()
        np.array(ref.amps)  # what _capture_baseline costs
        sentinel.check_qureg(ref, policy=pol, tick=1)
        probe_s = min(probe_s, time.perf_counter() - t0)
    overhead = probe_s * boundaries / clean_s

    # the recovery proof: flip one amplitude bit after the second
    # segment; the sentinels must catch it at that boundary, roll back to
    # the last verified generation, and replay to the bit-exact state
    telemetry.reset()
    with tempfile.TemporaryDirectory() as d:
        with sentinel_policy(spec):
            with fault_plan("state.corrupt:bitflip1:2"):
                t0 = time.perf_counter()
                healed = circ.run_segmented(env, checkpoint_dir=d,
                                            every_n_items=1)
                heal_s = time.perf_counter() - t0
        recovery_bitident = np.array_equal(want, np.asarray(healed.amps))
    rollbacks = telemetry.counter_value("segmented_rollbacks_total",
                                        outcome="replayed")

    return {
        "config": "sentinel_20q",
        "metric": f"{n}q segmented runs/sec with norm+checksum integrity "
                  "sentinels armed (zero breaches -- the pure probe cost)",
        "value": round(1.0 / armed_s, 2),
        "unit": "runs/sec",
        "vs_baseline": None,
        "detail": {
            "qubits": n,
            "depth": depth,
            "sentinel_spec": spec,
            "clean_run_ms": round(clean_s * 1e3, 2),
            "armed_run_ms": round(armed_s * 1e3, 2),
            "overhead_frac": round(overhead, 4),
            "ab_overhead_frac": round(armed_s / clean_s - 1.0, 4),
            "probe_ms_per_boundary": round(probe_s * 1e3, 2),
            "boundaries_per_run": int(boundaries),
            "checks_executed": int(checks),
            "armed_breaches": int(breaches),
            "heal_run_ms": round(heal_s * 1e3, 1),
            "rollbacks_replayed": int(rollbacks),
            "recovery_bitident": bool(recovery_bitident),
        },
    }


def bench_comm(n: int, depth: int, reps: int) -> dict:
    """CI-gate config ``comm_20q`` (round 8, ISSUE 10): the pipelined-
    collectives A/B on a real multi-device mesh. Runs the SAME random
    Clifford+T circuit monolithically (comm_pipeline=1) and pipelined
    (depth 4) under the explicit scheduler and asserts the final states
    are BIT-IDENTICAL (pipelining only re-times traffic; the sliced
    blend/mask/scatter compute is elementwise, so equality is exact, not
    approximate). The trace-time comm model is then re-planned WITH the
    pipeline stamp and cross-checked: journal verifier green
    (check_schedule re-prices the stamped journal -- the proof chunk-unit
    pricing is depth-invariant) and telemetry chunk-units == the model.
    Falls back to the host CPU devices when the default backend has a
    single device (the CI box forces 8 via
    ``xla_force_host_platform_device_count``); emits a note row when no
    multi-device mesh is constructible."""
    import time

    import jax

    import quest_tpu as qt
    from quest_tpu import telemetry
    from quest_tpu.analysis import check_circuit_comm
    from quest_tpu.parallel.scheduler import comm_chunks

    pipe = 4
    metric = (f"pipelined collectives A/B, {n}q random Clifford+T under "
              f"the explicit scheduler (monolithic vs depth-{pipe})")
    devs = jax.devices()
    if len(devs) < 2:
        try:
            devs = jax.devices("cpu")
        except RuntimeError:
            pass
    if len(devs) < 2:
        return {"config": "comm_20q", "metric": metric, "value": None,
                "unit": "x speedup", "vs_baseline": None,
                "note": "needs >= 2 devices "
                        "(set xla_force_host_platform_device_count)"}
    ndev = 1 << (len(devs).bit_length() - 1)
    env = qt.createQuESTEnv(devs[:ndev])
    circ = build_circuit(n, depth)
    k = max(min(reps, 3), 1)

    def run_leg(pl):
        # both legs run 1 warm + k timed applications from the same init,
        # so their final states stay directly comparable
        q = qt.createQureg(n, env)
        qt.initPlusState(q)
        with qt.explicit_mesh(env.mesh, comm_pipeline=pl):
            circ.run(q)
            q.amps.block_until_ready()
            best = float("inf")
            for _ in range(k):
                t0 = time.perf_counter()
                circ.run(q)
                q.amps.block_until_ready()
                best = min(best, time.perf_counter() - t0)
        return q, best

    q_mono, mono_s = run_leg(1)
    q_pipe, pipe_s = run_leg(pipe)
    bitident = np.array_equal(qt.get_np(q_mono), qt.get_np(q_pipe))

    t0 = sum(telemetry.counters("comm_chunk_units_total").values())
    findings, stats, journal = check_circuit_comm(
        circ, env.mesh, comm_pipeline=pipe, location="comm_20q")
    t1 = sum(telemetry.counters("comm_chunk_units_total").values())
    model = comm_chunks(stats)
    errors = sum(1 for f in findings if f.severity == "error")
    return {
        "config": "comm_20q",
        "metric": metric,
        "value": round(mono_s / pipe_s, 3),
        "unit": "x speedup",
        "vs_baseline": None,
        "detail": {
            "qubits": n,
            "depth": depth,
            "devices": ndev,
            "pipeline_depth": pipe,
            "monolithic_ms": round(mono_s * 1e3, 2),
            "pipelined_ms": round(pipe_s * 1e3, 2),
            "pipelined_bitident": bool(bitident),
            "journal_stamp": list(journal[0]) if journal else None,
            "journal_errors": int(errors),
            "model_chunk_units": round(model, 4),
            "telemetry_chunk_units": round(t1 - t0, 6),
            "model_matches_telemetry": bool(abs((t1 - t0) - model) < 1e-6),
        },
    }


def _comm_config(reps: int, smoke: bool) -> dict:
    """Run the comm_20q A/B, re-execing into an 8-virtual-host-device
    subprocess when this process's backend has a single device (the host
    device count is fixed at backend init, so it cannot be raised here).
    ``_QUEST_COMM_SUBPROC`` marks the child so a box where the flag does
    not take still terminates (bench_comm then emits its note row)."""
    import jax

    if jax.device_count() >= 2 or "_QUEST_COMM_SUBPROC" in os.environ:
        return bench_comm(20, 2 if smoke else 4, reps)
    flags = (os.environ.get("XLA_FLAGS", "")
             + " --xla_force_host_platform_device_count=8").strip()
    return _subprocess_config(
        ["--config", "comm", "--reps", str(reps)]
        + (["--smoke"] if smoke else []),
        env={"XLA_FLAGS": flags, "_QUEST_COMM_SUBPROC": "1"},
        budget_s=1800, unit="x speedup", slug="comm_20q",
        metric="pipelined collectives A/B, 20q random Clifford+T under "
               "the explicit scheduler (monolithic vs depth-4)")


def bench_dispatch(n: int, depth: int, reps: int) -> dict:
    """CI-gate config ``dispatch_20q`` (round 13, ISSUE 12): the fused
    circuit as frame-identity segment programs
    (``Circuit.compiled_segments``: ONE device dispatch per segment,
    however many tape entries it holds). Telemetry deltas prove the count
    exactly -- one ``device_dispatch_total{route="segment"}`` per segment
    -- and the headline is the tape entries one dispatch carries. The
    chain is asserted run-to-run DETERMINISTIC (bit-identical) and must
    agree with the whole-tape program ``Circuit.run`` dispatches within
    the dtype band; exact bit-identity ACROSS program granularities is an
    XLA-CPU non-goal (cross-program fma recontraction -- the documented
    tests/test_sharded_df.py caveat; on TPU the Mosaic kernel is opaque
    to XLA and the granularities coincide)."""
    import time

    import jax

    import quest_tpu as qt
    from quest_tpu import telemetry
    from quest_tpu.precision import real_dtype

    metric = (f"single-dispatch segment programs, {n}q fused Clifford+T "
              f"(tape entries per device dispatch)")
    env = qt.createQuESTEnv(jax.devices()[:1])
    fused = build_circuit(n, depth).fused(max_qubits=5, pallas=True)
    items = len(fused)

    def whole_state():
        q = qt.createQureg(n, env)
        qt.initPlusState(q)
        fused.run(q)
        return np.asarray(jax.device_get(q.amps))

    chain = fused.compiled_segments()           # whole tape, coarsest cuts

    def seg_state():
        q = qt.createQureg(n, env)
        qt.initPlusState(q)
        q.put(chain(q.amps))
        return np.asarray(jax.device_get(q.amps))

    a1 = whole_state()
    s0 = telemetry.counter_value("device_dispatch_total", route="segment")
    b1 = seg_state()
    seg_dispatches = int(telemetry.counter_value(
        "device_dispatch_total", route="segment") - s0)
    bit_identical = np.array_equal(b1, seg_state())
    route_maxdiff = float(np.max(np.abs(a1 - b1)))
    tol = 1e-13 if np.dtype(real_dtype()) == np.dtype("float64") else 1e-5
    del a1, b1

    # timing: 1 warm (above) + best-of-k
    k = max(min(reps, 3), 1)
    q = qt.createQureg(n, env)
    qt.initPlusState(q)
    amps = q.amps
    best_seg = float("inf")
    for _ in range(k):
        t0 = time.perf_counter()
        amps = chain(amps)
        amps.block_until_ready()
        best_seg = min(best_seg, time.perf_counter() - t0)
    del amps, q

    return {
        "config": "dispatch_20q",
        "metric": metric,
        "value": round(items / chain.num_segments, 2),
        "unit": "tape entries per dispatch",
        "vs_baseline": None,
        "detail": {
            "qubits": n,
            "depth": depth,
            "tape_items": items,
            "num_segments": chain.num_segments,
            "segment_dispatches": seg_dispatches,
            "bit_identical": bool(bit_identical),
            "route_maxdiff": route_maxdiff,
            "route_agreement_ok": bool(route_maxdiff <= tol),
            "segment_ms": round(best_seg * 1e3, 2),
        },
    }


def bench_sample(n: int, depth: int, shots: int, reps: int) -> dict:
    """CI-gate config ``sample_20q`` (round 19): on-device batched
    sampling. Headline is shots/sec through the batch-8 trajectory route
    (8 vmap lanes, each ending in the on-device S-shot sampler via the
    Engine ``finalize`` hook -- T*S int32 words cross to the host, never
    T*2^n amplitudes). The gate evidence rides in the detail: the
    one-dispatch request leg (circuit + S shots as ONE
    ``device_dispatch_total{route=request}`` launch,
    ``dispatches_per_request == 1``), its sampled marginal over a
    6-qubit target subset against the exact ``calcProbOfAllOutcomes``
    oracle (``marginals_match_oracle``), and fixed-seed replay
    bit-identity of the shot table (``seed_replay_bitident``)."""
    import time

    import jax

    import quest_tpu as qt
    from quest_tpu import telemetry
    from quest_tpu.engine import P
    from quest_tpu.ops import init as ops_init
    from quest_tpu.precision import real_dtype
    from quest_tpu.sampling import request as rq

    batch = 8
    metric = (f"shots/sec, {n}q circuit + on-device batched sampling "
              f"(batch-{batch} vmap lanes, S={shots} shots each)")
    env = qt.createQuESTEnv(jax.devices()[:1])
    dtype = np.dtype(real_dtype())

    # --- the one-dispatch request leg: correctness evidence ----------
    circ = build_circuit(n, depth)
    targets = tuple(range(6))           # 64-outcome marginal vs oracle
    s_req = max(int(shots), 4096)
    exe = rq.sample_request(circ, targets=targets, shots=s_req,
                            donate=False)

    def fresh():
        return ops_init.init_classical(1 << n, dtype, 0)

    r0 = telemetry.counter_value("device_dispatch_total", route="request")
    out = rq.to_host(exe(fresh(), 7))
    dispatches = int(telemetry.counter_value(
        "device_dispatch_total", route="request") - r0)
    table = out["shots"]
    transfer = int(telemetry.snapshot()["gauges"]
                   ["sample_host_transfer_bytes"])
    replay = rq.to_host(exe(fresh(), 7))["shots"]
    seed_replay_bitident = bool(np.array_equal(table, replay))

    # exact oracle: evolve the same circuit, read the 64 marginal
    # probabilities, compare against the empirical shot frequencies
    q = qt.createQureg(n, env)
    q.put(circ.fused(max_qubits=5, pallas=True).compiled_segments()(q.amps))
    oracle = np.asarray(qt.calcProbOfAllOutcomes(q, targets),
                        dtype=np.float64)
    freq = np.bincount(table, minlength=1 << len(targets)) / float(s_req)
    marginal_maxdiff = float(np.max(np.abs(freq - oracle)))
    tol = 4.0 / float(np.sqrt(s_req))
    del q, out, table, replay

    # --- the batch-8 throughput leg ----------------------------------
    # one mid-circuit measurement makes the tape carry the one named
    # seed Param the trajectory route binds per lane; the terminal
    # sampler composes in as the Engine finalize stage
    ens = build_circuit(n, depth)
    ens.applyMidMeasurement(0, P("m"), site=7)
    res = qt.run_ensemble(ens, batch, shots=int(shots), shot_seed=11)
    assert res.shot_tables.shape == (batch, int(shots))
    best = float("inf")
    for _ in range(max(min(reps, 3), 1)):
        t0 = time.perf_counter()
        res = qt.run_ensemble(ens, batch, shots=int(shots), shot_seed=11)
        best = min(best, time.perf_counter() - t0)
    total_shots = batch * int(shots)
    rate = total_shots / best

    return {
        "config": "sample_20q",
        "metric": metric,
        "value": round(rate, 1),
        "unit": "shots/sec",
        "vs_baseline": None,
        "detail": {
            "qubits": n,
            "depth": depth,
            "batch": batch,
            "shots_per_lane": int(shots),
            "total_shots": total_shots,
            "shots_per_sec": round(rate, 1),
            "ensemble_ms": round(best * 1e3, 2),
            "request_shots": s_req,
            "dispatches_per_request": dispatches,
            "marginals_match_oracle": bool(marginal_maxdiff <= tol),
            "marginal_maxdiff": marginal_maxdiff,
            "marginal_tol": tol,
            "seed_replay_bitident": seed_replay_bitident,
            "host_transfer_bytes": transfer,
            "transfer_is_o_s": bool(transfer == s_req * 4),
        },
    }


def bench_vqe(n: int, depth: int, reps: int) -> dict:
    """CI-gate config ``vqe_20q`` (round 20): the adjoint-mode gradient
    engine (quest_tpu/gradients/, docs/gradients.md). Headline is
    gradient-steps/sec through ``Engine.submit_grad`` at batch-8 (8
    concurrent optimizer lanes coalesce into ONE vmapped gradient
    program). The gate evidence rides in the detail: a warm sequential
    loop proving ``dispatches_per_grad == 1``
    (``device_dispatch_total{route=grad_request}`` deltas) and
    ``retraces == 0`` (``engine_trace_total`` flat), plus an
    adjoint-vs-``jax.grad`` A/B -- same circuit, same Hamiltonian, the
    adjoint's ~3-sweep backward walk timed against reverse-mode AD
    through the raw replay (which saves O(P) intermediate states), with
    values and gradients asserted to agree."""
    import time

    import jax
    import jax.numpy as jnp

    import quest_tpu as qt
    from quest_tpu import telemetry
    from quest_tpu.calculations import expec_pauli_sum_amps
    from quest_tpu.engine import Engine
    from quest_tpu.precision import real_dtype

    batch = 8
    metric = (f"gradient-steps/sec, {n}q VQE ansatz adjoint gradients "
              f"(batch-{batch} coalesced submit_grad lanes)")
    env = qt.createQuESTEnv(jax.devices()[:1])
    dtype = np.dtype(real_dtype())
    atol = 1e-5 if dtype == np.float32 else 1e-12

    circ = serving_ansatz(n, depth)
    names = circ.param_names
    rng = np.random.RandomState(20)
    codes = rng.randint(0, 4, size=(6, n)).astype(np.int32)
    coeffs = rng.normal(size=6)

    def draw():
        return {nm: float(v)
                for nm, v in zip(names, rng.uniform(0, 2 * np.pi,
                                                    len(names)))}

    # --- adjoint-vs-jax.grad A/B leg (smaller size: reverse-mode AD
    # through the replay checkpoints every intermediate state, O(P)
    # memory -- the cost the adjoint method exists to avoid) ------------
    n_ab = min(n, 14)
    ab_circ = serving_ansatz(n_ab, depth)
    ab_params = {nm: float(v) for nm, v in zip(
        ab_circ.param_names,
        rng.uniform(0, 2 * np.pi, len(ab_circ.param_names)))}
    ab_codes = codes[:, :n_ab].copy()
    gx = ab_circ.gradient((ab_codes, coeffs), donate=False)
    q = qt.createQureg(n_ab, env)
    amps_np = np.asarray(q.amps)
    out = gx(q.amps, ab_params)
    jax.block_until_ready(out["value"])
    num_slots = len(out["slot_grads"])

    lifted = ab_circ.lifted()
    replay = ab_circ._replay_fn(lifted)
    cf = jnp.asarray(coeffs, dtype=dtype)
    codes_t = tuple(tuple(int(x) for x in row) for row in ab_codes)

    @jax.jit
    def value_fn(vals):
        psi = replay(jnp.asarray(amps_np, dtype=dtype), vals)
        return expec_pauli_sum_amps(psi, cf, codes=codes_t, n=n_ab,
                                    density=False)

    grad_fn = jax.jit(jax.grad(value_fn))
    jvals = tuple(jnp.asarray(v) for v in gx.bind(ab_params))
    ref_val = value_fn(jvals)
    ref_grads = jax.block_until_ready(grad_fn(jvals))
    grads_match_jax = bool(
        abs(float(out["value"]) - float(ref_val)) <= atol
        and all(np.allclose(np.asarray(g), np.asarray(rg), atol=atol,
                            rtol=0)
                for g, rg in zip(out["slot_grads"], ref_grads)))
    best_adj = best_ad = float("inf")
    for _ in range(max(min(reps, 3), 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(gx(jnp.asarray(amps_np), ab_params)["value"])
        best_adj = min(best_adj, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready((value_fn(jvals), grad_fn(jvals)))
        best_ad = min(best_ad, time.perf_counter() - t0)

    # --- the serving legs: warm loop accounting + batch-8 throughput --
    eng = Engine(circ, env, hamiltonian=(codes, coeffs), max_batch=batch,
                 max_delay_ms=0.5)
    try:
        base = draw()
        eng.warmup_grad(base)
        # warm batch-8 round untimed: traces the padded vmap width once
        [f.result(timeout=600)
         for f in [eng.submit_grad(draw()) for _ in range(batch)]]
        tr0 = telemetry.counter_value("engine_trace_total",
                                      kind="param_replay")
        d0 = telemetry.counter_value("device_dispatch_total",
                                     route="grad_request")
        g0 = telemetry.counter_value("grad_requests_total")
        steps = 6
        for step in range(steps):
            p = {k: v + 0.01 * step for k, v in base.items()}
            eng.submit_grad(p).result(timeout=600)
        retraces = int(telemetry.counter_value(
            "engine_trace_total", kind="param_replay") - tr0)
        dispatches = int(telemetry.counter_value(
            "device_dispatch_total", route="grad_request") - d0)
        grad_reqs = int(telemetry.counter_value("grad_requests_total") - g0)
        dispatches_per_grad = dispatches / max(grad_reqs, 1)
        best_batch = float("inf")
        for _ in range(max(min(reps, 3), 1)):
            sweep = [draw() for _ in range(batch)]
            t0 = time.perf_counter()
            futs = [eng.submit_grad(p) for p in sweep]
            outs = [f.result(timeout=600) for f in futs]
            best_batch = min(best_batch, time.perf_counter() - t0)
        assert len(outs) == batch and all(
            len(grads) == len(names) for _, grads in outs)
        rate = batch / best_batch
    finally:
        eng.close()

    return {
        "config": "vqe_20q",
        "metric": metric,
        "value": round(rate, 2),
        "unit": "grad-steps/sec",
        "vs_baseline": None,
        "detail": {
            "qubits": n,
            "depth": depth,
            "batch": batch,
            "params": len(names),
            "grad_steps_per_sec": round(rate, 2),
            "batch_ms": round(best_batch * 1e3, 2),
            "warm_steps": steps,
            "retraces": retraces,
            "dispatches_per_grad": dispatches_per_grad,
            "ab_qubits": n_ab,
            "ab_params": num_slots,
            "adjoint_ms": round(best_adj * 1e3, 2),
            "jax_grad_ms": round(best_ad * 1e3, 2),
            "adjoint_vs_jax_grad": round(best_ad / best_adj, 2),
            "grads_match_jax": grads_match_jax,
        },
    }


def _trajectories_config(reps: int, smoke: bool) -> dict:
    """Run the trajectories_20q row, re-execing into an 8-virtual-device
    subprocess when this process's backend has a single device, so the
    mesh-replay leg (fixed seeds bit-identical on the sharded route at
    20q) runs even on single-device CI hosts -- the ``_comm_config``
    pattern."""
    import jax

    if jax.device_count() >= 2 or "_QUEST_TRAJ_SUBPROC" in os.environ:
        return bench_trajectories(20, 8 if smoke else 16, reps)
    flags = (os.environ.get("XLA_FLAGS", "")
             + " --xla_force_host_platform_device_count=8").strip()
    return _subprocess_config(
        ["--config", "trajectories", "--reps", str(reps)]
        + (["--smoke"] if smoke else []),
        env={"XLA_FLAGS": flags, "_QUEST_TRAJ_SUBPROC": "1"},
        budget_s=1800, unit="traj/sec", slug="trajectories_20q",
        metric="trajectories/sec, 20q noisy circuit as one batched vmap "
               "ensemble at state-vector cost")


#: the committed full-detail artifact, written next to this file
DETAIL_FILE = "BENCH_DETAIL.json"

#: hard cap on the printed headline line (VERDICT r5 ask #1: the driver's
#: tail window must never truncate it)
_HEADLINE_MAX_BYTES = 1024


def _write_detail(configs: list) -> str:
    """Write ``BENCH_DETAIL.json``: every per-config field previously
    embedded in the giant stdout line, plus the process-wide telemetry
    snapshot (pass counts, comm chunk-units by kind, engine-fallback
    counters, Mosaic compile seconds)."""
    from quest_tpu import telemetry

    detail = {
        "schema": "quest-tpu-bench-detail/1",
        "configs": configs,
        "telemetry": telemetry.snapshot(),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        DETAIL_FILE)
    with open(path, "w") as fh:
        json.dump(detail, fh, indent=1)
        fh.write("\n")
    return path


def _roofline_summary(detail: dict | None) -> str | None:
    """One human-readable line from a config's roofline fields."""
    d = detail or {}
    if "stream_floor_ms" not in d:
        return None
    parts = [f"floor {d['stream_floor_ms']}ms/pass"]
    if "per_pass_ms" in d:
        parts.append(f"per-pass {d['per_pass_ms']}ms = "
                     f"{d.get('per_pass_vs_floor')}x floor "
                     f"over {d.get('passes')} passes")
    if "eff_bandwidth_gbs" in d:
        parts.append(f"{d['eff_bandwidth_gbs']} GB/s stream")
    return ", ".join(parts)


def _emit(headline_cfg: dict, configs: list, emit: str,
          stamp: bool = True) -> None:
    """Emit the artifact chain, then exit non-zero if any row is a failed
    child's placeholder.

    ``full`` (subprocess mode): print the config WITH its detail and this
    process's telemetry snapshot as one JSON line for the parent to
    collect; no file writes. ``headline`` (top-level): write
    ``BENCH_DETAIL.json`` and print the compact <= 1 KB headline as the
    FINAL stdout line.

    Every row made in this process gets the ``platform``/``device_kind``
    JAX reports, so no number travels without its device; a child's row
    keeps the child's. ``stamp=False`` is the no-``--config`` parent, which
    made no row itself and never initialises a JAX backend."""
    if stamp:
        import jax

        dev = jax.devices()[0]
        for c in (*configs, headline_cfg):
            c.setdefault("platform", dev.platform)
            c.setdefault("device_kind", dev.device_kind)
    _emit_rows(headline_cfg, configs, emit)
    failed = [c.get("config") for c in configs if c.get("failed")]
    if failed:
        print(f"bench.py: failed configs: {failed}", file=sys.stderr)
        sys.exit(1)


def _emit_rows(headline_cfg: dict, configs: list, emit: str) -> None:
    if emit == "full":
        out = dict(headline_cfg)
        from quest_tpu import telemetry
        detail = dict(out.get("detail") or {})
        detail["telemetry"] = telemetry.snapshot()
        out["detail"] = detail
        print(json.dumps(out))
        return
    path = _write_detail(configs)
    line = {"metric": headline_cfg["metric"],
            "value": headline_cfg.get("value"),
            "unit": headline_cfg.get("unit"),
            "vs_baseline": headline_cfg.get("vs_baseline"),
            "platform": headline_cfg.get("platform"),
            "device_kind": headline_cfg.get("device_kind")}
    roof = _roofline_summary(headline_cfg.get("detail"))
    if roof:
        line["roofline"] = roof
    if len(configs) > 1:
        # compact per-config summary: slug -> [value, vs_baseline]
        line["configs"] = {
            c.get("config", f"cfg{i}"): [c.get("value"),
                                         c.get("vs_baseline")]
            for i, c in enumerate(configs)}
    line["detail_file"] = os.path.basename(path)
    text = json.dumps(line)
    # guarantee the cap: shed optional fields before ever truncating
    for drop in ("configs", "roofline"):
        if len(text) <= _HEADLINE_MAX_BYTES:
            break
        line.pop(drop, None)
        text = json.dumps(line)
    print(text)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--qubits", type=int, default=26)
    p.add_argument("--depth", type=int, default=8)
    p.add_argument("--reps", type=int, default=5)
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes for CI (12 qubits, depth 2)")
    p.add_argument("--config",
                   choices=["all", "statevec", "density", "density_f64",
                            "f64", "plan_f64", "plan_34q_f64", *_PLAN_ROWS,
                            "20q", "24q", "26q", "serve", "resilience",
                            "sentinel", "comm", "trajectories",
                            "dispatch", "pool", "sample", "vqe"],
                   default="all",
                   help="all: every BASELINE.json milestone config (default);"
                        " statevec: one random Clifford+T run at --qubits;"
                        " 20q/24q/26q: one statevec run at that size;"
                        " density: the 14q decoherence channel;"
                        " density_f64: the same channel circuit at"
                        " QUEST_PRECISION=2 (df kraus kernel bodies);"
                        " f64: the 20q statevec at QUEST_PRECISION=2"
                        " (double-float kernels);"
                        " plan_f64: the sharded 20q PRECISION=2 df comm"
                        " plan (CI smoke gate, df chunk-units at 2x);"
                        " plan_34q_f64: the 34q PRECISION=2 sharded df"
                        " plan + deferred comm A/B;"
                        " serve: the serving-engine serve_20q config"
                        " (cold vs cached replay, batch vs loop, cache"
                        " hits);"
                        " resilience: the resilience_20q row (fault-plan"
                        " steady-state overhead, retry trace cost,"
                        " segmented checkpointing, preempt->resume"
                        " bit-identity);"
                        " sentinel: the sentinel_20q row (armed-but-clean"
                        " integrity-probe overhead <5% CI gate, SDC"
                        " rollback-and-replay bit-identity);"
                        " comm: the comm_20q row (pipelined collectives"
                        " A/B on a real multi-device mesh, bit-identity +"
                        " depth-invariant comm model asserted);"
                        " trajectories: the trajectories_20q row (T noisy"
                        " trajectories as one vmap ensemble at"
                        " state-vector cost, ensemble-mean-vs-oracle +"
                        " seed-replay bit-identity asserted);"
                        " dispatch: the dispatch_20q row (whole-segment"
                        " single dispatch: one device dispatch per"
                        " frame-identity segment, the count from"
                        " telemetry + determinism asserted);"
                        " pool: the pool_20q row (replica-pool serving:"
                        " mixed-structure open-loop load over 3 replicas,"
                        " req/sec + p50/p99, one injected replica kill"
                        " mid-run with zero lost futures + failover"
                        " bit-identity + warmed-replacement zero-retrace"
                        " asserted);"
                        " sample: the sample_20q row (on-device batched"
                        " sampling: shots/sec at batch-8 via the Engine"
                        " finalize hook, one-dispatch request leg with"
                        " sampled-marginals-vs-oracle + fixed-seed"
                        " shot-table replay bit-identity asserted);"
                        " vqe: the vqe_20q row (adjoint-mode gradient"
                        " engine: grad-steps/sec at batch-8 via"
                        " submit_grad, adjoint-vs-jax.grad A/B,"
                        " retraces==0 + dispatches_per_grad==1 asserted)")
    p.add_argument("--emit", choices=["headline", "full"],
                   default="headline",
                   help="headline: compact <=1KB final line + "
                        "BENCH_DETAIL.json (default); full: one JSON line "
                        "with embedded detail (used for subprocess "
                        "sub-configs)")
    args = p.parse_args()
    if args.smoke:
        args.qubits, args.depth = 12, 2

    import jax

    from quest_tpu.compile_cache import enable_compile_cache

    # amortise the slow Mosaic compiles across runs
    enable_compile_cache()

    # One process per chip: the no---config parent and a PRECISION=2
    # re-exec run their configs as children, so they must not take the
    # device themselves. Every other run makes its rows here, and outside
    # --smoke refuses anything but a TPU: a timing from XLA:CPU or the
    # Pallas interpreter never appears under a device metric's name (the
    # plan_* rows are trace-time counts, no device and no timing).
    parent_only = (args.config == "all" and not args.smoke) or (
        args.config in ("f64", "density_f64", "plan_f64", "plan_34q_f64")
        and os.environ.get("QUEST_PRECISION") != "2")
    if not (parent_only or args.smoke or args.config.startswith("plan_")):
        platform = jax.devices()[0].platform
        if platform != "tpu":
            print("bench.py: no TPU found (jax.devices()[0].platform = "
                  f"{platform!r}); the CPU shrink runs only under --smoke",
                  file=sys.stderr)
            sys.exit(2)

    def sync(a):
        # forces the whole donated chain to drain (see module docstring)
        return float(jax.device_get(a.reshape(-1)[0]))

    if args.config == "density":
        r = bench_density(14 if not args.smoke else 6, args.reps, sync)
        _emit(r, [r], args.emit)
        return
    if args.config == "density_f64":
        # the df kraus kernel bodies (ops/pallas_df.py _ops_body_df kraus
        # arm) were never benched before round 6 (VERDICT r5 ask #7); the
        # reference anchors apply unchanged -- its qreal IS double
        if os.environ.get("QUEST_PRECISION") != "2":
            # precision is fixed at import; re-exec with the env set
            r = _subprocess_config(
                ["--config", "density_f64", "--reps", str(args.reps)]
                + (["--smoke"] if args.smoke else []),
                env={"QUEST_PRECISION": "2"}, budget_s=2400,
                unit="ops/sec", slug="density14_f64",
                metric="channel-ops/sec, 14-qubit density matrix "
                       "(mixDepolarising+mixKrausMap, PRECISION=2 "
                       "double-float)")
            _emit(r, [r], args.emit)
            return
        r = bench_density(14 if not args.smoke else 6, args.reps, sync)
        r["config"] = "density14_f64"
        r["metric"] += " (PRECISION=2 double-float)"
        _emit(r, [r], args.emit)
        return
    if args.config == "f64":
        if os.environ.get("QUEST_PRECISION") != "2":
            # precision is fixed at import; re-exec with the env set
            r = _subprocess_config(
                ["--config", "f64", "--reps", str(args.reps),
                 "--depth", str(args.depth)]
                + (["--smoke"] if args.smoke else []),
                env={"QUEST_PRECISION": "2"}, budget_s=2400,
                unit="gates/sec", slug="f64_20q",
                metric="gate-ops/sec, 20-qubit state-vector random "
                       "Clifford+T (PRECISION=2 double-float)")
            _emit(r, [r], args.emit)
            return
        r = bench_statevec(20 if not args.smoke else 12, args.depth,
                           args.reps, sync)
        r["config"] = "f64_20q"
        r["metric"] += " (PRECISION=2 double-float)"
        # the f64 reference anchor: round-3 measured engine-f64-on-TPU
        # throughput (866 gates/s at 20q) -- the number the df path must
        # beat 10x (VERDICT r4 ask #3); the reference-CPU anchor is the
        # same f64 build as the f32 rows (its qreal IS double)
        r["detail"]["engine_f64_gates_per_sec"] = 866.0
        r["detail"]["vs_engine_f64"] = round(r["value"] / 866.0, 2)
        _emit(r, [r], args.emit)
        return
    if args.config == "plan_f64":
        if os.environ.get("QUEST_PRECISION") != "2":
            # precision is fixed at import; re-exec with the env set (the
            # df route needs QUEST_PALLAS_DF=1 off-TPU)
            r = _subprocess_config(
                ["--config", "plan_f64"],
                env={"QUEST_PRECISION": "2", "QUEST_PALLAS_DF": "1"},
                budget_s=1200, unit="chunk-units", slug="plan_20q_f64",
                metric="20q PRECISION=2 sharded df plan comm chunk-units "
                       "(8-device model, frame transposes at the df 2x "
                       "scale)")
            _emit(r, [r], args.emit)
            return
        r = plan_20q_f64_smoke()
        _emit(r, [r], args.emit)
        return
    if args.config == "plan_34q_f64":
        if os.environ.get("QUEST_PRECISION") != "2":
            r = _subprocess_config(
                ["--config", "plan_34q_f64"],
                env={"QUEST_PRECISION": "2", "QUEST_PALLAS_DF": "1"},
                budget_s=2400, unit="blocks", slug="plan_34q_f64",
                metric="34q PRECISION=2 distributed plan: per-shard "
                       "double-float PallasRuns for v5p-16 execution")
            _emit(r, [r], args.emit)
            return
        r = plan_34q_f64()
        _emit(r, [r], args.emit)
        return
    if args.config in _PLAN_ROWS:
        r = _PLAN_ROWS[args.config]()
        _emit(r, [r], args.emit)
        return
    if args.config == "serve":
        r = bench_serving(20, 2 if args.smoke else 4, args.reps)
        _emit(r, [r], args.emit)
        return
    if args.config == "resilience":
        r = bench_resilience(20, 2 if args.smoke else 4, args.reps)
        _emit(r, [r], args.emit)
        return
    if args.config == "sentinel":
        r = bench_sentinel(20, 2 if args.smoke else 4, args.reps)
        _emit(r, [r], args.emit)
        return
    if args.config == "comm":
        r = _comm_config(args.reps, args.smoke)
        _emit(r, [r], args.emit)
        return
    if args.config == "trajectories":
        r = _trajectories_config(args.reps, args.smoke)
        _emit(r, [r], args.emit)
        return
    if args.config == "dispatch":
        r = bench_dispatch(20, 2 if args.smoke else 4, args.reps)
        _emit(r, [r], args.emit)
        return
    if args.config == "pool":
        r = bench_pool(20, 2 if args.smoke else 4, args.reps)
        _emit(r, [r], args.emit)
        return
    if args.config == "sample":
        r = bench_sample(20, 2 if args.smoke else 4,
                         8192 if args.smoke else 65536, args.reps)
        _emit(r, [r], args.emit)
        return
    if args.config == "vqe":
        r = bench_vqe(20, 2 if args.smoke else 4, args.reps)
        _emit(r, [r], args.emit)
        return
    if args.config in ("20q", "24q", "26q"):
        r = bench_statevec(int(args.config[:-1]), args.depth, args.reps,
                           sync)
        _emit(r, [r], args.emit)
        return
    if args.config == "statevec" or args.smoke:
        r = bench_statevec(args.qubits, args.depth, args.reps, sync)
        cfgs = [r]
        if args.smoke:
            # the CI bench-smoke gate asserts this config's relocation
            # A/B fields and its telemetry-vs-model cross-check
            cfgs.append(plan_20q_relocation_smoke())
            # ... and the two-slice row: hierarchical DCN chunk-units
            # strictly below flat on the modeled 2x8 mesh, per-(kind,
            # link) telemetry == model (ISSUE 14 gate)
            cfgs.append(plan_34q_2slice())
            # ... and the serving engine's serve_20q row: cached-replay
            # vs cold-compile ratio, batch-vs-loop bit-identity, zero
            # warm retraces, executable-cache hit counters
            cfgs.append(bench_serving(20, 2, 3))
            # ... and the sharded PRECISION=2 df plan's presence, 2x df
            # chunk-unit accounting and zero f64-engine fallbacks
            # (QUEST_PRECISION is fixed at import: budgeted subprocess)
            cfgs.append(_subprocess_config(
                ["--config", "plan_f64"],
                env={"QUEST_PRECISION": "2", "QUEST_PALLAS_DF": "1"},
                budget_s=1200, unit="chunk-units", slug="plan_20q_f64",
                metric="20q PRECISION=2 sharded df plan comm chunk-units "
                       "(8-device model, frame transposes at the df 2x "
                       "scale)"))
            # ... and the resilience row: armed-fault-plan steady-state
            # overhead (<10% CI gate), segmented checkpointing cost, and
            # the preempt -> resume bit-identity contract
            cfgs.append(bench_resilience(20, 2, 3))
            # ... and the sentinel row: armed-but-clean integrity-probe
            # overhead (<5% CI gate) and the SDC rollback-and-replay
            # bit-identity contract
            cfgs.append(bench_sentinel(20, 2, 3))
            # ... and the comm row: pipelined-collectives A/B on the
            # 8-virtual-device mesh -- bit-identity at depth 4 and the
            # depth-invariant comm model == telemetry (ISSUE 10 gate)
            cfgs.append(_comm_config(3, True))
            # ... and the trajectory row: T noisy trajectories as one
            # vmap ensemble -- ensemble mean inside the 4/sqrt(T) band
            # of the density oracle, fixed seeds replay bit-identically
            # (incl. the 20q sharded-mesh leg via the 8-device subprocess)
            cfgs.append(_trajectories_config(2, True))
            # ... and the dispatch row: whole-segment single-dispatch
            # A/B -- one dispatch per tape item vs one per segment,
            # telemetry-counted, routes deterministic (ISSUE 12 gate)
            cfgs.append(bench_dispatch(20, 2, 3))
            # ... and the pool row: replica-pool serving under one
            # injected replica kill -- zero lost futures, failover
            # bit-identity, warmed-replacement zero-retrace (ISSUE 13
            # gate)
            cfgs.append(bench_pool(20, 2, 3))
            # ... and the sample row: on-device batched sampling --
            # circuit + S shots as ONE request dispatch, sampled
            # marginals vs the exact oracle, fixed-seed shot-table
            # replay bit-identity, batch-8 shots/sec (ISSUE 18 gate)
            cfgs.append(bench_sample(20, 2, 8192, 3))
            # ... and the vqe row: adjoint-mode gradients served as
            # first-class traffic -- one grad_request dispatch per step,
            # zero warm retraces, batch-8 grad-steps/sec and the
            # adjoint-vs-jax.grad A/B (ISSUE 19 gate)
            cfgs.append(bench_vqe(20, 2, 3))
        _emit(r, cfgs, args.emit)
        return

    # all milestone configs (BASELINE.json "configs"); headline = 26q.
    # Every config is a child process in turn: a chip belongs to one
    # process at a time, so this parent never initialises a JAX backend,
    # and one slow cold compile (the density config's 2^28-amp Kraus
    # programs took many minutes) cannot sink the whole bench artifact
    # (the persistent compile cache makes the next attempt fast). A failed
    # child leaves its row with ``value: None`` and makes _emit exit 1.
    reps = ["--reps", str(args.reps)]

    def child(config, slug, unit, metric, flags=reps, budget_s=1800):
        return _subprocess_config(["--config", config, *flags], budget_s,
                                  metric, unit=unit, slug=slug)

    configs = []
    for n in (20, 24, 26):
        configs.append(child(
            f"{n}q", f"{n}q", "gates/sec",
            f"gate-ops/sec, {n}-qubit state-vector random Clifford+T",
            flags=reps + ["--depth", str(args.depth)]))
    configs.append(_budgeted_density(args.reps, budget_s=900))
    configs.append(_subprocess_config(
        ["--config", "f64", "--reps", str(args.reps),
         "--depth", str(args.depth)],
        budget_s=2400, env={"QUEST_PRECISION": "2"}, unit="gates/sec",
        slug="f64_20q",
        metric="gate-ops/sec, 20-qubit state-vector random Clifford+T "
               "(PRECISION=2 double-float)"))
    configs.append(_subprocess_config(
        ["--config", "density_f64", "--reps", str(args.reps)],
        budget_s=2400, env={"QUEST_PRECISION": "2"}, unit="ops/sec",
        slug="density14_f64",
        metric="channel-ops/sec, 14-qubit density matrix "
               "(mixDepolarising+mixKrausMap, PRECISION=2 double-float)"))
    configs.append(child("plan_34q", "plan_34q", "blocks",
                         "34q distributed plan", flags=[]))
    configs.append(_subprocess_config(
        ["--config", "plan_34q_f64"], budget_s=2400,
        env={"QUEST_PRECISION": "2", "QUEST_PALLAS_DF": "1"},
        unit="blocks", slug="plan_34q_f64",
        metric="34q PRECISION=2 distributed plan: per-shard double-float "
               "PallasRuns for v5p-16 execution"))
    configs.append(child("plan_17q_density", "plan_17q_density",
                         "kraus kernel ops",
                         "17q density-matrix channel plan", flags=[]))
    configs.append(child("plan_20q_relocation", "plan_20q_relocation",
                         "chunk-units", "20q sharded plan comm chunk-units",
                         flags=[]))
    configs.append(child("plan_34q_2slice", "plan_34q_2slice", "chunk-units",
                         "34q deferred plan DCN chunk-units", flags=[]))
    configs.append(child("serve", "serve_20q", "req/sec",
                         "serving engine, 20q depth-4 param ansatz"))
    configs.append(_subprocess_config(
        ["--config", "plan_f64"], budget_s=1200,
        env={"QUEST_PRECISION": "2", "QUEST_PALLAS_DF": "1"},
        unit="chunk-units", slug="plan_20q_f64",
        metric="20q PRECISION=2 sharded df plan comm chunk-units "
               "(8-device model, frame transposes at the df 2x scale)"))
    for config, unit in (("resilience", "runs/sec"), ("sentinel", "runs/sec"),
                         ("comm", "x speedup"), ("trajectories", "traj/sec"),
                         ("dispatch", "x fewer dispatches"),
                         ("pool", "req/sec"), ("sample", "shots/sec"),
                         ("vqe", "grad-steps/sec")):
        configs.append(child(config, f"{config}_20q", unit,
                             f"the {config}_20q row"))
    # headline = the 26q statevec config, selected by metric string so list
    # reordering can never silently change what is reported
    headline = dict(next(c for c in configs
                         if c["metric"].startswith("gate-ops/sec, 26-qubit")))
    _emit(headline, configs, args.emit, stamp=False)


#: plan-only rows (trace-time counts, no timing) that the no-``--config``
#: parent runs as children like every other config
_PLAN_ROWS = {
    "plan_34q": plan_34q_distributed,
    "plan_17q_density": plan_17q_density_distributed,
    "plan_20q_relocation": plan_20q_relocation_smoke,
    "plan_34q_2slice": plan_34q_2slice,
}


def _subprocess_config(extra_args: list, budget_s: int, metric: str,
                       env: dict | None = None,
                       unit: str = "ops/sec",
                       slug: str | None = None) -> dict:
    """Run one bench config in a budgeted subprocess so a slow compile (or
    a precision env that must be set before import) cannot sink the whole
    artifact; the persistent compile cache makes retries fast. The child
    runs with ``--emit full`` so its printed line carries the complete
    detail (and its own telemetry snapshot) for this parent to fold into
    BENCH_DETAIL.json. A child that prints no row leaves a placeholder
    marked ``failed``, which makes ``_emit`` exit non-zero."""
    import subprocess

    cmd = [sys.executable, os.path.abspath(__file__)] + extra_args \
        + ["--emit", "full"]

    def failed(note):
        return {"config": slug, "metric": metric, "value": None,
                "unit": unit, "vs_baseline": None, "note": note,
                "failed": True}

    full_env = dict(os.environ)
    full_env.update(env or {})
    try:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             timeout=budget_s, env=full_env,
                             cwd=os.path.dirname(os.path.abspath(__file__)))
        for line in out.stdout.splitlines():
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)
        return failed(f"config produced no JSON (rc={out.returncode}): "
                      f"{out.stderr[-400:]}")
    except subprocess.TimeoutExpired:
        return failed(f"cold compile exceeded the {budget_s}s budget; "
                      "rerun with a warm .jax_cache")
    except Exception as e:  # any other failure must not sink the artifact
        return failed(f"config subprocess failed: {e}")


def _budgeted_density(reps: int, budget_s: int) -> dict:
    return _subprocess_config(
        ["--config", "density", "--reps", str(reps)], budget_s,
        "channel-ops/sec, 14-qubit density matrix "
        "(mixDepolarising+mixKrausMap)", slug="density14")


if __name__ == "__main__":
    main()
