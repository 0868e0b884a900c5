"""Pipelined collectives (round 8): depth-parametric exchange launches.

The tentpole splits each per-device chunk into P contiguous sub-chunks and
interleaves sub-chunk k+1's ppermute/all_to_all with sub-chunk k's local
blend/mask/scatter (exchange._pipeline_schedule). This suite pins the
contract on the 8-virtual-device CPU mesh:

- BIT-identity at depths {1,2,4} (plus a depth-8 slice-width-1 edge
  case) across every launch site behind
  exchange._launch -- pair exchange (with local+sharded controls and the
  conj path), the X permute (whose local hi bits become the slice-index
  XOR ``src`` hook), the grouped all-to-all permute, the sliced diag /
  parity phases, and all three dist_swap regimes -- each compared in the
  SAME execution regime (one jitted program per depth; the diag sites
  eagerly), since FMA contraction differs across compiled programs;
- plane-agnosticism: the data-movement collectives carry the df 4-plane
  layout at every depth, and the QUEST_PALLAS_DF=1 fused f64 plan runs
  bit-identically at depth 1 vs 4 under the explicit scheduler;
- a density-matrix replica of the depth A/B through the public gate API;
- the scheduler journal's leading ("comm_pipeline", depth) stamp with
  depth-INVARIANT pricing (check_circuit_comm re-prices clean at every
  depth and the executed replay's comm_chunk_units_total telemetry sums
  to the same model);
- the ONE clamp (effective_comm_pipeline) and its QT209 info finding;
- the commcheck hazard state machines: the clean schedule (including the
  XOR consumption orders) is hazard-free, and each seeded pipelining bug
  (skip_prologue / double_issue / skip_land / drop_last_compute) is
  caught as QT207/QT208;
- the QT206 warn-once diagnostic on a malformed QUEST_COMM_PIPELINE and
  the env default threading into the comm_pipeline_depth gauge;
- retry-vs-pipeline: a transient exchange.collective fault at depth > 1
  replays the WHOLE launch bit-identically (guard wraps the full
  shard_map closure, never a mid-slice resume);
- tape codec: fused(comm_pipeline=) stamps every PallasRun/FrameSwap and
  round-trips through as_tape/plan_from_tape; an unstamped plan leaves
  comm_pipeline=None.
"""

import warnings

import numpy as np
import pytest

import jax
import quest_tpu as qt
from quest_tpu import fusion, planner, telemetry
from quest_tpu.analysis import commcheck as C
from quest_tpu.analysis.plancheck import check_circuit_comm
from quest_tpu.circuits import Circuit
from quest_tpu.parallel import exchange as X
from quest_tpu.parallel.scheduler import comm_chunks
from quest_tpu.resilience import fault_plan

ENV = qt.createQuESTEnv()  # 8-device mesh from conftest's virtual CPUs

pytestmark = pytest.mark.skipif(ENV.mesh is None or ENV.mesh.size < 8,
                                reason="needs the 8-device host mesh")

N = 6           # nl = 3 on 8 devices: qubits 3..5 sharded, chunk = 8 cols
DEPTHS = (2, 4)  # depth 8 (slice width 1) gets its own eager edge test


def _rand_state(planes=2, n=N, seed=0):
    rng = np.random.RandomState(seed)
    return jax.numpy.asarray(
        rng.normal(size=(planes, 1 << n)).astype(np.float32))


def _unitary(seed=1):
    rng = np.random.RandomState(seed)
    m = rng.normal(size=(2, 2)) + 1j * rng.normal(size=(2, 2))
    q, r = np.linalg.qr(m)
    q = q * (np.diag(r) / np.abs(np.diag(r)))
    # the kernels index the planar matrix with a traced rank bit: device
    # arrays, as the scheduler passes them
    return jax.numpy.asarray(np.stack([q.real, q.imag]), jax.numpy.float32)


def _diag(t, seed=2):
    th = np.random.RandomState(seed).uniform(size=1 << t)
    return jax.numpy.asarray(np.stack([np.cos(th), np.sin(th)]),
                             jax.numpy.float32)


U1 = _unitary()
D2 = _diag(2)
M = ENV.mesh

#: every launch site behind exchange._launch, each with local + sharded
#: controls where the signature takes them (the sliced ctrl mask tests the
#: GLOBAL in-chunk index, so depth must not move the masked half); the
#: conj paths ride diag_phase/pair exchange's matrix sign-flip
SITES = {
    "pair_exchange": lambda a, p: X.dist_apply_matrix1(
        a, U1, n=N, target=5, controls=(1, 4), control_states=(1, 0),
        mesh=M, pipeline=p),
    "pair_exchange_conj": lambda a, p: X.dist_apply_matrix1(
        a, U1, n=N, target=4, controls=(0,), control_states=(1,),
        conj=True, mesh=M, pipeline=p),
    "local_matrix": lambda a, p: X.dist_apply_local_matrix(
        a, U1, n=N, targets=(1,), controls=(0, 5), control_states=(1, 1),
        mesh=M, pipeline=p),
    # local targets 1,2 split across the slice width: at depth 4 both
    # become the src XOR, at depth 2 qubit 1 flips within the slice
    "x_permute": lambda a, p: X.dist_apply_x(
        a, n=N, targets=(5, 4, 1, 2), controls=(0,), control_states=(1,),
        mesh=M, pipeline=p),
    "x_permute_sharded_only": lambda a, p: X.dist_apply_x(
        a, n=N, targets=(3, 5), controls=(2,), control_states=(0,),
        mesh=M, pipeline=p),
    # shard<->local crossings AND a shard-shard relabel in one permute
    "grouped_permute": lambda a, p: X.dist_permute_bits(
        a, n=N, source=(5, 1, 2, 4, 3, 0), mesh=M, pipeline=p),
    "diag_phase": lambda a, p: X.dist_apply_diag_phase(
        a, D2, n=N, targets=(5, 0), controls=(1,), control_states=(1,),
        mesh=M, pipeline=p),
    "diag_phase_conj": lambda a, p: X.dist_apply_diag_phase(
        a, D2, n=N, targets=(2, 4), conj=True, mesh=M, pipeline=p),
    "parity_phase": lambda a, p: X.dist_apply_parity_phase(
        a, 0.37, n=N, qubits=(5, 1), controls=(0,), control_states=(1,),
        mesh=M, pipeline=p),
    "swap_local": lambda a, p: X.dist_swap(
        a, n=N, qb1=0, qb2=2, mesh=M, pipeline=p),
    "swap_rank_permute": lambda a, p: X.dist_swap(
        a, n=N, qb1=4, qb2=5, mesh=M, pipeline=p),
    "swap_odd_parity": lambda a, p: X.dist_swap(
        a, n=N, qb1=0, qb2=5, mesh=M, pipeline=p),
    # lo=1 caps the odd-parity slice limit at 2: depth 4 clamps
    "swap_odd_parity_clamped": lambda a, p: X.dist_swap(
        a, n=N, qb1=1, qb2=5, mesh=M, pipeline=p),
}
SITE_NAMES = list(SITES)

#: plane-agnostic data movers, fed the df 4-plane layout (round-7 plane
#: contract: the sliced collectives must carry any leading plane count)
MOVERS4 = {
    "grouped_permute": lambda s, p: X.dist_permute_bits(
        s, n=N, source=(5, 1, 2, 4, 3, 0), mesh=M, pipeline=p),
    "swap_rank_permute": lambda s, p: X.dist_swap(
        s, n=N, qb1=3, qb2=5, mesh=M, pipeline=p),
    "swap_odd_parity": lambda s, p: X.dist_swap(
        s, n=N, qb1=0, qb2=4, mesh=M, pipeline=p),
    "x_permute": lambda s, p: X.dist_apply_x(
        s, n=N, targets=(3, 5), mesh=M, pipeline=p),
}
MOVER_NAMES = list(MOVERS4)


# ---------------------------------------------------------------------------
# bit-identity: pipelined == monolithic at every site and depth
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def depth_matrix():
    """All sites x depths {1,2,4}: under jit the whole matrix runs as ONE
    program per depth (an eager per-call launch recompiles its shard_map
    every time -- batching per depth keeps the suite inside the tier-1
    budget), EXCEPT the diag-phase sites, which run eagerly: under jit,
    XLA-CPU contracts their complex-multiply into FMAs differently
    between the monolithic and the sliced program (a data-dependent 1-ULP
    artifact of compilation, not of the pipeline schedule), while eager
    same-regime launches are bit-identical at every depth. Every site
    reads the SAME input, so each output isolates its site."""
    diag = [s for s in SITE_NAMES if s.startswith("diag_phase")]
    rest = [s for s in SITE_NAMES if s not in diag]
    a2 = _rand_state(seed=3)
    a4 = _rand_state(planes=4, seed=5)
    outs = {}
    for pipe in (1,) + DEPTHS:
        run = jax.jit(lambda x, y, p=pipe: (
            [SITES[s](x, p) for s in rest],
            [MOVERS4[m](y, p) for m in MOVER_NAMES]))
        sv, df = jax.device_get(run(a2, a4))
        dv = jax.device_get([SITES[s](a2, pipe) for s in diag])
        by_site = dict(zip(rest, sv)) | dict(zip(diag, dv))
        outs[pipe] = {"sv": [np.asarray(by_site[s]) for s in SITE_NAMES],
                      "df": [np.asarray(o) for o in df]}
    return outs


@pytest.mark.parametrize("site", SITE_NAMES)
def test_pipelined_launch_is_bit_identical(site, depth_matrix):
    i = SITE_NAMES.index(site)
    base = depth_matrix[1]["sv"][i]
    for depth in DEPTHS:
        got = depth_matrix[depth]["sv"][i]
        assert np.array_equal(base, got), f"{site} diverged at depth {depth}"


@pytest.mark.parametrize("mover", MOVER_NAMES)
def test_data_movement_collectives_carry_four_planes(mover, depth_matrix):
    i = MOVER_NAMES.index(mover)
    base = depth_matrix[1]["df"][i]
    assert base.shape == (4, 1 << N)
    for depth in DEPTHS:
        got = depth_matrix[depth]["df"][i]
        assert np.array_equal(base, got), \
            f"{mover} df-plane divergence at depth {depth}"


def test_depth_eight_slice_width_one_edge():
    """Depth 8 on the 8-column chunk: slice width 1, so EVERY local X
    target becomes the src XOR (s_bits = 0) -- the degenerate edge of the
    permuted consumption order, eager in the same regime both sides."""
    a = _rand_state(seed=7)
    fn = lambda p: np.asarray(X.dist_apply_x(
        a, n=N, targets=(5, 1, 2), mesh=M, pipeline=p))
    assert np.array_equal(fn(1), fn(8))


# ---------------------------------------------------------------------------
# end-to-end depth A/B: statevector, density replica, df fused plan
# ---------------------------------------------------------------------------

def _mix_circuit(n, density=False):
    """Every scheduler dispatch class: dense pair exchange, X permute,
    swaps in all three regimes, diag/parity phases, a relocation."""
    rng = np.random.RandomState(7)
    m = rng.normal(size=(2, 2)) + 1j * rng.normal(size=(2, 2))
    u2, r = np.linalg.qr(m)
    u2 = u2 * (np.diag(r) / np.abs(np.diag(r)))
    c = Circuit(n, density)
    c.hadamard(0)
    c.hadamard(n - 1)
    c.controlledNot(n - 1, 0)
    c.controlledNot(0, n - 1)
    c.unitary(n - 2, u2)
    c.rotateZ(n - 1, 0.31)
    c.multiRotateZ([0, n - 1], -0.7)
    c.swapGate(0, 1)
    c.swapGate(1, n - 1)
    c.swapGate(n - 2, n - 1)
    c.multiQubitNot([0, n - 1])
    c.tGate(n - 1)
    return c


@pytest.mark.parametrize("density", [False, True])
def test_explicit_scheduler_depth_ab_bit_identical(density):
    n = 5 if not density else 3
    make = qt.createDensityQureg if density else qt.createQureg
    circ = _mix_circuit(n, density)
    outs = {}
    for pipe in (1, 4):
        q = make(n, ENV)
        qt.initDebugState(q)
        with qt.explicit_mesh(ENV.mesh, comm_pipeline=pipe):
            circ.run(q)
        outs[pipe] = qt.get_np(q)
    assert np.array_equal(outs[1], outs[4])


def test_sharded_df_fused_plan_depth_ab_bit_identical(monkeypatch):
    """The df 4-plane route end-to-end: a fused f64 plan's frame
    relabelings ride the scheduler's grouped permute at the configured
    depth and stay bit-identical."""
    if np.dtype(qt.precision.real_dtype()) != np.dtype("float64"):
        pytest.skip("needs QUEST_PRECISION=2 (the conftest default)")
    monkeypatch.setenv("QUEST_PALLAS_DF", "1")
    n = 12
    circ = _mix_circuit(n)
    fz = circ.fused(max_qubits=5, pallas=True, shard_devices=8,
                    dtype=np.float64)
    outs = {}
    for pipe in (1, 4):
        q = qt.createQureg(n, ENV)
        qt.initPlusState(q)
        telemetry.reset()
        with qt.explicit_mesh(ENV.mesh, comm_pipeline=pipe):
            fz.run(q)
        assert telemetry.counter_value("engine_fallback_total",
                                       reason="f64_engine") == 0
        outs[pipe] = np.asarray(q.amps)
    assert np.array_equal(outs[1], outs[4])


# ---------------------------------------------------------------------------
# journal stamp + depth-invariant pricing (model == telemetry)
# ---------------------------------------------------------------------------

def test_journal_stamp_and_depth_invariant_pricing():
    circ = _mix_circuit(5)
    results = {}
    for pipe in (1, 4):
        findings, stats, journal = check_circuit_comm(
            circ, ENV.mesh, comm_pipeline=pipe, location="pipe_ab")
        assert not [f for f in findings if f.severity == "error"], findings
        assert journal[0] == ("comm_pipeline", pipe)
        results[pipe] = (stats, journal)
    s1, j1 = results[1]
    s4, j4 = results[4]
    # pipelining re-times the same traffic, it never adds any: identical
    # journals (past the stamp) and identical priced stats
    assert j1[1:] == j4[1:]
    assert s1 == s4
    assert comm_chunks(s1) == pytest.approx(comm_chunks(s4))

    # the executed depth-4 replay books exactly the modelled chunk-units
    q = qt.createQureg(5, ENV)
    qt.initDebugState(q)
    telemetry.reset()
    with qt.explicit_mesh(ENV.mesh, comm_pipeline=4):
        circ.run(q)
    ran = sum(telemetry.counters("comm_chunk_units_total").values())
    assert ran == pytest.approx(comm_chunks(s4), abs=1e-9)


# ---------------------------------------------------------------------------
# the ONE clamp + commcheck hazard proofs
# ---------------------------------------------------------------------------

def test_effective_comm_pipeline_clamp():
    E = X.effective_comm_pipeline
    assert E(1, 4096) == 1
    assert E(3, 4096) == 2      # round down to a power of two
    assert E(0, 8) == 1
    assert E(-2, 8) == 1        # degenerate requests mean monolithic
    assert E(64, 8) == 8        # the slice limit caps
    assert E(8, 6) == 4         # the limit rounds down too
    assert E(8, 1) == 1


def test_commcheck_clean_schedule_is_hazard_free():
    for depth in (1, 2, 4, 8):
        assert C.check_pipeline_events(C.pipeline_events(depth), depth) == []
    # the XOR consumption order of dist_apply_x's hi-bit flips
    assert C.check_pipeline_events(
        C.pipeline_events(8, src=lambda k: k ^ 6), 8) == []
    assert C.check_comm_pipeline(4, 64) == []


def test_commcheck_clamp_reports_qt209_info():
    fs = C.check_comm_pipeline(64, 8)
    assert [f.code for f in fs] == ["QT209"]
    assert fs[0].severity == "info"
    assert "runs at 8" in fs[0].message


@pytest.mark.parametrize("knob,code", [
    ("skip_prologue", "QT207"),
    ("double_issue", "QT207"),
    ("skip_land", "QT207"),
    ("drop_last_compute", "QT208"),
])
def test_commcheck_mutations_are_caught(knob, code):
    ev = C.pipeline_events(4, **{knob: True})
    findings = C.check_pipeline_events(ev, 4)
    assert code in {f.code for f in findings}, findings
    assert all(f.severity in ("error",) for f in findings)


def test_commcheck_sweep_has_no_hazards():
    fs = C.sweep_comm_pipeline()
    assert fs, "sweep should at least report clamp bites"
    assert all(f.severity == "info" and f.code == "QT209" for f in fs), fs


# ---------------------------------------------------------------------------
# QT206 env diagnostic + env default threading
# ---------------------------------------------------------------------------

@pytest.fixture
def pipe_env(monkeypatch):
    monkeypatch.setattr(X, "_PIPE_ENV_WARNED", set())
    return monkeypatch


def test_pipe_env_non_integer_warns_once_and_defaults(pipe_env):
    pipe_env.setenv(X._PIPE_ENV, "fast")
    telemetry.reset()
    with pytest.warns(RuntimeWarning, match="QT206.*pipeline depth 1"):
        assert X.comm_pipeline_default() == X._DEF_COMM_PIPELINE
    assert telemetry.counter_value(
        "analysis_findings_total", code="QT206", severity="warning") == 1.0
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # second call must stay silent
        assert X.comm_pipeline_default() == X._DEF_COMM_PIPELINE


def test_pipe_env_below_minimum_clamps_to_monolithic(pipe_env):
    pipe_env.setenv(X._PIPE_ENV, "0")
    with pytest.warns(RuntimeWarning, match="monolithic minimum"):
        assert X.comm_pipeline_default() == 1


def test_pipe_env_valid_value_threads_to_launch_and_gauge(pipe_env):
    pipe_env.setenv(X._PIPE_ENV, "2")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert X.comm_pipeline_default() == 2
    a = _rand_state(seed=9)
    telemetry.reset()
    via_env = np.asarray(SITES["swap_rank_permute"](a, None))
    assert telemetry.snapshot()["gauges"]["comm_pipeline_depth"] == 2
    assert np.array_equal(via_env,
                          np.asarray(SITES["swap_rank_permute"](a, 2)))


def test_eager_launch_observes_collective_histogram():
    telemetry.reset()
    a = _rand_state(seed=11)
    SITES["swap_rank_permute"](a, 4)
    hist = telemetry.snapshot("comm_collective_ms")["histograms"]
    assert any("kind=swap_rank_permute" in k and "pipeline=4" in k
               for k in hist), hist


# ---------------------------------------------------------------------------
# retry contract: a transient fault replays the WHOLE pipelined launch
# ---------------------------------------------------------------------------

def test_pipelined_collective_transient_retries_bit_identical():
    # defer=False keeps the sharded Hadamard on the pair-exchange site
    # (the deferred policy would relocate), so the retried launch runs at
    # the full clamped depth 4 (n=5 on 8 devices: nl=2, chunk = 4 cols)
    with qt.explicit_mesh(ENV.mesh, defer=False, comm_pipeline=4):
        q0 = qt.createQureg(5, ENV)
        qt.hadamard(q0, 4)
    want = np.asarray(q0.amps)
    telemetry.reset()
    with fault_plan("exchange.collective:transient:1"):
        with qt.explicit_mesh(ENV.mesh, defer=False, comm_pipeline=4):
            q1 = qt.createQureg(5, ENV)
            qt.hadamard(q1, 4)
    assert np.array_equal(want, np.asarray(q1.amps))
    assert telemetry.counter_value("retry_attempts_total",
                                   site="exchange.collective",
                                   outcome="ok") == 1
    assert telemetry.snapshot()["gauges"]["comm_pipeline_depth"] == 4


# ---------------------------------------------------------------------------
# tape codec: fused(comm_pipeline=) stamps ride the run
# ---------------------------------------------------------------------------

def test_fused_comm_pipeline_stamps_and_roundtrips():
    c = Circuit(12)
    for q in range(12):
        c.hadamard(q)
    c.controlledNot(0, 11)
    c.tGate(11)
    fz = c.fused(max_qubits=5, pallas=True, shard_devices=8,
                 comm_pipeline=2)
    p = fusion.plan_from_tape(tuple(fz._tape))
    runs = [i for i in p.items
            if isinstance(i, (planner.PallasRun, planner.FrameSwap))]
    assert runs, "sharded pallas plan should carry PallasRun items"
    assert all(i.comm_pipeline == 2 for i in runs)

    # the stamp rides the run through the tape codec, and an unstamped
    # plan leaves None for the env default at run time
    again = fusion.plan_from_tape(fusion.as_tape(p))
    assert again.items == p.items
    bare = fusion.plan_from_tape(tuple(
        c.fused(max_qubits=5, pallas=True, shard_devices=8)._tape))
    assert all(i.comm_pipeline is None for i in bare.items
               if isinstance(i, (planner.PallasRun, planner.FrameSwap)))
