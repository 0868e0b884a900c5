"""Sharded double-float (PRECISION=2 fast path) parity suite -- round 7.

The reference's distributed build is double-precision by default (its whole
MPI exchange protocol runs on doubles, QuEST_precision.h:52-64,
QuEST_cpu_distributed.c); this suite pins the TPU analogue: a sharded f64
register executes fused PallasRuns per shard on the double-float 4-plane
kernels (ops/pallas_df) joined by the existing grouped collectives, instead
of collapsing to the ~170x-slower XLA-emulated-f64 engine path.

Covered here, all on the 8-virtual-device CPU mesh:

- kernel-level BIT-identity of the per-shard df run (incl. the grid>1
  manual-DMA kernel with the SMEM shard-index scalar) against the
  unsharded df kernel;
- plan-level parity of the sharded df route -- GSPMD and the explicit
  scheduler, deferred and immediate, ring depths {2,3,4}, density Kraus --
  against the unsharded df path and the f64 engine oracle, at the
  tolerance the df route promises under XLA:CPU (``ATOL_DF_CPU``: its
  compiler duplicates producer expressions and contracts each copy
  differently, so the error-free transforms are not exact there and a df
  program holds f32-product accuracy; measured plan-level deltas are
  4e-9 to 9e-9. The chip's 1e-12 budget, measured 7.9e-17, is held where
  it is true: ``chip_smoke.py``'s df phase and ``tools/df_verify.py``);
- zero engine_fallback_total{reason=f64_engine} on the sharded plans, with
  the generalized df_tile_mismatch guard counting (not raising) for plans
  built at non-DF geometry;
- per-shard folded frame swaps for SHARD-LOCAL blocks (satellite of
  ISSUE 3), else the explicit counted transpose;
- comm_chunk_units_total telemetry summing EXACTLY to the df-aware
  plan_circuit model, with frame transposes priced at the df 2x scale;
- the QUEST_DF_ACCURATE_ADD two-sum addition (Dekker near-cancellation
  caveat) and the df norm reduction vs a numpy f64 oracle.

The df route engages off-TPU only via QUEST_PALLAS_DF=1 (monkeypatched per
test), so the rest of the suite keeps the native-f64 CPU policy.
"""

import jax
import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu import fusion, planner, telemetry
from quest_tpu.circuits import Circuit
from quest_tpu.ops import pallas_gates as PG
from quest_tpu.ops import pallas_df as DF
from quest_tpu.parallel.scheduler import comm_chunks, plan_circuit

from .helpers import pallas_runs

if np.dtype(qt.precision.real_dtype()) != np.dtype("float64"):
    pytest.skip("sharded-df suite needs QUEST_PRECISION=2 (the conftest "
                "default)", allow_module_level=True)

ENV = qt.createQuESTEnv()
# what a df program is held to on XLA:CPU, as in tests/test_segments.py
# (test_df_route_segment_chain_contract) and for the reason given there
ATOL_DF_CPU = 2e-7
H = np.array([[1, 1], [1, -1]]) / np.sqrt(2)
X = np.array([[0, 1], [1, 0]])


@pytest.fixture
def df_route(monkeypatch):
    """Flip the double-float route on for the CPU backend."""
    monkeypatch.setenv("QUEST_PALLAS_DF", "1")


def _need_mesh(ndev=8):
    if len(jax.devices()) < ndev:
        pytest.skip(f"needs the {ndev}-device CPU mesh")
    return qt.createQuESTEnv(jax.devices()[:ndev])


def _rand_amps64(n, seed=3):
    rng = np.random.RandomState(seed)
    v = rng.normal(size=(2, 1 << n)) / np.sqrt(2 << n)
    return jax.numpy.asarray(v, jax.numpy.float64)


def _shard_run(mesh, planes, n_local, ops, **kw):
    """shard_map one per-shard df fused_local_run over the 4-plane state."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from quest_tpu.environment import AMP_AXIS

    def body(x):
        hi = jax.lax.axis_index(AMP_AXIS)
        return PG.fused_local_run(x, n=n_local, ops=ops, shard_index=hi,
                                  interpret=True, **kw)

    return shard_map(body, mesh=mesh, in_specs=P(None, AMP_AXIS),
                     out_specs=P(None, AMP_AXIS), check_vma=False)(planes)


# ---------------------------------------------------------------------------
# kernel level: bit-identity of the per-shard df kernels
# ---------------------------------------------------------------------------

def test_sharded_df_kernel_matches_unsharded():
    """The per-shard df run (sharded-qubit roles resolving against the
    SMEM shard-index scalar) reproduces the unsharded df kernel over the
    same ops. sublanes=4 forces grid>1 per shard, i.e. the manual-DMA
    kernel extended with the shard scalar (the round-5 single-tile Mosaic
    workaround generalized to the sharded grid).

    Two regimes: ops whose above-tile roles source identically in both
    programs are BIT-identical; adding ops whose grid-bit roles become
    shard-bit roles changes the compiled program, and XLA-CPU's fusion
    then re-contracts fma differently per program (the documented round-5
    EFT caveat) -- those stay within 1 ulp of the f32 planes (Mosaic on
    TPU lowers both identically)."""
    env = _need_mesh()
    n, n_local = 14, 11

    def run_both(ops):
        full = np.asarray(PG.fused_local_run(
            DF.df_split(amps64), n=n, ops=ops, sublanes=4, interpret=True))
        got = np.asarray(_shard_run(env.mesh, DF.df_split(amps64), n_local,
                                    ops, sublanes=4))
        return got, full

    amps64 = _rand_amps64(n)
    # identical-program regime: in-tile dense work + sharded control
    ops_bit = (
        ("matrix", 0, (), (), PG.HashableMatrix(H)),
        ("matrix", 3, (12,), (1,), PG.HashableMatrix(X)),  # sharded ctrl
        ("swap", 2, 6, (), ()),
        ("matrix", 12, (), (),                             # sharded diag tgt
         PG.HashableMatrix(np.diag([1, np.exp(0.3j)]))),
    )
    got, full = run_both(ops_bit)
    assert np.array_equal(got, full)

    # full role mix (sharded parity member + in-shard grid bit): 1-ulp
    ops_mix = ops_bit + (("parity", (1, 13), (), 0.4),
                         ("matrix", 8, (), (), PG.HashableMatrix(H)))
    got, full = run_both(ops_mix)
    assert np.max(np.abs(got - full)) <= 2 ** -52
    # and the df result tracks the native-f64 interpreter run
    ref = np.asarray(PG.fused_local_run(amps64 + 0, n=n, ops=ops_mix,
                                        sublanes=4, interpret=True))
    np.testing.assert_allclose(
        np.asarray(DF.df_join(jax.numpy.asarray(got))), ref, atol=5e-8)


def test_sharded_df_folded_swap_matches_explicit(df_route):
    """Satellite (ISSUE 3): a SHARD-LOCAL frame swap folds into the
    per-shard df run's DMA and is bit-identical to the explicit
    swap_bit_blocks pass + unfolded run. Geometry: 15q over 8 devices,
    12q shards, sublanes=16 -> per-shard tile_bits=11, grid=2; swap
    (hi=11, k=1) stays below the shard boundary."""
    env = _need_mesh()
    n, n_local, k = 15, 12, 1
    tile_bits = PG.local_qubits(n_local, 16)
    assert tile_bits + k <= n_local  # genuinely shard-local
    ops = (("matrix", 0, (), (), PG.HashableMatrix(H)),
           ("matrix", 5, (13,), (1,), PG.HashableMatrix(X)))
    amps64 = _rand_amps64(n, seed=5)
    planes = DF.df_split(amps64)

    folded = np.asarray(_shard_run(env.mesh, planes, n_local, ops,
                                   sublanes=16, load_swap_k=k,
                                   store_swap_k=k))
    swapped = PG.swap_bit_blocks(planes, n=n, lo1=tile_bits - k,
                                 lo2=tile_bits, k=k)
    explicit = np.asarray(_shard_run(env.mesh, swapped, n_local, ops,
                                     sublanes=16))
    explicit = np.asarray(PG.swap_bit_blocks(
        jax.numpy.asarray(explicit), n=n, lo1=tile_bits - k, lo2=tile_bits,
        k=k))
    assert np.array_equal(folded, explicit)


def test_sharded_f32_folded_swap_matches_explicit():
    """Same shard-local fold regression on the f32 per-shard grid kernel
    (the non-df arm of the lifted pallas_gates guard)."""
    env = _need_mesh()
    n, n_local, k = 15, 12, 1
    tile_bits = PG.local_qubits(n_local, 16)
    ops = (("matrix", 0, (), (), PG.HashableMatrix(H)),
           ("matrix", 5, (13,), (1,), PG.HashableMatrix(X)))
    rng = np.random.RandomState(9)
    amps = jax.numpy.asarray(
        rng.normal(size=(2, 1 << n)) / np.sqrt(2 << n), jax.numpy.float32)

    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from quest_tpu.environment import AMP_AXIS

    def run(x, **kw):
        def body(c):
            hi = jax.lax.axis_index(AMP_AXIS)
            return PG.fused_local_run(c, n=n_local, ops=ops, shard_index=hi,
                                      sublanes=16, interpret=True, **kw)
        return shard_map(body, mesh=env.mesh, in_specs=P(None, AMP_AXIS),
                         out_specs=P(None, AMP_AXIS), check_vma=False)(x)

    folded = np.asarray(run(amps + 0, load_swap_k=k, store_swap_k=k))
    swapped = PG.swap_bit_blocks(amps + 0, n=n, lo1=tile_bits - k,
                                 lo2=tile_bits, k=k)
    explicit = np.asarray(PG.swap_bit_blocks(
        run(swapped), n=n, lo1=tile_bits - k, lo2=tile_bits, k=k))
    assert np.array_equal(folded, explicit)


def test_collective_swap_stays_explicit_and_counted(df_route):
    """The sibling audit's other arm: a frame swap whose block reaches the
    SHARDED bits must NOT fold into the per-shard kernel -- it executes as
    the explicit (collective under GSPMD) transpose pass, counted in
    pallas_pass_total{kind=frame_swap}, and the run still avoids the
    engine."""
    env = _need_mesh()
    n, ndev = 12, 8
    circ = Circuit(n)
    rng = np.random.RandomState(7)
    for q in range(n):
        g, _ = np.linalg.qr(rng.randn(2, 2) + 1j * rng.randn(2, 2))
        circ.unitary(q, g)
    fz = circ.fused(max_qubits=5, pallas=True, shard_devices=ndev,
                    dtype=np.float64)
    assert any(r.load_swap_k or r.store_swap_k for r in pallas_runs(fz)), \
        "plan folded no frame swaps"
    qureg = qt.createQureg(n, env)
    qt.initPlusState(qureg)
    telemetry.reset()
    fz.run(qureg)
    assert telemetry.counter_value("engine_fallback_total",
                                   reason="f64_engine") == 0
    assert telemetry.counter_value("pallas_pass_total",
                                   kind="frame_swap") > 0
    ref = qt.createQureg(n, qt.createQuESTEnv(jax.devices()[:1]))
    qt.initPlusState(ref)
    circ.run(ref)
    np.testing.assert_allclose(np.asarray(qureg.amps), np.asarray(ref.amps),
                               atol=ATOL_DF_CPU)


# ---------------------------------------------------------------------------
# plan level: GSPMD / explicit scheduler / rings / density -- vs the oracle
# ---------------------------------------------------------------------------

def _parity_circuit(n):
    from __graft_entry__ import _random_layers

    circ = Circuit(n)
    _random_layers(circ, n, depth=2)
    rng = np.random.RandomState(17)
    for q in range(n):  # dense 1q unitaries everywhere incl. sharded bits
        g, _ = np.linalg.qr(rng.randn(2, 2) + 1j * rng.randn(2, 2))
        circ.unitary(q, g)
    return circ


def test_sharded_df_ring_parity_vs_oracle(df_route):
    """Acceptance core: ring depths {2,3,4} of the sharded df plan are
    BIT-identical to each other, match the unsharded df path to ~1e-15,
    and sit within 1e-13 of the f64 engine oracle; zero f64_engine
    fallbacks throughout."""
    env = _need_mesh()
    n, ndev = 12, 8
    circ = _parity_circuit(n)
    env1 = qt.createQuESTEnv(jax.devices()[:1])

    telemetry.reset()
    outs = {}
    for d in (2, 3, 4):
        fz = circ.fused(max_qubits=5, pallas=True, shard_devices=ndev,
                        dtype=np.float64, ring_depth=d)
        qd = qt.createQureg(n, env)
        qt.initPlusState(qd)
        fz.run(qd)
        assert len(qd.amps.sharding.device_set) == ndev
        outs[d] = np.asarray(qd.amps)
    assert np.array_equal(outs[2], outs[3])
    assert np.array_equal(outs[2], outs[4])
    assert telemetry.counter_value("engine_fallback_total",
                                   reason="f64_engine") == 0
    assert telemetry.counter_value("pallas_pass_total", dtype="df",
                                   kind="fused_run") > 0

    # unsharded df path (same plan shape, single device)
    fz1 = circ.fused(max_qubits=5, pallas=True, dtype=np.float64)
    q1 = qt.createQureg(n, env1)
    qt.initPlusState(q1)
    fz1.run(q1)
    np.testing.assert_allclose(outs[2], np.asarray(q1.amps),
                               atol=ATOL_DF_CPU)

    # f64 engine oracle (raw gate-by-gate replay)
    ref = qt.createQureg(n, env1)
    qt.initPlusState(ref)
    circ.run(ref)
    np.testing.assert_allclose(outs[2], np.asarray(ref.amps),
                               atol=ATOL_DF_CPU)


def test_sharded_df_explicit_scheduler_deferred_and_immediate(df_route):
    """The tentpole's scheduler arm: the SAME sharded df plan executes
    under the explicit distributed scheduler in both deferred and
    immediate modes -- per-shard df kernels joined by the scheduler's
    counted grouped permutes -- and matches the engine oracle. The two
    modes are bit-identical (a pure pallas tape defers nothing)."""
    env = _need_mesh()
    n, ndev = 12, 8
    circ = _parity_circuit(n)
    fz = circ.fused(max_qubits=5, pallas=True, shard_devices=ndev,
                    dtype=np.float64)
    outs = {}
    for defer in (True, False):
        q = qt.createQureg(n, env)
        qt.initPlusState(q)
        telemetry.reset()
        with qt.explicit_mesh(env.mesh, defer=defer):
            fz.run(q)
        assert telemetry.counter_value("engine_fallback_total",
                                       reason="f64_engine") == 0
        assert telemetry.counter_value("engine_fallback_total",
                                       reason="explicit_scheduler") == 0
        outs[defer] = np.asarray(q.amps)
    assert np.array_equal(outs[True], outs[False])
    ref = qt.createQureg(n, qt.createQuESTEnv(jax.devices()[:1]))
    qt.initPlusState(ref)
    circ.run(ref)
    np.testing.assert_allclose(outs[True], np.asarray(ref.amps),
                               atol=ATOL_DF_CPU)


def test_sharded_df_density_kraus_parity(df_route):
    """Density tape: the df 4-plane kraus kernel bodies execute per shard
    (flattened 2n-qubit state, conj-shadow column qubits relabeled by
    collective transposes) and match the engine oracle."""
    env = _need_mesh()
    n, ndev = 6, 8
    k2 = 1 / np.sqrt(2)
    circ = Circuit(n, is_density_matrix=True)
    for q in range(3):
        circ.hadamard(q)
    circ.controlledNot(0, 1)
    circ.mixDepolarising(n - 1, 0.05)       # column qubit 2n-1 is sharded
    circ.mixKrausMap(1, [np.array([[k2, 0], [0, k2]]),
                         np.array([[0, k2], [k2, 0]])])
    p2 = 0.25
    xx = np.kron([[0, 1], [1, 0]], [[0, 1], [1, 0]])
    circ.mixTwoQubitKrausMap(0, 2, [np.sqrt(1 - p2) * np.eye(4),
                                    np.sqrt(p2) * xx])
    fz = circ.fused(max_qubits=4, pallas=True, shard_devices=ndev,
                    dtype=np.float64)
    assert any(op[0].startswith("kraus")
               for r in pallas_runs(fz) for op in r.ops), \
        "no kraus kernel ops in the sharded df plan"
    rho = qt.createDensityQureg(n, env)
    qt.initPlusState(rho)
    telemetry.reset()
    fz.run(rho)
    assert telemetry.counter_value("engine_fallback_total",
                                   reason="f64_engine") == 0
    rho_ref = qt.createDensityQureg(n, qt.createQuESTEnv(jax.devices()[:1]))
    qt.initPlusState(rho_ref)
    for f, a, kw in circ._tape:
        f(rho_ref, *a, **kw)
    np.testing.assert_allclose(np.asarray(rho.amps),
                               np.asarray(rho_ref.amps), atol=ATOL_DF_CPU)
    # the trace is a sum of 2^n diagonal elements, each held to ATOL_DF_CPU
    # (measured 1.4e-7 here; 1e-12 is the chip's: chip_smoke.py's df phase)
    assert abs(qt.calcTotalProb(rho) - 1.0) < (1 << n) * ATOL_DF_CPU


def test_df_tile_mismatch_counts_on_sharded_plans(df_route):
    """The generalized guard: a plan built at NON-df tile geometry whose
    dense targets exceed the shard's df tile falls back to the engine with
    engine_fallback_total{reason=df_tile_mismatch} -- counted, not raised
    -- on the sharded route too. Needs 18-qubit shards: the df tile
    (DF_SUBLANES) only shrinks below the shard size past 17 local
    qubits."""
    env = _need_mesh()
    n = 21  # 18-qubit shards over 8 devices
    n_local = n - 3
    lq_df = PG.local_qubits(n_local, DF.DF_SUBLANES)
    lq_f32 = PG.local_qubits(n_local)
    assert lq_df < lq_f32 <= n_local  # the mismatch window
    # a dense target legal for the f32 shard geometry, above the df tile
    target = lq_df
    ops = (("matrix", target, (), (), PG.HashableMatrix(X)),)
    qureg = qt.createQureg(n, env)
    qt.initClassicalState(qureg, 0)
    telemetry.reset()
    fusion._apply_pallas_run(
        qureg, planner.PallasRun(ops, lq_f32))  # must not raise
    assert telemetry.counter_value("engine_fallback_total",
                                   reason="df_tile_mismatch") == 1
    amps = np.asarray(qureg.amps)
    assert amps[0, 1 << target] == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# comm model: df chunk-units at 2x, telemetry == plan_circuit exactly
# ---------------------------------------------------------------------------

def test_df_comm_chunk_units_match_model_and_double_planar(df_route):
    """Acceptance: the df-aware plan_circuit model's chunk-units equal the
    comm_chunk_units_total telemetry EXACTLY (trace-time and executed),
    and the frame transposes of the 4-plane df state price at exactly 2x
    their planar chunk-units."""
    env = _need_mesh()
    n, ndev = 12, 8
    circ = _parity_circuit(n)
    fz = circ.fused(max_qubits=5, pallas=True, shard_devices=ndev,
                    dtype=np.float64)

    telemetry.reset()
    stats = plan_circuit(fz, env.mesh, dtype=np.float64)
    model = comm_chunks(stats)
    assert stats["frame_transpose_chunks"] > 0
    assert stats["frame_transpose_chunks"] == pytest.approx(
        2.0 * stats["frame_transpose_planar_chunks"])
    planned = sum(telemetry.counters("comm_chunk_units_total").values())
    assert planned == pytest.approx(model, abs=1e-9)

    # executed run: same counters, same sum
    qureg = qt.createQureg(n, env)
    qt.initPlusState(qureg)
    telemetry.reset()
    with qt.explicit_mesh(env.mesh):
        fz.run(qureg)
    ran = telemetry.counters("comm_chunk_units_total")
    assert sum(ran.values()) == pytest.approx(model, abs=1e-9)
    assert any("kind=frame_transpose" in k for k in ran)


def test_dist_permute_bits_carries_four_planes():
    """The grouped permute collective carries the df 4-plane layout
    natively: permuting the split planes equals splitting the permuted
    planar state (the elementwise split commutes with pure data movement
    -- plane-level BIT equality)."""
    from quest_tpu.parallel import exchange as XX

    env = _need_mesh()
    n = 12
    amps64 = _rand_amps64(n, seed=21)
    # a shard<->local crossing plus a local->local move
    source = list(range(n))
    source[2], source[n - 1] = source[n - 1], source[2]
    source[0], source[1] = source[1], source[0]
    got = XX.dist_permute_bits(DF.df_split(amps64), n=n,
                               source=tuple(source), mesh=env.mesh)
    ref = DF.df_split(XX.dist_permute_bits(amps64 + 0, n=n,
                                           source=tuple(source),
                                           mesh=env.mesh))
    assert got.shape == (4, 1 << n)
    assert np.array_equal(np.asarray(got), np.asarray(ref))


def test_scheduler_frame_permute_matches_swap_bit_blocks(df_route):
    """sched.apply_frame_permute == swap_bit_blocks on both the planar and
    the 4-plane layouts, with planar-f64/df priced 2x vs planar f32."""
    env = _need_mesh()
    n, k = 12, 2
    tb = 9
    amps64 = _rand_amps64(n, seed=8)
    with qt.explicit_mesh(env.mesh) as sched:
        out64 = sched.apply_frame_permute(amps64 + 0, n=n, lo1=tb - k,
                                          lo2=tb, k=k)
        units_f64 = sched.stats["frame_transpose_chunks"]
        planes = DF.df_split(amps64)
        out_df = sched.apply_frame_permute(planes, n=n, lo1=tb - k,
                                           lo2=tb, k=k)
        units_df = sched.stats["frame_transpose_chunks"] - units_f64
        planar = sched.stats["frame_transpose_planar_chunks"]
    ref = PG.swap_bit_blocks(amps64 + 0, n=n, lo1=tb - k, lo2=tb, k=k)
    assert np.array_equal(np.asarray(out64), np.asarray(ref))
    # split commutes with the (pure data movement) relabeling exactly
    assert np.array_equal(np.asarray(out_df), np.asarray(DF.df_split(ref)))
    # both double-precision layouts price at 2x the planar units
    assert units_f64 == pytest.approx(units_df)
    assert units_f64 + units_df == pytest.approx(2.0 * planar)


# ---------------------------------------------------------------------------
# accurate two-sum df add (QUEST_DF_ACCURATE_ADD) + norm reduction
# ---------------------------------------------------------------------------

def test_df_add_accurate_fixes_near_cancellation():
    """The Dekker caveat, concretely: with hi components cancelling
    exactly, the sloppy add rounds x.lo + y.lo once (relative error
    ~2^-25 of the tiny result); the accurate variant's second TwoSum
    keeps the result exact."""
    x = (np.float32(1.0), np.float32(2.0 ** -25))
    y = (np.float32(-1.0), np.float32(2.0 ** -49))
    exact = (np.float64(x[0]) + np.float64(x[1])
             + np.float64(y[0]) + np.float64(y[1]))
    s_h, s_l = DF.df_add(x, y)
    sloppy = np.float64(np.asarray(s_h)) + np.float64(np.asarray(s_l))
    a_h, a_l = DF.df_add_accurate(x, y)
    accurate = np.float64(np.asarray(a_h)) + np.float64(np.asarray(a_l))
    assert accurate == exact
    assert abs(sloppy - exact) > 0  # the sloppy form really does round


def test_df_accurate_add_env_flag(monkeypatch):
    """QUEST_DF_ACCURATE_ADD=1 reaches the kernels (flag in the jit
    signature, so no stale cache) and preserves parity with the native
    f64 interpreter."""
    monkeypatch.setenv("QUEST_DF_ACCURATE_ADD", "1")
    assert DF.accurate_add_enabled()
    n = 10
    ops = (("matrix", 0, (), (), PG.HashableMatrix(H)),
           ("matrix", 3, (9,), (1,), PG.HashableMatrix(X)),
           ("parity", (0, 9), (), 0.77))
    amps64 = _rand_amps64(n, seed=11)
    ref = np.asarray(PG.fused_local_run(amps64 + 0, n=n, ops=ops,
                                        sublanes=4, interpret=True))
    got = np.asarray(DF.df_join(PG.fused_local_run(
        DF.df_split(amps64), n=n, ops=ops, sublanes=4, interpret=True)))
    np.testing.assert_allclose(got, ref, atol=5e-8)


def test_df_total_prob_matches_numpy_f64():
    """The df norm reduction (the Kahan-hygiene mirror of
    statevec_calcTotalProb, QuEST_cpu_distributed.c:62-119) matches the
    numpy f64 oracle to ~2^-47 relative, in both add modes."""
    n = 14
    amps64 = _rand_amps64(n, seed=13)
    a = np.asarray(amps64, dtype=np.float64)
    oracle = float(np.sum(a[0] * a[0] + a[1] * a[1]))
    for accurate in (False, True):
        got = float(DF.df_total_prob(DF.df_split(amps64),
                                     accurate=accurate))
        assert got == pytest.approx(oracle, rel=2.0 ** -46)
