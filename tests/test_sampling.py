"""On-device batched sampling & mid-circuit measurement (round 19).

Covers quest_tpu/sampling against the eager measurement oracle:

- sampled marginals match the exact outcome distribution on small
  registers, and a chi-square test at 20 qubits stays in bounds;
- fixed-seed shot tables are BIT-identical across the unsharded, 8-device
  mesh, f32 and df routes (dyadic circuits: every outcome probability is
  exactly representable in f32, so all routes walk the same CDF);
- mid-circuit measurement/collapse as tape items: fusion barrier,
  segment seam, engine seed-slot lift, and equality with the eager
  ``collapseToOutcome`` collapse on every route;
- the one-dispatch request: circuit + S shots + Pauli-sum expectation as
  ONE ``device_dispatch_total{route=request}`` launch moving O(S) bits
  (``sample_host_transfer_bytes``), never 2^N amplitudes;
- the f32 ``prob_of_all_outcomes`` compensated-accumulation regression
  against a f64 oracle;
- ``QUEST_SHOTS`` (QT801) and the QT005 deferred-window lint.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import quest_tpu as qt
from quest_tpu import capture, fusion, planner, sampling, segments, telemetry
from quest_tpu.engine import P
from quest_tpu.ops import init as ops_init
from quest_tpu.sampling import request as rq
from quest_tpu.sampling import sampler as sp

ENV1 = qt.createQuESTEnv(jax.devices()[:1])
ENV8 = qt.createQuESTEnv(jax.devices()[:8])


def _dyadic(q):
    """Gates whose outcome probabilities are all k * 2^-m: exact in f32,
    so every route's CDF is bitwise identical."""
    qt.hadamard(q, 0)
    qt.controlledNot(q, 0, 1)
    qt.hadamard(q, 3)
    qt.pauliX(q, 5)


def _generic(q):
    qt.hadamard(q, 0)
    qt.controlledNot(q, 0, 1)
    qt.rotateY(q, 2, 0.7)
    if q.num_qubits_represented > 3:
        qt.rotateX(q, 3, 1.1)


def _outcome_probs(q):
    """Exact outcome distribution of the register (f64 oracle)."""
    amps = np.asarray(q.amps, dtype=np.float64)
    if q.is_density_matrix:
        dim = 1 << q.num_qubits_represented
        return np.diagonal(amps[0].reshape(dim, dim))
    return amps[0] ** 2 + amps[1] ** 2


# ---------------------------------------------------------------------------
# sampler: marginals vs oracle, chi-square, bit-identity
# ---------------------------------------------------------------------------

def test_sampled_marginals_match_oracle_small():
    q = qt.createQureg(4, ENV1)
    _generic(q)
    p = _outcome_probs(q)
    shots = 40000
    tab = qt.sampleQureg(q, shots=shots, seed=11)
    assert tab.shape == (shots,) and tab.dtype == np.int32
    emp = np.bincount(tab, minlength=16) / shots
    # 1/sqrt(S) statistics: ~0.005 at 40k shots; 4 sigma margin
    assert np.abs(emp - p).max() < 4.0 / np.sqrt(shots)


def test_sampled_subset_targets_match_marginal_oracle():
    q = qt.createQureg(5, ENV1)
    _generic(q)
    p = _outcome_probs(q).reshape([2] * 5)  # [q4,...,q0] little-endian last
    # marginal over targets (1, 3): outcome bit0 = qubit 1, bit1 = qubit 3
    marg = np.zeros(4)
    for i in range(32):
        b1, b3 = (i >> 1) & 1, (i >> 3) & 1
        marg[b1 | (b3 << 1)] += p.reshape(-1)[i]
    shots = 40000
    tab = qt.sampleQureg(q, targets=(1, 3), shots=shots, seed=3)
    assert tab.max() < 4
    emp = np.bincount(tab, minlength=4) / shots
    assert np.abs(emp - marg).max() < 4.0 / np.sqrt(shots)


def test_density_register_sampling_matches_statevec():
    qs = qt.createQureg(3, ENV1)
    qd = qt.createDensityQureg(3, ENV1)
    for q in (qs, qd):
        _generic(q)
    ts = qt.sampleQureg(qs, shots=20000, seed=9)
    td = qt.sampleQureg(qd, shots=20000, seed=9)
    ps = np.bincount(ts, minlength=8) / 20000
    pd = np.bincount(td, minlength=8) / 20000
    assert np.abs(ps - pd).max() < 4.0 / np.sqrt(20000)


def test_chi_square_20q():
    """20-qubit register, marginal over 3 qubits: Pearson chi-square of
    the sampled table against the analytic marginal stays under the
    99.9%-ile of chi2(7) -- the millions-of-amps regime the sampler
    exists for, still one fixed-shape program."""
    q = qt.createQureg(20, ENV1)
    qt.hadamard(q, 0)
    qt.controlledNot(q, 0, 10)
    qt.rotateY(q, 19, 0.9)
    targets = (0, 10, 19)
    shots = 50000
    tab = qt.sampleQureg(q, targets=targets, shots=shots, seed=123)
    # analytic marginal: bell pair (bits 0,1 correlated), rotY on bit 2
    p1 = np.sin(0.45) ** 2  # P(qubit19 = 1)
    marg = np.zeros(8)
    for b2 in (0, 1):
        pb2 = p1 if b2 else 1 - p1
        marg[0 | (b2 << 2)] = 0.5 * pb2
        marg[3 | (b2 << 2)] = 0.5 * pb2
    emp = np.bincount(tab, minlength=8).astype(np.float64)
    mask = marg > 0
    chi2 = float(np.sum((emp[mask] - shots * marg[mask]) ** 2
                        / (shots * marg[mask])))
    # zero-probability outcomes must never be drawn
    assert emp[~mask].sum() == 0
    # df = 3 nonzero-cell count - 1 = 3; chi2(3) 99.9%-ile ~ 16.3
    assert chi2 < 16.3, f"chi2={chi2}"


@pytest.mark.parametrize("envname,prec", [
    ("mesh8-f64", 2), ("unsharded-f32", 1), ("mesh8-f32", 1)])
def test_fixed_seed_shot_tables_bitident_across_routes(envname, prec):
    """The acceptance bit-identity: one (circuit, seed, shots) spec
    yields the SAME int32 table on every execution route. Dyadic
    circuit, so the f32 CDF is exact on all of them."""
    env = ENV8 if envname.startswith("mesh8") else ENV1
    ref = qt.createQureg(6, ENV1)
    _dyadic(ref)
    want = qt.sampleQureg(ref, shots=1000, seed=42)
    q = qt.createQureg(6, env, precision_code=prec)
    _dyadic(q)
    got = qt.sampleQureg(q, shots=1000, seed=42)
    assert np.array_equal(want, got), f"route {envname} diverged"


def test_fixed_seed_shot_table_bitident_df_route(monkeypatch):
    """The df (double-float Pallas) route: the fused pallas circuit
    evolves the state, the sampler rides on top -- same table."""
    monkeypatch.setenv("QUEST_PALLAS_DF", "1")
    ref = qt.createQureg(6, ENV1)
    _dyadic(ref)
    want = qt.sampleQureg(ref, shots=500, seed=7)
    c = qt.Circuit(6)
    c.hadamard(0)
    c.controlledNot(0, 1)
    c.hadamard(3)
    c.pauliX(5)
    amps = c.fused(pallas=True).compiled(donate=False)(
        ops_init.init_classical(1 << 6, np.dtype("float32"), 0))
    got = np.asarray(sp.sample_jit(amps, np.uint32(7), n=6,
                                   targets=tuple(range(6)), shots=500))
    assert np.array_equal(want, got)


def test_draw_outcomes_never_out_of_range():
    """Draws at the CDF edges clamp branch-free (u=0 and u~1)."""
    p = jnp.asarray(np.full(8, 0.125, dtype=np.float32))
    u = jnp.asarray(np.array([0.0, 1.0 - 1e-7, 0.999999], dtype=np.float32))
    out = np.asarray(sp.draw_outcomes(p, u))
    assert out.min() >= 0 and out.max() <= 7


# ---------------------------------------------------------------------------
# mid-circuit measurement / collapse
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("env,prec", [(ENV1, 2), (ENV8, 2), (ENV1, 1)])
def test_mid_collapse_matches_eager_collapse(env, prec):
    for outcome in (0, 1):
        a = qt.createQureg(4, env, precision_code=prec)
        b = qt.createQureg(4, env, precision_code=prec)
        for q in (a, b):
            _generic(q)
        qt.collapseToOutcome(a, 1, outcome)
        qt.applyMidCollapse(b, 1, outcome)
        # rsqrt-renormalised vs 1/sqrt: allclose, not bit-exact
        tol = 1e-10 if prec == 2 else 1e-5
        np.testing.assert_allclose(np.asarray(a.amps), np.asarray(b.amps),
                                   atol=tol)


def test_mid_collapse_matches_eager_on_density():
    a = qt.createDensityQureg(3, ENV1)
    b = qt.createDensityQureg(3, ENV1)
    for q in (a, b):
        _generic(q)
        qt.mixDephasing(q, 0, 0.2)
    qt.collapseToOutcome(a, 0, 1)
    qt.applyMidCollapse(b, 0, 1)
    np.testing.assert_allclose(np.asarray(a.amps), np.asarray(b.amps),
                               atol=1e-10)


def test_mid_measurement_collapses_to_valid_branch():
    """The drawn branch is one of the two eager collapses, with the
    drawn-outcome frequency matching the marginal."""
    hits = 0
    trials = 40
    for s in range(trials):
        q = qt.createQureg(2, ENV1)
        qt.rotateY(q, 0, 0.8)  # P(1) = sin^2(0.4) ~ 0.1516
        qt.applyMidMeasurement(q, 0, s)
        amps = np.asarray(q.amps)
        p = amps[0] ** 2 + amps[1] ** 2
        # collapsed: exactly one of the target's branches survives
        odd = p.reshape(2, 2)[:, 1].sum()
        assert odd < 1e-12 or odd > 1 - 1e-12
        assert abs(p.sum() - 1.0) < 1e-9
        hits += odd > 0.5
    expect = np.sin(0.4) ** 2 * trials
    assert abs(hits - expect) < 4 * np.sqrt(trials * 0.16)


def test_mid_measurement_is_tapeable_and_fusion_barrier():
    c = qt.Circuit(3)
    c.hadamard(0)
    c.applyMidMeasurement(0, 5, site=0)
    c.applyMidCollapse(1, 0)
    assert len(c) == 3
    fn, args, kwargs = c._tape[1]
    assert fn.__name__ == "applyMidMeasurement"
    assert getattr(fn, "_fusion_barrier") and getattr(fn,
                                                      "_measurement_site")
    # the fuser refuses to capture a measurement site
    assert capture.capture(fn, args, kwargs, 3, np.dtype("float64")) is None


def test_segment_cuts_forced_at_measurement_seams():
    c = qt.Circuit(3)
    c.hadamard(0)
    c.hadamard(1)
    c.applyMidCollapse(0, 0)
    c.hadamard(2)
    c.pauliX(0)
    assert segments.measurement_seams(c._tape) == {2, 3}
    # unbounded greedy would be [0, 5]; the site forces [0,2,3,5]
    assert segments.segment_cuts(c._tape, 3) == [0, 2, 3, 5]


def test_mid_measurement_seed_lifts_through_engine():
    """P('m') at the seed position is a 'seed' slot: S requests replay
    ONE vmap executable, per-lane streams, deterministic."""
    c = qt.Circuit(2)
    c.hadamard(0)
    c.applyMidMeasurement(0, P("m"), site=0)
    lifted = c.lifted()
    assert [s.kind for s in lifted.slots] == ["seed"]
    with qt.Engine(c, max_batch=4, max_delay_ms=0.0) as eng:
        futs = eng.submit_many([{"m": s} for s in range(4)])
        states = [np.asarray(f.result()) for f in futs]
    for st in states:
        p = st[0] ** 2 + st[1] ** 2
        assert abs(p.sum() - 1.0) < 1e-9
        # collapsed to a definite branch of the measured qubit
        branch = p.reshape(2, 2)[:, 1].sum()
        assert branch < 1e-9 or branch > 1 - 1e-9
    # determinism: same seeds -> same states
    with qt.Engine(c, max_batch=4, max_delay_ms=0.0) as eng:
        futs = eng.submit_many([{"m": s} for s in range(4)])
        states2 = [np.asarray(f.result()) for f in futs]
    for a, b in zip(states, states2):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# the one-dispatch request
# ---------------------------------------------------------------------------

def test_sample_request_single_dispatch_and_o_s_transfer():
    c = qt.Circuit(4)
    c.hadamard(0)
    c.controlledNot(0, 1)
    c.rotateY(2, 0.3)
    exe = rq.sample_request(c, shots=256)
    amps = ops_init.init_classical(1 << 4, np.dtype("float64"), 0)
    before = telemetry.counter_value("device_dispatch_total",
                                     route="request")
    out = rq.to_host(exe(amps, 5))
    delta = telemetry.counter_value("device_dispatch_total",
                                    route="request") - before
    assert delta == 1, "circuit + sampling must be ONE dispatched program"
    assert exe.num_dispatches == 1
    assert out["shots"].shape == (256,)
    # O(S) words crossed, not O(2^N) amplitudes
    nbytes = telemetry.snapshot()["gauges"]["sample_host_transfer_bytes"]
    assert nbytes == out["shots"].nbytes


def test_sample_request_with_pauli_sum_and_mid_measurement():
    """Circuit + mid-circuit measurement + S shots + Pauli-sum
    expectation: one program, expectation matches the eager
    calcExpecPauliSum of the equivalently-collapsed state."""
    c = qt.Circuit(3)
    c.hadamard(0)
    c.controlledNot(0, 1)
    c.applyMidMeasurement(0, P("s"), site=1)
    codes = [3, 0, 0, 0, 3, 0]
    coeffs = [0.5, 0.25]
    exe = rq.sample_request(c, shots=128, pauli_codes=codes, coeffs=coeffs)
    before = telemetry.counter_value("device_dispatch_total",
                                     route="request")
    out = rq.to_host(exe(
        ops_init.init_classical(1 << 3, np.dtype("float64"), 0), 3))
    assert telemetry.counter_value("device_dispatch_total",
                                   route="request") - before == 1
    # eager oracle: replay the same tape (same seed) eagerly, then
    # calcExpecPauliSum
    q = qt.createQureg(3, ENV1)
    qt.hadamard(q, 0)
    qt.controlledNot(q, 0, 1)
    qt.applyMidMeasurement(q, 0, 3, site=1)
    ws = qt.createQureg(3, ENV1)
    want = qt.calcExpecPauliSum(q, codes, coeffs, ws)
    assert out["expec"] == pytest.approx(want, abs=1e-9)
    # and the shot table replays bit-identically
    out2 = rq.to_host(exe(
        ops_init.init_classical(1 << 3, np.dtype("float64"), 0), 3))
    assert np.array_equal(out["shots"], out2["shots"])


def test_sample_request_seed_varies_table_not_program():
    c = qt.Circuit(3)
    c.hadamard(0)
    c.rotateY(1, 0.4)
    exe = rq.sample_request(c, shots=200)
    t1 = rq.to_host(exe(
        ops_init.init_classical(1 << 3, np.dtype("float64"), 0), 1))
    t2 = rq.to_host(exe(
        ops_init.init_classical(1 << 3, np.dtype("float64"), 0), 2))
    assert not np.array_equal(t1["shots"], t2["shots"])
    # the executable is cached: same spec returns the same object
    assert rq.sample_request(c, shots=200) is exe


def test_engine_finalize_returns_shot_tables():
    """The Engine finalize hook: vmap batches return per-lane shot
    tables; the 2^n states never cross."""
    c = qt.Circuit(3)
    c.hadamard(0)
    c.rotateY(1, P("theta"))
    fin = sampling.sample_reduce(n=3, targets=(0, 1, 2), shots=64)
    red = sampling.expectation_reduce(n=3, codes=[3, 0, 0], coeffs=[1.0])

    def finalize(amps):
        return {"shots": fin(amps, 0), "expec": red(amps)}

    with qt.Engine(c, max_batch=2, max_delay_ms=0.0,
                   finalize=finalize) as eng:
        futs = eng.submit_many([{"theta": 0.1}, {"theta": 0.2}])
        outs = [f.result() for f in futs]
    for out, th in zip(outs, (0.1, 0.2)):
        assert isinstance(out["shots"], jax.Array)
        assert np.asarray(out["shots"]).shape == (64,)
        assert float(out["expec"]) == pytest.approx(0.0, abs=1e-9)


def test_run_ensemble_shots_on_device():
    c = qt.Circuit(2, is_density_matrix=True)
    c.hadamard(0)
    c.controlledNot(0, 1)
    c.mixDephasing(0, 0.1)
    res = qt.run_ensemble(c, 6, shots=50, shot_seed=3)
    assert res.states is None
    assert res.shot_tables.shape == (6, 50)
    assert res.shot_tables.dtype == np.int32
    # bell-pair outcomes under dephasing: only 0b00 and 0b11
    assert set(np.unique(res.shot_tables)) <= {0, 3}
    with pytest.raises(qt.QuESTError):
        res.density()
    # replay determinism
    res2 = qt.run_ensemble(c, 6, shots=50, shot_seed=3)
    assert np.array_equal(res.shot_tables, res2.shot_tables)


# ---------------------------------------------------------------------------
# satellites: f32 accuracy, counters, env, lint
# ---------------------------------------------------------------------------

def test_prob_of_all_outcomes_f32_regression_vs_f64_oracle():
    """The compensated rowwise group sum: f32 grouped marginals stay
    within ~1e-6 of the f64 oracle even when the naive per-group sum
    drifts to ~1e-5 (many tiny addends per group)."""
    rng = np.random.default_rng(0)
    n = 12
    v = rng.standard_normal(1 << n) + 1j * rng.standard_normal(1 << n)
    v /= np.linalg.norm(v)
    q64 = qt.createQureg(n, ENV1)
    q32 = qt.createQureg(n, ENV1, precision_code=1)
    for q in (q64, q32):
        qt.initStateFromAmps(q, v.real, v.imag)
    targets = [0, 5, 11]
    p64 = np.asarray(qt.calcProbOfAllOutcomes(q64, targets),
                     dtype=np.float64)
    p32 = np.asarray(qt.calcProbOfAllOutcomes(q32, targets),
                     dtype=np.float64)
    assert np.abs(p64 - p32).max() < 2e-6


def test_sampling_input_validation():
    q = qt.createQureg(2, ENV1)
    with pytest.raises(qt.QuESTError):
        qt.applyMidMeasurement(q, 5, 0)          # target out of range
    with pytest.raises(qt.QuESTError):
        qt.applyMidCollapse(q, 0, 2)             # outcome not in {0, 1}
    with pytest.raises(qt.QuESTError):
        qt.sampleQureg(q, targets=(0, 7))        # bad target set
    with pytest.raises(qt.QuESTError):
        qt.sampleQureg(q, shots=0)               # sub-1 shot count


def test_measure_host_syncs_counter_counts_old_path():
    q = qt.createQureg(2, ENV1)
    qt.hadamard(q, 0)
    before = telemetry.counter_value("measure_host_syncs_total")
    qt.measure(q, 0)
    qt.collapseToOutcome(q, 1, 0)
    assert telemetry.counter_value("measure_host_syncs_total") \
        - before == 2
    # the sampler adds none
    qt.sampleQureg(q, shots=16, seed=0)
    assert telemetry.counter_value("measure_host_syncs_total") \
        - before == 2


def test_quest_shots_env_default_and_qt801(monkeypatch):
    monkeypatch.setenv("QUEST_SHOTS", "37")
    rq._ENV_WARNED.clear()
    assert rq.shots_default() == 37
    monkeypatch.setenv("QUEST_SHOTS", "zero-point-five")
    rq._ENV_WARNED.clear()
    with pytest.warns(RuntimeWarning, match="QT801"):
        assert rq.shots_default() == rq.DEFAULT_SHOTS
    # warn-once: the second read is silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        assert rq.shots_default() == rq.DEFAULT_SHOTS


def test_tapelint_qt005_measurement_in_deferred_window():
    from quest_tpu.analysis import tapelint
    from quest_tpu.sampling.measure import applyMidCollapse
    tb = 9
    swap = (fusion._apply_frame_swap, (planner.FrameSwap(tb, 2),), {})
    tape = [swap, (applyMidCollapse, (0, 0), {}), swap]
    found = tapelint.lint_tape(tape, 6, is_density=True)
    assert any(f.code == "QT005" for f in found)
    # at identity (before any swap) the same site is clean
    tape_ok = [(applyMidCollapse, (0, 0), {}), swap, swap]
    found_ok = tapelint.lint_tape(tape_ok, 6, is_density=True)
    assert not any(f.code == "QT005" for f in found_ok)


def test_sampling_module_not_defer_safe():
    """sampling.measure is deliberately absent from _DEFER_SAFE_MODULES:
    a measurement site forces reconciliation under the explicit
    scheduler (the QT005 contract at plan level)."""
    from quest_tpu import circuits
    from quest_tpu.sampling.measure import applyMidMeasurement
    assert not circuits._defer_safe(applyMidMeasurement)
