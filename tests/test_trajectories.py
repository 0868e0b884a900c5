"""Trajectory noise engine (quest_tpu/trajectories/).

Contracts under test:

- **convergence**: the ensemble-mean density of T stochastic trajectories
  matches the density-matrix oracle at 10q within the 1/sqrt(T)
  statistical tolerance, for every built-in channel AND a 2-target
  explicit Kraus map (full rho max-element AND the reduced density on the
  channel targets);
- **bit-identical replay**: a fixed seed list replays bit-identically --
  run twice, unsharded vs the 8-device CPU mesh, f32 and the df fused
  route, and vmap-batched vs sequential dispatch;
- **seed independence of plan structure**: different seeds never retrace
  (``engine_trace_total{kind=param_replay}``) and constant-seed variants
  share one structure fingerprint;
- **diagnostics**: QT501 warns once on malformed QUEST_TRAJECTORIES,
  QT502 flags non-CPTP Kraus sets at trajectory sites, and the
  unravelable/validation error paths raise typed QuESTErrors.
"""

import warnings

import numpy as np
import pytest

import jax

import quest_tpu as qt
from quest_tpu import telemetry
from quest_tpu import trajectories as tr
from quest_tpu.circuits import Circuit
from quest_tpu.engine import P
from quest_tpu.validation import QuESTError

from .helpers import get_density

ENV1 = qt.createQuESTEnv(jax.devices()[:1])
ENV8 = qt.createQuESTEnv(jax.devices()[:8])

#: ensemble size of the convergence matrix; tolerance scales as
#: C / sqrt(T) with a fixed seed, so these are deterministic tests.
T_CONV = 256
TOL = 4.0 / np.sqrt(T_CONV)

#: a CPTP 2-target Kraus map that is NOT in the built-in table: a
#: two-qubit amplitude-damping-like map built from isometry pieces.
_K2A = np.zeros((4, 4)); _K2A[0, 0] = 1.0; _K2A[1, 1] = 1.0
_K2A[2, 2] = np.sqrt(0.4); _K2A[3, 3] = np.sqrt(0.7)
_K2B = np.zeros((4, 4)); _K2B[0, 2] = np.sqrt(0.6); _K2B[1, 3] = np.sqrt(0.3)
KRAUS_2T = (_K2A, _K2B)

CHANNEL_CASES = {
    "dephasing": lambda c: c.mixDephasing(3, 0.35),
    "two_qubit_dephasing": lambda c: c.mixTwoQubitDephasing(2, 5, 0.45),
    "depolarising": lambda c: c.mixDepolarising(1, 0.5),
    "two_qubit_depolarising": lambda c: c.mixTwoQubitDepolarising(4, 7, 0.6),
    "damping": lambda c: c.mixDamping(0, 0.4),
    "pauli": lambda c: c.mixPauli(6, 0.15, 0.1, 0.2),
    "kraus_2t": lambda c: c.mixTwoQubitKrausMap(3, 8, KRAUS_2T),
}


def _noisy_circuit(n, add_channel):
    """Entangled 10q base + one channel site (density tape: the oracle runs
    it exactly, the trajectory route unravels it)."""
    c = Circuit(n, is_density_matrix=True)
    for q in range(n):
        c.hadamard(q)
    for q in range(0, n - 1, 2):
        c.controlledNot(q, q + 1)
    c.rotateY(n // 2, 0.9)
    add_channel(c)
    c.rotateX(1, -0.4)
    return c


def _reduced(rho, targets, n):
    """Partial trace of rho (2^n x 2^n, qubit 0 = least-significant index
    bit) down to ``targets`` with targets[0] the low bit of the result."""
    t = len(targets)
    axes = [n - 1 - q for q in reversed(targets)]
    rest = [a for a in range(n) if a not in axes]
    x = rho.reshape((2,) * n * 2)
    perm = axes + rest + [a + n for a in axes] + [a + n for a in rest]
    x = x.transpose(perm)
    d, r = 2 ** t, 2 ** (n - t)
    x = x.reshape(d, r, d, r)
    return np.einsum("arbr->ab", x)


@pytest.mark.parametrize("channel", sorted(CHANNEL_CASES))
def test_ensemble_mean_converges_to_density_oracle(channel):
    n = 10
    c = _noisy_circuit(n, CHANNEL_CASES[channel])
    dm = qt.createDensityQureg(n, ENV1)
    c.run(dm)
    rho = get_density(dm)

    res = tr.run_ensemble(c, T_CONV, env=ENV1, base_seed=17)
    assert res.num_trajectories == T_CONV
    # every trajectory is a unit-norm pure state
    norms = np.sum(np.asarray(res.states, dtype=np.float64) ** 2,
                   axis=(1, 2))
    np.testing.assert_allclose(norms, 1.0, atol=1e-6)

    rho_e = res.density()
    assert abs(np.trace(rho_e) - 1.0) < 1e-6
    assert np.max(np.abs(rho_e - rho)) < TOL
    # the reduced state on the channel's own qubits (O(1) elements) must
    # also land inside the statistical band
    targets = {"dephasing": (3,), "two_qubit_dephasing": (2, 5),
               "depolarising": (1,), "two_qubit_depolarising": (4, 7),
               "damping": (0,), "pauli": (6,), "kraus_2t": (3, 8)}[channel]
    assert np.max(np.abs(_reduced(rho_e, list(targets), n)
                         - _reduced(rho, list(targets), n))) < TOL


def _eight_qubit_noisy():
    c = Circuit(8, is_density_matrix=True)
    for q in range(8):
        c.hadamard(q)
    c.controlledNot(0, 4)
    c.mixDepolarising(2, 0.3)
    c.rotateZ(5, 0.7)
    c.mixDamping(6, 0.25)
    c.mixTwoQubitDephasing(1, 3, 0.4)
    return tr.unravel(c)


def test_fixed_seed_replay_bit_identical_unsharded():
    u = _eight_qubit_noisy()
    seeds = [11, 22, 33, 44, 55, 66]
    a = tr.run_ensemble(u, env=ENV1, seeds=seeds)
    b = tr.run_ensemble(u, env=ENV1, seeds=seeds)
    assert np.array_equal(a.states, b.states)
    assert a.seeds == tuple(seeds) and a.seed_name == tr.SEED_PARAM


def test_fixed_seed_replay_bit_identical_f32():
    u = _eight_qubit_noisy()
    seeds = [5, 6, 7, 8]
    a = tr.run_ensemble(u, env=ENV1, seeds=seeds, precision_code=1)
    b = tr.run_ensemble(u, env=ENV1, seeds=seeds, precision_code=1)
    assert a.states.dtype == np.float32
    assert np.array_equal(a.states, b.states)


def test_fixed_seed_replay_bit_identical_sharded():
    """Twice over the 8-device mesh is bit-stable (ONE lowering replayed),
    and the mesh walks the single device's Kraus path: the draws are
    placement-independent (counter-based threefry, no device state), so
    every trajectory takes the same branch at every site and the states
    agree to rounding. Not to the bit: the mesh and the single device are
    two XLA:CPU lowerings of the program, and a branch taken otherwise
    would differ by amplitudes of order 0.1, not by a few eps."""
    u = _eight_qubit_noisy()
    seeds = [101, 202, 303, 404]
    one = tr.run_ensemble(u, env=ENV1, seeds=seeds)
    mesh_a = tr.run_ensemble(u, env=ENV8, seeds=seeds)
    mesh_b = tr.run_ensemble(u, env=ENV8, seeds=seeds)
    assert np.array_equal(mesh_a.states, mesh_b.states)
    states = np.asarray(one.states)
    eps = np.finfo(states.dtype).eps
    np.testing.assert_allclose(np.asarray(mesh_a.states), states, rtol=0,
                               atol=16 * eps)


def test_fixed_seed_replay_bit_identical_df(monkeypatch):
    """The fused double-float Pallas route (QUEST_PALLAS_DF=1, f64) replays
    a fixed seed list bit-identically."""
    monkeypatch.setenv("QUEST_PALLAS_DF", "1")
    u = _eight_qubit_noisy()
    fz = u.fused(max_qubits=5, pallas=True, dtype=np.float64)
    seeds = [9, 10, 11]
    a = tr.run_ensemble(fz, env=ENV1, seeds=seeds, precision_code=2)
    b = tr.run_ensemble(fz, env=ENV1, seeds=seeds, precision_code=2)
    assert np.array_equal(a.states, b.states)


def test_vmap_batch_matches_sequential_bit_identical():
    """One coalesced vmap dispatch and one-at-a-time sequential dispatch
    produce the same bits lane for lane -- the trajectory draw depends
    only on (seed, site), never on lane position or batch shape."""
    u = _eight_qubit_noisy()
    seeds = [3, 1, 4, 1, 5, 9]
    batched = tr.run_ensemble(u, env=ENV1, seeds=seeds)          # one vmap
    seq = tr.run_ensemble(u, env=ENV1, seeds=seeds, max_batch=1)
    assert np.array_equal(batched.states, seq.states)


def test_new_seeds_zero_retraces():
    """A warm trajectory structure serves ANY seed values with zero new
    traces: seeds are runtime lanes, not structure."""
    u = _eight_qubit_noisy()
    tr.run_ensemble(u, env=ENV1, seeds=[1, 2, 3, 4])   # warm the executable
    before = telemetry.counter_value("engine_trace_total",
                                     kind="param_replay")
    out = tr.run_ensemble(u, env=ENV1, seeds=[7_000_001, 42, 0, 123456789])
    after = telemetry.counter_value("engine_trace_total",
                                    kind="param_replay")
    assert after - before == 0
    assert out.states.shape[0] == 4


def test_constant_seed_variants_share_fingerprint():
    """Plain-int seeds lift to anonymous uint32 slots: two tapes differing
    only in the baked seed value share one structure fingerprint (and so
    one compiled executable)."""
    def build(seed, site_shift=0):
        c = Circuit(6)
        for q in range(6):
            c.hadamard(q)
        ops = tuple(qt.channels.kraus_ops("depolarising", 0.3))
        c.applyTrajectoryKraus((2,), ops, seed, site=site_shift)
        return c
    assert build(0).fingerprint() == build(987654).fingerprint()
    # the site index IS structure: different sites, different fingerprints
    assert build(0, 0).fingerprint() != build(0, 1).fingerprint()


def test_unravel_structure_and_errors():
    c = Circuit(4, is_density_matrix=True)
    c.hadamard(0)
    c.mixDepolarising(1, 0.2)
    c.mixDamping(2, 0.1)
    u = tr.unravel(c)
    assert not u.is_density_matrix and len(u) == 3
    sites = [(a, k) for f, a, k in u._tape
             if getattr(f, "__name__", "") == "applyTrajectoryKraus"]
    assert [k["site"] for _, k in sites] == [0, 1]
    assert all(isinstance(a[2], qt.Param) for a, _ in sites)

    bad = Circuit(2, is_density_matrix=True)
    bad.mixNonTPKrausMap(0, [np.eye(2) * 0.5])
    with pytest.raises(QuESTError, match="unravel"):
        tr.unravel(bad)

    with pytest.raises(QuESTError, match="seed Param"):
        tr.run_ensemble(Circuit(2), 4, env=ENV1)  # no channel sites


def test_apply_trajectory_kraus_validation():
    dm = qt.createDensityQureg(2, ENV1)
    ops = tuple(qt.channels.kraus_ops("damping", 0.3))
    with pytest.raises(QuESTError, match="pure states"):
        qt.applyTrajectoryKraus(dm, (0,), ops, 1)
    sv = qt.createQureg(2, ENV1)
    with pytest.raises(QuESTError):  # non-CPTP set
        qt.applyTrajectoryKraus(sv, (0,), (np.eye(2) * 0.5,), 1)
    # eager CPTP application keeps unit norm
    qt.initPlusState(sv)
    qt.applyTrajectoryKraus(sv, (0,), ops, seed=4, site=0)
    assert abs(qt.calcTotalProb(sv) - 1.0) < 1e-10


def test_qt501_malformed_env_warns_once(monkeypatch):
    from quest_tpu.trajectories import ensemble as ens
    ens._ENV_WARNED.clear()
    monkeypatch.setenv("QUEST_TRAJECTORIES", "not-a-number")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert tr.trajectory_count_default() == tr.DEFAULT_TRAJECTORIES
        assert tr.trajectory_count_default() == tr.DEFAULT_TRAJECTORIES
    hits = [w for w in rec if "QT501" in str(w.message)]
    assert len(hits) == 1
    monkeypatch.setenv("QUEST_TRAJECTORIES", "0")
    with warnings.catch_warnings(record=True) as rec2:
        warnings.simplefilter("always")
        assert tr.trajectory_count_default() == 1  # clamped to minimum
    assert any("QT501" in str(w.message) for w in rec2)
    monkeypatch.setenv("QUEST_TRAJECTORIES", "12")
    assert tr.trajectory_count_default() == 12


def test_qt502_non_cptp_site_flagged():
    from quest_tpu.analysis import tapelint
    bad = Circuit(2)
    bad.applyTrajectoryKraus((0,), (np.eye(2) * 0.5,), P("s"))
    codes = [f.code for f in tapelint.lint_circuit(bad)]
    assert "QT502" in codes
    good = Circuit(2)
    good.applyTrajectoryKraus(
        (0,), tuple(qt.channels.kraus_ops("depolarising", 0.25)), P("s"))
    assert "QT502" not in [f.code for f in tapelint.lint_circuit(good)]


def test_trajectory_counters_increment():
    c = Circuit(3, is_density_matrix=True)
    c.hadamard(0)
    c.mixDephasing(1, 0.2)
    c.mixDamping(2, 0.3)
    runs0 = telemetry.counter_value("trajectory_runs_total")
    sites0 = telemetry.counter_value("trajectory_sites_total")
    ens0 = telemetry.counter_value("trajectory_ensembles_total")
    res = tr.run_ensemble(c, 5, env=ENV1, base_seed=2)
    assert telemetry.counter_value("trajectory_runs_total") - runs0 == 5
    assert telemetry.counter_value("trajectory_sites_total") - sites0 == 10
    assert telemetry.counter_value("trajectory_ensembles_total") - ens0 == 1
    # the free function is the result method's implementation
    np.testing.assert_array_equal(res.density(),
                                  qt.ensemble_density(res.states))
