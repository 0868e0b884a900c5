"""Explicit distributed path (parallel/) vs the default GSPMD path.

Model: the reference runs its single test binary under mpirun and asserts
identical amplitudes against the serial oracle (SURVEY.md section 4); here
the 8-virtual-device CPU mesh plays the role of the 8-rank MPI job, and the
default single-program path plays the role of the serial oracle.
"""

import numpy as np
import pytest

import jax
import quest_tpu as qt

from .helpers import TOL
from quest_tpu.parallel import plan_circuit
from quest_tpu.parallel.mesh import local_qubit_count

ENV = qt.createQuESTEnv()  # 8-device mesh from conftest's virtual CPUs

pytestmark = pytest.mark.skipif(ENV.mesh is None or ENV.mesh.size < 8,
                                reason="needs the 8-device host mesh")


def _random_unitary(rng, dim):
    m = rng.normal(size=(dim, dim)) + 1j * rng.normal(size=(dim, dim))
    q, r = np.linalg.qr(m)
    return q * (np.diag(r) / np.abs(np.diag(r)))


def _build(record, n, rng):
    """Gate sequence touching every dispatch class x locality regime.

    With 8 devices and n=5 state-vec qubits, nl = 2: qubits 2..4 are sharded.
    """
    u2 = _random_unitary(rng, 2)
    u4 = _random_unitary(rng, 4)
    record.hadamard(0)                       # local dense
    record.hadamard(n - 1)                   # sharded dense: pair exchange
    record.controlledNot(n - 1, 0)           # sharded control, local target
    record.controlledNot(0, n - 1)           # local control, sharded target
    record.unitary(n - 2, u2)                # sharded dense
    record.controlledUnitary(n - 1, 1, u2)   # sharded ctrl + local target
    record.twoQubitUnitary(0, n - 1, u4)     # relocation swap path
    record.rotateZ(n - 1, 0.31)              # comm-free diag on sharded qubit
    record.multiControlledPhaseFlip(list(range(n)))   # diag across all
    record.multiRotateZ([0, n - 1], -0.7)    # parity phase across shards
    record.swapGate(0, 1)                    # local swap
    record.swapGate(1, n - 1)                # mixed swap (odd-parity halves)
    record.swapGate(n - 2, n - 1)            # sharded-sharded swap
    record.multiQubitNot([0, n - 1])         # X with sharded target


class _Eager:
    def __init__(self, qureg):
        self.qureg = qureg

    def __getattr__(self, name):
        fn = getattr(qt, name)
        return lambda *a, **k: fn(self.qureg, *a, **k)


@pytest.mark.parametrize("density", [False, True])
def test_explicit_matches_default(density):
    n = 5 if not density else 4
    rng = np.random.RandomState(3)
    make = qt.createDensityQureg if density else qt.createQureg

    q_ref = make(n, ENV)
    qt.initDebugState(q_ref)
    _build(_Eager(q_ref), n, np.random.RandomState(3))

    q_dist = make(n, ENV)
    qt.initDebugState(q_dist)
    with qt.explicit_mesh(ENV.mesh):
        _build(_Eager(q_dist), n, np.random.RandomState(3))

    np.testing.assert_allclose(qt.get_np(q_dist), qt.get_np(q_ref), atol=TOL)


def test_explicit_on_circuit_tape():
    """The scheduler also works inside a jitted Circuit replay."""
    n = 5
    circ = qt.Circuit(n)
    _build(circ, n, np.random.RandomState(9))

    q_ref = qt.createQureg(n, ENV)
    qt.initPlusState(q_ref)
    _Eager_q = _Eager(q_ref)
    _build(_Eager_q, n, np.random.RandomState(9))

    q = qt.createQureg(n, ENV)
    qt.initPlusState(q)
    with qt.explicit_mesh(ENV.mesh):
        circ.run(q)

    np.testing.assert_allclose(qt.get_np(q), qt.get_np(q_ref), atol=TOL)
    # output keeps the register's sharding across the explicit kernels
    assert len(q.amps.sharding.device_set) == ENV.mesh.size


def test_plan_stats_comm_free_circuit():
    """Diagonal/phase circuits must plan zero communication (the reference's
    phase kernels are exchange-free; ours must be too)."""
    circ = qt.Circuit(5)
    circ.rotateZ(4, 0.5)
    circ.tGate(3)
    circ.multiRotateZ([0, 2, 4], 1.1)
    circ.multiControlledPhaseShift([1, 3, 4], 0.2)
    stats = plan_circuit(circ, ENV.mesh)
    assert stats["pair_exchanges"] == 0
    assert stats["relocation_swaps"] == 0
    assert stats["rank_permutes"] == 0
    assert stats["comm_free"] == 4


def test_plan_stats_exchange_counts():
    """Deferred-permutation policy (round 3): a sharded 1q dense gate
    relocates once and STAYS local (no pair exchange, no swap-back);
    repeated gates on the same qubit are then free; the layout reconciles
    at replay end."""
    nl = local_qubit_count(5, ENV.mesh)
    circ = qt.Circuit(5)
    circ.hadamard(nl)                       # sharded -> one relocation
    circ.hadamard(nl)                       # now local: no further comm
    circ.hadamard(nl)
    stats = plan_circuit(circ, ENV.mesh)
    assert stats["pair_exchanges"] == 0
    assert stats["relocation_swaps"] == 1
    assert stats["local"] >= 3
    # reconcile undoes the single displacement at the end: one collective
    # at the single-crossing cost (== the old 1-swap cost)
    assert stats["reconcile_collectives"] == 1
    assert stats["reconcile_chunks"] == 1.0
    assert stats["reconcile_swap_equiv_chunks"] == 1


def test_deferred_swap_gate_is_virtual():
    """An uncontrolled SWAP gate under the deferred scheduler moves no
    data: pure layout update, zero comm, zero compute."""
    nl = local_qubit_count(5, ENV.mesh)
    circ = qt.Circuit(5)
    circ.swapGate(0, 4)          # virtual relabel
    circ.hadamard(4)             # logical 4 now physically at 0: local!
    stats = plan_circuit(circ, ENV.mesh)
    assert stats["virtual_swaps"] == 1
    assert stats["pair_exchanges"] == 0 and stats["relocation_swaps"] == 0
    # the relabel is undone at the end by the reconciliation collective
    assert stats["reconcile_collectives"] >= 1
    assert stats["reconcile_chunks"] > 0


def test_deferred_relocation_beats_reference_policy_on_bench_circuit():
    """VERDICT r2 next #3 'done' criterion: on the 34q bench circuit the
    deferred scheduler cuts relocation traffic >= 40% vs the reference
    policy it used to mirror (immediate swap-back per gate,
    QuEST_cpu_distributed.c:1526-1568)."""
    from __graft_entry__ import _random_layers
    from quest_tpu.parallel.scheduler import comm_chunks

    circ = qt.Circuit(34)
    _random_layers(circ, 34, 8)

    deferred = plan_circuit(circ, ENV.mesh)
    immediate = plan_circuit(circ, ENV.mesh, defer=False)

    # >= 40% less relocation/exchange traffic in chunk units (the
    # reference policy pays 2 chunks per pair exchange / rank permute)
    assert comm_chunks(deferred) <= 0.6 * comm_chunks(immediate), \
        (deferred, immediate)
    assert deferred["pair_exchanges"] == 0  # nothing uses the 2-chunk path


def test_deferred_survives_mixed_tape_with_qft_and_phase_funcs():
    """VERDICT r3 next #8 'done' criterion: operator entries (QFT, named
    phase functions, projectors, matrixN) remap their coordinates through
    the scheduler instead of forcing reconciliation, so deferral keeps
    >= 30% of its comm win on realistic mixed tapes."""
    import numpy as np

    from __graft_entry__ import _random_layers
    from quest_tpu.datatypes import phaseFunc
    from quest_tpu.parallel.scheduler import comm_chunks

    n = 34
    circ = qt.Circuit(n)
    _random_layers(circ, n, 3)
    # interleave non-gate entries that used to be deferral barriers
    circ.applyQFT(list(range(n - 6, n)))          # gates on sharded qubits
    _random_layers(circ, n, 2)
    circ.applyNamedPhaseFunc([0, 1, 2, n - 1], [4], 0, phaseFunc.NORM)
    circ.applyPhaseFunc([2, n - 2], 0, [0.5], [2.0])
    circ.applyProjector(n - 1, 0)
    circ.applyMatrixN([0, 1], np.kron(np.eye(2), np.diag([1, 1j])))
    _random_layers(circ, n, 3)

    deferred = plan_circuit(circ, ENV.mesh)
    immediate = plan_circuit(circ, ENV.mesh, defer=False)
    assert comm_chunks(deferred) <= 0.7 * comm_chunks(immediate), \
        (deferred, immediate)
    # the operator entries themselves planned comm-free
    assert deferred["comm_free"] >= 4


def test_operator_entries_execute_correctly_under_deferred_layout():
    """Remapped operator entries (phase funcs, projector, matrixN, sub-
    diagonal, QFT) must produce IDENTICAL amplitudes when replayed while
    the deferred layout is non-identity (qubits physically permuted)."""
    from quest_tpu.datatypes import createSubDiagonalOp, phaseFunc

    n = 5
    nl = local_qubit_count(n, ENV.mesh)
    sub = createSubDiagonalOp(1)
    sub.elems[:] = [1.0, 1j]

    circ = qt.Circuit(n)
    circ.hadamard(n - 1)              # sharded: relocates, layout now permuted
    circ.hadamard(nl)                 # second displacement
    circ.applyPhaseFunc([0, n - 1], 0, [0.3], [2.0])
    circ.applyNamedPhaseFunc([1, n - 1], [2], 0, phaseFunc.NORM)
    circ.applyQFT([0, 1, n - 1])
    circ.applyMatrixN([n - 1], np.diag([1.0, 1j]))
    circ.applySubDiagonalOp([n - 2], sub)
    circ.applyProjector(n - 1, 0)
    circ.hadamard(0)

    q_ref = qt.createQureg(n, ENV)
    qt.initPlusState(q_ref)
    for f, a, kw in circ._tape:
        f(q_ref, *a, **kw)

    # the plan really defers across the operator entries: displacements
    # stay outstanding (reconciled only at replay end) while the operator
    # entries run comm-free on the permuted layout
    stats = plan_circuit(circ, ENV.mesh)
    assert stats["relocation_swaps"] >= 1
    # replay-end reconciliation happened, by whichever policy was cheaper
    assert stats["reconcile_collectives"] >= 1 or \
        stats["reconcile_swaps"] >= 1
    assert stats["comm_free"] >= 5

    q = qt.createQureg(n, ENV)
    qt.initPlusState(q)
    with qt.explicit_mesh(ENV.mesh):
        circ.run(q)

    np.testing.assert_allclose(qt.get_np(q), qt.get_np(q_ref), atol=TOL)


def test_measurement_under_explicit_mesh():
    """Eager measurement composes with the explicit context (host RNG +
    collapse run outside shard_map)."""
    qt.seedQuEST(ENV, [5])
    q = qt.createQureg(5, ENV)
    qt.initZeroState(q)
    with qt.explicit_mesh(ENV.mesh):
        qt.hadamard(q, 4)
        qt.controlledNot(q, 4, 0)
        outcome = qt.measure(q, 4)
        assert qt.measure(q, 0) == outcome  # Bell pair correlation
    assert abs(qt.calcTotalProb(q) - 1) < TOL


def _channel_suite(rec, n, rng):
    """Every mix* channel, with targets in both the local and sharded zones
    (with 8 devices and a 4-qubit density register the flattened state has
    2n=8 qubits, nl=5: column qubits n..2n-1 include sharded ones, and the
    channels' shifted applications (t, t+n) always touch the sharded zone)."""
    k = 1 / np.sqrt(2)
    kraus1 = [np.array([[k, 0], [0, k]]), np.array([[0, k], [k, 0]])]
    u4 = _random_unitary(rng, 4)
    kraus2 = [u4 * 0.8, 1j * 0.6 * u4]
    rec.mixDephasing(0, 0.12)
    rec.mixDephasing(n - 1, 0.2)
    rec.mixTwoQubitDephasing(0, n - 1, 0.15)
    rec.mixDepolarising(0, 0.1)
    rec.mixDepolarising(n - 1, 0.25)
    rec.mixDamping(1, 0.3)
    rec.mixDamping(n - 1, 0.17)
    rec.mixTwoQubitDepolarising(0, n - 1, 0.2)
    rec.mixTwoQubitDepolarising(n - 2, n - 1, 0.3)
    rec.mixPauli(n - 1, 0.05, 0.1, 0.15)
    rec.mixKrausMap(1, kraus1)
    rec.mixKrausMap(n - 1, kraus1)
    rec.mixTwoQubitKrausMap(n - 2, n - 1, kraus2)
    rec.mixNonTPKrausMap(n - 1, [0.9 * np.eye(2)])


def test_explicit_density_channels_match_default():
    """VERDICT round 1, next-round #3: every decoherence channel must run
    under the explicit scheduler (the analogue of the reference's
    half-chunk exchange protocols, QuEST_cpu_distributed.c:535-868) and
    agree with the single-program path."""
    n = 4
    q_ref = qt.createDensityQureg(n, ENV)
    qt.initDebugState(q_ref)
    _channel_suite(_Eager(q_ref), n, np.random.RandomState(5))

    q_dist = qt.createDensityQureg(n, ENV)
    qt.initDebugState(q_dist)
    with qt.explicit_mesh(ENV.mesh) as sched:
        _channel_suite(_Eager(q_dist), n, np.random.RandomState(5))
        stats = dict(sched.stats)

    np.testing.assert_allclose(qt.get_np(q_dist), qt.get_np(q_ref), atol=TOL)
    # the channels really took the scheduler path, and sharded targets
    # exercised the relocation planner
    assert stats["channel_superops"] >= 10
    assert stats["relocation_swaps"] > 0 or stats["pair_exchanges"] > 0
    # output stays sharded over the full mesh
    assert len(q_dist.amps.sharding.device_set) == ENV.mesh.size


def test_explicit_density_channels_on_circuit_tape():
    """Channels under explicit_mesh inside a jitted Circuit replay."""
    n = 4
    circ = qt.Circuit(n, is_density_matrix=True)
    _channel_suite(circ, n, np.random.RandomState(7))

    q_ref = qt.createDensityQureg(n, ENV)
    qt.initDebugState(q_ref)
    _channel_suite(_Eager(q_ref), n, np.random.RandomState(7))

    q = qt.createDensityQureg(n, ENV)
    qt.initDebugState(q)
    with qt.explicit_mesh(ENV.mesh):
        circ.run(q)
    np.testing.assert_allclose(qt.get_np(q), qt.get_np(q_ref), atol=TOL)


def test_deferred_falls_back_when_no_free_slot():
    """A sharded 1q dense gate whose controls occupy every local slot has
    no relocation room; deferred mode must fall back to the reference's
    pair exchange rather than raise (immediate mode never errored here)."""
    n = 5
    nl = local_qubit_count(n, ENV.mesh)  # 2 local slots on the 8-dev mesh
    circ = qt.Circuit(n)
    circ.multiControlledUnitary(list(range(nl)), n - 1, np.eye(2))
    stats = plan_circuit(circ, ENV.mesh)
    assert stats["pair_exchanges"] == 1
    # and amplitudes still agree with the single-device path
    import jax
    q = qt.createQureg(n, ENV)
    qt.initPlusState(q)
    with qt.explicit_mesh(ENV.mesh):
        circ.run(q)
    ref = qt.createQureg(n, qt.createQuESTEnv(jax.devices()[:1]))
    qt.initPlusState(ref)
    circ.run(ref)
    np.testing.assert_allclose(np.asarray(q.amps), np.asarray(ref.amps),
                               atol=TOL, rtol=TOL)


def test_two_d_mesh_ici_dcn_plan_split_and_execution():
    """VERDICT r2 next #9: an emulated 2-slice x 4-chip topology. The env
    orders devices slice-major (chip axis = minor shard bits), execution
    stays green on the 8-device mesh, and plan stats split the comm volume
    into ICI vs DCN chunks -- only ops touching the TOP log2(slices)
    sharded qubit(s) cross DCN."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    env = qt.createQuESTEnv(jax.devices()[:8], num_slices=2)
    assert env.num_slices == 2

    n = 8
    nl = local_qubit_count(n, env.mesh)  # 5: shard bits 5(i),6(i),7(dcn)
    circ = qt.Circuit(n)
    circ.hadamard(nl)            # lowest shard bit: ICI relocation
    circ.hadamard(n - 1)         # top shard bit: DCN relocation
    stats = plan_circuit(circ, env.mesh, num_slices=env.num_slices)
    assert stats["ici_chunks"] > 0
    assert stats["dcn_chunks"] > 0
    # single-slice classification: everything is ICI
    stats1 = plan_circuit(circ, env.mesh, num_slices=1)
    assert stats1["dcn_chunks"] == 0 and stats1["ici_chunks"] > 0

    # execution on the 2-slice env matches the single-device oracle
    q = qt.createQureg(n, env)
    qt.initPlusState(q)
    circ.run(q)
    ref = qt.createQureg(n, qt.createQuESTEnv(jax.devices()[:1]))
    qt.initPlusState(ref)
    circ.run(ref)
    np.testing.assert_allclose(np.asarray(q.amps), np.asarray(ref.amps),
                               atol=TOL, rtol=TOL)


def test_plan_comm_volume_model():
    """plan_circuit's per-device communication volume follows the cost
    model (2 chunks per pair exchange / rank permute, 1 per relocation,
    0 for virtual swaps, measured reconcile_chunks for reconciliation),
    consistent with the reported op counts."""
    n = 5
    circ = qt.Circuit(n)
    circ.hadamard(n - 1)
    circ.hadamard(n - 1)          # resident after the first relocation
    circ.swapGate(1, n - 1)       # virtual under deferral
    stats = plan_circuit(circ, ENV.mesh)
    cv = stats["comm_volume"]
    chunk = (1 << n) // ENV.mesh.size
    assert cv["chunk_amps"] == chunk
    expect = chunk * (2.0 * stats["pair_exchanges"]
                      + 1.0 * stats["relocation_swaps"]
                      + 2.0 * stats["rank_permutes"]
                      + stats["reconcile_chunks"])
    assert cv["amps_per_device"] == expect
    assert expect > 0  # the sharded hadamard cannot be free
    from quest_tpu.precision import real_dtype
    bytes_per_amp = 2 * np.dtype(real_dtype(None)).itemsize  # planar (re, im)
    assert cv["bytes_per_device"] == cv["amps_per_device"] * bytes_per_amp


def _host_bit_permute(vec, n, source):
    """Oracle: new_bit[q] = old_bit[source[q]] on a flat (2, 2^n) array."""
    j = np.arange(1 << n)
    i = np.zeros_like(j)
    for q in range(n):
        i |= ((j >> q) & 1) << source[q]
    return vec[:, i]


def test_dist_permute_bits_matches_host_oracle():
    """The one-collective reconciliation primitive realises arbitrary bit
    permutations (round 5; replaces the per-cycle swap chain of the
    reference's swapQubitAmps, QuEST_cpu_distributed.c:1443-1459)."""
    from quest_tpu.parallel import exchange as X

    n = 7
    rng = np.random.RandomState(11)
    q = qt.createQureg(n, ENV)
    qt.initDebugState(q)
    host = qt.get_np(q)
    host = np.stack([host.real, host.imag])
    perms = [
        tuple(rng.permutation(n)) for _ in range(4)
    ] + [
        tuple(range(n)),                      # identity: no-op
        (0, 1, 2, 3, 5, 4, 6),                # shard<->shard only (nl=4)
        (0, 1, 2, 6, 4, 5, 3),                # one crossing (m=1)
        (3, 1, 2, 0, 4, 5, 6),                # local<->local only
        (4, 5, 2, 3, 0, 1, 6),                # two crossings (m=2)
    ]
    for source in perms:
        out = X.dist_permute_bits(q.amps, n=n, source=source, mesh=ENV.mesh)
        ref = _host_bit_permute(host, n, source)
        np.testing.assert_allclose(np.asarray(out), ref, atol=TOL,
                                   err_msg=f"source={source}")
        assert len(out.sharding.device_set) == ENV.mesh.size


def test_permute_collective_stats_model():
    from quest_tpu.parallel import exchange as X

    n = 7  # nl = 4 on the 8-device mesh
    # identity: nothing
    s = X.permute_collective_stats(n, tuple(range(n)), ENV.mesh)
    assert s["collectives"] == 0 and s["chunk_units"] == 0.0
    # single crossing = the odd-parity half-exchange's cost exactly
    s = X.permute_collective_stats(n, (0, 1, 2, 6, 4, 5, 3), ENV.mesh)
    assert s["crossing_bits"] == 1 and s["chunk_units"] == 1.0
    assert s["collectives"] == 1 and not s["relabel_ppermute"]
    # m crossings cost 2*(1 - 2^-m) < 2, NOT m units
    s = X.permute_collective_stats(n, (4, 5, 6, 3, 0, 1, 2), ENV.mesh)
    assert s["crossing_bits"] == 3 and s["chunk_units"] == 2.0 * (1 - 0.125)
    # shard->shard displacement adds one full re-route (2 units)
    s = X.permute_collective_stats(n, (0, 1, 2, 3, 5, 4, 6), ENV.mesh)
    assert s["relabel_ppermute"] and s["crossing_bits"] == 0
    assert s["chunk_units"] == 2.0


def test_collective_reconcile_cuts_deferred_tail():
    """A/B: the deferred plan's reconciliation rides one collective at
    <=2 chunk-units where the swap chain paid 1 unit per displaced qubit
    (VERDICT r4 ask #8)."""
    n = 6
    circ = qt.Circuit(n)
    # touch every sharded qubit densely so several relocations are live at
    # replay end
    for q in range(n):
        circ.hadamard(q)
    for q in range(3, n):
        circ.unitary(q, np.array([[0, 1j], [1j, 0]]))
    circ.controlledNot(0, n - 1)
    stats_new = plan_circuit(circ, ENV.mesh)
    stats_old = plan_circuit(circ, ENV.mesh, collective_reconcile=False)
    # the old policy pays per-swap; the new one a bounded collective
    assert stats_old["reconcile_swaps"] >= 2
    assert stats_new["reconcile_swaps"] == 0
    assert stats_new["reconcile_collectives"] >= 1
    assert stats_new["reconcile_chunks"] <= 2.0
    assert stats_new["reconcile_chunks"] < stats_old["reconcile_chunks"]
    # both record the same swap-equivalent for the A/B, and the old path's
    # actual cost equals that equivalent
    assert stats_new["reconcile_swap_equiv_chunks"] == \
        stats_old["reconcile_swap_equiv_chunks"] == \
        stats_old["reconcile_chunks"]
    from quest_tpu.parallel.scheduler import comm_chunks
    assert comm_chunks(stats_new) < comm_chunks(stats_old)

    # and the collective path EXECUTES to the same amplitudes
    q_ref = qt.createQureg(n, ENV)
    qt.initPlusState(q_ref)
    circ.run(q_ref)
    q_new = qt.createQureg(n, ENV)
    qt.initPlusState(q_new)
    with qt.explicit_mesh(ENV.mesh):
        circ.run(q_new)
    np.testing.assert_allclose(qt.get_np(q_new), qt.get_np(q_ref), atol=TOL)


def test_batched_relocations_ab_and_execution():
    """Round-6 acceptance (ISSUE 2): relocations pending between two runs
    coalesce into grouped permutes -- the batched plan's relocation chunk
    units must match the plan_circuit comm model, beat the per-swap
    pricing, and execute to the GSPMD amplitudes."""
    from quest_tpu import telemetry
    from quest_tpu.parallel.scheduler import comm_chunks

    n = 14
    from __graft_entry__ import _random_layers
    circ = qt.Circuit(n)
    _random_layers(circ, n, depth=3)

    batched = plan_circuit(circ, ENV.mesh)
    per_swap = plan_circuit(circ, ENV.mesh, batch_relocations=False)
    # the batch machinery engaged, priced below what the same swaps would
    # have cost serially, and the total plan is cheaper
    assert batched["relocation_batches"] > 0
    assert batched["relocation_batch_qubits"] >= \
        2 * batched["relocation_batches"]
    assert batched["relocation_batch_chunks"] < \
        batched["relocation_batch_swap_equiv_chunks"]
    assert comm_chunks(batched) < comm_chunks(per_swap)

    # executed run: trace-time telemetry counters sum to the model exactly
    q = qt.createQureg(n, ENV)
    qt.initPlusState(q)
    telemetry.reset()
    with qt.explicit_mesh(ENV.mesh):
        circ.run(q)
    ran = telemetry.counters("comm_chunk_units_total")
    assert sum(ran.values()) == pytest.approx(comm_chunks(batched),
                                              abs=1e-9)
    assert any("kind=relocation_batch" in k for k in ran), ran

    # numerical parity: batched and per-swap policies both match GSPMD
    q_ref = qt.createQureg(n, ENV)
    qt.initPlusState(q_ref)
    circ.run(q_ref)
    np.testing.assert_allclose(qt.get_np(q), qt.get_np(q_ref), atol=TOL)
    q_ps = qt.createQureg(n, ENV)
    qt.initPlusState(q_ps)
    with qt.explicit_mesh(ENV.mesh, batch_relocations=False):
        circ.run(q_ps)
    np.testing.assert_allclose(qt.get_np(q_ps), qt.get_np(q_ref), atol=TOL)


def test_singleton_relocation_keeps_pair_swap_path():
    """A lone sharded dense gate (no pending lookahead work) must keep the
    1-unit dist_swap relocation: the grouped permute only ties at m=1."""
    n = 5
    circ = qt.Circuit(n)
    circ.hadamard(n - 1)
    circ.hadamard(n - 1)
    stats = plan_circuit(circ, ENV.mesh)
    assert stats["relocation_batches"] == 0
    assert stats["relocation_swaps"] == 1  # second gate rides the layout


def test_local_ctrl_mask_jit_composition_regression():
    """Two chained controlled-diagonal kernels under ONE jit must match
    the numpy oracle: the pre-round-6 grouped-view scatter select
    miscompiled exactly this composition (eager and single-kernel jit
    were correct), which the batched-relocation layouts surfaced."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from quest_tpu.environment import AMP_AXIS
    from quest_tpu.parallel import exchange as X

    n = 10
    rng = np.random.RandomState(3)
    base = rng.randn(2, 1 << n).astype(np.float32)
    sharding = NamedSharding(ENV.mesh, P(None, AMP_AXIS))
    amps0 = jax.device_put(jnp.asarray(base), sharding)

    def dg(a):
        return jnp.asarray(np.stack([[1.0, np.cos(a)],
                                     [0.0, np.sin(a)]]).astype(np.float32))

    def f(amps):
        amps = X.dist_apply_diag_phase(amps, dg(0.7), n=n, targets=(4,),
                                       controls=(5,), mesh=ENV.mesh)
        amps = X.dist_apply_diag_phase(amps, dg(1.3), n=n, targets=(4,),
                                       controls=(1,), mesh=ENV.mesh)
        return amps

    comp = base[0] + 1j * base[1]
    for ang, t, c in ((0.7, 4, 5), (1.3, 4, 1)):
        for i in range(1 << n):
            if ((i >> c) & 1) and ((i >> t) & 1):
                comp[i] *= np.exp(1j * ang)
    ref = np.stack([comp.real, comp.imag])
    np.testing.assert_allclose(np.asarray(jax.jit(f)(amps0)), ref,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(f(amps0)), ref, atol=1e-5)


@pytest.mark.parametrize("route", ["eager", "eager_density", "circuit",
                                   "fused_pallas"])
def test_register_stays_partitioned_not_replicated(route):
    """A gate on a SHARDED qubit must leave the register partitioned: each
    device keeps 1/ndev of the state. ``len(sharding.device_set)`` cannot
    see the failure this guards -- under jax 0.9 the compiler returned such
    a gate's result fully replicated (every device holding, and from then
    on updating, the whole state) and the device set still counted 8.
    ``Qureg.put`` moves nothing eagerly, so the eager routes see the layout
    the appliers themselves were lowered with."""
    from quest_tpu.circuits import Circuit

    n = 12
    ndev = ENV.mesh.size
    if route == "eager_density":
        q = qt.createDensityQureg(n // 2, ENV)
        qt.initPlusState(q)
        qt.hadamard(q, n // 2 - 1)
        qt.mixDepolarising(q, n // 2 - 1, 0.1)
        qt.mixDamping(q, n // 2 - 1, 0.2)
        qt.mixTwoQubitDepolarising(q, 0, n // 2 - 1, 0.1)
        qt.mixKrausMap(q, n // 2 - 1, [np.sqrt(0.5) * np.eye(2),
                                       np.sqrt(0.5) * np.diag([1.0, -1.0])])
    else:
        q = qt.createQureg(n, ENV)
    if route == "eager":
        qt.hadamard(q, n - 1)
        qt.controlledNot(q, n - 1, 0)
        qt.swapGate(q, n - 2, n - 1)
        qt.pauliX(q, n - 1)
    elif route != "eager_density":
        circ = Circuit(n)
        circ.hadamard(0)
        circ.hadamard(n - 1)
        circ.controlledNot(n - 1, 0)
        circ.rotateX(n - 2, 0.3)
        if route == "fused_pallas":
            circ = circ.fused(max_qubits=5, pallas=True, shard_devices=ndev)
        circ.run(q)
    shards = q.amps.addressable_shards
    assert len({s.device for s in shards}) == ndev
    assert {s.data.shape for s in shards} == {(2, (1 << n) // ndev)}
    assert abs(qt.calcTotalProb(q) - 1.0) < 1e-10
