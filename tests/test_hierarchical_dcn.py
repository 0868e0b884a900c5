"""Hierarchical DCN-aware collective planning (round 15, ISSUE 14).

The scheduler's two-tier mode (``hierarchical=True`` on ``explicit_mesh``
/ ``plan_circuit``) plans around the slow inter-slice link instead of
merely pricing it. This suite pins:

- the ICI/DCN shard-bit split itself (``parallel.mesh.slice_chip_bits`` /
  ``shard_bit_link``): num_slices=1 means every shard bit is ICI, a
  non-power-of-two slice count is rejected, and the boundary bit sits
  exactly at the chip/DCN split;
- flat (``hierarchical=False``) plans are stat-identical to the
  pre-round-15 scheduler (the num_slices=1 baseline) -- the A/B control;
- the hierarchical plan's DCN chunk-units are STRICTLY below flat's on a
  modeled two-slice mesh, with the per-(kind, link) cells summing
  exactly to the scalar totals;
- check_schedule re-prices the two-tier journal clean (per-(kind, link)
  cells proven against the stats), flags a tampered cell as QT103, and
  proves the once-per-reconcile DCN rule: the flat swap-chain's pivot
  decomposition trips QT108 where the hierarchical path decomposition
  stays silent;
- the staged ICI relay for an immediate-mode cross-slice SWAP (three
  mixed half-exchanges, one on DCN) executes bit-identically to the flat
  rank-permute route and journals its ``staged_relay`` marker;
- the two-slice journal stamp widens to ("comm_pipeline", base, dcn)
  while single-slice journals keep the 2-tuple (pre-round-15 decoders);
- QUEST_COMM_PIPELINE_DCN: malformed values warn ONCE via QT210
  (mirroring QT206), the resolution order is explicit arg > env > base
  depth, and fused(comm_pipeline_dcn=) stamps every PallasRun/FrameSwap
  and round-trips through as_tape/plan_from_tape.
"""

import warnings

import numpy as np
import pytest

import quest_tpu as qt
from quest_tpu import fusion, planner, telemetry
from jax.sharding import AbstractMesh
from quest_tpu.analysis.plancheck import check_circuit_comm, check_schedule
from quest_tpu.circuits import Circuit
from quest_tpu.environment import AMP_AXIS
from quest_tpu.parallel import exchange as X
from quest_tpu.parallel.mesh import shard_bit_link, slice_chip_bits
from quest_tpu.parallel.scheduler import comm_chunks, plan_circuit

import bench

ENV = qt.createQuESTEnv()  # 8-device mesh from conftest's virtual CPUs

needs_mesh = pytest.mark.skipif(ENV.mesh is None or ENV.mesh.size < 8,
                                reason="needs the 8-device host mesh")

MESH8 = AbstractMesh((8,), (AMP_AXIS,))


def _plan20(**kw):
    return plan_circuit(bench.build_circuit(20, 4), MESH8, **kw)


# ---------------------------------------------------------------------------
# the ICI/DCN shard-bit split
# ---------------------------------------------------------------------------

def test_single_slice_means_all_ici():
    # 20q on 8 devices: nl=17, shard bits at positions 17..19
    assert slice_chip_bits(MESH8, 1) == 3
    for q in (17, 18, 19):
        assert shard_bit_link(20, MESH8, 1, q) == "ici"
    assert shard_bit_link(20, MESH8, 1, 16) is None


def test_boundary_bit_sits_at_chip_dcn_split():
    # 2 slices of 4 chips: 2 ICI chip bits, the top shard bit crosses DCN
    assert slice_chip_bits(MESH8, 2) == 2
    assert shard_bit_link(20, MESH8, 2, 17) == "ici"
    assert shard_bit_link(20, MESH8, 2, 18) == "ici"
    assert shard_bit_link(20, MESH8, 2, 19) == "dcn"
    # 4 slices of 2 chips: one ICI bit, two DCN bits
    assert slice_chip_bits(MESH8, 4) == 1
    assert [shard_bit_link(20, MESH8, 4, q) for q in (17, 18, 19)] == \
        ["ici", "dcn", "dcn"]


def test_non_power_of_two_slice_count_rejected():
    with pytest.raises(ValueError, match="power of two"):
        slice_chip_bits(MESH8, 3)
    with pytest.raises(ValueError, match="partition"):
        slice_chip_bits(MESH8, 16)  # more slices than devices
    with pytest.raises(ValueError, match="power of two"):
        shard_bit_link(20, MESH8, 6, 19)


# ---------------------------------------------------------------------------
# flat control + the strict hierarchical DCN reduction
# ---------------------------------------------------------------------------

def test_flat_two_slice_plan_is_stat_identical_to_single_slice():
    base = _plan20(num_slices=1)
    flat = _plan20(num_slices=2)
    # the ICI/DCN split re-attributes, never re-plans: every shared stat
    # is unchanged and the link split sums back to the single-slice total
    for k in base:
        if k not in ("ici_chunks", "dcn_chunks", "chunks_by_kind_link"):
            assert flat[k] == base[k], k
    assert flat["ici_chunks"] + flat["dcn_chunks"] == \
        pytest.approx(base["ici_chunks"])


def test_hierarchical_dcn_chunks_strictly_below_flat():
    flat = _plan20(num_slices=2)
    hier = _plan20(num_slices=2, hierarchical=True)
    assert hier["dcn_chunks"] < flat["dcn_chunks"]
    # the per-(kind, link) cells are exact, not approximate bookkeeping
    for st in (flat, hier):
        assert sum(st["chunks_by_kind_link"].values()) == \
            pytest.approx(comm_chunks(st))
        dcn = sum(v for c, v in st["chunks_by_kind_link"].items()
                  if c.endswith("/dcn"))
        assert dcn == pytest.approx(st["dcn_chunks"])


# ---------------------------------------------------------------------------
# check_schedule: two-tier re-pricing, QT108, staged_relay records
# ---------------------------------------------------------------------------

def test_two_tier_journal_reprices_clean_both_modes():
    circ = bench.build_circuit(20, 4)
    for hier in (False, True):
        findings, stats, journal = check_circuit_comm(
            circ, MESH8, num_slices=2, hierarchical=hier)
        assert not [f for f in findings if f.severity == "error"], findings
        assert not [f for f in findings if f.code == "QT108"], findings


def test_tampered_kind_link_cell_is_flagged_qt103():
    circ = bench.build_circuit(20, 4)
    journal: list = []
    stats = plan_circuit(circ, MESH8, num_slices=2, hierarchical=True,
                         journal=journal)
    cell = next(iter(stats["chunks_by_kind_link"]))
    stats["chunks_by_kind_link"][cell] += 0.5
    findings = check_schedule(journal, stats, 20, MESH8, num_slices=2)
    assert any(f.code == "QT103" and cell in f.message for f in findings)


def test_flat_swap_chain_trips_qt108_hierarchical_does_not():
    # collective_reconcile=False forces the reconcile swap chain: flat's
    # pivot decomposition moves the DCN bit up to k-1 times per k-cycle,
    # the hierarchical path decomposition touches it exactly once
    circ = bench.build_circuit(20, 4)
    codes = {}
    for hier in (False, True):
        findings, _stats, _j = check_circuit_comm(
            circ, MESH8, num_slices=2, hierarchical=hier,
            collective_reconcile=False)
        codes[hier] = [f for f in findings if f.code == "QT108"]
        assert all(f.severity == "warning" for f in codes[hier])
        assert not [f for f in findings
                    if f.severity == "error"], findings
    assert codes[False], "flat pivot chain should move a DCN bit twice"
    assert not codes[True], codes[True]


def test_deferred_cross_slice_swap_relays_once_on_dcn():
    # regression (round-15 review): a deferred swapGate(17,19) -- both
    # positions sharded, 19 the DCN bit -- reconciles through the staged
    # ICI relay. The DCN position must ride ONLY the middle swap of the
    # (o,r);(h,r);(o,r) chain: the executor once put it on the outer
    # pair, paying the slow link twice and tripping its own QT108
    c = Circuit(20)
    c.swapGate(17, 19)
    journal: list = []
    stats = plan_circuit(c, MESH8, num_slices=2, hierarchical=True,
                         collective_reconcile=False, journal=journal)
    assert stats["staged_relays"] == 1
    # 1 DCN + 2 ICI chunk-units -- exactly what _chain_plan priced
    assert stats["chunks_by_kind_link"]["reconciliation/dcn"] == \
        pytest.approx(1.0)
    assert stats["chunks_by_kind_link"]["reconciliation/ici"] == \
        pytest.approx(2.0)
    swaps = [r for r in journal if r[0] == "reconcile_swap"]
    assert [max(a, b) for _, _, a, b in swaps] == [17, 19, 17]
    findings = check_schedule(journal, stats, 20, MESH8, num_slices=2)
    assert not [f for f in findings if f.code == "QT108"], findings
    assert not [f for f in findings if f.severity == "error"], findings


def test_truncated_reconcile_chain_is_flagged():
    # a journal that ends mid-reconciliation must not silently discard
    # the accumulated DCN touch counts: the unterminated chain is QT103
    # and the leftovers still get reconcile_done's QT108 emission
    journal = [("comm_pipeline", 1, 1),
               ("reconcile_swap", 20, 19, 0),
               ("reconcile_swap", 20, 19, 0)]
    stats = {"reconcile_chunks": 2.0,
             "chunks_by_kind_link": {"reconciliation/dcn": 2.0}}
    findings = check_schedule(journal, stats, 20, MESH8, num_slices=2)
    assert any(f.code == "QT103" and "reconciliation chain" in f.message
               for f in findings)
    assert any(f.code == "QT108" and "moved 2 times" in f.message
               for f in findings)


def test_malformed_staged_relay_record_is_flagged():
    # a relay that stages through a SHARDED slot (or around a non-DCN
    # swap) defeats its purpose; check_schedule rejects the record
    journal = [("comm_pipeline", 1, 1),
               ("staged_relay", 20, 18, 17, 0)]  # 18 is ICI, not DCN
    findings = check_schedule(journal, {}, 20, MESH8, num_slices=2)
    assert any(f.code == "QT103" and "staged_relay" in f.message
               for f in findings)


# ---------------------------------------------------------------------------
# executed staged relay + journal stamps
# ---------------------------------------------------------------------------

@needs_mesh
def test_immediate_cross_slice_swap_relays_bit_identically():
    # n=6 on 8 devices: nl=3; 2 slices -> position 5 is the DCN bit.
    # defer=False keeps the both-sharded SWAP on the immediate path where
    # flat pays a full-chunk rank permute (2 units on DCN) and
    # hierarchical stages through local slot 0 (3 mixed swaps, 1 on DCN)
    results = {}
    for hier in (False, True):
        q = qt.createQureg(6, ENV)
        qt.initDebugState(q)
        telemetry.reset()
        with qt.explicit_mesh(ENV.mesh, num_slices=2, defer=False,
                              hierarchical=hier) as sched:
            qt.swapGate(q, 3, 5)
            stats = sched.stats
        results[hier] = (np.asarray(q.amps), dict(stats))
    flat_amps, flat_stats = results[False]
    hier_amps, hier_stats = results[True]
    assert np.array_equal(flat_amps, hier_amps)
    assert flat_stats["rank_permutes"] == 1
    assert flat_stats["staged_relays"] == 0
    assert hier_stats["staged_relays"] == 1
    assert hier_stats["relocation_swaps"] == 3
    assert hier_stats["rank_permutes"] == 0
    # the relay wins on the weighted model: 1 DCN unit vs 2
    assert hier_stats["dcn_chunks"] < flat_stats["dcn_chunks"]


def test_two_slice_journal_stamp_widens_to_three_tuple():
    circ = bench.build_circuit(20, 2)
    journal: list = []
    plan_circuit(circ, MESH8, num_slices=2, comm_pipeline=4,
                 comm_pipeline_dcn=2, journal=journal)
    assert journal[0] == ("comm_pipeline", 4, 2)
    # single-slice journals keep the 2-tuple pre-round-15 decoders expect
    journal = []
    plan_circuit(circ, MESH8, num_slices=1, comm_pipeline=4,
                 journal=journal)
    assert journal[0] == ("comm_pipeline", 4)


# ---------------------------------------------------------------------------
# QUEST_COMM_PIPELINE_DCN: QT210 warn-once + resolution order + codec
# ---------------------------------------------------------------------------

@pytest.fixture
def dcn_env(monkeypatch):
    monkeypatch.setattr(X, "_PIPE_DCN_ENV_WARNED", set())
    return monkeypatch


def test_dcn_env_non_integer_warns_once_and_inherits(dcn_env):
    dcn_env.setenv(X._PIPE_DCN_ENV, "fast")
    telemetry.reset()
    with pytest.warns(RuntimeWarning, match="QT210"):
        assert X.comm_pipeline_dcn_default() == 1
    assert telemetry.counter_value(
        "analysis_findings_total", code="QT210", severity="warning") == 1.0
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # second call must stay silent
        assert X.comm_pipeline_dcn_default() == 1


def test_dcn_env_unset_inherits_base_depth(dcn_env):
    dcn_env.delenv(X._PIPE_DCN_ENV, raising=False)
    assert X.comm_pipeline_dcn_default() is None
    assert X.resolve_pipeline_dcn(None, 4) == X.resolve_pipeline(4)


def test_dcn_resolution_order_arg_env_base(dcn_env):
    dcn_env.setenv(X._PIPE_DCN_ENV, "8")
    assert X.resolve_pipeline_dcn(2, 4) == 2     # explicit arg wins
    assert X.resolve_pipeline_dcn(None, 4) == 8  # then the env
    dcn_env.delenv(X._PIPE_DCN_ENV)
    assert X.resolve_pipeline_dcn(None, 4) == X.resolve_pipeline(4)


def _fused_12q(**kw):
    c = Circuit(12)
    for q in range(12):
        c.hadamard(q)
    c.controlledNot(0, 11)
    c.tGate(11)
    return c.fused(max_qubits=5, pallas=True, shard_devices=8, **kw)


def test_fused_comm_pipeline_dcn_stamps_and_roundtrips():
    fz = _fused_12q(comm_pipeline=4, comm_pipeline_dcn=2)
    plan = fusion.plan_from_tape(tuple(fz._tape))
    stamped = [i for i in plan.items
               if isinstance(i, (planner.PallasRun, planner.FrameSwap))]
    assert stamped, "sharded pallas plan should carry PallasRun items"
    assert all(i.comm_pipeline == 4 and i.comm_pipeline_dcn == 2
               for i in stamped)
    # the encoder/decoder round trip carries the stamp with the run
    again = fusion.plan_from_tape(fusion.as_tape(plan))
    assert [getattr(i, "comm_pipeline_dcn", None) for i in again.items] \
        == [getattr(i, "comm_pipeline_dcn", None) for i in plan.items]
