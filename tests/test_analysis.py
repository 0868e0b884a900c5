"""Static-analysis suite: quest_tpu/analysis (plan verifier, DMA-ring
checker, tape linter) -- the ISSUE 6 mutation-testing contract.

Every checker must (a) pass clean over the real planner/scheduler output
and (b) catch a seeded fault:

- ringcheck: hazard-free sweep over every reachable (ring, chunks,
  geometry) point; an off-by-one store wait, an overfilled prologue and
  skipped epilogue waits are each caught (QT201/QT202);
- plancheck frames: the 20q fused Pallas plan replays to identity; a
  dropped folded store swap (QT102) and an out-of-range grid block
  (QT106) are caught, as is a dense op targeting outside the tile
  (QT101) and control/target aliasing (QT105);
- plancheck schedule: the explicit scheduler's journal re-prices to the
  plan_circuit stats exactly; a mispriced chunk-unit total (QT103) and a
  dropped relocation record (QT104) are caught;
- tapelint: adjacent cancellations (QT001), mergeable rotations (QT002),
  cache-defeating constant angles cross-checked against
  params.lift_tape (QT003), malformed events (QT004);
- the QUEST_PALLAS_RING env diagnostic (QT205) warns once per value and
  states the clamped depth; QUEST_VERIFY=1 gates Circuit.fused().

All checks are zero-device: nothing here executes a state vector.
"""

import dataclasses
import warnings

import numpy as np
import pytest

from quest_tpu import analysis as A
from quest_tpu import events, fusion, planner, telemetry
from jax.sharding import AbstractMesh
from quest_tpu.circuits import Circuit
from quest_tpu.environment import AMP_AXIS
from quest_tpu.ops import pallas_gates as PG

import bench

H = np.array([[1, 1], [1, -1]]) / np.sqrt(2)


def _codes(findings):
    return sorted({f.code for f in findings})


# ---------------------------------------------------------------------------
# ringcheck: hazard freedom and fault injection
# ---------------------------------------------------------------------------

def test_ring_sweep_reachable_is_hazard_free():
    findings = A.sweep_reachable()
    assert not A.error_findings(findings), A.render_text(findings)
    # the f64 geometry derates deep rings against the VMEM budget, so the
    # sweep is expected to NOTE derates -- as info, never as errors
    assert set(_codes(findings)) <= {"QT204"}


def test_ring_mutation_store_wait_off_by_one():
    ev = A.ring_events(16, 3, store_wait_offset=1)
    findings = A.check_events(ev, 16, 3, location="mut")
    assert "QT202" in _codes(A.error_findings(findings))


def test_ring_mutation_overfilled_prologue():
    ev = A.ring_events(16, 2, prologue_fill=3)
    findings = A.check_events(ev, 16, 2, location="mut")
    assert "QT201" in _codes(A.error_findings(findings))


def test_ring_mutation_skipped_epilogue_waits():
    ev = A.ring_events(16, 3, skip_final_waits=True)
    findings = A.check_events(ev, 16, 3, location="mut")
    assert "QT202" in _codes(A.error_findings(findings))


def test_ring_vmem_budget_violation_is_flagged():
    # 2 slots x 32 MiB cannot fit the 48 MiB budget at any depth >= 2
    findings = A.check_ring(8, 2, 32 << 20, location="big")
    assert "QT203" in _codes(A.error_findings(findings))


def test_effective_ring_depth_is_the_shared_clamp():
    # capped by the chunk count, floored at the 2-slot minimum
    assert PG.effective_ring_depth(5, 2, 1024) == 2
    assert PG.effective_ring_depth(1, 16, 1024) == 2
    # VMEM derate: 2*ring*8MiB <= 48MiB first holds at ring 3
    assert PG.effective_ring_depth(5, 100, 8 << 20) == 3
    assert PG.effective_ring_depth(4, 100, 1024) == 4


# ---------------------------------------------------------------------------
# plancheck frames: the 20q fused plan and its mutations
# ---------------------------------------------------------------------------

def _plan_20q():
    fz = bench.build_circuit(20, 2).fused(max_qubits=5, pallas=True)
    return fusion.plan_from_tape(fz._tape)


def test_fused_plan_replays_clean():
    findings = A.check_plan(_plan_20q(), 20)
    assert not A.error_findings(findings), A.render_text(findings)


def test_plan_mutation_dropped_store_swap():
    plan = _plan_20q()
    for i, it in enumerate(plan.items):
        if isinstance(it, planner.PallasRun) and it.store_swap_k:
            plan.items[i] = dataclasses.replace(it, store_swap_k=0)
            break
    else:
        pytest.fail("20q plan no longer folds a store swap")
    assert "QT102" in _codes(A.error_findings(A.check_plan(plan, 20)))


def test_plan_mutation_grid_block_out_of_range():
    plan = _plan_20q()
    for i, it in enumerate(plan.items):
        if isinstance(it, planner.PallasRun) and it.load_swap_k:
            hi = it.tile_bits if it.load_swap_hi is None else it.load_swap_hi
            plan.items[i] = dataclasses.replace(it, load_swap_hi=hi + 9)
            break
    else:
        pytest.fail("20q plan no longer folds a load swap")
    assert "QT106" in _codes(A.error_findings(A.check_plan(plan, 20)))


def test_plan_mutation_dense_target_outside_tile():
    op = ("matrix", 12, (), (), PG.HashableMatrix(H))
    plan = planner.FusePlan(items=[planner.PallasRun(ops=(op,), tile_bits=10)])
    assert "QT101" in _codes(A.error_findings(A.check_plan(plan, 16)))
    with pytest.raises(A.AnalysisError) as err:
        A.verify_plan(plan, nsv=16, emit=False)
    assert "QT101" in str(err.value)


def test_plan_control_target_aliasing():
    op = ("matrix", 3, (3, 5), (1, 1), PG.HashableMatrix(H))
    plan = planner.FusePlan(items=[planner.PallasRun(ops=(op,), tile_bits=10)])
    assert "QT105" in _codes(A.error_findings(A.check_plan(plan, 16)))


def test_plan_identity_frame_required_before_dense_item():
    # a lone load swap leaves the frame active across a FusedBlock
    run = planner.PallasRun(ops=(), tile_bits=10, load_swap_k=2)
    blk = planner.FusedBlock(qubits=(0, 1), matrix=np.eye(4))
    plan = planner.FusePlan(items=[run, blk])
    assert "QT102" in _codes(A.error_findings(A.check_plan(plan, 16)))


# ---------------------------------------------------------------------------
# plancheck schedule: journal re-pricing and layout replay
# ---------------------------------------------------------------------------

MESH8 = AbstractMesh((8,), (AMP_AXIS,))


def test_schedule_reprices_clean_batched_and_per_swap():
    circ = bench.build_circuit(20, 4)
    for batch in (True, False):
        findings, stats, journal = A.check_circuit_comm(
            circ, MESH8, batch_relocations=batch)
        assert findings == [], A.render_text(findings)
        assert journal, "scheduler journaled nothing"


def test_schedule_mutation_mispriced_chunk_unit():
    findings, stats, journal = A.check_circuit_comm(
        bench.build_circuit(20, 4), MESH8)
    assert findings == []
    bad = dict(stats)
    bad["relocation_batch_chunks"] = bad.get("relocation_batch_chunks", 0) + 1
    got = A.check_schedule(journal, bad, 20, MESH8)
    assert "QT103" in _codes(A.error_findings(got))


def test_schedule_mutation_dropped_relocation_record():
    findings, stats, journal = A.check_circuit_comm(
        bench.build_circuit(20, 4), MESH8)
    assert findings == []
    dropped = list(journal)
    for i, rec in enumerate(dropped):
        if rec[0] == "permute":
            del dropped[i]
            break
    else:
        pytest.fail("batched schedule journaled no permute record")
    got = A.check_schedule(dropped, stats, 20, MESH8)
    assert "QT104" in _codes(A.error_findings(got))


def test_schedule_mutation_dropped_dist_swap_record():
    findings, stats, journal = A.check_circuit_comm(
        bench.build_circuit(20, 4), MESH8, batch_relocations=False)
    assert findings == []
    dropped = list(journal)
    for i, rec in enumerate(dropped):
        if rec[0] == "dist_swap":
            del dropped[i]
            break
    else:
        pytest.fail("per-swap schedule journaled no dist_swap record")
    got = A.check_schedule(dropped, stats, 20, MESH8)
    assert A.error_findings(got)


# ---------------------------------------------------------------------------
# tapelint
# ---------------------------------------------------------------------------

def test_lint_adjacent_cancellation_qt001():
    c = Circuit(2)
    c.hadamard(0)
    c.hadamard(0)
    assert "QT001" in _codes(A.lint_circuit(c))


def test_lint_mergeable_rotations_qt002():
    c = Circuit(2)
    c.rotateZ(0, 0.3)
    c.rotateZ(0, 0.4)
    assert "QT002" in _codes(A.lint_circuit(c))


def test_lint_constant_angles_qt003_cross_checked_with_lift_tape():
    from quest_tpu.params import lift_tape

    c = Circuit(2)
    c.rotateZ(0, 0.3)
    c.rotateX(1, 0.7)
    findings = [f for f in A.lint_circuit(c) if f.code == "QT003"]
    assert len(findings) == 1
    lifted = lift_tape(tuple(c._tape))
    anon = sum(1 for s in lifted.slots if s.name is None)
    assert anon == 2 and "2 constant" in findings[0].message


def test_lint_no_qt003_when_params_are_lifted():
    from quest_tpu.engine import P

    c = Circuit(2)
    c.rotateZ(0, P("a"))
    c.rotateX(1, P("b"))
    assert "QT003" not in _codes(A.lint_circuit(c))


def test_lint_malformed_event_qt004():
    dup = events.GateEvent("matrix", targets=(1, 1), matrix=np.eye(4))
    olap = events.GateEvent("matrix", targets=(0,), controls=(0,),
                            matrix=np.eye(2))
    assert "QT004" in _codes(A.lint_events([dup], "synthetic"))
    assert "QT004" in _codes(A.lint_events([olap], "synthetic"))


def test_lint_barrier_resets_windows():
    # an unfusable passthrough between the pair must suppress QT001
    c = Circuit(2)
    c.hadamard(0)
    c.initZeroState()
    c.hadamard(0)
    assert "QT001" not in _codes(A.lint_circuit(c))


# ---------------------------------------------------------------------------
# QT205: malformed QUEST_PALLAS_RING diagnostic
# ---------------------------------------------------------------------------

@pytest.fixture
def ring_env(monkeypatch):
    monkeypatch.setattr(PG, "_RING_ENV_WARNED", set())
    return monkeypatch


def test_ring_env_non_integer_warns_once_and_defaults(ring_env):
    ring_env.setenv(PG._RING_ENV, "abc")
    telemetry.reset()
    with pytest.warns(RuntimeWarning, match="QT205.*ring depth 3"):
        assert PG.ring_depth_default() == PG._DEF_RING_DEPTH
    assert telemetry.counter_value(
        "analysis_findings_total", code="QT205", severity="warning") == 1.0
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # second call must stay silent
        assert PG.ring_depth_default() == PG._DEF_RING_DEPTH


def test_ring_env_below_minimum_clamps_to_two(ring_env):
    ring_env.setenv(PG._RING_ENV, "1")
    with pytest.warns(RuntimeWarning, match="ring depth 2"):
        assert PG.ring_depth_default() == 2


def test_ring_env_valid_value_is_silent(ring_env):
    ring_env.setenv(PG._RING_ENV, "4")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert PG.ring_depth_default() == 4


# ---------------------------------------------------------------------------
# QUEST_VERIFY gating and the diagnostics surface
# ---------------------------------------------------------------------------

def test_verify_enabled_parsing(monkeypatch):
    for off in ("", "0", "false", "off", " OFF "):
        monkeypatch.setenv("QUEST_VERIFY", off)
        assert not A.verify_enabled()
    for on in ("1", "true", "yes"):
        monkeypatch.setenv("QUEST_VERIFY", on)
        assert A.verify_enabled()


def test_quest_verify_passes_a_clean_fused_compile(monkeypatch):
    monkeypatch.setenv("QUEST_VERIFY", "1")
    telemetry.reset()
    fz = bench.build_circuit(20, 2).fused(max_qubits=5, pallas=True)
    assert fz.num_qubits == 20
    assert telemetry.counter_value("analysis_plans_verified_total") == 1.0


def test_render_and_summary_shapes():
    import json

    f = A.make_finding("QT101", "t outside tile", location="x")
    s = A.summarize([f])
    assert s == {"total": 1, "by_severity": {"error": 1, "warning": 0,
                                            "info": 0},
                 "by_code": {"QT101": 1}}
    doc = json.loads(A.render_json([f]))
    assert doc["findings"][0]["code"] == "QT101"
    assert "QT101" in A.render_text([f])
    assert "no findings" in A.render_text([])


def test_catalog_codes_are_banded():
    for code, (sev, _title, _hint) in A.CATALOG.items():
        assert code.startswith("QT") and sev in A.SEVERITIES
        band = int(code[2])
        # 0=tape lint, 1=plan verify, 2=DMA ring, 3=resilience/runtime,
        # 4=integrity sentinels / watchdog, 5=trajectory noise engine,
        # 6=concurrency verifier, 7=request tracing, 8=sampling,
        # 9=API-surface parity auditor
        assert band in (0, 1, 2, 3, 4, 5, 6, 7, 8, 9)
