"""Static-analysis subsystem: prove schedule invariants before execution.

The checkers share one diagnostics framework (:mod:`.diagnostics`;
codes ``QT0xx`` lint / ``QT1xx`` plan / ``QT2xx`` kernel / ``QT6xx``
concurrency / ``QT7xx`` tracing / ``QT9xx`` surface parity):

- :mod:`.plancheck` -- symbolic FusePlan frame replay and scheduler
  journal re-pricing (the model-vs-plan gate),
- :mod:`.ringcheck` -- abstract DMA-ring pipeline hazard/VMEM proofs,
- :mod:`.commcheck` -- abstract comm-pipeline (pipelined collective)
  transfer/compute hazard proofs,
- :mod:`.tapelint` -- GateEvent tape lints (cancellations, mergeable
  rotations, param-lift candidates, apply-time traps),
- :mod:`.concheck` -- the concurrency verifier for the serving fleet:
  QT601 lock-order deadlock-cycle analysis over the runtime
  held-while-acquiring graph, the deterministic
  :class:`~.concheck.InterleavingExplorer` (schedule-complete racing of
  submit/close, quarantine-failover, and hedged dispatch), and the
  QT603/QT604 atomicity + raw-lock AST lints
  (``tools/lint.py --concurrency``),
- :mod:`.tracecheck` -- request-trace integrity (QT702 open spans in
  finished traces, QT703 trace contexts leaked across pooled-thread
  reuse; ``tools/lint.py --trace FILE``),
- :mod:`.surface` -- the QT9xx API-surface parity auditor: the vendored
  reference L5 manifest audited (AST + inspect, zero-device) against
  the live exports into the committed ``PARITY.md`` / ``parity.json``
  fact table (``tools/lint.py --surface``, docs/parity.md), with
  :mod:`.conformance` carrying the generated dense-oracle replay specs
  the harness in tests/test_conformance.py walks.

Reachable three ways: the ``tools/lint.py`` CLI, the pytest suites, and
``QUEST_VERIFY=1`` runtime gating -- :func:`verify_plan` runs at
``Circuit.fused()`` compile time, flight-records findings
(``analysis_findings_total{code,severity}``) and raises
:class:`AnalysisError` on error-severity findings. See docs/analysis.md.
"""

from __future__ import annotations

import os

from .. import telemetry
from .diagnostics import (CATALOG, SEVERITIES, AnalysisError, Finding,
                          emit_findings, error_findings, make_finding,
                          render_json, render_text, summarize)
from .commcheck import (check_comm_pipeline, check_pipeline_events,
                        pipeline_events, sweep_comm_pipeline)
from .concheck import (SCENARIOS, CountingFuture, ExplorationResult,
                       InterleavingExplorer, await_future, check_atomicity,
                       check_lock_order, check_raw_locks, lint_concurrency,
                       run_scenario)
from .plancheck import (check_circuit_comm, check_plan, check_schedule,
                        check_tape)
from .ringcheck import check_events, check_ring, ring_events, sweep_reachable
from .tapelint import lint_circuit, lint_events, lint_tape
from .tracecheck import check_live_traces, check_trace_file, check_traces
from .surface import (FACT_COLUMNS, REFERENCE_MANIFEST, ManifestEntry,
                      SurfaceAudit, SurfaceRow, audit_surface,
                      check_manifest_files, check_surface, parity_json,
                      render_parity_md, write_manifest_files)
from .conformance import (ORACLE_SPECS, ROUTE_MATRIX_NAMES, ConformanceCase,
                          conformance_cases, route_cases)

__all__ = [
    "Finding", "AnalysisError", "CATALOG", "SEVERITIES",
    "make_finding", "emit_findings", "error_findings",
    "render_text", "render_json", "summarize",
    "check_plan", "check_tape", "check_schedule", "check_circuit_comm",
    "ring_events", "check_events", "check_ring", "sweep_reachable",
    "pipeline_events", "check_pipeline_events", "check_comm_pipeline",
    "sweep_comm_pipeline",
    "lint_events", "lint_tape", "lint_circuit",
    "check_lock_order", "InterleavingExplorer", "ExplorationResult",
    "await_future", "CountingFuture", "SCENARIOS", "run_scenario",
    "lint_concurrency", "check_raw_locks", "check_atomicity",
    "check_traces", "check_live_traces", "check_trace_file",
    "verify_enabled", "verify_plan", "check_smoke_spec",
    "ManifestEntry", "SurfaceRow", "SurfaceAudit", "REFERENCE_MANIFEST",
    "FACT_COLUMNS", "audit_surface", "check_surface",
    "check_manifest_files", "write_manifest_files", "render_parity_md",
    "parity_json",
    "ConformanceCase", "ORACLE_SPECS", "ROUTE_MATRIX_NAMES",
    "conformance_cases", "route_cases",
]

_VERIFY_ENV = "QUEST_VERIFY"


def verify_enabled() -> bool:
    """True when ``QUEST_VERIFY`` requests compile-time plan
    verification (any value but empty/0/false/off)."""
    return os.environ.get(_VERIFY_ENV, "").strip().lower() not in (
        "", "0", "false", "off")


def verify_plan(plan, *, nsv: int, dtype=None, shard_qubits=None,
                location: str = "plan",
                raise_on_error: bool = True, emit: bool = True):
    """The ``QUEST_VERIFY=1`` gate: run :func:`check_plan`, flight-record
    the findings, and raise :class:`AnalysisError` when any carry error
    severity. Returns the findings for callers that want them."""
    findings = check_plan(plan, nsv, dtype=dtype,
                          shard_qubits=shard_qubits, location=location)
    if emit:
        emit_findings(findings)
        telemetry.inc("analysis_plans_verified_total")
    if raise_on_error and error_findings(findings):
        raise AnalysisError(findings)
    return findings


def check_smoke_spec(spec: dict) -> list:
    """Run every applicable checker over one bench smoke-plan spec (a
    ``bench.smoke_plan_specs()`` row): tape lint always; the frame/ring
    plan check when the spec carries ``fused`` kwargs; the comm-schedule
    re-pricing when it names a ``mesh_shape`` (on the fused circuit when
    one was built, matching what the bench config itself plans).
    Returns the concatenated findings -- the one implementation behind
    ``tools/lint.py --bench-plans`` and the tier-1 analysis gate."""
    from jax.sharding import AbstractMesh
    from ..environment import AMP_AXIS

    name = spec["name"]
    circ = spec["build"]()
    findings = lint_tape(list(circ._tape), circ.num_qubits,
                         is_density=circ.is_density_matrix,
                         location=f"{name}.tape")
    fz = None
    if spec.get("fused"):
        kw = dict(spec["fused"])
        fz = circ.fused(**kw)
        # frame grid blocks may reach sharded qubits (collective
        # transposes), so the plan is verified over the FULL space; the
        # DMA-ring grid, though, is what one shard's kernel sweeps
        nsv = (2 if circ.is_density_matrix else 1) * circ.num_qubits
        d = int(kw.get("shard_devices") or 1)
        shard_q = nsv - (d.bit_length() - 1) if d > 1 else None
        findings += check_tape(fz._tape, nsv, dtype=kw.get("dtype"),
                               shard_qubits=shard_q,
                               location=f"{name}.plan")
    if spec.get("mesh_shape"):
        mesh = AbstractMesh(tuple(spec["mesh_shape"]), (AMP_AXIS,))
        target = fz if fz is not None else circ
        sched_findings, _stats, _journal = check_circuit_comm(
            target, mesh, dtype=spec.get("dtype"),
            comm_pipeline=spec.get("comm_pipeline"),
            num_slices=int(spec.get("num_slices", 1)),
            hierarchical=bool(spec.get("hierarchical", False)),
            comm_pipeline_dcn=spec.get("comm_pipeline_dcn"),
            location=f"{name}.schedule")
        findings += sched_findings
    return findings
