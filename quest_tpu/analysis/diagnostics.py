"""Diagnostics framework for the static-analysis subsystem.

Every checker (:mod:`.plancheck`, :mod:`.ringcheck`, :mod:`.tapelint`)
reports :class:`Finding` records drawn from one code catalog:

- ``QT0xx`` -- tape lint (circuit-level advice and apply-time traps),
- ``QT1xx`` -- plan verification (FusePlan frames, scheduler journals,
  chunk-unit pricing),
- ``QT2xx`` -- kernel/DMA-ring checks (slot hazards, VMEM budget, ring
  configuration),
- ``QT3xx`` -- resilience/runtime hardening (multihost bring-up timeout,
  fault-plan and env-knob hygiene, segmented execution and checkpoint
  generations -- docs/resilience.md),
- ``QT4xx`` -- online integrity sentinels and the self-healing loop
  (norm/trace drift, per-shard checksum divergence, watchdog deadlines
  -- :mod:`quest_tpu.resilience.sentinel`, docs/resilience.md),
- ``QT6xx`` -- concurrency verification of the serving fleet (lock-order
  deadlock cycles, locks held across blocking boundaries / future
  resolution, atomicity and raw-lock lints --
  :mod:`quest_tpu.analysis.concheck` over
  :mod:`quest_tpu.resilience.sync`, docs/analysis.md),
- ``QT7xx`` -- request-tracing hygiene (malformed ``QUEST_TRACE``, spans
  left open at export, trace contexts leaked across pooled-thread reuse
  -- :mod:`quest_tpu.analysis.tracecheck` over
  :mod:`quest_tpu.telemetry`, docs/observability.md),
- ``QT8xx`` -- sampling (``QUEST_SHOTS`` hygiene --
  :mod:`quest_tpu.sampling`, docs/sampling.md).

Each finding carries a severity (``error`` | ``warning`` | ``info``), a
human-readable location and a one-line fix hint. :func:`emit_findings`
flight-records findings on the telemetry registry
(``analysis_findings_total{code,severity}``) so verified runs leave the
same parseable trail as every other engine subsystem
(docs/observability.md).

This module deliberately imports nothing heavier than
:mod:`quest_tpu.telemetry`, so low-level modules (ops.pallas_gates) can
report diagnostics without import cycles.
"""

from __future__ import annotations

import json
import os
from dataclasses import asdict, dataclass
from typing import Iterable, Optional

from .. import telemetry

__all__ = [
    "Finding", "AnalysisError", "CATALOG", "SEVERITIES",
    "make_finding", "emit_findings", "error_findings",
    "render_text", "render_json", "summarize", "parse_env_int",
]

#: severity levels, most severe first
SEVERITIES: tuple[str, ...] = ("error", "warning", "info")

#: code -> (default severity, title, default fix hint)
CATALOG: dict[str, tuple[str, str, str]] = {
    # -- QT0xx: tape lint ---------------------------------------------------
    "QT001": ("warning", "adjacent self-inverse gate pair cancels",
              "delete both gates; they compose to the identity"),
    "QT002": ("info", "adjacent same-axis rotations are mergeable",
              "merge into one rotation of the summed angle"),
    "QT003": ("info", "constant angles at liftable positions defeat the "
                      "structure-fingerprint cache",
              "record the angles as engine.P(...) Params so "
              "structure-equal circuits share one compiled executable"),
    "QT004": ("error", "control/target overlap in a captured gate event",
              "use disjoint control and target qubits; this only fails "
              "at apply time"),
    "QT005": ("error", "measurement site inside a deferred-relocation "
                       "window",
              "a mid-circuit measurement/collapse reduces the target's "
              "marginal in RAW amplitude order, but the frame is not at "
              "identity there: move the site to an identity boundary or "
              "let the scheduler reconcile before it"),
    "QT006": ("error", "non-differentiable site in a tape submitted for "
                       "differentiation",
              "the adjoint backward sweep cannot invert a mid-circuit "
              "measurement or trajectory-Kraus site: submit the unitary "
              "tape as a grad_request and compose the measurement / "
              "noise statistics as a separate sample_request "
              "(quest_tpu.sampling.request) on the forward state"),
    # -- QT1xx: plan verification -------------------------------------------
    "QT101": ("error", "dense kernel-op target outside the legal "
                       "physical tile",
              "re-plan: dense targets must sit below tile_bits in the "
              "run's frame"),
    "QT102": ("error", "frame permutation does not compose back to "
                       "identity",
              "the folded load/store swaps and FrameSwap items must "
              "restore the identity frame before any non-Pallas item"),
    "QT103": ("error", "chunk-unit totals diverge from the plan_circuit "
                       "pricing model",
              "re-derive the per-kind prices (_swap_price, "
              "permute_collective_stats, plane_unit_scale) against the "
              "scheduler stats"),
    "QT104": ("error", "relocation schedule does not restore the tracked "
                       "layout at reconcile",
              "every deferred relocation/virtual swap must be matched by "
              "the reconcile permute or swap chain"),
    "QT105": ("error", "kernel-op control/target overlap inside a "
                       "PallasRun",
              "the lowered op reuses a qubit in both roles; fix the "
              "lowering or the source gate"),
    "QT106": ("error", "folded frame-swap geometry exceeds the run "
                       "geometry",
              "k must be <= tile_bits - LANE_BITS, hi >= tile_bits and "
              "hi + k <= n for the kernel's bit-block swap"),
    "QT107": ("error", "segment-program stamp diverges from the frame-"
                       "identity segmentation",
              "item.seg must equal the count of identity returns before "
              "the item, in FusePlan order (quest_tpu.segments."
              "stamp_plan); re-stamp via Circuit.fused or drop the "
              "stamps (None skips the check per item)"),
    "QT108": ("warning", "DCN shard bit moved more than once inside one "
                         "reconciliation",
              "a hierarchical reconcile should touch each DCN-crossing "
              "bit at most once (path-decompose swap chains with the DCN "
              "position as an endpoint, or fold the crossings into one "
              "grouped collective); plan with hierarchical=True"),
    # -- QT2xx: kernel / DMA ring -------------------------------------------
    "QT201": ("error", "DMA ring load-slot hazard",
              "a ring slot's load must start, be waited, and be consumed "
              "by exactly one compute before the slot is refilled"),
    "QT202": ("error", "DMA ring store-slot hazard or unpaired copy/wait",
              "a slot's previous store must drain (store-wait at "
              "c - ring) before its output buffer is rewritten, and "
              "every started copy must be waited"),
    "QT203": ("error", "ring VMEM budget exceeded at minimum depth",
              "even the 2-slot ring does not fit _RING_VMEM_BUDGET; "
              "shrink the tile (sublanes) or raise the budget"),
    "QT204": ("info", "ring depth clamped or derated from the requested "
                      "operating point",
              "the effective ring is capped by the chunk count and the "
              "VMEM budget; request a smaller depth to silence this"),
    "QT205": ("warning", "QUEST_PALLAS_RING is malformed or out of range",
              "set QUEST_PALLAS_RING to an integer >= 2 (the 2-slot "
              "minimum); the malformed value was replaced"),
    "QT206": ("warning", "QUEST_COMM_PIPELINE is malformed or out of "
                         "range",
              "set QUEST_COMM_PIPELINE to an integer >= 1 (1 = the "
              "monolithic launch); the malformed value was replaced"),
    "QT207": ("error", "comm pipeline slice overlap hazard",
              "each sub-chunk transfer must be issued exactly once, land "
              "before the compute that consumes it, and feed exactly one "
              "compute"),
    "QT208": ("error", "comm pipeline epilogue not drained",
              "every issued transfer must land and be consumed and every "
              "output slice emitted in order before the launch returns"),
    "QT209": ("info", "comm pipeline depth clamped to the slice geometry",
              "the effective depth is the largest power of two not above "
              "the requested depth and the chunk's slice limit; request "
              "a smaller depth to silence this"),
    "QT210": ("warning", "QUEST_COMM_PIPELINE_DCN is malformed or out of "
                         "range",
              "set QUEST_COMM_PIPELINE_DCN to an integer >= 1 (unset "
              "inherits the base QUEST_COMM_PIPELINE depth); the "
              "malformed value was replaced"),
    # -- QT3xx: resilience (fault injection, retry, segmented runs) ---------
    "QT301": ("error", "multi-host initialization timed out or failed "
                       "against the coordinator",
              "check the coordinator address and network reachability; "
              "the message names the initialization_timeout that was "
              "applied (QUEST_INIT_TIMEOUT_S / init(...) argument)"),
    "QT302": ("warning", "malformed or unknown QUEST_FAULTS entry ignored",
              "use site:kind:nth (nth a positive integer, optionally "
              "'N+') with a site/kind from "
              "quest_tpu.resilience.faultinject.SITES"),
    "QT303": ("warning", "malformed resilience environment value replaced "
                         "by its default",
              "QUEST_RETRY_MAX / QUEST_RETRY_BASE_MS / "
              "QUEST_RETRY_DEADLINE_MS / QUEST_ENGINE_QUEUE_MAX / "
              "QUEST_INIT_TIMEOUT_S must be numeric"),
    "QT304": ("error", "segmented execution misconfiguration",
              "every_n_items and keep must be >= 1, and the tape must "
              "return to the identity frame at its end (a Circuit.fused "
              "plan always does)"),
    "QT305": ("warning", "checkpoint generation failed verification "
                         "during resume",
              "the generation was skipped and resume fell back to an "
              "older verified snapshot; investigate the named shard for "
              "torn writes or corruption"),
    "QT307": ("warning", "malformed replica-pool/admission environment "
                         "value replaced by its default",
              "QUEST_POOL_REPLICAS must be an integer >= 1; "
              "QUEST_HEDGE_MS and QUEST_TENANT_QPS must be integers >= 0 "
              "(0 disables hedging / the quota); the malformed value was "
              "replaced"),
    "QT310": ("warning", "QUEST_ASYNC_DEPTH is malformed or out of range",
              "set QUEST_ASYNC_DEPTH to 0 (synchronous dispatch: the "
              "batcher drains each batch before issuing the next) or a "
              "positive integer completion-ring depth (default 2: up to "
              "that many batches in flight on the device while the host "
              "coalesces the next); the malformed value was replaced"),
    # -- QT4xx: integrity sentinels / self-healing (docs/resilience.md) -----
    "QT401": ("error", "total-probability drift beyond the precision "
                       "tolerance band",
              "the register's norm (or density trace) left the f32/df "
              "band: silent data corruption or a non-unitary bug; the "
              "segmented runner rolls back to the last CRC-verified "
              "generation and replays"),
    "QT402": ("error", "per-shard checksum divergence",
              "one shard's partial-norm checksum disagrees with the "
              "psum-folded total the other shards agree on; the finding "
              "names the divergent shard -- suspect that device's memory "
              "or interconnect"),
    "QT403": ("warning", "malformed or unknown QUEST_SENTINEL entry "
                         "ignored",
              "use kind[:cadence] with kind in "
              "quest_tpu.resilience.sentinel.KINDS and cadence a "
              "positive integer, 'every_N', or 'segment'"),
    "QT404": ("error", "density-register trace/hermiticity breach",
              "Re tr(rho) drifted from 1 beyond the band or rho is no "
              "longer Hermitian within it; the state is not a density "
              "matrix any more -- roll back or fail closed"),
    "QT405": ("error", "watchdog deadline exceeded (hung collective or "
                       "dispatch)",
              "the guarded call did not return within QUEST_WATCHDOG_MS; "
              "a typed QuESTHangError was raised instead of blocking "
              "forever -- check the mesh for a wedged device"),
    # -- QT5xx: trajectory noise engine (docs/trajectories.md) --------------
    "QT501": ("warning", "malformed QUEST_TRAJECTORIES value ignored",
              "set QUEST_TRAJECTORIES to a positive integer ensemble "
              "size; the default trajectory count was used instead "
              "(statistical error scales as 1/sqrt(T))"),
    "QT502": ("error", "non-CPTP Kraus set at a trajectory channel site",
              "sum_k K_k^dagger K_k deviates from identity: the "
              "trajectory sampler's selection probabilities would be "
              "biased and the ensemble mean would NOT converge to the "
              "channel; renormalise the operator set (non-TP maps have "
              "no unraveling -- keep them on the density route via "
              "mixNonTP*)"),
    # -- QT6xx: concurrency verifier (analysis/concheck.py) -----------------
    "QT600": ("error", "concurrency lint could not parse module",
              "the file fed to tools/lint.py --concurrency has a syntax "
              "error; fix the module (or exclude it from the scanned "
              "paths) so the QT603/QT604 AST passes can run"),
    "QT601": ("error", "lock-order cycle: potential deadlock",
              "two threads acquire the named locks in opposite orders; "
              "break the cycle by imposing one total order (the pool "
              "lock orders BEFORE any engine lock) or by dropping one "
              "lock before taking the other -- the finding carries the "
              "first-occurrence acquisition stack of each edge"),
    "QT602": ("error", "lock held across a blocking boundary",
              "release every instrumented lock before device dispatch, "
              "Future resolution/result(), thread join, or a condition "
              "wait on a different lock: the blocked-on work may need "
              "the held lock (the round-13 resolve-inside-close "
              "deadlock class)"),
    "QT603": ("warning", "field of a lock-owning class mutated both with "
                         "and without its lock held",
              "guard every mutation of the named attribute with the "
              "class's lock (or rename it to mark single-threaded "
              "ownership); mixed locked/unlocked writes are how atomic "
              "invariants silently rot"),
    "QT604": ("error", "raw threading lock constructed in instrumented "
                       "serving code",
              "construct quest_tpu.resilience.sync.Lock/RLock/Condition "
              "(named) instead of threading.* so the lock participates "
              "in the order graph, metrics, and the interleaving "
              "explorer; append '# concheck: allow-raw-lock' with a "
              "reason for deliberate exceptions"),
    "QT605": ("warning", "QUEST_CONCHECK is malformed or out of range",
              "set QUEST_CONCHECK to 0 (off, the default) or an integer "
              ">= 1 to enable the instrumented sync layer; the "
              "malformed value was replaced"),
    # -- QT7xx: request-tracing hygiene (analysis/tracecheck.py) ------------
    "QT701": ("warning", "malformed QUEST_TRACE value; tracing stays off",
              "set QUEST_TRACE to off, errors, all, or a head-sampling "
              "rate in (0, 1) (e.g. 0.01); the malformed value warns "
              "once per process and tracing remains disabled"),
    "QT702": ("warning", "trace span opened but never closed at export",
              "every TraceContext.child() must be end()-ed on all paths "
              "(success, error, cancellation) before the layer that "
              "minted the trace calls finish_trace; an open span at "
              "export means a hop's error path dropped its handle"),
    "QT703": ("error", "trace context leaked across pooled-thread reuse",
              "a worker thread still holds finished trace context(s): "
              "pair every set_current_trace with clear_current_trace "
              "after future resolution, or the next request dispatched "
              "on that thread inherits a dead trace"),
    "QT704": ("warning", "request phase vector does not tile its "
                         "end-to-end latency within 10%",
              "the union of the trace's canonical phase windows (overlap "
              "between dispatch and device counted once -- async "
              "dispatch legitimately overlaps them) covers less than 90% "
              "or more than 110% of the request's wall-clock: an "
              "instrumentation site is missing a phase attribution or "
              "double-counting one"),
    # -- QT8xx: sampling (quest_tpu/sampling) -------------------------------
    "QT801": ("warning", "malformed QUEST_SHOTS value ignored",
              "set QUEST_SHOTS to an integer >= 1; the malformed value "
              "warns once per process and the default shot count is "
              "used"),
    # -- QT9xx: API-surface parity audit (analysis/surface.py,
    #    docs/parity.md) ----------------------------------------------------
    "QT901": ("error", "reference L5 function missing from the public "
                       "surface",
              "a REFERENCE_MANIFEST row has no callable quest_tpu "
              "export: implement it (or, if the reference really dropped "
              "it, remove the vendored manifest row in the same PR)"),
    "QT902": ("error", "public signature drifted from the vendored "
                       "manifest",
              "parameter names must match the manifest row verbatim -- "
              "callers port QuEST programs against these names; update "
              "the function or (for a deliberate API change) the "
              "manifest row, never silently"),
    "QT903": ("error", "public L5 function skips the validation layer",
              "the function takes user input but no direct or delegated "
              "validate_* call was found: add the guard (quest_tpu/"
              "validation.py) and a VALIDATION_CASES regression entry, "
              "or mark the manifest row needs_validation=False when "
              "there is genuinely nothing to check"),
    "QT904": ("warning", "L5 function has no tier-1 test call site",
              "no literal call under tests/ exercises this function; "
              "add an ORACLE_SPECS conformance row or a direct test"),
    "QT905": ("error", "committed parity manifest is stale",
              "PARITY.md / parity.json no longer match the audited "
              "tree; regenerate with `python tools/lint.py --surface "
              "--write` and commit the result"),
    "QT906": ("warning", "L5 export is undocumented",
              "give the function a docstring and regenerate the "
              "docs/api pages (python tools/gen_docs.py) so the "
              "documented column flips green"),
}


@dataclass(frozen=True)
class Finding:
    """One diagnostic: a catalog code, its severity, where it was found,
    what is wrong, and a one-line fix hint."""

    code: str
    severity: str
    message: str
    location: str
    hint: str

    def __str__(self) -> str:
        return (f"{self.code} [{self.severity}] {self.location}: "
                f"{self.message} ({self.hint})")


class AnalysisError(Exception):
    """Raised by the ``QUEST_VERIFY=1`` gate on error-severity findings.

    Carries the full finding list on ``.findings`` so callers (and tests)
    can inspect exactly which invariants failed."""

    def __init__(self, findings: list[Finding]):
        self.findings = list(findings)
        errs = [f for f in findings if f.severity == "error"]
        super().__init__(
            f"{len(errs)} error-severity analysis finding(s):\n"
            + "\n".join(f"  {f}" for f in errs))


def make_finding(code: str, message: str, location: str,
                 hint: Optional[str] = None,
                 severity: Optional[str] = None) -> Finding:
    """Build a :class:`Finding`, defaulting severity and hint from the
    catalog entry for ``code`` (which must exist)."""
    default_sev, _title, default_hint = CATALOG[code]
    sev = severity if severity is not None else default_sev
    if sev not in SEVERITIES:
        raise ValueError(f"unknown severity {sev!r}; pick from {SEVERITIES}")
    return Finding(code=code, severity=sev, message=message,
                   location=location,
                   hint=hint if hint is not None else default_hint)


def emit_findings(findings: Iterable[Finding]) -> None:
    """Flight-record findings on the telemetry registry:
    ``analysis_findings_total{code,severity}`` (one increment each)."""
    for f in findings:
        telemetry.inc("analysis_findings_total", code=f.code,
                      severity=f.severity)


def parse_env_int(env: str, default: int, *, minimum: int, code: str,
                  warned: set, noun: str = "value",
                  below: Optional[str] = None) -> int:
    """The ONE env-int-parse-with-diagnostic: read integer env knob
    ``env``, falling back to ``default`` on a malformed value and clamping
    to ``minimum``, and flight-record a catalog ``code`` finding
    (telemetry + RuntimeWarning) naming the value actually used -- once
    per distinct raw value, tracked in the caller-owned ``warned`` set
    (so each knob warns per process, not per launch). The silent coercion
    stays -- the caller must still launch -- but it is no longer silent.
    Shared by ``QUEST_PALLAS_RING`` (QT205), ``QUEST_COMM_PIPELINE``
    (QT206), ``QUEST_COMM_PIPELINE_DCN`` (QT210) and the replica-pool
    knobs ``QUEST_POOL_REPLICAS`` / ``QUEST_HEDGE_MS`` /
    ``QUEST_TENANT_QPS`` (QT307) instead of per-knob hand-rolled
    parsers."""
    raw = os.environ.get(env, "").strip()
    if not raw:
        return default
    try:
        v = int(raw)
    except ValueError:
        _env_int_diagnostic(env, code, raw, default, "is not an integer",
                            noun, warned)
        return default
    if v < minimum:
        _env_int_diagnostic(
            env, code, raw, minimum,
            below if below is not None else f"is below the minimum "
                                            f"{minimum}", noun, warned)
        return minimum
    return v


def _env_int_diagnostic(env: str, code: str, raw: str, used: int,
                        why: str, noun: str, warned: set) -> None:
    if raw in warned:
        return
    warned.add(raw)
    import warnings

    f = make_finding(code, f"{env}={raw!r} {why}; running with {noun} "
                           f"{used}", f"env:{env}")
    emit_findings([f])
    warnings.warn(str(f), RuntimeWarning, stacklevel=4)


def error_findings(findings: Iterable[Finding]) -> list[Finding]:
    """The error-severity subset, in order."""
    return [f for f in findings if f.severity == "error"]


def summarize(findings: Iterable[Finding]) -> dict:
    """Aggregate counts: total, per severity, and per code -- the shape
    the dryrun's ``# analysis:`` line and the CLI summary print."""
    fs = list(findings)
    by_sev = {s: 0 for s in SEVERITIES}
    by_code: dict[str, int] = {}
    for f in fs:
        by_sev[f.severity] = by_sev.get(f.severity, 0) + 1
        by_code[f.code] = by_code.get(f.code, 0) + 1
    return {"total": len(fs), "by_severity": by_sev,
            "by_code": dict(sorted(by_code.items()))}


def render_text(findings: Iterable[Finding]) -> str:
    """Human-readable report, most severe first, stable within severity."""
    fs = sorted(findings, key=lambda f: (SEVERITIES.index(f.severity),
                                         f.code, f.location))
    if not fs:
        return "no findings"
    lines = [str(f) for f in fs]
    s = summarize(fs)
    lines.append(f"-- {s['total']} finding(s): "
                 + ", ".join(f"{n} {sev}" for sev, n in
                             s["by_severity"].items() if n))
    return "\n".join(lines)


def render_json(findings: Iterable[Finding]) -> str:
    """Machine-readable report: ``{"findings": [...], "summary": {...}}``
    -- the shape the CI lint gate parses."""
    fs = list(findings)
    return json.dumps({"findings": [asdict(f) for f in fs],
                       "summary": summarize(fs)}, sort_keys=True)
