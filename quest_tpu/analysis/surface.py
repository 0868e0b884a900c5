"""Static API-surface parity auditor (QT9xx band, docs/parity.md).

The reference gives every public L5 function a Catch2 case against a
brute-force oracle (tests/ with the vendored Catch2 header); the analogue
here is a *zero-device static pass* over our own surface. A vendored
:data:`REFERENCE_MANIFEST` (name, parameter names, register kind,
category -- one row per QuEST.h L5 function, frozen from
``native/include/QuEST.h``) is audited against the live package with
``ast`` + ``inspect`` only -- nothing is executed on a device -- and every
function is classified into per-fact columns:

- ``exists``    -- exported from ``quest_tpu`` and callable,
- ``signature`` -- live parameter names match the vendored manifest row,
- ``validates`` -- reaches ``validation.py`` (transitive fixpoint over
  module-local helpers; rows with ``needs_validation=False`` take no
  user input worth guarding),
- ``documented``-- has a docstring AND appears on a ``docs/api`` page,
- ``tested``    -- has a literal call site somewhere under ``tests/``
  (AST scan, so meta-tests iterating names via ``getattr`` don't count),
- ``sharded``   -- called from a test module running the default 8-device
  mesh env (``createQuESTEnv()`` with no argument),
- ``df``        -- called from a test module exercising the f32/double-float
  route (``precision_code=1`` registers or ``QUEST_PALLAS_DF``),
- ``grad``      -- a parameter position is adjoint-liftable
  (:data:`quest_tpu.params._LIFTABLE`, the QT006 audit's registry),
- ``tape``      -- composable onto a :class:`~quest_tpu.circuits.Circuit`
  tape (:func:`quest_tpu.circuits._resolve` accepts it),
- ``oracle``    -- the generated conformance harness
  (:mod:`.conformance`) carries a dense-oracle replay spec for it.

:func:`audit_surface` returns the classified rows plus QT901-QT906
findings; :func:`render_parity_md` / :func:`parity_json` serialize the
committed ``PARITY.md`` / ``parity.json`` manifests and
:func:`check_manifest_files` raises QT905 when they are stale vs. the
tree (the CI gate: ``tools/lint.py --surface``; regenerate with
``--surface --write``). Every scan input is injectable so the auditor
itself is testable with seeded manifest mutations (tests/test_surface.py).
"""

from __future__ import annotations

import ast
import importlib
import inspect
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Iterable, Mapping, Optional, Sequence

from .diagnostics import Finding, emit_findings, make_finding

__all__ = [
    "ManifestEntry", "SurfaceRow", "SurfaceAudit", "TestScan",
    "REFERENCE_MANIFEST", "FACT_COLUMNS", "PARITY_MD", "PARITY_JSON",
    "audit_surface", "check_surface", "check_manifest_files",
    "write_manifest_files", "render_parity_md", "parity_json",
    "scan_validated", "scan_tests", "scan_documented",
]

#: repo-relative names of the committed manifest artifacts
PARITY_MD = "PARITY.md"
PARITY_JSON = "parity.json"

#: fact columns, in manifest order
FACT_COLUMNS: tuple[str, ...] = (
    "exists", "signature", "validates", "documented", "tested",
    "sharded", "df", "grad", "tape", "oracle")

#: register-kind vocabulary for :attr:`ManifestEntry.kind`
KINDS: tuple[str, ...] = ("statevec", "density", "any", "none")


@dataclass(frozen=True)
class ManifestEntry:
    """One vendored reference-surface row: the contract a live export is
    audited against. ``params`` are the exact live parameter names
    (QT902 compares them verbatim); ``kind`` is the register kind the
    function consumes; ``category`` the implementing module;
    ``needs_validation=False`` marks functions whose inputs carry nothing
    to guard (destructors, reporters, fixed-state inits, env syncs)."""

    name: str
    params: tuple[str, ...]
    kind: str
    category: str
    needs_validation: bool = True


def _e(name: str, params: tuple[str, ...], kind: str, category: str,
       needs_validation: bool = True) -> ManifestEntry:
    return ManifestEntry(name, params, kind, category, needs_validation)


#: the vendored reference L5 surface (one row per QuEST.h function)
REFERENCE_MANIFEST: tuple[ManifestEntry, ...] = (
    _e('applyDiagonalOp', ('qureg', 'op'), 'any', 'operators'),
    _e('applyFullQFT', ('qureg',), 'any', 'operators'),
    _e('applyGateMatrixN', ('qureg', 'targets', 'u'), 'any', 'operators'),
    _e('applyGateSubDiagonalOp', ('qureg', 'targets', 'op'), 'any', 'operators'),
    _e('applyMatrix2', ('qureg', 'target', 'u'), 'any', 'operators'),
    _e('applyMatrix4', ('qureg', 't1', 't2', 'u'), 'any', 'operators'),
    _e('applyMatrixN', ('qureg', 'targets', 'u'), 'any', 'operators'),
    _e('applyMultiControlledGateMatrixN', ('qureg', 'controls', 'targets', 'u'), 'any', 'operators'),
    _e('applyMultiControlledMatrixN', ('qureg', 'controls', 'targets', 'u'), 'any', 'operators'),
    _e('applyMultiVarPhaseFunc', ('qureg', 'qubits_flat', 'num_qubits_per_reg', 'encoding', 'coeffs', 'exponents', 'num_terms_per_reg'), 'any', 'operators'),
    _e('applyMultiVarPhaseFuncOverrides', ('qureg', 'qubits_flat', 'num_qubits_per_reg', 'encoding', 'coeffs', 'exponents', 'num_terms_per_reg', 'override_inds', 'override_phases'), 'any', 'operators'),
    _e('applyNamedPhaseFunc', ('qureg', 'qubits_flat', 'num_qubits_per_reg', 'encoding', 'func_name'), 'any', 'operators'),
    _e('applyNamedPhaseFuncOverrides', ('qureg', 'qubits_flat', 'num_qubits_per_reg', 'encoding', 'func_name', 'override_inds', 'override_phases'), 'any', 'operators'),
    _e('applyParamNamedPhaseFunc', ('qureg', 'qubits_flat', 'num_qubits_per_reg', 'encoding', 'func_name', 'params'), 'any', 'operators'),
    _e('applyParamNamedPhaseFuncOverrides', ('qureg', 'qubits_flat', 'num_qubits_per_reg', 'encoding', 'func_name', 'params', 'override_inds', 'override_phases'), 'any', 'operators'),
    _e('applyPauliHamil', ('in_qureg', 'hamil', 'out_qureg'), 'any', 'operators'),
    _e('applyPauliSum', ('in_qureg', 'all_pauli_codes', 'term_coeffs', 'out_qureg'), 'any', 'operators'),
    _e('applyPhaseFunc', ('qureg', 'qubits', 'encoding', 'coeffs', 'exponents'), 'any', 'operators'),
    _e('applyPhaseFuncOverrides', ('qureg', 'qubits', 'encoding', 'coeffs', 'exponents', 'override_inds', 'override_phases'), 'any', 'operators'),
    _e('applyProjector', ('qureg', 'target', 'outcome'), 'any', 'operators'),
    _e('applyQFT', ('qureg', 'qubits'), 'any', 'operators'),
    _e('applySubDiagonalOp', ('qureg', 'targets', 'op'), 'any', 'operators'),
    _e('applyTrotterCircuit', ('qureg', 'hamil', 'time', 'order', 'reps'), 'any', 'operators'),
    _e('bindArraysToStackComplexMatrixN', ('num_qubits', 'real', 'imag', 're_storage', 'im_storage'), 'none', 'datatypes'),
    _e('calcDensityInnerProduct', ('rho1', 'rho2'), 'density', 'calculations'),
    _e('calcExpecDiagonalOp', ('qureg', 'op'), 'any', 'operators'),
    _e('calcExpecPauliHamil', ('qureg', 'hamil', 'workspace'), 'any', 'calculations'),
    _e('calcExpecPauliProd', ('qureg', 'targets', 'paulis', 'workspace'), 'any', 'calculations'),
    _e('calcExpecPauliSum', ('qureg', 'all_pauli_codes', 'term_coeffs', 'workspace'), 'any', 'calculations'),
    _e('calcFidelity', ('qureg', 'pure_state'), 'any', 'calculations'),
    _e('calcHilbertSchmidtDistance', ('a', 'b'), 'density', 'calculations'),
    _e('calcInnerProduct', ('bra', 'ket'), 'statevec', 'calculations'),
    _e('calcProbOfAllOutcomes', ('qureg', 'targets'), 'any', 'calculations'),
    _e('calcProbOfOutcome', ('qureg', 'target', 'outcome'), 'any', 'calculations'),
    _e('calcPurity', ('qureg',), 'density', 'calculations'),
    _e('calcTotalProb', ('qureg',), 'any', 'calculations', needs_validation=False),
    _e('clearRecordedQASM', ('qureg',), 'any', 'reporting', needs_validation=False),
    _e('cloneQureg', ('target', 'source'), 'any', 'state_init'),
    _e('collapseToOutcome', ('qureg', 'target', 'outcome'), 'any', 'gates'),
    _e('compactUnitary', ('qureg', 'target', 'alpha', 'beta'), 'any', 'gates'),
    _e('controlledCompactUnitary', ('qureg', 'control', 'target', 'alpha', 'beta'), 'any', 'gates'),
    _e('controlledMultiQubitUnitary', ('qureg', 'control', 'targets', 'u'), 'any', 'gates'),
    _e('controlledNot', ('qureg', 'control', 'target'), 'any', 'gates'),
    _e('controlledPauliY', ('qureg', 'control', 'target'), 'any', 'gates'),
    _e('controlledPhaseFlip', ('qureg', 'q1', 'q2'), 'any', 'gates'),
    _e('controlledPhaseShift', ('qureg', 'q1', 'q2', 'angle'), 'any', 'gates'),
    _e('controlledRotateAroundAxis', ('qureg', 'control', 'target', 'angle', 'axis'), 'any', 'gates'),
    _e('controlledRotateX', ('qureg', 'control', 'target', 'angle'), 'any', 'gates'),
    _e('controlledRotateY', ('qureg', 'control', 'target', 'angle'), 'any', 'gates'),
    _e('controlledRotateZ', ('qureg', 'control', 'target', 'angle'), 'any', 'gates'),
    _e('controlledTwoQubitUnitary', ('qureg', 'control', 't1', 't2', 'u'), 'any', 'gates'),
    _e('controlledUnitary', ('qureg', 'control', 'target', 'u'), 'any', 'gates'),
    _e('copyStateFromGPU', ('qureg',), 'any', 'registers'),
    _e('copyStateToGPU', ('qureg',), 'any', 'registers'),
    _e('copySubstateFromGPU', ('qureg', 'start_ind', 'num_amps'), 'any', 'registers'),
    _e('copySubstateToGPU', ('qureg', 'start_ind', 'num_amps'), 'any', 'registers'),
    _e('createCloneQureg', ('qureg', 'env'), 'any', 'registers', needs_validation=False),
    _e('createComplexMatrixN', ('num_qubits',), 'none', 'datatypes'),
    _e('createDensityQureg', ('num_qubits', 'env', 'precision_code'), 'none', 'registers'),
    _e('createDiagonalOp', ('num_qubits', 'env'), 'none', 'operators'),
    _e('createDiagonalOpFromPauliHamilFile', ('path', 'env'), 'none', 'operators'),
    _e('createPauliHamil', ('num_qubits', 'num_sum_terms'), 'none', 'datatypes'),
    _e('createPauliHamilFromFile', ('path',), 'none', 'datatypes'),
    _e('createQuESTEnv', ('devices', 'num_slices'), 'none', 'environment'),
    _e('createQureg', ('num_qubits', 'env', 'precision_code'), 'none', 'registers'),
    _e('createSubDiagonalOp', ('num_qubits',), 'none', 'datatypes'),
    _e('destroyComplexMatrixN', ('matrix',), 'none', 'datatypes', needs_validation=False),
    _e('destroyDiagonalOp', ('op', 'env'), 'none', 'operators', needs_validation=False),
    _e('destroyPauliHamil', ('hamil',), 'none', 'datatypes', needs_validation=False),
    _e('destroyQuESTEnv', ('env',), 'none', 'environment', needs_validation=False),
    _e('destroyQureg', ('qureg', 'env'), 'any', 'registers', needs_validation=False),
    _e('destroySubDiagonalOp', ('op',), 'none', 'datatypes', needs_validation=False),
    _e('diagonalUnitary', ('qureg', 'targets', 'op'), 'any', 'gates'),
    _e('getAmp', ('qureg', 'index'), 'statevec', 'calculations'),
    _e('getDensityAmp', ('qureg', 'row', 'col'), 'density', 'calculations'),
    _e('getEnvironmentString', ('env',), 'none', 'environment', needs_validation=False),
    _e('getImagAmp', ('qureg', 'index'), 'statevec', 'calculations'),
    _e('getNumAmps', ('qureg',), 'any', 'state_init'),
    _e('getNumQubits', ('qureg',), 'any', 'state_init', needs_validation=False),
    _e('getProbAmp', ('qureg', 'index'), 'statevec', 'calculations'),
    _e('getQuESTSeeds', ('env',), 'none', 'environment', needs_validation=False),
    _e('getRealAmp', ('qureg', 'index'), 'statevec', 'calculations'),
    _e('hadamard', ('qureg', 'target'), 'any', 'gates'),
    _e('initBlankState', ('qureg',), 'any', 'state_init', needs_validation=False),
    _e('initClassicalState', ('qureg', 'state_index'), 'any', 'state_init'),
    _e('initComplexMatrixN', ('matrix', 'real', 'imag'), 'none', 'datatypes'),
    _e('initDebugState', ('qureg',), 'any', 'state_init', needs_validation=False),
    _e('initDiagonalOp', ('op', 'reals', 'imags'), 'none', 'operators'),
    _e('initDiagonalOpFromPauliHamil', ('op', 'hamil'), 'none', 'operators'),
    _e('initPauliHamil', ('hamil', 'coeffs', 'codes'), 'none', 'datatypes'),
    _e('initPlusState', ('qureg',), 'any', 'state_init', needs_validation=False),
    _e('initPureState', ('qureg', 'pure'), 'any', 'state_init'),
    _e('initStateFromAmps', ('qureg', 'reals', 'imags'), 'any', 'state_init'),
    _e('initZeroState', ('qureg',), 'any', 'state_init', needs_validation=False),
    _e('invalidQuESTInputError', ('errMsg', 'errFunc'), 'none', 'validation'),
    _e('measure', ('qureg', 'target'), 'any', 'gates'),
    _e('measureWithStats', ('qureg', 'target'), 'any', 'gates'),
    _e('mixDamping', ('qureg', 'target', 'prob'), 'density', 'decoherence'),
    _e('mixDensityMatrix', ('combine', 'prob', 'other'), 'density', 'decoherence'),
    _e('mixDephasing', ('qureg', 'target', 'prob'), 'density', 'decoherence'),
    _e('mixDepolarising', ('qureg', 'target', 'prob'), 'density', 'decoherence'),
    _e('mixKrausMap', ('qureg', 'target', 'ops'), 'density', 'decoherence'),
    _e('mixMultiQubitKrausMap', ('qureg', 'targets', 'ops'), 'density', 'decoherence'),
    _e('mixNonTPKrausMap', ('qureg', 'target', 'ops'), 'density', 'decoherence'),
    _e('mixNonTPMultiQubitKrausMap', ('qureg', 'targets', 'ops'), 'density', 'decoherence'),
    _e('mixNonTPTwoQubitKrausMap', ('qureg', 'q1', 'q2', 'ops'), 'density', 'decoherence'),
    _e('mixPauli', ('qureg', 'target', 'px', 'py', 'pz'), 'density', 'decoherence'),
    _e('mixTwoQubitDephasing', ('qureg', 'q1', 'q2', 'prob'), 'density', 'decoherence'),
    _e('mixTwoQubitDepolarising', ('qureg', 'q1', 'q2', 'prob'), 'density', 'decoherence'),
    _e('mixTwoQubitKrausMap', ('qureg', 'q1', 'q2', 'ops'), 'density', 'decoherence'),
    _e('multiControlledMultiQubitNot', ('qureg', 'controls', 'targets'), 'any', 'gates'),
    _e('multiControlledMultiQubitUnitary', ('qureg', 'controls', 'targets', 'u'), 'any', 'gates'),
    _e('multiControlledMultiRotatePauli', ('qureg', 'controls', 'targets', 'paulis', 'angle'), 'any', 'gates'),
    _e('multiControlledMultiRotateZ', ('qureg', 'controls', 'targets', 'angle'), 'any', 'gates'),
    _e('multiControlledPhaseFlip', ('qureg', 'qubits'), 'any', 'gates'),
    _e('multiControlledPhaseShift', ('qureg', 'qubits', 'angle'), 'any', 'gates'),
    _e('multiControlledTwoQubitUnitary', ('qureg', 'controls', 't1', 't2', 'u'), 'any', 'gates'),
    _e('multiControlledUnitary', ('qureg', 'controls', 'target', 'u'), 'any', 'gates'),
    _e('multiQubitNot', ('qureg', 'targets'), 'any', 'gates'),
    _e('multiQubitUnitary', ('qureg', 'targets', 'u'), 'any', 'gates'),
    _e('multiRotatePauli', ('qureg', 'targets', 'paulis', 'angle'), 'any', 'gates'),
    _e('multiRotateZ', ('qureg', 'qubits', 'angle'), 'any', 'gates'),
    _e('multiStateControlledUnitary', ('qureg', 'controls', 'states', 'target', 'u'), 'any', 'gates'),
    _e('pauliX', ('qureg', 'target'), 'any', 'gates'),
    _e('pauliY', ('qureg', 'target'), 'any', 'gates'),
    _e('pauliZ', ('qureg', 'target'), 'any', 'gates'),
    _e('phaseShift', ('qureg', 'target', 'angle'), 'any', 'gates'),
    _e('printRecordedQASM', ('qureg',), 'any', 'reporting', needs_validation=False),
    _e('reportPauliHamil', ('hamil',), 'none', 'reporting', needs_validation=False),
    _e('reportQuESTEnv', ('env',), 'none', 'environment', needs_validation=False),
    _e('reportQuregParams', ('qureg',), 'any', 'reporting', needs_validation=False),
    _e('reportState', ('qureg',), 'any', 'reporting', needs_validation=False),
    _e('reportStateToScreen', ('qureg', 'env', 'report_rank'), 'any', 'reporting', needs_validation=False),
    _e('rotateAroundAxis', ('qureg', 'target', 'angle', 'axis'), 'any', 'gates'),
    _e('rotateX', ('qureg', 'target', 'angle'), 'any', 'gates'),
    _e('rotateY', ('qureg', 'target', 'angle'), 'any', 'gates'),
    _e('rotateZ', ('qureg', 'target', 'angle'), 'any', 'gates'),
    _e('sGate', ('qureg', 'target'), 'any', 'gates'),
    _e('seedQuEST', ('env', 'seeds'), 'none', 'environment'),
    _e('seedQuESTDefault', ('env',), 'none', 'environment', needs_validation=False),
    _e('setAmps', ('qureg', 'start_ind', 'reals', 'imags', 'num_amps'), 'statevec', 'state_init'),
    _e('setDensityAmps', ('qureg', 'start_row', 'start_col', 'reals', 'imags', 'num_amps'), 'density', 'state_init'),
    _e('setDiagonalOpElems', ('op', 'start_ind', 'reals', 'imags', 'num_elems'), 'none', 'operators'),
    _e('setQuregToPauliHamil', ('qureg', 'hamil'), 'any', 'operators'),
    _e('setWeightedQureg', ('fac1', 'qureg1', 'fac2', 'qureg2', 'fac_out', 'out'), 'any', 'state_init'),
    _e('sqrtSwapGate', ('qureg', 'qb1', 'qb2'), 'any', 'gates'),
    _e('startRecordingQASM', ('qureg',), 'any', 'reporting', needs_validation=False),
    _e('stopRecordingQASM', ('qureg',), 'any', 'reporting', needs_validation=False),
    _e('swapGate', ('qureg', 'qb1', 'qb2'), 'any', 'gates'),
    _e('syncDiagonalOp', ('op',), 'none', 'operators', needs_validation=False),
    _e('syncQuESTEnv', ('env',), 'none', 'environment', needs_validation=False),
    _e('syncQuESTSuccess', ('success_code',), 'none', 'environment', needs_validation=False),
    _e('tGate', ('qureg', 'target'), 'any', 'gates'),
    _e('twoQubitUnitary', ('qureg', 't1', 't2', 'u'), 'any', 'gates'),
    _e('unitary', ('qureg', 'target', 'u'), 'any', 'gates'),
    _e('writeRecordedQASMToFile', ('qureg', 'filename'), 'any', 'reporting'),
)


@dataclass(frozen=True)
class SurfaceRow:
    """One audited function: its manifest row plus the fact-column verdict."""

    name: str
    category: str
    kind: str
    facts: Mapping[str, bool]

    def fact(self, column: str) -> bool:
        return bool(self.facts[column])

    def as_dict(self) -> dict[str, Any]:
        return {"name": self.name, "category": self.category,
                "kind": self.kind,
                "facts": {c: bool(self.facts[c]) for c in FACT_COLUMNS}}


@dataclass(frozen=True)
class SurfaceAudit:
    """The audit result: one row per manifest entry plus the findings."""

    rows: tuple[SurfaceRow, ...]
    findings: tuple[Finding, ...]

    def row(self, name: str) -> SurfaceRow:
        for r in self.rows:
            if r.name == name:
                return r
        raise KeyError(name)

    def summary(self) -> dict[str, int]:
        return {c: sum(1 for r in self.rows if r.fact(c))
                for c in FACT_COLUMNS}


@dataclass(frozen=True)
class TestScan:
    """AST scan of ``tests/``: which functions have literal call sites,
    and which test files run the sharded / df routes."""

    calls: Mapping[str, frozenset[str]]
    sharded_files: frozenset[str]
    df_files: frozenset[str]

    def tested(self, name: str) -> bool:
        return bool(self.calls.get(name))

    def sharded(self, name: str) -> bool:
        return bool(self.calls.get(name, frozenset()) & self.sharded_files)

    def df(self, name: str) -> bool:
        return bool(self.calls.get(name, frozenset()) & self.df_files)


def _package_root() -> Path:
    return Path(__file__).resolve().parents[1]


def _repo_root() -> Path:
    return Path(__file__).resolve().parents[2]


# ---------------------------------------------------------------------------
# static scans (ast only -- no execution)
# ---------------------------------------------------------------------------

def scan_validated(package_root: Optional[Path] = None) -> frozenset[str]:
    """Function names (across the package's top-level L5 modules) that
    reach the validation layer: a direct ``V.validate_*`` /
    ``validate_*`` / ``invalid_quest_input_error`` call or a ``raise``,
    or -- to transitive fixpoint -- a call into any function that does
    (``mixKrausMap -> _mix_kraus``, ``multiRotatePauli ->
    _multi_rotate_pauli``, ``applyFullQFT -> _qft_on -> hadamard``)."""
    root = package_root if package_root is not None else _package_root()
    funcs: dict[tuple[str, str], set[str]] = {}
    validated: set[tuple[str, str]] = set()
    for path in sorted(root.glob("*.py")):
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError:
            continue
        for node in tree.body:
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            key = (path.stem, node.name)
            calls: set[str] = set()
            direct = False
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call):
                    fn = sub.func
                    if isinstance(fn, ast.Name):
                        calls.add(fn.id)
                        if (fn.id.startswith("validate")
                                or fn.id == "invalid_quest_input_error"):
                            direct = True
                    elif isinstance(fn, ast.Attribute):
                        calls.add(fn.attr)
                        if (isinstance(fn.value, ast.Name)
                                and fn.value.id in ("V", "validation")):
                            direct = True
                elif isinstance(sub, ast.Raise):
                    direct = True
            funcs[key] = calls
            if direct:
                validated.add(key)
    by_name: dict[str, list[tuple[str, str]]] = {}
    for mod, name in funcs:
        by_name.setdefault(name, []).append((mod, name))
    changed = True
    while changed:
        changed = False
        for key, calls in funcs.items():
            if key in validated:
                continue
            if any(cand in validated
                   for callee in calls
                   for cand in by_name.get(callee, [])):
                validated.add(key)
                changed = True
    return frozenset(name for _mod, name in validated)


def scan_tests(tests_root: Optional[Path] = None) -> TestScan:
    """AST-walk every ``tests/*.py`` for literal call sites (``foo(...)``
    and ``qt.foo(...)``) and flag each file's route coverage: sharded
    when it builds the default no-argument (8-device) env, df when it
    creates ``precision_code=1`` registers or drives the Pallas
    double-float route."""
    root = (tests_root if tests_root is not None
            else _repo_root() / "tests")
    calls: dict[str, set[str]] = {}
    sharded: set[str] = set()
    df: set[str] = set()
    for path in sorted(root.glob("*.py")):
        text = path.read_text()
        try:
            tree = ast.parse(text)
        except SyntaxError:
            continue
        if re.search(r"createQuESTEnv\(\s*\)", text):
            sharded.add(path.name)
        if re.search(r"precision_code\s*=\s*1\b|QUEST_PALLAS_DF", text):
            df.add(path.name)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                fn = node.func
                if isinstance(fn, ast.Name):
                    calls.setdefault(fn.id, set()).add(path.name)
                elif isinstance(fn, ast.Attribute):
                    calls.setdefault(fn.attr, set()).add(path.name)
    return TestScan(
        calls={k: frozenset(v) for k, v in calls.items()},
        sharded_files=frozenset(sharded), df_files=frozenset(df))


def scan_documented(docs_root: Optional[Path] = None) -> frozenset[str]:
    """Function names with an entry (``def name(``) on any generated
    ``docs/api`` page."""
    root = (docs_root if docs_root is not None
            else _repo_root() / "docs" / "api")
    names: set[str] = set()
    if root.is_dir():
        for path in sorted(root.glob("*.md")):
            names.update(re.findall(r"`def (\w+)\(", path.read_text()))
    return frozenset(names)


def _grad_names() -> frozenset[str]:
    from .. import params
    return frozenset(params._LIFTABLE)


def _tape_names(names: Iterable[str]) -> frozenset[str]:
    from .. import circuits
    out = set()
    for name in names:
        try:
            circuits._resolve(name)
        except AttributeError:
            continue
        out.add(name)
    return frozenset(out)


def _oracle_names() -> frozenset[str]:
    from .conformance import ORACLE_SPECS
    return frozenset(ORACLE_SPECS)


# ---------------------------------------------------------------------------
# the audit
# ---------------------------------------------------------------------------

def audit_surface(
    manifest: Sequence[ManifestEntry] = REFERENCE_MANIFEST,
    *,
    namespace: Optional[Mapping[str, Any]] = None,
    validated: Optional[frozenset[str]] = None,
    tests: Optional[TestScan] = None,
    documented: Optional[frozenset[str]] = None,
    grad_names: Optional[frozenset[str]] = None,
    tape_names: Optional[frozenset[str]] = None,
    oracle_names: Optional[frozenset[str]] = None,
) -> SurfaceAudit:
    """Classify every manifest row against the live package surface and
    return the rows plus QT901/QT902/QT903/QT904/QT906 findings. Every
    input is injectable; the defaults audit the real tree (``quest_tpu``
    exports, the :func:`scan_validated` fixpoint, the :func:`scan_tests`
    call-site scan, the ``docs/api`` pages, the engine lift registry,
    the Circuit tape resolver and the conformance spec registry)."""
    ns: Mapping[str, Any] = (namespace if namespace is not None
                             else vars(importlib.import_module("quest_tpu")))
    vset = validated if validated is not None else scan_validated()
    tscan = tests if tests is not None else scan_tests()
    dset = documented if documented is not None else scan_documented()
    gset = grad_names if grad_names is not None else _grad_names()
    tset = (tape_names if tape_names is not None
            else _tape_names([m.name for m in manifest]))
    oset = oracle_names if oracle_names is not None else _oracle_names()

    rows: list[SurfaceRow] = []
    findings: list[Finding] = []
    for entry in manifest:
        live = ns.get(entry.name)
        exists = callable(live)
        loc = f"quest_tpu.{entry.category}.{entry.name}"
        sig_ok = False
        doc_ok = False
        if exists:
            try:
                live_params = tuple(inspect.signature(live).parameters)
            except (TypeError, ValueError):
                live_params = ()
            sig_ok = live_params == entry.params
            if not sig_ok:
                findings.append(make_finding(
                    "QT902",
                    f"{entry.name} signature drifted: manifest "
                    f"({', '.join(entry.params)}) vs live "
                    f"({', '.join(live_params)})", loc))
            doc_ok = bool(inspect.getdoc(live)) and entry.name in dset
            if not doc_ok:
                findings.append(make_finding(
                    "QT906",
                    f"{entry.name} is undocumented "
                    f"(docstring: {bool(inspect.getdoc(live))}, docs/api "
                    f"page entry: {entry.name in dset})", loc))
        else:
            findings.append(make_finding(
                "QT901",
                f"reference L5 function {entry.name} "
                f"({entry.category}, {entry.kind}) is missing from the "
                f"quest_tpu public surface", loc))
        valid_ok = (not entry.needs_validation) or entry.name in vset
        if exists and not valid_ok:
            findings.append(make_finding(
                "QT903",
                f"{entry.name} takes user input but never reaches "
                f"validation.py (no direct or delegated validate_* call "
                f"found)", loc))
        tested = tscan.tested(entry.name)
        if exists and not tested:
            findings.append(make_finding(
                "QT904",
                f"{entry.name} has no literal call site under tests/",
                loc))
        facts = {
            "exists": exists,
            "signature": sig_ok,
            "validates": exists and valid_ok,
            "documented": doc_ok,
            "tested": tested,
            "sharded": tscan.sharded(entry.name),
            "df": tscan.df(entry.name),
            "grad": entry.name in gset,
            "tape": entry.name in tset,
            "oracle": entry.name in oset,
        }
        rows.append(SurfaceRow(entry.name, entry.category, entry.kind,
                               facts))
    return SurfaceAudit(rows=tuple(rows), findings=tuple(findings))


# ---------------------------------------------------------------------------
# manifest serialization + staleness gate
# ---------------------------------------------------------------------------

_MD_HEADER = """\
# L5 API-surface parity manifest

Generated by `python tools/lint.py --surface --write` from the vendored
reference manifest (`quest_tpu/analysis/surface.py`, frozen from
`native/include/QuEST.h`). **Do not edit by hand** -- CI fails (QT905)
when this file is stale vs. the audited tree. Column semantics:
docs/parity.md.

| column | meaning |
|---|---|
| exists | exported from `quest_tpu` and callable |
| sig | live parameter names match the vendored manifest |
| valid | reaches `validation.py` (or `needs_validation=False`) |
| doc | docstring + `docs/api` page entry |
| test | literal call site under `tests/` |
| shard | called from an 8-device-mesh test module |
| df | called from an f32/double-float-route test module |
| grad | adjoint-liftable parameter position (engine lift registry) |
| tape | composable onto a `Circuit` tape |
| oracle | dense-oracle replay spec in `analysis/conformance.py` |
"""


def _cell(v: bool) -> str:
    return "x" if v else "."


def render_parity_md(audit: SurfaceAudit) -> str:
    """The committed ``PARITY.md`` text: the legend, one table row per
    function (sorted by category then name), the per-column summary and
    the red-cell backlog. Deterministic -- no timestamps."""
    lines = [_MD_HEADER]
    lines.append("| function | category | kind | "
                 + " | ".join(("exists", "sig", "valid", "doc", "test",
                               "shard", "df", "grad", "tape", "oracle"))
                 + " |")
    lines.append("|---|---|---|" + "---|" * len(FACT_COLUMNS))
    for r in sorted(audit.rows, key=lambda r: (r.category, r.name)):
        cells = " | ".join(_cell(r.fact(c)) for c in FACT_COLUMNS)
        lines.append(f"| `{r.name}` | {r.category} | {r.kind} | {cells} |")
    total = len(audit.rows)
    s = audit.summary()
    lines.append("")
    lines.append("## Summary")
    lines.append("")
    lines.append("| column | green |")
    lines.append("|---|---|")
    for c in FACT_COLUMNS:
        lines.append(f"| {c} | {s[c]}/{total} |")
    red = sorted(r.name for r in audit.rows if not r.fact("oracle"))
    lines.append("")
    lines.append("## Red cells: no dense-oracle replay spec yet")
    lines.append("")
    lines.append("Each is a concrete next PR: add an `ORACLE_SPECS` row in "
                 "`quest_tpu/analysis/conformance.py` and the generated "
                 "harness picks it up (docs/parity.md).")
    lines.append("")
    lines.append(", ".join(f"`{n}`" for n in red) if red else "(none)")
    lines.append("")
    return "\n".join(lines)


def parity_json(audit: SurfaceAudit) -> str:
    """The committed ``parity.json`` text: the machine-readable manifest
    (``{"version", "columns", "functions", "summary"}``)."""
    payload = {
        "version": 1,
        "columns": list(FACT_COLUMNS),
        "functions": [r.as_dict()
                      for r in sorted(audit.rows,
                                      key=lambda r: (r.category, r.name))],
        "summary": audit.summary(),
        "total": len(audit.rows),
    }
    return json.dumps(payload, indent=1, sort_keys=True) + "\n"


def check_manifest_files(audit: SurfaceAudit,
                         repo_root: Optional[Path] = None) -> list[Finding]:
    """QT905 staleness gate: the committed ``PARITY.md`` /
    ``parity.json`` must byte-match what the audited tree regenerates."""
    root = repo_root if repo_root is not None else _repo_root()
    findings: list[Finding] = []
    for fname, render in ((PARITY_MD, render_parity_md),
                          (PARITY_JSON, parity_json)):
        path = root / fname
        want = render(audit)
        have = path.read_text() if path.is_file() else None
        if have != want:
            state = "missing" if have is None else "stale"
            findings.append(make_finding(
                "QT905",
                f"{fname} is {state} vs. the audited tree; regenerate "
                f"with `python tools/lint.py --surface --write`",
                str(path)))
    return findings


def write_manifest_files(audit: SurfaceAudit,
                         repo_root: Optional[Path] = None) -> list[Path]:
    """Regenerate the committed manifest artifacts; returns the paths."""
    root = repo_root if repo_root is not None else _repo_root()
    out = []
    for fname, render in ((PARITY_MD, render_parity_md),
                          (PARITY_JSON, parity_json)):
        path = root / fname
        path.write_text(render(audit))
        out.append(path)
    return out


def check_surface(*, write: bool = False,
                  repo_root: Optional[Path] = None,
                  emit: bool = True) -> tuple[SurfaceAudit, list[Finding]]:
    """The ``tools/lint.py --surface`` entry point: run the audit, gate
    the committed manifests (QT905; ``write=True`` regenerates them
    first), flight-record every finding on
    ``analysis_findings_total{code,severity}`` and return
    ``(audit, findings)``."""
    audit = audit_surface()
    findings = list(audit.findings)
    if write:
        write_manifest_files(audit, repo_root)
    findings += check_manifest_files(audit, repo_root)
    if emit:
        emit_findings(findings)
    return audit, findings
