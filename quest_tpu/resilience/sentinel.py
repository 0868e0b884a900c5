"""Online integrity sentinels: cheap invariants that catch silent data
corruption (SDC) while a computation is still running.

The reference's own defense against silent corruption is
``calcTotalProb`` -- "check it stays 1" (statevec_calcTotalProb, Kahan
summation, QuEST_cpu_distributed.c:62-119) -- applied manually by the
user between circuit runs. At fleet scale a flipped amplitude bit on one
device produces no exception, just a wrong answer, so this module makes
the invariant ONLINE: the segmented runner and the serving engine probe
the live state at a configurable cadence, and a breach feeds the
self-healing loop (rollback-and-replay in
:mod:`~quest_tpu.resilience.segmented`, health quarantine in
:mod:`~quest_tpu.engine.engine`).

Three sentinel kinds (:data:`KINDS`):

- ``norm``     -- total probability must stay 1 within a precision-aware
  band (:func:`tolerance`): f32 registers get the wide band the pairwise
  f32 cascade needs, f64 / double-float registers (the PRECISION=2 route
  accumulates within ~2^-47) get the tight one. On a density register
  this is Re tr(rho) -- QT401 (QT404 for density) on breach.
- ``checksum`` -- per-shard partial-norm checksums folded via ONE
  ``lax.psum``: every shard returns its local partial plus the folded
  total, so all shards provably agree on the total or the QT402 finding
  NAMES the divergent shard (non-finite or out-of-range partial, or a
  shard whose psum result disagrees). This is the shard-attribution
  channel the norm check lacks.
- ``trace``    -- density registers only: Re tr(rho) plus hermiticity
  (max |rho - rho^H| within the band) -- QT404 on breach; counted
  ``outcome=skipped`` on state-vectors.

Configuration (``QUEST_SENTINEL`` env, read once, or an explicit
:class:`SentinelPolicy`):

    QUEST_SENTINEL=norm:every_2,checksum:segment
    QUEST_SENTINEL=default          # norm + checksum, every segment

Each entry is ``kind[:cadence]`` where cadence is ``segment`` (every
check opportunity, the default), ``every_N``, or a bare integer ``N``
(every Nth opportunity). Malformed entries are skipped with a QT403
diagnostic (``strict=True`` raises) -- same hygiene as ``QUEST_FAULTS``.

Every executed check counts ``sentinel_checks_total{kind,outcome}``
(``ok`` | ``breach`` | ``skipped``). With no policy armed every probe
point is one module-level boolean read -- the zero-cost discipline of
:mod:`.faultinject`, asserted by the sentinels-off test.
"""

from __future__ import annotations

import contextlib
import os
from typing import TYPE_CHECKING, Iterable, Iterator, NamedTuple

import numpy as np

from .. import telemetry
from ..validation import QuESTError
from . import sync as _sync

if TYPE_CHECKING:
    import jax

    from ..analysis.diagnostics import Finding
    from ..registers import Qureg

__all__ = ["KINDS", "ENV_VAR", "DEFAULT_SPEC", "SentinelSpec",
           "SentinelPolicy", "enabled", "active_policy", "install",
           "clear", "sentinel_policy", "tolerance", "check_amps",
           "check_qureg"]

ENV_VAR = "QUEST_SENTINEL"

#: sentinel kinds a policy may arm
KINDS: tuple[str, ...] = ("norm", "checksum", "trace")

#: what ``QUEST_SENTINEL=default`` (or ``1``/``on``) arms
DEFAULT_SPEC = "norm:segment,checksum:segment"

#: precision-aware tolerance bands for the norm/trace/checksum invariants
#: (|total - 1| must stay inside): f32 needs the wide band (pairwise f32
#: cascade error ~1e-7/amp over 2^20+ terms plus per-gate rounding), f64
#: and the double-float route (~2^-47 accumulation) get the tight one
_TOL = {np.dtype(np.float32): 1e-4, np.dtype(np.float64): 1e-9}


def tolerance(dtype: np.dtype | type | str) -> float:
    """The drift band for a register of real ``dtype`` (see module
    docstring); unknown dtypes get the conservative f32 band."""
    return _TOL.get(np.dtype(dtype), 1e-4)


def _qt403(entry: str, why: str) -> None:
    from ..analysis.diagnostics import emit_findings, make_finding
    emit_findings([make_finding(
        "QT403", f"{ENV_VAR} entry {entry!r} ignored: {why}",
        "resilience.sentinel")])


class SentinelSpec(NamedTuple):
    """One armed sentinel: its kind and cadence (in check opportunities
    -- segment boundaries for the segmented runner, dispatches for the
    engine)."""
    kind: str
    cadence: int = 1

    def due(self, tick: int) -> bool:
        """True when 1-based opportunity ``tick`` should run this check."""
        return tick % self.cadence == 0


class SentinelPolicy:
    """A parsed sentinel policy: which kinds run, at what cadence."""

    def __init__(self,
                 specs: Iterable[SentinelSpec] | tuple = ()) -> None:
        self.specs: tuple[SentinelSpec, ...] = tuple(specs)

    @classmethod
    def parse(cls, text: str, strict: bool = False) -> "SentinelPolicy":
        """Parse ``kind[:cadence][,...]`` (see module docstring);
        malformed entries are skipped with a QT403 diagnostic, or raise
        when ``strict``. ``default``/``on``/``1`` arm
        :data:`DEFAULT_SPEC`; ``off``/``0`` arm nothing."""
        low = text.strip().lower()
        if low in ("", "off", "0", "none"):
            return cls(())
        if low in ("default", "on", "1"):
            text = DEFAULT_SPEC
        specs = []
        for entry in filter(None, (e.strip() for e in text.split(","))):
            parts = entry.split(":")
            kind, cad_s = parts[0], (parts[1] if len(parts) == 2 else
                                     "segment")
            why = None
            cadence = 1
            if len(parts) > 2:
                why = "expected kind[:cadence]"
            elif kind not in KINDS:
                why = f"unknown kind (one of {KINDS})"
            else:
                c = cad_s[len("every_"):] if cad_s.startswith("every_") \
                    else cad_s
                if c == "segment":
                    cadence = 1
                elif c.isdigit() and int(c) >= 1:
                    cadence = int(c)
                else:
                    why = ("cadence must be 'segment', 'every_N' or a "
                           "positive integer")
            if why is not None:
                if strict:
                    raise QuESTError(
                        f"bad {ENV_VAR} entry {entry!r}: {why} [QT403]",
                        "SentinelPolicy.parse")
                _qt403(entry, why)
                continue
            specs.append(SentinelSpec(kind, cadence))
        return cls(specs)

    def due_kinds(self, tick: int) -> tuple[str, ...]:
        """The kinds due at 1-based opportunity ``tick``, in spec order,
        deduplicated."""
        seen: list[str] = []
        for s in self.specs:
            if s.due(tick) and s.kind not in seen:
                seen.append(s.kind)
        return tuple(seen)


# -- module-level policy management (the zero-cost disabled path) -----------

_active: SentinelPolicy | None = None
_env_read = False
_state_lock = _sync.Lock("sentinel.state")


def _load_env() -> None:
    global _active, _env_read
    with _state_lock:
        if _env_read:
            return
        _env_read = True
        text = os.environ.get(ENV_VAR, "").strip()
        if text:
            pol = SentinelPolicy.parse(text)
            if pol.specs:
                _active = pol


def enabled() -> bool:
    """True when a sentinel policy is armed (env or explicit). The first
    call reads ``QUEST_SENTINEL`` once; afterwards this is one boolean."""
    if not _env_read:
        _load_env()
    return _active is not None


def active_policy() -> SentinelPolicy | None:
    """The armed policy, or None."""
    if not _env_read:
        _load_env()
    return _active


def install(policy: SentinelPolicy | str | None) -> None:
    """Arm ``policy`` (a :class:`SentinelPolicy`, a spec string, or None
    to disarm), replacing whatever was active."""
    global _active, _env_read
    with _state_lock:
        _env_read = True
        if isinstance(policy, str):
            policy = SentinelPolicy.parse(policy, strict=True)
        _active = policy if (policy is None or policy.specs) else None


def clear() -> None:
    """Disarm all sentinels (probe points become no-ops again)."""
    install(None)


@contextlib.contextmanager
def sentinel_policy(
        policy: SentinelPolicy | str) -> Iterator[SentinelPolicy | None]:
    """Context manager arming ``policy`` for the block (tests/bench);
    restores the previous policy on exit."""
    global _active, _env_read
    prev, prev_read = _active, _env_read
    install(policy)
    try:
        yield active_policy()
    finally:
        with _state_lock:
            _active, _env_read = prev, prev_read


# -- the checks -------------------------------------------------------------

def _finding(code: str, message: str, where: str) -> Finding:
    from ..analysis.diagnostics import emit_findings, make_finding
    f = make_finding(code, message, where or "resilience.sentinel")
    emit_findings([f])
    return f


def _shard_partials(amps, mesh):
    """(per-shard partial |amp|^2 sums, psum-folded totals) as host
    arrays of length D. On the mesh each shard computes its local
    partial and ONE ``lax.psum`` folds the total, returned per shard --
    so either every shard holds the same total or the disagreement
    itself localizes the fault. Unsharded registers degenerate to one
    "shard"."""
    import jax.numpy as jnp

    from ..ops.reduce import _csum

    if mesh is None or mesh.size <= 1:
        # sum|amps|^2 via the JITTED cascade (total_prob_statevec is
        # exactly _csum(a0^2 + a1^2), which is the norm on a statevector
        # and the purity on a density register): the eager _csum tree
        # would cost ~100x more per probe than the compiled program
        from ..ops.reduce import total_prob_statevec
        p = float(total_prob_statevec(amps))
        return np.array([p]), np.array([p])

    from jax import lax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from ..environment import AMP_AXIS

    def kernel(a):
        p = _csum(a[0] * a[0] + a[1] * a[1])
        t = lax.psum(p, AMP_AXIS)
        return jnp.stack([p, t]).reshape(2, 1)

    out = np.asarray(shard_map(
        kernel, mesh=mesh, in_specs=P(None, AMP_AXIS),
        out_specs=P(None, AMP_AXIS))(amps))
    return out[0], out[1]


def _check_norm(amps: jax.Array, density: bool, n: int, tol: float,
                where: str) -> Finding | None:
    from ..ops import reduce as R

    if density:
        total = float(R.total_prob_density(amps, n=n))
        code, what = "QT404", "Re tr(rho)"
    else:
        total = float(R.total_prob_statevec(amps))
        code, what = "QT401", "total probability"
    drift = abs(total - 1.0)
    if np.isfinite(total) and drift <= tol:
        return None
    return _finding(
        code, f"{what} {total!r} drifted |delta|={drift:.3e} beyond the "
        f"{tol:.1e} band for dtype {np.dtype(amps.dtype).name}", where)


def _check_checksum(amps: jax.Array, density: bool, tol: float,
                    where: str,
                    mesh: jax.sharding.Mesh | None) -> Finding | None:
    partials, totals = _shard_partials(amps, mesh)
    # sum|amps|^2 is the norm (statevec) or purity (density): both must
    # land in [0, 1] within the band, and every shard's folded total
    # must agree -- a violation names the shard
    bad = [i for i, p in enumerate(partials)
           if not np.isfinite(p) or p < -tol or p > 1.0 + tol]
    if not bad and totals.size > 1 and not np.all(totals == totals[0]):
        bad = [int(np.argmax(totals != totals[0]))]
    total = totals[0] if np.isfinite(totals[0]) else float("nan")
    global_bad = not np.isfinite(total) or total > 1.0 + tol or total < -tol
    if not bad and not global_bad:
        return None
    shard = bad[0] if bad else int(np.argmax(
        ~np.isfinite(partials) | (partials > 1.0 + tol)))
    return _finding(
        "QT402", f"per-shard checksum divergence: shard {shard} partial "
        f"|amps|^2 = {partials[shard]!r} (psum-folded total {total!r}, "
        f"band {tol:.1e}, {len(partials)} shard(s))", where)


def _check_trace(amps: jax.Array, density: bool, n: int, tol: float,
                 where: str) -> Finding | str | None:
    if not density:
        return "skipped"
    from ..ops import reduce as R

    total = float(R.total_prob_density(amps, n=n))
    host = np.asarray(amps)
    dim = 1 << n
    re = host[0].reshape(dim, dim)
    im = host[1].reshape(dim, dim)
    asym = max(float(np.max(np.abs(re - re.T))),
               float(np.max(np.abs(im + im.T))))
    drift = abs(total - 1.0)
    if np.isfinite(total) and drift <= tol and np.isfinite(asym) \
            and asym <= tol:
        return None
    return _finding(
        "QT404", f"density register breached trace/hermiticity: "
        f"Re tr(rho) = {total!r} (|delta|={drift:.3e}), "
        f"max |rho - rho^H| = {asym:.3e}, band {tol:.1e}", where)


def check_amps(amps: jax.Array, *, density: bool = False,
               n: int | None = None,
               mesh: jax.sharding.Mesh | None = None,
               policy: SentinelPolicy | None = None,
               tick: int = 1, where: str = "") -> list:
    """Run every armed sentinel due at opportunity ``tick`` over a
    planar ``(2, 2**nsv)`` amplitude array; returns the breach findings
    (empty = clean). ``n`` is the represented qubit count (density
    registers need it for the trace); ``mesh`` enables the per-shard
    checksum fold. Each executed check counts
    ``sentinel_checks_total{kind,outcome}``; findings are already
    flight-recorded when returned."""
    pol = policy if policy is not None else active_policy()
    if pol is None or not pol.specs:
        return []
    if n is None:
        n = int(np.log2(amps.shape[-1])) // (2 if density else 1)
    tol = tolerance(amps.dtype)
    findings = []
    for kind in pol.due_kinds(tick):
        if kind == "norm":
            out = _check_norm(amps, density, n, tol, where)
        elif kind == "checksum":
            out = _check_checksum(amps, density, tol, where, mesh)
        else:
            out = _check_trace(amps, density, n, tol, where)
        outcome = ("skipped" if out == "skipped"
                   else "ok" if out is None else "breach")
        telemetry.inc("sentinel_checks_total", kind=kind, outcome=outcome)
        if outcome == "breach":
            telemetry.event("resilience.sentinel_breach", kind=kind,
                            code=out.code, where=where)
            findings.append(out)
    return findings


def check_qureg(qureg: Qureg, *, policy: SentinelPolicy | None = None,
                tick: int = 1, where: str = "") -> list:
    """:func:`check_amps` over a live register (mesh inferred from its
    sharding)."""
    from ..circuits import _register_mesh

    return check_amps(qureg.amps, density=qureg.is_density_matrix,
                      n=qureg.num_qubits_represented,
                      mesh=_register_mesh(qureg), policy=policy,
                      tick=tick, where=where)
