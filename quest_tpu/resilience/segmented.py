"""Preemption-safe segmented execution with verified checkpoints.

A fused :class:`~quest_tpu.planner.FusePlan` tape is not interruptible at
arbitrary points: between a PallasRun's folded load swap and its store
swap the amplitudes live in a PERMUTED frame, and a snapshot taken there
is not a state the public API can name. The points where the frame
returns to identity -- exactly what ``analysis/plancheck`` (QT102/QT103)
proves exist before every non-plan item and at plan end -- are the legal
segment boundaries. :func:`segment_plan` recomputes them here by symbolic
frame replay of the tape's swap blocks (the same bit-block composition
plancheck walks).

:func:`run_segmented` executes the tape segment by segment; at each
selected boundary it writes one checkpoint GENERATION: a full
:func:`~quest_tpu.checkpoint.saveQureg` snapshot (amplitudes + env seeds
+ MT19937 RNG cursor, per-shard CRC32 in the index) plus a
``segment.json`` manifest recording the tape cursor and the circuit
fingerprint. Generations are retained ``keep`` deep; the preemption
fault-injection site (``segment.boundary:preempt``) fires BETWEEN
segments, after the checkpoint is durable.

:func:`resume_segmented` walks generations newest-first, picks the last
one that passes :func:`~quest_tpu.checkpoint.verify_snapshot` (rejected
generations are flight-recorded QT305 and skipped -- a CRC-divergent
shard counts ``outcome=skipped_corrupt`` with the expected/actual CRC32
in the finding, every other failure ``outcome=rejected_gen`` -- so a
torn or bit-flipped shard falls back to the previous generation instead
of failing the resume), reloads the register and RNG, and replays the
remaining segments. Segment executables are deterministic functions of
the tape slice, and snapshot round-trips are exact, so an interrupted +
resumed run is bit-identical to an uninterrupted segmented run -- the
property tests/test_resilience.py proves on the 8-device mesh for both
the f32 and the double-float route.

Self-healing (ISSUE 8): with a sentinel policy armed
(:mod:`~quest_tpu.resilience.sentinel`, ``QUEST_SENTINEL``), every
segment boundary is also an integrity probe. A breach (norm drift,
per-shard checksum divergence, trace/hermiticity loss) triggers
rollback-and-replay BEFORE the corrupt state can be checkpointed: the
register rolls back to the last verified state -- the CRC-verified
generation at the segment's start cursor, or an in-memory baseline for
the first segment of a fresh run (writing a gen-0 snapshot just to have
a rollback target would charge every clean run the cost of one extra
checkpoint) -- and the segment replays on the same route under the
:func:`guard.sentinel_replay` escalation lattice: retry -> eager
fallback-route replay -> fail closed with
:class:`~quest_tpu.resilience.errors.QuESTIntegrityError`. Because
fault-injection visits are counted, an injected single-bit flip
(``state.corrupt:bitflip<shard>:nth``) does NOT re-fire on the replay,
so recovery is provably bit-identical to the uncorrupted run.
"""

from __future__ import annotations

import json
import os
import shutil

from typing import TYPE_CHECKING

from .. import telemetry
from ..validation import QuESTError
from . import faultinject, guard, sentinel
from .errors import QuESTChecksumError, QuESTIntegrityError

if TYPE_CHECKING:
    from ..analysis.diagnostics import Finding
    from ..circuits import Circuit
    from ..environment import QuESTEnv
    from ..registers import Qureg
    from .sentinel import SentinelPolicy

__all__ = ["segment_plan", "run_segmented", "resume_segmented"]

_MANIFEST = "segment.json"
_GEN_PREFIX = "gen_"


def _qt304(message: str) -> QuESTError:
    from ..analysis.diagnostics import emit_findings, make_finding
    emit_findings([make_finding("QT304", message, "resilience.segmented")])
    return QuESTError(f"{message} [QT304]", "run_segmented")


def _qt305(gen_dir: str, why: str) -> None:
    from ..analysis.diagnostics import emit_findings, make_finding
    emit_findings([make_finding(
        "QT305", f"checkpoint generation {os.path.basename(gen_dir)!r} "
        f"failed verification ({why}); falling back to an older generation",
        "resilience.segmented")])


def _qt305_crc(gen_dir: str, e: QuESTChecksumError) -> None:
    from ..analysis.diagnostics import emit_findings, make_finding
    expected = e.expected_crc if e.expected_crc is not None else 0
    actual = e.actual_crc if e.actual_crc is not None else 0
    emit_findings([make_finding(
        "QT305", f"checkpoint generation {os.path.basename(gen_dir)!r} "
        f"shard {e.shard!r} is corrupt: payload CRC32 {actual:#010x} != "
        f"indexed {expected:#010x}; skipping this generation",
        "resilience.segmented")])


# This module runs circuits but is imported with the package it lives in,
# whose leaves (guard, sync, sentinel) every layer below circuits imports:
# what it takes from above (segments, circuits, checkpoint, registers) it
# imports where it uses it.


def segment_plan(tape: list, nsv: int, every_n_items: int = 1) -> list:
    """The selected checkpoint cuts for ``tape``: a sorted list of tape
    indices starting at 0 and ending at ``len(tape)``, each a
    frame-identity boundary, spaced at least ``every_n_items`` tape
    entries apart (the next identity boundary when the exact spacing
    lands mid-permutation). Boundaries come from
    :func:`quest_tpu.segments.identity_boundaries` -- the same seams the
    round-13 segment programs dispatch over, so a checkpoint cadence and
    a segment-program chain always agree on where the frame is identity."""
    from ..segments import identity_boundaries
    if every_n_items < 1:
        raise _qt304(f"every_n_items must be >= 1, got {every_n_items}")
    boundaries = identity_boundaries(tape, nsv)
    if boundaries[-1] != len(tape):
        raise _qt304(
            "tape does not return to the identity frame at its end "
            "(plancheck QT103 would reject this plan)")
    cuts = [0]
    for b in boundaries[1:]:
        if b - cuts[-1] >= every_n_items:
            cuts.append(b)
    if cuts[-1] != len(tape):
        cuts.append(len(tape))
    return cuts


def _as_qureg(circuit, target):
    from ..environment import QuESTEnv
    from ..registers import Qureg, createDensityQureg, createQureg

    if isinstance(target, Qureg):
        return target
    if isinstance(target, QuESTEnv):
        make = (createDensityQureg if circuit.is_density_matrix
                else createQureg)
        return make(circuit.num_qubits, target)
    raise QuESTError(
        f"run_segmented needs a QuESTEnv or Qureg, got {type(target)!r}",
        "run_segmented")


def _gen_dirs(checkpoint_dir: str) -> list:
    """Existing generation dirs sorted ascending by tape cursor."""
    out = []
    if not os.path.isdir(checkpoint_dir):
        return out
    for name in os.listdir(checkpoint_dir):
        if name.startswith(_GEN_PREFIX):
            try:
                cursor = int(name[len(_GEN_PREFIX):])
            except ValueError:
                continue
            out.append((cursor, os.path.join(checkpoint_dir, name)))
    return [p for _, p in sorted(out)]


def _checkpoint(circuit: Circuit, qureg: Qureg, checkpoint_dir: str,
                cursor: int, every_n_items: int, keep: int) -> str:
    from ..checkpoint import saveQureg

    gen = os.path.join(checkpoint_dir, f"{_GEN_PREFIX}{cursor:08d}")
    saveQureg(qureg, gen)
    manifest = {"cursor": cursor, "total_items": len(circuit._tape),
                "fingerprint": circuit.fingerprint(),
                "every_n_items": every_n_items}
    tmp = os.path.join(gen, _MANIFEST + ".tmp")
    with open(tmp, "w") as f:
        json.dump(manifest, f)
    os.replace(tmp, os.path.join(gen, _MANIFEST))
    telemetry.inc("segmented_checkpoints_total")
    gens = _gen_dirs(checkpoint_dir)
    for stale in gens[:-keep] if keep > 0 else []:
        shutil.rmtree(stale, ignore_errors=True)
    return gen


def _run_segment(circuit: Circuit, qureg: Qureg, lo: int,
                 hi: int) -> None:
    # round 13: the segment rides quest_tpu.segments.run_slice -- ONE
    # segment-program dispatch, cached on the PARENT circuit's stable
    # token (the pre-round-13 path built a throwaway Circuit per segment
    # whose fresh cache token forced a full recompile of every segment
    # on every run AND every healing replay).
    from .. import segments

    with telemetry.span("segmented.segment", lo=lo, hi=hi):
        segments.run_slice(circuit, qureg, lo, hi)
    telemetry.inc("segmented_segments_total")
    if faultinject.enabled():
        # the SDC injection point: one visit of state.corrupt per segment
        # execution (replays re-visit it, so an nth-scoped bit-flip stays
        # out of the healing replay by construction)
        corrupted = guard.corrupt_amps(qureg.amps)
        if corrupted is not qureg.amps:
            qureg.put(corrupted)


def _capture_baseline(qureg):
    """In-memory rollback target for the first segment of a fresh run
    (no disk generation exists yet): host amplitudes + the env RNG
    stream, the same pair a generation snapshot round-trips."""
    import numpy as np
    env = qureg.env
    rng = env.rng.get_state() if env is not None and env.rng is not None \
        else None
    return np.array(qureg.amps), rng


def _rollback(qureg: Qureg, lo: int, checkpoint_dir: str,
              baseline: tuple | None) -> None:
    telemetry.event("segmented.rollback", cursor=lo,
                    source="baseline" if baseline is not None else "gen")
    if baseline is not None:
        host, rng = baseline
        import jax
        sharding = getattr(qureg.amps, "sharding", None)
        qureg.put(jax.device_put(host) if sharding is None
                  else jax.device_put(host, sharding))
        if rng is not None and qureg.env is not None \
                and qureg.env.rng is not None:
            qureg.env.rng.set_state(rng)
        return
    from ..checkpoint import loadQureg

    gen = os.path.join(checkpoint_dir, f"{_GEN_PREFIX}{lo:08d}")
    # CRC-verified, fail-closed: a corrupt rollback target raises rather
    # than feeding the replay a second bad state
    restored = loadQureg(gen, qureg.env)
    qureg.put(restored.amps)


def _heal(circuit: Circuit, qureg: Qureg, lo: int, hi: int,
          checkpoint_dir: str, baseline: tuple | None,
          policy: SentinelPolicy | None,
          findings: list[Finding]) -> None:
    """Drive rollback-and-replay for a breached segment ``[lo, hi)``."""
    where = f"segment[{lo}:{hi}]"
    telemetry.event("segmented.heal", lo=lo, hi=hi,
                    codes=",".join(f.code for f in findings))

    def _recheck(stage: str) -> None:
        # tick=0 is divisible by every cadence: a healing re-check always
        # runs ALL armed sentinel kinds, whatever the boundary schedule
        again = sentinel.check_qureg(qureg, policy=policy, tick=0,
                                     where=f"{where}:{stage}")
        if again:
            raise QuESTIntegrityError(
                f"sentinel breach persists after {stage} of {where}: "
                + "; ".join(f.code for f in again),
                "run_segmented", findings=again)

    def replay():
        _rollback(qureg, lo, checkpoint_dir, baseline)
        _run_segment(circuit, qureg, lo, hi)
        _recheck("replay")
        return True

    def degrade():
        # eager per-item replay with the Pallas route forced onto the
        # engine fallback lattice: a compiled segment would cache-hit the
        # suspect executable, so degradation must bypass the cache
        _rollback(qureg, lo, checkpoint_dir, baseline)
        from ..circuits import _register_mesh
        from ..environment import pallas_mesh

        with pallas_mesh(_register_mesh(qureg)):
            with faultinject.fault_plan("pallas.dispatch:compile:1+"):
                for f, a, kw in circuit._tape[lo:hi]:
                    telemetry.inc("device_dispatch_total", route="item")
                    f(qureg, *a, **kw)
        _recheck("degraded replay")
        return True

    guard.sentinel_replay(replay, degrade, site="segment.sentinel")


def _execute(circuit: Circuit, qureg: Qureg, cuts: list, start: int,
             checkpoint_dir: str, every_n_items: int,
             keep: int) -> Qureg:
    armed = sentinel.enabled()
    policy = sentinel.active_policy() if armed else None
    tick = 0
    for lo, hi in zip(cuts, cuts[1:]):
        if hi <= start:
            continue
        tick += 1
        baseline = None
        if armed and not os.path.isdir(
                os.path.join(checkpoint_dir, f"{_GEN_PREFIX}{lo:08d}")):
            # first segment of a fresh run: no generation to roll back to
            baseline = _capture_baseline(qureg)
        _run_segment(circuit, qureg, lo, hi)
        if armed:
            findings = sentinel.check_qureg(
                qureg, policy=policy, tick=tick,
                where=f"segment[{lo}:{hi}]")
            if findings:
                _heal(circuit, qureg, lo, hi, checkpoint_dir, baseline,
                      policy, findings)
        _checkpoint(circuit, qureg, checkpoint_dir, hi, every_n_items, keep)
        if hi < cuts[-1]:
            # the injectable preemption point: the checkpoint above is
            # durable, so a preemption here resumes from cursor == hi
            guard.segment_boundary(hi, checkpoint_dir)
    return qureg


def run_segmented(circuit: Circuit, target: QuESTEnv | Qureg, *,
                  checkpoint_dir: str, every_n_items: int = 1,
                  keep: int = 2) -> Qureg:
    """Execute ``circuit`` segment by segment (see module docstring).

    ``target`` is a :class:`~quest_tpu.environment.QuESTEnv` (a fresh
    |0...0> register is created over it) or an existing
    :class:`~quest_tpu.registers.Qureg`. Returns the final register; the
    last generation under ``checkpoint_dir`` holds the completed state
    (cursor == len(tape))."""
    if keep < 1:
        raise _qt304(f"keep must be >= 1, got {keep}")
    qureg = _as_qureg(circuit, target)
    nsv = (2 if circuit.is_density_matrix else 1) * circuit.num_qubits
    cuts = segment_plan(circuit._tape, nsv, every_n_items)
    os.makedirs(checkpoint_dir, exist_ok=True)
    telemetry.event("segmented.run", segments=len(cuts) - 1,
                    items=len(circuit._tape))
    return _execute(circuit, qureg, cuts, 0, checkpoint_dir,
                    every_n_items, keep)


def resume_segmented(circuit: Circuit, checkpoint_dir: str,
                     env: QuESTEnv, *,
                     every_n_items: int | None = None,
                     keep: int = 2) -> Qureg:
    """Restart a :func:`run_segmented` execution from the last VERIFIED
    generation under ``checkpoint_dir`` (see module docstring), replaying
    the remaining segments; returns the final register. ``every_n_items``
    defaults to the value recorded in the manifest, so resumed
    checkpointing continues on the original cadence."""
    gens = _gen_dirs(checkpoint_dir)
    if not gens:
        raise QuESTError(
            f"no checkpoint generations under {checkpoint_dir!r}",
            "resume_segmented")
    from ..checkpoint import loadQureg, verify_snapshot

    chosen = manifest = None
    for gen in reversed(gens):
        mpath = os.path.join(gen, _MANIFEST)
        try:
            with open(mpath) as f:
                m = json.load(f)
            verify_snapshot(gen)
        except QuESTChecksumError as e:
            # silent payload corruption, specifically: name both CRCs and
            # count it apart from structural rejections
            _qt305_crc(gen, e)
            telemetry.inc("segmented_resume_total",
                          outcome="skipped_corrupt")
            continue
        except (OSError, ValueError, QuESTError) as e:
            _qt305(gen, str(e))
            telemetry.inc("segmented_resume_total", outcome="rejected_gen")
            continue
        if m.get("fingerprint") != circuit.fingerprint():
            raise QuESTError(
                f"checkpoint generation {os.path.basename(gen)!r} belongs "
                f"to a different circuit (fingerprint mismatch)",
                "resume_segmented")
        chosen, manifest = gen, m
        break
    if chosen is None:
        telemetry.inc("segmented_resume_total", outcome="no_verified_gen")
        raise QuESTError(
            f"no generation under {checkpoint_dir!r} passed verification",
            "resume_segmented")

    qureg = loadQureg(chosen, env)
    cursor = int(manifest["cursor"])
    n_items = (int(manifest.get("every_n_items", 1))
               if every_n_items is None else every_n_items)
    telemetry.inc("segmented_resume_total", outcome="verified")
    telemetry.event("segmented.resume", cursor=cursor,
                    generation=os.path.basename(chosen))
    if cursor >= len(circuit._tape):
        return qureg
    nsv = (2 if circuit.is_density_matrix else 1) * circuit.num_qubits
    cuts = segment_plan(circuit._tape, nsv, n_items)
    if cursor not in cuts:
        raise QuESTError(
            f"manifest cursor {cursor} is not a segment boundary of this "
            f"circuit at every_n_items={n_items}", "resume_segmented")
    return _execute(circuit, qureg, cuts, cursor, checkpoint_dir,
                    n_items, keep)
