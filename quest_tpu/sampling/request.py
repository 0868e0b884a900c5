"""One-dispatch sampling requests: circuit + shots + Pauli-sum expectation.

The round-18 ``request_executable`` collapsed a request's circuit to ONE
device program but still ended with a 2^N amplitude transfer the client
never wanted. The builders here compose the terminal readout INTO that
program as its traceable ``reduce(amps)`` stage, so a full request --
state evolution, S measurement shots, a Pauli-sum expectation -- is one
dispatched program (``device_dispatch_total{route=request}`` delta == 1)
and the host sees O(S) bits + one scalar, never the amplitudes
(``sample_host_transfer_bytes`` records what actually crossed).

``shots_default()`` supplies the S when the caller does not:
``QUEST_SHOTS`` env, warn-once QT801 on malformed values.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp
import numpy as np

from .. import cache as _ec
from .. import segments
from .. import telemetry
from .. import validation as V
from ..calculations import expec_pauli_sum_amps
from ..circuits import _amps_mesh, named_program
from ..environment import active_pallas_mesh, pallas_mesh
from ..parallel import scheduler as _dist
from ..params import _SEED, bind as _bind
from ..validation import QuESTError
from . import sampler as _sampler

if TYPE_CHECKING:
    from ..circuits import Circuit
    from ..registers import Qureg

__all__ = ["shots_default", "sample_reduce", "expectation_reduce",
           "sample_request", "sampleQureg", "to_host", "DEFAULT_SHOTS"]

#: shot count when neither an argument nor QUEST_SHOTS says otherwise.
DEFAULT_SHOTS = 1024

_ENV_WARNED: set = set()


def shots_default() -> int:
    """Shot count from ``QUEST_SHOTS`` (malformed or sub-1 values warn
    once as QT801 and fall back to ``DEFAULT_SHOTS``)."""
    from ..analysis.diagnostics import parse_env_int
    return parse_env_int("QUEST_SHOTS", DEFAULT_SHOTS, minimum=1,
                         code="QT801", warned=_ENV_WARNED,
                         noun="shot count")


def _record_transfer(out) -> None:
    """Gauge the bytes a sampling result moves to the host: O(S) shot
    words + O(1) scalars -- the acceptance evidence against the 2^N
    amplitude transfer the pre-round-19 readout paid."""

    leaves = jax.tree_util.tree_leaves(out)
    telemetry.set_gauge(
        "sample_host_transfer_bytes",
        sum(int(np.asarray(x).nbytes) for x in leaves))


def to_host(res):
    """Materialise a sampling-request result on the host (numpy leaves)
    and gauge the bytes that crossed: the result-side half of the
    submit/result host contract."""

    out = jax.tree_util.tree_map(np.asarray, res)
    _record_transfer(out)
    return out


def sample_reduce(*, n: int, targets, shots: int, site: int = 0,
                  density: bool = False):
    """A traceable ``reduce(amps, seed)`` producing the (S,) int32 shot
    table over ``targets`` -- the terminal stage of a one-dispatch
    sampling request. Cached per spec so its identity is stable in the
    request-executable LRU key."""
    targets = tuple(int(t) for t in targets)
    key = ("sample_reduce", n, targets, int(shots), int(site),
           bool(density))

    def build():
        fn = _sampler.sample_density if density \
            else _sampler.sample_statevec

        def reduce(amps, seed):
            return fn(amps, n=n, targets=targets, shots=int(shots),
                      seed=seed, site=site)

        return reduce

    return _ec.executables().get_or_create(key, build)


def expectation_reduce(*, n: int, codes, coeffs, density: bool = False):
    """A traceable ``reduce(amps)`` computing ``sum_t c_t <P_t>`` -- the
    ``calcExpecPauliSum`` contraction lowered onto the fused request path
    (per-term Pauli-product segments chained inside the one program,
    reusing ``calculations._pauli_prod_amps``). Cached per spec."""
    codes_t = tuple(tuple(int(c) for c in row) for row in
                    np.asarray(codes, dtype=np.int64).reshape(-1, n))
    coeffs_t = tuple(float(c) for c in np.asarray(coeffs,
                                                  dtype=np.float64))
    if len(codes_t) != len(coeffs_t):
        raise QuESTError(
            f"expectation_reduce: {len(codes_t)} Pauli terms vs "
            f"{len(coeffs_t)} coefficients")
    key = ("expec_reduce", n, codes_t, coeffs_t, bool(density))

    def build():
        def reduce(amps):
            cf = jnp.asarray(np.asarray(coeffs_t, dtype=np.float64),
                             dtype=amps.dtype)
            return expec_pauli_sum_amps(amps, cf, codes=codes_t, n=n,
                                        density=density)

        return reduce

    return _ec.executables().get_or_create(key, build)


def sample_request(circuit: Circuit, *, targets=None,
                   shots: int | None = None, site: int = 0,
                   pauli_codes=None, coeffs=None, donate: bool = True):
    """The WHOLE sampling request as ONE dispatched program: every
    frame-identity segment of ``circuit``, the S-shot sampler over
    ``targets`` (default: all qubits), and optionally the Pauli-sum
    expectation of (``pauli_codes``, ``coeffs``) -- composed via
    :func:`quest_tpu.segments.request_executable` with the state donated
    end-to-end. Returns an executable called as ``fn(amps, seed)``
    yielding ``{"shots": (S,) int32}`` (plus ``"expec"`` when a Pauli
    sum was given); one call counts exactly one
    ``device_dispatch_total{route="request"}``.

    ``shots`` defaults to :func:`shots_default` (QUEST_SHOTS). The seed
    is a RUNTIME argument -- S different seeds replay one executable --
    and the shot count is static shape. The reduce closures are
    LRU-cached per spec, so repeated builds of the same request spec
    share one compiled program."""
    if shots is None:
        shots = shots_default()
    if int(shots) < 1:
        raise QuESTError(f"shots must be >= 1, got {shots}")
    n = circuit.num_qubits
    density = circuit.is_density_matrix
    if targets is None:
        targets = tuple(range(n))
    targets = tuple(int(t) for t in targets)
    shot_red = sample_reduce(n=n, targets=targets, shots=int(shots),
                             site=site, density=density)
    expec_red = None
    if pauli_codes is not None or coeffs is not None:
        if pauli_codes is None or coeffs is None:
            raise QuESTError(
                "sample_request needs both pauli_codes and coeffs (or "
                "neither)")
        expec_red = expectation_reduce(n=n, codes=pauli_codes,
                                      coeffs=coeffs, density=density)

    key = ("sample_request", circuit._cache_token, shot_red, expec_red,
           donate)

    def build():
        def reduce(amps, seed):
            out = {"shots": shot_red(amps, seed)}
            if expec_red is not None:
                out["expec"] = expec_red(amps)
            return out

        def coerce(seed):
            return (seed if hasattr(seed, "dtype")
                    else np.asarray(int(seed), dtype=np.uint32))

        lifted = circuit.lifted()
        seed_positions = tuple(
            i for i, s in enumerate(lifted.slots)
            if s.kind == _SEED and s.name is not None)
        if not lifted.slots:
            # constant tape: the round-18 request chain, with the sampler
            # (and its runtime seed) as the terminal reduce stage
            inner = segments.request_executable(circuit, donate=donate,
                                                reduce=reduce)

            def fn(amps, seed, _inner=inner):
                return _inner(amps, coerce(seed))

            fn.num_segments = inner.num_segments
            fn.num_dispatches = 1
            return fn

        # slotted tape (Params / lifted constants): ONE jitted program of
        # the lifted whole-tape replay + reduce. Every NAMED seed slot
        # (e.g. applyMidMeasurement's P("...") draw seed) binds to the
        # request's runtime seed -- one uint32 drives every mid-circuit
        # draw (per-site streams via fold_in) AND the terminal shot
        # table, so a request replays bit-identically from its seed
        # alone. Other named Params must be pre-bound on the tape (this
        # route takes no params dict; use the Engine for those).

        base_values = _bind(lifted, {lifted.slots[i].name: 0
                                     for i in seed_positions})
        body = circuit._replay_fn(lifted)

        def whole(amps, seed, _body=body, _base=base_values,
                  _pos=frozenset(seed_positions), _reduce=reduce):
            values = tuple(seed if i in _pos else v
                           for i, v in enumerate(_base))
            return _reduce(_body(amps, values), seed)

        inner = jax.jit(named_program(whole, circuit, "sample"),
                        donate_argnums=(0,) if donate else ())
        sched = _dist.active()
        mesh = sched.mesh if sched else None
        pmesh = active_pallas_mesh()

        def fn(amps, seed, _inner=inner, _mesh=mesh, _pmesh=pmesh):
            pm = _pmesh if _pmesh is not None else _amps_mesh(amps)
            telemetry.inc("device_dispatch_total", route="request")
            with _dist.explicit_mesh(_mesh), pallas_mesh(pm):
                return _inner(amps, coerce(seed))

        fn.num_segments = 1
        fn.num_dispatches = 1
        return fn

    return _ec.executables().get_or_create(key, build)


def sampleQureg(qureg: Qureg, targets=None, shots: int | None = None,
                seed: int = 0, site: int = 0) -> np.ndarray:
    """Eager convenience: draw ``shots`` outcome samples over
    ``targets`` (default: all qubits) of ``qureg``'s CURRENT state as
    one on-device program; returns the (S,) int32 shot table
    (targets[0] = LSB of each outcome). The register is not modified.
    Only the table crosses to the host -- O(S) words, gauge-recorded as
    ``sample_host_transfer_bytes``."""
    func = "sampleQureg"
    n = qureg.num_qubits_represented
    if targets is None:
        targets = tuple(range(n))
    V.validate_multi_targets(qureg, targets, func)
    if shots is None:
        shots = shots_default()
    if int(shots) < 1:
        raise QuESTError(f"shots must be >= 1, got {shots}")
    table = _sampler.sample_jit(
        qureg.amps, np.asarray(int(seed), dtype=np.uint32), n=n,
        targets=tuple(int(t) for t in targets), shots=int(shots),
        site=int(site), density=qureg.is_density_matrix)
    out = np.asarray(table)
    _record_transfer(out)
    telemetry.inc("sample_shots_total", int(shots))
    return out
