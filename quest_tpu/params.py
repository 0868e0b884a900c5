"""Parameterized tapes: runtime gate angles instead of trace-time constants.

The reference (and the eager API here) receives every rotation angle as a
host float and bakes the resulting 2x2 matrix into the kernel launch; a
``Circuit`` tape goes further and bakes it into the jitted XLA program, so a
parameter sweep (VQE/QAOA, or many users submitting variants of one ansatz)
re-runs the whole trace/fuse/compile pipeline per parameter set -- at 34q
the compile dwarfs the execution it guards.

This module makes values *runtime arguments* of one compiled replay:

- :class:`Param` (alias ``P``) is a named placeholder recordable anywhere a
  gate angle or ``Complex`` scalar goes on a tape:
  ``circ.rotateZ(0, P("theta"))``.
- :func:`lift_tape` canonicalises a recorded tape into a :class:`LiftedTape`
  whose *value slots* cover every ``Param`` AND every plain float/complex
  constant sitting at a liftable position (the ``_LIFTABLE`` registry below:
  the angle/Complex-scalar arguments of the rotation and phase family).
  Constants elsewhere (unitary matrices, channel probabilities, qubit
  indices) stay baked structure.
- :func:`materialize_entry` substitutes the slot values back at replay time,
  inside the jit trace, so gate matrices are assembled from *traced* scalars
  (``matrices.py`` carries the traced assembly branches) and one executable
  replays for arbitrary value vectors -- including through a fused plan
  (plan structure never depends on values): in a dense plan parameterized
  entries join window blocks whose matrices are composed inside the program
  (fusion._apply_deferred_block), in a Pallas plan they ride as
  apply-time-assembled barriers between the static kernel runs.

Two tapes that differ only in lifted values produce the SAME
:func:`quest_tpu.cache.structure_fingerprint`, which is what lets the
executable cache serve "same ansatz, different angles" traffic with zero
recompiles (docs/serving.md).

Besides the ``'real'``/``'complex'`` angle slots there is a third kind,
``'seed'``: an integer PRNG-seed slot (uint32 on device) carried by
trajectory-noise entries (quest_tpu/trajectories). Seed positions lift
*plain ints* too -- a seed is always a runtime value, never structure -- so
T trajectories of one noisy circuit share a single compiled replay and
differ only in their stacked seed lanes (docs/trajectories.md).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp
import numpy as np

from .validation import QuESTError

__all__ = ["Param", "P", "LiftedTape", "Slot", "ParamExecutable",
           "lift_tape", "lift_slot_census", "bind", "materialize_entry",
           "materialize_tape", "has_params", "is_value"]


class Param:
    """Named placeholder for a runtime gate parameter.

    Record it anywhere a gate angle / ``Complex`` scalar goes::

        from quest_tpu.engine import P
        circ.rotateZ(0, P("theta"))

    The value is supplied per execution (``Engine.submit({"theta": 0.3})``
    or ``Circuit.parameterized()(amps, {"theta": 0.3})``); the compiled
    executable is value-independent. The same name may appear in several
    slots -- every occurrence receives the one bound value.
    """
    __slots__ = ("name",)

    def __init__(self, name: str):
        if not isinstance(name, str) or not name:
            raise ValueError("Param name must be a non-empty string")
        self.name = name

    def __repr__(self):
        return f"P({self.name!r})"

    def __eq__(self, other):
        return isinstance(other, Param) and other.name == self.name

    def __hash__(self):
        return hash(("quest_tpu.Param", self.name))


#: short alias matching the ISSUE's recording idiom: ``rotateZ(q, P("t"))``
P = Param


#: tape-arg positions (qureg excluded) and kwarg names whose values are
#: liftable runtime scalars, per API function: the angle / Complex-scalar
#: arguments of the rotation, phase and compact-unitary family. Everything
#: else a tape entry carries (targets, controls, unitary matrices, channel
#: probabilities -- whose superoperators are assembled host-side) is
#: structure and stays baked.
_REAL, _CPLX, _SEED = "real", "complex", "seed"
_LIFTABLE = {
    # trajectory noise: the per-trajectory PRNG seed is a runtime uint32
    # slot -- T trajectories replay one compiled program with T seed
    # streams stacked by the engine's vmap batcher (quest_tpu/trajectories)
    "applyTrajectoryKraus": {2: _SEED, "seed": _SEED},
    # mid-circuit measurement (round 19, sampling.measure): the draw seed
    # is the same runtime uint32 slot kind -- S sampled requests replay
    # one compiled program with S seed streams
    "applyMidMeasurement": {1: _SEED, "seed": _SEED},
    "phaseShift": {1: _REAL, "angle": _REAL},
    "controlledPhaseShift": {2: _REAL, "angle": _REAL},
    "multiControlledPhaseShift": {1: _REAL, "angle": _REAL},
    "rotateX": {1: _REAL, "angle": _REAL},
    "rotateY": {1: _REAL, "angle": _REAL},
    "rotateZ": {1: _REAL, "angle": _REAL},
    "rotateAroundAxis": {1: _REAL, "angle": _REAL},
    "controlledRotateX": {2: _REAL, "angle": _REAL},
    "controlledRotateY": {2: _REAL, "angle": _REAL},
    "controlledRotateZ": {2: _REAL, "angle": _REAL},
    "controlledRotateAroundAxis": {2: _REAL, "angle": _REAL},
    "multiRotateZ": {1: _REAL, "angle": _REAL},
    "multiControlledMultiRotateZ": {2: _REAL, "angle": _REAL},
    "multiRotatePauli": {2: _REAL, "angle": _REAL},
    "multiControlledMultiRotatePauli": {3: _REAL, "angle": _REAL},
    "compactUnitary": {1: _CPLX, 2: _CPLX, "alpha": _CPLX, "beta": _CPLX},
    "controlledCompactUnitary": {2: _CPLX, 3: _CPLX,
                                 "alpha": _CPLX, "beta": _CPLX},
}


def is_value(x) -> bool:
    """True for the scalar types the lifter treats as runtime values when
    they sit at a liftable position: Params, floats and complex numbers
    (ints and bools are always structure -- they index qubits)."""
    if isinstance(x, Param):
        return True
    if isinstance(x, bool) or isinstance(x, (int, np.integer)):
        return False
    return isinstance(x, (float, complex, np.floating, np.complexfloating))


def _is_seed_value(x) -> bool:
    """Lifting rule for ``'seed'``-kind positions: unlike angle positions
    (where ints are structure), a plain integer at a seed position IS the
    runtime value -- it lifts to an anonymous uint32 slot so plan structure
    never depends on the seed."""
    if isinstance(x, Param):
        return True
    return (isinstance(x, (int, np.integer))
            and not isinstance(x, bool))


def has_params(args, kwargs=None) -> bool:
    """True when a tape entry's arguments carry a :class:`Param` anywhere
    (one level into tuples/lists) -- the fusion planner's pre-check: such
    entries have no matrix at plan time (capture._entry_has_params)."""
    items = list(args) + list((kwargs or {}).values())
    for x in items:
        if isinstance(x, Param):
            return True
        if isinstance(x, (tuple, list)) and any(
                isinstance(e, Param) for e in x):
            return True
    return False


@dataclass(frozen=True)
class Slot:
    """One runtime value slot of a lifted tape. ``name`` is None for an
    anonymous slot (a lifted constant, replayed with ``default`` unless the
    caller rebinds the whole vector); named slots come from :class:`Param`
    placeholders and MUST be bound at execution."""
    index: int
    kind: str                      # 'real' | 'complex' | 'seed'
    name: Optional[str] = None
    default: Optional[complex] = None


class _SlotRef:
    """Placeholder living in a lifted entry's argument template."""
    __slots__ = ("index",)

    def __init__(self, index: int):
        self.index = index

    def __repr__(self):
        return f"<slot {self.index}>"


@dataclass(frozen=True)
class LiftedTape:
    """A tape with its runtime values factored out: ``entries`` are
    ``(fn, args, kwargs)`` templates holding :class:`_SlotRef` markers,
    ``slots`` describes each value position in template order."""
    entries: tuple
    slots: tuple

    @property
    def param_names(self) -> tuple:
        """Ordered unique Param names (first-appearance order)."""
        seen = []
        for s in self.slots:
            if s.name is not None and s.name not in seen:
                seen.append(s.name)
        return tuple(seen)


def lift_tape(tape) -> LiftedTape:
    """Factor a recorded tape's runtime values into slots (see module
    docstring for the lifting rule). A :class:`Param` at a position the
    registry doesn't cover is an error -- there is no traced assembly route
    for it (e.g. a channel probability, whose superoperator is built
    host-side)."""
    entries = []
    slots: list[Slot] = []

    def lift_value(v, kind):
        if isinstance(v, Param):
            slots.append(Slot(len(slots), kind, name=v.name))
        else:
            slots.append(Slot(len(slots), kind, default=v))
        return _SlotRef(len(slots) - 1)

    def liftable(v, kind):
        if kind is None:
            return False
        if kind == _SEED:
            return _is_seed_value(v)
        return is_value(v)

    for fn, args, kwargs in tape:
        spec = _LIFTABLE.get(getattr(fn, "__name__", ""), {})
        if hasattr(fn, "_lift_positions"):
            # a fused block whose matrix is assembled in the program
            # (fusion._apply_deferred_block) says where its values ride
            spec = fn._lift_positions(args)
        new_args = []
        for i, v in enumerate(args):
            kind = spec.get(i)
            if liftable(v, kind):
                new_args.append(lift_value(v, kind))
            elif isinstance(v, Param) or (
                    isinstance(v, (tuple, list))
                    and any(isinstance(e, Param) for e in v)):
                raise QuESTError(
                    f"Param is not supported at argument {i} of "
                    f"'{getattr(fn, '__name__', fn)}' -- only gate angles "
                    "and Complex scalars of the rotation/phase family can "
                    "be runtime parameters")
            else:
                new_args.append(v)
        new_kwargs = {}
        for k, v in kwargs.items():
            kind = spec.get(k)
            if liftable(v, kind):
                new_kwargs[k] = lift_value(v, kind)
            elif isinstance(v, Param):
                raise QuESTError(
                    f"Param is not supported for keyword '{k}' of "
                    f"'{getattr(fn, '__name__', fn)}'")
            else:
                new_kwargs[k] = v
        entries.append((fn, tuple(new_args), new_kwargs))
    return LiftedTape(tuple(entries), tuple(slots))


def lift_slot_census(tape) -> tuple[int, int]:
    """``(anonymous, named)`` slot counts of ``lift_tape(tape)``: how many
    liftable positions carry constants vs ``Param`` placeholders. Anonymous
    slots are the executable-cache hazard -- structure-equal circuits that
    differ only in those constants cannot share a compiled program
    (cache.structure_fingerprint bakes them) -- and the count is
    what the tape linter reports as QT003 (quest_tpu/analysis)."""
    slots = lift_tape(tuple(tape)).slots
    anon = sum(1 for s in slots if s.name is None)
    return anon, len(slots) - anon


def bind(lifted: LiftedTape, params=None, device: bool = True) -> tuple:
    """Resolve a lifted tape's slots to a values tuple -- the ``values``
    argument of the parameterized replay.

    ``params`` maps Param names to numbers (missing names raise); anonymous
    slots replay their recorded defaults. With ``device=True`` (the
    executable hot path) scalars are coerced to NUMPY 0-d arrays at the
    process float/complex width (f64/c128 under jax x64, else f32/c64),
    seeds to uint32, so the jit signature is stable across calls. Numpy,
    not jnp, on purpose: ``jnp.asarray(v, dtype=...)`` enqueues a
    convert_element_type COMPUTATION per scalar on the submitter's
    thread, behind whatever the device is running; a numpy scalar costs
    microseconds and has the identical abstract value (no retrace). The
    single-request executable takes the tuple as it is, one transfer a
    slot at call time; the Engine's batch program takes the same scalars
    packed one array per slot kind (:func:`_pack_rows`).
    ``device=False`` returns plain Python scalars (a tape materialized
    with them replays through the constant/numpy assembly path -- the
    bit-identity baseline the tests compare against)."""
    params = params or {}
    rdt = jnp.result_type(float)
    cdt = jnp.result_type(complex)
    out = []
    for s in lifted.slots:
        if s.name is not None:
            if s.name not in params:
                missing = sorted({t.name for t in lifted.slots
                                  if t.name is not None
                                  and t.name not in params})
                raise QuESTError(
                    f"missing values for Params {missing}; got "
                    f"{sorted(params)}")
            v = params[s.name]
        else:
            v = s.default
        if s.kind == _SEED:
            # seeds are integer PRNG material: uint32 on the hot path (a
            # stable jit signature the vmap batcher can stack per lane), a
            # plain int on the host/constant path. int() first so the
            # engine's warmup binding (0.0 for every name) coerces cleanly.
            out.append(np.asarray(int(v), dtype=np.uint32) if device
                       else int(v))
        elif device:
            out.append(np.asarray(v, dtype=cdt if s.kind == _CPLX else rdt))
        else:
            out.append(complex(v) if s.kind == _CPLX else float(v))
    return tuple(out)


def _pack_layout(lifted: LiftedTape) -> tuple:
    """How a lifted tape's values cross into a batched program: one array
    per slot kind the tape HAS, in the order real, complex, seed, each
    ``(kind, slot indices in slot order)``. A kind without a slot has no
    entry (and the program no argument for it)."""
    packs = []
    for kind in (_REAL, _CPLX, _SEED):
        cols = tuple(s.index for s in lifted.slots if s.kind == kind)
        if cols:
            packs.append((kind, cols))
    return tuple(packs)


def _pack_rows(packs: tuple, values: tuple) -> tuple:
    """One bound values tuple (``bind(..., device=True)``) as one row per
    pack of ``packs``: the row of kind k holds the tuple's k-kind scalars
    in slot order, at the width ``bind`` gave them. Rows of several
    requests stack into the batched program's arguments."""
    return tuple(np.array([values[i] for i in cols]) for _, cols in packs)


def _unpack_columns(packs: tuple, packed: tuple) -> tuple:
    """The inverse of :func:`_pack_rows` inside a traced program: from the
    stacked packs (one ``(batch, n_kind)`` array a kind) the per-slot
    tuple of ``(batch,)`` columns, by static index, in slot order -- what
    a vmapped replay takes as its values."""
    out = {}
    for (_, cols), arr in zip(packs, packed):
        for j, i in enumerate(cols):
            out[i] = arr[:, j]
    return tuple(out[i] for i in range(len(out)))


class ParamExecutable:
    """A compiled parameterized replay bound to one circuit's slot layout.

    The underlying ``fn(amps, values)`` may be SHARED across structure-equal
    circuits (it comes out of the executable LRU keyed by the structure
    fingerprint); this wrapper carries the owning circuit's
    :class:`LiftedTape` so named Params bind and anonymous slots default to
    that circuit's own recorded constants.
    """

    def __init__(self, fn, lifted: LiftedTape, fingerprint: str):
        self._fn = fn
        self.__name__ = fn.__name__      # the jitted program's
        self.lifted = lifted
        self.fingerprint = fingerprint

    @property
    def param_names(self) -> tuple:
        return self.lifted.param_names

    def bind(self, params=None) -> tuple:
        """Resolve ``params`` (Param name -> number) to the values tuple."""
        return bind(self.lifted, params)

    def __call__(self, amps, params=None):
        """Replay onto ``amps`` (donated) with the given Param values."""
        return self._fn(amps, self.bind(params))

    def with_values(self, amps, values):
        """Replay with an already-bound values tuple (the Engine hot path)."""
        return self._fn(amps, values)


def materialize_entry(entry, values):
    """Substitute a lifted entry's slot markers with the bound (possibly
    traced) scalars: ``(fn, args, kwargs)`` ready to replay."""
    fn, args, kwargs = entry
    args = tuple(values[a.index] if isinstance(a, _SlotRef) else a
                 for a in args)
    if kwargs:
        kwargs = {k: values[v.index] if isinstance(v, _SlotRef) else v
                  for k, v in kwargs.items()}
    return fn, args, kwargs


def materialize_tape(lifted: LiftedTape, values) -> list:
    """The lifted tape with every slot substituted -- host scalars (from
    ``bind(..., device=False)``) give back a plain constant tape."""
    return [materialize_entry(e, values) for e in lifted.entries]
