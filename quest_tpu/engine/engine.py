"""Micro-batched ensemble execution of parameterized circuits.

The reference serves exactly one caller: every gate is an eager kernel
launch against one register. The serving shape this module targets is the
opposite -- many requests that are *variants of one circuit structure*
(a VQE/QAOA parameter sweep, or many users submitting the same ansatz with
their own angles) arriving concurrently. Three mechanisms make that cheap:

- **One executable, many parameter vectors**: the engine replays its
  circuit through the parameterized executable
  (:meth:`quest_tpu.circuits.Circuit.parameterized`), so a warm submit
  triggers zero retraces -- values are runtime arguments. A raw tape is
  fused first (:meth:`Engine._plan_program`): its Param gates join dense
  window blocks whose matrices are composed inside the program, so a
  request makes one pass over its state per block, not per gate.
- **Micro-batching**: ``submit(params)`` returns a
  :class:`concurrent.futures.Future` immediately; a background batcher
  coalesces pending requests up to ``max_batch`` within a ``max_delay_ms``
  window and dispatches them together. Unsharded registers run every
  dispatch as the ONE fixed-shape ``vmap``-over-params program (``B``
  states evolve in one fused XLA program -- the ensemble analogue of
  cuQuantum's batched ``custatevecApplyMatrix``), short batches padded to
  ``max_batch``: one executable ever compiles, and a request computes the
  same bits whether or not it was coalesced (batch lanes are independent
  and identical). A batch crosses to the device in one piece: the
  initial state and one packed array per slot kind in, the lanes as the
  program's own outputs (ONE array of them where the finalize states a
  lane as a vector: :meth:`Engine._execB`). Sharded registers replay
  sequentially with donated buffers inside the one dispatch instead (a
  (B, 2, N) batch axis would fight the amplitude sharding for the mesh).
- **Executable reuse across structures**: executables are fetched from the
  process-global LRU (:mod:`quest_tpu.cache`) per dispatch, keyed by
  the circuit's structure fingerprint -- a second Engine over a
  structure-equal circuit compiles nothing (``plan_cache_hit_total``).

Telemetry (docs/observability.md): ``engine_requests_total``,
``engine_batches_total{mode=vmap|sequential}``, ``engine_batch_size`` and
``engine_request_latency_seconds`` histograms, ``engine_queue_depth``
gauge, ``engine_trace_total{kind=param_replay}`` (one increment per jit
trace of the replay -- the retrace detector tests assert on).

Failure semantics (ISSUE 7 -- request-level, like Orca-style serving):

- **Deadlines**: ``submit(params, timeout=)`` sets a wall-clock deadline;
  requests still queued past it resolve with
  :class:`~quest_tpu.resilience.QuESTTimeoutError` instead of dispatching
  (``engine_request_timeouts_total``).
- **Backpressure**: the queue is bounded (``queue_max`` ctor arg /
  ``QUEST_ENGINE_QUEUE_MAX`` env); a full queue raises
  :class:`~quest_tpu.resilience.QuESTBackpressureError` at submit
  (``engine_backpressure_total``) rather than growing unboundedly.
- **Poisoned-batch bisection**: when a batched dispatch fails, the
  batcher bisects the batch through the SAME padded executable
  (``engine_bisections_total``) -- healthy requests complete with
  bit-identical results (vmap lanes are independent), and each poisoned
  request gets its own exception. The ``engine.request`` fault-injection
  site (quest_tpu.resilience.faultinject) pins injected poison to a
  request at submit time, which is how the isolation tests drive this.
- **Typed cancellation**: ``close(drain=False)`` resolves still-queued
  futures with :class:`~quest_tpu.resilience.QuESTCancelledError` --
  a waiter blocked on ``result()`` always wakes with a typed error.

Health states (ISSUE 8 -- engine-level, fed by the integrity machinery):

- :meth:`health` is ``healthy`` | ``degraded`` | ``quarantined``.
  A sentinel breach on a dispatch result (``QUEST_SENTINEL`` armed,
  :mod:`quest_tpu.resilience.sentinel` -- the corrupt result is NEVER
  served; its future resolves with
  :class:`~quest_tpu.resilience.QuESTIntegrityError`) marks the engine
  ``degraded``; a second breach, or a watchdog deadline expiry
  (``QUEST_WATCHDOG_MS`` around the whole dispatch, typed
  :class:`~quest_tpu.resilience.QuESTHangError`), marks it
  ``quarantined``.
- A quarantined engine rejects submits through the existing
  backpressure path (``QuESTBackpressureError``,
  ``engine_backpressure_total{reason=quarantined}``) until the operator
  calls :meth:`revive` -- in-flight and already-queued work still
  completes, so quarantine sheds load without dropping accepted futures.
- Three consecutive clean dispatches heal ``degraded`` back to
  ``healthy``; transitions count
  ``engine_health_transitions_total{from,to}``.

Async dispatch pipeline (round 18 -- the host-side twin of PR 8's
prologue/steady-state/epilogue collective pipeline):

- **Host/device overlap**: with ``async_depth >= 1`` (ctor arg /
  ``QUEST_ASYNC_DEPTH``, default 2, QT310 warn-once) the batcher never
  blocks between the queue and the device -- it issues the traced vmap
  program for batch k, parks the in-flight result in a bounded
  **completion ring**, and immediately returns to coalescing batch k+1
  while k executes. Ring entries retire (device sync + per-lane future
  resolution) when the ring is full, when the queue idles, and at
  close; a retire-time device error/hang/breach is attributed to the
  RING ENTRY's requests, never to the batch being issued
  (``engine_async_retires_total{outcome}``, ``engine_async_inflight``).
  ``async_depth=0`` restores strictly synchronous dispatch -- the A/B
  baseline; both routes run the identical padded executable, so async
  and sync results are bit-identical by construction.
- **Serial issue on timeshared backends**: XLA:CPU executes
  concurrently enqueued programs by timesharing the same host cores
  (no private execution stream), so running two batch programs ahead
  of each other costs ~20% per batch -- more than the host time it
  hides. On CPU, ring admission therefore device-syncs the in-flight
  head before the next issue and -- when a spare host core exists --
  defers its RESOLUTION until just after it: assembly and coalescing
  overlap device execution on the way in, the sentinel gate and future
  resolution on the way out, and the device never timeshares two
  batches. On a single-core host there is nothing to overlap (the
  "overlapped" host thread is starved by the execution thread), so
  the head resolves before the issue. Admission and settling run
  outside the dispatch watchdog; each blocking sync is bounded by its
  own ``engine.retire`` deadline and charged to the entry it retires.
- **Continuous batching** (Orca, PAPERS.md): while a batch is in
  flight, the device -- not the ``max_delay_ms`` timer -- paces the
  window: every arrival restarts the timer, and the window closes when
  it is full, when ``max_delay_ms`` passed without an arrival, or at
  once when the batch ahead is done; a late submit joins the NEXT vmap
  window instead of waiting out a full coalescing tick (the padded
  fixed-shape program makes the join point well-defined).

Lifecycle: construct, optionally :meth:`warmup`, ``submit``/``run``, then
:meth:`close` -- which drains the queue AND the completion ring (every
accepted future resolves) and joins the batcher thread. The engine is
also a context manager.
"""

from __future__ import annotations

import math
import os
import threading
import time
from collections import deque
from concurrent.futures import Future

import jax
import jax.numpy as jnp
import numpy as np

from .. import cache as _cache
from .. import fusion
from .. import planner
from .. import telemetry
from ..analysis.diagnostics import (emit_findings, make_finding,
                                    parse_env_int)
from ..circuits import Circuit, named_program
from ..environment import pallas_mesh
from ..gradients import grad_reduce
from ..gradients.adjoint import gatewise
from ..ops import init as ops_init
from ..parallel import scheduler as _dist
from ..params import (_SEED, _pack_layout, _pack_rows, _unpack_columns,
                      bind)
from ..precision import real_dtype
from ..resilience import faultinject as _faults
from ..resilience import guard as _guard
from ..resilience import sentinel as _sentinel
from ..resilience import sync as _sync
from ..resilience import watchdog as _watchdog
from ..resilience.errors import (PoisonedRequestFault, QuESTBackpressureError,
                                 QuESTCancelledError, QuESTHangError,
                                 QuESTIntegrityError, QuESTTimeoutError,
                                 TransientFault)
from ..validation import QuESTError

__all__ = ["Engine", "HEALTH_STATES"]

#: engine health states, healthiest first
HEALTH_STATES = ("healthy", "degraded", "quarantined")

#: consecutive clean dispatches that heal ``degraded`` -> ``healthy``
_HEAL_STREAK = 3

#: how often an open coalescing window looks whether the device has
#: finished the batch ahead (seconds); arrivals wake it at once
_WINDOW_POLL_S = 0.0005


class _Request:
    """One queued parameter set: bound values in the form the engine's
    mode dispatches (the per-slot tuple where batches replay
    sequentially; one row per slot kind, the request's lane of the batch
    program's arguments, where they are vmapped), the caller's future,
    the enqueue timestamp, an optional wall-clock deadline, the injected
    poison kind pinned at submit time (None on healthy requests), and the
    request's trace context (None whenever tracing is off)."""
    __slots__ = ("values", "fut", "t0", "deadline", "poison", "trace")

    def __init__(self, values: tuple, fut: Future, t0: float,
                 deadline: float | None, poison: str | None,
                 trace=None):
        self.values = values
        self.fut = fut
        self.t0 = t0
        self.deadline = deadline
        self.poison = poison
        self.trace = trace


class _Inflight:
    """One completion-ring entry: the issued batch's in-flight result, its
    requests, the issuing dispatch's ordinal (the sentinel tick), and
    ``synced``: whether a device sync has proved the batch complete (the
    serial-issue admission syncs an entry and leaves its resolution for
    after the next issue)."""
    __slots__ = ("out", "batch", "tick", "synced")

    def __init__(self, out, batch: list, tick: int):
        self.out = out
        self.batch = batch
        self.tick = tick
        self.synced = False


def _plan_items(circuit) -> int:
    """How many entries of ``circuit``'s tape are items of a fusion plan
    (blocks, kernel runs, frame swaps) rather than recorded gates."""
    return sum(not isinstance(item, tuple)
               for item in fusion.plan_from_tape(circuit._tape).items)


_ASYNC_ENV = "QUEST_ASYNC_DEPTH"
_ASYNC_ENV_WARNED: set = set()


def async_depth_default() -> int:
    """``QUEST_ASYNC_DEPTH`` (default 2): completion-ring depth of the
    async dispatch pipeline -- how many issued batches may be in flight on
    the device while the host coalesces the next. ``0`` means synchronous
    dispatch (the batcher drains each batch before issuing another -- the
    A/B baseline the bench compares against). Malformed or negative values
    fall back through :func:`parse_env_int` with a QT310 warn-once."""
    return parse_env_int(_ASYNC_ENV, 2, minimum=0, code="QT310",
                         warned=_ASYNC_ENV_WARNED,
                         noun="async completion-ring depth")


def _env_queue_max() -> int:
    """``QUEST_ENGINE_QUEUE_MAX`` (0/unset = unbounded); malformed values
    fall back to unbounded with a QT303 diagnostic."""
    raw = os.environ.get("QUEST_ENGINE_QUEUE_MAX", "").strip()
    if not raw:
        return 0
    try:
        return max(0, int(raw))
    except ValueError:
        emit_findings([make_finding(
            "QT303", f"QUEST_ENGINE_QUEUE_MAX={raw!r} is not numeric; "
            "using the default", "engine.Engine")])
        return 0


class Engine:
    """Serving runtime for one circuit structure (see module docstring).

    ``circuit`` may be a raw or fused :class:`~quest_tpu.circuits.Circuit`
    recorded with :class:`~quest_tpu.params.Param` placeholders. A
    fused one is replayed as given; a raw one is replayed through its
    dense plan unless the engine is sharded (:meth:`_plan_program`), so
    replies agree with the gate-by-gate replay to rounding, not bitwise.
    ``env`` supplies the device mesh; with a multi-device env the initial
    state shards over it and batches replay sequentially. ``initial`` is ``"zero"`` (|0...0>),
    ``"plus"``, or a concrete planar (2, 2^nsv) array.
    """

    def __init__(self, circuit, env=None, *, precision_code: int | None = None,
                 max_batch: int = 8, max_delay_ms: float = 2.0,
                 initial="zero", donate: bool = True,
                 queue_max: int | None = None,
                 async_depth: int | None = None,
                 finalize=None, hamiltonian=None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_delay_ms < 0:
            raise ValueError(f"max_delay_ms must be >= 0, got {max_delay_ms}")
        if queue_max is None:
            queue_max = _env_queue_max()
        if queue_max < 0:
            raise ValueError(f"queue_max must be >= 0, got {queue_max}")
        if async_depth is None:
            async_depth = async_depth_default()
        if async_depth < 0:
            raise ValueError(f"async_depth must be >= 0, got {async_depth}")
        #: completion-ring depth; 0 = synchronous dispatch (A/B baseline)
        self.async_depth = int(async_depth)
        #: pending-queue bound; 0 = unbounded (the pre-ISSUE-7 behavior)
        self.queue_max = int(queue_max)
        self.circuit = circuit
        self.env = env
        self.max_batch = int(max_batch)
        self.max_delay_s = float(max_delay_ms) / 1e3
        self._donate = bool(donate)
        # round 19: optional traceable terminal stage composed INSIDE the
        # dispatched program (e.g. a sampling.sample_reduce shot table) --
        # futures then resolve to finalize(final_amps), never the 2^N
        # amplitudes, and the amps-shaped sentinel / corrupt-injection
        # gates are bypassed (the result is not a state). Must be a
        # stable (cached) callable: it keys the executable LRU.
        self._finalize = finalize
        # what a finalize says of itself (the adjoint gradient reduce): its
        # dispatch route label, so that grad_request traffic and its traces
        # count apart from engine_param/engine_vmap, and the host-side
        # ``unpack`` of a result that is ONE vector (:meth:`_lanes`)
        self._route = getattr(finalize, "dispatch_route", None)
        self._unpack = getattr(finalize, "unpack", None)
        self._trace_labels = {"route": self._route} if self._route else {}
        # round 20: the observable whose gradient submit_grad serves; the
        # companion gradient engine (same ansatz, grad_reduce finalize)
        # builds lazily on first use
        self._hamiltonian = hamiltonian
        self._precision_code = precision_code
        self._grad_companion: "Engine" | None = None
        self.dtype = real_dtype(precision_code)
        nsv = (2 if circuit.is_density_matrix else 1) * circuit.num_qubits
        self.num_amps = 1 << nsv
        self._sharding = (env.sharding(self.num_amps)
                          if env is not None else None)
        self._mesh = env.mesh if self._sharding is not None else None
        #: True when batches replay sequentially over the sharded register
        self.sharded = self._mesh is not None and self._mesh.size > 1

        if isinstance(initial, str):
            if initial == "zero":
                amps = ops_init.init_classical(self.num_amps, self.dtype, 0)
            elif initial == "plus":
                re = jnp.full((self.num_amps,),
                              1.0 / math.sqrt(self.num_amps), self.dtype)
                amps = jnp.stack([re, jnp.zeros_like(re)])
            else:
                raise ValueError(
                    f"initial must be 'zero', 'plus' or an array, "
                    f"got {initial!r}")
        else:
            amps = jnp.asarray(initial, dtype=self.dtype)
            if amps.shape != (2, self.num_amps):
                raise ValueError(
                    f"initial amps shape {amps.shape} != (2, {self.num_amps})")
        if self._sharding is not None:
            amps = jax.device_put(amps, self._sharding)
        #: planar initial-state template; each request donates a fresh copy
        self.initial_amps = amps

        #: what the two forward executables replay: the circuit's dense
        #: plan, or the circuit itself (see _plan_program)
        self._program = self._plan_program()
        self._lifted = self._program.lifted()
        #: which slots ride in which argument of the batch program: one
        #: packed array per slot kind the tape has (params._pack_layout)
        self._packs = _pack_layout(self._lifted)
        self.fingerprint = circuit.fingerprint()
        self._cv = _sync.Condition("engine.cv")
        self._q: deque = deque()
        # completion ring (round 18): in-flight issued batches awaiting
        # their device sync. BATCHER-THREAD-ONLY -- submit/close never
        # touch it, so it needs no lock; the loop drains it before exit.
        # Entries are _Inflight records.
        self._ring: deque = deque()
        # when the device was last seen done with a batch: the earliest a
        # batch launched behind it can have started (_charge_device)
        self._last_ready = 0.0
        self._serial: bool | None = None  # resolved lazily by _issue_serial
        self._cores: int | None = None  # resolved lazily by _spare_core
        self._open = True
        self._health = "healthy"
        self._breaches = 0        # sentinel breaches since last full heal
        self._clean_streak = 0    # consecutive clean dispatches
        self._dispatches = 0      # dispatch ordinal = the sentinel tick
        self._t_first: float | None = None  # batcher pop instant (tracing)
        self._thread = threading.Thread(target=self._loop,
                                        name="quest-engine", daemon=True)
        self._thread.start()
        # seed-kind slots mark a trajectory-noise structure: each vmap lane
        # of a batch then carries an independent PRNG stream
        # (quest_tpu/trajectories), surfaced here for the flight recorder
        self.seed_slots = sum(1 for s in self._lifted.slots
                              if s.kind == _SEED)
        # the blocks of the replayed program are the full passes a request
        # makes over its state; the barriers are its gate-by-gate entries
        blocks = _plan_items(self._program)
        telemetry.event("engine.start", fingerprint=self.fingerprint[:12],
                        nsv=nsv, max_batch=self.max_batch,
                        sharded=self.sharded, async_depth=self.async_depth,
                        params=len(self._lifted.param_names),
                        seed_slots=self.seed_slots, plan_blocks=blocks,
                        plan_barriers=len(self._program._tape) - blocks)

    # -- submission ---------------------------------------------------------

    @property
    def param_names(self) -> tuple:
        """Ordered Param names every submit must bind."""
        return self._lifted.param_names

    def submit(self, params: dict | None = None,
               timeout: float | None = None) -> Future:
        """Queue one parameter set; returns a Future resolving to the final
        planar (2, 2^nsv) amplitude array (one lane of the batch program's
        outputs, an array of its own).
        ``timeout`` (seconds) sets a deadline: a request still queued when
        it expires resolves with QuESTTimeoutError instead of running."""
        return self.submit_many([params], timeout=timeout)[0]

    def submit_many(self, params_list, timeout: float | None = None) -> list:
        """Queue several parameter sets ATOMICALLY (single lock hold), so an
        idle engine coalesces them into one dispatch -- the deterministic
        enqueue the bench and dryrun batching assertions rely on. Raises
        QuESTBackpressureError (accepting NONE of them) when the bounded
        queue cannot take the whole list."""
        if not params_list:
            return []
        if timeout is not None and timeout < 0:
            raise ValueError(f"timeout must be >= 0, got {timeout}")
        if not self._open:
            raise RuntimeError("Engine is closed")
        values_list = [bind(self._lifted, p) for p in params_list]
        if self._mode() == "vmap":
            # a request's lane of the batch program's arguments is packed
            # here, on the submitter's thread: the batcher only stacks rows
            values_list = [_pack_rows(self._packs, v) for v in values_list]
        futs = []
        with self._cv:
            if not self._open:
                raise RuntimeError("Engine is closed")
            if self._health == "quarantined":
                # quarantine sheds load through the EXISTING backpressure
                # contract: callers already handle QuESTBackpressureError
                telemetry.inc("engine_backpressure_total",
                              reason="quarantined")
                raise QuESTBackpressureError(
                    f"engine is quarantined ({self._breaches} integrity "
                    f"breach(es) recorded): rejecting "
                    f"{len(values_list)} request(s); investigate, then "
                    f"revive()", "Engine.submit")
            if self.queue_max and \
                    len(self._q) + len(values_list) > self.queue_max:
                telemetry.inc("engine_backpressure_total")
                raise QuESTBackpressureError(
                    f"engine queue full ({len(self._q)} pending, "
                    f"queue_max={self.queue_max}): rejecting "
                    f"{len(values_list)} request(s)", "Engine.submit")
            now = time.perf_counter()
            deadline = None if timeout is None else now + timeout
            # tracing (round 17): one boolean read when off. A pool-side
            # attempt span bound to this thread is adopted as the parent
            # (the request stays ONE waterfall across the hop); otherwise
            # the engine mints the root and owns finishing it.
            tracing = telemetry.trace_on()
            adopt = telemetry.current_trace() if tracing else None
            for values in values_list:
                fut = Future()
                # injected poison pins to the REQUEST here, at submit time,
                # so the nth-visit counting stays deterministic no matter
                # how the batcher later coalesces or bisects
                poison = _faults.fire("engine.request") \
                    if _faults.enabled() else None
                if not tracing:
                    ctx = None
                elif adopt is not None and len(values_list) == 1:
                    ctx = adopt.child("engine.request",
                                      engine=self.fingerprint[:8],
                                      **self._trace_labels)
                else:
                    ctx = telemetry.start_trace(
                        "request", t0=now, kind="engine",
                        engine=self.fingerprint[:8], **self._trace_labels)
                self._q.append(
                    _Request(values, fut, now, deadline, poison, ctx))
                futs.append(fut)
            telemetry.inc("engine_requests_total", len(futs))
            telemetry.set_gauge("engine_queue_depth", len(self._q))
            self._cv.notify_all()
        return futs

    def run(self, params: dict | None = None):
        """Synchronous convenience: ``submit(params).result()``."""
        return self.submit(params).result()

    # -- health -------------------------------------------------------------

    def health(self) -> str:
        """Current health state: ``healthy`` | ``degraded`` |
        ``quarantined`` (see module docstring)."""
        with self._cv:
            return self._health

    def is_open(self) -> bool:
        """True until :meth:`close` begins; a closed engine rejects every
        submit with ``RuntimeError``. The pool's dispatch path reads this
        to distinguish a drain-closed replica (fail over) from a genuine
        request error (settle)."""
        with self._cv:
            return self._open

    def revive(self) -> str:
        """Operator acknowledgement after a quarantine: transition
        ``quarantined`` -> ``degraded`` (submits are accepted again, and
        ``healthy`` returns after :data:`_HEAL_STREAK` clean dispatches).
        No-op in any other state. Returns the new state."""
        with self._cv:
            if self._health == "quarantined":
                self._transition("degraded", reason="revive")
                self._clean_streak = 0
            return self._health

    def _transition(self, to: str, *, reason: str) -> None:
        # callers hold self._cv
        if to == self._health:
            return
        telemetry.inc("engine_health_transitions_total",
                      **{"from": self._health, "to": to})
        telemetry.event("engine.health", previous=self._health, state=to,
                        reason=reason)
        self._health = to

    def _note_breach(self, *, hang: bool) -> None:
        with self._cv:
            self._clean_streak = 0
            if hang:
                # a wedged dispatch is not self-healable: straight to
                # quarantined, the operator must look at the mesh
                self._transition("quarantined", reason="hang")
                return
            self._breaches += 1
            self._transition(
                "quarantined" if self._breaches >= 2 else "degraded",
                reason="sentinel_breach")

    def _note_clean(self) -> None:
        with self._cv:
            if self._health != "degraded":
                return
            self._clean_streak += 1
            if self._clean_streak >= _HEAL_STREAK:
                self._breaches = 0
                self._transition("healthy", reason="clean_streak")

    def warmup(self, params: dict | None = None) -> "Engine":
        """Trace + compile both dispatch shapes (single and full batch) so
        every subsequent submit performs zero retraces. Named Params warm
        up at 0.0 unless ``params`` is given."""
        p = params if params is not None else {n: 0.0
                                              for n in self.param_names}
        self.run(p)
        if self.max_batch > 1:
            for f in self.submit_many([p] * self.max_batch):
                f.result()
        return self

    # -- gradients (round 20) -----------------------------------------------

    def grad_engine(self) -> "Engine":
        """The companion gradient engine: same ansatz, same batching knobs,
        finalized by the adjoint gradient reduce (quest_tpu/gradients), so
        T optimizer chains coalesce into ONE vmapped forward+backward
        program dispatched as ``route=grad_request``. Built lazily on
        first use; requires ``hamiltonian=`` at construction."""
        with self._cv:
            if self._grad_companion is not None:
                return self._grad_companion
            if self._hamiltonian is None:
                raise QuESTError(
                    "Engine.submit_grad needs the observable: construct "
                    "the Engine with hamiltonian=(pauli_codes, term_coeffs) "
                    "or a PauliHamil", "Engine.submit_grad")

        # the reduce replays the raw tape and plans its own backward half
        # (the dense plan's blocks: adjoint._plan_blocks): a plan the
        # caller fused is spelled out again, a raw tape is taken
        circuit = gatewise(self.circuit)
        red = grad_reduce(circuit, self._hamiltonian, dtype=self.dtype)
        eng = Engine(circuit, self.env,
                     precision_code=self._precision_code,
                     max_batch=self.max_batch,
                     max_delay_ms=self.max_delay_s * 1e3,
                     initial=self.initial_amps,
                     donate=self._donate,
                     queue_max=self.queue_max,
                     async_depth=self.async_depth,
                     finalize=red)
        with self._cv:
            if self._grad_companion is None:
                self._grad_companion = eng
                eng = None
        if eng is not None:  # lost the build race
            eng.close(drain=False)
        return self._grad_companion

    def submit_grad(self, params: dict | None = None,
                    timeout: float | None = None) -> Future:
        """Queue one optimizer step: a Future resolving to ``(value,
        grads)`` -- E = ⟨ψ(θ)|H|ψ(θ)⟩ and the full adjoint gradient as a
        Param-name -> derivative dict (shared-Param slots already summed
        by the chain rule), host scalars both. Warm steps perform zero
        retraces and ONE device dispatch per coalesced batch."""
        eng = self.grad_engine()
        telemetry.inc("grad_requests_total")
        telemetry.inc("grad_slots_total",
                      float(eng._finalize.num_slots))
        inner = eng.submit(params, timeout=timeout)
        fut: Future = Future()

        def _chain(f, _fut=fut):
            exc = f.exception()
            if exc is not None:
                _sync.resolve_future(_fut, exception=exc,
                                     site="engine.submit_grad")
            else:
                out = f.result()
                _sync.resolve_future(_fut,
                                     result=(out["value"], out["grads"]),
                                     site="engine.submit_grad")

        inner.add_done_callback(_chain)
        return fut

    def warmup_grad(self, params: dict | None = None) -> "Engine":
        """Compile both gradient dispatch shapes ahead of traffic (the
        gradient analogue of :meth:`warmup`)."""
        self.grad_engine().warmup(params)
        return self

    # -- lifecycle ----------------------------------------------------------

    def close(self, drain: bool = True) -> None:
        """Stop accepting work and join the batcher. ``drain=True``
        (default) dispatches everything still queued first; ``drain=False``
        resolves pending futures with a typed QuESTCancelledError instead
        (in-flight work still completes). Every accepted future resolves
        either way -- a waiter blocked on ``result()`` always wakes.

        A QUARANTINED engine never drains: work accepted before the
        quarantine would otherwise sit behind a batcher the operator has
        been told to investigate (and, after a hang, one that may be
        wedged), so ``drain=True`` downgrades to the typed cancellation
        path -- queued futures resolve promptly with QuESTCancelledError
        and only in-flight work is waited on."""
        dropped: list = []
        with self._cv:
            if drain and self._health == "quarantined":
                drain = False
            if not drain:
                while self._q:
                    dropped.append(self._q.popleft())
            self._open = False
            self._cv.notify_all()
        # resolve OUTSIDE the lock: done callbacks (the pool's failover
        # re-dispatch) may take other locks, and holding self._cv across
        # arbitrary callbacks invites lock-order inversions
        for req in dropped:
            # a typed resolution, not Future.cancel(): cancel() is a
            # no-op on futures a waiter already holds in RUNNING
            # transitions elsewhere, and CancelledError carries no
            # context -- this names the drop
            exc = QuESTCancelledError(
                "request dropped by Engine.close before dispatch",
                "Engine.close")
            self._trace_error(req, exc, "queue_wait")
            _sync.resolve_future(req.fut, exception=exc, site="engine.close")
        if self._thread.is_alive() and \
                self._thread is not threading.current_thread():
            _sync.join_thread(self._thread)
        comp = self._grad_companion
        if comp is not None:
            comp.close(drain=drain)
        telemetry.set_gauge("engine_queue_depth", 0)
        telemetry.event("engine.close", drained=drain)

    def __enter__(self) -> "Engine":
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close(drain=exc_type is None)
        return False

    # -- executables --------------------------------------------------------

    def _plan_program(self):
        """The circuit both forward executables replay. A raw tape is
        fused with the dense planner first: its Param gates join window
        blocks whose matrices are composed inside the program
        (fusion._apply_deferred_block), so a request makes one pass over
        its state per block instead of one per gate -- and ``_exec1`` and
        ``_execB`` replay the SAME plan, so a lone and a coalesced request
        run the same arithmetic. Kept as given: a circuit the caller
        already fused, a sharded engine's circuit (its gates route through
        the sharding-aware appliers one by one), a circuit under a
        values-aware finalize (the adjoint sweep walks the raw tape
        backward), and a tape with seed slots (a trajectory's Kraus draws
        are thresholds on the state, so a sharded and an unsharded
        ensemble must do the same arithmetic to walk the same path)."""
        circuit = self.circuit
        if self.sharded or getattr(self._finalize, "wants_values", False):
            return circuit
        if _plan_items(circuit) or any(s.kind == _SEED
                                       for s in circuit.lifted().slots):
            return circuit
        plan = planner.dense_plan(circuit._tape, circuit.num_qubits,
                                  self.dtype,
                                  is_density=circuit.is_density_matrix)
        # the batch executable vmaps the replay, and a static dense block's
        # own entry may take a kernel over ONE state: every dense block
        # enters as its factor list, applied through the gate primitive
        plan.items = [item.factored() if isinstance(item, planner.FusedBlock)
                      else item for item in plan.items]
        program = Circuit(circuit.num_qubits, circuit.is_density_matrix)
        program._tape = fusion.as_tape(plan)
        return program

    def _exec1(self):
        """The single-request parameterized executable, re-fetched from the
        global LRU per dispatch (warm dispatches therefore count
        ``plan_cache_hit_total`` -- the acceptance signal that nothing
        recompiled)."""
        with pallas_mesh(self._mesh):
            return self._program.parameterized(donate=self._donate,
                                               reduce=self._finalize)

    def _execB(self):
        """The vmap-over-params batch executable (unsharded registers):
        ONE fused program evolving ``max_batch`` states, batches padded to
        that size so the shape -- and hence the compiled program -- is
        constant. Its calling convention is what crosses the host-device
        boundary once a batch: IN, the initial state (unbatched, not
        donated: the program makes its own batch of it) and one
        ``(max_batch, n)`` array per slot kind the tape has
        (params._pack_layout), from which the per-slot values tuple is
        rebuilt by static column index before the vmap; OUT, a tuple of
        ``max_batch`` lanes, each a result of its own (a state, or what an
        armed ``finalize``, composed inside the vmapped body, makes of one),
        or ONE ``(max_batch, k)`` array of vectors (:meth:`_lanes`)."""
        circuit, width, packs = self._program, self.max_batch, self._packs
        finalize, apart = self._finalize, self._unpack is None  # or ONE array

        def build():
            inner = circuit._replay_fn(circuit.lifted())
            if finalize is not None and getattr(finalize, "wants_values",
                                                False):
                # values-aware finalize (adjoint gradient): the backward
                # sweep re-assembles daggered gates from each lane's own
                # traced slot values
                body = lambda amps, values: finalize(inner(amps, values),  # noqa: E731
                                                     values)
            elif finalize is not None:
                body = lambda amps, values: finalize(inner(amps, values))  # noqa: E731
            else:
                body = inner
            if (finalize is not None
                    and getattr(finalize, "wants_values", False)
                    and jax.default_backend() == "cpu"):
                # the adjoint forward+backward body vmaps badly on
                # XLA:CPU (measured 20q batch-8: ~20x the compile and
                # ~5x the run time of the lanes executed back-to-back);
                # lax.map traces the body ONCE and runs the lanes as a
                # scan -- still one fixed-shape program, one dispatch
                batched = lambda amps_b, values_b: jax.lax.map(  # noqa: E731
                    lambda av: body(av[0], av[1]), (amps_b, values_b))
            else:
                batched = jax.vmap(body, in_axes=(0, 0))

            def program(amps, *packed):
                amps_b = jnp.broadcast_to(amps[None], (width,) + amps.shape)
                out = batched(amps_b, _unpack_columns(packs, packed))
                return tuple(jax.tree_util.tree_map(lambda a: a[i], out)
                             for i in range(width)) if apart else out

            jitted = jax.jit(named_program(program, circuit, "engine_vmap",
                                           f"b{width}"))

            def fn(amps, *packed, _inner=jitted):
                with _dist.explicit_mesh(None), pallas_mesh(None):
                    return _inner(amps, *packed)

            fn.__name__ = jitted.__name__
            return fn

        return _cache.executables().get_or_create(self._batch_key(), build)

    def _batch_key(self) -> tuple:
        """The executable-cache key of :meth:`_execB`: the fingerprint is
        the replayed PLAN's, so engines share a batch program exactly when
        they replay the same structure."""
        return ("param_vmap", self._program.fingerprint(), self.max_batch,
                self.dtype.str, self._finalize)

    # -- batcher ------------------------------------------------------------

    def _loop(self) -> None:
        while True:
            with self._cv:
                while not self._q and self._open and not self._ring:
                    self._cv.wait()
                if not self._q:
                    if not self._ring:
                        return  # closed and fully drained (queue AND ring)
                    batch = None  # idle (or closing) with work in flight
                else:
                    batch = [self._q.popleft()]
                    t_open = time.perf_counter()
                    deadline = t_open + self.max_delay_s
                    held = 0
                    while len(batch) < self.max_batch:
                        if self._q:
                            batch.append(self._q.popleft())
                            continue
                        if not self._open:
                            break
                        now = time.perf_counter()
                        wait = deadline - now
                        if self._ring:
                            # continuous batching (round 18): with a batch
                            # in flight the device, not the first request's
                            # timer, paces the window. What is here would
                            # only queue behind the batch executing, so
                            # waiting for the next request costs nothing
                            # until that batch is done: every arrival
                            # restarts the timer, and the window closes
                            # when it is full, when ``max_delay_ms`` has
                            # passed with no arrival, or -- at once --
                            # when the batch ahead is done (its retire is
                            # the admission's first step). A submit wakes
                            # the wait; the device's progress is polled.
                            if self._ring_head_ready():
                                break
                            if len(batch) > held:
                                held = len(batch)
                                deadline = now + self.max_delay_s
                            wait = min(_WINDOW_POLL_S, deadline - now)
                        if wait <= 0:
                            break
                        self._cv.wait(wait)
                    telemetry.set_gauge("engine_queue_depth", len(self._q))
            if batch is None:
                # queue idle but batches in flight: retire the oldest ring
                # entry (its futures resolve) before sleeping -- the ring
                # never outlives the loop and never waits on new traffic
                self._retire_oldest()
                continue
            live = self._expire(batch)
            if live:
                # t_first (the pop instant) is the window's own reading:
                # queue_wait/coalesce attribution costs the untraced path
                # zero extra clock reads. Handed over on the instance so
                # _dispatch keeps its one-argument seam (tests wrap it
                # with lambda b: ...).
                self._t_first = t_open
                self._dispatch(live)

    def _expire(self, batch: list) -> list:
        """Resolve requests whose deadline passed while queued with
        QuESTTimeoutError; return the still-live remainder."""
        now = time.perf_counter()
        live = []
        for req in batch:
            if req.deadline is not None and now >= req.deadline:
                telemetry.inc("engine_request_timeouts_total")
                exc = QuESTTimeoutError(
                    f"request deadline expired after "
                    f"{now - req.t0:.3f}s in queue "
                    f"(timeout={req.deadline - req.t0:.3f}s)",
                    "Engine.submit")
                self._trace_error(req, exc, "queue_wait")
                _sync.resolve_future(req.fut, exception=exc,
                                     site="engine.expire")
            else:
                live.append(req)
        return live

    def _mode(self) -> str:
        # unsharded engines with batching enabled ALWAYS run the one
        # fixed-shape padded vmap program, even for a lone request: every
        # request then executes in an identical batch lane of the identical
        # executable, so coalesced and uncoalesced traffic is bit-identical
        # BY CONSTRUCTION (XLA's batched and unbatched contractions do not
        # share accumulation order, so a separate B=1 program would drift
        # ~1 ulp per gate) -- and exactly one executable ever compiles.
        # max_batch=1 opts out for latency-only deployments.
        return ("vmap" if (not self.sharded and self.max_batch > 1
                           and self._lifted.slots) else "sequential")

    def _dispatch(self, batch: list) -> None:
        t_first = self._t_first
        mode = self._mode()
        self._dispatches += 1
        telemetry.inc("engine_batches_total", mode=mode)
        telemetry.observe("engine_batch_size", len(batch))
        # tracing (round 17): attribute queue_wait (enqueue -> batcher
        # pop) and coalesce (pop -> window close) per request, then bind
        # the batch's contexts to this thread so retry/guard/bisect hops
        # inside the dispatch can link to them. The binding MUST clear
        # after the futures resolve (QT703) -- the finally below. From
        # here on every window is charged from the trace's own mark
        # (_charge), so the phases tile the request whatever the host
        # does between two of the batcher's regions.
        traced = [r.trace for r in batch if r.trace is not None]
        if traced:
            t_close = time.perf_counter()
            for req in batch:
                if req.trace is not None:
                    req.trace.charge("queue_wait", min(
                        t_close, req.t0 if t_first is None
                        else max(req.t0, t_first)))
                    req.trace.charge("coalesce", t_close)
            telemetry.set_current_trace(traced)
        # the injectable hang/transient point: one visit per dispatch; with
        # QUEST_WATCHDOG_MS armed the WHOLE dispatch (tracing included --
        # it begins and ends on the watchdog's worker thread, so jax's
        # thread-local trace state never splits) is deadline-bounded
        kind = _faults.fire("engine.dispatch") if _faults.enabled() else None
        ringable = (mode == "vmap" and self.async_depth > 0
                    and bool(self._lifted.slots))
        deferred = False
        try:
            with telemetry.span("engine.dispatch", mode=mode,
                                batch=len(batch)):
                if kind == "transient":
                    # an injected issue-time transient fails THIS batch
                    # before it reaches the device (or the completion
                    # ring): the bisection ladder below re-dispatches it,
                    # so healthy requests still complete and attribution
                    # never leaks onto a different in-flight batch
                    raise TransientFault("engine.dispatch", kind)
                if ringable:
                    # ring admission runs OUTSIDE the dispatch watchdog:
                    # each retire is its own deadline-bounded blocking
                    # boundary (guard.device_sync), so a retire-time hang
                    # is charged to the RETIRED entry -- wrapping it in
                    # this batch's dispatch deadline would misattribute
                    # the wedge to the batch being issued. The wait for
                    # ring capacity is this batch's queue_wait.
                    with telemetry.region("engine.admit") as rg:
                        self._ring_admit()
                    if traced:
                        self._charge(batch, "queue_wait", rg.t1)
                deferred = _watchdog.watched(
                    lambda: self._dispatch_one(batch, mode, defer=True),
                    site="engine.dispatch", hang=(kind == "hang"))
        except QuESTHangError as e:
            # no bisection: a wedged dispatch would wedge each half too;
            # fail the batch typed and quarantine the engine
            self._note_breach(hang=True)
            for req in batch:
                self._trace_error(req, e)
                _sync.resolve_future(req.fut, exception=e,
                                     site="engine.dispatch")
        except QuESTIntegrityError as e:
            # a corrupt result was caught BEFORE any future resolved with
            # it: fail the remainder typed, degrade (quarantine on repeat)
            self._note_breach(hang=False)
            for req in batch:
                self._trace_error(req, e)
                _sync.resolve_future(req.fut, exception=e,
                                     site="engine.dispatch")
        except Exception:
            # a failed batch bisects through the same executable: healthy
            # requests complete bit-identically, poisoned ones carry their
            # own exception -- one bad parameter set never fails neighbors
            self._bisect(batch, mode)
        except BaseException as e:  # interpreter teardown must not hang waiters
            for req in batch:
                self._trace_error(req, e)
                _sync.resolve_future(req.fut, exception=e,
                                     site="engine.dispatch")
        else:
            # a deferred batch is merely ISSUED: health credit and latency
            # observation move to its ring retire, where the device sync
            # actually proves the dispatch clean
            if not deferred:
                self._note_clean()
        finally:
            if traced:
                telemetry.clear_current_trace()
        if deferred:
            # entries the admission proved complete resolve only NOW,
            # after the issue: their sentinel gate and future resolution
            # overlap the batch just put on the device instead of
            # holding it idle
            self._ring_settle()
            return
        now = time.perf_counter()
        for req in batch:
            telemetry.observe("engine_request_latency_seconds", now - req.t0)

    def _dispatch_one(self, batch: list, mode: str,
                      defer: bool = False) -> bool:
        """Run one batch on its route. Returns True when the batch was
        ISSUED onto the completion ring (async vmap path -- its futures
        resolve at retire), False when it was fully dispatched and
        resolved synchronously. ``defer=False`` (the bisection ladder's
        calls) forces the synchronous route: a re-dispatched half must
        resolve before the ladder recurses, never re-enter the ring."""
        # device dispatch is a blocking boundary: flight-record QT602 if
        # any instrumented lock is still held on the dispatching thread
        _sync.guard_blocking("engine.dispatch")
        if mode == "vmap":
            return self._dispatch_vmap(batch, defer=defer)
        self._dispatch_sequential(batch)
        return False

    def _bisect(self, batch: list, mode: str, _prev: dict | None = None) -> None:
        telemetry.inc("engine_bisections_total")
        if len(batch) == 1:
            req = batch[0]
            try:
                self._dispatch_one(batch, mode)
            except BaseException as e:
                if req.poison is not None:
                    telemetry.inc("engine_poisoned_requests_total")
                self._trace_error(req, e)
                _sync.resolve_future(req.fut, exception=e,
                                     site="engine.bisect")
            return
        mid = len(batch) // 2
        for half in (batch[:mid], batch[mid:]):
            # each bisection level gets one span per traced request,
            # linked to the request's previous (failed) level so the
            # waterfall shows the isolation search (round 17)
            spans: dict = {}
            for r in half:
                if r.trace is not None:
                    sp = r.trace.child("engine.bisect", size=len(half))
                    prev = None if _prev is None else _prev.get(id(r))
                    sp.link(prev if prev is not None else r.trace,
                            kind="bisect")
                    spans[id(r)] = sp
            try:
                self._dispatch_one(half, mode)
            except BaseException:
                for sp in spans.values():
                    sp.end(status="error")
                self._bisect(half, mode, _prev=spans)
            else:
                for sp in spans.values():
                    sp.end()

    def _sentinel_gate(self, amps, tick: int | None = None) -> None:
        """Check one dispatch result against the armed sentinel policy
        (no-op boolean when ``QUEST_SENTINEL`` is off); raises
        QuESTIntegrityError rather than letting a corrupt state reach its
        future. The ``state.corrupt`` injection visit happens here too, so
        SDC tests corrupt real results, not synthetic arrays. A ring
        retire passes the ISSUING dispatch's ordinal as ``tick`` so the
        sentinel tick tracks the batch being checked, not whatever the
        host has issued since."""
        if self._finalize is not None:
            # finalized results (shot tables, expectations) are not
            # amps-shaped states -- the integrity sentinels don't apply
            return amps
        if not _sentinel.enabled():
            return amps
        findings = _sentinel.check_amps(
            amps, density=self.circuit.is_density_matrix,
            n=self.circuit.num_qubits,
            mesh=self._mesh if self.sharded else None,
            tick=self._dispatches if tick is None else tick,
            where="engine.dispatch")
        if findings:
            raise QuESTIntegrityError(
                "dispatch result breached the integrity sentinels: "
                + "; ".join(f.code for f in findings),
                "Engine._dispatch", findings=findings)
        return amps

    def _maybe_corrupt(self, amps):
        if self._finalize is not None:
            # the corrupt injector flips amplitude words; a finalized
            # result is an arbitrary pytree -- skip (chaos scenarios
            # exercise the amps-returning routes)
            return amps
        if not _faults.enabled():
            return amps
        return _guard.corrupt_amps(amps)

    def _named(self, res):
        """One finalized result as its future resolves to it: a vector the
        finalize names (``unpack``) is fetched and named on the host,
        anything else stays what the program returned."""
        return res if self._unpack is None else self._unpack(np.asarray(res))

    def _lanes(self, out, count: int):
        """The first ``count`` lanes of a batch result, each as its future
        resolves to it. The batch program returns its lanes as outputs of
        their own (:meth:`_execB`), so a lane is an index and retiring a
        batch dispatches nothing. Where the finalize states a lane as a
        vector and carries the ``unpack`` that names its entries (the
        adjoint gradient reduce) the batch is ONE array, a row a lane: an
        output is a buffer and a ``jax.Array`` the launch pays for before
        the program is enqueued, and eight lanes of a value and 2 x 160
        derivatives were 2,568 of them. The array is fetched in ONE
        transfer (after the sync the path already made) and named row by
        row on the host: such a future resolves to host scalars."""
        if self._unpack is None:
            return out[:count]
        return [self._unpack(row) for row in np.asarray(out)[:count]]

    @staticmethod
    def _charge(batch, phase: str, t_end: float) -> None:
        """Attribute to ``phase``, for every traced request of ``batch``,
        the window from the trace's mark to ``t_end`` -- a stamp of the
        batcher's region that just ended
        (:meth:`~quest_tpu.telemetry.TraceContext.charge`). Every window
        starts where the last one ended, so a request's phases tile its
        root span by construction, whatever the host does between two
        regions."""
        for req in batch:
            if req.trace is not None:
                req.trace.charge(phase, t_end)

    def _charge_device(self, batch, t_ready: float) -> None:
        """The ``device`` phase of a batch the device has just been seen
        done with, as the stream-ordered estimate: the chip runs one
        program at a time, in launch order, so the batch ran from its
        launch (the trace's mark) or from when the batch ahead was seen
        done, whichever is later, to ``t_ready``. The time it spent
        launched behind the batch ahead is ``queue_wait``."""
        self._charge(batch, "queue_wait", min(self._last_ready, t_ready))
        self._charge(batch, "device", t_ready)
        self._last_ready = t_ready

    def _trace_done(self, req) -> None:
        """Close the resolve phase at this instant and finish an
        engine-owned trace at the same stamp (adopted pool children only
        close their span -- the pool's settle owns finishing the root)."""
        tr = req.trace
        if tr is None:
            return
        now = time.perf_counter()
        tr.charge("resolve", now)
        if tr.owns_root:
            telemetry.finish_trace(tr, now=now)
        else:
            tr.end()

    def _trace_error(self, req, exc, phase: str = "resolve") -> None:
        """Mark a request's trace failed: errored traces are ALWAYS
        retained (the QUEST_TRACE=errors contract), so every resolve-with-
        exception site pairs with this. What is left of the request's
        time is charged too, to ``phase``: a dispatched request is being
        settled; one that never left the queue only waited."""
        tr = req.trace
        if tr is None:
            return
        now = time.perf_counter()
        tr.charge(phase, now)
        if tr.owns_root:
            telemetry.finish_trace(tr, error=type(exc).__name__, now=now)
        else:
            tr.event("error", type=type(exc).__name__)
            tr.end(status="error")

    def _launch(self, batch, call, program, route: str):
        """Run ``call`` (one launch of the executable ``program`` on
        ``route``) as the ``engine.launch`` region and charge it to the
        traced requests of ``batch``: ``compile`` when the launch
        retraced, ``dispatch`` otherwise. A launch retraced when JAX's
        compile path reported anything on this thread inside it (two
        thread-local reads, ``telemetry.compile_mark``); it then leaves one
        ``program.first_call`` record -- in the middle of a served
        window too, which is how an operator learns which program
        recompiled and what that cost."""
        mark = telemetry.compile_mark()
        with telemetry.region("engine.launch") as rg:
            out = call()
        retraced = telemetry.compile_mark() is not mark and \
            telemetry.first_call(mark, rg, program.__name__,
                                 self._route or route)
        self._charge(batch, "compile" if retraced else "dispatch", rg.t1)
        return out

    def _sync(self, batch, out) -> None:
        """Block until ``out`` is ready, as the ``engine.sync`` region,
        and charge the ``device`` phase. Synchronous routes only: a ring
        entry syncs in :meth:`_retire_oldest`, bounded by its own
        deadline."""
        with telemetry.region("engine.sync") as rg:
            jax.block_until_ready(out)
        self._charge_device(batch, rg.t1)

    def _lookup(self, batch, fetch):
        """Fetch the executable as the ``engine.lookup`` region. The
        dispatch preamble before it (context binding, the watchdog hop)
        is ``dispatch``; the fetch itself ``cache_lookup``."""
        with telemetry.region("engine.lookup") as rg:
            x = fetch()
        self._charge(batch, "dispatch", rg.t0)
        self._charge(batch, "cache_lookup", rg.t1)
        return x

    def _dispatch_sequential(self, batch: list) -> None:
        x = self._lookup(batch, self._exec1)
        for req in batch:
            if req.poison is not None:
                raise PoisonedRequestFault("engine.request", req.poison)
            # one param-replay program launch per request (host-side
            # count: inside the program it would count traces)
            telemetry.inc("device_dispatch_total",
                          route=self._route or "engine_param")
            one = (req,)
            if req.trace is not None:
                # sequential replays are serial: time spent on earlier
                # batch mates is this request's in-batch queueing
                req.trace.charge("queue_wait", time.perf_counter())
            res = self._launch(one, lambda: self._maybe_corrupt(
                x.with_values(self.initial_amps + 0, req.values)), x, "param")
            if req.trace is not None:
                # an explicit sync (the device phase) separates dispatch
                # from device drain. Tracing-armed requests only -- the
                # untraced path never blocks.
                self._sync(one, res)
            self._sentinel_gate(res)
            # trace bookkeeping BEFORE the resolution: a woken waiter
            # must observe its trace already finished (the pool's settle
            # callback runs inside resolve_future and copies the phase
            # vector when it closes the root)
            self._trace_done(req)
            _sync.resolve_future(req.fut, result=self._named(res),
                                 site="engine.dispatch")

    def _dispatch_vmap(self, batch: list, defer: bool = False) -> bool:
        for req in batch:
            # an injected poisoned request fails the whole batched program
            # (the real-world analogue: one NaN-producing parameter set or
            # device-rejected lane) -- _bisect isolates it
            if req.poison is not None:
                raise PoisonedRequestFault("engine.request", req.poison)
        traced = any(req.trace is not None for req in batch)
        if not self._lifted.slots:
            # value-free structure: every request computes the same state
            telemetry.inc("device_dispatch_total",
                          route=self._route or "engine_param")
            x = self._lookup(batch, self._exec1)
            out = self._launch(batch, lambda: self._maybe_corrupt(
                x.with_values(self.initial_amps + 0, ())), x, "param")
            if traced:
                self._sync(batch, out)
            self._sentinel_gate(out)
            with telemetry.region("engine.resolve"):
                out = self._named(out)
                for req in batch:
                    self._trace_done(req)
                    _sync.resolve_future(req.fut, result=out,
                                         site="engine.dispatch")
            return False
        # async pipeline: ring admission (eager retires, the in-flight
        # bound, the serial-issue gate) already ran in _dispatch, outside
        # the dispatch watchdog -- this method only assembles and issues
        defer = defer and self.async_depth > 0
        # host-side batch assembly (pad to the fixed vmap shape): on the
        # traced path this lands in the dispatch phase. Each request
        # brought its lane packed (submit_many); one numpy stack a slot
        # kind makes the program's value arguments, which enter it as
        # plain transfers: nothing is dispatched to the device here
        with telemetry.region("engine.assemble") as rg:
            pad = self.max_batch - len(batch)
            rows = [req.values for req in batch] + [batch[-1].values] * pad
            packed = [np.stack(kind) for kind in zip(*rows)]
        self._charge(batch, "dispatch", rg.t1)
        fnB = self._lookup(batch, self._execB)
        # the whole coalesced batch is ONE vmap program launch, handed the
        # initial state and one array a slot kind
        telemetry.inc("device_dispatch_total",
                      route=self._route or "engine_vmap")
        telemetry.inc("engine_launch_args_total", 1 + len(packed))
        out = self._launch(batch, lambda: fnB(self.initial_amps, *packed),
                           fnB, "engine_vmap")
        # ... and what it handed back: an array a lane, or one a batch
        telemetry.inc("engine_launch_results_total",
                      len(jax.tree_util.tree_leaves(out)))
        if defer:
            # ASYNC ISSUE: park the in-flight result on the completion
            # ring and return to coalescing -- the device executes batch k
            # while the host assembles batch k+1. Futures resolve at
            # retire; so do health credit, latency observation and the
            # device phase, which begins where this launch returned (a
            # jit COMPILE is synchronous at the call site, and a warm
            # launch's few host microseconds on the device are not worth
            # two phases that overlap)
            self._ring.append(_Inflight(out, batch, self._dispatches))
            telemetry.set_gauge("engine_async_inflight", len(self._ring))
            return True
        if traced or self.async_depth == 0:
            # async_depth=0 is the TRUE synchronous baseline: it drains
            # each batch before resolving it -- the batcher never runs
            # ahead of the device, the A/B floor the serve bench compares
            # the completion ring against
            self._sync(batch, out)
        # each request's resolve phase runs from the device sync to ITS
        # resolution: the sentinel gate and the wait behind earlier lanes.
        # The windows deliberately overlap -- phases tile each request's
        # own end-to-end latency, they are not a global partition.
        with telemetry.region("engine.resolve"):
            for req, lane in zip(batch, self._lanes(out, len(batch))):
                lane = self._maybe_corrupt(lane)
                self._sentinel_gate(lane)
                self._trace_done(req)
                _sync.resolve_future(req.fut, result=lane,
                                     site="engine.dispatch")
        return False

    def _fail_batch(self, batch: list, exc, *, site: str) -> None:
        """Resolve every still-pending future in ``batch`` with ``exc``
        (already-resolved lanes -- e.g. the ones a retire served before a
        later lane breached -- are left alone)."""
        for req in batch:
            if req.fut.done():
                continue
            self._trace_error(req, exc)
            _sync.resolve_future(req.fut, exception=exc, site=site)

    def _ring_head_ready(self) -> bool:
        """Non-blocking poll: has the device finished the OLDEST in-flight
        batch? Drives eager retirement -- the batcher resolves completed
        work between issues instead of parking it until the ring's
        backpressure bound forces a (then-instant) sync. A buffer without
        a readiness probe counts as ready: retiring it blocks no longer
        than the probe-less sync path always did."""

        # one program made every lane: its first array speaks for all
        leaves = jax.tree_util.tree_leaves(self._ring[0].out)
        probe = getattr(leaves[0], "is_ready", None) if leaves else None
        if probe is None:
            return True
        try:
            return bool(probe())
        except Exception:  # pragma: no cover - deleted/donated buffer
            return True

    def _issue_serial(self) -> bool:
        """Whether issue must wait for the in-flight batch's device sync.

        XLA:CPU has no private execution stream: two concurrently
        enqueued batch programs EXECUTE concurrently, timesharing the
        same host cores (measured ~20% per-batch throughput penalty with
        two large batches in flight), so running ahead of the device
        costs more than the host time it hides. On CPU the pipeline
        therefore still overlaps assembly, coalescing and resolution
        with device execution but never two batch programs with each
        other. Stream-ordered backends (TPU/GPU) queue enqueued work in
        hardware order -- there ``async_depth`` alone governs."""
        s = self._serial
        if s is None:
            s = self._serial = jax.default_backend() == "cpu"
        return s

    def _spare_core(self) -> bool:
        """Whether a host core is free while the device executes -- the
        precondition for deferring resolution past the next issue. On a
        single-core host the batcher thread and the XLA execution
        thread timeshare one core, so "overlapped" host work is merely
        starved work; there the pipeline resolves before issuing."""
        c = self._cores
        if c is None:
            c = self._cores = os.cpu_count() or 1
        return c > 1

    def _ring_admit(self) -> None:
        """Make room on the completion ring before an issue. Eagerly
        retires whatever the device already finished (non-blocking
        probe), enforces the ``async_depth`` in-flight bound, and -- on
        serial-issue backends -- device-syncs the head: the proof of
        completion must precede the next issue. With a spare host core
        the head stays UNresolved so its resolution work overlaps the
        next issue (see :meth:`_ring_settle`); on a single-core host it
        resolves right here (see :meth:`_spare_core`).
        Batcher-thread-only; runs outside the dispatch watchdog, each
        blocking sync bounded by its own ``engine.retire`` deadline."""
        while self._ring and self._ring_head_ready():
            self._retire_oldest()
        while len(self._ring) >= self.async_depth:
            self._retire_oldest()
        if self._issue_serial():
            # device still busy (the eager loop above would have caught
            # an idle one): wait for it bounded. With a spare core the
            # entry is synced but NOT resolved -- resolution after the
            # next issue keeps the device fed, the host work runs on
            # another core. On a single-core host that deferral inverts:
            # the settling thread is starved by the very execution it
            # "overlaps" (measured: future resolution drifting ~0.5s into
            # a 2.3s batch at 20q), so resolve-before-issue -- the
            # latency-optimal order when host and device share the core.
            defer_resolve = self._spare_core()
            while self._ring and not self._ring[0].synced:
                self._retire_oldest(sync_only=defer_resolve)

    def _ring_settle(self) -> None:
        """Resolve ring entries whose device work admission already
        proved complete -- called right AFTER an issue, so the sentinel
        gate and future resolution run while the just-issued batch
        executes."""
        while self._ring and self._ring[0].synced:
            self._retire_oldest()

    def _drop_entry(self, entry) -> None:
        """Remove a failed entry from the ring if it is still the head
        (resolve-stage failures already popped it)."""
        if self._ring and self._ring[0] is entry:
            self._ring.popleft()
            telemetry.set_gauge("engine_async_inflight", len(self._ring))

    def _retire_oldest(self, *, sync_only: bool = False) -> bool:
        """Retire the OLDEST completion-ring entry: device-sync its
        in-flight batch and resolve its futures, lane by lane, through
        the same corrupt/sentinel/trace gates as a synchronous dispatch.
        ``sync_only=True`` is the serial-issue admission step: it
        device-syncs the head IN PLACE (same bounded wait, failures
        attributed identically) but leaves it on the ring unresolved,
        for a post-issue :meth:`_ring_settle`. Never raises -- every
        failure mode resolves the ENTRY's futures typed (hang ->
        quarantine, sentinel breach -> degrade/quarantine, anything
        else -> the synchronous bisection ladder re-dispatches), so a
        retire-time fault is attributed to the batch that actually
        failed, never to whatever the host happens to be issuing (the
        no-cross-batch-misattribution contract the chaos
        ``async_dispatch_fault`` scenario proves). Returns False when the
        ring is empty. Batcher-thread-only, like the ring itself."""
        if not self._ring:
            return False

        entry = self._ring[0]
        out, batch, tick = entry.out, entry.batch, entry.tick
        traced = [r.trace for r in batch if r.trace is not None]
        if traced:
            telemetry.set_current_trace(traced)
        # the sync is a blocking boundary exactly like the dispatch is
        _sync.guard_blocking("engine.retire")
        outcome = "ok"
        retired = True
        try:
            with telemetry.span("engine.retire", batch=len(batch),
                                inflight=len(self._ring) - 1,
                                stage="resolve" if entry.synced else "sync"):
                if not entry.synced:
                    with telemetry.region("engine.sync") as rg:
                        _guard.device_sync(
                            lambda: jax.block_until_ready(out))
                    entry.synced = True
                    self._charge_device(batch, rg.t1)
                if sync_only:
                    # proven complete, left on the ring: the entry's
                    # resolution is deferred past the next issue
                    retired = False
                    return True
                self._ring.popleft()
                telemetry.set_gauge("engine_async_inflight", len(self._ring))
                with telemetry.region("engine.resolve"):
                    for req, lane in zip(batch,
                                         self._lanes(out, len(batch))):
                        lane = self._maybe_corrupt(lane)
                        self._sentinel_gate(lane, tick=tick)
                        self._trace_done(req)
                        _sync.resolve_future(req.fut, result=lane,
                                             site="engine.retire")
        except QuESTHangError as e:
            # the device wedged AFTER issue: same quarantine as a
            # synchronous hang, charged to this entry's requests
            outcome = "hang"
            self._drop_entry(entry)
            self._note_breach(hang=True)
            self._fail_batch(batch, e, site="engine.retire")
        except QuESTIntegrityError as e:
            outcome = "integrity"
            self._drop_entry(entry)
            self._note_breach(hang=False)
            self._fail_batch(batch, e, site="engine.retire")
        except Exception:
            # a device-side error surfacing at the sync: re-dispatch the
            # entry's unresolved requests through the SYNCHRONOUS
            # bisection ladder (defer=False), so healthy lanes complete
            # bit-identically and poisoned ones fail typed
            outcome = "error"
            self._drop_entry(entry)
            pending = [r for r in batch if not r.fut.done()]
            if pending:
                self._bisect(pending, "vmap")
        except BaseException as e:  # teardown must not hang waiters
            outcome = "error"
            self._drop_entry(entry)
            self._fail_batch(batch, e, site="engine.retire")
        else:
            if retired:
                self._note_clean()
        finally:
            if retired:
                telemetry.inc("engine_async_retires_total", outcome=outcome)
            if traced:
                telemetry.clear_current_trace()
        if retired:
            now = time.perf_counter()
            for req in batch:
                telemetry.observe("engine_request_latency_seconds",
                                  now - req.t0)
        return True
