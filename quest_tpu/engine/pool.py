"""Replica-pool serving: health-aware routing, quarantine drain/failover,
hedged dispatch, and warm replacement spawning.

One :class:`~quest_tpu.engine.engine.Engine` serves one circuit structure
from one batcher thread; the fleet shape ROADMAP item 1 asks for is many
replicas serving heterogeneous multi-tenant traffic. :class:`EnginePool`
is that front-end. It owns N replicas (each a lazily-populated map of
structure fingerprint -> ``Engine``) and routes every submit by three
signals, in order:

1. **health** -- the replica's worst engine state plus a pool-level
   override (``healthy`` routes before ``degraded``; ``quarantined``
   never routes),
2. **structure affinity** -- same-fingerprint requests prefer a replica
   that already holds that executable, so heterogeneous traffic does not
   serialize behind one batcher (and a cold replica is not warmed by
   accident on the hot path),
3. **load** -- least outstanding requests breaks ties.

Robustness behaviors (ISSUE 13):

- **Failover + quarantine drain**: when a replica quarantines (sentinel
  breach, hang, or an injected ``pool.replica`` fault), the pool pulls it
  from rotation, closes its engines with ``drain=False`` -- every queued
  future resolves with a typed
  :class:`~quest_tpu.resilience.QuESTCancelledError` -- and the done
  callbacks re-dispatch those requests to healthy peers. No caller future
  is ever dropped, and the recovered results are bit-identical: the same
  fingerprint fetches the same executable, and the PR 4 vmap contract
  makes every batch lane identical. Counted
  ``pool_failovers_total{reason}``. A replacement replica is then spawned
  in the background and **warmed from the fingerprint manifest**
  (:meth:`EnginePool.warm_from_manifest`; with ``QUEST_COMPILE_CACHE``
  set the compile itself reloads from disk) BEFORE it joins rotation --
  its first real request performs zero retraces
  (``engine_trace_total{kind=param_replay}`` stays flat).
- **Admission control**: every submit passes the per-tenant token-bucket
  front door first (:mod:`.admission` -- ``QuESTBackpressureError`` with
  ``reason="quota"``, high-priority reserve band, the
  ``admission_*_total`` counters). Admitted requests that momentarily
  have NO routable replica (e.g. mid-failover) park in priority-ordered
  pending queues (high drains first) instead of being rejected.
- **Ahead-of-demand compilation** (round 18): the pool counts requests
  per structure fingerprint; :meth:`EnginePool.precompile` ranks the
  manifest by that frequency and warms the most popular executables OFF
  the request path (``engine_precompile_total{outcome=warmed|cached|
  error}`` -- the already-warm probe is a non-mutating LRU ``peek``, so
  ranking never perturbs eviction order). ``precompile_ms`` > 0 runs it
  periodically on a background ``quest-pool-precompile`` thread -- the
  JAX persistent-compilation-cache discipline (PAPERS.md) applied to the
  in-memory plan cache: never compile on the request path.
- **Hedged dispatch** (``hedge_ms`` > 0): a request outstanding on a
  ``degraded`` replica past the hedge deadline is re-issued to a healthy
  peer through :func:`~quest_tpu.resilience.retry.call_with_retry`
  (site ``pool.hedge``, retryable on backpressure); first completion
  wins, the loser's future is cancelled (the engines' own
  ``fut.done()`` guards make the late result a no-op). Both outcomes are
  bit-identical by the same executable-identity argument, so hedging
  never changes answers -- only tail latency.
  ``pool_hedges_total{outcome=issued|won_primary|won_hedge}``.

Env knobs (all through
:func:`~quest_tpu.analysis.diagnostics.parse_env_int`, malformed values
warn once with QT307): ``QUEST_POOL_REPLICAS`` (default 2),
``QUEST_HEDGE_MS`` (default 0 = hedging off), and ``QUEST_TENANT_QPS``
(read by :mod:`.admission`).

Telemetry: ``pool_requests_total{tenant,priority}``,
``pool_routes_total{outcome=affinity|healthy|degraded|parked}``,
``pool_failovers_total{reason}``, ``pool_quarantines_total{reason}``,
``pool_replacements_total{reason}``, ``pool_hedges_total{outcome}``, and
the ``pool_replicas`` rotation gauge, on top of everything the member
engines already emit.

Locking: the pool condition variable orders BEFORE any engine lock --
pool code may read engine health under the pool lock, but never holds an
engine lock while taking the pool lock (engine done callbacks run with
no engine lock held; ``Engine.close`` resolves cancelled futures after
releasing its lock for exactly this reason). Both locks live on the
instrumented sync layer (:mod:`quest_tpu.resilience.sync`: ``pool.cv``
orders before ``engine.cv``), so with ``QUEST_CONCHECK=1`` the ordering
contract is *verified* -- an inversion shows up as a QT601 cycle in the
lock-order graph, and a future resolved under either lock as QT602
(docs/analysis.md, the round-15 concurrency verifier).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import Future

from .. import telemetry
from ..resilience import faultinject as _faults
from ..resilience import retry as _retry
from ..resilience import sync as _sync
from ..resilience.errors import (QuESTBackpressureError, QuESTCancelledError,
                                 QuESTHangError, QuESTIntegrityError,
                                 QuESTRetryError)
from .admission import PRIORITIES, AdmissionController
from .engine import Engine

__all__ = ["EnginePool"]

_RANK = {"healthy": 0, "degraded": 1, "quarantined": 2}
_STATES = ("healthy", "degraded", "quarantined")

#: replica-failure exception -> ``pool_failovers_total{reason}`` label;
#: anything NOT here (timeouts, poisoned requests, value errors) is a
#: REQUEST failure and propagates to the caller instead of failing over
_FAILOVER_REASONS = (
    (QuESTCancelledError, "drain"),
    (QuESTHangError, "hang"),
    (QuESTIntegrityError, "integrity"),
    (QuESTBackpressureError, "backpressure"),
)

#: QT307 warn-once tracking, one set per knob so the same malformed raw
#: value still warns on each distinct knob
_REPLICAS_WARNED: set = set()
_HEDGE_WARNED: set = set()


def _env_replicas() -> int:
    from ..analysis.diagnostics import parse_env_int
    return parse_env_int("QUEST_POOL_REPLICAS", 2, minimum=1, code="QT307",
                         warned=_REPLICAS_WARNED, noun="replica count")


def _env_hedge_ms() -> int:
    from ..analysis.diagnostics import parse_env_int
    return parse_env_int("QUEST_HEDGE_MS", 0, minimum=0, code="QT307",
                         warned=_HEDGE_WARNED, noun="hedge deadline (ms)")


def _failover_reason(exc) -> str | None:
    for cls, reason in _FAILOVER_REASONS:
        if isinstance(exc, cls):
            return reason
    return None


class _PoolRequest:
    """One pool-level request: the caller's future plus everything needed
    to re-dispatch it (circuit, params, tenant) and the bookkeeping the
    failover/hedge machinery reads (attempt count, replicas already
    failed on, in-flight engine futures)."""

    __slots__ = ("circuit", "fingerprint", "params", "tenant", "priority",
                 "fut", "deadline", "t0", "attempts", "failed", "inner",
                 "hedged", "dispatched_at", "last_exc", "settled",
                 "trace", "last_span")

    def __init__(self, circuit, fingerprint, params, tenant, priority,
                 deadline):
        self.circuit = circuit
        self.fingerprint = fingerprint
        self.params = params
        self.tenant = tenant
        self.priority = priority
        self.fut: Future = Future()
        self.deadline = deadline
        self.t0 = time.monotonic()
        self.attempts = 0
        self.failed: set = set()          # replica ids this request failed on
        self.inner: list = []    # (replica, engine_future, is_hedge, span)
        self.hedged = False
        self.dispatched_at: float | None = None
        self.last_exc = None
        self.settled = False
        self.trace = None                 # pool-minted TraceContext root
        self.last_span = None             # most recent attempt/hedge span

    def remaining(self) -> float | None:
        if self.deadline is None:
            return None
        return max(0.0, self.deadline - time.monotonic())


class _Replica:
    """One pool member: a map of fingerprint -> Engine, a pool-level state
    override (quarantine sticks even after its engines are closed), and
    the outstanding-request set routing and hedging read."""

    __slots__ = ("id", "engines", "state", "in_rotation", "outstanding",
                 "build_lock")

    def __init__(self, rid: int):
        self.id = rid
        self.engines: dict = {}
        self.state = "healthy"
        self.in_rotation = False
        self.outstanding: set = set()
        self.build_lock = _sync.Lock("pool.build")

    def health(self) -> str:
        """Worst of the pool-level state and every member engine's
        health (the routing signal)."""
        h = _RANK[self.state]
        for eng in self.engines.values():
            h = max(h, _RANK[eng.health()])
        return _STATES[h]


class EnginePool:
    """Health-aware replica pool over :class:`Engine` (module docstring).

    ``env`` and the engine knobs (``max_batch``/``max_delay_ms``/
    ``queue_max``/``precision_code``/``donate``) are shared by every
    engine the pool builds. ``replicas`` defaults to
    ``QUEST_POOL_REPLICAS`` (2), ``hedge_ms`` to ``QUEST_HEDGE_MS``
    (0 = off); ``admission`` accepts a pre-built
    :class:`~quest_tpu.engine.admission.AdmissionController` (otherwise
    one is created from ``tenant_qps`` / ``QUEST_TENANT_QPS``).
    ``spawn_replacements=False`` disables automatic replacement of
    quarantined replicas (tests that count replicas exactly use it).
    """

    def __init__(self, env=None, *, replicas: int | None = None,
                 max_batch: int = 8, max_delay_ms: float = 2.0,
                 queue_max: int | None = None, hedge_ms: float | None = None,
                 tenant_qps: int | None = None, admission=None,
                 precision_code: int | None = None, donate: bool = True,
                 spawn_replacements: bool = True,
                 precompile_ms: float = 0.0, finalize=None):
        if replicas is None:
            replicas = _env_replicas()
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        if hedge_ms is None:
            hedge_ms = _env_hedge_ms()
        if hedge_ms < 0:
            raise ValueError(f"hedge_ms must be >= 0, got {hedge_ms}")
        if precompile_ms < 0:
            raise ValueError(
                f"precompile_ms must be >= 0, got {precompile_ms}")
        self._env = env
        # finalize (round 19): forwarded to every engine the pool builds --
        # futures resolve to finalize(final_amps) (e.g. on-device shot
        # tables) instead of amplitude arrays
        self._engine_kw = dict(max_batch=max_batch,
                               max_delay_ms=max_delay_ms,
                               queue_max=queue_max,
                               precision_code=precision_code, donate=donate,
                               finalize=finalize)
        self.hedge_s = float(hedge_ms) / 1e3
        self.admission = (admission if admission is not None
                          else AdmissionController(tenant_qps))
        self._spawn_replacements = bool(spawn_replacements)
        self._cv = _sync.Condition("pool.cv")
        self._replicas: list[_Replica] = []
        self._manifest: dict = {}         # fingerprint -> circuit
        # round 20: per-fingerprint finalize overrides -- gradient traffic
        # rides the ordinary routing/failover machinery under a derived
        # "grad:<ham>:<fp>" fingerprint whose engines are built with the
        # adjoint grad_reduce finalize instead of the pool-wide one
        self._finalize_for: dict = {}
        self._freq: dict = {}             # fingerprint -> request count
        self._pending = {p: deque() for p in PRIORITIES}
        self._next_rid = 0
        self._closed = False
        self._max_attempts = max(3, int(replicas) + 2)
        self._workers: list[threading.Thread] = []
        for _ in range(int(replicas)):
            rep = _Replica(self._next_rid)
            self._next_rid += 1
            rep.in_rotation = True
            self._replicas.append(rep)
        telemetry.set_gauge("pool_replicas", int(replicas))
        self._hedge_thread = None
        if self.hedge_s > 0:
            self._hedge_thread = threading.Thread(
                target=self._hedge_loop, name="quest-pool-hedge",
                daemon=True)
            self._hedge_thread.start()
        self.precompile_s = float(precompile_ms) / 1e3
        self._precompile_thread = None
        if self.precompile_s > 0:
            self._precompile_thread = threading.Thread(
                target=self._precompile_loop, name="quest-pool-precompile",
                daemon=True)
            self._precompile_thread.start()
        telemetry.event("pool.start", replicas=int(replicas),
                        hedge_ms=float(hedge_ms),
                        precompile_ms=float(precompile_ms))

    # -- submission ---------------------------------------------------------

    def submit(self, circuit, params: dict | None = None, *,
               tenant: str = "default", priority: str = "normal",
               timeout: float | None = None) -> Future:
        """Admit + route one request; returns a Future resolving to the
        final planar amplitude array no matter which replica (or how many
        failovers) served it."""
        return self.submit_many(circuit, [params], tenant=tenant,
                                priority=priority, timeout=timeout)[0]

    def submit_many(self, circuit, params_list, *, tenant: str = "default",
                    priority: str = "normal",
                    timeout: float | None = None,
                    _fingerprint: str | None = None) -> list:
        """Admit ``len(params_list)`` requests atomically (the quota sees
        one take), then route each independently. ``_fingerprint``
        (internal) overrides the routing key -- submit_grad derives one
        per (structure, observable) so gradient engines never collide
        with plain replay engines of the same ansatz."""
        if priority not in PRIORITIES:
            raise ValueError(
                f"priority must be one of {PRIORITIES}, got {priority!r}")
        if not params_list:
            return []
        if timeout is not None and timeout < 0:
            raise ValueError(f"timeout must be >= 0, got {timeout}")
        with self._cv:
            if self._closed:
                raise RuntimeError("EnginePool is closed")
        # tracing (round 17): one boolean read when off; the pool mints
        # the request's root trace (backdated to admission entry) and its
        # settle owns finishing it -- the engines the attempts land on
        # adopt the attempt span and only close their own children
        tracing = telemetry.trace_on()
        t_adm = time.perf_counter() if tracing else 0.0
        try:
            self.admission.admit(tenant, priority, len(params_list))
        except QuESTBackpressureError as e:
            if tracing:
                # errored requests are ALWAYS captured, and an admission
                # shed errors before any request object exists: mint a
                # one-span error trace for the batch
                ctx = telemetry.start_trace(
                    "request", t0=t_adm, kind="pool", tenant=tenant,
                    priority=priority)
                if ctx is not None:
                    ctx.record_span("pool.admission", t_adm,
                                    time.perf_counter() - t_adm,
                                    status="error")
                    telemetry.finish_trace(ctx, error=type(e).__name__)
            raise
        t_admitted = time.perf_counter() if tracing else 0.0
        telemetry.inc("pool_requests_total", len(params_list),
                      tenant=tenant, priority=priority)
        fp = _fingerprint if _fingerprint is not None \
            else circuit.fingerprint()
        with self._cv:
            self._manifest.setdefault(fp, circuit)
            # per-structure frequency telemetry: the precompiler's ranking
            # signal (round 18)
            self._freq[fp] = self._freq.get(fp, 0) + len(params_list)
        deadline = None if timeout is None else time.monotonic() + timeout
        futs = []
        for params in params_list:
            req = _PoolRequest(circuit, fp, params, tenant, priority,
                               deadline)
            if tracing:
                req.trace = telemetry.start_trace(
                    "request", t0=t_adm, kind="pool", tenant=tenant,
                    priority=priority)
                if req.trace is not None:
                    req.trace.record_span("pool.admission", t_adm,
                                          t_admitted - t_adm)
            futs.append(req.fut)
            self._route(req)
        return futs

    def run(self, circuit, params: dict | None = None, **kw):
        """Synchronous convenience: ``submit(...).result()``."""
        return self.submit(circuit, params, **kw).result()

    # -- gradients (round 20) -----------------------------------------------

    def submit_grad(self, circuit, params: dict | None = None, *,
                    hamiltonian, tenant: str = "default",
                    priority: str = "normal",
                    timeout: float | None = None) -> Future:
        """Route one variational optimizer step fleet-wide: a Future
        resolving to ``(value, grads)`` from the adjoint gradient engine
        for ``circuit`` against ``hamiltonian`` (a PauliHamil or
        ``(pauli_codes, term_coeffs)``)."""
        return self.submit_grad_many(circuit, [params],
                                     hamiltonian=hamiltonian, tenant=tenant,
                                     priority=priority, timeout=timeout)[0]

    def submit_grad_many(self, circuit, params_list, *, hamiltonian,
                         tenant: str = "default", priority: str = "normal",
                         timeout: float | None = None) -> list:
        """Batch form of :meth:`submit_grad`: gradient requests share the
        ordinary admission/affinity/failover machinery under a derived
        fingerprint, coalescing into the replica's vmapped
        ``route=grad_request`` program."""
        import hashlib

        from ..gradients import grad_reduce
        from ..precision import real_dtype

        red = grad_reduce(
            circuit, hamiltonian,
            dtype=real_dtype(self._engine_kw.get("precision_code")))
        ham_key = hashlib.sha1(
            repr(red.hamiltonian).encode()).hexdigest()[:12]
        gfp = f"grad:{ham_key}:{circuit.fingerprint()}"
        with self._cv:
            self._finalize_for[gfp] = red
        telemetry.inc("grad_requests_total", len(params_list))
        telemetry.inc("grad_slots_total",
                      float(red.num_slots * len(params_list)))
        inner = self.submit_many(circuit, params_list, tenant=tenant,
                                 priority=priority, timeout=timeout,
                                 _fingerprint=gfp)
        outs = []
        for f in inner:
            fut: Future = Future()

            def _chain(src, _fut=fut):
                exc = src.exception()
                if exc is not None:
                    _sync.resolve_future(_fut, exception=exc,
                                         site="pool.submit_grad")
                else:
                    out = src.result()
                    _sync.resolve_future(
                        _fut, result=(out["value"], out["grads"]),
                        site="pool.submit_grad")

            f.add_done_callback(_chain)
            outs.append(fut)
        return outs

    # -- routing ------------------------------------------------------------

    def _select_locked(self, fingerprint, exclude=frozenset(),
                       allow_degraded: bool = True):
        """Routing policy (pool lock held): healthiest state first, then
        structure affinity, then least-loaded; quarantined never routes."""
        best = best_key = None
        for rep in self._replicas:
            if not rep.in_rotation or rep.id in exclude:
                continue
            h = rep.health()
            if h == "quarantined" or (h == "degraded"
                                      and not allow_degraded):
                continue
            # structure-count before id: a cold fingerprint lands on the
            # replica serving the fewest structures, so heterogeneous
            # traffic spreads instead of serializing behind one batcher
            key = (_RANK[h], 0 if fingerprint in rep.engines else 1,
                   len(rep.outstanding), len(rep.engines), rep.id)
            if best_key is None or key < best_key:
                best, best_key = rep, key
        return best

    def _route(self, req: _PoolRequest) -> None:
        parked = cancel = False
        rep = None
        with self._cv:
            if self._closed:
                cancel = True
            else:
                rep = self._select_locked(req.fingerprint,
                                          exclude=req.failed)
                if rep is None and req.failed:
                    # every non-failed replica is unroutable; a replica
                    # this request once failed on may have healed -- a
                    # stale exclusion must not park the request forever
                    rep = self._select_locked(req.fingerprint)
                if rep is None:
                    telemetry.inc("pool_routes_total", outcome="parked")
                    self._pending[req.priority].append(req)
                    parked = True
                else:
                    telemetry.inc(
                        "pool_routes_total",
                        outcome=("affinity"
                                 if req.fingerprint in rep.engines
                                 else rep.health()))
        if cancel:
            self._settle(req, exc=QuESTCancelledError(
                "request dropped: EnginePool is closed",
                "EnginePool.submit"))
            return
        if parked:
            if req.trace is not None:
                req.trace.event("parked", priority=req.priority)
            self.admission.note_queued(req.tenant, req.priority)
            return
        self._dispatch_attempt(req, rep)

    def _attempt_span(self, req: _PoolRequest, rep: _Replica, name: str,
                      link_kind: str):
        """Open one attempt span (time since the last attributed point
        lands in ``queue_wait``) and link it to the previous attempt --
        the failover/hedge causality edge the waterfall renders."""
        req.trace.charge("queue_wait", time.perf_counter())
        sp = req.trace.child(name, replica=rep.id, attempt=req.attempts)
        if req.last_span is not None:
            sp.link(req.last_span, kind=link_kind)
        req.last_span = sp
        return sp

    def _attempt_failed(self, req: _PoolRequest, sp) -> None:
        """Close a failed attempt span; the re-route that follows charges
        what is left of it, and its own latency, to ``queue_wait``."""
        if sp is not None:
            sp.end(status="error")

    def _dispatch_attempt(self, req: _PoolRequest, rep: _Replica) -> None:
        req.attempts += 1
        if req.attempts > self._max_attempts:
            self._settle(req, exc=req.last_exc or QuESTRetryError(
                f"request failed over {req.attempts - 1} time(s) without "
                f"a replica completing it", "EnginePool.submit"))
            return
        sp = None if req.trace is None else \
            self._attempt_span(req, rep, "pool.attempt", "failover")
        if _faults.enabled():
            # the injectable replica-death point: one visit per routed
            # dispatch attempt, so a plan's nth visit replays identically
            kind = _faults.fire("pool.replica")
            if kind is not None:
                req.failed.add(rep.id)
                req.last_exc = QuESTCancelledError(
                    f"injected {kind} fault at site 'pool.replica' "
                    f"(replica {rep.id})", "EnginePool._dispatch")
                self._attempt_failed(req, sp)
                self._quarantine(rep, reason=kind)
                telemetry.inc("pool_failovers_total", reason=kind)
                self._route(req)
                return
        eng = None
        try:
            eng = self._engine_for(rep, req.fingerprint, req.circuit)
            if req.trace is not None:
                # engine resolution (a miss builds + compiles) is the
                # pool-side cache_lookup phase
                req.trace.charge("cache_lookup", time.perf_counter())
            f = self._adopted_submit(req, sp, eng)
            if req.trace is not None:
                # the submit hop (param bind + engine-lock wait, which
                # can block behind the batcher) is queueing too; pool and
                # engine charge the trace's one mark, so what the batcher
                # has charged by now is not charged again
                req.trace.charge("queue_wait", time.perf_counter())
        except QuESTBackpressureError as e:
            req.failed.add(rep.id)
            req.last_exc = e
            self._attempt_failed(req, sp)
            if eng is not None and eng.health() == "quarantined":
                self._quarantine(rep, reason="quarantined")
            telemetry.inc("pool_failovers_total", reason="backpressure")
            self._route(req)
            return
        except RuntimeError as e:
            if eng is not None and not eng.is_open():
                # the quarantine drain closed this engine between routing
                # and submit (the interleaving explorer's
                # pool_failover_race window): the drain's zero-lost-futures
                # contract covers it -- fail over, don't settle
                req.failed.add(rep.id)
                req.last_exc = QuESTCancelledError(
                    f"replica {rep.id} closed during dispatch",
                    "EnginePool._dispatch")
                self._attempt_failed(req, sp)
                telemetry.inc("pool_failovers_total", reason="closed")
                self._route(req)
                return
            self._attempt_failed(req, sp)
            self._settle(req, exc=e)
            return
        except BaseException as e:
            self._attempt_failed(req, sp)
            self._settle(req, exc=e)
            return
        with self._cv:
            req.dispatched_at = time.monotonic()
            req.inner.append((rep, f, False, sp))
            rep.outstanding.add(req)
        f.add_done_callback(
            lambda fut, req=req, rep=rep: self._on_done(req, rep, fut,
                                                        hedge=False))

    def _adopted_submit(self, req: _PoolRequest, sp, eng):
        """``Engine.submit`` with this request's attempt span bound to the
        submitting thread, so the engine adopts it as the parent of its
        ``engine.request`` child (ONE waterfall across the hop). The
        previous binding is restored: a failover re-dispatch runs on an
        engine batcher thread that is still working for its own batch."""
        if req.trace is None:
            if not telemetry.trace_on():
                return eng.submit(req.params, timeout=req.remaining())
            # rate-sampled out: shield the engine from adopting whatever
            # trace the dispatching thread happens to be bound to
            prev = telemetry.current_traces()
            telemetry.set_current_trace(None)
            try:
                return eng.submit(req.params, timeout=req.remaining())
            finally:
                telemetry.set_current_trace(prev or None)
        prev = telemetry.current_traces()
        telemetry.set_current_trace(sp)
        try:
            return eng.submit(req.params, timeout=req.remaining())
        finally:
            telemetry.set_current_trace(prev or None)

    def _settle(self, req: _PoolRequest, result=None, exc=None) -> bool:
        """Resolve the caller's future exactly once (concurrent engine
        completions race through here; the first wins)."""
        with self._cv:
            if req.settled:
                return False
            req.settled = True
            self._cv.notify_all()
        if req.trace is not None:
            # the pool minted this root, so the pool finishes it -- BEFORE
            # resolving, so a woken caller observes a complete trace. The
            # window since the last attributed point (the engine handoff
            # in _on_done) is the pool-side resolve; a request that never
            # reached an engine only ever waited
            now = time.perf_counter()
            req.trace.charge("resolve" if req.dispatched_at is not None
                             else "queue_wait", now)
            telemetry.finish_trace(
                req.trace,
                error=None if exc is None else type(exc).__name__, now=now)
        # resolution happens OUTSIDE the pool lock (the settled flag above
        # is the once-guard); resolve_future re-verifies that under
        # QUEST_CONCHECK=1 (QT602 on any instrumented lock still held)
        _sync.resolve_future(req.fut, result=result, exception=exc,
                             site="pool.settle")
        telemetry.observe("pool_request_latency_seconds",
                          time.monotonic() - req.t0)
        return True

    def _on_done(self, req: _PoolRequest, rep: _Replica, fut,
                 *, hedge: bool) -> None:
        with self._cv:
            mine = next((p[3] for p in req.inner if p[1] is fut), None)
            req.inner = [p for p in req.inner if p[1] is not fut]
            if not any(p[0] is rep for p in req.inner):
                rep.outstanding.discard(req)
            siblings = list(req.inner)
            settled = req.settled
            self._cv.notify_all()
        if fut.cancelled():
            if mine is not None:
                mine.end(status="cancelled")
            return  # a hedge loser we cancelled while still queued
        exc = fut.exception()
        if settled:
            # hedge loser (or late failover echo): drop silently, but the
            # waterfall marks the losing span cancelled
            if mine is not None:
                mine.end(status="cancelled")
            return
        if exc is None:
            if mine is not None:
                mine.end()
            if self._settle(req, result=fut.result()):
                if req.hedged:
                    telemetry.inc("pool_hedges_total",
                                  outcome=("won_hedge" if hedge
                                           else "won_primary"))
                for _rep2, f2, _h, sp2 in siblings:
                    f2.cancel()  # engines guard fut.done(): safe either way
                    if sp2 is not None:
                        sp2.end(status="cancelled")
            self._drain_pending()
            return
        if mine is not None:
            mine.end(status="error")
            req.last_span = mine  # the failover link target
        # a replica-level failure quarantines the replica...
        if isinstance(exc, QuESTHangError):
            self._quarantine(rep, reason="hang")
        elif isinstance(exc, QuESTIntegrityError):
            with self._cv:
                state = rep.health()
            if state == "quarantined":
                self._quarantine(rep, reason="integrity")
        if siblings:
            return  # another attempt is still in flight; let it decide
        reason = _failover_reason(exc)
        if reason is None:
            # request-level failure (timeout, poison, user error): the
            # caller gets the typed error, no failover
            self._settle(req, exc=exc)
            return
        req.failed.add(rep.id)
        req.last_exc = exc
        telemetry.inc("pool_failovers_total", reason=reason)
        telemetry.event("pool.failover", replica=rep.id, reason=reason,
                        attempts=req.attempts)
        self._route(req)
        self._drain_pending()

    def _drain_pending(self) -> None:
        """Dispatch parked requests that became routable (high first)."""
        while True:
            req = rep = None
            with self._cv:
                if self._closed:
                    return
                for prio in PRIORITIES:
                    dq = self._pending[prio]
                    if dq:
                        cand = self._select_locked(dq[0].fingerprint,
                                                   exclude=dq[0].failed) \
                            or self._select_locked(dq[0].fingerprint)
                        if cand is not None:
                            req, rep = dq.popleft(), cand
                            break
                if req is None:
                    return
            self._dispatch_attempt(req, rep)

    # -- engines ------------------------------------------------------------

    def _engine_for(self, rep: _Replica, fingerprint, circuit=None):
        with self._cv:
            eng = rep.engines.get(fingerprint)
            if circuit is None:
                circuit = self._manifest.get(fingerprint)
        if eng is not None:
            return eng
        if circuit is None:
            raise KeyError(f"no circuit recorded for fingerprint "
                           f"{fingerprint[:12]}...")
        with rep.build_lock:
            with self._cv:
                eng = rep.engines.get(fingerprint)
                override = self._finalize_for.get(fingerprint)
            if eng is not None:
                return eng
            kw = self._engine_kw
            if override is not None:
                kw = {**kw, "finalize": override}
            elif isinstance(fingerprint, str) and \
                    fingerprint.startswith("grad:"):
                # a grad manifest row without its registered observable
                # (e.g. replayed into a fresh pool) must fail loud -- a
                # plain engine under this key would serve amps where the
                # caller expects (value, grads)
                raise KeyError(
                    f"gradient fingerprint {fingerprint[:24]}... has no "
                    "registered observable; route it through submit_grad")
            eng = Engine(circuit, self._env, **kw)
            with self._cv:
                rep.engines[fingerprint] = eng
            return eng

    # -- quarantine / failover / replacement --------------------------------

    def _quarantine(self, rep: _Replica, *, reason: str) -> None:
        with self._cv:
            if rep.state == "quarantined":
                return
            rep.state = "quarantined"
            rep.in_rotation = False
            engines = list(rep.engines.values())
            spawn = self._spawn_replacements and not self._closed
            self._cv.notify_all()
        telemetry.inc("pool_quarantines_total", reason=reason)
        telemetry.set_gauge("pool_replicas", self._rotation_count())
        telemetry.event("pool.quarantine", replica=rep.id, reason=reason)
        # drain on a helper thread: _quarantine may be running ON one of
        # this replica's batcher threads (hang/integrity done callbacks),
        # and Engine.close joins the batcher
        drainer = threading.Thread(
            target=self._drain_replica, args=(engines,),
            name=f"quest-pool-drain-{rep.id}", daemon=True)
        drainer.start()
        with self._cv:
            self._workers.append(drainer)
        if spawn:
            spawner = threading.Thread(
                target=self._spawn_replacement, args=(reason,),
                name="quest-pool-respawn", daemon=True)
            spawner.start()
            with self._cv:
                self._workers.append(spawner)

    def _drain_replica(self, engines) -> None:
        """Close a quarantined replica's engines without draining: every
        queued future resolves QuESTCancelledError, whose done callbacks
        fail the requests over to healthy peers (zero dropped futures);
        in-flight batches complete and still serve their waiters."""
        for eng in engines:
            try:
                eng.close(drain=False)
            except Exception:  # pragma: no cover - close must not cascade
                pass

    def _spawn_replacement(self, reason: str) -> None:
        try:
            with self._cv:
                if self._closed:
                    return
                rep = _Replica(self._next_rid)
                self._next_rid += 1
                manifest = dict(self._manifest)
            for fp, circ in manifest.items():
                self._engine_for(rep, fp, circ).warmup()
        except Exception as e:  # pragma: no cover - respawn best-effort
            telemetry.event("pool.respawn_failed", error=type(e).__name__)
            return
        stillborn = None
        with self._cv:
            if self._closed:
                stillborn = list(rep.engines.values())
            else:
                rep.in_rotation = True
                self._replicas.append(rep)
                self._cv.notify_all()
        if stillborn is not None:
            self._drain_replica(stillborn)
            return
        telemetry.inc("pool_replacements_total", reason=reason)
        telemetry.set_gauge("pool_replicas", self._rotation_count())
        telemetry.event("pool.replacement", replica=rep.id,
                        warmed=len(manifest))
        self._drain_pending()

    def warm_from_manifest(self, manifest=None, replica=None) -> list:
        """Pre-build and :meth:`Engine.warmup` the executables for every
        fingerprint in ``manifest`` (default: every structure this pool
        has served; alternatively a ``{fingerprint: circuit}`` map or an
        iterable of circuits) on ``replica`` (an id, or None = every
        in-rotation replica). With ``QUEST_COMPILE_CACHE`` set the warmup
        compile reloads from disk, so even a fresh process serves its
        first real request with zero retraces. Returns the warmed
        fingerprints."""
        if manifest is None:
            with self._cv:
                manifest = dict(self._manifest)
        elif not isinstance(manifest, dict):
            manifest = {c.fingerprint(): c for c in manifest}
        with self._cv:
            for fp, circ in manifest.items():
                self._manifest.setdefault(fp, circ)
            if replica is None:
                reps = [r for r in self._replicas if r.in_rotation]
            elif isinstance(replica, _Replica):
                reps = [replica]
            else:
                reps = [r for r in self._replicas if r.id == replica]
                if not reps:
                    raise ValueError(f"no replica with id {replica!r}")
        for rep in reps:
            for fp, circ in manifest.items():
                self._engine_for(rep, fp, circ).warmup()
        return sorted(manifest)

    @property
    def manifest(self) -> dict:
        """Fingerprint -> circuit map of every structure served so far."""
        with self._cv:
            return dict(self._manifest)

    @property
    def frequencies(self) -> dict:
        """Fingerprint -> request count: the manifest frequency telemetry
        the ahead-of-demand precompiler ranks by."""
        with self._cv:
            return dict(self._freq)

    # -- ahead-of-demand compilation (round 18) ------------------------------

    def precompile(self, limit: int | None = None, replica=None) -> list:
        """Warm the plan cache OFF the request path: rank every structure
        fingerprint this pool has served by request frequency (descending,
        fingerprint-lexicographic tiebreak) and ensure the hottest
        ``limit`` of them (None = all) hold warm executables on
        ``replica`` (an id, or None = every in-rotation replica).

        Per (fingerprint, replica) outcome, counted
        ``engine_precompile_total{outcome}``:

        - ``cached`` -- the replica's engine exists and the process-global
          LRU still holds its batch executable (probed with the
          NON-MUTATING :meth:`~quest_tpu.cache.LRUCache.peek`, so
          ranking never promotes a precompiled entry over one live
          traffic is using);
        - ``warmed`` -- a cold engine was built (or an evicted executable
          re-warmed) via :meth:`Engine.warmup`;
        - ``error`` -- the warm attempt failed; request traffic is
          unaffected (the hot path compiles lazily as before).

        Returns the fingerprints warm on every targeted replica, in rank
        order."""
        from .. import cache as _ec
        with self._cv:
            ranked = sorted(self._freq,
                            key=lambda fp: (-self._freq[fp], fp))
            manifest = {fp: self._manifest[fp] for fp in ranked
                        if fp in self._manifest}
            if replica is None:
                reps = [r for r in self._replicas if r.in_rotation]
            else:
                reps = [r for r in self._replicas if r.id == replica]
                if not reps:
                    raise ValueError(f"no replica with id {replica!r}")
        if limit is not None:
            manifest = dict(list(manifest.items())[:max(0, limit)])
        done = []
        for fp, circ in manifest.items():
            ok = True
            for rep in reps:
                with self._cv:
                    eng = rep.engines.get(fp)
                try:
                    if eng is not None and eng._open:
                        if eng._mode() != "vmap" or _ec.executables().peek(
                                eng._batch_key()) is not None:
                            telemetry.inc("engine_precompile_total",
                                          outcome="cached")
                            continue
                        eng.warmup()
                    else:
                        self._engine_for(rep, fp, circ).warmup()
                    telemetry.inc("engine_precompile_total",
                                  outcome="warmed")
                except Exception as e:
                    ok = False
                    telemetry.inc("engine_precompile_total",
                                  outcome="error")
                    telemetry.event("pool.precompile_failed",
                                    fingerprint=fp[:12],
                                    error=type(e).__name__)
            if ok:
                done.append(fp)
        if done:
            telemetry.event("pool.precompile", warmed=len(done),
                            replicas=len(reps))
        return done

    def _precompile_loop(self) -> None:
        while True:
            with self._cv:
                if self._closed:
                    return
                self._cv.wait(self.precompile_s)
                if self._closed:
                    return
            try:
                self.precompile()
            except Exception as e:  # pragma: no cover - warm best-effort
                telemetry.event("pool.precompile_failed",
                                fingerprint="", error=type(e).__name__)

    # -- hedging ------------------------------------------------------------

    def _hedge_loop(self) -> None:
        while True:
            with self._cv:
                if self._closed:
                    return
                now = time.monotonic()
                cands = []
                for rep in self._replicas:
                    if not rep.in_rotation or rep.health() != "degraded":
                        continue
                    for req in list(rep.outstanding):
                        if (req.settled or req.hedged
                                or req.dispatched_at is None
                                or now - req.dispatched_at < self.hedge_s):
                            continue
                        peer = self._select_locked(
                            req.fingerprint,
                            exclude={rep.id} | req.failed,
                            allow_degraded=False)
                        if peer is not None:
                            req.hedged = True
                            cands.append((req, peer))
            for req, peer in cands:
                self._issue_hedge(req, peer)
            with self._cv:
                if self._closed:
                    return
                self._cv.wait(max(self.hedge_s / 2.0, 0.001))

    def _issue_hedge(self, req: _PoolRequest, peer: _Replica) -> None:
        telemetry.inc("pool_hedges_total", outcome="issued")
        telemetry.event("pool.hedge", replica=peer.id,
                        attempts=req.attempts)
        sp = None
        if req.trace is not None:
            # the hedged duplicate links to the outstanding primary
            # attempt; note the duplicate does NOT take over last_span or
            # the phase mark -- the primary still owns the request unless
            # the hedge wins, and _on_done marks the loser cancelled
            sp = req.trace.child("pool.hedge", replica=peer.id,
                                 attempt=req.attempts)
            if req.last_span is not None:
                sp.link(req.last_span, kind="hedge")

        def attempt():
            return self._engine_for(peer, req.fingerprint,
                                    req.circuit).submit(
                req.params, timeout=req.remaining())

        try:
            if sp is not None or telemetry.trace_on():
                prev = telemetry.current_traces()
                telemetry.set_current_trace(sp)
                try:
                    f = _retry.call_with_retry(
                        attempt, site="pool.hedge",
                        retryable=(QuESTBackpressureError,))
                finally:
                    telemetry.set_current_trace(prev or None)
            else:
                f = _retry.call_with_retry(
                    attempt, site="pool.hedge",
                    retryable=(QuESTBackpressureError,))
        except Exception:
            if sp is not None:
                sp.end(status="error")
            with self._cv:
                req.hedged = False  # primary still owns it; may re-hedge
            return
        with self._cv:
            req.inner.append((peer, f, True, sp))
            peer.outstanding.add(req)
        f.add_done_callback(
            lambda fut, req=req, rep=peer: self._on_done(req, rep, fut,
                                                         hedge=True))

    # -- introspection / lifecycle ------------------------------------------

    def _rotation_count(self) -> int:
        with self._cv:
            return sum(1 for r in self._replicas if r.in_rotation)

    def health(self) -> dict:
        """Replica id -> health state, quarantined ex-members included."""
        with self._cv:
            return {rep.id: rep.health() for rep in self._replicas}

    def rotation(self) -> list:
        """Ids of the replicas currently accepting traffic."""
        with self._cv:
            return [rep.id for rep in self._replicas if rep.in_rotation]

    def await_rotation(self, k: int, timeout: float | None = None) -> int:
        """Block until at least ``k`` replicas are in rotation (e.g. a
        replacement finished warming); raises TimeoutError otherwise."""
        with self._cv:
            ok = self._cv.wait_for(
                lambda: self._closed or sum(
                    1 for r in self._replicas if r.in_rotation) >= k,
                timeout)
            count = sum(1 for r in self._replicas if r.in_rotation)
        if not ok or count < k:
            raise TimeoutError(
                f"pool rotation did not reach {k} (have {count})")
        return count

    def revive(self, replica_id: int) -> str:
        """Operator acknowledgement after a quarantine: return the
        replica to rotation. Engines its drain closed are discarded (they
        rebuild lazily, warm via the executable LRU); surviving engines
        are :meth:`Engine.revive`-d. Returns the replica's new health."""
        with self._cv:
            reps = [r for r in self._replicas if r.id == replica_id]
            if not reps:
                raise ValueError(f"no replica with id {replica_id!r}")
            rep = reps[0]
            rep.state = "healthy"
            for fp in [fp for fp, e in rep.engines.items()
                       if not e._open]:
                del rep.engines[fp]
            engines = list(rep.engines.values())
        for eng in engines:
            eng.revive()
        with self._cv:
            rep.in_rotation = True
            self._cv.notify_all()
        telemetry.set_gauge("pool_replicas", self._rotation_count())
        telemetry.event("pool.revive", replica=rep.id)
        self._drain_pending()
        with self._cv:
            return rep.health()

    def close(self, drain: bool = True) -> None:
        """Close every engine on every replica (``drain`` as in
        :meth:`Engine.close`); parked pending requests resolve with a
        typed QuESTCancelledError. Every accepted future resolves."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
            parked = [r for p in PRIORITIES for r in self._pending[p]]
            for p in PRIORITIES:
                self._pending[p].clear()
            reps = list(self._replicas)
            workers = list(self._workers)
            self._cv.notify_all()
        for req in parked:
            self._settle(req, exc=QuESTCancelledError(
                "request dropped by EnginePool.close before dispatch",
                "EnginePool.close"))
        for t in workers:
            _sync.join_thread(t)
        for rep in reps:
            for eng in list(rep.engines.values()):
                try:
                    eng.close(drain=drain)
                except Exception:  # pragma: no cover
                    pass
        if self._hedge_thread is not None and self._hedge_thread.is_alive():
            _sync.join_thread(self._hedge_thread)
        if self._precompile_thread is not None \
                and self._precompile_thread.is_alive():
            _sync.join_thread(self._precompile_thread)
        telemetry.set_gauge("pool_replicas", 0)
        telemetry.event("pool.close", drained=drain)

    def __enter__(self) -> "EnginePool":
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close(drain=exc_type is None)
        return False
