"""Concurrency verifier for the serving fleet (QT6xx band).

The serving path -- engine batchers, quarantine drainers, replacement
spawners, the hedge loop, admission buckets -- is exactly the code a
test suite exercises least: its bugs live in interleavings the wall
clock rarely produces (the round-13 quarantined-``close`` deadlock was
found by hand). This module makes three of those bug classes mechanical,
over the instrumented primitives of :mod:`quest_tpu.resilience.sync`:

- :func:`check_lock_order` -- **QT601** deadlock-cycle analysis over the
  runtime held-while-acquiring graph ``sync.lock_order_edges()``
  records. A cycle (``pool.cv -> engine.cv -> pool.cv``) means two
  threads can take the same locks in opposing order; the finding names
  the cycle and carries the first-occurrence acquisition stack of every
  edge on it.
- :class:`InterleavingExplorer` -- a seeded, deterministic schedule
  explorer (loom/DPOR-lite): it installs itself as the sync layer's
  controller, parks every controlled thread at each sync operation
  (lock acquire/release, condition wait/notify, thread join, and
  :func:`await_future`), and replays the scenario under systematically
  varied schedules on two interleaved layers -- fresh-seed restarts
  whose per-schedule thread priorities each impose a different
  macro-ordering (the PCT idea: some seed starves each thread across a
  whole race window), alternating with branch flips over the recorded
  choice points of earlier runs (shallowest first) -- deduplicated by
  trace fingerprint, bounded by ``max_schedules`` and
  ``max_steps``. A schedule where no parked thread is runnable while a
  scenario thread is unfinished is a **deadlock breach**; a controlled
  thread crashing is a breach; every scenario's own invariant check
  (zero lost futures, no double resolution, bit-identical results)
  runs after each schedule. Three production scenarios ship here
  (:data:`SCENARIOS`): ``engine_close_race``, ``pool_failover_race``
  and ``hedge_race``.
- :func:`lint_concurrency` -- the AST pass behind
  ``tools/lint.py --concurrency``: **QT603** flags fields of a
  lock-owning class mutated both with and without the class lock held
  (an intra-class call-graph fixpoint absorbs the ``callers hold
  self._cv`` helper idiom), **QT604** flags raw
  ``threading.Lock/RLock/Condition`` construction in serving code that
  should be on the instrumented layer (``# concheck: allow-raw-lock``
  opts a deliberate line out; ``sync.py`` and this module are
  allowlisted -- the instrumenter cannot instrument itself).

The explorer's own latches are deliberately raw: they must never route
through the layer they schedule.
"""

from __future__ import annotations

import ast
import os
import re
import threading
import time
from concurrent.futures import CancelledError, Future
from concurrent.futures import TimeoutError as FutureTimeoutError
from typing import Any, Callable, Iterable, List, Optional, Tuple

from ..resilience import sync as _sync
from .diagnostics import Finding, emit_findings, make_finding

__all__ = [
    "check_lock_order",
    "InterleavingExplorer", "ExplorationResult", "await_future",
    "CountingFuture", "SCENARIOS", "run_scenario",
    "lint_concurrency", "check_raw_locks", "check_atomicity",
]


# ---------------------------------------------------------------------------
# QT601: lock-order deadlock-cycle analysis
# ---------------------------------------------------------------------------

def check_lock_order(graph: Optional[dict] = None, *,
                     location: str = "concheck.lock_order",
                     emit: bool = True) -> List[Finding]:
    """Detect cycles in the held-while-acquiring graph (QT601).

    ``graph`` defaults to everything :func:`sync.lock_order_edges`
    recorded so far in this process (``QUEST_CONCHECK=1`` runs, explorer
    schedules). Each distinct cycle yields one error finding naming the
    cycle and quoting the first-occurrence acquisition stack of every
    edge on it -- the two (or more) call paths that can deadlock."""
    if graph is None:
        graph = _sync.lock_order_edges()
    adj: dict = {}
    nodes = set()
    for (a, b) in graph:
        adj.setdefault(a, set()).add(b)
        nodes.add(a)
        nodes.add(b)
    findings: List[Finding] = []
    seen: set = set()
    WHITE, GREY, BLACK = 0, 1, 2
    color = {n: WHITE for n in nodes}
    path: List[str] = []

    def visit(n: str) -> None:
        color[n] = GREY
        path.append(n)
        for m in sorted(adj.get(n, ())):
            if color[m] == GREY:
                cyc = tuple(path[path.index(m):])
                k = cyc.index(min(cyc))
                canon = cyc[k:] + cyc[:k]
                if canon in seen:
                    continue
                seen.add(canon)
                ring = list(canon) + [canon[0]]
                stacks = []
                for a, b in zip(ring, ring[1:]):
                    e = graph.get((a, b), {})
                    if e.get("stack"):
                        stacks.append(f"--- {a} held while acquiring {b} "
                                      f"(seen {e.get('count', '?')}x):\n"
                                      f"{e['stack']}")
                findings.append(make_finding(
                    "QT601",
                    "lock-order cycle " + " -> ".join(ring) + ": threads "
                    "taking these locks in opposing order can deadlock"
                    + ("\n" + "".join(stacks) if stacks else ""),
                    location))
            elif color[m] == WHITE:
                visit(m)
        path.pop()
        color[n] = BLACK

    for n in sorted(nodes):
        if color[n] == WHITE:
            visit(n)
    if emit and findings:
        emit_findings(findings)
    return findings


# ---------------------------------------------------------------------------
# deterministic interleaving explorer
# ---------------------------------------------------------------------------

#: adopted thread-name prefixes: the serving fleet's worker threads
_ADOPT_PREFIXES = ("quest-engine", "quest-pool")


def _norm(name: str) -> str:
    """Thread-name fingerprint: replica/thread ordinals collapse so the
    same logical schedule hashes identically across runs."""
    return re.sub(r"\d+", "N", name)


class _WaitToken:
    __slots__ = ("notified",)

    def __init__(self) -> None:
        self.notified = False


class _TState:
    """Controller-side view of one controlled thread."""

    __slots__ = ("thread", "name", "norm", "ordinal", "gate", "parked",
                 "eligible", "finished", "holds", "scenario")

    def __init__(self, thread: threading.Thread, ordinal: int,
                 scenario: bool) -> None:
        self.thread = thread
        self.name = thread.name
        self.norm = _norm(thread.name)
        self.ordinal = ordinal
        # the explorer's gates are raw on purpose: the scheduler must
        # never route through the layer it is scheduling
        self.gate = threading.Event()
        self.parked: Optional[tuple] = None
        self.eligible: Optional[Callable[[], bool]] = None
        self.finished = False
        self.holds: list = []         # lock objects, one entry per acquire
        self.scenario = scenario      # scenario-owned (vs adopted) thread


def _prio(seed: int, ordinal: int) -> int:
    """Deterministic per-(schedule, thread) priority: an integer hash
    mix, so each seed induces a near-uniform random ordering over the
    registered threads. No RNG state -- replays are exact."""
    h = (ordinal * 2654435761 + seed * 0x9E3779B9 + 0x7F4A7C15) \
        & 0xFFFFFFFF
    h ^= h >> 16
    h = (h * 0x45D9F3B) & 0xFFFFFFFF
    return h ^ (h >> 16)


class _Run:
    """Per-schedule state: registered threads, cooperative waiters, the
    decision trail, and the breaches this schedule produced."""

    def __init__(self, prefix: Tuple[int, ...], seed: int = 0) -> None:
        self.prefix = prefix
        self.seed = seed                 # per-schedule priority seed
        self.reglock = threading.Lock()  # concheck: allow-raw-lock
        self.states: dict = {}           # Thread -> _TState
        self.owners: dict = {}           # lock object -> [state, depth]
        self.waiters: dict = {}          # Condition -> [_WaitToken]
        self.sched_evt = threading.Event()
        self.detached = False
        self.steps = 0
        self.alts: List[int] = []        # eligible count per choice point
        self.taken: List[int] = []       # index chosen per choice point
        self.trace: List[tuple] = []     # (thread norm, parked op)
        self.breaches: List[str] = []
        self.truncated = False
        self.diverged = False
        self._ordinal = 0

    def snapshot(self) -> list:
        with self.reglock:
            return list(self.states.values())

    def next_ordinal(self) -> int:
        with self.reglock:
            self._ordinal += 1
            return self._ordinal


def _always() -> bool:
    return True


class ExplorationResult:
    """What :meth:`InterleavingExplorer.explore` found: schedule counts,
    the distinct-interleaving count, invariant breaches (strings, each
    prefixed with the schedule that produced it) and the QT602 findings
    the schedules flight-recorded."""

    def __init__(self) -> None:
        self.schedules = 0
        self.interleavings = 0
        self.truncated = 0
        self.breaches: List[str] = []
        self.qt602: List[Finding] = []

    @property
    def ok(self) -> bool:
        return not self.breaches and not self.qt602

    def __repr__(self) -> str:
        return (f"<ExplorationResult schedules={self.schedules} "
                f"interleavings={self.interleavings} "
                f"breaches={len(self.breaches)} qt602={len(self.qt602)}>")


def await_future(fut: Future, timeout: Optional[float] = None):
    """Yield-aware ``fut.result()``: under the interleaving explorer the
    wait is a scheduling point (eligible once the future resolves, or
    always when timed -- the modeled spurious timeout); otherwise it is a
    plain ``result()`` behind the QT602 blocking-boundary guard."""
    ctrl = _sync.get_controller()
    if ctrl is not None and ctrl.controls_current():
        return ctrl.op_future(fut, timeout)
    _sync.guard_blocking("await_future")
    return fut.result(timeout)


class CountingFuture(Future):
    """A Future that counts resolution attempts -- the probe the
    double-resolution invariant checks read (``resolves`` must end at
    exactly 1 on a settled request)."""

    def __init__(self) -> None:
        super().__init__()
        self.resolves = 0

    def set_result(self, result) -> None:
        self.resolves += 1
        super().set_result(result)

    def set_exception(self, exc) -> None:
        self.resolves += 1
        super().set_exception(exc)


class InterleavingExplorer:
    """Deterministic schedule controller over the instrumented sync
    layer (module docstring). One instance explores one scenario at a
    time::

        result = InterleavingExplorer().explore(scenario)
        assert result.ok and result.interleavings > 1

    A *scenario* is any object with ``setup() -> ctx``,
    ``threads(ctx) -> [(name, fn), ...]``, ``check(ctx) -> [breach
    strings]`` and ``teardown(ctx)``; an optional ``warm()`` runs once
    before exploration, outside the controller, to pre-compile
    executables so every schedule replays cheaply."""

    def __init__(self, *, max_schedules: int = 64, max_steps: int = 400,
                 stall_s: float = 120.0) -> None:
        self.max_schedules = int(max_schedules)
        self.max_steps = int(max_steps)
        self.stall_s = float(stall_s)
        self._run: Optional[_Run] = None

    # -- controller protocol (called by quest_tpu.resilience.sync) ----------

    def controls_current(self) -> bool:
        run = self._run
        if run is None or run.detached:
            return False
        with run.reglock:
            return threading.current_thread() in run.states

    def op_acquire(self, lock, blocking: bool = True,
                   timeout: float = -1) -> bool:
        run, st = self._current()
        while True:
            if not self._park(run, st, ("acquire", lock.name),
                              self._acquire_elig(run, st, lock)):
                return lock.acquire(blocking, timeout)  # detached
            # the grant can race an UNCONTROLLED holder of the real lock
            # (a free-running thread from outside the schedule); wait it
            # out briefly instead of re-parking, so a loaded machine's
            # longer hold windows don't burn the schedule's step budget
            # on retries. Controlled threads are all parked at this
            # point, so the short block cannot reorder the schedule.
            if _sync._acquire_checked(lock, True, 0.05):
                st.holds.append(lock)
                own = run.owners.setdefault(lock, [st, 0])
                own[1] += 1
                return True
            # still held past the grace window: yield again

    def op_release(self, lock) -> None:
        run, st = self._current()
        if not self._park(run, st, ("release", lock.name), _always):
            lock.release()
            return
        _sync._release_checked(lock)
        self._drop_hold(run, st, lock)

    def op_wait(self, cond, timeout: Optional[float] = None) -> bool:
        run, st = self._current()
        lock = cond._lock
        held = _sync._held_stack()
        ent = None
        for h in held:
            if h.lock is lock:
                ent = h
                break
        if ent is None:
            raise RuntimeError(
                f"cannot wait on un-acquired instrumented lock "
                f"{cond.name!r}"
                + (" (dropped by chaos_drop_lock)"
                   if cond.name in _sync._dropped else ""))
        others = tuple(h.lock.name for h in held if h.lock is not lock)
        if others:
            _sync._qt602(f"cond:{cond.name}.wait", others,
                         "condition wait on a different lock")
        token = _WaitToken()
        run.waiters.setdefault(cond, []).append(token)
        # cooperative wait: really release the lock (mirroring the
        # instrumented wait), park until notified -- or immediately
        # grantable when timed, which models the spurious/timeout wakeup
        _sync._release_checked(lock)
        self._drop_hold(run, st, lock)
        elig = _always if timeout is not None else (lambda: token.notified)
        granted = self._park(run, st, ("wait", cond.name), elig)
        toks = run.waiters.get(cond, [])
        if token in toks:
            toks.remove(token)
        if not granted:  # detached mid-wait: reacquire for real and go on
            lock.acquire()
            return token.notified
        while True:
            if not self._park(run, st, ("wakeup", cond.name),
                              self._acquire_elig(run, st, lock)):
                lock.acquire()
                return token.notified
            # same uncontrolled-holder grace window as op_acquire
            if _sync._acquire_checked(lock, True, 0.05):
                st.holds.append(lock)
                own = run.owners.setdefault(lock, [st, 0])
                own[1] += 1
                return token.notified

    def op_notify(self, cond, n: Optional[int] = None) -> None:
        run, st = self._current()
        if not self._park(run, st, ("notify", cond.name), _always):
            try:
                cond._real.notify_all() if n is None else cond._real.notify(n)
            except RuntimeError:
                pass
            return
        toks = run.waiters.get(cond, [])
        for tok in toks if n is None else toks[:n]:
            tok.notified = True
        try:
            # wake real waiters too (threads that began waiting before
            # the controller attached); needs the real lock, which a
            # chaos-dropped acquire never took -- hence the except
            cond._real.notify_all() if n is None else cond._real.notify(n)
        except RuntimeError:
            pass

    def op_join(self, thread: threading.Thread,
                timeout: Optional[float] = None) -> None:
        run, st = self._current()
        with run.reglock:
            target = run.states.get(thread)

        def elig() -> bool:
            if timeout is not None:
                return True
            if target is not None:
                return target.finished
            return not thread.is_alive()

        if not self._park(run, st, ("join", _norm(thread.name)), elig):
            thread.join(timeout)
            return
        if target is not None and not target.finished:
            thread.join(0)  # modeled timeout expiry
        else:
            thread.join(timeout)

    def op_future(self, fut: Future, timeout: Optional[float] = None):
        run, st = self._current()
        elig = _always if timeout is not None else fut.done
        if not self._park(run, st, ("future", "result"), elig):
            # detached (post-run, all threads free-running): never hang a
            # leaked schedule -- an unresolvable future here is already a
            # recorded breach, so a short bound is enough
            return fut.result(timeout if timeout is not None else 2.0)
        if not fut.done():
            raise FutureTimeoutError(
                "modeled timeout: future unresolved at this scheduling "
                "point")
        return fut.result(0)

    # -- internals -----------------------------------------------------------

    def _current(self) -> Tuple[_Run, _TState]:
        run = self._run
        assert run is not None
        with run.reglock:
            return run, run.states[threading.current_thread()]

    @staticmethod
    def _acquire_elig(run: _Run, st: _TState, lock) -> Callable[[], bool]:
        def elig() -> bool:
            if lock.name in _sync._dropped:
                return True
            own = run.owners.get(lock)
            if own is not None and own[1] > 0:
                # held by a controlled thread: grantable only to the
                # owner of a reentrant lock (a non-reentrant self-acquire
                # stays ineligible forever == a detected self-deadlock)
                return own[0] is st and lock.reentrant
            return not (not lock.reentrant and lock._real.locked())
        return elig

    @staticmethod
    def _drop_hold(run: _Run, st: _TState, lock) -> None:
        if lock in st.holds:
            st.holds.remove(lock)
        own = run.owners.get(lock)
        if own is not None and own[0] is st:
            own[1] -= 1
            if own[1] <= 0:
                del run.owners[lock]

    def _park(self, run: _Run, st: _TState, op: tuple,
              elig: Callable[[], bool]) -> bool:
        if run.detached:
            return False
        st.eligible = elig
        st.parked = op
        run.sched_evt.set()
        st.gate.wait()
        # clear parked BEFORE the gate: while the gate is set the
        # scheduler counts this thread as busy (grant pending), and once
        # the gate clears parked is already None -- there is no window
        # where a consumed park still looks grantable, so a slow wakeup
        # (loaded box, 1 CPU) cannot be re-granted and burn steps
        st.parked = None
        st.eligible = None
        st.gate.clear()
        return not run.detached

    def _register(self, run: _Run, t: threading.Thread,
                  scenario_thread: bool) -> _TState:
        st = _TState(t, run.next_ordinal(), scenario_thread)
        orig_run = t.run

        def wrapped_run() -> None:
            try:
                orig_run()
            finally:
                st.finished = True
                run.sched_evt.set()

        t.run = wrapped_run  # type: ignore[method-assign]
        with run.reglock:
            run.states[t] = st
        return st

    def _quiesce(self, run: _Run) -> bool:
        deadline = time.monotonic() + self.stall_s
        while True:
            run.sched_evt.clear()
            # a set gate means a grant is pending consumption: the thread
            # was woken but has not run yet -- it is busy, not parked
            # (re-granting it would be a free no-op step, and a scheduler
            # hot loop here can burn the whole step budget before the
            # woken thread ever gets CPU time on a saturated machine)
            busy = [s for s in run.snapshot()
                    if not s.finished
                    and (s.parked is None or s.gate.is_set())]
            if not busy:
                return True
            if time.monotonic() > deadline:
                run.breaches.append(
                    "scheduler stall: controlled thread(s) did not yield: "
                    + ", ".join(s.name for s in busy))
                return False
            run.sched_evt.wait(0.05)

    def _schedule(self, run: _Run) -> None:
        while True:
            if not self._quiesce(run):
                return
            live = [s for s in run.snapshot() if not s.finished]
            if not any(s.scenario for s in live):
                return  # every scenario thread completed
            eligible = [s for s in live if s.parked is not None
                        and s.eligible is not None and s.eligible()]
            eligible.sort(key=lambda s: (s.norm, s.ordinal))
            if not eligible:
                run.breaches.append(
                    "deadlock: no runnable thread; parked: " + ", ".join(
                        f"{s.name}@{s.parked}" for s in live
                        if s.parked is not None))
                return
            if run.steps >= self.max_steps:
                run.truncated = True
                return
            if len(eligible) > 1:
                d = len(run.taken)
                if d < len(run.prefix):
                    want = run.prefix[d]
                    if want >= len(eligible):
                        want = 0
                        run.diverged = True
                else:
                    # beyond the replayed prefix, the default choice is
                    # the thread with the highest seeded priority -- NOT
                    # a fixed sort position. A fixed default makes the
                    # alphabetically-first thread (an engine batcher) win
                    # every branch, so the default schedule drains queues
                    # instantly and any race that needs the consumer
                    # starved across a window (quarantine landing on a
                    # queued request) hides behind a long all-non-default
                    # prefix the DFS budget never builds. Per-schedule
                    # priorities (the PCT insight) starve each thread for
                    # whole windows in SOME schedule while every choice
                    # stays a pure function of (seed, ordinal): replays
                    # and recorded prefixes are unaffected.
                    want = max(range(len(eligible)),
                               key=lambda i: _prio(run.seed,
                                                   eligible[i].ordinal))
                run.alts.append(len(eligible))
                run.taken.append(want)
                chosen = eligible[want]
            else:
                chosen = eligible[0]
            run.steps += 1
            # the ordinal keeps same-named threads (two "quest-engine"
            # batchers, a scenario's t0-/t1- pair) distinct in the
            # fingerprint; it is registration order, deterministic under
            # a replayed prefix
            run.trace.append((chosen.norm, chosen.ordinal, chosen.parked))
            chosen.gate.set()

    def _detach(self, run: _Run) -> None:
        run.detached = True
        for st in run.snapshot():
            st.gate.set()

    def _run_schedule(self, scenario, prefix: Tuple[int, ...],
                      seed: int = 0) -> Tuple[_Run, list]:
        run = _Run(prefix, seed)
        qt602_mark = len(_sync.blocking_findings())
        ctx = None
        owned: List[threading.Thread] = []
        self._run = run
        try:
            _sync.set_controller(self)
            try:
                ctx = scenario.setup()
                for name, fn in scenario.threads(ctx):
                    t = threading.Thread(
                        target=self._scenario_body(run, name, fn),
                        name=name, daemon=True)
                    self._register(run, t, scenario_thread=True)
                    owned.append(t)
                    t.start()
                self._schedule(run)
            finally:
                self._detach(run)
                for t in owned:
                    t.join(15.0)
                    if t.is_alive():
                        run.breaches.append(
                            f"scenario thread {t.name!r} leaked past "
                            f"detach")
            if ctx is not None:
                try:
                    run.breaches.extend(scenario.check(ctx))
                except Exception as e:
                    run.breaches.append(
                        f"invariant check raised {type(e).__name__}: {e}")
        finally:
            if ctx is not None:
                try:
                    scenario.teardown(ctx)
                except Exception:
                    pass
            self._run = None
            _sync.set_controller(None)
        return run, _sync.blocking_findings()[qt602_mark:]

    @staticmethod
    def _scenario_body(run: _Run, name: str,
                       fn: Callable[[], None]) -> Callable[[], None]:
        def body() -> None:
            try:
                fn()
            except BaseException as e:
                run.breaches.append(
                    f"scenario thread {name!r} raised "
                    f"{type(e).__name__}: {e}")
        return body

    def explore(self, scenario) -> ExplorationResult:
        """Run ``scenario`` under systematically varied schedules
        (class docstring). Returns the aggregate
        :class:`ExplorationResult`."""
        result = ExplorationResult()
        explorer = self
        saved_sync = (_sync._env_read, _sync._active)
        _sync.configure(True)
        orig_start = threading.Thread.start
        orig_hook = threading.excepthook

        def patched_start(t: threading.Thread) -> None:
            run = explorer._run
            if (run is not None and not run.detached
                    and t.name.startswith(_ADOPT_PREFIXES)):
                with run.reglock:
                    known = t in run.states
                if not known:
                    explorer._register(run, t, scenario_thread=False)
            orig_start(t)

        def hook(args) -> None:
            run = explorer._run
            if run is not None:
                with run.reglock:
                    known = args.thread in run.states
                if known:
                    run.breaches.append(
                        f"thread {args.thread.name!r} crashed: "
                        f"{args.exc_type.__name__}: {args.exc_value}")
                    run.sched_evt.set()
                    return
            orig_hook(args)

        threading.Thread.start = patched_start  # type: ignore[method-assign]
        threading.excepthook = hook
        try:
            warm = getattr(scenario, "warm", None)
            if warm is not None:
                warm()
            frontier: List[Tuple[int, ...]] = []
            visited = {()}
            traces: set = set()
            while result.schedules < self.max_schedules:
                k = result.schedules
                # two interleaved exploration layers: even schedules
                # restart from an EMPTY prefix under a fresh priority
                # seed (each seed is a whole different macro-ordering --
                # some starve the consumer through the race window, some
                # run the killer first, some the client); odd schedules
                # refine recorded runs by flipping one branch. Seeds
                # alone miss fine interleavings, branch flips alone pin
                # ever-longer prefixes that freeze the macro-ordering.
                prefix = frontier.pop() if (k % 2 == 1 and frontier) \
                    else ()
                run, qt602 = self._run_schedule(scenario, prefix, k)
                result.schedules += 1
                result.qt602.extend(qt602)
                result.breaches.extend(
                    f"[schedule {result.schedules}, prefix {prefix}] {b}"
                    for b in run.breaches)
                if run.truncated:
                    result.truncated += 1
                traces.add(tuple(run.trace))
                if not run.diverged:
                    # deepest alternatives first, so the LIFO frontier
                    # pops the SHALLOWEST flip next: early choices set
                    # the macro-ordering (who wins the race window), and
                    # pinning a near-complete prefix would freeze every
                    # schedule into the same trace with only tail noise
                    # -- the per-seed priorities would never get to act
                    for d in reversed(range(len(prefix), len(run.alts))):
                        for j in range(1, run.alts[d]):
                            p = tuple(run.taken[:d]) + (j,)
                            if p not in visited:
                                visited.add(p)
                                frontier.append(p)
            result.interleavings = len(traces)
        finally:
            threading.Thread.start = orig_start  # type: ignore[method-assign]
            threading.excepthook = orig_hook
            _sync.set_controller(None)
            self._run = None
            _sync._env_read, _sync._active = saved_sync
        return result


# ---------------------------------------------------------------------------
# the three production scenarios
# ---------------------------------------------------------------------------

def _demo_circuit():
    from ..circuits import Circuit
    from ..params import Param

    c = Circuit(2)
    c.hadamard(0)
    c.rotateX(0, Param("a"))
    c.rotateZ(1, Param("b"))
    c.controlledNot(0, 1)
    return c


_PARAMS_A = {"a": 0.37, "b": -1.1}
_PARAMS_B = {"a": 1.9, "b": 0.61}


class _ScenarioBase:
    """Shared plumbing: one demo param circuit, reference results
    computed once in ``warm()`` (which also pre-compiles the vmap
    executable into the process-global LRU so every schedule replays it
    warm)."""

    #: engine knobs shared by warm() and every schedule's engines -- the
    #: vmap executable key includes max_batch, so these must agree
    engine_kw = dict(max_batch=2, max_delay_ms=0.0)

    def __init__(self) -> None:
        self.circ = None
        self.expected: dict = {}

    def warm(self) -> None:
        import numpy as np

        from ..engine.engine import Engine

        if self.circ is None:
            self.circ = _demo_circuit()
        eng = Engine(self.circ, **self.engine_kw)
        try:
            eng.warmup()
            for key, params in (("a", _PARAMS_A), ("b", _PARAMS_B)):
                self.expected[key] = np.asarray(eng.run(params))
        finally:
            eng.close()

    def _bitcheck(self, label: str, got, key: str) -> List[str]:
        import numpy as np

        if not np.array_equal(np.asarray(got), self.expected[key]):
            return [f"{label}: result is not bit-identical to the "
                    f"reference"]
        return []


class EngineCloseRaceScenario(_ScenarioBase):
    """``submit`` racing ``close(drain=False)`` on one engine: the
    accepted-or-rejected contract. Every schedule must end with the
    submission either rejected typed (engine already closed), cancelled
    typed (queued, then dropped by close), or served bit-identically --
    never hung, never an untyped error."""

    name = "engine_close_race"

    def setup(self) -> dict:
        from ..engine.engine import Engine

        return {"eng": Engine(self.circ, **self.engine_kw), "out": {}}

    def threads(self, ctx: dict) -> list:
        from ..resilience.errors import QuESTCancelledError

        eng, out = ctx["eng"], ctx["out"]

        def submit() -> None:
            try:
                fut = eng.submit(_PARAMS_A)
            except RuntimeError as e:
                out["submit"] = ("rejected", str(e))
                return
            try:
                out["submit"] = ("served", await_future(fut))
            except QuESTCancelledError:
                out["submit"] = ("cancelled", None)

        def close() -> None:
            eng.close(drain=False)

        return [("t0-submit", submit), ("t1-close", close)]

    def check(self, ctx: dict) -> List[str]:
        out = ctx["out"].get("submit")
        if out is None:
            return ["submit thread recorded no outcome"]
        kind, val = out
        if kind == "served":
            return self._bitcheck("submit", val, "a")
        if kind not in ("cancelled", "rejected"):
            return [f"unexpected submit outcome {kind!r}"]
        return []

    def teardown(self, ctx: dict) -> None:
        ctx["eng"].close(drain=False)


class PoolFailoverRaceScenario(_ScenarioBase):
    """Quarantine-drain/failover racing live submissions on a 2-replica
    pool: a killer quarantines replica 0 while a client submits two
    requests and awaits both. Invariants: zero lost futures (every
    accepted future resolves -- a drain hands its cancelled work to the
    failover path), no double resolution (crash-free run), and the
    recovered results are bit-identical to the reference."""

    name = "pool_failover_race"

    def setup(self) -> dict:
        from ..engine.pool import EnginePool

        pool = EnginePool(replicas=2, spawn_replacements=False,
                          hedge_ms=0, **self.engine_kw)
        fp = self.circ.fingerprint()
        for rep in pool._replicas:
            pool._engine_for(rep, fp, self.circ)
        return {"pool": pool, "results": {}, "errors": {}}

    def threads(self, ctx: dict) -> list:
        pool = ctx["pool"]

        def client() -> None:
            futs = pool.submit_many(self.circ, [_PARAMS_A, _PARAMS_B])
            for i, f in enumerate(futs):
                try:
                    ctx["results"][i] = await_future(f)
                except Exception as e:  # lost futures surface in check()
                    ctx["errors"][i] = e

        def killer() -> None:
            pool._quarantine(pool._replicas[0], reason="test")

        return [("t0-client", client), ("t1-killer", killer)]

    def check(self, ctx: dict) -> List[str]:
        breaches: List[str] = []
        for i, key in enumerate(("a", "b")):
            if i in ctx["errors"]:
                e = ctx["errors"][i]
                breaches.append(f"request {i} lost: "
                                f"{type(e).__name__}: {e}")
            elif i not in ctx["results"]:
                breaches.append(f"request {i} never resolved")
            else:
                breaches += self._bitcheck(f"request {i} (post-failover)",
                                           ctx["results"][i], key)
        return breaches

    def teardown(self, ctx: dict) -> None:
        ctx["pool"].close(drain=False)


class HedgeRaceScenario(_ScenarioBase):
    """Hedged dispatch racing primary completion: a request in flight on
    a degraded replica is hedged to a healthy peer (the pool's
    ``_issue_hedge``, driven from a scenario thread so the race itself is
    the schedule, not the hedge loop's timer). First completion wins;
    the caller's future must resolve exactly once, bit-identically, in
    every schedule."""

    name = "hedge_race"

    def setup(self) -> dict:
        from ..engine import pool as _pool_mod
        from ..engine.pool import EnginePool

        pool = EnginePool(replicas=2, spawn_replacements=False,
                          hedge_ms=0, **self.engine_kw)
        fp = self.circ.fingerprint()
        rep0, rep1 = pool._replicas
        eng0 = pool._engine_for(rep0, fp, self.circ)
        pool._engine_for(rep1, fp, self.circ)
        eng0._note_breach(hang=False)  # degraded: the hedge precondition
        with pool._cv:
            pool._manifest.setdefault(fp, self.circ)
        req = _pool_mod._PoolRequest(self.circ, fp, _PARAMS_A, "default",
                                     "normal", None)
        req.fut = CountingFuture()
        return {"pool": pool, "req": req, "rep0": rep0, "rep1": rep1,
                "out": {}}

    def threads(self, ctx: dict) -> list:
        pool, req = ctx["pool"], ctx["req"]

        def primary() -> None:
            pool._dispatch_attempt(req, ctx["rep0"])
            with pool._cv:
                inner = [f for (_r, f, h, _sp) in req.inner if not h]
            try:
                if inner:
                    await_future(inner[0])
            except (CancelledError, Exception):
                pass  # a cancelled hedge loser is a legal outcome
            try:
                ctx["out"]["result"] = await_future(req.fut)
            except Exception as e:
                ctx["out"]["error"] = e

        def hedger() -> None:
            with pool._cv:
                req.hedged = True
            pool._issue_hedge(req, ctx["rep1"])
            with pool._cv:
                inner = [f for (_r, f, h, _sp) in req.inner if h]
            try:
                if inner:
                    await_future(inner[0])
            except (CancelledError, Exception):
                pass

        return [("t0-primary", primary), ("t1-hedger", hedger)]

    def check(self, ctx: dict) -> List[str]:
        req, out = ctx["req"], ctx["out"]
        breaches: List[str] = []
        if "error" in out:
            e = out["error"]
            breaches.append(f"caller future failed: "
                            f"{type(e).__name__}: {e}")
        elif "result" not in out:
            breaches.append("caller future never resolved")
        else:
            breaches += self._bitcheck("hedged request", out["result"], "a")
        if req.fut.resolves > 1:
            breaches.append(
                f"double resolution: caller future resolved "
                f"{req.fut.resolves}x")
        if not req.settled:
            breaches.append("request completed without settling")
        return breaches

    def teardown(self, ctx: dict) -> None:
        ctx["pool"].close(drain=False)


class AsyncDispatchDrainScenario(_ScenarioBase):
    """Async dispatch racing ``close(drain=True)`` on a completion-ring
    engine (round 18): two clients submit while a closer drains. Ring
    admission separates ISSUE from RESOLUTION, so the close path must
    retire every admitted entry before the batcher exits -- an exit
    condition that forgets the ring strands resolved-on-device work in
    unresolved futures. Invariants: each submission ends served
    bit-identically, cancelled typed, or rejected typed -- never hung,
    never untyped -- and the ring is empty once close returns."""

    name = "async_dispatch_drain"

    def setup(self) -> dict:
        from ..engine.engine import Engine

        return {"eng": Engine(self.circ, async_depth=2, **self.engine_kw),
                "out": {}}

    def threads(self, ctx: dict) -> list:
        from ..resilience.errors import QuESTCancelledError

        eng, out = ctx["eng"], ctx["out"]

        def submitter(slot: str, params: dict):
            def submit() -> None:
                try:
                    fut = eng.submit(params)
                except RuntimeError as e:
                    out[slot] = ("rejected", str(e))
                    return
                try:
                    out[slot] = ("served", await_future(fut))
                except QuESTCancelledError:
                    out[slot] = ("cancelled", None)
            return submit

        def close() -> None:
            eng.close(drain=True)
            out["ring_after_close"] = len(eng._ring)

        return [("t0-submitA", submitter("a", _PARAMS_A)),
                ("t1-submitB", submitter("b", _PARAMS_B)),
                ("t2-close", close)]

    def check(self, ctx: dict) -> List[str]:
        out = ctx["out"]
        breaches: List[str] = []
        for slot in ("a", "b"):
            rec = out.get(slot)
            if rec is None:
                breaches.append(f"submit {slot!r} recorded no outcome")
                continue
            kind, val = rec
            if kind == "served":
                breaches += self._bitcheck(f"submit {slot!r}", val, slot)
            elif kind not in ("cancelled", "rejected"):
                breaches.append(f"unexpected submit outcome {kind!r}")
        ring = out.get("ring_after_close")
        if ring is None:
            breaches.append("close thread recorded no outcome")
        elif ring:
            breaches.append(
                f"{ring} completion-ring entr{'y' if ring == 1 else 'ies'} "
                "survived close(drain=True)")
        return breaches

    def teardown(self, ctx: dict) -> None:
        ctx["eng"].close(drain=False)


#: name -> scenario class, the explorer's production scenario registry
SCENARIOS = {
    EngineCloseRaceScenario.name: EngineCloseRaceScenario,
    PoolFailoverRaceScenario.name: PoolFailoverRaceScenario,
    HedgeRaceScenario.name: HedgeRaceScenario,
    AsyncDispatchDrainScenario.name: AsyncDispatchDrainScenario,
}


def run_scenario(name: str, *, max_schedules: int = 64,
                 max_steps: int = 400) -> ExplorationResult:
    """Explore one registered scenario by name (:data:`SCENARIOS`)."""
    cls = SCENARIOS.get(name)
    if cls is None:
        raise KeyError(f"unknown scenario {name!r}; "
                       f"pick from {sorted(SCENARIOS)}")
    return InterleavingExplorer(max_schedules=max_schedules,
                                max_steps=max_steps).explore(cls())


# ---------------------------------------------------------------------------
# QT603/QT604: the AST atomicity + raw-lock lints
# ---------------------------------------------------------------------------

_RAW_PRAGMA = "concheck: allow-raw-lock"
_LOCK_CTORS = ("Lock", "RLock", "Condition")
#: files allowed to construct raw primitives: the instrumented layer
#: itself and the explorer that schedules it
_RAW_ALLOWLIST = (os.path.join("resilience", "sync.py"),
                  os.path.join("analysis", "concheck.py"))


def _is_lock_ctor(node: ast.expr) -> bool:
    """True for ``<anything>.Lock/RLock/Condition(...)`` -- matches both
    ``threading.Lock()`` and ``_sync.Lock(...)`` shapes."""
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in _LOCK_CTORS)


def check_raw_locks(path: str, tree: ast.Module, lines: List[str], *,
                    location: Optional[str] = None) -> List[Finding]:
    """QT604: raw ``threading.Lock/RLock/Condition`` construction in
    code that should build on the instrumented sync layer. A line
    carrying ``# concheck: allow-raw-lock`` is a deliberate opt-out."""
    rel = path.replace(os.sep, "/")
    if any(rel.endswith(a.replace(os.sep, "/")) for a in _RAW_ALLOWLIST):
        return []
    findings: List[Finding] = []
    threading_aliases = {"threading"}
    from_imported: set = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "threading":
                    threading_aliases.add(a.asname or a.name)
        elif isinstance(node, ast.ImportFrom) and node.module == "threading":
            for a in node.names:
                if a.name in _LOCK_CTORS:
                    from_imported.add(a.asname or a.name)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        raw = False
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in _LOCK_CTORS
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in threading_aliases):
            raw = True
        elif (isinstance(node.func, ast.Name)
              and node.func.id in from_imported):
            raw = True
        if not raw:
            continue
        line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if _RAW_PRAGMA in line:
            continue
        findings.append(make_finding(
            "QT604",
            f"raw threading.{getattr(node.func, 'attr', None) or node.func.id}() "  # type: ignore[union-attr]
            f"constructed; serving code must use the instrumented "
            f"quest_tpu.resilience.sync wrappers",
            location or f"{os.path.basename(path)}:{node.lineno}"))
    return findings


class _MethodScan(ast.NodeVisitor):
    """One method's lock-relative facts: ``self.F`` mutations and
    ``self.m()`` call sites, each tagged with whether a ``with
    self.<lock>:`` block encloses the site."""

    def __init__(self, lock_attrs: set) -> None:
        self.lock_attrs = lock_attrs
        self.depth = 0
        self.mutations: List[Tuple[str, bool, int]] = []  # (field, locked, line)
        self.calls: List[Tuple[str, bool]] = []           # (method, locked)

    def _is_lock_item(self, expr: ast.expr) -> bool:
        return (isinstance(expr, ast.Attribute)
                and isinstance(expr.value, ast.Name)
                and expr.value.id == "self"
                and expr.attr in self.lock_attrs)

    def visit_With(self, node: ast.With) -> None:
        locked = any(self._is_lock_item(item.context_expr)
                     for item in node.items)
        if locked:
            self.depth += 1
        self.generic_visit(node)
        if locked:
            self.depth -= 1

    def _note_target(self, target: ast.expr, lineno: int) -> None:
        if (isinstance(target, ast.Attribute)
                and isinstance(target.value, ast.Name)
                and target.value.id == "self"
                and target.attr not in self.lock_attrs):
            self.mutations.append((target.attr, self.depth > 0, lineno))

    def visit_Assign(self, node: ast.Assign) -> None:
        for t in node.targets:
            self._note_target(t, node.lineno)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign) -> None:
        self._note_target(node.target, node.lineno)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if node.value is not None:
            self._note_target(node.target, node.lineno)
        self.generic_visit(node)

    def visit_Delete(self, node: ast.Delete) -> None:
        for t in node.targets:
            self._note_target(t, node.lineno)
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call) -> None:
        f = node.func
        if (isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name)
                and f.value.id == "self"):
            self.calls.append((f.attr, self.depth > 0))
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        pass  # nested defs (callbacks) run on foreign threads; skip

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        pass


def _class_atomicity(cls: ast.ClassDef, path: str) -> List[Finding]:
    methods = [n for n in cls.body if isinstance(n, ast.FunctionDef)]
    lock_attrs = set()
    for m in methods:
        for node in ast.walk(m):
            if isinstance(node, ast.Assign) and _is_lock_ctor(node.value):
                for t in node.targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        lock_attrs.add(t.attr)
    if not lock_attrs:
        return []
    scans = {}
    for m in methods:
        scan = _MethodScan(lock_attrs)
        for stmt in m.body:
            scan.visit(stmt)
        scans[m.name] = scan
    # intra-class call-graph fixpoint: a method every caller invokes
    # under the lock is itself a locked context ("callers hold self._cv"
    # helpers); __init__'s call sites are pre-publication and ignored
    sites: dict = {}
    for caller, scan in scans.items():
        if caller == "__init__":
            continue
        for callee, locked in scan.calls:
            if callee in scans:
                sites.setdefault(callee, []).append((caller, locked))
    locked_methods: set = set()
    changed = True
    while changed:
        changed = False
        for m, callers in sites.items():
            if m in locked_methods or m == "__init__":
                continue
            if all(locked or c in locked_methods for c, locked in callers):
                locked_methods.add(m)
                changed = True
    findings: List[Finding] = []
    fields: dict = {}
    for mname, scan in scans.items():
        if mname == "__init__":
            continue
        method_locked = mname in locked_methods
        for field, locked, lineno in scan.mutations:
            fields.setdefault(field, {"locked": [], "bare": []})[
                "locked" if (locked or method_locked) else "bare"
            ].append((mname, lineno))
    for field in sorted(fields):
        info = fields[field]
        if info["locked"] and info["bare"]:
            lm, ll = info["locked"][0]
            bm, bl = info["bare"][0]
            findings.append(make_finding(
                "QT603",
                f"{cls.name}.{field} is mutated under the class lock in "
                f"{lm} (line {ll}) but WITHOUT it in {bm} (line {bl}); "
                f"one of the two is lying about the locking contract",
                f"{os.path.basename(path)}:{bl}"))
    return findings


def check_atomicity(path: str, tree: ast.Module) -> List[Finding]:
    """QT603 over one parsed module: for every lock-owning class, fields
    mutated both with and without the class lock held (module
    docstring). Scope: direct ``self.F`` assignments outside
    ``__init__``; container-method mutations and cross-object writes are
    out of reach of a syntactic pass and stay the suite's job."""
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef):
            findings += _class_atomicity(node, path)
    return findings


def lint_concurrency(paths: Optional[Iterable[str]] = None, *,
                     emit: bool = True) -> List[Finding]:
    """The ``tools/lint.py --concurrency`` entry point: run the QT603
    atomicity lint and the QT604 raw-lock lint over ``paths`` (files or
    directories; default: the whole ``quest_tpu`` package)."""
    if paths is None:
        paths = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, names in os.walk(p):
                files += [os.path.join(root, n) for n in names
                          if n.endswith(".py")]
        else:
            files.append(p)
    findings: List[Finding] = []
    for path in sorted(files):
        with open(path, "r") as fh:
            source = fh.read()
        try:
            tree = ast.parse(source, filename=path)
        except SyntaxError as e:
            findings.append(make_finding(
                "QT600", f"unparseable module: {e}",
                os.path.basename(path)))
            continue
        lines = source.splitlines()
        findings += check_raw_locks(path, tree, lines)
        findings += check_atomicity(path, tree)
    if emit and findings:
        emit_findings(findings)
    return findings
