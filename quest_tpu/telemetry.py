"""Engine flight recorder: one metrics spine for every layer.

The reference simulator ships no timers, counters, or trace hooks (SURVEY.md
section 5 -- its only introspection is reportQuregParams and the QASM log),
and until round 6 this build's own perf evidence was scattered across ad-hoc
dicts (scheduler.stats), bench-only printouts (per-pass floors) and silent
fast-path bailouts nobody could see. This module is the single registry all
of them report into and every artifact is derived from:

- **Counters / gauges / histograms**, labeled Prometheus-style
  (``inc("engine_fallback_total", reason="df_tile_mismatch")``) -- the
  fusion planner, the distributed scheduler, the exchange kernels, the
  Pallas dispatch layer and the trajectory noise engine (the
  ``trajectory_*`` series: channel sites unraveled per kind, trajectories
  run, ensembles driven -- docs/trajectories.md) all record here (see the
  instrumentation map in docs/observability.md).
- **Nested host-side spans** with monotonic timing
  (``with span("fusion.plan", qubits=26): ...``): each completed span
  aggregates into the registry (count / total_s / max_s) and, optionally,
  streams one JSONL event (``QUEST_TELEMETRY_JSONL=/path`` or
  :func:`export_jsonl`).
  A span also opens a ``jax.profiler.TraceAnnotation`` of its name for
  its lifetime: under a profiler session the program's spans sit in the
  xplane's host lines, on the device trace's clock (with no session, one
  activity check). Hot paths use :func:`region` instead (``circuit.run``,
  the engine's per-batch ``engine.*`` regions): aggregate and annotate
  only -- no ring event, no wall-clock read -- and the handle keeps the
  window's ``perf_counter`` stamps for the engine's phase attribution.
- **Compile events from inside JAX** (:func:`watch_jax_compiles`):
  ``jax.monitoring`` listeners feed ``jax_trace_seconds``,
  ``jax_lower_seconds``, ``jax_backend_compile_seconds``,
  ``jax_cache_retrieval_seconds`` and ``jax_cache_{hits,misses}_total``;
  nested intervals on one thread are charged once, however many nest
  (:func:`_own_time`). A window in which a program is first called reads
  the thread's totals at both ends (:func:`compile_mark`) and, where they
  moved, emits one ``program.first_call`` event that names the program
  and tiles the window into trace, lowering, compile, cache load and the
  rest (:func:`first_call`).
- **Snapshots**: :func:`snapshot` returns the whole registry as one nested
  JSON-ready dict -- ``benchmark/run.py`` takes one where set-up ends and
  one after the window, and every per-layer reader is a pure function of
  the two (docs/observability.md).
- **Request traces** (round 17): a :class:`TraceContext`
  (trace_id / span_id / parent_id) minted at ``Engine.submit`` /
  ``EnginePool.submit`` and propagated across every thread hop of the
  serving path, with causal span links for hedges, failovers, retries and
  bisection halves. Each request accumulates the canonical :data:`PHASES`
  vector (``queue_wait``/``coalesce``/``cache_lookup``/``compile``/
  ``dispatch``/``device``/``resolve``) into ``request_phase_ms{phase}``
  histograms (p50/p95/p99 in :func:`snapshot`), and completed traces
  export as Perfetto-loadable Chrome trace JSON
  (:func:`export_chrome_trace`, ``tools/traceview.py``). Sampling is
  head-based via ``QUEST_TRACE=off|errors|<rate>|all`` (malformed values
  warn once as QT701); errored requests are always captured; the off
  path is one boolean read (:func:`trace_on`), same contract as
  :func:`span`.
- **Async serving series** (round 18): the completion-ring engine
  reports ``engine_async_inflight`` (gauge: ring occupancy after every
  admit / retire) and
  ``engine_async_retires_total{outcome=ok|hang|integrity|error}`` (one
  per retired in-flight batch, through the same corrupt / sentinel /
  trace gates as a synchronous dispatch); the pool's ahead-of-demand
  compiler counts ``engine_precompile_total{outcome=warmed|cached|
  error}``; whole-request chaining launches exactly one program per
  request -- ``device_dispatch_total{route="request"}``, the round-18
  dispatch floor (docs/serving.md).

Semantics notes:

- Everything here is HOST-side accounting. Inside ``jax.jit`` the
  instrumented code runs once per *trace*, so counters count traced work
  (plan shape, comm chunk-units of the compiled program), not per-execution
  device work; span durations around jitted calls measure dispatch (plus
  compilation on the first call), not device drain.
- **Zero overhead when disabled**: ``QUEST_TELEMETRY=0`` rebinds the whole
  public surface to no-op stubs at import (a disabled process records
  nothing and allocates nothing). In-process, :func:`disabled` flips the
  same guard temporarily -- tests use it to assert bit-identical results.
- Thread-safe: one lock around the registry maps, a thread-local span
  stack, so instrumented code may run from any thread.
"""

from __future__ import annotations

import collections
import contextlib
import json
import os
import sys
import threading
import time

__all__ = [
    "enabled", "disabled", "inc", "set_gauge", "observe", "span", "region",
    "event", "watch_jax_compiles", "compile_mark", "kernel_traced",
    "first_call",
    "counter_value", "counter_total", "counters", "snapshot", "reset",
    "export_jsonl", "events",
    "PHASES", "TraceContext", "trace_on", "trace_mode", "trace_policy",
    "start_trace", "finish_trace", "current_trace", "current_traces",
    "set_current_trace", "clear_current_trace", "trace_event_current",
    "traces", "trace_thread_leaks", "export_chrome_trace", "export_traces",
    "chrome_trace_events",
]

#: import-time master switch; QUEST_TELEMETRY=0 swaps in the no-op stubs
_ENV_ENABLED = os.environ.get("QUEST_TELEMETRY", "1").strip().lower() \
    not in ("0", "false", "off")

#: if set, every completed span / event streams one JSON line here
_JSONL_ENV = "QUEST_TELEMETRY_JSONL"

#: default cap on the in-memory event ring (oldest dropped first,
#: counted in ``telemetry_events_dropped_total``): a flight recorder must
#: never grow without bound inside a long-lived server. Overridable via
#: QUEST_TELEMETRY_EVENTS_MAX (parsed lazily at first event; QT303
#: warn-once on malformed values).
_MAX_EVENTS = 1 << 16
_EVENTS_MAX_ENV = "QUEST_TELEMETRY_EVENTS_MAX"
_EVENTS_MAX_WARNED: set = set()

#: the canonical per-request phase vector (docs/observability.md): every
#: finished trace carries all seven keys (0.0 when a phase never ran)
PHASES = ("queue_wait", "coalesce", "cache_lookup", "compile",
          "dispatch", "device", "resolve")

#: head-based trace sampling knob: off | errors | <rate in (0,1)> | all
_TRACE_ENV = "QUEST_TRACE"
_TRACE_WARNED: set = set()

#: cap on retained finished traces (oldest dropped first)
_MAX_TRACES = 4096

#: per-series reservoir cap backing the p50/p95/p99 snapshot rollups
_SAMPLE_CAP = 8192


def _label_key(labels: dict) -> str:
    """Canonical ``{k=v,...}`` suffix (sorted keys; '' when unlabeled)."""
    if not labels:
        return ""
    items = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return "{" + items + "}"


def _series_key(name: str, labels: dict) -> str:
    return name + _label_key(labels)


#: ``jax.profiler.TraceAnnotation``, resolved at the first span (telemetry
#: imports without JAX); False where JAX has no profiler to annotate
_ANNOTATION = None


def _annotate(name: str, labels: dict):
    """An entered profiler annotation of this name, or None. It puts the
    program's span into the profiler's host lines, on the device trace's
    clock; with no profiler session it is one activity check."""
    global _ANNOTATION
    cls = _ANNOTATION
    if cls is None:
        try:
            from jax.profiler import TraceAnnotation as cls
        except ImportError:  # pragma: no cover - JAX is a hard dependency
            cls = False
        _ANNOTATION = cls
    if not cls:
        return None
    ann = cls(name, **labels)
    ann.__enter__()
    return ann


class _SpanHandle:
    """One live span: context manager recording a monotonic duration into
    the registry on exit (and one JSONL event), under a profiler
    annotation of the same name. Nesting is tracked via the registry's
    thread-local stack; ``path`` is the '/'-joined ancestry."""

    __slots__ = ("_reg", "name", "labels", "_t0", "path", "duration_s",
                 "_ann")

    def __init__(self, reg: "MetricsRegistry", name: str, labels: dict):
        self._reg = reg
        self.name = name
        self.labels = labels
        self._t0 = 0.0
        self.path = name
        self.duration_s = None
        self._ann = None

    def __enter__(self):
        stack = self._reg._span_stack()
        if stack:
            self.path = stack[-1].path + "/" + self.name
        stack.append(self)
        self._ann = _annotate(self.name, self.labels)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.duration_s = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        stack = self._reg._span_stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._reg._finish_span(self)
        return False


class _Region:
    """One live hot-path region (:meth:`MetricsRegistry.region`): the
    ``perf_counter`` stamps ``t0``/``t1`` of a window on the calling
    thread, under a profiler annotation of its name. On exit it
    aggregates count / total / max under its name and writes nothing
    else. With ``reg`` None (an in-process :func:`disabled` block) it
    still stamps -- an armed request trace is charged from the stamps --
    and records nothing."""

    __slots__ = ("_reg", "name", "t0", "t1", "_ann")

    def __init__(self, reg, name: str):
        self._reg = reg
        self.name = name
        self.t0 = self.t1 = 0.0
        self._ann = None

    def __enter__(self):
        if self._reg is not None:
            self._ann = _annotate(self.name, {})
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        if self._reg is not None:
            self._reg._aggregate_span(self.name, self.t1 - self.t0)
        return False


class _NullSpan:
    """Shared no-op span and region for the disabled path (no allocation
    per call)."""

    __slots__ = ()
    duration_s = None
    path = ""
    t0 = t1 = 0.0

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()


def _registry_lock():
    """The registry's lock from the instrumented sync layer
    (``resilience.sync``, name ``telemetry.registry``, ``record=False``
    so recording a metric never records a metric). Telemetry sits below
    everything, so the layer is probed via sys.modules instead of
    imported: at bootstrap (sync itself imports telemetry first) this
    falls back to a raw lock, which sync adopts at ITS import."""
    sync = sys.modules.get(__name__.rsplit(".", 1)[0] + ".resilience.sync")
    if sync is not None:
        return sync.Lock("telemetry.registry", record=False)
    return threading.Lock()  # concheck: allow-raw-lock (bootstrap only)


class MetricsRegistry:
    """Process-global metric store; all module-level helpers delegate to
    one shared instance (:data:`REGISTRY`)."""

    def __init__(self):
        self._lock = _registry_lock()
        self._local = threading.local()
        self.enabled = _ENV_ENABLED
        self._jsonl_fh = None
        self._jsonl_path = os.environ.get(_JSONL_ENV)
        #: event-ring cap; resolved lazily at the first append so the
        #: QT303 diagnostic (which imports analysis.diagnostics, which
        #: imports this module) never runs during telemetry bootstrap
        self._events_max: int | None = None
        self._reset_locked()

    # -- storage ------------------------------------------------------------

    def _reset_locked(self):
        self._counters: dict[str, float] = {}
        self._gauges: dict[str, float] = {}
        self._hists: dict[str, dict] = {}
        self._spans: dict[str, dict] = {}
        #: the event ring; its bound is set at the first append, when
        #: the cap is resolved (:meth:`_events_cap`)
        self._events: collections.deque = collections.deque()
        self._events_dropped = 0
        #: bounded raw-sample reservoirs backing snapshot percentiles,
        #: series-keyed like _hists (only observe_sampled series get one)
        self._samples: dict[str, list] = {}
        #: retained finished request traces (JSON-ready dicts)
        self._traces: list[dict] = []
        #: thread ident -> (thread name, live TraceContext tuple): the
        #: QT703 leak scan reads this (a pooled thread that still holds a
        #: finished trace after future resolution leaked its context)
        self._thread_traces: dict[int, tuple] = {}

    def reset(self) -> None:
        """Drop every recorded metric and event (tests, bench sections)."""
        with self._lock:
            self._reset_locked()

    def _span_stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    # -- recording ----------------------------------------------------------

    def inc(self, name: str, value: float = 1.0, **labels) -> None:
        """Add ``value`` to the counter series ``name{labels}``."""
        if not self.enabled:
            return
        key = _series_key(name, labels)
        with self._lock:
            self._counters[key] = self._counters.get(key, 0.0) + value

    def set_gauge(self, name: str, value: float, **labels) -> None:
        """Set the gauge series ``name{labels}`` to ``value``."""
        if not self.enabled:
            return
        key = _series_key(name, labels)
        with self._lock:
            self._gauges[key] = float(value)

    def observe(self, name: str, value: float, **labels) -> None:
        """Record one observation into the histogram ``name{labels}``
        (count / sum / min / max aggregate -- enough to derive rates and
        spot outliers without shipping raw samples)."""
        if not self.enabled:
            return
        key = _series_key(name, labels)
        v = float(value)
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                self._hists[key] = {"count": 1, "sum": v, "min": v, "max": v}
            else:
                h["count"] += 1
                h["sum"] += v
                h["min"] = min(h["min"], v)
                h["max"] = max(h["max"], v)

    def observe_sampled(self, name: str, value: float, **labels) -> None:
        """:meth:`observe`, plus the raw value lands in a bounded
        per-series reservoir (sliding window of the last ``_SAMPLE_CAP``)
        so :meth:`snapshot` can report p50/p95/p99 for this series. Used
        for the SLO rollup series (``request_phase_ms{phase}``); ordinary
        histograms stay count/sum/min/max."""
        if not self.enabled:
            return
        key = _series_key(name, labels)
        v = float(value)
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = {"count": 1, "sum": v,
                                        "min": v, "max": v}
            else:
                h["count"] += 1
                h["sum"] += v
                h["min"] = min(h["min"], v)
                h["max"] = max(h["max"], v)
            s = self._samples.get(key)
            if s is None:
                s = self._samples[key] = []
            if len(s) < _SAMPLE_CAP:
                s.append(v)
            else:
                s[(h["count"] - 1) % _SAMPLE_CAP] = v

    def span(self, name: str, **labels):
        """Context manager timing a nested host-side region."""
        if not self.enabled:
            return _NULL_SPAN
        return _SpanHandle(self, name, labels)

    def region(self, name: str):
        """Context manager for a HOT-PATH window (``circuit.run``, the
        engine's per-batch regions): aggregate and annotate only. It
        counts into the span aggregates (count / total_s / max_s under
        ``name``) and opens the profiler annotation, and writes no ring
        event, reads no wall clock and streams no JSONL line -- a span
        per application would evict the ring's rare events
        (``pallas.compile``) within minutes. The handle carries the
        window's ``t0``/``t1`` stamps."""
        return _Region(self if self.enabled else None, name)

    def event(self, name: str, **fields) -> None:
        """Append one raw flight-recorder event (JSONL-exportable)."""
        if not self.enabled:
            return
        self._append_event({"kind": "event", "name": name, "t": time.time(),
                            **fields})

    def _aggregate_span(self, key: str, dur_s: float) -> None:
        with self._lock:
            agg = self._spans.get(key)
            if agg is None:
                self._spans[key] = {"count": 1, "total_s": dur_s,
                                    "max_s": dur_s}
            else:
                agg["count"] += 1
                agg["total_s"] += dur_s
                agg["max_s"] = max(agg["max_s"], dur_s)

    def _finish_span(self, sp: _SpanHandle) -> None:
        self._aggregate_span(_series_key(sp.name, sp.labels), sp.duration_s)
        self._append_event({"kind": "span", "name": sp.name, "t": time.time(),
                            "path": sp.path, "dur_s": round(sp.duration_s, 9),
                            **({"labels": sp.labels} if sp.labels else {})})

    def _events_cap(self) -> int:
        """The ring cap, parsed from QUEST_TELEMETRY_EVENTS_MAX on first
        use (outside the registry lock: the QT303 warn-once path records
        a finding counter, which takes it)."""
        cap = self._events_max
        if cap is None:
            cap = _MAX_EVENTS
            if os.environ.get(_EVENTS_MAX_ENV, "").strip():
                try:
                    from .analysis.diagnostics import parse_env_int
                    cap = parse_env_int(
                        _EVENTS_MAX_ENV, _MAX_EVENTS, minimum=1,
                        code="QT303", warned=_EVENTS_MAX_WARNED,
                        noun="telemetry event-buffer cap")
                except ImportError:  # pragma: no cover - bootstrap only
                    pass
            self._events_max = cap
        return cap

    def _append_event(self, ev: dict) -> None:
        cap = self._events_cap()
        with self._lock:
            ring = self._events
            if ring.maxlen != cap:
                ring = self._events = collections.deque(ring, maxlen=cap)
            if len(ring) == cap:   # the append below evicts the oldest
                self._events_dropped += 1
                key = "telemetry_events_dropped_total"
                self._counters[key] = self._counters.get(key, 0.0) + 1
            ring.append(ev)
        path = self._jsonl_path
        if path:
            self._stream_jsonl(ev, path)

    def _stream_jsonl(self, ev: dict, path: str) -> None:
        try:
            if self._jsonl_fh is None:
                self._jsonl_fh = open(path, "a", buffering=1)
            self._jsonl_fh.write(json.dumps(ev) + "\n")
        except OSError:  # a broken sink must never take the engine down
            self._jsonl_path = None

    # -- reading ------------------------------------------------------------

    def counter_value(self, name: str, **labels) -> float:
        """Value of one exact counter series (0.0 if never incremented)."""
        with self._lock:
            return self._counters.get(_series_key(name, labels), 0.0)

    def counter_total(self, name: str) -> float:
        """Sum of a counter across ALL label series."""
        prefix = name + "{"
        with self._lock:
            return sum(v for k, v in self._counters.items()
                       if k == name or k.startswith(prefix))

    def counters(self, name: str) -> dict:
        """{label-suffix: value} for every series of ``name`` ('' when
        unlabeled) -- the per-reason breakdown tests assert against."""
        prefix = name + "{"
        out = {}
        with self._lock:
            for k, v in self._counters.items():
                if k == name:
                    out[""] = v
                elif k.startswith(prefix):
                    out[k[len(name):]] = v
        return out

    def snapshot(self, prefix: str | None = None) -> dict:
        """The whole registry as one JSON-ready dict; ``prefix`` filters
        series names. Histogram/span sums are rounded to keep artifacts
        compact and diff-stable."""
        def keep(k):
            return prefix is None or k.startswith(prefix)

        def num(v):
            return int(v) if float(v).is_integer() else round(v, 6)

        def hist(k, h):
            out = {"count": h["count"], "sum": round(h["sum"], 6),
                   "min": round(h["min"], 6), "max": round(h["max"], 6)}
            s = self._samples.get(k)
            if s:  # percentile rollups only for reservoir-backed series
                arr = sorted(s)
                for q, lbl in ((0.50, "p50"), (0.95, "p95"), (0.99, "p99")):
                    out[lbl] = round(
                        arr[min(len(arr) - 1, int(q * len(arr)))], 6)
            return out

        with self._lock:
            return {
                "counters": {k: num(v)
                             for k, v in sorted(self._counters.items())
                             if keep(k)},
                "gauges": {k: round(v, 6)
                           for k, v in sorted(self._gauges.items())
                           if keep(k)},
                "histograms": {
                    k: hist(k, h)
                    for k, h in sorted(self._hists.items()) if keep(k)},
                "spans": {
                    k: {"count": a["count"],
                        "total_s": round(a["total_s"], 6),
                        "max_s": round(a["max_s"], 6)}
                    for k, a in sorted(self._spans.items()) if keep(k)},
            }

    def events(self) -> list:
        """A copy of the in-memory event ring (most recent last)."""
        with self._lock:
            return list(self._events)

    def export_jsonl(self, path: str, clear: bool = False) -> int:
        """Write every buffered event as one JSON line each; returns the
        number of lines written. ``clear`` drops the buffer afterwards.
        When the ring dropped events (buffer cap, satellite of round 17)
        a leading ``{"kind": "meta", ...}`` line reports how many, so a
        consumer can tell a quiet server from a saturated ring."""
        with self._lock:
            evs = list(self._events)
            dropped = self._events_dropped
            if clear:
                self._events.clear()
        if dropped:
            evs.insert(0, {"kind": "meta", "events_dropped": dropped,
                           "events_max": self._events_cap()})
        with open(path, "w") as fh:
            for ev in evs:
                fh.write(json.dumps(ev) + "\n")
        return len(evs)


#: the process-global registry every instrumented layer reports into
REGISTRY = MetricsRegistry()


# ---------------------------------------------------------------------------
# module-level convenience surface (what instrumented code imports)
# ---------------------------------------------------------------------------

def enabled() -> bool:
    """True when telemetry is recording (QUEST_TELEMETRY != 0 and not
    inside a :func:`disabled` block)."""
    return REGISTRY.enabled


@contextlib.contextmanager
def disabled():
    """Temporarily disable all recording in-process (tests use this to
    assert the instrumented paths are result-identical without telemetry;
    for true zero-overhead use QUEST_TELEMETRY=0 at process start)."""
    prev = REGISTRY.enabled
    REGISTRY.enabled = False
    try:
        yield
    finally:
        REGISTRY.enabled = prev


def inc(name: str, value: float = 1.0, **labels) -> None:
    REGISTRY.inc(name, value, **labels)


def set_gauge(name: str, value: float, **labels) -> None:
    REGISTRY.set_gauge(name, value, **labels)


def observe(name: str, value: float, **labels) -> None:
    REGISTRY.observe(name, value, **labels)


def span(name: str, **labels):
    return REGISTRY.span(name, **labels)


def region(name: str):
    return REGISTRY.region(name)


def event(name: str, **fields) -> None:
    REGISTRY.event(name, **fields)


def counter_value(name: str, **labels) -> float:
    return REGISTRY.counter_value(name, **labels)


def counter_total(name: str) -> float:
    return REGISTRY.counter_total(name)


def counters(name: str) -> dict:
    return REGISTRY.counters(name)


def snapshot(prefix: str | None = None) -> dict:
    return REGISTRY.snapshot(prefix)


def reset() -> None:
    REGISTRY.reset()


def export_jsonl(path: str, clear: bool = False) -> int:
    return REGISTRY.export_jsonl(path, clear)


def events() -> list:
    return REGISTRY.events()


# ---------------------------------------------------------------------------
# request tracing (round 17): causal span trees across the serving fleet
# ---------------------------------------------------------------------------

#: resolved QUEST_TRACE policy: mode in {"off","errors","rate","all"},
#: rate in [0,1]. Resolved lazily on the first trace_on() call so the
#: QT701 diagnostic (analysis.diagnostics imports this module) never runs
#: during telemetry bootstrap; trace_policy() overrides it in-process.
_TRACE_MODE = "off"
_TRACE_RATE = 0.0
_TRACE_RESOLVED = False

#: per-process monotonic trace-id sequence (advanced under REGISTRY._lock)
_TRACE_SEQ = 0


def _parse_trace(raw: str):
    """(mode, rate, error) for one QUEST_TRACE value; error is a human
    fragment when the value is malformed (mode falls back to off)."""
    v = raw.strip().lower()
    if v in ("", "off", "0", "0.0", "false", "none"):
        return "off", 0.0, None
    if v in ("errors", "error"):
        return "errors", 0.0, None
    if v in ("all", "on", "1", "1.0", "true"):
        return "all", 1.0, None
    try:
        rate = float(v)
    except ValueError:
        return "off", 0.0, "is not off|errors|<rate in (0,1)>|all"
    if not 0.0 <= rate <= 1.0:
        return "off", 0.0, f"rate {rate:g} is outside [0, 1]"
    if rate >= 1.0:
        return "all", 1.0, None
    return "rate", rate, None


def _resolve_trace_mode() -> None:
    global _TRACE_MODE, _TRACE_RATE, _TRACE_RESOLVED
    raw = os.environ.get(_TRACE_ENV, "")
    mode, rate, err = _parse_trace(raw)
    if err is not None and raw.strip() not in _TRACE_WARNED:
        _TRACE_WARNED.add(raw.strip())
        try:  # deferred: diagnostics imports telemetry, never the reverse
            import warnings

            from .analysis.diagnostics import emit_findings, make_finding
            f = make_finding(
                "QT701",
                f"{_TRACE_ENV}={raw!r} {err}; tracing stays off",
                f"env:{_TRACE_ENV}")
            emit_findings([f])
            warnings.warn(str(f), RuntimeWarning, stacklevel=4)
        except ImportError:  # pragma: no cover - bootstrap only
            pass
    _TRACE_MODE, _TRACE_RATE, _TRACE_RESOLVED = mode, rate, True


def trace_on() -> bool:
    """True when request tracing is armed. The hot-path contract matches
    :func:`span`: with QUEST_TRACE unset this is one boolean read (after
    a one-time env parse) and every instrumented site bails on it."""
    if not _TRACE_RESOLVED:
        _resolve_trace_mode()
    return _TRACE_MODE != "off" and REGISTRY.enabled


def trace_mode() -> str:
    """The resolved sampling mode: off | errors | rate | all."""
    if not _TRACE_RESOLVED:
        _resolve_trace_mode()
    return _TRACE_MODE


@contextlib.contextmanager
def trace_policy(mode):
    """In-process QUEST_TRACE override (bench phase sections, tests):
    ``with trace_policy("all"): ...`` arms tracing regardless of the
    environment, restoring the prior policy on exit. Raises ValueError
    on a malformed mode (in-process callers get errors, not QT701)."""
    global _TRACE_MODE, _TRACE_RATE, _TRACE_RESOLVED
    m, r, err = _parse_trace(str(mode))
    if err is not None:
        raise ValueError(f"bad trace mode {mode!r}: {err}")
    prev = (_TRACE_MODE, _TRACE_RATE, _TRACE_RESOLVED)
    _TRACE_MODE, _TRACE_RATE, _TRACE_RESOLVED = m, r, True
    try:
        yield
    finally:
        _TRACE_MODE, _TRACE_RATE, _TRACE_RESOLVED = prev


class _Trace:
    """Shared mutable state of one request trace; every
    :class:`TraceContext` handle points at one of these. Mutated only
    under ``REGISTRY._lock``."""

    __slots__ = ("trace_id", "name", "labels", "wall0", "perf0", "spans",
                 "links", "events", "phases", "error", "sampled", "done",
                 "nspans", "mark")

    def __init__(self, trace_id, name, labels, wall0, perf0, sampled):
        self.trace_id = trace_id
        self.name = name
        self.labels = labels
        self.wall0 = wall0      # epoch seconds at perf0 (chrome ts base)
        self.perf0 = perf0      # perf_counter origin for span offsets
        self.spans: dict[str, dict] = {}
        self.links: list[dict] = []
        self.events: list[dict] = []
        self.phases: dict[str, float] = {}
        self.error = None
        self.sampled = sampled
        self.done = False
        self.nspans = 0
        #: perf_counter stamp up to which the request's time has been
        #: charged to a phase (:meth:`TraceContext.charge`)
        self.mark = perf0


class TraceContext:
    """A handle onto one span of one request trace.

    Minted by :func:`start_trace` (the root span, ``owns_root=True``) and
    by :meth:`child`; carries ``trace_id`` / ``span_id`` / ``parent_id``
    across thread hops. The layer that minted the root finishes it
    (:func:`finish_trace`); adopted child contexts only :meth:`end` their
    own span. All methods are cheap dict appends under the registry lock
    and are only ever called on the armed path (``trace_on()`` gated)."""

    __slots__ = ("_tr", "span_id", "owns_root")

    def __init__(self, tr: _Trace, span_id: str, owns_root: bool):
        self._tr = tr
        self.span_id = span_id
        self.owns_root = owns_root

    @property
    def trace_id(self) -> str:
        return self._tr.trace_id

    @property
    def parent_id(self):
        sp = self._tr.spans.get(self.span_id)
        return sp["parent"] if sp else None

    @property
    def done(self) -> bool:
        return self._tr.done

    def _add_span(self, name, parent, t0, dur_ms, status, labels,
                  cat=None) -> str:
        tr = self._tr
        with REGISTRY._lock:
            sid = f"s{tr.nspans}"
            tr.nspans += 1
            sp = {"id": sid, "parent": parent, "name": name,
                  "t0_ms": round((t0 - tr.perf0) * 1e3, 6),
                  "dur_ms": dur_ms, "status": status,
                  "thread": threading.current_thread().name}
            if cat:
                sp["cat"] = cat
            if labels:
                sp["labels"] = labels
            tr.spans[sid] = sp
        return sid

    def child(self, name: str, **labels) -> "TraceContext":
        """Open a child span under this one; the returned context must be
        :meth:`end`-ed (a finished trace with an open span is QT702)."""
        sid = self._add_span(name, self.span_id, time.perf_counter(),
                             None, "open", labels)
        return TraceContext(self._tr, sid, False)

    def end(self, status: str = "ok") -> None:
        """Close this context's span (idempotent)."""
        now = time.perf_counter()
        tr = self._tr
        with REGISTRY._lock:
            sp = tr.spans.get(self.span_id)
            if sp is not None and sp["dur_ms"] is None:
                sp["dur_ms"] = round(
                    (now - tr.perf0) * 1e3 - sp["t0_ms"], 6)
                sp["status"] = status

    def record_span(self, name: str, t0: float, dur_s: float,
                    status: str = "ok", **labels) -> str:
        """Record an already-measured closed span (``t0`` from
        ``time.perf_counter()``) under this context; returns its id."""
        return self._add_span(name, self.span_id, t0,
                              round(dur_s * 1e3, 6), status, labels)

    def charge(self, name: str, t_end: float) -> None:
        """Attribute to the canonical phase ``name`` the window from the
        trace's mark (where its last charged window ended; the root's
        start at first) to ``t_end``, a ``perf_counter`` stamp, and move
        the mark there: the trace's phase vector accumulates it AND a
        closed ``cat="phase"`` span is recorded so the waterfall shows
        where the time sat. Every layer that works for the request
        charges the one mark, so the phases tile the root span by
        construction -- what happens between two charged windows falls to
        the later one, nothing is dropped and nothing counted twice (a
        ``t_end`` at or before the mark, as from the slower leg of a
        hedge, charges nothing)."""
        tr = self._tr
        with REGISTRY._lock:
            t0 = tr.mark
            if t_end <= t0:
                return
            tr.mark = t_end
            ms = (t_end - t0) * 1e3
            tr.phases[name] = tr.phases.get(name, 0.0) + ms
            sid = f"s{tr.nspans}"
            tr.nspans += 1
            tr.spans[sid] = {
                "id": sid, "parent": self.span_id, "name": name,
                "t0_ms": round((t0 - tr.perf0) * 1e3, 6),
                "dur_ms": round(ms, 6), "status": "ok", "cat": "phase",
                "thread": threading.current_thread().name}

    def add_link(self, frm, to, kind: str) -> None:
        """Record a causal link between two spans (hedge duplicate ->
        primary, failover re-dispatch -> failed attempt, retry attempts,
        bisection halves). ``frm``/``to`` are contexts or span ids."""
        fid = frm.span_id if isinstance(frm, TraceContext) else frm
        tid = to.span_id if isinstance(to, TraceContext) else to
        with REGISTRY._lock:
            self._tr.links.append({"from": fid, "to": tid, "kind": kind})

    def link(self, to, kind: str) -> None:
        """:meth:`add_link` from this context's span."""
        self.add_link(self, to, kind)

    def event(self, name: str, **fields) -> None:
        """Append a point event to the trace (rendered as instants)."""
        tr = self._tr
        t_ms = round((time.perf_counter() - tr.perf0) * 1e3, 6)
        with REGISTRY._lock:
            tr.events.append({"name": name, "t_ms": t_ms, "span": self.span_id,
                              **({"fields": fields} if fields else {})})


def start_trace(name: str, t0: float | None = None,
                **labels) -> TraceContext | None:
    """Mint a new request trace and return its root context, or None when
    tracing is off (callers store the None and every later hop skips on
    it). ``t0`` backdates the root to an earlier ``perf_counter`` reading
    (e.g. admission entry) so pre-mint work lands inside the trace.
    Retention is decided at :func:`finish_trace`: mode ``all`` keeps
    everything, ``rate`` keeps a head-based coin flip drawn here, and
    errored requests are always kept (the ``errors`` mode contract)."""
    if not trace_on():
        return None
    global _TRACE_SEQ
    perf = time.perf_counter()
    wall = time.time()
    if t0 is not None:
        wall -= perf - t0
        perf = t0
    if _TRACE_MODE == "all":
        sampled = True
    elif _TRACE_MODE == "rate":
        import random
        sampled = random.random() < _TRACE_RATE
    else:
        sampled = False
    with REGISTRY._lock:
        _TRACE_SEQ += 1
        trace_id = f"{os.getpid():x}-{_TRACE_SEQ:06d}"
    tr = _Trace(trace_id, name, labels, wall, perf, sampled)
    ctx = TraceContext(tr, "s0", True)
    with REGISTRY._lock:
        tr.nspans = 1
        tr.spans["s0"] = {"id": "s0", "parent": None, "name": name,
                          "t0_ms": 0.0, "dur_ms": None, "status": "open",
                          "thread": threading.current_thread().name,
                          **({"labels": labels} if labels else {})}
    return ctx


def finish_trace(ctx: TraceContext | None, error: str | None = None,
                 now: float | None = None) -> None:
    """Close a trace minted by :func:`start_trace` (idempotent): the root
    span closes, the phase vector is completed to all :data:`PHASES` keys
    and fed into the ``request_phase_ms{phase}`` rollups, and the trace is
    retained (sampled, or ``error`` is set) or discarded. ``now`` closes
    the root at a ``perf_counter`` stamp the caller already holds -- the
    end of the request's last phase window, so that the phases tile the
    root exactly and no clock read falls between the two."""
    if ctx is None:
        return
    tr = ctx._tr
    if now is None:
        now = time.perf_counter()
    with REGISTRY._lock:
        if tr.done:
            return
        tr.done = True
        tr.error = error
        root = tr.spans["s0"]
        if root["dur_ms"] is None:
            root["dur_ms"] = round((now - tr.perf0) * 1e3, 6)
            root["status"] = "error" if error else "ok"
        for p in PHASES:
            tr.phases.setdefault(p, 0.0)
        keep = tr.sampled or error is not None
        if keep:
            REGISTRY._traces.append({
                "trace_id": tr.trace_id, "name": tr.name,
                "labels": tr.labels, "t0": tr.wall0,
                "dur_ms": root["dur_ms"], "error": error,
                "phases_ms": {p: round(v, 6) for p, v in
                              sorted(tr.phases.items())},
                "spans": list(tr.spans.values()),
                "links": list(tr.links), "events": list(tr.events)})
            drop = len(REGISTRY._traces) - _MAX_TRACES
            if drop > 0:
                del REGISTRY._traces[:drop]
        phases = dict(tr.phases)
    for p, ms in phases.items():
        REGISTRY.observe_sampled("request_phase_ms", ms, phase=p)
    REGISTRY.inc("trace_requests_total",
                 outcome="error" if error else
                 ("sampled" if tr.sampled else "unsampled"))


def set_current_trace(ctxs) -> None:
    """Bind the trace context(s) being worked for to the current thread
    (a single context, an iterable, or None/empty to clear). Batchers
    bind the whole batch before dispatch and MUST clear after the futures
    resolve -- a pooled thread still holding finished traces is QT703."""
    if ctxs is None:
        tup = ()
    elif isinstance(ctxs, TraceContext):
        tup = (ctxs,)
    else:
        tup = tuple(c for c in ctxs if c is not None)
    t = threading.current_thread()
    REGISTRY._local.trace = tup
    with REGISTRY._lock:
        if tup:
            REGISTRY._thread_traces[t.ident] = (t.name, tup)
        else:
            REGISTRY._thread_traces.pop(t.ident, None)


def clear_current_trace() -> None:
    """Unbind this thread's trace context(s) (see QT703)."""
    set_current_trace(None)


def current_trace() -> TraceContext | None:
    """The innermost trace context bound to this thread, if any."""
    cur = getattr(REGISTRY._local, "trace", ())
    return cur[-1] if cur else None


def current_traces() -> tuple:
    """All trace contexts bound to this thread (a dispatching batcher
    works for every traced request in the batch at once)."""
    return getattr(REGISTRY._local, "trace", ())


def trace_event_current(name: str, **fields) -> None:
    """Record a point event on every trace bound to this thread (retry
    attempts, degrades): no-op when nothing is bound."""
    for ctx in current_traces():
        ctx.event(name, **fields)


def trace_thread_leaks() -> list:
    """(thread_name, trace_id) pairs for threads whose bound contexts are
    ALL finished -- the QT703 signal (context leaked across pooled-thread
    reuse; the next request on that thread would inherit a dead trace)."""
    with REGISTRY._lock:
        items = list(REGISTRY._thread_traces.items())
    leaks = []
    for _tid, (tname, ctxs) in items:
        if ctxs and all(c.done for c in ctxs):
            leaks.append((tname, ctxs[-1].trace_id))
    return leaks


def traces() -> list:
    """Retained finished traces (JSON-ready dicts, oldest first). Treat
    as read-only; :func:`reset` drops them."""
    with REGISTRY._lock:
        return list(REGISTRY._traces)


def chrome_trace_events(trs: list) -> list:
    """Convert trace dicts (:func:`traces` / ``export_traces`` files) to
    Chrome trace-event objects: one ``ph="X"`` complete event per span
    (phase spans keep ``cat="phase"``), ``ph="s"/"f"`` flow events per
    causal link, instants for trace events, and thread-name metadata.
    Pure function -- ``tools/traceview.py --chrome`` uses it offline."""
    events = [{"ph": "M", "pid": 0, "tid": 0, "name": "process_name",
               "args": {"name": "quest_tpu"}}]
    tids: dict[str, int] = {}

    def tid_of(thread_name):
        tid = tids.get(thread_name)
        if tid is None:
            tid = tids[thread_name] = len(tids) + 1
            events.append({"ph": "M", "pid": 0, "tid": tid,
                           "name": "thread_name",
                           "args": {"name": thread_name}})
        return tid

    flow = 0
    for t in trs:
        base_us = t["t0"] * 1e6
        by_id = {sp["id"]: sp for sp in t["spans"]}
        for sp in t["spans"]:
            events.append({
                "ph": "X", "pid": 0, "tid": tid_of(sp.get("thread", "?")),
                "name": sp["name"], "cat": sp.get("cat", "span"),
                "ts": base_us + sp["t0_ms"] * 1e3,
                "dur": (sp["dur_ms"] or 0.0) * 1e3,
                "args": {"trace_id": t["trace_id"], "span_id": sp["id"],
                         "status": sp.get("status", "ok"),
                         **sp.get("labels", {})}})
        for ln in t.get("links", ()):
            a, b = by_id.get(ln["from"]), by_id.get(ln["to"])
            if a is None or b is None:
                continue
            flow += 1
            events.append({"ph": "s", "pid": 0,
                           "tid": tid_of(a.get("thread", "?")),
                           "id": flow, "name": ln["kind"], "cat": "link",
                           "ts": base_us + a["t0_ms"] * 1e3})
            events.append({"ph": "f", "bp": "e", "pid": 0,
                           "tid": tid_of(b.get("thread", "?")),
                           "id": flow, "name": ln["kind"], "cat": "link",
                           "ts": base_us + b["t0_ms"] * 1e3})
        for ev in t.get("events", ()):
            sp = by_id.get(ev.get("span"))
            events.append({
                "ph": "i", "pid": 0, "s": "t",
                "tid": tid_of((sp or {}).get("thread", "?")),
                "name": ev["name"], "cat": "event",
                "ts": base_us + ev["t_ms"] * 1e3,
                "args": {"trace_id": t["trace_id"],
                         **ev.get("fields", {})}})
    return events


def export_chrome_trace(path: str) -> int:
    """Write every retained trace as Perfetto-loadable Chrome trace-event
    JSON (``{"traceEvents": [...]}``); returns the trace count."""
    trs = traces()
    with open(path, "w") as fh:
        json.dump({"traceEvents": chrome_trace_events(trs),
                   "displayTimeUnit": "ms"}, fh)
    return len(trs)


def export_traces(path: str) -> int:
    """Write the retained traces verbatim (``{"traces": [...]}``), the
    ``tools/traceview.py`` input format; returns the trace count."""
    trs = traces()
    with open(path, "w") as fh:
        json.dump({"traces": trs}, fh)
    return len(trs)


# ---------------------------------------------------------------------------
# compile events from inside JAX: which step traced, lowered, compiled
# ---------------------------------------------------------------------------

#: ``jax.monitoring`` duration event -> the histogram it feeds (seconds)
_JAX_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax_trace_seconds",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax_lower_seconds",
    "/jax/core/compile/backend_compile_duration":
        "jax_backend_compile_seconds",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "jax_cache_retrieval_seconds",
}
#: ``jax.monitoring`` event -> the counter it feeds
_JAX_EVENTS = {
    "/jax/compilation_cache/cache_hits": "jax_cache_hits_total",
    "/jax/compilation_cache/cache_misses": "jax_cache_misses_total",
}
#: the intervals JAX announces where they START (a ``record_scalar`` of the
#: event's name from ``dispatch.log_elapsed_time``) as well as where they
#: end; a cache retrieval is only reported once it is over, and holds none
_JAX_ANNOUNCED = frozenset(e for e in _JAX_DURATIONS
                           if "/compilation_cache/" not in e)
_JAX_WATCHING = False

#: one thread's running totals (:func:`compile_mark`), in order: the four
#: phases are the four series' own-time sums, in ``_JAX_DURATIONS``' order
_MARK_FIELDS = ("events", "traces", "trace_s", "lower_s", "compile_s",
                "cache_load_s", "cache_hits", "cache_misses", "kernels",
                "kernel_trace_s")
_MARK_ZERO = (0, 0, 0.0, 0.0, 0.0, 0.0, 0, 0, 0, 0.0)
#: duration event -> (histogram, its phase's place in the totals)
_JAX_PHASE = {event: (name, 2 + i)
              for i, (event, name) in enumerate(_JAX_DURATIONS.items())}


class _CompileState(threading.local):
    """What one thread's listeners keep: ``seen``, the stack of
    :func:`_own_time`; ``mark``, the thread's running totals as one
    immutable tuple (:data:`_MARK_FIELDS`), replaced -- never changed --
    by every compile-path event on the thread, so that whoever holds an
    earlier one tells by identity that something compiled since;
    ``kernels``, the names of the kernels traced since the last
    ``program.first_call`` record, in order."""

    def __init__(self):
        self.seen: list = []
        self.mark: tuple = _MARK_ZERO
        self.kernels: list = []

    def add(self, *grown) -> None:
        """Replace the totals: ``grown`` is (place, by) pairs."""
        mark = list(self.mark)
        for place, by in grown:
            mark[place] += by
        self.mark = tuple(mark)


_COMPILING = _CompileState()


def _own_time(seen: list, duration: float, announced: bool) -> float:
    """What a finished interval of ``duration`` seconds is charged: its
    length less what the intervals nested in it on this thread were
    charged already.

    ``seen`` is one thread's stack. ``None`` marks an interval JAX has
    announced and not yet reported (:func:`_jax_started`); a number is what
    the intervals reported since the entry below it were charged, all of
    them: finished siblings merge into one number as they are reported
    (their charged time is all a parent needs), so the stack is as deep as
    the nest, however many intervals nest. When an ``announced`` interval
    ends, what stands above its marker is what nested in it; an interval
    that is not announced (a retrieval) holds nothing. No clock is
    compared. Every number is at most the wall time its intervals covered,
    so on one thread the sums of all series never exceed the wall time
    covered. (An interval that was open when the listeners were registered
    finds no marker and claims the whole stack: too little, never too
    much.)"""
    inner = 0.0
    while announced and seen:
        top = seen.pop()
        if top is None:
            break
        inner += top
    own = max(0.0, duration - inner)
    if seen and seen[-1] is not None:
        seen[-1] += own + inner      # a sibling of the tail: one entry
    else:
        seen.append(own + inner)
    return own


def _jax_started(event: str, _value=None, **_kw) -> None:
    """JAX announces that an interval of its compile path begins."""
    if event in _JAX_ANNOUNCED:
        _COMPILING.seen.append(None)


def _jax_duration(event: str, duration: float, **_kw) -> None:
    """One finished interval of JAX's compile path, reported at its end.
    JAX reports an inner ``jit``'s trace and again inside its caller's
    (and a cache retrieval inside the backend compile that made it): an
    interval is charged its own time LESS what the intervals nested in it
    on this thread were already charged, whatever their series, so that
    the four sums together never exceed the wall time of the thread
    (:func:`_own_time`). The stack is kept while recording is
    :func:`disabled` too; only the observation is not made."""
    series = _JAX_PHASE.get(event)
    if series is None:
        return
    name, place = series
    state = _COMPILING
    own = _own_time(state.seen, duration, event in _JAX_ANNOUNCED)
    if not REGISTRY.enabled:
        return
    REGISTRY.observe(name, own)
    state.add((0, 1), (1, place == 2), (place, own))


def _jax_event(event: str, **_kw) -> None:
    name = _JAX_EVENTS.get(event)
    if name is not None and REGISTRY.enabled:
        REGISTRY.inc(name)
        # jax_cache_hits_total -> cache_hits
        _COMPILING.add((_MARK_FIELDS.index(name[4:-6]), 1))


def watch_jax_compiles() -> bool:
    """Register the ``jax.monitoring`` listeners that feed
    ``jax_trace_seconds``, ``jax_lower_seconds``,
    ``jax_backend_compile_seconds``, ``jax_cache_retrieval_seconds``
    (histograms: count, sum, max) and ``jax_cache_hits_total`` /
    ``jax_cache_misses_total``. Called when ``quest_tpu`` is imported;
    idempotent. They fire only when JAX traces, lowers or compiles:
    nothing runs on a warm call."""
    global _JAX_WATCHING
    if _JAX_WATCHING:
        return True
    try:
        from jax import monitoring
        # an interval's start has to be announced (_own_time): JAX 0.9
        monitoring.register_scalar_listener(_jax_started)
    except (ImportError, AttributeError):  # pragma: no cover
        return False
    monitoring.register_event_duration_secs_listener(_jax_duration)
    monitoring.register_event_listener(_jax_event)
    _JAX_WATCHING = True
    return True


# ---------------------------------------------------------------------------
# a first call: which program compiled, which kernels, what each phase cost
# ---------------------------------------------------------------------------

def compile_mark() -> tuple:
    """This thread's running compile totals (:data:`_MARK_FIELDS`): an
    immutable tuple that every compile-path event on the thread replaces.
    Held across a window, ``compile_mark() is mark`` says that nothing
    traced, lowered, compiled or loaded inside it -- one thread-local read
    at each end, which is all a warm call pays."""
    return _COMPILING.mark


def kernel_traced(name: str, mark: tuple) -> int:
    """Note that the kernel ``name`` was traced on this thread since
    ``mark`` was read (``ops.pallas_gates``, once a new kernel signature):
    what JAX's trace events were charged inside goes to the thread's
    ``kernel_trace_s``. Returns how many of them fired there."""
    state = _COMPILING
    grown = dict(zip(_MARK_FIELDS, (b - a for a, b in zip(mark, state.mark))))
    state.kernels.append(name)
    state.add((_MARK_FIELDS.index("kernels"), 1),
              (_MARK_FIELDS.index("kernel_trace_s"), grown["trace_s"]))
    return grown["traces"]


def first_call(mark: tuple, rg, program: str, route: str) -> bool:
    """Close a window in which a program may have been called for the
    first time: ``mark`` is :func:`compile_mark` read where the region
    ``rg`` began (a caller that holds the same mark again has nothing to
    close, and need not make the name). When, and only when, a
    compile-path event fired on this thread since, emit ONE
    ``program.first_call`` event -- ``program`` (the jitted function's
    name, ``circuits.named_program``'s), ``route``, the region's own
    ``dur_s``, and the phases ``trace_s``, ``lower_s``, ``compile_s``,
    ``cache_load_s`` (the listeners' own-time sums over the window) and
    ``rest_s`` (the remainder: transfer, launch, the put), which so add up
    to ``dur_s`` by construction; beside them ``kernel_trace_s`` (the part
    of ``trace_s`` inside the kernels' compile records), ``kernels`` (the
    kernels first traced here, in program order) and ``cache_hits`` /
    ``cache_misses``. Returns whether it did. A retrace in the middle of a served window
    makes the same record."""
    state = _COMPILING
    now = state.mark
    if now is mark or not REGISTRY.enabled:
        return False
    grown = dict(zip(_MARK_FIELDS, (b - a for a, b in zip(mark, now))))
    dur_s = round(rg.t1 - rg.t0, 6)
    phases = {k: round(grown[k], 6)
              for k in ("trace_s", "lower_s", "compile_s", "cache_load_s")}
    phases["rest_s"] = round(dur_s - sum(phases.values()), 6)
    kernels = state.kernels[len(state.kernels) - grown["kernels"]:]
    del state.kernels[:]         # named once; the list stays short
    REGISTRY.event(
        "program.first_call", program=program, route=route, dur_s=dur_s,
        **phases,
        kernel_trace_s=round(grown["kernel_trace_s"], 6), kernels=kernels,
        nested_traces=grown["traces"], cache_hits=grown["cache_hits"],
        cache_misses=grown["cache_misses"])
    return True


# ---------------------------------------------------------------------------
# QUEST_TELEMETRY=0: swap the whole surface for no-op stubs at import, so a
# disabled process pays nothing beyond one module import (no allocation, no
# lock, no dict lookups -- the "zero-overhead-when-disabled" guarantee)
# ---------------------------------------------------------------------------

if not _ENV_ENABLED:  # pragma: no cover - exercised via subprocess test
    def _noop(*args, **kwargs):
        return None

    def _zero(*args, **kwargs):
        return 0.0

    def _empty_dict(*args, **kwargs):
        return {}

    def _null_span(*args, **kwargs):
        return _NULL_SPAN

    def _false(*args, **kwargs):
        return False

    def _empty_list(*args, **kwargs):
        return []

    def _empty_tuple(*args, **kwargs):
        return ()

    inc = set_gauge = observe = event = reset = _noop  # noqa: F811
    span = region = _null_span                         # noqa: F811

    def watch_jax_compiles():                          # noqa: F811
        return False
    compile_mark = _noop                               # noqa: F811
    first_call = _false                                # noqa: F811

    kernel_traced = _zero                              # noqa: F811
    counter_value = counter_total = _zero              # noqa: F811
    counters = _empty_dict                             # noqa: F811

    def snapshot(prefix=None):                         # noqa: F811
        return {"counters": {}, "gauges": {}, "histograms": {}, "spans": {}}

    def export_jsonl(path, clear=False):               # noqa: F811
        return 0

    def events():                                      # noqa: F811
        return []

    # tracing rides the same master switch: a telemetry-disabled process
    # never traces, whatever QUEST_TRACE says (chrome_trace_events stays
    # live -- it is a pure converter over already-exported files)
    trace_on = _false                                                # noqa: F811
    start_trace = finish_trace = current_trace = _noop               # noqa: F811
    set_current_trace = clear_current_trace = _noop                  # noqa: F811
    trace_event_current = _noop                                      # noqa: F811
    current_traces = _empty_tuple                                    # noqa: F811
    traces = trace_thread_leaks = _empty_list                        # noqa: F811

    def trace_mode():                                  # noqa: F811
        return "off"

    @contextlib.contextmanager
    def trace_policy(mode):                            # noqa: F811
        yield

    def export_chrome_trace(path):                     # noqa: F811
        return 0

    def export_traces(path):                           # noqa: F811
        return 0
