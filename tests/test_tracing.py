"""End-to-end request tracing (quest_tpu.telemetry span trees, round 17).

Contracts under test:

- QUEST_TRACE unset: ``trace_on()`` is False, engine requests carry no
  trace and the registry retains nothing (the zero-overhead-off
  contract);
- ONE engine request under ``trace_policy("all")`` mints ONE trace whose
  canonical 7-phase vector (queue_wait, coalesce, cache_lookup, compile,
  dispatch, device, resolve) sums within 1% of its end-to-end latency,
  with every span closed, and exports as Perfetto-loadable Chrome
  trace-event JSON;
- hedged dispatch: the duplicate span links ``kind="hedge"`` to the
  primary attempt, the losing leg's span ends ``cancelled``, and both
  legs share ONE trace_id (first-completion-wins stays attributable);
- quarantine failover: the re-dispatched attempt keeps the SAME trace_id
  and links ``kind="failover"`` to the failed attempt's span;
- sampling: ``errors`` mode retains errored requests only; a malformed
  QUEST_TRACE warns once as QT701 and tracing stays off;
- QT702 (span never closed) / QT703 (context leaked across pooled-thread
  reuse) fire on synthetic leaks and stay silent after a clean serving
  run (quest_tpu.analysis.tracecheck);
- the flight-recorder event ring caps at QUEST_TELEMETRY_EVENTS_MAX,
  counts ``telemetry_events_dropped_total`` and export_jsonl leads with
  the meta line (round-17 satellite);
- the interleaving explorer's production serving scenarios stay
  schedule-complete (zero breaches) with tracing armed.
"""

import json
import threading
import time
import warnings

import numpy as np
import pytest

import jax

import quest_tpu as qt
from quest_tpu import analysis as A
from quest_tpu import telemetry
from quest_tpu.analysis import concheck as C
from quest_tpu.circuits import Circuit
from quest_tpu.engine import Engine, EnginePool, P
from quest_tpu.resilience import faultinject

ENV1 = qt.createQuESTEnv(jax.devices()[:1])

PHASES = ("queue_wait", "coalesce", "cache_lookup", "compile",
          "dispatch", "device", "resolve")


def _ansatz(n=3):
    c = Circuit(n)
    for q in range(n):
        c.rotateY(q, P(f"t{q}"))
    for q in range(n - 1):
        c.controlledNot(q, q + 1)
    return c


def _params(c, seed):
    rng = np.random.default_rng(seed)
    return {name: float(v) for name, v
            in zip(c.lifted().param_names, rng.uniform(-2, 2, 64))}


def _block(eng):
    """Stall ``eng``'s dispatches behind an Event; returns the gate."""
    gate = threading.Event()
    orig = eng._dispatch_one

    def blocked(batch, mode):
        gate.wait(30)
        return orig(batch, mode)

    eng._dispatch_one = blocked
    return gate


def _wait(pred, timeout=10.0):
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout:
        if pred():
            return True
        time.sleep(0.01)
    return pred()


def _pool_traces():
    return [t for t in telemetry.traces()
            if t["labels"].get("kind") == "pool"]


# ---------------------------------------------------------------------------
# off by default: the zero-overhead contract
# ---------------------------------------------------------------------------

def test_tracing_off_by_default(monkeypatch):
    monkeypatch.delenv("QUEST_TRACE", raising=False)
    monkeypatch.setattr(telemetry, "_TRACE_RESOLVED", False)
    monkeypatch.setattr(telemetry, "_TRACE_MODE", "off")
    telemetry.reset()
    assert telemetry.trace_on() is False
    assert telemetry.trace_mode() == "off"
    assert telemetry.start_trace("request") is None
    telemetry.finish_trace(None)  # None flows through every hop for free
    c = _ansatz()
    with Engine(c, ENV1, max_batch=2, max_delay_ms=0.0) as eng:
        np.asarray(eng.submit(_params(c, 0)).result(60))
    assert telemetry.traces() == []
    assert telemetry.trace_thread_leaks() == []


# ---------------------------------------------------------------------------
# the acceptance path: one request, full phase vector, Perfetto export
# ---------------------------------------------------------------------------

def test_single_request_full_phase_vector(tmp_path):
    c = _ansatz()
    telemetry.reset()
    with Engine(c, ENV1, max_batch=2, max_delay_ms=0.0) as eng:
        with telemetry.trace_policy("all"):
            np.asarray(eng.submit(_params(c, 1)).result(60))
    trs = telemetry.traces()
    assert len(trs) == 1
    t = trs[0]
    assert t["labels"]["kind"] == "engine"
    assert t["error"] is None and t["dur_ms"] > 0
    assert sorted(t["phases_ms"]) == sorted(PHASES)
    # the tiling contract (PR 26): consecutive phase windows share their
    # stamps and the root closes on the last one's, so the vector sums to
    # the request's latency by construction -- on a loaded host too
    frac = sum(t["phases_ms"].values()) / t["dur_ms"]
    assert abs(frac - 1.0) <= 0.01, (frac, t["phases_ms"], t["dur_ms"])
    # every span closed (QT702-clean), root present, one trace_id
    assert all(sp["dur_ms"] is not None for sp in t["spans"])
    assert A.check_traces(trs) == []
    assert A.check_live_traces() == []
    # Perfetto round-trip: complete events per span, phase rows kept
    out = tmp_path / "chrome.json"
    assert telemetry.export_chrome_trace(str(out)) == 1
    doc = json.loads(out.read_text())
    evs = doc["traceEvents"]
    assert any(e.get("ph") == "X" for e in evs)
    # phase rows render for every ATTRIBUTED phase (a warm request may
    # legitimately have a zero compile phase and no row for it)
    rows = {e["name"] for e in evs if e.get("cat") == "phase"}
    assert rows <= set(PHASES)
    assert {"queue_wait", "device", "resolve"} <= rows
    # ...and the raw export round-trips through the file checker clean
    raw = tmp_path / "traces.json"
    assert telemetry.export_traces(str(raw)) == 1
    assert A.check_trace_file(str(raw)) == []


def test_batch_requests_each_get_own_trace():
    c = _ansatz()
    telemetry.reset()
    with Engine(c, ENV1, max_batch=4, max_delay_ms=5.0) as eng:
        with telemetry.trace_policy("all"):
            for f in eng.submit_many([_params(c, s) for s in range(4)]):
                f.result(60)
    trs = telemetry.traces()
    assert len(trs) == 4
    assert len({t["trace_id"] for t in trs}) == 4
    for t in trs:
        frac = sum(t["phases_ms"].values()) / t["dur_ms"]
        assert abs(frac - 1.0) <= 0.01, (frac, t["phases_ms"])
    assert A.check_live_traces() == []


# ---------------------------------------------------------------------------
# causal links across the fleet: hedge + failover
# ---------------------------------------------------------------------------

def test_hedge_duplicate_links_and_loser_cancelled():
    c = _ansatz()
    with EnginePool(ENV1, replicas=2, max_batch=2, max_delay_ms=0.0,
                    hedge_ms=40) as pool:
        pool.submit(c, _params(c, 0)).result(60)   # builds the affine engine
        rep = next(r for r in pool._replicas if r.engines)
        eng0 = rep.engines[c.fingerprint()]
        telemetry.reset()
        gate = _block(eng0)                        # primary stalls...
        try:
            with telemetry.trace_policy("all"):
                fut = pool.submit(c, _params(c, 7))
                eng0._note_breach(hang=False)      # ...and is degraded
                fut.result(60)                     # hedge completes it
        finally:
            gate.set()
        # the losing leg's span ends cancelled once the stalled primary
        # drains; poll rather than race its batcher thread
        assert _wait(lambda: any(
            sp["status"] == "cancelled"
            for t in _pool_traces() for sp in t["spans"]))
    trs = _pool_traces()
    assert len(trs) == 1                           # ONE trace for the request
    t = trs[0]
    assert t["error"] is None
    hedges = [lk for lk in t["links"] if lk["kind"] == "hedge"]
    assert len(hedges) == 1
    spans = {sp["id"]: sp for sp in t["spans"]}
    assert spans[hedges[0]["from"]]["name"] == "pool.hedge"
    assert spans[hedges[0]["to"]]["name"] == "pool.attempt"
    assert any(sp["status"] == "cancelled" for sp in t["spans"])
    assert all(sp["dur_ms"] is not None for sp in t["spans"])
    assert not [f for f in A.check_live_traces() if f.code == "QT703"]


def test_failover_keeps_trace_id_and_links():
    c = _ansatz()
    with EnginePool(ENV1, replicas=2, max_batch=2, max_delay_ms=0.0,
                    spawn_replacements=False) as pool:
        pool.submit(c, _params(c, 0)).result(60)
        telemetry.reset()
        with telemetry.trace_policy("all"):
            with faultinject.fault_plan("pool.replica:kill:1"):
                r = pool.submit(c, _params(c, 3)).result(60)
        assert r is not None
    trs = _pool_traces()
    assert len(trs) == 1                           # same trace end to end
    t = trs[0]
    assert t["error"] is None                      # the request SUCCEEDED
    attempts = [sp for sp in t["spans"] if sp["name"] == "pool.attempt"]
    assert len(attempts) >= 2                      # failed + re-dispatched
    assert any(sp["status"] == "error" for sp in attempts)
    fo = [lk for lk in t["links"] if lk["kind"] == "failover"]
    assert len(fo) >= 1
    spans = {sp["id"]: sp for sp in t["spans"]}
    for lk in fo:                                  # retry -> failed attempt
        assert spans[lk["to"]]["status"] in ("error", "cancelled")
    assert all(sp["dur_ms"] is not None for sp in t["spans"])


# ---------------------------------------------------------------------------
# sampling semantics: errors mode, QT701 warn-once
# ---------------------------------------------------------------------------

def test_errors_mode_retains_errored_requests_only():
    telemetry.reset()
    with telemetry.trace_policy("errors"):
        ok = telemetry.start_trace("request", kind="unit")
        assert ok is not None                      # minted, head-unsampled
        telemetry.finish_trace(ok)
        bad = telemetry.start_trace("request", kind="unit")
        telemetry.finish_trace(bad, error="QuESTPoisonError")
    trs = telemetry.traces()
    assert len(trs) == 1
    assert trs[0]["error"] == "QuESTPoisonError"


def test_finish_trace_is_idempotent():
    telemetry.reset()
    with telemetry.trace_policy("all"):
        ctx = telemetry.start_trace("request", kind="unit")
        telemetry.finish_trace(ctx)
        telemetry.finish_trace(ctx, error="late")  # no second record
    trs = telemetry.traces()
    assert len(trs) == 1 and trs[0]["error"] is None
    assert sorted(trs[0]["phases_ms"]) == sorted(PHASES)


def test_qt701_malformed_trace_env_warns_once(monkeypatch):
    monkeypatch.setenv("QUEST_TRACE", "lots")
    monkeypatch.setattr(telemetry, "_TRACE_WARNED", set())
    monkeypatch.setattr(telemetry, "_TRACE_RESOLVED", False)
    telemetry.reset()
    with pytest.warns(RuntimeWarning, match="QT701"):
        assert telemetry.trace_on() is False       # falls back to off
    assert telemetry.trace_mode() == "off"
    assert telemetry.counter_value("analysis_findings_total",
                                   code="QT701", severity="warning") == 1.0
    monkeypatch.setattr(telemetry, "_TRACE_RESOLVED", False)
    with warnings.catch_warnings():                # second resolve: silent
        warnings.simplefilter("error")
        assert telemetry.trace_on() is False


@pytest.mark.parametrize("raw,mode,rate", [
    ("off", "off", 0.0), ("", "off", 0.0), ("errors", "errors", 0.0),
    ("all", "all", 1.0), ("1", "all", 1.0), ("0.25", "rate", 0.25),
])
def test_trace_mode_parse_table(raw, mode, rate):
    m, r, err = telemetry._parse_trace(raw)
    assert (m, r, err) == (mode, rate, None)


@pytest.mark.parametrize("raw", ["lots", "2.5", "-0.1"])
def test_trace_mode_parse_rejects(raw):
    m, _r, err = telemetry._parse_trace(raw)
    assert m == "off" and err is not None


# ---------------------------------------------------------------------------
# QT702 / QT703 integrity findings
# ---------------------------------------------------------------------------

def test_qt702_open_span_in_finished_trace():
    telemetry.reset()
    with telemetry.trace_policy("all"):
        ctx = telemetry.start_trace("request", kind="unit")
        ctx.child("leaky.handle", site="test")     # never end()-ed
        telemetry.finish_trace(ctx)
    findings = A.check_traces(telemetry.traces())
    assert [f.code for f in findings] == ["QT702"]
    assert "leaky.handle" in findings[0].message
    telemetry.reset()


def test_qt703_thread_bound_to_finished_trace():
    telemetry.reset()
    with telemetry.trace_policy("all"):
        ctx = telemetry.start_trace("request", kind="unit")
        telemetry.set_current_trace(ctx)           # batcher-style bind...
        telemetry.finish_trace(ctx)                # ...never cleared
        try:
            leaks = telemetry.trace_thread_leaks()
            assert len(leaks) == 1
            assert leaks[0][1] == ctx.trace_id
            findings = A.check_live_traces()
            assert any(f.code == "QT703" for f in findings)
        finally:
            telemetry.clear_current_trace()
    assert telemetry.trace_thread_leaks() == []
    telemetry.reset()


# ---------------------------------------------------------------------------
# satellite: bounded flight-recorder event ring
# ---------------------------------------------------------------------------

def test_event_ring_caps_and_reports_drops(tmp_path, monkeypatch):
    monkeypatch.setenv("QUEST_TELEMETRY_EVENTS_MAX", "8")
    monkeypatch.setattr(telemetry.REGISTRY, "_events_max", None)
    telemetry.reset()
    for i in range(20):
        telemetry.event("ring.probe", i=i)
    evs = telemetry.REGISTRY.events()
    assert len(evs) == 8                           # ring capped
    assert evs[-1]["i"] == 19                      # newest retained
    assert telemetry.counter_value(
        "telemetry_events_dropped_total") == 12.0
    out = tmp_path / "events.jsonl"
    assert telemetry.export_jsonl(str(out)) == 9   # 8 events + meta line
    lines = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert lines[0] == {"kind": "meta", "events_dropped": 12,
                        "events_max": 8}
    telemetry.reset()


def test_event_ring_default_has_no_meta_line(tmp_path):
    telemetry.reset()
    telemetry.event("one.event")
    out = tmp_path / "events.jsonl"
    assert telemetry.export_jsonl(str(out)) == 1   # nothing dropped
    [line] = [json.loads(ln) for ln in out.read_text().splitlines()]
    assert line["kind"] == "event" and line["name"] == "one.event"
    telemetry.reset()


# ---------------------------------------------------------------------------
# concurrency: the serving races stay schedule-complete with tracing armed
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(C.SCENARIOS))
def test_explorer_scenarios_clean_under_tracing(name):
    sc = C.SCENARIOS[name]()
    sc.warm()
    sc.warm = lambda: None
    telemetry.reset()
    with telemetry.trace_policy("all"):
        r = C.InterleavingExplorer(max_schedules=8).explore(sc)
    assert r.breaches == []
    assert r.qt602 == []
    assert r.schedules > 1
    # the explored fleet left no thread bound to a dead trace
    assert not [f for f in A.check_live_traces() if f.code == "QT703"]
    telemetry.reset()
