"""The readers of what the program records about itself (PR 26): each is a
pure ``read(m)`` over the two registry snapshots and the engine's traces, on a
hand-built ``m``, and returns None where the series is absent -- as on a
commit whose program has no such span or listener."""

import pytest

import run as harness
from window import Window


def reader(name):
    return harness.load_module("layer_metrics", name).read


def snap(spans=None, histograms=None, counters=None):
    return {"counters": counters or {}, "gauges": {},
            "histograms": histograms or {}, "spans": spans or {}}


def trace(t0, error=None, **phases):
    return {"t0": t0, "error": error, "dur_ms": sum(phases.values()),
            "phases_ms": phases}


def measured(**over):
    m = {"window": Window(t0=0.0, wall0=100.0), "before": snap(),
         "after": snap(), "engine_traces": []}
    m.update(over)
    return m


@pytest.mark.parametrize("name", ["host_launch_ms.lib",
                                  "host_launch_ms.small"])
def test_host_launch_is_span_growth_over_count_growth(name):
    read = reader(name)
    m = measured(
        before=snap({"circuit.run": {"count": 2, "total_s": 0.5,
                                     "max_s": 0.4}}),
        after=snap({"circuit.run": {"count": 1002, "total_s": 0.75,
                                    "max_s": 0.4}}))
    assert read(m) == pytest.approx(0.25)          # 0.25 s over 1000, in ms
    # a span that first appears inside the window counts from zero
    m["before"] = snap()
    assert read(m) == pytest.approx(0.75e3 / 1002)
    # absent (the parent commit), or no application in the window: nothing
    assert read(measured()) is None
    same = snap({"circuit.run": {"count": 2, "total_s": 0.5, "max_s": 0.4}})
    assert read(measured(before=same, after=same)) is None


ENGINE = {
    "engine_queue_ms.serve": 30.0 + 2.0,           # queue_wait + coalesce
    "engine_issue_ms.serve": 1.0 + 4.0 + 0.0,      # lookup, dispatch, compile
    "engine_device_ms.serve": 500.0,
    "engine_resolve_ms.serve": 3.0,
}


@pytest.mark.parametrize("name", sorted(ENGINE))
def test_engine_phase_reader_is_the_window_s_median(name):
    read = reader(name)
    mid = dict(queue_wait=30.0, coalesce=2.0, cache_lookup=1.0, dispatch=4.0,
               compile=0.0, device=500.0, resolve=3.0)
    low = {k: v / 2 for k, v in mid.items()}
    high = {k: v * 2 for k, v in mid.items()}
    m = measured(engine_traces=[
        trace(101.0, **low), trace(102.0, **mid), trace(103.0, **high),
        # left out: a warm-up request from before the window, a failed one
        trace(99.0, **{k: 1e6 for k in mid}),
        trace(104.0, error="QuESTTimeoutError", **{k: 1e6 for k in mid})])
    assert read(m) == pytest.approx(ENGINE[name])
    assert read(measured()) is None


def test_the_four_engine_readers_cover_the_seven_phases_once():
    import quest_tpu.telemetry as telemetry

    covered = [p for name in ENGINE
               for p in harness.load_module("layer_metrics", name).PHASES]
    assert sorted(covered) == sorted(telemetry.PHASES)


def test_compile_readers_read_the_set_up_snapshot():
    hists = {
        "jax_trace_seconds": {"count": 40, "sum": 2.5, "min": 0, "max": 1},
        "jax_lower_seconds": {"count": 4, "sum": 1.25, "min": 0, "max": 1},
        "jax_backend_compile_seconds": {"count": 4, "sum": 0.5, "min": 0,
                                        "max": 1},
        "jax_cache_retrieval_seconds": {"count": 3, "sum": 0.125, "min": 0,
                                        "max": 1},
    }
    m = measured(before=snap(histograms=hists),
                 after=snap(histograms={k: dict(v, sum=99.0)
                                        for k, v in hists.items()}))
    assert reader("trace_lower_s")(m) == pytest.approx(3.75)
    assert reader("backend_compile_s")(m) == pytest.approx(0.625)
    # a cold run retrieves nothing: the series is simply not there
    del hists["jax_cache_retrieval_seconds"]
    assert reader("backend_compile_s")(m) == pytest.approx(0.5)
    assert reader("trace_lower_s")(measured()) is None
    assert reader("backend_compile_s")(measured()) is None


@pytest.mark.parametrize("counters, fused, barriers", [
    # the plan of today: every Param in a block, no barrier series at all
    ({"fusion_param_fused_total{mode=dense}": 160}, 160, 0),
    # a plan that fell back: the barriers have a name, and fused reads 0
    ({"fusion_param_barriers_total{mode=dense}": 160}, 0, 160),
    ({"fusion_param_fused_total{mode=dense}": 120,
      "fusion_param_barriers_total{mode=dense}": 36,
      "fusion_param_barriers_total{mode=pallas}": 4}, 120, 40),
    # a program whose planner counts neither: nothing to read
    ({"device_dispatch_total{route=engine_vmap}": 9}, None, None),
])
def test_planner_counters_are_the_totals_of_the_after_snapshot(
        counters, fused, barriers):
    m = measured(after=snap(counters=counters))
    assert reader("param_fused.serve")(m) == fused
    assert reader("param_barriers.serve")(m) == barriers


def test_planner_counters_have_their_entries(bench):
    entries = {e["name"]: e for e in bench["per_layer"]}
    for name, better in (("param_fused.serve", "higher"),
                         ("param_barriers.serve", "lower")):
        assert entries[name] == {
            "name": name, "unit": "count", "better": better,
            "source": "program_counter", "layer": "planner",
            "moves": "request_p50_ms",
            "workloads": ["ansatz20.serve-closed16"]}


def test_every_new_metric_has_its_file_and_its_entry(bench):
    new = ["host_launch_ms.lib", "host_launch_ms.small", *sorted(ENGINE),
           "trace_lower_s", "backend_compile_s"]
    entries = {e["name"]: e for e in bench["per_layer"]}
    for name in new:
        assert entries[name]["source"] == "program_span"
        assert callable(reader(name))
    # the compile pair is every cell's; the rest list their cells
    assert "workloads" not in entries["trace_lower_s"]
    assert "workloads" not in entries["backend_compile_s"]
    names = [e["name"] for e in bench["per_layer"]]
    at = names.index("host_launch_ms.lib")
    assert names[at:at + 8] == [
        "host_launch_ms.lib", "host_launch_ms.small",
        "engine_queue_ms.serve", "engine_issue_ms.serve",
        "engine_device_ms.serve", "engine_resolve_ms.serve",
        "trace_lower_s", "backend_compile_s"]
