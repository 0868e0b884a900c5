"""The four readers of set-up from inside the program (PR 39): each a pure
``read(m)`` over the snapshot taken where set-up ends, on a hand-built ``m``,
and None where its series is absent -- as on the parent commit, whose program
has no such span, gauge or field."""

import json
import os

import pytest

import run as harness
from test_program_readers import measured, snap

NEW = ("kernel_trace_s", "planner_s", "cache_load_s", "program_import_s")
LIBRARY = ["density14.block", "sv20.block", "sv26.block", "sv31x4.block",
           "sv30.block", "df26.block"]


def reader(name):
    return harness.load_module("layer_metrics", name).read


def hist(total, count=1):
    return {"count": count, "sum": total, "min": 0.0, "max": total}


def gauges(**g):
    s = snap()
    s["gauges"] = g
    return s


@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_on_the_parent_is_none_and_does_not_raise(name):
    assert reader(name)(measured()) is None
    # what the parent does record is not mistaken for it
    parent = snap(spans={"circuit.run": {"count": 2, "total_s": 1.0,
                                         "max_s": 0.9}},
                  histograms={"fusion.plan_seconds{mode=dense}": hist(0.5),
                              "engine_batch_size": hist(8.0)},
                  counters={"pallas_pass_total{kind=fused_run}": 3})
    assert reader(name)(measured(before=parent)) is None


def test_kernel_trace_s_sums_every_kind_of_the_set_up_snapshot():
    before = snap(histograms={
        "mosaic_compile_seconds{kind=df}": hist(45.5, 12),
        "mosaic_compile_seconds{kind=float32}": hist(2.25, 3),
        "mosaic_compile_seconds{kind=window_dot}": hist(0.25),
        "jax_trace_seconds": hist(50.0, 13000)})
    after = snap(histograms={
        "mosaic_compile_seconds{kind=df}": hist(99.0, 13)})
    assert reader("kernel_trace_s")(measured(before=before, after=after)) \
        == pytest.approx(48.0)
    unlabeled = snap(histograms={"mosaic_compile_seconds": hist(1.5)})
    assert reader("kernel_trace_s")(measured(before=unlabeled)) == 1.5


def test_planner_s_is_every_arm_s_span():
    before = snap(spans={
        "fusion.plan{mode=pallas}": {"count": 1, "total_s": 0.75,
                                     "max_s": 0.75},
        "fusion.plan{mode=dense}": {"count": 2, "total_s": 0.5,
                                    "max_s": 0.3},
        "fusion.plan{mode=pallas_sharded}": {"count": 1, "total_s": 0.125,
                                             "max_s": 0.125},
        "fusion.planner": {"count": 1, "total_s": 64.0, "max_s": 64.0},
        "circuit.run": {"count": 2, "total_s": 9.0, "max_s": 8.0}})
    assert reader("planner_s")(measured(before=before)) \
        == pytest.approx(1.375)
    # the parent's served cell: a dense plan under no span
    assert reader("planner_s")(measured(before=snap(
        spans={"engine.launch": {"count": 9, "total_s": 1.0,
                                 "max_s": 0.9}}))) is None


def test_cache_load_s_is_the_retrievals_alone_and_0_where_none_was_made():
    hists = {"jax_trace_seconds": hist(2.5, 40),
             "jax_backend_compile_seconds": hist(0.5, 4),
             "jax_cache_retrieval_seconds": hist(0.125, 3)}
    m = measured(before=snap(histograms=hists),
                 after=snap(histograms={k: hist(99.0) for k in hists}))
    assert reader("cache_load_s")(m) == pytest.approx(0.125)
    # a program that listened and compiled everything itself: 0, not None
    del hists["jax_cache_retrieval_seconds"]
    assert reader("cache_load_s")(m) == 0.0


def test_program_import_s_is_the_package_s_own_gauge():
    m = measured(before=gauges(**{
        "quest_tpu_import_seconds{jax_included=0}": 0.1875,
        "pallas_ring_depth": 3.0}))
    assert reader("program_import_s")(m) == 0.1875
    assert reader("program_import_s")(measured(
        before=gauges(pallas_ring_depth=3.0))) is None


def test_the_four_are_declared_as_the_issue_says(bench):
    specs = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NEW)
    for name in NEW:
        spec = dict(specs[name])
        workloads = spec.pop("workloads", None)
        assert spec == {"name": name, "unit": "s", "better": "lower",
                        "source": "program_span", "moves": "setup_s",
                        "layer": "planner" if name == "planner_s"
                        else "compile"}
        assert workloads == (LIBRARY if name == "kernel_trace_s" else None)
        assert os.path.isfile(os.path.join(harness.HERE, "layer_metrics",
                                           name + ".py"))
    assert len(json.dumps(bench)) < 64 * 1024
