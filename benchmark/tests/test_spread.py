"""``spread.py``: the figures a bound is set from, on numbers known by hand,
and one set of two rehearsed runs end to end."""

import json
import statistics

import pytest

import spread
from conftest import run_child


def test_the_driver_s_figure_leaves_out_the_farthest_run():
    # PR 28's kind of set: five runs within 0.4 ms and one cold run 3 ms off
    values = [70.1, 70.2, 70.3, 70.4, 70.5, 73.5]
    assert spread.trimmed_range(values) == pytest.approx(0.4)
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert spread.iqr_share(values) == pytest.approx((q3 - q1) / 70.35)
    f = spread.figures(values)
    assert f["median"] == pytest.approx(70.35) and f["n"] == 6
    assert f["trimmed_share"] == pytest.approx(0.4 / 70.35)
    # two far-off runs: one is left out, the other is the range
    assert spread.trimmed_range([70.0, 70.1, 70.2, 70.3, 73.0, 74.0]) \
        == pytest.approx(3.0)
    assert spread.trimmed_range([1.0, 2.0]) is None
    # a count that reads 0 in every run has a range and no share
    zeros = spread.figures([0, 0, 0])
    assert zeros["trimmed_range"] == 0 and zeros["trimmed_share"] is None
    assert zeros["iqr_share"] is None


def test_inside_a_window_seconds_stalls_and_batches():
    # 20 requests a second of 50 ms each for 3 s, one round of 4 stalled
    requests = [[c, k, k * 0.05, k * 0.05 + 0.05, True]
                for k in range(60) for c in range(1)]
    requests += [[9, i, 1.2, 1.2 + 0.2, True] for i in range(4)]
    dump = {"seconds": 3.0, "requests": requests,
            "histograms": {"engine_batch_size": {"count": 8, "sum": 62}},
            "program_spans": {"engine.launch": {"count": 8, "total_s": 0.16},
                              "never": {"count": 0, "total_s": 0.0}}}
    got = spread.inside(dump)
    assert got["rate_by_second"] == [19, 24, 20]     # by completion time
    assert got["p50_by_second"] == [pytest.approx(50.0)] * 3
    assert got["stalled"] == 4 and got["stalled_end_s"] == [1.4]
    assert got["longest_ms"][0] == pytest.approx(200.0)
    assert got["batches"] == 8 and got["batch_width"] == 7.75
    assert got["region_ms"] == {"engine.launch": pytest.approx(20.0)}


def test_populations_split_at_the_two_values_of_queue_wait():
    def tr(wait, dur):
        return {"error": None, "dur_ms": dur,
                "phases_ms": {"queue_wait": wait, "dispatch": 20.0}}

    traces = [tr(1.0, 55.0)] * 10 + [tr(18.0, 70.0)] * 10
    got = spread.populations({"engine_traces": traces})
    assert got["low"]["n"] == 10 and got["high"]["n"] == 10
    assert got["low"]["dur_p50"] == 55.0 and got["high"]["dur_p50"] == 70.0
    assert got["high"]["phases_p50"]["queue_wait"] == 18.0
    assert spread.populations({"engine_traces": traces[:3]}) is None


def test_a_set_of_two_rehearsed_runs(tmp_path):
    rc, _, out = run_child(["benchmark/spread.py", "--workload", "sv20.block",
                            "--seeds", f"3,{2**31 + 9}", "--seconds", "1",
                            "--rehearse", "--label", "two", "--out",
                            str(tmp_path)])
    assert rc == 0, out[-3000:]
    with open(tmp_path / "two.json") as f:
        kept = json.load(f)
    assert [r["seed"] for r in kept["rows"]] == [3, 2**31 + 9]
    assert all(r["result"]["correct"] and r["inside"]["rate_by_second"]
               for r in kept["rows"])
