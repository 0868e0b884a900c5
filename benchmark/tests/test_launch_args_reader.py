"""``launch_args.serve`` (PR 31): arrays handed to the engine's batch program
a launch, from two registry snapshots; None where the program does not count
them or launched no batch in the window."""

import pytest

import run as harness

VMAP = "device_dispatch_total{route=engine_vmap}"
ARGS = "engine_launch_args_total"


def snap(**counters):
    return {"counters": counters, "gauges": {}, "histograms": {}, "spans": {}}


def measured(before, after):
    return {"before": before, "after": after}


@pytest.fixture(scope="module")
def read():
    return harness.load_module("layer_metrics", "launch_args.serve").read


@pytest.mark.parametrize("args_a_launch", [161, 2, 4])
def test_growth_of_the_arguments_over_growth_of_the_launches(read,
                                                             args_a_launch):
    # set-up launched 5 batches before the window, the window 110 more
    before = snap(**{VMAP: 5, ARGS: 5 * args_a_launch,
                     "device_dispatch_total{route=engine_param}": 1})
    after = snap(**{VMAP: 115, ARGS: 115 * args_a_launch,
                    "device_dispatch_total{route=engine_param}": 1})
    assert read(measured(before, after)) == args_a_launch
    # one launch in the window
    after = snap(**{VMAP: 6, ARGS: 6 * args_a_launch})
    assert read(measured(before, after)) == args_a_launch


def test_a_program_that_does_not_count_its_arguments_reads_nothing(read):
    before, after = snap(**{VMAP: 5}), snap(**{VMAP: 115})
    assert read(measured(before, after)) is None
    assert read(measured(snap(), snap())) is None


def test_no_batch_in_the_window_reads_nothing(read):
    same = snap(**{VMAP: 5, ARGS: 10})
    assert read(measured(same, same)) is None
    # sequential dispatches only: the batch program never ran
    after = snap(**{VMAP: 5, ARGS: 10,
                    "device_dispatch_total{route=engine_param}": 40})
    assert read(measured(same, after)) is None
