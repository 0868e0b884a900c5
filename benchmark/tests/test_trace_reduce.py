"""``trace_reduce.py`` on a synthetic trace whose numbers are known exactly,
and on a small trace recorded on a v5e chip (``data/density14_v5e.xplane.pb``:
0.8 s of ``density14.block``, PR 25)."""

import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _ev(mid, start_ns, dur_ns):
    return (f"events {{ metadata_id: {mid} offset_ps: {start_ns * 1000} "
            f"duration_ps: {dur_ns * 1000} }}")


def _synthetic():
    """Six runs of one program, 100 ns apart: an 80 ns run is a 50 ns kernel
    and a 30 ns copy, then 20 ns of idle while the host syncs."""
    from jax.profiler import ProfileData

    runs = " ".join(_ev(1, 100 * i, 80) for i in range(6))
    ops = " ".join(_ev(2, 100 * i, 50) + " " + _ev(3, 100 * i + 50, 30)
                   for i in range(6))
    host = _ev(3, 50, 520) + " " + " ".join(
        _ev(1, 100 * i - 5, 5) + " " + _ev(2, 100 * i, 95) for i in range(1, 6))
    return ProfileData.from_text_proto('''
planes { name: "/device:TPU:0"
  lines { name: "XLA Modules" %s }
  lines { name: "XLA Ops" %s }
  event_metadata { key: 1 value { id: 1 name: "jit_fn(1)" } }
  event_metadata { key: 2 value { id: 2 name: "%%_k.1 = f32[8]{0} custom-call(f32[8]{0} %%p), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 3 value { id: 3 name: "%%copy.2 = f32[8]{0} copy(f32[8]{0} %%p)" } }
}
planes { name: "/host:CPU"
  lines { name: "python3" %s }
  event_metadata { key: 1 value { id: 1 name: "apply" } }
  event_metadata { key: 2 value { id: 2 name: "sync" } }
  event_metadata { key: 3 value { id: 3 name: "bench.slice" } }
}''' % (runs, ops, host))


def test_synthetic_trace_reduces_to_known_numbers():
    r = tr.reduce(_synthetic())
    # the capture's first and last run are dropped: runs 1..4, 100 -> 480 ns
    assert r["runs"] == 4 and r["module"] == "jit_fn(1)"
    assert r["window_s"] == pytest.approx(380e-9)
    assert r["busy_s"] == pytest.approx(320e-9)          # union, 4 x 80
    assert r["kernel_s"] == pytest.approx(200e-9) and r["kernel_launches"] == 4
    assert r["xla_s"] == pytest.approx(120e-9) and r["xla_ops"] == 4
    assert r["device_ops"][0][0] == "_k.1 (custom-call)"
    # three gaps of 20 ns, each 15 ns under the sync and 5 under the next apply
    assert r["idle_gaps"] == [["sync", pytest.approx(45e-9)],
                              ["apply", pytest.approx(15e-9)]]


SPANS = [("wait", 0, 100), ("wait", 10, 110),            # two client threads
         ("engine.dispatch", 20, 60), ("engine.launch", 30, 50),
         ("submit", 40, 45)]                             # a third client


def test_idle_goes_to_the_innermost_open_span():
    """The program's span beats the benchmark's, also one that opened later
    on another thread; among the program's the innermost; instants under no
    span are ``unannotated``."""
    assert tr.timeline(SPANS) == [
        (0, 20, "wait"), (20, 30, "engine.dispatch"),
        (30, 50, "engine.launch"), (50, 60, "engine.dispatch"),
        (60, 110, "wait")]
    assert tr.label_gaps([(5, 35), (55, 120)], SPANS) == {
        "wait": 15 + 50, "engine.dispatch": 10 + 5, "engine.launch": 5,
        "unannotated": 10}
    assert tr.label_gaps([(5, 35)], []) == {"unannotated": 30}


def test_program_spans_are_read_by_name_without_their_labels():
    from jax.profiler import ProfileData

    profile = ProfileData.from_text_proto('''
planes { name: "/host:CPU"
  lines { name: "python3" %s %s %s }
  event_metadata { key: 1 value { id: 1 name: "engine.dispatch#batch=8,mode=vmap#" } }
  event_metadata { key: 2 value { id: 2 name: "engine.launch" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.plan" } }
}''' % (_ev(1, 20, 40), _ev(2, 30, 20), _ev(3, 0, 5)))
    assert tr.annotations(profile) == [("engine.dispatch", 20, 60),
                                       ("engine.launch", 30, 50)]
    assert set(tr.PROGRAM_SPANS) >= {
        "circuit.run", "engine.admit", "engine.assemble", "engine.lookup",
        "engine.launch", "engine.sync", "engine.resolve", "engine.dispatch",
        "engine.retire"}


def test_union_is_not_a_sum():
    assert tr.union([(0, 10), (5, 12), (20, 30), (30, 31)]) == [(0, 12), (20, 31)]


def test_short_name_and_kernel_kind():
    k = ('%_fused_local_run.2 = f32[2,512]{1,0:T(8,128)} custom-call(f32[2,512]'
         '{1,0} %b), custom_call_target="tpu_custom_call"')
    assert tr.is_kernel(k) and tr.short_name(k) == "_fused_local_run.2 (custom-call)"
    c = ('%copy-start = (bf16[2,3]{1,0:T(8,128)(2,1)S(1)}, bf16[2,3]{1,0}, '
         'u32[]{:S(2)}) copy-start(bf16[2,3]{1,0} %constant.1)')
    assert not tr.is_kernel(c) and tr.short_name(c) == "copy-start (copy-start)"


def test_no_whole_run_reduces_to_nothing():
    from jax.profiler import ProfileData

    empty = ProfileData.from_text_proto('planes { name: "/host:CPU" }')
    assert tr.reduce(empty) is None


def test_recorded_v5e_trace():
    """The recorded density trace: two Mosaic launches an application, the
    relayout copies on the XLA side, a device that is busy all but 2-3%."""
    profile = tr.load(os.path.join(DATA, "density14_v5e.xplane.pb"))
    r = tr.reduce(profile)
    runs = [m for m in tr.module_runs(profile)["/device:TPU:0"]
            if m[0] == r["module"]]
    (_, lo, hi), = tr.annotations(profile, ("bench.slice",))
    assert r["runs"] == sum(1 for _, a, b in runs[1:-1] if a >= lo and b <= hi)
    assert r["runs"] >= 10
    assert r["kernel_launches"] / r["runs"] == 2.0
    assert 27.0 < r["kernel_s"] / r["runs"] * 1e3 < 30.0
    assert 12.0 < r["xla_s"] / r["runs"] * 1e3 < 14.0
    assert r["busy_s"] <= r["window_s"]
    assert 0.01 < 1 - r["busy_s"] / r["window_s"] < 0.04
    assert r["device_ops"][0][0].startswith("_fused_local_run")
    assert {g[0] for g in r["idle_gaps"]} <= {"apply", "sync", "unannotated"}
    # as PR 25's reduction read this file, before idle went span by span
    assert (r["runs"], r["kernel_launches"], r["xla_ops"]) == (17, 34.0, 102.0)
    assert r["window_s"] == pytest.approx(0.720194557, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.706840511, rel=1e-9)
    assert r["kernel_s"] == pytest.approx(0.48512625, rel=1e-9)
    assert r["xla_s"] == pytest.approx(0.221714261, rel=1e-9)
    assert sum(g[1] for g in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
