"""The benchmark's command: one cell, one seed, one measured window.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that owns the chip. It resolves the cell by name from
``BENCHMARK.json`` (its configuration in ``configs/``, its traffic mix in
``traffic/`` and that mix's generator in ``loops/``, its circuit builder in
``circuits/``, its driver in ``drivers/``, every metric in ``e2e_metrics/`` or
``layer_metrics/``) and knows no cell itself. It refuses any platform but
``tpu``, keeps JAX's compile cache at one fixed path inside the checkout,
builds the cell, warms the cell's own shapes (all of that is ``setup_s``),
measures for ``--seconds``, checks what the timed path produced against the
plain reference once the window has closed, and prints every number compared
beside its limit as the last lines of standard error and the contract's
result, with those numbers under ``checks``, as the last line of standard
output.

``--trace 0`` reports the cell's end-to-end metrics. ``--trace 1`` profiles a
short slice of the window, reduces it with ``trace_reduce.py`` and reports
the per-layer metrics, the device's busy time and the breakdown.

``--rehearse`` is the sandbox rehearsal: the configuration's ``rehearse``
sizes, CPU allowed, interpreted kernels allowed. It drives the same control
flow to the last line, marks the line ``"rehearsed": true`` and writes no
time at all: only counts appear under ``metrics``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import contextlib
import glob
import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

EXIT_NO_CHIP = 3
EXIT_NO_PROGRAM = 4


def log(msg: str) -> None:
    print(f"# [{time.perf_counter() - T_START:7.2f}s] {msg}", file=sys.stderr,
          flush=True)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(f"{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Run:
    """One cell resolved, and what a run of it gathers."""

    def __init__(self, workload: str, seed: int, rehearse: bool = False):
        self.bench = load_json(ROOT, "BENCHMARK.json")
        cells = {c["name"]: c for c in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"benchmark: no workload {workload!r} in "
                             f"BENCHMARK.json (has {sorted(cells)})")
        self.cell = cells[workload]
        files = {c["name"]: c["file"] for c in self.bench["configs"]}
        self.config = load_json(ROOT, files[self.cell["config"]])
        self.traffic = load_json(HERE, "traffic",
                                 self.cell["traffic"] + ".json")
        self.builder = load_module("circuits",
                                   self.config["circuit"]["builder"])
        self.loop = load_module("loops", self.traffic["loop"])
        self.seed = seed
        self.rehearse = rehearse
        self.circuit_args = (self.config["rehearse"]["circuit_args"]
                             if rehearse else self.config["circuit"]["args"])
        self.spans = {}
        #: None in every benchmark run; the control (``control.py``) puts a
        #: rounding function here and the checks then compare the reference
        #: computed in that lower precision IN THE PROGRAM'S PLACE
        self.control = None

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans[name] = time.perf_counter() - t0

    def metrics_of(self, group: str) -> list:
        """The cell's metrics of ``end_to_end`` or ``per_layer``."""
        return [m for m in self.bench[group]
                if self.cell["name"] in m.get("workloads",
                                              [self.cell["name"]])]

    # -- what the checks compare: the program's output, or the control's ----

    def output_planes(self, program, psi0, ops):
        if self.control is None:
            return program()
        import numpy as np
        import reference

        low = reference.run_statevector(psi0, ops, lower=self.control)
        return np.stack([low.real, low.imag])

    def output_blocks(self, program, psi0, n, ops, rows, cols):
        if self.control is None:
            return program()
        import numpy as np
        import reference

        low = reference.run_density_blocks(psi0, n, ops, rows, cols,
                                           lower=self.control).reshape(-1)
        return np.stack([low.real, low.imag])


def cache_dir() -> str:
    """Where JAX's compile cache is kept: where the environment says, or at
    one fixed path inside the checkout (the path is part of the key)."""
    return os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 os.path.join(ROOT, ".jax_cache"))


def set_up_apart(run: Run, trace: int) -> int:
    """A cell's first run on a cache sets the cell up twice: once in a child
    that compiles, fills the cache, leaves a marker there and exits, then in
    this process, which so loads its programs from the cache like every
    later run. A process that has compiled its own program serves 4-10%
    slower through its whole window (``PERF.md`` section 6, PR 29), and a
    check's every set holds one such run. Both set-ups are in ``setup_s``.
    This process has not touched JAX yet: the child owns the chip alone.
    Returns the child's exit code (0 where there was nothing to do)."""
    marker = os.path.join(cache_dir(), run.cell["name"] + ".set-up")
    if run.rehearse or os.path.exists(marker):
        return 0
    log("no set-up of this cell in the cache yet: one in a process of its own")
    with run.span("apart_s"):
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--workload",
             run.cell["name"], "--seed", str(run.seed), "--trace", str(trace),
             "--set-up-only", marker], stdout=subprocess.DEVNULL)
    return child.returncode


def start_jax(run: Run):
    """Import JAX with the cache placed; the device row, or exit."""
    cache_dir()     # in the environment before JAX reads it
    if run.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        # nothing to save at rehearsal sizes, and XLA:CPU logs every load
        os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    # cache every program, the small ones of set-up and check too, so that
    # only a checkout's first run of a cell compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # a Mosaic kernel's payload carries its debug locations into the cache
    # key: keep only the frame the op was written in, and that one relative
    # to the checkout, so that neither a line moved in a caller nor the
    # checkout's own path makes every kernel program compile anew
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(ROOT + os.sep))
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        log(f"no device: {exc}")
        raise SystemExit(EXIT_NO_CHIP)
    d = devices[0]
    row = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devices)}
    if not run.rehearse:
        if d.platform != "tpu" or jax.default_backend() != "tpu":
            log(f"refused: platform {d.platform!r}, not 'tpu'")
            raise SystemExit(EXIT_NO_CHIP)
        if len(devices) < run.cell["chips"]:
            log(f"refused: {len(devices)} chip(s), the cell asks for "
                f"{run.cell['chips']}")
            raise SystemExit(EXIT_NO_CHIP)
    return row


def peaks_for(kind: str, rehearse: bool):
    table = load_json(HERE, "peaks.json")
    if kind in table:
        return table[kind]
    if rehearse:
        return None
    raise SystemExit(f"benchmark: device kind {kind!r} is not in peaks.json; "
                     "add it with its source, do not default it")


def refuse_over_roofline(name: str, value: float) -> None:
    """A share of a roofline over 100% is a fault of the yardstick."""
    if "roofline" in name and value > 100.0:
        raise SystemExit(f"benchmark: {name} reads {value}% of its roofline: "
                         "bytes counted too high or time left out")


def memory_peak() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def traced_slice(trace_dir: str, length: float, offset: float):
    """``during`` of the loop: profile ``length`` seconds of the window,
    starting ``offset`` seconds into it."""
    import jax

    def during(win):
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        time.sleep(max(0.0, win.t0 + offset - time.perf_counter()))
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench.slice"):
            time.sleep(length)
        jax.profiler.stop_trace()

    return during


def reduce_slice(trace_dir: str) -> dict | None:
    import trace_reduce

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        return None
    return trace_reduce.reduce(trace_reduce.load(paths[-1]))


def program_checks(run: Run, driver, telemetry, after: dict) -> list:
    """Counts that fail a run: a kernel left for the engine, an interpreted
    kernel, a timeout, a poisoned request."""
    from metric_util import counter_total

    out = []
    for name in ("engine_fallback_total", "engine_request_timeouts_total",
                 "engine_poisoned_requests_total"):
        out.append((name, float(counter_total(after, name)), 0.0))
    compiles = [e for e in telemetry.events()
                if e.get("name") == "pallas.compile"]
    interpreted = sum(1 for e in compiles if e.get("interpret"))
    if not run.rehearse:
        out.append(("interpreted_kernels", float(interpreted), 0.0))
        if driver.expects_kernels:
            out.append(("kernels_missing", float(not compiles), 0.0))
    return out


def dump_window(path: str, win, m: dict) -> None:
    """``--dump``: every request of the window on the window's clock, what
    the program counted over it, and the engine's phase vectors of a traced
    run, for ``spread.py`` to read."""
    def grown(kind, fields):
        return {k: {f: v[f] - m["before"][kind].get(k, {}).get(f, 0)
                    for f in fields}
                for k, v in m["after"][kind].items()}

    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump({
            "seconds": win.deadline - win.t0, "end": win.end - win.t0,
            "requests": [[c, k, a - win.t0, b - win.t0, bool(ok)]
                         for c, k, a, b, ok in win.requests],
            "spans": m["spans"],
            "histograms": grown("histograms", ("count", "sum")),
            "program_spans": grown("spans", ("count", "total_s")),
            "engine_traces": [
                {"t0": t["t0"] - win.wall0, "dur_ms": t["dur_ms"],
                 "error": t["error"], "phases_ms": t["phases_ms"]}
                for t in m["engine_traces"] if t["t0"] >= win.wall0]}, f)


def measure(run: Run, driver, seconds: float, trace: bool, device: dict,
            peaks, setup_s: float, dump: str | None = None) -> dict:
    """The window, the check and the result line of one seed."""
    from quest_tpu import telemetry

    during = None
    if trace:
        trace_dir = os.path.join(ROOT, ".bench_trace", run.cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        during = traced_slice(trace_dir,
                              min(run.traffic["trace_slice_s"], 0.4 * seconds),
                              0.3 * seconds)
    before = telemetry.snapshot()
    win = run.loop.run(driver.request, run.traffic, seconds, during)
    after = telemetry.snapshot()
    device = dict(device, memory_peak_bytes=memory_peak())
    took = sorted((r[3] - r[2]) * 1e3 for r in win.requests)
    log(f"window: {len(took)} requests in {win.end - win.t0:.3f}s; each, ms: "
        + " ".join(f"p{q}={took[len(took) * q // 100]:.3f}"
                   for q in (50, 90, 99) if took)
        + " longest " + " ".join(f"{t:.1f}" for t in took[:-4:-1]))

    reduced = reduce_slice(trace_dir) if trace else None
    checks = driver.check(win) + program_checks(run, driver, telemetry, after)
    correct = not win.errors and all(value <= limit
                                     for _, value, limit in checks)
    for err in win.errors[:5]:
        log(f"request failed: {err}")
    log(f"check: reference {run.spans.get('reference_s', 0.0):.2f}s")

    measured = {
        "window": win, "spans": run.spans,
        "setup_s": setup_s, "before": before, "after": after,
        "trace": reduced, "shapes": driver.shapes(), "peaks": peaks,
        "engine_traces": telemetry.traces() if trace else [],
    }
    if dump:
        dump_window(dump, win, measured)
    metrics = {}
    for spec in run.metrics_of("per_layer" if trace else "end_to_end"):
        if run.rehearse and spec["source"] != "program_counter":
            continue       # a CPU time is never written under a device name
        kind = "layer_metrics" if trace else "e2e_metrics"
        value = load_module(kind, spec["name"]).read(measured)
        if value is None:
            continue
        refuse_over_roofline(spec["name"], value)
        metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    result = {"correct": bool(correct), "attempted": len(win.requests),
              "failed": sum(1 for r in win.requests if not r[4]),
              "metrics": metrics, "device": device}
    if trace and reduced is not None:
        if not run.rehearse:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if run.rehearse:
        result["rehearsed"] = True
    # every number compared, beside its limit: the result's last key
    result["checks"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in checks}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--dump", metavar="FILE",
                    help="also write the window's requests there (spread.py)")
    ap.add_argument("--set-up-only", metavar="MARKER",
                    help="set the cell up, write MARKER, print no result "
                         "(what a first run on a cold cache starts)")
    args = ap.parse_args(argv)

    run = Run(args.workload, args.seed, args.rehearse)
    seconds = (args.seconds if args.seconds is not None
               else float(run.bench["run_seconds"]))
    if args.trace:
        os.environ.setdefault("QUEST_TRACE", "all")   # the engine's phases
    if not args.set_up_only:
        rc = set_up_apart(run, args.trace)
        if rc:
            return rc
    device = start_jax(run)
    peaks = peaks_for(device["kind"], run.rehearse)
    try:
        import quest_tpu  # noqa: F401
    except ImportError as exc:
        log(f"the program is not in this checkout: {exc}")
        return EXIT_NO_PROGRAM
    run.spans["import_s"] = (time.perf_counter() - T_START
                             - run.spans.get("apart_s", 0.0))
    log(f"{run.cell['name']} seed {run.seed} on {device}")

    driver = load_module("drivers", run.config["driver"]).Driver(run)
    try:
        driver.setup()
        setup_s = time.perf_counter() - T_START
        log("set-up " + " ".join(f"{k}={v:.2f}" for k, v in run.spans.items()))
        if args.set_up_only:
            os.makedirs(os.path.dirname(args.set_up_only), exist_ok=True)
            with open(args.set_up_only, "w") as f:
                f.write(f"{run.cell['name']} set up in {setup_s:.1f}s\n")
            return 0
        result = measure(run, driver, seconds, bool(args.trace), device, peaks,
                         setup_s, args.dump)
    finally:
        driver.close()
    # the last lines of standard error, and the last line of standard output
    for name, c in result["checks"].items():
        print(f"check {name}: value {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
