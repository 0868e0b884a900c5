"""Gradients: the least time the chip could take for the gradients of one
batch over the time its program took. The bound is HBM: one read and one
write of a lane's register for every application the adjoint method makes
(``bytes_model_grad``: forward, Hamiltonian, two backward sweeps, a bracket a
parameter, reckoned from the tape and the Hamiltonian alone), times the lanes
the batch carried (the mean width of the window's batches,
``engine_batch_size``: the padded program computes ``max_batch`` lanes
whatever the width, so a half-empty batch halves the share), over the chip's
bandwidth (``peaks.json``); the time is the median ``device`` phase of the
window's gradient requests (the engine's stream-ordered estimate of their
batch's execution: the host's stamps, which the xplane's busy time of the one
whole run in a traced slice bears out to 0.1%; nothing on a tree that does
not label a gradient request's trace). No kernel comes with this path (XLA
ops only): this share of the sweep's one-read-one-write floor is the roofline
it has. A few per cent today; over 100% the count is wrong."""

from metric_util import histogram_delta, percentile


def gradient_traces(m) -> list:
    """The window's finished traces of gradient requests: those the program
    labelled ``route=grad_request`` (none on a tree from before the label)."""
    wall0 = m["window"].wall0
    return [t for t in m["engine_traces"]
            if t["t0"] >= wall0 and not t["error"]
            and (t.get("labels") or {}).get("route") == "grad_request"]


def read(m):
    peaks, shapes = m["peaks"], m["shapes"]
    device_ms = [t["phases_ms"].get("device", 0.0)
                 for t in gradient_traces(m)]
    count, total = histogram_delta(m, "engine_batch_size")
    if not peaks or not device_ms or not count or "grad_bytes" not in shapes:
        return None
    device_s = percentile(device_ms, 0.50) / 1e3
    if device_s <= 0.0:
        return None
    floor_s = shapes["grad_bytes"] * (total / count) / peaks["hbm_bytes_per_s"]
    return 100.0 * floor_s / device_s
