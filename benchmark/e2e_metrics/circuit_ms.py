"""Wall time of one application of the configuration's circuit, API call to
synced state, on a register that streams from HBM: all the time of the window
over all its applications (a single application is too short for the host's
clock)."""

from metric_util import window_ms_per_request as read  # noqa: F401
