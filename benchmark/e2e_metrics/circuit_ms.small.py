"""``circuit_ms`` of a cell whose register is smaller than four times the
chip's VMEM: the host's dispatch and the launches set its time, not the HBM
stream, and it spreads from run to run as host times do. Its own metric so
that its bound does not loosen the streaming cells'."""

from metric_util import window_ms_per_request as read  # noqa: F401
