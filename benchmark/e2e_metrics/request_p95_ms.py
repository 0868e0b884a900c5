"""95th percentile of client-side latency over every request of the window:
the tail a variational client's slowest lane waits for."""

from metric_util import latencies_ms, percentile


def read(m):
    lat = latencies_ms(m)
    return percentile(lat, 0.95) if lat else None
