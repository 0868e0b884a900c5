"""Median client-side latency, ``submit`` to a synced state, over every
request of the window."""

from metric_util import latencies_ms, percentile


def read(m):
    lat = latencies_ms(m)
    return percentile(lat, 0.50) if lat else None
