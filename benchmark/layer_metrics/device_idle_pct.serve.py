"""Device: share of the served cell's traced slice in which no op ran on the
chip."""

from metric_util import idle_pct as read  # noqa: F401
