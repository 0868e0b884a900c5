"""API / tape layer: programs the host dispatched per application
(``device_dispatch_total``, every route), counted over the whole window."""

from metric_util import dispatches_per_request as read  # noqa: F401
