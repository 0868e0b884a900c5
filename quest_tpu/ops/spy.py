"""Spy operands: how the planner observes what a tape entry would do.

``capture.capture`` replays a tape entry against a stand-in register instead
of a state. Every primitive the planner understands is declared with
:func:`records`; handed a :class:`Spy` as its first argument it passes the
call to the spy's recorder of its own name and does no work. The spy
carries its recorders: nothing process-wide is swapped, so any number of
threads capture, plan and trace side by side (a serving Engine's batcher
thread captures inside its jit trace while another Engine is planning).
"""

from __future__ import annotations

import functools


class Spy:
    """Base of the stand-ins (capture._SpyQureg, capture._SpyAmps).
    ``recorders`` maps a primitive's ``__name__`` to the callable that
    takes its arguments, the spy first."""

    recorders: dict = {}


def records(fn):
    """Declare ``fn`` (a gate primitive or a kernel applier, register or
    amplitudes first) capturable. A spy with no recorder under the name
    makes the call a TypeError, as handing the stand-in to the real
    primitive would: that capture then yields a barrier."""
    name = fn.__name__

    @functools.wraps(fn)
    def call(first, *args, **kwargs):
        if isinstance(first, Spy):
            rec = first.recorders.get(name)
            if rec is None:
                raise TypeError(f"'{name}' is not captured here")
            return rec(first, *args, **kwargs)
        return fn(first, *args, **kwargs)

    return call
