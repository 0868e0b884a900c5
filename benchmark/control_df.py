"""The control of ``correct`` for a float64 configuration: ``control.py`` with
the next precision below double in its table, float32. The reference replayed
with every gate matrix and every written amplitude rounded to float32 stands
in the program's place -- what a program that ran in float32, or that left
the low planes of the double-float state out, would give -- and has to come
out as NOT correct, by orders of magnitude.

    python3 benchmark/control_df.py --workload df26.block --seeds 1,2,3 [--seconds 2]
    python3 benchmark/control_df.py --workload df26.block --seeds 1,2,3 --host-only

Arguments and output are ``control.py``'s.
"""

from __future__ import annotations

import sys

import numpy as np

import control


def float32(x):
    """Round to float32 and back (real and imaginary part each)."""
    x = np.asarray(x)

    def rnd(a):
        return a.astype(np.float32).astype(np.float64)

    return rnd(x.real) + 1j * rnd(x.imag) if np.iscomplexobj(x) else rnd(x)


control.LOWER["float64"] = float32


if __name__ == "__main__":
    sys.exit(control.main())
