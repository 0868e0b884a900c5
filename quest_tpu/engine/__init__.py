"""Serving engine: parameterized replay, plan/executable cache, and
micro-batched ensemble execution.

The reference simulator compiles nothing and serves one caller; this
package is the serving layer the compiled-``Circuit`` execution model
needs to handle sweep/ensemble traffic (ROADMAP north star):

- :mod:`.params` -- :class:`Param` placeholders (alias ``P``) making gate
  angles/Complex scalars *runtime arguments* of one compiled replay, plus
  the constant-lifting canonicalisation behind structure fingerprints.
- :mod:`.cache` -- the structure fingerprint, the bounded telemetered LRU
  every compiled replay routes through, and JAX persistent-compilation-
  cache wiring (``QUEST_COMPILE_CACHE``) so cold starts survive restarts.
- :mod:`.engine` -- :class:`Engine`: ``submit(params) -> Future`` with a
  micro-batcher coalescing requests into one ``vmap``-over-params program
  (unsharded) or a donated-buffer sequential replay (sharded).
- :mod:`.pool` -- :class:`EnginePool`: N replicas behind health-aware,
  structure-affine routing, with quarantine failover (zero dropped
  futures, bit-identical recovery), hedged dispatch, and warm replacement
  spawning from a fingerprint manifest.
- :mod:`.admission` -- per-tenant token-bucket quotas with a
  high-priority reserve band in front of the pool
  (``QuESTBackpressureError`` with ``reason="quota"``).

Quickstart::

    from quest_tpu.circuits import Circuit
    from quest_tpu.engine import Engine, P

    c = Circuit(20)
    for q in range(20):
        c.rotateZ(q, P(f"theta{q}"))
    ...
    with Engine(c, env, max_batch=8) as eng:
        futs = eng.submit_many([{f"theta{q}": v for q, v in enumerate(vec)}
                                for vec in sweep])
        states = [f.result() for f in futs]

See docs/serving.md for lifecycle, batching knobs and cache sizing.
"""

import os as _os

from .admission import (  # noqa: F401
    PRIORITIES, AdmissionController, TokenBucket,
)
from ..cache import (  # noqa: F401
    LRUCache, enable_persistent_cache, executables, structure_fingerprint,
)
from .engine import Engine  # noqa: F401
from ..params import (  # noqa: F401
    LiftedTape, P, Param, ParamExecutable, Slot, bind, lift_tape,
)
from .pool import EnginePool  # noqa: F401

__all__ = [
    "Param", "P", "ParamExecutable", "LiftedTape", "Slot", "lift_tape",
    "bind", "LRUCache", "executables", "structure_fingerprint",
    "enable_persistent_cache", "Engine", "EnginePool",
    "AdmissionController", "TokenBucket", "PRIORITIES",
]

# opt-in cross-restart compile cache: wire it up as early as possible so
# the first Engine/Circuit compile of the process already persists
if _os.environ.get("QUEST_COMPILE_CACHE"):  # pragma: no cover - env wiring
    enable_persistent_cache()
