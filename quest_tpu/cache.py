"""Plan/executable cache: structure fingerprints + a bounded telemetered LRU.

The reference compiles nothing, so it has no compile-cost cliff to
amortise; this build's ``Circuit`` executables are whole XLA programs whose
trace/fuse/Mosaic-compile cost at scale dwarfs a single execution. Three
layers keep that cost off the serving hot path:

1. :func:`structure_fingerprint` -- a content hash of a tape's STRUCTURE
   (gate names, targets/controls, value-slot kinds, baked operand bytes --
   never the lifted values), so "same ansatz, different angles" keys to the
   same executable.
2. :class:`LRUCache` -- a bounded, thread-safe, in-memory LRU all compiled
   replays route through (the per-``Circuit`` caches of earlier rounds grew
   without limit per (mode, mesh) key), with uniform
   ``plan_cache_{hit,miss,evict}_total{cache=...}`` counters and a
   ``plan_cache_size`` gauge.
3. :func:`enable_persistent_cache` -- wiring for JAX's persistent
   compilation cache (``QUEST_COMPILE_CACHE`` env or explicit path), so the
   cold-start Mosaic/XLA compile survives process restarts; an evicted or
   restarted executable re-traces but re-loads its binaries from disk.

Capacity defaults to ``QUEST_PLAN_CACHE_SIZE`` (128). Cache keys hold no
device buffers -- entries are host callables closing over jitted functions,
so eviction frees the jit cache via the executable's refcount.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
from collections import OrderedDict

import jax
import numpy as np

from . import telemetry
from .compile_cache import CACHE_ENV
from .params import Param, _SlotRef, lift_tape
from .resilience import sync as _sync

__all__ = ["LRUCache", "executables", "structure_fingerprint",
           "enable_persistent_cache"]


class LRUCache:
    """Bounded thread-safe LRU with flight-recorder counters.

    ``get_or_create(key, factory)`` is the one entry point the executable
    paths use: a hit refreshes recency and counts
    ``plan_cache_hit_total{cache=name}``; a miss runs ``factory()`` under
    the lock (factories here build cheap host wrappers -- compilation
    happens lazily at first call), stores, counts a miss, and evicts
    least-recently-used entries past ``capacity`` (counted per eviction).
    """

    def __init__(self, capacity: int = 128, name: str = "exec"):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.name = name
        # re-entrant: a factory may itself route nested executables through
        # the same cache (compiled_segments builds its per-segment replays)
        self._lock = _sync.RLock("engine.cache")
        self._od: OrderedDict = OrderedDict()

    def __len__(self) -> int:
        with self._lock:
            return len(self._od)

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._od

    def peek(self, key, default=None):
        """Non-mutating probe: no recency refresh, no hit/miss counters,
        no eviction-order side effects. The pool's ahead-of-demand
        precompiler (round 18) uses this to classify a fingerprint as
        already-warm without promoting it over entries live traffic is
        actually using."""
        with self._lock:
            return self._od.get(key, default)

    def get(self, key, default=None):
        """Telemetered lookup (hit/miss counted, recency refreshed)."""
        with self._lock:
            if key in self._od:
                self._od.move_to_end(key)
                telemetry.inc("plan_cache_hit_total", cache=self.name)
                return self._od[key]
        telemetry.inc("plan_cache_miss_total", cache=self.name)
        return default

    def put(self, key, value) -> None:
        with self._lock:
            self._od[key] = value
            self._od.move_to_end(key)
            self._evict_locked()
        telemetry.set_gauge("plan_cache_size", len(self), cache=self.name)

    def get_or_create(self, key, factory):
        with self._lock:
            if key in self._od:
                self._od.move_to_end(key)
                telemetry.inc("plan_cache_hit_total", cache=self.name)
                return self._od[key]
            telemetry.inc("plan_cache_miss_total", cache=self.name)
            value = factory()
            self._od[key] = value
            self._evict_locked()
        telemetry.set_gauge("plan_cache_size", len(self), cache=self.name)
        return value

    def _evict_locked(self) -> None:
        while len(self._od) > self.capacity:
            self._od.popitem(last=False)
            telemetry.inc("plan_cache_evict_total", cache=self.name)

    def clear(self) -> None:
        with self._lock:
            self._od.clear()
        telemetry.set_gauge("plan_cache_size", 0, cache=self.name)

    def keys(self) -> list:
        with self._lock:
            return list(self._od)


#: process-global executable cache every compiled Circuit replay routes
#: through (Circuit.compiled / compiled_segments / parameterized and the
#: Engine's batch executables); bounded so a long-lived server submitting
#: many circuit structures cannot grow it without limit
_EXECUTABLES = LRUCache(
    int(os.environ.get("QUEST_PLAN_CACHE_SIZE", "128")), name="executable")


def executables() -> LRUCache:
    """The process-global compiled-replay LRU."""
    return _EXECUTABLES


# ---------------------------------------------------------------------------
# structure fingerprint
# ---------------------------------------------------------------------------

def _canon(x):
    """Canonical hashable form of one tape operand: value slots collapse to
    their kind, baked operands hash by content, unknown objects by identity
    (unique -- never wrongly shared)."""
    if isinstance(x, _SlotRef):
        return ("slot",)
    if isinstance(x, Param):  # un-lifted tape: still a value slot
        return ("slot",)
    if x is None or isinstance(x, (str, bytes)):
        return x
    if isinstance(x, bool) or isinstance(x, (int, np.integer)):
        return ("i", int(x))
    if isinstance(x, (float, np.floating)):
        return ("f", repr(float(x)))
    if isinstance(x, (complex, np.complexfloating)):
        return ("c", repr(complex(x)))
    if isinstance(x, np.ndarray):
        a = np.ascontiguousarray(x)
        return ("a", a.shape, a.dtype.str,
                hashlib.sha1(a.tobytes()).hexdigest())
    if type(x).__name__ == "HashableMatrix":  # pallas op payloads
        return ("hm",) + _canon(np.asarray(x.arr))[1:]
    if isinstance(x, (tuple, list)):
        return ("t", tuple(_canon(e) for e in x))
    if isinstance(x, dict):  # the kwargs of a nested tape entry
        return ("d", tuple(sorted((k, _canon(v)) for k, v in x.items())))
    if callable(x):
        return ("fn", getattr(x, "__module__", ""),
                getattr(x, "__qualname__", repr(x)))
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        return ("dc", type(x).__name__,
                tuple(_canon(getattr(x, f.name))
                      for f in dataclasses.fields(x)))
    # opaque object: identity-keyed so distinct operands never collide (the
    # same tape re-fingerprinting stays stable; sharing is simply forgone)
    return ("obj", type(x).__name__, id(x))


def structure_fingerprint(tape, num_qubits: int, is_density: bool,
                          extra=()) -> str:
    """Content hash of a tape's structure. Lifted value slots (angles,
    Complex scalars -- see :mod:`.params`) contribute only their existence,
    so two tapes differing in those values collide (by design: they share
    one executable); anything else differing -- gate names, targets,
    controls, baked matrices, channel probabilities -- changes the hash."""
    lifted = lift_tape(tuple(tape))
    tokens = [("hdr", int(num_qubits), bool(is_density), _canon(tuple(extra)))]
    for fn, args, kwargs in lifted.entries:
        tokens.append((_canon(fn), _canon(args),
                       tuple(sorted((k, _canon(v))
                             for k, v in kwargs.items()))))
    return hashlib.sha256(repr(tokens).encode()).hexdigest()


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

def enable_persistent_cache(path: str | None = None,
                            min_compile_secs: float = 0.5) -> str | None:
    """Point JAX's persistent compilation cache at ``path`` (default: the
    ``QUEST_COMPILE_CACHE`` env var; no-op returning None when neither is
    set). Compiled XLA/Mosaic binaries then survive process restarts: a
    cold Engine still traces, but re-loads its executables from disk
    instead of recompiling -- the cross-process leg of the plan/executable
    cache (the in-memory LRU covers the in-process leg).

    ``JAX_COMPILATION_CACHE_DIR`` outranks both: where it is set the cache
    is placed from outside and no directory is set here (the returned
    path is then that variable's)."""
    path = path or os.environ.get("QUEST_COMPILE_CACHE")
    if not path:
        return None
    if os.environ.get(CACHE_ENV):
        path = os.environ[CACHE_ENV]
    else:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float(min_compile_secs))
    telemetry.event("engine.persistent_cache", path=path)
    return path
