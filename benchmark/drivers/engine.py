"""The serving path: one ``Engine`` over the configuration's ansatz.

A request is ``submit(angles)``, the wait for its future, and the sync of the
state it resolves to, timed from the client's side. The seed draws every
client's angle sets; the engine's one padded program serves all of them.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import reference
import states


class Driver:
    #: the engine replays its dense plan of the tape (window GEMMs and
    #: diagonal passes, XLA only): no Pallas kernel is expected
    expects_kernels = False

    def __init__(self, run):
        self.run = run
        self.args = run.circuit_args
        self.n = self.args["num_qubits"]
        self.keep_every = run.config["check"]["keep_every"]
        self.kept = {}
        self._lock = threading.Lock()

    # -- set-up -------------------------------------------------------------

    def setup(self):
        import quest_tpu as qt
        from quest_tpu.circuits import Circuit
        from quest_tpu.engine import Engine, P

        run, cfg = self.run, self.run.config
        circ = Circuit(self.n)
        run.builder.build(circ, angle=P, **self.args)
        env = qt.createQuESTEnv()
        with run.span("plan_s"):
            self.engine = Engine(circ, env, **cfg["engine"])
        self.names = run.builder.param_names(**self.args)
        if set(self.names) != set(self.engine.param_names):
            raise ValueError("the engine's Params differ from the builder's")
        self.load_state()
        with run.span("first_call_s"):
            self.request(0, 0)
        with run.span("warm_s"):
            # every client at once, twice: the coalesced batches the window
            # will see, through the one padded program
            for _ in range(2):
                futs = [self.engine.submit(pool[0]) for pool in self.pools]
                self.sync([f.result(timeout=900) for f in futs])
        self.kept.clear()

    def load_state(self):
        """The seed's angle sets, one pool a client."""
        traffic = self.run.traffic
        self.pools = [states.angle_sets(self.run.seed, c, self.names,
                                        traffic["inputs_per_client"])
                      for c in range(traffic["clients"])]
        self.kept.clear()

    def shapes(self) -> dict:
        return {"state_bytes": 8 << self.n}

    # -- the timed path -----------------------------------------------------

    def sync(self, x):
        import jax

        jax.block_until_ready(x)

    def request(self, client, k) -> bool:
        import jax

        pool = self.pools[client]
        params = pool[k % len(pool)]
        with jax.profiler.TraceAnnotation("submit"):
            fut = self.engine.submit(params)
        with jax.profiler.TraceAnnotation("wait"):
            state = fut.result(timeout=900)
            self.sync(state)
        if (k + client) % self.keep_every == 0:
            with self._lock:
                self.kept[(client, k)] = (state, time.perf_counter())
        return True

    # -- correctness, outside the window ------------------------------------

    def check(self, window) -> list:
        """A seeded sample of the requests the window finished, each against
        the reference replay of the ansatz at that request's own angles."""
        cfg = self.run.config["check"]
        limits = cfg["limits"]
        done = sorted(key for key, (_, t) in self.kept.items()
                      if window.t0 <= t)
        if not done:
            return [("requests_left_unchecked", 1.0, 0.0)]
        rng = np.random.default_rng([self.run.seed, 11])
        picks = rng.choice(len(done), size=min(cfg["requests"], len(done)),
                           replace=False)
        psi0 = np.zeros(1 << self.n, dtype=np.complex128)
        psi0[0] = 1.0

        def compare(i):
            client, k = done[i]
            pool = self.pools[client]
            tape = reference.Tape()
            self.run.builder.build(tape, angle=pool[k % len(pool)].__getitem__,
                                   **self.args)
            want = reference.run_statevector(psi0, tape.ops, threads=1)
            got = self.run.output_planes(
                lambda: np.asarray(self.kept[(client, k)][0]), psi0, tape.ops)
            return reference.errors(got[0], got[1], want)

        t0 = time.perf_counter()
        # one replay a host thread: at 2^20 amplitudes a gate is too short to
        # split, a request is not
        with ThreadPoolExecutor(min(len(picks), reference.host_threads())) as ex:
            errs = list(ex.map(compare, sorted(picks)))
        worst_max = max(e[0] for e in errs)
        worst_l2 = max(e[1] for e in errs)
        self.run.spans["reference_s"] = time.perf_counter() - t0
        return [("err_max", worst_max, limits["err_max"]),
                ("err_l2", worst_l2, limits["err_l2"]),
                ("requests_left_unchecked", 0.0, 0.0)]

    def close(self):
        if hasattr(self, "engine"):
            self.engine.close()
