"""A QuEST library program: the configuration's circuit, fused into its
Pallas plan, applied to one resident register and synced.

A request is one application: ``fused.run(register)`` then
``block_until_ready`` -- the sync a measurement forces. The register is
never re-initialised inside the window, so the state evolves; the check
re-loads the seed's state after the window and drives the same compiled
program once more.
"""

from __future__ import annotations

import time

import numpy as np

import reference
import states


class Driver:
    #: a run of this driver that compiled no Pallas kernel is not correct
    expects_kernels = True

    def __init__(self, run):
        self.run = run
        cfg = run.config
        self.density = cfg["register"] == "density"
        self.args = run.circuit_args
        self.n = self.args["num_qubits"]
        self.applications = 0

    # -- set-up -------------------------------------------------------------

    def setup(self):
        import quest_tpu as qt
        from quest_tpu.circuits import Circuit

        run, cfg = self.run, self.run.config
        circ = Circuit(self.n, is_density_matrix=self.density)
        run.builder.build(circ, **self.args)
        with run.span("plan_s"):
            self.fused = circ.fused(**cfg["fused"])
        env = qt.createQuESTEnv()
        with run.span("state_s"):
            make = qt.createDensityQureg if self.density else qt.createQureg
            self.q = make(self.n, env)
            self.load_state()
            self.sync()
        with run.span("first_call_s"):
            self.apply()
        with run.span("warm_s"):
            self.apply()

    def load_state(self):
        """The seed's initial state into the register (the old one is freed)."""
        self.applications = 0
        if self.density:
            self.psi0, rho = states.projector_planes(self.run.seed, self.n)
            self.q.put(rho)
        else:
            self.q.put(states.statevector_planes(self.run.seed, self.n))

    def shapes(self) -> dict:
        return {"state_bytes": 8 << ((2 if self.density else 1) * self.n)}

    # -- the timed path -----------------------------------------------------

    def sync(self):
        import jax

        jax.block_until_ready(self.q.amps)

    def apply(self):
        self.fused.run(self.q)
        self.sync()
        self.applications += 1

    def request(self, client, k) -> bool:
        import jax

        if client != 0:
            raise ValueError("a library register has one caller")
        with jax.profiler.TraceAnnotation("apply"):
            self.fused.run(self.q)
        with jax.profiler.TraceAnnotation("sync"):
            self.sync()
        self.applications += 1
        return True

    # -- correctness, outside the window ------------------------------------

    def norm(self) -> float:
        """Total probability of the register: squared norm, or the trace."""
        import jax.numpy as jnp

        amps = self.q.amps
        if self.density:
            return float(jnp.sum(amps[0, ::(1 << self.n) + 1]))
        return float(jnp.sum(amps * amps))

    def check(self, window) -> list:
        """Numbers compared, each ``(name, value, limit)``."""
        limits = self.run.config["check"]["limits"]
        out = []
        # the window's own final state: every application so far kept the
        # total probability (a step that zeroes or blows up the state, or a
        # NaN, fails here)
        drift = abs(self.norm() - 1.0) / max(self.applications, 1)
        out.append(("drift_per_application", drift,
                    limits["drift_per_application"]))
        # the same compiled program, once more, from the seed's state
        self.load_state()
        self.apply()
        tape = reference.Tape()
        self.run.builder.build(tape, **self.args)
        if self.density:
            out += self._check_blocks(tape, limits)
        else:
            out += self._check_vector(tape, limits)
        return out

    def _check_vector(self, tape, limits) -> list:
        psi0 = states.to_complex(states.statevector_planes(self.run.seed,
                                                           self.n))
        got = self.run.output_planes(lambda: np.asarray(self.q.amps),
                                     psi0, tape.ops)
        t0 = time.perf_counter()
        want = reference.run_statevector(psi0, tape.ops)
        self.run.spans["reference_s"] = time.perf_counter() - t0
        err_max, err_l2 = reference.errors(got[0], got[1], want)
        return [("err_max", err_max, limits["err_max"]),
                ("err_l2", err_l2, limits["err_l2"])]

    def _check_blocks(self, tape, limits) -> list:
        import jax.numpy as jnp

        cfg = self.run.config
        count = (cfg["rehearse"]["blocks"] if self.run.rehearse
                 else cfg["check"]["blocks"])
        active = reference.support(tape.ops)
        rows, cols = states.sample_pairs(self.run.seed, count,
                                         self.n - len(active))
        idx = reference.block_indices(self.n, active, rows, cols)
        flat = jnp.asarray(idx.reshape(-1).astype(np.int32))
        psi0 = states.to_complex(self.psi0)
        got = self.run.output_blocks(
            lambda: np.asarray(jnp.take(self.q.amps, flat, axis=1)),
            psi0, self.n, tape.ops, rows, cols)
        t0 = time.perf_counter()
        want = reference.run_density_blocks(psi0, self.n, tape.ops, rows, cols)
        self.run.spans["reference_s"] = time.perf_counter() - t0
        err_max, err_l2 = reference.errors(got[0], got[1], want)
        return [("err_max", err_max, limits["err_max"]),
                ("err_l2", err_l2, limits["err_l2"]),
                ("trace_err", abs(self.norm() - 1.0), limits["trace_err"])]

    def close(self):
        pass
