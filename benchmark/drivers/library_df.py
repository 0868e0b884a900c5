"""A QuEST library program in the reference's default precision: the
``library`` driver's request (``fused.run(register)`` then
``block_until_ready``, one application a request) on a PRECISION=2 register,
which on the chip is the double-float kernel route (``ops/pallas_df.py``:
each float64 plane an unevaluated sum of two float32 planes).

What differs from ``library``: ``QUEST_PRECISION=2`` is in the environment
before the plan and the register are made (the program reads it at the call,
and turns JAX's x64 mode on then), and under ``--rehearse``
``QUEST_PALLAS_DF=1`` too, so that the CPU takes the route the chip takes on
its own; the register comes from ``createQureg`` and has to be float64 with
x64 on; the seed's state is float64, normalised in float64, so that the low
planes carry amplitude bits and a program that dropped them would be seen;
before anything compiles the plan is refused if a run holds more ops than a
df kernel takes (it would be cut as it executes, a pass the plan did not
state and a counted ``engine_fallback_total``) or if a relabeling would not
ride its kernel's DMA; and the check compares every amplitude of the timed
program's float64 output with the numpy complex128 replay at limits in the
1e-12 class. ``shapes()`` counts 16 bytes an amplitude.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

import reference
import states
from drivers.library import Driver as Library

#: exit code of a plan refused before any compile (``library_large``'s)
EXIT_PLAN_REFUSED = 5


def statevector_planes64(seed: int, num_qubits: int):
    """(2, 2^n) float64 planes (re, im) of a normalised Gaussian vector, made
    on the device: the draw of ``states.statevector_planes`` for the same
    seed, widened to float64 and normalised THERE, so that its norm is 1 to
    float64 rounding and the low float32 plane of every amplitude is
    occupied (a float32 vector widened as it is would have none)."""
    import jax
    import jax.numpy as jnp

    def make(key):
        g = jax.random.normal(key, (2, 1 << num_qubits), dtype=jnp.float32)
        g = g.astype(jnp.float64)
        return g / jnp.sqrt(jnp.sum(g * g))

    return jax.jit(make)(states._key(seed))


class Driver(Library):

    def setup(self):
        os.environ["QUEST_PRECISION"] = "2"
        if self.run.rehearse:
            os.environ["QUEST_PALLAS_DF"] = "1"
        import jax
        import quest_tpu as qt
        from quest_tpu.circuits import Circuit

        run, cfg = self.run, self.run.config
        self.env = qt.createQuESTEnv(jax.devices()[:1])
        circ = Circuit(self.n)
        run.builder.build(circ, **self.args)
        with run.span("plan_s"):
            self.fused = circ.fused(**cfg["fused"])
        with run.span("state_s"):
            self.q = qt.createQureg(self.n, self.env)
            if (self.q.amps.dtype != np.float64
                    or not jax.config.jax_enable_x64):
                raise RuntimeError(
                    f"QUEST_PRECISION=2 gave a {self.q.amps.dtype} register "
                    f"(x64 {jax.config.jax_enable_x64})")
            self.refuse_uncut_plan()
            self.load_state()
            self.sync()
        with run.span("first_call_s"):
            self.apply()
        with run.span("warm_s"):
            self.apply()

    def refuse_uncut_plan(self):
        """Exit, before anything compiles, where the plan is not one this
        register's kernels run as stated: a run that is not on the
        double-float route, one longer than a df kernel takes (the executor
        would cut it, ``df_max_ops_split``: what a planner that does not cut
        df runs gives at this size), or one whose relabeling would run as an
        explicit pass beside its kernel."""
        from quest_tpu import fusion
        from quest_tpu.ops.pallas_df import DF_MAX_OPS

        plan = fusion.plan_from_tape(self.fused._tape)
        for r in (i for i in plan.items if isinstance(i, fusion.PallasRun)):
            route = fusion._route(self.q, r)
            why = None
            if route.kind != "df_local":
                why = f"its route is {route.kind} ({route.reason})"
            elif len(r.ops) > DF_MAX_OPS:
                why = (f"a df kernel takes {DF_MAX_OPS}: it would be cut as "
                       "it runs (df_max_ops_split)")
            elif route.reason is not None:
                why = f"its relabeling would not fold ({route.reason})"
            if why is not None:
                print(f"# refused: a fused run of {len(r.ops)} ops with the "
                      f"relabelings k={r.load_swap_k}@{r.load_swap_hi} / "
                      f"k={r.store_swap_k}@{r.store_swap_hi}: {why}",
                      file=sys.stderr, flush=True)
                raise SystemExit(EXIT_PLAN_REFUSED)

    def seed_state(self):
        return statevector_planes64(self.run.seed, self.n)

    def load_state(self):
        """The seed's float64 state into the register, the old one freed
        first."""
        self.applications = 0
        self.q.amps.delete()
        self.q.put(self.seed_state())

    def shapes(self) -> dict:
        return {"state_bytes": 16 << self.n}

    def norm(self) -> float:
        import quest_tpu as qt

        return float(qt.calcTotalProb(self.q))

    # -- correctness, outside the window ------------------------------------

    def check(self, window) -> list:
        """``library``'s check at this configuration's limits; under
        ``--rehearse`` at the rehearsal's (XLA:CPU contracts the error-free
        transforms of the df arithmetic, so the interpreted kernels keep
        about float32 accuracy there: only the chip holds the real limits)."""
        cfg = self.run.config
        limits = (cfg["rehearse"]["limits"] if self.run.rehearse
                  else cfg["check"]["limits"])
        drift = abs(self.norm() - 1.0) / max(self.applications, 1)
        # the same compiled program, once more, from the seed's state
        self.load_state()
        self.apply()
        tape = reference.Tape()
        self.run.builder.build(tape, **self.args)
        return [("drift_per_application", drift,
                 limits["drift_per_application"])] \
            + self._check_vector(tape, limits)

    def _check_vector(self, tape, limits) -> list:
        psi0 = states.to_complex(self.seed_state())
        got = self.run.output_planes(lambda: np.asarray(self.q.amps),
                                     psi0, tape.ops)
        if got.dtype != np.float64:
            raise RuntimeError(f"the program's output is {got.dtype}")
        t0 = time.perf_counter()
        want = reference.run_statevector(psi0, tape.ops)
        self.run.spans["reference_s"] = time.perf_counter() - t0
        err_max, err_l2 = reference.errors(got[0], got[1], want)
        return [("err_max", err_max, limits["err_max"]),
                ("err_l2", err_l2, limits["err_l2"])]
