"""A QuEST library program on a register no single device can hold: the
``library`` driver's request (``fused.run(register)`` then
``block_until_ready``, one application a request) on a state-vector register
sharded an equal part per chip over the host's chips.

What differs from ``library``: the plan is made for the shards
(``shard_devices``); the seed's state is made on the chips, shard by shard
(``states_sharded``); before the window the register is asserted to live an
equal part on each device; and the check compares every amplitude with the
plain reference computed where the register lives, beside the output
(``reference_planes``), because neither 2^31 amplitudes in one host array nor
their complex128 replay fit a run. Only API the program had before this driver is used.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import reference_planes
import states_sharded
from drivers.library import Driver as Library


class Driver(Library):

    def setup(self):
        import jax
        import quest_tpu as qt
        from quest_tpu.circuits import Circuit
        from quest_tpu.registers import Qureg

        run, cfg = self.run, self.run.config
        want = cfg["devices"]
        # a rehearsal takes what the sandbox has: one device, or the four
        # virtual ones a test gives it
        count = min(want, len(jax.devices())) if run.rehearse else want
        self.env = qt.createQuESTEnv(jax.devices()[:count])
        circ = Circuit(self.n)
        run.builder.build(circ, **self.args)
        fused = dict(cfg["fused"], shard_devices=count if count > 1 else None)
        with run.span("plan_s"):
            self.fused = circ.fused(**fused)
        with run.span("state_s"):
            # createQureg's own register, but for its |0...0>: that is made
            # whole on one device before it is placed, which this size
            # cannot be
            self.q = Qureg(self.n, False, self.seed_state(), self.env)
            self.sync()
        with run.span("first_call_s"):
            self.apply()
        with run.span("warm_s"):
            self.apply()
        self.assert_sharded(count)

    def seed_state(self):
        """The seed's state, sharded as the environment shards a register."""
        from quest_tpu.environment import AMP_AXIS

        return states_sharded.statevector_planes(
            self.run.seed, self.n, self.env.mesh, AMP_AXIS)

    def load_state(self):
        self.applications = 0
        self.q.put(self.seed_state())

    def assert_sharded(self, count: int):
        """What ``chip_smoke.phase_sharded`` asserts: the register lives on
        every device, an equal part on each."""
        amps = self.q.amps
        on = {str(s.device): int(np.prod(s.data.shape))
              for s in amps.addressable_shards}
        if (len(amps.sharding.device_set) != count or len(on) != count
                or any(v != (2 << self.n) // count for v in on.values())):
            raise RuntimeError(f"the register is not 1/{count} a device: {on}")

    def shapes(self) -> dict:
        devices = len(self.q.amps.sharding.device_set)
        return {"state_bytes": 8 << self.n, "devices": devices,
                "shard_bytes": (8 << self.n) // devices,
                "cell": self.run.cell["name"]}

    # -- correctness, outside the window ------------------------------------

    def _check_vector(self, tape, limits) -> list:
        import jax

        ops, out = tape.ops, self.q.amps
        if self.run.control is None:
            got = reference_planes.split(out)
        out.delete()                # the planes take its place on the chips
        del out
        if self.run.control is not None:
            # the control: the reference in the next precision below, in the
            # program's place
            lower = reference_planes.LOWER[self.run.config["precision"]]
            got = reference_planes.run_statevector(self.seed_state(), self.n,
                                                   ops, lower=lower)
        t0 = time.perf_counter()
        want = jax.block_until_ready(
            reference_planes.run_statevector(self.seed_state(), self.n, ops))
        self.run.spans["reference_s"] = time.perf_counter() - t0
        err_max, err_l2 = reference_planes.errors(got, want)
        print("# check: total probability of the output "
              f"{reference_planes.total_probability(got)!r}, of the "
              f"reference {reference_planes.total_probability(want)!r} "
              f"(made in {self.run.spans['reference_s']:.2f} s)",
              file=sys.stderr, flush=True)
        del got, want
        self.load_state()           # the register is left holding a state
        return [("err_max", err_max, limits["err_max"]),
                ("err_l2", err_l2, limits["err_l2"])]
