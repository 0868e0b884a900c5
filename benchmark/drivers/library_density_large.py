"""A QuEST library program on a DENSITY register that fills half its chip:
``library_large``'s order of work (the request is ``library``'s:
``fused.run(register)`` then ``block_until_ready``, one application a
request) for a register of 2^30 elements of a 15-qubit density matrix, beside
which nothing state-sized may stand.

The order: the plan first, and the plan is REFUSED before anything is
allocated or compiled (``EXIT_PLAN_REFUSED``, a line on standard error) where
it holds anything but fused runs -- a raw tape entry is a channel no frame
localised, which runs as a Kraus sum over three states, and a ``FusedBlock``
a dense window pass, which holds two; then the public constructor's own
register, ``createDensityQureg``, and the refusal again where a run would not
run whole in its kernel (``fusion._route``: an explicit relabeling writes a
second state); ``calcTotalProb`` of its |0><0|, which is then freed; the
seed's projector, written where it lives block by block
(``states_density``). After the window the check loads the seed's state
again, runs the same compiled program once, takes its output to the host in
pieces, frees the device, runs the plain reference
(``reference_density_planes``) from the seed's state there and compares every
amplitude. Nothing of that is in the window or in ``setup_s``.

Under ``--rehearse`` the configuration's ``rehearse.sublanes`` cuts the tile
so small that, at the rehearsal's few qubits, a neighbouring pair's columns
straddle its edge as they do at 2^19 on the chip: the plan is then made by
``fusion.plan`` at that tile (``Circuit.fused`` has no such option: it plans
for the register's own), and the routes read the same sublane count.
"""

from __future__ import annotations

import sys
import time

import numpy as np

import reference
import reference_density_planes
import reference_planes
import states_density
from drivers.library_large import (EXIT_PLAN_REFUSED, PIECES, Driver as Large,
                                   errors_by_piece, to_host)


class Driver(Large):

    def setup(self):
        import jax
        import quest_tpu as qt
        from quest_tpu.circuits import Circuit

        run = self.run
        circ = Circuit(self.n, is_density_matrix=True)
        run.builder.build(circ, **self.args)
        with run.span("plan_s"):
            self.fused = self.plan(circ)
        self.refuse_plan()
        self.env = qt.createQuESTEnv(jax.devices()[:1])
        with run.span("state_s"):
            self.q = qt.createDensityQureg(self.n, self.env)
            self.refuse_unfolded_plan()
            if qt.calcTotalProb(self.q) != 1.0:
                raise RuntimeError("createDensityQureg's |0><0| has not "
                                   "trace 1")
            self.load_state()
            self.sync()
        with run.span("first_call_s"):
            self.apply()
        with run.span("warm_s"):
            self.apply()

    def plan(self, circ):
        """``circ.fused(**fused)``: the normal path. A rehearsal plans the
        same tape at the tile its ``rehearse.sublanes`` cut."""
        cfg = self.run.config
        sublanes = cfg["rehearse"].get("sublanes") if self.run.rehearse \
            else None
        if sublanes is None:
            return circ.fused(**cfg["fused"])
        from quest_tpu import fusion
        from quest_tpu.circuits import Circuit
        from quest_tpu.ops import pallas_gates

        pallas_gates._DEF_SUBLANES = sublanes     # what the routes read
        plan = fusion.plan(
            tuple(circ._tape), self.n, np.dtype(cfg["precision"]),
            max_qubits=cfg["fused"]["max_qubits"], is_density=True,
            pallas_tile_bits=pallas_gates.local_qubits(2 * self.n, sublanes))
        fused = Circuit(self.n, is_density_matrix=True)
        fused._tape = fusion.as_tape(plan)
        return fused

    def refuse_plan(self):
        """Exit, before anything is allocated or compiled, where the plan
        holds an item that is no fused run."""
        from quest_tpu import fusion

        plan = fusion.plan_from_tape(self.fused._tape)
        for item in plan.items:
            if isinstance(item, fusion.PallasRun):
                continue
            if isinstance(item, tuple):
                what = (f"the raw tape entry {item[0].__name__}"
                        f"{tuple(item[1])!r}: a barrier in the plan, which "
                        "runs outside any kernel (a channel as a Kraus sum "
                        "over three states)")
            else:
                what = (f"a {type(item).__name__}: a pass over the whole "
                        "state outside any kernel, which holds a second one")
            print(f"# refused: the plan holds {what}; the register's "
                  f"{self.shapes()['state_bytes']} bytes leave no room for "
                  "it", file=sys.stderr, flush=True)
            raise SystemExit(EXIT_PLAN_REFUSED)

    def load_state(self):
        """The seed's projector into the register, the old state freed
        FIRST."""
        self.applications = 0
        self.q.amps.delete()
        self.psi0, rho = states_density.projector_planes(self.run.seed,
                                                         self.n)
        self.q.put(rho)

    def shapes(self) -> dict:
        return {"state_bytes": 8 << (2 * self.n)}

    # -- correctness, outside the window ------------------------------------

    def check(self, window) -> list:
        """Numbers compared, each ``(name, value, limit)``: the program's, or
        where ``control.py`` has put a rounding function on the run the
        control's in their place."""
        limits = self.run.config["check"]["limits"]
        control = self.run.control is not None
        read = self.readings(window, sound=not control, control=control)
        values = read["control" if control else "sound"]
        return [(name, values[name], limits[name])
                for name in ("drift_per_application", "err_max", "err_l2",
                             "trace_err")]

    def readings(self, window, sound: bool = True,
                 control: bool = False) -> dict:
        """``{"sound": {...}, "control": {...}}``: every number of the check
        as the program gives it and, for ``control``, with the reference in
        the next precision below in the program's place
        (``reference_planes.LOWER``), both against ONE run of the reference
        (``control_density.py`` asks for both at once)."""
        import jax

        # the window's own final state: every application kept the trace
        drift = abs(self.norm() - 1.0) / max(self.applications, 1)
        # the same compiled program, once more, from the seed's state
        self.load_state()
        self.apply()
        tape = reference.Tape()
        self.run.builder.build(tape, **self.args)
        got = {}
        if sound:
            got["sound"] = (to_host(self.q.amps, 1),    # the output, whole
                            abs(self.norm() - 1.0))
        self.q.amps.delete()                # the reference takes its place
        if control:
            lower = reference_planes.LOWER[self.run.config["precision"]]
            low = self.reference(tape.ops, lower)
            got["control"] = (
                np.stack([to_host(p, 0).reshape(-1) for p in low]),
                abs(reference_density_planes.trace(low[0], self.n) - 1.0))
            for p in low:
                p.delete()
        t0 = time.perf_counter()
        want = jax.block_until_ready(self.reference(tape.ops))
        self.run.spans["reference_s"] = time.perf_counter() - t0
        out = {}
        for label, (amps, trace_err) in got.items():
            err_max, err_l2 = errors_by_piece(amps, want)
            out[label] = {"drift_per_application": drift, "err_max": err_max,
                          "err_l2": err_l2, "trace_err": trace_err}
        print("# check: trace of the reference "
              f"{reference_density_planes.trace(want[0], self.n)!r} (made in "
              f"{self.run.spans['reference_s']:.2f} s); {want[0].size} "
              f"amplitudes compared in {PIECES} pieces",
              file=sys.stderr, flush=True)
        del got
        for p in want:
            p.delete()
        # left holding a state, as ``library_large`` leaves one
        self.q.put(states_density.projector_planes(self.run.seed, self.n)[1])
        return out

    def reference(self, ops, lower=None) -> tuple:
        """``reference_density_planes.run_density`` from the seed's
        projector, made as planes of their own where they live."""
        re, im = states_density.projector_rows(self.run.seed, self.n)
        return reference_density_planes.run_density(re, im, self.n, ops,
                                                    lower)
