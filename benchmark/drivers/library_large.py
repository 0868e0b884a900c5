"""A QuEST library program on a register that fills its chip: the
``library`` driver's request (``fused.run(register)`` then
``block_until_ready``, one application a request) on a state-vector register
of half the device's memory, so that nothing state-sized may stand beside it.

What differs from ``library``: the register comes from ``createQureg`` and
its |0...0> is freed before the seed's state is made (two states do not fit);
the seed's state is made block by block (``states_sharded`` on the
environment's one-device mesh: ``states.statevector_planes`` draws the whole
vector in one call); before anything compiles the plan is refused if one of
its relabelings would not ride its kernel's DMA (an explicit pass writes a
second state, and a program that cannot fit is not worth its compile); and
the check compares every amplitude with the plain reference
(``reference_planes``) computed on the chip AFTER the program's output has
gone to the host, piece by piece, because output and reference together are
the whole device. Nothing of that is in the window or in ``setup_s``.
"""

from __future__ import annotations

import math
import sys
import time

import numpy as np

import reference_planes
import states_sharded
from drivers.library import Driver as Library

#: exit code of a plan refused before any compile (``run.py`` has 3 and 4)
EXIT_PLAN_REFUSED = 5

#: pieces the state travels in between device and host, and is compared in
PIECES = 16


def to_host(x, axis: int) -> np.ndarray:
    """The device array ``x`` as one host array, fetched in ``PIECES``
    slices along ``axis`` (a slice is the only device temporary): the
    amplitudes of a (2, N) state, the rows of a plane."""
    import jax

    size = x.shape[axis] // PIECES
    cut = jax.jit(lambda x, at: jax.lax.dynamic_slice_in_dim(x, at, size,
                                                             axis))
    out = np.empty(x.shape, dtype=x.dtype)
    for p in range(PIECES):
        at = [slice(None)] * x.ndim
        at[axis] = slice(p * size, (p + 1) * size)
        out[tuple(at)] = np.asarray(cut(x, np.int32(p * size)))
    return out


def rows_to_device(host, rows_sharding) -> tuple:
    """The planes ``(re, im)`` of a (2, N) host array on the device, each
    rows of 128 lanes (``reference_planes.split``'s form, made here from
    the host because that split holds its input beside its output)."""
    import jax

    return tuple(jax.device_put(host[p].reshape(-1, reference_planes.LANES),
                                rows_sharding) for p in (0, 1))


def errors_by_piece(got, want) -> tuple:
    """``reference.errors`` of the host array ``got`` (2, N) against the
    device planes ``want`` (re, im), every amplitude, ``PIECES`` pieces of
    rows at a time: (max |got - want| / max |want|, ||got - want|| /
    ||want||), the sums carried in Python floats."""
    import jax
    import jax.numpy as jnp

    rows = want[0].shape[0] // PIECES
    lanes = reference_planes.LANES

    @jax.jit
    def piece(gr, gi, wr, wi, at):
        wr, wi = (jax.lax.dynamic_slice(w, (at, np.int32(0)), (rows, lanes))
                  for w in (wr, wi))
        d2 = (gr - wr) ** 2 + (gi - wi) ** 2
        w2 = wr * wr + wi * wi
        return jnp.max(d2), jnp.sum(d2), jnp.max(w2), jnp.sum(w2)

    d_max = d_sum = w_max = w_sum = 0.0
    for p in range(PIECES):
        g = [jnp.asarray(got[i, p * rows * lanes:(p + 1) * rows * lanes]
                         .reshape(rows, lanes)) for i in (0, 1)]
        a, b, c, d = (float(v) for v in piece(*g, *want,
                                              np.int32(p * rows)))
        d_max, w_max = max(d_max, a), max(w_max, c)
        d_sum, w_sum = d_sum + b, w_sum + d
    return math.sqrt(d_max / w_max), math.sqrt(d_sum / w_sum)


class Driver(Library):

    def setup(self):
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
            # the public constructor's own register; its 8 GiB of |0...0>
            # are read once (calcTotalProb) and gone before the seed's state
            self.q = qt.createQureg(self.n, self.env)
            self.refuse_unfolded_plan()
            if qt.calcTotalProb(self.q) != 1.0:
                raise RuntimeError("createQureg's |0...0> is not normalised")
            self.load_state()
            self.sync()
        with run.span("first_call_s"):
            self.apply()
        with run.span("warm_s"):
            self.apply()

    def refuse_unfolded_plan(self):
        """Exit, before anything compiles, where a relabeling of the plan
        would run as an explicit pass beside its kernel: that pass writes a
        second state, which this register leaves no room for."""
        from quest_tpu import fusion

        plan = fusion.plan_from_tape(self.fused._tape)
        runs = [i for i in plan.items if isinstance(i, fusion.PallasRun)]
        for r in runs:
            reason = fusion._route(self.q, r).reason
            if reason is not None:
                print(f"# refused: a fused run of {len(r.ops)} ops with the "
                      f"relabelings k={r.load_swap_k}@{r.load_swap_hi} / "
                      f"k={r.store_swap_k}@{r.store_swap_hi} would not run "
                      f"whole in its kernel ({reason}); the explicit pass "
                      f"beside it needs a second {self.shapes()['state_bytes']}"
                      " bytes", file=sys.stderr, flush=True)
                raise SystemExit(EXIT_PLAN_REFUSED)

    def seed_state(self):
        from quest_tpu.environment import AMP_AXIS

        return states_sharded.statevector_planes(
            self.run.seed, self.n, self.env.mesh, AMP_AXIS)

    def load_state(self):
        """The seed's state into the register, the old one freed FIRST."""
        self.applications = 0
        self.q.amps.delete()
        self.q.put(self.seed_state())

    def shapes(self) -> dict:
        return {"state_bytes": 8 << self.n}

    def norm(self) -> float:
        import quest_tpu as qt

        return float(qt.calcTotalProb(self.q))

    # -- correctness, outside the window ------------------------------------

    def _check_vector(self, tape, limits) -> list:
        import jax

        if self.run.control is None:
            got = to_host(self.q.amps, 1)   # the program's output, whole
        self.q.amps.delete()                # the reference takes its place
        state = self.seed_state()
        rows = reference_planes._rows(state.sharding)
        seed = to_host(state, 1)
        state.delete()
        if self.run.control is not None:
            # the control: the reference in the next precision below, in the
            # program's place
            lower = reference_planes.LOWER[self.run.config["precision"]]
            low = self.reference(seed, rows, tape.ops, lower)
            got = np.stack([to_host(p, 0).reshape(-1) for p in low])
            for p in low:
                p.delete()
        t0 = time.perf_counter()
        want = jax.block_until_ready(self.reference(seed, rows, tape.ops))
        self.run.spans["reference_s"] = time.perf_counter() - t0
        del seed
        err_max, err_l2 = errors_by_piece(got, want)
        print("# check: total probability of the reference "
              f"{reference_planes.total_probability(want)!r} "
              f"(made in {self.run.spans['reference_s']:.2f} s); "
              f"{got.size // 2} amplitudes compared in {PIECES} pieces",
              file=sys.stderr, flush=True)
        del got
        for p in want:
            p.delete()
        # left holding a state: the control's check follows this one and
        # reads the register's norm first
        self.q.put(self.seed_state())
        return [("err_max", err_max, limits["err_max"]),
                ("err_l2", err_l2, limits["err_l2"])]

    def reference(self, seed, rows, ops, lower=None) -> tuple:
        """``reference_planes.run_statevector`` from the host copy ``seed``
        of the seed's state: the same gates by the same programs, the
        planes cut on the host (its ``split`` keeps the (2, N) array beside
        the planes it cuts, twice this state)."""
        import jax
        import jax.numpy as jnp

        re, im = rows_to_device(seed, rows)
        if lower is not None:
            # each plane rounded where it lies: a rounded copy beside both
            # planes is a third of a chip more than there is
            rounded = jax.jit(lower, donate_argnums=(0,))
            re, im = rounded(re), rounded(im)
        for target, m, controls in reference_planes.gates_of(ops):
            if target >= reference_planes.TILE_BITS:
                program = reference_planes._program(
                    reference_planes.apply_gate, rows, self.n, target=target,
                    controls=controls, lower=lower)
                arg = np.stack([m.real, m.imag]).astype(np.float32)
            else:
                above = tuple(c for c in controls
                              if c >= reference_planes.TILE_BITS)
                program = reference_planes._program(
                    reference_planes.apply_tile_matrix, rows, self.n,
                    lower=lower, controls=above)
                arg = reference_planes.tile_matrix(
                    m, target, [c for c in controls if c not in above])
            re, im = program(re, im, arg if lower is None
                             else lower(jnp.asarray(arg)))
        return re, im
