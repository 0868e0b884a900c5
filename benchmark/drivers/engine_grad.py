"""The gradient path: ``Engine.submit_grad`` on one ``Engine`` over the
configuration's ansatz and observable.

A request is ``submit_grad(angles)``, the wait for its future, and the sync of
the energy and the 160 derivatives it resolves to, timed from the client's
side. The seed draws every client's angle sets and never the Hamiltonian,
whose codes are static in the program: the engine's gradient companion serves
every request through its one padded batch program.

The clients step in rounds, as the source's loop does (``bench_vqe``:
``futs = [eng.submit_grad(p) for p in sweep]`` from one thread, then the wait
for all eight): see :class:`Rounds`.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import bytes_model_grad
import reference
import reference_grad
import states


#: how long a lane waits for the rest of its round before the round is sent
#: as it stands. Longer than a batch takes (1.5 s at 20 qubits), so that a
#: lane that fell out of step meets the others at their next round and does
#: not start one of its own; it runs out only where lanes have stopped: the
#: window's last round, when the deadline fell between two lanes' replies
ROUND_PATIENCE_S = 2.5


class Rounds:
    """The source's loop on the closed loop's callers. ``bench_vqe`` sends the
    eight lanes of a step from ONE thread and waits for all eight; the closed
    loop gives every lane a thread of its own. So the lanes meet here: a lane
    hands over its angles when its optimiser is ready for the next gradient,
    and the lane that completes the round sends all of them, in lane order,
    from its own thread, through ``send`` (``Engine.submit_grad``). A lane's
    request is timed from the moment it was ready, the wait for the others
    included."""

    def __init__(self, lanes: int, send):
        self.lanes, self.send = lanes, send
        self.cv = threading.Condition()
        self.ready = {}     # lane -> angles, of the round that gathers
        self.sent = {}      # lane -> its future, or what its send raised

    def submit(self, lane, angles):
        with self.cv:
            self.ready[lane] = angles
            end = time.perf_counter() + ROUND_PATIENCE_S
            while lane in self.ready:
                left = end - time.perf_counter()
                if len(self.ready) == self.lanes or left <= 0:
                    self._send_round()
                else:
                    self.cv.wait(left)
            out = self.sent.pop(lane)
        if isinstance(out, BaseException):
            raise out
        return out

    def _send_round(self):
        for lane in sorted(self.ready):
            try:
                self.sent[lane] = self.send(self.ready[lane])
            except Exception as exc:    # that lane's request fails, alone
                self.sent[lane] = exc
        self.ready.clear()
        self.cv.notify_all()


class Driver:
    #: the adjoint sweep walks the tape gate by gate through XLA's ops: no
    #: Pallas kernel is expected
    expects_kernels = False

    def __init__(self, run):
        self.run = run
        self.args = run.circuit_args
        self.n = self.args["num_qubits"]
        self.codes, self.coeffs = reference_grad.hamiltonian(run.config,
                                                             self.n)
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
            self.engine = Engine(
                circ, env, hamiltonian=(np.asarray(self.codes, np.int32),
                                        np.asarray(self.coeffs)),
                **cfg["engine"])
        self.names = run.builder.param_names(**self.args)
        if set(self.names) != set(self.engine.param_names):
            raise ValueError("the engine's Params differ from the builder's")
        self.rounds = Rounds(run.traffic["clients"], self.engine.submit_grad)
        self.load_state()
        with run.span("first_call_s"):
            # builds the companion, compiles its one batch program, and
            # sends one request of the seed's through it
            self.engine.warmup_grad()
            self.sync(self.engine.submit_grad(self.pools[0][0])
                      .result(timeout=900))
        with run.span("warm_s"):
            # every client at once, twice: the coalesced width the window sees
            for _ in range(2):
                futs = [self.engine.submit_grad(pool[0])
                        for pool in self.pools]
                self.sync([f.result(timeout=900) for f in futs])
        self.load_state()

    def load_state(self):
        """The seed's angle sets, one pool a client, and the counts the
        check reads the growth of from here on."""
        traffic = self.run.traffic
        self.pools = [states.angle_sets(self.run.seed, c, self.names,
                                        traffic["inputs_per_client"])
                      for c in range(traffic["clients"])]
        self.kept.clear()
        self.counted = self.counts()

    def counts(self) -> dict:
        from quest_tpu import telemetry

        return {
            "dispatches": telemetry.counter_value("device_dispatch_total",
                                                  route="grad_request"),
            "launches": telemetry.counter_total("engine_batches_total"),
            "retraces": telemetry.counter_value("engine_trace_total",
                                                kind="param_replay"),
        }

    def tape(self, params) -> list:
        """The reference's tape of the ansatz at one request's angles."""
        tape, order = reference.Tape(), []

        def angle(name):
            order.append(name)
            return params[name]

        self.run.builder.build(tape, angle=angle, **self.args)
        if order != self.names:
            raise ValueError("the tape's rotations are not in param_names "
                             "order")
        return tape.ops

    def shapes(self) -> dict:
        ops = self.tape(self.pools[0][0])
        state_bytes = 8 << self.n
        return {"state_bytes": state_bytes,
                "grad_bytes": bytes_model_grad.gradient_bytes(
                    ops, len(self.codes), state_bytes)}

    # -- the timed path -----------------------------------------------------

    def sync(self, x):
        import jax

        jax.block_until_ready(x)

    def request(self, client, k) -> bool:
        import jax

        pool = self.pools[client]
        with jax.profiler.TraceAnnotation("submit"):
            fut = self.rounds.submit(client, pool[k % len(pool)])
        with jax.profiler.TraceAnnotation("wait"):
            reply = fut.result(timeout=900)
            self.sync(reply)
        # a reply is a float and 160 floats: every one is kept
        with self._lock:
            self.kept[(client, k)] = (reply, time.perf_counter())
        return True

    # -- correctness, outside the window ------------------------------------

    def reply_of(self, key, ops) -> tuple:
        """``(E, gradient in param_names order)`` as the timed path returned
        them, or, under the control, as the reference gives them in the
        lower precision."""
        if self.run.control is not None:
            return reference_grad.gradient(ops, self.codes, self.coeffs,
                                           lower=self.run.control)
        import jax

        value, grads = self.kept[key][0]
        host = jax.device_get([value] + [grads[name] for name in self.names])
        return float(host[0]), np.array(host[1:], np.float64)

    def check(self, window) -> list:
        """A seeded pick of the requests the window finished, each against
        the reference's energy and gradient at that request's own angles,
        and what the program counted over the window."""
        cfg = self.run.config["check"]
        limits = cfg["limits"]
        now = self.counts()
        grown = {k: now[k] - self.counted[k] for k in now}
        program = [
            ("retraces_in_window", float(grown["retraces"]), 0.0),
            ("dispatches_not_one_a_launch",
             float(abs(grown["dispatches"] - grown["launches"])), 0.0)]
        done = sorted(key for key, (_, t) in self.kept.items()
                      if window.t0 <= t)
        if not done:
            return [("requests_left_unchecked", 1.0, 0.0)] + program
        rng = np.random.default_rng([self.run.seed, 11])
        picks = sorted(rng.choice(len(done),
                                  size=min(cfg["requests"], len(done)),
                                  replace=False))
        jobs = []
        for i in picks:
            client, k = done[i]
            pool = self.pools[client]
            ops = self.tape(pool[k % len(pool)])
            jobs.append((done[i], ops, reference_grad.shift_picks(
                rng, ops, cfg["shift_components"])))

        t0 = time.perf_counter()
        # (a) and (b) of every picked request side by side on host threads:
        # at 2^20 amplitudes a gate is too short to split, a sweep is not
        with ThreadPoolExecutor(min(2 * len(jobs),
                                    reference.host_threads())) as ex:
            swept = [ex.submit(reference_grad.gradient, ops, self.codes,
                               self.coeffs) for _, ops, _ in jobs]
            shifted = [ex.submit(reference_grad.shift, ops, self.codes,
                                 self.coeffs, which)
                       for _, ops, which in jobs]
            errs = [reference_grad.errors(self.reply_of(key, ops), a.result(),
                                          which, b.result())
                    for (key, ops, which), a, b in zip(jobs, swept, shifted)]
        self.run.spans["reference_s"] = time.perf_counter() - t0
        return ([(name, max(e[name] for e in errs), limits[name])
                 for name in reference_grad.ERRORS]
                + [("requests_left_unchecked", 0.0, 0.0)] + program)

    def close(self):
        if hasattr(self, "engine"):
            self.engine.close()
